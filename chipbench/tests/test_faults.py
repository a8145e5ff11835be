"""``correct`` comes out false when the timed path is broken underneath,
once for each fault a cell can have; and the lower-precision control,
put in the program's place, comes out not correct.

These skip the harness's look for a chip and drive the rest of a run on
the tiny cells (``data/tiny``), on the CPU. On one chip no exchange
between chips exists to leave out."""

import jax
import jax.numpy as jnp
import numpy as np

from test_cells_as_files import run_tiny, tiny_cell


def state_unchanged(trainer):
    real = trainer._step

    def step(params, opt_state, toks, tgts):
        copy = lambda t: jax.tree.map(jnp.copy, t)
        _, _, m = real(copy(params), copy(opt_state), toks, tgts)
        return params, opt_state, m

    trainer._step = step


def half_batch(trainer):
    """The second half of the rows left out, the mean over the rest."""
    real = trainer._step

    def step(params, opt_state, toks, tgts):
        h = toks.shape[0] // 2
        return real(params, opt_state,
                    jnp.concatenate([toks[:h], toks[:h]]),
                    jnp.concatenate([tgts[:h], tgts[:h]]))

    trainer._step = step


def unshifted_targets(trainer):
    """A fault in the program's sampler: the targets are the tokens
    themselves, not the tokens that follow them."""
    real = trainer.sample_batch

    def sample_batch(epoch=None, step=None):
        toks, _ = real(epoch, step)
        return toks, toks

    trainer.sample_batch = sample_batch


def over(res):
    return [n for n, c in res["compared"].items()
            if not c["value"] <= c["limit"]]


def test_train_state_left_unchanged_is_not_correct():
    res = run_tiny("tiny-train.seq128", 7, fault=state_unchanged)
    assert res["correct"] is False
    # no gradient reached the optimizer, no weight moved: both read 1
    assert res["compared"]["grad_norm_gap"]["value"] > 0.9
    assert res["compared"]["param_change_gap"]["value"] > 0.9


def test_train_half_the_batch_left_out_is_not_correct():
    res = run_tiny("tiny-train.seq128", 8, fault=half_batch)
    assert res["correct"] is False
    assert "grad_norm_gap" in over(res) or "loss_gap_step1" in over(res)


def test_train_sampler_fault_is_not_correct():
    """The check batches are cut from the benchmark's own stream, so a
    fault in the program's sampler does not reach the reference."""
    res = run_tiny("tiny-train.seq128", 11, fault=unshifted_targets)
    assert res["correct"] is False
    assert "batch_rows_not_from_stream" in over(res)


def test_serve_altered_token_is_not_correct():
    def alter(engine):
        real = engine._decode

        def decode(*a):
            ck, cv, nxt = real(*a)
            return ck, cv, (nxt + 1) % engine.cfg.vocab_size

        engine._decode = decode

    res = run_tiny("tiny.batch", 9, fault=alter)
    assert res["correct"] is False
    assert "served_logit_gap" in over(res)


def test_serve_short_answer_is_not_correct():
    def cut(engine):
        real = engine._finished
        engine._finished = lambda req, tok: (
            len(req.generated) >= max(1, req.max_new_tokens - 1)
            or real(req, tok))

    res = run_tiny("tiny.batch", 10, fault=cut)
    assert res["correct"] is False and "wrong_length" in over(res)


def test_int8_control_is_not_correct_training():
    """The control is the reference in int8, put in the program's place:
    it goes through the comparison a run goes through
    (``compare_sides``) and comes out not correct, where the program on
    the same seeds comes out correct. The toy cell's limits stand in the
    same way between its readings as the real cells' do between theirs
    (PERF.md section 2); the number that fails it here is the first
    gradient's error."""
    import importlib

    cell = tiny_cell("tiny-train.seq128")
    drv = importlib.import_module("drivers.lm_train")
    for seed in (21, 22, 23):
        r = drv.readings(cell, seed, jax.devices()[:1], control=True)
        assert r["program"]["correct"] is True, r["program"]
        assert r["int8"]["correct"] is False, r["int8"]
        assert (r["int8"]["grad_err_gap"]
                > 3 * r["program"]["grad_err_gap"])


def test_int8_control_reads_above_the_program_serving():
    import time

    from drivers import serve_engine

    cell = tiny_cell("tiny.batch")
    gaps = []
    for seed in (31, 32, 33):
        out = serve_engine.run(cell, seed=seed, seconds=0.5, trace=False,
                               devices=jax.devices()[:1],
                               t_proc=time.perf_counter(), root=None,
                               control=True)
        prog = dict((n, v) for n, v, _ in out.compared)
        ctl = out.counters["control"]["int8"]
        gaps.append((prog["served_logit_gap"], ctl["max"],
                     prog["served_logit_gap_mean"], ctl["mean"]))
    # some forty tokens a seed: the widest gap swings, the mean less so
    assert sum(c > p for p, c, _, _ in gaps) >= 2, gaps
    assert (np.mean([c for _, _, _, c in gaps])
            > 3 * np.mean([p for _, _, p, _ in gaps])), gaps
