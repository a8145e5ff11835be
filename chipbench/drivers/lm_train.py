"""Driver ``lm_train``: the LM trainer's own loop, timed.

The window drives ``LMTrainer.fit()`` -> ``_train_one_epoch`` (its
``sample_batch`` -> the jitted step -> the per-step fetch of the loss),
stamped through the trainer's ``step_hook``. ``fit`` has no stop that
does not write a checkpoint (a preemption request saves the whole state),
so the hook ends a phase by raising ``_Stop``, which ``fit`` lets through.

Set-up builds ONE trainer, gives it the benchmark's weights and token
stream, drives it through ``check_steps + 1`` steps in that same loop
(reading, between steps, what ``correct`` compares) and hands the same
object to the window.

``correct`` compares a SIDE with the plain reference: what stands in the
program's place. A side is {"loss": per-step losses, "grad": first-step
gradient norms per leaf, "change": norms of the weights' change per leaf,
"grad_tree": (the first gradient as host arrays, a scale)}. The
program's side is read from the trainer; a control's or a fault's is the
reference computed in that way (``reference_side``), and goes through
the same ``compare_sides``.

Traffic file keys: seq_len, rows_per_replica, mesh {data, model},
optimizer {name, learning_rate, weight_decay, cosine_decay_steps, b1, b2,
eps}, remat, remat_policy, loss_chunk, attn_impl, stream_tokens,
check_steps, trace_seconds, reference {q_block, loss_chunk}.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

import flops
import harness
import reference
import traffic_gen
import weights
from drivers.common import free_device_memory, transformer_config


class _Stop(Exception):
    """Ends a phase of ``fit`` from the step hook."""


def _log(msg: str) -> None:
    print(f"[chipbench:lm_train] {msg}", file=sys.stderr, flush=True)


def _adam_mu(opt_state):
    """The first-moment tree inside the optax chain's state."""
    import jax

    found = [x for x in jax.tree.leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(x, "mu")]
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state, found {len(found)}")
    return found[0].mu


def _step_losses(jsonl_path: str) -> dict:
    """{global step index: loss} as the trainer reported them."""
    out = {}
    with open(jsonl_path) as f:
        for line in f:
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if rec.get("kind") == "step":
                out[int(rec["step"])] = float(rec["loss"])
    return out


def token_stream(cell, dims, seed: int) -> np.ndarray:
    return traffic_gen.token_stream(seed, dims.vocab,
                                    cell.traffic["stream_tokens"])


def build_trainer(cell, dims, seed: int, workdir: str, devices):
    """The trainer the configuration and the traffic file describe, with
    the benchmark's weights and token stream in place of its own."""
    import jax

    from distributed_model_parallel_tpu.config import (
        MeshConfig,
        OptimizerConfig,
    )
    from distributed_model_parallel_tpu.parallel.spmd_pipeline import (
        shard_params,
    )
    from distributed_model_parallel_tpu.train.lm_trainer import (
        LMTrainConfig,
        LMTrainer,
    )

    tr = cell.traffic
    mesh = tr["mesh"]
    if mesh["data"] * mesh["model"] != len(devices):
        raise RuntimeError(f"mesh {mesh} needs "
                           f"{mesh['data'] * mesh['model']} devices, the "
                           f"cell has {len(devices)}")
    mcfg, dtype = transformer_config(
        cell.config, dims, attn_impl=tr["attn_impl"], remat=tr["remat"],
        remat_policy=tr["remat_policy"], loss_chunk=tr["loss_chunk"],
        tp_axis="model" if mesh["model"] > 1 else None)
    o = tr["optimizer"]
    seq = tr["seq_len"]
    tcfg = LMTrainConfig(
        model=mcfg, mesh=MeshConfig(data=mesh["data"], model=mesh["model"]),
        optimizer=OptimizerConfig(
            name=o["name"], learning_rate=o["learning_rate"],
            weight_decay=o["weight_decay"],
            cosine_decay_steps=o["cosine_decay_steps"]),
        batch_size=tr["rows_per_replica"] * mesh["data"], seq_len=seq,
        steps_per_epoch=10 ** 9, epochs=1, n_tokens=2 * (seq + 2),
        seed=weights.fold_seed(seed), eval_fraction=0.0, eval_batches=0,
        log_dir=os.path.join(workdir, "log"), log_name="lm",
        checkpoint_dir=os.path.join(workdir, "ckpt"))
    trainer = LMTrainer(tcfg)
    trainer.logger.echo = False
    # the benchmark's weights and data, in the program's layout/placement
    del trainer.params
    params = weights.make_params(seed, dims, dtype)
    trainer.params = shard_params(params, mcfg, trainer.spec)
    del params
    trainer.tokens = token_stream(cell, dims, seed)
    trainer._n_train = len(trainer.tokens)
    jax.block_until_ready(trainer.params)
    return trainer, dtype


def drive_setup(cell, dims, seed, workdir, devices, fault=None):
    """Build the one trainer and drive it, through ``fit``, over its
    first ``check_steps + 1`` steps, reading between steps what
    ``correct`` compares. Returns (trainer, dtype, prog, state); the
    hook stays installed and ``state`` switches it to the window."""
    import jax

    tr = cell.traffic
    trainer, dtype = build_trainer(cell, dims, seed, workdir, devices)
    if fault is not None:
        fault(trainer)
    check_steps = int(tr["check_steps"])
    prog = {"grad": None, "change": None, "grad_tree": None}
    state = {"phase": "setup", "t0": None, "k0": 0, "stamps": [],
             "seconds": 0.0}
    b1 = tr["optimizer"]["b1"]

    def hook(t):
        now = time.perf_counter()
        k = t._global_step
        if state["phase"] == "setup":
            if k == 1 and prog["grad"] is None:
                # the first gradient as AdamW got it: mu_1 = (1 - b1) g.
                # It waits on the host (the chip has no room for it
                # beside the step), in the moments' own dtype
                mu = _adam_mu(t.opt_state)
                prog["grad"] = {n: v / (1.0 - b1) for n, v in
                                reference.leaf_norms(mu).items()}
                prog["grad_tree"] = (jax.device_get(mu), 1.0 / (1.0 - b1))
                _log(f"first gradient to the host in "
                     f"{time.perf_counter() - now:.2f}s")
            if k == check_steps and prog["change"] is None:
                p0 = weights.make_params(seed, dims, dtype)
                prog["change"] = reference.leaf_norms(t.params, p0)
                del p0
            if k >= check_steps + 1:
                raise _Stop
            return
        if state["t0"] is None:
            state["t0"], state["k0"] = now, k
        state["stamps"].append(now)
        if now - state["t0"] >= state["seconds"]:
            raise _Stop

    trainer.step_hook = hook
    try:
        trainer.fit()
    except _Stop:
        pass
    return trainer, dtype, prog, state


def run(cell, *, seed, seconds, trace, devices, t_proc, root,
        fault=None) -> harness.RunOutput:
    tr = cell.traffic
    dims = weights.Dims.from_config(cell.config)
    workdir = tempfile.mkdtemp(prefix="chipbench_lm_")
    spans = harness.Spans()
    try:
        trainer, dtype, prog, state = drive_setup(
            cell, dims, seed, workdir, devices, fault)
        check_steps = int(tr["check_steps"])
        # ---- the window: the same object, the same call ----------------
        win = harness.TraceWindow(trace)
        state["seconds"] = (min(seconds, float(tr["trace_seconds"]))
                            if trace else seconds)
        state["phase"] = "window"
        win.start()
        setup_s = time.perf_counter() - t_proc
        try:
            with spans.span("fit"):
                trainer.fit()
        except _Stop:
            pass
        win.stop()
        stamps = state["stamps"]
        steps = len(stamps) - 1
        window_s = stamps[-1] - stamps[0]
        losses = _step_losses(trainer.logger.jsonl_path)
        peak = harness.memory_peak_bytes(devices)
        tokens_per_step = trainer.config.batch_size * trainer.config.seq_len
        fed = [trainer.sample_batch(0, s) for s in range(check_steps)]
        mesh_rows = trainer.config.batch_size
        window_losses = [losses[s] for s in sorted(losses)
                         if s >= state["k0"]]
        failed = sum(1 for x in window_losses if not np.isfinite(x))
        trace_obj = win.load()
        free_device_memory(trainer)
        del trainer
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    metrics = {
        "setup_s": setup_s,
        "train_tok_s": steps * tokens_per_step / window_s / len(devices),
    }
    counters = {
        "steps": steps, "tokens_per_step": tokens_per_step,
        "step_flops": flops.train_flops_per_step(
            dims, mesh_rows, tr["seq_len"]),
        "sequences_per_step": mesh_rows, "seq_len": tr["seq_len"],
        "heads_per_chip": dims.n_heads // tr["mesh"]["model"],
        "sequences_per_chip": tr["rows_per_replica"],
    }
    t_ref = time.perf_counter()
    prog["loss"] = [losses[s] for s in range(check_steps)]
    batches, bad_rows = own_batches(token_stream(cell, dims, seed), fed)
    compared = compare_sides(cell, dims, seed, dtype, batches,
                             {"program": prog}, bad_rows)["program"][0]
    _log(f"set-up {setup_s:.1f}s, window {window_s:.2f}s ({steps} steps), "
         f"reference {time.perf_counter() - t_ref:.1f}s")
    return harness.RunOutput(
        metrics=metrics, attempted=steps, failed=failed, window_s=window_s,
        counters=counters, spans=spans, dims=dims, compared=compared,
        trace=trace_obj, memory_peak=peak)


def own_batches(stream: np.ndarray, fed: list) -> tuple:
    """The check batches cut from the benchmark's own stream by the
    benchmark's own rule: each row the program's sampler returned is
    looked up in the stream by its first tokens, and the row and its
    targets are cut there (tokens s..s+T, targets s+1..s+T+1). Returns
    (batches, rows at fault): a row is at fault if it is no such cut, if
    its targets are not the cut's, or if it repeats an earlier row."""
    key_len = 16
    windows = np.lib.stride_tricks.sliding_window_view(stream, key_len)
    out, bad, seen = [], 0, set()
    for toks, tgts in fed:
        toks, tgts = np.asarray(toks), np.asarray(tgts)
        own_t, own_y = toks.copy(), tgts.copy()
        t = toks.shape[1]
        for i, row in enumerate(toks):
            starts = [int(s0) for s0 in np.flatnonzero(
                (windows == row[:key_len]).all(axis=1))
                if s0 + t + 1 <= len(stream)
                and np.array_equal(stream[s0:s0 + t], row)]
            if not starts:
                bad += 1
                continue
            s0 = starts[0]
            own_y[i] = stream[s0 + 1:s0 + t + 1]
            bad += int(s0 in seen or not np.array_equal(own_y[i], tgts[i]))
            seen.add(s0)
        out.append((own_t, own_y))
    return out, bad


def reference_side(cell, dims, seed, dtype, batches, *, quant=None,
                   target_keep=None, against=None, keep_grad=False):
    """The plain reference over the same weights and batches, as a side:
    per-step losses, first-step gradient norms, change after the steps;
    with ``against`` ({name: side's "grad_tree"}) also "grad_err": how
    far each of those first gradients lies from its own, leaf by leaf."""
    tr = cell.traffic
    p0 = weights.make_params(seed, dims, dtype)
    ref = reference.TrainReference(
        dims, p0, tr["optimizer"], quant=quant,
        q_block=tr["reference"]["q_block"],
        loss_chunk=tr["reference"]["loss_chunk"], target_keep=target_keep)
    del p0
    losses, grad, first = [], None, None
    for toks, tgts in batches:
        if grad is None:
            loss, grad, first = ref.step(toks, tgts, against=against,
                                         keep_grad=keep_grad)
        else:
            loss, _, _ = ref.step(toks, tgts)
        losses.append(loss)
    ref.drop_moments()
    change = reference.leaf_norms(
        ref.stacked_params(), weights.make_params(seed, dims, dtype))
    return {"loss": losses, "grad": grad, "change": change,
            "grad_err": first["err"],
            "grad_tree": (first["grad_tree"], 1.0)}


def gaps(side, ref, err) -> dict:
    """The numbers ``correct`` compares (see PERF.md section 2) between
    a side and the reference; ``err`` is the side's entry of the
    reference's "grad_err"."""
    out = {}
    for i, (a, b) in enumerate(zip(side["loss"], ref["loss"])):
        out[f"loss_gap_step{i + 1}"] = abs(a - b) / abs(b)
    (out["grad_norm_gap"], out["grad_norm_leaf"],
     out["grad_norm_gap_median"]) = reference.worst_leaf_gap(
        side["grad"], ref["grad"])
    (out["grad_err_gap"], out["grad_err_leaf"],
     out["grad_err_gap_median"]) = reference.worst_leaf_gap(
        None, ref["grad"], err=err)
    skip = reference.near_zero_gradient_leaves(ref["grad"])
    (out["param_change_gap"], out["param_change_leaf"],
     out["param_change_gap_median"]) = reference.worst_leaf_gap(
        side["change"], ref["change"], skip)
    return out


def compare_sides(cell, dims, seed, dtype, batches, sides: dict,
                  bad_rows: int = 0) -> dict:
    """One run of the plain reference, every side held against it:
    {name: ([(number, value, limit)], every gap read)}."""
    ref = reference_side(cell, dims, seed, dtype, batches, against={
        name: side["grad_tree"] for name, side in sides.items()})
    out = {}
    for name, side in sides.items():
        g = gaps(side, ref, ref["grad_err"][name])
        g["batch_rows_not_from_stream"] = float(bad_rows)
        _log(f"{name}: worst leaves: grad norm {g['grad_norm_leaf']}, grad "
             f"error {g['grad_err_leaf']}, change "
             f"{g['param_change_leaf']}; ref losses {ref['loss']}, "
             f"{name} {side['loss']}")
        out[name] = ([(n, float(g[n]), float(lim)) for n, lim in
                      cell.checks["limits"].items()], g)
    return out


def readings(cell, seed: int, devices, control: bool,
             detail: bool = False) -> dict:
    """For setting limits (tools/readings.py) and for showing that the
    control comes out not correct: on one seed the program's side, the
    int8 control's and, with ``detail``, the milder int8_fwd control's
    and that of the fault "the second half of each row's targets left
    out of the loss, the mean taken over the rest" planted in the
    reference, all through ``compare_sides`` as a run's is. No measured
    window. Each side: every gap read, and ``correct`` by the cell's
    limits."""
    dims = weights.Dims.from_config(cell.config)
    workdir = tempfile.mkdtemp(prefix="chipbench_lm_")
    try:
        trainer, dtype, prog, _ = drive_setup(cell, dims, seed, workdir,
                                              devices)
        n = int(cell.traffic["check_steps"])
        losses = _step_losses(trainer.logger.jsonl_path)
        prog["loss"] = [losses[s] for s in range(n)]
        fed = [trainer.sample_batch(0, s) for s in range(n)]
        free_device_memory(trainer)
        del trainer
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    batches, bad_rows = own_batches(token_stream(cell, dims, seed), fed)
    sides = {"program": prog}
    side = lambda **kw: reference_side(cell, dims, seed, dtype, batches,
                                       keep_grad=True, **kw)
    if control:
        sides["int8"] = side(quant="int8")
    if detail:
        sides["int8_fwd"] = side(quant="int8_fwd")
        keep = np.zeros(cell.traffic["seq_len"], np.float32)
        keep[:len(keep) // 2] = 1.0
        sides["half_targets"] = side(target_keep=keep)
    out = {"seed": seed}
    for name, (rows, g) in compare_sides(cell, dims, seed, dtype, batches,
                                         sides, bad_rows).items():
        out[name] = {"correct": harness.is_correct(rows), **g}
    return out
