#!/usr/bin/env python
"""Does the system still start on the chip? The quickest proof, end to end.

``python chip_smoke.py`` drives the main paths once on ONE TPU chip through
the entry points a user would call, at widths that fill the chip's
kernels (depth and step counts cut, weights random from a seed; the
benchmark's own models are ``chipbench/configs/``):

1. **device** — one ``jax.devices()``; anything but a TPU is refused;
2. **cnn** — ``train.trainer.Trainer`` as ``scripts/train_data_parallel.py
   --device-data`` builds it: MobileNetV2, CIFAR-shaped synthetic data,
   global batch 512, bf16 compute / f32 params. Loss finite and falling;
   the telemetry header names the device. Once more with
   ``OptimizerConfig(fused=True)``: the compiled fused-SGD kernel's
   parameters after the same steps against the optax chain's;
3. **lm** — ``train.lm_trainer.LMTrainer`` on a long-context LM (vocab
   32 000, d_model 1024, 8 heads x 128, 8 layers, RoPE, bf16) at seq 8192,
   ``attn_impl="auto"``: the dispatch table picks the compiled flash
   forward and FA2 backward. Two steps at seq 2048, flash against XLA
   attention, on one batch. The checkpoint ``fit()`` wrote is read back;
4. **serve** — ``serve.Engine`` on that checkpoint's parameters: requests
   of 64-1500 prompt tokens joining and leaving an 8-slot batch, the
   compiled paged-decode and paged-prefill kernels under
   ``attn_impl="auto"``. bf16 decode logits against ``attn_impl="xla"``;
   a float32 engine (chunks, decode rounds and verify windows all
   through the kernels) token-identical to ``"xla"``, and with prefix
   cache + speculation token-identical to both off.

5. **serve-routed**, **serve-hybrid** — the same engine on the two
   other block families at toy depth: sigmoid-routed dropless experts
   with sliding layers in rings, and gated-delta linear-attention layers
   3:1 with full attention (a recurrent state a slot): the rule's decode
   kernel and chunked form against the recurrence at the published
   widths, then kernels against the XLA forms, token for token;
   **serve-looped** — a stack run three times over shared weights
   (sandwich norms, the norm closing each pass, the exit gate; 16 heads
   of 128 with no grouping): the decode logits of a captured batch
   through the pools, twelve cache layers deep, kernel against XLA and
   both against the full forward with no cache; tokens, the loop's
   counters.

``--multichip`` is a separate run for a four-chip host: only the
cross-chip paths (GSPMD dp 4, shard_map DDP, the four-stage pipeline, LM
dp2 x tp2, LM pp2 1F1B), each against its one-device step, with every
train-state array's sharding and every device's memory checked.
``--tiny`` rehearses either form at toy sizes with the kernels
interpreted, on whatever backend the caller chose (``JAX_PLATFORMS=cpu``);
its last line reports that backend, so it can never be read as a chip pass.

All phases share ONE process and one set of compiled programs: a chip
belongs to one process at a time, so nothing here starts a child that
needs it (the only child is ``make`` for the native data library). The
first failed check raises; no phase is allowed to fail and let the script
print ``ok``. The last stdout line is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The wall times printed on earlier lines are for finding a hang, not a
benchmark.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib.metadata
import json
import os
import shutil
import sys
import tempfile
import time

# Sizes. FULL is the width the chip is proven at; TINY is the rehearsal.
FULL = dict(
    cnn=dict(model="mobilenetv2", batch=512, steps_per_epoch=4, epochs=2),
    lm=dict(vocab=32_000, d_model=1024, heads=8, layers=8, d_ff=4096,
            seq=8192, batch=2, steps=4, parity_seq=2048),
    serve=dict(n_slots=8, page=16, max_seq=2048, chunk=128, n_requests=12,
               prompt=(64, 1500), new=(32, 128), shared_prefix=256),
    routed=dict(vocab=8192, d_model=512, heads=8, kv_heads=2, head_dim=128,
                d_ff=1024, d_expert=256, experts=16, held=(4, 4), top_k=4,
                window=128),
    # the linear layers' published widths (30 heads of 96 x 192) and the
    # full layers' 30 heads with no grouping, at a small hidden size
    hybrid=dict(vocab=8192, d_model=512, heads=30, head_dim=128, d_ff=1024,
                lin_heads=30, lin_dk=96, lin_dv=192, rule_tokens=512),
    # the looped stack's heads as published (16 of 128, no grouping)
    looped=dict(vocab=8192, d_model=512, heads=16, head_dim=128, d_ff=1024,
                layers=4, passes=3),
    multi=dict(lm_seq=2048, lm_batch=16, loss_chunk=512),
)
TINY = dict(
    cnn=dict(model="tinycnn", batch=32, steps_per_epoch=4, epochs=2),
    lm=dict(vocab=256, d_model=64, heads=2, layers=2, d_ff=128,
            seq=256, batch=2, steps=4, parity_seq=128),
    serve=dict(n_slots=4, page=16, max_seq=128, chunk=16, n_requests=6,
               prompt=(8, 80), new=(8, 24), shared_prefix=32),
    routed=dict(vocab=256, d_model=64, heads=4, kv_heads=2, head_dim=32,
                d_ff=128, d_expert=32, experts=16, held=(4, 4), top_k=4,
                window=16),
    hybrid=dict(vocab=256, d_model=64, heads=4, head_dim=32, d_ff=128,
                lin_heads=4, lin_dk=8, lin_dv=64, rule_tokens=80),
    looped=dict(vocab=256, d_model=64, heads=2, head_dim=32, d_ff=128,
                layers=3, passes=3),
    multi=dict(lm_seq=128, lm_batch=16, loss_chunk=0),
)

# Stated tolerances.
# bf16 LM losses, flash vs XLA attention (and sharded vs one chip): the
# loss is a mean over >= 4k tokens of f32 cross-entropies of bf16 logits.
LM_LOSS_RTOL = 1e-2
# bf16 decode logits, kernel vs XLA gather path: eight layers of bf16
# residual stream; 2^-5 of the largest logit is eight bf16 ulps.
DECODE_LOGIT_RTOL = 2.0 ** -5
# fused-SGD kernel vs the optax chain on the SAME gradients: the two
# differ by f32 rounding only (FMA contraction), so a few ulp of each
# leaf's largest update. Whole training runs are compared by loss only:
# under bf16 compute a one-ulp difference in a weight is amplified step
# by step (8 steps on the chip: epoch losses 0.3% apart, zero-initialised
# BatchNorm biases 200% apart), so parameters after N steps are not a
# measure of the kernel.
FUSED_UPDATE_ULPS, FUSED_LOSS_RTOL = 8, 2e-2
# f32 one-device vs sharded step (tests/test_train.py::
# test_dp_sharded_step_matches_single_device pins the same on the CPU):
# the first-step loss, and the step's parameter UPDATE (all leaves as one
# vector, L2) — the gradient, free of the weights' scale. Summation order
# moves it by 4e-3 through BatchNorm's cancellations (tinycnn on the CPU;
# the CPU test's atol allows as much); a wrong reduction moves it by
# O(1). Not per leaf: a conv bias ahead of a BatchNorm has a true
# gradient of zero, so its update is rounding noise on both sides.
DP_LOSS_RTOL, DP_UPDATE_RTOL = 1e-4, 2e-2
# The gated delta rule's forms on the same float32 inputs under true
# float32 products: the decode kernel against the step (the same sums in
# another order), the chunked form against the recurrence token by token
# (a solve and six products a sub-chunk for 64 rank-one updates): values
# of order 1, so a few hundred ulps.
RULE_ATOL = 2e-4
# The looped stack's float32 decode logits under true float32 products,
# through the paged cache against the full forward with no cache: the
# same sums in another order through passes x layers sandwich-normed
# sublayer pairs (the CPU tests hold 1e-4 at nine layer passes).
LOOP_LOGIT_ATOL = 5e-4


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    """The script's assert (``assert`` vanishes under ``python -O``)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")
    log(f"  ok: {what}")


class CompileMeter:
    """Backend-compile seconds and persistent-cache hits/misses, from
    ``jax.monitoring`` (a hit still fires the duration event, with the
    retrieval time)."""

    def __init__(self):
        from jax import monitoring

        self.seconds = 0.0
        self.hits = self.misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.seconds += duration

    def _event(self, event: str, **kw) -> None:
        if event.endswith("compilation_cache/cache_hits"):
            self.hits += 1
        elif event.endswith("compilation_cache/cache_misses"):
            self.misses += 1


@contextlib.contextmanager
def phase(name: str, meter: CompileMeter):
    import jax

    t0, c0 = time.perf_counter(), meter.seconds
    log(f"phase {name}: start")
    yield
    peak = [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()]
    log(f"phase {name}: ok wall_s={time.perf_counter() - t0:.1f} "
        f"compile_s={meter.seconds - c0:.1f} peak_bytes_in_use={peak}")


def host_leaves(tree) -> list:
    import jax

    return jax.tree.leaves(jax.device_get(tree))


def has_custom_call(jitted, *args) -> bool:
    """Did ``jitted(*args)`` lower to a compiled Pallas kernel? (The
    interpreter lowers to plain HLO ops instead.)"""
    return "tpu_custom_call" in jitted.lower(*args).as_text()


# ---------------------------------------------------------------------------
# single chip
# ---------------------------------------------------------------------------

def cnn_config(size: dict, workdir: str, name: str, **kw):
    """The TrainConfig scripts/train_data_parallel.py builds for
    ``--bf16 --device-data -type synthetic``, cut to a few steps."""
    from distributed_model_parallel_tpu.config import (
        DataConfig,
        MeshConfig,
        ModelConfig,
        OptimizerConfig,
        TrainConfig,
    )

    batch = size["batch"]
    defaults = dict(
        model=ModelConfig(name=size["model"], dtype="bfloat16"),
        data=DataConfig(name="synthetic", batch_size=batch,
                        eval_batch_size=batch,
                        synthetic_train_size=batch * size["steps_per_epoch"],
                        synthetic_eval_size=batch),
        optimizer=OptimizerConfig(learning_rate=0.1, warmup_steps=0),
        mesh=MeshConfig(data=1),
        epochs=size["epochs"],
        device_resident_data=True,
        steps_per_dispatch=2,
        log_every_n_steps=1,
        log_dir=os.path.join(workdir, "log"),
        checkpoint_dir=os.path.join(workdir, f"ckpt-{name}"),
        log_name=name,
    )
    defaults.update(kw)
    return TrainConfig(**defaults)


def phase_cnn(size: dict, workdir: str, dev) -> None:
    import jax
    import numpy as np
    import optax

    from distributed_model_parallel_tpu.train.trainer import Trainer
    from distributed_model_parallel_tpu.utils.telemetry import read_records

    on_tpu = dev.platform == "tpu"
    cfg = cnn_config(size, workdir, "cnn-optax")
    optax_t = Trainer(cfg)
    hist = optax_t.fit()
    losses = [h["loss_train"] for h in hist]
    log(f"  {size['model']} bs{size['batch']} optax epoch losses: {losses}")
    check(all(np.isfinite(x) for x in losses), "cnn losses finite")
    check(losses[-1] < losses[0], "cnn loss lower at the last epoch")
    head = [r for r in read_records(optax_t.logger.jsonl_path)
            if r["kind"] == "run_start"][0]["device"]
    check(head["platform"] == dev.platform
          and head["device_kind"] == dev.device_kind,
          f"telemetry header names the device ({head['device_kind']})")

    fused_cfg = cnn_config(
        size, workdir, "cnn-fused",
        optimizer=dataclasses.replace(cfg.optimizer, fused=True))
    fused_t = Trainer(fused_cfg)
    if on_tpu:
        idx = np.zeros((fused_cfg.steps_per_dispatch, size["batch"]),
                       np.int64)
        check(has_custom_call(
            fused_t._multi_step, fused_t.state, jax.random.key(0),
            fused_t._dev_images, fused_t._dev_labels, idx),
            "fused-SGD step lowers to the Pallas custom call")
    fhist = fused_t.fit()
    flosses = [h["loss_train"] for h in fhist]
    log(f"  fused-SGD epoch losses: {flosses}")
    check(all(np.isfinite(x) for x in flosses) and flosses[-1] < flosses[0],
          "fused-SGD losses finite and falling")
    check(all(abs(f - o) <= FUSED_LOSS_RTOL * o
              for f, o in zip(flosses, losses)),
          f"fused-SGD epoch losses within {FUSED_LOSS_RTOL:g} of the optax "
          f"chain's")

    # The kernel's arithmetic, on the trained parameter tree: two
    # consecutive updates (so the momentum buffer is exercised) from
    # identical gradients through each trainer's optimizer.
    params = optax_t.state.params
    grads = jax.tree.map(lambda p: 0.01 * p + 1e-3, params)

    def two_updates(tx):
        @jax.jit
        def run(params, grads):
            state = tx.init(params)
            u1, state = tx.update(grads, state, params)
            u2, _ = tx.update(grads, state, optax.apply_updates(params, u1))
            return u1, u2

        if on_tpu and tx is fused_t.tx:
            check(has_custom_call(run, params, grads),
                  "fused optimizer update lowers to the Pallas custom call")
        return host_leaves(run(params, grads))

    worst = max(
        float(np.abs(f - o).max() / np.abs(o).max())
        for f, o in zip(two_updates(fused_t.tx), two_updates(optax_t.tx)))
    tol = FUSED_UPDATE_ULPS * float(np.finfo(np.float32).eps)
    check(worst <= tol,
          f"fused-SGD updates == the optax chain's on identical gradients "
          f"(worst leaf off by {worst:.2e} of its largest update, "
          f"tol {tol:.2e} = {FUSED_UPDATE_ULPS} ulp)")


def lm_model(size: dict, seq: int, attn_impl: str, **kw):
    """The smoke's LM at ``size``: FULL's, or its toy at --tiny."""
    import jax.numpy as jnp

    from distributed_model_parallel_tpu.models import transformer as tfm

    return tfm.TransformerConfig(
        vocab_size=size["vocab"], d_model=size["d_model"],
        n_heads=size["heads"], n_layers=size["layers"], d_ff=size["d_ff"],
        max_seq_len=seq, pos_embedding="rope", remat=True,
        remat_policy="dots", dtype=jnp.bfloat16, attn_impl=attn_impl, **kw)


def lm_config(model, workdir: str, name: str, *, batch: int, seq: int,
              steps: int, **kw):
    from distributed_model_parallel_tpu.train.lm_trainer import LMTrainConfig

    return LMTrainConfig(
        model=model, batch_size=batch, seq_len=seq,
        n_tokens=4 * batch * (seq + 1), eval_batches=0,
        steps_per_epoch=steps, epochs=1,
        log_dir=os.path.join(workdir, "log"), log_name=name,
        checkpoint_dir=os.path.join(workdir, f"ckpt-{name}"), **kw)


def two_steps(trainer) -> tuple[float, float]:
    """Two optimizer steps on ONE batch, through ``trainer._step``.
    The second loss depends on the first step's gradients, so it checks
    the backward pass too (at a random init the first loss is ~ln(vocab)
    whatever the attention computes)."""
    import jax.numpy as jnp

    toks, tgts = (jnp.asarray(x) for x in trainer.sample_batch(0, 0))
    out = []
    for _ in range(2):
        trainer.params, trainer.opt_state, m = trainer._step(
            trainer.params, trainer.opt_state, toks, tgts)
        out.append(float(m["loss"]))
    return out[0], out[1]


def check_losses_agree(name: str, got, ref) -> None:
    import numpy as np

    check(all(np.isfinite(got)) and got[1] < got[0],
          f"{name}: losses finite and falling {got}")
    check(all(abs(g - r) <= LM_LOSS_RTOL * abs(r) for g, r in zip(got, ref)),
          f"{name}: losses {got} agree with {ref} (rtol {LM_LOSS_RTOL:g})")


def phase_lm(size: dict, workdir: str, on_tpu: bool):
    """Returns (model config, restored params) for the serving phase."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_model_parallel_tpu.train.checkpoint import Checkpointer
    from distributed_model_parallel_tpu.train.lm_trainer import LMTrainer
    from distributed_model_parallel_tpu.utils.telemetry import read_records

    # On a TPU "auto" must find the compiled kernels by itself; off it
    # (the --tiny rehearsal) "flash" runs them interpreted.
    attn = "auto" if on_tpu else "flash"
    seq = size["seq"]
    cfg = lm_config(lm_model(size, seq, attn), workdir, "lm",
                    batch=size["batch"], seq=seq, steps=size["steps"])
    t = LMTrainer(cfg)
    if on_tpu:
        toks, tgts = (jnp.asarray(x) for x in t.sample_batch(0, 0))
        check(has_custom_call(t._step, t.params, t.opt_state, toks, tgts),
              f'seq {seq} attn_impl="auto" step lowers to the Pallas '
              f'flash kernels')
    t.fit()
    losses = [r["loss"] for r in read_records(t.logger.jsonl_path)
              if r["kind"] == "step"]
    log(f"  lm d{size['d_model']}x{size['layers']}L seq {seq} "
        f"batch {size['batch']} step losses: {losses}")
    check(len(losses) == size["steps"] and all(np.isfinite(losses)),
          "lm losses finite")
    check(losses[-1] < losses[0], "lm loss lower at the last step")

    # checkpoint round trip, the way scripts/generate.py reads it
    restored = Checkpointer(cfg.checkpoint_dir).restore_subtree(
        {"params": jax.tree.map(jnp.zeros_like, t.params)}, "lm")["params"]
    check(all(np.array_equal(a, b) for a, b in zip(
        host_leaves(t.params), host_leaves(restored))),
        "checkpoint read back bit-equal")
    del t

    pseq = size["parity_seq"]
    pair = {}
    for impl in ("flash", "xla"):
        pt = LMTrainer(lm_config(lm_model(size, pseq, impl), workdir,
                                 f"lm-{impl}", batch=size["batch"], seq=pseq,
                                 steps=2))
        if impl == "flash" and on_tpu:
            toks, tgts = (jnp.asarray(x) for x in pt.sample_batch(0, 0))
            check(has_custom_call(pt._step, pt.params, pt.opt_state, toks,
                                  tgts),
                  f"seq {pseq} flash step lowers to the Pallas custom call")
        pair[impl] = two_steps(pt)
        del pt
    check_losses_agree(f"seq {pseq} flash vs xla", pair["flash"],
                       pair["xla"])
    return cfg.model, restored


def make_requests(size: dict, vocab: int, seed: int):
    """(prompt, max_new) pairs, shortest prompt first, lengths spread over
    the range. Every other prompt long enough opens with one shared
    prefix: the prefix cache can serve it to a request admitted after an
    earlier holder has prefilled, which the ones beyond the slot count
    are."""
    import numpy as np

    rng = np.random.default_rng(seed)
    n = size["n_requests"]
    shared = rng.integers(0, vocab, size["shared_prefix"]).tolist()
    lens = np.linspace(*size["prompt"], n).astype(int)
    out = []
    for i, plen in enumerate(lens):
        body = rng.integers(0, vocab, int(plen)).tolist()
        if i % 2 == 0 and plen > len(shared):
            body[:len(shared)] = shared
        out.append((body, int(rng.integers(size["new"][0],
                                           size["new"][1] + 1))))
    return out


def run_engine(params, cfg, serve_cfg, requests, snapshot_at=None):
    """Serve ``requests`` to completion. Returns (tokens per request,
    engine, snapshot): ``snapshot`` is the decode batch the engine was
    about to feed at iteration ``snapshot_at`` (pools copied: the live
    ones are donated to the next step; ``seqs``: each decoding slot's
    tokens so far, the one it is about to feed last)."""
    import jax.numpy as jnp
    import numpy as np

    from distributed_model_parallel_tpu.serve import Engine
    from distributed_model_parallel_tpu.serve.scheduler import RequestState

    snap = {}

    def hook(iteration):
        if snapshot_at is None or snap or iteration < snapshot_at:
            return
        decoding = [r for r in eng.results()
                    if r.state is RequestState.DECODE]
        if len(decoding) < 2:
            return
        b = serve_cfg.n_slots
        tokens, positions = np.zeros(b, np.int32), np.zeros(b, np.int32)
        active = np.zeros(b, bool)
        tables = np.zeros((b, eng.cache.pages_per_seq), np.int32)
        seqs = {r.slot: list(r.prompt) + list(r.generated) for r in decoding}
        for r in decoding:
            tokens[r.slot] = r.generated[-1]
            positions[r.slot] = r.prompt_len + len(r.generated) - 1
            active[r.slot] = True
            tables[r.slot] = eng.cache.table_array(r.rid)
        snap.update(ck=jnp.copy(eng.cache.ck), cv=jnp.copy(eng.cache.cv),
                    tokens=tokens, positions=positions, active=active,
                    tables=tables, seqs=seqs)

    eng = Engine(params, cfg, serve_cfg, step_hook=hook)
    reqs = [eng.submit(p, n, rid=f"r{i}")
            for i, (p, n) in enumerate(requests)]
    eng.run()
    check(all(r.state is RequestState.COMPLETED
              and len(r.generated) == r.max_new_tokens for r in reqs),
          f"all {len(reqs)} requests finished "
          f"(attn_impl={serve_cfg.attn_impl}, dtype={cfg.dtype.__name__}, "
          f"prefix_cache={serve_cfg.prefix_cache}, spec_k={serve_cfg.spec_k})")
    return [list(r.generated) for r in reqs], eng, snap


def phase_serve(size: dict, model, params, on_tpu: bool, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_model_parallel_tpu.serve import ServeConfig
    from distributed_model_parallel_tpu.serve.model import (
        decode_logits,
        make_decode_step,
        make_prefill_step,
    )

    kernel = "auto" if on_tpu else "pallas"    # see phase_lm
    pages_per_seq = -(-size["max_seq"] // size["page"])
    geometry = dict(n_slots=size["n_slots"], page_size=size["page"],
                    n_pages=(size["n_slots"] + 1) * pages_per_seq,
                    max_seq_len=size["max_seq"], prefill_chunk=size["chunk"])
    cfg = dataclasses.replace(model, max_seq_len=size["max_seq"],
                              attn_impl="auto", remat=False)
    requests = make_requests(size, cfg.vocab_size, seed)
    log(f"  {len(requests)} requests, prompt lengths "
        f"{[len(p) for p, _ in requests]}, new tokens "
        f"{[n for _, n in requests]}")
    check(len(requests) > size["n_slots"],
          "more requests than slots: some join as others leave")

    # -- bf16, the kernel, the path scripts/generate.py routes through
    serve_cfg = ServeConfig(attn_impl=kernel, **geometry)
    _, eng, snap = run_engine(params, cfg, serve_cfg, requests,
                              snapshot_at=len(requests))
    check(eng.cache.pool.free_pages == eng.cache.pool.n_pages,
          "page pool back to empty")
    check(bool(snap), "a decode batch was captured mid-run")
    b = size["n_slots"]
    if on_tpu:
        check(has_custom_call(
            make_decode_step(cfg, page_size=size["page"], impl=kernel),
            params, eng.cache.pools, None, jnp.zeros(b, jnp.int32),
            jnp.zeros(b, jnp.int32),
            (jnp.zeros((b, pages_per_seq), jnp.int32), None),
            jnp.zeros(b, bool), None),
              'attn_impl="auto" decode step lowers to the Pallas '
                   'paged-decode kernel')
        check(has_custom_call(
            make_prefill_step(cfg, page_size=size["page"],
                              chunk=size["chunk"], impl=kernel),
            params, eng.cache.pools, None,
            jnp.zeros((1, size["chunk"]), jnp.int32), jnp.int32(0),
            jnp.int32(1), (jnp.zeros(pages_per_seq, jnp.int32), None),
            jax.random.key(0)),
              'attn_impl="auto" prefill step lowers to the Pallas '
                   'paged-prefill kernel')
    del eng

    # (a) bf16 decode logits on the captured batch, kernel vs XLA gather
    logits = {}
    for impl in (kernel, "xla"):
        fn = jax.jit(lambda p, ck, cv, tok, pos, tab, act, impl=impl:
                     decode_logits(p, (ck, cv, None, None), None, tok, pos,
                                   (tab, None), act, cfg,
                                   page_size=size["page"], impl=impl)[2])
        out = fn(params, snap["ck"], snap["cv"], snap["tokens"],
                 snap["positions"], snap["tables"], snap["active"])
        logits[impl] = np.asarray(out, np.float32)[snap["active"]]
    ref = logits["xla"]
    worst = float(np.abs(logits[kernel] - ref).max())
    scale = float(np.abs(ref).max())
    check(np.isfinite(ref).all() and ref.shape[1] == cfg.vocab_size,
          f"decode logits finite, shape {ref.shape}")
    check(worst <= DECODE_LOGIT_RTOL * scale,
          f"bf16 decode logits, kernel vs xla: max |diff| {worst:.3g} <= "
          f"{DECODE_LOGIT_RTOL:g} x largest logit {scale:.3g}")
    del snap, logits

    # (b) float32 params and cache, true-f32 matmuls: the paths differ by
    # rounding only, so greedy tokens are identical. Why f32: a decode
    # round, a prompt chunk and the speculative verify window go through
    # two kernels and, under "xla", attend_rows; they sum in different
    # orders, so "speculation changes no token" cannot rest on any two
    # being bit-equal.
    cfg32 = dataclasses.replace(cfg, dtype=jnp.float32)
    params32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        runs = {}
        for name, kw in (("kernel", dict(attn_impl=kernel)),
                         ("xla", dict(attn_impl="xla")),
                         ("kernel+prefix+spec", dict(
                             attn_impl=kernel, prefix_cache=True,
                             spec_k=4))):
            toks, eng, _ = run_engine(params32, cfg32,
                                      ServeConfig(**geometry, **kw),
                                      requests)
            runs[name] = toks
            if name == "kernel+prefix+spec":
                log(f"  prefix cache hit rate {eng.cache_hit_rate}, draft "
                    f"accept rate {eng.draft_accept_rate}")
                check(bool(eng.cache_hit_rate),
                      "the prefix cache served some prompt tokens")
            del eng
    check(runs["kernel"] == runs["xla"],
          "f32 engine: greedy tokens identical, kernel vs xla")
    check(runs["kernel+prefix+spec"] == runs["kernel"],
          "f32 engine: prefix cache + speculation change no token")


def phase_serve_routed(size: dict, block: dict, on_tpu: bool,
                       seed: int) -> None:
    """The gated, routed, mixed-attention block through the engine: a
    dense layer and one period of sliding, sliding, full, sliding; heads
    wider than the hidden size; RMSNorm, QK-norm, a SiLU-gated FFN;
    sigmoid-routed dropless experts of which a quarter are held, beside a
    shared one; sliding layers in rings, the full layer in the pool."""
    import jax
    import jax.numpy as jnp

    from distributed_model_parallel_tpu.models import transformer as tfm
    from distributed_model_parallel_tpu.ops import moe
    from distributed_model_parallel_tpu.serve import ServeConfig

    kernel = "auto" if on_tpu else "pallas"
    s = tfm.LayerKind(window=block["window"], rope=True, ffn="moe")
    kinds = (dataclasses.replace(s, ffn="dense"), s, s,
             dataclasses.replace(s, window=None, rope=False), s)
    cfg = tfm.TransformerConfig(
        vocab_size=block["vocab"], d_model=block["d_model"],
        n_heads=block["heads"], n_kv_heads=block["kv_heads"],
        d_head=block["head_dim"], n_layers=len(kinds), d_ff=block["d_ff"],
        max_seq_len=size["max_seq"], dtype=jnp.float32,
        pos_embedding="rope", norm="rmsnorm", ffn="swiglu", qk_norm=True,
        layer_kinds=kinds, moe_experts=block["experts"],
        moe_top_k=block["top_k"], moe_dropless=True, moe_scoring="sigmoid",
        moe_routed_scale=2.5, moe_router_bias=True,
        moe_d_ff=block["d_expert"], moe_shared_experts=1,
        moe_experts_held=block["held"])
    params = tfm.init_params(jax.random.key(seed), cfg)
    # the held experts' products on rows sorted by expert: the kernel
    # (compiled on the chip, interpreted off it) against ragged_dot, at a
    # chunk's rows: groups of unequal size, one empty, rows past the held
    bp = jax.tree.map(lambda a: a[0], params["blocks"][1])
    rows = size["chunk"] * block["top_k"]
    sizes = jnp.asarray([rows // 16, 0, rows // 4, 3], jnp.int32)
    xs = jax.random.normal(jax.random.key(seed + 1), (rows, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        visits = moe.row_tile_visits(sizes, rows)
        got = moe.expert_products(xs, bp, sizes, visits,
                                  interpret=not on_tpu)
        ragged = jax.lax.ragged_dot
        want = ragged(jax.nn.silu(ragged(xs, bp["we_g"], sizes))
                      * ragged(xs, bp["we_u"], sizes), bp["we_d"], sizes)
    held = int(sizes.sum())
    worst = float(jnp.max(jnp.abs(got[:held] - want[:held])))
    check(worst <= RULE_ATOL, f"grouped expert products, kernel vs "
          f"ragged_dot over {held} held rows of {rows} in "
          f"{int(visits[3])} visits: within {worst:.2e} (limit "
          f"{RULE_ATOL:.0e})")
    pages_per_seq = -(-size["max_seq"] // size["page"])
    geometry = dict(n_slots=size["n_slots"], page_size=size["page"],
                    n_pages=(size["n_slots"] + 1) * pages_per_seq,
                    max_seq_len=size["max_seq"], prefill_chunk=size["chunk"])
    requests = make_requests(size, cfg.vocab_size, seed)
    with jax.default_matmul_precision("highest"):
        runs = {}
        for name, impl in (("kernel", kernel), ("xla", "xla")):
            runs[name], eng, _ = run_engine(
                params, cfg, ServeConfig(attn_impl=impl, **geometry),
                requests)
            lay = eng.cache.layout
            check(lay.n_ring == 4 and lay.n_full == 1
                  and lay.ring_pages < pages_per_seq,
                  f"sliding layers keep rings of {lay.ring_pages} pages, "
                  f"the full layer {pages_per_seq} a sequence")
            check(eng.cache.ring_pool.free_pages == eng.cache.ring_pool.n_pages
                  and eng.cache.pool.free_pages == eng.cache.pool.n_pages,
                  "rings and page pool back to empty")
            counters = eng.moe_counters()
            check(sorted(counters) == [1, 2, 3, 4] and all(
                0 < c["held_assignments"] < block["top_k"]
                * c["tokens_routed"] for c in counters.values()),
                "every routed layer counted tokens on its held experts")
            del eng
    check(runs["kernel"] == runs["xla"],
          "f32 routed engine: greedy tokens identical, kernel vs xla")


def phase_serve_hybrid(size: dict, block: dict, on_tpu: bool,
                       seed: int) -> None:
    """The gated-delta layers: the rule's three forms against one another
    at the block's widths (the decode kernel on a state pool against the
    step on the layer's slab; the chunked form against the recurrence),
    then two periods of linear, linear, linear, full through the engine
    (norms on the sublayers' outputs, whole-vector QK-norm, no rotation;
    the full layers' KV heads stored as the pool pads them), kernels
    against the XLA forms."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_model_parallel_tpu.models import transformer as tfm
    from distributed_model_parallel_tpu.ops import gated_delta as gd
    from distributed_model_parallel_tpu.serve import ServeConfig
    from distributed_model_parallel_tpu.serve.paged_kv import (
        memory_gauges,
        stored_kv_heads,
    )

    kernel = "auto" if on_tpu else "pallas"
    h, dk, dv = block["lin_heads"], block["lin_dk"], block["lin_dv"]
    n, t = size["n_slots"], block["rule_tokens"]
    ks = jax.random.split(jax.random.key(seed), 8)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (n, t, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (n, t, h, dk))
             + jax.random.normal(ks[2], (n, 1, h, dk)))
    v = jax.random.normal(ks[3], (n, t, h, dv))
    log_alpha = -jax.random.uniform(ks[4], (n, t, h), minval=1e-3, maxval=0.3)
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[5], (n, t, h)))
    state = jax.random.normal(ks[6], (n, h, dk, dv))
    with jax.default_matmul_precision("highest"):
        # one decode round on layer 1 of a pool of three, row 0 idle
        check(gd.decode_kernel_takes(h, dv), "the decode kernel takes the "
              f"block's heads ({h} x {dv})")
        pool = jnp.stack([gd.pool_state(state * s) for s in (1.0, 0.5, 2.0)])
        alpha1 = jnp.exp(log_alpha[:, 0]).at[0].set(1.0)
        beta1 = beta[:, 0].at[0].set(0.0)
        args = (jnp.int32(1), q[:, 0], k[:, 0], v[:, 0], alpha1, beta1)
        decode = jax.jit(gd.gated_delta_decode, static_argnames=("impl",))
        o_k, pool_k = decode(pool, *args, impl=kernel)
        o_x, pool_x = decode(pool, *args, impl="xla")
        if on_tpu:
            check(has_custom_call(jax.jit(lambda p: gd.gated_delta_decode(
                p, *args, impl=kernel)), pool),
                "the decode round's state update is the compiled kernel")
        worst = max(float(jnp.max(jnp.abs(o_k - o_x))),
                    float(jnp.max(jnp.abs(pool_k - pool_x))))
        check(worst <= RULE_ATOL, f"gated-delta decode kernel vs the step "
              f"on the slab: within {worst:.2e} (limit {RULE_ATOL:.0e})")
        check(bool(jnp.array_equal(pool_k[1, 0], pool[1, 0]))
              and bool(jnp.array_equal(pool_k[::2], pool[::2])),
              "an idle row's state and the other layers' stay bit for bit")
        # the chunked form against the recurrence, rows of unequal length
        valid = jnp.arange(t)[None, :] < (t - 7 * jnp.arange(n))[:, None]
        o_c, s_c = jax.jit(gd.gated_delta_chunk)(q, k, v, log_alpha, beta,
                                                 state, valid)

        def token(s, xs):
            q, k, v, la, b, ok = xs
            o, s = gd.gated_delta_step(
                q, k, v, jnp.where(ok[:, None], jnp.exp(la), 1.0),
                jnp.where(ok[:, None], b, 0.0), s)
            return s, o

        rows = lambda x: jnp.moveaxis(x, 1, 0)
        s_r, o_r = jax.jit(lambda *xs: jax.lax.scan(token, state, xs))(
            *(rows(x) for x in (q, k, v, log_alpha, beta, valid)))
        worst = max(float(jnp.max(jnp.abs(s_c - s_r))), float(jnp.max(
            jnp.where(valid[..., None, None], jnp.abs(o_c - rows(o_r)), 0))))
        check(worst <= RULE_ATOL, f"gated-delta chunked form ({t} tokens) "
              f"vs the recurrence: within {worst:.2e} (limit "
              f"{RULE_ATOL:.0e})")
    # as the engine runs them (the chip's default products): logged only
    o_d, s_d = jax.jit(gd.gated_delta_chunk)(q, k, v, log_alpha, beta, state,
                                             valid)
    log(f"chunked form at default precision: state within "
        f"{float(jnp.max(jnp.abs(s_d - s_r))):.2e} of the recurrence")

    lin, full = tfm.LayerKind(mixer="gated_delta"), tfm.LayerKind()
    cfg = tfm.TransformerConfig(
        vocab_size=block["vocab"], d_model=block["d_model"],
        n_heads=block["heads"], n_kv_heads=block["heads"],
        d_head=block["head_dim"], n_layers=8, d_ff=block["d_ff"],
        max_seq_len=size["max_seq"], dtype=jnp.float32,
        pos_embedding="rope", norm="rmsnorm", norm_eps=1e-6, ffn="swiglu",
        qk_norm_whole=True, norm_placement="post",
        layer_kinds=(lin, lin, lin, full) * 2, lin_key_heads=h,
        lin_value_heads=h, lin_key_dim=dk, lin_value_dim=dv,
        lin_neg_eigval=True)
    params = tfm.init_params(jax.random.key(seed), cfg)
    params["embed"] = params["embed"] * 50.0       # unit embeddings
    pages_per_seq = -(-size["max_seq"] // size["page"])
    geometry = dict(n_slots=size["n_slots"], page_size=size["page"],
                    n_pages=(size["n_slots"] + 1) * pages_per_seq,
                    max_seq_len=size["max_seq"], prefill_chunk=size["chunk"])
    requests = make_requests(size, cfg.vocab_size, seed)
    with jax.default_matmul_precision("highest"):
        runs = {}
        for name, impl in (("kernel", kernel), ("xla", "xla")):
            runs[name], eng, _ = run_engine(
                params, cfg, ServeConfig(attn_impl=impl, **geometry),
                requests)
            lay = eng.cache.layout
            check((lay.n_state, lay.n_full, lay.n_ring) == (6, 2, 0)
                  and eng.cache.ck.shape[3] == stored_kv_heads(cfg.kv_heads),
                  f"six state layers hold no page; two full layers store "
                  f"{eng.cache.ck.shape[3]} KV heads for {cfg.kv_heads}")
            gauges = memory_gauges(eng.cache)
            check(gauges["state_slots"] == 0 and eng.cache.pool.free_pages
                  == eng.cache.pool.n_pages and eng.moe_counters() == {},
                  "slots and page pool back to empty")
            del eng
    check(runs["kernel"] == runs["xla"],
          "f32 hybrid engine: greedy tokens identical, kernels vs xla")


def phase_serve_looped(size: dict, block: dict, on_tpu: bool,
                       seed: int) -> None:
    """A looped stack through the engine: ``layers`` layers run
    ``passes`` times over the same leaves, a cache layer a (pass, layer);
    sandwich norms, the final norm closing every pass, the exit gate."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_model_parallel_tpu.models import transformer as tfm
    from distributed_model_parallel_tpu.serve import ServeConfig
    from distributed_model_parallel_tpu.serve.model import decode_logits

    kernel = "auto" if on_tpu else "pallas"
    t, n_layers = block["passes"], block["layers"]
    cfg = tfm.TransformerConfig(
        vocab_size=block["vocab"], d_model=block["d_model"],
        n_heads=block["heads"], n_kv_heads=block["heads"],
        d_head=block["head_dim"], n_layers=n_layers, d_ff=block["d_ff"],
        max_seq_len=size["max_seq"], dtype=jnp.float32,
        pos_embedding="rope", rope_theta=1e6, norm="rmsnorm", norm_eps=1e-6,
        ffn="swiglu", norm_placement="sandwich", n_passes=t,
        loop_final_norm=True, exit_gate=True)
    params = tfm.init_params(jax.random.key(seed), cfg)
    params["embed"] = params["embed"] * 50.0       # unit embeddings
    pages_per_seq = -(-size["max_seq"] // size["page"])
    geometry = dict(n_slots=size["n_slots"], page_size=size["page"],
                    n_pages=(size["n_slots"] + 1) * pages_per_seq,
                    max_seq_len=size["max_seq"], prefill_chunk=size["chunk"])
    requests = make_requests(size, cfg.vocab_size, seed)
    with jax.default_matmul_precision("highest"):
        runs = {}
        for name, impl in (("kernel", kernel), ("xla", "xla")):
            runs[name], eng, snap = run_engine(
                params, cfg, ServeConfig(attn_impl=impl, **geometry),
                requests, snapshot_at=size["n_slots"])
            check(eng.cache.ck.shape[0] == t * n_layers
                  and jax.tree.leaves(params["blocks"])[0].shape[0]
                  == n_layers,
                  f"{t * n_layers} cache layers for {n_layers} layers of "
                  f"weights")
            got = eng.loop_counters()
            fed = sum(len(p) + n - 1 for p, n in requests)
            check(got["tokens"] == fed
                  and got["token_passes"] == t * fed
                  and abs(sum(got["exit_mass"]) - fed) <= 1e-3 * fed
                  and min(got["exit_mass"]) > 0,
                  f"loop counters: {fed} tokens, {t} passes each, the "
                  f"gate's mass {[round(m) for m in got['exit_mass']]}")
            check(eng.cache.pool.free_pages == eng.cache.pool.n_pages,
                  "page pool back to empty")
            del eng
        check(runs["kernel"] == runs["xla"],
              "f32 looped engine: greedy tokens identical, kernel vs xla")
        # the captured decode batch (the xla run's: same tokens, same
        # pools): logits through the cache under each impl, and of the
        # full forward over each row's whole sequence, no cache
        logits = {}
        for impl in (kernel, "xla"):
            out = jax.jit(lambda p, ck, cv, tok, pos, tab, act, impl=impl:
                          decode_logits(p, (ck, cv, None, None, None, None),
                                        None, tok, pos, (tab, None), act,
                                        cfg, page_size=size["page"],
                                        impl=impl)[2])(
                params, snap["ck"], snap["cv"], snap["tokens"],
                snap["positions"], snap["tables"], snap["active"])
            logits[impl] = np.asarray(out, np.float32)[snap["active"]]
        worst = float(np.abs(logits[kernel] - logits["xla"]).max())
        check(worst <= LOOP_LOGIT_ATOL,
              f"looped decode logits through {t * n_layers} cache layers, "
              f"kernel vs xla: within {worst:.2e} (limit "
              f"{LOOP_LOGIT_ATOL:.0e})")
        rows = [i for i in range(size["n_slots"]) if snap["active"][i]]
        full = [np.asarray(tfm.apply(
            params, jnp.asarray(snap["seqs"][i], jnp.int32)[None],
            cfg)[0, -1], np.float32) for i in rows]
        worst = float(np.abs(logits["xla"] - np.stack(full)).max())
        check(worst <= LOOP_LOGIT_ATOL,
              f"looped decode logits of {len(rows)} rows, through the "
              f"cache vs the full forward with none: within {worst:.2e} "
              f"(limit {LOOP_LOGIT_ATOL:.0e})")


def run_single(sizes: dict, workdir: str, meter: CompileMeter, dev,
               seed: int) -> None:
    on_tpu = dev.platform == "tpu"
    with phase("cnn", meter):
        phase_cnn(sizes["cnn"], workdir, dev)
    with phase("lm", meter):
        model, params = phase_lm(sizes["lm"], workdir, on_tpu)
    with phase("serve", meter):
        phase_serve(sizes["serve"], model, params, on_tpu, seed)
    with phase("serve-routed", meter):
        phase_serve_routed(sizes["serve"], sizes["routed"], on_tpu, seed)
    with phase("serve-hybrid", meter):
        phase_serve_hybrid(sizes["serve"], sizes["hybrid"], on_tpu, seed)
    with phase("serve-looped", meter):
        phase_serve_looped(sizes["serve"], sizes["looped"], on_tpu, seed)


# ---------------------------------------------------------------------------
# four chips (--multichip): only what exists across chips
# ---------------------------------------------------------------------------

def check_placement(name: str, tree, devices) -> None:
    """Every array of ``tree`` lives on exactly ``devices``, and every
    one of them holds memory — code that has only seen virtual CPU
    devices may have put everything on device 0."""
    import jax

    want = set(devices)
    bad = [jax.tree_util.keystr(path)
           for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]
           if set(x.sharding.device_set) != want]
    check(not bad, f"{name}: every train-state array is laid over the "
                   f"{len(want)} devices of its mesh"
                   + (f" (not: {bad[:4]})" if bad else ""))
    check_memory(name, devices)


def check_memory(name: str, devices) -> None:
    stats = [d.memory_stats() for d in devices]
    if all(s is not None for s in stats):      # the CPU reports none
        in_use = [s["bytes_in_use"] for s in stats]
        check(all(n > 0 for n in in_use),
              f"{name}: bytes_in_use non-zero on every device {in_use}")


def check_step_matches(name: str, loss, updates, ref_loss, ref_updates):
    import numpy as np

    check(abs(loss - ref_loss) <= DP_LOSS_RTOL * abs(ref_loss),
          f"{name}: first-step loss {loss:.6f} == one-device "
          f"{ref_loss:.6f} (rtol {DP_LOSS_RTOL:g})")
    def norm(leaves):
        return float(np.sqrt(sum(np.sum(np.square(x, dtype=np.float64))
                                 for x in leaves)))

    off = norm([u - r for u, r in zip(updates, ref_updates)])
    off /= norm(ref_updates)
    check(len(updates) == len(ref_updates) and off <= DP_UPDATE_RTOL,
          f"{name}: post-step parameters == one-device step (update off "
          f"by {off:.2e} of its norm, tol {DP_UPDATE_RTOL:g})")


def cnn_one_step(trainer, rng):
    """One step on the first global batch; (loss, per-leaf update)."""
    bs = trainer.config.data.batch_size
    images, labels = trainer.train_ds.images[:bs], trainer.train_ds.labels[:bs]
    before = host_leaves(trainer.state.params)
    trainer.state, m = trainer._train_step(
        trainer.state, rng, *trainer._shard_batch(images, labels))
    return float(m["loss"]), [
        a - b for a, b in zip(host_leaves(trainer.state.params), before)]


def phase_multi_cnn(size: dict, workdir: str, devices) -> None:
    import jax

    from distributed_model_parallel_tpu.config import MeshConfig, ModelConfig
    from distributed_model_parallel_tpu.train.pipeline_trainer import (
        PipelineTrainer,
    )
    from distributed_model_parallel_tpu.train.trainer import Trainer

    rng = jax.random.key(7)

    def cfg(name, **kw):
        # float32 (and true-f32 matmuls, below) so that one device and
        # four differ by summation order only; augmentation off because
        # DDP folds the replica index into its rng.
        base = cnn_config(size, workdir, name, device_resident_data=False)
        return base.replace(
            model=kw.pop("model", ModelConfig(name=size["model"])),
            data=dataclasses.replace(base.data, augment=False), **kw)

    ref = cnn_one_step(Trainer(cfg("one-device")), rng)
    log(f"  one-device first-step loss {ref[0]:.6f}")

    t = Trainer(cfg("gspmd-dp4", mesh=MeshConfig(data=4)))
    got = cnn_one_step(t, rng)
    check_step_matches("GSPMD dp4", *got, *ref)
    check_placement("GSPMD dp4", t.state, devices)
    del t

    # Per-replica BatchNorm would normalise 128-sample shards; psum-synced
    # statistics are the one-device step's math.
    t = Trainer(cfg("ddp-dp4", mesh=MeshConfig(data=4), strategy="ddp",
                    model=ModelConfig(name=size["model"],
                                      batchnorm="sync")))
    got = cnn_one_step(t, rng)
    check_step_matches("shard_map DDP dp4", *got, *ref)
    check_placement("shard_map DDP dp4", t.state, devices)
    del t

    # The reference's four-stage pipeline (scripts/train_model_parallel.py:
    # boundaries 0,4,10,16,19, 8 micro-batches). BatchNorm-free, so that
    # micro-batching leaves the full-batch gradient and the one-device
    # step is an exact reference.
    nobn = ModelConfig(name=size["model"], batchnorm="none")
    boundaries = [0, 4, 10, 16, 19] if size["model"] == "mobilenetv2" else None
    ref = cnn_one_step(Trainer(cfg("one-device-nobn", model=nobn)), rng)
    pt = PipelineTrainer(cfg("pipe4", model=nobn, mesh=MeshConfig(stage=4),
                             num_microbatches=8,
                             stage_boundaries=boundaries))
    runner = pt.runner
    bs = size["batch"]
    before = host_leaves(runner.merged_params())
    metrics = runner.train_step(rng, pt.train_ds.images[:bs],
                                pt.train_ds.labels[:bs])
    check_step_matches(
        "four-stage pipeline M=8", float(metrics["loss"]),
        [a - b for a, b in zip(host_leaves(runner.merged_params()), before)],
        *ref)
    homes = [{d for leaf in jax.tree.leaves(st.params)
              for d in leaf.devices()} for st in runner.stages]
    check(all(len(h) == 1 for h in homes)
          and len(set().union(*homes)) == 4,
          f"each pipeline stage's parameters live on their own device "
          f"{[sorted(d.id for d in h) for h in homes]}")
    check_memory("four-stage pipeline", devices)


def phase_multi_lm(lm: dict, multi: dict, workdir: str, on_tpu: bool,
                   devices) -> None:
    from distributed_model_parallel_tpu.config import MeshConfig
    from distributed_model_parallel_tpu.train.lm_trainer import LMTrainer

    seq, batch = multi["lm_seq"], multi["lm_batch"]
    attn = "auto" if on_tpu else "flash"

    def trainer(name, mesh, **kw):
        tp = "model" if mesh.model > 1 else None
        model = lm_model(lm, seq, attn, tp_axis=tp,
                         loss_chunk=multi["loss_chunk"])
        return LMTrainer(lm_config(model, workdir, name, batch=batch,
                                   seq=seq, steps=2, mesh=mesh, **kw))

    t = trainer("lm-one-device", MeshConfig())
    ref = two_steps(t)
    log(f"  one-device losses {ref}")
    del t
    for name, log_name, mesh, kw in (
            ("LM dp2 x tp2", "lm-dp2-tp2", MeshConfig(data=2, model=2), {}),
            ("LM pp2 1F1B M=8 (x dp2)", "lm-pp2-dp2",
             MeshConfig(data=2, stage=2),
             dict(pipeline_schedule="1f1b", num_microbatches=8))):
        t = trainer(log_name, mesh, **kw)
        got = two_steps(t)
        check_losses_agree(name, got, ref)
        check_placement(name, {"params": t.params, "opt": t.opt_state},
                        devices)
        del t


def run_multichip(sizes: dict, workdir: str, meter: CompileMeter, dev) -> None:
    import jax

    devices = jax.devices()[:4]
    if len(devices) < 4:
        raise SystemExit(f"--multichip needs four devices, JAX reports "
                         f"{len(jax.devices())}")
    on_tpu = dev.platform == "tpu"
    with phase("multichip-cnn", meter), \
            jax.default_matmul_precision("highest"):
        phase_multi_cnn(sizes["cnn"], workdir, devices)
    with phase("multichip-lm", meter):
        phase_multi_lm(sizes["lm"], sizes["multi"], workdir, on_tpu, devices)


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--multichip", action="store_true",
                    help="run only the cross-chip paths (four devices)")
    ap.add_argument("--tiny", action="store_true",
                    help="rehearse at toy sizes, kernels interpreted, on "
                         "the backend the caller chose")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from distributed_model_parallel_tpu.utils.compile_cache import (
        enable_compile_cache,
    )
    from distributed_model_parallel_tpu.utils.device_contact import (
        EXIT_NO_ACCELERATOR,
        require_devices,
    )

    cache_dir = enable_compile_cache()
    dev = require_devices("chip-smoke")[0]
    if dev.platform != "tpu" and not args.tiny:
        print(f"[chip-smoke] no usable accelerator: platform "
              f"{dev.platform!r}; only --tiny rehearses off the chip",
              file=sys.stderr, flush=True)
        return EXIT_NO_ACCELERATOR
    import jax

    versions = {p: importlib.metadata.version(p)
                for p in ("jax", "jaxlib", "libtpu", "flax", "optax",
                          "orbax-checkpoint")}
    log(f"versions {versions}")
    log(f"device {dev.platform} {dev.device_kind!r} x{len(jax.devices())}")
    log(f"compile cache {cache_dir or 'off (JAX_PLATFORMS=cpu)'}")
    meter = CompileMeter()
    sizes = TINY if args.tiny else FULL
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    t0 = time.perf_counter()
    try:
        if args.multichip:
            run_multichip(sizes, workdir, meter, dev)
        else:
            run_single(sizes, workdir, meter, dev, args.seed)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    entries = len(os.listdir(cache_dir)) if cache_dir else 0
    log(f"all phases ok wall_s={time.perf_counter() - t0:.1f} "
        f"compile_s={meter.seconds:.1f} cache_hits={meter.hits} "
        f"cache_misses={meter.misses} cache_entries={entries}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
