"""Benchmark: MobileNetV2/CIFAR-10 train-step throughput per chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "mfu"}.

Baseline anchor (BASELINE.md): the reference's data-parallel MobileNetV2
CIFAR-10 run at global batch 512 on 4 GPUs takes 0.396 s/batch
(``Readme.md:286``) = 1292.9 samples/s total = **323.2 samples/s/GPU**.
``vs_baseline`` is our per-chip throughput divided by that per-GPU number.
``mfu`` (model-FLOPs-utilization: XLA cost-analysis FLOPs per step / step
time / chip peak bf16 FLOP/s) makes the efficiency claim absolute rather
than relative to a 2019 GPU anchor; null off-TPU where peak is unknown.

The timed region is the full jitted train step — on-device augmentation,
forward, backward, SGD update — at batch 512 on however many chips are
visible (per-chip = total / n_chips). bfloat16 compute, float32 params.

Env knobs: DMP_BENCH_MODEL (mobilenetv2 | resnet50 | ...), DMP_BENCH_BATCH,
DMP_BENCH_STEPS, DMP_BENCH_SPD, and DMP_BENCH_WORKLOAD=lm for the
long-context Transformer train step (DMP_BENCH_SEQ, default 8192;
DMP_BENCH_REMAT=full|dots selects the block remat policy;
DMP_BENCH_LOSS_CHUNK is the chunked cross-entropy head's chunk size in
tokens, e.g. 8192 — 0 = dense head) measured in tokens/s/chip.
DMP_BENCH_WORKLOAD=decode is the dense-cache batch decode bench;
DMP_BENCH_WORKLOAD=serve replays a seeded open-loop Poisson trace through
the continuous-batching serving engine (serve/) against the static-batch
baseline and reports tokens/s/chip + p50/p99 TTFT/per-token latency +
page-pool occupancy (DMP_BENCH_SERVE_* knobs; docs/SERVING.md).
DMP_BENCH_SERVE_TRACE=chat switches to a seeded MULTI-TURN chat trace
(shared system prompt + per-conversation turns, each turn re-sending the
full history) replayed through the engine with prefix caching +
speculative decoding ON vs both OFF (the PR 9 engine) — the headline
gains cache_hit_rate / prefill_tokens_saved / draft_accept_rate and the
bar is >3x tokens/s/chip (DMP_BENCH_SERVE_CHAT_* knobs).

Failure semantics: a run that fails exits non-zero. First device contact
is one attempt and refuses any backend but a TPU unless the caller set
``JAX_PLATFORMS=cpu`` itself (utils/device_contact.py); an error during
the run propagates.
Every run also appends a telemetry stream (utils/telemetry; DMP_TELEMETRY
overrides the path, default /tmp/dmp_bench_log/bench_telemetry.jsonl) that
``scripts/dmp_report.py`` renders.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

BASELINE_SAMPLES_PER_SEC_PER_GPU = 512 / 0.396 / 4  # Readme.md:286


def _log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


from distributed_model_parallel_tpu.utils.compile_cache import (  # noqa: E402
    enable_compile_cache,
)
from distributed_model_parallel_tpu.utils.device_contact import (  # noqa: E402
    require_devices,
)


# The single >1.0-is-a-measurement-error policy point, shared with
# scripts/dmp_report.py (re-exported here for the bench record writers).
from distributed_model_parallel_tpu.utils.profiling import (  # noqa: E402
    demand_frac_of_peak,
)

# Every headline record embeds the active parallel plan (axis degrees +
# strategy, autotune/plan.py) so BENCH_*/MULTICHIP_* artifacts are
# self-describing and the planner's measured validation shares one
# record shape (docs/AUTOTUNE.md).
from distributed_model_parallel_tpu.autotune.plan import (  # noqa: E402
    plan_payload,
)


def _telemetry_run(workload: str, meta: dict):
    """Bench telemetry stream (utils/telemetry): DMP_TELEMETRY overrides
    the path; the default lands next to the bench logs."""
    from distributed_model_parallel_tpu.utils.telemetry import TelemetryRun

    path = os.environ.get(
        "DMP_TELEMETRY", "/tmp/dmp_bench_log/bench_telemetry.jsonl")
    return TelemetryRun(path, run=f"bench-{workload}",
                        meta=dict(workload=workload, **meta))


def _maybe_gate(telemetry) -> dict | None:
    """Run the cross-run perf regression gate (utils/baseline.py) on the
    stream this bench just wrote: compare the headline metrics against
    the baseline ledger's noise band and record a typed ``gate`` record.

    Warn-only by default — the bench still prints its headline and exits
    0; ``DMP_BENCH_GATE=strict`` makes :func:`_enforce_gate` exit 1 on a
    regression (after the headline JSON printed — the driver contract),
    ``DMP_BENCH_GATE=off`` skips entirely. ``DMP_BENCH_LEDGER`` points
    at the ledger (default: the repo's committed BASELINE_LEDGER.jsonl);
    ``DMP_BENCH_GATE_UPDATE=1`` appends a green run to it. The gate must
    never take down a measurement that succeeded: any internal error
    logs and returns None.
    """
    if os.environ.get("DMP_BENCH_GATE", "warn") == "off":
        return None
    try:
        from distributed_model_parallel_tpu.utils import baseline as bl
        from distributed_model_parallel_tpu.utils.telemetry import (
            read_records,
        )

        ledger_path = os.environ.get("DMP_BENCH_LEDGER", os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "BASELINE_LEDGER.jsonl"))
        recs = read_records(telemetry.path)
        # The default stream path appends across bench invocations: gate
        # only THIS run's records (from the last run_start header on).
        last = max(i for i, r in enumerate(recs)
                   if r.get("kind") == "run_start")
        points = bl.extract_points(recs[last:])
        if not points:
            return None
        result = bl.gate_points(points, bl.load_ledger(ledger_path))
        bl.emit_gate_record(telemetry, result, ledger_path=ledger_path)
        for v in result["regressions"]:
            attr = v.get("attribution") or {}
            where = attr.get("span") or attr.get("phase")
            _log(f"gate: REGRESSION {v['metric']}: {v['value']:g} vs "
                 f"baseline {v['baseline']:g} ± {v['tolerance']:g}"
                 + (f" — {where!r} grew {attr.get('baseline_share'):.1%}"
                    f" -> {attr.get('share'):.1%}" if where else ""))
        if result["ok"]:
            _log(f"gate: pass ({len(result['verdicts'])} metrics within "
                 f"the noise band of {ledger_path})")
            if os.environ.get("DMP_BENCH_GATE_UPDATE") == "1":
                bl.append_entries(ledger_path, bl.entries_from_points(
                    points, green=True,
                    source=f"bench:{os.path.basename(telemetry.path)}"))
        return result
    except Exception as e:  # noqa: BLE001 - observability must not kill bench
        _log(f"gate skipped: {type(e).__name__}: {e}")
        return None


def _enforce_gate(result: dict | None) -> None:
    """Strict mode: fail the run AFTER the headline printed."""
    if (result is not None and not result["ok"]
            and os.environ.get("DMP_BENCH_GATE") == "strict"):
        _log("gate: DMP_BENCH_GATE=strict — failing the run on the "
             "regression above")
        raise SystemExit(1)


def build_lm_bench(*, mesh=None, model=None, batch=None, seq=None,
                   steps=None, num_microbatches=None, schedule=None):
    """Long-context Transformer train-step workload, env-configured
    (DMP_BENCH_SEQ/BATCH/MOE_EXPERTS/PP/...; module docstring).

    Returns ``(trainer, step, info)`` where ``step()`` runs one train step
    (mutating the trainer's params/opt_state) and returns the device
    metrics, and ``info`` carries the static measurement identity (cfg,
    batch, seq, moe, n_chips, steps, tag). Shared with
    ``benchmarks/run_step_profile.py`` so the profiled program IS the
    timed program by construction, and with the parallelism autotuner's
    measured validation (``scripts/dmp_plan.py --measure``), whose
    keyword overrides — per-candidate ``mesh``/``num_microbatches``, a
    small ``model``, short ``steps`` — take precedence over the env knobs
    so every candidate is timed through THIS builder.
    """
    from distributed_model_parallel_tpu.config import MeshConfig
    from distributed_model_parallel_tpu.models import transformer as tfm
    from distributed_model_parallel_tpu.train.lm_trainer import (
        LMTrainConfig,
        LMTrainer,
    )

    n_chips = len(jax.devices())
    if seq is None:
        seq = (model.max_seq_len if model is not None
               else int(os.environ.get("DMP_BENCH_SEQ", "8192")))
    if batch is None:
        batch = int(os.environ.get("DMP_BENCH_BATCH", str(2 * n_chips)))
    if steps is None:
        steps = max(4, int(os.environ.get("DMP_BENCH_STEPS", "16")))
    # DMP_BENCH_MOE_EXPERTS > 0 swaps every block's FFN for a top-k routed
    # MoE (DMP_BENCH_MOE_TOPK, default 2) — the on-chip MoE throughput row
    # (drop rate reported alongside).
    moe = (model.moe_experts if model is not None
           else int(os.environ.get("DMP_BENCH_MOE_EXPERTS", "0")))
    if mesh is None:
        # DMP_BENCH_PP/DMP_BENCH_MICRO/DMP_BENCH_SCHEDULE bench the
        # pipeline schedules over a real stage axis (multi-chip rounds).
        pp = int(os.environ.get("DMP_BENCH_PP", "1"))
        if n_chips % pp:
            raise SystemExit(
                f"DMP_BENCH_PP={pp} must divide the chip count ({n_chips}); "
                f"a partial mesh would silently under-report the per-chip "
                f"numbers, which divide by all {n_chips} chips")
        mesh = MeshConfig(stage=pp, data=n_chips // pp)
    if model is None:
        model = tfm.TransformerConfig(
            vocab_size=32_000, d_model=1024, n_heads=8, n_layers=8,
            d_ff=4096, max_seq_len=seq, pos_embedding="rope",
            moe_experts=moe,
            moe_top_k=int(os.environ.get("DMP_BENCH_MOE_TOPK", "2")),
            remat=True,
            remat_policy=os.environ.get("DMP_BENCH_REMAT", "dots"),
            loss_chunk=int(os.environ.get("DMP_BENCH_LOSS_CHUNK", "0")),
            dtype=jnp.bfloat16)
    cfg = LMTrainConfig(
        model=model,
        batch_size=batch, seq_len=seq, n_tokens=4 * batch * (seq + 1),
        # A throughput bench needs no held-out eval, and at small batch the
        # default 10% tail cannot fit one seq_len eval window (ADVICE r3).
        eval_batches=0,
        mesh=mesh,
        num_microbatches=(num_microbatches if num_microbatches is not None
                          else int(os.environ.get("DMP_BENCH_MICRO", "1"))),
        pipeline_schedule=(schedule if schedule is not None
                           else os.environ.get("DMP_BENCH_SCHEDULE",
                                               "gpipe")),
        # Interleaved virtual stages (1f1b only; DMP_BENCH_VS=2 on a
        # multi-chip stage axis).
        virtual_stages=int(os.environ.get("DMP_BENCH_VS", "1")),
        log_dir="/tmp/dmp_bench_log", checkpoint_dir="/tmp/dmp_bench_ckpt",
    )
    t = LMTrainer(cfg)
    toks, tgts = t.sample_batch()
    toks, tgts = jnp.asarray(toks), jnp.asarray(tgts)
    _log(f"lm bench: seq={seq} batch={batch} layers={cfg.model.n_layers} "
         f"d_model={cfg.model.d_model}")

    def step():
        t.params, t.opt_state, m = t._step(t.params, t.opt_state,
                                           toks, tgts)
        return m

    tag = f"moe{moe}x{cfg.model.moe_top_k}_" if moe else ""
    if cfg.mesh.stage > 1:
        # Microbatch count is part of the measurement identity: the bubble
        # fraction (S-1)/(M+S-1) moves throughput ~2x across M.
        tag += (f"pp{cfg.mesh.stage}m{cfg.num_microbatches}_"
                f"{cfg.pipeline_schedule}_")
        if cfg.virtual_stages > 1:
            tag += f"v{cfg.virtual_stages}_"
    info = dict(cfg=cfg, batch=batch, seq=seq, moe=moe, n_chips=n_chips,
                steps=steps, tag=tag, step_args=(toks, tgts))
    return t, step, info


def bench_lm() -> None:
    """Long-context Transformer train-step bench (tokens/s/chip + MFU).

    The flagship long-context workload: flash-attention pallas kernels,
    RoPE, causal LM loss, one full SPMD train step at DMP_BENCH_SEQ tokens
    (default 8192 — the sequence length PARITY.md's kernel numbers quote).
    """
    from distributed_model_parallel_tpu.utils.profiling import (
        compiled_flops,
        fetch,
        fetch_overhead,
        lm_model_flops,
        peak_flops_per_chip,
    )

    t, step, info = build_lm_bench()
    cfg, batch, seq = info["cfg"], info["batch"], info["seq"]
    moe, n_chips, steps = info["moe"], info["n_chips"], info["steps"]
    toks, tgts = info["step_args"]
    telemetry = _telemetry_run("lm", dict(
        batch_size=batch, seq_len=seq, n_chips=n_chips,
        tokens_per_step=batch * seq,
        model_flops_per_step=lm_model_flops(cfg.model, batch, seq)))

    fetch(step())                       # compile + warm
    t_fetch = fetch_overhead()
    t0 = time.perf_counter()
    m = None
    for _ in range(steps):
        m = step()
    fetch(m)
    dt = max(1e-9, time.perf_counter() - t0 - t_fetch) / steps

    # MFU counts MODEL FLOPs analytically (utils/profiling.lm_model_flops).
    # XLA cost analysis is structurally unable to count this program: the
    # decoder stacks its L blocks in a lax.scan whose body cost analysis
    # counts ONCE (verified on v5e: an 8-iteration scanned matmul reports
    # 1 body), and the pallas flash-attention kernels are custom calls
    # with no registered cost, so every score/value matmul counts zero.
    # Rounds 1-2 published the cost-analysis number (0.11 at seq 8k) —
    # that undercounted ~4.4x; the step was already running at ~0.49.
    # The analytic count excludes remat/FA2-recompute (MFU, not HFU).
    flops = lm_model_flops(cfg.model, batch, seq)
    ca = compiled_flops(t._step, t.params, t.opt_state, toks, tgts)
    _log(f"model flops/step: {flops / 1e12:.2f} TF analytic "
         f"({(ca or 0) / 1e12:.2f} TF by cost analysis — lower bound only, "
         f"scan bodies counted once, pallas kernels zero)")
    peak = peak_flops_per_chip()
    # The analytic count covers the GLOBAL batch (unlike cost_analysis,
    # which reports the per-device partitioned module), so normalize by
    # the fleet's peak: per-chip FLOPs over per-chip peak.
    mfu = (round(flops / n_chips / dt / peak, 4)
           if flops and peak else None)
    tokens_per_s_per_chip = batch * seq / dt / n_chips
    tag = info["tag"]
    out = {
        "metric": f"lm_{tag}seq{seq}_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_s_per_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": None,   # the reference has no LM workload to anchor on
        "mfu": mfu,
        "plan": plan_payload(cfg.mesh, "spmd",
                             num_microbatches=cfg.num_microbatches),
    }
    if moe:
        out["moe_drop_rate"] = round(float(m["moe_drop"]), 4)
    telemetry.step(step=0, step_time_s=dt,
                   tokens_per_s=batch * seq / dt, mfu=mfu)
    telemetry.memory()
    telemetry.record("bench", **out)
    gate = _maybe_gate(telemetry)
    telemetry.finish()
    print(json.dumps(out))
    _enforce_gate(gate)


def build_decode_bench():
    """KV-cache greedy-decode workload, env-configured (DMP_BENCH_BATCH/
    PROMPT/GEN). Returns ``(gen, gen_args, info)``: ``gen(*gen_args)``
    runs one prompt+decode program. Shared with the step profiler."""
    from distributed_model_parallel_tpu.models import transformer as tfm

    batch = int(os.environ.get("DMP_BENCH_BATCH", "8"))
    t0_len = int(os.environ.get("DMP_BENCH_PROMPT", "128"))
    steps = int(os.environ.get("DMP_BENCH_GEN", "512"))
    cfg = tfm.TransformerConfig(
        vocab_size=32_000, d_model=1024, n_heads=8, n_layers=8, d_ff=4096,
        max_seq_len=t0_len + steps, pos_embedding="rope",
        dtype=jnp.bfloat16)
    params = tfm.init_params(jax.random.key(0), cfg)
    prompt = jnp.zeros((batch, t0_len), jnp.int32)
    gen = jax.jit(lambda p, pr: tfm.generate(p, cfg, pr, steps))
    info = dict(cfg=cfg, batch=batch, prompt_len=t0_len, gen_steps=steps)
    return gen, (params, prompt), info


def bench_decode() -> None:
    """KV-cache autoregressive decode throughput (greedy): tokens/s/chip.

    DMP_BENCH_PROMPT (default 128) prompt tokens batched DMP_BENCH_BATCH
    (default 8) wide, DMP_BENCH_GEN (default 512) generated tokens, on the
    same 8-layer d1024 model the LM train bench uses. Decode is
    bandwidth-bound (each step streams all params + the KV cache for one
    token), so the companion number is the implied HBM traffic at the
    measured rate vs peak."""
    from distributed_model_parallel_tpu.config import MeshConfig
    from distributed_model_parallel_tpu.models import transformer as tfm
    from distributed_model_parallel_tpu.utils.profiling import (
        fetch,
        fetch_overhead,
        peak_hbm_bytes_per_chip,
    )

    gen, (params, prompt), info = build_decode_bench()
    cfg, batch = info["cfg"], info["batch"]
    t0_len, steps = info["prompt_len"], info["gen_steps"]
    telemetry = _telemetry_run("decode", dict(
        batch_size=batch, prompt_len=t0_len, gen_steps=steps))
    _log(f"decode bench: batch={batch} prompt={t0_len} gen={steps}")
    fetch(gen(params, prompt))          # compile + warm
    t_fetch = fetch_overhead()
    t0 = time.perf_counter()
    out = gen(params, prompt)
    fetch(out)
    dt = max(1e-9, time.perf_counter() - t0 - t_fetch)
    toks_per_s = batch * steps / dt
    # Per decode step every parameter is read once; the cached attention
    # reads a BLOCK-QUANTIZED prefix of the cache (generate() decodes in
    # 256-position read-boundary segments — round 5; through round 4 it
    # read the full padded [total] with masking every step). bf16 bytes,
    # k and v.
    n_params = sum(x.size for x in jax.tree.leaves(params))
    total_len = t0_len + steps
    seg = tfm.DECODE_READ_SEG            # generate()'s segment size
    read_sum = sum(min(total_len, (p // seg + 1) * seg)
                   for p in range(t0_len, total_len - 1))
    read_sum += total_len          # the prefill emit counts one full read
    kv_bytes_total = cfg.n_layers * batch * read_sum * \
        cfg.kv_heads * cfg.head_dim * 2 * 2
    hbm_peak = peak_hbm_bytes_per_chip()
    implied = (2 * n_params * steps + kv_bytes_total) / dt
    frac, frac_err = demand_frac_of_peak(implied, hbm_peak)
    out = {
        "metric": f"lm_decode_bs{batch}_tokens_per_sec_per_chip",
        "value": round(toks_per_s, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": None,   # the reference has no inference path at all
        "mfu": None,
        # Demand-side estimate (analytic bytes / measured time), not a
        # hardware counter — same labeling convention as the CNN rows.
        "demand_gbs": round(implied / 1e9, 1),
        "demand_frac_of_peak": frac,
        # generate() is one unsharded jit (default placement) — the plan
        # says so rather than implying a mesh layout that isn't there.
        "plan": plan_payload(MeshConfig(), "decode"),
    }
    if frac_err:
        out["demand_frac_error"] = frac_err
    # Phase attribution (prefill / per-token decode / sampling) so a
    # decode regression is attributable like a training one.
    phase = decode_phase_record(info, params, prompt, dt)
    telemetry.record("step_phase", **phase)
    out["step_phase"] = phase
    telemetry.step(step=0, step_time_s=dt / max(1, steps),
                   tokens_per_s=toks_per_s)
    telemetry.memory()
    telemetry.record("bench", **out)
    gate = _maybe_gate(telemetry)
    telemetry.finish()
    print(json.dumps(out))
    _enforce_gate(gate)


def decode_phase_record(info: dict, params, prompt, dt_total: float) -> dict:
    """``step_phase``-style attribution for the decode bench: where the
    generate program's wall time goes — prompt prefill vs per-token
    cached decode vs sampling — so a serving regression is attributable
    to a phase like a training one (the train bench's host/h2d/device
    split). Measured as serialized sub-program probes (each jitted and
    synced on its own), with the per-token decode derived as the
    remainder of the measured total; on CPU the phase timings are
    omitted honestly (dispatch overhead swamps sub-millisecond
    phases there), but the pipeline identity is still recorded."""
    from distributed_model_parallel_tpu.models import transformer as tfm
    from distributed_model_parallel_tpu.utils.profiling import (
        fetch,
        fetch_overhead,
    )

    cfg, batch = info["cfg"], info["batch"]
    t0_len, steps = info["prompt_len"], info["gen_steps"]
    rec: dict = {"pipeline": {
        "workload": "decode",
        "batch": batch, "prompt_len": t0_len, "gen_steps": steps,
        "kv_cache": "dense",           # bench_decode times generate()'s
                                       # dense read-boundary cache; the
                                       # paged engine is BENCH_serve
        "read_segment": tfm.DECODE_READ_SEG,
    }}
    if jax.devices()[0].platform == "cpu":
        rec["phases"] = None
        rec["reason"] = ("cpu: per-phase probe times are dominated by "
                         "dispatch overhead, not attributable phase cost")
        return rec
    t_fetch = fetch_overhead()

    def timed(fn, *args, n=3):
        fetch(fn(*args))               # compile + warm
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(*args)
        fetch(out)
        return max(0.0, (time.perf_counter() - t0 - t_fetch) / n)

    # Prefill proxy: one full forward over the prompt (the batched
    # prefill is exactly one forward that also writes the cache).
    # Reduce to the last position's argmax INSIDE the jitted fn — what
    # prefill actually consumes — so the timed bracket's closing fetch
    # moves [B] ints, not the whole [B, T, V] logits (a ~65 MB D2H
    # would swamp the compute being attributed).
    prefill_s = timed(jax.jit(
        lambda p, pr: jnp.argmax(tfm.apply(p, pr, cfg)[:, -1], axis=-1)),
        params, prompt)
    # Sampling: the per-step argmax over [B, V] logits.
    logits = jnp.zeros((batch, cfg.vocab_size), cfg.dtype)
    sample_token_s = timed(jax.jit(
        lambda lg: jnp.argmax(lg, axis=-1).astype(jnp.int32)), logits)
    decode_token_s = max(
        0.0, dt_total - prefill_s - steps * sample_token_s) / steps
    # Per-token convenience values ride in the pipeline identity; the
    # ``phases`` dict keeps UNIFORM units (wall seconds of the whole
    # generate run) so the report's share computation is meaningful —
    # mixing a per-run prefill with per-token decode would attribute
    # regressions to the wrong phase.
    rec["pipeline"]["decode_token_s"] = round(decode_token_s, 6)
    rec["pipeline"]["sample_token_s"] = round(sample_token_s, 6)
    rec["phases"] = {
        "prefill_s": round(prefill_s, 6),
        "decode_s": round(decode_token_s * steps, 6),
        "sample_s": round(sample_token_s * steps, 6),
        "n_steps": steps,
        "derivation": "decode_s = total - prefill - n*sample_token",
    }
    return rec


def build_serve_trace():
    """Seeded open-loop serving trace: Poisson arrivals
    (DMP_BENCH_SERVE_RATE req/s, exponential inter-arrivals), per-request
    prompt/generation lengths drawn uniform from env-configured ranges.
    The SAME trace drives both the continuous engine and the static
    baseline, so the speedup is a property of the scheduler, not the
    workload draw. Returns ``(trace, model_cfg)``."""
    from distributed_model_parallel_tpu.models import transformer as tfm

    rng = np.random.default_rng(int(os.environ.get(
        "DMP_BENCH_SERVE_SEED", "0")))
    n_reqs = int(os.environ.get("DMP_BENCH_SERVE_REQS", "48"))
    rate = float(os.environ.get("DMP_BENCH_SERVE_RATE", "50"))
    p_lo, p_hi = (int(x) for x in os.environ.get(
        "DMP_BENCH_SERVE_PROMPT", "16,96").split(","))
    g_lo, g_hi = (int(x) for x in os.environ.get(
        "DMP_BENCH_SERVE_GEN", "16,256").split(","))
    # Generation lengths are EOS-terminated in real traffic — roughly
    # geometric, not uniform. Default: exponential with mean at a
    # quarter of the cap, clipped to [g_lo, g_hi]; the heavy tail is
    # exactly what makes static batching pay for its stragglers.
    # DMP_BENCH_SERVE_GEN_DIST=uniform flattens it.
    gen_dist = os.environ.get("DMP_BENCH_SERVE_GEN_DIST", "exp")

    def draw_gen() -> int:
        if gen_dist == "uniform":
            return int(rng.integers(g_lo, g_hi + 1))
        return int(min(g_hi, g_lo + rng.exponential((g_hi - g_lo) / 4)))
    cfg = tfm.TransformerConfig(
        vocab_size=int(os.environ.get("DMP_BENCH_SERVE_VOCAB", "8192")),
        d_model=int(os.environ.get("DMP_BENCH_SERVE_DMODEL", "512")),
        n_heads=8,
        n_layers=int(os.environ.get("DMP_BENCH_SERVE_LAYERS", "4")),
        d_ff=int(os.environ.get("DMP_BENCH_SERVE_DFF", "2048")),
        max_seq_len=p_hi + g_hi, pos_embedding="rope",
        dtype=jnp.bfloat16)
    t = 0.0
    trace = []
    for i in range(n_reqs):
        t += float(rng.exponential(1.0 / rate)) if rate > 0 else 0.0
        trace.append(dict(
            arrival_s=t,
            prompt=[int(x) for x in rng.integers(0, cfg.vocab_size,
                                                 rng.integers(p_lo,
                                                              p_hi + 1))],
            max_new_tokens=draw_gen(),
            seed=i))
    return trace, cfg


def build_serve_chat_trace():
    """Seeded multi-turn chat trace (``DMP_BENCH_SERVE_TRACE=chat``):
    ``CONVS`` conversations share one system prompt and run ``TURNS``
    turns each; every turn re-sends the full history (system + all prior
    user/assistant exchanges) plus fresh user tokens — the redundancy
    profile real chat traffic has and prefix caching monetizes.
    Generation lengths are fixed per (conversation, turn) draws so the
    same trace replays bit-for-bit through every engine configuration.
    Returns ``(chat, cfg)``; knobs:
    DMP_BENCH_SERVE_CHAT_{CONVS,TURNS,SYSTEM,USER,GEN} plus the shared
    DMP_BENCH_SERVE_{SEED,VOCAB,DMODEL,LAYERS,DFF}."""
    from distributed_model_parallel_tpu.models import transformer as tfm

    rng = np.random.default_rng(int(os.environ.get(
        "DMP_BENCH_SERVE_SEED", "0")))
    n_convs = int(os.environ.get("DMP_BENCH_SERVE_CHAT_CONVS", "8"))
    n_turns = int(os.environ.get("DMP_BENCH_SERVE_CHAT_TURNS", "5"))
    # A tool-heavy agent profile: the shared system prompt dominates the
    # first turn, the replayed history dominates the rest, and replies
    # are short and structured — the redundancy real multi-turn traffic
    # shows (vLLM/SGLang report >70% prefix reuse for agentic
    # workloads, where contexts are huge and tool-call outputs small).
    sys_len = int(os.environ.get("DMP_BENCH_SERVE_CHAT_SYSTEM", "512"))
    user_len = int(os.environ.get("DMP_BENCH_SERVE_CHAT_USER", "16"))
    gen_cap = int(os.environ.get("DMP_BENCH_SERVE_CHAT_GEN", "32"))
    vocab = int(os.environ.get("DMP_BENCH_SERVE_VOCAB", "8192"))
    max_seq = sys_len + n_turns * (user_len + gen_cap)
    # Chat mode defaults to float32: the cross-config determinism gate
    # (cache+spec tokens == baseline tokens, asserted every run) compares
    # tokens across three compiled program shapes, and bf16's coarse
    # rounding can flip greedy near-ties between shapes on CPU — f32 is
    # bitwise stable across all of them (same reason attend_rows pins
    # f32 score accumulation). DMP_BENCH_SERVE_DTYPE=bfloat16 opts back.
    dtype = jnp.dtype(os.environ.get("DMP_BENCH_SERVE_DTYPE", "float32"))
    cfg = tfm.TransformerConfig(
        vocab_size=vocab,
        d_model=int(os.environ.get("DMP_BENCH_SERVE_DMODEL", "512")),
        n_heads=8,
        n_layers=int(os.environ.get("DMP_BENCH_SERVE_LAYERS", "4")),
        d_ff=int(os.environ.get("DMP_BENCH_SERVE_DFF", "2048")),
        max_seq_len=max_seq, pos_embedding="rope", dtype=dtype)
    system = [int(x) for x in rng.integers(0, vocab, sys_len)]
    # Conversation STARTS stagger (open-loop reality: sessions do not
    # all begin in the same instant) — so the first conversation's
    # prefill publishes the shared system prompt to the radix tree
    # before the rest arrive, instead of 8 thundering-herd cold
    # prefills of the same prefix. Tokens are unaffected (pure function
    # of prompt + seed); only admission timing moves.
    stagger = float(os.environ.get("DMP_BENCH_SERVE_CHAT_STAGGER_S",
                                   "0.3"))
    chat = {"system": system, "n_turns": n_turns, "stagger_s": stagger,
            "convs": []}
    for c in range(n_convs):
        chat["convs"].append({
            "users": [[int(x) for x in rng.integers(0, vocab, user_len)]
                      for _ in range(n_turns)],
            # EOS-style exponential cap, like the Poisson trace's draws.
            "gens": [int(min(gen_cap, 8 + rng.exponential(gen_cap / 3)))
                     for _ in range(n_turns)],
        })
    return chat, cfg


def _replay_chat(chat, engine) -> list[list[list[int]]]:
    """Drive one engine through the whole chat campaign, wave by wave
    (turn t of every conversation submitted together, then run to
    drain — a closed loop: turn t+1's prompt embeds turn t's reply).
    Returns per-turn per-conversation generated tokens."""
    convs = chat["convs"]
    histories = [list(chat["system"]) + list(conv["users"][0])
                 for conv in convs]
    stagger = float(chat.get("stagger_s", 0.0))
    turns = []
    for t in range(chat["n_turns"]):
        wave = [engine.submit(histories[c], conv["gens"][t],
                              seed=1000 * c + t, rid=f"c{c}t{t}",
                              arrival_s=(c * stagger if t == 0 else 0.0))
                for c, conv in enumerate(convs)]
        engine.run(record_summary=False)   # ONE campaign summary at the end
        for c, req in enumerate(wave):
            if req.error is not None:
                raise RuntimeError(f"chat request {req.rid} failed: "
                                   f"{req.error}")
            if t + 1 < chat["n_turns"]:
                histories[c] = (histories[c] + req.generated
                                + list(convs[c]["users"][t + 1]))
        turns.append([r.generated for r in wave])
    return turns


def bench_serve_chat() -> None:
    """Multi-turn chat serving bench (``DMP_BENCH_SERVE_TRACE=chat``).

    Replays one seeded chat campaign through the engine twice —
    prefix caching + speculative decoding ON, then both OFF (the PR 9
    engine) — and reports tokens/s/chip for both, the speedup, cache hit
    rate, prefill tokens saved and draft accept rate. The two runs'
    token streams are asserted identical (the determinism contract that
    makes the comparison fair), and the acceptance bar is >3x.
    """
    from distributed_model_parallel_tpu.config import MeshConfig
    from distributed_model_parallel_tpu.models import transformer as tfm
    from distributed_model_parallel_tpu.serve import Engine, ServeConfig

    chat, cfg = build_serve_chat_trace()
    n_chips = len(jax.devices())
    params = tfm.init_params(jax.random.key(0), cfg)
    n_slots = int(os.environ.get("DMP_BENCH_SERVE_SLOTS", "8"))
    page = int(os.environ.get("DMP_BENCH_SERVE_PAGE", "16"))
    spec_k = int(os.environ.get("DMP_BENCH_SERVE_SPEC_K", "6"))
    pages_per_seq = -(-cfg.max_seq_len // page)
    n_convs = len(chat["convs"])
    telemetry = _telemetry_run("serve", dict(
        trace="chat", n_convs=n_convs, n_turns=chat["n_turns"],
        n_slots=n_slots, page_size=page, spec_k=spec_k,
        d_model=cfg.d_model, n_layers=cfg.n_layers))

    def make_config(on: bool) -> ServeConfig:
        return ServeConfig(
            n_slots=n_slots, page_size=page,
            # Room for the resident batch PLUS every conversation's
            # cached history (the tree evicts LRU if this is short).
            n_pages=(n_slots + n_convs + 1) * pages_per_seq,
            max_seq_len=cfg.max_seq_len,
            prefill_chunk=int(os.environ.get(
                "DMP_BENCH_SERVE_CHUNK", "32")),
            prefix_cache=on, spec_k=spec_k if on else 0)

    # Warm every compiled program (prefill + decode + the whole verify
    # width ladder) with inert dispatches; compile stays out of both
    # timed walls.
    for on in (True, False):
        Engine(params, cfg, make_config(on), slo_metrics=False).warmup()
    _log("serve-chat: programs warmed (compile excluded)")

    def run(on: bool):
        engine = Engine(params, cfg, make_config(on), telemetry=telemetry)
        turns = _replay_chat(chat, engine)
        summary = engine.summary()
        _log(f"serve-chat[{'cache+spec' if on else 'baseline'}]: "
             f"{summary['tokens_generated']} tokens in "
             f"{summary['wall_s']:.1f}s "
             f"({summary['tokens_per_s'] or 0:.1f} tok/s, "
             f"hit {summary['cache_hit_rate'] or 0:.2f}, "
             f"accept {summary['draft_accept_rate'] or 0:.2f})")
        return turns, summary

    on_turns, on_sum = run(True)
    off_turns, off_sum = run(False)
    if on_turns != off_turns:
        raise RuntimeError(
            "cache+spec run decoded different tokens than the baseline "
            "engine — the determinism contract is broken; refusing to "
            "report a throughput comparison between different outputs")
    tok_s = (on_sum["tokens_per_s"] or 0.0) / n_chips
    base_tok_s = (off_sum["tokens_per_s"] or 0.0) / n_chips
    out = {
        "metric": f"lm_serve_chat_bs{n_slots}_tokens_per_sec_per_chip",
        "value": round(tok_s, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": None,   # the reference repo has no serving path
        "mfu": None,
        "baseline_tokens_per_s_per_chip": round(base_tok_s, 1),
        "speedup_vs_baseline_engine": (round(tok_s / base_tok_s, 3)
                                       if base_tok_s else None),
        "tokens_identical_to_baseline": True,
        "cache_hit_rate": (round(on_sum["cache_hit_rate"], 4)
                           if on_sum["cache_hit_rate"] is not None
                           else None),
        "prefill_tokens_saved": on_sum["prefill_tokens_saved"],
        "draft_accept_rate": (round(on_sum["draft_accept_rate"], 4)
                              if on_sum["draft_accept_rate"] is not None
                              else None),
        "draft_tokens_proposed": on_sum["draft_tokens_proposed"],
        "spec_k": spec_k,
        "decode_steps": on_sum["decode_steps"],
        "baseline_decode_steps": off_sum["decode_steps"],
        "ttft_p50_s": round(on_sum["ttft_s"].get("p50", 0), 4),
        "ttft_p99_s": round(on_sum["ttft_s"].get("p99", 0), 4),
        "baseline_ttft_p99_s": round(off_sum["ttft_s"].get("p99", 0), 4),
        "token_latency_p50_s": round(
            on_sum["token_latency_s"].get("p50", 0), 5),
        "token_latency_p99_s": round(
            on_sum["token_latency_s"].get("p99", 0), 5),
        "page_occupancy_max": round(
            on_sum["page_occupancy"].get("max", 0), 3),
        "requests": n_convs * chat["n_turns"],
        "requests_completed": on_sum["requests_completed"],
        "plan": plan_payload(MeshConfig(), "serve"),
    }
    telemetry.memory()
    telemetry.record("bench", **out)
    gate = _maybe_gate(telemetry)
    telemetry.finish()
    print(json.dumps(out))
    _enforce_gate(gate)


def bench_serve() -> None:
    """Continuous-batching serving bench (``DMP_BENCH_WORKLOAD=serve``).

    Replays one seeded open-loop Poisson trace through the serving
    engine twice — continuous (iteration-level join/evict) and the
    static-batch baseline (admission only when the whole batch drained)
    — and reports tokens/s/chip, p50/p99 TTFT and per-token latency,
    page-pool occupancy and the continuous-vs-static speedup. The
    acceptance bar this bench exists to measure: continuous >= 1.5x
    static tokens/s/chip at no worse p99 TTFT on the same trace.

    Env knobs: DMP_BENCH_SERVE_{REQS,RATE,SEED,PROMPT,GEN,SLOTS,PAGE,
    VOCAB,DMODEL,LAYERS,DFF} (see build_serve_trace).
    """
    from distributed_model_parallel_tpu.config import MeshConfig
    from distributed_model_parallel_tpu.models import transformer as tfm
    from distributed_model_parallel_tpu.serve import Engine, ServeConfig

    trace, cfg = build_serve_trace()
    n_chips = len(jax.devices())
    params = tfm.init_params(jax.random.key(0), cfg)
    n_slots = int(os.environ.get("DMP_BENCH_SERVE_SLOTS", "8"))
    page = int(os.environ.get("DMP_BENCH_SERVE_PAGE", "16"))
    pages_per_seq = -(-cfg.max_seq_len // page)
    telemetry = _telemetry_run("serve", dict(
        n_requests=len(trace), n_slots=n_slots, page_size=page,
        d_model=cfg.d_model, n_layers=cfg.n_layers))

    def make_config(policy: str) -> ServeConfig:
        return ServeConfig(
            n_slots=n_slots, page_size=page,
            # Pool sized for a full batch of worst-case requests plus one
            # waiting admission: slots are the backpressure point, the
            # pool the safety margin (occupancy reported either way).
            n_pages=(n_slots + 1) * pages_per_seq,
            max_seq_len=cfg.max_seq_len,
            prefill_chunk=int(os.environ.get(
                "DMP_BENCH_SERVE_CHUNK", "32")),
            policy=policy)

    # Warmup: the step builders are memoized per geometry, so one tiny
    # engine run compiles the prefill + decode programs both timed runs
    # (continuous AND static — policy is host-side) then share; compile
    # is excluded from both walls, like every other bench here.
    warm = Engine(params, cfg, make_config("continuous"),
                  slo_metrics=False)   # keep warmup out of the registry
    warm.submit(trace[0]["prompt"], 2, seed=0)
    warm.run()
    _log("serve: programs warmed (compile excluded from timed runs)")

    def run(policy: str) -> dict:
        engine = Engine(params, cfg, make_config(policy),
                        telemetry=telemetry)
        for r in trace:
            engine.submit(r["prompt"], r["max_new_tokens"],
                          arrival_s=r["arrival_s"], seed=r["seed"])
        summary = engine.run()
        _log(f"serve[{policy}]: {summary['tokens_generated']} tokens in "
             f"{summary['wall_s']:.1f}s "
             f"({summary['tokens_per_s'] or 0:.1f} tok/s, "
             f"slot util {summary['slot_utilization']:.2f})")
        return summary

    cont = run("continuous")
    static = run("static")
    tok_s = (cont["tokens_per_s"] or 0.0) / n_chips
    static_tok_s = (static["tokens_per_s"] or 0.0) / n_chips
    out = {
        "metric": f"lm_serve_bs{n_slots}_tokens_per_sec_per_chip",
        "value": round(tok_s, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": None,   # the reference has no serving path at all
        "mfu": None,
        "static_tokens_per_s_per_chip": round(static_tok_s, 1),
        "speedup_vs_static": (round(tok_s / static_tok_s, 3)
                              if static_tok_s else None),
        "ttft_p50_s": round(cont["ttft_s"].get("p50", 0), 4),
        "ttft_p99_s": round(cont["ttft_s"].get("p99", 0), 4),
        "static_ttft_p99_s": round(static["ttft_s"].get("p99", 0), 4),
        "token_latency_p50_s": round(
            cont["token_latency_s"].get("p50", 0), 5),
        "token_latency_p99_s": round(
            cont["token_latency_s"].get("p99", 0), 5),
        "queue_wait_p99_s": round(cont["queue_wait_s"].get("p99", 0), 4),
        "slot_utilization": round(cont["slot_utilization"], 3),
        "static_slot_utilization": round(static["slot_utilization"], 3),
        "page_occupancy_mean": round(
            cont["page_occupancy"].get("mean", 0), 3),
        "page_occupancy_max": round(
            cont["page_occupancy"].get("max", 0), 3),
        "requests": len(trace),
        "requests_completed": cont["requests_completed"],
        # The engine's decode programs run on default placement (no mesh
        # axes yet — ROADMAP item 3's TP engine will change this).
        "plan": plan_payload(MeshConfig(), "serve"),
    }
    telemetry.memory()
    telemetry.record("bench", **out)
    gate = _maybe_gate(telemetry)
    telemetry.finish()
    print(json.dumps(out))
    _enforce_gate(gate)


def bench_serve_fleet() -> None:
    """Multi-replica fleet serving bench + replica-kill drill
    (``DMP_BENCH_SERVE_FLEET=N``, N >= 2; docs/SERVING.md "Fleet
    serving").

    Replays one seeded open-loop Poisson trace (build_serve_trace)
    through an N-replica :class:`ServeFleet` twice: once clean — the
    headline **fleet tokens/s/chip** — and once with replica ``r1``
    killed mid-stream at round ``DMP_BENCH_SERVE_KILL_ROUND`` (its
    in-flight requests migrate live to peers) and grown back after
    ``DMP_BENCH_SERVE_REVIVE_ROUNDS``. The drill's gates, all asserted:
    zero lost requests, every request's tokens bitwise identical to the
    clean run (migrated ones included — the determinism contract), and
    post-kill admission p99 TTFT within
    ``DMP_BENCH_SERVE_FLEET_TTFT_FACTOR`` (default 4x) of pre-kill.

    A third pass runs the crash drill: the same trace with a
    write-ahead journal (serve/journal.py) and ``r1`` HARD-crashed (no
    drain) at the kill round — zero lost requests, bitwise token parity
    again, and ``recovery_time_s`` emitted for the baseline gate.
    """
    from distributed_model_parallel_tpu.config import MeshConfig
    from distributed_model_parallel_tpu.models import transformer as tfm
    from distributed_model_parallel_tpu.serve import (
        Engine,
        ServeConfig,
        ServeFleet,
    )
    from distributed_model_parallel_tpu.serve.scheduler import summarize

    trace, cfg = build_serve_trace()
    n_replicas = int(os.environ["DMP_BENCH_SERVE_FLEET"])
    n_chips = len(jax.devices())
    params = tfm.init_params(jax.random.key(0), cfg)
    n_slots = int(os.environ.get("DMP_BENCH_SERVE_SLOTS", "8"))
    page = int(os.environ.get("DMP_BENCH_SERVE_PAGE", "16"))
    kill_round = int(os.environ.get("DMP_BENCH_SERVE_KILL_ROUND", "40"))
    revive_rounds = int(os.environ.get("DMP_BENCH_SERVE_REVIVE_ROUNDS",
                                       "20"))
    ttft_factor = float(os.environ.get("DMP_BENCH_SERVE_FLEET_TTFT_FACTOR",
                                       "4.0"))
    # Cell topology (serve/cells.py): 0 = flat fleet (the pre-cell
    # drill shape, still the default so existing ledgers keep gating).
    n_cells = int(os.environ.get("DMP_BENCH_SERVE_CELLS", "0"))
    # Absolute band floor: on an unsaturated fleet the pre-kill p99 is
    # just one prefill (~ms on CPU), and a purely multiplicative band
    # would flag the drill for sub-second re-admission waits that are
    # round-time granularity, not a regression.
    ttft_floor = float(os.environ.get("DMP_BENCH_SERVE_FLEET_TTFT_FLOOR",
                                      "0.5"))
    pages_per_seq = -(-cfg.max_seq_len // page)
    serve = ServeConfig(
        n_slots=n_slots, page_size=page,
        # Per-replica pool: a full batch of worst-case requests plus one
        # waiting admission, like the single-engine bench.
        n_pages=(n_slots + 1) * pages_per_seq,
        max_seq_len=cfg.max_seq_len,
        prefill_chunk=int(os.environ.get("DMP_BENCH_SERVE_CHUNK", "32")))
    telemetry = _telemetry_run("serve", dict(
        trace="fleet", n_replicas=n_replicas, n_cells=n_cells or None,
        n_requests=len(trace), n_slots=n_slots, page_size=page,
        kill_round=kill_round, d_model=cfg.d_model,
        n_layers=cfg.n_layers))
    # One warmed engine compiles the programs every replica shares
    # (builders are memoized per geometry) — compile stays out of both
    # timed walls.
    Engine(params, cfg, serve, slo_metrics=False).warmup()
    _log(f"serve-fleet: programs warmed for {n_replicas} replicas")

    def run(kill: bool):
        fleet = ServeFleet(params, cfg, serve, n_replicas,
                           telemetry=telemetry, cells=n_cells or None,
                           revive_after=revive_rounds if kill else None)
        if kill:
            def hook(rnd):
                if rnd == kill_round:
                    n = fleet.kill_replica("r1")
                    _log(f"serve-fleet: killed r1 at round {rnd}, "
                         f"{n} requests migrating")
            fleet.step_hook = hook
        for r in trace:
            fleet.submit(r["prompt"], r["max_new_tokens"],
                         arrival_s=r["arrival_s"], seed=r["seed"])
        summary = fleet.run()
        _log(f"serve-fleet[{'kill-drill' if kill else 'clean'}]: "
             f"{summary['tokens_generated']} tokens in "
             f"{summary['wall_s']:.1f}s "
             f"({summary['tokens_per_s'] or 0:.1f} tok/s, "
             f"{summary['migrations']} migrations)")
        return fleet, summary

    clean_fleet, clean = run(False)
    drill_fleet, drill = run(True)
    if "r1" not in drill_fleet.kill_times:
        raise RuntimeError(
            f"kill drill never fired: the trace drained in "
            f"{drill['rounds']} rounds, before kill round {kill_round} "
            f"(DMP_BENCH_SERVE_KILL_ROUND) — lower the kill round or "
            f"lengthen the trace; the drill numbers would have measured "
            f"a run with zero migrations")
    if drill["requests_failed"] or clean["requests_failed"]:
        raise RuntimeError(
            f"fleet drill lost requests: clean {clean['requests_failed']} "
            f"failed, drill {drill['requests_failed']} failed")
    clean_toks = {r.rid: r.generated for r in clean_fleet.results()}
    for r in drill_fleet.results():
        if r.generated != clean_toks[r.rid]:
            raise RuntimeError(
                f"request {r.rid} decoded different tokens after the "
                f"replica kill ({r.migrations} migrations) — the "
                f"migration path broke the determinism contract")
    if any(rep.state != "live" for rep in drill_fleet.replicas):
        raise RuntimeError("killed replica did not grow back")
    # Pre/post-kill admission TTFT: requests ADMITTED before vs after
    # the kill instant (fleet clock).
    kill_t = drill_fleet.kill_times["r1"]
    done = [r for r in drill_fleet.results()
            if r.t_first_token is not None and r.t_admitted is not None]
    pre = summarize([max(0.0, r.t_first_token - r.arrival_s)
                     for r in done if r.t_admitted < kill_t])
    post = summarize([max(0.0, r.t_first_token - r.arrival_s)
                      for r in done if r.t_admitted >= kill_t])
    # Reference = the worse of pre-kill p99 and the clean run's overall
    # p99 (an unloaded pre-kill window understates steady-state TTFT).
    ref = max([x for x in (pre.get("p99"), clean["ttft_s"].get("p99"))
               if x is not None], default=None)
    post_ok = (post.get("p99") is None or ref is None
               or post["p99"] <= max(ref * ttft_factor, ttft_floor))
    # Crash drill (serve/journal.py): the same trace with a write-ahead
    # journal and replica r1 HARD-crashed (no drain, no export) at the
    # kill round — every lost request is re-admitted from the journal
    # and replayed bitwise. recovery_time_s is the gated headline
    # (utils/baseline.py GATE_METRICS, lower-better).
    import tempfile

    from distributed_model_parallel_tpu.serve.journal import RequestJournal

    with tempfile.TemporaryDirectory(prefix="dmp-bench-journal-") as jdir:
        journal = RequestJournal(os.path.join(jdir, "journal.jsonl"))
        crash_fleet = ServeFleet(params, cfg, serve, n_replicas,
                                 telemetry=telemetry,
                                 cells=n_cells or None,
                                 revive_after=revive_rounds,
                                 journal=journal)

        def crash_hook(rnd):
            if rnd == kill_round:
                n = crash_fleet.crash_replica("r1")
                _log(f"serve-fleet: hard-crashed r1 at round {rnd}, "
                     f"{n} requests re-admitted from the journal")
        crash_fleet.step_hook = crash_hook
        for r in trace:
            crash_fleet.submit(r["prompt"], r["max_new_tokens"],
                               arrival_s=r["arrival_s"], seed=r["seed"])
        crash = crash_fleet.run()
        if "r1" not in crash_fleet.kill_times:
            raise RuntimeError(
                f"crash drill never fired: the trace drained before "
                f"round {kill_round}")
        if crash["requests_failed"]:
            raise RuntimeError(
                f"crash drill lost {crash['requests_failed']} requests "
                f"— the journal recovery path dropped accepted work")
        for r in crash_fleet.results():
            if r.generated != clean_toks[r.rid]:
                raise RuntimeError(
                    f"request {r.rid} decoded different tokens after "
                    f"the hard crash — journal replay broke the "
                    f"determinism contract")
        _log(f"serve-fleet[crash-drill]: {crash['crash_recovered']} "
             f"recovered from the journal in "
             f"{crash['recovery_time_s']:.4f}s, tokens bitwise "
             f"identical")
        crash_fleet.close()
    tok_s = (clean["tokens_per_s"] or 0.0) / n_chips
    drill_tok_s = (drill["tokens_per_s"] or 0.0) / n_chips
    out = {
        "metric": (f"lm_serve_fleet{n_replicas}_bs{n_slots}"
                   f"_tokens_per_sec_per_chip"),
        "value": round(tok_s, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": None,   # the reference repo has no serving path
        "mfu": None,
        "n_replicas": n_replicas,
        "drill_tokens_per_s_per_chip": round(drill_tok_s, 1),
        "tokens_identical_after_kill": True,
        "requests": len(trace),
        "requests_completed": drill["requests_completed"],
        "requests_migrated": drill["requests_migrated"],
        "migrations": drill["migrations"],
        "replica_grew_back": True,
        "router_assignments": drill["router"]["assignments"],
        "ttft_p50_s": round(clean["ttft_s"].get("p50", 0), 4),
        "ttft_p99_s": round(clean["ttft_s"].get("p99", 0), 4),
        "pre_kill_ttft_p99_s": (round(pre["p99"], 4)
                                if pre.get("p99") is not None else None),
        "post_kill_ttft_p99_s": (round(post["p99"], 4)
                                 if post.get("p99") is not None else None),
        "post_kill_ttft_factor": ttft_factor,
        "post_kill_ttft_ok": bool(post_ok),
        "replica_crashes": crash["replica_crashes"],
        "crash_recovered": crash["crash_recovered"],
        "recovery_time_s": round(crash["recovery_time_s"], 6),
        "tokens_identical_after_crash": True,
        "token_latency_p99_s": round(
            clean["token_latency_s"].get("p99", 0), 5),
        "page_occupancy_max": None,
        # The replicas run replicated on disjoint pool slices (no mesh
        # axes — ROADMAP item 2's TP engine will change this). The
        # fleet SHAPE rides in the plan so BASELINE_LEDGER entries from
        # different replica counts / cell layouts never gate each other.
        "plan": {**plan_payload(MeshConfig(), "serve"),
                 "n_replicas": n_replicas,
                 "cells": (drill_fleet.cells.as_dict()
                           if drill_fleet.cells is not None else None)},
    }
    clean_fleet.close()
    drill_fleet.close()
    telemetry.memory()
    telemetry.record("bench", **out)
    gate = _maybe_gate(telemetry)
    telemetry.finish()
    print(json.dumps(out))
    if not post_ok:
        raise SystemExit(
            f"post-kill admission p99 TTFT {post['p99']:.3f}s exceeds "
            f"max({ttft_factor}x reference {ref:.3f}s, floor "
            f"{ttft_floor}s)")
    _enforce_gate(gate)


def bench_serve_overload() -> None:
    """Overload-protection bench (``DMP_BENCH_SERVE_TRACE=overload``;
    docs/SERVING.md "Overload and graceful degradation").

    Phase A replays the seeded request population closed-loop through a
    plain engine — the clean **capacity** and every request's reference
    tokens. Phase B replays it open-loop at ``OVERLOAD_FACTOR`` × that
    capacity (default 2x, plus a 0.3x cool-down tail the brownout
    resolves against) through an engine with the whole overload plane
    armed: queue-wait budgets + total deadlines, a bounded submission
    queue, and the brownout ladder. Headline: **goodput tokens/s/chip**
    — tokens of requests completed within deadline over the saturated
    window — plus ``shed_fraction``; both gate in the baseline ledger
    (utils/baseline.GATE_METRICS).

    Asserted every run (RuntimeError on violation): every non-completed
    request carries a typed shed record, the live queue stays bounded
    every iteration, brownout fires and resolves, and every completed
    request's tokens are bitwise the capacity run's (level-3-clamped
    requests: its prefix). The goodput band
    (``DMP_BENCH_SERVE_GOODPUT_BAND``, default 0.8 of capacity) exits
    nonzero AFTER the headline JSON prints, like the fleet drill's TTFT
    gate.
    """
    from distributed_model_parallel_tpu.config import MeshConfig
    from distributed_model_parallel_tpu.models import transformer as tfm
    from distributed_model_parallel_tpu.serve import Engine, ServeConfig
    from distributed_model_parallel_tpu.serve.scheduler import RequestState

    trace, cfg = build_serve_trace()
    rng = np.random.default_rng(
        int(os.environ.get("DMP_BENCH_SERVE_SEED", "0")) + 1)
    factor = float(os.environ.get("DMP_BENCH_SERVE_OVERLOAD_FACTOR", "2.0"))
    band = float(os.environ.get("DMP_BENCH_SERVE_GOODPUT_BAND", "0.8"))
    n_chips = len(jax.devices())
    params = tfm.init_params(jax.random.key(0), cfg)
    n_slots = int(os.environ.get("DMP_BENCH_SERVE_SLOTS", "8"))
    page = int(os.environ.get("DMP_BENCH_SERVE_PAGE", "16"))
    pages_per_seq = -(-cfg.max_seq_len // page)
    base = dict(
        n_slots=n_slots, page_size=page,
        n_pages=(n_slots + 1) * pages_per_seq,
        max_seq_len=cfg.max_seq_len,
        prefill_chunk=int(os.environ.get("DMP_BENCH_SERVE_CHUNK", "32")))
    telemetry = _telemetry_run("serve", dict(
        trace="overload", n_requests=len(trace), n_slots=n_slots,
        page_size=page, overload_factor=factor,
        d_model=cfg.d_model, n_layers=cfg.n_layers))
    Engine(params, cfg, ServeConfig(**base), slo_metrics=False).warmup()
    _log("serve-overload: programs warmed (compile excluded)")

    # -- phase A: clean capacity, closed loop, nothing sheds
    cap_eng = Engine(params, cfg, ServeConfig(**base), telemetry=telemetry)
    for i, r in enumerate(trace):
        cap_eng.submit(r["prompt"], r["max_new_tokens"], rid=f"o{i}",
                       seed=r["seed"])
    cap = cap_eng.run()
    capacity = cap["tokens_per_s"] or 0.0
    wall_a = max(cap["wall_s"], 1e-3)
    reference = {q.rid: list(q.generated) for q in cap_eng.results()}
    _log(f"serve-overload[capacity]: {cap['tokens_generated']} tokens at "
         f"{capacity:.1f} tok/s")

    # -- phase B: the same population at factor x capacity + cool-down
    n_over = max(1, int(len(trace) * 0.75))
    mean_tokens = sum(len(v) for v in reference.values()) / len(reference)
    t, arrivals = 0.0, []
    for i in range(len(trace)):
        rate = ((factor if i < n_over else 0.3) * capacity / mean_tokens
                if capacity else 1.0)
        t += float(rng.exponential(1.0 / rate))
        arrivals.append(t)
    # Budgets scale with the measured capacity wall so the drill is
    # machine-speed-independent; the absolute floors only need to clear
    # scheduler-granularity jitter (~ms), so a short CPU smoke trace
    # still genuinely overloads.
    serve = ServeConfig(
        **base,
        queue_budget_s=float(os.environ.get(
            "DMP_BENCH_SERVE_QUEUE_BUDGET_S", max(0.15 * wall_a, 0.05))),
        deadline_s=float(os.environ.get(
            "DMP_BENCH_SERVE_DEADLINE_S", max(1.2 * wall_a, 0.4))),
        max_queue=int(os.environ.get("DMP_BENCH_SERVE_MAX_QUEUE",
                                     2 * n_slots)),
        brownout=True,
        brownout_ttft_target_s=max(0.08 * wall_a, 0.02),
        brownout_budget=0.25,
        brownout_window_s=max(0.10 * wall_a, 0.06),
        brownout_max_new=max(8, int(mean_tokens / 2)),
        brownout_hold_iters=4)
    eng = Engine(params, cfg, serve, telemetry=telemetry)
    queue_bounded = True

    def hook(_it):
        # eng._now still holds the PREVIOUS iteration's clock here, and
        # that iteration's overflow trim ran at exactly that clock — so
        # the arrived backlog it reports must already be within bound.
        nonlocal queue_bounded
        if eng.sched.arrived_backlog(eng._now) > serve.max_queue:
            queue_bounded = False

    eng.step_hook = hook
    for i, (r, arr) in enumerate(zip(trace, arrivals)):
        eng.submit(r["prompt"], r["max_new_tokens"], rid=f"o{i}",
                   seed=r["seed"], arrival_s=arr,
                   priority="batch" if i % 3 == 2 else "interactive")
    over = eng.run()
    results = {q.rid: q for q in eng.results()}
    phase1 = [results[f"o{i}"] for i in range(n_over)]
    t_end = max((q.t_done for q in phase1 if q.t_done is not None),
                default=None)
    completed = [q for q in results.values()
                 if q.state is RequestState.COMPLETED]
    goodput = (sum(len(q.generated) for q in completed
                   if eng._in_deadline(q) and q.t_done is not None
                   and q.t_done <= t_end) / t_end if t_end else 0.0)
    _log(f"serve-overload[{factor:g}x]: {over['tokens_generated']} tokens, "
         f"goodput {goodput:.1f} tok/s "
         f"({goodput / capacity if capacity else 0:.2f}x capacity), "
         f"shed {over['requests_shed']}, brownout {over['brownout']}")
    # Hard invariants — a violation is a broken engine, not a slow one.
    unaccounted = [q.rid for q in results.values()
                   if q.state is not RequestState.COMPLETED
                   and q.shed_reason is None]
    if unaccounted or over["requests_failed"]:
        raise RuntimeError(
            f"overload run lost requests without typed shed records: "
            f"unaccounted {unaccounted}, failed {over['requests_failed']}")
    if not queue_bounded:
        raise RuntimeError("live queue exceeded its bound mid-run — the "
                           "per-iteration overflow trim is broken")
    for q in completed:
        ref = reference[q.rid]
        ok = (q.generated == ref[:len(q.generated)]
              if q.max_new_requested is not None else q.generated == ref)
        if not ok:
            raise RuntimeError(
                f"request {q.rid} decoded different tokens under "
                f"overload — degradation must never change tokens")
    bo = over["brownout"] or {}
    if not bo.get("max_level_seen"):
        raise RuntimeError("brownout never fired under "
                           f"{factor:g}x overload — the ladder is dead "
                           f"or the drill is not actually overloading")
    if bo.get("level"):
        raise RuntimeError(f"brownout did not resolve after the load "
                           f"dropped (final level {bo['level']})")
    goodput_chip = goodput / n_chips
    # requests_rejected (queue-full) is a SUBSET of requests_shed —
    # every typed shed, deadline or bound, counts exactly once here.
    shed_fraction = over["requests_shed"] / len(trace)
    out = {
        "metric": (f"lm_serve_overload_bs{n_slots}"
                   f"_goodput_tokens_per_sec_per_chip"),
        "value": round(goodput_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": None,   # the reference repo has no serving path
        "mfu": None,
        "goodput_tokens_per_s": round(goodput_chip, 1),
        "capacity_tokens_per_s_per_chip": round(capacity / n_chips, 1),
        "goodput_fraction_of_capacity": (round(goodput / capacity, 3)
                                         if capacity else None),
        "goodput_band": band,
        "overload_factor": factor,
        "requests": len(trace),
        "requests_completed": over["requests_completed"],
        "requests_shed": over["requests_shed"],
        "requests_rejected": over["requests_rejected"],
        "shed_by_reason": over["shed_by_reason"],
        "shed_fraction": round(shed_fraction, 4),
        "brownout_max_level": bo.get("max_level_seen"),
        "brownout_transitions": bo.get("transitions"),
        "queue_budget_s": serve.queue_budget_s,
        "deadline_s": serve.deadline_s,
        "max_queue": serve.max_queue,
        "tokens_identical_to_capacity_run": True,
        "ttft_p99_s": round(over["ttft_s"].get("p99", 0), 4),
        "token_latency_p99_s": round(
            over["token_latency_s"].get("p99", 0), 5),
        "plan": plan_payload(MeshConfig(), "serve"),
    }
    telemetry.memory()
    telemetry.record("bench", **out)
    gate = _maybe_gate(telemetry)
    telemetry.finish()
    print(json.dumps(out))
    if capacity and goodput < band * capacity:
        raise SystemExit(
            f"goodput {goodput:.1f} tok/s under {factor:g}x overload is "
            f"below {band:.0%} of clean capacity {capacity:.1f} tok/s — "
            f"the overload plane is not holding throughput at saturation")
    _enforce_gate(gate)


def build_cnn_bench(model_name: str, batch: int, steps_per_dispatch: int,
                    image_size: int = 32):
    """The headline CNN workload: a device-resident Trainer plus a
    ``dispatch()`` closure running ``steps_per_dispatch`` scanned train
    steps per call. Shared by this bench and the hardware profiler
    (benchmarks/run_step_profile.py), so the profiled program IS the timed
    program by construction.

    ``image_size`` > 32 compiles the on-device resize stage in (32px
    synthetic uint8 on the wire, bilinear upsample inside the step) and
    switches the model to its ImageNet stride table — the reference's
    224px finetune workload shape (``Readme.md:186-205``)."""
    from distributed_model_parallel_tpu.config import (
        DataConfig,
        MeshConfig,
        ModelConfig,
        OptimizerConfig,
        TrainConfig,
    )
    from distributed_model_parallel_tpu.train.trainer import Trainer

    n_chips = len(jax.devices())
    extra = {"input_layout": "imagenet"} if image_size != 32 else {}
    cfg = TrainConfig(
        model=ModelConfig(name=model_name, dtype="bfloat16", extra=extra),
        data=DataConfig(name="synthetic", batch_size=batch,
                        eval_batch_size=batch,
                        image_size=image_size,
                        # Generate native 32px so the on-device upsample is
                        # actually compiled into the step (a 224px-native
                        # dataset would make resolve_input_size skip it).
                        synthetic_native_size=32,
                        synthetic_train_size=batch * 4,
                        synthetic_eval_size=batch),
        # DMP_BENCH_FUSED_OPT=1 swaps the optax per-leaf update chain for
        # the fused Pallas SGD kernel (ops/pallas_optim.py).
        optimizer=OptimizerConfig(learning_rate=0.4, warmup_steps=10,
                                  fused=bool(int(os.environ.get(
                                      "DMP_BENCH_FUSED_OPT", "0")))),
        mesh=MeshConfig(data=n_chips),
        device_resident_data=True,
        steps_per_dispatch=steps_per_dispatch,
        log_dir="/tmp/dmp_bench_log",
        checkpoint_dir="/tmp/dmp_bench_ckpt",
    )
    trainer = Trainer(cfg)

    # Device-resident fast path: the dataset lives on the chips; each
    # dispatched program runs steps_per_dispatch full train steps (lax.scan
    # over on-device index gathers) — the TPU-native data path. Per-step
    # math is identical to the per-batch path (parity-tested in
    # tests/test_train.py).
    n = len(trainer.train_ds)
    rng = jax.random.key(0)
    idx_rng = np.random.default_rng(0)

    def dispatch():
        nonlocal rng
        rng, sub = jax.random.split(rng)
        idx = jnp.asarray(idx_rng.integers(
            0, n, (steps_per_dispatch, batch)).astype(np.int64))
        state, m = trainer._multi_step(trainer.state, sub,
                                       trainer._dev_images,
                                       trainer._dev_labels, idx)
        trainer.state = state
        return m

    return trainer, dispatch


def step_phase_record(trainer, donation: dict, *, n_probe: int = 4) -> dict:
    """The ``step_phase`` breakdown record: per-step host-input / h2d /
    device seconds measured through the real streaming input pipeline,
    plus the no-silent-fallback proof that the raw-speed levers are
    actually active (device prefetch observed keeping batches in flight,
    donation aliases committed by XLA, the configured grad reduction and
    optimizer kernel). ``dmp_report.py`` renders it, so that a win can be
    attributed to a lever instead of guessed.

    On CPU the phase timings are omitted honestly (host wall-clock around
    an XLA:CPU call has no h2d/device boundary to attribute), but the
    pipeline-active proof is still real.
    """
    from distributed_model_parallel_tpu.data.loader import (
        DevicePrefetchLoader,
    )
    from distributed_model_parallel_tpu.utils.profiling import (
        fetch,
        fetch_overhead,
    )

    cfg = trainer.config
    if cfg.grad_bucket_mb is not None:
        grad_reduction = f"bucketed_psum@{cfg.grad_bucket_mb:g}MB"
    elif cfg.strategy == "ddp":
        grad_reduction = f"ddp:{cfg.ddp_allreduce}"
    else:
        grad_reduction = f"xla-inferred ({cfg.strategy})"
    pipeline = {
        # Which input path the TIMED loop actually used: a
        # device-resident bench never streams, so its prefetch numbers
        # below are a probe of the streaming path, not a property of the
        # headline measurement — labeled so attribution can't credit a
        # lever that wasn't in the measured loop.
        "input_path": ("device-resident"
                       if cfg.device_resident_data else "streaming"),
        "device_prefetch_depth": cfg.data.device_prefetch,
        "host_prefetch_depth": cfg.data.prefetch,
        "device_resident_data": cfg.device_resident_data,
        "steps_per_dispatch": (cfg.steps_per_dispatch
                               if cfg.device_resident_data else 1),
        "fused_optimizer": cfg.optimizer.fused,
        "grad_reduction": grad_reduction,
        "donation_aliases": donation.get("n_aliased"),
        "donation_dropped": donation.get("dropped"),
    }
    rec: dict = {"pipeline": pipeline}
    sub = jax.random.key(2)
    state = trainer.state
    if cfg.data.device_prefetch > 0:
        # Activity proof for the STREAMING path: drive real batches
        # through the wrapper and record the largest
        # uploaded-but-unconsumed lead it sustained. (On a
        # device-resident bench this is a side probe — input_path above
        # marks what the timed loop used.)
        dp = DevicePrefetchLoader(trainer.train_loader,
                                  trainer._shard_batch,
                                  depth=cfg.data.device_prefetch)
        it = iter(dp)
        for _ in range(min(3, len(trainer.train_loader))):
            images, labels = next(it)
            state, m = trainer._train_step(state, sub, images, labels)
        it.close()
        fetch(m)
        pipeline["device_prefetch_max_lead"] = dp.last_stats["max_lead"]
    if jax.devices()[0].platform == "cpu":
        rec["phases"] = None
        rec["reason"] = "cpu: no h2d/device boundary to attribute"
    else:
        # Serialized per-phase walk of the streaming path: host batch
        # assembly, sharded upload, device step — each bracketed by its
        # own sync so the costs cannot hide behind one another (this is
        # attribution, not the throughput number).
        t_fetch = fetch_overhead()
        host_s, h2d_s, dev_s = [], [], []
        it = iter(trainer.train_loader)
        for _ in range(n_probe):
            t0 = time.perf_counter()
            try:
                images, labels = next(it)
            except StopIteration:
                it = iter(trainer.train_loader)
                images, labels = next(it)
            t1 = time.perf_counter()
            sharded = trainer._shard_batch(images, labels)
            jax.block_until_ready(sharded)
            t2 = time.perf_counter()
            state, m = trainer._train_step(state, sub, *sharded)
            fetch(m)
            t3 = time.perf_counter()
            host_s.append(t1 - t0)
            h2d_s.append(t2 - t1)
            dev_s.append(max(0.0, t3 - t2 - t_fetch))
        rec["phases"] = {
            "host_input_s": round(sum(host_s) / len(host_s), 6),
            "h2d_s": round(sum(h2d_s) / len(h2d_s), 6),
            "device_s": round(sum(dev_s) / len(dev_s), 6),
            "n_steps": n_probe,
        }
    trainer.state = state
    return rec


def main() -> None:
    _log(f"compile cache: {enable_compile_cache()}")
    t_start = time.perf_counter()
    devs = require_devices("bench")
    _log(f"devices: {devs}")
    _log(f"device ready after {time.perf_counter() - t_start:.1f}s")
    _run_workload()


def _run_workload() -> None:
    if os.environ.get("DMP_BENCH_WORKLOAD") == "lm":
        bench_lm()
        return
    if os.environ.get("DMP_BENCH_WORKLOAD") == "decode":
        bench_decode()
        return
    if os.environ.get("DMP_BENCH_WORKLOAD") == "serve":
        if int(os.environ.get("DMP_BENCH_SERVE_FLEET", "0")) >= 2:
            bench_serve_fleet()
        elif os.environ.get("DMP_BENCH_SERVE_TRACE") == "chat":
            bench_serve_chat()
        elif os.environ.get("DMP_BENCH_SERVE_TRACE") == "overload":
            bench_serve_overload()
        else:
            bench_serve()
        return

    n_chips = len(jax.devices())
    batch = int(os.environ.get("DMP_BENCH_BATCH", "512"))
    steps_per_dispatch = int(os.environ.get("DMP_BENCH_SPD", "10"))
    # DMP_BENCH_MODEL switches the workload (e.g. resnet50 for the
    # BASELINE.json north-star model); the headline metric stays the
    # reference's MobileNetV2 table (Readme.md:286).
    model_name = os.environ.get("DMP_BENCH_MODEL", "mobilenetv2")
    # DMP_BENCH_IMG=224 benches the compute-bound native-resolution
    # workload (on-device 32->224 upsample + ImageNet stride table).
    image_size = int(os.environ.get("DMP_BENCH_IMG", "32"))
    telemetry = _telemetry_run("cnn", dict(
        model=model_name, batch_size=batch, image_size=image_size,
        steps_per_dispatch=steps_per_dispatch, n_chips=n_chips))
    trainer, dispatch = build_cnn_bench(model_name, batch,
                                        steps_per_dispatch, image_size)

    # Warmup (compile) + steady-state timing. A host fetch of the final
    # metrics is the sync point (utils/profiling.py module docstring). The
    # dispatches chain through trainer.state, so fetching the last loss
    # waits for all.
    from distributed_model_parallel_tpu.utils.profiling import fetch, fetch_overhead

    t0 = time.perf_counter()
    for i in range(2):
        fetch(dispatch())
        _log(f"warmup dispatch {i} done at {time.perf_counter() - t0:.1f}s")
    t_fetch = fetch_overhead()
    _log(f"fetch round-trip overhead: {t_fetch * 1e3:.1f} ms")

    n_dispatch = int(os.environ.get("DMP_BENCH_STEPS", "50")) // steps_per_dispatch
    n_dispatch = max(1, n_dispatch)
    m = None
    t0 = time.perf_counter()
    for _ in range(n_dispatch):
        m = dispatch()
    fetch(m)
    n_steps = n_dispatch * steps_per_dispatch
    total = time.perf_counter() - t0
    if total <= t_fetch:
        _log(f"WARNING: timed loop ({total * 1e3:.1f} ms) <= fetch round-trip "
             f"({t_fetch * 1e3:.1f} ms); measurement invalid — raise "
             f"DMP_BENCH_STEPS")
    # Floor guards against a noisy single-sample fetch_overhead exceeding a
    # short timed loop (division by zero downstream).
    dt = max(1e-9, total - t_fetch) / n_steps

    samples_per_sec_per_chip = batch / dt / n_chips
    # The 323.2 samples/s/GPU anchor is the reference's MobileNetV2 bs-512
    # table (Readme.md:286); any other model OR batch size has no published
    # reference number, so the ratio is omitted rather than misquoted.
    vs_baseline = (round(
        samples_per_sec_per_chip / BASELINE_SAMPLES_PER_SEC_PER_GPU, 3)
        if model_name == "mobilenetv2" and batch == 512 and image_size == 32
        else None)
    # MFU: cost-analysis FLOPs of ONE train step over the chip's peak.
    # Must be the loop-free single-step program (_train_step): the scanned
    # _multi_step's loop body is counted once by cost analysis regardless
    # of trip count (verified on v5e), so analyzing it and dividing by
    # steps_per_dispatch understated MFU 10x in rounds 1-2. The CNN step
    # (convs + BN + SGD, no scan, no pallas) is exactly what cost
    # analysis counts correctly.
    from distributed_model_parallel_tpu.utils.profiling import (
        peak_flops_per_chip,
    )

    sub = jax.random.key(1)
    img_shape = trainer.train_ds.images.shape[1:]
    # The probe batch must sit in the step's declared batch sharding: the
    # on-device dataset is replicated, and lower() rejects a sharding
    # mismatch outright (which used to silently null the MFU column).
    step_args = (trainer.state, sub,
                 jax.device_put(
                     trainer._dev_images[:batch].reshape(batch, *img_shape),
                     trainer._batch_sh),
                 jax.device_put(trainer._dev_labels[:batch],
                                trainer._batch_sh))
    from distributed_model_parallel_tpu.utils.profiling import (
        aot_compile,
        bytes_accessed_of,
        cost_analysis_of,
        donation_report,
        peak_hbm_bytes_per_chip,
    )

    # ONE AOT compile of the streaming single step serves the cost
    # analysis (MFU/bytes) AND the donation proof of the step_phase
    # record below.
    compiled_step, lower_warns = aot_compile(trainer._train_step,
                                             *step_args)
    ca = cost_analysis_of(compiled_step)
    donation = donation_report(compiled_step, lower_warns)
    flops = float(ca["flops"]) if ca.get("flops") else None
    peak = peak_flops_per_chip()
    # compiled.cost_analysis() reports the per-device partitioned HLO
    # module, so normalize by one chip's peak: per-device FLOPs over
    # per-device peak IS the fleet MFU under SPMD (ADVICE r2).
    mfu = (round(flops / dt / peak, 4)
           if flops and peak else None)
    # Bandwidth story: the demand-side cost-analysis
    # byte rate can exceed the physical peak (VMEM-resident reuse still
    # counts once per use), so it is labeled what it is — demand, not a
    # counter. The saturation evidence is the committed hardware trace
    # benchmarks/step_profile_r5.json: MEASURED per-op device timings
    # (jax.profiler TPU timeline) with 0.02 ms inter-module gaps, against
    # ANALYTIC per-op operand bytes — per-fusion footprint rates cluster
    # at the 819 GB/s v5e peak over ~90% of the step (above-peak rates =
    # VMEM reuse). Reproducible via benchmarks/run_step_profile.py.
    bytes_step = bytes_accessed_of(ca)
    hbm_peak = peak_hbm_bytes_per_chip()
    demand_gbs = round(bytes_step / dt / 1e9, 1) if bytes_step else None
    demand_frac, frac_err = demand_frac_of_peak(
        bytes_step / dt if bytes_step else None, hbm_peak)
    img_tag = "" if image_size == 32 else f"at{image_size}"
    out = {
        "metric": (f"{model_name}_cifar10{img_tag}_bs{batch}"
                   f"_train_samples_per_sec_per_chip"),
        "value": round(samples_per_sec_per_chip, 2),
        "unit": "samples/s/chip",
        "vs_baseline": vs_baseline,
        "mfu": mfu,
        "demand_gbs": demand_gbs,
        "demand_frac_of_peak": demand_frac,
        "plan": plan_payload(
            trainer.config.mesh, trainer.config.strategy,
            num_microbatches=trainer.config.num_microbatches),
    }
    if frac_err:
        out["demand_frac_error"] = frac_err
    # The committed hardware trace only covers the workload it profiled —
    # don't claim measured saturation for other models/batches.
    if model_name == "mobilenetv2" and batch == 512 and image_size == 32:
        out["hbm_saturation_measured"] = "benchmarks/step_profile_r5.json"
    telemetry.step(step=0, step_time_s=dt,
                   samples_per_s=batch / dt, mfu=mfu)
    if flops:
        # Per-device cost-analysis FLOPs: the report CLI divides by one
        # chip's peak directly (meta key name marks the normalization).
        telemetry.record("cost_analysis", device_flops_per_step=flops,
                         bytes_accessed_per_step=bytes_step)
    # Phase attribution + pipeline-active proof (dmp_report.py renders
    # it).
    phase = step_phase_record(trainer, donation)
    telemetry.record("step_phase", **phase)
    out["step_phase"] = phase
    telemetry.memory()
    telemetry.record("bench", **out)
    gate = _maybe_gate(telemetry)
    telemetry.finish()
    print(json.dumps(out))
    _enforce_gate(gate)


if __name__ == "__main__":
    main()
