"""Driver ``serve_engine``: ``serve.Engine`` under a seeded request
stream, timed.

The window drives ``Engine.submit`` and the loop ``Engine.step_once``
(what ``Engine.run()`` and the fleet call). Set-up makes the weights,
builds the engine, warms its two programs, and runs the same loop under
the same traffic for ``run_in_s`` seconds so that the window opens on a
full engine (with one prefill chunk an iteration an empty engine needs
some seconds before its decode batch is what steady traffic gives it).

Arrivals ``backlog``: the queue is topped up to ``depth_per_slot``
requests a slot before every iteration (an offline batch job).
Arrivals ``open``: requests fall due on the stream's schedule whatever
the engine does; each is submitted at the loop's first turn after its due
time, with ``arrival_s`` = the due time, so TTFT counts the wait. The loop
then runs until every request due in the window is terminal, or
``drain_cap_s`` has passed; what is not finished then has failed.

Traffic file keys: engine {n_slots, max_seq_len, pool_tokens,
prefill_chunk}, requests {...: see traffic_gen}, run_in_s, drain_cap_s,
trace_seconds, check {sample, min_tokens, pad_to, q_block}.
"""

from __future__ import annotations

import sys
import time

import numpy as np

import flops
import harness
import reference
import traffic_gen
import weights
from drivers.common import free_device_memory, transformer_config

FAILED_MS = 1e9           # a request that never got there, in ms
CONTROLS = ("int8", "int8_fwd")    # read by tools/readings.py only


def _log(msg: str) -> None:
    print(f"[chipbench:serve_engine] {msg}", file=sys.stderr, flush=True)


def build_engine(cell, dims, seed: int):
    from distributed_model_parallel_tpu.serve import Engine, ServeConfig

    eng_p = cell.traffic["engine"]
    mcfg, dtype = transformer_config(cell.config, dims, attn_impl="auto",
                                     remat=False)
    params = weights.make_params(seed, dims, dtype)
    base = ServeConfig()         # tuning fields stay the program's choice
    if eng_p["pool_tokens"] % base.page_size:
        raise ValueError("pool_tokens not a multiple of the page size")
    serve = ServeConfig(
        n_slots=eng_p["n_slots"], max_seq_len=eng_p["max_seq_len"],
        n_pages=eng_p["pool_tokens"] // base.page_size,
        prefill_chunk=eng_p["prefill_chunk"])
    eng = Engine(params, mcfg, serve)
    return eng, params, serve


class Loop:
    """The benchmark's side of the serving loop: offers load, turns the
    engine, and counts what each iteration processed."""

    def __init__(self, cell, eng, dims, seed, spans):
        self.eng, self.dims, self.spans = eng, dims, spans
        self.tr = cell.traffic
        self.stream = traffic_gen.RequestStream(self.tr["requests"], seed,
                                                dims.vocab)
        self.mode = self.stream.mode
        self.n_slots = self.tr["engine"]["n_slots"]
        self.depth = (int(self.tr["requests"]["arrivals"].get(
            "depth_per_slot", 0)) * self.n_slots)
        self.t0 = time.monotonic()
        self.requests: list = []          # (Request, due_s, seen_s)
        self.next_due = 0.0
        self._pending = None
        self.iter_rows: list = []         # per iteration accounting

    def now(self) -> float:
        return time.monotonic() - self.t0

    def _submit(self, ids, n_out, due, now):
        req = self.eng.submit(ids, n_out, rid=f"r{len(self.requests)}",
                              arrival_s=due)
        self.requests.append((req, due, now))

    def offer(self, now: float) -> None:
        if self.mode == "backlog":
            while len(self.eng.sched.queue) < self.depth:
                ids, n_out, _ = self.stream.next()
                self._submit(ids, n_out, now, now)
            return
        while True:
            if self._pending is None:
                ids, n_out, gap = self.stream.next()
                self.next_due += gap
                self._pending = (ids, n_out, self.next_due)
            if self._pending[2] > now:
                return
            ids, n_out, due = self._pending
            self._pending = None
            self._submit(ids, n_out, due, now)

    def turn(self) -> None:
        """Offer what is due, then one engine iteration."""
        now = self.now()
        with self.spans.span("offer"):
            self.offer(now)
        before = [(r, r.prefill_cursor, len(r.generated))
                  for r in self.eng.sched.slots if r is not None]
        queued = list(self.eng.sched.queue)[:self.n_slots]
        w0 = time.perf_counter()
        with self.spans.span("step_once"):
            progress = self.eng.step_once(now, self.t0)
        w1 = time.perf_counter()
        if not progress:
            self.spans.rename_last("step_idle")
        # what this iteration processed (admissions of this turn included)
        seen = {id(r) for r, _, _ in before}
        before += [(r, 0, 0) for r in queued
                   if id(r) not in seen and r.t_admitted is not None]
        pre_f, dec_f, contexts, new_tokens = 0, 0, [], 0
        for r, cur0, gen0 in before:
            dc = r.prefill_cursor - cur0
            dg = len(r.generated) - gen0
            new_tokens += dg
            last = dc > 0 and r.prefill_cursor >= r.prompt_len
            if dc > 0:
                pre_f += flops.prefill_flops(self.dims, cur0, dc, last)
            if dg - (1 if last else 0) > 0:
                ctx = r.prompt_len + len(r.generated) - 1
                contexts.append(ctx)
                dec_f += flops.serve_token_flops(self.dims, ctx, True)
        self.iter_rows.append((w1 - w0, pre_f, dec_f, contexts, new_tokens))
        if not progress:
            with self.spans.span("idle_wait"):
                time.sleep(0.0005)


def run(cell, *, seed, seconds, trace, devices, t_proc, root,
        fault=None, control=False) -> harness.RunOutput:
    import jax

    tr = cell.traffic
    dims = weights.Dims.from_config(cell.config)
    spans = harness.Spans()
    eng, params, serve = build_engine(cell, dims, seed)
    if fault is not None:
        fault(eng)
    eng.warmup()
    jax.block_until_ready((eng.cache.ck, eng.cache.cv))
    loop = Loop(cell, eng, dims, seed, spans)
    # ---- run-in: same loop, same traffic, before the window -------------
    t_end = loop.now() + float(tr["run_in_s"])
    while loop.now() < t_end:
        loop.turn()
    # ---- the window -------------------------------------------------------
    win = harness.TraceWindow(trace)
    length = min(seconds, float(tr["trace_seconds"])) if trace else seconds
    win.start()
    loop.spans = spans = harness.Spans()       # the window's spans only
    setup_s = time.perf_counter() - t_proc
    ws = loop.now()
    i0 = len(loop.iter_rows)
    while loop.now() - ws < length:
        loop.turn()
    we = loop.now()
    i1 = len(loop.iter_rows)
    backlog = sum(1 for r, _, _ in loop.requests
                  if r.t_first_token is None and not r.done)
    win.stop()
    window_s = we - ws
    # a traced run measures only the stretch it profiles; the loop then
    # runs on, unmeasured, to a plain run's length, so that ``correct``
    # compares as many served tokens as a plain run does
    wc = we
    if trace:
        while loop.now() - ws < seconds:
            loop.turn()
        wc = loop.now()
    due = [(r, d, s) for r, d, s in loop.requests if ws <= d < we]
    due_check = [r for r, d, _ in loop.requests if ws <= d < wc]
    if loop.mode == "open":
        # keep offering load while the window's requests drain
        cap = loop.now() + float(tr["drain_cap_s"])
        while (any(not r.done for r in due_check) and loop.now() < cap):
            loop.turn()
    peak = harness.memory_peak_bytes(devices)
    rows = loop.iter_rows[i0:i1]
    out_tokens = sum(r[4] for r in rows)
    from distributed_model_parallel_tpu.serve.scheduler import RequestState

    def completed_between(a, b):
        return [r for r, _, _ in loop.requests
                if r.state is RequestState.COMPLETED
                and r.t_done is not None and a <= r.t_done < b]

    finished = completed_between(ws, we)
    metrics = {"setup_s": setup_s,
               "serve_tok_s": out_tokens / window_s / len(devices)}
    counters = {
        "iterations": len(rows),
        "prefill_flops": sum(r[1] for r in rows),
        "decode_flops": sum(r[2] for r in rows),
        "decode_contexts": [r[3] for r in rows if r[3]],
        "out_tokens": out_tokens,
        "finished_in_window": len(finished),
        "requests_per_s": len(finished) / window_s,
        "backlog_at_close": backlog,
    }
    if loop.mode == "open":
        ttft, tpot, qwait, late = [], [], [], []
        for r, d, s in due:
            late.append((s - d) * 1e3)
            ok = r.state is RequestState.COMPLETED
            ttft.append((r.t_first_token - d) * 1e3
                        if ok and r.t_first_token is not None else FAILED_MS)
            if ok and len(r.generated) > 1:
                tpot.append((r.t_done - r.t_first_token) * 1e3
                            / (len(r.generated) - 1))
            elif not ok:
                tpot.append(FAILED_MS)
            if r.t_admitted is not None:
                qwait.append(max(0.0, r.t_admitted - d) * 1e3)
        p95 = traffic_gen.percentile_nearest_rank
        if ttft:
            metrics["ttft_p95_ms"] = p95(ttft, 95)
        if tpot:
            metrics["tpot_p95_ms"] = p95(tpot, 95)
        counters.update(queue_wait_ms=qwait, gen_late_ms=late)
        attempted = len(due)
        failed = sum(1 for r, _, _ in due
                     if r.state is not RequestState.COMPLETED)
        checkable = [r for r in due_check
                     if r.state is RequestState.COMPLETED]
    else:
        attempted = len(finished)
        failed = sum(1 for r, _, _ in loop.requests
                     if r.state is RequestState.FAILED)
        checkable = completed_between(ws, wc)
    _log(f"window {window_s:.2f}s: {len(rows)} iterations, "
         f"{len(finished)} requests finished, {out_tokens} tokens out")
    trace_obj = win.load()
    served = [(np.asarray(r.prompt, np.int32),
               np.asarray(r.generated, np.int32), r.max_new_tokens)
              for r in pick_sample(checkable, seed, tr["check"]["sample"])]
    free_device_memory(eng.cache, eng)
    del eng, loop
    t_ref = time.perf_counter()
    compared = compare(cell, dims, params, served,
                       counters if control else None)
    _log(f"set-up {setup_s:.1f}s, reference "
         f"{time.perf_counter() - t_ref:.1f}s")
    return harness.RunOutput(
        metrics=metrics, attempted=attempted, failed=failed,
        window_s=window_s, counters=counters, spans=spans, dims=dims,
        compared=compared, trace=trace_obj, memory_peak=peak)


def pick_sample(finished, seed: int, n: int) -> list:
    """The longest finished request and n - 1 others drawn from the seed."""
    if not finished:
        return []
    by_len = sorted(finished,
                    key=lambda r: -(r.prompt_len + len(r.generated)))
    rest = by_len[1:]
    rng = np.random.default_rng([int(seed), 0x5A3B1E])
    idx = rng.permutation(len(rest))[:max(0, n - 1)]
    return [by_len[0]] + [rest[i] for i in idx]


def logit_gaps(params, dims, served, pad_to: int, q_block: int,
               controls=()) -> tuple:
    """For each served request, the reference's logits at every served
    position: one forward over prompt + served tokens, padded to the next
    multiple of ``pad_to`` (causal, so padding changes nothing) so that a
    few programs serve every length. Returns ({"max", "mean"}: the gaps
    by which the served tokens lie below the reference's best, {control:
    the same of the tokens that lower-precision forward puts first},
    tokens compared)."""
    import jax.numpy as jnp

    got, got_c = [], {c: [] for c in controls}
    for prompt, gen, _ in served:
        seq = np.concatenate([prompt, gen[:-1]])
        toks = np.zeros(pad_to * -(-len(seq) // pad_to), np.int32)
        toks[:len(seq)] = seq
        rows = np.arange(len(prompt) - 1, len(seq))
        rows_p = np.full(256 * -(-len(rows) // 256), rows[-1], np.int32)
        rows_p[:len(rows)] = rows
        at = np.arange(len(rows))

        def logits(quant):
            return np.asarray(reference.sequence_logits(
                params, jnp.asarray(toks), jnp.asarray(rows_p), dims=dims,
                quant=quant, q_block=q_block))[:len(rows)]

        lg = logits(None)
        best = lg.max(axis=-1)
        got.append(best - lg[at, gen])
        for c in controls:
            got_c[c].append(best - lg[at, logits(c).argmax(-1)])

    def stats(parts):
        g = np.concatenate(parts)
        return {"max": float(g.max()), "mean": float(g.mean())}

    return (stats(got), {c: stats(v) for c, v in got_c.items()},
            sum(len(g) for g in got))


def compare(cell, dims, params, served, control_into=None):
    """``control_into``: a dict that also gets the int8 control's gap
    (tools/readings.py; a benchmark run never computes it)."""
    limits = cell.checks["limits"]
    short = sum(1 for _, gen, want in served if len(gen) != want)
    bad = sum(int(((gen < 0) | (gen >= dims.vocab)).sum())
              for _, gen, _ in served)
    if served:
        chk = cell.traffic["check"]
        gap, gap_c, n = logit_gaps(
            params, dims, served, chk["pad_to"], chk["q_block"],
            controls=CONTROLS if control_into is not None else ())
        if control_into is not None:
            control_into["control"] = gap_c
            control_into["tokens_compared"] = n
    else:                              # nothing finished: not correct
        gap, n = {"max": float("nan"), "mean": float("nan")}, 0
    _log(f"compared {n} served tokens of {len(served)} requests")
    got = {"served_logit_gap": gap["max"],
           "served_logit_gap_mean": gap["mean"],
           "wrong_length": float(short),
           "out_of_vocab": float(bad),
           "too_few_compared": float(max(
               0, cell.traffic["check"]["min_tokens"] - n))}
    return [(name, float(got[name]), float(lim))
            for name, lim in limits.items()]
