"""The serving engine loop: continuous batching over the paged KV cache.

One iteration = admit → prefill (a bounded number of chunks, interleaved
so long prompts never stall the resident batch) → one decode step for
every active slot → evict finished sequences (their slot and pages are
reusable the very next iteration). The decode step runs at a fixed slot
width with idle rows masked, so a request's tokens are a pure function of
its own (prompt, seed) — joining a busy batch mid-flight decodes exactly
what a solo run would (tests/test_serve.py pins this).

SLO accounting: per-request TTFT, queue wait and per-token latency land
in the process metrics registry (``serve_ttft_s`` / ``serve_queue_wait_s``
/ ``serve_token_latency_s`` histograms, ``serve_page_occupancy`` gauge)
and as typed ``serve`` telemetry records the report renders
(docs/OBSERVABILITY.md). A killed engine never drops requests silently:
every in-flight and queued request is marked failed with a typed error
and a ``serve`` record before the exception propagates.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from distributed_model_parallel_tpu.models.transformer import (
    TransformerConfig,
    validate_sampling,
)
from distributed_model_parallel_tpu.serve.model import (
    in_proj_d_last,
    in_proj_relaid,
    init_stats,
    make_decode_step,
    make_prefill_step,
    make_verify_step,
    stats_by_layer,
)
from distributed_model_parallel_tpu.serve.paged_kv import (
    CacheKindError,
    CacheLayout,
    PagedKVCache,
    PagePoolError,
    memory_gauges,
    share_granularity_for,
)
from distributed_model_parallel_tpu.serve.spec import NGramProposer
from distributed_model_parallel_tpu.serve.scheduler import (
    Request,
    RequestState,
    Scheduler,
    summarize,
)
from distributed_model_parallel_tpu.utils import tracing
from distributed_model_parallel_tpu.utils.metering import EngineMeter
from distributed_model_parallel_tpu.utils.telemetry import registry
from distributed_model_parallel_tpu.utils.tracing import span


class EngineKilled(RuntimeError):
    """The engine loop died mid-stream; every in-flight request has been
    marked failed (typed) before this propagated."""


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Engine geometry + sampling policy (per-engine, compiled in).

    ``n_pages`` is the pool capacity — the admission backpressure point;
    ``max_seq_len`` bounds any single request (prompt + generation) and
    sets the static per-sequence page-table width; ``prefill_chunk`` is
    the one compiled prompt-chunk size (any prompt length = some number
    of chunks, so repeated CLI calls hit the compile cache).
    """

    n_slots: int = 8
    page_size: int = 16
    n_pages: int = 256
    max_seq_len: int = 512
    prefill_chunk: int = 32
    prefill_chunks_per_iter: int = 1
    policy: str = "continuous"       # "continuous" | "static" (baseline)
    attn_impl: str = "auto"          # paged-attention impl (ops/)
    # Prefix-cache reuse (serve/prefix_cache.py): finished prefixes stay
    # resident in a refcounted radix tree; a request whose prompt matches
    # admits holding the cached pages, prefills only the suffix, and its
    # admission reservation bills only the uncached pages.
    prefix_cache: bool = False
    # Speculative decoding: an n-gram self-drafting proposer (serve/
    # spec.py) proposes up to spec_k tokens per iteration and one
    # batched verify forward (serve/model.make_verify_step) commits the
    # model-verified prefix. 0 = off (single-token decode, PR 9 path).
    spec_k: int = 0
    spec_ngram: int = 3              # longest lookup order tried
    temperature: float = 0.0
    top_k: int | None = None
    top_p: float | None = None
    eos_id: int | None = None
    # -- overload protection (docs/SERVING.md "Overload and graceful
    # degradation"). Defaults are per-request-overridable on Request.
    # A queued request older than queue_budget_s sheds typed (reason
    # queue-deadline); one past deadline_s sheds (queued) or aborts
    # (in-flight, pages returned immediately) with reason
    # total-deadline. None = no budget (the PR 9 behavior).
    queue_budget_s: float | None = None
    deadline_s: float | None = None
    # Submission-queue bound: beyond it, submission is REJECTED with a
    # typed record (reason queue-full) instead of growing an unbounded
    # host-side queue. The fleet bounds its own pending list at
    # max_queue * n_replicas. None = unbounded (PR 9 behavior).
    max_queue: int | None = None
    # Brownout: the deterministic degradation ladder (serve/overload.py)
    # driven by a TTFT burn-rate rule and a page-occupancy ceiling —
    # spec-off -> prefill-share -> clamp-max-new, walked back on
    # resolution. Degradation never changes a completed request's
    # tokens (a clamped request's stream is the bitwise prefix of its
    # unclamped one).
    brownout: bool = False
    brownout_ttft_target_s: float = 1.0   # SLO target feeding the burn rule
    brownout_budget: float = 0.25         # tolerated violation fraction
    brownout_window_s: float = 10.0       # short burn window (long = 4x)
    brownout_occupancy_ceiling: float = 0.95
    brownout_max_new: int = 32            # level-3 cap on admissions' max_new
    brownout_hold_iters: int = 8          # min ticks between level moves
    # Live status exporter (utils/statusz.py): queue depth, page
    # occupancy and slot state under /statusz, SLO histograms under
    # /metrics. Same one-exporter-per-process semantics as
    # TrainConfig.statusz_port; None = DMP_STATUSZ_PORT, unset = no-op.
    statusz_port: int | None = None


class Engine:
    """Continuous-batching decode engine over one replicated model.

    ``step_hook(iteration)`` (tests, chaos drills) runs once per loop
    iteration; an exception it raises takes the typed-failure path like
    any other engine death.
    """

    def __init__(self, params: dict, cfg: TransformerConfig,
                 serve: ServeConfig, *, telemetry=None, step_hook=None,
                 slo_metrics: bool = True, replica: str | None = None,
                 clock=None, journal=None, meter: bool = True):
        if cfg.moe_experts and not cfg.moe_dropless:
            raise ValueError(
                "the capacity-dropping MoE layer is batch-coupled "
                "(which tokens an expert drops depends on co-resident "
                "tokens), which breaks continuous batching's per-request "
                "determinism; serve routed models with moe_dropless=True "
                "(ops/moe.moe_ffn_dropless), or decode this one via "
                "models.transformer.generate")
        if cfg.tp_axis is not None or cfg.sp_axis is not None:
            raise ValueError("the serving engine runs replicated; build "
                             "it with tp_axis=None/sp_axis=None (sharded "
                             "decode stays on generate_sharded)")
        if serve.max_seq_len > cfg.max_seq_len:
            raise ValueError(
                f"serve max_seq_len {serve.max_seq_len} exceeds the "
                f"model's max_seq_len {cfg.max_seq_len}")
        if serve.prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got "
                             f"{serve.prefill_chunk}")
        validate_sampling(cfg, serve.temperature, serve.top_k, serve.top_p)
        if serve.spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {serve.spec_k}")
        if serve.spec_ngram < 1:
            raise ValueError(f"spec_ngram must be >= 1, got "
                             f"{serve.spec_ngram}")
        # The attention in-projections of stacked layers in the layout
        # their product takes (``d`` last), converted here once: no step
        # then cuts a layer's ``wq``/``wkv`` out of the stack to transpose
        # it. The caller's tree is left as it was; one that already holds
        # the converted leaves (another engine's ``params``) comes back
        # as it is.
        self.params = in_proj_d_last(params)
        self.cfg = cfg
        self.serve = serve
        self.telemetry = telemetry
        self.step_hook = step_hook
        # Write-ahead request journal (serve/journal.py): committed-token
        # watermarks from the decode loop, exactly one terminal per
        # accepted request. None = journal off, zero behavior change.
        self.journal = journal
        # Resource meter (utils/metering.py): per-request chip-second /
        # page-second bills and the per-iteration utilization ledger.
        # Pure observation — the soak drill gates a byte-identical
        # schedule digest with metering on vs off, and metering overhead
        # at < 2% of iteration time. meter=False turns the plane off.
        self.meter = EngineMeter(replica=replica) if meter else None
        # Fleet membership (serve/fleet.py): the replica name tags this
        # engine's serve records and statusz provider so a multi-replica
        # stream stays attributable. None = standalone engine (PR 9
        # behavior, provider named serve-{policy}).
        self.replica = replica
        # Pluggable clock: every timestamp the engine takes (run-loop
        # now, TTFT, completion) comes from here. Default is the real
        # monotonic clock; a SimClock (serve/traffic.py) makes the whole
        # request lifecycle a deterministic function of the trace — the
        # chaos-scenario replay contract.
        self._clock = clock if clock is not None else time.monotonic
        # slo_metrics=False keeps this engine out of the process-wide
        # registry (serve_* counters/histograms/gauge) — warmup/probe
        # engines must not pollute the samples a telemetry stream's
        # metrics record snapshots for the real runs.
        self._slo_metrics = slo_metrics
        # Caches by layer kind, from what the engine already knows: in a
        # model of sliding layers beside full ones, a layer whose window
        # (plus the most tokens one program writes before it reads) is
        # much shorter than max_seq_len keeps a ring a slot, every other
        # layer whole contexts in the shared pool. A stack of equal
        # layers keeps whole pages as it always has, and so does every
        # model under the prefix cache (a shared prefix needs every
        # layer's pages whole). A gated-delta layer holds no K/V at all:
        # one recurrent state a slot, in pools indexed by slot. Such a
        # model cannot share prefixes (PagedKVCache raises) nor speculate.
        layout = CacheLayout.of(
            cfg, page_size=serve.page_size, max_seq_len=serve.max_seq_len,
            span=max(serve.prefill_chunk, serve.spec_k + 1),
            whole_pages=serve.prefix_cache)
        if layout.n_state and serve.spec_k:
            raise CacheKindError(
                "speculation verifies a window of drafts in one forward and "
                "drops the rejected ones; a state layer's recurrent state "
                "has then already taken them in. It would take a verify "
                "step that keeps the state at each position of the window "
                "and rolls back to the last accepted one "
                "(serve/model.make_verify_step); run this model with "
                "spec_k=0")
        self.cache = PagedKVCache(
            cfg, n_pages=serve.n_pages, page_size=serve.page_size,
            max_seq_len=serve.max_seq_len,
            prefix_cache=serve.prefix_cache,
            layout=layout, n_seqs=serve.n_slots,
            # Shared prefixes end on a page AND prefill-chunk boundary,
            # so a cache-hit request's remaining chunks are the same
            # compiled program at the same pos0 stream as the cold run's
            # — the bitwise-parity argument in docs/SERVING.md.
            share_granularity=share_granularity_for(serve.page_size,
                                                    serve.prefill_chunk))
        self.sched = Scheduler(self.cache, serve.n_slots,
                               policy=serve.policy,
                               prefill_chunks_per_iter=(
                                   serve.prefill_chunks_per_iter),
                               queue_budget_s=serve.queue_budget_s,
                               deadline_s=serve.deadline_s,
                               max_queue=serve.max_queue)
        # Request-trace plane (docs/TRACING.md "Request tracing"): the
        # scheduler's admission-side rtrace records go to this engine's
        # stream, tagged with the replica origin in fleet mode so the
        # timeline joiner can attribute them (and link migration hops)
        # on the fleet's shared stream.
        self.sched.sink = telemetry
        self._trace_fields = ({"replica": replica}
                              if replica is not None else {})
        self.sched.trace_fields = self._trace_fields
        # Brownout ladder (serve/overload.py): per-engine, fed and
        # ticked once per iteration; None = feature off, zero cost.
        if serve.brownout:
            from distributed_model_parallel_tpu.serve.overload import (
                BrownoutController,
            )

            self.brownout = BrownoutController(serve)
        else:
            self.brownout = None
        self._shed_by_reason: dict[str, int] = {}
        self._rejected = 0
        self._sampled = serve.temperature > 0
        kw = dict(page_size=serve.page_size, impl=serve.attn_impl,
                  layout=layout, temperature=serve.temperature,
                  top_k=serve.top_k, top_p=serve.top_p)
        # The routed layers' counters (tokens routed, tokens a held
        # expert) and the looped stack's (tokens, passes taken, the exit
        # gate's mass a pass), summed on the device by every step and
        # fetched only when somebody asks (moe_counters, loop_counters).
        self._stats = init_stats(cfg)
        self._prefill = make_prefill_step(cfg, chunk=serve.prefill_chunk,
                                          **kw)
        self._decode = make_decode_step(cfg, **kw)
        # Speculative decoding: decode rounds run a verify program from a
        # compiled WIDTH LADDER (powers of two up to spec_k + 1) — each
        # round dispatches the smallest width covering its longest live
        # draft, so a round where only one row drafts two tokens never
        # pays the full spec_k forward (the fixed-width program's cost is
        # set by its width, not by how many drafts actually ride it).
        self._verify_widths: list[int] = []
        self._verify: dict[int, object] = {}
        if serve.spec_k:
            w = 2
            while w < serve.spec_k + 1:
                self._verify_widths.append(w)
                w *= 2
            self._verify_widths.append(serve.spec_k + 1)
            self._verify = {w: make_verify_step(cfg, width=w, **kw)
                            for w in self._verify_widths}
        self._proposers: dict[str, NGramProposer] = {}
        # SHADOW gating: acceptance is bursty — the model wanders, then
        # locks into spans the n-gram index predicts perfectly — so a
        # request drafts for real only after its proposer has proven
        # itself, scoring single-token predictions against committed
        # tokens on the cheap path (free, host-side). Two consecutive
        # shadow hits go live; a zero-accept verify round goes back to
        # shadow. Deterministic: a pure function of the committed
        # stream, so the pinned spec-on/off parity is untouched (gating
        # moves WHEN drafts ride, never which tokens commit).
        self._spec_streak: dict[str, int] = {}
        self._spec_live: dict[str, bool] = {}
        self._requests: list[Request] = []
        # Per-slot page tables, maintained incrementally: reservation ==
        # allocation, so a request's table is final at admission — one
        # host write per join, not a rebuild per decode step.
        self._tables_np = np.zeros(
            (serve.n_slots, self.cache.pages_per_seq), np.int32)
        # ... and, where sliding layers keep rings, each slot's ring pages
        self._rings_np = (np.zeros((serve.n_slots, layout.ring_pages),
                                   np.int32)
                          if layout.ring_pages else None)
        self._auto_rid = 0
        self._iterations = 0
        self._now = 0.0               # live open-loop clock (last iteration)
        self._decode_steps = 0
        self._decode_tokens = 0       # useful tokens out of decode steps
        self._occupancy: list[float] = []
        self._wall_s = 0.0            # accumulates across run() calls
        # Real (monotonic) per-iteration wall samples, independent of
        # the pluggable clock — the crashrecovery scenario gates journal
        # overhead against their p50 even under a SimClock.
        self._iter_s: list[float] = []
        # prefix-cache + speculative-decoding accounting
        self._prompt_tokens = 0       # prompt tokens of admitted requests
        self._cached_tokens = 0       # of those, served from the tree
        self._draft_proposed = 0
        self._draft_accepted = 0
        # Live status exporter (utils/statusz.py): queue depth / page
        # occupancy / slot state under /statusz. No-op when no port is
        # configured anywhere in the process.
        from distributed_model_parallel_tpu.utils import statusz

        statusz.maybe_serve(serve.statusz_port)
        # One provider per policy (or per fleet replica): a later engine
        # of the same name replaces the entry. Warmup/probe engines
        # (slo_metrics=False) stay off the exporter like they stay out
        # of the registry.
        self._provider = (f"serve-{replica}" if replica is not None
                          else f"serve-{serve.policy}")
        if slo_metrics:
            statusz.register(self._provider, self._status)

    def _status(self) -> dict:
        """The engine's /statusz provider payload."""
        relaid = in_proj_relaid(self.params)
        return {
            "workload": "serve",
            "policy": self.serve.policy,
            "replica": self.replica,
            "iterations": self._iterations,
            "queue_depth": len(self.sched.queue),
            "active_requests": sum(1 for r in self._requests
                                   if not r.done and r.slot is not None),
            "n_slots": self.serve.n_slots,
            "page_occupancy": self.cache.occupancy,
            "requests_submitted": len(self._requests),
            # overload protection, live (docs/SERVING.md)
            "requests_shed": sum(self._shed_by_reason.values()),
            "requests_rejected": self._rejected,
            "shed_by_reason": dict(sorted(self._shed_by_reason.items())),
            "brownout_level": (self.brownout.level
                               if self.brownout is not None else None),
            "max_queue": self.serve.max_queue,
            # prefix sharing + speculative decoding, live
            "prefix_cache": self.serve.prefix_cache,
            "spec_k": self.serve.spec_k,
            # the cache by layer kind, and what a model with state layers
            # is refused (CacheKindError at construction, or on the call)
            "layers_by_cache_kind": {
                "full": self.cache.layout.n_full,
                "ring": self.cache.layout.n_ring,
                "state": self.cache.layout.n_state},
            # a looped stack: passes over the same weights, and the cache
            # layers they fill (passes x the model's layers)
            "passes": self.cfg.n_passes,
            "cache_layers": self.cache.layout.cache_layers,
            # the in-projection leaves this engine holds with the
            # contracted axis last (serve/model.in_proj_d_last): new
            # arrays beside the tree of a caller that keeps its own
            "weights_relaid": len(relaid),
            "weights_relaid_bytes": sum(w.nbytes for w in relaid),
            "refused_for_state_layers": (
                ["prefix_cache", "spec_k", "export_request",
                 "import_request"] if self.cache.layout.n_state else []),
            "cache_hit_rate": self.cache_hit_rate,
            "shared_pages": self.cache.shared_pages,
            "cached_prefix_pages": (len(self.cache.prefix)
                                    if self.cache.prefix is not None
                                    else 0),
            "draft_accept_rate": self.draft_accept_rate,
            # resource metering, live (utils/metering.py)
            "utilization": (self.meter.utilization()
                            if self.meter is not None else None),
            "open_bills": (len(self.meter._bills)
                           if self.meter is not None else None),
            "healthy": True,
        }

    @property
    def cache_hit_rate(self) -> float | None:
        """Prompt tokens served from the prefix tree / prompt tokens
        admitted (None before any admission or with the cache off)."""
        if not self.serve.prefix_cache or not self._prompt_tokens:
            return None
        return self._cached_tokens / self._prompt_tokens

    @property
    def draft_accept_rate(self) -> float | None:
        if not self.serve.spec_k or not self._draft_proposed:
            return None
        return self._draft_accepted / self._draft_proposed

    def warmup(self) -> None:
        """Dispatch every compiled program once with INERT inputs (no
        active rows, no valid prefill tokens — every cache write masked
        away, outputs discarded), so compilation happens here and never
        inside a timed serving run. Idle-safe: pool/tables/stats are
        untouched; cache buffers round-trip through the donating calls.
        The step builders are memoized per geometry, so one warmed
        engine warms every engine sharing its geometry — including the
        whole speculative width ladder, which otherwise compiles lazily
        at the first round that drafts each width."""
        for step, inputs in self._inert_calls():
            self._run(step, *inputs)

    def _inert_calls(self) -> list:
        """(step, inputs) for every compiled program, inputs INERT: no
        active rows, no valid prefill tokens."""
        b = self.serve.n_slots
        n = self.cache.pages_per_seq
        r = self.cache.layout.ring_pages
        i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
        table = (i32(n), i32(r) if r else None)
        if self.cache.layout.n_state:
            table += (jnp.int32(0),)
        tables = (i32(b, n), i32(b, r) if r else None)
        idle = jnp.zeros((b,), bool)
        keys = (jax.vmap(jax.random.key)(jnp.zeros((b,), jnp.uint32))
                if self._sampled else None)
        # prefill: zero valid tokens -> every write dropped
        calls = [(self._prefill, (i32(1, self.serve.prefill_chunk),
                                  jnp.int32(0), jnp.int32(0), table,
                                  jax.random.key(0))),
                 (self._decode, (i32(b), i32(b), tables, idle, keys))]
        calls += [(self._verify[w], (i32(b, w), i32(b),
                                     jnp.ones((b,), jnp.int32), tables, idle,
                                     keys))
                  for w in self._verify_widths]
        return calls

    def op_scopes(self, scopes) -> dict:
        """{module name: {instruction name: scope}} of the engine's
        compiled programs (``utils/tracing.op_scopes``): which device ops
        of a profiler trace run under which ``jax.named_scope``
        (``moe_route``, ``moe_experts``, ``attn_sliding``, ...). Compiles
        each program once more (from the compile cache where there is
        one): for a traced run's reader, after its window."""
        return dict(tracing.op_scopes(
            step.lower(self.params, self.cache.pools, self._stats,
                       *inputs).compile().as_text(), scopes)
            for step, inputs in self._inert_calls())

    def _run(self, step, *inputs):
        """Dispatch one jitted step on the device state it donates (the
        cache's pools, the routed layers' counters); returns its tokens."""
        self.cache.pools, self._stats, out = step(
            self.params, self.cache.pools, self._stats, *inputs)
        return out

    def _slot_tables(self, slot=None) -> tuple:
        """(table, ring) of one slot, or of all of them, for a step; with
        the slot itself where state layers keep a state a slot (the
        prefill step has to be told; a decode row is its slot)."""
        pick = (lambda a: a) if slot is None else (lambda a: a[slot])
        out = (jnp.asarray(pick(self._tables_np)),
               jnp.asarray(pick(self._rings_np))
               if self._rings_np is not None else None)
        if slot is not None and self.cache.layout.n_state:
            out += (jnp.int32(slot),)
        return out

    def moe_counters(self) -> dict:
        """The routed layers' counters since the engine was built, fetched
        from the device (a sync: ask between iterations, not inside one):
        ``{layer: {"tokens_routed", "held_assignments",
        "tokens_per_held_expert", "experts_touched",
        "row_tile_visits"}}`` (``experts_touched``: held experts that got
        a token, summed over the layer's calls; ``row_tile_visits``: the
        (row tile, expert) pairs the grouped products visited, so
        ``held_assignments`` over it is the rows a visit held); empty for
        a dense model."""
        return {layer: {"tokens_routed": int(row[-3]),
                        "held_assignments": int(row[:-3].sum()),
                        "tokens_per_held_expert": row[:-3].tolist(),
                        "experts_touched": int(row[-2]),
                        "row_tile_visits": int(row[-1])}
                for layer, row in stats_by_layer(self._stats,
                                                 self.cfg).items()}

    def loop_counters(self) -> dict:
        """The looped stack's counters since the engine was built, fetched
        from the device (a sync, like :meth:`moe_counters`): ``{"passes",
        "tokens", "token_passes", "exit_mass"}``: valid tokens through the
        stack (prompt and decode alike), the passes they took
        (``passes`` each while every row runs every pass), and for each
        pass the sum over those tokens of the exit gate's probability of
        leaving there (sums to ``tokens``; all on the last pass without a
        gate); empty for a stack run once."""
        loop = self._stats["loop"]
        if loop is None:
            return {}
        return {"passes": self.cfg.n_passes,
                "tokens": int(loop["tokens"]),
                "token_passes": int(loop["token_passes"]),
                "exit_mass": np.asarray(loop["exit_mass"]).tolist()}

    # -- submission ---------------------------------------------------------

    def submit(self, prompt, max_new_tokens: int, *, rid: str | None = None,
               arrival_s: float = 0.0, seed: int = 0,
               priority: str = "interactive",
               queue_budget_s: float | None = None,
               deadline_s: float | None = None,
               tenant: str | None = None) -> Request:
        prompt = [int(t) for t in prompt]
        if rid is None:
            rid = f"req-{self._auto_rid}"
            self._auto_rid += 1
        req = Request(rid=rid, prompt=prompt,
                      max_new_tokens=int(max_new_tokens),
                      arrival_s=float(arrival_s), seed=int(seed),
                      priority=priority, queue_budget_s=queue_budget_s,
                      deadline_s=deadline_s, tenant=tenant)
        # Stamp the request trace at entry into the serving tier: every
        # later rtrace record (admission, prefill, decode, terminal)
        # rides this identity. No stream, no stamp — rtrace then no-ops
        # everywhere downstream.
        if self.telemetry is not None and req.trace_id is None:
            req.trace_id = tracing.new_trace_id()
            self._rtrace(req, "submitted", prompt_tokens=req.prompt_len,
                         max_new_tokens=req.max_new_tokens,
                         priority=req.priority)
        return self.enqueue(req)

    def _rtrace(self, req: Request, event: str, **fields) -> None:
        """Engine-side rtrace emission: this engine's stream as the
        sink, tagged with the replica origin in fleet mode."""
        tracing.rtrace(req, event, sink=self.telemetry,
                       **self._trace_fields, **fields)

    def _validate_prompt(self, req: Request) -> None:
        bad = [t for t in req.prompt
               if not (0 <= t < self.cfg.vocab_size)]
        if bad:
            raise ValueError(f"prompt tokens {bad} outside vocab "
                             f"[0, {self.cfg.vocab_size})")

    def enqueue(self, req: Request, *, force: bool = False) -> Request:
        """Accept an already-built :class:`Request` — the fleet router's
        entry point (serve/fleet.py), and the re-admission path for a
        request drained off a quarantined peer (its committed tokens,
        cursor and ``resume`` payload ride on the object). A full
        bounded queue (``ServeConfig.max_queue``) REJECTS the request
        with a typed ``shed`` record (reason ``queue-full``) instead of
        growing without bound — callers check ``req.done``.
        ``force=True`` bypasses the bound: a migrated-in request is
        already-admitted load being moved, not new demand, and must
        never be dropped by its destination's queue bound."""
        self._validate_prompt(req)
        # The bound rejects ALREADY-ARRIVED submissions against the live
        # arrived backlog (the runaway-client case). Future-dated
        # open-loop trace entries are pre-registrations, not load — they
        # enqueue, and the per-iteration overflow trim (``_iterate``)
        # bounds the live backlog once they arrive.
        if (not force and self.sched.max_queue is not None
                and req.arrival_s <= self._now
                and self.sched.arrived_backlog(self._now)
                >= self.sched.max_queue):
            self._reject(req, "queue-full")
            return req
        self.sched.submit(req)
        self._requests.append(req)
        return req

    def try_enqueue(self, req: Request) -> bool:
        """Bounded enqueue with NO side effects on refusal — the fleet
        dispatcher's entry point: a ``False`` feeds the router's
        circuit breaker and the request stays on the fleet queue."""
        self._validate_prompt(req)
        if self.sched.full:
            return False
        self.sched.submit(req)
        self._requests.append(req)
        return True

    def _reject(self, req: Request, reason: str) -> None:
        """Typed submission rejection (queue-full): terminal, counted,
        recorded — never an unbounded host-side list."""
        req.state = RequestState.FAILED
        req.shed_reason = reason
        req.error = f"rejected: {reason}"
        if self.journal is not None:
            # A rejected request usually predates its intent (the
            # journal drops unknown rids); a fleet-accepted one whose
            # re-dispatch bounced still owes its single terminal.
            self.journal.terminal(req.rid, "shed")
        if self.meter is not None:
            self.meter.terminal(req, "shed", self.telemetry)
        self._rtrace(req, "shed", reason=reason, state="queued")
        self._requests.append(req)
        self._rejected += 1
        self._shed_by_reason[reason] = self._shed_by_reason.get(reason, 0) + 1
        if self._slo_metrics:
            reg = registry()
            reg.counter("serve_rejected_total").inc()
            reg.counter("serve_shed_total").inc()
        if self.telemetry is not None:
            self.telemetry.record(
                "shed", request=req.rid, reason=reason,
                priority=req.priority, state="queued",
                policy=self.serve.policy, prompt_tokens=req.prompt_len,
                new_tokens=len(req.generated),
                **({"replica": self.replica}
                   if self.replica is not None else {}))

    # -- live migration (serve/fleet.py) ------------------------------------

    def drain(self) -> list[Request]:
        """Take every live request off this engine for migration to a
        peer replica, in submission order. Each resident request's
        committed state is serialized onto the object itself: the
        ``resume`` payload carries its written KV pages **by value**
        (``PagedKVCache.export_request``), so nothing references this
        engine's pool or radix tree afterwards. Queued requests ride
        along untouched (a queued request that was itself migrated in
        keeps the payload it still carries). Slots and pages return to
        this engine immediately; terminal requests stay for the record.

        Where sliding layers keep rings or state layers a recurrent state
        (``CacheLayout``) there is no run of whole pages to copy: a
        resident request leaves with its
        tokens alone and the peer rebuilds its K/V by prefill over
        prompt + committed tokens, as after a crash (``Request.replay``,
        serve/journal.py: the last committed token is re-sampled and
        asserted against the one it carries).
        """
        out: list[Request] = []
        by_replay = bool(self.cache.layout.ring_pages
                         or self.cache.layout.n_state)
        for req in self._requests:
            if req.done:
                continue
            if req.slot is not None and by_replay:
                req.prefill_cursor = 0
                req.cached_prompt_tokens = 0
                req.replay = bool(req.generated)
                req.state = RequestState.QUEUED
                self._rtrace(req, "export", pages=0, replay=True,
                             n_tokens=len(req.prefill_tokens))
            elif req.slot is not None:
                if req.state is RequestState.PREFILL:
                    # Positions [0, cursor) are prefilled and written.
                    n_written = req.prefill_cursor
                else:
                    # Plain decode feeds a committed token back BEFORE
                    # writing its KV, so the last committed token's slot
                    # is unwritten (and under speculation may hold a
                    # rejected draft's write) — the same boundary
                    # ``_complete`` trims before the prefix tree.
                    n_written = req.prompt_len + len(req.generated) - 1
                k, v = self.cache.export_request(
                    req.rid, n_written, req=req, sink=self.telemetry,
                    trace_fields=self._trace_fields)
                req.resume = {
                    "k": k, "v": v, "n_written": n_written,
                    "state": ("decode" if req.state is RequestState.DECODE
                              else "prefill"),
                }
                req.state = RequestState.QUEUED
            if self.meter is not None:
                # Residency ends here for this replica: a ``hop`` meter
                # record bills it for exactly what it hosted (hop index
                # = the residency being closed; the destination's next
                # record carries migrations + 1, so the chain links).
                self.meter.close_hop(req, self.telemetry)
            self.sched.withdraw(req)
            self._proposers.pop(req.rid, None)
            self._spec_streak.pop(req.rid, None)
            self._spec_live.pop(req.rid, None)
            req.migrations += 1
            out.append(req)
        self._requests = [r for r in self._requests if r.done]
        return out

    def _restore_imported(self, req: Request) -> None:
        """Finish admitting a migrated-in request: its pages are already
        imported — resume at the exact committed position (mid-prefill
        cursors are chunk-aligned, so the remaining chunks replay the
        cold run's exact program stream; mid-decode requests re-enter
        the decode batch as if they had never left)."""
        payload = req.resume
        req.resume = None
        req.state = (RequestState.DECODE if payload["state"] == "decode"
                     else RequestState.PREFILL)
        if self.serve.spec_k:
            # The proposer is a pure function of the committed stream —
            # rebuild it from prompt + committed tokens. Gating restarts
            # in shadow mode (re-prove on this replica); that moves WHEN
            # drafts ride, never which tokens commit.
            prop = NGramProposer(self.serve.spec_k,
                                 max_order=self.serve.spec_ngram)
            prop.extend(req.prompt)
            prop.extend(req.generated)
            self._proposers[req.rid] = prop

    def clear_cache(self) -> int:
        """Drop the prefix tree and verify every page is back on the
        free list — the quarantine invariant ("all pages of the dead
        replica are returned"). Call after :meth:`drain`; a page still
        held here would mean an exported request left a reference
        behind. Returns the tree pages freed."""
        freed = self.cache.drop_prefix()
        if self.cache.pool.used_pages:
            raise PagePoolError(
                f"engine {self._provider}: {self.cache.pool.used_pages} "
                f"pages still held after drain + prefix drop")
        return freed

    # -- hard crash (serve/journal.py crash recovery) -----------------------

    def kill(self, reason: str = "injected-crash") -> None:
        """Hard-crash this engine: NO drain, no per-request terminals —
        engine object, page pool and prefix tree are simply abandoned
        (``ServeFleet.crash_replica`` discards them). The exhaust is a
        typed failure record carrying the journal position (the exact
        replay point) and a flight-recorder bundle, so the postmortem is
        self-contained; re-serving the lost requests is the journal's
        job, not this method's."""
        if self.telemetry is not None:
            self.telemetry.failure(
                "replica-crashed", detail=reason,
                iteration=self._iterations,
                **({"replica": self.replica}
                   if self.replica is not None else {}),
                **({"journal": self.journal.position()}
                   if self.journal is not None else {}))
        from distributed_model_parallel_tpu.utils import flightrec

        flightrec.dump("replica-crashed", telemetry_run=self.telemetry)

    # -- the loop -----------------------------------------------------------

    def run(self, *, max_iterations: int | None = None,
            record_summary: bool = True) -> dict:
        """Drive the loop until every submitted request is terminal (or
        ``max_iterations``). Returns the summary dict (also emitted as
        the ``serve`` summary telemetry record unless
        ``record_summary=False`` — a multi-wave driver (a chat campaign,
        one wave a turn) calls run() per wave and records ONE campaign
        summary at the end instead of one per wave)."""
        t0 = self._clock()
        try:
            # Spans from the loop (prefill chunks, decode rounds,
            # admissions) go to this engine's own stream for the scope
            # of the run — the request-lifecycle timeline
            # scripts/dmp_trace.py renders next to the per-request
            # serve records.
            with tracing.sink_scope(self.telemetry):
                while not self.sched.idle():
                    if (max_iterations is not None
                            and self._iterations >= max_iterations):
                        break
                    now = self._clock() - t0
                    made_progress = self.step_once(now, t0)
                    if not made_progress:
                        nxt = self.sched.next_arrival()
                        if nxt is not None:
                            # Open loop: nothing resident, next request
                            # not arrived yet — sleep to its arrival (a
                            # virtual clock skips straight there).
                            adv = getattr(self._clock, "advance_to",
                                          None)
                            if adv is not None:
                                adv(t0 + nxt)
                            else:
                                time.sleep(max(0.0, min(nxt - now,
                                                        0.05)))
        except BaseException as e:
            self._fail_inflight(f"{type(e).__name__}: {e}")
            self._wall_s += self._clock() - t0
            if self.telemetry is not None:
                self.telemetry.failure(
                    "engine-killed", detail=f"{type(e).__name__}: {e}",
                    iteration=self._iterations,
                    **({"journal": self.journal.position()}
                       if self.journal is not None else {}))
            # Crash flight recorder (utils/flightrec.py): capture the
            # state at the moment of death — ring records, thread
            # stacks, span stacks, page-pool state. No-op when no
            # recorder is installed.
            from distributed_model_parallel_tpu.utils import flightrec

            flightrec.dump("engine-killed", telemetry_run=self.telemetry,
                           error=e)
            if not isinstance(e, Exception):
                # KeyboardInterrupt/SystemExit keep their semantics —
                # the typed-failure bookkeeping above still ran.
                raise
            raise EngineKilled(
                f"engine died at iteration {self._iterations}; "
                f"in-flight requests marked failed") from e
        # Accumulate: a multi-turn driver (a chat campaign) calls
        # run() per wave and reads one whole-campaign summary at the end.
        self._wall_s += self._clock() - t0
        return self.summary(record=record_summary)

    def step_once(self, now: float, t0: float) -> bool:
        """One engine iteration (admit → prefill chunk(s) → decode round
        → evict) at open-loop clock ``now`` (seconds since the monotonic
        origin ``t0``). ``run()`` loops over this; the fleet
        (serve/fleet.py) drives its replicas' iterations round-robin
        through it directly so every replica shares one clock.

        The ``engine_step`` span (profiler-only, like the dotted phases
        below it) covers all of it, hook and meter tick included; its
        phases are the ``admit``, ``prefill_chunk`` and ``decode_round``
        spans below, and what they leave over is the tail of ``_iterate``
        (occupancy, brownout tick, gauges)."""
        with span("engine_step", stream=False, iteration=self._iterations):
            if self.step_hook is not None:
                self.step_hook(self._iterations)
            self._iterations += 1
            self._now = now
            w0 = time.monotonic()
            progress = False
            try:
                progress = self._iterate(now, t0)
                return progress
            finally:
                dt = time.monotonic() - w0
                self._iter_s.append(dt)
                if self.meter is not None:
                    # The SAME wall sample just appended to _iter_s —
                    # that identity is what makes the duty buckets
                    # partition the iteration wall exactly (dmp_capacity
                    # --gate). A raise out of _iterate ticks with
                    # progress=False; the dead engine's ledger still sums
                    # to its wall.
                    self.meter.tick(
                        dt, progress=progress,
                        brownout=(self.brownout is not None
                                  and self.brownout.level >= 1),
                        has_work=(any(r is not None
                                      for r in self.sched.slots)
                                  or self.sched.arrived_backlog(now) > 0),
                        cache=self.cache)

    def _admit(self, now: float) -> int:
        """The iteration's admission phase (the ``admit`` span): sheds,
        the brownout ladder's admission-side knobs, the scheduler's
        admission pass with the page-table copies, and the queue-bound
        trim. Returns how many requests were admitted."""
        # Overload protection first: shed queued requests past their
        # budgets, abort in-flight ones past their total deadline (pages
        # return immediately — before admission, so a freed reservation
        # can admit someone this very iteration), then apply the
        # brownout ladder's admission-side knobs.
        for req, reason in self.sched.expire(now):
            self._shed(req, reason, now)
        for req in self.sched.active():
            dl = (req.deadline_s if req.deadline_s is not None
                  else self.serve.deadline_s)
            if dl is not None and now - req.arrival_s > dl:
                self._shed(req, "total-deadline", now)
        bo = self.brownout
        if bo is not None:
            from distributed_model_parallel_tpu.serve.overload import (
                apply_max_new_cap,
            )

            self.sched.prefill_chunks_per_iter = (
                self.serve.prefill_chunks_per_iter
                if bo.prefill_full_share else 1)
            # Clamp while waiting under level-3 brownout: the
            # reservation shrinks BEFORE admission bills it. The clamp
            # sticks (deterministic accounting); the clamped stream is
            # the bitwise prefix of the unclamped one. Each newly
            # clamped request gets a ``clamp`` rtrace record
            # (serve/overload.py).
            apply_max_new_cap(bo, self.sched.queue, now,
                              sink=self.telemetry,
                              trace_fields=self._trace_fields)
        admitted = self.sched.admit(now)
        for req in admitted:
            self._tables_np[req.slot] = self.cache.table_array(req.rid)
            if self._rings_np is not None:
                self._rings_np[req.slot] = self.cache.ring_array(req.rid)
            if self.meter is not None:
                # Residency starts here for cold, migrated-in and
                # crash-replayed admissions alike — each replica bills
                # only the residency it actually hosts.
                self.meter.open_bill(req.rid)
            if req.resume is not None:
                # A migrated-in request: its pages were imported by the
                # scheduler; resume at the exact committed position —
                # no prompt/cache accounting (its prefill was billed on
                # the source replica) and no second queue-wait sample.
                self._restore_imported(req)
                continue
            # Cache-hit admission: the shared pages already hold the
            # prefix KV — prefill starts at the first uncached token.
            req.prefill_cursor = req.cached_prompt_tokens
            self._prompt_tokens += req.prompt_len
            self._cached_tokens += req.cached_prompt_tokens
            if self.serve.spec_k:
                prop = NGramProposer(self.serve.spec_k,
                                     max_order=self.serve.spec_ngram)
                # Journal replays seed the proposer with the whole
                # replayed prefix (prompt + committed tokens minus the
                # re-sampled last); the final prefill chunk extends the
                # last one, so the stream carries every committed token.
                prop.extend(req.prefill_tokens)
                self._proposers[req.rid] = prop
            if self._slo_metrics and req.cached_prompt_tokens:
                registry().counter("serve_prefill_tokens_saved").inc(
                    req.cached_prompt_tokens)
            self._record_queue_wait(req)
        # Queue-bound trim AFTER admission (work-conserving: a request a
        # freed slot just absorbed must not count against the bound),
        # so the arrived backlog leaves every iteration within
        # max_queue — batch first, newest first.
        for req in self.sched.overflow(now):
            self._shed(req, "queue-full", now)
        return len(admitted)

    def _iterate(self, now: float, t0: float) -> bool:
        # On the stream only when somebody waits (a record for every idle
        # turn would drown it); on the profiler's timeline every turn.
        with span("admit", stream=bool(self.sched.queue)) as sp:
            sp.annotate(admitted=self._admit(now))
        bo = self.brownout
        progress = False
        for req in self.sched.prefilling():
            self._prefill_chunk(req, t0)
            progress = True
        decoding = self.sched.decoding()
        if decoding:
            self._decode_round(decoding, t0)
            progress = True
        occ = self.cache.occupancy
        self._occupancy.append(occ)
        if bo is not None:
            bo.observe_occupancy(occ)
            transition = bo.tick(now)
            if transition is not None:
                if self.telemetry is not None:
                    self.telemetry.record(
                        "brownout", policy=self.serve.policy,
                        **transition,
                        **({"replica": self.replica}
                           if self.replica is not None else {}))
                if self._slo_metrics and self.replica is None:
                    registry().gauge("serve_brownout_level").set(bo.level)
        # Fleet replicas (self.replica set) skip the process-global
        # gauge writes: N engines flapping one unlabeled gauge would
        # report whichever iterated last. The fleet aggregates ALL of
        # these gauges across live replicas itself (ServeFleet
        # _set_engine_gauges: occupancy max, shared-pages sum, pooled
        # hit/accept rates); per-replica values live on the /statusz
        # providers.
        if self._slo_metrics and self.replica is None:
            reg = registry()
            reg.gauge("serve_page_occupancy").set(occ)
            if self.serve.prefix_cache:
                reg.gauge("serve_shared_pages").set(self.cache.shared_pages)
                if self.cache_hit_rate is not None:
                    reg.gauge("serve_cache_hit_rate").set(
                        self.cache_hit_rate)
            if self.serve.spec_k and self.draft_accept_rate is not None:
                reg.gauge("serve_draft_accept_rate").set(
                    self.draft_accept_rate)
        return progress

    # -- prefill ------------------------------------------------------------

    def _prefill_chunk(self, req: Request, t0: float) -> None:
        with span("prefill_chunk", request=req.rid,
                  cursor=req.prefill_cursor):
            self._prefill_chunk_inner(req, t0)

    def _prefill_chunk_inner(self, req: Request, t0: float) -> None:
        chunk = self.serve.prefill_chunk
        # A journal-replay request prefills prompt + committed tokens
        # (minus the last, re-sampled below) — the crash-recovery path
        # (serve/journal.py); everyone else prefills just the prompt.
        seq = req.prefill_tokens
        lo = req.prefill_cursor
        n_valid = min(chunk, len(seq) - lo)
        with span("prefill_chunk.inputs", stream=False):
            toks = np.zeros((1, chunk), np.int32)
            toks[0, :n_valid] = seq[lo:lo + n_valid]
            inputs = (jnp.asarray(toks), jnp.int32(lo), jnp.int32(n_valid),
                      self._slot_tables(req.slot),
                      jax.random.key(req.seed))
        m = self.meter
        d0 = time.monotonic() if m is not None else 0.0
        with span("prefill_chunk.dispatch", stream=False):
            tok = self._run(self._prefill, *inputs)
        if m is not None:
            # A prefill chunk owns the whole slice: its full dispatch
            # wall bills to this one request (utils/metering.py).
            m.bill_prefill(req.rid, time.monotonic() - d0)
        req.prefill_cursor = lo + n_valid
        if req.prefill_cursor < len(seq):
            with span("prefill_chunk.commit", stream=False):
                self._rtrace(req, "prefill", cursor=req.prefill_cursor,
                             tokens=n_valid)
            return
        # Final chunk: its sampled token is the request's first
        # generated token (position t0) — TTFT stops here. On a
        # replay it is the LAST journaled token, re-sampled: the
        # determinism contract (tokens = f(prompt, seed)) makes it
        # bitwise-identical, and we assert that rather than trust it.
        with span("prefill_chunk.sync", stream=False):
            first = int(jax.device_get(tok)[0])
        with span("prefill_chunk.commit", stream=False):
            self._commit_first_token(req, first, seq, n_valid, t0)

    def _commit_first_token(self, req: Request, first: int, seq: list,
                            n_valid: int, t0: float) -> None:
        replaying = req.replay and bool(req.generated)
        if replaying:
            want = req.generated[-1]
            if first != want:
                raise AssertionError(
                    f"journal replay diverged for {req.rid!r}: "
                    f"re-sampled token {first} != journaled {want} "
                    f"at position {len(seq)} — the determinism "
                    f"contract (tokens = f(prompt, seed)) is broken")
        else:
            req.generated.append(first)
            if self.journal is not None:
                self.journal.commit(req.rid, (first,))
        req.replay = False
        # On a replay every journaled token is stamped here, when this
        # process delivers it again.
        now = self._stamp_tokens(req, len(req.generated) - len(req.t_tokens),
                                 t0)
        if req.t_first_token is None:
            req.t_first_token = now
            self._record_ttft(req)
        req.state = RequestState.DECODE
        self._rtrace(req, "prefill", cursor=req.prefill_cursor,
                     tokens=n_valid, ttft_s=self._ttft(req),
                     **({"replayed": len(req.generated)}
                        if replaying else {}))
        # Every prefilled position's KV is now written — offer the
        # pages to the prefix tree so the next request with this
        # prefix (the multi-turn case) admits warm. ``seq`` is the
        # prompt, or on replay the prompt + committed tokens minus
        # the re-sampled last — the same verified-written trim
        # boundary ``_complete`` uses.
        self.cache.insert_prefix(req.rid, seq)
        # The proposer's stream must carry EVERY committed token —
        # skipping the first generated one would shift its whole
        # index around the prompt/generation boundary.
        prop = self._proposers.get(req.rid)
        if prop is not None:
            self._shadow_score(req, first)
            prop.extend([first])
        if self._finished(req, first):
            self._complete(req, t0)

    def _stamp_tokens(self, req: Request, n: int, t0: float) -> float:
        """Stamp the ``n`` tokens just committed to ``req`` with the
        engine clock (``Request.t_tokens``: one float a token; the tokens
        of one speculative round share theirs). Returns the stamp."""
        now = self._clock() - t0
        req.t_tokens.extend([now] * n)
        return now

    # -- decode -------------------------------------------------------------

    def _decode_round(self, decoding: list[Request], t0: float) -> None:
        # Brownout level >= 1 sheds the speculative verify windows: the
        # single-token program commits identical tokens (the pinned
        # spec-on/off parity) at guaranteed-progress cost per round.
        spec = bool(self._verify) and (self.brownout is None
                                       or self.brownout.spec_enabled)
        with span("decode_round", batch=len(decoding), spec=spec):
            if spec:
                self._spec_round_inner(decoding, t0)
            else:
                self._decode_round_inner(decoding, t0)

    def _decode_round_inner(self, decoding: list[Request], t0: float) -> None:
        b = self.serve.n_slots
        with span("decode_round.inputs", stream=False):
            tokens = np.zeros((b,), np.int32)
            positions = np.zeros((b,), np.int32)
            active = np.zeros((b,), bool)
            seeds = np.zeros((b,), np.uint32)
            for req in decoding:
                s = req.slot
                tokens[s] = req.generated[-1]
                positions[s] = req.prompt_len + len(req.generated) - 1
                active[s] = True
                seeds[s] = req.seed
            keys = (jax.vmap(jax.random.key)(jnp.asarray(seeds))
                    if self._sampled else None)
            inputs = (jnp.asarray(tokens), jnp.asarray(positions),
                      self._slot_tables(), jnp.asarray(active), keys)
        m = self.meter
        d0 = time.monotonic() if m is not None else 0.0
        with span("decode_round.dispatch", stream=False):
            nxt = self._run(self._decode, *inputs)
        with span("decode_round.sync", stream=False):
            nxt = np.asarray(jax.device_get(nxt))
        with span("decode_round.commit", stream=False):
            self._commit_decode(decoding, nxt, d0, t0)

    def _commit_decode(self, decoding: list[Request], nxt, d0: float,
                       t0: float) -> None:
        m = self.meter
        if m is not None:
            # The round's wall (dispatch + host sync) apportions evenly
            # across the live decode slots it served.
            m.bill_decode([r.rid for r in decoding],
                          time.monotonic() - d0)
        self._decode_steps += 1
        self._decode_tokens += len(decoding)
        # Memory-pressure gauges ride every decode rtrace, computed once
        # per round (page state only moves on admission/eviction, never
        # inside the round) — the attribution that tells a memory stall
        # from a compute stall (ISSUE 16).
        gauges = (memory_gauges(self.cache) if self.telemetry is not None
                  else None)
        for req in decoding:
            tok = int(nxt[req.slot])
            req.generated.append(tok)
            self._stamp_tokens(req, 1, t0)
            if self.journal is not None:
                # Watermark the journal at the exact commit point — a
                # token enters ``generated`` iff the model chose it, so
                # the journal never sees a rejected draft.
                self.journal.commit(req.rid, (tok,))
            if gauges is not None:
                self._rtrace(req, "decode", new_tokens=1, **gauges)
            if self._finished(req, tok):
                self._complete(req, t0)
            else:
                # Spec engines route draft-less rounds through here —
                # score the shadow prediction, then feed the proposer
                # the committed token.
                prop = self._proposers.get(req.rid)
                if prop is not None:
                    self._shadow_score(req, tok)
                    prop.extend([tok])

    def _spec_round_inner(self, decoding: list[Request], t0: float) -> None:
        """One speculative round: every active slot verifies its n-gram
        draft in ONE fixed-width forward and commits the model-verified
        prefix — between 1 and ``width`` tokens per request per round.

        ``out[s, i]`` is the model's token for the position after window
        index ``i``; it is committed only while every draft before it
        matched the model's own choice, so the committed stream is
        bitwise the sequential decode stream (a draft can never smuggle
        in a token the model would not have produced — docs/SERVING.md,
        "Speculative decoding"). KV hygiene: a rejected draft leaves
        garbage KV only at positions at or past the NEXT round's window
        start, and every round rewrites its whole window before reading
        it, so garbage is always overwritten before it becomes readable;
        the last committed token's slot is the one position that may
        still hold a rejected write, which is why completion trims it
        before offering pages to the prefix tree.
        """
        b = self.serve.n_slots
        cap = self.serve.spec_k + 1
        proposals: dict[str, list[int]] = {}
        with span("decode_round.inputs", stream=False):
            for req in decoding:
                remaining = req.max_new_tokens - len(req.generated)
                if remaining > 1 and self._spec_live.get(req.rid):
                    proposals[req.rid] = self._proposers[
                        req.rid].propose()[:min(cap, remaining) - 1]
                else:
                    proposals[req.rid] = []  # shadow mode: prove it first
            longest = max((len(d) for d in proposals.values()), default=0)
        if longest == 0:
            # No row drafted (cold proposers, backoff, ends of budgets):
            # the single-token program commits the identical tokens (the
            # spec-on/off parity the tests pin) at 1/width the FLOPs.
            self._decode_round_inner(decoding, t0)
            return
        # Smallest compiled verify width covering the longest live draft.
        width = next(w for w in self._verify_widths if w >= longest + 1)
        drafts: dict[str, list[int]] = {}
        with span("decode_round.inputs", stream=False):
            tokens = np.zeros((b, width), np.int32)
            positions = np.zeros((b,), np.int32)
            # Idle rows keep n_valid=1 (writes are dropped via the active
            # mask; a zero-length row would make its garbage softmax all
            # -inf, and NaNs — however masked — have no business
            # existing).
            n_valid = np.ones((b,), np.int32)
            active = np.zeros((b,), bool)
            seeds = np.zeros((b,), np.uint32)
            for req in decoding:
                s = req.slot
                remaining = req.max_new_tokens - len(req.generated)
                w = min(width, remaining)
                draft = proposals[req.rid][:w - 1]
                drafts[req.rid] = draft
                tokens[s, 0] = req.generated[-1]
                tokens[s, 1:1 + len(draft)] = draft
                positions[s] = req.prompt_len + len(req.generated) - 1
                n_valid[s] = w
                active[s] = True
                seeds[s] = req.seed
            keys = (jax.vmap(jax.random.key)(jnp.asarray(seeds))
                    if self._sampled else None)
            inputs = (jnp.asarray(tokens), jnp.asarray(positions),
                      jnp.asarray(n_valid), self._slot_tables(),
                      jnp.asarray(active), keys)
        m = self.meter
        d0 = time.monotonic() if m is not None else 0.0
        with span("decode_round.dispatch", stream=False):
            out = self._run(self._verify[width], *inputs)
        with span("decode_round.sync", stream=False):
            out = np.asarray(jax.device_get(out))
        with span("decode_round.commit", stream=False):
            self._commit_spec(decoding, tokens, n_valid, out, drafts, d0,
                              t0)

    def _commit_spec(self, decoding: list[Request], tokens, n_valid, out,
                     drafts: dict, d0: float, t0: float) -> None:
        m = self.meter
        if m is not None:
            # A verify round is one batched forward like plain decode —
            # equal shares per live slot regardless of draft widths.
            m.bill_decode([r.rid for r in decoding],
                          time.monotonic() - d0)
        self._decode_steps += 1
        round_proposed = round_accepted = 0
        gauges = (memory_gauges(self.cache) if self.telemetry is not None
                  else None)
        for req in decoding:
            s = req.slot
            draft = drafts[req.rid]
            emitted: list[int] = []
            for i in range(int(n_valid[s])):
                if i > 0 and tokens[s, i] != out[s, i - 1]:
                    break                      # draft i-1 rejected
                tok = int(out[s, i])
                emitted.append(tok)
                if (self.serve.eos_id is not None
                        and tok == self.serve.eos_id):
                    break
            req.generated.extend(emitted)
            self._stamp_tokens(req, len(emitted), t0)
            if self.journal is not None:
                # Only the model-verified prefix reaches ``generated``
                # (the loop above breaks at the first rejected draft),
                # so the watermark can never advance past a speculative
                # tail the model didn't commit.
                self.journal.commit(req.rid, emitted)
            self._decode_tokens += len(emitted)
            # Accept accounting over REAL proposals only (window padding
            # that happens to match is decode luck, not drafting).
            accepted = max(0, min(len(emitted) - 1, len(draft)))
            round_proposed += len(draft)
            round_accepted += accepted
            if draft:
                if accepted == 0:
                    # Streak broken: back to shadow mode until the
                    # proposer re-proves itself on committed tokens.
                    self._spec_live[req.rid] = False
                    self._spec_streak[req.rid] = 0
            else:
                self._shadow_score(req, emitted[0])
            if gauges is not None:
                self._rtrace(req, "decode", new_tokens=len(emitted),
                             spec_proposed=len(draft),
                             spec_accepted=accepted, **gauges)
            if self._finished(req, emitted[-1]):
                self._complete(req, t0)
            else:
                self._proposers[req.rid].extend(emitted)
        self._draft_proposed += round_proposed
        self._draft_accepted += round_accepted
        if self._slo_metrics and round_proposed:
            reg = registry()
            reg.counter("serve_draft_tokens_proposed").inc(round_proposed)
            reg.counter("serve_draft_tokens_accepted").inc(round_accepted)

    def _shadow_score(self, req: Request, committed: int) -> None:
        """Score the proposer's single-token prediction against the
        token the model actually committed (called BEFORE the proposer
        sees it). Two consecutive hits promote the request to live
        drafting — the free filter that keeps verify width off the
        wander phase and on the predictable spans."""
        pred = self._proposers[req.rid].predict_next()
        if pred is not None and pred == committed:
            streak = self._spec_streak.get(req.rid, 0) + 1
            self._spec_streak[req.rid] = streak
            if streak >= 2:
                self._spec_live[req.rid] = True
        else:
            self._spec_streak[req.rid] = 0

    def _finished(self, req: Request, tok: int) -> bool:
        return (len(req.generated) >= req.max_new_tokens
                or (self.serve.eos_id is not None
                    and tok == self.serve.eos_id))

    # -- lifecycle ----------------------------------------------------------

    def _complete(self, req: Request, t0: float) -> None:
        # The stamp of the token that finished it (every caller has just
        # stamped one), so that t_tokens ends on t_done.
        req.t_done = req.t_tokens[-1]
        req.state = RequestState.COMPLETED
        if self.journal is not None:
            # Durable terminal BEFORE the engine forgets the request —
            # dedup'd by rid, so a recovered request re-completing after
            # a crash that already journaled its terminal is a no-op
            # (exactly-once accounting).
            self.journal.terminal(req.rid, "completed")
        # Offer the whole committed sequence (prompt + generation) to the
        # prefix tree BEFORE eviction drops our page references — this is
        # what makes a multi-turn follow-up (prior turns re-sent as the
        # new prompt) admit warm. The final token is always trimmed: its
        # KV slot is either unwritten (plain decode feeds a token back
        # before writing it) or may hold a rejected draft's write
        # (speculative rounds) — only verified-written positions are
        # shareable.
        self.cache.insert_prefix(
            req.rid, (req.prompt + req.generated)[:-1])
        self._proposers.pop(req.rid, None)
        self._spec_streak.pop(req.rid, None)
        self._spec_live.pop(req.rid, None)
        if self.meter is not None:
            # Close the bill BEFORE eviction drops the page table, so
            # the meter record reflects the final page reservation.
            self.meter.terminal(
                req, "completed", self.telemetry,
                good_tokens=(len(req.generated)
                             if self._in_deadline(req) else 0))
        self.sched.evict(req)
        if self.brownout is not None:
            self.brownout.observe_completed(self._ttft(req), req.t_done)
        token_s = None
        if len(req.generated) > 1 and req.t_first_token is not None:
            token_s = ((req.t_done - req.t_first_token)
                       / (len(req.generated) - 1))
        if self._slo_metrics:
            reg = registry()
            reg.counter("serve_requests_completed").inc()
            reg.counter("serve_tokens_generated").inc(len(req.generated))
            if token_s is not None:
                reg.histogram("serve_token_latency_s").observe(
                    token_s, exemplar=req.trace_id)
        self._rtrace(req, "completed", new_tokens=len(req.generated),
                     ttft_s=self._ttft(req),
                     queue_wait_s=self._queue_wait(req),
                     token_latency_s=token_s,
                     wall_s=req.t_done - req.arrival_s)
        if self.telemetry is not None:
            self.telemetry.record(
                "serve", event="completed", request=req.rid,
                policy=self.serve.policy,
                prompt_tokens=req.prompt_len,
                new_tokens=len(req.generated),
                queue_wait_s=self._queue_wait(req),
                ttft_s=self._ttft(req), token_latency_s=token_s,
                wall_s=req.t_done - req.arrival_s,
                **({"replica": self.replica, "migrations": req.migrations}
                   if self.replica is not None else {}))

    def _shed(self, req: Request, reason: str, now: float) -> None:
        """Shed one request with a typed record: a queued expiry (the
        scheduler already dequeued it) or an in-flight deadline abort —
        the latter evicts mid-stream, returning every reserved page
        immediately (chunk-aligned mid-prefill aborts included: eviction
        frees the whole table). Terminal, counted, never silent."""
        state_at = req.state.value
        if self.meter is not None:
            # One terminal meter record whether the request was resident
            # (deadline abort: its bill carries real cost) or still
            # queued (zero bill) — matching the rtrace terminal below.
            self.meter.terminal(
                req,
                "expired" if reason in ("total-deadline",
                                        "queue-deadline") else "shed",
                self.telemetry)
        if req.slot is not None:
            self.sched.evict(req)
        self._proposers.pop(req.rid, None)
        self._spec_streak.pop(req.rid, None)
        self._spec_live.pop(req.rid, None)
        req.state = RequestState.FAILED
        req.shed_reason = reason
        req.error = f"shed: {reason}"
        if self.journal is not None:
            self.journal.terminal(req.rid, "shed")
        self._shed_by_reason[reason] = self._shed_by_reason.get(reason, 0) + 1
        if reason == "queue-full":
            self._rejected += 1
        if self._slo_metrics:
            registry().counter("serve_shed_total").inc()
            if reason == "queue-full":
                registry().counter("serve_rejected_total").inc()
        # Typed terminal rtrace: deadline expiries are ``expired``,
        # everything else (queue-full displacement) is ``shed`` — the
        # joiner requires exactly one terminal event per trace.
        self._rtrace(req,
                     "expired" if reason in ("total-deadline",
                                             "queue-deadline") else "shed",
                     reason=reason, state=state_at,
                     waited_s=round(max(0.0, now - req.arrival_s), 4))
        if self.telemetry is not None:
            self.telemetry.record(
                "shed", request=req.rid, reason=reason,
                priority=req.priority, state=state_at,
                policy=self.serve.policy,
                waited_s=round(max(0.0, now - req.arrival_s), 4),
                prompt_tokens=req.prompt_len,
                new_tokens=len(req.generated),
                **({"replica": self.replica}
                   if self.replica is not None else {}))

    def _fail_inflight(self, detail: str) -> None:
        for req in self._requests:
            if req.done:
                continue
            if self.meter is not None:
                self.meter.terminal(req, "failed", self.telemetry)
            if req.slot is not None:
                self.sched.evict(req)
            elif any(q is req for q in self.sched.queue):
                self.sched.queue = deque(
                    q for q in self.sched.queue if q is not req)
            self._proposers.pop(req.rid, None)
            self._spec_streak.pop(req.rid, None)
            self._spec_live.pop(req.rid, None)
            req.state = RequestState.FAILED
            req.error = f"engine-killed: {detail}"
            if self.journal is not None:
                # A typed failure is REPORTED to the client, so it is a
                # real terminal: journal it and recovery never re-serves
                # the request. Hard crashes (Engine.kill) never run this
                # path — their requests stay non-terminal and the
                # journal replays them.
                self.journal.terminal(req.rid, "failed")
            self._rtrace(req, "failed", error="engine-killed")
            if self._slo_metrics:
                registry().counter("serve_requests_failed").inc()
            if self.telemetry is not None:
                self.telemetry.record(
                    "serve", event="failed", request=req.rid,
                    policy=self.serve.policy,
                    error="engine-killed", detail=detail,
                    prompt_tokens=req.prompt_len,
                    new_tokens=len(req.generated),
                    **({"replica": self.replica}
                       if self.replica is not None else {}))

    # -- SLO bookkeeping ----------------------------------------------------

    def _queue_wait(self, req: Request) -> float | None:
        if req.t_admitted is None:
            return None
        return max(0.0, req.t_admitted - req.arrival_s)

    def _ttft(self, req: Request) -> float | None:
        if req.t_first_token is None:
            return None
        return max(0.0, req.t_first_token - req.arrival_s)

    def _record_queue_wait(self, req: Request) -> None:
        w = self._queue_wait(req)
        if w is not None and self._slo_metrics:
            registry().histogram("serve_queue_wait_s").observe(
                w, exemplar=req.trace_id)

    def _record_ttft(self, req: Request) -> None:
        t = self._ttft(req)
        if t is not None and self._slo_metrics:
            registry().histogram("serve_ttft_s").observe(
                t, exemplar=req.trace_id)

    def _in_deadline(self, req: Request) -> bool:
        """Did this completed request land within its total deadline?
        (Always True with no deadline configured — goodput then equals
        throughput.)"""
        dl = (req.deadline_s if req.deadline_s is not None
              else self.serve.deadline_s)
        if dl is None or req.t_done is None:
            return True
        return req.t_done - req.arrival_s <= dl

    # -- results ------------------------------------------------------------

    def results(self) -> list[Request]:
        return list(self._requests)

    def summary(self, *, record: bool = True) -> dict:
        """Aggregate SLO + throughput view (and the ``serve`` summary
        record when a telemetry stream is attached and ``record``)."""
        completed = [r for r in self._requests
                     if r.state is RequestState.COMPLETED]
        # Shed requests (typed: deadlines, queue-full) are accounted
        # apart from real failures — shedding is the overload plane
        # WORKING, a failure is something breaking.
        shed = [r for r in self._requests
                if r.state is RequestState.FAILED and r.shed_reason]
        failed = [r for r in self._requests
                  if r.state is RequestState.FAILED and not r.shed_reason]
        tokens = sum(len(r.generated) for r in completed)
        goodput_tokens = sum(len(r.generated) for r in completed
                             if self._in_deadline(r))
        token_lat = [
            (r.t_done - r.t_first_token) / (len(r.generated) - 1)
            for r in completed
            if len(r.generated) > 1 and r.t_first_token is not None]
        out = {
            "policy": self.serve.policy,
            "n_slots": self.serve.n_slots,
            "requests_completed": len(completed),
            "requests_failed": len(failed),
            # Overload-protection accounting (docs/SERVING.md): typed
            # sheds by reason, bounded-queue rejections, goodput (tokens
            # of requests that completed WITHIN their deadline — equal
            # to tokens_generated when no deadline is configured), and
            # the brownout ladder's travel.
            "requests_shed": len(shed),
            "requests_rejected": self._rejected,
            "shed_by_reason": dict(sorted(self._shed_by_reason.items())),
            "goodput_tokens": goodput_tokens,
            "goodput_tokens_per_s": (goodput_tokens / self._wall_s
                                     if self._wall_s > 0 else None),
            "brownout": (self.brownout.summary()
                         if self.brownout is not None else None),
            "tokens_generated": tokens,
            "wall_s": self._wall_s,
            "tokens_per_s": (tokens / self._wall_s if self._wall_s > 0
                             else None),
            "iterations": self._iterations,
            "decode_steps": self._decode_steps,
            # Slot efficiency: useful tokens per decode ROUND over the
            # batch width — the deterministic (timing-free) continuous-
            # vs-static comparison the tests gate on. Under speculative
            # decoding a round can commit several tokens per slot, so
            # this can legitimately exceed 1.0 — there it reads as the
            # tokens-per-round speedup, not a utilization fraction.
            "slot_utilization": (
                self._decode_tokens
                / (self._decode_steps * self.serve.n_slots)
                if self._decode_steps else None),
            # Prefix-cache reuse + speculative decoding (docs/SERVING.md;
            # tests/test_spec_decode.py reads these).
            "prefix_cache": self.serve.prefix_cache,
            "spec_k": self.serve.spec_k,
            "cache_hit_rate": self.cache_hit_rate,
            "prefill_tokens_saved": self._cached_tokens,
            "shared_pages": self.cache.shared_pages,
            "cached_prefix_pages": (len(self.cache.prefix)
                                    if self.cache.prefix is not None
                                    else 0),
            "prefix_evictions": (self.cache.prefix.evictions
                                 if self.cache.prefix is not None else 0),
            "draft_accept_rate": self.draft_accept_rate,
            "draft_tokens_proposed": self._draft_proposed,
            "draft_tokens_accepted": self._draft_accepted,
            "ttft_s": summarize(
                [t for t in (self._ttft(r) for r in completed)
                 if t is not None]),
            "queue_wait_s": summarize(
                [w for w in (self._queue_wait(r) for r in completed)
                 if w is not None]),
            "token_latency_s": summarize(token_lat),
            "page_occupancy": summarize(self._occupancy),
            # REAL per-iteration wall time (monotonic even under a
            # SimClock) — the denominator of the crashrecovery
            # scenario's journal-overhead gate (< 3% of p50).
            "iteration_s": summarize(self._iter_s),
            # Resource-metering plane (utils/metering.py): duty-cycle
            # ledger, per-tenant cost rollup, metering's own overhead.
            "metering": (self.meter.summary()
                         if self.meter is not None else None),
        }
        if record and self.telemetry is not None:
            self.telemetry.record("serve", event="summary", **out)
            if self.meter is not None and self.replica is None:
                # Standalone engines emit their own utilization record;
                # fleet replicas' are emitted (with cell labels) by
                # ServeFleet.summary so quarantine time is folded first.
                self.meter.record_utilization(self.telemetry)
        return out
