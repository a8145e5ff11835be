"""Paged KV cache: a device page pool + host-side page tables.

The dense decode cache pads every sequence to the batch maximum and holds
the slab until the whole batch drains. Here the cache is a pool of
fixed-size pages — ``[L, n_pages, page_size, Hkv, Dh]`` per K and V on
device — and each sequence owns exactly ``ceil(len / page_size)`` pages,
recorded in a host-side page table. Pages return to the free list the
moment a sequence finishes, so memory capacity (and therefore admission)
is decoupled from both batch width and the longest co-resident sequence.

Pages are **refcounted** (copy-on-write prefix sharing,
serve/prefix_cache.py): a page written once for a token prefix can back
every sequence whose prompt starts with those tokens — each holder takes
a reference, and the page returns to the free list only when the last
reference drops. "Copy-on-write" here is page-granular and by
construction: a sharer's own writes always land at positions past the
shared prefix, i.e. in freshly allocated pages, so a shared page is never
written twice and no actual copy ever happens.

Allocation is deterministic (FIFO free list): the same submit/finish
order always produces the same physical placement, which keeps engine
runs — and their telemetry — reproducible. Pages are **not** cleared on
free: the attention read path masks past-length positions to exact 0.0
(ops/paged_attention.attend_rows), so stale contents are unreachable by
construction rather than by memset.

Pages are also the **migration unit** (serve/fleet.py): a live sequence
leaves one replica and resumes on another by copying its written pages'
contents — :meth:`PagedKVCache.export_request` serializes the K/V
contents of a sequence's written prefix to host arrays, and
:meth:`PagedKVCache.import_request` allocates **fresh** pages on the
destination pool and writes those contents back. The payload is pure
values, never page ids, so a migrated sequence carries no references
into the source replica's pool or radix tree — the source can drop
everything (and be quarantined) the moment the export returns.
"""

from __future__ import annotations

import dataclasses
import math
from collections import deque

import jax.numpy as jnp
import numpy as np

from distributed_model_parallel_tpu.utils import tracing


class PagePoolError(RuntimeError):
    """A page-accounting invariant was violated (double alloc/free) or an
    allocation exceeded capacity that admission should have checked."""


class CacheKindError(NotImplementedError):
    """Asked of a cache what only whole contexts in pages can give.
    Sliding (ring) layers: prefix sharing (a ring holds no page a second
    sequence could read) or migration of pages by value; the engine never
    asks: under ``prefix_cache`` it lays every layer out in whole pages,
    and it drains a ring cache by replaying tokens. State layers (a
    recurrent state a slot, no pages at all): the same two, and
    speculation; there is no other layout to fall back to, so the engine
    refuses ``prefix_cache`` and ``spec_k`` for such a model, each with
    what it would take (a state snapshot where a shared prefix ends; a
    rollback of the state past rejected drafts; the state by value), and
    drains by replay."""


@dataclasses.dataclass(frozen=True)
class CacheLayout:
    """Which pool each layer's K/V lives in, from the model configuration.

    A **full** layer keeps a sequence's whole context: pages from the
    shared pool, ``ceil(len / page)`` of them, reserved at admission. A
    **sliding** layer (a window much shorter than ``max_seq_len``) can
    never read past ``window + span - 1`` keys back from the newest, so
    it keeps a **ring** of ``ring_pages`` pages a sequence: logical page
    ``j`` lives in ring page ``j % ring_pages``, and a page is
    overwritten once every query that could see it is behind. ``span``
    is the most tokens one program writes before it reads (the prefill
    chunk, or the verify window).

    A **state** layer (``LayerKind.mixer == "gated_delta"``) has no K/V
    at all: it keeps one recurrent state ``[dk, Hv * dv]`` float32 and
    the short convolution's last ``K - 1`` inputs for each of the
    engine's slots, whatever the context: pools indexed by **slot**,
    allocated with the cache, counted by admission as "a slot" and never
    by the page pool. Nothing clears them on release: a sequence's first
    chunk starts from zeros inside the step (serve/model.py).

    Rings are for a model whose layers differ (sliding layers beside
    full ones). A stack of equal layers keeps whole pages whatever its
    window, as it always has, and so does every model under
    ``whole_pages`` (the engine passes ``serve.prefix_cache``: a shared
    prefix needs every layer's pages whole): such a cache shares
    prefixes and migrates by page like any other.

    **Cache layers are not weight layers** where the stack is looped
    (``cfg.n_passes`` passes over the same leaves): pass ``t`` of layer
    ``l`` keeps its own K/V (the keys of pass 2 are projections of pass
    1's output, not of the embedding), so ``n_full``, ``n_ring`` and
    ``n_state`` count ``passes`` times the model's layers of the kind,
    pass after pass: a page id spans all of them, and a token costs
    ``passes`` times a single walk's bytes.

    ``bodies``: for each of the ``n_lead + period`` layer bodies of
    ``cfg.layer_plan`` ``(ring, base, stride)``: in pass ``t`` the layer
    of repeat ``rep`` is cache layer ``t * (layers of its kind a pass) +
    base + rep * stride`` (:meth:`cache_layer`) of the ring pools
    (``ring`` True), of the full pools (False), or of the state pools
    (None: the layer holds no K/V).
    """

    ring_pages: int          # 0: no layer keeps a ring
    n_full: int
    n_ring: int
    bodies: tuple
    n_state: int = 0
    passes: int = 1

    @classmethod
    def of(cls, cfg, *, page_size: int, max_seq_len: int, span: int,
           whole_pages: bool = False) -> "CacheLayout":
        n_lead, period, n_periods = cfg.layer_plan
        kinds = cfg.kinds
        pages_per_seq = -(-max_seq_len // page_size)
        whole_pages = whole_pages or cfg.homogeneous

        def ring_of(kind):
            if (whole_pages or kind.window is None
                    or kind.mixer == "gated_delta"):
                return 0
            r = ring_pages_for(kind.window, page_size, span)
            return r if r < pages_per_seq else 0

        rings = [ring_of(k) for k in kinds]
        ring = [None if k.mixer == "gated_delta" else bool(r)
                for k, r in zip(kinds, rings)]
        in_period = ring[n_lead:n_lead + period]
        # body j is layer j of the first repeat: as many layers of its
        # kind lie before it; a repeat later, as many more as a period has
        bodies = tuple(
            (ring[j], ring[:j].count(ring[j]),
             0 if j < n_lead else in_period.count(ring[j]))
            for j in range(n_lead + period))
        t = cfg.n_passes
        return cls(ring_pages=max(rings, default=0),
                   n_full=t * ring.count(False), n_ring=t * ring.count(True),
                   bodies=bodies, n_state=t * ring.count(None), passes=t)

    @classmethod
    def all_full(cls, cfg) -> "CacheLayout":
        """Every layer keeps whole contexts: one stack of equal layers,
        a cache layer for each pass of each."""
        return cls(0, cfg.n_passes * cfg.n_layers, 0, ((False, 0, 1),),
                   passes=cfg.n_passes)

    @property
    def cache_layers(self) -> int:
        """Cache layers of every kind: the model's layers times its
        passes."""
        return self.n_full + self.n_ring + self.n_state

    def cache_layer(self, body: int, rep, t=0) -> tuple:
        """``(ring, layer)``: which pools hold the K/V (or the state) of
        layer body ``body``, repeat ``rep`` (traced or not), in pass
        ``t`` (traced or not), and at which index."""
        ring, base, stride = self.bodies[body]
        of_kind = {False: self.n_full, True: self.n_ring,
                   None: self.n_state}[ring]
        return ring, t * (of_kind // self.passes) + base + rep * stride


def stored_kv_heads(kv_heads: int) -> int:
    """KV heads a pool stores for a model of ``kv_heads``: above 16, the
    next multiple of 16. The paged kernels read a page as ``[page * Hkv,
    Dh]`` rows, a view of the pool only where the chip lays ``[Hkv, Dh]``
    out without padding; bfloat16 tiles hold 16 rows, so 30 heads lie
    there as 32, and the view became a copy of the whole pool, 2 GB a
    pool and step (read in the step compiled for a described v5e). The
    extra heads hold zeros; ``serve/model.paged_block`` pads what it
    writes and asks, and drops what they return."""
    return kv_heads if kv_heads <= 16 else -(-kv_heads // 16) * 16


def ring_pages_for(window: int, page_size: int, span: int) -> int:
    """Pages that hold every key the ``span`` newest queries can see
    under ``window``: ``window + span - 1`` keys, wherever they start."""
    return -(-(window + span - 1) // page_size) + 1


class PagePool:
    """Host-side refcounting allocator over ``n_pages`` physical ids.

    FIFO free list: deterministic placement for a deterministic op
    sequence. ``alloc`` hands out pages at refcount 1; ``retain`` adds a
    reference (prefix sharing); ``free`` drops one reference per page and
    returns the page to the free list only at refcount 0. ``alloc``
    raises :class:`PagePoolError` rather than over-committing — the
    scheduler checks ``free_pages`` before admitting, so a raise here is
    a scheduler bug, not backpressure.
    """

    def __init__(self, n_pages: int):
        if n_pages < 1:
            raise ValueError(f"pool needs >= 1 page, got {n_pages}")
        self.n_pages = n_pages
        self._free: deque[int] = deque(range(n_pages))
        self._refs: dict[int, int] = {}
        # Low-water mark of the free list over the pool's lifetime — the
        # memory-pressure gauge rtrace decode records carry (how close
        # did this pool ever come to stalling admission).
        self.free_watermark = n_pages

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return len(self._refs)

    @property
    def shared_pages(self) -> int:
        """Pages held by more than one reference (prefix sharing live)."""
        return sum(1 for c in self._refs.values() if c > 1)

    def refcount(self, page: int) -> int:
        return self._refs.get(page, 0)

    def alloc(self, n: int) -> list[int]:
        if n < 0:
            raise ValueError(f"alloc count must be >= 0, got {n}")
        if n > len(self._free):
            raise PagePoolError(
                f"allocation of {n} pages exceeds the {len(self._free)} "
                f"free (of {self.n_pages}); admission must queue, not "
                f"over-commit")
        pages = [self._free.popleft() for _ in range(n)]
        for p in pages:
            self._refs[p] = 1
        if len(self._free) < self.free_watermark:
            self.free_watermark = len(self._free)
        return pages

    def retain(self, pages: list[int]) -> None:
        """Add one reference to each allocated page (a sharer joining)."""
        for p in pages:
            if p not in self._refs:
                raise PagePoolError(
                    f"retaining page {p} that is not allocated")
        for p in pages:
            self._refs[p] += 1

    def free(self, pages: list[int]) -> None:
        """Drop one reference per page; a page returns to the free list
        only when its last holder lets go (refcount 0)."""
        for p in pages:
            if p not in self._refs:
                raise PagePoolError(
                    f"freeing page {p} that is not allocated (double "
                    f"free, or a page the pool never handed out)")
            self._refs[p] -= 1
            if self._refs[p] == 0:
                del self._refs[p]
                self._free.append(p)


class PagedKVCache:
    """Device page pools + per-sequence page tables for one model.

    ``ck``/``cv``: [L, n_pages, page_size, Hkv, Dh] device arrays the
    engine threads through its jitted steps (donated, so XLA updates
    them in place; ``L`` counts cache layers: a looped stack's passes
    times its layers, ``CacheLayout``), beside the sliding layers' rings ``wk``/``wv`` and
    the state layers' ``state``/``tail`` where the model has such layers
    (``CacheLayout``; None where it has not). The page table of
    sequence ``sid`` maps logical page
    ``i`` (tokens [i*page, (i+1)*page)) to a physical pool page;
    :meth:`table_array` pads it to the static per-sequence maximum with
    id 0 — padded entries are masked by length in the attention read, so
    any in-range id is safe.

    ``prefix_cache=True`` keeps a radix tree over token prefixes
    (serve/prefix_cache.py): finished prefixes stay resident (refcounted
    by the tree), a new sequence whose prompt matches admits holding the
    cached pages, and the tree is evicted LRU-leaf-first when admission
    needs the capacity back. ``share_granularity`` (tokens; a multiple of
    ``page_size``) quantizes how much prefix a sharer may reuse — the
    engine passes ``lcm(page_size, prefill_chunk)`` so a cache-hit
    request's remaining prefill chunks are bit-identical program
    invocations to the cold run's (the determinism argument in
    docs/SERVING.md).
    """

    def __init__(self, cfg, *, n_pages: int, page_size: int,
                 max_seq_len: int, prefix_cache: bool = False,
                 share_granularity: int | None = None,
                 layout: CacheLayout | None = None, n_seqs: int = 0):
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if max_seq_len < 1:
            raise ValueError(f"max_seq_len must be >= 1, got {max_seq_len}")
        self.cfg = cfg
        self.page_size = page_size
        self.max_seq_len = max_seq_len
        self.pages_per_seq = -(-max_seq_len // page_size)
        self.pool = PagePool(n_pages)
        self._tables: dict[object, list[int]] = {}
        if share_granularity is None:
            share_granularity = page_size
        if share_granularity % page_size != 0:
            raise ValueError(
                f"share_granularity {share_granularity} must be a "
                f"multiple of page_size {page_size}")
        self.share_granularity = share_granularity
        if prefix_cache:
            from distributed_model_parallel_tpu.serve.prefix_cache import (
                PrefixCache,
            )

            self.prefix = PrefixCache(self.pool, page_size)
        else:
            self.prefix = None
        # Caches by layer kind (CacheLayout): ck/cv hold the full layers,
        # wk/wv the sliding layers' rings, ``ring_pages`` pages for each
        # of the ``n_seqs`` sequences that can be resident at once (the
        # engine's slots), handed out at admission and returned with the
        # sequence. No layout: every layer is full.
        if layout is None:
            layout = CacheLayout.all_full(cfg)
        self.layout = layout
        if layout.ring_pages and prefix_cache:
            raise CacheKindError(
                "prefix sharing needs every layer's whole context in "
                "shareable pages; this layout keeps a ring a sequence for "
                "the sliding layers (CacheLayout.of(..., whole_pages=True) "
                "keeps none; docs/SERVING.md)")
        shape = (layout.n_full, n_pages, page_size,
                 stored_kv_heads(cfg.kv_heads), cfg.head_dim)
        self.ck = jnp.zeros(shape, cfg.dtype)
        self.cv = jnp.zeros_like(self.ck)
        self.wk = self.wv = self.ring_pool = None
        self._rings: dict[object, list[int]] = {}
        if layout.ring_pages:
            if n_seqs < 1:
                raise ValueError("a cache with sliding layers needs n_seqs, "
                                 "the most sequences resident at once")
            self.ring_pool = PagePool(n_seqs * layout.ring_pages)
            self.wk = jnp.zeros((layout.n_ring, self.ring_pool.n_pages)
                                + shape[2:], cfg.dtype)
            self.wv = jnp.zeros_like(self.wk)
        # State layers: for each of the ``n_seqs`` slots one recurrent
        # state [dk, Hv * dv] float32 (ops/gated_delta.pool_state: the
        # heads side by side, whole lane tiles) and the convolution's last
        # K - 1 inputs.
        self.state = self.tail = None
        if layout.n_state:
            if n_seqs < 1:
                raise ValueError("a cache with state layers needs n_seqs, "
                                 "the engine's slots")
            if prefix_cache:
                raise CacheKindError(
                    "prefix sharing needs every layer's whole context in "
                    "shareable pages; a state layer keeps one recurrent "
                    "state a slot and no page. Sharing would take a "
                    "snapshot of the state where the shared prefix ends "
                    "(docs/SERVING.md)")
            self.state = jnp.zeros(
                (layout.n_state, n_seqs, cfg.lin_key_dim,
                 cfg.lin_value_heads * cfg.lin_value_dim), jnp.float32)
            self.tail = jnp.zeros(
                (layout.n_state, n_seqs, cfg.lin_conv - 1,
                 cfg.lin_channels), cfg.dtype)

    def pages_needed(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page_size)

    @property
    def pools(self) -> tuple:
        """The device state the jitted steps thread (and donate)."""
        return (self.ck, self.cv, self.wk, self.wv, self.state, self.tail)

    @pools.setter
    def pools(self, pools) -> None:
        self.ck, self.cv, self.wk, self.wv, self.state, self.tail = pools

    @property
    def kv_bytes_per_token(self) -> int:
        """Bytes one token of context holds in the shared pool: K and V
        of every full cache layer (every pass of a looped stack)."""
        return (self.ck.nbytes + self.cv.nbytes) // (
            self.pool.n_pages * self.page_size)

    @property
    def state_bytes_per_slot(self) -> int:
        """Bytes one slot holds in the state layers' pools (0 without)."""
        if self.state is None:
            return 0
        return (self.state.nbytes + self.tail.nbytes) // self.state.shape[1]

    def open(self, sid) -> None:
        """Start ``sid``'s table; a sequence of a model with sliding
        layers takes its ring here (admission checked the room)."""
        if sid in self._tables:
            raise PagePoolError(f"sequence {sid!r} is already open")
        self._tables[sid] = []
        if self.ring_pool is not None:
            self._rings[sid] = self.ring_pool.alloc(self.layout.ring_pages)

    def ring_room(self) -> bool:
        """Whether one more sequence's ring fits (always, without
        sliding layers)."""
        return (self.ring_pool is None
                or self.ring_pool.free_pages >= self.layout.ring_pages)

    def ring_array(self, sid) -> np.ndarray:
        """[ring_pages] int32: ring page ``j % ring_pages`` holds
        ``sid``'s logical page ``j`` of every sliding layer."""
        return np.asarray(self._rings[sid], np.int32)

    def ensure(self, sid, n_tokens: int) -> None:
        """Grow ``sid``'s table to cover ``n_tokens`` positions. The
        scheduler reserves capacity at admission, so a raise here means
        an accounting bug, not load."""
        if n_tokens > self.max_seq_len:
            raise PagePoolError(
                f"sequence {sid!r} wants {n_tokens} tokens > max_seq_len "
                f"{self.max_seq_len}")
        table = self._tables[sid]
        need = self.pages_needed(n_tokens) - len(table)
        if need > 0:
            table.extend(self.pool.alloc(need))

    def release(self, sid) -> None:
        """Drop ``sid``'s reference on every page of its table
        (eviction/completion). Shared pages survive under the prefix
        tree's (or another sequence's) reference."""
        self.pool.free(self._tables.pop(sid))
        if self.ring_pool is not None:
            self.ring_pool.free(self._rings.pop(sid))

    def table_array(self, sid) -> np.ndarray:
        """[pages_per_seq] int32, padded with 0 (masked by length)."""
        table = self._tables[sid]
        out = np.zeros((self.pages_per_seq,), np.int32)
        out[:len(table)] = table
        return out

    @property
    def occupancy(self) -> float:
        return self.pool.used_pages / self.pool.n_pages

    # -- prefix sharing ------------------------------------------------------

    def _usable_prefix(self, tokens: list[int], matched_pages: int) -> int:
        """Tokens of a raw page-tree match a sharer may actually reuse:
        quantized down to ``share_granularity`` and capped at
        ``len(tokens) - 1`` — the final prompt token is always recomputed
        so the last prefill chunk produces the first-token logits."""
        g = self.share_granularity
        m = min(matched_pages * self.page_size, len(tokens) - 1)
        return max(0, (m // g) * g)

    def _admission(self, tokens: list[int],
                   capacity: int) -> tuple[int, list[int], int, int]:
        """One radix match + one evictable walk:
        ``(cached_tokens, shared_pages, fresh_pages, available_pages)``.
        The request fits iff ``fresh_pages <= available_pages`` —
        available counts the free list plus tree pages evictable without
        touching the would-be-shared path."""
        cached = 0
        shared: list[int] = []
        if self.prefix is not None:
            pages = self.prefix.match(tokens, touch=False)
            cached = self._usable_prefix(tokens, len(pages))
            shared = pages[:cached // self.page_size]
        fresh = self.pages_needed(capacity) - len(shared)
        avail = self.pool.free_pages
        if self.prefix is not None:
            avail += self.prefix.evictable_pages(exclude=set(shared))
        return cached, shared, fresh, avail

    def peek_admission(self, tokens: list[int],
                       capacity: int) -> tuple[int, int, int]:
        """Side-effect-free admission bill:
        ``(cached_tokens, fresh_pages, available_pages)``."""
        cached, _, fresh, avail = self._admission(tokens, capacity)
        return cached, fresh, avail

    def try_admit(self, sid, tokens: list[int],
                  capacity: int) -> int | None:
        """Admission in ONE pass (the scheduler's per-iteration hot
        path): peek the post-sharing bill, and — when it fits — open
        ``sid`` holding the cached prefix, evict tree-only pages if the
        fresh suffix needs the room, and allocate the rest of the
        reservation. Returns the cached token count, or ``None`` when
        the request must keep queuing (no side effects then)."""
        cached, shared, fresh, avail = self._admission(tokens, capacity)
        if fresh > avail or not self.ring_room():
            return None
        self.open(sid)
        if shared:
            # Recency bump + hit accounting: a cheap matched-path walk,
            # not a second full match.
            self.prefix.touch_path(tokens, len(shared))
            self.pool.retain(shared)
            self._tables[sid].extend(shared)
        short = (self.pages_needed(capacity) - len(self._tables[sid])
                 - self.pool.free_pages)
        if short > 0:
            self.prefix.evict(short)
        self.ensure(sid, capacity)
        return cached

    def admit_with_prefix(self, sid, tokens: list[int],
                          capacity: int) -> int:
        """:meth:`try_admit` for callers that already checked the fit —
        insufficient room here raises (an accounting bug, not
        backpressure)."""
        got = self.try_admit(sid, tokens, capacity)
        if got is None:
            cached, fresh, avail = self.peek_admission(tokens, capacity)
            raise PagePoolError(
                f"sequence {sid!r} needs {fresh} fresh pages but only "
                f"{avail} are free or evictable; admission must queue")
        return got

    def insert_prefix(self, sid, tokens: list[int]) -> int:
        """Offer ``sid``'s pages for the **fully written** prefix
        ``tokens`` to the radix tree (no-op without a prefix cache).
        Only full pages are insertable; the tree retains every page it
        adopts, so they outlive the sequence. Returns pages newly
        adopted. Callers must pass only tokens whose KV is verified
        written — under speculative decoding the last committed token's
        slot may hold a rejected draft's KV, so the engine always trims
        the tail (serve/engine.py)."""
        if self.prefix is None:
            return 0
        return self.prefix.insert(tokens, self._tables[sid])

    @property
    def evictable_pages(self) -> int:
        if self.prefix is None:
            return 0
        return self.prefix.evictable_pages()

    def page_share(self, sid) -> float:
        """``sid``'s fractional page-pool reservation: one per exclusive
        page, ``1/refcount`` per shared one — a page three holders share
        costs each of them a third. The resource meter integrates this
        over residency into page-seconds (utils/metering.py); pages held
        only by the prefix tree belong to nobody and cost nobody. 0.0
        for an unknown/evicted sid (the meter may tick between eviction
        and bill close)."""
        table = self._tables.get(sid)
        if not table:
            return 0.0
        refcount = self.pool.refcount
        return sum(1.0 / c for p in table if (c := refcount(p)) > 0)

    @property
    def shared_pages(self) -> int:
        return self.pool.shared_pages

    # -- live request migration (serve/fleet.py) -----------------------------

    def export_request(self, sid, n_tokens: int, *, req=None, sink=None,
                       trace_fields=None):
        """Serialize the K/V **contents** of ``sid``'s first ``n_tokens``
        written positions to host arrays ``(k, v)`` of shape
        ``[L, pages, page_size, Hkv, Dh]`` (``L`` cache layers) — whole
        pages, values only. Shared prefix pages are exported by value like any other, so the
        payload holds no reference to this pool (the destination
        allocates fresh pages; see :meth:`import_request`). The caller
        guarantees every exported position's KV is actually written —
        the engine's drain hook passes the committed-and-written prefix
        (serve/engine.py ``drain``). When the caller passes the traced
        ``req`` (and its stream ``sink``), the hop's source half lands
        on the request timeline as an ``export`` rtrace record."""
        self._whole_contexts_only("export_request")
        table = self._tables[sid]
        n = self.pages_needed(n_tokens)
        if n > len(table):
            raise PagePoolError(
                f"sequence {sid!r}: exporting {n_tokens} tokens spans "
                f"{n} pages but the table holds {len(table)}")
        idx = np.asarray(table[:n], np.int32)
        # One host fetch per pool: [L, n, page, Hkv, Dh], L the cache
        # layers (every pass of a looped stack: a page id spans them all).
        k = np.asarray(self.ck[:, idx]) if n else np.zeros(
            (self.ck.shape[0], 0) + self.ck.shape[2:], self.ck.dtype)
        v = np.asarray(self.cv[:, idx]) if n else np.zeros_like(k)
        if req is not None:
            tracing.rtrace(req, "export", sink=sink, pages=n,
                           n_tokens=n_tokens, **(trace_fields or {}))
        return k, v

    def import_request(self, sid, k, v, capacity: int, *,
                       req=None, sink=None, trace_fields=None) -> bool:
        """Admit a migrated sequence: reserve ``capacity`` positions of
        **fresh** pages (evicting tree-only pages if the room is needed
        — the exported KV is authoritative, so nothing is shared on
        arrival) and write the exported page contents into them. Returns
        ``False`` without side effects when the reservation does not
        fit — the scheduler keeps the request queued, exactly like a
        cold admission that finds no pages. A traced ``req``/``sink``
        records the hop's destination half (an ``import`` rtrace) on
        success only — a bounced import is queue time, not a hop."""
        self._whole_contexts_only("import_request")
        need = self.pages_needed(capacity)
        avail = self.pool.free_pages
        if self.prefix is not None:
            avail += self.prefix.evictable_pages()
        if need > avail:
            return False
        n = int(k.shape[1])
        if n > need:
            raise PagePoolError(
                f"sequence {sid!r}: payload carries {n} pages but the "
                f"reservation is only {need}")
        self.open(sid)
        short = need - self.pool.free_pages
        if short > 0:
            self.prefix.evict(short)
        self.ensure(sid, capacity)
        if n:
            idx = jnp.asarray(self._tables[sid][:n], jnp.int32)
            self.ck = self.ck.at[:, idx].set(
                jnp.asarray(k).astype(self.ck.dtype))
            self.cv = self.cv.at[:, idx].set(
                jnp.asarray(v).astype(self.cv.dtype))
        if req is not None:
            tracing.rtrace(req, "import", sink=sink, pages=n,
                           **(trace_fields or {}))
        return True

    def _whole_contexts_only(self, what: str) -> None:
        if self.ring_pool is not None:
            raise CacheKindError(
                f"{what}: a sliding layer's ring is not a run of whole "
                f"pages that could be copied by value; migrate such a "
                f"sequence by replaying its tokens (serve/journal.py)")
        if self.state is not None:
            raise CacheKindError(
                f"{what}: a state layer's memory is its slot's recurrent "
                f"state, not pages; moving it would take the state and the "
                f"convolution's tail by value. Migrate such a sequence by "
                f"replaying its tokens (serve/journal.py)")

    def cached_prefix_tokens(self, tokens: list[int]) -> int:
        """Usable cached-prefix length for ``tokens`` (quantized to the
        share granularity, side-effect free) — the router's
        prefix-affinity signal (serve/router.py). 0 without a cache."""
        if self.prefix is None:
            return 0
        pages = self.prefix.match(tokens, touch=False)
        return self._usable_prefix(tokens, len(pages))

    def drop_prefix(self) -> int:
        """Evict the ENTIRE radix tree (a replica being quarantined must
        return every page it holds). Pages still referenced by a
        resident sequence survive its tree reference dropping — callers
        drain sequences first. Returns pages freed."""
        if self.prefix is None:
            return 0
        return len(self.prefix.evict(len(self.prefix)))


def memory_gauges(cache: PagedKVCache) -> dict:
    """The memory-pressure snapshot rtrace ``decode`` records carry
    (docs/TRACING.md "Request tracing"): pool occupancy, free/used page
    counts, pages resident under the prefix radix tree, and the pool's
    lifetime free-list low-water mark — enough to tell a latency stall
    caused by page pressure from one caused by compute."""
    return {
        "occupancy": cache.occupancy,
        "free_pages": cache.pool.free_pages,
        "used_pages": cache.pool.used_pages,
        "prefix_pages": len(cache.prefix) if cache.prefix is not None else 0,
        "free_watermark": cache.pool.free_watermark,
        # cache layers (a looped stack: passes x layers; the weights have
        # fewer) and what one token of context costs across them
        "cache_layers": cache.layout.cache_layers,
        "kv_bytes_per_token": cache.kv_bytes_per_token,
        # pages held by layer kind: a full cache layer holds every page of
        # the shared pool that is in use, a sliding layer the rings
        "full_layer_pages": cache.pool.used_pages * cache.layout.n_full,
        "sliding_layer_pages": (cache.ring_pool.used_pages
                                * cache.layout.n_ring
                                if cache.ring_pool is not None else 0),
        # a state layer holds no page: every resident sequence, one slot's
        # recurrent states and convolution tails, whatever its context
        "state_slots": len(cache._tables) if cache.state is not None else 0,
        "state_bytes": (len(cache._tables) * cache.state_bytes_per_slot
                        if cache.state is not None else 0),
    }


def share_granularity_for(page_size: int, prefill_chunk: int) -> int:
    """The engine's prefix-share quantum: a shared prefix must end on a
    page boundary (whole pages are the sharing unit) AND on a prefill
    chunk boundary (so the cold and cached runs dispatch bit-identical
    suffix chunks — same compiled program, same ``pos0`` stream)."""
    return math.lcm(page_size, prefill_chunk)
