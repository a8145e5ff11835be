"""Paged attention — the KV-cache read path of the serving engine.

The dense decode cache (``models/transformer._cached_block``) is one
``[L, B, T_max, Hkv, Dh]`` buffer padded to the longest sequence the batch
will ever reach: a sequence that finished early keeps its whole slab until
the batch drains, and the batch width is frozen at prefill. The serving
engine (``serve/``) replaces it with a vLLM-style **paged** cache: a pool
of fixed-size pages ``[n_pages, page_size, Hkv, Dh]`` per layer plus a
per-sequence page table, so a sequence holds exactly
``ceil(len / page_size)`` pages and returns them the moment it finishes.

This module is the attention read over that pool. Four tiers:

* :func:`attend_rows` — the single softmax/score definition (mirrors
  ``_cached_block``'s grouped-head scores + ``band_keep`` masking), so
  paged and dense decoding cannot diverge numerically; what both kernels
  are tested against;
* :func:`paged_attention_xla` — gather the table's pages into a
  contiguous ``[B, T, Hkv, Dh]`` view and run :func:`attend_rows`; works
  on every backend: ``impl="xla"``, and every call off a TPU under
  ``"auto"``. Its scores are float32 over every position of the table
  (``window + C`` keys under a window), written to HBM;
* :func:`paged_attention_kernel` — the Pallas TPU decode kernel (one
  query token a row): one grid step a row, the pools left whole in HBM,
  and inside the step a loop over the row's **live** blocks of pages
  (from the band's first page to ``pos // page``), so its work follows
  the context each row has and not the context it may reach. The page
  table rides in scalar-prefetch SMEM and names the pages the kernel
  copies itself, one DMA a page, into a double-buffered VMEM block (the
  next block's copies are in flight while this one is computed); the
  gathered ``[B, T, ...]`` intermediate never exists in HBM. Each block
  updates an online-softmax carry in VMEM, so VMEM use is two blocks
  plus the carry whatever the context;
* :func:`paged_prefill_attention` — the Pallas TPU prefill kernel (a
  chunk of ``C`` query tokens a row: a prompt chunk, or the speculative
  verify window): the same pools, table, page copies and carry, on a
  grid of (row, KV head, tile of the chunk's queries). A step loops over
  the key blocks its tile can see, from the band's first page to the
  tile's causal limit or the row's length, takes its KV head's rows out
  of each interleaved block once, in VMEM, and multiplies the head's
  ``H / Hkv`` query heads against them only: the float32
  ``[C, max_seq_len]`` scores of the gather path never exist.

Both kernels agree with :func:`attend_rows` to rounding, not bitwise
(the tolerances tests/test_paged_attention.py states; exact greedy
tokens are pinned at engine level). ``impl="pallas"`` means both, decode
rounds and chunks alike, interpreted off the chip.

Masking is sanitizing, not just causal: positions past a row's length are
zeroed in K/V *and* banded out of the scores, so stale page contents
(freed pages are reused without clearing) contribute exact ``0.0`` to
every reduction — a row's values depend only on its own written tokens,
never on who held the page before. That invariant is what makes
continuous batching per-request deterministic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_model_parallel_tpu.ops.pallas_attention import (
    _LANE_W,
    NEG_INF,
    _when_banded,
    band_keep,
)


def attend_rows(q: jax.Array, kr: jax.Array, vr: jax.Array,
                positions: jax.Array, lengths: jax.Array,
                window: int | None = None,
                k_positions: jax.Array | None = None) -> jax.Array:
    """Grouped-head cached attention over per-row contiguous K/V.

    q: [B, C, H, Dh] queries (C contiguous tokens per row); kr/vr:
    [B, T, Hkv, Dh]; positions: [B, C] absolute token positions;
    lengths: [B] valid K prefix per row (everything at k_pos >= length is
    zeroed before any reduction — see module docstring); k_positions:
    [B, T] the keys' absolute positions where kr/vr do not start at
    position 0 (None: 0..T-1). Returns [B, C, H, Dh].

    The score/softmax expression is ``_cached_block``'s exactly (query
    head h attends kv head h // G; same ``band_keep`` predicate), so the
    paged paths stay numerically on the dense path's definition.
    """
    b, c, h, dh = q.shape
    t, hkv = kr.shape[1], kr.shape[2]
    kpos = (jnp.arange(t)[None, :] if k_positions is None
            else k_positions)                                    # [1|B, T]
    valid = kpos < lengths[:, None]                              # [B, T]
    kr = jnp.where(valid[:, :, None, None], kr, 0)
    vr = jnp.where(valid[:, :, None, None], vr, 0)
    qg = q.reshape(b, c, hkv, h // hkv, dh)
    # Scores and softmax accumulate in f32 regardless of the cache dtype
    # (preferred_element_type): bf16-accumulated dots are not bitwise
    # stable across lowerings (XLA gather path vs pallas interpret), and
    # pinning the accumulator is also just better serving numerics.
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kr,
                   preferred_element_type=jnp.float32) * (dh ** -0.5)
    keep = band_keep(positions[:, :, None], kpos[:, None, :],
                     window)                                     # [B, C, T]
    keep = jnp.logical_and(keep, valid[:, None, :])
    s = jnp.where(keep[:, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p,
                   vr.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    return o.reshape(b, c, h, dh).astype(q.dtype)


def paged_attention_xla(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                        tables: jax.Array, positions: jax.Array,
                        lengths: jax.Array,
                        window: int | None = None) -> jax.Array:
    """Pure-XLA paged attention: gather then :func:`attend_rows`.

    q: [B, C, H, Dh]; k_pool/v_pool: [P, page, Hkv, Dh]: one layer's
    slab, or the layers' slabs end to end with ``tables`` moved to the
    layer's (:func:`paged_attention`); tables: [B, N] physical page ids
    (rows padded with any in-range id — padded pages are masked by
    ``lengths``); positions:
    [B, C]; lengths: [B]. Materializes the gathered [B, N*page, Hkv, Dh]
    view and the float32 scores over it in HBM — fine off-TPU; on a TPU
    the decode round wants :func:`paged_attention_kernel` and a chunk
    :func:`paged_prefill_attention`.

    Under a ``window`` narrower than the table, only the pages a row's C
    queries can see are gathered (from the page of key ``pos0 - window +
    1``: at most ``window + C - 1`` keys, whatever the context), so a
    sliding layer's scores are [C, window + C] and not [C, max_seq_len].
    """
    b, n = tables.shape
    page = k_pool.shape[1]
    c = q.shape[1]
    span = n if window is None else min(n, -(-(window + c - 1) // page) + 1)
    if span < n:
        first = _first_page(positions[:, 0], page, window)       # [B]
        idx = first[:, None] + jnp.arange(span)[None, :]         # [B, span]
        tables = jnp.take_along_axis(tables, jnp.minimum(idx, n - 1), axis=1)
        # a page past the table is a copy of the last one under a position
        # no query reaches: masked like any key ahead of its query
        k_positions = (idx[:, :, None] * page
                       + jnp.arange(page)[None, None, :]).reshape(b, -1)
    else:
        k_positions = None
    kr = k_pool[tables].reshape(b, span * page, *k_pool.shape[2:])
    vr = v_pool[tables].reshape(b, span * page, *v_pool.shape[2:])
    return attend_rows(q, kr, vr, positions, lengths, window, k_positions)


# ---------------------------------------------------------------------------
# Pallas kernel (decode: one query token per row)
# ---------------------------------------------------------------------------

# Keys a turn of the decode kernel handles at the least, however many KV
# heads a page holds: at 32 stored heads 512 rows are ONE page of 16 keys,
# and a turn's fixed costs (two copies to issue and wait for, a block
# update) were paid 82 times a row and layer at a context of 1,300
# (PERF.md section 6, PR 32); 64 keys are 2,048 rows, 0.5 MB a buffer. At
# 2 and at 8 KV heads the 512 rows already hold 256 and 64 keys.
_DECODE_MIN_BLOCK_KEYS = 64


def _pages_per_block(page: int, hkv: int, dh: int, n: int) -> int:
    """Pages one loop turn of the decode kernel handles, from the shapes
    alone: a block is 512 rows of the pool's ``[P, page * Hkv, Dh]`` view
    while a head is at most 128 wide (256 keys at two KV heads: 128 KB of
    bf16 for K, as much for V), fewer rows for wider heads, but
    ``_DECODE_MIN_BLOCK_KEYS`` keys at the least; at least one page, never
    more than a row's table holds."""
    rows = min(512, max(256, 65536 // dh))
    return max(1, min(n, max(rows // (page * hkv),
                             _DECODE_MIN_BLOCK_KEYS // page)))


def _page_copies(tables_ref, b, pools, bufs, sems, first, last, blk, slot,
                 *, ppb: int, page_rows: int, start: bool,
                 rolled: bool = False):
    """Start (or wait for) the copies of block ``blk``'s pages into buffer
    ``slot``: one DMA a page and pool, the page's id from row ``b`` of the
    table in SMEM. Pages outside ``[first, last]`` are not copied; the
    buffer holds there whatever an earlier block left. ``rolled``: one
    loop over the block's pages in place of ``ppb`` unrolled copies (the
    prefill kernel's 32-page blocks, three times a kernel, cost 0.6 s of
    tracing a layer kind unrolled: the serving cells' ``setup_s``)."""
    def page(i, carry):
        j = blk * ppb + i

        @pl.when(jnp.logical_and(j >= first, j <= last))
        def _page():
            # A wait needs the semaphore and the copy's size only.
            pid = tables_ref[b, j] if start else 0
            rows = pl.ds(i * page_rows, page_rows)
            for pool, (hbm, buf) in enumerate(zip(pools, bufs)):
                dma = pltpu.make_async_copy(
                    hbm.at[pid], buf.at[slot, rows], sems.at[pool, slot])
                if start:
                    dma.start()
                else:
                    dma.wait()
        return carry

    if rolled:
        jax.lax.fori_loop(0, ppb, page, None)
    else:
        for i in range(ppb):
            page(i, None)


def _paged_decode_kernel(tables_ref, pos_ref, q_ref, k_hbm, v_hbm, o_ref,
                         k_buf, v_buf, sems, m_scr, l_scr, acc_scr, *,
                         page: int, ppb: int, hkv: int, dh: int,
                         window: int | None):
    """Grid: (B,), one step a row. Scalar prefetch: tables [B, N], pos
    [B]. q/o blocks [1, H, Dh]. The K/V pools stay whole in HBM, seen as
    [P, page * Hkv, Dh]: row ``r`` of a page is key ``r // Hkv`` of KV
    head ``r % Hkv`` (the pool's own memory order, so a page is one
    contiguous copy that serves every KV head). Scratch: k_buf/v_buf
    [2, ppb * page * Hkv, Dh] (two blocks of ``ppb`` pages), DMA
    semaphores [2 (K, V), 2 (buffer)], and the online-softmax carry m/l
    [H, 128] f32 (sublane-major, lanes redundant) and acc [H, Dh] f32
    (the flash forward's idiom, ops/pallas_attention.py): fast-memory use
    is two blocks plus the carry whatever the context.

    The row's work follows its live context: a loop from the block that
    holds ``_first_page`` to the block that holds ``pos // page`` (one
    block for an idle row at ``pos == 0``). A turn starts the page copies
    of the next block into the other buffer (one DMA a page and pool,
    page ids from the table in SMEM), waits for its own, and makes one
    online-softmax update over the block. Pages of a block outside the
    row's band of pages are not copied; the buffer holds there whatever
    an earlier block left.

    Heads are not taken apart: a block's scores are all H query heads
    against all of its rows, [H, ppb * page * Hkv], two plain 2-D dots a
    block, and a column counts for a query head only if it is a key of
    that head's KV head (``head_ok``) inside the row's band. Reading one
    KV head's rows out of the interleaved block costs four times the
    whole update (PERF.md section 6, PR 27). Every position the band
    excludes — past ``pos``, or left of the window — is zeroed in K/V
    before the dots and banded out of the scores, so neither stale pool
    pages nor stale buffer rows reach a reduction (module docstring); a
    column of another KV head weighs exactly 0.0 against finite values.
    """
    b = pl.program_id(0)
    pos = pos_ref[b]
    h = q_ref.shape[1]
    page_rows = page * hkv
    bk, br = ppb * page, ppb * page_rows
    first = _first_page(pos, page, window)
    last = pos // page

    block_copies = functools.partial(
        _page_copies, tables_ref, b, (k_hbm, v_hbm), (k_buf, v_buf), sems,
        first, last, ppb=ppb, page_rows=page_rows)

    m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
    l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)
    first_blk = first // ppb
    last_blk = last // ppb
    block_copies(first_blk, first_blk % 2, start=True)
    # Row r of a block, column r of its scores: key r // Hkv, KV head
    # r % Hkv. Along lanes for the scores, along sublanes for K/V.
    col = jax.lax.broadcasted_iota(jnp.int32, (1, br), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (br, 1), 0)
    head_ok = (jax.lax.broadcasted_iota(jnp.int32, (h, 1), 0) // (h // hkv)
               == col % hkv)                               # [H, br]

    def block(blk, carry):
        slot = blk % 2

        @pl.when(blk < last_blk)
        def _prefetch():
            block_copies(blk + 1, 1 - slot, start=True)

        block_copies(blk, slot, start=False)
        keep_s = jnp.logical_and(
            head_ok, band_keep(pos, blk * bk + col // hkv, window))
        keep_kv = band_keep(pos, blk * bk + row // hkv, window)
        k = jnp.where(keep_kv, k_buf[slot], 0)             # [br, Dh]
        v = jnp.where(keep_kv, v_buf[slot], 0)
        s = jax.lax.dot_general(
            q_ref[0], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * (dh ** -0.5)
        s = jnp.where(keep_s, s, NEG_INF)                  # [H, br]
        m = m_scr[...]                                     # [H, LW]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1)[:, None])
        p = jnp.where(keep_s, jnp.exp(s - m_new[:, :1]), 0.0)
        alpha = jnp.exp(m - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1)[:, None]
        acc_scr[...] = alpha[:, :1] * acc_scr[...] + jnp.dot(
            p, v.astype(jnp.float32), preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        return carry

    jax.lax.fori_loop(first_blk, last_blk + 1, block, None)
    o_ref[0] = (acc_scr[...] / l_scr[:, :1]).astype(o_ref.dtype)


def _first_page(pos, page: int, window: int | None):
    """First logical page holding a key inside the row's band (0 if
    unwindowed): the page of key ``pos - window + 1``."""
    if window is None:
        return 0
    return jnp.maximum(0, (pos - window + 1) // page)


def paged_attention_kernel(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, tables: jax.Array,
                           positions: jax.Array,
                           window: int | None = None,
                           interpret: bool | None = None) -> jax.Array:
    """Pallas paged decode attention. q: [B, 1, H, Dh] (decode is one
    token per row); pools [P, page, Hkv, Dh] (one slab, or a stack of
    them end to end under a table of the layer's ids:
    :func:`paged_attention`); tables [B, N]; positions
    [B] (the query token's absolute position; the row attends positions
    [0, pos], band-clamped under ``window``). Returns [B, 1, H, Dh].

    ``interpret=None`` compiles the kernel on a TPU backend and
    interprets it elsewhere (the CPU tests).
    """
    if q.shape[1] != 1:
        raise ValueError(f"the paged decode kernel takes one query token "
                         f"per row, got C={q.shape[1]} (prefill chunks go "
                         f"through paged_prefill_attention)")
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    b, _, h, dh = q.shape
    _, page, hkv, _ = k_pool.shape
    ppb = _pages_per_block(page, hkv, dh, tables.shape[1])

    def q_map(bi, tables_ref, pos_ref):
        return (bi, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h, dh), q_map),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, h, dh), q_map),
        scratch_shapes=[
            pltpu.VMEM((2, ppb * page * hkv, dh), k_pool.dtype),
            pltpu.VMEM((2, ppb * page * hkv, dh), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((h, _LANE_W), jnp.float32),
            pltpu.VMEM((h, _LANE_W), jnp.float32),
            pltpu.VMEM((h, dh), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_decode_kernel, page=page, ppb=ppb, hkv=hkv, dh=dh,
        window=window)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="paged_decode_attention",
    )(tables.astype(jnp.int32), positions.astype(jnp.int32), q[:, 0],
      # a view, not a copy: the pool's rows in its own memory order
      k_pool.reshape(-1, page * hkv, dh),
      v_pool.reshape(-1, page * hkv, dh))
    return out[:, None]


# ---------------------------------------------------------------------------
# Pallas kernel (prefill: a chunk of C query tokens per row)
# ---------------------------------------------------------------------------

# Keys one loop turn of the prefill kernel handles, whatever the number
# of KV heads: every turn rescales the step's accumulators and row
# statistics, so 512 keys a turn read 13 % faster than 256 at 4,096 keys
# and 2 KV heads and 25 % at 8 (PERF.md section 6, PR 29).
_PREFILL_BLOCK_KEYS = 512
# ... unless that many keys of all KV heads, in two buffers a pool, pass
# this much fast memory: a page is copied as it lies in the pool, every KV
# head's rows, so at 32 stored heads a turn handles 128 keys (at 2 and at
# 8 heads the 512 stand).
_PREFILL_BLOCK_BYTES = 4 << 20
# Fast memory one grid step may hold in its query and output tiles and
# its carry, of the 16 MiB a kernel gets by default; the rest is for two
# K/V blocks and a block's scores.
_PREFILL_TILE_BYTES = 8 << 20


def _prefill_query_tile(c: int, g: int, dh: int, itemsize: int) -> int:
    """Query tokens one grid step of the prefill kernel handles, from the
    shapes alone: the largest power of two whose ``g`` query heads keep
    the q and o tiles (two buffers each), the float32 accumulator and the
    lane-wide maxima and sums inside ``_PREFILL_TILE_BYTES`` (256 tokens
    at StarCoder2's 12 heads a KV head and at K-EXAONE's 8, Dh 128,
    bfloat16); the whole chunk where that is shorter."""
    row = 4 * dh * itemsize + 4 * dh + 8 * _LANE_W
    rows = max(8, _PREFILL_TILE_BYTES // (row * g))
    return min(c, 1 << (rows.bit_length() - 1))


def _head_rows(buf, slot, kvh, bk: int, hkv: int):
    """KV head ``kvh``'s ``[bk, Dh]`` keys (or values) out of buffer
    ``slot`` of ``buf [2, bk * Hkv, Dh]``, whose row ``r`` is key
    ``r // Hkv`` of KV head ``r % Hkv``. 32-bit rows are one strided
    load. bfloat16 rows lie two to a 32-bit word (rows ``2j`` and
    ``2j + 1``: a key's even head and the next), which no strided load
    takes apart: the words of the head's pair are loaded (strided over
    the keys) and the head's half of each is widened in place, a shift
    or a mask, exactly."""
    if hkv == 1:
        return buf[slot]
    if buf.dtype.itemsize == 4:
        return buf[slot, pl.ds(kvh, bk, stride=hkv), :]
    words = buf.bitcast(jnp.uint32)            # [2, bk * Hkv // 2, Dh]
    if hkv == 2:
        w = words[slot]
    else:
        w = words[slot, pl.ds(kvh // 2, bk, stride=hkv // 2), :]
    half = jnp.where(kvh % 2 == 0, w << 16, w & jnp.uint32(0xFFFF0000))
    return pltpu.bitcast(half, jnp.float32).astype(buf.dtype)


def _paged_prefill_kernel(tables_ref, pos0_ref, len_ref, q_ref, k_hbm, v_hbm,
                          o_ref, k_buf, v_buf, sems, m_scr, l_scr, acc_scr,
                          *, page: int, ppb: int, hkv: int, dh: int, c: int,
                          window: int | None):
    """Grid: (B, Hkv, tiles of the C queries): one step is one tile of a
    row's queries against the keys of one KV head. Scalar prefetch:
    tables [B, N], pos0 [B] (the row's first query position: query ``i``
    is at ``pos0 + i``), lengths [B]. q/o blocks [1, TQ, G * Dh]: the
    ``G = H / Hkv`` query heads of the step's KV head side by side along
    lanes (``[B, C, H * Dh]`` is q's own memory order). The pools stay
    whole in HBM as in the decode kernel, ``[P, page * Hkv, Dh]``, and a
    page is copied as it lies there, all KV heads; the step takes its own
    head's rows out of the block in VMEM (:func:`_head_rows`), once a
    block for ``G x TQ`` query rows, so every product is between a query
    head and keys of its own KV head. Scratch: k_buf/v_buf
    [2, ppb * page * Hkv, Dh], DMA semaphores [2 (K, V), 2 (buffer)], the
    online-softmax carry m/l [G, TQ, 128] and acc [G, TQ, Dh], float32.

    The step's work follows the tile's live context: a loop over the key
    blocks from the block of ``_first_page`` of the tile's first query to
    the block of the last key any of its queries may see,
    ``min(length, last query + 1) - 1``: the causal limit of the tile,
    not of the chunk. Page copies are the decode kernel's
    (:func:`_page_copies`), double-buffered; a tile wholly in a row's
    padded tail, or a row of length 0, makes no turn and writes zeros.

    The mask is :func:`attend_rows`' own: a key counts for a query iff
    ``k_pos < length`` and ``band_keep(q_pos, k_pos, window)``. A block
    every key of which counts for every query of the tile takes a
    mask-free turn (the flash forward's idiom); in any other, the rows of
    K and V that no query of the tile may see (not copied, past the
    length, ahead of the tile) are zeroed before the products and the
    scores banded, so stale pool pages and stale buffer rows reach no
    reduction (module docstring). Scores, maxima, sums and accumulator
    are float32; the softmax weights enter the P V product in the
    cache's dtype (``ops/pallas_attention._flash_kernel``).
    """
    b, kvh, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    tq = q_ref.shape[1]
    g = q_ref.shape[2] // dh
    bk = ppb * page
    length = len_ref[b]
    q_lo = pos0_ref[b] + qi * tq
    q_hi = pos0_ref[b] + jnp.minimum((qi + 1) * tq, c) - 1
    kv_end = jnp.minimum(length, q_hi + 1)     # one past the tile's last key
    first = _first_page(q_lo, page, window)
    last = (kv_end + page - 1) // page - 1
    first_blk, end_blk = first // ppb, (kv_end + bk - 1) // bk
    block_copies = functools.partial(
        _page_copies, tables_ref, b, (k_hbm, v_hbm), (k_buf, v_buf), sems,
        first, last, ppb=ppb, page_rows=page * hkv, rolled=True)

    m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
    l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)
    block_copies(first_blk, first_blk % 2, start=True)
    q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0)
    key = jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    key_row = jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)

    def block(blk, carry):
        slot = blk % 2
        k0 = blk * bk

        @pl.when(blk + 1 < end_blk)
        def _prefetch():
            block_copies(blk + 1, 1 - slot, start=True)

        block_copies(blk, slot, start=False)

        def update(masked: bool):
            k = _head_rows(k_buf, slot, kvh, bk, hkv)      # [bk, Dh]
            v = _head_rows(v_buf, slot, kvh, bk, hkv)
            if masked:
                k_pos = k0 + key_row
                seen = jnp.logical_and(k_pos >= first * page, k_pos < kv_end)
                k = jnp.where(seen, k, 0)
                v = jnp.where(seen, v, 0)
                keep = jnp.logical_and(
                    band_keep(q_pos, k0 + key, window),
                    k0 + key < length)                     # [TQ, bk]
            for i in range(g):
                s = jax.lax.dot_general(
                    q_ref[0, :, i * dh:(i + 1) * dh], k,
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * (dh ** -0.5)
                if masked:
                    s = jnp.where(keep, s, NEG_INF)
                m = m_scr[i]                               # [TQ, LW]
                m_new = jnp.maximum(m, jnp.max(s, axis=-1)[:, None])
                p = jnp.exp(s - m_new[:, :1])
                if masked:
                    # a query with no key in this block while m is still
                    # at the sentinel: exp(NEG_INF - NEG_INF) = 1
                    p = jnp.where(keep, p, 0.0)
                alpha = jnp.exp(m - m_new)
                l_scr[i] = alpha * l_scr[i] + jnp.sum(p, axis=-1)[:, None]
                acc_scr[i] = alpha[:, :1] * acc_scr[i] + jnp.dot(
                    p.astype(v.dtype), v, preferred_element_type=jnp.float32)
                m_scr[i] = m_new

        # every key of the block before every query of the tile, inside
        # the length and (windowed) inside the last query's window
        interior = jnp.logical_and(k0 + bk - 1 <= q_lo, k0 + bk <= kv_end)
        if window is not None:
            interior = jnp.logical_and(interior, k0 > q_hi - window)
        _when_banded(True, interior, update)
        return carry

    jax.lax.fori_loop(first_blk, end_blk, block, None)
    for i in range(g):
        l = l_scr[i]
        o_ref[0, :, i * dh:(i + 1) * dh] = (
            acc_scr[i] / jnp.where(l == 0, 1.0, l)[:, :1]).astype(o_ref.dtype)


def prefill_kernel_takes(c: int, k_pool: jax.Array) -> bool:
    """Whether :func:`paged_prefill_attention` takes a call's shapes:
    more than one query token a row, and pools whose KV heads it can take
    apart in VMEM: 32-bit, one KV head, or bfloat16 with an even number
    of them (:func:`_head_rows`). Anything else stays on the gather
    path."""
    hkv = k_pool.shape[2]
    return c > 1 and (k_pool.dtype.itemsize == 4 or hkv == 1 or (
        k_pool.dtype == jnp.bfloat16 and hkv % 2 == 0))


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_prefill_attention(q: jax.Array, k_pool: jax.Array,
                            v_pool: jax.Array, tables: jax.Array,
                            pos0: jax.Array, lengths: jax.Array,
                            window: int | None = None,
                            interpret: bool | None = None) -> jax.Array:
    """Pallas paged prefill attention. q: [B, C, H, Dh], row ``b``'s
    queries at positions ``pos0[b] + arange(C)`` (a prompt chunk, or the
    speculative verify window); pools [P, page, Hkv, Dh] (one slab, or a
    stack of them end to end under a table of the layer's ids:
    :func:`paged_attention`); tables [B, N];
    lengths [B] valid K prefix per row (``pos0 + n_valid``: queries past
    it are the chunk's padding; what they return is finite and
    meaningless). Returns [B, C, H, Dh], :func:`attend_rows`' result to
    rounding.

    ``interpret=None`` compiles the kernel on a TPU backend and
    interprets it elsewhere (the CPU tests). Jitted on its own so that
    layers of one shape and window share one trace and one lowering of
    the kernel inside a step (four of K-EXAONE's five).
    """
    b, c, h, dh = q.shape
    _, page, hkv, _ = k_pool.shape
    if not prefill_kernel_takes(c, k_pool):
        raise ValueError(
            f"the paged prefill kernel takes C > 1 query tokens a row and "
            f"32-bit pools, one KV head, or bfloat16 pools with an even "
            f"number of KV heads; got C={c}, {k_pool.dtype} x {hkv} KV heads")
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    g = h // hkv
    tq = _prefill_query_tile(c, g, dh, q.dtype.itemsize)
    ppb = max(1, min(tables.shape[1], _PREFILL_BLOCK_KEYS // page,
                     _PREFILL_BLOCK_BYTES
                     // (4 * page * hkv * dh * k_pool.dtype.itemsize)))

    def q_map(bi, kvh, qi, tables_ref, pos0_ref, len_ref):
        return (bi, qi, kvh)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, hkv, pl.cdiv(c, tq)),
        in_specs=[
            pl.BlockSpec((1, tq, g * dh), q_map),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, tq, g * dh), q_map),
        scratch_shapes=[
            pltpu.VMEM((2, ppb * page * hkv, dh), k_pool.dtype),
            pltpu.VMEM((2, ppb * page * hkv, dh), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((g, tq, _LANE_W), jnp.float32),
            pltpu.VMEM((g, tq, _LANE_W), jnp.float32),
            pltpu.VMEM((g, tq, dh), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_prefill_kernel, page=page, ppb=ppb, hkv=hkv, dh=dh, c=c,
        window=window)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, c, h * dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
        name="paged_prefill_attention",
    )(tables.astype(jnp.int32), pos0.astype(jnp.int32),
      lengths.astype(jnp.int32), q.reshape(b, c, h * dh),
      # views, not copies: the pool's rows in its own memory order
      k_pool.reshape(-1, page * hkv, dh),
      v_pool.reshape(-1, page * hkv, dh))
    return out.reshape(b, c, h, dh)


def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    tables: jax.Array, positions: jax.Array,
                    lengths: jax.Array, window: int | None = None,
                    impl: str = "auto", layer=None) -> jax.Array:
    """Dispatch: the Pallas kernels on a TPU, the XLA gather path
    everywhere else. Pools ``[P, page, Hkv, Dh]`` are one slab; pools
    ``[L, P, page, Hkv, Dh]`` are the layers' slabs stacked, read at
    ``layer`` (an index, traced or not) without cutting the slab out:
    the stack is seen as ``[L * P, page, Hkv, Dh]``, its own memory
    order, and the table's page ids move by ``layer * P``. All three
    read paths follow that one rule; a step's pools reach the kernels as
    views of the buffers the step carries (a slab sliced out under a
    traced ``layer`` is a copy of the slab, 67 MB a pool and layer in
    the serving cells: PERF.md section 6, PR 31).

    ``impl``: "auto" | "xla" | "pallas". "auto" takes
    the kernels on a TPU backend and the gather path off it; "pallas"
    forces the kernels anywhere (interpreted off the chip); "xla" is the
    gather path. One query token a row (``C == 1``) is the decode kernel;
    ``C > 1`` (a prompt chunk, the speculative verify window) is the
    prefill kernel, which takes a row's positions as ``positions[:, 0] +
    arange(C)``: what both steps of serve/model.py pass. Pools whose KV
    heads the prefill kernel cannot take apart
    (:func:`prefill_kernel_takes`: 16-bit other than bfloat16, or an odd
    number of KV heads above one) keep their chunks on the gather path,
    decided by the shape. On a TPU "auto" means the compiled kernels and
    nothing else: a lowering error propagates."""
    if impl not in ("auto", "xla", "pallas"):
        raise ValueError(f"unknown paged-attention impl {impl!r}; "
                         f"known: auto, xla, pallas")
    if k_pool.ndim == 5:
        tables = tables + layer * k_pool.shape[1]
        k_pool = k_pool.reshape(-1, *k_pool.shape[2:])
        v_pool = v_pool.reshape(-1, *v_pool.shape[2:])
    c = q.shape[1]
    if impl == "pallas" or (impl == "auto"
                            and jax.devices()[0].platform == "tpu"):
        if c == 1:
            # Decode semantics: the one query token is the newest written
            # position, so the valid prefix is exactly positions + 1 — the
            # kernel derives lengths itself.
            return paged_attention_kernel(q, k_pool, v_pool, tables,
                                          positions[:, 0], window=window)
        if prefill_kernel_takes(c, k_pool):
            return paged_prefill_attention(q, k_pool, v_pool, tables,
                                           positions[:, 0], lengths,
                                           window=window)
    return paged_attention_xla(q, k_pool, v_pool, tables, positions,
                               lengths, window=window)
