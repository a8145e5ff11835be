"""Paged decode attention — the KV-cache read path of the serving engine.

The dense decode cache (``models/transformer._cached_block``) is one
``[L, B, T_max, Hkv, Dh]`` buffer padded to the longest sequence the batch
will ever reach: a sequence that finished early keeps its whole slab until
the batch drains, and the batch width is frozen at prefill. The serving
engine (``serve/``) replaces it with a vLLM-style **paged** cache: a pool
of fixed-size pages ``[n_pages, page_size, Hkv, Dh]`` per layer plus a
per-sequence page table, so a sequence holds exactly
``ceil(len / page_size)`` pages and returns them the moment it finishes.

This module is the attention read over that pool. Three tiers:

* :func:`attend_rows` — the single softmax/score definition of the XLA
  path, prefill chunks and the speculative verify window (mirrors
  ``_cached_block``'s grouped-head scores + ``band_keep`` masking), so
  paged and dense decoding cannot diverge numerically;
* :func:`paged_attention_xla` — gather the table's pages into a
  contiguous ``[B, T, Hkv, Dh]`` view and run :func:`attend_rows`; works
  on every backend (decode off-TPU, the prefill path, and the
  speculative-decoding verify step — its ``width``-token windows ride
  the same per-row-position support prefill chunks use);
* :func:`paged_attention_kernel` — the Pallas TPU kernel: one grid step
  a row, the pools left whole in HBM, and inside the step a loop over the
  row's **live** blocks of pages (from the band's first page to
  ``pos // page``), so its work follows the context each row has and not
  the context it may reach. The page table rides in scalar-prefetch SMEM
  and names the pages the kernel copies itself, one DMA a page, into a
  double-buffered VMEM block (the next block's copies are in flight
  while this one is computed); the gathered ``[B, T, ...]`` intermediate
  never exists in HBM. Each block updates an online-softmax carry in
  VMEM, so VMEM use is two blocks plus the carry whatever the context,
  and the kernel agrees with :func:`attend_rows` to rounding, not bitwise
  (the tolerance tests/test_paged_attention.py states; exact greedy
  tokens are pinned at engine level).

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

    q: [B, C, H, Dh]; k_pool/v_pool: [P, page, Hkv, Dh] (ONE layer's
    slab); tables: [B, N] physical page ids (rows padded with any
    in-range id — padded pages are masked by ``lengths``); positions:
    [B, C]; lengths: [B]. Materializes the gathered [B, N*page, Hkv, Dh]
    view in HBM — fine off-TPU and for prefill chunks; the decode hot
    loop on TPU wants :func:`paged_attention_kernel`.

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

def _pages_per_block(page: int, hkv: int, dh: int, n: int) -> int:
    """Pages one loop turn of the decode kernel handles, from the shapes
    alone: a block is 512 rows of the pool's ``[P, page * Hkv, Dh]`` view
    while a head is at most 128 wide (256 keys at two KV heads: 128 KB of
    bf16 for K, as much for V), fewer rows for wider heads; at least one
    page, never more than a row's table holds."""
    rows = min(512, max(256, 65536 // dh))
    return max(1, min(n, rows // (page * hkv)))


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

    def block_copies(blk, slot, start: bool):
        for i in range(ppb):
            j = blk * ppb + i

            @pl.when(jnp.logical_and(j >= first, j <= last))
            def _page():
                # A wait needs the semaphore and the copy's size only.
                pid = tables_ref[b, j] if start else 0
                rows = pl.ds(i * page_rows, page_rows)
                for hbm, buf, sem in ((k_hbm, k_buf, sems.at[0, slot]),
                                      (v_hbm, v_buf, sems.at[1, slot])):
                    dma = pltpu.make_async_copy(
                        hbm.at[pid], buf.at[slot, rows], sem)
                    if start:
                        dma.start()
                    else:
                        dma.wait()

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
    token per row); pools [P, page, Hkv, Dh]; tables [B, N]; positions
    [B] (the query token's absolute position; the row attends positions
    [0, pos], band-clamped under ``window``). Returns [B, 1, H, Dh].

    ``interpret=None`` compiles the kernel on a TPU backend and
    interprets it elsewhere (the CPU tests).
    """
    if q.shape[1] != 1:
        raise ValueError(f"the paged decode kernel takes one query token "
                         f"per row, got C={q.shape[1]} (prefill chunks go "
                         f"through paged_attention_xla)")
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


def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    tables: jax.Array, positions: jax.Array,
                    lengths: jax.Array, window: int | None = None,
                    impl: str = "auto") -> jax.Array:
    """Dispatch: the Pallas kernel for single-token decode on TPU, the
    XLA gather path everywhere else. ``impl``: "auto" | "xla" |
    "pallas". The kernel is decode-only (C == 1); multi-token prefill
    chunks take the gather path under EVERY impl — "pallas" forces the
    kernel for the decode steps (interpret mode off-TPU), it does not
    turn prefill into a kernel call. On a TPU "auto" means the compiled
    kernel and nothing else: a lowering error propagates."""
    if impl not in ("auto", "xla", "pallas"):
        raise ValueError(f"unknown paged-attention impl {impl!r}; "
                         f"known: auto, xla, pallas")
    use_kernel = q.shape[1] == 1 and (
        impl == "pallas"
        or (impl == "auto" and jax.devices()[0].platform == "tpu"))
    if use_kernel:
        # Decode semantics: the one query token is the newest written
        # position, so the valid prefix is exactly positions + 1 — the
        # kernel derives lengths itself.
        return paged_attention_kernel(q, k_pool, v_pool, tables,
                                      positions[:, 0], window=window)
    return paged_attention_xla(q, k_pool, v_pool, tables, positions,
                               lengths, window=window)
