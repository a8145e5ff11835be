"""Pallas flash attention for TPU — forward and backward kernels.

The hand-written-kernel tier of the stack (the reference's analog is the CUDA
kernels it consumes from PyTorch; SURVEY.md §2.2): blockwise online-softmax
causal attention that keeps the [T, T] score matrix out of HBM entirely —
scores live tile-by-tile in VMEM, the MXU does the matmuls, and only O([T, D])
touches HBM. Composes with ring attention (ops/ring_attention.py) which
handles the *cross-chip* blocking; this kernel is the *on-chip* blocking.

All three kernels stream K/V (or Q, for dk/dv) through VMEM one block per
grid step: the key/query sequence is a *grid dimension*, not a whole-sequence
VMEM block, so Mosaic double-buffers the next block's DMA against the current
block's MXU work and VMEM usage is O(block), independent of sequence length.
The online-softmax running state (m, l, acc) is carried across those grid
steps in f32 VMEM scratch — initialized on the first step of each row,
flushed to the output on the last. Causal (and windowed) programs clamp their
streaming index map to the diagonal band, so out-of-band grid steps fetch
nothing new and `pl.when` skips their compute entirely.

Backward is the FlashAttention-2 scheme: the forward also emits the per-row
logsumexp, and two kernels recompute score tiles from (q, k, lse) to produce
dq (grid over query blocks) and dk/dv (grid over key blocks) — so the
backward, like the forward, never materializes [T, T] in HBM. The
``bwd_impl="xla"`` escape hatch keeps the old recompute-with-XLA VJP.

Falls back to interpret mode off-TPU (tests run it on CPU), and pads the head
dim to the 128-lane tile when needed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def band_keep(q_pos, k_pos, window):
    """Causal (and optionally banded) keep-mask — the single definition all
    three kernels share so forward and backward masking cannot diverge."""
    keep = k_pos <= q_pos
    if window is not None:
        keep = jnp.logical_and(keep, k_pos > q_pos - window)
    return keep


def _band_start_k(qi, bq, window, block_k):
    """First K block intersecting any band in q block qi (0 if unwindowed)."""
    if window is None:
        return 0
    return jnp.maximum(0, (qi * bq - window + 1) // block_k)


def _last_k_block(qi, bq, block_k):
    """Last K block at or below the diagonal for q block qi (causal)."""
    return ((qi + 1) * bq - 1) // block_k


def _block_interior(qi, j, bq, bk, window):
    """True when the (q block qi, k block j) tile lies strictly inside the
    causal band — every key <= every query, and (windowed) every key inside
    the window — so ``band_keep`` would be all-true and the kernels may
    take their mask-free step. The complement of ``band_keep`` at block
    granularity: keep the two definitions side by side so they cannot
    drift."""
    interior = (j + 1) * bk - 1 <= qi * bq
    if window is not None:
        interior = jnp.logical_and(
            interior, j * bk > qi * bq + bq - 1 - window)
    return interior


def _when_banded(in_band, interior, step):
    """Dispatch one grid step to ``step(masked: bool)``: mask-free for
    band-interior tiles, masked for diagonal/window-edge tiles, skipped
    outside the band. Shared by all three kernels (the fast path matters
    because the forward is VPU-bound — benchmarks/run_kernel_profile.py)."""
    pl.when(jnp.logical_and(in_band, interior))(lambda: step(False))
    pl.when(jnp.logical_and(in_band, jnp.logical_not(interior)))(
        lambda: step(True))


def _kv_stream_map(causal, bq, bk, window):
    """Index map for K/V blocks streamed over the minor grid dim. Causal
    programs clamp j into the band [start, diag] so the out-of-band steps
    re-map to an already-resident block — Mosaic elides the repeat DMA —
    while `pl.when` in the kernel skips their compute."""
    if not causal:
        return lambda bh, i, j: (bh, j, 0)

    def index(bh, i, j):
        lo = _band_start_k(i, bq, window, bk)
        hi = _last_k_block(i, bq, bk)
        return (bh, jnp.clip(j, lo, hi), 0)

    return index


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                  *, num_k: int, causal: bool, scale: float,
                  window: int | None = None):
    """Grid: (batch*heads, num_q_blocks, num_k_blocks). Blocks: q/o [1, BQ, D];
    k/v [1, BK, D] (streamed over the minor grid dim); lse [1, 8, BQ] (per-row
    logsumexp of the scaled scores, for the backward, broadcast over 8
    sublanes for tile legality). Scratch: m/l [BQ, 128] f32 (sublane-major,
    lanes redundant), acc [BQ, D] f32 — the online-softmax carry across K
    steps. ``window`` (causal only): each
    query attends keys in (q_pos - window, q_pos]."""
    qi = pl.program_id(1)
    j = pl.program_id(2)
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def _step(masked: bool):
        q = q_ref[0] * scale                               # [BQ, D]
        k = k_ref[0]                                       # [BK, D]
        v = v_ref[0]
        # m/l ride sublane-major ([BQ, LW] with identical lanes) so every
        # step's broadcasts against [BQ, BK] tiles stay on the sublane axis
        # — no lane<->sublane relayout in the inner loop.
        m = m_scr[...]                                     # [BQ, LW]
        l = l_scr[...]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # [BQ, BK]
        keep = None
        if masked:
            q_pos = qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            k_pos = j * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            keep = band_keep(q_pos, k_pos, window)
            s = jnp.where(keep, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1)[:, None])     # [BQ, LW]
        p = jnp.exp(s - m_new[:, :1])
        if masked and window is not None:
            # A row whose every key in this block is banded out while m is
            # still at the sentinel would get exp(NEG_INF - NEG_INF) = 1;
            # zero masked entries explicitly. Unreachable without a window
            # (the first processed block always holds each row's diagonal),
            # so the unwindowed hot path pays nothing.
            p = jnp.where(keep, p, 0.0)
        alpha = jnp.exp(m - m_new)                               # [BQ, LW]
        l_new = alpha * l + jnp.sum(p, axis=-1)[:, None]
        acc_scr[...] = alpha[:, :1] * acc_scr[...] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    if causal:
        # Skip K blocks entirely outside the band: above the diagonal, and
        # (windowed) entirely left of the band. Their grid steps still run,
        # but fetch no new block (the index map clamps) and do no compute.
        # Blocks strictly inside the band (every key <= every query, no
        # window edge) take a mask-free step — the iota/compare/select VPU
        # passes run only on diagonal-crossing blocks, which matters
        # because the forward is VPU-bound
        # (benchmarks/run_kernel_profile.py).
        in_band = jnp.logical_and(j >= _band_start_k(qi, bq, window, bk),
                                  j <= _last_k_block(qi, bq, bk))
        _when_banded(in_band, _block_interior(qi, j, bq, bk, window), _step)
    else:
        _step(False)

    @pl.when(j == num_k - 1)
    def _finalize():
        l = l_scr[...]                                     # [BQ, LW]
        l_safe = jnp.where(l == 0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l_safe[:, :1]).astype(o_ref.dtype)
        # lse rides in an (8, lane)-tiled layout: Mosaic requires the last
        # two block dims divisible by (8, 128), so the per-row vector is
        # broadcast over 8 sublanes (read back as row 0). The sublane->lane
        # relayout happens once per q row, not per K step.
        m_col, l_col = m_scr[:, 0], l_safe[:, 0]           # [BQ]
        lse = jnp.where(l_scr[:, 0] == 0, NEG_INF, m_col + jnp.log(l_col))
        lse_ref[0] = jnp.broadcast_to(lse[None, :], (8, bq))


# ---------------------------------------------------------------------------
# backward kernels (FlashAttention-2): recompute p from (q, k, lse)
# ---------------------------------------------------------------------------

def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, acc_scr, lse_scr, delta_scr, *, num_k: int,
                         causal: bool, scale: float,
                         window: int | None = None):
    """Grid: (batch*heads, num_q_blocks, num_k_blocks), K/V streamed over the
    minor dim. dq_i = scale * sum_j ds_ij k_j with ds = p * (dO·v^T - delta);
    delta = rowsum(dO * O). Scratch: the dq accumulator [BQ, D] f32, plus
    sublane-major copies of lse/delta ([BQ, LW]) transposed once per q row
    so the K loop broadcasts without lane<->sublane relayouts."""
    qi = pl.program_id(1)
    j = pl.program_id(2)
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)
        lw = lse_scr.shape[1]
        lse_scr[...] = jnp.broadcast_to(lse_ref[0, 0][:, None], (bq, lw))
        delta_scr[...] = jnp.broadcast_to(delta_ref[0, 0][:, None], (bq, lw))

    def _step(masked: bool):
        q = q_ref[0]                                       # [BQ, D] (input
        do = do_ref[0]                                     # dtype for MXU)
        lse = lse_scr[:, :1]                               # [BQ, 1]
        delta = delta_scr[:, :1]
        k = k_ref[0]
        v = v_ref[0]
        s = scale * jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        p = jnp.exp(s - lse)                               # [BQ, BK] f32
        if masked:
            q_pos = qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            k_pos = j * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            p = jnp.where(band_keep(q_pos, k_pos, window), p, 0.0)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        acc_scr[...] += jnp.dot(ds, k, preferred_element_type=jnp.float32)

    if causal:
        in_band = jnp.logical_and(j >= _band_start_k(qi, bq, window, bk),
                                  j <= _last_k_block(qi, bq, bk))
        _when_banded(in_band, _block_interior(qi, j, bq, bk, window), _step)
    else:
        _step(False)

    @pl.when(j == num_k - 1)
    def _finalize():
        dq_ref[0] = acc_scr[...].astype(dq_ref.dtype)


def _q_bounds_for_k(ki, bk, bq, num_q, causal, window):
    """[start, end) of query blocks attending any key in key block ki."""
    if not causal:
        return 0, num_q
    start_q = (ki * bk) // bq
    if window is None:
        return start_q, num_q
    # Last query that can see any key in this block attends the block's
    # last key ((ki+1)*bk - 1) from window - 1 positions later.
    end_q = jnp.minimum(num_q, ((ki + 1) * bk - 1 + window - 1) // bq + 1)
    return start_q, end_q


def _q_stream_map(causal, bq, bk, num_q, window):
    """Index map for Q/dO (and lse/delta via ``lane_row``) blocks streamed
    over the dk/dv kernel's minor grid dim, clamped to the band like
    ``_kv_stream_map``."""
    if not causal:
        return lambda bh, ki, i: (bh, i, 0)

    def index(bh, ki, i):
        lo, hi = _q_bounds_for_k(ki, bk, bq, num_q, causal, window)
        return (bh, jnp.clip(i, lo, hi - 1), 0)

    return index


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_scr, dv_scr, *, num_q: int,
                          causal: bool, scale: float,
                          window: int | None = None):
    """Grid: (batch*heads, num_k_blocks, num_q_blocks), Q/dO/lse/delta
    streamed over the minor dim. dv_j = sum_i p_ij dO_i; dk_j = scale *
    sum_i ds_ij q_i. Scratch: dk/dv accumulators [BK, D] f32. Causal skips
    query blocks strictly above the diagonal (queries before this key block
    attend none of it); a window also skips query blocks past the band's
    lower edge."""
    ki = pl.program_id(1)
    i = pl.program_id(2)
    bk = k_ref.shape[1]
    bq = q_ref.shape[1]

    @pl.when(i == 0)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    # The whole step works in transposed score space — s^T [BK, BQ], keys on
    # sublanes, queries on lanes — so the per-query lse/delta vectors (which
    # arrive lane-major) broadcast along sublanes for free, and dk/dv land
    # sublane-major [BK, D] straight from the MXU. No lane<->sublane
    # relayout anywhere in the Q loop.
    def _step(masked: bool):
        k = k_ref[0]                                       # [BK, D] (input
        v = v_ref[0]                                       # dtype for MXU)
        q = q_ref[0]                                       # [BQ, D]
        do = do_ref[0]
        lse = lse_ref[0, 0]                                # [BQ] lane-major
        delta = delta_ref[0, 0]
        contract_d = (((1,), (1,)), ((), ()))
        s_t = scale * jax.lax.dot_general(                 # [BK, BQ]
            k, q, contract_d, preferred_element_type=jnp.float32)
        p_t = jnp.exp(s_t - lse[None, :])                  # [BK, BQ] f32
        if masked:
            k_pos = ki * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bk, bq), 0)
            q_pos = i * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bk, bq), 1)
            p_t = jnp.where(band_keep(q_pos, k_pos, window), p_t, 0.0)
        pc_t = p_t.astype(do.dtype)
        dv_scr[...] += jnp.dot(pc_t, do, preferred_element_type=jnp.float32)
        dp_t = jax.lax.dot_general(                        # [BK, BQ]
            v, do, contract_d, preferred_element_type=jnp.float32)
        ds_t = (p_t * (dp_t - delta[None, :]) * scale).astype(q.dtype)
        dk_scr[...] += jnp.dot(ds_t, q, preferred_element_type=jnp.float32)

    if causal:
        lo, hi = _q_bounds_for_k(ki, bk, bq, num_q, causal, window)
        in_band = jnp.logical_and(i >= lo, i < hi)
        _when_banded(in_band, _block_interior(i, ki, bq, bk, window), _step)
    else:
        _step(False)

    @pl.when(i == num_q - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# padding/layout plumbing shared by forward and backward
# ---------------------------------------------------------------------------

def _plan(t, d, causal, block_q, block_k, interpret):
    """Resolve (t_padded, d_padded, block_q, block_k, interpret)."""
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    t_pad = t
    if t % 128:
        if not causal and not interpret:
            raise ValueError(
                f"non-causal flash attention needs seq len divisible by 128 "
                f"on TPU (got {t}); pad inputs or use full_attention")
        if causal:
            t_pad = -(-t // 128) * 128

    def clamp(block: int) -> int:
        if not interpret:
            # On real TPUs the lse/delta tiles put the block on the lane
            # dim, so blocks must be multiples of 128 AND divide t_pad
            # (grid/loop counts floor silently otherwise). t_pad is a
            # multiple of 128 here, so search divisors in 128-lane units.
            m_units = t_pad // 128
            d_units = max(1, min(block // 128, m_units))
            while m_units % d_units:
                d_units -= 1
            return 128 * d_units
        # Interpret mode (tests): largest block <= requested that divides
        # t (halving preserves the power-of-two shape; bottoms out at 1).
        blk = min(block, t_pad)
        while t_pad % blk:
            blk //= 2
        return blk

    d_pad = max(128, d) if not interpret else d
    return t_pad, d_pad, clamp(block_q), clamp(block_k), interpret


def _pad_bhtd(x, t_pad, d_pad):
    """[B, T, H, D] -> [B*H, T_pad, D_pad]."""
    b, t, h, d = x.shape
    if t_pad != t or d_pad != d:
        x = jnp.pad(x, [(0, 0), (0, t_pad - t), (0, 0), (0, d_pad - d)])
    return x.transpose(0, 2, 1, 3).reshape(b * h, t_pad, d_pad)


def _unpad_bthd(x, b, h, t, d):
    """[B*H, T_pad, D_pad] -> [B, T, H, D]."""
    t_pad, d_pad = x.shape[1], x.shape[2]
    x = x.reshape(b, h, t_pad, d_pad).transpose(0, 2, 1, 3)
    return x[:, :t, :, :d]


_SEQ_SEMANTICS = ("parallel", "parallel", "arbitrary")
# Lane width of the sublane-major [BQ, _LANE_W] m/l/lse/delta scratch tiles
# (all 128 lanes carry the same per-row value; column 0 is read back).
_LANE_W = 128


def _flash_impl(q, k, v, causal, block_q, block_k, interpret, window=None):
    """Run the forward kernel; returns (o [B,T,H,D], lse [B*H, T_pad] f32)
    — lse stays in the padded flat layout for the backward (which re-tiles
    it to 8 sublanes alongside delta)."""
    b, t, h, d = q.shape
    t_pad, d_pad, bq, bk, interp = _plan(t, d, causal, block_q, block_k,
                                         interpret)
    scale = d ** -0.5
    num_k = t_pad // bk
    qf, kf, vf = (_pad_bhtd(x, t_pad, d_pad) for x in (q, k, v))
    kernel = functools.partial(_flash_kernel, num_k=num_k, causal=causal,
                               scale=scale, window=window)
    kv_map = _kv_stream_map(causal, bq, bk, window)
    o, lse = pl.pallas_call(
        kernel,
        grid=(b * h, t_pad // bq, num_k),
        in_specs=[
            pl.BlockSpec((1, bq, d_pad), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bk, d_pad), kv_map),
            pl.BlockSpec((1, bk, d_pad), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d_pad), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, 8, bq), lambda bh, i, j: (bh, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, t_pad, d_pad), q.dtype),
            jax.ShapeDtypeStruct((b * h, 8, t_pad), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, _LANE_W), jnp.float32),
            pltpu.VMEM((bq, _LANE_W), jnp.float32),
            pltpu.VMEM((bq, d_pad), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_SEQ_SEMANTICS),
        interpret=interp,
    )(qf, kf, vf)
    # Keep only sublane row 0 as the residual (the 8 rows are identical
    # copies written for tile legality) — 1x, not 8x, memory per layer.
    return _unpad_bthd(o, b, h, t, d), lse[:, 0, :]


def _bwd_prep(q, k, v, o, lse, g, t_pad, d_pad):
    """Shared backward preprocessing: delta = rowsum(dO * O) (tiny
    elementwise pass in plain XLA; padded rows get delta 0 and g 0, so
    they contribute nothing), lse padding for callers holding only the
    real-T lse, and the 8-sublane tiling both vectors need for Mosaic
    block-layout legality."""
    b, t, h, d = q.shape
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = delta.transpose(0, 2, 1).reshape(b * h, t)
    if t_pad != t:
        delta = jnp.pad(delta, [(0, 0), (0, t_pad - t)])
    if lse.shape[1] != t_pad:
        # Padded rows have zero cotangents, so any finite lse keeps their
        # p finite and their contributions zero.
        lse = jnp.pad(lse, [(0, 0), (0, t_pad - lse.shape[1])])
    delta = jnp.broadcast_to(delta[:, None, :], (b * h, 8, t_pad))
    lse = jnp.broadcast_to(lse[:, None, :], (b * h, 8, t_pad))
    qf, kf, vf, gf = (_pad_bhtd(x, t_pad, d_pad) for x in (q, k, v, g))
    return qf, kf, vf, gf, lse, delta


def _bwd_dq_call(qf, kf, vf, gf, lse, delta, *, bq, bk, d_pad, causal, scale,
                 window, interp, out_dtype):
    """The dq kernel as one pallas_call (own block shape)."""
    bh_n, t_pad, _ = qf.shape
    num_q, num_k = t_pad // bq, t_pad // bk
    q_row_spec = pl.BlockSpec((1, bq, d_pad), lambda bh, i, j: (bh, i, 0))
    q_vec_spec = pl.BlockSpec((1, 8, bq), lambda bh, i, j: (bh, 0, i))
    kv_map = _kv_stream_map(causal, bq, bk, window)
    kv_spec = pl.BlockSpec((1, bk, d_pad), kv_map)
    return pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, num_k=num_k, causal=causal,
                          scale=scale, window=window),
        grid=(bh_n, num_q, num_k),
        in_specs=[
            q_row_spec, kv_spec, kv_spec,
            # dO is per-query-row: blocked like q.
            q_row_spec, q_vec_spec, q_vec_spec,
        ],
        out_specs=q_row_spec,
        out_shape=jax.ShapeDtypeStruct((bh_n, t_pad, d_pad), out_dtype),
        scratch_shapes=[pltpu.VMEM((bq, d_pad), jnp.float32),
                        pltpu.VMEM((bq, _LANE_W), jnp.float32),
                        pltpu.VMEM((bq, _LANE_W), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_SEQ_SEMANTICS),
        interpret=interp,
    )(qf, kf, vf, gf, lse, delta)


def _bwd_dkv_call(qf, kf, vf, gf, lse, delta, *, bq, bk, d_pad, causal,
                  scale, window, interp, k_dtype, v_dtype):
    """The dk/dv kernel as one pallas_call (own block shape)."""
    bh_n, t_pad, _ = qf.shape
    num_q, num_k = t_pad // bq, t_pad // bk
    q_map = _q_stream_map(causal, bq, bk, num_q, window)
    q_stream_spec = pl.BlockSpec((1, bq, d_pad), q_map)
    vec_stream_spec = pl.BlockSpec(
        (1, 8, bq), lambda bh, ki, i: (bh, 0, q_map(bh, ki, i)[1]))
    k_blk_spec = pl.BlockSpec((1, bk, d_pad), lambda bh, ki, i: (bh, ki, 0))
    return pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, num_q=num_q, causal=causal,
                          scale=scale, window=window),
        grid=(bh_n, num_k, num_q),
        in_specs=[
            q_stream_spec, k_blk_spec, k_blk_spec,
            q_stream_spec, vec_stream_spec, vec_stream_spec,
        ],
        out_specs=[k_blk_spec, k_blk_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bh_n, t_pad, d_pad), k_dtype),
            jax.ShapeDtypeStruct((bh_n, t_pad, d_pad), v_dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d_pad), jnp.float32),
            pltpu.VMEM((bk, d_pad), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_SEQ_SEMANTICS),
        interpret=interp,
    )(qf, kf, vf, gf, lse, delta)


def _flash_bwd_impl(q, k, v, o, lse, g, causal, block_q, block_k, interpret,
                    window=None, dq_blocks: tuple[int, int] | None = None,
                    dkv_blocks: tuple[int, int] | None = None):
    """Pallas backward: dq/dk/dv with [T, T] never in HBM.

    ``dq_blocks``/``dkv_blocks`` optionally give each backward kernel its
    own (q block, k block) tile shape — the two kernels have opposite
    residency (dq keeps queries resident and streams K/V; dk/dv the
    reverse), so their best tiles differ from the forward's and from each
    other (per-kernel sweep: benchmarks/run_kernel_profile.py; both
    prefer 1024x1024 on v5e where the forward wants 512x1024).
    Unset, both inherit ``block_q``/``block_k``."""
    b, t, h, d = q.shape
    t_pad, d_pad, bq, bk, interp = _plan(t, d, causal, block_q, block_k,
                                         interpret)
    scale = d ** -0.5
    qf, kf, vf, gf, lse_t, delta = _bwd_prep(q, k, v, o, lse, g, t_pad, d_pad)

    def resolve(blocks):
        if blocks is None:
            return bq, bk
        _, _, rq, rk, _ = _plan(t, d, causal, blocks[0], blocks[1],
                                interpret)
        return rq, rk

    bq1, bk1 = resolve(dq_blocks)
    dq = _bwd_dq_call(qf, kf, vf, gf, lse_t, delta, bq=bq1, bk=bk1,
                      d_pad=d_pad, causal=causal, scale=scale, window=window,
                      interp=interp, out_dtype=q.dtype)

    bq2, bk2 = resolve(dkv_blocks)
    dk, dv = _bwd_dkv_call(qf, kf, vf, gf, lse_t, delta, bq=bq2, bk=bk2,
                           d_pad=d_pad, causal=causal, scale=scale,
                           window=window, interp=interp, k_dtype=k.dtype,
                           v_dtype=v.dtype)

    return (_unpad_bthd(dq, b, h, t, d), _unpad_bthd(dk, b, h, t, d),
            _unpad_bthd(dv, b, h, t, d))


# ---------------------------------------------------------------------------
# public differentiable entry
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, causal, block_q, block_k, interpret, bwd_impl, window,
           dq_blocks, dkv_blocks):
    return _flash_impl(q, k, v, causal, block_q, block_k, interpret,
                       window)[0]


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret, bwd_impl, window,
               dq_blocks, dkv_blocks):
    o, lse = _flash_impl(q, k, v, causal, block_q, block_k, interpret, window)
    if bwd_impl == "xla":
        # The XLA-recompute backward reads only (q, k, v); don't hold the
        # output and lse in residual HBM for nothing.
        return o, (q, k, v, None, None)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, block_q, block_k, interpret, bwd_impl, window,
               dq_blocks, dkv_blocks, res, g):
    """Backward dispatch: the pallas FlashAttention-2 kernels by default
    (no [T, T] in HBM), or the XLA recompute formulation (``bwd_impl="xla"``,
    materializes scores — the pre-kernel behavior, kept as an escape hatch).
    Both are parity-pinned in tests/test_pallas_attention.py."""
    q, k, v, o, lse = res
    if bwd_impl == "xla":
        from distributed_model_parallel_tpu.ops.ring_attention import (
            full_attention,
        )

        _, vjp = jax.vjp(
            lambda q, k, v: full_attention(q, k, v, causal=causal), q, k, v)
        return vjp(g)
    return _flash_bwd_impl(q, k, v, o, lse, g, causal, block_q, block_k,
                           interpret, window, dq_blocks=dq_blocks,
                           dkv_blocks=dkv_blocks)


_flash.defvjp(_flash_fwd, _flash_bwd)


# Flash-vs-XLA dispatch table, keyed by device_kind prefix. Values are
# measured, not guessed — benchmarks/dispatch_sweep.json holds the v5e
# sweep rows each entry was derived from (benchmarks/run_sweep.py across
# seq/dtype/head_dim). Unlisted TPU generations inherit the "tpu" row
# (same MXU/VMEM architecture; re-sweep to specialize); non-TPU platforms
# never auto-select flash — pallas interpret mode is orders of magnitude
# slower than XLA's fused attention.
#
# min_seq: crossover sequence length per compute dtype; None = never
#   auto-select for that dtype. bf16 crossover 1024 (streamed-K/V kernel,
#   r3 sweep: 0.17 vs 0.40 ms at hd 64, 0.16 vs 0.41 ms at hd 128; at 512
#   XLA still wins ~2x). float32 crossover 1024 too (r3 f32 sweeps,
#   dispatch_sweep_r3_f32.json / grad_sweep_r3_f32.json: fwd+bwd flash
#   wins 3.3x at 1024 and 4.5x at 4096, XLA wins at 512; XLA f32 cannot
#   run seq 8k at all). Precision footing is equal, not degraded: at
#   jax's DEFAULT matmul precision XLA's f32 attention also runs
#   single-pass MXU dots — measured max-abs error vs a float64 reference
#   on unit-scale inputs is 1.1e-2 (XLA f32) vs 7.6e-3 (flash f32), the
#   same bf16-pass class. Callers raising precision globally (e.g.
#   jax.default_matmul_precision('float32')) get true-f32 dots only from
#   XLA — the kernel does not consult that context — so should_use_flash
#   declines f32 auto-dispatch whenever the precision config is raised
#   (_matmul_precision_raised).
# block_q/block_k: fastest measured tile shape (clamped to seq at call
#   time).
# max_head_dim: the kernel keeps [block, D] tiles resident in VMEM; above
#   this, tiles spill and XLA wins regardless of seq.
_DISPATCH_TABLE: dict[str, dict] = {
    # bwd kernels carry their own measured tiles (dq_/dkv_block_*): both
    # backward kernels prefer 1024x1024 on v5e where the forward's best
    # is 512x1024 (benchmarks/run_kernel_profile.py, seq-8k hd-128 sweep).
    "TPU v5 lite": {"min_seq": {"bfloat16": 1024, "float32": 1024},
                    "block_q": 512, "block_k": 1024, "max_head_dim": 256,
                    "dq_block_q": 1024, "dq_block_k": 1024,
                    "dkv_block_q": 1024, "dkv_block_k": 1024},
    "tpu": {"min_seq": {"bfloat16": 1024, "float32": 1024},
            "block_q": 512, "block_k": 1024, "max_head_dim": 256,
            "dq_block_q": 1024, "dq_block_k": 1024,
            "dkv_block_q": 1024, "dkv_block_k": 1024},
}


def dispatch_entry(device=None) -> dict | None:
    """The dispatch-table row for ``device`` (default ``jax.devices()[0]``);
    None on non-TPU platforms, the generic "tpu" row for unlisted TPUs."""
    from distributed_model_parallel_tpu.utils.profiling import (
        match_device_kind,
    )

    device = device if device is not None else jax.devices()[0]
    if device.platform != "tpu":
        return None
    specific = {k: v for k, v in _DISPATCH_TABLE.items() if k != "tpu"}
    return (match_device_kind(specific, device)
            or _DISPATCH_TABLE["tpu"])


def default_blocks(device=None) -> tuple[int, int]:
    """Per-platform (block_q, block_k) kernel tile defaults (the kernel
    itself clamps them to the actual sequence length)."""
    entry = dispatch_entry(device) or _DISPATCH_TABLE["tpu"]
    return entry["block_q"], entry["block_k"]


def _matmul_precision_raised() -> bool:
    """True when jax_default_matmul_precision is set above DEFAULT (e.g.
    'float32'/'highest'/'high'/'tensorfloat32') — the caller explicitly
    asked for more-than-single-pass MXU dots."""
    prec = jax.config.jax_default_matmul_precision
    return prec is not None and str(prec).lower() not in ("default", "fastest",
                                                          "bfloat16")


def should_use_flash(t: int, *, causal: bool = True, impl: str = "auto",
                     head_dim: int = 64, dtype=None,
                     device=None) -> bool:
    """Single home for the flash-vs-XLA dispatch heuristic (used by
    models/transformer and ops/ring_attention): "flash"/"xla" force an
    implementation; "auto" consults the per-platform dispatch table —
    sequence-length crossover by compute dtype, and a head-dim cap above
    which the kernel's VMEM tiles spill."""
    if impl == "flash":
        return True
    if impl == "xla":
        return False
    if impl != "auto":
        raise ValueError(f"unknown attn impl {impl!r}; known: auto, xla, flash")
    if not causal:
        return False
    entry = dispatch_entry(device)
    if entry is None:
        return False
    if head_dim > entry["max_head_dim"]:
        return False
    dtype_name = jnp.dtype(dtype).name if dtype is not None else "bfloat16"
    # Unlisted dtypes (e.g. float64 under x64) stay on XLA: the kernel
    # computes at bf16-input precision, so only dtypes with an explicit
    # measured entry may auto-select it.
    if dtype_name == "float32" and _matmul_precision_raised():
        # The f32 crossover was measured at jax's DEFAULT matmul precision,
        # where XLA's attention runs the same single-pass MXU dots as the
        # kernel. A caller who raised jax_default_matmul_precision asked
        # for true-f32 dots — which only XLA honors (the kernel does not
        # consult the precision context) — so auto must not route them to
        # the kernel's lower-precision math.
        return False
    min_seq = entry["min_seq"].get(dtype_name)
    if min_seq is None:
        return False
    return t >= min_seq


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int | None = None,
                    block_k: int | None = None,
                    interpret: bool | None = None,
                    bwd_impl: str = "flash",
                    window: int | None = None,
                    dq_blocks: tuple[int, int] | None = None,
                    dkv_blocks: tuple[int, int] | None = None) -> jax.Array:
    """[B, T, H, D] -> [B, T, H, D] causal attention, pallas-blocked.

    ``interpret=None`` auto-selects interpret mode off-TPU. Default block
    sizes (``block_q``/``block_k`` = None) come from the per-platform
    dispatch table (``dispatch_entry``; blocks clamp to the sequence length
    for short inputs).

    K/V stream through VMEM one block per grid step (the sequence is a grid
    dimension, not a resident VMEM block), so per-program VMEM is O(block)
    and the sequence ceiling is set by HBM, not VMEM — seq 32k+ compiles
    and runs on a single v5e in both directions. Past the single-chip HBM
    budget, the long-context route is sequence parallelism over the ``seq``
    mesh axis (ops/ring_attention.py), which shards T before the kernel
    runs.

    ``window=W`` (causal only) restricts each query to the last W keys —
    sliding-window/local attention. Both directions skip blocks entirely
    outside the band (no DMA, no compute), so cost drops from O(T^2)
    toward O(T*W).

    Differentiable via a custom VJP: the FlashAttention-2 backward kernels
    recompute score tiles from the saved logsumexp, so neither direction
    puts [T, T] in HBM; ``bwd_impl="xla"`` selects the old
    recompute-with-XLA backward instead (full/causal only — it has no
    windowed reference formulation).
    """
    if bwd_impl not in ("flash", "xla"):
        raise ValueError(f"unknown bwd_impl {bwd_impl!r}; known: flash, xla")
    explicit_blocks = block_q is not None or block_k is not None
    if block_q is None or block_k is None:
        dq, dk = default_blocks()
        block_q = block_q if block_q is not None else dq
        block_k = block_k if block_k is not None else dk
    if not explicit_blocks:
        # Fully-defaulted callers get the measured per-kernel backward
        # tiles; a caller who tuned block_q/block_k (VMEM pressure, a
        # sweep) keeps control of BOTH directions — the table's backward
        # tiles were measured at head_dim 128 and must not override an
        # explicit choice.
        entry = dispatch_entry() or {}
        if dq_blocks is None and "dq_block_q" in entry:
            dq_blocks = (entry["dq_block_q"], entry["dq_block_k"])
        if dkv_blocks is None and "dkv_block_q" in entry:
            dkv_blocks = (entry["dkv_block_q"], entry["dkv_block_k"])
    if window is not None:
        if not causal:
            raise ValueError("window requires causal attention")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        if bwd_impl == "xla":
            raise ValueError("window is only supported with bwd_impl='flash'")
    return _flash(q, k, v, causal, block_q, block_k, interpret, bwd_impl,
                  window, dq_blocks, dkv_blocks)
