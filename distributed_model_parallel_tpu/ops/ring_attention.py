"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference has no attention at all (SURVEY.md §5 — pure-CNN workload), but
long-context is first-class for this framework. Two standard schemes, both
expressed as named-axis collectives so they compose with the ``data``/
``stage``/``model`` axes:

* **Ring attention** (`ring_attention`): Q stays put; (K, V) blocks rotate
  around the ``seq`` axis ring via ``ppermute`` while an online-softmax
  accumulator (running max / denominator / weighted values, à la
  Flash/blockwise attention) folds in one block per hop. Peak memory is one
  (K, V) block per device and comms ride the ICI ring — the long-context
  workhorse.
* **Ulysses** (`ulysses_attention`): ``all_to_all`` re-shards from
  sequence-sharded to head-sharded, runs ordinary full attention on complete
  sequences for a subset of heads, and re-shards back. Cheaper compute
  plumbing when heads ≥ axis size; 2 all-to-alls per call.

Both must be called inside ``shard_map`` with ``axis_name`` bound, with
inputs sharded on the sequence dimension: q, k, v are the *local* shards
``[B, T_local, H, Dh]``.

Ring attention composes with the pallas flash kernels
(ops/pallas_attention.py): ``impl="auto"``/``"flash"`` runs each hop's
(Q_local, K_block) tile through the on-chip blocked kernel — the ring is the
*cross-chip* blocking, the kernel the *on-chip* blocking — merging hop
outputs via their logsumexp. Its backward is a second ring pass driving the
FlashAttention-2 dq/dkv kernels per hop, with dk/dv accumulators riding the
ring alongside their (K, V) blocks, so no [T_local, T_local] score tensor is
ever materialized in HBM in either direction. The ``"xla"`` block math
(which does materialize the per-hop local score tensor) remains for short
shards and non-TPU platforms; both paths accumulate in f32 regardless of
input dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_NEG = -1e30


def _block_attn(q, k, v, *, scale, q_pos, k_pos, causal):
    """Scores + masking for one (Q_local, K_block) pair, f32 accumulation.

    Returns (m, l, o): per-query running max, softmax denominator terms and
    value accumulator contributions for this block (all f32).
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        mask = k_pos[None, :] <= q_pos[:, None]        # [Tq, Tk]
        s = jnp.where(mask[None, None], s, -jnp.inf)
    m = jnp.max(s, axis=-1)                            # [B,H,Tq]
    # Guard fully-masked rows (exp(-inf - -inf)): zero them via finite max.
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe[..., None])                 # [B,H,Tq,Tk]
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    l = jnp.sum(p, axis=-1)                            # [B,H,Tq]
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return m_safe, l, o                                # o [B,Tq,H,Dh] f32


def _ring_xla(q: jax.Array, k: jax.Array, v: jax.Array, axis_name: str,
              causal: bool) -> jax.Array:
    """The XLA block-math ring: materializes each hop's local score tensor
    (fine at short T_local); online-softmax state carried in f32."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    t_local = q.shape[1]
    scale = q.shape[-1] ** -0.5
    q_pos = idx * t_local + jnp.arange(t_local)

    # Online-softmax accumulators — f32 regardless of input dtype (bf16
    # running state would silently degrade vs the single-device kernel,
    # which accumulates f32).
    m_acc = jnp.full(q.shape[:1] + (q.shape[2], t_local), -jnp.inf,
                     jnp.float32)                       # [B,H,Tq]
    l_acc = jnp.zeros_like(m_acc)
    o_acc = jnp.zeros(q.shape, jnp.float32)

    def body(t, carry):
        m_acc, l_acc, o_acc, k_t, v_t = carry
        src = (idx - t) % n                             # origin of this block
        k_pos = src * t_local + jnp.arange(t_local)

        def compute():
            return _block_attn(q, k_t, v_t, scale=scale, q_pos=q_pos,
                               k_pos=k_pos, causal=causal)

        if causal:
            # Blocks entirely above the diagonal (src > idx) are fully
            # masked; skip their score matmuls at runtime. The (0, 0, 0)
            # stand-in is exactly what _block_attn returns for a fully
            # masked block (m_safe=0, l=0, o=0), so the merge below is
            # bit-identical — this halves the average per-hop compute,
            # the ring analog of the flash kernel's diagonal block skip.
            m_b, l_b, o_b = jax.lax.cond(
                src <= idx, compute,
                lambda: (jnp.zeros_like(m_acc), jnp.zeros_like(l_acc),
                         jnp.zeros_like(o_acc)))
        else:
            m_b, l_b, o_b = compute()
        m_new = jnp.maximum(m_acc, m_b)
        # Rescale old and new contributions onto the common max.
        a = jnp.where(jnp.isfinite(m_acc), jnp.exp(m_acc - m_new), 0.0)
        b = jnp.exp(m_b - m_new) * jnp.where(l_b > 0, 1.0, 0.0)
        l_new = a * l_acc + b * l_b
        o_new = (a[..., None].transpose(0, 2, 1, 3) * o_acc
                 + b[..., None].transpose(0, 2, 1, 3) * o_b)
        # Rotate (K, V) one hop around the ring.
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_t = jax.lax.ppermute(k_t, axis_name, perm)
        v_t = jax.lax.ppermute(v_t, axis_name, perm)
        return m_new, l_new, o_new, k_t, v_t

    carry = (m_acc, l_acc, o_acc, k, v)
    for t in range(n):   # static unroll: n is the mesh-axis size
        carry = body(t, carry)
    _, l_acc, o_acc, _, _ = carry
    denom = jnp.where(l_acc > 0, l_acc, 1.0)[..., None].transpose(0, 2, 1, 3)
    return (o_acc / denom).astype(q.dtype)


# ---------------------------------------------------------------------------
# kernel-in-ring: each hop runs the pallas flash kernel, outputs merged by lse
# ---------------------------------------------------------------------------

def _hop_is_full(idx, t):
    """At hop t, device idx holds block src = (idx - t) mod n; under causal
    masking the block contributes iff src <= idx, i.e. no ring wraparound."""
    return idx >= t


def _lse_to_bht(lse, b, h, t):
    """[B*H, T_pad] -> [B, H, T] (dropping causal padding rows)."""
    return lse.reshape(b, h, -1)[:, :, :t]


def _merge_by_lse(o_acc, lse_acc, o_b, lse_b):
    """Merge two normalized attention outputs via their logsumexp (all f32;
    o [B,T,H,D], lse [B,H,T]). A fully-masked side carries lse = -1e30 and
    drops out of the weights."""
    m = jnp.maximum(lse_acc, lse_b)
    w_a = jnp.exp(lse_acc - m)                          # [B,H,T]
    w_b = jnp.exp(lse_b - m)
    tot = w_a + w_b
    wa = (w_a / tot).transpose(0, 2, 1)[..., None]      # [B,T,H,1]
    wb = (w_b / tot).transpose(0, 2, 1)[..., None]
    return wa * o_acc + wb * o_b, m + jnp.log(tot)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _ring_flash(q, k, v, axis_name, causal):
    o, _ = _ring_flash_fwd_impl(q, k, v, axis_name, causal)
    return o


def _ring_flash_fwd_impl(q, k, v, axis_name, causal):
    from distributed_model_parallel_tpu.ops.pallas_attention import (
        _flash_impl,
        default_blocks,
    )

    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, t, h, _ = q.shape
    bq, bk = default_blocks()
    perm = [(i, (i + 1) % n) for i in range(n)]

    o_acc = jnp.zeros(q.shape, jnp.float32)
    lse_acc = jnp.full((b, h, t), _NEG, jnp.float32)
    k_t, v_t = k, v
    for hop in range(n):      # static unroll: n is the mesh-axis size
        def compute(k_t=k_t, v_t=v_t, hop_causal=(causal and hop == 0)):
            o_b, lse_b = _flash_impl(q, k_t, v_t, hop_causal, bq, bk, None)
            return o_b.astype(jnp.float32), _lse_to_bht(lse_b, b, h, t)

        if causal and hop > 0:
            # Blocks from above the diagonal (wrapped around the ring) are
            # fully masked: skip the kernel at runtime, merge a no-op.
            o_b, lse_b = jax.lax.cond(
                _hop_is_full(idx, hop), compute,
                lambda: (jnp.zeros(q.shape, jnp.float32),
                         jnp.full((b, h, t), _NEG, jnp.float32)))
        else:
            o_b, lse_b = compute()
        o_acc, lse_acc = _merge_by_lse(o_acc, lse_acc, o_b, lse_b)
        k_t = jax.lax.ppermute(k_t, axis_name, perm)
        v_t = jax.lax.ppermute(v_t, axis_name, perm)
    return o_acc.astype(q.dtype), lse_acc


def _ring_flash_fwd(q, k, v, axis_name, causal):
    o, lse = _ring_flash_fwd_impl(q, k, v, axis_name, causal)
    return o, (q, k, v, o, lse)


def _ring_flash_bwd(axis_name, causal, res, g):
    """Second ring pass driving the FlashAttention-2 backward kernels: each
    hop computes this device's (dq, dk, dv) tile against the visiting (K, V)
    block from the *global* saved (o, lse) — the hop tiles of the global
    softmax sum exactly to the full gradients — with the dk/dv accumulators
    rotating in lockstep with their blocks (home after n hops)."""
    from distributed_model_parallel_tpu.ops.pallas_attention import (
        _flash_bwd_impl,
        default_blocks,
        dispatch_entry,
    )

    q, k, v, o, lse = res
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, t, h, _ = q.shape
    bq, bk = default_blocks()
    # Per-kernel measured dispatch tiles (ADVICE r4: the non-ring flash
    # path already uses them; without this the sp-ring backward left the
    # ~9% dq/dkv tile win on the table).
    entry = dispatch_entry() or {}
    dq_blocks = ((entry["dq_block_q"], entry["dq_block_k"])
                 if "dq_block_q" in entry else None)
    dkv_blocks = ((entry["dkv_block_q"], entry["dkv_block_k"])
                  if "dkv_block_q" in entry else None)
    perm = [(i, (i + 1) % n) for i in range(n)]
    # _flash_bwd_impl reads lse in its residual [B*H, T_pad] layout.
    lse_flat = lse.reshape(b * h, t)

    dq = jnp.zeros(q.shape, jnp.float32)
    dk_t = jnp.zeros(k.shape, jnp.float32)
    dv_t = jnp.zeros(v.shape, jnp.float32)
    k_t, v_t = k, v
    for hop in range(n):
        def compute(k_t=k_t, v_t=v_t, hop_causal=(causal and hop == 0)):
            dq_b, dk_b, dv_b = _flash_bwd_impl(
                q, k_t, v_t, o, lse_flat, g, hop_causal, bq, bk, None,
                dq_blocks=dq_blocks, dkv_blocks=dkv_blocks)
            return (dq_b.astype(jnp.float32), dk_b.astype(jnp.float32),
                    dv_b.astype(jnp.float32))

        if causal and hop > 0:
            dq_b, dk_b, dv_b = jax.lax.cond(
                _hop_is_full(idx, hop), compute,
                lambda: (jnp.zeros(q.shape, jnp.float32),
                         jnp.zeros(k.shape, jnp.float32),
                         jnp.zeros(v.shape, jnp.float32)))
        else:
            dq_b, dk_b, dv_b = compute()
        dq = dq + dq_b
        dk_t = dk_t + dk_b
        dv_t = dv_t + dv_b
        k_t = jax.lax.ppermute(k_t, axis_name, perm)
        v_t = jax.lax.ppermute(v_t, axis_name, perm)
        dk_t = jax.lax.ppermute(dk_t, axis_name, perm)
        dv_t = jax.lax.ppermute(dv_t, axis_name, perm)
    return (dq.astype(q.dtype), dk_t.astype(k.dtype), dv_t.astype(v.dtype))


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, axis_name: str,
                   *, causal: bool = True, impl: str = "auto") -> jax.Array:
    """Blockwise ring attention over ``axis_name``.

    q/k/v: local shards [B, T_local, H, Dh]; the global sequence is the
    concatenation of shards in axis-index order. Returns the local output
    shard [B, T_local, H, Dh].

    ``impl``: "flash" runs each hop through the pallas flash kernel
    (kernel-in-ring; on-chip blocked in both directions), "xla" uses the
    einsum block math (materializes the [Tq, Tk] hop tile), "auto" picks
    flash when the shared dispatch heuristic favors it for the *local*
    shard length (long-shard TPU runs) and the shard length tiles cleanly.
    """
    if impl not in ("auto", "flash", "xla"):
        raise ValueError(f"unknown ring impl {impl!r}; known: auto, flash, xla")
    use_flash = impl == "flash"
    if impl == "auto":
        from distributed_model_parallel_tpu.ops.pallas_attention import (
            should_use_flash,
        )

        use_flash = (q.shape[1] % 128 == 0
                     and should_use_flash(q.shape[1], causal=causal,
                                          head_dim=q.shape[-1],
                                          dtype=q.dtype))
    if use_flash:
        return _ring_flash(q, k, v, axis_name, causal)
    return _ring_xla(q, k, v, axis_name, causal)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      axis_name: str, *, causal: bool = True,
                      impl: str = "auto") -> jax.Array:
    """All-to-all (DeepSpeed-Ulysses style) sequence parallelism.

    Re-shards [B, T/n, H, Dh] -> [B, T, H/n, Dh], runs full softmax attention
    over the complete sequence for the local head subset, then re-shards back.
    Requires H % axis_size == 0. ``impl`` is the flash-vs-XLA selector
    (``should_use_flash``): "auto" consults the measured dispatch table
    (bf16 and f32 both auto-select at their measured crossover, and a
    raised matmul-precision context auto-declines the kernel); "flash"
    forces the pallas kernel for dtypes/regimes the table excludes.
    """
    n = jax.lax.axis_size(axis_name)
    if q.shape[2] % n:
        raise ValueError(f"heads {q.shape[2]} not divisible by axis size {n}")

    def seq_to_heads(x):   # [B, T/n, H, Dh] -> [B, T, H/n, Dh]
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    def heads_to_seq(x):   # [B, T, H/n, Dh] -> [B, T/n, H, Dh]
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    t = qh.shape[1]
    # The local compute is ordinary attention over the complete sequence, so
    # the pallas flash kernel drops in where it wins (shared heuristic).
    from distributed_model_parallel_tpu.ops.pallas_attention import (
        flash_attention,
        should_use_flash,
    )
    if should_use_flash(t, causal=causal, impl=impl,
                        head_dim=qh.shape[-1], dtype=qh.dtype):
        return heads_to_seq(flash_attention(qh, kh, vh, causal=causal))
    scale = qh.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", qh, kh) * scale
    if causal:
        mask = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, vh)
    return heads_to_seq(o)


def full_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   *, causal: bool = True) -> jax.Array:
    """Reference single-device attention ([B, T, H, Dh]) for parity tests and
    the non-sequence-parallel path."""
    t = q.shape[1]
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        mask = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)
