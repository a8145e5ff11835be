"""The gated delta rule: linear attention whose memory is a recurrent state.

A layer keeps, for each sequence and head, one matrix ``S`` ``[dk, dv]``
whatever the context (Gated DeltaNet, arXiv:2412.06464). A token with key
``k``, value ``v``, decay ``alpha`` in (0, 1) and write strength ``beta``
moves it by

    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T

and reads ``o_t = S_t^T q_t``: the state forgets by ``alpha``, and the
write replaces what the state held under ``k_t`` by ``v_t`` (the delta
rule) instead of adding to it. Three forms of the same numbers:

* :func:`gated_delta_step`: the recurrence itself, one token a row (a
  decode round), on states handed in by value; what the others are tested
  against;
* :func:`gated_delta_chunk`: ``T`` tokens a row at once (a prompt chunk,
  a full forward), in sub-chunks of ``SUB`` tokens: inside a sub-chunk
  the writes are solved for together (the WY form of arXiv:2406.06484
  section 3 with the gate of arXiv:2412.06464 section 3.3), between
  sub-chunks the state is carried by a ``lax.scan``;
* :func:`gated_delta_decode`: the decode round on the engine's **state
  pool** ``[L, n_slots, dk, Hv * dv]``: layer ``layer``'s states are read
  once and written once, in place: a Pallas kernel on a TPU (the pool
  aliased in and out, ``layer`` a scalar argument), :func:`gated_delta_step`
  on the layer's slab elsewhere.

:func:`causal_conv` is the short depthwise convolution in front of the
rule, with the ``K - 1`` inputs it carries from one call to the next.

The state, the decays (carried as logarithms) and the solve are float32
whatever the model's dtype. A token with ``valid`` false has ``alpha =
1`` and ``beta = 0``: it moves nothing.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
SUB = 64          # tokens of one sub-chunk of the chunked form


def causal_conv(u: jax.Array, w: jax.Array, tail: jax.Array,
                n_valid: jax.Array) -> tuple[jax.Array, jax.Array]:
    """``c_t = silu(sum_i w_i * x_{t - K + 1 + i})``, depthwise.

    u: [B, C, ch] this call's inputs; w: [K, ch]; tail: [B, K - 1, ch]
    the ``K - 1`` inputs before ``u[:, 0]`` (zeros at a sequence's
    start); n_valid: [B] how many of the ``C`` inputs exist (the rest is
    padding, at the end). Returns ``(c [B, C, ch] float32, tail1)``:
    ``tail1`` is the last ``K - 1`` **valid** inputs, ``tail`` itself for
    a row with none.
    """
    k, c = w.shape[0], u.shape[1]
    x = jnp.concatenate([tail.astype(u.dtype), u], axis=1)  # [B, K-1+C, ch]
    wf = w.astype(F32)
    acc = sum(x[:, i:i + c].astype(F32) * wf[i] for i in range(k))
    tail1 = jax.vmap(lambda xb, n: jax.lax.dynamic_slice_in_dim(
        xb, n, k - 1, axis=0))(x, n_valid)                 # [B, K-1, ch]
    return jax.nn.silu(acc), tail1.astype(tail.dtype)


def gated_delta_step(q, k, v, alpha, beta, state):
    """One token a row. q, k: [B, H, dk]; v: [B, H, dv]; alpha, beta:
    [B, H]; state: [B, H, dk, dv] float32. Returns ``(o [B, H, dv]
    float32, state1)``."""
    q, k, v = q.astype(F32), k.astype(F32), v.astype(F32)
    sa = state * alpha.astype(F32)[..., None, None]
    u = (v - jnp.einsum("bhkv,bhk->bhv", sa, k, precision=HI)
         ) * beta.astype(F32)[..., None]
    state1 = sa + k[..., :, None] * u[..., None, :]
    return jnp.einsum("bhkv,bhk->bhv", state1, q, precision=HI), state1


def _solve_unit_lower(a, rhs):
    """``(I + a)^-1 rhs`` for strictly lower ``a`` [..., C, C] and rhs
    [..., C, n], by forward substitution, a row a turn, in float32 on the
    vector unit. (The series ``I - a + a^2 - ...`` ends after ``C`` terms
    but cancels catastrophically where neighbouring keys are alike and
    ``beta`` is near 2: ``(2L)^k`` has entries of 1e27 at ``C`` = 64 for
    an inverse whose entries are 2.)"""
    c = a.shape[-1]

    def row(i, x):
        a_i = jax.lax.dynamic_slice_in_dim(a, i, 1, axis=-2)   # [..., 1, C]
        r_i = jax.lax.dynamic_slice_in_dim(rhs, i, 1, axis=-2)
        # rows of x at and after i are still zero, a_i is zero there too
        x_i = r_i - jnp.sum(jnp.swapaxes(a_i, -1, -2) * x, axis=-2,
                            keepdims=True)
        return jax.lax.dynamic_update_slice_in_dim(x, x_i, i, axis=-2)

    return jax.lax.fori_loop(0, c, row, jnp.zeros_like(rhs))


def gated_delta_chunk(q, k, v, log_alpha, beta, state, valid):
    """``T`` tokens a row. q, k: [B, T, H, dk]; v: [B, T, H, dv];
    log_alpha (<= 0), beta: [B, T, H]; state: [B, H, dk, dv] float32;
    valid: [B, T] bool. Returns ``(o [B, T, H, dv] float32, state1)``;
    ``o`` at a token that is not valid is meaningless.

    With ``gamma_i`` the product of a sub-chunk's decays up to token
    ``i``: ``A = strict_lower(diag(beta) (gamma_i / gamma_j) K K^T)``,
    ``W = (I + A)^-1 diag(beta) (gamma K)``, ``U = (I + A)^-1 diag(beta)
    V``; then with the state ``S`` the sub-chunk starts from, ``D = U - W
    S`` are the values it really writes, ``O = (gamma Q) S + tril((gamma_i
    / gamma_j) Q K^T) D`` and ``S' = gamma_C S + ((gamma_C / gamma) K)^T
    D``. Everything that does not read ``S`` is computed for all
    sub-chunks at once; the scan carries ``S`` alone.
    """
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    n = -(-t // SUB)
    pad = n * SUB - t
    keep = valid[..., None]
    log_alpha = jnp.where(keep, log_alpha.astype(F32), 0.0)
    beta = jnp.where(keep, beta.astype(F32), 0.0)
    # ... whatever it holds: zero times a padding row's NaN is NaN
    q, k, v = (jnp.where(keep[..., None], x, 0) for x in (q, k, v))

    def subs(x):                      # [B, T, H, ...] -> [n, B, H, SUB, ...]
        x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape(b, n, SUB, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 2, 3)

    q, k, v = subs(q), subs(k), subs(v)
    g = jnp.cumsum(subs(log_alpha), axis=-1)               # [n, B, H, SUB]
    beta = subs(beta)
    i, j = jnp.arange(SUB)[:, None], jnp.arange(SUB)[None, :]
    ratio = jnp.exp(jnp.where(i >= j, g[..., :, None] - g[..., None, :],
                              -jnp.inf))                   # gamma_i/gamma_j
    dot = functools.partial(jnp.einsum, preferred_element_type=F32)
    a = jnp.where(i > j, beta[..., None] * ratio
                  * dot("...id,...jd->...ij", k, k), 0.0)
    eg = jnp.exp(g)[..., None]
    rhs = jnp.concatenate([eg * k.astype(F32), v.astype(F32)],
                          axis=-1) * beta[..., None]
    # the inverse by substitution (a turn moves [C, C], not [C, dk + dv]),
    # then one true-float32 product
    inv = _solve_unit_lower(a, jnp.broadcast_to(jnp.eye(SUB, dtype=F32),
                                                a.shape))
    wu = jnp.einsum("...ij,...jn->...in", inv, rhs, precision=HI)
    w, u = wu[..., :dk], wu[..., dk:]
    attn = ratio * dot("...id,...jd->...ij", q, k)         # tril: ratio is
    qg = eg * q.astype(F32)
    g_end = g[..., -1:]
    kd = jnp.exp(g_end - g)[..., None] * k.astype(F32)
    decay = jnp.exp(g_end)[..., None]                      # [n, B, H, 1, 1]

    def sub_chunk(s, xs):
        w, u, attn, qg, kd, decay = xs
        d = u - dot("...ik,...kv->...iv", w, s)
        o = dot("...ik,...kv->...iv", qg, s) + dot("...ij,...jv->...iv",
                                                   attn, d)
        return decay * s + dot("...ik,...iv->...kv", kd, d), o

    state1, o = jax.lax.scan(sub_chunk, state.astype(F32),
                             (w, u, attn, qg, kd, decay))
    o = jnp.moveaxis(jnp.moveaxis(o, 3, 2), 0, 1)          # [B, n, SUB, H, dv]
    return o.reshape(b, n * SUB, h, dv)[:, :t], state1


# ---------------------------------------------------------------------------
# The decode round on the state pool
# ---------------------------------------------------------------------------

def pool_state(state: jax.Array) -> jax.Array:
    """[..., H, dk, dv] -> [..., dk, H * dv]: a state as the pool keeps
    it, the heads side by side along the last axis. (float32 [96, 192]
    would be laid out on the chip in tiles of 128 lanes, 256 for every
    192: a third more bytes to hold and to move; [96, 30 * 192] is 45
    whole tiles.)"""
    s = jnp.moveaxis(state, -3, -2)                        # [..., dk, H, dv]
    return s.reshape(*s.shape[:-2], -1)


def unpool_state(slab: jax.Array, n_heads: int) -> jax.Array:
    """The inverse of :func:`pool_state`."""
    s = slab.reshape(*slab.shape[:-1], n_heads, -1)
    return jnp.moveaxis(s, -2, -3)


def _head_group(dv: int) -> int:
    """Heads whose values fill whole 128-lane tiles: 2 at ``dv`` 192."""
    return math.lcm(dv, 128) // dv


def decode_kernel_takes(n_heads: int, dv: int) -> bool:
    """Whether :func:`gated_delta_decode`'s kernel takes the shapes: the
    heads fall into groups of whole lane tiles."""
    return n_heads % _head_group(dv) == 0


def _lane_blocks(n_groups: int, group_lanes: int, dk: int) -> int:
    """Blocks the kernel cuts a row's ``H * dv`` lanes into: the fewest
    (of whole head groups) that keep a block of the state under 1 MiB, so
    that two buffers each way stay far inside a kernel's fast memory."""
    for n in range(1, n_groups + 1):
        if (n_groups % n == 0
                and dk * group_lanes * (n_groups // n) * 4 <= 1 << 20):
            return n
    return n_groups


def _decode_kernel(layer_ref, qt_ref, kt_ref, v_ref, a_ref, b_ref, s_ref,
                   o_ref, s_out, *, dv: int, group: int, n_groups: int):
    """Grid: (row, lane block). Scalar prefetch: ``layer`` [1]. Blocks:
    the state ``[1, 1, dk, lanes]`` of layer ``layer`` and this row (in,
    and out at the same place); ``qt``/``kt`` ``[1, 1, dk, heads]``: the
    block's heads' queries and keys as columns; ``v``, ``a``, ``b``,
    ``o`` ``[1, 1, lanes]``: values, decays and write strengths spread
    over their head's lanes. A group of ``group`` heads is ``group * dv``
    lanes, whole tiles: its keys are spread along the lanes by a select
    on the lane's head, the two sums over ``dk`` run along sublanes, and
    everything is float32 on the vector unit: the step's bytes are the
    state's, read once and written once."""
    dk = s_ref.shape[2]
    lanes = group * dv
    head_of = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1) // dv

    def spread(ref, p):
        e = jnp.broadcast_to(ref[0, 0, :, p * group:p * group + 1],
                             (dk, lanes))
        for i in range(1, group):
            col = ref[0, 0, :, p * group + i:p * group + i + 1]
            e = jnp.where(head_of == i, col, e)
        return e

    for p in range(n_groups):
        sl = slice(p * lanes, (p + 1) * lanes)
        ke, qe = spread(kt_ref, p), spread(qt_ref, p)
        sa = s_ref[0, 0, :, sl] * a_ref[0, :, sl]
        u = (v_ref[0, :, sl] - jnp.sum(sa * ke, axis=0, keepdims=True)
             ) * b_ref[0, :, sl]
        s1 = sa + ke * u
        s_out[0, 0, :, sl] = s1
        o_ref[0, :, sl] = jnp.sum(s1 * qe, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _decode_call(pool, layer, q, k, v, alpha, beta, interpret):
    n_layers, n, dk, hd = pool.shape
    h = q.shape[1]
    dv = hd // h
    group = _head_group(dv)
    n_blk = _lane_blocks(h // group, group * dv, dk)
    hb = h // n_blk                         # heads a lane block
    lanes = hb * dv

    def columns(x):                         # [N, H, dk] -> [N, n_blk, dk, hb]
        return jnp.swapaxes(x.astype(F32).reshape(n, n_blk, hb, dk), 2, 3)

    def over_lanes(x):                      # [N, H] -> [N, 1, H * dv]
        return jnp.repeat(x.astype(F32), dv, axis=-1)[:, None]

    row = lambda i, j, layer_ref: (i, 0, j)                    # noqa: E731
    cols = lambda i, j, layer_ref: (i, j, 0, 0)                # noqa: E731
    slab = lambda i, j, layer_ref: (layer_ref[0], i, 0, j)     # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n, n_blk),
        in_specs=[pl.BlockSpec((1, 1, dk, hb), cols),
                  pl.BlockSpec((1, 1, dk, hb), cols),
                  pl.BlockSpec((1, 1, lanes), row),
                  pl.BlockSpec((1, 1, lanes), row),
                  pl.BlockSpec((1, 1, lanes), row),
                  pl.BlockSpec((1, 1, dk, lanes), slab)],
        out_specs=[pl.BlockSpec((1, 1, lanes), row),
                   pl.BlockSpec((1, 1, dk, lanes), slab)],
    )
    o, pool = pl.pallas_call(
        functools.partial(_decode_kernel, dv=dv, group=group,
                          n_groups=hb // group),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n, 1, hd), F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 6 (the scalar counts): the pool, updated where it lies
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="gated_delta_decode",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), columns(q), columns(k),
      v.astype(F32).reshape(n, 1, hd), over_lanes(alpha), over_lanes(beta),
      pool)
    return o.reshape(n, h, dv), pool


def gated_delta_kernel(pool, layer, q, k, v, alpha, beta,
                       interpret: bool | None = None):
    """:func:`gated_delta_decode`'s Pallas kernel. ``interpret=None``
    compiles it on a TPU backend and interprets it elsewhere (the CPU
    tests)."""
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    return _decode_call(pool, layer, q, k, v, alpha, beta,
                        interpret=interpret)


def gated_delta_decode(pool, layer, q, k, v, alpha, beta, *,
                       impl: str = "auto"):
    """One decode round of layer ``layer`` on the state pool. pool:
    [L, N, dk, H * dv] float32 (:func:`pool_state`), row ``i`` of the
    round is slot ``i``; q, k: [N, H, dk]; v: [N, H, dv]; alpha, beta:
    [N, H] (an idle row: 1 and 0, its state stays as it is). Returns
    ``(o [N, H, dv] float32, pool)``.

    ``impl``: "auto" | "xla" | "pallas", as ``ops/paged_attention``:
    "auto" is the kernel on a TPU and :func:`gated_delta_step` on the
    layer's slab elsewhere; "pallas" the kernel anywhere (interpreted off
    the chip). Shapes the kernel does not take (:func:`decode_kernel_takes`)
    stay on the slab."""
    if impl not in ("auto", "xla", "pallas"):
        raise ValueError(f"unknown gated-delta impl {impl!r}; known: auto, "
                         f"xla, pallas")
    h, dv = q.shape[1], v.shape[-1]
    if (impl == "pallas" or (impl == "auto"
                             and jax.devices()[0].platform == "tpu")
            ) and decode_kernel_takes(h, dv):
        return gated_delta_kernel(pool, layer, q, k, v, alpha, beta)
    o, s1 = gated_delta_step(q, k, v, alpha, beta,
                             unpool_state(pool[layer], h))
    return o, pool.at[layer].set(pool_state(s1))
