"""The plain reference of the hybrid block: gated-delta linear-attention
layers beside full softmax attention, norms on the sublayers' outputs.

One sequence, no cache, no batching, no kernels, no chunked form: float32
``jax.numpy`` with true-float32 products, the linear layer as the
recurrence it is, token by token. It imports nothing of the program, so a
fault in ``models/transformer.py``, ``ops/gated_delta.py`` or ``serve/``
cannot reach both sides of a comparison
(tests/test_gated_delta_serving.py). The benchmark keeps its own copy
(chipbench/model_types/olmo_hybrid.py).

The equations (``x`` is [T, d]; RMSNorm is ``x / sqrt(mean(x^2) + eps) *
g`` in float32; ``h`` is a sublayer's input as it arrives: nothing
normalises it):

* layer: ``a = x + RMSNorm(Mix(x); g1)``, ``y = a + RMSNorm(MLP(a); g2)``,
  ``MLP(h) = (silu(h Wg) * (h Wu)) Wd``;
* full layer, ``Mix = Attn``: ``q = h Wq``, ``k, v = h Wkv``; RMSNorm over
  the **whole** vector of q and of k (all heads at once) before the split
  into heads; no rotation; causal softmax attention, scores ``q k^T /
  sqrt(Dh)``; ``concat(o) Wo``;
* linear layer, ``Mix = GatedDelta``: ``u = h Wqkv``, ``g = h Wgate``,
  ``a = h Wa``, ``b = h Wb``; ``c_t = silu(sum_i w_i u_{t-K+1+i})``
  depthwise, zeros left of the sequence; ``c`` split into q, k ``[Hk,
  dk]`` and v ``[Hv, dv]`` (a key head serves ``Hv / Hk`` value heads);
  ``q <- q / (|q| + 1e-6) / sqrt(dk)``, ``k <- k / (|k| + 1e-6)``;
  ``alpha = exp(-exp(A_log) softplus(a + dt_bias))``, ``beta = top *
  sigmoid(b)`` (``top`` 2 where the state transition may have negative
  eigenvalues, else 1); a head's state ``S`` [dk, dv] from zero:
  ``S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T``,
  ``o_t = S_t^T q_t``; ``Mix = concat(RMSNorm(o_t; gamma) * silu(g_t))
  Wo``;
* head: ``RMSNorm(x; gf) W_head``.

``layers``: one dict a layer (float leaves of any dtype, upcast here); a
layer with ``lin_wqkv`` is a linear one.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _dot(x, w):
    return jnp.dot(x, w.reshape(w.shape[0], -1),
                   precision=HI).reshape(*x.shape[:-1], *w.shape[1:])


def rms_norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def attention(q, k, v):
    """q [T, H, Dh], k/v [T, Hkv, Dh], causal; query head h reads KV head
    h // (H / Hkv)."""
    t, h, dh = q.shape
    hkv = k.shape[1]
    qg = q.reshape(t, hkv, h // hkv, dh)
    s = jnp.einsum("qhgd,khd->hgqk", qg, k, precision=HI) * dh ** -0.5
    keep = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    return jnp.einsum("hgqk,khd->qhgd", p, v, precision=HI).reshape(t, h, dh)


def full_mix(bp, h, eps):
    q, kv = _dot(h, bp["wq"]), _dot(h, bp["wkv"])   # [T,H,Dh], [T,Hkv,2Dh]
    dh = q.shape[-1]
    k, v = kv[..., :dh], kv[..., dh:]
    whole = lambda x, g: rms_norm(x.reshape(x.shape[0], -1), g.reshape(-1),
                                  eps).reshape(x.shape)
    q, k = whole(q, bp["q_norm"]), whole(k, bp["k_norm"])
    o = attention(q, k, v)
    return _dot(o.reshape(o.shape[0], -1), bp["wo"])


def short_conv(u, w):
    """u [T, ch], w [K, ch]: ``silu(sum_i w_i u_{t-K+1+i})``, zeros left
    of the sequence."""
    k, t = w.shape[0], u.shape[0]
    x = jnp.concatenate([jnp.zeros((k - 1, u.shape[1]), F32), u])
    return jax.nn.silu(sum(x[i:i + t] * w[i] for i in range(k)))


def delta_rule(q, k, v, alpha, beta):
    """The recurrence, a token a turn. q, k [T, H, dk]; v [T, H, dv];
    alpha, beta [T, H]. Returns o [T, H, dv]."""
    def token(s, xs):
        q, k, v, alpha, beta = xs
        s = s * alpha[:, None, None]
        # sums on the vector unit: float32 as written, whatever a matrix
        # unit's passes would make of an einsum
        u = (v - jnp.sum(s * k[:, :, None], axis=1)) * beta[:, None]
        s = s + k[:, :, None] * u[:, None, :]
        return s, jnp.sum(s * q[:, :, None], axis=1)

    s0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), F32)
    return jax.lax.scan(token, s0, (q, k, v, alpha, beta), unroll=8)[1]


def linear_mix(bp, h, eps, *, key_heads, neg_eigval):
    t = h.shape[0]
    hv = bp["lin_wa"].shape[-1]
    dv = bp["lin_norm"].shape[-1]
    hk = key_heads
    dk = (bp["lin_wqkv"].shape[-1] - hv * dv) // (2 * hk)
    c = short_conv(_dot(h, bp["lin_wqkv"]), bp["lin_conv"])
    q = c[:, :hk * dk].reshape(t, hk, dk)
    k = c[:, hk * dk:2 * hk * dk].reshape(t, hk, dk)
    v = c[:, 2 * hk * dk:].reshape(t, hv, dv)
    unit = lambda x: x / (jnp.sqrt(jnp.sum(x * x, -1, keepdims=True)) + 1e-6)
    q = jnp.repeat(unit(q) * dk ** -0.5, hv // hk, axis=1)
    k = jnp.repeat(unit(k), hv // hk, axis=1)
    alpha = jnp.exp(-jnp.exp(bp["lin_A_log"]) * jax.nn.softplus(
        _dot(h, bp["lin_wa"]) + bp["lin_dt_bias"]))
    beta = jax.nn.sigmoid(_dot(h, bp["lin_wb"])) * (2.0 if neg_eigval
                                                    else 1.0)
    o = delta_rule(q, k, v, alpha, beta)
    gate = jax.nn.silu(_dot(h, bp["lin_wgate"])).reshape(t, hv, dv)
    o = rms_norm(o, bp["lin_norm"], eps) * gate
    return _dot(o.reshape(t, -1), bp["lin_wo"])


def layer(bp, x, *, eps, key_heads, neg_eigval):
    if "lin_wqkv" in bp:
        mix = linear_mix(bp, x, eps, key_heads=key_heads,
                         neg_eigval=neg_eigval)
    else:
        mix = full_mix(bp, x, eps)
    a = x + rms_norm(mix, bp["ln1_scale"], eps)
    mlp = _dot(jax.nn.silu(_dot(a, bp["wg"])) * _dot(a, bp["wu"]), bp["wd"])
    return a + rms_norm(mlp, bp["ln2_scale"], eps)


def sequence_logits(top: dict, layers: list, tokens, *, eps: float,
                    key_heads: int, neg_eigval: bool):
    """tokens [T] -> logits [T, vocab] float32. ``top``: ``embed``,
    ``ln_f_scale``, ``head``."""
    f32 = lambda tree: jax.tree.map(lambda a: jnp.asarray(a, F32), tree)
    x = jnp.asarray(top["embed"], F32)[tokens]
    for bp in layers:
        x = layer(f32(bp), x, eps=eps, key_heads=key_heads,
                  neg_eigval=neg_eigval)
    return _dot(rms_norm(x, jnp.asarray(top["ln_f_scale"], F32), eps),
                jnp.asarray(top["head"], F32))
