"""The plain reference of the gated, routed, mixed-attention block.

One sequence, no cache, no batching, no kernels: float32 ``jax.numpy``
with true-float32 products. It imports nothing of the program, so a fault
in ``models/transformer.py``, ``ops/moe.py`` or ``serve/`` cannot reach
both sides of a comparison (tests/test_gated_moe_serving.py). The
benchmark keeps its own copy (chipbench/models/exaone_moe.py).

The equations (``x`` is [T, d]; every norm is RMSNorm in float32,
``x / sqrt(mean(x^2) + eps) * g``):

* layer: ``a = x + Attn(RMSNorm(x; g1))``, ``y = a + FFN(RMSNorm(a; g2))``;
* attention: ``q = h Wq``, ``k, v = h Wkv``, no biases; RMSNorm over each
  head of q and of k (``q_norm``, ``k_norm``) where the tree has them;
  on a layer that rotates, RoPE (half-split) on q and k; key ``j`` is seen
  from query ``i`` iff ``0 <= i - j`` and, under a window ``w``,
  ``i - j < w``; scores ``q k^T / sqrt(Dh)``, softmax, ``o = P v``,
  output ``concat(o) Wo``;
* dense FFN: ``(silu(h Wg) * (h Wu)) Wd``;
* routed FFN: ``s = sigmoid(h Wr)``; ``S`` = the k indices of largest
  ``s + b``; ``w_e = scale * s_e / sum_{j in S} s_j``;
  ``sum_{e in S, e held} w_e E_e(h) + E_shared(h)``, every ``E`` a gated
  FFN as above. ``held = (first, count)`` says which experts the tree's
  ``we_*`` leaves hold; what the others would add is left out;
* head: ``RMSNorm(x; gf) W_head``.

``layers``: one dict a layer (float leaves of any dtype, upcast here),
``kinds``: one ``(window | None, rotates)`` a layer.
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


def rope(x, theta):
    """x [T, H, Dh], positions 0..T-1, pairs (i, i + Dh/2)."""
    t, _, dh = x.shape
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def attention(q, k, v, window):
    """q [T, H, Dh], k/v [T, Hkv, Dh]; query head h reads KV head
    h // (H / Hkv)."""
    t, h, dh = q.shape
    hkv = k.shape[1]
    qg = q.reshape(t, hkv, h // hkv, dh)
    s = jnp.einsum("qhgd,khd->hgqk", qg, k, precision=HI) * dh ** -0.5
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    keep = j <= i
    if window is not None:
        keep &= (i - j) < window
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    return jnp.einsum("hgqk,khd->qhgd", p, v, precision=HI).reshape(t, h, dh)


def gated(h, wg, wu, wd):
    return _dot(jax.nn.silu(_dot(h, wg)) * _dot(h, wu), wd)


def routed(bp, h, *, top_k, scale, held):
    """The routed experts' part of the FFN, [T, d]."""
    s = jax.nn.sigmoid(_dot(h, bp["router"]))                   # [T, E]
    pick = s + bp["router_bias"] if "router_bias" in bp else s
    _, chosen = jax.lax.top_k(pick, top_k)                      # [T, k]
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = scale * w / jnp.sum(w, axis=-1, keepdims=True)
    first, count = held
    y = jnp.zeros_like(h)
    for e in range(count):
        w_e = jnp.sum(jnp.where(chosen == first + e, w, 0.0), axis=-1)
        y = y + w_e[:, None] * gated(h, bp["we_g"][e], bp["we_u"][e],
                                     bp["we_d"][e])
    return y


def layer(bp, x, kind, *, eps, theta, top_k, scale, held):
    window, rotates = kind
    h = rms_norm(x, bp["ln1_scale"], eps)
    q = _dot(h, bp["wq"])                                       # [T, H, Dh]
    kv = _dot(h, bp["wkv"])                                     # [T, Hkv, 2Dh]
    dh = q.shape[-1]
    k, v = kv[..., :dh], kv[..., dh:]
    if "q_norm" in bp:
        q = rms_norm(q, bp["q_norm"], eps)
        k = rms_norm(k, bp["k_norm"], eps)
    if rotates:
        q, k = rope(q, theta), rope(k, theta)
    o = attention(q, k, v, window)
    x = x + _dot(o.reshape(o.shape[0], -1), bp["wo"])
    h = rms_norm(x, bp["ln2_scale"], eps)
    if "router" in bp:
        y = routed(bp, h, top_k=top_k, scale=scale, held=held)
        if "ws_g" in bp:
            y = y + gated(h, bp["ws_g"], bp["ws_u"], bp["ws_d"])
    else:
        y = gated(h, bp["wg"], bp["wu"], bp["wd"])
    return x + y


def sequence_logits(top: dict, layers: list, kinds: list, tokens, *,
                    eps: float, theta: float, top_k: int, scale: float,
                    held: tuple):
    """tokens [T] -> logits [T, vocab] float32. ``top``: ``embed``,
    ``ln_f_scale``, ``head``."""
    f32 = lambda tree: jax.tree.map(lambda a: jnp.asarray(a, F32), tree)
    x = jnp.asarray(top["embed"], F32)[tokens]
    for bp, kind in zip(layers, kinds):
        x = layer(f32(bp), x, kind, eps=eps, theta=theta, top_k=top_k,
                  scale=scale, held=held)
    return _dot(rms_norm(x, jnp.asarray(top["ln_f_scale"], F32), eps),
                jnp.asarray(top["head"], F32))
