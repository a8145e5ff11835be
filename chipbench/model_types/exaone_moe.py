"""The yardstick for ``model_type: exaone_moe`` (K-EXAONE): the sizes one
chip holds, weights from the seed, operations and bytes from shapes, the
plain reference, and the mapping onto the program's configuration.

Layer equations (configs/k-exaone-236b-a23b-ep8.json ``assumed`` says
which of them the published keys do not fix). ``x`` is [T, d]; every norm
is RMSNorm, ``x / sqrt(mean(x^2) + eps) * g``, in float32:

* layer: ``a = x + Attn(RMSNorm(x; g1))``, ``y = a + FFN(RMSNorm(a; g2))``;
* attention: ``q = h Wq``, ``k, v = h Wkv``, no biases; RMSNorm over each
  head of q and of k; on SLIDING layers RoPE (half-split) on q and k, and
  key ``j`` is seen from query ``i`` iff ``0 <= i - j < window``; on FULL
  layers causal only and no rotation; scores ``q k^T / sqrt(Dh)``, softmax
  in float32, ``o = P v``, output ``concat(o) Wo``;
* layer 0 (dense): ``FFN(h) = (silu(h Wg) * (h Wu)) Wd``;
* sparse layers: ``s = sigmoid(h Wr)`` in float32 over ALL experts of the
  router's published width; ``S`` = the top-k indices of ``s + b``;
  ``w_e = scale * s_e / sum_{j in S} s_j``;
  ``FFN(h) = sum_{e in S, e held here} w_e E_e(h) + E_shared(h)``, each
  ``E`` a gated FFN as above. What the experts held elsewhere would add is
  left out;
* head: ``RMSNorm(x_L; gf) W_head`` over the vocabulary slice held.

The reference imports nothing of the program; it reuses the benchmark's
own ``reference.py`` for the true-float32 (or control-precision) linear
product and RoPE. The parameter tree is the program's interface
(``models/transformer.init_params`` where layers differ: ``lead`` and
``blocks`` by ``layer_plan``), made here and handed to both sides.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import reference
from reference import F32, HI, linear, rope

BF16 = 2
# Unit embeddings and a small selection bias, so that the random router
# spreads its load as a trained one does. With embeddings of 0.02 the
# residual stream after layer 0 is its attention's output, nearly the mean
# of V over the context and so nearly the same for every token: all
# tokens then choose the same few experts (read on the chip, PR 28: the
# busiest held expert at 6.4 times the mean, some at none; with these
# values 1.3 to 1.6 at a smaller width on the CPU). A bias of 0.1 on
# scores that spread by 0.2 alone triples an expert's share; one of 0.01
# still moves it by an eighth, and the share of a chunk's assignments
# that falls on the 16 held experts then differs by 3 % from seed to
# seed, which decides how often they spill over a row tile of the grouped
# product (512 rows for 512 expected): `serve_tok_s` spread by 0.58 %
# over six seeds, more than half its bound (my chip runs, PR 28). At
# 0.002 the bias still decides one choice in seven (neighbouring scores
# lie about 0.01 apart) and moves an expert's share by 3 %.
EMBED_STD = 1.0
SCALE_STD = 0.1
ROUTER_BIAS_STD = 0.002


def layer_plan(kinds: tuple) -> tuple:
    """(n_lead, period, n_periods): the split into leading layers and
    repeats of a pattern with the fewest distinct layer bodies, as the
    program arranges its parameter tree (``transformer_config`` checks
    that the program's own plan is this one)."""
    n, best = len(kinds), None
    for lead in range(n):
        rest = kinds[lead:]
        for p in range(1, len(rest) + 1):
            if len(rest) % p == 0 and rest == rest[:p] * (len(rest) // p):
                if best is None or lead + p < best[0] + best[1]:
                    best = (lead, p, len(rest) // p)
                break
    return best


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes one chip holds of a configuration of this model type."""

    vocab: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int                 # the dense layers' width
    d_expert: int             # an expert's width (routed and shared)
    n_layers: int
    n_experts: int            # the router's width: all published experts
    held: tuple               # (first, count): the experts held here
    top_k: int
    n_shared: int
    routed_scale: float
    norm_topk: bool
    rope_theta: float
    norm_eps: float
    context: int
    windows: tuple            # a layer: its window, or None (full)
    sparse: tuple             # a layer: routed FFN or dense

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        n = cfg["num_hidden_layers"]
        dep = cfg["deployment"]
        held = (dep["experts_held"]["first"], dep["experts_held"]["count"])
        if held[1] != cfg["num_experts"]:
            raise ValueError("num_experts is the count held here and has "
                             "to equal deployment.experts_held.count")
        if cfg["scoring_func"] != "sigmoid" or cfg["hidden_act"] != "silu":
            raise ValueError("this model type scores with sigmoid and "
                             "gates with silu")
        if cfg["n_group"] != 1 or cfg["topk_group"] != 1:
            raise ValueError("group-limited routing is not written")
        return cls(
            vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
            n_heads=cfg["num_attention_heads"],
            n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            d_ff=cfg["intermediate_size"],
            d_expert=cfg["moe_intermediate_size"], n_layers=n,
            n_experts=dep["router_width"], held=held,
            top_k=cfg["num_experts_per_tok"],
            n_shared=cfg["num_shared_experts"],
            routed_scale=float(cfg["routed_scaling_factor"]),
            norm_topk=bool(cfg["norm_topk_prob"]),
            rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
            norm_eps=float(cfg["rms_norm_eps"]),
            context=cfg["max_position_embeddings"],
            windows=tuple(w or None for w in cfg["sliding_windows"][:n]),
            sparse=tuple(t == "sparse" for t in cfg["mlp_layer_types"][:n]))

    @property
    def kinds(self) -> tuple:
        """(window, rotates, sparse) a layer: sliding layers rotate."""
        return tuple((w, w is not None, s)
                     for w, s in zip(self.windows, self.sparse))

    @property
    def plan(self) -> tuple:
        return layer_plan(self.kinds)

    def layer_shapes(self, layer: int) -> dict:
        """name -> (shape, kind, fan_in) of one layer's leaves."""
        d, h, hkv, dh = (self.d_model, self.n_heads, self.n_kv_heads,
                         self.head_dim)
        out = {
            "ln1_scale": ((d,), "scale", None),
            "wq": ((d, h, dh), "matrix", d),
            "wkv": ((d, hkv, 2 * dh), "matrix", d),
            "q_norm": ((dh,), "scale", None),
            "k_norm": ((dh,), "scale", None),
            "wo": ((h * dh, d), "matrix", h * dh),
            "ln2_scale": ((d,), "scale", None),
        }
        if self.sparse[layer]:
            g, fe, fs = self.held[1], self.d_expert, (self.d_expert
                                                      * self.n_shared)
            out.update({
                "router": ((d, self.n_experts), "matrix", d),
                "router_bias": ((self.n_experts,), "router_bias", None),
                "we_g": ((g, d, fe), "matrix", d),
                "we_u": ((g, d, fe), "matrix", d),
                "we_d": ((g, fe, d), "matrix", fe),
                "ws_g": ((d, fs), "matrix", d),
                "ws_u": ((d, fs), "matrix", d),
                "ws_d": ((fs, d), "matrix", fs),
            })
        else:
            f = self.d_ff
            out.update({"wg": ((d, f), "matrix", d),
                        "wu": ((d, f), "matrix", d),
                        "wd": ((f, d), "matrix", f)})
        return out

    def top_shapes(self) -> dict:
        d = self.d_model
        return {"embed": ((self.vocab, d), "embed", None),
                "ln_f_scale": ((d,), "scale", None),
                "head": ((d, self.vocab), "matrix", d)}

    def n_params(self) -> int:
        shapes = list(self.top_shapes().values())
        for l in range(self.n_layers):
            shapes += self.layer_shapes(l).values()
        return sum(math.prod(s) for s, _, _ in shapes)


# -- weights --------------------------------------------------------------------

def make_params(seed: int, dims: Dims, dtype, out_shardings=None):
    """The whole tree in ONE jitted call, in the program's arrangement:
    ``lead``: one dict a leading layer; ``blocks``: one dict a position of
    the period, stacked on [n_periods]. Drawn in float32, rounded once to
    ``dtype``; every norm scale and the router bias random, so that a
    fault in any of them shows."""
    import jax
    import jax.numpy as jnp

    import weights

    n_lead, period, n_periods = dims.plan

    def draw(key, shape, kind, fan_in):
        x = jax.random.normal(key, shape, jnp.float32)
        if kind == "matrix":
            x = x * (fan_in ** -0.5)
        elif kind == "embed":
            x = x * EMBED_STD
        elif kind == "router_bias":
            x = x * ROUTER_BIAS_STD
        else:
            x = 1.0 + SCALE_STD * x
        return x.astype(dtype)

    def one_layer(key, layer):
        shapes = dims.layer_shapes(layer)
        keys = jax.random.split(key, len(shapes))
        return {name: draw(k, *shapes[name])
                for k, name in zip(keys, shapes)}

    def build(key):
        k_top, k_layers = jax.random.split(key)
        top = dims.top_shapes()
        out = {name: draw(k, *top[name])
               for k, name in zip(jax.random.split(k_top, len(top)), top)}
        lk = jax.random.split(k_layers, dims.n_layers)
        out["lead"] = tuple(one_layer(lk[i], i) for i in range(n_lead))
        out["blocks"] = tuple(
            jax.tree.map(lambda *xs: jnp.stack(xs), *[
                one_layer(lk[n_lead + rep * period + i],
                          n_lead + rep * period + i)
                for rep in range(n_periods)])
            for i in range(period))
        return out

    fn = jax.jit(build, out_shardings=out_shardings)
    return fn(jax.random.key(weights.fold_seed(seed)))


def layers_of(params: dict, dims: Dims) -> list:
    """One dict a layer, in order, out of the program's arrangement."""
    import jax

    n_lead, period, n_periods = dims.plan
    out = list(params["lead"])
    for rep in range(n_periods):
        out += [jax.tree.map(lambda a: a[rep], params["blocks"][i])
                for i in range(period)]
    return out


def transformer_config(cfg: dict, dims: Dims, **overrides):
    """The program's TransformerConfig for a configuration file of this
    model type (HF key names). Widths go through unchanged."""
    import jax.numpy as jnp

    from distributed_model_parallel_tpu.models.transformer import (
        LayerKind,
        TransformerConfig,
    )

    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        cfg["torch_dtype"]]
    kinds = tuple(LayerKind(window=w, rope=r, ffn="moe" if s else "dense")
                  for w, r, s in dims.kinds)
    kw = dict(
        vocab_size=dims.vocab, d_model=dims.d_model, n_heads=dims.n_heads,
        n_kv_heads=dims.n_kv_heads, d_head=dims.head_dim,
        n_layers=dims.n_layers, d_ff=dims.d_ff, max_seq_len=dims.context,
        dtype=dtype, pos_embedding="rope", rope_theta=dims.rope_theta,
        norm="rmsnorm", norm_eps=dims.norm_eps, ffn="swiglu", qk_norm=True,
        layer_kinds=kinds, moe_experts=dims.n_experts,
        moe_top_k=dims.top_k, moe_dropless=True, moe_scoring="sigmoid",
        moe_norm_topk=dims.norm_topk, moe_routed_scale=dims.routed_scale,
        moe_router_bias=True, moe_d_ff=dims.d_expert,
        moe_shared_experts=dims.n_shared, moe_experts_held=dims.held)
    kw.update(overrides)
    mcfg = TransformerConfig(**kw)
    if mcfg.layer_plan != dims.plan:
        raise RuntimeError(f"the program arranges its layers as "
                           f"{mcfg.layer_plan}, the benchmark's weights as "
                           f"{dims.plan}")
    return mcfg, dtype


# -- operations and bytes, from shapes -------------------------------------------

def _attn_params(dims: Dims) -> int:
    d, h, hkv, dh = dims.d_model, dims.n_heads, dims.n_kv_heads, dims.head_dim
    return d * h * dh + d * hkv * 2 * dh + h * dh * d


@functools.cache
def token_matmul_params(dims: Dims) -> int:
    """Matrix parameters every token meets, the routed experts left out:
    attention projections, layer 0's MLP, and in a sparse layer the router
    (its full width) and the shared expert."""
    n = dims.n_layers * _attn_params(dims)
    for sparse in dims.sparse:
        n += (dims.d_model * dims.n_experts
              + 3 * dims.d_model * dims.d_expert * dims.n_shared
              if sparse else 3 * dims.d_model * dims.d_ff)
    return n


def assignment_flops(dims: Dims) -> int:
    """One token through one routed expert: three products of
    ``d x d_expert``."""
    return 6 * dims.d_model * dims.d_expert


def _attended(dims: Dims, context: int) -> int:
    """Keys one token at ``context`` (itself included) attends, summed
    over the layers: the window on a sliding layer."""
    return sum(context if w is None else min(context, w)
               for w in dims.windows)


def serve_token_flops(dims: Dims, context: int, with_head: bool) -> int:
    """Forward of ONE token that attends ``context`` keys, the routed
    experts left out (counted by the assignments that fell on held
    experts: ``assignment_flops``)."""
    f = 2 * token_matmul_params(dims)
    f += 4 * dims.n_heads * dims.head_dim * _attended(dims, context)
    if with_head:
        f += 2 * dims.d_model * dims.vocab
    return f


def prefill_flops(dims: Dims, start: int, n_tokens: int, last: bool) -> int:
    """Forward of prompt positions [start, start + n_tokens), the routed
    experts left out."""
    def upto(n, w):        # sum_{p < n} min(p + 1, w)
        if w is None or n <= w:
            return n * (n + 1) // 2
        return w * (w + 1) // 2 + (n - w) * w
    pairs = sum(upto(start + n_tokens, w) - upto(start, w)
                for w in dims.windows)
    f = 2 * token_matmul_params(dims) * n_tokens
    f += 4 * dims.n_heads * dims.head_dim * pairs
    if last:
        f += 2 * dims.d_model * dims.vocab
    return f


def paged_decode_least_s(dims: Dims, counters: dict, peaks: dict):
    """The paged decode kernel's least time over the window's decode
    rounds: each live row reads K and V of what its layer keeps in reach
    (``min(context, window)`` on a sliding layer) and does QK^T and PV."""
    import flops

    total = 0.0
    for contexts in counters.get("decode_contexts", ()):
        keys = sum(_attended(dims, c) for c in contexts)
        total += flops.roofline_seconds(
            4 * dims.n_heads * dims.head_dim * keys,
            2 * keys * dims.n_kv_heads * dims.head_dim * BF16, peaks)[0]
    return total or None


def moe_experts_least_s(dims: Dims, counters: dict, peaks: dict):
    """The grouped products' least time over the window: the weights of
    the held experts a call touched (three matrices each, read once a
    call) and ``assignment_flops`` an assignment, from the program's
    counters. Bandwidth-bound at this cell's sizes (32 tokens an expert a
    chunk, a handful a decode round)."""
    import flops

    moe = counters.get("moe")
    if not moe:
        return None
    return flops.roofline_seconds(
        assignment_flops(dims) * moe["held_assignments"],
        3 * dims.d_model * dims.d_expert * BF16 * moe["experts_touched"],
        peaks)[0]


LEAST_SECONDS = {"paged_decode": paged_decode_least_s,
                 "moe_experts": moe_experts_least_s}


# -- the plain reference ------------------------------------------------------------

def rms_norm(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def attention(q, k, v, window, q_block: int, quant=None):
    """q [T, H, Dh], k/v [T, Hkv, Dh]; query head h reads KV head
    h // (H / Hkv); blocks of ``q_block`` query rows so that the [T, T]
    scores never exist at once. Under a window a block meets only the
    keys it can see: the ``q_block + window`` before its last row (a
    slice that would start before key 0 starts at 0; the mask is by
    position either way)."""
    import jax
    import jax.numpy as jnp

    t, h, dh = q.shape
    hkv = k.shape[1]
    qb = min(q_block, t)
    if t % qb:
        raise ValueError(f"sequence {t} not a multiple of q_block {qb}")
    span = t if window is None else min(t, qb + window)
    qg = q.reshape(t, hkv, h // hkv, dh)
    rnd = (reference.QUANT[quant][1] if quant else None) or (lambda x: x)
    k = rnd(k)

    def block(i):
        lo = jnp.clip((i + 1) * qb - span, 0, t - span)
        ks = jax.lax.dynamic_slice_in_dim(k, lo, span, axis=0)
        vs = jax.lax.dynamic_slice_in_dim(v, lo, span, axis=0)
        qs = jax.lax.dynamic_slice_in_dim(qg, i * qb, qb, axis=0)
        s = jnp.einsum("qhgd,khd->hgqk", rnd(qs), ks,
                       precision=HI) * dh ** -0.5
        qpos = (i * qb + jnp.arange(qb))[:, None]
        kpos = (lo + jnp.arange(span))[None, :]
        keep = kpos <= qpos
        if window is not None:
            keep &= (qpos - kpos) < window
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, vs, precision=HI)

    return jax.lax.map(block, jnp.arange(t // qb)).reshape(t, h, dh)


ROW_BLOCK = 4096      # rows of a gated FFN computed at once


def gated(h, wg, wu, wd, quant):
    """(silu(h wg) * (h wu)) wd, in blocks of ROW_BLOCK rows where the
    sequence is a multiple of that (a 16k-token sequence at 18,432 wide
    would hold 3.6 GB of float32 between the products)."""
    import jax

    def ffn(hb):
        return linear(jax.nn.silu(linear(hb, wg, quant))
                      * linear(hb, wu, quant), wd, quant)

    t = h.shape[0]
    if t <= ROW_BLOCK or t % ROW_BLOCK:
        return ffn(h)
    return jax.lax.map(ffn, h.reshape(t // ROW_BLOCK, ROW_BLOCK, -1)
                       ).reshape(t, -1)


def routed(bp, h, valid, dims: Dims, quant):
    """The held routed experts' part of the FFN for h [T, d]; ``valid``
    [T]: tokens that exist (padding is sent nowhere). The router is in
    float32 whatever ``quant`` (as the program's is whatever its dtype).
    Each held expert computes on its own tokens only: they are gathered
    ``cap`` rows at a time (``max(256, T / 4)``; the mean is T * k /
    n_experts, a sixteenth of T at 8 of 128), in as many turns as the
    expert has tokens for, so that no choice is ever dropped."""
    import jax
    import jax.numpy as jnp

    t = h.shape[0]
    s = jax.nn.sigmoid(jnp.dot(h, bp["router"].astype(F32), precision=HI))
    _, chosen = jax.lax.top_k(s + bp["router_bias"].astype(F32), dims.top_k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if dims.norm_topk:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * dims.routed_scale
    first, count = dims.held
    cap = min(t, max(256, t // 4))
    h_pad = jnp.concatenate([h, jnp.zeros((1, h.shape[1]), F32)])

    def one(y, xs):
        e, wg, wu, wd = xs
        hit = jnp.logical_and(chosen == first + e, valid[:, None])
        w_e = jnp.concatenate([jnp.sum(jnp.where(hit, w, 0.0), -1),
                               jnp.zeros((1,), F32)])
        mine = jnp.any(hit, axis=-1)
        turn_of = (jnp.cumsum(mine) - 1) // cap      # of a token of mine

        def turn(c, y):
            idx = jnp.nonzero(jnp.logical_and(mine, turn_of == c),
                              size=cap, fill_value=t)[0]
            ye = gated(h_pad[idx], wg.astype(F32), wu.astype(F32),
                       wd.astype(F32), quant) * w_e[idx][:, None]
            return y.at[idx].add(ye, mode="drop")

        n_turns = (jnp.sum(mine) + cap - 1) // cap
        return jax.lax.fori_loop(0, n_turns, turn, y), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (jnp.arange(count), bp["we_g"], bp["we_u"],
                         bp["we_d"]))
    return y


def layer_fwd(bp, x, valid, kind, dims: Dims, quant, q_block: int):
    """One layer on one sequence x [T, d]; bp holds the layer's leaves in
    their stored dtype, upcast as they are used."""
    window, rotates, sparse = kind
    f32 = lambda name: bp[name].astype(F32)
    h = rms_norm(x, f32("ln1_scale"), dims.norm_eps)
    q = linear(h, f32("wq"), quant)                      # [T, H, Dh]
    kv = linear(h, f32("wkv"), quant)                    # [T, Hkv, 2 Dh]
    k, v = kv[..., :dims.head_dim], kv[..., dims.head_dim:]
    q = rms_norm(q, f32("q_norm"), dims.norm_eps)
    k = rms_norm(k, f32("k_norm"), dims.norm_eps)
    if rotates:
        q, k = rope(q, dims.rope_theta), rope(k, dims.rope_theta)
    o = attention(q, k, v, window, q_block, quant)
    x = x + linear(o.reshape(o.shape[0], -1), f32("wo"), quant)
    h = rms_norm(x, f32("ln2_scale"), dims.norm_eps)
    if sparse:
        y = routed(bp, h, valid, dims, quant)
        y = y + gated(h, f32("ws_g"), f32("ws_u"), f32("ws_d"), quant)
    else:
        y = gated(h, f32("wg"), f32("wu"), f32("wd"), quant)
    return x + y


def _sequence_logits(params, tokens, rows, n_valid, *, dims, quant,
                     q_block):
    import jax.numpy as jnp

    x = params["embed"][tokens].astype(F32)
    valid = jnp.arange(tokens.shape[0]) < n_valid
    for bp, kind in zip(layers_of(params, dims), dims.kinds):
        x = layer_fwd(bp, x, valid, kind, dims, quant, q_block)
    xr = rms_norm(x[rows], params["ln_f_scale"].astype(F32), dims.norm_eps)
    return linear(xr, params["head"].astype(F32), quant)


@functools.cache
def _jitted():
    import jax

    return jax.jit(_sequence_logits,
                   static_argnames=("dims", "quant", "q_block"))


def sequence_logits(params, tokens, rows, *, dims, quant=None,
                    q_block=1024):
    """params: the program-arranged tree (any float dtype); tokens [T]
    (padded: causal, so padding after the rows asked for changes
    nothing, and padding is routed to no expert); rows [R]: positions
    whose next-token logits are wanted. Returns [R, vocab] float32. The
    signature is ``reference.sequence_logits``'s, so one comparison
    serves every model type."""
    import jax.numpy as jnp

    n_valid = jnp.max(rows) + 1          # the last row asked for is the last token
    return _jitted()(params, tokens, rows, n_valid, dims=dims, quant=quant,
                     q_block=q_block)
