"""The yardstick for ``model_type: olmo_hybrid`` (Olmo-Hybrid-7B): the
sizes one chip holds, weights from the seed, operations and bytes from
shapes, the plain reference, and the mapping onto the program's
configuration.

Layer equations (configs/olmo-hybrid-7b-pp2.json ``assumed`` says which
of them the published keys do not fix). ``x`` is [T, d]; RMSNorm is ``x /
sqrt(mean(x^2) + eps) * g`` in float32; ``h`` is a sublayer's input as it
arrives (``x`` for ``Mix``, ``a`` for ``MLP``): nothing normalises it:

* layer: ``a = x + RMSNorm(Mix(x); g1)``, ``y = a + RMSNorm(MLP(a); g2)``,
  ``MLP(h) = (silu(h Wg) * (h Wu)) Wd``, no biases; head: ``RMSNorm(x_L;
  gf) W_head``;
* full layer (``full_attention``), ``Mix = Attn``: ``q = h Wq``, ``k, v =
  h Wkv``, no biases; RMSNorm over the WHOLE vector of q and of k (all
  heads at once) before the split into heads; no rotation; causal softmax
  attention, scores ``q k^T / sqrt(Dh)`` in float32; ``concat(o) Wo``;
* linear layer (``linear_attention``), ``Mix = GatedDelta``, ``Hk`` key
  heads of ``dk``, ``Hv`` value heads of ``dv``, a convolution of ``K``:
  ``u = h [Wq | Wk | Wv]`` ([T, 2 Hk dk + Hv dv]), ``g = h Wgate`` ([T, Hv
  dv]), ``a = h Wa``, ``b = h Wb`` ([T, Hv] each);
  ``c_t = silu(sum_{i < K} w_i * u_{t-K+1+i})`` depthwise over channels,
  zeros left of the sequence, no bias; ``c`` split into ``q_t, k_t``
  ([Hk, dk]) and ``v_t`` ([Hv, dv]); ``q_t <- q_t / (|q_t| + 1e-6) /
  sqrt(dk)``, ``k_t <- k_t / (|k_t| + 1e-6)`` a head;
  ``alpha_t = exp(-exp(A_log) * softplus(a_t + dt_bias))`` in (0, 1),
  ``beta_t = 2 sigmoid(b_t)`` in (0, 2) (``linear_allow_neg_eigval``;
  without it the 2 goes), a head, float32;
  a head's state ``S`` [dk, dv], ``S_0 = 0``:
  ``S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T``,
  ``o_t = S_t^T q_t``;
  ``Mix = concat_heads(RMSNorm(o_t; gamma[dv]) * silu(g_t)) Wo``.

The reference runs the linear layer as that recurrence, TOKEN BY TOKEN (a
``lax.scan`` over T): never the chunked form the program's prefill uses,
no cache, no kernels. It imports nothing of the program; it reuses the
benchmark's own ``reference.py`` for the true-float32 (or
control-precision) linear product and the blocked causal attention, and
``exaone_moe``'s RMSNorm, gated FFN in row blocks and ``layer_plan``. The
controls (``int8``, ``int8_fwd``) round every projection's operands; the
recurrence itself stays float32 (as the router does in ``exaone_moe``'s
controls). The parameter tree is the program's interface
(``models/transformer.init_params``: ``blocks``, one dict a position of
the period, stacked on the repeats), made here and handed to both sides.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import reference
from model_types.exaone_moe import gated, layer_plan, rms_norm
from reference import F32, linear

BF16 = 2
F32_BYTES = 4
# Unit embeddings (PR 28's lesson: with embeddings of 0.02 the first
# layers see nearly the same vector for every token) and norm scales that
# are not all one, so that a fault in any of them shows.
EMBED_STD = 1.0
SCALE_STD = 0.1
# The decay's leaves as the rule's authors draw them (Gated DeltaNet's
# and Mamba2's initialisation): A uniform in (0, 16), dt log-uniform in
# (0.001, 0.1), dt_bias = softplus^-1(dt); and a small Wa, so that a
# token's input moves its decay around the drawn value and does not
# decide it: alpha then lies mostly in 0.9-0.999 (read on the chip:
# configs/olmo-hybrid-7b-pp2.json assumed.gates).
A_RANGE = (1e-3, 16.0)
DT_RANGE = (1e-3, 0.1)
WA_STD = 0.1          # times the N(0, 1/fan_in) of every matrix
KINDS = {"linear_attention": "linear", "full_attention": "full"}


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes one chip holds of a configuration of this model type."""

    vocab: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    n_layers: int
    kinds: tuple              # a layer: "linear" | "full"
    lin_key_heads: int
    lin_value_heads: int
    lin_key_dim: int
    lin_value_dim: int
    lin_conv: int
    neg_eigval: bool
    norm_eps: float
    context: int

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        n = cfg["num_hidden_layers"]
        if cfg["hidden_act"] != "silu" or cfg["attention_bias"]:
            raise ValueError("this model type gates with silu and has no "
                             "attention biases")
        if cfg["rope_parameters"].get("rope_theta") is not None:
            raise ValueError("this model type's full layers do not rotate "
                             "(rope_theta null); a theta is not written")
        if cfg["hidden_size"] % cfg["num_attention_heads"]:
            raise ValueError("head size = hidden_size / num_attention_heads")
        if cfg["linear_num_value_heads"] % cfg["linear_num_key_heads"]:
            raise ValueError("value heads are a multiple of key heads")
        return cls(
            vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
            n_heads=cfg["num_attention_heads"],
            n_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
            d_ff=cfg["intermediate_size"], n_layers=n,
            kinds=tuple(KINDS[t] for t in cfg["layer_types"][:n]),
            lin_key_heads=cfg["linear_num_key_heads"],
            lin_value_heads=cfg["linear_num_value_heads"],
            lin_key_dim=cfg["linear_key_head_dim"],
            lin_value_dim=cfg["linear_value_head_dim"],
            lin_conv=cfg["linear_conv_kernel_dim"],
            neg_eigval=bool(cfg["linear_allow_neg_eigval"]),
            norm_eps=float(cfg["rms_norm_eps"]),
            context=cfg["max_position_embeddings"])

    @property
    def plan(self) -> tuple:
        return layer_plan(self.kinds)

    @property
    def n_linear(self) -> int:
        return self.kinds.count("linear")

    @property
    def n_full(self) -> int:
        return self.kinds.count("full")

    @property
    def conv_channels(self) -> int:
        return (2 * self.lin_key_heads * self.lin_key_dim
                + self.lin_value_heads * self.lin_value_dim)

    @property
    def state_bytes(self) -> int:
        """One layer's recurrent state of one sequence, float32."""
        return (self.lin_value_heads * self.lin_key_dim * self.lin_value_dim
                * F32_BYTES)

    def layer_shapes(self, layer: int) -> dict:
        """name -> (shape, kind, fan_in) of one layer's leaves."""
        d, f = self.d_model, self.d_ff
        out = {
            "ln1_scale": ((d,), "scale", None),
            "ln2_scale": ((d,), "scale", None),
            "wg": ((d, f), "matrix", d),
            "wu": ((d, f), "matrix", d),
            "wd": ((f, d), "matrix", f),
        }
        if self.kinds[layer] == "linear":
            hv, dv, k = (self.lin_value_heads, self.lin_value_dim,
                         self.lin_conv)
            ch = self.conv_channels
            out.update({
                "lin_wqkv": ((d, ch), "matrix", d),
                "lin_wgate": ((d, hv * dv), "matrix", d),
                "lin_wa": ((d, hv), "small", d),
                "lin_wb": ((d, hv), "matrix", d),
                "lin_conv": ((k, ch), "matrix", k),
                "lin_A_log": ((hv,), "A_log", None),
                "lin_dt_bias": ((hv,), "dt_bias", None),
                "lin_norm": ((dv,), "scale", None),
                "lin_wo": ((hv * dv, d), "matrix", hv * dv),
            })
        else:
            h, hkv, dh = self.n_heads, self.n_kv_heads, self.head_dim
            out.update({
                "wq": ((d, h, dh), "matrix", d),
                "wkv": ((d, hkv, 2 * dh), "matrix", d),
                "q_norm": ((h, dh), "scale", None),
                "k_norm": ((hkv, dh), "scale", None),
                "wo": ((h * dh, d), "matrix", h * dh),
            })
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
    """The whole tree in ONE jitted call, in the program's arrangement
    (``blocks``: one dict a position of the period, stacked on
    [n_periods]; one dict where all layers are alike). Drawn in float32,
    rounded once to ``dtype``; ``A_log`` and ``dt_bias`` stay float32 (as
    the program's own initialiser keeps them); every norm scale random."""
    import jax
    import jax.numpy as jnp

    import weights

    n_lead, period, n_periods = dims.plan

    def draw(key, shape, kind, fan_in):
        if kind == "A_log":
            return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                              *A_RANGE))
        if kind == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, math.log(DT_RANGE[0]),
                math.log(DT_RANGE[1])))
            return dt + jnp.log(-jnp.expm1(-dt))          # softplus^-1
        x = jax.random.normal(key, shape, jnp.float32)
        if kind in ("matrix", "small"):
            x = x * (fan_in ** -0.5) * (WA_STD if kind == "small" else 1.0)
        elif kind == "embed":
            x = x * EMBED_STD
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
        blocks = tuple(
            jax.tree.map(lambda *xs: jnp.stack(xs), *[
                one_layer(lk[n_lead + rep * period + i],
                          n_lead + rep * period + i)
                for rep in range(n_periods)])
            for i in range(period))
        if (n_lead, period) == (0, 1):
            out["blocks"] = blocks[0]
        else:
            out["lead"] = tuple(one_layer(lk[i], i) for i in range(n_lead))
            out["blocks"] = blocks
        return out

    fn = jax.jit(build, out_shardings=out_shardings)
    return fn(jax.random.key(weights.fold_seed(seed)))


def layers_of(params: dict, dims: Dims) -> list:
    """One dict a layer, in order, out of the program's arrangement."""
    import jax

    n_lead, period, n_periods = dims.plan
    blocks = params["blocks"]
    if isinstance(blocks, dict):
        blocks = (blocks,)
    out = list(params.get("lead", ()))
    for rep in range(n_periods):
        out += [jax.tree.map(lambda a: a[rep], blocks[i])
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
    kinds = tuple(LayerKind(mixer="gated_delta" if k == "linear"
                            else "attention") for k in dims.kinds)
    kw = dict(
        vocab_size=dims.vocab, d_model=dims.d_model, n_heads=dims.n_heads,
        n_kv_heads=dims.n_kv_heads, d_head=dims.head_dim,
        n_layers=dims.n_layers, d_ff=dims.d_ff, max_seq_len=dims.context,
        dtype=dtype, pos_embedding="rope", norm="rmsnorm",
        norm_eps=dims.norm_eps, ffn="swiglu", qk_norm_whole=True,
        norm_placement="post", layer_kinds=kinds,
        lin_key_heads=dims.lin_key_heads,
        lin_value_heads=dims.lin_value_heads, lin_key_dim=dims.lin_key_dim,
        lin_value_dim=dims.lin_value_dim, lin_conv=dims.lin_conv,
        lin_neg_eigval=dims.neg_eigval)
    kw.update(overrides)
    mcfg = TransformerConfig(**kw)
    if mcfg.layer_plan != dims.plan:
        raise RuntimeError(f"the program arranges its layers as "
                           f"{mcfg.layer_plan}, the benchmark's weights as "
                           f"{dims.plan}")
    return mcfg, dtype


# -- operations and bytes, from shapes -------------------------------------------

def linear_layer_matmul_params(dims: Dims) -> int:
    """The six projections in and the one out."""
    d, hv = dims.d_model, dims.lin_value_heads
    vd = hv * dims.lin_value_dim
    return d * (dims.conv_channels + vd + 2 * hv) + vd * d


def full_layer_matmul_params(dims: Dims) -> int:
    d, h, hkv, dh = dims.d_model, dims.n_heads, dims.n_kv_heads, dims.head_dim
    return d * h * dh + d * hkv * 2 * dh + h * dh * d


@functools.cache
def token_matmul_params(dims: Dims) -> int:
    """Matrix parameters every token meets, the head left out."""
    return (dims.n_linear * linear_layer_matmul_params(dims)
            + dims.n_full * full_layer_matmul_params(dims)
            + dims.n_layers * 3 * dims.d_model * dims.d_ff)


def rule_token_flops(dims: Dims) -> int:
    """One token through one linear layer's rule and convolution, as the
    recurrence needs them: a head's ``S^T k``, the rank-one update and
    ``S^T q`` are ``2 dk dv`` each; the convolution ``2 K`` a channel.
    (The chunked form multiplies more; what an algorithm adds to the
    mathematics is never counted.)"""
    return (6 * dims.lin_value_heads * dims.lin_key_dim * dims.lin_value_dim
            + 2 * dims.lin_conv * dims.conv_channels)


def serve_token_flops(dims: Dims, context: int, with_head: bool) -> int:
    """Forward of ONE token that attends ``context`` keys (itself
    included) on the full layers."""
    f = 2 * token_matmul_params(dims)
    f += dims.n_linear * rule_token_flops(dims)
    f += 4 * dims.n_heads * dims.head_dim * context * dims.n_full
    if with_head:
        f += 2 * dims.d_model * dims.vocab
    return f


def prefill_flops(dims: Dims, start: int, n_tokens: int, last: bool) -> int:
    """Forward of prompt positions [start, start + n_tokens)."""
    upto = lambda n: n * (n + 1) // 2                          # noqa: E731
    pairs = (upto(start + n_tokens) - upto(start)) * dims.n_full
    f = (2 * token_matmul_params(dims)
         + dims.n_linear * rule_token_flops(dims)) * n_tokens
    f += 4 * dims.n_heads * dims.head_dim * pairs
    if last:
        f += 2 * dims.d_model * dims.vocab
    return f


def paged_decode_least_s(dims: Dims, counters: dict, peaks: dict):
    """The paged decode kernel's least time over the window's decode
    rounds: each live row reads K and V of its context on the full layers
    (the model's KV heads: what the pool pads them to is the program's)
    and does QK^T and PV."""
    import flops

    total = 0.0
    for contexts in counters.get("decode_contexts", ()):
        keys = sum(contexts) * dims.n_full
        total += flops.roofline_seconds(
            4 * dims.n_heads * dims.head_dim * keys,
            2 * keys * dims.n_kv_heads * dims.head_dim * BF16, peaks)[0]
    return total or None


def linattn_decode_least_s(dims: Dims, counters: dict, peaks: dict):
    """The rule's least time over the window's decode rounds: each live
    row's state of each linear layer read once and written once (float32),
    and the recurrence's operations. Bandwidth-bound."""
    import flops

    total = 0.0
    for contexts in counters.get("decode_contexts", ()):
        rows = len(contexts) * dims.n_linear
        total += flops.roofline_seconds(
            6 * dims.lin_value_heads * dims.lin_key_dim
            * dims.lin_value_dim * rows,
            2 * dims.state_bytes * rows, peaks)[0]
    return total or None


def linattn_prefill_least_s(dims: Dims, counters: dict, peaks: dict):
    """The rule's least time over the window's prefill chunks
    (``counters["prefill_chunks"]``: (start, tokens) a chunk): the
    sequence's state in and out once a chunk and layer, q, k and v once
    (bfloat16), and the recurrence's operations a token."""
    import flops

    total = 0.0
    qkv = dims.conv_channels * BF16
    for _, n_tokens in counters.get("prefill_chunks", ()):
        total += flops.roofline_seconds(
            6 * dims.lin_value_heads * dims.lin_key_dim
            * dims.lin_value_dim * n_tokens * dims.n_linear,
            (2 * dims.state_bytes + qkv * n_tokens) * dims.n_linear,
            peaks)[0]
    return total or None


LEAST_SECONDS = {"paged_decode": paged_decode_least_s,
                 "linattn_decode": linattn_decode_least_s,
                 "linattn_prefill": linattn_prefill_least_s}


# -- the plain reference ------------------------------------------------------------

def short_conv(u, w):
    """u [T, ch], w [K, ch]: ``silu(sum_i w_i u_{t-K+1+i})``, zeros left
    of the sequence."""
    import jax
    import jax.numpy as jnp

    k, t = w.shape[0], u.shape[0]
    x = jnp.concatenate([jnp.zeros((k - 1, u.shape[1]), F32), u])
    return jax.nn.silu(sum(x[i:i + t] * w[i] for i in range(k)))


def delta_rule(q, k, v, alpha, beta):
    """The recurrence, a token a turn, float32. q, k [T, H, dk]; v [T, H,
    dv]; alpha, beta [T, H]. Returns o [T, H, dv]."""
    import jax
    import jax.numpy as jnp

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


def gates(bp, h, dims: Dims, quant=None):
    """(alpha, beta) [T, Hv] of one linear layer for inputs h."""
    import jax
    import jax.numpy as jnp

    f32 = lambda name: bp[name].astype(F32)                    # noqa: E731
    alpha = jnp.exp(-jnp.exp(f32("lin_A_log")) * jax.nn.softplus(
        linear(h, f32("lin_wa"), quant) + f32("lin_dt_bias")))
    beta = jax.nn.sigmoid(linear(h, f32("lin_wb"), quant)) * (
        2.0 if dims.neg_eigval else 1.0)
    return alpha, beta


def linear_mix(bp, h, dims: Dims, quant):
    import jax
    import jax.numpy as jnp

    t = h.shape[0]
    hk, hv = dims.lin_key_heads, dims.lin_value_heads
    dk, dv = dims.lin_key_dim, dims.lin_value_dim
    f32 = lambda name: bp[name].astype(F32)                    # noqa: E731
    c = short_conv(linear(h, f32("lin_wqkv"), quant), f32("lin_conv"))
    q = c[:, :hk * dk].reshape(t, hk, dk)
    k = c[:, hk * dk:2 * hk * dk].reshape(t, hk, dk)
    v = c[:, 2 * hk * dk:].reshape(t, hv, dv)
    unit = lambda x: x / (jnp.sqrt(jnp.sum(                    # noqa: E731
        x * x, -1, keepdims=True)) + 1e-6)
    q = jnp.repeat(unit(q) * dk ** -0.5, hv // hk, axis=1)
    k = jnp.repeat(unit(k), hv // hk, axis=1)
    alpha, beta = gates(bp, h, dims, quant)
    o = delta_rule(q, k, v, alpha, beta)
    gate = jax.nn.silu(linear(h, f32("lin_wgate"), quant)).reshape(t, hv, dv)
    o = rms_norm(o, f32("lin_norm"), dims.norm_eps) * gate
    return linear(o.reshape(t, -1), f32("lin_wo"), quant)


def full_mix(bp, h, dims: Dims, quant, q_block: int):
    f32 = lambda name: bp[name].astype(F32)                    # noqa: E731
    q = linear(h, f32("wq"), quant)                      # [T, H, Dh]
    kv = linear(h, f32("wkv"), quant)                    # [T, Hkv, 2 Dh]
    k, v = kv[..., :dims.head_dim], kv[..., dims.head_dim:]
    whole = lambda x, g: rms_norm(                             # noqa: E731
        x.reshape(x.shape[0], -1), g.reshape(-1),
        dims.norm_eps).reshape(x.shape)
    q, k = whole(q, f32("q_norm")), whole(k, f32("k_norm"))
    o = reference.attention(q, k, v, None, q_block, quant)
    return linear(o.reshape(o.shape[0], -1), f32("wo"), quant)


def layer_fwd(bp, x, kind: str, dims: Dims, quant, q_block: int):
    """One layer on one sequence x [T, d]; bp holds the layer's leaves in
    their stored dtype, upcast as they are used."""
    f32 = lambda name: bp[name].astype(F32)                    # noqa: E731
    mix = (linear_mix(bp, x, dims, quant) if kind == "linear"
           else full_mix(bp, x, dims, quant, q_block))
    a = x + rms_norm(mix, f32("ln1_scale"), dims.norm_eps)
    mlp = gated(a, f32("wg"), f32("wu"), f32("wd"), quant)
    return a + rms_norm(mlp, f32("ln2_scale"), dims.norm_eps)


def _sequence_logits(params, tokens, rows, *, dims, quant, q_block):
    x = params["embed"][tokens].astype(F32)
    for bp, kind in zip(layers_of(params, dims), dims.kinds):
        x = layer_fwd(bp, x, kind, dims, quant, q_block)
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
    nothing); rows [R]: positions whose next-token logits are wanted.
    Returns [R, vocab] float32. The signature is
    ``reference.sequence_logits``'s, so one comparison serves every model
    type."""
    return _jitted()(params, tokens, rows, dims=dims, quant=quant,
                     q_block=q_block)


def gate_spread(params, tokens, *, dims):
    """(alpha, beta) of every linear layer over one sequence, [n_linear,
    T, Hv] each: what the configuration file's ``assumed.gates`` was read
    with (float32, the reference's own forward)."""
    import jax.numpy as jnp

    x = params["embed"][tokens].astype(F32)
    got = []
    for bp, kind in zip(layers_of(params, dims), dims.kinds):
        if kind == "linear":
            got.append(gates(bp, x, dims))
        x = layer_fwd(bp, x, kind, dims, None, 1024)
    return (jnp.stack([a for a, _ in got]), jnp.stack([b for _, b in got]))
