"""The yardstick for ``model_type: ouro`` (Ouro-2.6B, the LoopLM family:
"Scaling Latent Reasoning via Looped Language Models"): the sizes one chip
holds, weights from the seed, operations and bytes from shapes, the plain
reference, and the mapping onto the program's configuration.

Layer equations (configs/ouro-2.6b.json ``assumed`` says which of them the
published keys do not fix). ``h_0 = E[tokens]``; ``T = total_ut_steps``
passes over ``L = num_hidden_layers`` layers; RMSNorm is ``x / sqrt(mean(
x^2) + eps) * g`` in float32; the weights do not depend on the pass::

    for t in 0..T-1:                         # one pass ("UT step")
      for l in 0..L-1:
        a   = RMSNorm_{l,in1}(x)
        q, k, v = a Wq_l, a Wk_l, a Wv_l     # [., H, Dh] each; no bias;
                                             # RoPE(theta) on q and k
        K[t,l], V[t,l] <- append(k, v)       # the cache entry is (t, l)
        o   = softmax(q K[t,l]^T / sqrt(Dh), causal) V[t,l]
        x   = x + RMSNorm_{l,out1}(o Wo_l)
        m   = RMSNorm_{l,in2}(x)
        x   = x + RMSNorm_{l,out2}((silu(m Wg_l) * (m Wu_l)) Wd_l)
      x   = RMSNorm_f(x)                     # every pass; feeds the next
      g_t = sigmoid(x . w_gate + b_gate)     # exit gate, a number a token
    logits = x W_head                        # early_exit_threshold 1:
                                             # always the last pass
    p_exit(t)   = g_t * prod_{j<t} (1 - g_j)
    p_exit(T-1) = prod_{j<T-1} (1 - g_j)     # counted, not acted on

The reference runs exactly that on one sequence: float32, true-float32
products (``reference.linear``: ``Precision.HIGHEST``), no cache (pass
``t``'s keys are recomputed from pass ``t``'s stream, which is what a
cache entry a (pass, layer) holds), no kernels, nothing of the program
imported. It reuses the benchmark's own ``reference.py`` for the linear
product (and the ``int8`` / ``int8_fwd`` controls), the rotation and the
blocked causal attention, and ``exaone_moe``'s RMSNorm and gated FFN. The
parameter tree is the program's interface
(``models/transformer.init_params``: ``blocks`` one dict stacked on [L],
``L`` layers' leaves however many passes run), made here and handed to
both sides.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import reference
from model_types.exaone_moe import gated, rms_norm
from reference import F32, linear

BF16 = 2
# Unit embeddings and norm scales that are not all one (PR 28's and PR
# 32's lessons: a fault in any leaf has to show); a gate that neither
# always stays nor always leaves.
EMBED_STD = 1.0
SCALE_STD = 0.1
GATE_BIAS_STD = 0.5


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
    passes: int
    norm_eps: float
    rope_theta: float
    context: int

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        n = cfg["num_hidden_layers"]
        if cfg["hidden_act"] != "silu":
            raise ValueError("this model type gates with silu")
        if any(t != "full_attention" for t in cfg["layer_types"][:n]) or (
                cfg.get("use_sliding_window") or cfg.get("sliding_window")):
            raise ValueError("this model type's layers are full attention, "
                             "no window")
        if cfg.get("rope_scaling") is not None:
            raise ValueError("rope_scaling is not written")
        if cfg["tie_word_embeddings"]:
            raise ValueError("the head is untied in this model type")
        if cfg["early_exit_threshold"] != 1:
            raise ValueError(
                "early_exit_threshold != 1: rows that leave the loop at "
                "different passes are run neither by the program nor by "
                "this reference")
        return cls(
            vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
            n_heads=cfg["num_attention_heads"],
            n_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"], d_ff=cfg["intermediate_size"],
            n_layers=n, passes=cfg["total_ut_steps"],
            norm_eps=float(cfg["rms_norm_eps"]),
            rope_theta=float(cfg["rope_theta"]),
            context=cfg["max_position_embeddings"])

    @property
    def cache_layers(self) -> int:
        """A K/V entry a (pass, layer)."""
        return self.passes * self.n_layers

    @property
    def kv_bytes_per_token(self) -> int:
        """K and V of one token in every cache layer, bfloat16."""
        return (self.cache_layers * 2 * self.n_kv_heads * self.head_dim
                * BF16)

    def layer_shapes(self) -> dict:
        """name -> (shape, kind, fan_in) of one layer's leaves."""
        d, f = self.d_model, self.d_ff
        h, hkv, dh = self.n_heads, self.n_kv_heads, self.head_dim
        return {
            "ln1_scale": ((d,), "scale", None),
            "ln1_out_scale": ((d,), "scale", None),
            "ln2_scale": ((d,), "scale", None),
            "ln2_out_scale": ((d,), "scale", None),
            "wq": ((d, h, dh), "matrix", d),
            "wkv": ((d, hkv, 2 * dh), "matrix", d),
            "wo": ((h * dh, d), "matrix", h * dh),
            "wg": ((d, f), "matrix", d),
            "wu": ((d, f), "matrix", d),
            "wd": ((f, d), "matrix", f),
        }

    def top_shapes(self) -> dict:
        d = self.d_model
        return {"embed": ((self.vocab, d), "embed", None),
                "ln_f_scale": ((d,), "scale", None),
                "head": ((d, self.vocab), "matrix", d),
                "gate_w": ((d,), "matrix", d),
                "gate_b": ((), "gate_bias", None)}

    def layer_params(self) -> int:
        return sum(math.prod(s) for s, _, _ in self.layer_shapes().values())

    def n_params(self) -> int:
        return (self.n_layers * self.layer_params()
                + sum(math.prod(s) for s, _, _ in self.top_shapes().values()))


# -- weights --------------------------------------------------------------------

def make_params(seed: int, dims: Dims, dtype, out_shardings=None):
    """The whole tree in ONE jitted call, in the program's arrangement
    (``blocks``: one dict, stacked on [n_layers]: the passes share it).
    Drawn in float32, rounded once to ``dtype``; every norm scale, the
    gate's weight and its bias random."""
    import jax
    import jax.numpy as jnp

    import weights

    def draw(key, shape, kind, fan_in):
        x = jax.random.normal(key, shape, jnp.float32)
        if kind == "matrix":
            x = x * (fan_in ** -0.5)
        elif kind == "embed":
            x = x * EMBED_STD
        elif kind == "gate_bias":
            x = x * GATE_BIAS_STD
        else:
            x = 1.0 + SCALE_STD * x
        return x.astype(dtype)

    def build(key):
        k_top, k_layers = jax.random.split(key)
        top, per = dims.top_shapes(), dims.layer_shapes()
        out = {name: draw(k, *top[name])
               for k, name in zip(jax.random.split(k_top, len(top)), top)}
        out["blocks"] = {
            name: draw(k, (dims.n_layers,) + per[name][0], *per[name][1:])
            for k, name in zip(jax.random.split(k_layers, len(per)), per)}
        return out

    fn = jax.jit(build, out_shardings=out_shardings)
    return fn(jax.random.key(weights.fold_seed(seed)))


def transformer_config(cfg: dict, dims: Dims, **overrides):
    """The program's TransformerConfig for a configuration file of this
    model type (HF key names). Widths go through unchanged."""
    import jax.numpy as jnp

    from distributed_model_parallel_tpu.models.transformer import (
        TransformerConfig,
    )

    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        cfg["torch_dtype"]]
    kw = dict(
        vocab_size=dims.vocab, d_model=dims.d_model, n_heads=dims.n_heads,
        n_kv_heads=dims.n_kv_heads, d_head=dims.head_dim,
        n_layers=dims.n_layers, d_ff=dims.d_ff, max_seq_len=dims.context,
        dtype=dtype, pos_embedding="rope", rope_theta=dims.rope_theta,
        norm="rmsnorm", norm_eps=dims.norm_eps, ffn="swiglu",
        norm_placement="sandwich", n_passes=dims.passes,
        loop_final_norm=True, exit_gate=True,
        early_exit_threshold=float(cfg["early_exit_threshold"]))
    kw.update(overrides)
    return TransformerConfig(**kw), dtype


# -- operations and bytes, from shapes -------------------------------------------

def layer_matmul_params(dims: Dims) -> int:
    """One layer's matrices: q, k and v, the way out, the gated MLP."""
    d, h, hkv, dh = dims.d_model, dims.n_heads, dims.n_kv_heads, dims.head_dim
    return (d * h * dh + d * hkv * 2 * dh + h * dh * d
            + 3 * d * dims.d_ff)


def stack_bytes(dims: Dims) -> int:
    """One read of the stack's weights, norms included, bfloat16: what
    every pass reads again (4.9 GB does not stay in fast memory, and pass
    ``t + 1`` needs pass ``t``'s output)."""
    return dims.n_layers * dims.layer_params() * BF16


def serve_token_flops(dims: Dims, context: int, with_head: bool) -> int:
    """Forward of ONE token that attends ``context`` keys (itself
    included): every per-layer term ``passes`` times (the score and value
    products over ``passes * n_layers`` cache layers), the head once. The
    gate's 2 d a pass (16 K of 20 G) is left out: with one pass the count
    is a plain stack's."""
    f = dims.passes * dims.n_layers * (
        2 * layer_matmul_params(dims)
        + 4 * dims.n_heads * dims.head_dim * context)
    if with_head:
        f += 2 * dims.d_model * dims.vocab
    return f


def prefill_flops(dims: Dims, start: int, n_tokens: int, last: bool) -> int:
    """Forward of prompt positions [start, start + n_tokens): query p
    sees p + 1 keys, in every cache layer."""
    upto = lambda n: n * (n + 1) // 2                          # noqa: E731
    pairs = upto(start + n_tokens) - upto(start)
    f = dims.passes * dims.n_layers * (
        2 * layer_matmul_params(dims) * n_tokens
        + 4 * dims.n_heads * dims.head_dim * pairs)
    if last:
        f += 2 * dims.d_model * dims.vocab
    return f


def paged_decode_least_s(dims: Dims, counters: dict, peaks: dict):
    """The paged decode kernel's least time over the window's decode
    rounds: each live row reads K and V of its context in every cache
    layer (1.5 MiB a token at the published sizes) and does QK^T and PV."""
    import flops

    total = 0.0
    for contexts in counters.get("decode_contexts", ()):
        keys = sum(contexts)
        total += flops.roofline_seconds(
            4 * dims.n_heads * dims.head_dim * keys * dims.cache_layers,
            keys * dims.kv_bytes_per_token, peaks)[0]
    return total or None


def loop_decode_least_s(dims: Dims, counters: dict, peaks: dict):
    """The looped stack's least time over the window's decode rounds: the
    stack's weights read once a PASS (the passes depend on each other and
    the stack does not stay in fast memory, so ``passes`` reads are the
    least), the live contexts' K/V in every cache layer read once, and
    the rows' operations. Bandwidth-bound at any batch the cell runs."""
    import flops

    total = 0.0
    for contexts in counters.get("decode_contexts", ()):
        total += flops.roofline_seconds(
            sum(serve_token_flops(dims, c, False) for c in contexts),
            dims.passes * stack_bytes(dims)
            + sum(contexts) * dims.kv_bytes_per_token, peaks)[0]
    return total or None


def loop_prefill_least_s(dims: Dims, counters: dict, peaks: dict):
    """The looped stack's least time over the window's prefill chunks
    (``counters["prefill_chunks"]``: (start, tokens) a chunk): for each
    the greater of its operations over the peak and its bytes over the
    bandwidth: the stack's weights a pass, and K/V of the chunk's context
    in every cache layer (the keys before it read, its own written)."""
    import flops

    total = 0.0
    for start, n_tokens in counters.get("prefill_chunks", ()):
        total += flops.roofline_seconds(
            prefill_flops(dims, start, n_tokens, False),
            dims.passes * stack_bytes(dims)
            + (start + n_tokens) * dims.kv_bytes_per_token, peaks)[0]
    return total or None


LEAST_SECONDS = {"paged_decode": paged_decode_least_s,
                 "loop_decode": loop_decode_least_s,
                 "loop_prefill": loop_prefill_least_s}


# -- the plain reference ------------------------------------------------------------

def layer_fwd(bp, x, dims: Dims, quant, q_block: int):
    """One layer on one sequence x [T, d]; bp holds the layer's leaves in
    their stored dtype, upcast as they are used."""
    f32 = lambda name: bp[name].astype(F32)                    # noqa: E731
    norm = lambda y, name: rms_norm(y, f32(name), dims.norm_eps)  # noqa: E731
    a = norm(x, "ln1_scale")
    q = linear(a, f32("wq"), quant)                      # [T, H, Dh]
    kv = linear(a, f32("wkv"), quant)                    # [T, Hkv, 2 Dh]
    k, v = kv[..., :dims.head_dim], kv[..., dims.head_dim:]
    q = reference.rope(q, dims.rope_theta)
    k = reference.rope(k, dims.rope_theta)
    o = reference.attention(q, k, v, None, q_block, quant)
    x = x + norm(linear(o.reshape(o.shape[0], -1), f32("wo"), quant),
                 "ln1_out_scale")
    m = norm(x, "ln2_scale")
    return x + norm(gated(m, f32("wg"), f32("wu"), f32("wd"), quant),
                    "ln2_out_scale")


def passes_fwd(params, tokens, *, dims, quant, q_block):
    """Every pass over every layer on one sequence: ``(x [T, d] after the
    last pass's norm, gates [passes, T])``."""
    import jax
    import jax.numpy as jnp

    x = params["embed"][tokens].astype(F32)
    gates = []
    for _ in range(dims.passes):            # the same leaves every pass
        x, _ = jax.lax.scan(
            lambda x, bp: (layer_fwd(bp, x, dims, quant, q_block), None),
            x, params["blocks"])
        x = rms_norm(x, params["ln_f_scale"].astype(F32), dims.norm_eps)
        gates.append(jax.nn.sigmoid(
            jnp.sum(x * params["gate_w"].astype(F32), axis=-1)
            + params["gate_b"].astype(F32)))
    return x, jnp.stack(gates)


def _sequence_logits(params, tokens, rows, *, dims, quant, q_block):
    x, _ = passes_fwd(params, tokens, dims=dims, quant=quant,
                      q_block=q_block)
    return linear(x[rows], params["head"].astype(F32), quant)


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


def exit_probabilities(params, tokens, *, dims):
    """p_exit [passes, T] of one sequence, float32: what the program's
    ``Engine.loop_counters()["exit_mass"]`` sums over the tokens it ran."""
    import jax.numpy as jnp

    _, g = passes_fwd(params, tokens, dims=dims, quant=None, q_block=1024)
    stay = jnp.cumprod(1.0 - g, axis=0)
    before = jnp.concatenate([jnp.ones_like(g[:1]), stay[:-1]])
    return jnp.concatenate([(g * before)[:-1], before[-1:]])
