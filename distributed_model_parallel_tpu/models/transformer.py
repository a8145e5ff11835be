"""Decoder-only Transformer LM — the multi-axis-parallelism flagship.

The reference's zoo is CNN-only, so this model exists for the capabilities the
framework must carry beyond it: tensor parallelism, single-program SPMD
pipelining (homogeneous stacked blocks), and long-context sequence parallelism
(ring attention / Ulysses). It is written as pure functions over an explicit
parameter pytree — not linen — because every parallel path wants direct
control of array layout:

* ``params["blocks"]`` holds all L blocks *stacked* on a leading axis, so
  ``lax.scan`` runs them on one device, the ``stage`` mesh axis shards them
  for the SPMD pipeline, and PartitionSpecs shard head/ffn dims for tensor
  parallelism (Megatron split: column-parallel qkv/ffn-in, row-parallel
  out/ffn-out with a trailing psum).
* attention dispatches on the bound sequence axis: full causal attention by
  default, ring attention inside a ``seq`` shard_map.

Pre-LN, GELU MLP, learned positional embeddings, weight-tied LM head kept
separate (simplicity > tying) is the default block. Other blocks are read
from ``TransformerConfig`` fields, never from a model's name: the norm
(``norm``), the dense FFN (``ffn``), a head size of its own (``d_head``),
QK-norm (a head or the whole vector), where the norms sit
(``norm_placement``), and one ``LayerKind`` a layer where layers differ
(what mixes the tokens: softmax attention, with a window or none, rotated
or not, or the gated delta rule's recurrent state, ops/gated_delta.py;
dense or routed FFN: ``layer_kinds``, arranged by ``layer_plan`` as
leading layers and a scanned period), and how many passes run over the
stack (``n_passes``, ``run_passes``: the same leaves every pass, the
final norm closing each, the exit gate read off each). ``block_apply``
and the serving engine's steps (serve/model.py) take every kind;
``generate`` and its dense cache keep to the default block run once.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from distributed_model_parallel_tpu.ops.ring_attention import (
    full_attention,
    ring_attention,
)

@dataclasses.dataclass(frozen=True)
class LayerKind:
    """What one layer is, beyond the widths all layers share: what mixes
    its tokens (``mixer``: ``"attention"``, softmax attention ``window``
    keys back, None = causal over everything, ``rope`` = rotate q and k;
    or ``"gated_delta"``, the linear-attention layer of ``lin_*`` widths,
    which reads neither) and its FFN (``"dense"`` or ``"moe"``)."""

    window: int | None = None
    rope: bool = False
    ffn: str = "dense"
    mixer: str = "attention"


# Length of the MoE stats vector every block's aux channel carries:
# [load-balance loss, router z-loss, drop rate] (ops/moe._route). Dense
# blocks carry zeros so the channel is shape-uniform across models.
AUX_STATS = 3


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 1024
    d_model: int = 128
    n_heads: int = 4
    n_layers: int = 4
    d_ff: int = 512
    max_seq_len: int = 256
    dtype: Any = jnp.float32
    # Parallelism hooks (None = off). These name mesh axes and only take
    # effect inside a shard_map that binds them.
    tp_axis: str | None = None     # tensor parallel: heads/ffn sharded
    sp_axis: str | None = None     # sequence parallel: ring attention
    sp_impl: str = "ring"          # "ring" | "ulysses"
    # Attention kernel for the non-sequence-parallel path: "auto" consults
    # the measured per-platform dispatch table
    # (ops/pallas_attention._DISPATCH_TABLE — v5e crossover: seq 1024 for
    # both bf16 and f32 with the streamed-K/V kernels). Training uses the
    # FlashAttention-2 backward kernels (score tiles recomputed from the
    # saved logsumexp), so neither direction materializes [T, T] in HBM;
    # fwd+bwd reaches 97 TFLOPS at seq 8k head-dim 128 bf16
    # (benchmarks/grad_sweep_r3_hd128.json; plain XLA cannot compile 8k
    # at all). "xla" / "flash" force one implementation.
    attn_impl: str = "auto"
    # Sliding-window (local) attention: each token attends the last W
    # positions. Training runs on the flash kernels' banded block-skipping
    # (compute O(T*W) both directions; requires attn_impl="flash", no
    # sequence-parallel axis); generation band-masks the prefill and the
    # KV-cache scores with the same (pos-W, pos] band.
    attn_window: int | None = None
    remat: bool = False            # jax.checkpoint each block: recompute
                                   # activations in backward (HBM for FLOPs —
                                   # the long-context memory lever)
    # Remat granularity when remat=True: "full" recomputes the whole block
    # in the backward; "dots" saves matmul/einsum outputs and recomputes
    # only the cheap elementwise ops (jax.checkpoint_policies.
    # dots_with_no_batch_dims_saveable) — most of full-remat's memory win
    # at a fraction of its recompute FLOPs, usually the better MFU point
    # for long-sequence training.
    remat_policy: str = "full"
    # Mixture-of-experts FFN (0 = dense). When > 0 every block's MLP is a
    # top-k routed MoE (ops/moe.py); ep_axis shards experts over the
    # ``expert`` mesh axis inside a shard_map. MoE replaces the FFN, so
    # tp_axis then only shards attention.
    moe_experts: int = 0
    moe_top_k: int = 1
    # Defaults from the committed capacity x aux x z sweep
    # (benchmarks/moe_sweep_r5.json): cf 1.5 + aux 0.05 + z 1e-3 reaches
    # <2% steady-state drop within ~45 training steps at 8x2 experts,
    # ~18% faster than cf 2.0 (smaller expert queues = fewer gathered
    # bytes and smaller FFN batches).
    moe_capacity_factor: float = 1.5
    moe_aux_weight: float = 0.05   # load-balance loss weight in lm_loss
    # Router z-loss weight (ST-MoE): penalizes squared logsumexp of the
    # router logits so they don't drift large (which makes routing
    # saturate and bf16 logits overflow). 0 = off.
    moe_z_weight: float = 1e-3
    ep_axis: str | None = None
    # Positional encoding: "learned" (additive table, the default) or
    # "rope" (rotary: q/k rotated per position inside attention — relative
    # positions, no learned table, extrapolates past the training length).
    # Under sequence parallelism each shard rotates with its global offset.
    pos_embedding: str = "learned"
    rope_theta: float = 10000.0
    # Grouped-query attention: k/v get n_kv_heads heads (must divide
    # n_heads); queries keep n_heads. None = multi-head (k/v fused in
    # wqkv); 1 = multi-query. The KV cache shrinks by n_heads/n_kv_heads —
    # the long-context decode memory lever.
    n_kv_heads: int | None = None
    # Chunked cross-entropy head: compute logits + log-softmax in
    # loss_chunk-token slices under jax.checkpoint so [B, T, V] never
    # materializes (chunked_token_loss) — the long-context TRAINING memory
    # lever on the head side (the head, not attention, is the single-chip
    # HBM ceiling past ~32k tokens). 0 = dense head.
    loss_chunk: int = 0
    # -- the block, from fields (defaults: the Pre-LN LayerNorm GELU block) --
    # Head size where it is not d_model // n_heads (q is then
    # [d_model, n_heads * d_head] and wo [n_heads * d_head, d_model]).
    d_head: int | None = None
    norm: str = "layernorm"        # "layernorm" | "rmsnorm" (f32, no bias)
    norm_eps: float = 1e-5
    # Dense FFN: "gelu" (w1/b1/w2/b2) or "swiglu" (silu(h wg) * (h wu)) wd,
    # bias-free.
    ffn: str = "gelu"
    qk_norm: bool = False          # RMSNorm over each head of q and of k
    # ... or over the whole vector of q and of k, all heads, before the
    # split into heads (scales [H, Dh] and [Hkv, Dh]).
    qk_norm_whole: bool = False
    # Where a sublayer's norm sits: "pre" (on its input, x + f(norm(x))),
    # "post" (on its output, x + norm(f(x)): nothing normalises what
    # the sublayer reads; the leaves are ln1/ln2 either way) or
    # "sandwich" (both, x + norm_out(f(norm(x))): ln1/ln2 on the way in,
    # ln1_out/ln2_out on the way out).
    norm_placement: str = "pre"
    # The looped stack: the layers run ``n_passes`` times, one pass after
    # another over the SAME leaves (``run_passes``); a pass's K/V are its
    # own (serve/paged_kv.CacheLayout). ``loop_final_norm``: the final
    # norm closes every pass, so the next pass and the head read the
    # normed stream (``unembed`` then norms nothing). ``exit_gate``:
    # leaves ``gate_w`` [d], ``gate_b`` []; sigmoid(x . gate_w + gate_b)
    # on each pass's output is the probability of leaving the loop there.
    # ``early_exit_threshold``: only 1.0 (every row runs every pass; the
    # gate is counted, not acted on).
    n_passes: int = 1
    loop_final_norm: bool = False
    exit_gate: bool = False
    early_exit_threshold: float = 1.0
    # The gated-delta layers (LayerKind.mixer == "gated_delta"): key and
    # value heads (values a multiple of keys: a key head serves a group),
    # their sizes, the short convolution's length, and whether beta spans
    # (0, 2) (a state transition with negative eigenvalues) or (0, 1).
    lin_key_heads: int = 0
    lin_value_heads: int = 0
    lin_key_dim: int = 0
    lin_value_dim: int = 0
    lin_conv: int = 4
    lin_neg_eigval: bool = False
    # One LayerKind a layer where layers differ (window or none, rotated
    # or not, dense or routed FFN); None = every layer alike, from
    # attn_window / pos_embedding / moe_experts above.
    layer_kinds: tuple | None = None
    # The routed layer. moe_dropless: every chosen expert computes (no
    # capacity, ops/moe.moe_ffn_dropless: the serving path); otherwise the
    # capacity-dropping softmax layer above (training). The fields below
    # are read by the dropless layer only.
    moe_dropless: bool = False
    moe_scoring: str = "softmax"   # "softmax" | "sigmoid"
    moe_norm_topk: bool = True     # chosen scores normalised to sum 1
    moe_routed_scale: float = 1.0  # ... then scaled
    moe_router_bias: bool = False  # per-expert bias, for the choice only
    moe_d_ff: int | None = None    # expert width (None = d_ff)
    moe_shared_experts: int = 0    # always-on experts of that width
    # (first, count): which of the moe_experts this chip holds (expert
    # parallelism's share); None = all. The router keeps its full width.
    moe_experts_held: tuple | None = None

    def __post_init__(self):
        if self.norm not in ("layernorm", "rmsnorm"):
            raise ValueError(f"unknown norm {self.norm!r}")
        if self.ffn not in ("gelu", "swiglu"):
            raise ValueError(f"unknown ffn {self.ffn!r}")
        if self.norm_placement not in ("pre", "post", "sandwich"):
            raise ValueError(
                f"unknown norm_placement {self.norm_placement!r}")
        if self.n_passes < 1:
            raise ValueError(f"n_passes must be >= 1, got {self.n_passes}")
        if self.early_exit_threshold != 1.0:
            raise NotImplementedError(
                f"early_exit_threshold={self.early_exit_threshold}: only 1.0 "
                f"is run (every row takes every pass). Rows leaving the loop "
                f"at different passes (a decode round whose rows take "
                f"unequal work) and the cache entries of the passes they "
                f"skip are not written (serve/model._layers, "
                f"serve/scheduler.py)")
        if any(k.mixer == "gated_delta" for k in self.layer_kinds or ()):
            if min(self.lin_key_heads, self.lin_key_dim,
                   self.lin_value_dim) < 1 or (
                    self.lin_value_heads % max(1, self.lin_key_heads)):
                raise ValueError(
                    "gated-delta layers need lin_key_heads, lin_key_dim, "
                    "lin_value_dim and lin_value_heads, a multiple of "
                    "lin_key_heads")
        if (self.layer_kinds is not None
                and len(self.layer_kinds) != self.n_layers):
            raise ValueError(
                f"layer_kinds names {len(self.layer_kinds)} layers, "
                f"n_layers is {self.n_layers}")
        if self.attn_window is not None and self.attn_window < 1:
            raise ValueError(
                f"attn_window must be >= 1, got {self.attn_window}")
        if self.loss_chunk < 0:
            raise ValueError(
                f"loss_chunk must be >= 0 (0 = dense head), got "
                f"{self.loss_chunk}")

    @property
    def head_dim(self) -> int:
        return (self.d_head if self.d_head is not None
                else self.d_model // self.n_heads)

    @property
    def lin_channels(self) -> int:
        """q | k | v of a gated-delta layer: the convolution's channels."""
        return (2 * self.lin_key_heads * self.lin_key_dim
                + self.lin_value_heads * self.lin_value_dim)

    @property
    def kinds(self) -> tuple:
        """One LayerKind a layer."""
        if self.layer_kinds is not None:
            return tuple(self.layer_kinds)
        return (LayerKind(self.attn_window, self.pos_embedding == "rope",
                          "moe" if self.moe_experts else "dense"),
                ) * self.n_layers

    @property
    def layer_plan(self) -> tuple:
        """(n_lead, period, n_periods): the layers as ``n_lead`` leading
        ones and then ``n_periods`` repeats of a pattern ``period`` long,
        the split with the fewest distinct layer bodies (a stack of equal
        layers is (0, 1, n_layers)). ``run_layers`` unrolls the leading
        layers and one period and scans over the repeats."""
        kinds, n = self.kinds, self.n_layers
        best = None
        for lead in range(n):
            rest = kinds[lead:]
            for p in range(1, len(rest) + 1):
                if len(rest) % p == 0 and rest == rest[:p] * (len(rest) // p):
                    if best is None or lead + p < best[0] + best[1]:
                        best = (lead, p, len(rest) // p)
                    break
        return best

    @property
    def homogeneous(self) -> bool:
        return self.layer_plan[:2] == (0, 1)

    @property
    def looped(self) -> bool:
        """Whether anything of the looped stack is on: more than one
        pass, the norm that closes a pass, or the exit gate."""
        return self.n_passes > 1 or self.loop_final_norm or self.exit_gate

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads if self.n_kv_heads is not None else self.n_heads

    @property
    def gqa(self) -> bool:
        return self.n_kv_heads is not None

    @property
    def moe(self) -> "MoEConfig | None":
        if not self.moe_experts:
            return None
        from distributed_model_parallel_tpu.ops.moe import MoEConfig
        return MoEConfig(num_experts=self.moe_experts, d_model=self.d_model,
                         d_ff=self.moe_d_ff or self.d_ff,
                         top_k=self.moe_top_k,
                         capacity_factor=self.moe_capacity_factor,
                         normalize_gates=self.moe_norm_topk,
                         scoring=self.moe_scoring,
                         routed_scale=self.moe_routed_scale,
                         held=self.moe_experts_held)


def _init_attention(k, cfg: TransformerConfig, stack, L: int) -> dict:
    """The attention leaves of ``L`` stacked layers."""
    d, dt = cfg.d_model, cfg.dtype
    hd = cfg.n_heads * cfg.head_dim
    out = {"wo": stack(k[3], (hd, d), hd)}
    if cfg.qk_norm_whole:
        out["q_norm"] = jnp.ones((L, cfg.n_heads, cfg.head_dim), dt)
        out["k_norm"] = jnp.ones((L, cfg.kv_heads, cfg.head_dim), dt)
    elif cfg.qk_norm:
        out["q_norm"] = jnp.ones((L, cfg.head_dim), dt)
        out["k_norm"] = jnp.ones((L, cfg.head_dim), dt)
    if cfg.gqa:
        if not (1 <= cfg.kv_heads <= cfg.n_heads):
            raise ValueError(f"n_kv_heads={cfg.kv_heads} must be in "
                             f"[1, n_heads={cfg.n_heads}]")
        if cfg.n_heads % cfg.kv_heads:
            raise ValueError(f"n_kv_heads={cfg.kv_heads} must divide "
                             f"n_heads={cfg.n_heads}")
        out["wq"] = stack(k[2], (d, cfg.n_heads, cfg.head_dim), d)
        out["wkv"] = stack(jax.random.fold_in(k[2], 1),
                           (d, cfg.kv_heads, 2 * cfg.head_dim), d)
    else:
        # [d, H, 3*Dh]: head dim explicit so tensor parallelism shards
        # whole heads (column-parallel over the H axis).
        out["wqkv"] = stack(k[2], (d, cfg.n_heads, 3 * cfg.head_dim), d)
    return out


def _init_gated_delta(key, cfg: TransformerConfig, stack, L: int) -> dict:
    """The gated-delta leaves of ``L`` stacked layers: the projections to
    q | k | v (``lin_wqkv``, the convolution's channels), to the output
    gate, and to the decay's and the write strength's inputs, a head
    each; the depthwise convolution; ``A_log`` and ``dt_bias`` of the
    decay ``exp(-exp(A_log) softplus(a + dt_bias))``, drawn as the rule's
    authors do (``A`` uniform in (0, 16), ``dt`` log-uniform in (0.001,
    0.1)); the gated norm's scale over a value head; the way out."""
    d, dt = cfg.d_model, cfg.dtype
    hv, ch = cfg.lin_value_heads, cfg.lin_channels
    ks = jax.random.split(key, 8)
    a = jax.random.uniform(ks[5], (L, hv), jnp.float32, 1e-3, 16.0)
    step = jnp.exp(jax.random.uniform(ks[6], (L, hv), jnp.float32,
                                      jnp.log(1e-3), jnp.log(0.1)))
    return {
        "lin_wqkv": stack(ks[0], (d, ch), d),
        "lin_wgate": stack(ks[1], (d, hv * cfg.lin_value_dim), d),
        "lin_wa": stack(ks[2], (d, hv), d),
        "lin_wb": stack(ks[3], (d, hv), d),
        "lin_conv": stack(ks[4], (cfg.lin_conv, ch), cfg.lin_conv),
        "lin_A_log": jnp.log(a),
        "lin_dt_bias": step + jnp.log(-jnp.expm1(-step)),   # softplus^-1
        "lin_norm": jnp.ones((L, cfg.lin_value_dim), dt),
        "lin_wo": stack(ks[7], (hv * cfg.lin_value_dim, d),
                        hv * cfg.lin_value_dim),
    }


def _init_blocks(k, cfg: TransformerConfig, kind: LayerKind, L: int) -> dict:
    """``L`` layers of one kind, stacked on a leading axis. ``k``: 8 keys
    (the default block draws from them as it always has)."""
    d, f = cfg.d_model, cfg.d_ff
    dt = cfg.dtype

    def stack(key, shape, fan_in):
        return jax.random.normal(key, (L,) + shape, dt) * (fan_in ** -0.5)

    blocks = {
        "ln1_scale": jnp.ones((L, d), dt),
        "ln2_scale": jnp.ones((L, d), dt),
    }
    if cfg.norm == "layernorm":
        blocks["ln1_bias"] = jnp.zeros((L, d), dt)
        blocks["ln2_bias"] = jnp.zeros((L, d), dt)
    if cfg.norm_placement == "sandwich":
        for name in ("ln1_out", "ln2_out"):
            blocks[name + "_scale"] = jnp.ones((L, d), dt)
            if cfg.norm == "layernorm":
                blocks[name + "_bias"] = jnp.zeros((L, d), dt)
    if kind.mixer == "gated_delta":
        blocks.update(_init_gated_delta(k[2], cfg, stack, L))
    else:
        blocks.update(_init_attention(k, cfg, stack, L))
    if kind.ffn == "moe" and cfg.moe_dropless:
        E, fe = cfg.moe_experts, cfg.moe_d_ff or f
        G = cfg.moe_experts_held[1] if cfg.moe_experts_held else E
        blocks.update({
            "router": stack(k[4], (d, E), d),
            "we_g": stack(k[5], (G, d, fe), d),
            "we_u": stack(jax.random.fold_in(k[5], 1), (G, d, fe), d),
            "we_d": stack(k[7], (G, fe, d), fe),
        })
        if cfg.moe_router_bias:
            blocks["router_bias"] = jnp.zeros((L, E), dt)
        if cfg.moe_shared_experts:
            fs = fe * cfg.moe_shared_experts
            blocks.update({
                "ws_g": stack(jax.random.fold_in(k[4], 1), (d, fs), d),
                "ws_u": stack(jax.random.fold_in(k[4], 2), (d, fs), d),
                "ws_d": stack(jax.random.fold_in(k[4], 3), (fs, d), fs),
            })
    elif kind.ffn == "moe":
        E = cfg.moe_experts
        blocks.update({
            "router": stack(k[4], (d, E), d),
            "w_in": stack(k[5], (E, d, f), d),
            "w_out": stack(k[7], (E, f, d), f),
        })
    elif cfg.ffn == "swiglu":
        blocks.update({
            "wg": stack(k[4], (d, f), d),
            "wu": stack(jax.random.fold_in(k[4], 1), (d, f), d),
            "wd": stack(k[5], (f, d), f),
        })
    else:
        blocks.update({
            "w1": stack(k[4], (d, f), d),
            "b1": jnp.zeros((L, f), dt),
            "w2": stack(k[5], (f, d), f),
            "b2": jnp.zeros((L, d), dt),
        })
    return blocks


def init_params(rng: jax.Array, cfg: TransformerConfig) -> dict:
    """Parameter pytree. A stack of equal layers: ``blocks`` is one dict,
    stacked on a leading [n_layers] axis (``n_layers`` of them however
    many passes run over them). Where layers differ
    (``cfg.layer_plan``): ``lead`` is a tuple of single layers' dicts and
    ``blocks`` a tuple with one dict a position of the period, each
    stacked on [n_periods]."""
    k = jax.random.split(rng, 8)
    d = cfg.d_model
    dt = cfg.dtype
    n_lead, period, n_periods = cfg.layer_plan
    kinds = cfg.kinds
    out = {
        "embed": jax.random.normal(k[0], (cfg.vocab_size, d), dt) * 0.02,
        "ln_f_scale": jnp.ones((d,), dt),
        "head": jax.random.normal(k[6], (d, cfg.vocab_size), dt)
        * (d ** -0.5),
    }
    if cfg.norm == "layernorm":
        out["ln_f_bias"] = jnp.zeros((d,), dt)
    if cfg.exit_gate:
        out["gate_w"] = jax.random.normal(
            jax.random.fold_in(k[6], 1), (d,), dt) * (d ** -0.5)
        out["gate_b"] = jnp.zeros((), dt)
    if cfg.homogeneous:
        out["blocks"] = _init_blocks(k, cfg, kinds[0], cfg.n_layers)
    else:
        def keys(i):
            return jax.random.split(jax.random.fold_in(rng, 1 + i), 8)

        out["lead"] = tuple(
            jax.tree.map(lambda a: a[0],
                         _init_blocks(keys(i), cfg, kinds[i], 1))
            for i in range(n_lead))
        out["blocks"] = tuple(
            _init_blocks(keys(n_lead + i), cfg, kinds[n_lead + i],
                         n_periods) for i in range(period))
    if cfg.pos_embedding == "learned":
        out["pos"] = jax.random.normal(k[1], (cfg.max_seq_len, d), dt) * 0.02
    elif cfg.pos_embedding != "rope":
        raise ValueError(f"unknown pos_embedding {cfg.pos_embedding!r}")
    return out


def layer_norm(x, scale, bias, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def rms_norm(x, scale, eps=1e-5):
    """x / sqrt(mean(x^2) + eps) * scale, computed in float32."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                           + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _norm(tree: dict, name: str, x, cfg: TransformerConfig):
    """The configuration's norm with the leaves ``<name>_scale`` (and
    ``<name>_bias``) of ``tree``."""
    if cfg.norm == "rmsnorm":
        return rms_norm(x, tree[name + "_scale"], cfg.norm_eps)
    return layer_norm(x, tree[name + "_scale"], tree[name + "_bias"],
                      cfg.norm_eps)


def apply_rope(x: jax.Array, positions: jax.Array,
               theta: float = 10000.0) -> jax.Array:
    """Rotary position embedding (GPT-NeoX half-split convention).

    x: [B, T, H, Dh] (Dh even), positions: [T] absolute token positions
    shared across the batch, or [B, T] per-row positions (the serving
    engine's continuous decode batch, where every row sits at its own
    offset). Rotates each (x[..., i], x[..., i + Dh/2]) pair by
    position * theta^(-2i/Dh); q·k then depends only on relative
    position, which is what makes the per-shard global offsets under
    sequence parallelism (and the per-step offsets in cached decoding)
    compose exactly with full attention.
    """
    dh = x.shape[-1]
    if dh % 2:
        raise ValueError(f"RoPE needs an even head_dim, got {dh}")
    inv_freq = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    # [T, Dh/2] (shared) or [B, T, Dh/2] (per-row); the trailing [T, 1, F]
    # broadcast shape is the same either way.
    ang = positions.astype(jnp.float32)[..., :, None] * inv_freq
    cos = jnp.cos(ang)[..., :, None, :]
    sin = jnp.sin(ang)[..., :, None, :]
    if positions.ndim == 1:
        cos, sin = cos[None], sin[None]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _rope_qk(q: jax.Array, k: jax.Array, cfg: TransformerConfig
             ) -> tuple[jax.Array, jax.Array]:
    """Rotate q/k for the training path. Inside a sequence-parallel
    shard_map each shard covers [i*T_local, (i+1)*T_local); outside, the
    (global) sequence starts at 0."""
    t = q.shape[1]
    start = (jax.lax.axis_index(cfg.sp_axis) * t
             if cfg.sp_axis is not None else 0)
    positions = start + jnp.arange(t)
    return (apply_rope(q, positions, cfg.rope_theta),
            apply_rope(k, positions, cfg.rope_theta))


def _in_proj(bp: dict, name: str, h: jax.Array) -> jax.Array:
    """``h [B,T,d]`` through the in-projection ``name``: the stored leaf
    ``[d,H,X]``, or ``name_t [H,X,d]`` where the layer holds that instead
    (the serving engine's layout, serve/model.in_proj_d_last: the
    product's right-hand side as XLA:TPU takes it, ``d`` minor). The same
    contraction in the same types either way."""
    if name + "_t" in bp:
        return jnp.einsum("btd,hxd->bthx", h, bp[name + "_t"])
    return jnp.einsum("btd,dhx->bthx", h, bp[name])


def _qkv_proj(bp: dict, h: jax.Array, cfg: TransformerConfig):
    """Project to q [B,T,H(_local),Dh] and k/v [B,T,Hkv(_local),Dh] —
    fused wqkv for multi-head, separate wq/wkv for grouped-query. One
    helper for training, prefill, and cached decode so they never
    diverge."""
    if cfg.gqa:
        q = _in_proj(bp, "wq", h)
        k, v = jnp.split(_in_proj(bp, "wkv", h), 2, axis=-1)
    else:
        q, k, v = jnp.split(_in_proj(bp, "wqkv", h), 3, axis=-1)
    if cfg.qk_norm_whole:
        # over all heads at once: the mean runs over the whole vector
        whole = lambda x, g: rms_norm(                        # noqa: E731
            x.reshape(*x.shape[:-2], -1), g.reshape(-1),
            cfg.norm_eps).reshape(x.shape)
        q, k = whole(q, bp["q_norm"]), whole(k, bp["k_norm"])
    elif cfg.qk_norm:
        q = rms_norm(q, bp["q_norm"], cfg.norm_eps)
        k = rms_norm(k, bp["k_norm"], cfg.norm_eps)
    return q, k, v


def _repeat_kv(x: jax.Array, q: jax.Array) -> jax.Array:
    """Broadcast kv heads up to the query head count ([..., Hkv, Dh] ->
    [..., H, Dh]). The group factor comes from *local* shapes so it is
    correct under tensor-parallel head sharding."""
    groups = q.shape[2] // x.shape[2]
    return x if groups == 1 else jnp.repeat(x, groups, axis=2)


def _attention(q, k, v, cfg: TransformerConfig, window: int | None):
    if cfg.sp_axis is not None:
        if window is not None:
            raise ValueError(
                "attn_window is not supported with sequence parallelism")
        if cfg.sp_impl == "ring":
            return ring_attention(q, k, v, cfg.sp_axis, causal=True,
                                  impl=cfg.attn_impl)
        from distributed_model_parallel_tpu.ops.ring_attention import (
            ulysses_attention,
        )
        return ulysses_attention(q, k, v, cfg.sp_axis, causal=True,
                                 impl=cfg.attn_impl)
    from distributed_model_parallel_tpu.ops.pallas_attention import (
        flash_attention,
        should_use_flash,
    )
    if window is not None:
        # Banded compute lives in the flash kernels (both directions);
        # there is no windowed XLA fallback, so the knob forces flash.
        if cfg.attn_impl != "flash":
            raise ValueError(
                "attn_window requires attn_impl='flash' (the banded "
                "block-skipping lives in the pallas kernels)")
        return flash_attention(q, k, v, causal=True, window=window)
    if should_use_flash(q.shape[1], causal=True, impl=cfg.attn_impl,
                        head_dim=q.shape[-1], dtype=q.dtype):
        return flash_attention(q, k, v, causal=True)
    return full_attention(q, k, v, causal=True)


def sublayer_in(bp: dict, name: str, x, cfg: TransformerConfig):
    """What a sublayer reads: the normed stream, or under
    ``norm_placement="post"`` the stream as it arrives."""
    return x if cfg.norm_placement == "post" else _norm(bp, name, x, cfg)


def sublayer_out(bp: dict, name: str, y, cfg: TransformerConfig):
    """What a sublayer adds to the stream: its result, under
    ``norm_placement="post"`` the norm of it (the leaves ``<name>``),
    under ``"sandwich"`` the norm of it too (``<name>_out``: ``<name>``
    normed what the sublayer read)."""
    if cfg.norm_placement == "post":
        return _norm(bp, name, y, cfg)
    if cfg.norm_placement == "sandwich":
        return _norm(bp, name + "_out", y, cfg)
    return y


def gated_delta_inputs(bp: dict, h: jax.Array, cfg: TransformerConfig,
                       tail: jax.Array, n_valid: jax.Array):
    """A gated-delta layer up to its rule, for h [B, C, d]: the six
    projections, the short causal convolution over q | k | v (``tail``
    [B, K - 1, ch]: the inputs before this call's; ``n_valid`` [B]: how
    many of the C tokens exist), unit keys and queries (``q / sqrt(dk)``
    besides), the decay and the write strength a head. Returns ``(q, k
    [B, C, Hv, dk], v [B, C, Hv, dv], log_alpha, beta [B, C, Hv]
    float32, gate [B, C, Hv * dv], tail1)``; a key head's q and k are
    repeated for the value heads it serves. One definition for the full
    forward (``block_apply``) and the serving steps."""
    from distributed_model_parallel_tpu.ops.gated_delta import causal_conv

    b, c = h.shape[:2]
    hk, hv = cfg.lin_key_heads, cfg.lin_value_heads
    dk, dv = cfg.lin_key_dim, cfg.lin_value_dim
    f32 = jnp.float32
    with jax.named_scope("linattn_proj"):
        u = h @ bp["lin_wqkv"]
        gate = h @ bp["lin_wgate"]
        a = jnp.einsum("bcd,dh->bch", h, bp["lin_wa"],
                       preferred_element_type=f32)
        bb = jnp.einsum("bcd,dh->bch", h, bp["lin_wb"],
                        preferred_element_type=f32)
    with jax.named_scope("linattn_conv"):
        cv, tail1 = causal_conv(u, bp["lin_conv"], tail, n_valid)
        q, k, v = jnp.split(cv, [hk * dk, 2 * hk * dk], axis=-1)
        unit = lambda x: x / (jnp.sqrt(jnp.sum(                # noqa: E731
            jnp.square(x), axis=-1, keepdims=True)) + 1e-6)
        q = unit(q.reshape(b, c, hk, dk)) * dk ** -0.5
        k = unit(k.reshape(b, c, hk, dk))
        if hv != hk:
            q = jnp.repeat(q, hv // hk, axis=2)
            k = jnp.repeat(k, hv // hk, axis=2)
        v = v.reshape(b, c, hv, dv)
        log_alpha = -jnp.exp(bp["lin_A_log"].astype(f32)) * jax.nn.softplus(
            a + bp["lin_dt_bias"].astype(f32))
        beta = jax.nn.sigmoid(bb) * (2.0 if cfg.lin_neg_eigval else 1.0)
    dt = h.dtype
    return (q.astype(dt), k.astype(dt), v.astype(dt), log_alpha, beta,
            gate, tail1)


def gated_delta_output(bp: dict, o: jax.Array, gate: jax.Array,
                       cfg: TransformerConfig) -> jax.Array:
    """A gated-delta layer after its rule: o [B, C, Hv, dv] float32
    through the gated norm (RMSNorm over a head, times ``silu(gate)``)
    and the way out, -> [B, C, d]."""
    b, c = o.shape[:2]
    with jax.named_scope("linattn_gate"):
        o = rms_norm(o, bp["lin_norm"], cfg.norm_eps) * jax.nn.silu(
            gate.reshape(o.shape).astype(jnp.float32))
    with jax.named_scope("linattn_proj"):
        return o.reshape(b, c, -1).astype(gate.dtype) @ bp["lin_wo"]


def _gated_delta_full(bp: dict, h: jax.Array, cfg: TransformerConfig):
    """The gated-delta mixing of whole sequences h [B, T, d], each from
    its own start: no tail, a zero state."""
    from distributed_model_parallel_tpu.ops.gated_delta import (
        gated_delta_chunk,
    )

    b, t = h.shape[:2]
    q, k, v, log_alpha, beta, gate, _ = gated_delta_inputs(
        bp, h, cfg,
        jnp.zeros((b, cfg.lin_conv - 1, cfg.lin_channels), h.dtype),
        jnp.full((b,), t, jnp.int32))
    with jax.named_scope("linattn_rule"):
        o, _ = gated_delta_chunk(
            q, k, v, log_alpha, beta,
            jnp.zeros((b, cfg.lin_value_heads, cfg.lin_key_dim,
                       cfg.lin_value_dim), jnp.float32),
            jnp.ones((b, t), bool))
    return gated_delta_output(bp, o, gate, cfg)


def block_apply(bp: dict, x: jax.Array, cfg: TransformerConfig,
                kind: LayerKind | None = None
                ) -> tuple[jax.Array, jax.Array]:
    """One transformer block on [B, T(_local), d]. ``bp`` holds *unstacked*
    per-layer arrays (a leaf slice of params["blocks"]); ``kind`` says
    what this layer is where layers differ (None: ``cfg.kinds[0]``).
    Returns ``(x, aux)`` where ``aux`` is the MoE load-balance loss (0
    for dense).

    Tensor parallelism: when ``cfg.tp_axis`` is bound, wqkv/w1 arrive
    column-sharded and wo/w2 row-sharded (shard_map hands each device its
    slice); the two psums below complete the Megatron pattern.
    """
    b, t, d = x.shape
    kind = cfg.kinds[0] if kind is None else kind

    h = sublayer_in(bp, "ln1", x, cfg)
    if kind.mixer == "gated_delta":
        if cfg.tp_axis is not None or cfg.sp_axis is not None:
            raise NotImplementedError(
                "the gated-delta layer runs on one chip: its tensor- and "
                "sequence-parallel forms are not written (ROADMAP M6)")
        o = _gated_delta_full(bp, h, cfg)
    else:
        q, k, v = _qkv_proj(bp, h, cfg)      # q:[B,T,H,Dh] kv:[B,T,Hkv,Dh]
        if kind.rope:
            q, k = _rope_qk(q, k, cfg)
        k, v = _repeat_kv(k, q), _repeat_kv(v, q)
        o = _attention(q, k, v, cfg, kind.window)  # [B,T,H_local,Dh]
        o = o.reshape(b, t, -1) @ bp["wo"]   # row-parallel: partial sums
        if cfg.tp_axis is not None:
            o = jax.lax.psum(o, cfg.tp_axis)
    x = x + sublayer_out(bp, "ln1", o, cfg)

    h = sublayer_in(bp, "ln2", x, cfg)
    h, aux = _ffn(bp, h, cfg, tp_axis=cfg.tp_axis, ep_axis=cfg.ep_axis,
                  kind=kind)
    return x + sublayer_out(bp, "ln2", h, cfg), aux


def _gated(h, wg, wu, wd):
    """(silu(h wg) * (h wu)) wd, bias-free."""
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def _ffn(bp: dict, h: jax.Array, cfg: TransformerConfig, *,
         tp_axis: str | None, ep_axis: str | None,
         kind: LayerKind | None = None, valid=None):
    """Post-attention MLP tail, shared by the training path
    (``block_apply``), cached decoding (``_decode_block``) and the paged
    steps (serve/model.py) so they cannot diverge. Returns (y, aux): the
    [AUX_STATS] losses, or for the dropless routed layer its counters
    (ops/moe.moe_ffn_dropless; ``valid`` [B, T] says which tokens count
    and are routed at all)."""
    kind = cfg.kinds[0] if kind is None else kind
    if kind.ffn == "moe" and cfg.moe_dropless:
        from distributed_model_parallel_tpu.ops.moe import moe_ffn_dropless
        if tp_axis is not None or ep_axis is not None:
            raise NotImplementedError(
                "the dropless routed layer runs on one chip: its tensor-"
                "and expert-parallel forms are not written (ROADMAP M2)")
        y, counts = moe_ffn_dropless(bp, h, cfg.moe, valid=valid)
        if cfg.moe_shared_experts:
            with jax.named_scope("moe_shared"):
                y = y + _gated(h, bp["ws_g"], bp["ws_u"], bp["ws_d"])
        return y, counts
    if kind.ffn == "moe":
        from distributed_model_parallel_tpu.ops.moe import moe_ffn
        y, aux = moe_ffn(
            {"router": bp["router"], "w_in": bp["w_in"],
             "w_out": bp["w_out"]},
            h, cfg.moe, ep_axis=ep_axis)
        return y, aux.astype(jnp.float32)
    if cfg.ffn == "swiglu":
        y = _gated(h, bp["wg"], bp["wu"], bp["wd"])
        if tp_axis is not None:
            y = jax.lax.psum(y, tp_axis)
        return y, jnp.zeros((AUX_STATS,), jnp.float32)
    y = jax.nn.gelu(h @ bp["w1"] + bp["b1"])
    y = y @ bp["w2"]
    if tp_axis is not None:
        y = jax.lax.psum(y, tp_axis)
    y = y + bp["b2"]                         # bias added once, post-psum
    return y, jnp.zeros((AUX_STATS,), jnp.float32)


def run_layers(params: dict, carry, fn, cfg: TransformerConfig):
    """Every layer in order. ``fn(bp, kind, body, rep, carry) -> (carry,
    out)`` is one layer: ``bp`` its unstacked leaves, ``body`` (static)
    which of the ``n_lead + period`` distinct layer bodies of
    ``cfg.layer_plan`` it is, ``rep`` which repeat of the period (traced
    under the scan; 0 for a leading layer), so the layer's index is
    ``body + rep * period``. Leading layers and the positions of one
    period are unrolled; the repeats are one ``lax.scan`` (a stack of
    equal layers is a scan over all of them, as it always was; a single
    repeat is unrolled too, which keeps every index static). Returns
    ``(carry, outs)``: one ``out`` a body, those of the period stacked
    on [n_periods]."""
    n_lead, period, _ = cfg.layer_plan
    kinds = cfg.kinds
    outs = []
    for i in range(n_lead):
        carry, o = fn(params["lead"][i], kinds[i], i, 0, carry)
        outs.append(o)
    blocks = params["blocks"]
    if isinstance(blocks, dict):
        blocks = (blocks,)
    # the stack's own length: a pipeline stage hands in its slice
    n_periods = jax.tree.leaves(blocks[0])[0].shape[0]

    def body(carry, xs):
        bps, rep = xs
        got = []
        for i in range(period):
            carry, o = fn(bps[i], kinds[n_lead + i], n_lead + i, rep, carry)
            got.append(o)
        return carry, tuple(got)

    if n_periods == 1:
        carry, got = body(carry, (jax.tree.map(lambda a: a[0], blocks), 0))
        got = jax.tree.map(lambda a: a[None], got)
    else:
        carry, got = jax.lax.scan(body, carry,
                                  (tuple(blocks), jnp.arange(n_periods)))
    return carry, tuple(outs) + tuple(got)


def exit_gate(params: dict, x: jax.Array) -> jax.Array:
    """The looped stack's exit gate on a pass's output x [..., d]:
    ``sigmoid(x . gate_w + gate_b)`` [...], float32: the probability of
    leaving the loop after this pass, given that the row is still in."""
    with jax.named_scope("exit_gate"):
        z = jnp.einsum("...d,d->...", x, params["gate_w"],
                       preferred_element_type=jnp.float32)
        return jax.nn.sigmoid(z + params["gate_b"].astype(jnp.float32))


def exit_distribution(gates: jax.Array) -> jax.Array:
    """gates [T, ...] (``exit_gate`` of each pass) -> p_exit [T, ...]:
    ``p_exit(t) = g_t * prod_{j<t} (1 - g_j)``, the last pass taking what
    is left, ``prod_{j<T-1} (1 - g_j)``: sums to 1 over T."""
    still_in = jnp.cumprod(1.0 - gates, axis=0)
    before = jnp.concatenate([jnp.ones_like(gates[:1]), still_in[:-1]])
    return jnp.concatenate([(gates * before)[:-1], before[-1:]])


def run_passes(params: dict, x: jax.Array, rest, fn,
               cfg: TransformerConfig):
    """All passes over all layers: THE definition of the looped stack,
    for the full forward (``layers_forward``) and the serving steps
    (serve/model._layers). ``fn(t, bp, kind, body, rep, (x, rest)) ->
    ((x, rest), out)`` is one layer as ``run_layers`` takes it, told
    which pass ``t`` it runs in; ``rest`` is whatever rides beside the
    stream (the serving steps' pools; None). Every pass walks the same
    leaves of ``params``; ``cfg.loop_final_norm`` closes each pass with
    the final norm, and ``cfg.exit_gate`` reads the gate off each pass's
    output. Returns ``(x, rest, outs, gates)``.

    A configuration with nothing of the loop on (``not cfg.looped``) is
    ``run_layers`` and nothing else: ``outs`` as it gives them, ``gates``
    None. Otherwise the passes are ONE ``lax.scan`` around the layer
    walk, so the stack's body is traced and compiled once whatever
    ``n_passes`` (``t`` is traced, the weights are closed over, ``rest``
    is in the carry: pools written in place stay in place); ``outs`` and
    ``gates`` ([T, ...] float32, None without the gate) are stacked on a
    leading [n_passes] axis."""
    if not cfg.looped:
        (x, rest), outs = run_layers(params, (x, rest),
                                     functools.partial(fn, 0), cfg)
        return x, rest, outs, None

    def one_pass(carry, t):
        with jax.named_scope("loop_stack"):
            (x, rest), outs = run_layers(params, carry,
                                         functools.partial(fn, t), cfg)
            if cfg.loop_final_norm:
                x = _norm(params, "ln_f", x, cfg)
        gate = exit_gate(params, x) if cfg.exit_gate else None
        return (x, rest), (outs, gate)

    (x, rest), (outs, gates) = jax.lax.scan(
        one_pass, (x, rest), jnp.arange(cfg.n_passes))
    return x, rest, outs, gates


def blocks_scan(blocks: dict, x: jax.Array, cfg: TransformerConfig
                ) -> tuple[jax.Array, jax.Array]:
    """Run stacked blocks of equal layers (single device, or a pipeline
    stage's slice of them). Returns ``(x, aux)``; aux is the mean
    per-layer MoE load-balance loss."""
    return layers_forward({"blocks": blocks}, x, cfg)


def layers_forward(params: dict, x: jax.Array, cfg: TransformerConfig
                   ) -> tuple[jax.Array, jax.Array]:
    """Every layer of ``params`` on x, every pass of them
    (``run_passes``: ``lead`` and ``blocks`` as ``init_params`` arranges
    them). Returns ``(x, aux)`` like :func:`blocks_scan`."""
    apply = block_apply
    if cfg.remat:
        if cfg.remat_policy == "dots":
            policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        elif cfg.remat_policy == "full":
            policy = None
        else:
            raise ValueError(f"unknown remat_policy {cfg.remat_policy!r}; "
                             f"known: full, dots")
        apply = jax.checkpoint(block_apply, static_argnums=(2, 3),
                               policy=policy)

    def layer(t, bp, kind, body, rep, carry):
        x, aux = apply(bp, carry[0], cfg, kind)
        if kind.ffn == "moe" and cfg.moe_dropless:
            # the dropless layer's counters are not a loss
            aux = jnp.zeros((AUX_STATS,), jnp.float32)
        return (x, None), aux

    out, _, auxes, _ = run_passes(params, x, None, layer, cfg)
    auxes = jnp.concatenate([a.reshape(-1, AUX_STATS) for a in auxes])
    return out, jnp.mean(auxes, axis=0)       # [AUX_STATS], mean over layers


def embed(params: dict, tokens: jax.Array, cfg: TransformerConfig,
          *, pos_offset: int = 0) -> jax.Array:
    if cfg.pos_embedding == "rope":
        # Positions enter through q/k rotation in attention, not the embed.
        # The rotation path (_rope_qk) counts from 0 (or the shard's global
        # offset), so an embed-level offset cannot be honored — reject it
        # loudly rather than return silently mis-rotated logits. Cached
        # decoding handles its own offsets (generate/forward_one).
        if pos_offset:
            raise ValueError(
                "pos_offset is not supported with pos_embedding='rope'; "
                "use generate() for offset (cached) decoding")
        return params["embed"][tokens]
    t = tokens.shape[1]
    pos = jax.lax.dynamic_slice_in_dim(params["pos"], pos_offset, t)
    return params["embed"][tokens] + pos[None]


def unembed(params: dict, x: jax.Array,
            cfg: TransformerConfig | None = None) -> jax.Array:
    """Final norm and head (``cfg`` None: the default LayerNorm). Under
    ``cfg.loop_final_norm`` the last pass already closed with the norm
    (``run_passes``): the head reads x as it is."""
    if cfg is None:
        x = layer_norm(x, params["ln_f_scale"], params["ln_f_bias"])
    elif not cfg.loop_final_norm:
        x = _norm(params, "ln_f", x, cfg)
    return x @ params["head"]


def hidden_with_aux(params: dict, tokens: jax.Array, cfg: TransformerConfig,
                    *, pos_offset: int = 0) -> tuple[jax.Array, jax.Array]:
    """Forward up to the final hidden states: [B, T] int tokens ->
    ([B, T, d] pre-head activations, moe aux loss). Shared by the dense
    head (``apply_with_aux``) and the chunked head (``lm_loss`` with
    ``loss_chunk``) so the two paths cannot drift."""
    x = embed(params, tokens, cfg, pos_offset=pos_offset)
    return layers_forward(params, x, cfg)


def apply_with_aux(params: dict, tokens: jax.Array, cfg: TransformerConfig,
                   *, pos_offset: int = 0) -> tuple[jax.Array, jax.Array]:
    """Full forward: [B, T] int tokens -> ([B, T, V] logits, moe aux loss)."""
    x, aux = hidden_with_aux(params, tokens, cfg, pos_offset=pos_offset)
    return unembed(params, x, cfg), aux


def apply(params: dict, tokens: jax.Array, cfg: TransformerConfig,
          *, pos_offset: int = 0) -> jax.Array:
    """Full forward: [B, T] int tokens -> [B, T, V] logits."""
    return apply_with_aux(params, tokens, cfg, pos_offset=pos_offset)[0]


def aux_loss(aux: jax.Array, cfg: TransformerConfig) -> jax.Array:
    """Weighted scalar loss contribution of the [AUX_STATS] stats vector:
    balance and z are loss terms with their own weights; drop rate is a
    metric only (zero-gradient by construction)."""
    return (cfg.moe_aux_weight * aux[0]
            + cfg.moe_z_weight * aux[1])


def token_loss(logits: jax.Array, targets: jax.Array, aux: jax.Array,
               cfg: TransformerConfig) -> jax.Array:
    """Mean next-token cross-entropy + weighted MoE auxiliary losses.
    The single shared loss for the single-device and SPMD-pipeline paths
    (their parity is what tests compare)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll) + aux_loss(aux, cfg)


def chunked_nll_sum(params: dict, x: jax.Array, targets: jax.Array,
                    chunk: int) -> jax.Array:
    """SUM of next-token NLL over ``unembed(x)`` without ever materializing
    the ``[B, T, V]`` logits tensor.

    At long context the single-chip HBM ceiling is the vocabulary head,
    not attention: seq-64k x 32k-vocab logits are 4.3 GB bf16 plus f32
    softmax temporaries (measured: the seq-64k train step wants 20.7 GB
    on a 15.8 GB v5e with the dense head; flash attention itself is
    O(T)). This scans the sequence in ``chunk``-token slices, computing
    each slice's logits + log-softmax inside a ``jax.checkpoint`` region
    so the backward rematerializes them per chunk: peak memory drops to
    O(B * chunk * V) for one extra head forward of recompute (the same
    FLOPs-for-HBM trade the block remat makes; the fused-linear-CE trick,
    expressed as scan + remat instead of a custom kernel).

    Sum units so callers pick their own normalization: the dense-head-
    equivalent mean loss (``chunked_token_loss``) and the SPMD 1F1B head
    (``parallel/spmd_pipeline``, which accumulates sums across microbatches
    and shards) share this one definition."""
    b, t, d = x.shape
    if t % chunk:
        raise ValueError(f"seq len {t} not divisible by loss_chunk={chunk}")
    n = t // chunk
    xs = x.reshape(b, n, chunk, d).swapaxes(0, 1)        # [n, B, c, D]
    ts = targets.reshape(b, n, chunk).swapaxes(0, 1)     # [n, B, c]

    @jax.checkpoint
    def body(carry, xt):
        xc, tc = xt
        logp = jax.nn.log_softmax(unembed(params, xc).astype(jnp.float32),
                                  axis=-1)
        nll = -jnp.take_along_axis(logp, tc[..., None], axis=-1)[..., 0]
        return carry + nll.sum(), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xs, ts))
    return total


def chunked_token_loss(params: dict, x: jax.Array, targets: jax.Array,
                       aux: jax.Array, cfg: TransformerConfig,
                       chunk: int) -> jax.Array:
    """``token_loss`` over ``unembed(x)`` via ``chunked_nll_sum`` — the
    [B, T, V] logits never materialize (see that docstring)."""
    b, t, _ = x.shape
    return (chunked_nll_sum(params, x, targets, chunk) / (b * t)
            + aux_loss(aux, cfg))


def lm_loss(params: dict, tokens: jax.Array, targets: jax.Array,
            cfg: TransformerConfig) -> jax.Array:
    """Mean next-token cross-entropy (+ weighted MoE load-balance loss)."""
    if cfg.loss_chunk:
        x, aux = hidden_with_aux(params, tokens, cfg)
        return chunked_token_loss(params, x, targets, aux, cfg,
                                  cfg.loss_chunk)
    logits, aux = apply_with_aux(params, tokens, cfg)
    return token_loss(logits, targets, aux, cfg)


def _require_default_block(cfg: TransformerConfig, what: str) -> None:
    """``generate`` and its dense cache know the default block only
    (LayerNorm, the GELU or capacity-routed FFN, every layer alike); the
    other kinds are served through ``serve.Engine`` (ROADMAP M1)."""
    if cfg.looped:
        raise NotImplementedError(
            f"{what} runs a stack once: a looped stack (n_passes="
            f"{cfg.n_passes}, loop_final_norm={cfg.loop_final_norm}, "
            f"exit_gate={cfg.exit_gate}) would take a dense cache "
            f"n_passes x n_layers deep and the walk repeated over it; "
            f"serve this configuration through serve.Engine")
    if (not cfg.homogeneous or cfg.norm != "layernorm" or cfg.ffn != "gelu"
            or cfg.qk_norm or cfg.qk_norm_whole or cfg.moe_dropless
            or cfg.norm_placement != "pre"
            or cfg.kinds[0].mixer != "attention"):
        raise NotImplementedError(
            f"{what} runs the default block only (LayerNorm, GELU or "
            f"capacity-routed FFN, all layers alike); serve this "
            f"configuration through serve.Engine")


def _cached_block(bp: dict, ck: jax.Array, cv: jax.Array, layer: jax.Array,
                  x: jax.Array, positions: jax.Array,
                  cfg: TransformerConfig, *,
                  tp_axis: str | None = None, read_len: int | None = None):
    """One block for C contiguous token positions with a STACKED KV cache.

    x: [B, C, d]; positions: [C] absolute positions (contiguous);
    ck/cv: [L, B, T_total, Hkv, Dh] — ALL layers' caches (kv heads only,
    the GQA memory win; Hkv is the LOCAL head count under tensor
    parallelism); ``layer`` (traced scalar) selects this block's slab.
    Returns (x, ck, cv) with the [layer, :, positions] slab updated.

    The whole stack stays in the enclosing scan's CARRY and this function
    writes one [B, C, Hkv, Dh] slab — so XLA updates the cache buffer in
    place across layers and steps. The pre-round-5 layout (per-layer
    caches as scan xs with stacked ys outputs) forced a full-cache
    materialization every decode step: ~25% of decode device time was
    whole-cache copies (hardware trace).

    ``read_len`` (static) scores against only the first ``read_len``
    cache positions instead of the whole padding — callers guarantee
    every attended position is below it (``generate`` decodes in
    read-boundary segments); the masked unwritten tail was pure wasted
    HBM reads. Masking stays position-index based, so shapes are static
    under scan. C=1 is the decode step; C=chunk is chunked prefill
    (scores peak at O(C * read_len) instead of O(T0^2)).

    ``tp_axis`` enables the Megatron psums (wo and the dense FFN) when the
    block runs inside a shard_map with head-sharded weights — the decode
    counterpart of ``block_apply``'s training-path psums.
    """
    b, c = x.shape[:2]
    total = ck.shape[2]
    _require_default_block(cfg, "the dense-cache decode path")

    h = layer_norm(x, bp["ln1_scale"], bp["ln1_bias"])
    q, k, v = _qkv_proj(bp, h, cfg)      # q:[B,C,H,Dh] kv:[B,C,Hkv,Dh]
    if cfg.pos_embedding == "rope":
        # The cache holds *rotated* keys (prefill rotates too), so one
        # rotation at insert time makes scores relative-position correct.
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype)[None],
                                      (layer, 0, positions[0], 0, 0))
    cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype)[None],
                                      (layer, 0, positions[0], 0, 0))
    rl = total if read_len is None else min(read_len, total)
    # This layer's written prefix (reads AFTER the write above, so the
    # current positions' keys are included in the scores).
    kr = jax.lax.dynamic_slice(
        ck, (layer, 0, 0, 0, 0), (1, *ck.shape[1:]))[0, :, :rl]
    vr = jax.lax.dynamic_slice(
        cv, (layer, 0, 0, 0, 0), (1, *cv.shape[1:]))[0, :, :rl]
    # Grouped scores: query head h attends kv head h // G (G=1 for MHA),
    # matching _repeat_kv's head mapping in the training path.
    hkv = ck.shape[3]
    qg = q.reshape(b, c, hkv, q.shape[2] // hkv, cfg.head_dim)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kr) * (cfg.head_dim ** -0.5)
    # Same (pos - W, pos] band predicate as the training kernels
    # (ops/pallas_attention.band_keep; pure causal when attn_window=None) —
    # it also masks the cache's not-yet-written tail (key pos > query pos).
    from distributed_model_parallel_tpu.ops.pallas_attention import band_keep

    keep = band_keep(positions[:, None], jnp.arange(rl)[None, :],
                     cfg.attn_window)                  # [C, rl]
    s = jnp.where(keep[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, vr)         # [B,C,Hkv,G,Dh]
    o = o.reshape(b, c, -1) @ bp["wo"]
    if tp_axis is not None:
        o = jax.lax.psum(o, tp_axis)
    x = x + o

    h = layer_norm(x, bp["ln2_scale"], bp["ln2_bias"])
    h, _ = _ffn(bp, h, cfg, tp_axis=tp_axis, ep_axis=None)
    return x + h, ck, cv


# Decode read-boundary segment size: each segment's scan reads the cache
# prefix up to the next multiple of this, so a decode step's K/V bytes
# follow the live length in steps of this size, not max_seq_len.
DECODE_READ_SEG = 256


def _filter_top_k(logits: jax.Array, k: int) -> jax.Array:
    """Mask all but the k highest logits to -inf (k static; [B, V])."""
    kth = jax.lax.top_k(logits, k)[0][:, -1:]
    return jnp.where(logits < kth, -jnp.inf, logits)


def _filter_top_p(logits: jax.Array, p: float) -> jax.Array:
    """Nucleus filtering: keep the smallest set of tokens whose cumulative
    probability reaches p (always at least the top token). Static-shape:
    argsort, exclusive cumulative softmax mass, scatter the per-rank keep
    mask back through the sort permutation — a value threshold would also
    keep any token whose logit *ties* the last-kept one, letting duplicate
    logits outside the nucleus leak into the sampling set."""
    b, v = logits.shape
    order = jnp.argsort(logits, axis=-1)[:, ::-1]        # descending ranks
    sorted_logits = jnp.take_along_axis(logits, order, axis=-1)
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    # Exclusive cumsum: a token is kept if the mass *before* it is < p.
    keep_sorted = (jnp.cumsum(probs, axis=-1) - probs) < p
    keep = jnp.zeros((b, v), bool).at[
        jnp.arange(b)[:, None], order].set(keep_sorted)
    return jnp.where(keep, logits, -jnp.inf)


def validate_sampling(cfg: TransformerConfig, temperature: float,
                      top_k: int | None, top_p: float | None) -> None:
    """The one set of sampling-knob rules ``generate`` and the serving
    engine (serve/engine.py) both enforce."""
    if (top_k is not None or top_p is not None) and temperature <= 0:
        raise ValueError("top_k/top_p filter the sampling distribution; "
                         "set temperature > 0 (greedy ignores them)")
    if top_k is not None and not (1 <= top_k <= cfg.vocab_size):
        raise ValueError(f"top_k must be in [1, {cfg.vocab_size}], got {top_k}")
    if top_p is not None and not (0.0 < top_p <= 1.0):
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")


def make_sampler(cfg: TransformerConfig, temperature: float,
                 top_k: int | None, top_p: float | None):
    """``sample(logits [B, V], key) -> [B] int32``: greedy argmax at
    temperature 0, else temperature/top-k/nucleus sampling — the single
    token-selection definition ``generate`` and the serving engine share
    (one ``key`` drives the whole batch; per-row-keyed callers vmap it)."""
    validate_sampling(cfg, temperature, top_k, top_p)

    def sample(logits, sub):
        if temperature > 0:
            logits = logits / temperature
            if top_k is not None:
                logits = _filter_top_k(logits, top_k)
            if top_p is not None:
                logits = _filter_top_p(logits, top_p)
            return jax.random.categorical(sub, logits).astype(jnp.int32)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    return sample


def generate(params: dict, cfg: TransformerConfig, prompt: jax.Array,
             steps: int, *, rng: jax.Array | None = None,
             temperature: float = 0.0, top_k: int | None = None,
             top_p: float | None = None, tp_axis: str | None = None,
             prefill_chunk: int | None = None) -> jax.Array:
    """Autoregressive decoding with a per-layer KV cache.

    prompt: [B, T0] int32 -> [B, T0 + steps]. Greedy when temperature == 0,
    else softmax sampling at the given temperature, optionally filtered by
    ``top_k`` (keep the k best tokens) and/or ``top_p`` (nucleus: smallest
    set reaching cumulative probability p) — both static-shape jittable.
    The whole decode is jittable: one ``lax.scan`` per 256-position
    read-boundary segment (DECODE_READ_SEG; each segment's step reads
    only the block-quantized written cache prefix — static shapes, cache
    updated in place via dynamic_update_slice), the TPU-native
    replacement for a Python token-by-token loop. Long generations
    compile one small scan per segment.

    ``tp_axis`` runs the cached blocks tensor-parallel: call inside a
    shard_map whose block weights are head-sharded over that axis (the
    training layout — ``generate_sharded`` wraps this) and the KV cache
    holds only the local heads while wo/FFN psums complete each block.
    ``prefill_chunk`` processes the prompt in C-token slices against the
    growing cache instead of one [T0, T0]-score batched forward: same
    FLOPs, peak attention memory O(C * T_total) — the long-prompt lever.

    The reference has no inference path at all; this rounds out the LM
    tooling the flagship model needs.
    """
    _require_default_block(cfg, "generate()")
    b, t0 = prompt.shape
    total = t0 + steps
    if steps < 1:
        raise ValueError("steps must be >= 1")
    if total > cfg.max_seq_len:
        raise ValueError(f"prompt + steps = {total} exceeds max_seq_len "
                         f"{cfg.max_seq_len}")
    if prefill_chunk is not None:
        if prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got "
                             f"{prefill_chunk}")
        if t0 % prefill_chunk:
            raise ValueError(f"prompt length {t0} not divisible by "
                             f"prefill_chunk={prefill_chunk}")
    if rng is None:
        rng = jax.random.key(0)
    sample = make_sampler(cfg, temperature, top_k, top_p)

    rng, sub = jax.random.split(rng)
    if prefill_chunk is not None:
        # -- Chunked prefill: run each C-token slice of the prompt through
        # every layer's cached block (intra-slice causality and the band
        # come from the shared position mask), writing the cache as it
        # goes. The batched path's [T0, T0] score tensor never exists.
        hkv = (params["blocks"]["wkv"].shape[2] if cfg.gqa
               else params["blocks"]["wqkv"].shape[2])   # LOCAL kv heads
        cache_k = jnp.zeros((cfg.n_layers, b, total, hkv, cfg.head_dim),
                            cfg.dtype)
        cache_v = jnp.zeros_like(cache_k)
        n_chunks = t0 // prefill_chunk
        toks_c = prompt.reshape(b, n_chunks, prefill_chunk).swapaxes(0, 1)

        def chunk_step(carry, xs):
            cache_k, cache_v = carry
            toks, j = xs
            positions = j * prefill_chunk + jnp.arange(prefill_chunk)
            x = params["embed"][toks]
            if cfg.pos_embedding == "learned":
                x = x + jax.lax.dynamic_slice_in_dim(
                    params["pos"], j * prefill_chunk, prefill_chunk)[None]

            def layer(carry2, xs2):
                x, ck, cv = carry2
                bp, li = xs2
                x, ck, cv = _cached_block(bp, ck, cv, li, x, positions,
                                          cfg, tp_axis=tp_axis)
                return (x, ck, cv), None

            (x, cache_k, cache_v), _ = jax.lax.scan(
                layer, (x, cache_k, cache_v),
                (params["blocks"], jnp.arange(cfg.n_layers)))
            return (cache_k, cache_v), unembed(params, x[:, -1:])[:, 0]

        (cache_k, cache_v), chunk_logits = jax.lax.scan(
            chunk_step, (cache_k, cache_v),
            (toks_c, jnp.arange(n_chunks)))
        tok0 = sample(chunk_logits[-1], sub)     # token at position t0
    else:
        # -- Batched prefill: one forward over the whole prompt fills every
        # layer's KV cache at once (O(1) forwards, not O(t0) steps).
        x = embed(params, prompt, cfg)

        def prefill_layer(x, bp):
            h = layer_norm(x, bp["ln1_scale"], bp["ln1_bias"])
            q, k, v = _qkv_proj(bp, h, cfg)    # kv carry Hkv heads
            if cfg.pos_embedding == "rope":
                positions = jnp.arange(t0)
                q = apply_rope(q, positions, cfg.rope_theta)
                k = apply_rope(k, positions, cfg.rope_theta)
            # Cache Hkv-head k/v; attention runs on broadcast heads.
            kr, vr = _repeat_kv(k, q), _repeat_kv(v, q)
            if cfg.attn_window is None:
                o = full_attention(q, kr, vr, causal=True)
            else:
                # Banded prefill: the shared band predicate keeps this,
                # the cached decode, and the training kernels on one
                # definition. Prompts are short, so the explicit mask is
                # fine here.
                from distributed_model_parallel_tpu.ops.pallas_attention import (
                    band_keep,
                )

                s = (jnp.einsum("bqhd,bkhd->bhqk", q, kr)
                     * (cfg.head_dim ** -0.5))
                posa = jnp.arange(t0)
                keep = band_keep(posa[:, None], posa[None, :],
                                 cfg.attn_window)
                s = jnp.where(keep[None, None], s, -jnp.inf)
                o = jnp.einsum("bhqk,bkhd->bqhd",
                               jax.nn.softmax(s, axis=-1).astype(q.dtype),
                               vr)
            o = o.reshape(b, t0, -1) @ bp["wo"]
            if tp_axis is not None:
                o = jax.lax.psum(o, tp_axis)
            x = x + o
            h = layer_norm(x, bp["ln2_scale"], bp["ln2_bias"])
            h, _ = _ffn(bp, h, cfg, tp_axis=tp_axis, ep_axis=None)
            return x + h, (k.astype(cfg.dtype), v.astype(cfg.dtype))

        x, (ks, vs) = jax.lax.scan(prefill_layer, x, params["blocks"])
        pad = [(0, 0), (0, 0), (0, total - t0), (0, 0), (0, 0)]
        cache_k = jnp.pad(ks, pad)               # [L, B, total, Hkv, Dh]
        cache_v = jnp.pad(vs, pad)
        tok0 = sample(unembed(params, x)[:, -1], sub)  # token at position t0

    # -- Decode: one cached step per new position.
    def forward_one(cache_k, cache_v, tok, pos, read_len):
        x = params["embed"][tok][:, None, :]
        if cfg.pos_embedding == "learned":
            x = x + jax.lax.dynamic_slice_in_dim(params["pos"], pos, 1)[None]

        def layer(carry, xs):
            x, ck, cv = carry
            bp, li = xs
            x, ck, cv = _cached_block(bp, ck, cv, li, x,
                                      jnp.reshape(pos, (1,)), cfg,
                                      tp_axis=tp_axis, read_len=read_len)
            return (x, ck, cv), None

        (x, cache_k, cache_v), _ = jax.lax.scan(
            layer, (x, cache_k, cache_v),
            (params["blocks"], jnp.arange(cfg.n_layers)))
        return unembed(params, x)[:, 0], cache_k, cache_v   # [B, V]

    def make_body(read_len):
        def body(carry, pos):
            cache_k, cache_v, tok, rng = carry
            logits, cache_k, cache_v = forward_one(cache_k, cache_v, tok,
                                                   pos, read_len)
            rng, sub = jax.random.split(rng)
            tok_next = sample(logits, sub)
            return (cache_k, cache_v, tok_next, rng), tok_next
        return body

    # Positions t0 .. total-2 consume tokens t0 .. total-2 and emit
    # tokens t0+1 .. total-1 (steps-1 of them; tok0 is already emitted).
    # Decoding runs in READ-BOUNDARY SEGMENTS: position p only attends
    # keys 0..p, so a scan whose positions all sit below a static boundary
    # reads just that cache prefix — the written part plus <SEG slack —
    # instead of the full padded [total] every step. Decode is HBM-bound
    # on exactly that read; the masked-out tail was pure wasted bandwidth.
    # Each boundary compiles its own small scan.
    SEG = DECODE_READ_SEG
    parts = []
    carry = (cache_k, cache_v, tok0, rng)
    p = t0
    while p < total - 1:
        hi = min(total, (p // SEG + 1) * SEG)
        p_end = min(total - 1, hi)          # positions p..p_end-1 read <=hi
        carry, toks_seg = jax.lax.scan(
            make_body(hi), carry, jnp.arange(p, p_end))
        parts.append(toks_seg)
        p = p_end
    toks = jnp.concatenate(parts, axis=0) if parts else \
        jnp.zeros((0, b), jnp.int32)
    return jnp.concatenate([prompt, tok0[:, None], toks.T], axis=1)


def generate_sharded(params: dict, cfg: TransformerConfig, prompt: jax.Array,
                     steps: int, spec, *, rng: jax.Array | None = None,
                     temperature: float = 0.0, top_k: int | None = None,
                     top_p: float | None = None,
                     prefill_chunk: int | None = None) -> jax.Array:
    """``generate`` under a device mesh: batch over ``data``, heads over
    ``model`` (tensor-parallel KV cache — each device caches only its local
    kv heads; wo/FFN psums complete each block, exactly the training
    layout from ``parallel/tensor_parallel.block_specs``).

    Greedy decoding is token-identical to replicated ``generate``
    (tests/test_generate_sharded.py). Sampled decoding folds the data-shard
    index into the key (ADVICE r4: a replicated key would draw identical
    noise on every shard — correlated samples across the batch), so under a
    sharded batch the streams are independent but differ from the
    replicated run's per-row split; the psum'd logits themselves are
    bit-identical across the model axis.

    A model trained tp-sharded no longer has to be gathered onto one
    device to decode (the r3 gap: a 256k-token model the framework could
    train but not serve sharded).
    """
    from jax.sharding import NamedSharding

    from distributed_model_parallel_tpu.parallel.tensor_parallel import (
        kv_heads_shardable,
        param_specs,
    )

    if cfg.moe_experts and cfg.ep_axis:
        raise ValueError("expert-parallel decode is not implemented; "
                         "decode with experts replicated (ep_axis=None)")
    # Decode ignores the pipeline axis: blocks stay layer-stacked on every
    # device (stage_axis=None), sharded over model only.
    pspecs = param_specs(None, cfg.tp_axis,
                         moe=bool(cfg.moe_experts), ep_axis=None,
                         learned_pos=cfg.pos_embedding == "learned",
                         gqa=cfg.gqa,
                         shard_kv=kv_heads_shardable(cfg, spec))
    params = jax.tree.map(
        lambda x, ps: jax.device_put(x, NamedSharding(spec.mesh, ps)),
        params, pspecs, is_leaf=lambda x: isinstance(x, P))
    if rng is None:
        rng = jax.random.key(0)

    # Static: fold only when >1 shard exists — fold_in(rng, 0) != rng, so
    # a size-1 axis would needlessly diverge from replicated sampling.
    fold_data = (spec.data_axis is not None
                 and spec.mesh.shape[spec.data_axis] > 1)

    def body(params, prompt, rng):
        # Each data shard must sample an independent stream: the rng enters
        # replicated (in_specs P()), so without folding in the shard index
        # every shard would draw IDENTICAL noise for its (different) rows —
        # correlated samples across the batch at temperature > 0.
        if fold_data:
            rng = jax.random.fold_in(
                rng, jax.lax.axis_index(spec.data_axis))
        return generate(params, cfg, prompt, steps, rng=rng,
                        temperature=temperature, top_k=top_k, top_p=top_p,
                        tp_axis=cfg.tp_axis, prefill_chunk=prefill_chunk)

    fn = jax.shard_map(
        body, mesh=spec.mesh,
        in_specs=(pspecs, P(spec.data_axis), P()),
        out_specs=P(spec.data_axis),
        check_vma=False)
    return fn(params, prompt, rng)


def build_transformer(model_config) -> "TransformerConfig":
    """Registry adapter: ModelConfig.extra carries TransformerConfig fields."""
    extra = dict(model_config.extra)
    extra.setdefault("vocab_size", max(model_config.num_classes, 32))
    return TransformerConfig(**extra)
