"""Weights from the seed, made by the benchmark: one jitted call, on the
device, in the dtype they are trained or served in.

The tree layout is the program's parameter interface
(``models/transformer.init_params`` with RoPE and grouped-query
attention): the benchmark hands this tree to the trainer or the engine in
place of the program's own init, and hands the very same values to the
plain reference. Unlike the program's init, every LayerNorm scale and
bias and every MLP bias is random, so that a fault in any of them shows.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes one chip holds of a configuration (the chip's share under
    tensor parallelism is cut by the program's shardings, not here)."""

    vocab: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    n_layers: int
    rope_theta: float
    window: int | None
    norm_eps: float
    context: int

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        d, h = cfg["hidden_size"], cfg["num_attention_heads"]
        if d % h:
            raise ValueError(f"hidden_size {d} not divisible by heads {h}")
        return cls(vocab=cfg["vocab_size"], d_model=d, n_heads=h,
                   n_kv_heads=cfg["num_key_value_heads"], head_dim=d // h,
                   d_ff=cfg["intermediate_size"],
                   n_layers=cfg["num_hidden_layers"],
                   rope_theta=float(cfg["rope_theta"]),
                   window=cfg.get("sliding_window"),
                   norm_eps=float(cfg["norm_epsilon"]),
                   context=cfg["max_position_embeddings"])

    def leaf_shapes(self) -> dict:
        """name -> (shape, kind, fan_in); block leaves carry the leading
        layer axis."""
        L, d, f = self.n_layers, self.d_model, self.d_ff
        h, hkv, dh = self.n_heads, self.n_kv_heads, self.head_dim
        return {
            "embed": ((self.vocab, d), "embed", None),
            "blocks/ln1_scale": ((L, d), "scale", None),
            "blocks/ln1_bias": ((L, d), "bias", None),
            "blocks/wq": ((L, d, h, dh), "matrix", d),
            "blocks/wkv": ((L, d, hkv, 2 * dh), "matrix", d),
            "blocks/wo": ((L, h * dh, d), "matrix", h * dh),
            "blocks/ln2_scale": ((L, d), "scale", None),
            "blocks/ln2_bias": ((L, d), "bias", None),
            "blocks/w1": ((L, d, f), "matrix", d),
            "blocks/b1": ((L, f), "bias", None),
            "blocks/w2": ((L, f, d), "matrix", f),
            "blocks/b2": ((L, d), "bias", None),
            "ln_f_scale": ((d,), "scale", None),
            "ln_f_bias": ((d,), "bias", None),
            "head": ((d, self.vocab), "matrix", d),
        }

    def n_params(self) -> int:
        import math
        return sum(math.prod(s) for s, _, _ in self.leaf_shapes().values())


EMBED_STD = 0.018     # StarCoder2's published initializer_range
BIAS_STD = 0.02
SCALE_STD = 0.1


def fold_seed(seed: int) -> int:
    """--seed may exceed 31 bits; JAX keys and numpy both take this."""
    return int(seed) % (2 ** 31 - 1)


def make_params(seed: int, dims: Dims, dtype, out_shardings=None):
    """The whole tree in ONE jitted call. Values are drawn in float32 and
    rounded once to ``dtype``."""
    import jax
    import jax.numpy as jnp

    shapes = dims.leaf_shapes()
    names = list(shapes)

    def build(key):
        keys = jax.random.split(key, len(names))
        out = {"blocks": {}}
        for k, name in zip(keys, names):
            shape, kind, fan_in = shapes[name]
            x = jax.random.normal(k, shape, jnp.float32)
            if kind == "matrix":
                x = x * (fan_in ** -0.5)
            elif kind == "embed":
                x = x * EMBED_STD
            elif kind == "bias":
                x = x * BIAS_STD
            else:
                x = 1.0 + SCALE_STD * x
            x = x.astype(dtype)
            if name.startswith("blocks/"):
                out["blocks"][name.split("/", 1)[1]] = x
            else:
                out[name] = x
        return out

    fn = jax.jit(build, out_shardings=out_shardings)
    return fn(jax.random.key(fold_seed(seed)))
