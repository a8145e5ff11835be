"""What both drivers need: the program's model configuration from a
configuration file, and freeing the device before the reference runs."""

from __future__ import annotations

import gc


def transformer_config(cfg: dict, dims, **overrides):
    """The program's TransformerConfig for a configuration file (HF key
    names). Widths go through unchanged."""
    import jax.numpy as jnp

    from distributed_model_parallel_tpu.models.transformer import (
        TransformerConfig,
    )

    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        cfg["torch_dtype"]]
    kw = dict(vocab_size=dims.vocab, d_model=dims.d_model,
              n_heads=dims.n_heads, n_layers=dims.n_layers, d_ff=dims.d_ff,
              max_seq_len=dims.context, dtype=dtype, pos_embedding="rope",
              rope_theta=dims.rope_theta, n_kv_heads=dims.n_kv_heads,
              attn_window=dims.window)
    kw.update(overrides)
    return TransformerConfig(**kw), dtype


def free_device_memory(*holders) -> None:
    """Drop the program's state and compiled programs so that the
    reference has the chip to itself (the peak was read before)."""
    import jax

    for h in holders:
        for k in list(vars(h)):
            try:
                delattr(h, k)
            except AttributeError:
                pass
    gc.collect()
    jax.clear_caches()
    gc.collect()
