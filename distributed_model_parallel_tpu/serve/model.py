"""Paged prefill/decode forward over ``models/transformer`` params.

Two jitted programs serve every request shape:

* the **prefill step** runs one fixed-size chunk of one request's prompt
  against the growing paged cache (the final partial chunk is padded and
  its writes dropped), so any prompt length reuses one compiled program —
  the compile-cache story behind the CLI satellite;
* the **decode step** advances every active slot one token. It is
  compiled at the engine's fixed slot width with idle slots masked
  (writes dropped via out-of-range page ids), which is what makes a
  request's tokens independent of who shares the batch: same program,
  row-independent math, own pages — a mid-batch join decodes bitwise
  what a solo run would.

The block math is ``models/transformer``'s own pieces (``_qkv_proj``,
``apply_rope``, ``layer_norm``, ``_ffn``, ``unembed``) with the dense
cache's write/read swapped for the page pool
(``ops/paged_attention``) — the training/decode definitions stay single-
source. MoE FFNs are rejected by the engine: expert capacity dropping
couples co-resident tokens, which would break per-request determinism.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from distributed_model_parallel_tpu.models.transformer import (
    TransformerConfig,
    _ffn,
    _qkv_proj,
    apply_rope,
    layer_norm,
    make_sampler,
    unembed,
)
from distributed_model_parallel_tpu.ops.paged_attention import (
    paged_attention,
)


def paged_block(bp: dict, ck: jax.Array, cv: jax.Array, layer: jax.Array,
                x: jax.Array, positions: jax.Array, write_pages: jax.Array,
                write_offsets: jax.Array, tables: jax.Array,
                lengths: jax.Array, cfg: TransformerConfig, *,
                impl: str) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One transformer block over the paged cache.

    x: [B, C, d]; positions: [B, C] absolute; write_pages/write_offsets:
    [B, C] physical (page, offset) per token — an out-of-range page id
    drops the write (idle slots, prompt padding); tables: [B, N];
    lengths: [B] valid K prefix (after this step's writes); ck/cv:
    [L, P, page, Hkv, Dh] pools, ``layer`` (traced) selects the slab.
    The paged counterpart of ``transformer._cached_block``.
    """
    b, c = x.shape[:2]
    h = layer_norm(x, bp["ln1_scale"], bp["ln1_bias"])
    q, k, v = _qkv_proj(bp, h, cfg)          # q:[B,C,H,Dh] kv:[B,C,Hkv,Dh]
    if cfg.pos_embedding == "rope":
        # Per-row positions: the continuous batch has every row at its
        # own offset. The cache stores rotated keys, like the dense path.
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    ck = ck.at[layer, write_pages, write_offsets].set(
        k.astype(ck.dtype), mode="drop")
    cv = cv.at[layer, write_pages, write_offsets].set(
        v.astype(cv.dtype), mode="drop")
    kp = jax.lax.dynamic_index_in_dim(ck, layer, 0, keepdims=False)
    vp = jax.lax.dynamic_index_in_dim(cv, layer, 0, keepdims=False)
    o = paged_attention(q, kp, vp, tables, positions, lengths,
                        window=cfg.attn_window, impl=impl)
    x = x + o.reshape(b, c, -1) @ bp["wo"]
    h = layer_norm(x, bp["ln2_scale"], bp["ln2_bias"])
    h, _ = _ffn(bp, h, cfg, tp_axis=None, ep_axis=None)
    return x + h, ck, cv


def _layers_scan(params: dict, ck, cv, x, positions, write_pages,
                 write_offsets, tables, lengths, cfg, impl):
    def layer(carry, xs):
        x, ck, cv = carry
        bp, li = xs
        x, ck, cv = paged_block(bp, ck, cv, li, x, positions, write_pages,
                                write_offsets, tables, lengths, cfg,
                                impl=impl)
        return (x, ck, cv), None

    (x, ck, cv), _ = jax.lax.scan(
        layer, (x, ck, cv),
        (params["blocks"], jnp.arange(cfg.n_layers)))
    return x, ck, cv


def _embed_rows(params: dict, tokens: jax.Array, positions: jax.Array,
                cfg: TransformerConfig) -> jax.Array:
    """[B, C] tokens at per-row absolute positions -> [B, C, d]. Learned
    positions gather per row (clipped: padded prefill tails may index
    past the table; their rows are never read)."""
    x = params["embed"][tokens]
    if cfg.pos_embedding == "learned":
        idx = jnp.clip(positions, 0, cfg.max_seq_len - 1)
        x = x + params["pos"][idx]
    return x


@functools.lru_cache(maxsize=64)
def make_prefill_step(cfg: TransformerConfig, *, page_size: int,
                      n_pages: int, chunk: int, impl: str,
                      temperature: float = 0.0, top_k: int | None = None,
                      top_p: float | None = None):
    """One request's prompt chunk against the paged cache.

    Returns ``step(params, ck, cv, tokens [1, chunk], pos0, n_valid,
    table [N], key) -> (ck, cv, next_token [1])``. ``pos0``/``n_valid``
    are traced scalars, so every chunk of every prompt length hits one
    compiled program. The returned token is sampled from the last VALID
    position's logits — meaningful only on the final chunk (it becomes
    the request's first generated token, ``generate()``'s ``tok0``);
    earlier chunks discard it.
    """
    sampler = make_sampler(cfg, temperature, top_k, top_p)
    sampled = temperature > 0

    def step(params, ck, cv, tokens, pos0, n_valid, table, key):
        positions = (pos0 + jnp.arange(chunk))[None]          # [1, C]
        valid = (jnp.arange(chunk) < n_valid)[None]           # [1, C]
        pages = table[jnp.clip(positions // page_size, 0,
                               table.shape[0] - 1)]
        pages = jnp.where(valid, pages, n_pages)              # drop pads
        offsets = positions % page_size
        lengths = (pos0 + n_valid)[None]                      # [1]
        x = _embed_rows(params, tokens, positions, cfg)
        x, ck, cv = _layers_scan(params, ck, cv, x, positions, pages,
                                 offsets, table[None], lengths, cfg, impl)
        xl = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)
        logits = unembed(params, xl)[:, 0]                    # [1, V]
        sub = (jax.random.fold_in(key, pos0 + n_valid - 1) if sampled
               else key)
        return ck, cv, sampler(logits, sub)

    return jax.jit(step, donate_argnums=(1, 2))


@functools.lru_cache(maxsize=64)
def make_verify_step(cfg: TransformerConfig, *, page_size: int,
                     n_pages: int, width: int, impl: str,
                     temperature: float = 0.0, top_k: int | None = None,
                     top_p: float | None = None):
    """Speculative-decoding verification: ``width`` tokens per slot in
    ONE batched forward (the last committed token plus ``width - 1``
    draft tokens), emitting the model's own choice at every position.

    Returns ``step(params, ck, cv, tokens [B, W], positions [B],
    n_valid [B], tables [B, N], active [B] bool, keys [B]) ->
    (ck, cv, out_tokens [B, W])`` where ``out_tokens[b, i]`` is the
    token the model picks for absolute position ``positions[b] + i + 1``
    given the window prefix through ``i`` — exactly what sequential
    decode would emit there, because each query row's math is
    position-independent of batch shape and sampling folds the
    per-request key with the query position (the same fold the
    single-token decode step uses). The host-side accept rule
    (serve/engine.py) keeps ``out[i]`` only while the drafts before it
    matched, so spec-on and spec-off token streams are identical by
    construction.

    ``n_valid`` clamps each row's window (a request near its token
    budget processes fewer positions); writes past it — and every write
    of an idle row — are dropped via out-of-range page ids, the same
    masking idiom as prefill padding.
    """
    sampler = make_sampler(cfg, temperature, top_k, top_p)
    sampled = temperature > 0

    def window_sample(logits, keys, positions):
        # logits [B, W, V]; fold each row's key with each query position
        # (positions[b] + i) — bitwise the decode/prefill fold for the
        # same (seed, position).
        if not sampled:
            b, w, v = logits.shape
            return sampler(logits.reshape(b * w, v), None).reshape(b, w)

        def row(lg, key, p0):
            subs = jax.vmap(jax.random.fold_in,
                            in_axes=(None, 0))(key, p0 + jnp.arange(width))
            return jax.vmap(lambda l, s: sampler(l[None], s)[0])(lg, subs)

        return jax.vmap(row)(logits, keys, positions)

    def step(params, ck, cv, tokens, positions, n_valid, tables, active,
             keys):
        pos = positions[:, None] + jnp.arange(width)[None]    # [B, W]
        valid = jnp.logical_and(
            jnp.arange(width)[None] < n_valid[:, None],
            active[:, None])                                  # [B, W]
        pages = jnp.take_along_axis(
            tables, jnp.clip(pos // page_size, 0, tables.shape[1] - 1),
            axis=1)
        pages = jnp.where(valid, pages, n_pages)              # drop invalid
        offsets = pos % page_size
        lengths = positions + n_valid                         # [B]
        x = _embed_rows(params, tokens, pos, cfg)
        x, ck, cv = _layers_scan(params, ck, cv, x, pos, pages, offsets,
                                 tables, lengths, cfg, impl)
        logits = unembed(params, x)                           # [B, W, V]
        return ck, cv, window_sample(logits, keys, positions)

    return jax.jit(step, donate_argnums=(1, 2))


def decode_logits(params: dict, ck: jax.Array, cv: jax.Array,
                  tokens: jax.Array, positions: jax.Array,
                  tables: jax.Array, active: jax.Array,
                  cfg: TransformerConfig, *, page_size: int, n_pages: int,
                  impl: str) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The decode step's forward: feed ``tokens [B]`` at ``positions [B]``
    through the paged cache (idle rows' writes dropped) and return
    ``(ck, cv, logits [B, V])``. :func:`make_decode_step` samples from
    these; chip_smoke.py compares them across ``impl`` values."""
    pos2 = positions[:, None]                                 # [B, 1]
    pages = jnp.take_along_axis(tables, pos2 // page_size, axis=1)
    pages = jnp.where(active[:, None], pages, n_pages)        # idle: drop
    offsets = pos2 % page_size
    lengths = positions + 1
    x = _embed_rows(params, tokens[:, None], pos2, cfg)
    x, ck, cv = _layers_scan(params, ck, cv, x, pos2, pages, offsets,
                             tables, lengths, cfg, impl)
    return ck, cv, unembed(params, x)[:, 0]


@functools.lru_cache(maxsize=64)
def make_decode_step(cfg: TransformerConfig, *, page_size: int,
                     n_pages: int, impl: str, temperature: float = 0.0,
                     top_k: int | None = None, top_p: float | None = None):
    """One token for every slot of the fixed-width decode batch.

    Returns ``step(params, ck, cv, tokens [B], positions [B], tables
    [B, N], active [B] bool, keys [B]) -> (ck, cv, next_tokens [B])``.
    Idle slots compute garbage rows (masked writes, outputs ignored) so
    the program never re-specializes on occupancy. Sampling folds each
    row's key with its own position — a request's stream is a pure
    function of (request seed, position), independent of the batch.
    """
    sampler = make_sampler(cfg, temperature, top_k, top_p)
    sampled = temperature > 0

    def row_sample(logits, keys, positions):
        if not sampled:
            return sampler(logits, None)
        subs = jax.vmap(jax.random.fold_in)(keys, positions)
        return jax.vmap(lambda lg, s: sampler(lg[None], s)[0])(logits, subs)

    def step(params, ck, cv, tokens, positions, tables, active, keys):
        ck, cv, logits = decode_logits(
            params, ck, cv, tokens, positions, tables, active, cfg,
            page_size=page_size, n_pages=n_pages, impl=impl)
        return ck, cv, row_sample(logits, keys, positions)

    return jax.jit(step, donate_argnums=(1, 2))
