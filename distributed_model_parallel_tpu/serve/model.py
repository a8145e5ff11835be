"""Paged prefill/decode forward over ``models/transformer`` params.

Two jitted programs serve every request shape (a third, the verify
step, where the engine speculates):

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

The block math is ``models/transformer``'s own pieces (``_norm``,
``_qkv_proj``, ``apply_rope``, ``_ffn``, ``unembed``) with the dense
cache's write/read swapped for the page pools (``ops/paged_attention``)
— the training/decode definitions stay single-source, and what a layer
is (its norm and where it sits, FFN, window, rotation, or no attention
at all) comes from the configuration, one ``LayerKind`` a layer. Two
block bodies: :func:`paged_block` (softmax attention over pages or a
ring) and :func:`state_block` (the gated delta rule over a recurrent
state a slot, ``ops/gated_delta``). Routed FFNs are served by the
dropless layer (``ops/moe.moe_ffn_dropless``): a token's result is its
own whatever shares the call. The capacity-dropping layer couples
co-resident tokens and stays refused (serve/engine.py).

A looped stack (``TransformerConfig.n_passes``) is served by the same
three steps: :func:`_layers` runs ``transformer.run_passes``, the walk
over the layers repeated over the same leaves inside one jitted step,
each pass writing its K/V into its own cache layer of the donated pools
(serve/paged_kv.CacheLayout: the pools are ``n_passes`` times as deep as
the weights) and reading them back whole with that index.

The steps read the attention in-projections of layers stacked under
``run_layers``' scan with the contracted axis last (``wq_t [L, H, Dh,
d]``, ``wkv_t``, ``wqkv_t``: :func:`in_proj_d_last`, which
``Engine.__init__`` applies once to the tree it is given;
``Engine._status()`` reports the leaves it holds so and their bytes as
``weights_relaid`` and ``weights_relaid_bytes``). A tree as stored (``wq
[L, d, H, Dh]``) serves the same tokens through the same steps, with
each layer's leaves cut out of the stack and transposed on the way into
the product.

Every step takes and returns the device state it donates: ``pools``
(``PagedKVCache.pools``, six arrays by layer kind: the full layers' K and
V pools, the sliding layers' ring pools, the state layers' recurrent
states and convolution tails, serve/paged_kv.CacheLayout; None where the
model has no layer of the kind) and ``stats`` (:func:`init_stats`: the
routed layers' counters and the looped stack's, summed on the device;
None to count nothing). ``tables`` is
``(table, ring)``: a sequence's pages of the shared pool and its ring
pages (None where no layer keeps a ring); the prefill step of a model
with state layers takes ``(table, ring, slot)``: which slot's state the
row is (the decode batch's row ``i`` is slot ``i``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from distributed_model_parallel_tpu.models.transformer import (
    LayerKind,
    TransformerConfig,
    _ffn,
    _qkv_proj,
    apply_rope,
    gated_delta_inputs,
    gated_delta_output,
    exit_distribution,
    make_sampler,
    run_passes,
    sublayer_in,
    sublayer_out,
    unembed,
)
from distributed_model_parallel_tpu.ops.gated_delta import (
    gated_delta_chunk,
    gated_delta_decode,
    pool_state,
    unpool_state,
)
from distributed_model_parallel_tpu.ops.paged_attention import (
    paged_attention,
)
from distributed_model_parallel_tpu.serve.paged_kv import CacheLayout


def paged_block(bp: dict, kind: LayerKind, pools: tuple, where: tuple,
                x: jax.Array, positions: jax.Array, writes: dict,
                offsets: jax.Array, lengths: jax.Array, valid: jax.Array,
                cfg: TransformerConfig, *, impl: str):
    """One transformer block over the paged cache.

    x: [B, C, d]; positions: [B, C] absolute; pools: (ck, cv, wk, wv,
    ...), the first four each [L_kind, P, page, Hkv, Dh]; where: (ring,
    layer): this layer is layer ``layer`` (traced or not) of the ring
    pools or of the full ones; writes[ring] = (pages [B, C], tables
    [B, N]): the physical page of each token — an out-of-range id drops the write (idle slots,
    prompt padding) — and the logical-to-physical table the read follows;
    offsets: [B, C] within the page; lengths: [B] valid K prefix (after
    this step's writes); valid: [B, C] tokens that exist (the routed
    layer sends no other anywhere). The layer's K and V are written in
    place into its pool (the donated carry), and the pool goes to the
    attention read whole, with ``layer``: the read finds the layer's
    pages inside the stack (``paged_attention``), and no slab is cut out
    for it. Returns ``(x, pools, aux)``. The paged counterpart of
    ``transformer._cached_block``.
    """
    b, c = x.shape[:2]
    ring, layer = where
    at = 2 if ring else 0
    kpool, vpool = pools[at:at + 2]
    pages, tables = writes[ring]
    h = sublayer_in(bp, "ln1", x, cfg)
    q, k, v = _qkv_proj(bp, h, cfg)          # q:[B,C,H,Dh] kv:[B,C,Hkv,Dh]
    if kind.rope:
        # Per-row positions: the continuous batch has every row at its
        # own offset. The cache stores rotated keys, like the dense path.
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    n_heads = q.shape[2]
    extra = kpool.shape[-2] - k.shape[2]
    if extra:
        # the pool stores more KV heads than the model has
        # (paged_kv.stored_kv_heads): zero heads, and zero queries to them
        heads = lambda n: ((0, 0), (0, 0), (0, n), (0, 0))      # noqa: E731
        q = jnp.pad(q, heads(extra * (n_heads // k.shape[2])))
        k, v = jnp.pad(k, heads(extra)), jnp.pad(v, heads(extra))
    kpool = kpool.at[layer, pages, offsets].set(
        k.astype(kpool.dtype), mode="drop")
    vpool = vpool.at[layer, pages, offsets].set(
        v.astype(vpool.dtype), mode="drop")
    with jax.named_scope("attn_sliding" if ring else "attn_full"):
        o = paged_attention(q, kpool, vpool, tables, positions, lengths,
                            window=kind.window, impl=impl, layer=layer)
    if extra:
        o = o[:, :, :n_heads]
    pools = pools[:at] + (kpool, vpool) + pools[at + 2:]
    x = x + sublayer_out(bp, "ln1", o.reshape(b, c, -1) @ bp["wo"], cfg)
    x, aux = _ffn_sublayer(bp, kind, x, valid, cfg)
    return x, pools, aux


def _ffn_sublayer(bp: dict, kind: LayerKind, x, valid, cfg):
    """The block's second half, the same after either mixer."""
    h = sublayer_in(bp, "ln2", x, cfg)
    h, aux = _ffn(bp, h, cfg, tp_axis=None, ep_axis=None, kind=kind,
                  valid=valid)
    return x + sublayer_out(bp, "ln2", h, cfg), aux


def state_block(bp: dict, kind: LayerKind, pools: tuple, layer,
                x: jax.Array, valid: jax.Array, fresh,
                cfg: TransformerConfig, *, impl: str):
    """One block whose mixer is the gated delta rule, over the state
    pools.

    x: [B, C, d]; pools[4:]: (state [L, N, dk, Hv * dv] float32, tail
    [L, N, K - 1, ch]); ``layer`` (traced or not) this layer's index in
    them; valid: [B, C] tokens that exist: padding of a last chunk and
    idle decode rows leave state and tail as they are. Row ``i`` of the
    batch is row ``i`` of the pools, in both callers:

    * the decode round (``fresh`` None, ``C == 1``, ``N`` the engine's
      slots): the recurrence's one step on every row of the layer's slab,
      read once and written once where it lies (``gated_delta_decode``:
      the pool goes in whole with ``layer``, no slab is cut out);
    * the prefill chunk (``B == N == 1``: :func:`_layers` hands in the
      row's own slot, all layers of it, and puts it back): the chunked
      form from the slot's state, or from zeros where ``fresh`` says the
      sequence starts here (nothing cleared the slot when its last
      tenant left).

    Returns ``(x, pools, aux)`` like :func:`paged_block`.
    """
    b, c = x.shape[:2]
    state, tails = pools[4:]
    if b != state.shape[1] or (fresh is None and c != 1):
        raise NotImplementedError(
            "a state layer takes one token a slot (the decode round) or "
            "one sequence's chunk (prefill); a verify window would need "
            "the state rolled back past rejected drafts")
    h = sublayer_in(bp, "ln1", x, cfg)
    tail = tails[layer]
    if fresh is not None:
        tail = jnp.where(fresh, jnp.zeros_like(tail), tail)
    q, k, v, log_alpha, beta, gate, tail1 = gated_delta_inputs(
        bp, h, cfg, tail, jnp.sum(valid, axis=1, dtype=jnp.int32))
    with jax.named_scope("linattn_rule"):
        if fresh is None:
            live = valid[:, 0, None]
            # an idle row: alpha 1, beta 0, and zeros for whatever its
            # garbage token gave (zero times NaN would be NaN)
            q, k, v = (jnp.where(live[..., None], a[:, 0], 0)
                       for a in (q, k, v))
            o, state = gated_delta_decode(
                state, layer, q, k, v,
                jnp.where(live, jnp.exp(log_alpha[:, 0]), 1.0),
                jnp.where(live, beta[:, 0], 0.0), impl=impl)
            o = o[:, None]
        else:
            s0 = state[layer]
            s0 = jnp.where(fresh, jnp.zeros_like(s0), s0)
            o, s1 = gated_delta_chunk(
                q, k, v, log_alpha, beta,
                unpool_state(s0, cfg.lin_value_heads), valid)
            state = state.at[layer].set(pool_state(s1))
    tails = tails.at[layer].set(tail1)
    x = x + sublayer_out(bp, "ln1", gated_delta_output(bp, o, gate, cfg),
                         cfg)
    x, aux = _ffn_sublayer(bp, kind, x, valid, cfg)
    return x, pools[:4] + (state, tails), aux


def _layers(params: dict, pools, stats, x, positions, pages, tables,
            valid, lengths, cfg, layout: CacheLayout | None, impl: str,
            page_size: int, fresh=None):
    """All passes over all layers over the paged cache (``run_passes``:
    a looped stack walks the same leaves ``cfg.n_passes`` times inside
    this one step, the pools in the carry across passes, and pass ``t``
    of a layer reads and writes its own cache layer,
    ``CacheLayout.cache_layer``). pages [B, C]: each token's logical
    page (an invalid token's is irrelevant); tables: (table [B, N], ring
    [B, R] or None[, slot [B]: the prefill row's slot, for state
    layers]); ``fresh`` (the prefill step's; None in a decode round):
    the row's sequence starts with this call (a state layer then starts
    from zeros). Returns ``(x, pools, stats)`` with the routed layers'
    counters and the looped stack's added to ``stats``
    (:func:`init_stats`)."""
    table, ring, *rest = tables
    mine = None
    if rest:
        # The prefill row's own slot of the state pools, every layer of
        # it, taken out once and put back once: the layers work on 27 MB
        # and the pools (0.85 GB) see one read and one write in place.
        # (Sliced and updated a layer at a time under the scan, XLA
        # rematerialised an update to shorten the pool's live range, and
        # a second update of one buffer is a copy of it: read in the step
        # compiled for a described v5e.)
        mine = (0, rest[0][0], 0, 0)
        whole = pools[4:]
        pools = pools[:4] + tuple(
            jax.lax.dynamic_slice(p, mine, (p.shape[0], 1) + p.shape[2:])
            for p in whole)
    n = table.shape[1]
    offsets = positions % page_size

    def physical(tab, pool):
        got = jnp.take_along_axis(tab, jnp.clip(pages, 0, n - 1), axis=1)
        return jnp.where(valid, got, pool.shape[1])        # drop invalid

    writes = {False: (physical(table, pools[0]), table)}
    if ring is not None:
        # logical page j of a sliding layer lives in ring page j % R
        ring_table = jnp.take(ring, jnp.arange(n) % ring.shape[1], axis=1)
        writes[True] = (physical(ring_table, pools[2]), ring_table)
    layout = layout or CacheLayout.all_full(cfg)
    routed = cfg.moe_dropless

    def layer(t, bp, kind, body, rep, carry):
        x, pools = carry
        is_ring, at = layout.cache_layer(body, rep, t)
        if is_ring is None:                    # a state layer: no K/V
            x, pools, aux = state_block(
                bp, kind, pools, at, x, valid, fresh, cfg, impl=impl)
        else:
            x, pools, aux = paged_block(
                bp, kind, pools, (is_ring, at), x, positions, writes,
                offsets, lengths, valid, cfg, impl=impl)
        return (x, pools), (aux if routed and kind.ffn == "moe" else None)

    x, pools, counts, gates = run_passes(params, x, pools, layer, cfg)
    if mine is not None:
        pools = pools[:4] + tuple(
            jax.lax.dynamic_update_slice(p, new, mine)
            for p, new in zip(whole, pools[4:]))
    if stats is None:
        return x, pools, stats
    stats = dict(stats)
    if stats["moe"] is not None:
        if cfg.looped:                         # counts: stacked on [passes]
            counts = jax.tree.map(lambda c: jnp.sum(c, axis=0), counts)
        stats["moe"] = jax.tree.map(jnp.add, stats["moe"], counts)
    if stats["loop"] is not None:
        n = jnp.sum(valid, dtype=jnp.int32)
        # without a gate every token leaves after the last pass
        mass = (jnp.zeros((cfg.n_passes,), jnp.float32).at[-1].set(n)
                if gates is None else jnp.sum(
                    jnp.where(valid, exit_distribution(gates), 0.0),
                    axis=(1, 2)))
        stats["loop"] = jax.tree.map(jnp.add, stats["loop"], {
            "tokens": n, "token_passes": cfg.n_passes * n,
            "exit_mass": mass})
    return x, pools, stats


def init_stats(cfg: TransformerConfig) -> dict:
    """The zeroed counters the steps sum on the device, ``{"moe", "loop"}``:

    * ``moe``: the routed layers', in ``run_layers``' order of bodies
      (None for a model without dropless routed layers): int32 [repeats,
      G + 3] a routed body: tokens a held expert, tokens routed, experts
      touched and row tiles visited a call (ops/moe.moe_ffn_dropless),
      summed over a looped stack's passes. :func:`stats_by_layer` puts
      them in layer order.
    * ``loop``: the looped stack's (None for a stack run once with no
      gate and no norm in the loop): ``tokens`` (valid tokens through the
      stack), ``token_passes`` (passes they took: ``n_passes`` each
      today, what rows that leave early would lower), int32; ``exit_mass``
      [n_passes] float32, the sum over those tokens of the exit gate's
      ``p_exit(t)`` (``transformer.exit_distribution``)."""
    moe = None
    if cfg.moe_dropless and cfg.moe_experts:
        n_lead, period, n_periods = cfg.layer_plan
        g = cfg.moe.held_range[1]
        moe = tuple(
            jnp.zeros((1 if i < n_lead else n_periods, g + 3), jnp.int32)
            if cfg.kinds[i].ffn == "moe" else None
            for i in range(n_lead + period))
    loop = None
    if cfg.looped:
        loop = {"tokens": jnp.zeros((), jnp.int32),
                "token_passes": jnp.zeros((), jnp.int32),
                "exit_mass": jnp.zeros((cfg.n_passes,), jnp.float32)}
    return {"moe": moe, "loop": loop}


def stats_by_layer(stats, cfg: TransformerConfig) -> dict:
    """{layer index: host int array [G + 3]} of the routed layers
    (``stats``: :func:`init_stats`' dict as the steps returned it)."""
    import numpy as np

    n_lead, period, _ = cfg.layer_plan
    out = {}
    for body, rows in enumerate((stats or {}).get("moe") or ()):
        if rows is None:
            continue
        for rep, row in enumerate(np.asarray(rows)):
            out[body + rep * period] = row
    return dict(sorted(out.items()))


IN_PROJECTIONS = ("wq", "wkv", "wqkv")


def _block_groups(params: dict) -> tuple:
    """The dicts of stacked layer leaves: ``blocks`` itself, or one a
    position of the period."""
    blocks = params["blocks"]
    return (blocks,) if isinstance(blocks, dict) else tuple(blocks)


@jax.jit
def _d_last(leaves: list) -> list:
    return [jnp.moveaxis(w, 1, -1) for w in leaves]


def in_proj_d_last(params: dict) -> dict:
    """``params`` with the attention in-projections of every group of
    ``blocks`` stacked over more than one layer held in the layout their
    product takes: ``wq [L, d, H, Dh]`` becomes ``wq_t [L, H, Dh, d]``, ``wkv``
    ``wkv_t [L, Hkv, 2 Dh, d]``, ``wqkv`` ``wqkv_t``;
    ``transformer._qkv_proj`` reads whichever a layer holds.

    Why: XLA:TPU gives the right-hand side of ``h [B, T, d] x w`` the
    layout with the contracted axis ``d`` minor. Of a stored leaf, ``d``
    third from minor, every step cut each layer out of the stack under
    ``run_layers``' scan and transposed it (at 16 heads with no grouping:
    all layers' at the step's entry), 5-7 % of three of the benchmark's
    cells; of a leaf with ``d`` last the product reads the stack in
    place, as the MLP's do. Why only where ``L > 1``: a layer whose index
    is static (a leading layer, a period that does not repeat) is not cut
    out of anything, the compiler fuses its transposition into the
    product's own read, and there is nothing to take out; converted all
    the same, K-EXAONE's five such layers cost the prefill step 4.6 ms of
    23 on the chip (other operands lost their place in fast memory). The
    stored format (``init_params``, checkpoints, the sharding rules)
    stays ``[L, d, H, Dh]``; the engine converts once, when it is built
    (``Engine.__init__``, and nothing else calls this).

    One jitted call over all the leaves. Every other leaf is passed
    through, the same array; a tree that already holds the ``*_t``
    leaves comes back as it is. The caller's own leaves are not donated:
    who keeps its tree holds both forms."""
    groups = _block_groups(params)
    found = [(g, name) for g, bp in enumerate(groups)
             for name in IN_PROJECTIONS
             if name in bp and bp[name].shape[0] > 1]
    if not found:
        return params
    new = [dict(bp) for bp in groups]
    for (g, name), w in zip(found,
                            _d_last([groups[g][n] for g, n in found])):
        del new[g][name]
        new[g][name + "_t"] = w
    return dict(params, blocks=(
        new[0] if isinstance(params["blocks"], dict) else tuple(new)))


def in_proj_relaid(params: dict) -> list:
    """The ``*_t`` leaves of a tree (:func:`in_proj_d_last`'s)."""
    return [bp[name + "_t"] for bp in _block_groups(params)
            for name in IN_PROJECTIONS if name + "_t" in bp]


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
                      chunk: int, impl: str,
                      layout: CacheLayout | None = None,
                      temperature: float = 0.0, top_k: int | None = None,
                      top_p: float | None = None):
    """One request's prompt chunk against the paged cache.

    Returns ``step(params, pools, stats, tokens [1, chunk], pos0,
    n_valid, tables ([N], [R] | None[, slot]), key) -> (pools,
    stats, next_token [1])``. ``slot`` (a scalar) says whose recurrent
    state the row is, where the model has state layers; a chunk at
    ``pos0 == 0`` with a valid token starts them from zeros.
    ``pos0``/``n_valid`` are traced scalars, so every chunk of every
    prompt length hits one compiled program. The returned token is
    sampled from the last VALID position's logits — meaningful only on
    the final chunk (it becomes the request's first generated token,
    ``generate()``'s ``tok0``); earlier chunks discard it.
    """
    sampler = make_sampler(cfg, temperature, top_k, top_p)
    sampled = temperature > 0

    def prefill_step(params, pools, stats, tokens, pos0, n_valid, tables,
                     key):
        positions = (pos0 + jnp.arange(chunk))[None]          # [1, C]
        valid = (jnp.arange(chunk) < n_valid)[None]           # [1, C]
        lengths = (pos0 + n_valid)[None]                      # [1]
        x = _embed_rows(params, tokens, positions, cfg)
        x, pools, stats = _layers(
            params, pools, stats, x, positions, positions // page_size,
            jax.tree.map(lambda t: t[None], tables), valid, lengths, cfg,
            layout, impl, page_size,
            fresh=jnp.logical_and(pos0 == 0, n_valid > 0))
        xl = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)
        logits = unembed(params, xl, cfg)[:, 0]               # [1, V]
        sub = (jax.random.fold_in(key, pos0 + n_valid - 1) if sampled
               else key)
        return pools, stats, sampler(logits, sub)

    return jax.jit(prefill_step, donate_argnums=(1, 2))


@functools.lru_cache(maxsize=64)
def make_verify_step(cfg: TransformerConfig, *, page_size: int,
                     width: int, impl: str,
                     layout: CacheLayout | None = None,
                     temperature: float = 0.0, top_k: int | None = None,
                     top_p: float | None = None):
    """Speculative-decoding verification: ``width`` tokens per slot in
    ONE batched forward (the last committed token plus ``width - 1``
    draft tokens), emitting the model's own choice at every position.

    Returns ``step(params, pools, stats, tokens [B, W], positions [B],
    n_valid [B], tables ([B, N], [B, R] | None), active [B] bool, keys
    [B]) -> (pools, stats, out_tokens [B, W])`` where ``out_tokens[b, i]``
    is the token the model picks for absolute position ``positions[b] +
    i + 1`` given the window prefix through ``i`` — exactly what
    sequential decode would emit there, because each query row's math is
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

    def verify_step(params, pools, stats, tokens, positions, n_valid,
                    tables, active, keys):
        pos = positions[:, None] + jnp.arange(width)[None]    # [B, W]
        valid = jnp.logical_and(
            jnp.arange(width)[None] < n_valid[:, None],
            active[:, None])                                  # [B, W]
        lengths = positions + n_valid                         # [B]
        x = _embed_rows(params, tokens, pos, cfg)
        x, pools, stats = _layers(params, pools, stats, x, pos,
                                  pos // page_size, tables, valid, lengths,
                                  cfg, layout, impl, page_size)
        logits = unembed(params, x, cfg)                      # [B, W, V]
        return pools, stats, window_sample(logits, keys, positions)

    return jax.jit(verify_step, donate_argnums=(1, 2))


def decode_logits(params: dict, pools: tuple, stats, tokens: jax.Array,
                  positions: jax.Array, tables: tuple, active: jax.Array,
                  cfg: TransformerConfig, *, page_size: int, impl: str,
                  layout: CacheLayout | None = None):
    """The decode step's forward: feed ``tokens [B]`` at ``positions [B]``
    through the paged cache (idle rows' writes dropped) and return
    ``(pools, stats, logits [B, V])``. :func:`make_decode_step` samples
    from these; chip_smoke.py compares them across ``impl`` values."""
    pos2 = positions[:, None]                                 # [B, 1]
    lengths = positions + 1
    x = _embed_rows(params, tokens[:, None], pos2, cfg)
    x, pools, stats = _layers(params, pools, stats, x, pos2,
                              pos2 // page_size, tables, active[:, None],
                              lengths, cfg, layout, impl, page_size)
    return pools, stats, unembed(params, x, cfg)[:, 0]


@functools.lru_cache(maxsize=64)
def make_decode_step(cfg: TransformerConfig, *, page_size: int, impl: str,
                     layout: CacheLayout | None = None,
                     temperature: float = 0.0, top_k: int | None = None,
                     top_p: float | None = None):
    """One token for every slot of the fixed-width decode batch.

    Returns ``step(params, pools, stats, tokens [B], positions [B],
    tables ([B, N], [B, R] | None), active [B] bool, keys [B]) ->
    (pools, stats, next_tokens [B])``. Idle slots compute garbage rows
    (masked writes, outputs ignored, routed to no expert) so the program
    never re-specializes on occupancy. Sampling folds each row's key with
    its own position — a request's stream is a pure function of (request
    seed, position), independent of the batch.
    """
    sampler = make_sampler(cfg, temperature, top_k, top_p)
    sampled = temperature > 0

    def row_sample(logits, keys, positions):
        if not sampled:
            return sampler(logits, None)
        subs = jax.vmap(jax.random.fold_in)(keys, positions)
        return jax.vmap(lambda lg, s: sampler(lg[None], s)[0])(logits, subs)

    def decode_step(params, pools, stats, tokens, positions, tables,
                    active, keys):
        pools, stats, logits = decode_logits(
            params, pools, stats, tokens, positions, tables, active, cfg,
            page_size=page_size, impl=impl, layout=layout)
        return pools, stats, row_sample(logits, keys, positions)

    return jax.jit(decode_step, donate_argnums=(1, 2))
