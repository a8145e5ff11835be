"""Mixture-of-Experts with expert parallelism over the ``expert`` mesh axis.

Beyond the reference (SURVEY.md §2.3 lists EP as absent) but part of this
framework's first-class parallelism set: top-k token routing — top-1
(switch-style, raw gate) or top-2+ (GShard-style, gates normalized over the
selected experts) — with static capacity, experts sharded
one-per-device-group over the ``expert`` axis, and token exchange via
``all_to_all`` — the TPU-native form of expert dispatch: static-shaped
scatter/gather against per-choice queue-slot indices (round 5; the one-hot
einsum masks used through round 4 cost N*E*C*d MAC per layer — orders of
magnitude more than the experts themselves at bench shapes). Dropped
tokens pass through on the residual path.

Shapes (inside shard_map over the expert axis):
  x_local:        [B_local, T, d]   tokens on this device group
  expert params:  [E_local, ...]    experts owned by this group
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 4
    d_model: int = 64
    d_ff: int = 128
    capacity_factor: float = 2.0
    top_k: int = 1
    # Only consulted for top_k > 1: renormalize the selected experts' gates to
    # sum to 1 (GShard). top-1 always uses the raw softmax prob (Switch).
    normalize_gates: bool = True
    # Read by the dropless layer (moe_ffn_dropless) only: how the router's
    # logits become scores ("softmax" | "sigmoid"), the factor on the
    # chosen (normalised) scores, and (first, count), the experts this
    # chip holds of the num_experts the router chooses among (None: all).
    scoring: str = "softmax"
    routed_scale: float = 1.0
    held: tuple | None = None

    def __post_init__(self):
        if not (1 <= self.top_k <= self.num_experts):
            raise ValueError(
                f"top_k={self.top_k} must be in [1, num_experts="
                f"{self.num_experts}]")
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown scoring {self.scoring!r}")
        first, count = self.held_range
        if not (0 <= first and count >= 1
                and first + count <= self.num_experts):
            raise ValueError(f"held={self.held} is not a range of the "
                             f"{self.num_experts} experts")

    @property
    def held_range(self) -> tuple:
        return self.held if self.held is not None else (0, self.num_experts)


def init_moe_params(rng: jax.Array, cfg: MoEConfig) -> dict:
    k1, k2, k3 = jax.random.split(rng, 3)
    E, d, f = cfg.num_experts, cfg.d_model, cfg.d_ff
    return {
        "router": jax.random.normal(k1, (d, E)) * (d ** -0.5),
        "w_in": jax.random.normal(k2, (E, d, f)) * (d ** -0.5),
        "w_out": jax.random.normal(k3, (E, f, d)) * (f ** -0.5),
    }


def _route(router, x, cfg: MoEConfig):
    """Top-k routing with per-expert capacity, in INDEX form.

    Returns ``(experts [N,k] i32, gates [N,k], slot [N,k] i32,
    keep [N,k] bool, cap, stats [3] f32)`` for N flattened tokens:
    ``slot[n,j] = experts[n,j] * cap + queue position`` — each kept
    token-choice owns a unique slot in the [E*cap] expert-queue space,
    which is what lets dispatch/combine be gathers instead of the
    [N, E, C] one-hot einsums this module used through round 4 (those
    masks cost N*E*C*d MAC/layer — ~2 PFLOP at the bench shape, >100x
    the expert FFN math itself; the index form is pure data movement).

    ``stats`` is

    * ``[0]`` load-balance loss (Switch/GShard first-choice form),
    * ``[1]`` router z-loss — mean squared logsumexp of the router
      logits, the logit-drift regularizer (ST-MoE); weighted into the
      training loss by ``TransformerConfig.moe_z_weight``,
    * ``[2]`` drop rate — the fraction of the N*k token-choices whose
      expert queue was already at capacity (``pos >= cap``); those
      choices ride the residual path. A metric, not a loss term: it is
      piecewise-constant in the params (zero gradient), and surfacing it
      is what turns silent capacity overflow into an observable.

    Choice j's queue positions are offset by all earlier choices'
    assignments (GShard ordering), so a token's second choice never
    collides with first-choice traffic.
    """
    n = x.shape[0]
    E = cfg.num_experts
    k = cfg.top_k
    # Capacity scales with k (GShard): each token makes k assignments, so
    # holding capacity_factor fixed keeps the drop rate constant across k.
    cap = max(1, int(cfg.capacity_factor * k * n / E))
    logits = x @ router                               # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, k)          # [N, k] each
    if k > 1 and cfg.normalize_gates:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)

    counts = jnp.zeros((E,), jnp.int32)               # queue heads per expert
    slots, keeps = [], []
    for j in range(k):                                # k is static (config)
        e_j = experts[:, j]                           # [N]
        onehot = jax.nn.one_hot(e_j, E, dtype=jnp.int32)
        # Position of each token within its expert's queue, past all
        # choice-<j traffic.
        pos_all = jnp.cumsum(onehot, axis=0) - 1 + counts       # [N, E]
        pos = jnp.take_along_axis(pos_all, e_j[:, None], axis=1)[:, 0]
        keep = pos < cap
        slots.append(e_j * cap + jnp.minimum(pos, cap - 1))
        keeps.append(keep)
        counts = counts + jnp.sum(onehot, axis=0)
    slot = jnp.stack(slots, axis=1)                   # [N, k]
    keep = jnp.stack(keeps, axis=1)                   # [N, k]

    # Load-balancing loss over first-choice assignment fractions
    # (Switch/GShard form).
    first_choice = jax.nn.one_hot(experts[:, 0], E)
    frac_tokens = jnp.mean(first_choice, axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    balance = E * jnp.sum(frac_tokens * frac_probs)
    z = jnp.mean(jax.nn.logsumexp(logits.astype(jnp.float32),
                                  axis=-1) ** 2)
    drop_rate = 1.0 - jnp.sum(keep.astype(jnp.float32)) / (n * k)
    stats = jnp.stack([balance.astype(jnp.float32), z,
                       jax.lax.stop_gradient(drop_rate)])
    return experts, gates, slot, keep, cap, stats


def moe_ffn(params: dict, x: jax.Array, cfg: MoEConfig,
            ep_axis: str | None = None) -> tuple[jax.Array, jax.Array]:
    """MoE FFN on [B, T, d]. Returns ``(y, stats)`` where stats is the
    ``[balance_loss, z_loss, drop_rate]`` f32 vector from :func:`_route`.

    Without ``ep_axis``: all experts local (dense dispatch einsums).
    With ``ep_axis`` (inside shard_map): params arrive expert-sharded
    [E_local, ...]; expert inputs are exchanged with ``all_to_all`` so each
    device group runs only its own experts, then results return the same way.
    """
    b, t, d = x.shape
    n = b * t
    xf = x.reshape(-1, d)                             # [N, d]
    experts, gates, slot, keep, cap, aux = _route(params["router"], xf, cfg)
    E = cfg.num_experts

    # Dispatch as a scatter of token IDs into queue slots, then a gather:
    # kept slots are unique (queue positions), so .at[].set never collides;
    # dropped choices scatter to the out-of-bounds sentinel E*cap and are
    # dropped; unfilled slots keep token id N -> gather the zero pad row.
    token_ids = jnp.arange(n, dtype=jnp.int32)
    slot_token = jnp.full((E * cap,), n, jnp.int32)
    for j in range(cfg.top_k):
        idx = jnp.where(keep[:, j], slot[:, j], E * cap)
        slot_token = slot_token.at[idx].set(token_ids, mode="drop")
    xf_pad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)])
    expert_in = xf_pad[slot_token].reshape(E, cap, d)

    if ep_axis is not None:
        # [E, C, d] -> exchange so this device holds its experts' tokens from
        # ALL groups (tiled: split expert axis by ep, concat source-major on
        # the capacity axis): -> [E_local, ep*C, d].
        expert_in = jax.lax.all_to_all(
            expert_in, ep_axis, split_axis=0, concat_axis=1, tiled=True)
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", expert_in, params["w_in"]))
        expert_out = jnp.einsum("ecf,efd->ecd", h, params["w_out"])
        # Inverse exchange: [E_local, ep*C, d] -> [ep*E_local, C, d], chunks
        # source-major on axis 0 == global expert order.
        expert_out = jax.lax.all_to_all(
            expert_out, ep_axis, split_axis=1, concat_axis=0, tiled=True)
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", expert_in, params["w_in"]))
        expert_out = jnp.einsum("ecf,efd->ecd", h, params["w_out"])

    # Combine: gather each kept choice's expert output back to its token,
    # weighted by the (differentiable) gate. Gate gradients flow exactly as
    # in the einsum form; the gathers transpose to scatter-adds under AD.
    out_flat = expert_out.reshape(E * cap, d)
    y = jnp.zeros((n, d), x.dtype)
    for j in range(cfg.top_k):
        w = jnp.where(keep[:, j], gates[:, j], 0).astype(x.dtype)
        y = y + w[:, None] * out_flat[slot[:, j]]
    # f32 expert params would promote the adds above; a bf16 residual
    # stream must come back bf16 (a promoted carry breaks the blocks
    # lax.scan under mixed precision).
    return y.reshape(b, t, d).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# The dropless layer (serving): every chosen expert that is held computes
# ---------------------------------------------------------------------------

def route_scores(params: dict, xf: jax.Array, cfg: MoEConfig):
    """The router of the dropless layer, in float32 (true-float32 product:
    a rounded logit flips a choice): scores ``s`` [N, E] (sigmoid or
    softmax of ``xf @ router``), the ``top_k`` indices of largest
    ``s + router_bias`` (the bias, where the tree has one, decides the
    choice only) and their weights: the chosen scores, normalised to sum
    to one (``normalize_gates``) and scaled by ``routed_scale``. Returns
    ``(experts [N, k] int32, weights [N, k] float32)``."""
    logits = jnp.dot(xf.astype(jnp.float32),
                     params["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    s = (jax.nn.sigmoid(logits) if cfg.scoring == "sigmoid"
         else jax.nn.softmax(logits, axis=-1))
    pick = s
    if "router_bias" in params:
        pick = s + params["router_bias"].astype(jnp.float32)
    _, experts = jax.lax.top_k(pick, cfg.top_k)
    w = jnp.take_along_axis(s, experts, axis=-1)
    if cfg.normalize_gates:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return experts.astype(jnp.int32), w * cfg.routed_scale


# The grouped products' row tile: an MXU pass streams up to 128 rows through
# a 128 x 128 block of weights, so a visit with fewer rows costs the same and
# one with more costs more (XLA:TPU's ragged dot works in tiles of 512: 16
# experts of 3 or 32 rows each pay for 512).
ROW_TILE = 128
# The bytes of the weight blocks a grid step streams from HBM (all of K by
# some of an expert's columns, of each product's weights): megabytes, so
# that the copy runs at the memory's bandwidth; two buffers of it are held
# in fast memory.
_WEIGHT_BLOCK_BYTES = 12 << 20


def row_tile(rows: int) -> int:
    """The grouped products' row tile for ``rows`` sorted rows."""
    return min(ROW_TILE, rows)


def row_tile_visits(sizes: jax.Array, rows: int):
    """The schedule of the grouped products: which (row tile, group) pairs
    of ``rows`` sorted rows, ``row_tile(rows)`` to a row tile, hold a row,
    in row order. ``sizes`` int32 [G], the groups' rows (the rows past
    their sum belong to no group and are in no visit). Returns ``(offsets
    [G + 1], group [V], row tile [V], n_visits)``, V = ceil(rows / tile)
    + G - 1, the most there can be; entries past ``n_visits`` repeat the
    last group and run past its tiles: nothing reads them."""
    g, tile = sizes.shape[0], row_tile(rows)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tile
    tiles = jnp.where(sizes > 0, (ends - 1) // tile - first + 1, 0)
    upto = jnp.cumsum(tiles)
    v = jnp.arange(-(-rows // tile) + g - 1, dtype=jnp.int32)
    group = jnp.minimum(jnp.sum(v[:, None] >= upto[None, :], axis=1), g - 1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return offsets, group, first[group] + v - (upto - tiles)[group], upto[-1]


def _grouped_kernel(offsets_ref, group_ref, tile_ref, x_ref, *refs, tm: int):
    """Grid: (tiles of N, visits); a visit is one (row tile, group) pair
    that holds a row. Scalar prefetch: the schedule of
    :func:`row_tile_visits`. x block [tm, K]: the visit's row tile; one
    weight block [K, tn] of the visit's group, or two (gate and up); out
    block [tm, tn]. One product over all of K, summed in float32; the
    rows of the tile that are the group's own are written
    (``silu(gate) * up`` in float32, rounded once, where there are two
    products) and the others left as they stand: the next visits of the
    same row tile, which follow at once, write theirs."""
    *w_refs, o_ref = refs
    v = pl.program_id(1)
    g = group_ref[v]
    x = x_ref[...]
    y = jnp.dot(x, w_refs[0][...], preferred_element_type=jnp.float32)
    if len(w_refs) == 2:
        y = jax.nn.silu(y) * jnp.dot(x, w_refs[1][...],
                                     preferred_element_type=jnp.float32)
    row = tile_ref[v] * tm + jax.lax.broadcasted_iota(
        jnp.int32, o_ref.shape, 0)
    mine = jnp.logical_and(row >= offsets_ref[g], row < offsets_ref[g + 1])
    o_ref[...] = jnp.where(mine, y.astype(o_ref.dtype), o_ref[...])


def _weight_block_cols(k: int, n: int, itemsize: int, n_w: int) -> int:
    """Columns of a weight block [K, tn]: the largest multiple of 128 that
    divides ``n`` and keeps the step's ``n_w`` blocks inside
    ``_WEIGHT_BLOCK_BYTES``; ``n`` itself where no multiple of 128 divides
    it (a block as wide as the array is always allowed)."""
    most = min(n, max(128, _WEIGHT_BLOCK_BYTES // (n_w * k * itemsize)))
    for tn in range(most // 128 * 128, 0, -128):
        if n % tn == 0:
            return tn
    return n


@functools.partial(jax.jit, static_argnames=("interpret",))
def grouped_matmul(x: jax.Array, weights: tuple, visits: tuple,
                   interpret: bool = False) -> jax.Array:
    """``x[rows of group g] @ w[g]`` for every group, rows sorted by
    group: the Pallas TPU kernel ``moe_grouped_matmul``. x [M, K];
    ``weights`` one [G, K, N] array, or two (gate, up: the result is
    ``silu(x @ gate[g]) * (x @ up[g])``, both products in one pass over
    the rows); ``visits`` from :func:`row_tile_visits` at this ``M``.
    Returns [M, N] in x's dtype; rows of no group (past
    the groups' sum) are not written and hold anything.

    The work follows the groups' rows: the grid is (tiles of N, visits),
    as many visits as there are (row tile, group) pairs holding a row.
    A step's weight block is all of K by ``tn`` columns of the visit's
    group, copied from HBM under the step before it (the blocks are
    pipelined), and not copied again where the next visit is the same
    group's next row tile: an expert's weights are read once a call
    however its rows lie. A row's result is its own: one float32 sum
    over K, whatever shares the row's tile. Jitted on its own, so the
    layers and steps that call it at one shape share one trace."""
    m, k = x.shape
    n_w, n = len(weights), weights[0].shape[2]
    tile, n_visits = row_tile(m), visits[3]
    w_size = weights[0].dtype.itemsize
    tn = _weight_block_cols(k, n, w_size, n_w)

    def x_map(ni, v, offsets, group, tiles):
        return (tiles[v], 0)

    def w_map(ni, v, offsets, group, tiles):
        return (group[v], 0, ni)

    def o_map(ni, v, offsets, group, tiles):
        return (tiles[v], ni)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(pl.cdiv(n, tn), n_visits),
        in_specs=[pl.BlockSpec((tile, k), x_map)]
        + [pl.BlockSpec((None, k, tn), w_map)] * n_w,
        out_specs=pl.BlockSpec((tile, tn), o_map),
    )
    # two buffers a block, the float32 products, and room for the compiler
    blocks = (tile * k + tile * tn) * x.dtype.itemsize + n_w * k * tn * w_size
    return pl.pallas_call(
        functools.partial(_grouped_kernel, tm=tile),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=2 * blocks + 4 * n_w * tile * tn + (16 << 20)),
        interpret=interpret,
        name="moe_grouped_matmul",
    )(*visits[:3], x, *weights)


def expert_products(xs: jax.Array, params: dict, sizes: jax.Array,
                    visits: tuple, interpret: bool | None = None
                    ) -> jax.Array:
    """The held experts' SiLU-gated MLPs on rows sorted by expert:
    ``(silu(xs @ we_g[g]) * (xs @ we_u[g])) @ we_d[g]`` for the rows of
    group g. ``interpret=None``: the kernel (:func:`grouped_matmul`,
    gate and up in one call, down in a second) on a TPU backend,
    ``jax.lax.ragged_dot`` elsewhere (the CPU tests' path, and what the
    kernel is tested against); True or False forces the kernel,
    interpreted or compiled."""
    if interpret is None and jax.devices()[0].platform != "tpu":
        a = (jax.nn.silu(jax.lax.ragged_dot(xs, params["we_g"], sizes))
             * jax.lax.ragged_dot(xs, params["we_u"], sizes))
        return jax.lax.ragged_dot(a, params["we_d"], sizes)
    a = grouped_matmul(xs, (params["we_g"], params["we_u"]), visits,
                       interpret=bool(interpret))
    return grouped_matmul(a, (params["we_d"],), visits,
                          interpret=bool(interpret))


def moe_ffn_dropless(params: dict, x: jax.Array, cfg: MoEConfig,
                     valid: jax.Array | None = None
                     ) -> tuple[jax.Array, jax.Array]:
    """Routed SiLU-gated experts without capacity: the router chooses
    ``top_k`` of all ``num_experts`` for every token, and every choice
    that falls on an expert held here (``cfg.held``; ``we_g``/``we_u``
    [G, d, f] and ``we_d`` [G, f, d] hold those G) computes. What the
    experts held elsewhere would add is left out: under expert
    parallelism the other chips add it; on one chip nothing stands in.

    The held assignments are sorted by expert, their tokens gathered into
    one [N * k, d] buffer (the worst case: every choice held here; rows
    past the held ones belong to no group and are zeroed), three grouped
    products (:func:`expert_products`: work follows the rows the groups
    really have, a row tile of at most ``ROW_TILE`` at a time) and a
    gather back, each token summing its choices in the order it made
    them. A row's result is its own: nothing depends on
    which other tokens share the call, so a request's tokens do not
    depend on its batch.

    x [..., d]; ``valid`` [...] bool: tokens that exist (padding and idle
    rows are routed nowhere and not counted). Returns ``(y, counts)``,
    counts int32 [G + 3]: tokens a held expert, then tokens routed, then
    held experts that got a token at all, then the (row tile, expert)
    pairs that held a row (:func:`row_tile_visits`: each reads one
    expert's weights once a product; held rows over visits says how full
    the row tiles were).
    """
    d = x.shape[-1]
    xf = x.reshape(-1, d)
    n, k = xf.shape[0], cfg.top_k
    first, g = cfg.held_range
    with jax.named_scope("moe_route"):
        experts, w = route_scores(params, xf, cfg)
        local = experts - first
        held = jnp.logical_and(local >= 0, local < g)
        if valid is not None:
            held = jnp.logical_and(held, valid.reshape(-1, 1))
        group = jnp.where(held, local, g).reshape(-1)          # [N * k]
        order = jnp.argsort(group, stable=True)
        sizes = jnp.zeros((g + 1,), jnp.int32).at[group].add(1)[:g]
        n_held = jnp.sum(sizes)
        token = order // k               # the sorted rows' tokens
        visits = row_tile_visits(sizes, n * k)
    with jax.named_scope("moe_experts"):
        xs = xf[token]                                         # [N * k, d]
        o = expert_products(xs, params, sizes, visits)
        o = jnp.where((jnp.arange(n * k) < n_held)[:, None], o, 0)
    with jax.named_scope("moe_combine"):
        back = jnp.argsort(order)        # row of (token, choice) in o
        picked = o[back].reshape(n, k, d).astype(jnp.float32)
        wk = jnp.where(held, w, 0.0)
        y = jnp.zeros((n, d), jnp.float32)
        for j in range(k):               # the token's own order: fixed
            y = y + wk[:, j, None] * picked[:, j]
    routed = n if valid is None else jnp.sum(valid)
    counts = jnp.concatenate([
        sizes, jnp.asarray(routed, jnp.int32)[None],
        jnp.sum(sizes > 0, dtype=jnp.int32)[None], visits[3][None]])
    return y.astype(x.dtype).reshape(x.shape), counts
