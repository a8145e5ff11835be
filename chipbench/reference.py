"""The plain reference: StarCoder2's published block in straightforward
float32 ``jax.numpy``, true-float32 matrix products, no kernels, no cache,
no batching tricks. It imports nothing of the program and is handed
nothing the program made: its weights are the benchmark's own, from the
seed (``weights.make_params``).

Block (arXiv:2402.19173; HF ``Starcoder2DecoderLayer``): pre-LayerNorm
(eps from the config), grouped-query attention with rotary embeddings
(half-split convention) and one sliding window, tanh-GELU MLP with
biases, untied head after a final LayerNorm. Two departures from the
published model, both the configuration files' (the program has neither):
no biases on the q/k/v/o projections, and an untied LM head.

``quant="int8"`` is the CONTROL, not the reference: the linear layers'
products, forward and backward, and the attention's QK^T take operands
rounded to int8 (per-tensor absmax), the nearest precision below the
bfloat16 the configurations state (and the one a v5e computes natively).
``"int8_fwd"`` (only the linear layers' forward operands, the backward
left in float32) is the mildest int8 path and is read beside it: it has
to come out not correct too.

What the reference keeps in the configuration's storage type: the
configuration states bfloat16 weights with no float32 master copy, so the
training reference rounds its weights to bfloat16 after each update (the
arithmetic of the update, and the AdamW moments, stay float32).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def round_int8(x):
    """Per-tensor absmax rounding to 255 levels."""
    s = jnp.max(jnp.abs(x)) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.round(x / s) * s


def _through(rounder):
    """Rounded in the forward pass, the identity in the backward."""
    return lambda x: x + jax.lax.stop_gradient(rounder(x) - x)


def _product_in(rounder):
    """x [n, a] @ w [a, b] with every product in the lower precision:
    both operands rounded in the forward pass, and in the backward pass
    the incoming gradient rounded too, against the rounded operands."""
    @jax.custom_vjp
    def f(x, w):
        return jnp.dot(rounder(x), rounder(w), precision=HI)

    def fwd(x, w):
        xq, wq = rounder(x), rounder(w)
        return jnp.dot(xq, wq, precision=HI), (xq, wq)

    def bwd(res, g):
        xq, wq = res
        gq = rounder(g)
        return (jnp.dot(gq, wq.T, precision=HI),
                jnp.dot(xq.T, gq, precision=HI))

    f.defvjp(fwd, bwd)
    return f


# quant -> (product of a linear layer, rounding of q and k before QK^T
# or None). "int8" computes the linear layers' products in that
# precision, forward and backward; "int8_fwd" is the milder reading kept
# beside it: only the linear layers' forward operands.
QUANT = {
    "int8": (_product_in(round_int8), _through(round_int8)),
    "int8_fwd": (lambda x, w: jnp.dot(_through(round_int8)(x),
                                      _through(round_int8)(w),
                                      precision=HI), None),
}


def linear(x, w, quant):
    """x [..., a] @ w [a, ...] in true float32; ``quant`` None, or the
    control's precision (a key of QUANT)."""
    x2, w2 = x.reshape(-1, x.shape[-1]), w.reshape(w.shape[0], -1)
    y = (QUANT[quant][0](x2, w2) if quant
         else jnp.dot(x2, w2, precision=HI))
    return y.reshape(*x.shape[:-1], *w.shape[1:])


def layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def rope(x, theta: float):
    """x [T, H, Dh], positions 0..T-1, half-split pairs (i, i + Dh/2)."""
    t, _, dh = x.shape
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def attention(q, k, v, window, q_block: int, quant=None):
    """Causal sliding-window attention, q [T, H, Dh], k/v [T, Hkv, Dh];
    query head h reads kv head h // (H / Hkv). Blocks of query rows so the
    [T, T] scores never exist at once (recomputed in the backward)."""
    t, h, dh = q.shape
    hkv = k.shape[1]
    qb = min(q_block, t)
    if t % qb:
        raise ValueError(f"sequence {t} not a multiple of q_block {qb}")
    qg = q.reshape(t, hkv, h // hkv, dh)
    kpos = jnp.arange(t)
    # the control rounds the operands of QK^T only: softmax weights over
    # a thousand keys lie under 1/254 and a per-tensor int8 scale rounds
    # them all to nought (tried: served logits 1.0 off on average), which
    # no int8 attention does, so P V stays float32
    rnd = (QUANT[quant][1] if quant else None) or (lambda x: x)
    k = rnd(k)

    @jax.checkpoint
    def block(i):
        qs = jax.lax.dynamic_slice_in_dim(qg, i * qb, qb, axis=0)
        s = jnp.einsum("qhgd,khd->hgqk", rnd(qs), k,
                       precision=HI) * dh ** -0.5
        qpos = i * qb + jnp.arange(qb)
        keep = kpos[None, :] <= qpos[:, None]
        if window is not None:
            keep &= (qpos[:, None] - kpos[None, :]) < window
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v, precision=HI)

    out = jax.lax.map(block, jnp.arange(t // qb))
    return out.reshape(t, h, dh)


def layer_fwd(bp, x, dims, quant, q_block: int):
    """One block on one sequence x [T, d]; bp holds float32 leaves."""
    h = layer_norm(x, bp["ln1_scale"], bp["ln1_bias"], dims.norm_eps)
    q = linear(h, bp["wq"], quant)                       # [T, H, Dh]
    kv = linear(h, bp["wkv"], quant)                     # [T, Hkv, 2 Dh]
    k, v = kv[..., :dims.head_dim], kv[..., dims.head_dim:]
    q, k = rope(q, dims.rope_theta), rope(k, dims.rope_theta)
    o = attention(q, k, v, dims.window, q_block, quant)
    x = x + linear(o.reshape(o.shape[0], -1), bp["wo"], quant)
    h = layer_norm(x, bp["ln2_scale"], bp["ln2_bias"], dims.norm_eps)
    y = gelu_tanh(linear(h, bp["w1"], quant) + bp["b1"])
    return x + linear(y, bp["w2"], quant) + bp["b2"]


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


# -- serving: logits of chosen rows of one sequence --------------------------

@functools.partial(jax.jit, static_argnames=("dims", "quant", "q_block"))
def sequence_logits(params, tokens, rows, *, dims, quant=None,
                    q_block=1024):
    """params: the stacked tree (any float dtype); tokens [T]; rows [R]
    positions whose next-token logits are wanted. Returns [R, vocab]
    float32. Layers run one after another, each upcast as it is used."""
    x = params["embed"][tokens].astype(F32)

    def body(x, bp):
        return layer_fwd(_f32(bp), x, dims, quant, q_block), None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    xr = layer_norm(x[rows], params["ln_f_scale"].astype(F32),
                    params["ln_f_bias"].astype(F32), dims.norm_eps)
    return linear(xr, params["head"].astype(F32), quant)


# -- training: loss, gradients and AdamW, layer by layer ---------------------

def _head_loss(x, lnf_s, lnf_b, head, targets, *, dims, quant, chunk,
               denom, keep=None):
    """Sum of next-token NLL over x [T, d] / denom, in row chunks."""
    t = x.shape[0]
    c = min(chunk, t)
    xs = x.reshape(t // c, c, -1)
    ts = targets.reshape(t // c, c)
    ks = (jnp.ones_like(ts, F32) if keep is None
          else keep.reshape(t // c, c).astype(F32))

    @jax.checkpoint
    def one(args):
        xc, tc, kc = args
        logits = linear(layer_norm(xc, lnf_s, lnf_b, dims.norm_eps), head,
                        quant)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, tc[:, None], -1)[:, 0]
                        * kc)

    return jnp.sum(jax.lax.map(one, (xs, ts, ks))) / denom


class TrainReference:
    """Three (or so) optimizer steps in float32, holding per-layer
    weights in the configuration's storage dtype and float32 AdamW
    moments. ``step`` returns the loss, every leaf's gradient norm
    (block leaves per layer) and, where asked, how far other gradients
    lie from its own, leaf by leaf."""

    def __init__(self, dims, params, opt: dict, *, quant=None,
                 q_block: int = 1024, loss_chunk: int = 1024,
                 target_keep=None):
        self.dims, self.opt, self.quant = dims, opt, quant
        self.q_block, self.loss_chunk = q_block, loss_chunk
        self.store = params["embed"].dtype
        L = dims.n_layers
        self.layers = [jax.tree.map(lambda a: a[l], params["blocks"])
                       for l in range(L)]
        self.top = {k: v for k, v in params.items() if k != "blocks"}
        zeros = lambda tree: jax.tree.map(
            lambda a: jnp.zeros(a.shape, F32), tree)
        self.m_layers = [zeros(p) for p in self.layers]
        self.v_layers = [zeros(p) for p in self.layers]
        self.m_top, self.v_top = zeros(self.top), zeros(self.top)
        self.count = 0
        # fault hook for the tests: a [T] 0/1 mask of targets that count
        self.target_keep = target_keep

        dims_, quant_, qb = dims, quant, q_block

        @jax.jit
        def fwd(bp, x):
            return jax.vmap(lambda r: layer_fwd(_f32(bp), r, dims_, quant_,
                                                qb))(x)

        @jax.jit
        def bwd(bp, x, g):
            f = lambda p, xx: jax.vmap(
                lambda r: layer_fwd(p, r, dims_, quant_, qb))(xx)
            _, vjp = jax.vjp(f, _f32(bp), x)
            gp, gx = vjp(g)
            return gx, gp

        keep = (None if target_keep is None else jnp.asarray(target_keep))
        chunk = loss_chunk

        def head_all(x, s, bb, w, targets, denom):
            f = functools.partial(_head_loss, dims=dims_, quant=quant_,
                                  chunk=chunk, denom=denom, keep=keep)
            return jnp.sum(jax.vmap(lambda xr, tr: f(xr, s, bb, w, tr))(
                x, targets))

        self._head = jax.jit(jax.value_and_grad(head_all,
                                                argnums=(0, 1, 2, 3)))
        self._fwd, self._bwd = fwd, bwd
        self._adam = jax.jit(self._adam_leaf, donate_argnums=(2, 3))
        self._err = jax.jit(lambda g, other, scale: jnp.sqrt(jnp.sum(
            jnp.square(g - other.astype(F32) * scale))))

    def _lr(self, count: int) -> float:
        o = self.opt
        t = min(count, o["cosine_decay_steps"])
        return o["learning_rate"] * 0.5 * (
            1.0 + math.cos(math.pi * t / o["cosine_decay_steps"]))

    def _adam_leaf(self, p, g, m, v, count, lr):
        """optax.adamw: scale_by_adam, add_decayed_weights (every leaf),
        scale by -lr(count). Returns (p', m', v', |g|)."""
        o = self.opt
        b1, b2 = o["b1"], o["b2"]
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        t = count + 1
        u = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + o["eps"])
        pf = p.astype(F32)
        pf = pf - lr * (u + o["weight_decay"] * pf)
        return pf.astype(p.dtype), m, v, jnp.sqrt(jnp.sum(jnp.square(g)))

    def _update(self, params, grads, ms, vs):
        lr = jnp.asarray(self._lr(self.count), F32)
        cnt = jnp.asarray(self.count, F32)
        norms = {}
        for k in params:
            params[k], ms[k], vs[k], norms[k] = self._adam(
                params[k], grads[k], ms[k], vs[k], cnt, lr)
        return norms

    def step(self, tokens, targets, against=None, keep_grad=False):
        """tokens/targets [B, T] int. One AdamW step; returns
        (loss, {leaf: gradient norm, block leaves as [L]}, extra).
        ``against``: {name: (tree, scale)}, other sides' gradients of
        this step as host arrays in the stacked layout (``scale``
        multiplies them): ``extra["err"][name]`` is every leaf's
        ``||g - scale * other||``, laid out like the norms.
        ``keep_grad``: ``extra["grad_tree"]`` is this side's own
        gradient as such a host tree (float32)."""
        dims = self.dims
        against = against or {}
        err = {name: {} for name in against}
        kept = {"blocks": {}} if keep_grad else None

        def note(grads, layer=None):
            for k, g in grads.items():
                for name, (tree, scale) in against.items():
                    e = float(self._err(g, jnp.asarray(
                        tree[k] if layer is None
                        else tree["blocks"][k][layer]), scale))
                    if layer is None:
                        err[name][k] = e
                    else:
                        err[name].setdefault(
                            "blocks/" + k, np.zeros(dims.n_layers))[layer] = e
                if kept is not None and layer is None:
                    kept[k] = np.asarray(g)
                elif kept is not None:
                    kept["blocks"].setdefault(
                        k, [None] * dims.n_layers)[layer] = np.asarray(g)
        tokens, targets = jnp.asarray(tokens), jnp.asarray(targets)
        b, t = tokens.shape
        xs = [self.top["embed"][tokens].astype(F32)]
        for bp in self.layers:
            xs.append(self._fwd(bp, xs[-1]))
        denom = (b * t if self.target_keep is None else b * float(np.sum(
            np.asarray(self.target_keep))))

        loss, (gx, gs, gb, gw) = self._head(
            xs[-1], self.top["ln_f_scale"].astype(F32),
            self.top["ln_f_bias"].astype(F32), self.top["head"].astype(F32),
            targets, jnp.asarray(denom, F32))
        top_grads = {"ln_f_scale": gs, "ln_f_bias": gb, "head": gw}
        layer_norms = []
        for l in reversed(range(dims.n_layers)):
            gx, gp = self._bwd(self.layers[l], xs[l], gx)
            xs[l + 1] = None
            note(gp, l)
            layer_norms.append(self._update(
                self.layers[l], gp, self.m_layers[l], self.v_layers[l]))
            del gp
        layer_norms.reverse()
        top_grads["embed"] = jnp.zeros(self.top["embed"].shape, F32).at[
            tokens.reshape(-1)].add(gx.reshape(-1, gx.shape[-1]))
        note(top_grads)
        norms = self._update(self.top, top_grads, self.m_top, self.v_top)
        self.count += 1
        out = {k: float(v) for k, v in norms.items()}
        for k in layer_norms[0]:
            out["blocks/" + k] = np.array(
                [float(ln[k]) for ln in layer_norms])
        if kept is not None:
            kept["blocks"] = {k: np.stack(v)
                              for k, v in kept["blocks"].items()}
        return float(loss), out, {"err": err, "grad_tree": kept}

    def drop_moments(self):
        """Free the AdamW moments (the last step is done)."""
        self.m_layers = self.v_layers = self.m_top = self.v_top = None

    def stacked_params(self):
        """The current weights in the program's stacked layout."""
        out = dict(self.top)
        out["blocks"] = jax.tree.map(lambda *xs: jnp.stack(xs),
                                     *self.layers)
        return out


# -- norms of leaves, for either side ----------------------------------------

@jax.jit
def _leaf_norms(a, b):
    def one(x, y):
        d = x.astype(F32) - y.astype(F32)
        return jnp.sqrt(jnp.sum(jnp.square(d)))

    def blocks(x, y):
        d = x.astype(F32) - y.astype(F32)
        return jnp.sqrt(jnp.sum(jnp.square(d),
                                axis=tuple(range(1, d.ndim))))

    out = {k: one(v, b[k]) for k, v in a.items() if k != "blocks"}
    out.update({"blocks/" + k: blocks(v, b["blocks"][k])
                for k, v in a["blocks"].items()})
    return out


def leaf_norms(tree, minus=None) -> dict:
    """{leaf: ||tree - minus||}, block leaves per layer ([L] arrays).
    ``minus`` None means zero."""
    if minus is None:
        minus = jax.tree.map(jnp.zeros_like, tree)
    return {k: np.asarray(v, np.float64)
            for k, v in _leaf_norms(tree, minus).items()}


def leaf_gaps(prog: dict, ref: dict, skip=None, err=None) -> dict:
    """{leaf entry: gap between the program's and the reference's norm,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger}; block leaves are one entry a layer,
    ``name[layer]``. ``skip``: leaf entries (name, layer) to leave out.
    With ``err`` (the norms of the two sides' difference, laid out like
    the norms; ``prog`` is then not read) the difference's norm takes the
    gap's place: first order in a rounding that a gap of norms sees in
    the second order only."""
    names, d, r = [], [], []
    for k in sorted(ref):
        rv = np.atleast_1d(np.asarray(ref[k], np.float64))
        dv = (np.atleast_1d(np.asarray(err[k], np.float64))
              if err is not None else
              np.abs(np.atleast_1d(np.asarray(prog[k], np.float64)) - rv))
        for i in range(len(rv)):
            if skip and (k, i) in skip:
                continue
            names.append(f"{k}[{i}]" if len(rv) > 1 else k)
            d.append(dv[i])
            r.append(rv[i])
    d, r = np.array(d), np.array(r)
    gaps = d / np.maximum(r, float(np.median(r)))
    return dict(zip(names, gaps.tolist()))


def worst_leaf_gap(prog: dict, ref: dict, skip=None, err=None) -> tuple:
    """(widest gap, its leaf, the median leaf's gap) of leaf_gaps."""
    gaps = leaf_gaps(prog, ref, skip, err)
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst, float(np.median(list(gaps.values())))


def near_zero_gradient_leaves(ref_grad_norms: dict, rel=1e-3) -> set:
    """Leaf entries whose reference gradient is under ``rel`` of the
    median leaf's: under Adam they move by round-off alone."""
    allv = np.concatenate([np.atleast_1d(v) for v in ref_grad_norms.values()])
    med = float(np.median(allv))
    out = set()
    for k, v in ref_grad_norms.items():
        for i, x in enumerate(np.atleast_1d(v)):
            if x < rel * med:
                out.add((k, i))
    return out
