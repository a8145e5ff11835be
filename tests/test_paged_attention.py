"""Paged-attention parity: the serving cache's read path vs the dense
cache.

The contract (docs/SERVING.md): the XLA gather path produces BITWISE the
dense-cache result — paging is an indirection, never a numeric change.
The Pallas kernels (decode: one query token a row; prefill: a chunk of
them) stream the row's pages through an online softmax, so they sum in
another order and agree to rounding: float32 within a few ulp of the
row, bfloat16 within one bf16 ulp of each output (exact greedy-token
identity between the two is pinned at engine level, tests/test_serve.py
and tests/test_prefix_cache.py). Stale page contents
are unreachable on both paths (masked to exact zeros), so a request's
values cannot depend on who held its pages before."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from distributed_model_parallel_tpu.ops import paged_attention as pa

pytestmark = pytest.mark.serve


def _pool(seed, p=16, page=8, hkv=2, dh=16, dtype=jnp.float32):
    k = jax.random.key(seed)
    return (jax.random.normal(jax.random.fold_in(k, 0),
                              (p, page, hkv, dh), dtype),
            jax.random.normal(jax.random.fold_in(k, 1),
                              (p, page, hkv, dh), dtype))


def _case(h=4, dh=16, page=8, n=4):
    kp, vp = _pool(0, page=page, dh=dh)
    tables = jnp.asarray([[3, 7, 1, 0], [2, 5, 0, 0], [9, 8, 4, 6]],
                         jnp.int32)[:, :n]
    positions = jnp.asarray([19, 10, 31], jnp.int32)
    q = jax.random.normal(jax.random.key(7), (3, 1, h, dh))
    return q, kp, vp, tables, positions


def _dense(q, kp, vp, tables, positions, window=None):
    """The dense-cache reference: pages assembled contiguously in logical
    order, shared attend math — what _cached_block computes."""
    b, n = tables.shape
    page = kp.shape[1]
    kr = kp[tables].reshape(b, n * page, *kp.shape[2:])
    vr = vp[tables].reshape(b, n * page, *vp.shape[2:])
    return pa.attend_rows(q, kr, vr, positions[:, None], positions + 1,
                          window)


def _assert_rounding_close(out, ref, f32_ulps=4):
    """The kernel-vs-dense tolerance, set from the dtype: float32 within
    4 ulp of the row's largest output (the row = one head's [Dh] vector),
    bfloat16 within one bf16 ulp of each output."""
    assert out.dtype == ref.dtype and out.shape == ref.shape
    o = np.asarray(out, np.float32)
    r = np.asarray(ref, np.float32)
    if ref.dtype == jnp.bfloat16:
        tol = 2.0 ** (np.floor(np.log2(np.maximum(
            np.abs(r), np.finfo(np.float32).tiny))) - 7)
    else:
        tol = f32_ulps * np.finfo(np.float32).eps * np.abs(r).max(
            axis=-1, keepdims=True)
    assert (np.abs(o - r) <= tol).all(), float(np.abs(o - r).max())


def test_xla_gather_matches_dense_bitwise():
    q, kp, vp, tables, positions = _case()
    out = pa.paged_attention_xla(q, kp, vp, tables, positions[:, None],
                                 positions + 1)
    assert (out == _dense(q, kp, vp, tables, positions)).all()


def test_kernel_interpret_matches_dense_bitwise():
    q, kp, vp, tables, positions = _case()
    out = pa.paged_attention_kernel(q, kp, vp, tables, positions,
                                    interpret=True)
    _assert_rounding_close(out, _dense(q, kp, vp, tables, positions))


def test_kernel_windowed_matches_dense_bitwise():
    q, kp, vp, tables, positions = _case()
    out = pa.paged_attention_kernel(q, kp, vp, tables, positions,
                                    window=8, interpret=True)
    _assert_rounding_close(out,
                           _dense(q, kp, vp, tables, positions, window=8))


def test_kernel_gqa_grouping_matches_dense():
    # 8 query heads over 2 kv heads: head h reads kv head h // 4, the
    # _cached_block mapping the shared math must reproduce.
    q, kp, vp, tables, positions = _case(h=8)
    out = pa.paged_attention_kernel(q, kp, vp, tables, positions,
                                    interpret=True)
    _assert_rounding_close(out, _dense(q, kp, vp, tables, positions))


def test_stale_page_contents_unreachable():
    """Rewriting every position past each row's length — including pages
    the row's table points at but hasn't filled, with NaN — must not
    change a single bit of the output: freed pages are reused without
    clearing, so this is the isolation continuous batching rests on."""
    q, kp, vp, tables, positions = _case()
    ref = pa.paged_attention_xla(q, kp, vp, tables, positions[:, None],
                                 positions + 1)
    kn, vn = np.array(kp), np.array(vp)
    page = kp.shape[1]
    used = set()
    for row, pos in zip(np.asarray(tables), np.asarray(positions)):
        for j, pid in enumerate(row):
            for off in range(page):
                if j * page + off <= pos:
                    used.add((int(pid), off))
    for pid in range(kn.shape[0]):
        for off in range(page):
            if (pid, off) not in used:
                kn[pid, off] = np.nan
                vn[pid, off] = np.nan
    out = pa.paged_attention_xla(q, jnp.asarray(kn), jnp.asarray(vn),
                                 tables, positions[:, None], positions + 1)
    assert (out == ref).all()
    clean = pa.paged_attention_kernel(q, kp, vp, tables, positions,
                                      interpret=True)
    outk = pa.paged_attention_kernel(q, jnp.asarray(kn), jnp.asarray(vn),
                                     tables, positions, interpret=True)
    assert (outk == clean).all()
    _assert_rounding_close(outk, ref)


# The kernel's loop runs over blocks of several pages, from the block of
# the band's first page to the block of pos // page. Positions below are
# in units the code derives (bk = keys a block), so the edges stay edges
# if the block size changes. Each case: (positions, window) as functions
# of (bk, page, n_pages).
_BLOCK_EDGES = {
    "idle-row-context-1": lambda bk, page, n: ([0], None),
    "ends-on-a-blocks-last-key":
        lambda bk, page, n: ([bk - 1, 2 * bk - 1], None),
    "ends-on-a-blocks-first-key":
        lambda bk, page, n: ([bk, 2 * bk], None),
    "ends-mid-page":
        lambda bk, page, n: ([bk + page + page // 2 - 1], None),
    "rows-of-1-2-and-max-blocks":
        lambda bk, page, n: ([page // 2, bk + 1, n * page - 1], None),
    "window-first-page-not-block-aligned":
        lambda bk, page, n: ([2 * bk + 5 * page + 2, bk + 3, 2],
                             bk + 2 * page),
    "unused-table-entries-name-a-nan-page":
        lambda bk, page, n: ([0, page - 1, bk + 1, 2 * bk - 1], None),
}


@pytest.mark.parametrize("edge", list(_BLOCK_EDGES))
def test_kernel_block_loop_edges(edge):
    """The block loop's bounds, against the dense reference. Every row's
    table holds its live pages and, outside them, the id of a page that
    is NaN throughout (id 0), and the interpreter hands the kernel NaN
    for scratch it has not written: a page the loop copied and the band
    did not mask, or a buffer row the loop left stale and the band did
    not mask, turns the output NaN."""
    page, hkv, h, dh, blocks = 8, 2, 4, 16, 3
    ppb = pa._pages_per_block(page, hkv, dh, 1 << 30)
    n, bk = blocks * ppb, ppb * page
    positions, window = _BLOCK_EDGES[edge](bk, page, n)
    b = len(positions)
    kp, vp = _pool(17, p=1 + b * n, page=page, hkv=hkv, dh=dh)
    # Non-negative V: over hundreds of keys a signed V averages out to a
    # tenth of its terms, and 4 ulp of that output lies under the rounding
    # of either side's sums. Without cancellation the tolerance holds as
    # it stands.
    kp, vp = kp.at[0].set(jnp.nan), jnp.abs(vp).at[0].set(jnp.nan)
    ids = 1 + np.random.default_rng(3).permutation(b * n).reshape(b, n)
    live = np.asarray([[pa._first_page(p, page, window) <= j <= p // page
                        for j in range(n)] for p in positions])
    tables = jnp.asarray(np.where(live, ids, 0), jnp.int32)
    positions = jnp.asarray(positions, jnp.int32)
    q = jax.random.normal(jax.random.key(23), (b, 1, h, dh))
    out = pa.paged_attention_kernel(
        q, kp, vp, tables, positions, window=window,
        interpret=pltpu.InterpretParams(uninitialized_memory="nan"))
    ref = _dense(q, kp, vp, jnp.asarray(ids, jnp.int32), positions,
                 window=window)
    assert np.isfinite(np.asarray(ref)).all()
    _assert_rounding_close(out, ref)


# The prefill kernel: a chunk of C queries a row from pos0, against the
# live blocks of the row's pages. Sizes in units the code derives (bk =
# keys a block), so the edges stay edges if the block size changes. Each
# case: a function of (bk, page) giving the chunk, the rows' first
# positions and valid counts, the heads, the window and the dtype.
def _prefill(c, pos0, n_valid=None, h=4, hkv=2, window=None,
             dtype=jnp.float32, tile_bytes=None):
    return dict(c=c, pos0=pos0, n_valid=n_valid or [c] * len(pos0), h=h,
                hkv=hkv, window=window, dtype=dtype, tile_bytes=tile_bytes)


_PREFILL_CASES = {
    "verify-window-of-4-rows-at-differing-pos0":
        lambda bk, page: _prefill(4, [0, page + 3, bk - 2], [4, 4, 3]),
    "chunk-of-32-from-position-0":
        lambda bk, page: _prefill(32, [0]),
    "pos0-inside-a-page":
        lambda bk, page: _prefill(32, [bk + page + 3]),
    "pos0-on-a-block-boundary":
        lambda bk, page: _prefill(32, [bk, 2 * bk]),
    "chunk-crosses-a-block-edge":
        lambda bk, page: _prefill(48, [bk - 20]),
    "padded-tail":
        lambda bk, page: _prefill(32, [bk + 5], [9]),
    "row-of-length-0-beside-a-live-one":
        lambda bk, page: _prefill(8, [0, 11], [0, 8]),
    "query-tiles-of-16-the-last-partial-and-wholly-padded":
        lambda bk, page: _prefill(40, [bk - 9], [20], tile_bytes=1),
    "one-kv-head":
        lambda bk, page: _prefill(32, [bk - 7], h=4, hkv=1),
    "eight-kv-heads":
        lambda bk, page: _prefill(32, [bk - 7], h=16, hkv=8),
    "window-narrower-than-the-context":
        lambda bk, page: _prefill(32, [2 * bk + 5],
                                  window=bk + 2 * page + 3),
    "window-narrower-than-the-chunk":
        lambda bk, page: _prefill(32, [bk - 10, 3], window=8),
    "window-and-query-tiles":
        lambda bk, page: _prefill(48, [bk + 1], [41], window=20,
                                  tile_bytes=1),
    "bfloat16-two-kv-heads":
        lambda bk, page: _prefill(32, [bk - 7], [30], dtype=jnp.bfloat16),
    "bfloat16-eight-kv-heads-windowed":
        lambda bk, page: _prefill(32, [bk - 7], h=16, hkv=8,
                                  window=bk // 2, dtype=jnp.bfloat16),
    "bfloat16-one-kv-head":
        lambda bk, page: _prefill(32, [bk + 3], h=4, hkv=1,
                                  dtype=jnp.bfloat16),
}


def _prefill_inputs(case, page, dh, seed):
    """Pools whose page 0 is NaN throughout, a table of every row's own
    pages (``ids``), and the same table with page 0 wherever the kernel
    has no business (``live``): left of the band's first page, past the
    row's length."""
    b, c, hkv, window = (len(case["pos0"]), case["c"], case["hkv"],
                         case["window"])
    pos0 = np.asarray(case["pos0"])
    lengths = pos0 + np.asarray(case["n_valid"])
    n = -(-(int(pos0.max()) + c) // page) + 1
    kp, vp = _pool(seed, p=1 + b * n, page=page, hkv=hkv, dh=dh,
                   dtype=case["dtype"])
    # Non-negative V, as in the decode kernel's edge cases above.
    kp, vp = kp.at[0].set(jnp.nan), jnp.abs(vp).at[0].set(jnp.nan)
    ids = 1 + np.random.default_rng(seed).permutation(b * n).reshape(b, n)
    live = np.asarray([[pa._first_page(p, page, window) <= j < -(-ln // page)
                        for j in range(n)] for p, ln in zip(pos0, lengths)])
    q = jax.random.normal(jax.random.key(seed + 1),
                          (b, c, case["h"], dh), case["dtype"])
    return (q, kp, vp, jnp.asarray(ids, jnp.int32),
            jnp.asarray(np.where(live, ids, 0), jnp.int32),
            jnp.asarray(pos0, jnp.int32), jnp.asarray(lengths, jnp.int32))


def _interpreter(dtype):
    """Scratch the kernel has not written reads as NaN, so a buffer row
    the loop left stale and the band did not mask turns the output NaN.
    That interpreter has no 16-bit-to-word view of a buffer
    (``_head_rows``); bfloat16 cases take the plain one."""
    if dtype == jnp.bfloat16:
        return True
    return pltpu.InterpretParams(uninitialized_memory="nan")


@pytest.mark.parametrize("name", list(_PREFILL_CASES))
def test_prefill_kernel_matches_attend_rows(name, monkeypatch):
    """The prefill kernel in interpret mode against ``attend_rows`` on
    the gathered view, on every valid query (a padded query's output is
    finite and compared with nothing). A page the loop copied and the
    mask let through, or a table entry read outside the band, is NaN."""
    page, dh = 8, 16
    case = _PREFILL_CASES[name](pa._PREFILL_BLOCK_KEYS, page)
    if case["tile_bytes"]:
        # the smallest query tile the kernel cuts: several tiles a chunk
        monkeypatch.setattr(pa, "_PREFILL_TILE_BYTES", case["tile_bytes"])
        assert pa._prefill_query_tile(case["c"], 2, dh, 4) < case["c"]
    q, kp, vp, ids, live, pos0, lengths = _prefill_inputs(case, page, dh, 31)
    out = pa.paged_prefill_attention(
        q, kp, vp, live, pos0, lengths, window=case["window"],
        interpret=_interpreter(case["dtype"]))
    positions = pos0[:, None] + jnp.arange(case["c"])[None]
    ref = pa.paged_attention_xla(q, kp, vp, ids, positions, lengths,
                                 case["window"])
    valid = np.arange(case["c"])[None] < np.asarray(case["n_valid"])[:, None]
    assert np.isfinite(np.asarray(out, np.float32)).all()
    assert np.isfinite(np.asarray(ref, np.float32)[valid]).all()
    # float32: 16 ulp of the row, not the decode cases' 4. A chunk is
    # the worst of a hundred times as many outputs, each over hundreds
    # of keys; against a float64 softmax the kernel reads 7-10 ulp at
    # the worst and the gather path 3.5-6.
    _assert_rounding_close(out[valid], ref[valid], f32_ulps=16)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_prefill_kernel_stale_pages_unreachable(dtype):
    """The prefill kernel's side of ``test_stale_page_contents_
    unreachable``: every pool position at or past a row's length, on its
    own pages and on everybody else's, rewritten with NaN and with large
    values, changes no bit of any query's output."""
    page, dh = 8, 16
    bk = pa._PREFILL_BLOCK_KEYS
    case = _prefill(24, [bk - 5, 2], [13, 24], window=bk, dtype=dtype)
    q, kp, vp, ids, _, pos0, lengths = _prefill_inputs(case, page, dh, 41)
    kp, vp = kp.at[0].set(0.5), vp.at[0].set(0.5)
    run = functools.partial(pa.paged_prefill_attention, window=bk,
                            interpret=_interpreter(dtype))
    clean = run(q, kp, vp, ids, pos0, lengths)
    written = np.zeros(kp.shape[:2], bool)
    for row, ln in zip(np.asarray(ids), np.asarray(lengths)):
        for j, pid in enumerate(row):
            written[pid, :max(0, min(page, ln - j * page))] = True
    for poison in (np.nan, 3e38 if dtype == jnp.float32 else 3e4):
        kn = jnp.where(written[:, :, None, None], kp, poison)
        vn = jnp.where(written[:, :, None, None], vp, poison)
        out = run(q, kn, vn, ids, pos0, lengths)
        assert np.isfinite(np.asarray(out, np.float32)).all()
        assert (out == clean).all()


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_prefill_chunk_matches_whole_prompt(impl):
    """A C-token chunk read of the paged cache scores what the same
    positions score in a single whole-prompt pass (intra-chunk causality
    comes from the shared band mask), on the gather path and through the
    prefill kernel (``impl="pallas"``, interpreted). XLA's CPU dot picks
    its blocking from the operand shapes, so an 8-row chunk and the
    24-row prompt contract in different orders: equal to float32
    rounding, not bitwise. What serving needs from it — chunking changes
    no greedy token — is asserted on the engine below."""
    kp, vp = _pool(3)
    table = jnp.asarray([[5, 2, 11, 4]], jnp.int32)
    t0 = 24
    q = jax.random.normal(jax.random.key(9), (1, t0, 4, 16))
    whole = pa.paged_attention_xla(
        q, kp, vp, table, jnp.arange(t0)[None], jnp.asarray([t0]))
    chunk = 8
    parts = [
        pa.paged_attention(
            q[:, lo:lo + chunk], kp, vp, table,
            (lo + jnp.arange(chunk))[None], jnp.asarray([lo + chunk]),
            impl=impl)
        for lo in range(0, t0, chunk)
    ]
    _assert_rounding_close(jnp.concatenate(parts, axis=1), whole)

    from distributed_model_parallel_tpu.models import transformer as tfm
    from distributed_model_parallel_tpu.serve import Engine, ServeConfig

    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                                n_layers=2, d_ff=64, max_seq_len=128,
                                pos_embedding="rope")
    params = tfm.init_params(jax.random.key(0), cfg)
    prompt = list(range(1, t0 + 1))
    tokens = []
    for prefill_chunk in (chunk, 32):
        eng = Engine(params, cfg, ServeConfig(
            n_slots=2, page_size=8, n_pages=32, max_seq_len=64,
            prefill_chunk=prefill_chunk, attn_impl=impl))
        req = eng.submit(prompt, 16)
        eng.run()
        tokens.append(req.generated)
    assert tokens[0] == tokens[1]


def test_dispatch_sends_chunks_to_the_prefill_kernel(monkeypatch):
    """``impl="pallas"`` runs a multi-token call through the prefill
    kernel; pools it cannot take apart (float16, three KV heads) stay on
    the gather path, by the shape."""
    q, kp, vp, tables, positions = _case()
    q4 = jax.random.normal(jax.random.key(8), (3, 4, *q.shape[2:]))
    pos = positions[:, None] - 3 + jnp.arange(4)[None]
    calls = []
    real = pa.paged_prefill_attention
    monkeypatch.setattr(pa, "paged_prefill_attention",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    out = pa.paged_attention(q4, kp, vp, tables, pos, positions + 1,
                             impl="pallas")
    ref = pa.paged_attention(q4, kp, vp, tables, pos, positions + 1,
                             impl="xla")
    assert calls == [1]
    _assert_rounding_close(out, ref)
    assert pa.prefill_kernel_takes(4, kp.astype(jnp.bfloat16))
    assert not pa.prefill_kernel_takes(1, kp)
    assert not pa.prefill_kernel_takes(4, kp.astype(jnp.float16))
    odd = jnp.zeros((4, 8, 3, 16), jnp.bfloat16)
    assert not pa.prefill_kernel_takes(4, odd)
    with pytest.raises(ValueError, match="even number of KV heads"):
        pa.paged_prefill_attention(
            jnp.zeros((1, 4, 6, 16), jnp.bfloat16), odd, odd,
            jnp.zeros((1, 2), jnp.int32), jnp.zeros(1, jnp.int32),
            jnp.ones(1, jnp.int32), interpret=True)


def test_dispatch_rejects_unknown_impl_and_multi_token_kernel():
    q, kp, vp, tables, positions = _case()
    with pytest.raises(ValueError, match="impl"):
        pa.paged_attention(q, kp, vp, tables, positions[:, None],
                           positions + 1, impl="cuda")
    with pytest.raises(ValueError, match="one query token"):
        pa.paged_attention_kernel(jnp.tile(q, (1, 2, 1, 1)), kp, vp,
                                  tables, positions, interpret=True)


def test_bfloat16_kernel_parity():
    kp, vp = _pool(5, dtype=jnp.bfloat16)
    tables = jnp.asarray([[1, 2, 0, 0]], jnp.int32)
    positions = jnp.asarray([13], jnp.int32)
    q = jax.random.normal(jax.random.key(11), (1, 1, 4, 16),
                          jnp.bfloat16)
    x = pa.paged_attention_xla(q, kp, vp, tables, positions[:, None],
                               positions + 1)
    k = pa.paged_attention_kernel(q, kp, vp, tables, positions,
                                  interpret=True)
    assert x.dtype == jnp.bfloat16
    _assert_rounding_close(k, x)


@pytest.mark.parametrize("ring", [False, True], ids=["full", "window-ring"])
@pytest.mark.parametrize("c", [1, 4], ids=["decode", "chunk"])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_stacked_pool_reads_one_layers_pages(impl, c, ring):
    """A pool ``[L, P, page, Hkv, Dh]`` read at ``layer`` is the read of
    that layer's slab alone, bit for bit on every path (the same pages
    through the same sums), for the first, a middle and the last layer,
    with ``layer`` static and traced under ``lax.scan`` (how the serving
    steps pass it). Every other layer's slab holds large finite garbage:
    a page id that missed its layer's offset would bring it in. Under a
    window the table is a sliding layer's: ring pages, repeated."""
    n_layers, window = 4, (8 if ring else None)
    q, _, _, tables, positions = _case()
    if ring:
        tables = jnp.take(tables[:, :3], jnp.arange(tables.shape[1]) % 3,
                          axis=1)
    q = jax.random.normal(jax.random.key(8), (3, c, *q.shape[2:]))
    pos = positions[:, None] - (c - 1) + jnp.arange(c)[None]
    # Non-negative V, as in the kernels' edge cases above.
    slabs = [(kp, jnp.abs(vp))
             for kp, vp in map(_pool, range(50, 50 + n_layers))]
    read = functools.partial(pa.paged_attention, q, tables=tables,
                             positions=pos, lengths=positions + 1,
                             window=window, impl=impl)

    @jax.jit
    def read_layers(kstack, vstack, layers):
        return jax.lax.scan(
            lambda _, i: (None, read(k_pool=kstack, v_pool=vstack, layer=i)),
            None, layers)[1]

    for layer in (0, 2, n_layers - 1):
        kp, vp = slabs[layer]
        kstack, vstack = (
            jnp.full((n_layers, *slab.shape), 3e38).at[layer].set(slab)
            for slab in (kp, vp))
        alone = read(k_pool=kp, v_pool=vp)
        assert np.isfinite(np.asarray(alone)).all()
        assert (read(k_pool=kstack, v_pool=vstack, layer=layer)
                == alone).all()
        assert (read_layers(kstack, vstack, jnp.asarray([layer]))[0]
                == alone).all()
        _assert_rounding_close(alone, pa.paged_attention_xla(
            q, kp, vp, tables, pos, positions + 1, window),
            f32_ulps=4 if c == 1 else 16)
    # every layer its own contents, one traced read after another
    kstack, vstack = (jnp.stack(pools) for pools in zip(*slabs))
    outs = read_layers(kstack, vstack, jnp.arange(n_layers))
    for layer, (kp, vp) in enumerate(slabs):
        assert (outs[layer] == read(k_pool=kp, v_pool=vp)).all()
