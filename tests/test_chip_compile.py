"""The main path's Pallas kernels compile for the chip, asked of the
chip's own compiler without the chip.

libtpu compiles for a *described* ``v5e:2x2`` device here in the
sandbox: what Mosaic or XLA:TPU would refuse on the chip (an unsupported
matmul shape, a misaligned slice, too much VMEM) is refused here, at no
chip time. Interpret-mode tests cannot see any of that. Nothing runs, so
these say nothing about results — parity lives in the interpret-mode
tests next to each kernel.

The topology is described inside a module-scoped fixture: one process
at a time may load the TPU library, and only the xdist worker that is
handed this file may try. All compiles stay in this one file for the
same reason.
"""

import functools
import itertools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from distributed_model_parallel_tpu.ops import (
    gated_delta as gd,
    paged_attention as pa,
    pallas_attention as fa,
    pallas_optim,
)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot ask"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described device can be written to the persistent
    # cache but never read back without a chip; keep it out of the way.
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("b,h,hkv,t,dtype,window", [
    (8, 8, 8, 2048, jnp.bfloat16, None),
    (8, 8, 8, 2048, jnp.float32, None),
    (8, 8, 2, 2048, jnp.bfloat16, None),
    (8, 8, 2, 2048, jnp.float32, None),
    (8, 8, 8, 2048, jnp.bfloat16, 512),
    # the benchmark's serving cells (chipbench/configs/starcoder2-3b.json
    # under traffic/code-*.json): 32 slots, 256 pages a row, window = cap
    (32, 24, 2, 4096, jnp.bfloat16, 4096),
    # k-exaone-236b-a23b-ep8 under traffic/mixed-batch.json: 64 slots, 64
    # query heads over 8 KV heads, 1,024 pages a row; the full layer, and
    # a sliding layer (its table is the ring's pages, repeated)
    (64, 64, 8, 16384, jnp.bfloat16, None),
    (64, 64, 8, 16384, jnp.bfloat16, 128),
    # olmo-hybrid-7b-pp2 under traffic/longgen-batch.json: 32 slots, 30
    # heads with no grouping, stored as 32 (paged_kv.stored_kv_heads), 512
    # pages a row
    (32, 32, 32, 8192, jnp.bfloat16, None),
], ids=["bf16", "f32", "bf16-gqa", "f32-gqa", "bf16-window",
        "starcoder2-3b-serving", "k-exaone-full-layer",
        "k-exaone-sliding-layer", "olmo-hybrid-full-layer"])
def test_paged_decode_kernel_compiles(one_chip, b, h, hkv, t, dtype, window):
    """Serving decode shapes: heads x 128, page 16, one pool page for
    every slot's full context."""
    dh, page = 128, 16
    n = t // page

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = sds((b * n, page, hkv, dh), dtype)
    text = _compiled_text(
        functools.partial(pa.paged_attention_kernel, window=window,
                          interpret=False),
        sds((b, 1, h, dh), dtype), pool, pool, sds((b, n), jnp.int32),
        sds((b,), jnp.int32))
    assert "tpu_custom_call" in text
    # the name the benchmark's readers find the kernel by
    assert "%paged_decode_attention" in text


@pytest.mark.parametrize("h,hkv,n,dtype,window", [
    # starcoder2-3b under traffic/code-*.json: 256 pages a row, window =
    # the context cap
    (24, 2, 256, jnp.bfloat16, 4096),
    # k-exaone-236b-a23b-ep8 under traffic/mixed-batch.json: 1,024 pages
    # a row; the full layer, and a sliding layer (its table is the ring's
    # pages, repeated)
    (64, 8, 1024, jnp.bfloat16, None),
    (64, 8, 1024, jnp.bfloat16, 128),
    (8, 2, 128, jnp.float32, None),
    # olmo-hybrid-7b-pp2: 32 stored heads, one query head a KV head; a
    # turn's keys are cut to what two buffers of all heads' rows may hold
    (32, 32, 512, jnp.bfloat16, None),
], ids=["starcoder2-3b-serving", "k-exaone-full-layer",
        "k-exaone-sliding-layer", "f32-gqa", "olmo-hybrid-full-layer"])
def test_paged_prefill_kernel_compiles(one_chip, h, hkv, n, dtype, window):
    """The serving cells' prefill chunk: 512 query tokens of one row,
    heads x 128, page 16, within the default scoped VMEM."""
    c, dh, page = 512, 128, 16

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = sds((32 * n, page, hkv, dh), dtype)
    text = _compiled_text(
        functools.partial(pa.paged_prefill_attention, window=window,
                          interpret=False),
        sds((1, c, h, dh), dtype), pool, pool, sds((1, n), jnp.int32),
        sds((1,), jnp.int32), sds((1,), jnp.int32))
    assert "tpu_custom_call" in text
    # the name a trace lists the kernel under, and not the decode
    # kernel's, which the benchmark's decode readers match
    assert "%paged_prefill_attention" in text
    assert "%paged_decode_attention" not in text
    # the pools reach the kernel as views of themselves
    assert not re.search(r"= bf16\[\d+,\d+,128\]\S* copy\(", text)


def _compile_kernels(monkeypatch):
    """The paged kernels ask jax.devices() whether to interpret; here
    they compile for the described chip."""
    for name in ("paged_attention_kernel", "paged_prefill_attention"):
        monkeypatch.setattr(
            pa, name, functools.partial(getattr(pa, name), interpret=False))
    monkeypatch.setattr(gd, "gated_delta_kernel", functools.partial(
        gd.gated_delta_kernel, interpret=False))


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_prefill_step_holds_the_kernel_and_no_score_tensor(
        one_chip, monkeypatch, impl):
    """A small ``jit_prefill_step`` compiled for the chip. Through the
    kernel it holds ``%paged_prefill_attention`` and no float32 array of
    chunk x max_seq_len elements a head: the gather path's scores, which
    the same step under ``impl="xla"`` does hold (the control: the
    pattern finds what it looks for)."""
    from distributed_model_parallel_tpu.models import transformer as tfm
    from distributed_model_parallel_tpu.serve.model import make_prefill_step

    _compile_kernels(monkeypatch)
    chunk, max_seq, page = 128, 2048, 16
    cfg = tfm.TransformerConfig(
        vocab_size=1024, d_model=256, n_heads=4, n_kv_heads=2, d_head=128,
        n_layers=2, d_ff=512, max_seq_len=max_seq, dtype=jnp.bfloat16,
        pos_embedding="rope")

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = jax.tree.map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(lambda: tfm.init_params(jax.random.key(0), cfg)))
    pool = sds((cfg.n_layers, 512, page, 2, 128), jnp.bfloat16)
    text = make_prefill_step(
        cfg, page_size=page, chunk=chunk, impl=impl).lower(
        params, (pool, pool, None, None), None, sds((1, chunk), jnp.int32),
        sds((), jnp.int32), sds((), jnp.int32),
        (sds((max_seq // page,), jnp.int32), None), None
    ).compile().as_text()
    assert text.startswith("HloModule jit_prefill_step")
    scores = re.findall(rf"f32\[(?:\d+,)*{chunk},{max_seq}\]", text)
    if impl == "pallas":
        assert re.search(r"%paged_prefill_attention[\w.]* = .*custom-call\(",
                         text)
        assert not scores
    else:
        assert "%paged_prefill_attention" not in text
        assert scores


def _pool_ops(text: str, pool: tuple) -> tuple:
    """``(slabs, pools)``: the instructions of a compiled program, as
    ``(opcode, line)``, that yield one layer's slab of the stacked pool
    ``[L, P, page, Hkv, Dh]`` (``[1,]P,page,Hkv,Dh``, or the kernels'
    view ``P,page*Hkv,Dh``) and those that yield the whole of it
    (``L,P,...`` or ``L*P,...``). What moves nothing is left out: a
    parameter, a bitcast, an element of a tuple."""
    n_layers, p, page, hkv, dh = pool
    assert n_layers > 1     # or a slab is the pool, and the write yields it

    def yielding(*rows):
        shapes = "|".join(f"{r},(?:{page},{hkv}|{page * hkv}),{dh}"
                          for r in rows)
        ops = re.finditer(
            rf"^\s*(?:ROOT )?%\S+ = bf16\[(?:{shapes})\]\S* ([\w-]+)\(.*$",
            text, re.M)
        return [(m.group(1), m.group(0)) for m in ops if m.group(1)
                not in ("parameter", "bitcast", "get-tuple-element")]

    return (yielding(f"1,{p}", p),
            yielding(f"{n_layers},{p}", n_layers * p))


@pytest.mark.parametrize("step", ["prefill", "decode", "verify"])
@pytest.mark.parametrize("layers", ["equal-layers", "mixed-kinds"])
def test_serving_steps_cut_no_slab_out_of_the_pools(
        one_chip, monkeypatch, layers, step):
    """``jit_prefill_step``, ``jit_decode_step`` and ``jit_verify_step``
    compiled for the chip with pools of the serving cells' size (shapes
    only: nothing is allocated, and pools small enough for fast memory
    are prefetched there whole, which no deployment's are). Under four
    equal layers the layer index is traced (``run_layers``' scan); under
    full and sliding layers mixed in one period it is static, and the
    sliding layers keep rings. Either way the stacked pools reach the
    kernels as views of the buffers the step carries: no op cuts one
    layer's slab out, and the only ops that yield a pool are the writes
    in place."""
    from distributed_model_parallel_tpu.models import transformer as tfm
    from distributed_model_parallel_tpu.serve import model as sm
    from distributed_model_parallel_tpu.serve.paged_kv import CacheLayout

    _compile_kernels(monkeypatch)
    page, chunk, width, max_seq, n_pages, slots = 16, 512, 4, 4096, 8192, 64
    kw = dict(vocab_size=1024, d_model=256, n_heads=16, n_kv_heads=8,
              d_head=128, d_ff=512, max_seq_len=max_seq, dtype=jnp.bfloat16,
              pos_embedding="rope")
    if layers == "equal-layers":
        cfg = tfm.TransformerConfig(n_layers=4, **kw)
        assert cfg.layer_plan == (0, 1, 4)
    else:
        full = tfm.LayerKind(None, False, "dense")
        sliding = tfm.LayerKind(128, True, "dense")
        cfg = tfm.TransformerConfig(
            n_layers=5, layer_kinds=(full, sliding, sliding, full, sliding),
            **kw)
        assert cfg.layer_plan == (0, 5, 1)
    layout = CacheLayout.of(cfg, page_size=page, max_seq_len=max_seq,
                            span=chunk)
    rings = layout.ring_pages

    def sds(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = jax.tree.map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(lambda: tfm.init_params(jax.random.key(0), cfg)))
    full = sds((layout.n_full, n_pages, page, 8, 128), jnp.bfloat16)
    ring = (sds((layout.n_ring, slots * rings, page, 8, 128), jnp.bfloat16)
            if rings else None)
    pools = (full, full, ring, ring)
    assert (layout.n_full, layout.n_ring) == ((2, 3) if rings else (4, 0))
    n = max_seq // page
    table = (sds((n,)), sds((rings,)) if rings else None)
    tables = (sds((slots, n)), sds((slots, rings)) if rings else None)
    active = sds((slots,), jnp.bool_)
    kws = dict(page_size=page, impl="pallas", layout=layout)
    lowered = {
        "prefill": lambda: sm.make_prefill_step(cfg, chunk=chunk, **kws).lower(
            params, pools, None, sds((1, chunk)), sds(()), sds(()), table,
            None),
        "decode": lambda: sm.make_decode_step(cfg, **kws).lower(
            params, pools, None, sds((slots,)), sds((slots,)), tables,
            active, None),
        "verify": lambda: sm.make_verify_step(cfg, width=width, **kws).lower(
            params, pools, None, sds((slots, width)), sds((slots,)),
            sds((slots,)), tables, active, None),
    }[step]()
    text = lowered.compile().as_text()
    assert text.startswith(f"HloModule jit_{step}_step")
    kernel = "decode" if step == "decode" else "prefill"
    assert len(re.findall(
        rf"%paged_{kernel}_attention[\w.]* = .*custom-call\(", text)
    ) == (1 if layers == "equal-layers" else 5)
    for pool in filter(None, (full, ring)):
        slabs, whole = _pool_ops(text, pool.shape)
        assert not slabs, slabs[0][1][:300]
        moved = [line for op, line in whole if not (
            op == "scatter" or (op == "fusion" and "/scatter\"" in line))]
        assert not moved, moved[0][:300]
        assert whole                       # the pattern reads this program


@pytest.mark.parametrize("step", ["prefill", "decode", "verify"])
def test_looped_steps_cut_no_slab_and_copy_no_pool_at_any_pass(
        one_chip, monkeypatch, step):
    """The steps of a looped stack (48 layers run four times over the
    same leaves: ``run_passes``' scan around ``run_layers``' scan, the
    pass and the layer both traced) at the shapes of the benchmark's cell
    ``ouro-2.6b.reason-batch``: pools ``[192, 320, 16, 16, 128]`` (a cache
    layer a (pass, layer), 16 stored heads with no grouping), 16 rows, a
    256-token chunk; sandwich norms, the norm that closes a pass, the
    exit gate. The stack's body is compiled ONCE (one paged kernel call
    in the program, not four nor 192), no op cuts a cache layer's slab out
    of the pools, and the only ops that yield a pool are the writes in
    place: the outer loop carries the pools as the inner one does."""
    from distributed_model_parallel_tpu.models import transformer as tfm
    from distributed_model_parallel_tpu.serve import model as sm
    from distributed_model_parallel_tpu.serve.paged_kv import CacheLayout

    _compile_kernels(monkeypatch)
    page, chunk, width, max_seq, n_pages, slots = 16, 256, 4, 1024, 320, 16
    cfg = tfm.TransformerConfig(
        vocab_size=1024, d_model=256, n_heads=16, n_kv_heads=16, d_head=128,
        n_layers=48, d_ff=512, max_seq_len=max_seq, dtype=jnp.bfloat16,
        pos_embedding="rope", rope_theta=1e6, norm="rmsnorm", ffn="swiglu",
        norm_placement="sandwich", n_passes=4, loop_final_norm=True,
        exit_gate=True)
    layout = CacheLayout.of(cfg, page_size=page, max_seq_len=max_seq,
                            span=chunk)
    assert (layout.n_full, layout.passes, layout.ring_pages) == (192, 4, 0)

    def sds(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    as_sds = functools.partial(jax.tree.map, lambda x: sds(x.shape, x.dtype))
    params = as_sds(jax.eval_shape(
        lambda: tfm.init_params(jax.random.key(0), cfg)))
    assert params["blocks"]["wq"].shape[0] == 48
    stats = as_sds(jax.eval_shape(lambda: sm.init_stats(cfg)))
    pool = sds((192, n_pages, page, 16, 128), jnp.bfloat16)
    pools = (pool, pool, None, None, None, None)
    n = max_seq // page
    tables, active = (sds((slots, n)), None), sds((slots,), jnp.bool_)
    kws = dict(page_size=page, impl="pallas", layout=layout)
    lowered = {
        "prefill": lambda: sm.make_prefill_step(cfg, chunk=chunk, **kws).lower(
            params, pools, stats, sds((1, chunk)), sds(()), sds(()),
            (sds((n,)), None), None),
        "decode": lambda: sm.make_decode_step(cfg, **kws).lower(
            params, pools, stats, sds((slots,)), sds((slots,)), tables,
            active, None),
        "verify": lambda: sm.make_verify_step(cfg, width=width, **kws).lower(
            params, pools, stats, sds((slots, width)), sds((slots,)),
            sds((slots,)), tables, active, None),
    }[step]()
    text = lowered.compile().as_text()
    assert text.startswith(f"HloModule jit_{step}_step")
    kernel = "decode" if step == "decode" else "prefill"
    assert len(re.findall(
        rf"%paged_{kernel}_attention[\w.]* = .*custom-call\(", text)) == 1
    slabs, whole = _pool_ops(text, pool.shape)
    assert not slabs, slabs[0][1][:300]
    moved = [line for op, line in whole if not (
        op == "scatter" or (op == "fusion" and "/scatter\"" in line))]
    assert not moved, moved[0][:300]
    assert whole                           # the pattern reads this program
    # the scopes the benchmark's readers find the stack and the gate by
    assert "loop_stack" in text and "exit_gate" in text


def _outside_fusions(text: str) -> str:
    """The instructions of a compiled program that are ops of their own:
    the bodies of the computations no fusion calls (the entry, the loops'
    bodies and conditions). What sits inside a fused computation is read
    and written by its fusion alone."""
    fused = set(re.findall(r"kind=k\w+, calls=(%[\w.-]+)", text))
    return "\n".join(
        body for name, body in re.findall(
            r"^(?:ENTRY )?(%[\w.-]+) \([^\n]*\{\n(.*?)^\}", text,
            re.M | re.S) if name not in fused)


def _in_proj_ops(text: str, leaves: list) -> list:
    """The ops of a compiled program that yield an attention
    in-projection: an array with the axes of one of ``leaves`` (``[L, d,
    H, X]`` as stored) in any order, of one layer (with or without its
    leading 1) or of the whole stack. Left out: what moves nothing (a
    parameter, a bitcast, an element of a tuple) and ``copy-done``, the
    end of a prefetch into fast memory that keeps the layout and runs
    under other ops (a layer whose index is static gets one in either
    layout)."""
    shapes = set()
    for n_layers, *axes in (leaf.shape for leaf in leaves):
        for order in itertools.permutations(axes):
            for lead in ((), (1,), (n_layers,)):
                shapes.add(",".join(map(str, lead + order)))
    shapes = "|".join(sorted(shapes))
    ops = re.finditer(
        rf"^\s*(?:ROOT )?%\S+ = bf16\[(?:{shapes})\]\S* ([\w-]+)\(.*$",
        _outside_fusions(text), re.M)
    return [m.group(0).strip() for m in ops if m.group(1) not in (
        "parameter", "bitcast", "get-tuple-element", "copy-done")]


def _in_proj_family(family: str):
    """(cfg, page, chunk, max_seq, n_pages, slots) at the attention
    widths of three of the benchmark's configurations."""
    from distributed_model_parallel_tpu.models import transformer as tfm

    kw = dict(vocab_size=1024, d_head=128, dtype=jnp.bfloat16,
              pos_embedding="rope")
    if family == "starcoder2-widths":
        # d 3072, 24 heads over 2, the MLP's 12,288: four equal layers
        # under run_layers' scan, pools of the cells' size
        return tfm.TransformerConfig(
            d_model=3072, n_heads=24, n_kv_heads=2, n_layers=4, d_ff=12288,
            max_seq_len=4096, **kw), 16, 512, 4096, 8192, 32
    if family == "looped":
        # test_looped_steps_cut_no_slab_and_copy_no_pool_at_any_pass's
        # stack at Ouro's widths: 16 heads with no grouping, the passes'
        # scan around the layers'
        return tfm.TransformerConfig(
            d_model=2048, n_heads=16, n_kv_heads=16, n_layers=8, d_ff=5632,
            max_seq_len=1024, rope_theta=1e6, norm="rmsnorm", ffn="swiglu",
            norm_placement="sandwich", n_passes=4, loop_final_norm=True,
            exit_gate=True, **kw), 16, 256, 1024, 320, 16
    # K-EXAONE's kinds and attention widths (d 6144, 64 heads over 8):
    # one period of five, every index static, every position its own
    # leaves [1, d, H, X] (25 MB the smallest; a chunk of 256 keeps the
    # activations, 3 MB each, well under that)
    full = tfm.LayerKind(None, False, "dense")
    sliding = tfm.LayerKind(128, True, "dense")
    return tfm.TransformerConfig(
        d_model=6144, n_heads=64, n_kv_heads=8, n_layers=5, d_ff=512,
        max_seq_len=4096, norm="rmsnorm", ffn="swiglu", qk_norm=True,
        layer_kinds=(sliding, sliding, sliding, full, sliding),
        **kw), 16, 256, 4096, 8192, 32


def _in_proj_program(one_chip, family: str, step: str, convert: bool):
    """``(compiled, stored in-projection leaves)`` of one serving step of
    ``family``, from the tree as stored or as an engine holds it."""
    from distributed_model_parallel_tpu.models import transformer as tfm
    from distributed_model_parallel_tpu.serve import model as sm
    from distributed_model_parallel_tpu.serve.paged_kv import CacheLayout

    cfg, page, chunk, max_seq, n_pages, slots = _in_proj_family(family)
    layout = CacheLayout.of(cfg, page_size=page, max_seq_len=max_seq,
                            span=chunk)
    rings = layout.ring_pages

    def sds(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    as_sds = functools.partial(jax.tree.map, lambda x: sds(x.shape, x.dtype))
    stored = jax.eval_shape(lambda: tfm.init_params(jax.random.key(0), cfg))
    leaves = [bp[name] for bp in sm._block_groups(stored)
              for name in sm.IN_PROJECTIONS if name in bp]
    params = as_sds(jax.eval_shape(sm.in_proj_d_last, stored) if convert
                    else stored)
    # at static indices the engine keeps the leaves as stored
    assert bool(sm.in_proj_relaid(params)) == (
        convert and family != "mixed-kinds")
    stats = as_sds(jax.eval_shape(lambda: sm.init_stats(cfg)))
    pool = lambda n, p: (sds((n, p, page, cfg.kv_heads, 128),  # noqa: E731
                             jnp.bfloat16) if n else None)
    full, ring = pool(layout.n_full, n_pages), pool(layout.n_ring,
                                                    slots * rings)
    pools = (full, full, ring, ring, None, None)
    n = max_seq // page
    table = (sds((n,)), sds((rings,)) if rings else None)
    tables = (sds((slots, n)), sds((slots, rings)) if rings else None)
    active = sds((slots,), jnp.bool_)
    kws = dict(page_size=page, impl="pallas", layout=layout)
    lowered = {
        "prefill": lambda: sm.make_prefill_step(cfg, chunk=chunk, **kws).lower(
            params, pools, stats, sds((1, chunk)), sds(()), sds(()), table,
            None),
        "decode": lambda: sm.make_decode_step(cfg, **kws).lower(
            params, pools, stats, sds((slots,)), sds((slots,)), tables,
            active, None),
        "verify": lambda: sm.make_verify_step(cfg, width=4, **kws).lower(
            params, pools, stats, sds((slots, 4)), sds((slots,)),
            sds((slots,)), tables, active, None),
    }[step]()
    return lowered.compile(), leaves


@pytest.mark.parametrize("step", ["prefill", "decode", "verify"])
@pytest.mark.parametrize("family", ["starcoder2-widths", "looped",
                                    "mixed-kinds"])
def test_steps_of_a_converted_tree_move_no_in_projection(
        one_chip, monkeypatch, family, step):
    """The three serving steps compiled from the tree as an ``Engine``
    holds it (``serve/model.in_proj_d_last``: under a scan ``wq_t [L, H,
    Dh, d]``, ``wkv_t [L, Hkv, 2 Dh, d]``, the contracted axis last), at
    the attention widths of StarCoder2 under the layers' scan, of Ouro
    under the passes' scan around it, and of K-EXAONE's mixed kinds at
    static indices (left as stored: nothing cuts such a layer out, and
    its transposition sits inside the product's fusion): no ``copy`` and
    no slice fusion yields one layer's ``wq`` or ``wkv``, or the stack's:
    the products read the leaves where they lie, as the MLP's do, and the
    step holds under 10 MB of temporaries. The control is the next
    test."""
    _compile_kernels(monkeypatch)
    compiled, leaves = _in_proj_program(one_chip, family, step, convert=True)
    text = compiled.as_text()
    assert text.startswith(f"HloModule jit_{step}_step")
    moved = _in_proj_ops(text, leaves)
    assert not moved, moved[0][:300]
    assert compiled.memory_analysis().temp_size_in_bytes < 10e6


@pytest.mark.parametrize("step", ["prefill", "decode"])
@pytest.mark.parametrize("family", ["starcoder2-widths", "looped"])
def test_steps_of_the_stored_tree_cut_out_and_transpose_them(
        one_chip, monkeypatch, family, step):
    """The control of the test above, and what the benchmark's traces
    showed before the engine converted its tree: from the leaves as
    stored (``[L, d, H, Dh]``: the contracted axis third from minor) the
    same steps under the same scans do yield them. Grouped heads: one
    layer's ``wq`` and ``wkv`` sliced out of the stack, every layer
    (``constant_dynamic-slice_fusion``, and a ``copy`` to the product's
    layout in the prefill step). 16 heads with no grouping: the whole
    stack's, copied at the step's entry (135 MB of temporaries at 8
    layers, the ``wkv``'s; 1.21 GB at Ouro's 48, both)."""
    _compile_kernels(monkeypatch)
    compiled, leaves = _in_proj_program(one_chip, family, step,
                                        convert=False)
    moved = _in_proj_ops(compiled.as_text(), leaves)
    assert len(moved) >= 2, moved                  # wq's and wkv's
    if family == "looped":
        assert all(" copy(" in op for op in moved)
        # the stack's wkv in HBM (its wq, half of that, fits fast memory)
        assert compiled.memory_analysis().temp_size_in_bytes >= max(
            2 * leaf.size for leaf in leaves)
    else:
        assert any("dynamic-slice" in op for op in moved)


def _plain_passes(params, x, rest, fn, cfg):
    """``run_passes`` as the parent commit walked the layers: once, with
    no norm, no gate and no outer loop."""
    from distributed_model_parallel_tpu.models import transformer as tfm

    (x, rest), outs = tfm.run_layers(params, (x, rest),
                                     functools.partial(fn, 0), cfg)
    return x, rest, outs, None


@pytest.mark.parametrize("step", ["prefill", "decode"])
@pytest.mark.parametrize("family", ["windowed-gqa", "routed-mixed",
                                    "gated-delta-hybrid"])
def test_steps_of_a_stack_run_once_compile_to_what_the_parent_compiles(
        one_chip, monkeypatch, family, step):
    """The blocks of the benchmark's older configurations (the default
    block under a window with grouped queries; the gated, routed block
    with sliding and full layers; gated-delta layers beside full ones), at
    ``n_passes = 1``: the program the chip's compiler makes of each step
    is, instruction for instruction, the one it makes of the parent
    commit's walk (``_plain_passes``: ``run_layers`` once and nothing
    around it). Source positions aside: they name the walking function."""
    from distributed_model_parallel_tpu.models import transformer as tfm
    from distributed_model_parallel_tpu.ops import moe
    from distributed_model_parallel_tpu.serve import model as sm
    from distributed_model_parallel_tpu.serve.paged_kv import (
        CacheLayout,
        stored_kv_heads,
    )

    _compile_kernels(monkeypatch)
    monkeypatch.setattr(moe, "expert_products", functools.partial(
        moe.expert_products, interpret=False))
    page, chunk, max_seq, n_pages, slots = 16, 256, 2048, 1024, 8
    kw = dict(vocab_size=1024, d_model=256, d_head=128, d_ff=512,
              max_seq_len=max_seq, dtype=jnp.bfloat16, pos_embedding="rope")
    if family == "windowed-gqa":
        cfg = tfm.TransformerConfig(n_layers=4, n_heads=8, n_kv_heads=2,
                                    attn_window=max_seq, **kw)
    elif family == "routed-mixed":
        s = tfm.LayerKind(window=128, rope=True, ffn="moe")
        f = tfm.LayerKind(window=None, rope=False, ffn="moe")
        cfg = tfm.TransformerConfig(
            n_layers=5, n_heads=8, n_kv_heads=2, norm="rmsnorm",
            ffn="swiglu", qk_norm=True,
            layer_kinds=(tfm.LayerKind(128, True, "dense"), s, s, f, s),
            moe_experts=16, moe_top_k=4, moe_dropless=True,
            moe_scoring="sigmoid", moe_d_ff=256, moe_shared_experts=1,
            moe_experts_held=(4, 4), **kw)
    else:
        lin, full = tfm.LayerKind(mixer="gated_delta"), tfm.LayerKind()
        cfg = tfm.TransformerConfig(
            n_layers=8, n_heads=30, n_kv_heads=30, norm="rmsnorm",
            ffn="swiglu", qk_norm_whole=True, norm_placement="post",
            layer_kinds=(lin, lin, lin, full) * 2, lin_key_heads=30,
            lin_value_heads=30, lin_key_dim=96, lin_value_dim=192,
            lin_neg_eigval=True, **kw)
    assert not cfg.looped
    layout = CacheLayout.of(cfg, page_size=page, max_seq_len=max_seq,
                            span=chunk)
    assert layout.passes == 1

    def sds(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    as_sds = functools.partial(jax.tree.map, lambda x: sds(x.shape, x.dtype))
    params = as_sds(jax.eval_shape(
        lambda: tfm.init_params(jax.random.key(0), cfg)))
    stats = as_sds(jax.eval_shape(lambda: sm.init_stats(cfg)))
    hkv, rings = stored_kv_heads(cfg.kv_heads), layout.ring_pages
    pool = lambda n, p: (sds((n, p, page, hkv, 128),       # noqa: E731
                             jnp.bfloat16) if n else None)
    full, ring = pool(layout.n_full, n_pages), pool(layout.n_ring,
                                                    slots * rings)
    state = tail = None
    if layout.n_state:
        state = sds((layout.n_state, slots, 96, 30 * 192), jnp.float32)
        tail = sds((layout.n_state, slots, 3, cfg.lin_channels),
                   jnp.bfloat16)
    pools = (full, full, ring, ring, state, tail)
    n = max_seq // page
    kws = dict(page_size=page, impl="pallas", layout=layout)

    def compiled():
        for make in (sm.make_prefill_step, sm.make_decode_step):
            make.cache_clear()
        if step == "decode":
            lowered = sm.make_decode_step(cfg, **kws).lower(
                params, pools, stats, sds((slots,)), sds((slots,)),
                (sds((slots, n)), sds((slots, rings)) if rings else None),
                sds((slots,), jnp.bool_), None)
        else:
            table = (sds((n,)), sds((rings,)) if rings else None)
            if layout.n_state:
                table += (sds(()),)
            lowered = sm.make_prefill_step(cfg, chunk=chunk, **kws).lower(
                params, pools, stats, sds((1, chunk)), sds(()), sds(()),
                table, None)
        text = lowered.compile().as_text()
        # the instructions, without where in the Python they came from
        # (the table of stack frames and each instruction's index into it)
        body = text[text.index("\n\n", text.index("StackFrames")):] if (
            "StackFrames" in text) else text
        # ... nor a kernel's serialized body, which carries its own
        body = re.sub(r'"body": ?"[^"]*"', '"body":""', body)
        return text.split("\n", 1)[0] + re.sub(r" stack_frame_id=\d+", "",
                                                 body)

    ours = compiled()
    monkeypatch.setattr(sm, "run_passes", _plain_passes)
    theirs = compiled()
    for make in (sm.make_prefill_step, sm.make_decode_step):
        make.cache_clear()
    assert f"HloModule jit_{step}_step" in ours
    assert ours == theirs


def test_gated_delta_decode_kernel_compiles(one_chip):
    """The decode round's state update at the published widths (30 heads
    of 96 x 192, 32 slots, 12 layers): Mosaic takes it, and the pool goes
    in and comes out as one buffer: no op of the program yields a pool or
    a layer's slab of it but the kernel."""
    n_layers, n, h, dk, dv = 12, 32, 30, 96, 192

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    text = jax.jit(
        functools.partial(gd.gated_delta_kernel, interpret=False),
        donate_argnums=(0,)).lower(
        sds((n_layers, n, dk, h * dv), jnp.float32), sds((), jnp.int32),
        sds((n, h, dk), jnp.bfloat16), sds((n, h, dk), jnp.bfloat16),
        sds((n, h, dv), jnp.bfloat16), sds((n, h), jnp.float32),
        sds((n, h), jnp.float32)).compile().as_text()
    assert re.search(r"%gated_delta_decode[\w.]* = .*custom-call\(", text)
    moved = _state_ops(text, (n_layers, n, dk, h * dv))
    assert [op for op, _ in moved] == ["custom-call"], moved


def _state_ops(text: str, pool: tuple) -> list:
    """The instructions, as ``(opcode, line)``, that yield the state pool
    ``[L, N, dk, Hv * dv]`` or one layer's slab of it (``[1,]N,dk,...``);
    what moves nothing is left out."""
    n_layers, n, dk, hd = pool
    shapes = f"{n_layers},{n},{dk},{hd}|1,{n},{dk},{hd}|{n},{dk},{hd}"
    # "%name = <type, maybe a tuple> opcode(": a layout's "T(8,128)" has
    # no space before it, an opcode has
    ops = re.finditer(r"^\s*(?:ROOT )?%\S+ = (.*?)\s([a-z][\w-]*)\(.*$",
                      text, re.M)
    return [(m.group(2), m.group(0)) for m in ops
            if re.search(rf"f32\[(?:{shapes})\]", m.group(1))
            and m.group(2) not in ("parameter", "bitcast",
                                   "get-tuple-element", "tuple", "while")]


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_hybrid_steps_copy_no_state_slab_and_no_pool(
        one_chip, monkeypatch, step):
    """The steps of a model with state layers (two periods of linear,
    linear, linear, full, so the layer index is traced; the published
    linear widths and 30 heads stored as 32; 32 slots), compiled for the
    chip. Decode: the state pool is yielded by the three kernel calls of
    the period's body and by nothing else: no slab ``f32[1,32,96,5760]``
    is cut out. Prefill: one write in place of the row's own slot (27 MB
    at the cell's 12 layers), the pool never copied. Either way the K/V
    pools of 32 stored heads reach the paged kernels as views (the 30
    heads the model has would be laid out as 32 and copied whole)."""
    from distributed_model_parallel_tpu.models import transformer as tfm
    from distributed_model_parallel_tpu.serve import model as sm
    from distributed_model_parallel_tpu.serve.paged_kv import (
        CacheLayout,
        stored_kv_heads,
    )

    _compile_kernels(monkeypatch)
    page, chunk, max_seq, n_pages, slots = 16, 512, 8192, 4096, 32
    lin, full = tfm.LayerKind(mixer="gated_delta"), tfm.LayerKind()
    cfg = tfm.TransformerConfig(
        vocab_size=1024, d_model=256, n_heads=30, n_kv_heads=30, d_head=128,
        n_layers=8, d_ff=512, max_seq_len=max_seq, dtype=jnp.bfloat16,
        pos_embedding="rope", norm="rmsnorm", ffn="swiglu",
        qk_norm_whole=True, norm_placement="post",
        layer_kinds=(lin, lin, lin, full) * 2, lin_key_heads=30,
        lin_value_heads=30, lin_key_dim=96, lin_value_dim=192,
        lin_neg_eigval=True)
    assert cfg.layer_plan == (0, 4, 2)
    layout = CacheLayout.of(cfg, page_size=page, max_seq_len=max_seq,
                            span=chunk)
    assert (layout.n_full, layout.n_state) == (2, 6)

    def sds(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = jax.tree.map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(lambda: tfm.init_params(jax.random.key(0), cfg)))
    assert stored_kv_heads(30) == 32
    kv = sds((2, n_pages, page, 32, 128), jnp.bfloat16)
    state = (6, slots, 96, 30 * 192)
    pools = (kv, kv, None, None, sds(state, jnp.float32),
             sds((6, slots, 3, 11520), jnp.bfloat16))
    n = max_seq // page
    kws = dict(page_size=page, impl="pallas", layout=layout)
    if step == "decode":
        lowered = sm.make_decode_step(cfg, **kws).lower(
            params, pools, None, sds((slots,)), sds((slots,)),
            (sds((slots, n)), None), sds((slots,), jnp.bool_), None)
    else:
        lowered = sm.make_prefill_step(cfg, chunk=chunk, **kws).lower(
            params, pools, None, sds((1, chunk)), sds(()), sds(()),
            (sds((n,)), None, sds(())), None)
    text = lowered.compile().as_text()
    moved = _state_ops(text, state)
    if step == "decode":
        assert len(re.findall(
            r"%gated_delta_decode[\w.]* = .*custom-call\(", text)) == 3
        assert [op for op, _ in moved] == ["custom-call"] * 3, moved
    else:
        assert "%gated_delta_decode" not in text
        # one write in place (and the fusion that holds it)
        assert [op for op, _ in moved].count("dynamic-update-slice") == 1
        assert all(op == "dynamic-update-slice" or (
            op == "fusion" and "dynamic_update_slice" in line)
            for op, line in moved), moved
    slabs, whole = _pool_ops(text, kv.shape)
    assert not slabs, slabs[0][1][:300]
    copies = [line for op, line in whole if not (
        op == "scatter" or (op == "fusion" and "/scatter\"" in line))]
    assert not copies, copies[0][:300]
    assert whole


def test_a_slab_cut_out_under_a_scan_is_found(one_chip):
    """The control of the test above: a layer's slab taken with
    ``dynamic_index_in_dim`` under a scan (how ``paged_block`` handed the
    kernels their pools before PR 31) is an op of its own in the compiled
    program, 67 MB written a pool and layer, and ``_pool_ops`` finds
    it."""
    n_layers, n_pages, page, hkv, dh, slots, n = 4, 8192, 16, 8, 128, 64, 256

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def read_all(q, kpool, vpool, tables, positions):
        def layer(q, i):
            k, v = (jax.lax.dynamic_index_in_dim(pool, i, 0, keepdims=False)
                    for pool in (kpool, vpool))
            return pa.paged_attention_kernel(q, k, v, tables, positions,
                                             interpret=False), None

        return jax.lax.scan(layer, q, jnp.arange(n_layers))[0]

    shape = (n_layers, n_pages, page, hkv, dh)
    pool = sds(shape, jnp.bfloat16)
    text = _compiled_text(
        read_all, sds((slots, 1, 16, dh), jnp.bfloat16), pool, pool,
        sds((slots, n), jnp.int32), sds((slots,), jnp.int32))
    assert "%paged_decode_attention" in text
    slabs, _ = _pool_ops(text, shape)
    assert len(slabs) >= 2                         # K's and V's


@pytest.mark.parametrize("rows", [4096, 512],
                         ids=["prefill-chunk-512x8", "decode-round-64x8"])
def test_grouped_expert_products_compile(one_chip, monkeypatch, rows):
    """The dropless routed layer (ops/moe.moe_ffn_dropless) at
    K-EXAONE's expert shapes: 16 held experts of 6144 x 2048 out of a
    router 128 wide, 8 chosen a token, every assignment of a 512-token
    chunk (or of a 64-row decode round) in one buffer. Mosaic has to
    take the grouped products' kernel (``moe_grouped_matmul``: gate and
    up in one call, down in a second, row tiles of 128 visited where a
    group holds a row) at these widths, and the layer holds no
    ``jax.lax.ragged_dot`` beside it: XLA:TPU's grouped kernel works in
    row tiles of 512 whatever a group holds."""
    from distributed_model_parallel_tpu.ops import moe

    # on a TPU backend the layer takes the kernel by itself; the described
    # chip is not the backend here
    monkeypatch.setattr(moe, "expert_products", functools.partial(
        moe.expert_products, interpret=False))
    cfg = moe.MoEConfig(num_experts=128, d_model=6144, d_ff=2048, top_k=8,
                        scoring="sigmoid", routed_scale=2.5, held=(0, 16))

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = {"router": sds((6144, 128)), "router_bias": sds((128,)),
              "we_g": sds((16, 6144, 2048)), "we_u": sds((16, 6144, 2048)),
              "we_d": sds((16, 2048, 6144))}
    text = _compiled_text(
        lambda p, x, v: moe.moe_ffn_dropless(p, x, cfg, valid=v),
        params, sds((rows // 8, 6144)), sds((rows // 8,), jnp.bool_))
    # the kernel's own name in a trace (no reader of the benchmark finds
    # it by name: ``moe_dev_share.mixed`` takes the ops under the scope
    # ``moe_experts``, whatever implements them)
    assert len(re.findall(
        r"%moe_grouped_matmul[\w.-]* = [^\n]*custom-call\(", text)) == 2
    assert "tpu_custom_call" in text
    assert "%ragged-dot" not in text
    # no expert's weights are copied on their way into the kernel
    assert not re.findall(r"= bf16\[16,(?:6144,2048|2048,6144)\]\S* "
                          r"(?:copy|fusion)\(", text)


def _flash_args(one_chip, t):
    # chip_smoke.py's LM shape: batch 2, 8 heads x 128, bf16.
    x = jax.ShapeDtypeStruct((2, t, 8, 128), jnp.bfloat16, sharding=one_chip)
    return x, x, x


def _v5e_flash(q, k, v):
    """flash_attention as dispatched on a v5e: the table's tiles, compiled."""
    e = fa._DISPATCH_TABLE["TPU v5 lite"]
    return fa.flash_attention(
        q, k, v, causal=True, interpret=False,
        block_q=e["block_q"], block_k=e["block_k"],
        dq_blocks=(e["dq_block_q"], e["dq_block_k"]),
        dkv_blocks=(e["dkv_block_q"], e["dkv_block_k"]))


@pytest.mark.parametrize("t", [8192, 2048])
def test_flash_forward_compiles(one_chip, t):
    text = _compiled_text(_v5e_flash, *_flash_args(one_chip, t))
    assert "tpu_custom_call" in text
    # the kernel's own name is the instruction's: what a trace reader
    # finds it by (docs/TRACING.md "Profiler timeline")
    assert "%flash_fwd" in text


@pytest.mark.parametrize("t", [8192, 2048])
def test_flash_forward_backward_compiles(one_chip, t):
    def loss_grads(q, k, v):
        return jax.grad(
            lambda q, k, v: _v5e_flash(q, k, v).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    text = _compiled_text(loss_grads, *_flash_args(one_chip, t))
    # forward + dq + dk/dv kernels
    assert text.count("tpu_custom_call") >= 3
    # under a bare jax.grad the name comes wrapped in the transform's
    # (%jvp_flash_fwd_, %transpose_jvp_flash_bwd_dq__); the train step
    # below shows the names a device trace of the trainer carries
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert re.search(rf"%\w*{name}_*\.\d+ = ", text), name


def test_lm_train_step_carries_its_names(one_chip, monkeypatch):
    """A small ``lm_train_step`` (scan over layers, remat, the banded
    flash kernels, chunked head) compiled for the chip: the module and
    the three kernels read by name, as a device trace will show them."""
    import optax

    from distributed_model_parallel_tpu.config import MeshConfig
    from distributed_model_parallel_tpu.mesh import make_mesh
    from distributed_model_parallel_tpu.models import transformer as tfm
    from distributed_model_parallel_tpu.parallel.spmd_pipeline import (
        make_spmd_train_step,
    )

    # the kernels ask jax.devices() whether to interpret; here they
    # compile for the described chip
    plan = fa._plan
    monkeypatch.setattr(
        fa, "_plan", lambda t, d, causal, bq, bk, interpret:
        plan(t, d, causal, bq, bk, False))
    (device,) = one_chip.device_set
    spec = make_mesh(MeshConfig(data=1), devices=[device])
    cfg = tfm.TransformerConfig(
        vocab_size=1024, d_model=256, n_heads=2, n_layers=2, d_ff=512,
        max_seq_len=2048, dtype=jnp.bfloat16, pos_embedding="rope",
        n_kv_heads=1, attn_window=1024, attn_impl="flash", remat=True,
        remat_policy="dots", loss_chunk=512)
    tx = optax.adamw(1e-3)
    params = jax.eval_shape(
        lambda: tfm.init_params(jax.random.key(0), cfg))
    tok = jax.ShapeDtypeStruct((1, 2048), jnp.int32)
    text = make_spmd_train_step(cfg, spec, tx).lower(
        params, jax.eval_shape(tx.init, params), tok, tok
    ).compile().as_text()
    assert text.startswith("HloModule jit_lm_train_step")
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert re.search(rf"%{name}\.\d+ = .*custom-call\(", text), name


@pytest.mark.parametrize("momentum", [0.9, 0.0], ids=["momentum", "plain"])
def test_fused_sgd_kernel_compiles(one_chip, momentum):
    """One flat bucket the size of MobileNetV2's parameters (3.5 M f32)."""
    flat = jax.ShapeDtypeStruct((3_500_000,), jnp.float32, sharding=one_chip)
    lr = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    run = functools.partial(pallas_optim._run_kernel, momentum=momentum,
                            weight_decay=5e-4, nesterov=False,
                            interpret=False)
    if momentum:
        text = _compiled_text(run, lr, flat, flat, flat)
    else:
        text = _compiled_text(lambda lr, p, g: run(lr, p, None, g),
                              lr, flat, flat)
    assert "tpu_custom_call" in text
