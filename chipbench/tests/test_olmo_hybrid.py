"""The model type ``olmo_hybrid`` as files: the cell's configuration
against the catalog row, a toy configuration written into a temporary
directory and run through the ``serve_scoped`` driver with no code edit
(the benchmark's token-by-token reference agrees with the program in
float32, both int8 controls fail), the operation and byte counts against
hand counts, and the reader that lays a model type's cost over the ops
under named scopes."""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from drivers import serve_model, serve_scoped
from model_types import olmo_hybrid as oh

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "olmo-hybrid-7b-pp2.longgen-batch"
SCOPES = ["linattn_proj", "linattn_conv", "linattn_rule", "linattn_gate",
          "attn_full"]


def toy_config(dtype="float32"):
    return {
        "model_type": "olmo_hybrid", "torch_dtype": dtype,
        "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 4, "intermediate_size": 128,
        "num_hidden_layers": 4, "vocab_size": 128,
        "max_position_embeddings": 256, "rms_norm_eps": 1e-6,
        "hidden_act": "silu", "attention_bias": False,
        "rope_parameters": {"rope_theta": None},
        "layer_types": ["linear_attention"] * 3 + ["full_attention"],
        "linear_num_key_heads": 2, "linear_num_value_heads": 4,
        "linear_key_head_dim": 8, "linear_value_head_dim": 64,
        "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
        "reduced": [],
    }


def write_cell(root, dtype="float32", limits=None):
    """A benchmark of one toy cell, as files under ``root``."""
    bench = os.path.join(root, "bench")
    for d in ("configs", "traffic", "checks"):
        os.makedirs(os.path.join(bench, d))
    traffic = harness.load_json(os.path.join(
        HERE, "data", "tiny", "bench", "traffic", "batch.json"))
    traffic.update(driver="serve_scoped", scopes=SCOPES)
    files = {
        "BENCHMARK.json": {
            "configs": [{"name": "toy", "file": "bench/configs/toy.json"}],
            "workloads": [{"name": "toy.batch", "config": "toy",
                           "traffic": "batch", "chips": 1}],
            "end_to_end": [
                {"name": "serve_tok_s", "unit": "tokens/s/chip"},
                {"name": "setup_s", "unit": "s"}],
            "per_layer": []},
        "bench/configs/toy.json": toy_config(dtype),
        "bench/traffic/batch.json": traffic,
        "bench/checks/toy.batch.json": {"limits": limits or {
            "served_logit_gap": 5e-3, "served_logit_gap_mean": 5e-4,
            "wrong_length": 0, "out_of_vocab": 0, "too_few_compared": 0}},
    }
    for path, obj in files.items():
        with open(os.path.join(root, path), "w") as f:
            json.dump(obj, f)
    return harness.load_cell(root, "toy.batch", bench_dir=bench)


def test_the_cell_names_the_model_type_and_the_scoped_driver():
    cell = harness.load_cell(ROOT, CELL)
    assert cell.traffic["driver"] == "serve_scoped" and cell.chips == 1
    assert cell.traffic["scopes"] == SCOPES
    assert serve_model.model_of(cell) is oh
    dims = oh.Dims.from_config(cell.config)
    # the published widths, every one
    assert (dims.d_model, dims.n_heads, dims.n_kv_heads, dims.head_dim,
            dims.d_ff, dims.vocab) == (3840, 30, 30, 128, 11008, 100352)
    assert (dims.lin_key_heads, dims.lin_value_heads, dims.lin_key_dim,
            dims.lin_value_dim, dims.lin_conv, dims.neg_eigval) == (
        30, 30, 96, 192, 4, True)
    assert dims.n_layers == 16 and dims.kinds == (
        "linear", "linear", "linear", "full") * 4
    assert dims.conv_channels == 11520 and dims.state_bytes == 2_211_840
    reck = cell.config["memory_reckoning_bytes"]
    assert dims.n_params() == reck["parameters"]["all"] == 4_100_788_944
    # ISSUE 32's hand count of a linear layer's mixing
    mix = (3840 * 17280 + 5760 * 3840 + 2 * 3840 * 30 + 11520 * 4 + 60 + 192)
    assert reck["parameters"]["linear_layer"] == mix + 3 * 3840 * 11008 + (
        2 * 3840) == 215_570_172
    eng = cell.traffic["engine"]
    assert reck["arrays"] == (2 * dims.n_params()
                              + eng["pool_tokens"] * 4 * 2 * 32 * 128 * 2
                              + 12 * eng["n_slots"] * (2_211_840 + 69_120))
    mcfg, _ = oh.transformer_config(cell.config, dims)
    assert mcfg.layer_plan == dims.plan == (0, 4, 4)
    assert mcfg.head_dim == 128 and mcfg.norm_placement == "post"
    assert [k.mixer for k in mcfg.kinds[:4]] == ["gated_delta"] * 3 + [
        "attention"]


def test_every_published_number_stands_unless_reduced():
    """The guide's rule, as the driver will apply it: every key of the
    catalog row's config under the same key, but for ``reduced``."""
    cfg = harness.load_json(os.path.join(
        BENCH, "configs", "olmo-hybrid-7b-pp2.json"))
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(json.loads(l) for l in f
                   if json.loads(l)["name"] == "Olmo-Hybrid-7B")
    assert cfg["reduced"] == ["num_hidden_layers"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != value and cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    assert cfg["source"] == row["source_url"]
    # a whole number of periods, at least four layers after no lead
    assert cfg["layer_types"][:16] == cfg["layer_types"][:4] * 4


def test_operation_and_byte_counts_by_hand():
    dims = oh.Dims.from_config(harness.load_cell(ROOT, CELL).config)
    lin = 3840 * (11520 + 5760 + 30 + 30) + 5760 * 3840
    full = 3840 * 30 * 128 + 3840 * 30 * 256 + 3840 * 3840
    per_token = 12 * lin + 4 * full + 16 * 3 * 3840 * 11008
    assert oh.token_matmul_params(dims) == per_token
    rule = 6 * 30 * 96 * 192 + 2 * 4 * 11520
    assert oh.rule_token_flops(dims) == rule
    # one token at context 1,000: four full layers see it, twelve do not
    assert oh.serve_token_flops(dims, 1000, True) == (
        2 * per_token + 12 * rule + 4 * 30 * 128 * 1000 * 4
        + 2 * 3840 * 100352)
    # a chunk of 512 from position 1,024: query p sees p + 1 keys
    pairs = 4 * sum(range(1025, 1537))
    assert oh.prefill_flops(dims, 1024, 512, False) == (
        (2 * per_token + 12 * rule) * 512 + 4 * 30 * 128 * pairs)
    assert oh.prefill_flops(dims, 0, 7, True) - oh.prefill_flops(
        dims, 0, 7, False) == 2 * 3840 * 100352
    peaks = harness.load_peaks("TPU v5 lite")
    rounds = {"decode_contexts": [[1000, 50], [3000]]}
    assert oh.paged_decode_least_s(dims, rounds, peaks) == pytest.approx(
        2 * 4 * 4050 * 30 * 128 * 2 / 819e9)
    # three live rows in all: each reads and writes 12 states of 2.2 MB
    assert oh.linattn_decode_least_s(dims, rounds, peaks) == pytest.approx(
        3 * 12 * 2 * 2_211_840 / 819e9)
    chunks = {"prefill_chunks": [(0, 512), (512, 100)]}
    assert oh.linattn_prefill_least_s(dims, chunks, peaks) == pytest.approx(
        12 * (2 * 2 * 2_211_840 + 612 * 11520 * 2) / 819e9)
    for cost in oh.LEAST_SECONDS.values():
        assert cost(dims, {}, peaks) is None


def test_a_toy_cell_of_the_model_type_runs_and_is_correct(tmp_path):
    cell = write_cell(str(tmp_path))
    with jax.default_matmul_precision("highest"):
        res = harness.run_cell(str(tmp_path), cell, 2 ** 31 + 9, 1.0, False,
                               jax.devices()[:1], time.perf_counter())
    assert res["correct"] is True, res["compared"]
    assert res["metrics"]["serve_tok_s"]["value"] > 0
    assert res["attempted"] > 0 and res["failed"] == 0


def test_controls_fail_and_the_windows_chunks_are_counted(tmp_path):
    cell = write_cell(str(tmp_path))
    out = serve_scoped.run(cell, seed=5, seconds=1.0, trace=False,
                           devices=jax.devices()[:1],
                           t_proc=time.perf_counter(), root=str(tmp_path),
                           control=True)
    # no routed layer: nothing of the routed layers' counters
    assert "moe" not in out.counters and "op_scopes" not in out.counters
    chunks = out.counters["prefill_chunks"]
    assert chunks and all(0 < n <= 16 and start % 16 == 0
                          for start, n in chunks)
    assert out.counters["prefill_flops"] >= sum(
        oh.prefill_flops(out.dims, s, n, False) for s, n in chunks)
    # float32 program against the float32 reference: rounding (amplified
    # by sixteen norms); both int8 controls lie far above it
    got = {n: v for n, v, _ in out.compared}
    assert got["served_logit_gap"] < 5e-3
    for c in serve_scoped.CONTROLS:
        assert out.counters["control"][c]["mean"] > 30 * max(
            got["served_logit_gap_mean"], 1e-5)


def test_a_broken_decay_is_not_correct(tmp_path):
    """The fault hook reaches the engine of this driver too: a program
    whose states forget faster than the reference's is not correct."""
    cell = write_cell(str(tmp_path))

    def fault(eng):
        eng.params = jax.tree_util.tree_map_with_path(
            lambda p, a: a + 1.0 if "lin_A_log" in str(p) else a,
            eng.params)

    with jax.default_matmul_precision("highest"):
        res = harness.run_cell(str(tmp_path), cell, 11, 1.0, False,
                               jax.devices()[:1], time.perf_counter(),
                               fault=fault)
    assert res["correct"] is False


def test_reference_recurrence_is_the_definition_at_toy_size():
    """The reference's rule against the state written out by hand, two
    tokens of one head."""
    q = jnp.asarray([[[1.0, 0.0]], [[0.0, 1.0]]])
    k = jnp.asarray([[[1.0, 0.0]], [[0.6, 0.8]]])
    v = jnp.asarray([[[2.0, 4.0, 6.0]], [[1.0, 1.0, 1.0]]])
    alpha = jnp.asarray([[0.5], [0.9]])
    beta = jnp.asarray([[1.0], [2.0]])
    o = np.asarray(oh.delta_rule(q, k, v, alpha, beta))
    s1 = np.outer([1.0, 0.0], [2.0, 4.0, 6.0])       # from zero: k v^T
    sa = 0.9 * s1
    k2 = np.asarray([0.6, 0.8])
    s2 = sa + 2.0 * np.outer(k2, np.asarray([1.0, 1.0, 1.0]) - sa.T @ k2)
    np.testing.assert_allclose(o[0, 0], s1.T @ [1.0, 0.0], atol=1e-6)
    np.testing.assert_allclose(o[1, 0], s2.T @ [0.0, 1.0], atol=1e-6)
    # the drawn gates: alpha mostly in 0.9-0.999, beta over (0, 2)
    dims = oh.Dims.from_config(dict(toy_config(), linear_num_value_heads=64,
                                    linear_num_key_heads=64))
    params = oh.make_params(3, dims, jnp.float32)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 128, size=96))
    alpha, beta = (np.asarray(x) for x in oh.gate_spread(params, toks,
                                                         dims=dims))
    assert alpha.shape == (3, 96, 64)
    assert 0 < alpha.min() and alpha.max() < 1
    assert np.mean((alpha > 0.9) & (alpha < 0.9999)) > 0.5
    assert 0 < beta.min() and beta.max() < 2 and beta.std() > 0.2


def test_scope_roofline_counts_the_scopes_ops_of_the_named_module():
    from readers import scope_roofline

    lines = {
        "modules": [("jit_prefill_step(1)", 0, 1000),
                    ("jit_decode_step(2)", 2000, 500)],
        "ops": [("%fusion.3 fusion out=() in=()", 10, 400),
                ("%gated_delta_decode.1 custom-call out=() in=()", 2010, 200),
                ("%fusion.9 fusion out=() in=()", 2300, 50),
                ("%fusion.3 fusion out=() in=()", 2400, 7)],
    }
    op_scopes = {
        "jit_prefill_step": {"%fusion.3": "linattn_rule"},
        "jit_decode_step": {"%gated_delta_decode.1": "linattn_rule",
                            "%fusion.9": "linattn_rule",
                            "%fusion.3": "linattn_conv"},
    }

    class Trace:
        devices = {"d0": lines}

    class Out:
        counters = {"op_scopes": op_scopes,
                    "decode_contexts": [[10, 20, 30]]}
        dims = oh.Dims.from_config(harness.load_cell(ROOT, CELL).config)

    class Ctx:
        trace, out = Trace, Out
        peaks = harness.load_peaks("TPU v5 lite")
        cell = harness.load_cell(ROOT, CELL)

    spec = {"scopes": ["linattn_rule"], "module": "^jit_decode_step$",
            "cost": "linattn_decode"}
    least = 3 * 12 * 2 * 2_211_840 / 819e9
    assert scope_roofline.read(Ctx, spec) == pytest.approx(
        100 * least / 250e-9)
    # no map (a program or driver without one), no such cost, no such op
    Out.counters = {"decode_contexts": [[10]]}
    assert scope_roofline.read(Ctx, spec) is None
    Out.counters = {"op_scopes": op_scopes, "decode_contexts": [[10]]}
    assert scope_roofline.read(Ctx, dict(spec, cost="nothing")) is None
    assert scope_roofline.read(Ctx, dict(spec, scopes=["attn_full"])) is None
    assert scope_roofline.read(Ctx, dict(spec, module="^jit_verify")) is None
