"""A model type's yardstick is one module found by name
(``model_types/<model_type>.py``), and the ``serve_model`` driver runs a
cell of it with ``drivers/serve_engine``'s loop and comparison: a toy
configuration of ``exaone_moe`` written as files into a temporary
directory runs with no code edit, the benchmark's reference agrees with
the program (float32), both int8 controls fail, and the operation counts
are the hand counts."""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from drivers import serve_model
from model_types import exaone_moe as em

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "k-exaone-236b-a23b-ep8.mixed-batch"


def toy_config(dtype="float32"):
    n = 5
    return {
        "model_type": "exaone_moe", "torch_dtype": dtype,
        "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 32, "intermediate_size": 128,
        "moe_intermediate_size": 32, "num_hidden_layers": n,
        "num_experts": 4, "num_experts_per_tok": 4, "num_shared_experts": 1,
        "vocab_size": 128, "max_position_embeddings": 256,
        "rms_norm_eps": 1e-5, "rope_parameters": {"rope_theta": 1e4},
        "routed_scaling_factor": 2.5, "norm_topk_prob": True,
        "scoring_func": "sigmoid", "hidden_act": "silu", "n_group": 1,
        "topk_group": 1,
        "sliding_windows": [8, 8, 8, 0, 8], "mlp_layer_types":
        ["dense", "sparse", "sparse", "sparse", "sparse"],
        "deployment": {"router_width": 16,
                       "experts_held": {"first": 4, "count": 4}},
        "reduced": [],
    }


def write_cell(root, dtype="float32", limits=None):
    """A benchmark of one toy cell, as files under ``root``."""
    bench = os.path.join(root, "bench")
    for d in ("configs", "traffic", "checks"):
        os.makedirs(os.path.join(bench, d))
    traffic = harness.load_json(os.path.join(
        HERE, "data", "tiny", "bench", "traffic", "batch.json"))
    traffic["driver"] = "serve_model"
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
            "served_logit_gap": 1e-3, "served_logit_gap_mean": 1e-4,
            "wrong_length": 0, "out_of_vocab": 0, "too_few_compared": 0}},
    }
    for path, obj in files.items():
        with open(os.path.join(root, path), "w") as f:
            json.dump(obj, f)
    return harness.load_cell(root, "toy.batch", bench_dir=bench)


def test_the_cell_names_a_model_type_the_benchmark_has():
    cell = harness.load_cell(ROOT, CELL)
    assert cell.traffic["driver"] == "serve_model"
    assert serve_model.model_of(cell) is em
    dims = em.Dims.from_config(cell.config)
    # the published widths, and the chip's share of the counts
    assert (dims.d_model, dims.n_heads, dims.n_kv_heads, dims.head_dim,
            dims.d_ff, dims.d_expert, dims.top_k) == (
        6144, 64, 8, 128, 18432, 2048, 8)
    assert (dims.n_layers, dims.n_experts, dims.held, dims.vocab) == (
        5, 128, (0, 16), 19200)
    assert dims.windows == (128, 128, 128, None, 128)
    assert dims.sparse == (False, True, True, True, True)
    assert dims.n_params() == cell.config["memory_reckoning_bytes"][
        "parameters"]["all"] == 3_712_028_416
    mcfg, _ = em.transformer_config(cell.config, dims)
    assert mcfg.layer_plan == dims.plan == (0, 5, 1)
    assert mcfg.head_dim == 128 and mcfg.moe.held_range == (0, 16)


def test_every_published_number_stands_unless_reduced():
    """The guide's rule, as the driver will apply it: every number of the
    catalog row's config under the same key, but for ``reduced``."""
    cfg = harness.load_json(os.path.join(
        BENCH, "configs", "k-exaone-236b-a23b-ep8.json"))
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(json.loads(l) for l in f
                   if json.loads(l)["name"] == "K-EXAONE-236B-A23B")
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != value and cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    assert cfg["source"] == row["source_url"]


def test_operation_counts_by_hand():
    dims = em.Dims.from_config(harness.load_cell(ROOT, CELL).config)
    attn = 6144 * 8192 + 2 * 6144 * 1024 + 8192 * 6144       # q, k+v, o
    per_token = (5 * attn + 3 * 6144 * 18432
                 + 4 * (6144 * 128 + 3 * 6144 * 2048))
    assert em.token_matmul_params(dims) == per_token
    assert em.assignment_flops(dims) == 6 * 6144 * 2048
    # one token at context 1,000: 128 keys on four layers, 1,000 on one
    keys = 4 * 128 + 1000
    assert em.serve_token_flops(dims, 1000, True) == (
        2 * per_token + 4 * 64 * 128 * keys + 2 * 6144 * 19200)
    # a chunk of 512 from position 1,024: every query sees 128 keys on a
    # sliding layer; on the full layer query p sees p + 1
    pairs = 4 * 512 * 128 + sum(range(1025, 1537))
    assert em.prefill_flops(dims, 1024, 512, False) == (
        2 * per_token * 512 + 4 * 64 * 128 * pairs)
    peaks = harness.load_peaks("TPU v5 lite")
    # two decode rounds' K/V bytes: bandwidth-bound
    least = em.paged_decode_least_s(
        dims, {"decode_contexts": [[1000, 50], [3000]]}, peaks)
    k = (4 * 128 + 1000) + (4 * 50 + 50) + (4 * 128 + 3000)
    assert least == pytest.approx(2 * k * 8 * 128 * 2 / 819e9)
    moe = {"held_assignments": 5000, "experts_touched": 100}
    assert em.moe_experts_least_s(dims, {"moe": moe}, peaks) == (
        pytest.approx(100 * 3 * 6144 * 2048 * 2 / 819e9))
    assert em.moe_experts_least_s(dims, {}, peaks) is None


def test_a_toy_cell_of_the_model_type_runs_and_is_correct(tmp_path):
    cell = write_cell(str(tmp_path))
    with jax.default_matmul_precision("highest"):
        res = harness.run_cell(str(tmp_path), cell, 2 ** 31 + 9, 1.0, False,
                               jax.devices()[:1], time.perf_counter())
    assert res["correct"] is True, res["compared"]
    assert res["metrics"]["serve_tok_s"]["value"] > 0
    assert res["attempted"] > 0 and res["failed"] == 0


def test_counters_of_the_routed_layers_reach_the_run(tmp_path):
    cell = write_cell(str(tmp_path))
    out = serve_model.run(cell, seed=5, seconds=1.0, trace=False,
                          devices=jax.devices()[:1],
                          t_proc=time.perf_counter(), root=str(tmp_path),
                          control=True)
    moe = out.counters["moe"]
    assert moe["tokens_routed"] > 0
    assert 0 < moe["held_assignments"] < 4 * moe["tokens_routed"]
    assert len(moe["tokens_per_held_expert"]) == 4        # routed layers
    assert out.counters["routed_flops"] == (
        6 * 64 * 32 * moe["held_assignments"])
    assert all(x >= 1.0 for x in out.counters["moe_load_imbalance"])
    # float32 program against the float32 reference: rounding only; both
    # int8 controls lie far above it
    got = {n: v for n, v, _ in out.compared}
    assert got["served_logit_gap"] < 1e-3
    for c in serve_model.CONTROLS:
        assert out.counters["control"][c]["mean"] > 30 * max(
            got["served_logit_gap_mean"], 1e-5)


def test_a_broken_router_bias_is_not_correct(tmp_path):
    """The fault hook reaches the engine of this driver too: a program
    whose router prefers other experts than the reference's is not
    correct."""
    cell = write_cell(str(tmp_path))

    def fault(eng):
        eng.params = jax.tree_util.tree_map_with_path(
            lambda p, a: (a + (jnp.arange(a.shape[-1]) % 2).astype(a.dtype)
                          if "router_bias" in str(p) else a), eng.params)

    with jax.default_matmul_precision("highest"):
        res = harness.run_cell(str(tmp_path), cell, 11, 1.0, False,
                               jax.devices()[:1], time.perf_counter(),
                               fault=fault)
    assert res["correct"] is False


def test_reference_is_the_programs_block_at_toy_size():
    """The benchmark's reference against the program's own paged path is
    what a run compares; here the reference alone, against a plain dense
    evaluation of one sparse layer's routed part (every held expert on
    every token, weighted by hand)."""
    dims = em.Dims.from_config(toy_config())
    params = em.make_params(3, dims, jnp.float32)
    bp = em.layers_of(params, dims)[2]
    h = jax.random.normal(jax.random.key(1), (40, 64), jnp.float32)
    valid = jnp.arange(40) < 33
    got = np.asarray(em.routed(bp, h, valid, dims, None))
    s = jax.nn.sigmoid(jnp.dot(h, bp["router"], precision="highest"))
    _, chosen = jax.lax.top_k(s + bp["router_bias"], 4)
    w = jnp.take_along_axis(s, chosen, -1)
    w = 2.5 * w / w.sum(-1, keepdims=True)
    want = np.zeros((40, 64), np.float32)
    for e in range(4):
        w_e = np.asarray(jnp.where(chosen == 4 + e, w, 0).sum(-1))
        y = em.gated(h, bp["we_g"][e], bp["we_u"][e], bp["we_d"][e], None)
        want += w_e[:, None] * np.asarray(y)
    want[33:] = 0
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_scope_share_lays_the_programs_map_over_the_trace():
    """An op event belongs to the module it starts in; the program's map
    says which of that module's instructions run under which scope;
    containers are left out."""
    from readers import scope_share

    lines = {
        "modules": [("jit_prefill_step(1)", 0, 1000),
                    ("jit_decode_step(2)", 2000, 500)],
        "ops": [("%ragged-dot.1 custom-call out=() in=()", 10, 700),
                ("%fusion.3 fusion out=() in=()", 800, 50),
                ("%while.2 while out=() in=()", 2000, 400),
                ("%fusion.3 fusion out=() in=()", 2010, 30),
                ("%copy.1 copy out=() in=()", 2100, 5),
                ("%fusion.3 fusion out=() in=()", 5000, 9)],   # no module
    }
    op_scopes = {
        "jit_prefill_step": {"%ragged-dot.1": "moe_experts",
                             "%fusion.3": "moe_route"},
        "jit_decode_step": {"%fusion.3": "attn_full",
                            "%while.2": "moe_experts"},
    }
    ns = scope_share.scope_ns
    assert ns(lines, op_scopes, {"moe_experts", "moe_route"}) == 750
    assert ns(lines, op_scopes, {"attn_full"}) == 30
    assert ns(lines, {}, {"attn_full"}) == 0
    # a kernel the compiler renamed is counted by its op name
    assert ns(lines, {}, {"moe_experts"}, ["^%ragged-dot"]) == 700
