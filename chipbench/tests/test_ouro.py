"""The model type ``ouro`` as files: the cell's configuration against the
catalog row, a toy configuration written into a temporary directory and
run through the ``serve_scoped`` driver with no code edit (the
benchmark's plain reference agrees with the program in float32, both int8
controls fail), the operation and byte counts against hand counts (with
one pass they are a plain stack's), and the two costs of the looped stack
under 100 % on a hand-made trace."""

import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from drivers import serve_model, serve_scoped
from model_types import ouro

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "ouro-2.6b.reason-batch"
SCOPES = ["loop_stack", "exit_gate", "attn_full"]
LOOP_METRICS = ["engine_step_p50_ms.loop", "engine_host_exposed_ms.loop",
                "decode_round_dev_ms.loop", "prefill_chunk_dev_ms.loop",
                "device_idle.loop", "serve_mfu.loop",
                "paged_decode_roofline.loop", "loop_decode_roofline",
                "loop_prefill_roofline"]


def toy_config(dtype="float32", **kw):
    return dict({
        "model_type": "ouro", "torch_dtype": dtype, "hidden_size": 64,
        "num_attention_heads": 2, "num_key_value_heads": 2, "head_dim": 32,
        "intermediate_size": 128, "num_hidden_layers": 3, "vocab_size": 128,
        "max_position_embeddings": 256, "rms_norm_eps": 1e-6,
        "hidden_act": "silu", "rope_theta": 1000000, "rope_scaling": None,
        "layer_types": ["full_attention"] * 3, "sliding_window": None,
        "use_sliding_window": False, "tie_word_embeddings": False,
        "total_ut_steps": 3, "early_exit_threshold": 1, "reduced": []}, **kw)


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
            "served_logit_gap": 2e-3, "served_logit_gap_mean": 2e-4,
            "wrong_length": 0, "out_of_vocab": 0, "too_few_compared": 0}},
    }
    for path, obj in files.items():
        with open(os.path.join(root, path), "w") as f:
            json.dump(obj, f)
    return harness.load_cell(root, "toy.batch", bench_dir=bench)


def test_the_cell_is_as_the_issue_names_it():
    cell = harness.load_cell(ROOT, CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "ouro-2.6b", "reason-batch", 1)
    assert cell.traffic["driver"] == "serve_scoped"
    assert cell.traffic["scopes"] == SCOPES
    assert serve_model.model_of(cell) is ouro
    eng = cell.traffic["engine"]
    assert eng == {"n_slots": 16, "max_seq_len": 1024, "pool_tokens": 5120,
                   "prefill_chunk": 256}
    dims = ouro.Dims.from_config(cell.config)
    # the published widths, every one; nothing cut
    assert dims == ouro.Dims(
        vocab=49152, d_model=2048, n_heads=16, n_kv_heads=16, head_dim=128,
        d_ff=5632, n_layers=48, passes=4, norm_eps=1e-6, rope_theta=1e6,
        context=65536)
    assert dims.cache_layers == 192
    assert dims.kv_bytes_per_token == 1_572_864 == 3 * 2 ** 19   # 1.5 MiB
    reck = cell.config["memory_reckoning_bytes"]
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert dims.layer_params() == reck["parameters"]["layer"] == layer
    assert dims.n_params() == reck["parameters"]["all"] == (
        48 * layer + 2 * 49152 * 2048 + 2048 + 2048 + 1) == 2_667_974_657
    assert reck["kv_bytes_per_token"] == dims.kv_bytes_per_token
    assert reck["arrays"] == (2 * dims.n_params()
                              + eng["pool_tokens"] * 1_572_864)
    assert 13.38e9 < reck["arrays"] < 13.40e9
    mcfg, _ = ouro.transformer_config(cell.config, dims)
    assert (mcfg.n_layers, mcfg.n_passes, mcfg.norm_placement) == (
        48, 4, "sandwich")
    assert mcfg.loop_final_norm and mcfg.exit_gate and mcfg.head_dim == 128
    assert mcfg.layer_plan == (0, 1, 48) and mcfg.kv_heads == 16
    # its metrics, each listed for this cell alone, each with its file
    listed = {m["name"]: m for m in cell.per_layer()}
    assert sorted(listed) == sorted(LOOP_METRICS)
    for name, m in listed.items():
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tok_s"
        spec = harness.load_json(os.path.join(BENCH, "layer_metrics",
                                              f"{name}.json"))
        assert os.path.exists(os.path.join(BENCH, "readers",
                                           spec["reader"] + ".py"))
        if "cost" in spec:
            assert spec["cost"] in ouro.LEAST_SECONDS
    assert [m["name"] for m in cell.end_to_end()] == ["serve_tok_s",
                                                      "setup_s"]
    assert len(cell.bench["workloads"]) == 6
    assert all(w["chips"] == 1 for w in cell.bench["workloads"])


def test_every_published_number_stands():
    """The guide's rule, as the driver will apply it: every key of the
    catalog row's config under the same key; ``reduced`` is empty."""
    cfg = harness.load_json(os.path.join(BENCH, "configs", "ouro-2.6b.json"))
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(json.loads(l) for l in f
                   if json.loads(l)["name"] == "Ouro-2.6B")
    assert cfg["reduced"] == []
    for key, value in row["config"].items():
        assert cfg[key] == value, key
    assert cfg["source"] == row["source_url"]
    assert (cfg["total_ut_steps"], cfg["early_exit_threshold"]) == (4, 1)
    assert cfg["deployment"]["chips_sharing_a_layer"] == 1
    assert cfg["deployment"]["pipeline_stages"] == 1
    assert cfg["departures"]["served_context"]["run"] == 1024
    for key in ("four_norms_a_layer", "final_norm_inside_the_loop",
                "cache_entry_a_pass_and_layer", "no_biases_no_qk_norm",
                "exit_gate", "head_reads_the_last_pass"):
        assert cfg["assumed"][key]


def test_operation_and_byte_counts_by_hand():
    dims = ouro.Dims.from_config(harness.load_cell(ROOT, CELL).config)
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert ouro.layer_matmul_params(dims) == layer == 51_380_224
    head = 2 * 2048 * 49152
    # one token at context 300: 192 (pass, layer) entries see it
    assert ouro.serve_token_flops(dims, 300, True) == (
        4 * 48 * (2 * layer + 4 * 16 * 128 * 300) + head)
    assert 19.9e9 < ouro.serve_token_flops(dims, 1, True) < 20.0e9
    # a chunk of 256 from position 256: query p sees p + 1 keys
    pairs = sum(range(257, 513))
    assert ouro.prefill_flops(dims, 256, 256, False) == 4 * 48 * (
        2 * layer * 256 + 4 * 16 * 128 * pairs)
    assert ouro.prefill_flops(dims, 0, 7, True) - ouro.prefill_flops(
        dims, 0, 7, False) == head
    # with one pass the counts are a plain stack's
    once = dataclasses.replace(dims, passes=1)
    assert ouro.serve_token_flops(once, 300, True) == (
        48 * (2 * layer + 4 * 16 * 128 * 300) + head)
    assert 4 * (ouro.prefill_flops(once, 64, 100, False)) == (
        ouro.prefill_flops(dims, 64, 100, False))
    assert once.kv_bytes_per_token * 4 == dims.kv_bytes_per_token
    # bytes: the stack once, norms included
    stack = 48 * (layer + 4 * 2048) * 2
    assert ouro.stack_bytes(dims) == stack == 4_933_287_936
    peaks = harness.load_peaks("TPU v5 lite")
    rounds = {"decode_contexts": [[200, 50], [300]]}
    assert ouro.paged_decode_least_s(dims, rounds, peaks) == pytest.approx(
        550 * 1_572_864 / 819e9)
    # two rounds: four reads of the stack each, and the live K/V
    assert ouro.loop_decode_least_s(dims, rounds, peaks) == pytest.approx(
        (2 * 4 * stack + 550 * 1_572_864) / 819e9)
    # a full chunk is compute-bound (5.1 TFLOP: 25.9 ms against 24.6 of
    # bytes), a chunk of 32 tokens bandwidth-bound
    chunks = {"prefill_chunks": [(0, 256), (256, 32)]}
    full = ouro.prefill_flops(dims, 0, 256, False) / 197e12
    assert full == pytest.approx(25.9e-3, rel=0.01)
    assert full > (4 * stack + 256 * 1_572_864) / 819e9
    assert ouro.loop_prefill_least_s(dims, chunks, peaks) == pytest.approx(
        full + (4 * stack + 288 * 1_572_864) / 819e9)
    for cost in ouro.LEAST_SECONDS.values():
        assert cost(dims, {}, peaks) is None


def test_a_toy_cell_of_the_model_type_runs_and_is_correct(tmp_path):
    cell = write_cell(str(tmp_path))
    with jax.default_matmul_precision("highest"):
        res = harness.run_cell(str(tmp_path), cell, 2 ** 31 + 35, 1.0, False,
                               jax.devices()[:1], time.perf_counter())
    assert res["correct"] is True, res["compared"]
    assert res["metrics"]["serve_tok_s"]["value"] > 0
    assert res["attempted"] > 0 and res["failed"] == 0


def test_controls_fail_and_the_windows_chunks_are_counted(tmp_path):
    cell = write_cell(str(tmp_path))
    # which requests a 1-s window finishes depends on the host's load; the
    # tiny traffic's sample of 4 (some 40 tokens) can be one on which an
    # int8 control picks the reference's token everywhere and reads 0.
    # 48 requests are 180-390 tokens: the controls read 0.004-0.011
    cell.traffic["check"]["sample"] = 48
    with jax.default_matmul_precision("highest"):
        out = serve_scoped.run(cell, seed=5, seconds=1.0, trace=False,
                               devices=jax.devices()[:1],
                               t_proc=time.perf_counter(),
                               root=str(tmp_path), control=True)
    assert "moe" not in out.counters and "op_scopes" not in out.counters
    chunks = out.counters["prefill_chunks"]
    assert chunks and all(0 < n <= 16 and start % 16 == 0
                          for start, n in chunks)
    assert out.counters["prefill_flops"] >= sum(
        ouro.prefill_flops(out.dims, s, n, False) for s, n in chunks)
    # float32 program against the float32 reference: rounding through nine
    # sandwich-normed layer passes; both int8 controls lie far above it
    got = {n: v for n, v, _ in out.compared}
    assert got["served_logit_gap"] < 2e-3
    for c in serve_scoped.CONTROLS:
        assert out.counters["control"][c]["mean"] > 30 * max(
            got["served_logit_gap_mean"], 1e-5)


def test_weights_shared_by_the_passes_but_not_their_keys():
    """The reference itself: a pass more changes the logits (the passes
    are not the identity), the same leaves serve every pass (a tree has
    ``n_layers`` layers whatever ``passes``), and pass 2's keys are not
    pass 1's (the stream they project moved)."""
    dims = ouro.Dims.from_config(toy_config())
    params = ouro.make_params(3, dims, jnp.float32)
    assert all(leaf.shape[0] == 3
               for leaf in jax.tree.leaves(params["blocks"]))
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 128, size=32))
    rows = jnp.arange(32)
    with jax.default_matmul_precision("highest"):
        three = ouro.sequence_logits(params, toks, rows, dims=dims,
                                     q_block=32)
        two = ouro.sequence_logits(
            params, toks, rows, dims=dataclasses.replace(dims, passes=2),
            q_block=32)
        p_exit = np.asarray(ouro.exit_probabilities(params, toks, dims=dims))
    assert float(jnp.abs(three - two).mean()) > 0.05
    np.testing.assert_allclose(p_exit.sum(axis=0), 1.0, atol=1e-6)
    assert p_exit.shape == (3, 32) and p_exit.min() > 0


def test_a_skipped_pass_is_not_correct(tmp_path):
    """The fault hook reaches the engine of this driver too: a program
    that runs one pass fewer than the configuration says is not correct
    (its answers are another model's)."""
    cell = write_cell(str(tmp_path))

    def fault(eng):
        from distributed_model_parallel_tpu.serve import model as sm

        short = dataclasses.replace(eng.cfg, n_passes=eng.cfg.n_passes - 1)
        kw = dict(page_size=eng.serve.page_size, impl=eng.serve.attn_impl,
                  layout=eng.cache.layout)
        eng._prefill = sm.make_prefill_step(
            short, chunk=eng.serve.prefill_chunk, **kw)
        eng._decode = sm.make_decode_step(short, **kw)
        eng._stats = sm.init_stats(short)

    with jax.default_matmul_precision("highest"):
        res = harness.run_cell(str(tmp_path), cell, 11, 1.0, False,
                               jax.devices()[:1], time.perf_counter(),
                               fault=fault)
    assert res["correct"] is False


def _hand_made_context(counters, op_scopes=None):
    """A trace of one prefill step and two decode steps, by hand."""
    lines = {
        "modules": [("jit_prefill_step(1)", 0, 60_000_000),
                    ("jit_decode_step(2)", 70_000_000, 40_000_000),
                    ("jit_decode_step(2)", 120_000_000, 40_000_000)],
        "ops": [("%fusion.3 fusion out=() in=()", 10, 50_000_000),
                ("%fusion.8 fusion out=() in=()", 50_000_100, 2_000_000),
                ("%paged_decode_attention.9 custom-call out=() in=()",
                 70_000_010, 8_000_000),
                ("%fusion.5 fusion out=() in=()", 78_000_100, 28_000_000),
                ("%fusion.7 fusion out=() in=()", 106_000_200, 1_000_000),
                ("%paged_decode_attention.9 custom-call out=() in=()",
                 120_000_010, 8_000_000),
                ("%fusion.5 fusion out=() in=()", 128_000_100, 28_000_000)],
    }
    op_scopes = op_scopes or {
        "jit_prefill_step": {"%fusion.3": "loop_stack",
                             "%fusion.8": "exit_gate"},
        "jit_decode_step": {"%paged_decode_attention.9": "loop_stack",
                            "%fusion.5": "loop_stack",
                            "%fusion.7": "exit_gate"},
    }

    class Trace:
        devices = {"d0": lines}

    class Out:
        pass

    Out.counters = dict(counters, op_scopes=op_scopes)
    Out.dims = ouro.Dims.from_config(harness.load_cell(ROOT, CELL).config)

    class Ctx:
        trace, out = Trace, Out
        peaks = harness.load_peaks("TPU v5 lite")
        cell = harness.load_cell(ROOT, CELL)

    return Ctx


def test_the_loops_two_costs_read_under_100_on_a_hand_made_trace():
    """Steps as long as the prediction's (a 36-ms stack in a decode
    round, 50 ms in a full chunk), the cell's own shapes: 16 rows of 205
    tokens a round, one full chunk."""
    from readers import model_roofline, scope_roofline

    counters = {"decode_contexts": [[205] * 16, [205] * 16],
                "prefill_chunks": [(0, 256)]}
    ctx = _hand_made_context(counters)
    spec = {m: harness.load_json(os.path.join(
        BENCH, "layer_metrics", f"{m}.json"))
        for m in ("loop_decode_roofline", "loop_prefill_roofline",
                  "paged_decode_roofline.loop")}
    stack, kv = 4_933_287_936, 16 * 205 * 1_572_864
    decode = scope_roofline.read(ctx, spec["loop_decode_roofline"])
    # two rounds: the stack's ops, kernel included, 36 ms each
    assert decode == pytest.approx(
        100 * 2 * (4 * stack + kv) / 819e9 / 72e-3)
    assert 80 < decode < 100
    prefill = scope_roofline.read(ctx, spec["loop_prefill_roofline"])
    assert prefill == pytest.approx(
        100 * ouro.prefill_flops(ctx.out.dims, 0, 256, False) / 197e12
        / 50e-3)
    assert 45 < prefill < 60
    paged = model_roofline.read(ctx, spec["paged_decode_roofline.loop"])
    assert paged == pytest.approx(100 * 2 * kv / 819e9 / 16e-3)
    assert 75 < paged < 85         # the live K/V's floor is 6.3 ms a round
    # the gate's ops are no part of the stack's time, nor another module's
    assert scope_roofline.read(ctx, dict(
        spec["loop_decode_roofline"], scopes=["exit_gate"])) > 100
    assert scope_roofline.read(ctx, dict(
        spec["loop_decode_roofline"], module="^jit_verify")) is None
    # a program without the scopes (the parent's): nothing, and no raise
    bare = _hand_made_context(counters, op_scopes={"jit_decode_step": {}})
    assert scope_roofline.read(bare, spec["loop_decode_roofline"]) is None
    bare.out.counters.pop("op_scopes")
    assert scope_roofline.read(bare, spec["loop_prefill_roofline"]) is None
