"""Every file the harness loads is found by name, and a cell added as
files only runs with no code edit.

``data/tiny`` is such an addition: a BENCHMARK.json, two configurations,
three traffic mixes and their limits, none of which any code names. The
runs below skip the look for a chip and drive the rest of a run."""

import importlib
import json
import os
import shutil
import time

import jax
import pytest

import harness

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
TINY = os.path.join(HERE, "data", "tiny")


def tiny_cell(name):
    return harness.load_cell(TINY, name,
                             bench_dir=os.path.join(TINY, "bench"))


def run_tiny(name, seed, seconds=1.0, fault=None):
    return harness.run_cell(TINY, tiny_cell(name), seed, seconds, False,
                            jax.devices()[:1], time.perf_counter(),
                            fault=fault)


def test_benchmark_json_names_only_files_that_exist():
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    assert bench["paths"] == ["chipbench"]
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for w in bench["workloads"]:
        cell = harness.load_cell(ROOT, w["name"])
        assert cell.chips in (1, 4)
        importlib.import_module(f"drivers.{cell.traffic['driver']}")
        assert set(cell.checks["limits"])
        # every cell reports setup_s, another end-to-end metric and a
        # per-layer one
        names = {m["name"] for m in cell.end_to_end()}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer()
    for m in bench["per_layer"]:
        spec = harness.load_json(os.path.join(
            BENCH, "layer_metrics", f"{m['name']}.json"))
        # the reader and its parameters only: BENCHMARK.json alone says
        # what the metric is
        assert not set(spec) & set(m), (m["name"], set(spec) & set(m))
        assert hasattr(importlib.import_module(
            f"readers.{spec['reader']}"), "read")
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
        for c in m["workloads"]:      # the cell reports what it moves
            assert m["moves"] in {x["name"] for x in
                                  harness.load_cell(ROOT, c).end_to_end()}
    for c in bench["configs"]:
        cfg = harness.load_json(os.path.join(ROOT, c["file"]))
        assert cfg["reduced"] == c["reduced"]
        widths = ("hidden_size", "intermediate_size", "head_dim")
        assert not any(k in widths or k.endswith(("_dim", "_rank"))
                       for k in c["reduced"])


def test_unknown_device_kind_has_no_peaks():
    assert harness.load_peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        harness.load_peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        harness.load_peaks("source")


@pytest.mark.parametrize("name,metric", [
    ("tiny-train.seq128", "train_tok_s"),
    ("tiny.batch", "serve_tok_s"),
    ("tiny.steady", "ttft_p95_ms"),
])
def test_a_cell_added_as_files_runs(name, metric):
    res = run_tiny(name, seed=2 ** 31 + 5)
    assert res["correct"] is True, res["compared"]
    assert list(res)[-1] == "compared" and list(res)[0] == "correct"
    assert res["metrics"][metric]["value"] > 0
    assert res["metrics"]["setup_s"]["value"] > 0
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["count"] == 1
    json.dumps(res)


def test_a_four_chip_cell_is_one_more_traffic_file(tmp_path):
    """dp2 x tp2 of the tiny training configuration (grouped-query
    attention with a window under tensor parallelism), added to a copy
    of ``data/tiny`` as a traffic file, its limits and one entry of
    ``workloads``: the driver builds the mesh and shards the benchmark's
    weights with the program's own rule, and the one-device reference
    agrees."""
    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    root = str(tmp_path / "tiny")
    shutil.copytree(TINY, root)
    bench = harness.load_json(os.path.join(root, "BENCHMARK.json"))
    bench["workloads"].append({"name": "tiny-train.tp2dp2", "chips": 4,
                               "config": "tiny-train", "traffic": "tp2dp2",
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "train_tok_s":
            m["workloads"].append("tiny-train.tp2dp2")
    mix = harness.load_json(os.path.join(root, "bench", "traffic",
                                         "seq128.json"))
    mix.update(mesh={"data": 2, "model": 2}, rows_per_replica=1)
    for path, obj in (("BENCHMARK.json", bench),
                      ("bench/traffic/tp2dp2.json", mix)):
        with open(os.path.join(root, path), "w") as f:
            json.dump(obj, f)
    shutil.copy(os.path.join(root, "bench/checks/tiny-train.seq128.json"),
                os.path.join(root, "bench/checks/tiny-train.tp2dp2.json"))
    cell = harness.load_cell(root, "tiny-train.tp2dp2",
                             bench_dir=os.path.join(root, "bench"))
    res = harness.run_cell(root, cell, 2 ** 31 + 5, 1.0, False,
                           jax.devices()[:4], time.perf_counter())
    assert res["correct"] is True, res["compared"]
    assert res["device"]["count"] == 4
    assert res["metrics"]["train_tok_s"]["value"] > 0
