"""Cost-model parallelism autotuner (autotune/, docs/AUTOTUNE.md):
deterministic enumeration + ranking, the HBM feasibility filter,
hand-computed alpha-beta cost cases, trace-time op-count accounting,
``strategy="auto"`` end-to-end on the CPU 8-device mesh, elastic re-plan
on a shrunk mesh, and the ``scripts/dmp_plan.py --dry-run`` smoke
(wired like the chaos/soak smokes: the script module is imported and
driven in-process)."""

import dataclasses
import io
import json
import contextlib
import math

import pytest

import jax

from distributed_model_parallel_tpu.autotune import (
    Collective,
    CostCoefficients,
    InfeasiblePlanError,
    ParallelPlan,
    cnn_workload,
    collective_time_s,
    enumerate_plans,
    estimate_plan_memory,
    lm_workload,
    mesh_from_plan,
    observed_comm_table,
    plan_cost,
    plan_parallelism,
    plan_payload,
)
from distributed_model_parallel_tpu.autotune.search import WorkloadSpec
from distributed_model_parallel_tpu.config import MeshConfig
from distributed_model_parallel_tpu.models import transformer as tfm
from distributed_model_parallel_tpu.utils.telemetry import (
    read_records,
    wire_bytes_estimate,
    wire_ops_estimate,
)

pytestmark = pytest.mark.autotune


def _lm_cfg(**kw):
    base = dict(vocab_size=512, d_model=64, n_heads=8, n_layers=8,
                d_ff=256, max_seq_len=128, pos_embedding="rope")
    base.update(kw)
    return tfm.TransformerConfig(**base)


def _lm_w(batch=16, seq=128, **kw):
    return lm_workload(_lm_cfg(**kw), batch, seq)


# ---------------------------------------------------------------------------
# Enumeration: deterministic, complete, constraint-pruned
# ---------------------------------------------------------------------------

def test_enumeration_deterministic_and_counts():
    w = _lm_w()
    a = enumerate_plans(w, 8)
    b = enumerate_plans(w, 8)
    assert a == b                       # identical objects AND order
    # 8 = 2^3 over 4 usable axes (no MoE -> ep pinned at 1): exactly the
    # 20 ordered factorizations, all feasible for this divisible config.
    assert len(a) == 20
    assert all(p.num_devices == 8 for p in a)
    assert all(p.ep == 1 for p in a)
    assert all(w.batch_size % p.dp == 0 for p in a)


def test_enumeration_prunes_per_axis_constraints():
    # 3 heads: tp/sp degrees over 8 devices can never divide them.
    w = _lm_w(n_heads=3, d_ff=384)
    assert all(p.tp == 1 and p.sp == 1 for p in enumerate_plans(w, 8))
    # 6 layers: pp in {2} only (8 % pp == 0 candidates are 2, 4, 8).
    w = _lm_w(n_layers=6)
    assert {p.pp for p in enumerate_plans(w, 8)} == {1, 2}
    # batch 4: dp capped at 4.
    w = _lm_w(batch=4)
    assert all(p.dp <= 4 for p in enumerate_plans(w, 8))
    # MoE with 4 experts opens the expert axis at ep in {2, 4}.
    w = _lm_w(moe_experts=4)
    assert {p.ep for p in enumerate_plans(w, 8)} == {1, 2, 4}


def test_ranking_deterministic():
    w = _lm_w()
    d1 = plan_parallelism(w, 8, hbm_bytes=16e9)
    d2 = plan_parallelism(w, 8, hbm_bytes=16e9)
    assert [r.plan for r in d1.ranked] == [r.plan for r in d2.ranked]
    assert d1.chosen.plan == d2.chosen.plan
    assert len(d1.ranked) >= 20
    # Best-first by modeled step time.
    totals = [r.cost.total_s for r in d1.ranked]
    assert totals == sorted(totals)


# ---------------------------------------------------------------------------
# Memory-feasibility filter
# ---------------------------------------------------------------------------

def _big_cnn_workload():
    # Hand-built: 8 GB of replicated parameters — a known-OOM layout on a
    # 4 GB device unless the strategy shards them.
    return WorkloadSpec(kind="cnn", batch_size=512, flops_per_step=1e12,
                        param_count=2_000_000_000, param_bytes=8_000_000_000,
                        n_units=8, boundary_act_bytes_per_sample=4096)


def test_memory_filter_rejects_known_oom_layouts():
    w = _big_cnn_workload()
    d = plan_parallelism(w, 8, hbm_bytes=4e9)
    # Replicated-param engines cannot fit 8 GB params (+grads+momentum)
    # in 4 GB; only FSDP's dp-sharded layout survives.
    assert d.chosen.plan.strategy == "fsdp"
    rejected = {p.strategy for p, _ in d.rejected}
    assert "gspmd" in rejected
    for _, why in d.rejected:
        assert "GB" in why              # actionable reason, not a bool


def test_memory_filter_all_rejected_raises_typed():
    w = _big_cnn_workload()
    with pytest.raises(InfeasiblePlanError) as e:
        plan_parallelism(w, 8, hbm_bytes=1e6)
    assert "feasibility" in str(e.value)


def test_memory_estimate_shards_as_the_repo_does():
    w = _lm_w()
    repl = estimate_plan_memory(w, ParallelPlan("spmd", dp=8))
    pp = estimate_plan_memory(w, ParallelPlan("spmd", pp=8))
    # pp shards params 8x; the LM trainer's momentum is replicated, so
    # opt bytes must NOT shrink (memory.py models the repo, not a wish).
    assert pp["params_bytes"] == pytest.approx(repl["params_bytes"] / 8)
    assert pp["opt_bytes"] == repl["opt_bytes"]


# ---------------------------------------------------------------------------
# Alpha-beta cost model: hand-computed cases + trace-time seeding
# ---------------------------------------------------------------------------

def test_wire_ops_estimate_ring_counts():
    assert wire_ops_estimate("psum", 8) == 14          # 2(n-1)
    assert wire_ops_estimate("reduce_scatter", 8) == 7
    assert wire_ops_estimate("all_gather", 8) == 7
    assert wire_ops_estimate("ppermute", 8) == 1
    assert wire_ops_estimate("unknown_kind", 8) == 1


def test_collective_time_hand_computed():
    coeffs = CostCoefficients(alpha_s=1e-6, wire_bytes_per_s=1e9,
                              peak_flops_per_s=1e12)
    c = Collective("psum", "data", payload_bytes=1000, n=4, count=2)
    expected = 2 * (1e-6 * 6 + (2 * 3 / 4 * 1000) / 1e9)
    assert collective_time_s(c, coeffs) == pytest.approx(expected)


def test_plan_cost_hand_computed_dp_only():
    # One collective (grad psum over dp), fully hand-checkable.
    w = WorkloadSpec(kind="cnn", batch_size=8, flops_per_step=8e9,
                     param_count=1000, param_bytes=4000, n_units=2,
                     boundary_act_bytes_per_sample=16)
    coeffs = CostCoefficients(alpha_s=1e-6, wire_bytes_per_s=1e9,
                              peak_flops_per_s=1e12, overlap_fraction=0.0)
    cost = plan_cost(w, ParallelPlan("gspmd", dp=8), coeffs)
    compute = 8e9 / 8 / 1e12
    comm = (1e-6 * wire_ops_estimate("psum", 8)
            + wire_bytes_estimate("psum", 4000, 8) / 1e9)
    assert cost.compute_s == pytest.approx(compute)
    assert cost.comm_s == pytest.approx(comm)
    assert cost.bubble == 1.0
    assert cost.total_s == pytest.approx(compute + comm)
    # With overlap credit the grad reduction hides under the backward.
    lenient = dataclasses.replace(coeffs, overlap_fraction=1.0)
    cost2 = plan_cost(w, ParallelPlan("gspmd", dp=8), lenient)
    assert cost2.total_s == pytest.approx(
        compute + comm - min(comm, compute))


def test_plan_cost_bubble_and_microbatches():
    w = _lm_w()
    shallow = plan_cost(w, ParallelPlan("spmd", pp=8, num_microbatches=1))
    deep = plan_cost(w, ParallelPlan("spmd", pp=8, num_microbatches=16))
    assert shallow.bubble == pytest.approx(8.0)
    assert deep.bubble == pytest.approx((16 + 7) / 16)
    assert deep.compute_s * deep.bubble < shallow.compute_s * shallow.bubble


def test_enumeration_prunes_tp_sp_local_head_interplay():
    # heads=8 over 16 devices: tp4 x sp4 leaves 2 local heads, which sp=4
    # cannot scatter — the enumerator must skip it, not crash at trace.
    w = _lm_w(batch=16)
    plans = enumerate_plans(w, 16)
    assert not any(p.tp == 4 and p.sp == 4 for p in plans)
    assert any(p.tp == 2 and p.sp == 4 for p in plans)   # 4 local heads ok


def test_bf16_moe_expert_bytes_stay_positive():
    # Expert params must be priced at the model's real storage width:
    # with bf16 (2 B/param) a hardcoded 4 B/expert-param used to drive
    # the per-device params estimate (and the grad-psum payload) NEGATIVE.
    w = _lm_w(moe_experts=8, dtype="bfloat16")
    assert w.param_bytes == 2 * w.param_count
    plan = ParallelPlan("spmd", dp=2, ep=4)
    est = estimate_plan_memory(w, plan)
    assert est["params_bytes"] > 0 and est["grads_bytes"] > 0
    from distributed_model_parallel_tpu.autotune import plan_collectives

    for c in plan_collectives(w, plan):
        assert c.payload_bytes > 0
    assert plan_cost(w, plan).comm_hidden_s >= 0


def test_measure_failure_does_not_kill_planning():
    w = _lm_w()
    calls = []

    def flaky(plan):
        calls.append(plan)
        if len(calls) == 1:
            raise RuntimeError("compile blew up")
        return 0.5 + 0.1 * len(calls)

    d = plan_parallelism(w, 8, hbm_bytes=16e9, measure_fn=flaky,
                         measure_top=3)
    assert len(d.measured) == 3
    assert "error" in d.measured[0] and "measured_s" in d.measured[1]
    # Measured-best among the candidates that DID time.
    assert d.chosen.plan.payload()["axes"] == d.measured[1]["axes"]

    def always_fails(plan):
        raise RuntimeError("no devices")

    d2 = plan_parallelism(w, 8, hbm_bytes=16e9, measure_fn=always_fails,
                          measure_top=2)
    # Analytic best survives; errors are carried for the caller.
    assert d2.chosen.plan == d2.ranked[0].plan
    assert all("error" in m for m in d2.measured)


def test_enumeration_pins_sp_under_attn_window():
    # Sliding-window attention rejects sequence parallelism at trace
    # time (transformer._attention) — the enumerator must pin sp = 1.
    w = _lm_w(attn_window=32)
    plans = enumerate_plans(w, 8)
    assert plans and all(p.sp == 1 for p in plans)


def test_strategy_auto_rejects_explicit_spec(mesh8, tmp_path):
    from distributed_model_parallel_tpu.train.lm_trainer import (
        LMTrainConfig,
        LMTrainer,
    )
    from distributed_model_parallel_tpu.train.trainer import Trainer
    from tests.conftest import tiny_train_config

    with pytest.raises(ValueError, match="auto"):
        LMTrainer(LMTrainConfig(strategy="auto"), spec=mesh8)
    with pytest.raises(ValueError, match="auto"):
        Trainer(tiny_train_config(tmp_path, strategy="auto"), spec=mesh8)


def test_all_measurements_failed_reports_analytic():
    w = _lm_w()

    def always_fails(plan):
        raise RuntimeError("no devices")

    d = plan_parallelism(w, 8, hbm_bytes=16e9, measure_fn=always_fails,
                         measure_top=2)
    assert not d.measurement_won
    assert "analytic-best" in d.describe()


def test_undersubscribe_on_prime_device_count():
    # A 7-device slice (one device quarantined out of 8) has no feasible
    # factorization of exactly 7 — the trainers' auto path must fall
    # back to the largest smaller count, like fit_mesh_to_devices.
    w = _lm_w()   # batch 16, layers/heads 8: degree 7 fits no axis
    with pytest.raises(InfeasiblePlanError):
        plan_parallelism(w, 7, hbm_bytes=16e9)
    d = plan_parallelism(w, 7, hbm_bytes=16e9, allow_undersubscribe=True)
    assert d.n_devices == 6 or d.n_devices == 4
    assert d.chosen.plan.num_devices == d.n_devices


def test_pipeline_strategy_memory_is_per_stage():
    # The single-controller pipeline places each stage's params+opt on
    # its own device; charging full replication used to spuriously
    # reject every plan_for_stage_pipeline candidate.
    w = _big_cnn_workload()
    repl = estimate_plan_memory(w, ParallelPlan("spmd_pipeline", dp=1,
                                                pp=8))
    staged = estimate_plan_memory(w, ParallelPlan("pipeline", dp=1, pp=8))
    assert staged["params_bytes"] == pytest.approx(
        repl["params_bytes"] / 8)
    assert staged["opt_bytes"] == pytest.approx(repl["opt_bytes"] / 8)


def test_dmp_plan_measure_plus_dry_run_rejected():
    from scripts.dmp_plan import main

    with pytest.raises(SystemExit) as e:
        main(["--workload", "lm", "--devices", "8", "--dry-run",
              "--measure", "2"])
    assert "dry-run" in str(e.value)


def test_reason_startup_without_checkpoint(tmp_path):
    from distributed_model_parallel_tpu.autotune.planner import _reason_for

    class Cfg:
        elastic = True
        resume = True
        checkpoint_dir = str(tmp_path / "nonexistent")

    assert _reason_for(Cfg()) == "startup"   # nothing to resume yet
    Cfg.checkpoint_dir = str(tmp_path)
    (tmp_path / "slot").mkdir()
    assert _reason_for(Cfg()) == "elastic-replan"


def test_observed_fsdp_keeps_proportional_overlap_credit():
    # The observed per-axis total must not lose FSDP's reduce-scatter
    # overlap credit just because the all-gather iterates first.
    w = _big_cnn_workload()
    coeffs = CostCoefficients(alpha_s=1e-6, wire_bytes_per_s=1e9,
                              peak_flops_per_s=1e10, overlap_fraction=1.0)
    plan = ParallelPlan("fsdp", dp=8)
    analytic = plan_cost(w, plan, coeffs)
    obs = {"data": {"bytes": 1e9, "ops": 100.0}}
    seeded = plan_cost(w, plan, coeffs, observed=obs)
    assert analytic.comm_hidden_s > 0
    # Same overlappable share, applied to the observed total.
    assert seeded.comm_hidden_s / seeded.comm_s == pytest.approx(
        analytic.comm_hidden_s / analytic.comm_s)


def test_observed_comm_table_seeds_cost():
    counters = {
        "collective_wire_bytes_est{axis=data,kind=psum}": 1e6,
        "collective_wire_bytes_est{axis=data,kind=all_gather}": 5e5,
        "collective_ops_est{axis=data,kind=psum}": 28.0,
        "collective_traces{axis=data,kind=psum}": 2.0,   # ignored
    }
    obs = observed_comm_table(counters)
    assert obs["data"]["bytes"] == pytest.approx(1.5e6)
    assert obs["data"]["ops"] == pytest.approx(28.0)
    w = _big_cnn_workload()
    coeffs = CostCoefficients(alpha_s=1e-6, wire_bytes_per_s=1e9,
                              peak_flops_per_s=1e12, overlap_fraction=0.0)
    plan = ParallelPlan("gspmd", dp=8)
    seeded = plan_cost(w, plan, coeffs, observed=obs)
    assert seeded.comm_s == pytest.approx(1e-6 * 28.0 + 1.5e6 / 1e9)
    assert seeded.comm_s != plan_cost(w, plan, coeffs).comm_s


def test_record_collective_accounts_op_counts(mesh8):
    """The trace-time accounting writes the alpha term: one traced psum
    over the 8-way data axis adds 2(n-1)=14 estimated messages."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from distributed_model_parallel_tpu.ops.collectives import psum_mean
    from distributed_model_parallel_tpu.utils.telemetry import registry

    def key(name):
        return f"{name}{{axis=data,kind=psum}}"

    before = registry().snapshot()["counters"]
    x = jnp.arange(8.0)
    jax.jit(jax.shard_map(lambda v: psum_mean(v, "data"), mesh=mesh8.mesh,
                          in_specs=P("data"), out_specs=P("data"),
                          check_vma=False))(x)
    after = registry().snapshot()["counters"]
    delta_ops = (after.get(key("collective_ops_est"), 0)
                 - before.get(key("collective_ops_est"), 0))
    delta_traces = (after.get(key("collective_traces"), 0)
                    - before.get(key("collective_traces"), 0))
    assert delta_traces >= 1
    assert delta_ops == pytest.approx(14 * delta_traces)


# ---------------------------------------------------------------------------
# strategy="auto" end-to-end on the 8-device CPU mesh
# ---------------------------------------------------------------------------

def _plan_records(jsonl_path):
    return [r for r in read_records(jsonl_path) if r.get("kind") == "plan"]


def _tiny_lm_config(tmp_path, **kw):
    import os

    defaults = dict(
        model=tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                    n_layers=2, d_ff=64, max_seq_len=16),
        batch_size=8, seq_len=16, steps_per_epoch=2, epochs=1,
        n_tokens=2000, eval_batches=0,
        log_dir=os.path.join(str(tmp_path), "log"),
        checkpoint_dir=os.path.join(str(tmp_path), "ckpt"))
    defaults.update(kw)
    from distributed_model_parallel_tpu.train.lm_trainer import LMTrainConfig

    return LMTrainConfig(**defaults)


def test_strategy_auto_lm_end_to_end(tmp_path, devices):
    from distributed_model_parallel_tpu.train.lm_trainer import LMTrainer

    t = LMTrainer(_tiny_lm_config(tmp_path, strategy="auto"))
    # The planner used every live device and resolved "auto" away.
    assert t.config.strategy == "spmd"
    assert t.config.mesh.num_devices == len(jax.devices())
    t.fit()
    plans = _plan_records(t.logger.jsonl_path)
    assert len(plans) == 1
    p = plans[0]
    assert p["workload"] == "lm" and p["reason"] == "startup"
    assert math.prod(p["axes"].values()) == len(jax.devices())
    assert p["n_feasible"] >= 1 and p["cost"]["total_s"] > 0


def test_strategy_auto_cnn_trainer(tmp_path, devices):
    from distributed_model_parallel_tpu.train.trainer import Trainer
    from tests.conftest import tiny_train_config

    cfg = tiny_train_config(tmp_path, strategy="auto", epochs=1,
                            mesh=MeshConfig())
    t = Trainer(cfg)
    assert t.config.strategy in ("gspmd", "fsdp", "spmd_pipeline")
    assert t.config.mesh.num_devices == len(jax.devices())
    plans = _plan_records(t.logger.jsonl_path)
    assert len(plans) == 1 and plans[0]["workload"] == "cnn"
    assert plans[0]["strategy"] == t.config.strategy


def test_strategy_auto_rejects_unknown_lm():
    from distributed_model_parallel_tpu.train.lm_trainer import (
        LMTrainConfig,
        LMTrainer,
    )

    with pytest.raises(ValueError, match="spmd"):
        LMTrainer(LMTrainConfig(strategy="alpa"))


def test_elastic_replan_on_shrunk_mesh(tmp_path, devices, monkeypatch):
    """The acceptance journey: auto+elastic run on 8 devices, kill,
    restart on a 4-device slice — the restart RE-PLANS (new plan record,
    4-device layout) at the exact resumed global step, instead of
    blindly shrinking dp on the old mesh shape."""
    from distributed_model_parallel_tpu.train import elastic
    from distributed_model_parallel_tpu.train.lm_trainer import LMTrainer

    cfg = _tiny_lm_config(tmp_path, strategy="auto", elastic=True,
                          emergency_every=1, steps_per_epoch=3)
    t1 = LMTrainer(cfg)
    assert t1.config.mesh.num_devices == 8
    t1.fit()
    assert t1._global_step == 3

    monkeypatch.setattr(elastic, "live_device_count", lambda: 4)
    t2 = LMTrainer(dataclasses.replace(cfg, resume=True))
    assert t2.config.mesh.num_devices == 4
    assert t2._global_step == 3         # exact resume
    plans = _plan_records(t2.logger.jsonl_path)
    assert len(plans) == 2              # startup + re-plan (shared stream)
    replan = plans[-1]
    assert replan["reason"] == "elastic-replan"
    assert replan["n_devices"] == 4
    assert math.prod(replan["axes"].values()) == 4
    assert replan["global_step"] == 3   # stamped at the resume point


# ---------------------------------------------------------------------------
# dmp_plan.py CLI smoke (tier-1, wired like the chaos/soak smokes)
# ---------------------------------------------------------------------------

def _run_cli(argv):
    from scripts.dmp_plan import main

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        main(argv)
    return json.loads(buf.getvalue())


def test_dmp_plan_dry_run_smoke():
    argv = ["--workload", "lm", "--devices", "8", "--batch", "16",
            "--seq", "128", "--d-model", "64", "--d-ff", "256",
            "--vocab", "512", "--dry-run"]
    out = _run_cli(argv)
    assert out["n_feasible"] >= 20
    assert math.prod(out["axes"].values()) == 8
    assert len(out["ranked"]) == out["n_feasible"]
    # Deterministic: a second invocation produces the identical ranking.
    assert _run_cli(argv)["ranked"] == out["ranked"]


def test_dmp_plan_infeasible_exits_nonzero(capsys):
    from scripts.dmp_plan import main

    with pytest.raises(SystemExit) as e:
        main(["--workload", "lm", "--devices", "8", "--batch", "16",
              "--dry-run", "--hbm-gb", "0.0001"])
    assert e.value.code == 2
    rec = json.loads(capsys.readouterr().out)
    assert rec["error"] == "no-feasible-plan"


def test_dmp_plan_cnn_dry_run():
    out = _run_cli(["--workload", "cnn", "--model", "tinycnn",
                    "--devices", "8", "--batch", "64", "--dry-run"])
    assert out["strategy"] in ("gspmd", "fsdp", "spmd_pipeline")
    strategies = {r["strategy"] for r in out["ranked"]}
    assert "spmd_pipeline" in strategies   # pipeline splits enumerated


@pytest.mark.slow
def test_dmp_plan_measured_validation(devices):
    """--measure K times an LMTrainer step per candidate (on the plan's
    own mesh) and the measured-best wins — the acceptance mechanism for
    'analytic top-1 agrees with the measured-best of its top-3'."""
    out = _run_cli(["--workload", "lm", "--devices", "8", "--batch", "8",
                    "--seq", "16", "--d-model", "32", "--heads", "2",
                    "--layers", "2", "--d-ff", "64", "--vocab", "64",
                    "--measure", "2", "--measure-steps", "1"])
    assert len(out["measured"]) == 2
    timed = [m for m in out["measured"] if "measured_s" in m]
    assert timed                        # at least one candidate timed
    best = min(timed, key=lambda m: m["measured_s"])
    assert out["axes"] == best["axes"]


# ---------------------------------------------------------------------------
# Public auto_partition contract + plan payload shape
# ---------------------------------------------------------------------------

def test_auto_partition_public_reexports():
    from distributed_model_parallel_tpu import parallel

    assert parallel.cost_balanced_boundaries([1, 1, 1, 1], 2) == [0, 2, 4]
    assert callable(parallel.unit_costs)
    assert callable(parallel.compiled_flops_probe)
    assert callable(parallel.auto_boundaries)
    assert callable(parallel.microbatch_rows)


def test_lm_model_for_plan_switches_parallel_axes():
    from distributed_model_parallel_tpu.autotune import lm_model_for_plan

    base = _lm_cfg()
    m = lm_model_for_plan(base, ParallelPlan("spmd", dp=2, tp=2, sp=2))
    assert (m.tp_axis, m.sp_axis, m.ep_axis) == ("model", "seq", None)
    # And back off when a re-plan drops the axis.
    m2 = lm_model_for_plan(m, ParallelPlan("spmd", dp=8))
    assert (m2.tp_axis, m2.sp_axis) == (None, None)


def test_plan_payload_matches_plan_record_shape():
    mesh = MeshConfig(data=4, stage=2)
    payload = plan_payload(mesh, "spmd", num_microbatches=4)
    plan = ParallelPlan("spmd", dp=4, pp=2, num_microbatches=4)
    assert payload == plan.payload()
    assert mesh_from_plan(plan).axis_sizes() == mesh.axis_sizes()


def test_cnn_workload_probe_uses_unit_costs():
    from distributed_model_parallel_tpu.config import DataConfig, ModelConfig

    w = cnn_workload(ModelConfig(name="tinycnn"),
                     DataConfig(name="synthetic", batch_size=64))
    assert w.n_units >= 2
    assert len(w.unit_flop_costs) == w.n_units
    assert all(c >= 1.0 for c in w.unit_flop_costs)
    assert w.boundary_act_bytes_per_sample > 0
    assert w.flops_per_step > 0
