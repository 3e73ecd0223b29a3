"""Test fixtures: an 8-device virtual CPU mesh.

The reference's "parallel" test tier runs every test file under a real
launcher with 2 MPI/gloo ranks over localhost (reference:
.buildkite/gen-pipeline.sh:128-151, test/utils/common.py:32-70).  The TPU
analog is XLA host-platform device virtualization: one process, 8 virtual
CPU devices, real collectives through the same shard_map/psum code paths
that run on ICI.
"""

import os

# Must be set before jax is imported.  Force CPU: the ambient environment
# may point JAX_PLATFORMS at real TPU hardware, which tests must never
# touch — and every subprocess a test spawns inherits it.
os.environ["JAX_PLATFORMS"] = "cpu"
# Keras 3's backend is process-global and fixed at first keras import; pin
# it for the whole suite so collection order can't flip it (the TF
# frontend's suite runs in its own subprocess with backend=tensorflow).
os.environ.setdefault("KERAS_BACKEND", "jax")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import pytest  # noqa: E402


def use_real_backend(pkg: str) -> bool:
    """HOROVOD_REAL_BACKENDS=1 + the real package installed: contract
    fixtures skip their fake injection and the same tests run against
    reality (scripts/run_real_backends.py).  Shared here so every
    fixture gates identically."""
    import importlib.util
    return (os.environ.get("HOROVOD_REAL_BACKENDS") == "1"
            and importlib.util.find_spec(pkg) is not None)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "integration: multi-process launcher-in-the-loop tests (reference: "
        "test/integration/ tier)")


# ----------------------------------------------------------- tier marking
#
# The full suite outgrew its pre-commit role (measured 30m21s cold,
# 473 tests, 2026-08-01 — COVERAGE.md).  Tests costing >= ~8 s each
# (1,445 s of the total between them) carry the `slow` marker, assigned
# HERE from one list so the test files stay unmarked and the threshold
# is maintained in one place.  pyproject addopts deselects
# `slow` + `integration` by default (~6 min); the FULL suite is
#     python -m pytest tests/ -m "" -q
# and stays the milestone/round gate.  Deselection is not skipping:
# both tiers run with 0 skips.

_SLOW_FILES = {
    # every test is a multi-second subprocess example smoke
    "test_examples_smoke.py",
}
_SLOW_TESTS = {  # file::test (param ids stripped), >= ~8 s measured
    "test_bench.py": {
        # also individually marked slow (pre-existing) — listed for
        # completeness since this table is the tier's source of truth
        "test_bench_llama_cpu_contract", "test_bench_resnet_cpu_contract",
        "test_bench_autotune_cpu_contract",
        "test_bench_scaling_cpu_contract", "test_bench_wire_cpu_contract",
        "test_bench_overlap_cpu_contract", "test_bench_serve_cpu_contract",
        "test_bench_serve_users_cpu_contract",
        "test_bench_zero_cpu_contract", "test_bench_layout_cpu_contract",
    },
    "test_zero.py": {
        # the full level x wire x EF x k acceptance matrix (~18 combos x
        # 3 jitted chains); the fast tier keeps a 3-combo slice
        # (test_zero_levels_equivalent_core) and the CI jax-core leg
        # (-m "") runs the whole matrix
        "test_zero_levels_equivalent_matrix",
    },
    "test_models.py": {
        "test_inception_v3_forward_and_grads",
        "test_vgg16_features_train_and_param_count",
        "test_resnet_forward_shape", "test_master_weights_bf16_compute",
        "test_llama_chunked_ce_matches", "test_vgg_apply_adaptive_resolution",
        "test_llama_fused_projections_match",
    },
    "test_layout.py": {
        # the full mesh x level composition matrix (12 jitted chains)
        # and the lossy-wire level-equivalence proof; the fast tier
        # keeps a (2,2,2)-vs-reference slice + the gauge pin, and the
        # CI layout leg (-m "") runs the whole matrix
        "test_composed_matrix_all_meshes_levels",
        "test_composed_lossy_wire_levels_agree",
    },
    "test_pipeline.py": {
        "test_pipelined_llama_matches_sequential",
        "test_pipeline_composes_with_dp",
        "test_pipeline_various_microbatch_counts",
        "test_pipeline_gradients_match_sequential",
    },
    "test_expert.py": {
        "test_moe_llama_ep_path_matches_dense",
        "test_moe_llama_mixtral_config_trains",
        "test_moe_gradients_flow", "test_moe_capacity_drops_tokens",
    },
    "test_spark_ray.py": {
        "test_torch_estimator_end_to_end",
        "test_lightning_estimator_end_to_end",
        "test_lightning_callbacks_logger_validation_and_clip",
        "test_elastic_ray_executor_runs_function_elastically",
        "test_spark_run_local_executor_ranks_and_results",
        "test_programmatic_run_api",
        "test_ray_executor_local_pool_env_and_results",
        "test_linear_estimator_end_to_end",
        "test_linear_estimator_workers_converge_identically",
        "test_keras_estimator_runs_callbacks",
        "test_keras_estimator_early_stopping",
    },
    "test_spark_prepare.py": {
        "test_estimator_fit_on_dataframe",
        "test_prepare_dataframe_partition_parallel",
        "test_hdfs_store_estimator_end_to_end",
    },
    "test_spark_estimator_depth.py": {
        "test_run_elastic_shrinks_to_min_np",
        "test_elastic_fit_survives_worker_kill",
        "test_run_elastic_respects_reset_limit",
        # ~13 s each (tier-1 headroom, PR 8): full estimator fits; the
        # cheaper estimator-depth tests keep the fast-tier coverage and
        # the CI cluster leg (-m "") still runs these
        "test_sample_weight_col_torch_and_custom_loss_guard",
        "test_torch_estimator_cross_entropy_and_accuracy",
    },
    "test_serve.py": {
        # ~12 s per model family (tier-1 headroom, PR 8): the exact
        # engine==reference-greedy equivalence; the CI serving leg
        # (-m "") runs it, and the cheaper bit-near/eviction/scheduler
        # serve tests keep fast-tier coverage
        "test_engine_matches_reference_greedy_decode",
    },
    "test_serve_speed.py": {
        # 8 engine builds (~2 s jit each): the full prefix x chunked x
        # spec determinism matrix; the CI serving leg (-m "") runs it,
        # and the all-legs-on fast-tier test keeps the byte-identity
        # gate on every pre-commit run
        "test_determinism_matrix_all_leg_combinations",
    },
    "test_serve_integration.py": {
        # 55 s — the single most expensive tier-1 test (tier-1 headroom,
        # PR 8): the full hvdrun --serve E2E (orbax restore + 3 streamed
        # /generate).  The 2-proc fleet-lockstep serve test stays fast-
        # tier, and the CI serve smoke leg (-m "") runs this one on
        # every pipeline.
        "test_hvdrun_serve_end_to_end",
    },
    "test_elastic_serve_integration.py": {
        # ~2 fleets x (bring-up + reset round): the ISSUE-10 chaos
        # acceptance experiment; the CI elastic-serve smoke leg (-m "")
        # runs it on every pipeline, and the fast tier keeps the
        # jax-free redrive/fencing/drain coverage (tests/test_serve_ft).
        "test_elastic_serve_kill_mid_stream_redrives_and_drains",
    },
    "test_tune.py": {
        "test_distributed_trainable_forwards_worker_reports",
        "test_distributed_trainable_runs_workers",
    },
    "test_real_backend_fakes.py": {
        "test_ray_worker_pool_spread_placement_and_kill",
        "test_linear_estimator_fit_on_spark_executor",
    },
    "test_tensorflow.py": {"test_tf_frontend_suite_subprocess"},
    "test_sequence_parallel.py": {
        "test_ring_attention_flash_gradients_match_full"},
    "test_fsdp.py": {"test_fsdp_step_matches_replicated"},
    "test_elastic.py": {"test_jax_state_sharded_commit_restore_at_1gb"},
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        path, _, rest = item.nodeid.partition("::")
        fname = path.rsplit("/", 1)[-1]
        test = rest.split("[", 1)[0]
        if fname in _SLOW_FILES or test in _SLOW_TESTS.get(fname, ()):
            item.add_marker(pytest.mark.slow)


@pytest.fixture(scope="session")
def hvd():
    import horovod_tpu as hvd
    hvd.init()
    yield hvd


@pytest.fixture(scope="session")
def eight_device_mesh(hvd):
    return hvd.mesh()
