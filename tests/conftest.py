"""Test harness: simulate an 8-device TPU mesh on CPU.

The reference has no automated tests at all (SURVEY.md section 4) — every
distributed path there needs a real NCCL cluster.  Here, every parallelism
strategy is exercised without TPUs by forcing XLA's host platform to expose 8
virtual devices; the same shard_map/pjit programs then run unchanged on a real
TPU slice.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# Force the CPU platform whatever the caller's environment selects: the
# suite never claims a chip.
jax.config.update("jax_platforms", "cpu")

# Persistent XLA compile cache: the suite is compile-dominated on CPU, and
# caching roughly halves repeat-run wall clock (measured: 17s -> 9.7s for a
# representative pipeline compile).  Same rule as the program's
# (utils/compile_cache.py): JAX_COMPILATION_CACHE_DIR where it is set, else
# the suite's own fixed sub-directory of the checkout's cache root.
# JAX_ENABLE_COMPILATION_CACHE=0 turns it off (e.g. when bisecting
# compiler issues).
from ddl_tpu.utils.compile_cache import (  # noqa: E402
    ENV_JAX_CACHE,
    default_cache_root,
)

if not os.environ.get(ENV_JAX_CACHE):
    jax.config.update(
        "jax_compilation_cache_dir", str(default_cache_root() / "tests")
    )
jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)

import pytest  # noqa: E402

# ---------------------------------------------------------------------------
# Fast/slow tiers.  `-m "not slow"` is the core tier (~8 min cold, ~5 min
# with a warm compile cache, vs ~45 min for everything); the slow tier keeps
# the exhaustive parametrizations and end-to-end runs.  Membership is by
# measured duration (>= ~15 s on the dev CPU, 2026-07-30 run) and maintained
# centrally here so test files stay clean — re-measure with
# `pytest --durations=60` when adding heavy tests.
# ---------------------------------------------------------------------------
SLOW_TESTS = (
    "test_cli.py::test_cli_single_end_to_end",
    "test_convert.py::test_real_layout_forward_parity",
    "test_dropout.py::test_lm_interleaved_dropout_deterministic",
    "test_dropout.py::test_lm_pipeline_dropout_deterministic",
    "test_dropout.py::test_vit_pipeline_dropout_runs",
    "test_flash_attention.py::test_lm_flash_matches_dense_model",
    "test_grad_stats.py::",
    "test_lm_checkpoint.py::test_lm_restore_onto_different_mesh",
    "test_lm_checkpoint.py::test_lm_resume_matches_uninterrupted",
    "test_lm_pipeline.py::test_lm_pipeline_1f1b_matches_gpipe",
    "test_lm_pipeline.py::test_lm_pipeline_interleaved_1f1b",
    "test_lm_pipeline.py::test_lm_pipeline_checkpoint_interop",
    "test_lm_pipeline.py::test_lm_pipeline_flash_attention",
    "test_lm_pipeline.py::test_lm_pipeline_interleaved_checkpoint_interop",
    "test_lm_pipeline.py::test_lm_pipeline_interleaved_matches_single",
    "test_lm_pipeline.py::test_lm_pipeline_matches_single_dense",
    "test_lm_pipeline.py::test_lm_pipeline_moe_composition",
    "test_lm_pipeline.py::test_lm_pipeline_with_sequence_parallel_attention",
    "test_lm_pipeline.py::test_lm_pipeline_zb_matches_gpipe_and_1f1b",
    "test_vit.py::test_pipeline_zb_matches_gpipe_and_1f1b",
    "test_misc.py::TestGraftEntry::",
    "test_multihost.py::",
    "test_observability.py::test_train_lm_corpus_eval_writes_val_metrics",
    "test_observability.py::test_train_vit_writes_metric_csvs",
    "test_parallel.py::test_1f1b_matches_gpipe",
    "test_parallel.py::test_dp_matches_single",
    "test_parallel.py::test_pipeline_matches_sequential",
    "test_parallel.py::test_pipeline_remat_matches_no_remat",
    "test_parallel.py::test_strategies_learn",
    "test_pipeline_deep.py::",
    "test_preemption.py::test_sigterm_mid_training_checkpoints_and_resumes",
    "test_serve.py::test_engine_matches_sequential_decode",
    "test_serve.py::test_engine_matches_sequential_variants",
    "test_serve.py::test_shed_under_pressure_e2e",
    "test_serve_prefix.py::test_shared_prefix_bit_identical",
    "test_serve_prefix.py::test_int8_prefix_reuse_within_tolerance",
    "test_serve_prefix.py::test_chunked_prefill_interleaves_decode",
    "test_serve_prefix.py::test_serve_bench_scenario_cli",
    "test_trainer.py::test_resume_from_snapshot",
    "test_trainer.py::test_trainer_end_to_end",
    "test_transformer.py::TestLearning::test_remat_policy_invariance",
    "test_transformer.py::TestStrategyEquivalence::test_fsdp_matches_unsharded",
    "test_transformer.py::TestStrategyEquivalence::test_moe_ep_matches_single",
    "test_transformer.py::TestStrategyEquivalence::test_tp_sp_matches_single",
    "test_vit.py::test_pipeline_1f1b_matches_gpipe",
    "test_vit.py::test_pipeline_interleaved_matches_single",
    "test_vit.py::test_pipeline_interleaved_1f1b",
)


def pytest_collection_modifyitems(config, items):
    for item in items:
        if any(pat in item.nodeid for pat in SLOW_TESTS):
            item.add_marker(pytest.mark.slow)


@pytest.fixture(scope="session")
def tiny_model_cfg():
    """A miniature DenseNet for fast CPU tests (same code path as densenet121)."""
    from ddl_tpu.config import ModelConfig

    return ModelConfig(
        growth_rate=4,
        block_config=(2, 2),
        num_init_features=8,
        bn_size=2,
        num_classes=5,
        split_blocks=(1,),
        compute_dtype="float32",
        remat=False,
    )
