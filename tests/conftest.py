"""Test harness: run everything on 8 virtual CPU devices.

Mirrors the reference's cluster-free testing strategy (SURVEY §4): their
multi-rank tiers run single-node MPI with 2/4/8 processes; here the
substitute is a host-platform device count of 8, giving real multi-device
meshes (pp/tp/dp up to 8-way) without TPU hardware.
"""

import os

# Force, don't default: the test tier always runs on 8 virtual CPU devices,
# whatever accelerator the machine has. Set the config as well as the env
# vars, in case jax was imported before this file.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# XLA:CPU runs f32 matmuls at bf16 precision on AVX512-BF16 hosts; parity
# tests compare two differently-fused programs, so pin exact f32 matmuls.
jax.config.update("jax_default_matmul_precision", "highest")

# Persistent compilation cache for the CPU suite, opt-in
# (SMP_TEST_COMPILE_CACHE=1): the suite is XLA:CPU compile-bound (~1-2
# program compiles per test), so a warm cache amortizes most of its wall
# time. The tiering below (-m "not slow" is the CI tier, the full suite the
# nightly tier) is what keeps the default run inside its budget.
if os.environ.get("SMP_TEST_COMPILE_CACHE", "0") == "1":
    _cache_dir = os.path.join(os.path.dirname(__file__), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", _cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: heavy multi-compile tests (deselect with -m 'not slow')"
    )
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection tests (SIGTERM/bus faults via SMP_CHAOS); "
        "run with -m chaos",
    )


# Known-heavy tests (>=10s single-core, dominated by XLA pipeline compiles),
# centrally marked so `pytest -m "not slow"` gives a fast unit tier (the
# reference's tier 1 — SURVEY §4) while the full suite stays unchanged.
_SLOW_TESTS = (
    "test_memory_systems.py::TestActivationCheckpointing::test_pipeline_remat_parity",
    "test_memory_systems.py::TestActivationCheckpointing::test_loss_parity_with_remat",
    "test_memory_systems.py::TestShardedDataParallelism::test_zero2d_loss_parity",
    "test_memory_systems.py::TestOptimizerStateSharding::test_zero1_moments_sharded",
    "test_partition_wiring.py::TestCostDrivenBoundaries",
    "test_partition_wiring.py::TestManualPins",
    "test_partition_wiring.py::TestMeasuredLayerCosts",
    "test_checkpoint.py::TestShardedCheckpoint",
    "test_huggingface.py::TestEndToEnd",
    "test_optimizer.py::test_aot_executable_reused",
    "test_pipeline.py::test_pp2_with_more_microbatches",
    "test_pipeline.py::test_pp_matches_single_stage",
    "test_pipeline.py::test_pp_non_divisible_layers_pad",
    "test_context_parallel.py::TestCpEndToEnd",
    "test_transformer.py::TestStepIntegration",
    "test_transformer.py::TestCrossAttention",
    "test_transformer.py::TestLMHeadTPParity",
    "test_pipeline_1f1b.py::TestInterleavedParity",
    # Virtual-stage (pp*v >= 4) parity + compiled-HLO cases: each is a
    # multi-pipeline-compile end-to-end run, tier 2 by nature. (Tier-1
    # still guards v=1 schedule identity via the pure-numpy
    # test_v1_reduces_to_plain_schedule and runs the v=2 end-to-end
    # smoke + occupancy acceptance in TestVirtualStages.)
    "test_pipeline_1f1b.py::TestVirtualParity",
    "test_pipeline_1f1b.py::TestVirtualHLOGuard",
    "test_step.py::test_loss_decreases_transformer",
    "test_checkpoint.py::TestSaveLoad::test_partial_roundtrip",
    # Re-tiered from --durations with the compile cache off (each >= ~15s
    # single-core; all are end-to-end training loops, tier 2 by nature).
    "test_memory_systems.py::TestFp16LossScaling::test_fp16_training_runs_and_matches",
    "test_memory_systems.py::TestOptimizerStateSharding::test_zero1_loss_parity",
    "test_memory_systems.py::TestActivationOffload::test_offload_config_runs",
    "test_config_honored.py::TestManualPartition::test_partition_file_save_and_load",
    "test_config_honored.py::TestManualPartition::test_default_partition_with_pins",
    "test_checkpoint.py::TestSaveCheckpointDir::test_deferred_application",
    "test_checkpoint.py::TestSaveCheckpointDir::test_full_checkpoint",
    "test_checkpoint.py::TestSaveCheckpointDir::test_roundtrip_with_newest",
    "test_context_parallel.py::TestCpRealModelFeatures::test_lmhead_mask_dropout_runs_ring_with_ppermute",
    "test_moe.py::TestExpertParallel::test_transformer_layer_moe_trains",
    "test_delayed_init.py::test_delayed_init_matches_eager_init_numerically",
    "test_huggingface.py::TestRoundTrip::test_vit_encoder_trains_under_smp_step",
    "test_multiprocess.py::test_two_process_control_plane_and_checkpoint",
    # Generation tier 2: HF-comparison and python-reference beam tests
    # compile many decode programs / loop full forwards per token.
    "test_generate.py::TestHFGreedyParity",
    "test_generate.py::TestHFBeamParity",
    "test_generate.py::TestBeamSearch::test_matches_python_reference",
    "test_generate.py::TestSeq2SeqGreedyParity",
    "test_generate.py::TestPaddedPrompts::test_hf_gpt2_left_padded_parity",
    "test_generate.py::TestDistributedParity::test_tp4_matches_single_device",
    # End-to-end loops that each measured >= ~15s single-core
    # (--durations, same rule as the block above); the fast tier must fit
    # the driver's time limit.
    "test_generate.py::TestBeamSearch::test_seq2seq_beam_runs_and_improves_score",
    "test_generate.py::TestBeamSearch::test_seq2seq_num_return_sequences",
    "test_generate.py::TestZooGreedyParity",
    "test_generate.py::TestDistributedParity::test_generate_after_pp_training",
    "test_generate.py::TestHalfPrecision::test_bf16_config_casts_decode_params",
    "test_attention_dispatch.py::test_block_size_config_resolution",
    "test_native.py::test_multiprocess_mesh[4]",
    "test_encoder_decoder.py::test_cross_attention_masked_by_encoder_padding",
    "test_encoder_decoder.py::test_forward_shapes_and_causality",
    "test_encoder_decoder.py::test_padding_mask_2d_normalized",
    "test_checkpoint.py::TestAsyncSave::test_async_snapshot_is_exact",
    "test_checkpoint.py::TestSaveCheckpointDir::test_retention_gc",
    "test_moe.py::TestAuxLossPlumbing::test_balance_improves_with_aux_under_dp",
    "test_pipeline_1f1b.py::TestMemory::test_interleaved_uses_less_temp_memory_than_simple",
    "test_optimizer.py::TestFusedOptimizerStep",
    "test_step.py::test_step_recompiles_after_reinit_same_shapes",
    "test_data.py::TestPrefetch::test_trains_through_step_engine",
    # Causal ring-attention parity: measured >= ~20s single-core (same
    # --durations rule as the blocks above).
    "test_context_parallel.py::TestCpAttentionParity::test_matches_full_attention[True-ring]",
    # Zero-bubble (ZB-H1) heavy multi-compile cases: the acceptance gate
    # (one ZB compile + the pp=1 baseline) stays in the fast tier in
    # test_pipeline_zero_bubble.py; the cross-executor parity matrix and
    # the HLO permute guard each pay 2-4 extra pipeline compiles.
    "test_pipeline_zero_bubble.py::TestZeroBubbleParity",
    "test_pipeline_zero_bubble.py::TestDefaultPathGuard::test_zb_keeps_pipeline_permutes",
    # ZeRO-3 heavy multi-compile cases: the acceptance gate (baseline +
    # zero3 compile, parity + census + golden in one test) and the
    # adamw moment-mirroring check stay fast in test_zero3.py; the
    # pp2 composition, GSPMD-fallback A/B, and elastic round trips each
    # pay 2+ extra end-to-end compiles.
    "test_zero3.py::TestZero3Composition",
    "test_zero3.py::TestZero3Elastic",
    # Recompute-planner heavy multi-compile cases: the census acceptance
    # gate (stash + full + pp=1 baseline at the canonical config) and the
    # committed stash golden stay fast in test_recompute.py; the
    # per-mode parity matrix and the auto-degradation executor runs each
    # pay 2-3 extra pipeline compiles.
    "test_recompute.py::TestStashParity",
    "test_recompute.py::TestAutoDegradation",
    # Serving heavy extra-compile cases: the composite end-to-end (one
    # engine, every behavioral claim) and the tp2 golden gate stay fast
    # in test_serving.py; the neutered-constraint detector e2e and the
    # exec-cache warm start each pay 2+ extra serving-program compiles.
    "test_serving.py::TestServingXray::test_detector_fires_on_replicated_pool",
    "test_serving.py::TestExecCacheWarmStart",
    # Overlapped-tp heavy multi-compile cases: the acceptance gate
    # (GSPMD baseline + ring compile, parity + census + golden in one
    # test) and the neutered-ring detector stay fast in
    # test_tp_overlap.py; the fused-kernel parity runs and the
    # pp2/indivisible-seq/health compositions each pay 2+ extra
    # end-to-end compiles.
    "test_tp_overlap.py::TestFusedParity",
    "test_tp_overlap.py::TestComposition",
    # Controller heavy extra-compile case: the policy/router units and
    # the one-engine composite (drain parity, zero-recompile adoption,
    # canary promote + chaos rollback) stay fast in test_controller.py;
    # the in-process burst autoscale end-to-end pays 3 engines' compiles
    # (static reference, replica0, the warm-started standby).
    "test_controller.py::TestAutoscaleEndToEnd",
    # Quant heavy multi-compile cases: the fp8 acceptance gate (bf16
    # baseline + fp8 compile, parity + census + golden in one test),
    # the upcast-detector e2e, and the int8-KV serving gate stay fast
    # in test_quant.py; the checkpoint/elastic round trip builds three
    # fp8 setups and the weight-only parity runs pay 2 engines' + many
    # generate-reference compiles.
    "test_quant.py::TestQuantCheckpoint",
    "test_quant.py::TestDecodeWeightsInt8",
)


def pytest_collection_modifyitems(config, items):
    for item in items:
        if any(key in item.nodeid for key in _SLOW_TESTS):
            item.add_marker(pytest.mark.slow)


@pytest.fixture(autouse=True)
def _reset_smp():
    yield
    import smdistributed_modelparallel_tpu as smp

    smp.reset()


@pytest.fixture
def fresh_tp_registry():
    """The tp registry as a fresh process's ``smp.init({})`` makes it (the
    state keeps its registry over reset and init); the session's own is put
    back afterwards."""
    import smdistributed_modelparallel_tpu as smp
    from smdistributed_modelparallel_tpu.backend.state import state

    kept, state.tp_registry = state.tp_registry, None
    smp.reset()
    smp.init({})
    yield state.tp_registry
    state.tp_registry = kept


# -- committed smp.xray golden fingerprints (tests/goldens/) ------------
# Shared by the HLO regression gates in test_pipeline_1f1b.py and
# test_pipeline_zero_bubble.py; regenerate with
# ``python tests/goldens/generate_hlo_fingerprints.py`` after an
# INTENDED program-structure change.


def golden_hlo_fingerprint(name):
    import json

    path = os.path.join(
        os.path.dirname(__file__), "goldens", "hlo_fingerprints.json"
    )
    with open(path, encoding="utf-8") as f:
        return json.load(f)["programs"][name]


def assert_matches_hlo_golden(audit, golden_name):
    """Semantic-fingerprint gate: config, per-axis collective census,
    replication findings, and remat fraction must diff clean against the
    committed golden (memory sizes / content hashes are excluded — they
    move with jaxlib versions; parallel structure only moves when the
    program does)."""
    from smdistributed_modelparallel_tpu.utils import hlo_audit

    changes = hlo_audit.diff(
        audit.fingerprint, golden_hlo_fingerprint(golden_name),
        fields=hlo_audit.SEMANTIC_FIELDS,
    )
    assert changes == [], (
        f"compiled program drifted from golden {golden_name!r}: {changes}"
    )
