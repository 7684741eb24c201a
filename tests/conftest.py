"""Test bootstrap: simulate an 8-device TPU slice with fake CPU devices.

The reference simulated a multi-node cluster by spawning N OS processes
over gloo/TCP (pipegoose/testing/utils.py:20-41). On TPU the same
coverage comes from XLA's fake-device flag: one process, 8 CPU devices,
exercising the *real* jit/shard_map code paths (SURVEY.md §4).

Must run before the first backend touch anywhere in the test session.
"""
import os

from pipegoose_tpu.testing.fake_cluster import set_fake_device_flags

# operator-set XLA_FLAGS win (override=False): the conftest provides the
# 8-device default, not a mandate
set_fake_device_flags(8, override=False)
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402
import pytest  # noqa: E402

# tests always run on fake CPU devices
jax.config.update("jax_platforms", "cpu")

# Persistent XLA compilation cache for the SERVING tests (and the
# serving example subprocesses — tests/test_examples.py exports the
# same dir): the suite builds hundreds of ServingEngine instances over
# a handful of tiny BloomConfigs, and each instance's jit programs
# lower to HLO already seen — content-keyed cache hits replace the
# recompiles (measured 3.3x on tests/serving/test_kv_tier.py, cold).
# Scoped to tests/serving/: an earlier jaxlib segfaulted reading
# TRAINER-style executables (hybrid train steps) back, and the trainer
# tests have not been re-run with the cache on since; serving programs
# are jit-pure (scripts/lint_jit_safety.py) and round-trip cleanly —
# the full serving directory passed with in-process reloads. The
# thresholds drop to 0 because these programs each compile in
# milliseconds — the default 1s floor would cache nothing.
# JAX's own variable where set; else the checkout's fixed path, the
# same one chip_smoke.py and benchmark/run.py use.
JAX_CACHE_DIR = os.environ.get(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 ".jax_cache"))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


@pytest.fixture(autouse=True)
def _scoped_compilation_cache(request):
    """Enable the persistent cache for tests/serving/ only. jax
    memoizes is_cache_used() once, so flipping the dir needs
    reset_cache() too — serving tests are contiguous in collection
    order, so this fires twice per session, not per test."""
    from jax._src import compilation_cache as _cc

    want = request.node.nodeid.startswith("tests/serving/")
    have = jax.config.jax_compilation_cache_dir is not None
    if want != have:
        jax.config.update("jax_compilation_cache_dir",
                          JAX_CACHE_DIR if want else None)
        _cc.reset_cache()
    yield


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 fake CPU devices, got {len(devs)}"
    return devs


@pytest.fixture
def annotations(monkeypatch):
    """``jax.profiler.TraceAnnotation`` replaced by a recorder: the list
    of ("enter" | "exit", name) in order. (No profiler session in
    tier-1: under six workers a real one would be unsteady.)"""
    log = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name))
            return self

        def __exit__(self, *exc):
            log.append(("exit", self.name))
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    return log


# --- slow tier ------------------------------------------------------------
#
# Tier-1 is ``-m 'not slow'`` on six workers (``--dist loadfile``). What
# is left out is listed here: node id -> the seconds its setup and call
# took when the list was last measured in that same form (``-m slow -n 6
# --dist loadfile --durations=0``, PR 51). The rule: an entry that passes
# in under 12 s comes out of the list. Every entry keeps a cheaper
# sibling of the same subsystem in tier-1.
SLOW_TESTS = {
    "tests/nn/sequence_parallel/test_ring_attention.py::test_ring_dense_gqa_matches_repeated": 69.5,
    # FAILS when run (PR 51: a measured-over-predicted ratio of 0.31 where
    # it asks 0.4, decided by the host's load): guards nothing in either tier
    "tests/planner/test_planner.py::test_calibration_closes_loop_on_bench_hybrid_variants": 63.5,
    "tests/nn/sequence_parallel/test_ring_attention.py::test_ring_flash_matches_ring": 57.7,
    "tests/nn/sequence_parallel/test_ring_attention.py::test_ring_flash_gqa_matches_repeated": 51.7,
    "tests/test_examples.py::test_example_runs[plan_parallelism_demo.py]": 38.8,
    "tests/test_examples.py::test_example_runs[comm_overlap_demo.py]": 38.2,
    "tests/nn/tensor_parallel/test_overlap.py::test_column_row_overlap_forward_and_backward_parity[4]": 36.2,
    "tests/models/test_albert.py::test_tp_forward_and_grads_match": 36.1,
    "tests/models/test_mixtral.py::test_tp_grads_consistent_across_tensor_ranks": 31.9,
    "tests/test_comm_hybrid.py::test_int8_grad_comm_short_run_tracks_fp32": 28.6,
    "tests/ops/test_fused_ce.py::test_pp_heads_fused_ce_match_default": 27.1,
    "tests/models/test_bloom.py::test_tp_grads_match_single_device": 26.9,
    "tests/test_examples.py::test_example_runs[long_context.py]": 25.0,
    "tests/test_examples.py::test_example_runs[elastic_training_demo.py]": 23.2,
    "tests/test_examples.py::test_example_runs[flight_recorder_demo.py]": 22.3,
    "tests/test_examples.py::test_example_runs[quantized_serving_demo.py]": 20.9,
    "tests/testing/test_chaos.py::test_same_seed_same_injections_same_loss_trajectory": 20.5,
    "tests/nn/pipeline_parallel/test_1f1b.py::test_training_matches_gpipe": 19.1,
    "tests/distributed/test_compressed.py::test_compressed_all_reduce_mean_shapes_and_values": 18.6,
    "tests/models/test_mixtral.py::test_sliding_window_flash_matches_dense": 17.9,
    "tests/trainer/test_recovery.py::test_auto_recovery_restores_and_continues": 17.5,
    "tests/ops/test_fused_ce.py::test_llama_and_mixtral_fused_ce_match_default": 17.2,
    "tests/test_4d_parallel.py::test_4d_training_matches_single_device": 17.1,
    "tests/telemetry/test_health.py::test_sharded_health_matches_single_device_reference": 17.1,
    "tests/test_examples.py::test_example_runs[moe_training.py]": 16.6,
    "tests/test_examples.py::test_example_runs[telemetry_demo.py]": 16.5,
    "tests/models/test_bloom_sp.py::test_pp_sp_training_matches_single_device": 16.3,
    "tests/test_examples.py::test_example_runs[encoder_mlm.py]": 15.6,
    "tests/nn/pipeline_parallel/test_uneven_stages.py::test_uneven_grads_match_dense": 15.5,
    "tests/ops/test_fused_ce.py::test_bloom_loss_fused_matches_default": 15.5,
    "tests/test_comm_hybrid.py::test_plain_dp_grad_comm_matches_zero_path": 15.4,
    "tests/optim/test_diloco_4d.py::test_inner_steps_match_standalone_workers": 15.4,
    "tests/models/test_llama.py::test_1f1b_matches_dense_tied_and_untied": 15.1,
    "tests/test_comm_hybrid.py::test_overlap_hybrid_matches_monolithic": 15.1,
    "tests/models/test_mixtral_sp.py::test_sp_tp_training_matches_single_device": 14.9,
    "tests/models/test_mixtral_sp.py::test_pp_sp_training_matches_dense": 14.8,
    "tests/test_comm_hybrid.py::test_quantized_full_run_loss_parity[int8]": 14.2,
    "tests/models/test_albert_pp_sp.py::test_sp_loss_and_grads_match_dense": 14.1,
    "tests/nn/pipeline_parallel/test_1f1b.py::test_activation_memory_bound": 13.7,
    "tests/ops/test_flash_attention.py::test_bloom_flash_padded_matches_plain": 13.6,
    "tests/nn/tensor_parallel/test_layers.py::test_chunked_ce_matches_plain": 13.4,
    "tests/models/test_bloom_moe.py::test_moe_training_matches_single_device": 13.3,
    "tests/test_comm_hybrid.py::test_quantized_full_run_loss_parity[bf16]": 13.3,
    "tests/models/test_llama.py::test_upcycle_to_moe_matches_dense": 12.8,
    "tests/test_4d_parallel.py::test_pp_m4_aux_matches_microbatched_dense_reference": 12.6,
    "tests/testing/test_chaos.py::test_fit_raising_does_not_leak_armed_fault": 12.5,
    "tests/models/test_mixtral.py::test_sliding_window_generate_consistent": 12.4,
    "tests/models/test_albert.py::test_dp_training_matches_single_device": 12.2,
}


@pytest.fixture
def empty_iterations():
    """``empty_iterations(fn)``: a call of ``fn`` in iterations of an
    empty loop, the median over 15 batches of n calls, each timed back
    to back with n empty iterations, so whatever slows the machine (five
    other workers) meets both. The disabled-cost guards bound it by 300:
    5 µs where an iteration takes 17 ns, as on the machine the bound was
    set on."""
    import time

    def measure(fn, n=2000, rounds=15):
        ratios = []
        for _ in range(rounds):
            t0 = time.perf_counter()
            for _ in range(n):
                fn()
            t1 = time.perf_counter()
            for _ in range(n):
                pass
            ratios.append((t1 - t0) / (time.perf_counter() - t1))
        return sorted(ratios)[len(ratios) // 2]

    return measure


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.nodeid in SLOW_TESTS:
            item.add_marker(pytest.mark.slow)
