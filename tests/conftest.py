"""Test configuration: force an 8-device virtual CPU mesh.

Multi-chip hardware is not available in CI; all sharding logic is exercised on
``--xla_force_host_platform_device_count=8`` CPU devices (SURVEY.md §4's
"distributed without a cluster" strategy, re-imagined for JAX). Must run
before jax is imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# Cost-based placement needs no kill-switch here: on the CPU-only backend
# the suite runs on, the estimator charges no sync floor
# (plan/cost.py _cpu_only_backend), so mini-scale fixtures stay
# device-placed. Placement behavior is covered by tests/test_cost.py via
# explicit conf keys; an SRT_COST in the environment (the CI
# no-cost-placement matrix entry) still wins.

# Acceptance hook: SRT_STAGE_FUSION=0 flips the stage-fusion default off
# for a whole test run, verifying every suite still passes with the
# unfused plan shape (spark.rapids.sql.stageFusion.enabled=false).
if os.environ.get("SRT_STAGE_FUSION") == "0":
    from spark_rapids_tpu import config as _C  # noqa: E402
    _C.STAGE_FUSION_ENABLED.default = False

# SRT_PIPELINE=0 is additionally honored dynamically by
# parallel/pipeline.py (params_of) — every suite must pass with the
# serial dispatch path. SRT_PIPELINE_PREFETCH overrides the default
# prefetch depth (the CI matrix runs prefetchPartitions=1 vs default).
if os.environ.get("SRT_PIPELINE_PREFETCH"):
    from spark_rapids_tpu import config as _C2  # noqa: E402
    _C2.PIPELINE_PREFETCH_PARTITIONS.default = int(
        os.environ["SRT_PIPELINE_PREFETCH"])


@pytest.fixture
def rng():
    return np.random.default_rng(42)


def _map_count():
    try:
        with open("/proc/self/maps", "rb") as f:
            return sum(1 for _ in f)
    except OSError:       # non-Linux: no map table, no ceiling to dodge
        return 0


@pytest.fixture(autouse=True)
def _jit_map_pressure_relief():
    """Shed compiled executables before the kernel's mmap ceiling.

    A live XLA CPU executable for a real query kernel holds ~80 mmap
    regions and jax keeps every compiled program of the process alive,
    so a full single-process suite run accumulates memory maps
    monotonically; once the process crosses the kernel's
    vm.max_map_count ceiling (65530 by default) the next compile's mmap
    fails and XLA SIGSEGVs — the run dies at whatever test happens to
    compile there. Relief is tiered: first evict the OLDEST half of the
    engine's kernel cache (cold one-off kernels from earlier files; the
    current file's hot set survives, so there is no recompile storm),
    and only if the map table is still critical drop every jax cache
    (kernels recompile transparently — slow, but alive)."""
    yield
    import gc
    if _map_count() > 44000:
        from spark_rapids_tpu.ops import kernel_cache as kc
        cache = kc.cache()
        bound = cache.max_entries
        cache.configure(max(bound // 2, 64))
        cache.configure(bound)
        gc.collect()
        if _map_count() > 52000:
            import jax
            jax.clear_caches()
            gc.collect()


@pytest.fixture(autouse=True)
def _fault_state_isolation():
    """Snapshot + restore the process-global fault registry and recovery
    counters around EVERY test: a chaos test that arms a schedule (via
    faults.configure or a session conf collect) can no longer bleed an
    armed schedule or counter state into later tests, and an env-armed
    schedule (SRT_FAULTS) survives each test with exactly the state it
    entered with. The degraded batch target resets too — it is process
    state the OOM shrink rung leaks by design."""
    from spark_rapids_tpu import faults
    from spark_rapids_tpu.memory import oom
    state = faults.snapshot()
    yield
    faults.restore(state)
    oom.reset_degradation()


@pytest.fixture(autouse=True)
def _trace_ring_isolation():
    """Drop recorded flight-recorder events after every test so a traced
    test can never leak its ring contents (or query-id attribution) into
    a later test's assertions. Configuration (e.g. an env-armed
    SRT_TRACE=1 run) is left as-is — only the rings clear."""
    yield
    from spark_rapids_tpu import monitoring
    monitoring.reset()


@pytest.fixture(autouse=True)
def _telemetry_isolation():
    """Clear the live-telemetry registry and event-log routing after
    every test: a metrics-enabled test must never leak counter values,
    fleet payloads, or a configured event-log directory into a later
    test's scrape/record assertions. The enabled flag itself is left
    as-is so an env-armed SRT_METRICS=1 matrix run (whole-suite
    acceptance) keeps recording test to test — only the values clear."""
    yield
    from spark_rapids_tpu.monitoring import history, telemetry
    telemetry.reset()
    history.set_dir("")


@pytest.fixture(autouse=True)
def _cost_calibration_isolation():
    """Reset the cost model's self-calibration state after every test: a
    traced collect feeds observed sync/throughput numbers into
    process-global effective constants (plan/cost.py observe_query),
    which must never skew a later test's placement assertions."""
    yield
    from spark_rapids_tpu.plan import cost
    cost.reset_calibration()
