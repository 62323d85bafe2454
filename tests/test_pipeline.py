"""Pipelined partition executor (ISSUE 4): bit-identity vs the serial
path, deterministic ordering, recovery parity, and the overlap counters.

The contract under test: ``spark.rapids.sql.pipeline.*`` may only change
WHEN host work happens, never WHAT is computed — results (including
partition order) are bit-identical to the serial dispatch for every
prefetch depth, under seeded fault schedules, and with the watchdog
armed; ``SRT_PIPELINE=0`` / ``pipeline.enabled=false`` restore the
serial path exactly (no pipeline metrics entry, no threads).
"""

import os
import threading

import pytest

from spark_rapids_tpu import faults
from spark_rapids_tpu.api.dataframe import TpuSession
from spark_rapids_tpu.benchmarks import tpch
from spark_rapids_tpu.parallel import pipeline as PL

QUERIES = ["q1", "q3", "q5"]

# Under the serial CI matrix entry the overlap machinery is (correctly)
# inert; only the counter-presence assertions are meaningless then —
# bit-identity and recovery tests run in both modes.
requires_pipeline = pytest.mark.skipif(
    os.environ.get("SRT_PIPELINE", "") == "0",
    reason="pipeline disabled via SRT_PIPELINE=0")


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("tpch_pipeline"))
    tpch.generate(d, scale=0.003, files_per_table=3, seed=7)
    return d


def _session(chaos: str = "", pipeline: bool = True,
             prefetch: int = 2, **extra):
    s = TpuSession()
    s.set("spark.rapids.sql.variableFloatAgg.enabled", True)
    s.set("spark.rapids.sql.pipeline.enabled", pipeline)
    s.set("spark.rapids.sql.pipeline.prefetchPartitions", prefetch)
    s.set("spark.rapids.sql.test.faults", chaos)
    s.set("spark.rapids.sql.test.faults.seed", 7)
    s.set("spark.rapids.sql.retry.backoffMs", 1)
    if chaos:
        # The device scan cache would serve decoded units and skip the
        # host decode (and with it the ``scan`` fault site) entirely.
        s.set("spark.rapids.sql.format.scanCache.maxBytes", 0)
    for k, v in extra.items():
        s.set(k, v)
    return s


@pytest.fixture(scope="module")
def baselines(data_dir):
    """Serial-path device results (the bit-identity oracle)."""
    return {qn: tpch.QUERIES[qn](_session(pipeline=False), data_dir)
            .collect() for qn in QUERIES}


# ---------------------------------------------------------------------------
# Bit-identity + deterministic ordering
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("prefetch", [1, 2, 8])
@pytest.mark.parametrize("qname", QUERIES)
def test_bit_identical_vs_serial(qname, prefetch, baselines, data_dir):
    df = tpch.QUERIES[qname](_session(prefetch=prefetch), data_dir)
    got = df.collect()
    assert got == baselines[qname], (
        f"{qname} @ prefetchPartitions={prefetch} diverged from serial")


@pytest.mark.parametrize("prefetch", [1, 2, 8])
def test_deterministic_partition_ordering(prefetch, data_dir):
    """A bare multi-partition scan+filter (no agg/sort to mask ordering):
    collect order must equal the serial partition-order concatenation."""
    import glob
    from spark_rapids_tpu.plan.logical import col
    paths = sorted(glob.glob(f"{data_dir}/lineitem/*.parquet"))
    want = None
    for pipeline in (False, True):
        s = _session(pipeline=pipeline, prefetch=prefetch)
        df = s.read.parquet(*paths) \
            .filter(col("l_quantity") < 10) \
            .select("l_orderkey", "l_linenumber", "l_quantity")
        rows = df.collect()
        if want is None:
            want = rows
        else:
            assert rows == want, (
                f"ordering diverged at prefetchPartitions={prefetch}")
    assert want, "scan returned no rows — fixture too small"


# ---------------------------------------------------------------------------
# Serial escape hatches
# ---------------------------------------------------------------------------

def test_conf_off_restores_serial(data_dir, baselines):
    df = tpch.QUERIES["q1"](_session(pipeline=False), data_dir)
    got = df.collect()
    assert got == baselines["q1"]
    assert "Pipeline@query" not in df.metrics(), \
        "serial path must not open a pipeline"


def test_env_srt_pipeline_restores_serial(data_dir, baselines,
                                          monkeypatch):
    monkeypatch.setenv("SRT_PIPELINE", "0")
    df = tpch.QUERIES["q1"](_session(), data_dir)
    got = df.collect()
    assert got == baselines["q1"]
    assert "Pipeline@query" not in df.metrics(), \
        "SRT_PIPELINE=0 must not open a pipeline"


# ---------------------------------------------------------------------------
# Counters
# ---------------------------------------------------------------------------

@requires_pipeline
def test_overlap_counters_flow(data_dir, baselines):
    before = PL.counters().get("prefetchedPartitions", 0)
    df = tpch.QUERIES["q1"](_session(), data_dir)
    assert df.collect() == baselines["q1"]
    m = df.metrics().get("Pipeline@query")
    assert m is not None, df.metrics().keys()
    assert m.get("hostPrefetchMs", 0) > 0, m
    assert m.get("prefetchedPartitions", 0) >= 1, m
    assert 0 <= m.get("overlapRatio", -1) <= 1, m
    g = PL.counters()
    assert g.get("prefetchedPartitions", 0) > before
    assert "overlapRatio" in g


@requires_pipeline
def test_stall_count_is_published_whatever_the_load(data_dir):
    """``pipelineStalls`` is in the query's Pipeline entry whether or
    not a consume found its prefetch unfinished (the host's load decides
    that), so the entry has one shape on every run."""
    for _ in range(2):
        m = tpch.QUERIES["q6"](_session(), data_dir)
        m.collect()
        entry = m.metrics()["Pipeline@query"]
        assert entry.get("pipelineStalls", -1) >= 0, entry


@requires_pipeline
def test_concurrent_stage_materialization(data_dir):
    """Shuffled join (auto-broadcast off): the build- and probe-side
    exchanges are independent stages and materialize concurrently."""
    serial = tpch.QUERIES["q3"](_session(
        pipeline=False,
        **{"spark.rapids.sql.autoBroadcastJoinThreshold": -1}),
        data_dir).collect()
    df = tpch.QUERIES["q3"](_session(
        **{"spark.rapids.sql.autoBroadcastJoinThreshold": -1}), data_dir)
    got = df.collect()
    assert got == serial
    m = df.metrics().get("Pipeline@query")
    assert m is not None and m.get("concurrentStages", 0) >= 2, m


# ---------------------------------------------------------------------------
# Recovery parity: faults on prefetch threads re-raise at the ordered
# consumption point; the demotion ladder is unchanged
# ---------------------------------------------------------------------------

SCHEDULES = {
    "mixed": "transient@upload:1,oom@kernel:1,oom@upload:1",
    "scan-transient": "transient@scan:1,oom@concat:1",
}


@pytest.mark.parametrize("schedule", sorted(SCHEDULES))
@pytest.mark.parametrize("qname", QUERIES)
def test_bit_identical_under_faults(qname, schedule, baselines, data_dir):
    faults.reset_counters()
    df = tpch.QUERIES[qname](_session(SCHEDULES[schedule]), data_dir)
    got = df.collect()
    c = faults.counters()
    assert c.get("faultsInjected", 0) > 0, c
    assert got == baselines[qname], (
        f"{qname} under {schedule!r} diverged with the pipeline on")


def test_prefetch_fault_reraised_at_consumption(data_dir, baselines):
    """A transient raised on a PREFETCH thread surfaces at the ordered
    consumption point and recovers through the normal retry ladder."""
    faults.reset_counters()
    df = tpch.QUERIES["q1"](_session("transient@scan:1"), data_dir)
    got = df.collect()
    c = faults.counters()
    assert got == baselines["q1"]
    assert c.get("faultsInjected.transient@scan", 0) == 1, c
    assert c.get("retriesAttempted", 0) >= 1, c


def test_stall_on_prefetch_killed_by_watchdog(data_dir, baselines):
    """stall@scan hangs a prefetch thread; the watchdog kills the
    consuming attempt, the kill cancels the stalled prefetch, and the
    partition retry recomputes inline — bit-identical."""
    faults.reset_counters()
    s = _session("stall@scan:1")
    s.set("spark.rapids.sql.watchdog.enabled", True)
    s.set("spark.rapids.sql.watchdog.taskTimeoutMs", 1500)
    s.set("spark.rapids.sql.watchdog.maxAttempts", 3)
    got = tpch.QUERIES["q1"](s, data_dir).collect()
    c = faults.counters()
    assert got == baselines["q1"]
    assert c.get("watchdogKills", 0) >= 1, c
    assert c.get("partitionRetries", 0) >= 1, c


def test_stall_on_prefetch_without_watchdog_is_bounded(
        data_dir, baselines, monkeypatch):
    """Safety net: no watchdog armed, a stalled prefetch unwinds on its
    bounded timeout as DEADLINE_EXCEEDED -> transient retry."""
    monkeypatch.setattr(faults, "STALL_TIMEOUT_S", 0.2)
    faults.reset_counters()
    got = tpch.QUERIES["q1"](_session("stall@scan:1"), data_dir).collect()
    c = faults.counters()
    assert got == baselines["q1"]
    assert c.get("retriesAttempted", 0) >= 1, c


# ---------------------------------------------------------------------------
# No thread leaks
# ---------------------------------------------------------------------------

def test_no_lingering_prefetch_threads(data_dir):
    tpch.QUERIES["q1"](_session(), data_dir).collect()
    import time
    deadline = time.time() + 5
    while time.time() < deadline:
        alive = [t.name for t in threading.enumerate()
                 if t.name.startswith("srt-prefetch")
                 or t.name.startswith("srt-stage")]
        if not alive:
            return
        time.sleep(0.05)
    assert not alive, f"pipeline threads leaked: {alive}"


# ---------------------------------------------------------------------------
# The ordered consumer's poll (PERF.md, PR 24: one collect in ~1,850 raised
# a spurious TimeoutError on the parquet cell)
# ---------------------------------------------------------------------------

@requires_pipeline
def test_poll_timeout_racing_a_finished_prefetch(data_dir, monkeypatch):
    """The interleaving forced: every poll of a prefetch future gives up
    only once the future is done. The consumer has to take the task's
    result then, not hand the poll's own TimeoutError up as the task's."""
    import concurrent.futures
    import glob
    from spark_rapids_tpu.plan.logical import col
    paths = sorted(glob.glob(f"{data_dir}/lineitem/*.parquet"))

    def rows():
        s = _session(**{"spark.rapids.sql.format.scanCache.maxBytes": 0})
        return s.read.parquet(*paths).filter(col("l_quantity") < 10) \
            .select("l_orderkey", "l_linenumber").collect()

    want = rows()
    real = concurrent.futures.Future.result
    held_back = []

    def late_timeout(self, timeout=None):
        if timeout is None:
            return real(self)
        concurrent.futures.wait([self])
        held_back.append(self)
        raise concurrent.futures.TimeoutError()

    monkeypatch.setattr(concurrent.futures.Future, "result", late_timeout)
    got = rows()
    assert held_back, "no prefetch was polled: the pipeline did not run"
    assert got == want and want


@requires_pipeline
def test_prefetch_task_raising_timeout_error_surfaces_it():
    """A TimeoutError that the TASK raised is the task's, and goes up."""
    from spark_rapids_tpu.config import TpuConf
    from spark_rapids_tpu.ops.base import ExecContext

    class Source:
        def prefetch_host(self, ctx, partition):
            raise TimeoutError(f"partition {partition} timed out")

    ctx = ExecContext(TpuConf({}))
    pipe = PL.PartitionPipeline(ctx, Source(), 2, PL.params_of(ctx.conf))
    try:
        with pytest.raises(TimeoutError, match="partition 0 timed out"):
            pipe.consume(0, lambda: "never")
    finally:
        pipe.close()
