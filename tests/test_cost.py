"""Cost-based host/device placement (ISSUE 7 tentpole, plan/cost.py).

Small inputs cannot amortize the per-dispatch device sync floor, so the
planner places whole maximal subtrees on the host engine when the
footer-stats cost estimate says the host wins — and must leave the
legacy all-device plan untouched behind every gate (conf off, SRT_COST,
test mode, armed faults, non-inprocess transport, no file scan).
"""

import os
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as papq
import pytest

from spark_rapids_tpu import config as C
from spark_rapids_tpu.api.dataframe import TpuSession
from spark_rapids_tpu.plan import cost as COST
from spark_rapids_tpu.plan.logical import agg_count, agg_sum, col


@pytest.fixture(scope="module")
def pq_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("cost_pq")
    rng = np.random.default_rng(11)
    n = 50_000
    papq.write_table(pa.table({
        "k": rng.integers(0, 64, n, dtype=np.int64),
        "v": rng.uniform(0, 1, n),
    }), os.path.join(d, "t.parquet"))
    return str(d)


def _scan_agg(session, pq_dir):
    return session.read.parquet(os.path.join(pq_dir, "t.parquet")) \
        .group_by("k").agg(agg_sum(col("v")).alias("s"))


def _session(**conf):
    s = TpuSession()
    s.set("spark.rapids.sql.variableFloatAgg.enabled", True)
    s.set("spark.rapids.sql.cost.enabled", True)
    # The suite runs on a CPU-only backend, where the estimator charges
    # no sync floor. These scenarios are about what placement DOES once
    # a floor makes the host cheaper, so they set one explicitly, large
    # enough that every fixture here host-places whatever the shipped
    # default is (an explicit key always wins).
    s.set("spark.rapids.sql.cost.deviceSyncFloorMs", 50.0)
    for k, v in conf.items():
        s.set(k, v)
    return s


class TestCostEnabled:
    def test_conf_key_wins_over_env(self, monkeypatch):
        monkeypatch.setenv("SRT_COST", "0")
        conf = C.TpuConf({"spark.rapids.sql.cost.enabled": True})
        assert COST.cost_enabled(conf) is True

    def test_env_overrides_default(self, monkeypatch):
        monkeypatch.setenv("SRT_COST", "0")
        assert COST.cost_enabled(C.TpuConf()) is False
        monkeypatch.setenv("SRT_COST", "1")
        assert COST.cost_enabled(C.TpuConf()) is True

    def test_default_on(self, monkeypatch):
        monkeypatch.delenv("SRT_COST", raising=False)
        assert COST.cost_enabled(C.TpuConf()) is True


class TestStaticPlacement:
    def test_tiny_scan_plans_host(self, pq_dir):
        """A tiny parquet aggregate cannot amortize the sync floor: the
        whole subtree host-places and explain carries the estimate."""
        s = _session()
        phys = _scan_agg(s, pq_dir)._physical()
        assert phys.cost_report.placements == 1
        assert not phys.root_on_device
        assert "cost model: host placement" in phys.explain()

    def test_large_scan_stays_device(self, pq_dir):
        """The SF1-lineitem analog: input over the host-bytes ceiling
        never host-places, whatever the model says."""
        s = _session(**{"spark.rapids.sql.cost.maxHostBytes": 1024})
        phys = _scan_agg(s, pq_dir)._physical()
        assert phys.cost_report.placements == 0
        assert phys.root_on_device

    def test_device_wins_when_syncs_are_free(self, pq_dir):
        """Calibration constants drive the decision: with a zero sync
        floor and a fast device the model keeps the device plan."""
        s = _session(**{
            "spark.rapids.sql.cost.deviceSyncFloorMs": 0.0,
            "spark.rapids.sql.cost.deviceThroughputGBps": 10_000.0,
        })
        phys = _scan_agg(s, pq_dir)._physical()
        assert phys.cost_report.placements == 0
        assert phys.root_on_device

    def test_disabled_by_conf(self, pq_dir):
        s = _session(**{"spark.rapids.sql.cost.enabled": False})
        phys = _scan_agg(s, pq_dir)._physical()
        assert phys.cost_report.skipped == "disabled"
        assert phys.root_on_device

    def test_gated_in_test_mode(self, pq_dir):
        s = _session(**{
            "spark.rapids.sql.test.enabled": True,
            "spark.rapids.sql.test.allowedNonTpu": "",
        })
        phys = _scan_agg(s, pq_dir)._physical()   # must not raise
        assert phys.cost_report.skipped is not None
        assert phys.root_on_device

    def test_gated_under_armed_faults(self, pq_dir):
        s = _session(**{"spark.rapids.sql.test.faults": ""})
        phys = _scan_agg(s, pq_dir)._physical()
        assert "fault schedule" in phys.cost_report.skipped

    def test_gated_on_non_inprocess_transport(self, pq_dir):
        s = _session(**{"spark.rapids.sql.shuffle.transport": "hostfile"})
        phys = _scan_agg(s, pq_dir)._physical()
        assert "transport" in phys.cost_report.skipped

    def test_gated_without_file_scan(self):
        import spark_rapids_tpu as srt
        s = _session()
        df = s.create_dataframe({"k": [1, 2], "v": [1.0, 2.0]},
                                [("k", srt.INT64), ("v", srt.FLOAT64)])
        phys = df.group_by("k").agg(
            agg_sum(col("v")).alias("s"))._physical()
        assert "no footer-stats" in phys.cost_report.skipped
        assert phys.root_on_device

    def test_results_identical_on_vs_off(self, pq_dir):
        from spark_rapids_tpu.benchmarks.compare import compare_results
        on = _scan_agg(_session(), pq_dir).collect()
        off = _scan_agg(_session(**{
            "spark.rapids.sql.cost.enabled": False}), pq_dir).collect()
        assert compare_results(sorted(on), sorted(off), sort=True)

    def test_cost_metrics_surface(self, pq_dir):
        df = _scan_agg(_session(), pq_dir)
        df.collect()
        m = df.metrics()
        assert m["Cost@query"]["placements"] == 1
        assert m["Cost@query"]["estSyncs"] > 0

    def test_explain_mode_renders_estimates(self, pq_dir):
        s = _session(**{"spark.rapids.sql.cost.explain": True})
        report = _scan_agg(s, pq_dir)._physical().explain()
        assert "Cost model:" in report
        assert "syncs" in report


class TestRepartShortCircuit:
    """ISSUE 7 satellite: an exchange whose total input is below the
    cost threshold short-circuits to a host repartition — zero device
    round trips — and stays competitive with pandas."""

    N = 8

    def _repart(self, session, pq_dir):
        from spark_rapids_tpu.plan.logical import lit_col, murmur3_hash
        df = session.read.parquet(os.path.join(pq_dir, "t.parquet"))
        shuffled = df.repartition(self.N, col("k"))
        n = lit_col(self.N)
        bucket = ((murmur3_hash(col("k")) % n) + n) % n
        return shuffled.group_by(bucket.alias("bucket")) \
            .agg(agg_count().alias("n")).order_by("bucket")

    def test_tiny_repartition_places_host(self, pq_dir):
        phys = self._repart(_session(), pq_dir)._physical()
        assert phys.cost_report.placements == 1
        assert not phys.root_on_device
        # The repartition's exchange runs the host split path — no
        # ShuffleExchange materializes on the device engine.
        rows = phys.collect()
        ctx = phys.last_ctx
        assert not any(k.startswith("shuffle:") and k.endswith(":dev")
                       for k in ctx.cache)
        assert len(rows) <= self.N

    def test_repart_not_slower_than_pandas(self, pq_dir):
        """Regression pin for the r5 repart loss (0.24x vs pandas): the
        short-circuited host repartition must hold >= 0.8x a pandas
        implementation doing the same work (hash, materialize the N
        buckets, count each), plus a fixed allowance for the query
        machinery (admission, plan walk, the query's own second hash
        pass) that a 3-line numpy script does not pay and that is noise
        at bench scale. Medians over repeated warm runs keep CI stable;
        a regression to the per-partition device round-trip path is an
        order of magnitude, not a margin."""
        import pandas as pd
        from spark_rapids_tpu.exprs import hash as mh

        df = self._repart(_session(), pq_dir)

        def engine_once():
            t0 = time.perf_counter()
            df.collect()
            return time.perf_counter() - t0

        def pandas_once():
            t0 = time.perf_counter()
            tbl = papq.read_table(os.path.join(pq_dir, "t.parquet"),
                                  columns=["k"]).to_pandas()
            vals = tbl.k.to_numpy(np.int64)
            h = mh.hash_long(np, vals, np.uint32(mh.DEFAULT_SEED)) \
                .astype(np.int32)
            bucket = ((h.astype(np.int64) % self.N) + self.N) % self.N
            order = np.argsort(bucket, kind="stable")
            splits = np.cumsum(
                np.bincount(bucket, minlength=self.N))[:-1]
            parts = np.split(vals[order], splits)
            pd.Series({p: len(a) for p, a in enumerate(parts)}) \
                .sort_index()
            return time.perf_counter() - t0

        engine_once(), pandas_once()          # warm both paths
        eng = sorted(engine_once() for _ in range(5))[2]
        pdt = sorted(pandas_once() for _ in range(5))[2]
        assert eng <= pdt / 0.8 + 0.075, \
            f"host-short-circuited repart {eng:.4f}s vs pandas {pdt:.4f}s"


@pytest.mark.parametrize("qname", [
    "q1", "q6", "q22", "q11", "q14", "q19",
    # The join-heavy pair is the expensive half of the sweep: tier-1
    # keeps the scan/agg coverage fast, the CI replan matrix entry
    # (no slow filter) runs the full set.
    pytest.param("q3", marks=pytest.mark.slow),
    pytest.param("q5", marks=pytest.mark.slow)])
def test_tpch_parity_cost_on_vs_off(qname, tmp_path_factory):
    """Dual-engine parity across the suite: cost-model-on results match
    cost-model-off through the standard oracle comparator."""
    from spark_rapids_tpu.benchmarks import tpch
    d = getattr(test_tpch_parity_cost_on_vs_off, "_dir", None)
    if d is None:
        d = str(tmp_path_factory.mktemp("cost_tpch"))
        # Same scale/layout as tests/test_tpch.py: the cost-off runs
        # then reuse the device kernels that suite already compiled
        # (structural kernel-cache fingerprints) instead of adding a
        # whole second set of XLA executables to the process.
        tpch.generate(d, scale=0.003, files_per_table=3, seed=7)
        test_tpch_parity_cost_on_vs_off._dir = d
    on = tpch.QUERIES[qname](_session(), d).collect()
    off = tpch.QUERIES[qname](_session(**{
        "spark.rapids.sql.cost.enabled": False}), d).collect()
    from spark_rapids_tpu.benchmarks.compare import compare_results
    assert compare_results(on, off, sort=True), qname


@pytest.mark.parametrize("qname", [
    "repart",
    # rollup+window q67 and the xbb pivot dominate the sweep's wall
    # clock; fast tier-1 keeps repart (the satellite's regression
    # shape), the CI replan matrix entry runs all three.
    pytest.param("q67", marks=pytest.mark.slow),
    pytest.param("xbb_q5", marks=pytest.mark.slow)])
def test_suites_parity_cost_on_vs_off(qname, tmp_path_factory):
    from spark_rapids_tpu.benchmarks import suites
    d = getattr(test_suites_parity_cost_on_vs_off, "_dir", None)
    if d is None:
        d = str(tmp_path_factory.mktemp("cost_suites"))
        # Mirrors tests/test_suites.py's datagen so the cost-off device
        # runs hit that suite's kernel-cache entries (see the TPC-H
        # parity note above).
        suites.generate(d, scale=0.01, files_per_table=2)
        test_suites_parity_cost_on_vs_off._dir = d
    on = suites.QUERIES[qname](_session(), d).collect()
    off = suites.QUERIES[qname](_session(**{
        "spark.rapids.sql.cost.enabled": False}), d).collect()
    from spark_rapids_tpu.benchmarks.compare import compare_results
    assert compare_results(on, off, sort=True), qname


class TestCalibration:
    """Cost-model self-calibration (ISSUE 11 satellite): observed sync
    floors / throughput EWMA into effective constants, clamped, with
    explicit conf keys always winning."""

    @pytest.fixture(autouse=True)
    def _fresh_calibration(self):
        from spark_rapids_tpu.plan import cost
        cost.reset_calibration()
        yield
        cost.reset_calibration()

    @pytest.fixture
    def as_on_a_chip(self, monkeypatch):
        """Calibration semantics are backend-independent; stand the
        CPU-only sync-floor zeroing down so the constants stay
        observable."""
        from spark_rapids_tpu.plan import cost
        monkeypatch.setattr(cost, "_cpu_only_backend", lambda: False)

    def _conf(self, **raw):
        from spark_rapids_tpu.config import TpuConf
        return TpuConf(raw)

    def test_cpu_backend_charges_no_floor_unless_set(self):
        """The one backend special case: on JAX_PLATFORMS=cpu a sync is
        a function return, so the default floor is not charged — an
        explicit key still is."""
        from spark_rapids_tpu.config import TpuConf
        from spark_rapids_tpu.plan import cost
        assert cost.effective_sync_floor_ms(TpuConf()) == 0.0
        assert cost.effective_sync_floor_ms(TpuConf(
            {"spark.rapids.sql.cost.deviceSyncFloorMs": 7.0})) == 7.0

    def test_observation_moves_effective_values(self, as_on_a_chip):
        from spark_rapids_tpu import config as C
        from spark_rapids_tpu.plan import cost
        conf = self._conf()
        base = float(C.COST_SYNC_FLOOR_MS.default)
        assert cost.effective_sync_floor_ms(conf) == base
        cost.observe(sync_floor_ms=base / 2, device_gbps=4.0)
        assert cost.effective_sync_floor_ms(conf) == base / 2
        assert cost.effective_device_gbps(conf) == 4.0
        # EWMA: a second observation blends, not replaces.
        cost.observe(sync_floor_ms=base, alpha=0.5)
        eff = cost.effective_sync_floor_ms(conf)
        assert base / 2 < eff < base

    def test_clamped_to_4x_band(self, as_on_a_chip):
        from spark_rapids_tpu import config as C
        from spark_rapids_tpu.plan import cost
        conf = self._conf()
        base = float(C.COST_SYNC_FLOOR_MS.default)
        cost.observe(sync_floor_ms=base * 1000)
        assert cost.effective_sync_floor_ms(conf) == base * 4
        cost.reset_calibration()
        cost.observe(sync_floor_ms=base / 1000)
        assert cost.effective_sync_floor_ms(conf) == base / 4

    def test_explicit_conf_key_wins(self, as_on_a_chip):
        from spark_rapids_tpu.plan import cost
        conf = self._conf(**{"spark.rapids.sql.cost.deviceSyncFloorMs":
                             33.0})
        cost.observe(sync_floor_ms=5.0)
        assert cost.effective_sync_floor_ms(conf) == 33.0

    def test_disabled_leaves_constants(self, as_on_a_chip):
        from spark_rapids_tpu import config as C
        from spark_rapids_tpu.plan import cost
        conf = self._conf(**{"spark.rapids.sql.cost.calibration.enabled":
                             False})
        cost.observe(sync_floor_ms=1.0)
        assert cost.effective_sync_floor_ms(conf) == \
            float(C.COST_SYNC_FLOOR_MS.default)

    def test_error_pct_dampens_update(self):
        from spark_rapids_tpu.plan import cost
        cost.observe(sync_floor_ms=100.0)
        cost.observe(sync_floor_ms=10.0, error_pct=400.0, alpha=0.5)
        # weight = 0.5/(1+4) = 0.1 -> 0.9*100 + 0.1*10 = 91
        assert abs(cost.calibration_state()["sync_floor_ms"] - 91.0) < 1e-9

    def test_observe_query_reads_trace_spans(self, tmp_path):
        """A traced collect feeds real sync/upload spans into the
        calibration state."""
        from spark_rapids_tpu.plan import cost
        from spark_rapids_tpu.api.dataframe import TpuSession
        from spark_rapids_tpu.benchmarks import tpch
        d = str(tmp_path / "cal_tpch")
        tpch.generate(d, scale=0.003, files_per_table=1, seed=7)
        s = TpuSession()
        s.set("spark.rapids.sql.variableFloatAgg.enabled", True)
        s.set("spark.rapids.sql.trace.enabled", True)
        s.set("spark.rapids.sql.trace.level", "kernel")
        tpch.QUERIES["q6"](s, d).collect()
        state = cost.calibration_state()
        assert state["samples"] >= 1, state
        assert (state["sync_floor_ms"] or state["device_gbps"]), state
