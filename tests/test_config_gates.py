"""Every registered config key must change behavior somewhere: semaphore
admission, stableSort, hasNans, improvedFloatOps, cast gates,
replaceSortMergeJoin, skipAggPassReductionRatio (no decorative keys)."""

import threading
import time

import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu.api.dataframe import TpuSession
from spark_rapids_tpu.plan.logical import agg_sum, col, log_col as log_fn


def make_df(session, **conf):
    for k, v in conf.items():
        session.set(k, v)
    return session.create_dataframe(
        {"k": [1, 2, 1, 2], "v": [1.5, 2.5, 3.5, 4.5],
         "s": ["1.5", "x", "2", None]},
        [("k", srt.INT64), ("v", srt.FLOAT64), ("s", srt.STRING)],
        num_partitions=2)


class TestSemaphore:
    def test_concurrent_tasks_serialize(self):
        """concurrentTpuTasks=1 serializes two concurrent collects
        (GpuSemaphore.scala:74-87 behavior)."""
        from spark_rapids_tpu.memory.stores import TpuSemaphore
        # Direct instance: the process-global one is sized by whichever
        # collect ran first in this test process.
        sem = TpuSemaphore(1)
        windows = []
        lock = threading.Lock()

        def task():
            with sem:
                t0 = time.perf_counter()
                time.sleep(0.05)
                with lock:
                    windows.append((t0, time.perf_counter()))

        threads = [threading.Thread(target=task) for _ in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        windows.sort()
        for (s0, e0), (s1, _) in zip(windows, windows[1:]):
            assert s1 >= e0, "collects overlapped under 1 permit"

    def test_collect_goes_through_semaphore(self, monkeypatch):
        """Exec.collect acquires the configured semaphore."""
        from spark_rapids_tpu.memory import stores
        acquired = []
        real = stores.get_tpu_semaphore

        def spy(permits):
            acquired.append(permits)
            return real(permits)

        monkeypatch.setattr(stores, "get_tpu_semaphore", spy)
        s = TpuSession()
        s.set("spark.rapids.sql.concurrentTpuTasks", 3)
        make_df(s).select("k").collect()
        assert 3 in acquired


class TestExprGates:
    def test_improved_float_ops_gate(self):
        s = TpuSession()
        df = make_df(s).select(log_fn(col("v")).alias("l"))
        report = df.explain("NOT_ON_GPU")
        assert "improvedFloatOps" in report
        # Enabling the key clears the fallback.
        s2 = TpuSession()
        s2.set("spark.rapids.sql.improvedFloatOps.enabled", True)
        df2 = make_df(s2).select(log_fn(col("v")).alias("l"))
        assert "improvedFloatOps" not in df2.explain("NOT_ON_GPU")
        # Results agree either way.
        assert df.collect() == df2.collect()

    def test_cast_float_to_string_gate(self):
        s = TpuSession()
        df = make_df(s).select(col("v").cast("string").alias("t"))
        assert "castFloatToString" in df.explain("NOT_ON_GPU")
        s2 = TpuSession()
        s2.set("spark.rapids.sql.castFloatToString.enabled", True)
        df2 = make_df(s2).select(col("v").cast("string").alias("t"))
        assert "castFloatToString" not in df2.explain("NOT_ON_GPU")

    def test_cast_string_to_float_gate(self):
        s = TpuSession()
        df = make_df(s).select(col("s").cast("double").alias("d"))
        assert "castStringToFloat" in df.explain("NOT_ON_GPU")

    def test_replace_sort_merge_join_gate(self):
        s = TpuSession()
        s.set("spark.rapids.sql.replaceSortMergeJoin.enabled", False)
        left = make_df(s)
        right = s.create_dataframe(
            {"k2": [1, 2], "w": [9.0, 8.0]},
            [("k2", srt.INT64), ("w", srt.FLOAT64)])
        j = left.join_on(right, ["k"], ["k2"], strategy="shuffle")
        assert "replaceSortMergeJoin" in j.explain("NOT_ON_GPU")
        # Host fallback still computes the right answer.
        assert sorted(j.collect()) == sorted(j.collect_host())


class TestStableSort:
    def test_stable_sort_preserves_arrival_order(self):
        s = TpuSession()
        s.set("spark.rapids.sql.stableSort.enabled", True)
        df = s.create_dataframe(
            {"k": [1, 1, 1, 1], "i": [0, 1, 2, 3]},
            [("k", srt.INT64), ("i", srt.INT64)])
        out = df.order_by(col("k").asc()).collect()
        assert [r[1] for r in out] == [0, 1, 2, 3]

    def test_unstable_sort_still_sorts(self):
        s = TpuSession()
        s.set("spark.rapids.sql.stableSort.enabled", False)
        df = s.create_dataframe(
            {"k": [3, 1, 2, 1], "i": [0, 1, 2, 3]},
            [("k", srt.INT64), ("i", srt.INT64)])
        out = df.order_by(col("k").asc()).collect()
        assert [r[0] for r in out] == [1, 1, 2, 3]


class TestHasNans:
    def test_hasnans_false_matches_host_on_finite_data(self):
        s = TpuSession()
        s.set("spark.rapids.sql.hasNans", False)
        s.set("spark.rapids.sql.variableFloatAgg.enabled", True)
        df = make_df(s)
        q = df.group_by("k").agg(agg_sum(col("v")).alias("sv"))
        assert sorted(q.collect()) == sorted(q.collect_host())

    def test_hasnans_true_handles_nan(self):
        s = TpuSession()
        s.set("spark.rapids.sql.variableFloatAgg.enabled", True)
        df = s.create_dataframe(
            {"k": [1, 1, 2, 2], "v": [float("nan"), 1.0, 2.0, 3.0]},
            [("k", srt.INT64), ("v", srt.FLOAT64)])
        q = df.group_by("k").agg(agg_sum(col("v")).alias("sv"))
        got = dict(q.collect())
        import math
        assert math.isnan(got[1]) and got[2] == 5.0


class TestFormatAndMemoryGates:
    """Round-5 config additions: per-format read/write gates, per-format
    reader strategies, memory ceiling/reserve, metrics level."""

    def test_parquet_read_gate_falls_back(self, tmp_path):
        import pyarrow as pa
        import pyarrow.parquet as papq
        import numpy as np
        from spark_rapids_tpu.api.dataframe import TpuSession
        p = str(tmp_path / "t.parquet")
        papq.write_table(pa.table({"x": np.arange(10,
                                                  dtype=np.int64)}), p)
        s = TpuSession()
        s.set("spark.rapids.sql.format.parquet.read.enabled", False)
        df = s.read.parquet(p)
        report = df._physical().explain()
        assert "parquet scan disabled" in report
        assert sorted(r[0] for r in df.collect()) == list(range(10))

    def test_orc_reader_type_key(self, tmp_path):
        import pyarrow as pa
        import pyarrow.orc as paorc
        import numpy as np
        from spark_rapids_tpu.api.dataframe import TpuSession
        from spark_rapids_tpu.ops.base import ExecContext
        p = str(tmp_path / "t.orc")
        paorc.write_table(pa.table({"x": np.arange(5,
                                                   dtype=np.int64)}), p)
        s = TpuSession()
        s.set("spark.rapids.sql.format.orc.reader.type", "PERFILE")
        df = s.read.orc(p)
        phys = df._physical()
        scan = phys.root
        while scan.children:
            scan = scan.children[0]
        assert scan._reader_type(ExecContext(phys.conf)) == "PERFILE"
        assert df.collect() == [(i,) for i in range(5)]

    def test_write_gate_uses_host_engine(self, tmp_path):
        from spark_rapids_tpu import FLOAT64, INT64
        from spark_rapids_tpu.api.dataframe import TpuSession
        import pyarrow.parquet as papq
        import os
        s = TpuSession()
        s.set("spark.rapids.sql.format.parquet.write.enabled", False)
        df = s.create_dataframe({"x": [1, 2, 3]}, [("x", INT64)])
        out = str(tmp_path / "w")
        stats = df.write.parquet(out)
        assert stats["numOutputRows"] == 3
        files = [f for f in os.listdir(out) if f.endswith(".parquet")]
        rows = sum(papq.read_table(os.path.join(out, f)).num_rows
                   for f in files)
        assert rows == 3

    def test_memory_ceiling_and_reserve(self):
        from spark_rapids_tpu.ops.base import ExecContext, \
            _visible_device_bytes
        from spark_rapids_tpu.config import TpuConf
        visible = _visible_device_bytes()
        conf = TpuConf({
            "spark.rapids.memory.tpu.allocFraction": 0.9,
            "spark.rapids.memory.tpu.maxAllocFraction": 0.5,
            "spark.rapids.memory.tpu.reserve": 1024,
        })
        ctx = ExecContext(conf)
        assert ctx.catalog.device_budget == int(visible * 0.5) - 1024
        ctx.close()

    def test_metrics_level_filters(self):
        from spark_rapids_tpu import FLOAT64, INT64
        from spark_rapids_tpu.api.dataframe import TpuSession
        from spark_rapids_tpu.plan.logical import agg_count
        s = TpuSession()
        df = s.create_dataframe({"x": [1, 2, 3]}, [("x", INT64)]) \
            .agg(agg_count().alias("n"))
        df.collect()
        s.set("spark.rapids.sql.metrics.level", "ESSENTIAL")
        df.collect()    # re-plan under the new conf version
        m = df.metrics()
        allowed = {"numOutputRows", "totalTime"}
        # Audit-trail entries (Recovery/Pipeline/Scheduler@query) are
        # exempt from level filtering by contract — only the
        # per-operator entries must be filtered down.
        audit = {"Recovery@query", "Pipeline@query", "Scheduler@query"}
        assert m and all(set(v) <= allowed for k, v in m.items()
                         if k not in audit)


def test_generated_docs_in_sync():
    """docs/configs.md is the generated config reference (the reference's
    generated docs/configs.md discipline) — regen must be a no-op."""
    import os
    from spark_rapids_tpu.config import generate_docs
    path = os.path.join(os.path.dirname(__file__), "..", "docs",
                        "configs.md")
    assert open(path).read() == generate_docs()
