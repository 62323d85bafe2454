"""AQE-lite: stats-driven auto join strategy
(autoBroadcastJoinThreshold over parquet footer estimates) and
post-shuffle partition coalescing from exact materialized sizes
(GpuCustomShuffleReaderExec.scala:132 analog)."""

import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as papq
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu.api.dataframe import TpuSession
from spark_rapids_tpu.ops.join import (
    BroadcastHashJoinExec, ShuffledHashJoinExec)
from spark_rapids_tpu.parallel.exchange import ShuffleExchangeExec
from spark_rapids_tpu.plan.logical import agg_count, agg_sum, col


@pytest.fixture(scope="module")
def pq_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("aqe_pq")
    rng = np.random.default_rng(3)
    big = pa.table({
        "k": rng.integers(0, 50, 20_000, dtype=np.int64),
        "v": rng.uniform(0, 1, 20_000),
    })
    small = pa.table({
        "dk": np.arange(50, dtype=np.int64),
        "w": rng.uniform(0, 1, 50),
    })
    papq.write_table(big, os.path.join(d, "big.parquet"))
    papq.write_table(small, os.path.join(d, "small.parquet"))
    return str(d)


def _join(session, pq_dir):
    big = session.read.parquet(os.path.join(pq_dir, "big.parquet"))
    small = session.read.parquet(os.path.join(pq_dir, "small.parquet"))
    return big.join_on(small, ["k"], ["dk"])


def _find(root, cls):
    out = []

    def walk(n):
        if isinstance(n, cls):
            out.append(n)
        for c in n.children:
            walk(c)

    walk(root)
    return out


class TestAutoJoinStrategy:
    def test_small_build_side_broadcasts(self, pq_dir):
        s = TpuSession()
        phys = _join(s, pq_dir)._physical()
        assert _find(phys.root, BroadcastHashJoinExec)
        assert "auto join strategy -> broadcast" in phys.explain()

    def test_large_build_side_shuffles(self, pq_dir):
        s = TpuSession()
        s.set("spark.rapids.sql.autoBroadcastJoinThreshold", 64)
        phys = _join(s, pq_dir)._physical()
        joins = _find(phys.root, ShuffledHashJoinExec)
        joins = [j for j in joins
                 if not isinstance(j, BroadcastHashJoinExec)]
        assert joins
        assert "auto join strategy -> shuffle" in phys.explain()

    def test_threshold_minus_one_disables_broadcast(self, pq_dir):
        s = TpuSession()
        s.set("spark.rapids.sql.autoBroadcastJoinThreshold", -1)
        phys = _join(s, pq_dir)._physical()
        assert not _find(phys.root, BroadcastHashJoinExec)

    def test_both_strategies_agree(self, pq_dir):
        s1 = TpuSession()
        s2 = TpuSession()
        s2.set("spark.rapids.sql.autoBroadcastJoinThreshold", 64)
        r1 = sorted(_join(s1, pq_dir).collect())
        r2 = sorted(_join(s2, pq_dir).collect())
        assert r1 == r2


class TestPartitionCoalescing:
    def _agg(self, session):
        df = session.create_dataframe(
            {"k": list(range(100)) * 4, "v": list(range(400))},
            [("k", srt.INT64), ("v", srt.INT64)], num_partitions=4)
        return df.group_by("k").agg(agg_sum(col("v")).alias("s"),
                                    agg_count().alias("n"))

    def test_undersized_partitions_merge(self):
        s = TpuSession()
        q = self._agg(s)
        phys = q._physical()
        rows = phys.collect()
        assert len(rows) == 100
        # The aggregate exchange coalesced its tiny reduce partitions.
        from spark_rapids_tpu.ops.base import ExecContext
        ctx = ExecContext(phys.conf)
        ctx.cache["engine"] = "device"
        phys.root.collect(ctx, device=True)
        ex = _find(phys.root, ShuffleExchangeExec)
        coalescable = [e for e in ex if e.allow_coalesce]
        assert coalescable
        assert any(e.num_partitions(ctx)
                   < e.partitioning.num_partitions for e in coalescable)
        ctx.close()

    def test_disabled_by_conf(self):
        s = TpuSession()
        s.set("spark.rapids.sql.aqe.coalescePartitions.enabled", False)
        phys = self._agg(s)._physical()
        from spark_rapids_tpu.ops.base import ExecContext
        ctx = ExecContext(phys.conf)
        ctx.cache["engine"] = "device"
        phys.root.collect(ctx, device=True)
        ex = _find(phys.root, ShuffleExchangeExec)
        for e in ex:
            assert e.num_partitions(ctx) == e.partitioning.num_partitions
        ctx.close()

    def test_results_identical_with_and_without(self):
        s1 = TpuSession()
        s2 = TpuSession()
        s2.set("spark.rapids.sql.aqe.coalescePartitions.enabled", False)
        assert sorted(self._agg(s1).collect()) == \
            sorted(self._agg(s2).collect())
