"""Out-of-core sort: sorting a partition LARGER than
the device budget completes via the sample-sort spill path and matches
the host oracle — beyond the reference's v0.3 RequireSingleBatch
(GpuSortExec.scala:50)."""

import numpy as np
import pytest

from spark_rapids_tpu import FLOAT64, INT64
from spark_rapids_tpu.api.dataframe import TpuSession
from spark_rapids_tpu.plan.logical import col


def _session(budget_bytes):
    s = TpuSession()
    s.set("spark.rapids.memory.tpu.budgetBytes", budget_bytes)
    return s


def _data(n, seed=7):
    rng = np.random.default_rng(seed)
    return {"k": rng.integers(0, 1_000_000, n).tolist(),
            "v": rng.normal(size=n).tolist()}


def test_sort_larger_than_device_budget():
    n = 40_000
    data = _data(n)
    # ~40k rows x 2 f64 columns ~= 640KB of data; 256KB budget forces the
    # sample-sort split (plus spilling of the staged input).
    s = _session(256 * 1024)
    df = s.create_dataframe(data, [("k", INT64), ("v", FLOAT64)],
                            num_partitions=8) \
        .order_by(col("k").asc(), col("v").asc())
    got = df.collect()
    want = df.collect_host()
    assert got == want
    # The out-of-core path actually engaged (bucketed sort + spills).
    phys = df._physical()
    metrics = phys.last_ctx.metrics
    sort_m = [m for k, m in metrics.items() if "SortExec" in k]
    assert any(m.values.get("outOfCoreBuckets", 0) >= 2 for m in sort_m)


def test_sort_in_core_path_unchanged():
    data = _data(5_000)
    s = TpuSession()
    df = s.create_dataframe(data, [("k", INT64), ("v", FLOAT64)],
                            num_partitions=3) \
        .order_by(col("k").desc(), col("v").asc())
    assert df.collect() == df.collect_host()


def test_window_larger_than_device_budget():
    """Partition-chunked windows (the other half of out-of-core):
    a partitioned window over data beyond the device budget range-splits
    by partition key and matches the host oracle."""
    from spark_rapids_tpu.plan.logical import agg_sum, col
    n = 40_000
    rng = np.random.default_rng(11)
    data = {"g": rng.integers(0, 500, n).tolist(),
            "v": rng.normal(size=n).tolist()}
    s = _session(96 * 1024)
    s.set("spark.rapids.sql.variableFloatAgg.enabled", True)
    df = s.create_dataframe(data, [("g", INT64), ("v", FLOAT64)],
                            num_partitions=8)
    from spark_rapids_tpu.plan.logical import Window
    w = Window.partition_by(col("g"))
    out = df.with_column("s", agg_sum(col("v")).over(w))
    got = sorted(out.collect())
    want = sorted(out.collect_host())
    assert len(got) == n
    for a, b in zip(got, want):
        assert a[:2] == b[:2] and abs(a[2] - b[2]) < 1e-9
    phys = out._physical()
    wms = [m.values for k, m in phys.last_ctx.metrics.items()
           if "WindowExec" in k]
    assert any(v.get("outOfCoreBuckets", 0) >= 2 for v in wms)
