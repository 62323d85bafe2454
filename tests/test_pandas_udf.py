"""Pandas-UDF exec family: map_in_pandas,
apply_in_pandas (grouped map), cogrouped map, grouped-agg pandas UDFs —
host islands inside device plans with a bounded worker pool
(GpuMapInPandasExec / GpuFlatMapGroupsInPandasExec /
GpuCoGroupedMapInPandasExec / GpuAggregateInPandasExec,
PythonWorkerSemaphore)."""

import numpy as np
import pandas as pd
import pytest

from spark_rapids_tpu import FLOAT64, INT64, STRING
from spark_rapids_tpu.api.dataframe import TpuSession
from spark_rapids_tpu.plan.logical import col


def _session():
    return TpuSession()


def _df(s, n=200, parts=4, seed=3):
    rng = np.random.default_rng(seed)
    return s.create_dataframe(
        {"g": rng.integers(0, 9, n).tolist(),
         "v": np.round(rng.normal(size=n), 6).tolist()},
        [("g", INT64), ("v", FLOAT64)], num_partitions=parts)


def test_map_in_pandas():
    s = _session()

    def doubler(frames):
        for pdf in frames:
            out = pdf.copy()
            out["v2"] = out.v * 2.0
            yield out[["g", "v2"]]

    df = _df(s).map_in_pandas(doubler,
                              [("g", INT64), ("v2", FLOAT64)])
    got = sorted(df.collect())
    want = sorted(df.collect_host())
    assert got == want
    assert len(got) == 200
    assert all(abs(r[1]) < 20 for r in got)


def test_apply_in_pandas_grouped_map():
    s = _session()

    def center(pdf):
        out = pdf.copy()
        out["v"] = out.v - out.v.mean()
        return out

    df = _df(s).group_by("g").apply_in_pandas(
        center, [("g", INT64), ("v", FLOAT64)])
    got = sorted(df.collect())
    want = sorted(df.collect_host())
    assert len(got) == 200
    for a, b in zip(got, want):
        assert a[0] == b[0] and abs(a[1] - b[1]) < 1e-9
    # Per-group means are ~0 after centering.
    pdf = pd.DataFrame(got, columns=["g", "v"])
    assert pdf.groupby("g").v.mean().abs().max() < 1e-9


def test_cogrouped_map():
    s = _session()
    left = _df(s, n=60, seed=1)
    right = s.create_dataframe(
        {"k": [0, 1, 2, 3, 42], "w": [10.0, 20.0, 30.0, 40.0, 99.0]},
        [("k", INT64), ("w", FLOAT64)], num_partitions=2)

    def merge(lp, rp):
        n = len(lp)
        w = float(rp.w.iloc[0]) if len(rp) else -1.0
        g = int(lp.g.iloc[0]) if n else \
            (int(rp.k.iloc[0]) if len(rp) else -1)
        return pd.DataFrame({"g": [g], "n": [n], "w": [w]})

    df = left.group_by("g").cogroup(right.group_by("k")) \
        .apply_in_pandas(merge, [("g", INT64), ("n", INT64),
                                 ("w", FLOAT64)])
    got = sorted(df.collect())
    want = sorted(df.collect_host())
    assert got == want
    by_g = {r[0]: r for r in got}
    assert 42 in by_g and by_g[42][1] == 0      # right-only key
    assert by_g[0][2] == 10.0                   # matched key
    assert any(r[2] == -1.0 for r in got)       # left-only keys


def test_agg_in_pandas():
    s = _session()
    df = _df(s).group_by("g").agg_in_pandas(
        med=("v", lambda series: float(series.median()), FLOAT64),
        cnt=("v", lambda series: int(len(series)), INT64))
    got = sorted(df.collect())
    want = sorted(df.collect_host())
    assert got == want
    assert sum(r[2] for r in got) == 200


def test_worker_pool_is_bounded():
    import threading
    s = _session()
    s.set("spark.rapids.python.concurrentPythonWorkers", 2)
    active, peak = [], []
    lock = threading.Lock()

    def slow(pdf):
        import time
        with lock:
            active.append(1)
            peak.append(len(active))
        time.sleep(0.02)
        with lock:
            active.pop()
        return pdf

    _df(s, n=400, parts=1).group_by("g").apply_in_pandas(
        slow, [("g", INT64), ("v", FLOAT64)]).collect()
    assert max(peak) <= 2


def test_cogroup_null_keys_collide():
    """Regression (ISSUE 2 satellite): float-NaN group keys from the two
    cogrouped sides must land in ONE cogrouped call (Spark null-key
    grouping), not pair each side's null group with an empty frame —
    pandas returns nan keys under dropna=False, and two nans from two
    separate groupbys are neither equal nor same-hash."""
    s = _session()
    left = s.create_dataframe(
        {"k": [1.0, None, None, 2.0], "v": [10.0, 20.0, 30.0, 40.0]},
        [("k", FLOAT64), ("v", FLOAT64)])
    right = s.create_dataframe(
        {"k": [None, 3.0], "w": [100.0, 200.0]},
        [("k", FLOAT64), ("w", FLOAT64)])

    def merge(lpdf, rpdf):
        # (left rows, right rows) per cogrouped key: the null key must
        # see BOTH sides' rows in the same call.
        return pd.DataFrame({"nl": [float(len(lpdf))],
                             "nr": [float(len(rpdf))]})

    df = left.group_by("k").cogroup(right.group_by("k")).apply_in_pandas(
        merge, [("nl", FLOAT64), ("nr", FLOAT64)])
    got = sorted(df.collect())
    # Keys: 1.0 (1,0), 2.0 (1,0), 3.0 (0,1), null (2,1) — four calls,
    # with the two left nulls and one right null cogrouped together.
    assert got == [(0.0, 1.0), (1.0, 0.0), (1.0, 0.0), (2.0, 1.0)]
    assert df.collect_host() is not None  # host path tolerates it too
