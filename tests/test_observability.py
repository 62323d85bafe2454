"""ML hand-off + observability surfaces:
DataFrame.to_jax zero-host-round-trip export (ColumnarRdd.scala:41-49),
DataFrame.metrics (GpuExec.scala:27-56), trace annotations in timed(),
and the catalog's alloc-debug leak report (RapidsConf.scala:288)."""

import logging

import jax.numpy as jnp
import pytest

from spark_rapids_tpu import FLOAT64, INT64, STRING
from spark_rapids_tpu.api.dataframe import TpuSession
from spark_rapids_tpu.plan.logical import agg_sum, col


def _df(s):
    return s.create_dataframe(
        {"k": [1, 2, 2, 3, 3, 3], "v": [1.0, 2.0, 3.0, 4.0, 5.0, 6.0],
         "name": ["a", "bb", "ccc", "d", "e", "f"]},
        [("k", INT64), ("v", FLOAT64), ("name", STRING)])


def test_to_jax_device_export():
    s = TpuSession()
    out = _df(s).filter(col("k") > 1).to_jax()
    assert isinstance(out["k"], jnp.ndarray)
    assert out["k"].shape == (5,)
    assert sorted(out["k"].tolist()) == [2, 2, 3, 3, 3]
    assert out["v"].dtype == jnp.float64
    # Strings export as byte matrices + lengths.
    assert out["name"].ndim == 2
    assert out["name__len"].tolist() == [2, 3, 1, 1, 1]


def test_to_jax_rejects_nulls():
    s = TpuSession()
    df = s.create_dataframe({"x": [1.0, None, 3.0]}, [("x", FLOAT64)])
    with pytest.raises(ValueError, match="nulls"):
        df.to_jax()


def test_metrics_after_collect():
    s = TpuSession()
    s.set("spark.rapids.sql.variableFloatAgg.enabled", True)
    df = _df(s).group_by("k").agg(agg_sum(col("v")).alias("sv"))
    assert df.metrics() == {}
    df.collect()
    m = df.metrics()
    assert any("HashAggregateExec" in k for k in m)
    agg_metrics = next(v for k, v in m.items() if "HashAggregate" in k)
    assert agg_metrics.get("totalTime", 0) > 0


def test_memory_debug_leak_report(tmp_path, caplog):
    from spark_rapids_tpu.memory import BufferCatalog
    from tests.test_memory import make_batch
    cat = BufferCatalog(spill_dir=str(tmp_path), debug=True)
    cat.add_batch(make_batch(3))
    leaks = cat.leak_report()
    assert len(leaks) == 1
    bid, size, stack = leaks[0]
    assert size > 0 and "test_observability" in stack
    with caplog.at_level(logging.WARNING, "spark_rapids_tpu.memory"):
        cat.close()
    assert any("leaked" in r.message for r in caplog.records)


def test_audit_groups_exempt_from_metrics_level():
    """The metrics verbosity filter must never drop the per-query audit
    entries — and the exemption set is ONE registry (ops/base.py), not
    per-call-site tuples (ISSUE 9 satellite)."""
    from spark_rapids_tpu.ops.base import (audit_metric_groups,
                                           query_metrics_entry,
                                           register_audit_metric_group)
    # The five built-in audit groups are pre-registered.
    assert {"Recovery", "Pipeline", "Scheduler", "Transport",
            "Cost"} <= audit_metric_groups()
    s = TpuSession()
    s.set("spark.rapids.sql.variableFloatAgg.enabled", True)
    s.set("spark.rapids.sql.metrics.level", "ESSENTIAL")
    df = _df(s).group_by("k").agg(agg_sum(col("v")).alias("sv"))
    df.collect()
    phys = df._physical()
    # Seed audit counters that ESSENTIAL would filter if they were
    # operator metrics, plus a THIRD-PARTY group registered through the
    # same funnel.
    from spark_rapids_tpu.parallel import scheduler as SC
    SC.metrics_entry(phys.last_ctx).add("crossQueryEvictions", 2)
    query_metrics_entry(phys.last_ctx, "Recovery").add(
        "stageRecomputes", 1)
    query_metrics_entry(phys.last_ctx, "MyPlugin").add("customCounter", 3)
    assert "MyPlugin" in audit_metric_groups()
    m = df.metrics()
    # Operator entries are filtered down to the ESSENTIAL set...
    agg = next(v for k, v in m.items() if "HashAggregate" in k)
    assert set(agg) <= {"numOutputRows", "totalTime"}
    # ...audit entries keep every counter, including the plugin's.
    assert m["Scheduler@query"]["crossQueryEvictions"] == 2
    assert m["Recovery@query"]["stageRecomputes"] == 1
    assert m["MyPlugin@query"]["customCounter"] == 3
    # Idempotent re-registration.
    register_audit_metric_group("MyPlugin")
    assert "MyPlugin" in audit_metric_groups()


def test_transient_error_retries_query_once(monkeypatch):
    """Failure recovery (SURVEY 5.3): a transient backend error retries
    the whole query on a fresh context; deterministic errors do not."""
    from spark_rapids_tpu.plan.logical import agg_count
    s = TpuSession()
    df = _df(s).agg(agg_count().alias("n"))
    phys = df._physical()
    calls = {"n": 0}
    orig = type(phys.root).collect

    def flaky(self, ctx, device=True):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("UNAVAILABLE: Socket closed")
        return orig(self, ctx, device)

    monkeypatch.setattr(type(phys.root), "collect", flaky)
    assert phys.collect() == [(6,)]
    assert calls["n"] == 2

    calls["n"] = 0

    def hard(self, ctx, device=True):
        calls["n"] += 1
        raise ValueError("deterministic bug")

    monkeypatch.setattr(type(phys.root), "collect", hard)
    with pytest.raises(ValueError):
        phys.collect()
    assert calls["n"] == 1
