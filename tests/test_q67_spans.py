"""What TPC-DS q67 adds to the flight recorder (PR 33): the ``window``
phases of ``WindowExec``, one ``agg-consolidate`` span a level of the
aggregate's merge tree, and the counters of the Expand, the aggregate's
update and the window — on a small q67 over the benchmark's generator,
with tracing on, and nothing of them with tracing off.
"""

import collections
import importlib.util
import os

import numpy as np
import pytest

from spark_rapids_tpu import INT64, FLOAT64
from spark_rapids_tpu.api.dataframe import TpuSession
from spark_rapids_tpu.monitoring import recorder

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COUNTERS = ("expandRowsIn", "expandRowsOut", "expandProjections",
            "aggUpdateRows", "aggConsolidateLevels", "windowRowsIn",
            "windowBatches", "joinBuildRows", "exchangeRows",  # PR 35's two
            "joinEagerBatches")     # PR 37: tiny batches take one program


def _tpcds():
    spec = importlib.util.spec_from_file_location(
        "benchmark_tpcds_data",
        os.path.join(ROOT, "benchmark", "tpcds_data.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _session(trace, **conf):
    s = TpuSession()
    s.set("spark.rapids.sql.variableFloatAgg.enabled", True)
    s.set("spark.rapids.sql.hasNans", False)
    s.set("spark.rapids.sql.trace.enabled", trace)
    s.set("spark.rapids.sql.trace.level", "kernel")
    for k, v in conf.items():
        s.set(k, v)
    return s


def _spans(events):
    return [e for e in events if e[0] == "X"]


@pytest.fixture(scope="module")
def q67(tmp_path_factory):
    """One untraced and two traced collects of a small q67 (the second
    with the stage fusion off: ``ExpandExec`` itself), each one's events
    and counters."""
    tpcds = _tpcds()
    d = str(tmp_path_factory.mktemp("q67_spans"))
    tpcds.generate(d, scale=0.01, seed=2147483933, files_per_table=2)
    out = {}
    for name, trace, conf in (
            ("off", False, {}), ("on", True, {}),
            ("unfused", True,
             {"spark.rapids.sql.stageFusion.enabled": False})):
        recorder.reset()
        recorder.reset_counters()
        df = tpcds.QUERIES["q67"](_session(trace, **conf), d)
        rows = df.collect()
        out[name] = {"rows": rows, "events": recorder.events(),
                     "counters": recorder.counters(),
                     "plan": df._physical().root.pretty_tree()}
    recorder.configure(False)
    recorder.reset()
    recorder.reset_counters()
    return out


def test_nothing_is_recorded_with_tracing_off(q67):
    assert q67["off"]["events"] == [] and q67["off"]["counters"] == {}
    assert q67["off"]["rows"] == q67["on"]["rows"] == q67["unfused"]["rows"]
    recorder.configure(False)
    recorder.count("aggUpdateRows", 5)
    assert recorder.counters() == {}


@pytest.mark.parametrize("run", ["on", "unfused"])
def test_counters_add_up(q67, run):
    c = q67[run]["counters"]
    assert set(COUNTERS) | {"collects"} == set(c)
    assert c["collects"] == 1
    # a rollup of eight columns: nine projections of every input batch,
    # each at its input's capacity
    assert c["expandRowsOut"] == 9 * c["expandRowsIn"] > 0
    assert c["expandProjections"] % 9 == 0
    # the update is handed what the Expand gave, coalesced (a bucket
    # rounds up, never down)
    assert c["aggUpdateRows"] >= c["expandRowsOut"]
    assert c["windowBatches"] >= 1
    assert c["windowRowsIn"] >= c["windowBatches"]
    assert "ExpandExec" in q67[run]["plan"]
    assert ("FusedStageExec" in q67[run]["plan"]) == (run == "on")


def test_fused_and_unfused_expand_count_the_same(q67):
    for k in ("expandRowsIn", "expandRowsOut", "expandProjections"):
        assert q67["on"]["counters"][k] == q67["unfused"]["counters"][k]


def test_unfused_expand_has_one_span_an_input_batch(q67):
    spans = [e for e in _spans(q67["unfused"]["events"])
             if e[2] == "device-compute" and e[1] == "ExpandExec"]
    assert 9 * len(spans) == q67["unfused"]["counters"]["expandProjections"]


@pytest.mark.parametrize("cat,names", [
    ("window", {"gather", "compute"}), ("agg-consolidate", {"level"})])
def test_phases_are_not_nested_within_their_category(q67, cat, names):
    events = _spans(q67["on"]["events"])
    by_sid = {e[8]: e for e in events}
    phases = sorted((e for e in events if e[2] == cat), key=lambda e: e[3])
    assert phases and {e[1] for e in phases} == names
    for tid in {e[5] for e in phases}:
        mine = [e for e in phases if e[5] == tid]
        for a, b in zip(mine, mine[1:]):
            assert a[3] + a[4] <= b[3], (a[1], b[1])
    for e in phases:
        p = by_sid.get(e[9])
        while p is not None:
            assert p[2] != cat, (e[1], p[1])
            p = by_sid.get(p[9])


def test_window_phases_hold_no_childs_work(q67):
    """Under a ``window`` span: the operator's own dispatch, the reads a
    coalesce makes, what the runtime interposes — no exchange, no
    aggregate, no scan of the child's."""
    events = _spans(q67["on"]["events"])
    by_sid = {e[8]: e for e in events}
    inside = collections.Counter()
    for e in events:
        p = by_sid.get(e[9])
        while p is not None and p[2] != "window":
            p = by_sid.get(p[9])
        if p is not None and e[2] != "window":
            inside[(e[2], e[1])] += 1
    assert {cat for cat, _ in inside} <= \
        {"device-compute", "sync", "runtime", "compile"}
    assert {n for cat, n in inside if cat == "device-compute"} <= \
        {"WindowExec", "shrink-all"}
    assert inside[("device-compute", "WindowExec")] == \
        q67["on"]["counters"]["windowBatches"]


def test_consolidate_levels_carry_their_members(q67):
    levels = [e for e in _spans(q67["on"]["events"])
              if e[2] == "agg-consolidate"]
    assert len(levels) == q67["on"]["counters"]["aggConsolidateLevels"]
    for e in levels:
        args = e[7]
        assert args["op"] == "HashAggregateExec" and args["level"] >= 0
        assert args["members"] == len(args["capacities"]) >= 1
    # every tree starts at level 0
    assert sum(e[7]["level"] == 0 for e in levels) >= 1


def test_out_of_core_window_counts_its_split():
    from spark_rapids_tpu.plan.logical import Window, agg_sum, col
    rng = np.random.default_rng(11)
    n = 40_000
    s = _session(True, **{"spark.rapids.memory.tpu.budgetBytes": 96 * 1024})
    df = s.create_dataframe(
        {"g": rng.integers(0, 500, n).tolist(),
         "v": rng.normal(size=n).tolist()},
        [("g", INT64), ("v", FLOAT64)], num_partitions=8)
    out = df.with_column("s", agg_sum(col("v")).over(
        Window.partition_by(col("g"))))
    recorder.reset()
    recorder.reset_counters()
    try:
        assert len(out.collect()) == n
        c = recorder.counters()
        phases = [e for e in _spans(recorder.events()) if e[2] == "window"]
    finally:
        recorder.configure(False)
        recorder.reset()
        recorder.reset_counters()
    assert c["windowOutOfCoreSplits"] == 1
    assert c["windowBatches"] >= 2
    names = collections.Counter(e[1] for e in phases)
    assert names["split"] >= 2
    assert names["compute"] == names["gather"] == c["windowBatches"]
    phases.sort(key=lambda e: e[3])
    for a, b in zip(phases, phases[1:]):
        assert a[3] + a[4] <= b[3], (a[1], b[1])
