"""The cells ``tpcds_sf1_resident_q67`` and ``tpch_sf1_resident_q6`` as
COMMITTED (PR 33): the entries, the three per-layer readers of q67 on
hand-made contexts and on a program that has none of what they read (the
parent's: nothing, never a zero), the suite's row counts without
generating, the float32 control, then both cells through the CPU
rehearsal."""

import json
import os
import types

import pytest

from conftest import ROOT, rehearse
import control
import run
import tpcds_data

Q67 = "tpcds_sf1_resident_q67"
Q6 = "tpch_sf1_resident_q6"
NEW = {"window_exec_ms": "device execs: window (`ops/window.py`)",
       "agg_consolidate_ms":
           "device execs: aggregate merge tree (`ops/aggregate.py`)",
       "agg_update_mrows":
           "device execs: aggregate merge tree (`ops/aggregate.py`)"}


def recorder_ctx(queries, **category_ms):
    return {"recorder": types.SimpleNamespace(
        queries=queries, category_ms=category_ms, syncs=0)}


def lines(p, phase):
    return [json.loads(ln) for ln in p.stdout.splitlines()
            if ln.startswith('{"phase": "%s"' % phase)]


def test_entries_as_committed(bench):
    cells = {w["name"]: w for w in bench["workloads"]}
    q67, q6 = cells[Q67], cells[Q6]
    assert (q67["config"], q67["traffic"], q67["chips"]) == \
        ("tpcds_sf1_resident", "q67_closed1", 1)
    assert (q6["config"], q6["traffic"], q6["chips"]) == \
        ("tpch_sf1_resident", "q6_closed1", 1)
    assert all(len(c["why"]) <= 200 for c in (q67, q6))
    config, = [c for c in bench["configs"]
               if c["name"] == "tpcds_sf1_resident"]
    assert config["reduced"] == ["scale"] and len(config["source"]) <= 200
    with open(os.path.join(ROOT, config["file"])) as f:
        held = json.load(f)
    assert held["source"] == config["source"]
    assert held["suite"] == "tpcds_data" and held["chips"] == 1
    assert held["scale"] == 1.0 and held["reduced"] == ["scale"]
    assert held["architecture"] is None
    assert held["tables"] == tpcds_data.table_rows(1.0)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "tpch_sf1_resident.json")) as f:
        tpch = json.load(f)
    # the guarantees word for word, and no conf beyond the two
    # statements about the data
    assert held["guarantees"] == tpch["guarantees"]
    assert held["conf"] == tpch["conf"]
    assert "whole_currency_units" in held["assumed"]
    for name, layer in NEW.items():
        m, = [m for m in bench["per_layer"] if m["name"] == name]
        assert m["workloads"] == [Q67] and m["moves"] == "query_s"
        assert m["layer"] == layer and m["better"] == "lower"
    # q67 reports the thirteen that stand and its three; q6 the thirteen
    assert sum(run.applies(m, Q67, bench) for m in bench["per_layer"]) == 16
    assert sum(run.applies(m, Q6, bench) for m in bench["per_layer"]) == 13


def test_traffic_file():
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "q67_closed1.json")) as f:
        assert json.load(f) == {"loop": "closed", "clients": 1,
                                "mix": [{"query": "q67", "weight": 1}]}


def test_sf1_row_counts_without_generating():
    assert tpcds_data.table_rows(1.0) == {
        "store_sales": 2_880_404, "item": 18_000, "store": 12,
        "date_dim": 73_049}
    assert tpcds_data.table_rows(0.01)["date_dim"] == 73_049
    assert set(tpcds_data.QUERY_COLUMNS["q67"]) == \
        set(tpcds_data.table_rows(1.0))


@pytest.mark.parametrize("name,category", [
    ("window_exec_ms", "window"),
    ("agg_consolidate_ms", "agg-consolidate")])
def test_span_readers(name, category):
    read = run.metric_reader(name)
    assert read(recorder_ctx(4, **{category: 10.0, "shuffle": 99.0})) == 2.5
    assert read(recorder_ctx(4, **{category: 0.0})) == 0.0   # a real zero
    # a program without the spans (the parent of PR 33): nothing, no zero
    assert read(recorder_ctx(4, shuffle=99.0)) is None
    assert read(recorder_ctx(4)) is None
    assert read(recorder_ctx(0, **{category: 10.0})) is None


def test_agg_update_mrows(monkeypatch):
    from spark_rapids_tpu.monitoring import recorder
    read = run.metric_reader("agg_update_mrows")
    monkeypatch.setattr(recorder, "counters", lambda: {
        "aggUpdateRows": 36_000_000, "collects": 4}, raising=False)
    assert read({}) == pytest.approx(9.0)
    # no update ran, no collect was counted, or the recorder was off
    for c in ({"collects": 4}, {"aggUpdateRows": 5}, {}):
        monkeypatch.setattr(recorder, "counters", lambda c=c: c)
        assert read({}) is None
    # a program without the counter (the parent of PR 33)
    monkeypatch.delattr(recorder, "counters")
    assert read({}) is None


def test_float32_reference_is_not_correct(capsys):
    # Whole currency units, and pandas adds float32 groups up in float64:
    # the float32 reference differs only where a sum of the answer passes
    # 2^24 and is no multiple of its spacing there. At this scale that is
    # the grand total alone (~4.8e7, spacing 4), so one seed in four comes
    # out correct by chance (seed 8 does); at SF1 it is ~2.6e9, spacing
    # 256. PERF.md, "How correct is decided".
    passed = control.main(["--workload", Q67, "--seeds", "2,3,4",
                           "--scale", "0.02"])
    out = [json.loads(ln) for ln in
           capsys.readouterr().out.strip().splitlines()]
    assert passed == 0 and len(out) == 3
    for ln in out:
        assert not ln["correct"]
        assert ln["answers_wrong"] or ln["control_gap"] > 3 * ln["limit"]


@pytest.mark.parametrize("cell,query", [(Q67, "q67"), (Q6, "q6")])
def test_committed_cell_untraced(cell, query):
    res, p = rehearse(cell)
    assert res["correct"] is True and res["failed"] == 0
    assert res["device"]["count"] == 1
    assert set(res["metrics"]) == {"query_s", "setup_s"}
    head, = lines(p, "cell")
    assert head["queries"] == [query]
    assert head["conf"] == {
        "spark.rapids.sql.variableFloatAgg.enabled": True,
        "spark.rapids.sql.hasNans": False}
    window, = lines(p, "window")
    assert window["programs_compiled_in_window"] == 0


def test_q67_traced():
    res, p = rehearse(Q67, trace="1")
    assert res["correct"] is True
    got = res["metrics"]
    window, = lines(p, "window")
    for name in ("window_exec_ms", "agg_consolidate_ms"):
        assert got[name]["unit"] == "ms/query"
        # a part of the query, not the whole of it
        assert 0 < got[name]["value"] < 1e3 * window["query_s"]
    assert got["agg_update_mrows"]["unit"] == "Mrows/query"
    assert got["agg_update_mrows"]["value"] > 0
    assert got["plan_host_nodes"]["value"] == 0
    assert res["checks"]["max_rel_gap"]["value"] == 0.0
    # no device trace on a CPU: no device metric
    assert "device_busy_ms" not in got and "scan_hbm_roofline" not in got


def test_q6_traced_reports_none_of_the_three():
    res, _ = rehearse(Q6, trace="1")
    assert res["correct"] is True
    assert not set(NEW) & set(res["metrics"])
    assert {"plan_host_nodes", "syncs_per_query", "plan_bind_ms",
            "download_ms"} <= set(res["metrics"])
