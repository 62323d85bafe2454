"""The configuration ``tpch_sf10_resident`` and its cells
``tpch_sf10_resident_q3`` / ``_q1`` as COMMITTED (PR 35): the entries, the
suite module's generator (a function of the seed, the source's counts and
key relations, the listed columns alone), its reference under the float32
control, the three per-layer readers on hand-made contexts and on a
program that has none of what they read (the parent's: nothing, never a
zero), then both cells through the CPU rehearsal, which leaves every file
of ``benchmark/`` as it was."""

import datetime
import json
import os
import types

import numpy as np
import pyarrow as pa
import pyarrow.parquet as papq
import pytest

from conftest import BENCH, ROOT, rehearse
from test_add_cell import digests
import control
import run
import tpch_data
import tpch_sf10_data

Q3 = "tpch_sf10_resident_q3"
Q1 = "tpch_sf10_resident_q1"
CACHE = "ingest: scan cache (`io/scan.py`)"
NEW = {"scan_cache_refill_mb": (CACHE, [Q3, Q1], "MB/query"),
       "scan_cache_resident_gb": (CACHE, [Q3, Q1], "GB"),
       "join_build_ms": ("device execs: join (`ops/join.py`)", [Q3],
                         "ms/query")}
THREE = ["customer", "lineitem", "orders"]


def lines(p, phase):
    return [json.loads(ln) for ln in p.stdout.splitlines()
            if ln.startswith('{"phase": "%s"' % phase)]


def test_entries_as_committed(bench):
    cells = {w["name"]: w for w in bench["workloads"]}
    for name, traffic in ((Q3, "q3_closed1"), (Q1, "q1_closed1")):
        c = cells[name]
        assert (c["config"], c["traffic"], c["chips"]) == \
            ("tpch_sf10_resident", traffic, 1)
        assert len(c["why"]) <= 200
    config, = [c for c in bench["configs"]
               if c["name"] == "tpch_sf10_resident"]
    assert config["reduced"] == ["scale"]
    # the contract's form: 1 to 200 printable characters, on one line
    for text in (config["source"], config["why"], cells[Q3]["why"],
                 cells[Q1]["why"]):
        assert 1 <= len(text) <= 200 and text.isprintable(), text
    for word in ("TPC-H", "rev. 3", "scale factor 10", "Q3", "Q1",
                 "TpchLikeSpark.scala", "spark-rapids v0.3"):
        assert word in config["source"], word
    with open(os.path.join(ROOT, config["file"])) as f:
        held = json.load(f)
    assert held["source"] == config["source"]
    assert held["suite"] == "tpch_sf10_data" and held["chips"] == 1
    assert held["scale"] == 10.0 and held["reduced"] == ["scale"]
    assert held["files_per_table"] == 8
    rows = tpch_sf10_data.table_rows(10.0)
    assert held["tables"] == {t: rows[t] for t in THREE}
    with open(os.path.join(BENCH, "configs", "tpch_sf1_resident.json")) as f:
        sf1 = json.load(f)
    # keys, precision and guarantees word for word, and no conf beyond
    # the two statements about the data
    for k in ("keys", "precision", "guarantees", "conf", "deployment"):
        assert held[k] == sf1[k], k
    assert set(held["conf"]) == {
        "spark.rapids.sql.variableFloatAgg.enabled",
        "spark.rapids.sql.hasNans"}
    assert {"generator", "pruned_columns"} <= set(held["assumed"])
    for name, (layer, cells_of, unit) in NEW.items():
        m, = [m for m in bench["per_layer"] if m["name"] == name]
        assert m["workloads"] == cells_of and m["moves"] == "query_s"
        assert (m["layer"], m["unit"], m["better"]) == (layer, unit, "lower")
        assert os.path.exists(os.path.join(BENCH, "metrics", name + ".py"))
    # q3 reports the thirteen that stand and the three; q1 two of them
    assert sum(run.applies(m, Q3, bench) for m in bench["per_layer"]) == 16
    assert sum(run.applies(m, Q1, bench) for m in bench["per_layer"]) == 15
    # the new entries stand at the end of their lists
    assert [w["name"] for w in bench["workloads"]][-2:] == [Q3, Q1]
    assert [m["name"] for m in bench["per_layer"]][-3:] == list(NEW)


def test_the_suite_is_tpch_datas_but_for_the_generator():
    for name in ("QUERIES", "QUERY_COLUMNS", "SET_COMPARE", "table_rows",
                 "_paths"):
        assert getattr(tpch_sf10_data, name) is getattr(tpch_data, name)
    assert tpch_sf10_data.generate is not tpch_data.generate
    assert tpch_sf10_data.table_rows(10.0)["orders"] == 15_000_000
    # every table some query of the suite reads, with those columns alone
    assert set(tpch_sf10_data.COLUMNS) == {
        t for q in tpch_data.QUERY_COLUMNS.values() for t in q}
    assert tpch_sf10_data.COLUMNS["orders"] == [
        "o_custkey", "o_orderdate", "o_orderkey", "o_shippriority"]
    assert len(tpch_sf10_data.COLUMNS["lineitem"]) == 9


@pytest.fixture(scope="module")
def tables(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("sf10_gen"))
    rows = tpch_sf10_data.generate(d, scale=0.02, seed=2**31 + 7,
                                   files_per_table=4, tables=THREE)
    return d, rows, {t: papq.read_table(os.path.join(d, t)).to_pandas()
                     for t in THREE}


def test_generate_is_a_function_of_the_seed(tables, tmp_path):
    d, rows, _ = tables
    again, other = str(tmp_path / "again"), str(tmp_path / "other")
    assert tpch_sf10_data.generate(again, scale=0.02, seed=2**31 + 7,
                                   files_per_table=4, tables=THREE) == rows
    tpch_sf10_data.generate(other, scale=0.02, seed=2**31 + 8,
                            files_per_table=4, tables=["orders"])
    for t in THREE:
        files = sorted(os.listdir(os.path.join(d, t)))
        assert files == sorted(os.listdir(os.path.join(again, t)))
        for f in files:
            assert papq.read_table(os.path.join(d, t, f)).equals(
                papq.read_table(os.path.join(again, t, f))), (t, f)
    assert sorted(os.listdir(other)) == ["orders"]
    assert not papq.read_table(os.path.join(other, "orders")).equals(
        papq.read_table(os.path.join(d, "orders")))


def test_counts_columns_and_types(tables):
    d, rows, t = tables
    want = tpch_sf10_data.table_rows(0.02)
    assert rows["orders"] == want["orders"] == len(t["orders"])
    assert rows["customer"] == want["customer"] == len(t["customer"])
    assert rows["lineitem"] == len(t["lineitem"])
    # 1..7 lines an order, 4 the mean
    assert abs(rows["lineitem"] / want["lineitem"] - 1) < 0.02
    assert len(os.listdir(os.path.join(d, "lineitem"))) == 4
    for name in THREE:
        schema = papq.read_schema(tpch_sf10_data._paths(d, name)[0])
        assert sorted(schema.names) == tpch_sf10_data.COLUMNS[name]
    # the types tpch_data.generate writes
    ref = str(d) + "_ref"
    tpch_data.generate(ref, scale=0.001, seed=1, tables=THREE)
    for name in THREE:
        theirs = papq.read_schema(tpch_data._paths(ref, name)[0])
        mine = papq.read_schema(tpch_sf10_data._paths(d, name)[0])
        for col in mine.names:
            assert mine.field(col).type == theirs.field(col).type, col


def test_key_relations_and_distributions(tables):
    _, _, t = tables
    orders, li, cust = t["orders"], t["lineitem"], t["customer"]
    assert orders.o_orderkey.tolist() == list(range(1, len(orders) + 1))
    assert cust.c_custkey.tolist() == list(range(1, len(cust) + 1))
    per_order = li.groupby("l_orderkey").size()
    assert set(per_order.index) == set(orders.o_orderkey)   # every order
    assert per_order.min() == 1 and per_order.max() == 7
    # a third of the customers have no order
    assert orders.o_custkey.min() >= 1
    assert orders.o_custkey.max() < len(cust) * 2 // 3
    # dates: order < ship <= order + 121, flags by date
    j = li.merge(orders, left_on="l_orderkey", right_on="o_orderkey")
    ship = (j.l_shipdate - j.o_orderdate).map(lambda x: x.days)
    assert ship.min() >= 1 and ship.max() <= 121
    assert orders.o_orderdate.min() >= datetime.date(1992, 1, 1)
    assert orders.o_orderdate.max() <= datetime.date(1998, 8, 1)
    cutoff = datetime.date(1995, 6, 17)
    assert ((li.l_linestatus == "F") == (li.l_shipdate <= cutoff)).all()
    assert set(li.l_returnflag[li.l_shipdate > cutoff]) == {"N"}
    assert {"R", "A"} <= set(li.l_returnflag)
    assert set(np.round(li.l_discount * 100).astype(int)) == set(range(11))
    assert set(np.round(li.l_tax * 100).astype(int)) == set(range(9))
    assert li.l_quantity.min() == 1 and li.l_quantity.max() == 50
    assert 900 <= li.l_extendedprice.min() and \
        li.l_extendedprice.max() <= 105_000
    assert (np.round(li.l_extendedprice, 2) == li.l_extendedprice).all()
    assert set(cust.c_mktsegment) == set(tpch_data.SEGMENTS)
    assert (orders.o_shippriority == 0).all()


def test_every_listed_query_has_a_reference(tmp_path):
    d = str(tmp_path)
    tpch_sf10_data.generate(d, scale=0.01, seed=1)
    assert sorted(os.listdir(d)) == sorted(tpch_sf10_data.COLUMNS)
    for q in tpch_sf10_data.QUERY_COLUMNS:
        assert tpch_sf10_data.pandas_query(q, d)
    with pytest.raises(KeyError):
        tpch_sf10_data.generate(d, scale=0.01, seed=1, tables=["part"])


def test_float32_reference_is_not_correct(capsys):
    """``control.py`` swaps the SUITE's ``pa``; the reference is
    ``tpch_data``'s: ``pandas_query`` has to hand it over."""
    assert tpch_data.pa is pa
    for cell in (Q1, Q3):
        passed = control.main(["--workload", cell, "--seeds", "5,6",
                               "--scale", "0.05"])
        out = [json.loads(ln) for ln in
               capsys.readouterr().out.strip().splitlines()]
        assert passed == 0 and len(out) == 2
        for ln in out:
            assert not ln["correct"]
            assert ln["answers_wrong"] or ln["control_gap"] > 3 * ln["limit"]
    assert tpch_data.pa is pa and tpch_sf10_data.pa is pa


def recorder_ctx(queries, **category_ms):
    return {"recorder": types.SimpleNamespace(
        queries=queries, category_ms=category_ms, syncs=0)}


def test_join_build_ms():
    read = run.metric_reader("join_build_ms")
    ctx = recorder_ctx(4, **{"join-build": 10.0, "join-probe": 99.0})
    assert read(ctx) == 2.5
    assert read(recorder_ctx(4, **{"join-build": 0.0})) == 0.0
    # a program without the span (the parent of PR 35): nothing, no zero
    assert read(recorder_ctx(4, **{"join-probe": 99.0})) is None
    assert read(recorder_ctx(0, **{"join-build": 10.0})) is None


def test_scan_cache_readers(monkeypatch):
    from spark_rapids_tpu.io import scan
    from spark_rapids_tpu.monitoring import recorder
    refill = run.metric_reader("scan_cache_refill_mb")
    resident = run.metric_reader("scan_cache_resident_gb")
    monkeypatch.setattr(recorder, "counters", lambda: {"collects": 4})
    monkeypatch.setattr(scan, "counters", lambda: {
        "scanCacheRefillBytes": 0, "scanCacheResidentBytes": 4_040_000_000},
        raising=False)
    assert refill({}) == 0.0             # resident: a real zero
    assert resident({}) == pytest.approx(4.04)
    monkeypatch.setattr(scan, "counters", lambda: {
        "scanCacheRefillBytes": 268_000_000, "scanCacheResidentBytes": 0})
    assert refill({}) == pytest.approx(67.0)
    assert resident({}) is None          # a configuration without a cache
    monkeypatch.setattr(recorder, "counters", lambda: {})
    assert refill({}) is None            # the recorder counted no collect
    # a program without the counters (the parent of PR 35)
    monkeypatch.delattr(scan, "counters")
    monkeypatch.setattr(recorder, "counters", lambda: {"collects": 4})
    assert refill({}) is None and resident({}) is None


@pytest.fixture(scope="module")
def untouched():
    """Every file of ``benchmark/`` before the rehearsals below."""
    return digests(BENCH)


@pytest.mark.parametrize("cell,query", [(Q3, "q3"), (Q1, "q1")])
def test_committed_cell_untraced(cell, query, untouched):
    res, p = rehearse(cell)
    assert res["correct"] is True and res["failed"] == 0
    assert res["device"]["count"] == 1
    assert set(res["metrics"]) == {"query_s", "setup_s"}
    head, = lines(p, "cell")
    assert head["queries"] == [query] and head["config"] == \
        "tpch_sf10_resident"
    assert head["conf"] == {
        "spark.rapids.sql.variableFloatAgg.enabled": True,
        "spark.rapids.sql.hasNans": False}
    gen, = lines(p, "datagen")
    assert set(gen["rows"]) == set(tpch_data.QUERY_COLUMNS[query])
    window, = lines(p, "window")
    assert window["programs_compiled_in_window"] == 0


@pytest.mark.parametrize("cell", [Q3, Q1])
def test_committed_cell_traced(cell, untouched):
    res, _ = rehearse(cell, trace="1")
    assert res["correct"] is True
    got = res["metrics"]
    assert got["scan_cache_refill_mb"] == {"value": 0.0, "unit": "MB/query"}
    assert 0 < got["scan_cache_resident_gb"]["value"] < 0.1
    assert ("join_build_ms" in got) == (cell == Q3)
    if cell == Q3:
        assert got["join_build_ms"]["value"] > 0
    assert got["plan_host_nodes"]["value"] == 0
    # no device trace on a CPU: no device metric
    assert "device_busy_ms" not in got and "scan_hbm_roofline" not in got
    # and no file the benchmark had was touched by any of it
    after = digests(BENCH)
    assert {k: after[k] for k in untouched} == untouched
