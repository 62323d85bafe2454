"""The configuration ``tpch_sf10_mesh4`` and its cell ``tpch_sf10_mesh4_q5``
as COMMITTED: the entries and their form, the configuration
file, the new reader ``mesh_rounds_per_query`` on hand-made counters and
on a program that has none (the parent's: nothing, never a zero), the
three mesh readers it shares with ``tpch_sf1_mesh4_q5`` under their
``.sf10`` names, then the cell through the CPU rehearsal on four virtual
devices, which leaves every file of ``benchmark/`` as it was."""

import json
import os

import pytest

from conftest import BENCH, ROOT, rehearse
from test_add_cell import digests
import run
import tpch_sf10_data

CELL = "tpch_sf10_mesh4_q5"
CONFIG = "tpch_sf10_mesh4"
MESH = "shuffle: mesh exchange (`parallel/mesh_exchange.py`, `mesh.py`)"
NEW = {"mesh_exchange_ms.sf10": ("ms/query", "lower", "program_span"),
       "mesh_exchange_padding_pct.sf10": ("%", "lower", "program_counter"),
       "mesh_chip_balance_pct.sf10": ("%", "higher", "device_trace"),
       "mesh_rounds_per_query": ("rounds/query", "lower",
                                 "program_counter")}


def one_line(text):
    return 1 <= len(text) <= 200 and text.isprintable()


def test_entries_as_committed(bench):
    cells = {w["name"]: w for w in bench["workloads"]}
    cell = cells[CELL]
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "q5_closed1", 4)
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) == 2 and 2 * len(four) <= len(bench["workloads"])
    config, = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert config["reduced"] == ["scale"]
    assert config["file"] == "benchmark/configs/tpch_sf10_mesh4.json"
    for text in (config["source"], config["why"], cell["why"]):
        assert one_line(text), text
    for word in ("TPC-H", "rev. 3", "scale factor 10", "Q5",
                 "TpchLikeSpark.scala", "spark-rapids v0.3"):
        assert word in config["source"], word
    for name, (unit, better, source) in NEW.items():
        m, = [m for m in bench["per_layer"] if m["name"] == name]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert (m["unit"], m["better"], m["source"]) == (unit, better, source)
        assert m["layer"] == MESH and m["moves"] == "query_s"
        assert m["workloads"] == [CELL]
        assert os.path.exists(os.path.join(
            BENCH, "metrics", run.base(name) + ".py"))
    # the cell reports the thirteen that stand and its four; the SF1 mesh
    # cell its sixteen, as before
    assert sum(run.applies(m, CELL, bench) for m in bench["per_layer"]) == 17
    assert sum(run.applies(m, "tpch_sf1_mesh4_q5", bench)
               for m in bench["per_layer"]) == 16
    # the new entries stand at the end of their lists
    assert bench["configs"][-1]["name"] == CONFIG
    assert bench["workloads"][-1]["name"] == CELL
    assert [m["name"] for m in bench["per_layer"]][-4:] == list(NEW)


def test_configuration_file_names_its_source_and_cut(bench):
    config, = [c for c in bench["configs"] if c["name"] == CONFIG]
    with open(os.path.join(ROOT, config["file"])) as f:
        held = json.load(f)
    assert held["source"] == config["source"]
    assert held["suite"] == "tpch_sf10_data" and held["chips"] == 4
    assert held["partitions"] == 4 and held["files_per_table"] == 8
    assert held["scale"] == 10.0 and held["reduced"] == ["scale"]
    assert set(held["reduced_why"]) == {"scale"}
    assert "SF100" in held["reduced_why"]["scale"]
    rows = tpch_sf10_data.table_rows(10.0)
    assert held["tables"] == {t: rows[t] for t in tpch_sf10_data.QUERY_COLUMNS
                              ["q5"]}
    assert held["tables"]["lineitem"] == 60_000_000
    with open(os.path.join(BENCH, "configs", "tpch_sf10_resident.json")) as f:
        sf10 = json.load(f)
    for k in ("keys", "precision", "guarantees"):
        assert held[k] == sf10[k], k
    # the default broadcast threshold decides: no key for it
    assert held["conf"] == {
        "spark.rapids.sql.variableFloatAgg.enabled": True,
        "spark.rapids.sql.hasNans": False,
        "spark.rapids.sql.shuffle.transport": "mesh"}
    assert {"generator", "pruned_columns", "variableFloatAgg_hasNans",
            "partitions"} == set(held["assumed"])
    assert "autoBroadcastJoinThreshold" not in json.dumps(held)


def test_mesh_rounds_per_query(monkeypatch):
    from spark_rapids_tpu.monitoring import recorder
    from spark_rapids_tpu.parallel import mesh_exchange
    read = run.metric_reader("mesh_rounds_per_query")
    monkeypatch.setattr(recorder, "counters", lambda: {"collects": 4})
    monkeypatch.setattr(mesh_exchange, "counters", lambda: {
        "meshExchanges": 12, "meshRounds": 64})
    assert read({}) == 16.0
    monkeypatch.setattr(recorder, "counters", lambda: {})
    assert read({}) is None              # the recorder counted no collect
    monkeypatch.setattr(recorder, "counters", lambda: {"collects": 4})
    # a program without the counter (before rounds), or no exchange
    monkeypatch.setattr(mesh_exchange, "counters", lambda: {
        "meshExchanges": 12})
    assert read({}) is None
    monkeypatch.delattr(mesh_exchange, "counters")
    assert read({}) is None


def test_shared_readers_under_their_sf10_names():
    for name in NEW:
        assert run.metric_reader(name) is not None
    assert run.base("mesh_exchange_ms.sf10") == "mesh_exchange_ms"


@pytest.fixture(scope="module")
def untouched():
    """Every file of ``benchmark/`` before the rehearsals below."""
    return digests(BENCH)


def test_committed_cell_untraced(untouched):
    res, p = rehearse(CELL)
    assert res["correct"] is True and res["failed"] == 0
    assert res["device"]["count"] == 4
    assert set(res["metrics"]) == {"query_s", "setup_s"}
    head, = [json.loads(ln) for ln in p.stdout.splitlines()
             if ln.startswith('{"phase": "cell"')]
    assert head["queries"] == ["q5"] and head["config"] == CONFIG


def test_committed_cell_traced(untouched):
    res, _ = rehearse(CELL, trace="1")
    assert res["correct"] is True and res["failed"] == 0
    got = res["metrics"]
    assert got["mesh_rounds_per_query"]["unit"] == "rounds/query"
    assert got["mesh_rounds_per_query"]["value"] >= 1
    assert got["mesh_exchange_ms.sf10"]["value"] > 0
    assert 0 <= got["mesh_exchange_padding_pct.sf10"]["value"] < 100
    # no device trace on a CPU: no device metric
    assert "mesh_chip_balance_pct.sf10" not in got
    assert "device_busy_ms" not in got
    after = digests(BENCH)
    assert {k: after[k] for k in untouched} == untouched
