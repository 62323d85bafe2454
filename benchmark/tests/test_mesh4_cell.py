"""The four-chip cell ``tpch_sf1_mesh4_q5`` as COMMITTED (PR 27): its
three per-layer readers on hand-made contexts, on what a traced run on
four v5e chips printed (``data/mesh4_q5_traced.json``) and on a program
that has none of it (the parent's: nothing, never a zero), then the cell
itself through the CPU rehearsal on four virtual devices."""

import json
import os
import types

import pytest

from conftest import ROOT, rehearse
import run

CELL = "tpch_sf1_mesh4_q5"
NEW = ("mesh_exchange_ms", "mesh_chip_balance_pct",
       "mesh_exchange_padding_pct")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def recorder_ctx(queries, **category_ms):
    return {"recorder": types.SimpleNamespace(
        queries=queries, category_ms=category_ms, syncs=0)}


def test_entries_as_committed(bench):
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("tpch_sf1_mesh4", "q5_closed1", 4)
    config, = [c for c in bench["configs"] if c["name"] == cell["config"]]
    assert config["reduced"] == ["scale"] and len(config["source"]) <= 200
    # a path that test_add_cell.py does not write into its copy
    assert config["file"] != "benchmark/configs/tpch_sf1_mesh4.json"
    assert os.path.exists(os.path.join(ROOT, config["file"]))
    layers = set()
    for name in NEW:
        m, = [m for m in bench["per_layer"] if m["name"] == name]
        assert m["workloads"] == [CELL] and m["moves"] == "query_s"
        layers.add(m["layer"])
    assert len(layers) == 1
    # the cell reports the thirteen that stand, and the three
    assert sum(run.applies(m, CELL, bench)
               for m in bench["per_layer"]) == 16
    for other in ("tpch_sf1_resident_q1", "tpch_sf1_resident_q3"):
        assert sum(run.applies(m, other, bench)
                   for m in bench["per_layer"]) == 13


def test_traffic_file_is_what_test_add_cell_writes():
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "q5_closed1.json")) as f:
        assert f.read() == json.dumps(
            {"loop": "closed", "clients": 1,
             "mix": [{"query": "q5", "weight": 1}]})


def test_mesh_exchange_ms():
    read = run.metric_reader("mesh_exchange_ms")
    assert read(recorder_ctx(4, **{"mesh-exchange": 10.0,
                                   "shuffle": 99.0})) == 2.5
    # a program without the spans (the parent of PR 27): nothing, no zero
    assert read(recorder_ctx(4, shuffle=99.0)) is None
    assert read(recorder_ctx(4)) is None
    assert read(recorder_ctx(0, **{"mesh-exchange": 10.0})) is None


@pytest.mark.parametrize("devices,want", [
    ({"/device:TPU:0": 2.0, "/device:TPU:1": 0.1, "/device:TPU:2": 0.2,
      "/device:TPU:3": 0.4}, 5.0),
    ({"/device:TPU:0": 1.5, "/device:TPU:1": 1.5}, 100.0),
    ({"/device:TPU:0": 2.0, "/device:TPU:1": 0.0}, 0.0),
    ({"/device:TPU:0": 2.0}, None),             # one chip: no balance
    ({"/device:TPU:0": 0.0, "/device:TPU:1": 0.0}, None),   # none busy
])
def test_mesh_chip_balance_pct(devices, want):
    read = run.metric_reader("mesh_chip_balance_pct")
    got = read({"trace": {"devices": devices, "busiest": "/device:TPU:0",
                          "queries": 1}})
    assert got == (pytest.approx(want) if want is not None else None)


def test_mesh_chip_balance_pct_without_a_device_trace():
    assert run.metric_reader("mesh_chip_balance_pct")({"trace": None}) \
        is None


def test_mesh_exchange_padding_pct(monkeypatch):
    from spark_rapids_tpu.parallel import mesh_exchange
    read = run.metric_reader("mesh_exchange_padding_pct")
    monkeypatch.setattr(mesh_exchange, "counters", lambda: {
        "meshExchanges": 2, "meshLiveBytes": 250, "meshWireBytes": 1000})
    assert read({}) == pytest.approx(75.0)
    monkeypatch.setattr(mesh_exchange, "counters", lambda: {
        "meshLiveBytes": 1000, "meshWireBytes": 1000})
    assert read({}) == 0.0                       # a real zero: no padding
    # no exchange ran (a one-chip cell), or a program without the counter
    monkeypatch.setattr(mesh_exchange, "counters", lambda: {})
    assert read({}) is None
    monkeypatch.delattr(mesh_exchange, "counters")
    assert read({}) is None


def test_readers_on_the_recorded_chip_run():
    """What the traced run of the cell printed on four v5e chips (my chip
    run, PR 27): the balance is the reduction's least busy chip over its
    busiest, and the three metrics are there with sense in them."""
    with open(os.path.join(DATA, "mesh4_q5_traced.json")) as f:
        rec = json.load(f)
    reduction, metrics = rec["reduction"], rec["metrics"]
    assert len(reduction["devices"]) == 4
    got = run.metric_reader("mesh_chip_balance_pct")({"trace": reduction})
    assert got == pytest.approx(metrics["mesh_chip_balance_pct"]["value"])
    assert 0 < got <= 100
    assert run.metric_reader("device_busy_ms")({"trace": reduction}) == \
        pytest.approx(metrics["device_busy_ms"]["value"])
    assert 0 <= metrics["mesh_exchange_padding_pct"]["value"] < 100
    # the spans' sum is a time inside the query's
    assert 0 < metrics["mesh_exchange_ms"]["value"] < \
        1e3 * reduction["window_s"] / reduction["queries"]


def lines(p, phase):
    return [json.loads(ln) for ln in p.stdout.splitlines()
            if ln.startswith('{"phase": "%s"' % phase)]


def test_committed_cell_untraced():
    res, p = rehearse(CELL)
    assert res["correct"] is True and res["failed"] == 0
    assert res["device"]["count"] == 4
    assert set(res["metrics"]) == {"query_s", "setup_s"}
    cell, = lines(p, "cell")
    assert cell["config"] == "tpch_sf1_mesh4" and cell["queries"] == ["q5"]
    assert cell["conf"] == {
        "spark.rapids.sql.variableFloatAgg.enabled": True,
        "spark.rapids.sql.hasNans": False,
        "spark.rapids.sql.shuffle.transport": "mesh",
        "spark.rapids.sql.autoBroadcastJoinThreshold": -1}
    window, = lines(p, "window")
    assert window["programs_compiled_in_window"] == 0


def test_committed_cell_traced():
    res, p = rehearse(CELL, trace="1")
    assert res["correct"] is True
    assert res["device"]["count"] == 4
    got = res["metrics"]
    assert got["mesh_exchange_ms"]["value"] > 0
    assert got["mesh_exchange_ms"]["unit"] == "ms/query"
    assert 0 <= got["mesh_exchange_padding_pct"]["value"] < 100
    # no device trace on a CPU: no device metric, this one included
    assert "mesh_chip_balance_pct" not in got
    assert "device_busy_ms" not in got
    assert got["plan_host_nodes"]["value"] == 0
    # the spans are a part of the query, not the whole of it
    window, = lines(p, "window")
    assert got["mesh_exchange_ms"]["value"] < 1e3 * window["query_s"]
