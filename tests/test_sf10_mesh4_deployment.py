"""The four-chip SF10 deployment ``tpch_sf10_mesh4`` on the CPU:
TPC-H Q5 over a mesh of FOUR virtual devices with the ``conf`` of the
committed configuration file (``benchmark/configs/tpch_sf10_mesh4.json``)
and no broadcast threshold of its own, at a small scale from a seed.

The planner decides each join's strategy from the parquet footers, so the
footers' sizes are read as SF10's (scaled by 10 / scale): the default
64 MiB threshold then broadcasts customer and supplier and shuffles
lineitem x orders over the mesh, as it does at SF10. The scan's batches
and the exchange's round bound are lowered together so that lineitem's
exchange goes over in several rounds, as SF10's does. The rows are held
against the benchmark's plain float64 reference under ``compare.judge``'s
rule."""

import importlib.util
import json
import os

import pytest

from spark_rapids_tpu.api.dataframe import TpuSession
from spark_rapids_tpu.ops.join import (BroadcastHashJoinExec,
                                       ShuffledHashJoinExec)
from spark_rapids_tpu.parallel import mesh as M
from spark_rapids_tpu.parallel import mesh_exchange as MX
from spark_rapids_tpu.plan import logical as L
from spark_rapids_tpu.plan import pruning

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
MESH = 4
SCALE = 0.05
SEED = 2147483943
SCAN_ROWS = 8192            # the scan's batches, lowered from 1,048,576
ROUND_ROWS = 2 * SCAN_ROWS  # two scan batches a round at the most


def _bench_module(name):
    """A module of ``benchmark/`` by path, with ``benchmark/`` put on
    ``sys.path`` for the imports of its own (``tpch_sf10_data`` imports
    ``tpch_data``)."""
    import sys
    if BENCH not in sys.path:
        sys.path.append(BENCH)
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{name}", os.path.join(BENCH, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _execs(df):
    out = []

    def walk(node):
        out.append(node)
        for c in node.children:
            walk(c)
    walk(df._physical().root)
    return out


def _names(exec_):
    return {n for n, _ in exec_.schema}


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    import jax
    if len(jax.devices()) < MESH:
        pytest.skip(f"needs {MESH} devices")
    data, compare = _bench_module("tpch_sf10_data"), _bench_module("compare")
    with open(os.path.join(BENCH, "configs", "tpch_sf10_mesh4.json")) as f:
        config = json.load(f)
    data_dir = str(tmp_path_factory.mktemp("sf10_mesh4_data"))
    data.generate(data_dir, scale=SCALE, seed=SEED,
                  files_per_table=config["files_per_table"],
                  tables=sorted(data.QUERY_COLUMNS["q5"]))
    real = pruning.estimate_bytes

    def as_sf10(plan):
        est = real(plan)
        if isinstance(plan, L.FileScan) and est is not None:
            return int(est * config["scale"] / SCALE)
        return est

    mp = pytest.MonkeyPatch()
    mp.setattr(pruning, "estimate_bytes", as_sf10)
    mp.setattr(MX, "mesh_size", lambda: MESH)
    mp.setattr(MX, "mesh_for", lambda ctx: ctx.cache.setdefault(
        "mesh:singleton", M.make_mesh(MESH)))
    mp.setattr(MX, "MESH_ROUND_ROWS", ROUND_ROWS)
    try:
        s = TpuSession()
        for k, v in config["conf"].items():
            s.set(k, v)
        s.set("spark.rapids.sql.reader.batchSizeRows", SCAN_ROWS)
        MX.reset_counters()
        df = data.QUERIES["q5"](s, data_dir)
        rows = df.collect()
        yield {"config": config, "execs": _execs(df), "rows": rows,
               "exchanges": [v for k, v in df.metrics().items()
                             if k.startswith("MeshExchangeExec")],
               "process": MX.counters(), "compare": compare,
               "data": data,
               "reference": data.pandas_query("q5", data_dir)}
    finally:
        mp.undo()
        MX.reset_counters()


def test_configuration_file_is_the_deployment(cell):
    config = cell["config"]
    assert config["conf"] == {
        "spark.rapids.sql.variableFloatAgg.enabled": True,
        "spark.rapids.sql.hasNans": False,
        "spark.rapids.sql.shuffle.transport": "mesh"}
    assert config["chips"] == MESH == config["partitions"]
    assert config["suite"] == "tpch_sf10_data"
    assert set(config["tables"]) == set(cell["data"].QUERY_COLUMNS["q5"])
    rows = cell["data"].table_rows(config["scale"])
    assert config["tables"] == {t: rows[t] for t in config["tables"]}


def test_rows_equal_the_plain_reference(cell):
    compare, data = cell["compare"], cell["data"]
    verdict = compare.judge([{"query": "q5", "rows": cell["rows"]}],
                            {"q5": cell["reference"]}, data.SET_COMPARE,
                            sent=1)
    assert verdict["correct"], verdict
    assert len(cell["reference"]) > 0


def test_order_book_join_shuffled_over_the_mesh_the_rest_broadcast(cell):
    execs = cell["execs"]
    shuffled = [e for e in execs if type(e) is ShuffledHashJoinExec]
    assert len(shuffled) == 1
    join, = shuffled
    assert all(isinstance(c, MX.MeshExchangeExec) for c in join.children)
    assert "l_orderkey" in _names(join.children[0])
    assert "o_orderkey" in _names(join.children[1])
    broadcast = [e for e in execs if isinstance(e, BroadcastHashJoinExec)]
    builds = [_names(e.children[1]) for e in broadcast]
    assert any("c_custkey" in b for b in builds), builds
    assert any("s_suppkey" in b for b in builds), builds
    meshed = [e for e in execs if isinstance(e, MX.MeshExchangeExec)]
    # the join's two sides and the aggregate's
    assert len(meshed) == 3
    assert all(e.partitioning.num_partitions == MESH for e in meshed)


def test_lineitems_exchange_goes_over_in_rounds(cell):
    rounds = sorted(v["meshRounds"] for v in cell["exchanges"])
    assert len(rounds) == 3 and rounds[-1] >= 4
    c = cell["process"]
    assert c["meshRounds"] == sum(rounds) > c["meshExchanges"] == 3
    assert c["meshRoundRowsMax"] <= ROUND_ROWS
    assert c["meshHandoutPuts"] == c["meshLandPuts"] == c["meshRounds"]
    for v in cell["exchanges"]:
        assert v["meshExchanges"] == 1 and v["meshShardDevices"] == MESH
        assert not v.get("meshDegrades")
