"""A later PR adds a cell with data files and entries alone: here the
four-chip ``tpch_sf1_mesh4_q5`` (PERF.md, Open questions 1) is added to a
COPY of the benchmark — a configuration file, a traffic file, and the
entries in ``BENCHMARK.json`` — with no copied file edited, and runs in
the CPU rehearsal on four virtual devices."""

import hashlib
import json
import os
import shutil

from conftest import BENCH, ROOT, rehearse


def digests(top):
    out = {}
    for d, _, files in os.walk(top):
        if "__pycache__" in d:
            continue
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, top)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_mesh4_q5_is_added_as_data(tmp_path):
    root = str(tmp_path / "checkout")
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = digests(os.path.join(root, "benchmark"))

    with open(os.path.join(root, "benchmark", "configs",
                           "tpch_sf1_resident.json")) as f:
        config = json.load(f)
    config.update(
        chips=4,
        source="TPC-H specification rev. 3, scale factor 1, Q5, every "
               "join shuffled over a 2x2 mesh of one host",
        deployment="one process over four chips, shuffle over the mesh",
        conf={**config["conf"],
              "spark.rapids.sql.shuffle.transport": "mesh",
              "spark.rapids.sql.autoBroadcastJoinThreshold": -1})
    with open(os.path.join(root, "benchmark", "configs",
                           "tpch_sf1_mesh4.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(root, "benchmark", "traffic",
                           "q5_closed1.json"), "w") as f:
        json.dump({"loop": "closed", "clients": 1,
                   "mix": [{"query": "q5", "weight": 1}]}, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tpch_sf1_mesh4", "source": config["source"],
        "file": "benchmark/configs/tpch_sf1_mesh4.json",
        "reduced": ["scale"], "why": "four chips, one process"})
    bench["workloads"].append({
        "name": "tpch_sf1_mesh4_q5", "config": "tpch_sf1_mesh4",
        "traffic": "q5_closed1", "chips": 4,
        "why": "Q5 with every join shuffled over the mesh transport"})
    # query_s and setup_s have no ``workloads`` key: the cell reports both
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    res, p = rehearse("tpch_sf1_mesh4_q5", root=root, pythonpath=ROOT)
    assert res["correct"] is True and res["attempted"] >= 1
    assert res["device"]["count"] == 4
    assert set(res["metrics"]) == {"query_s", "setup_s"}
    cell = json.loads(p.stdout.splitlines()[0])
    assert cell["conf"]["spark.rapids.sql.shuffle.transport"] == "mesh"
    assert cell["queries"] == ["q5"] and cell["device_count"] == 4
    res, _ = rehearse("tpch_sf1_mesh4_q5", root=root, pythonpath=ROOT,
                      trace="1")
    assert res["correct"] is True
    assert {"plan_host_nodes", "syncs_per_query"} <= set(res["metrics"])

    after = digests(os.path.join(root, "benchmark"))
    after = {k: v for k, v in after.items() if k in before}
    assert after == before                   # no copied file was edited
