"""Every cell of BENCHMARK.json through ``run.py --rehearse-cpu``: the
whole control flow of a run on the CPU backend, with the result line the
contract fixes. No chip: ``run.py`` without the flag has to refuse."""

import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT, rehearse
import run

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    _BENCH = json.load(_f)
CELLS = [w["name"] for w in _BENCH["workloads"]]


def names(metrics, cell):
    return {m["name"] for m in metrics if run.applies(m, cell, _BENCH)}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_untraced(cell, bench):
    res, p = rehearse(cell)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert set(res["metrics"]) == names(bench["end_to_end"], cell)
    units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
    for k, m in res["metrics"].items():
        assert m["unit"] == units[k] and m["value"] > 0
    assert res["device"]["platform"] == "cpu" and "rehearsal" in res
    # every number compared, beside its limit, ends standard error
    tail = p.stderr.strip().splitlines()[-4:]
    for k, c in res["checks"].items():
        assert any(ln.startswith(f"check {k}: value=") for ln in tail)
        assert c["value"] <= c["limit"]
    window = [json.loads(ln) for ln in p.stdout.splitlines()
              if ln.startswith('{"phase": "window"')][0]
    assert window["programs_compiled_in_window"] == 0
    assert window["queries"] == res["attempted"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_traced(cell, bench, tmp_path):
    res, p = rehearse(cell, "--keep-trace", str(tmp_path), trace="1")
    assert res["correct"] is True
    got = set(res["metrics"])
    # on the CPU nothing is read from the device trace or its memory
    host = {m["name"] for m in bench["per_layer"]
            if m["source"] != "device_trace"
            and run.base(m["name"]) != "hbm_peak_gb"}
    assert got == host & names(bench["per_layer"], cell)
    assert "busy_s" not in res["device"]
    trace = [json.loads(ln) for ln in p.stdout.splitlines()
             if ln.startswith('{"phase": "trace"')][0]
    assert trace["platform"] == "cpu"
    assert trace["reduction"]["queries"] >= 1
    assert 0 < trace["reduction"]["busy_s"] <= \
        trace["reduction"]["window_s"]
    assert any(f.endswith(".xplane.pb") for f in os.listdir(tmp_path))


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=600)
    assert p.returncode != 0 and p.stdout.strip() == ""
