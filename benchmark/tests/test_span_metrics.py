"""The four per-layer metrics that read the flight recorder's span
categories and the idle-gap labels (PR 25): each reader on a hand-made
``ctx``, then both cells through the traced CPU rehearsal."""

import json
import os
import types

import pytest

from conftest import ROOT, rehearse
import run

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]


def recorder_ctx(queries, **category_ms):
    return {"recorder": types.SimpleNamespace(
        queries=queries, category_ms=category_ms, syncs=0)}


def trace_ctx(gaps, queries=4):
    return {"trace": {"queries": queries, "window_s": 4.0,
                      "idle_gaps": gaps}}


@pytest.mark.parametrize("name,category", [("plan_bind_ms", "planning"),
                                           ("download_ms", "download")])
def test_category_readers(name, category):
    read = run.metric_reader(name)
    assert read(recorder_ctx(4, **{category: 10.0, "sync": 99.0})) == 2.5
    # a program without the category (the parent of PR 25 has no
    # ``download``) reports nothing, and no query is no rate
    assert read(recorder_ctx(4, sync=99.0)) is None
    assert read(recorder_ctx(0, **{category: 10.0})) is None


def test_window_compile_ms():
    from spark_rapids_tpu.monitoring import recorder
    read = run.metric_reader("window_compile_ms")
    recorder.configure(True)
    try:
        assert "compile" in recorder.snapshot()["listeners"]
        assert read(recorder_ctx(4, download=8.0, compile=6.0)) == 1.5
        # nothing compiled, and a cell that downloads nothing: a zero
        assert read(recorder_ctx(4, download=8.0)) == 0.0
        assert read(recorder_ctx(4)) == 0.0
        assert read(recorder_ctx(0, download=8.0)) is None
    finally:
        recorder.configure(False)
    # a recorder that does not listen to the compiler (off, or a
    # program from before PR 25) reports nothing, not a zero
    assert recorder.snapshot()["listeners"] == []
    assert read(recorder_ctx(4, download=8.0, compile=6.0)) is None


def test_idle_unattributed_ms():
    read = run.metric_reader("idle_unattributed_ms")
    gaps = [["shuffle:exchange-materialize", 0.2], ["bench:query", 0.1]]
    assert read(trace_ctx(gaps)) == pytest.approx(25.0)
    assert read(trace_ctx(gaps[:1])) == 0.0     # not among the ten
    assert read({"trace": None}) is None        # no device trace
    assert read(trace_ctx(gaps, queries=0)) is None


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_reports_the_span_metrics(cell):
    res, _ = rehearse(cell, trace="1")
    got = res["metrics"]
    assert got["plan_bind_ms"]["value"] > 0
    assert got["download_ms"]["value"] > 0
    assert got["window_compile_ms"]["value"] == 0.0
    assert got["window_compile_ms"]["unit"] == "ms/query"
    assert "idle_unattributed_ms" not in got    # no device trace on a CPU
