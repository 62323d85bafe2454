"""``trace_reduce.py`` on a recorded trace of a v5e (a few KB, made by
``record_trace.py`` on the chip, PR 24) and on small made-up inputs."""

import os

import pytest

import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TINY = os.path.join(DATA, "tpu_v5e_tiny.xplane.pb")


def test_union_merges_what_overlaps():
    assert trace_reduce.union([(5, 6), (0, 2), (1, 3), (3, 4), (5.5, 5.7)]) \
        == [(0, 4), (5, 6)]
    assert trace_reduce.union([]) == []


def test_labels_take_the_innermost_annotation():
    anns = [("bench:query", 0, 100), ("AggExec:totalTime", 10, 40),
            ("PjitFunction(f)", 12, 20), ("SortExec:totalTime", 50, 90),
            ("bench:query", 200, 300)]
    got = trace_reduce._labels(anns, [5, 15, 30, 45, 60, 150, 250, 400])
    assert got == ["bench:query", "PjitFunction(f)", "AggExec:totalTime",
                   "bench:query", "SortExec:totalTime",
                   "outside bench:query", "bench:query",
                   "outside bench:query"]


def test_recorded_v5e_trace():
    r = trace_reduce.reduce_trace(TINY)
    assert r["queries"] == 3
    assert list(r["devices"]) == ["/device:TPU:0"] == [r["busiest"]]
    # three sleeps of 2 ms inside the window: the chip is mostly idle
    assert 0 < r["busy_s"] < 0.5 * r["window_s"]
    assert r["window_s"] == pytest.approx(EXPECT["window_s"], rel=1e-9)
    assert r["busy_s"] == pytest.approx(EXPECT["busy_s"], rel=1e-9)
    assert r["device_ops"][0][0] == EXPECT["top_op"]
    assert sum(s for _, s in r["device_ops"]) >= r["busy_s"] * 0.99  # top 10 of 14
    labels = [name for name, _ in r["idle_gaps"]]
    assert "bench:query" in labels           # the sleeps
    idle = sum(s for _, s in r["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)


def test_a_trace_without_queries_reduces_to_nothing(tmp_path):
    # the same file, read for an annotation it does not hold
    old = trace_reduce.QUERY_ANNOTATION
    trace_reduce.QUERY_ANNOTATION = "no:such"
    try:
        assert trace_reduce.reduce_trace(TINY) is None
    finally:
        trace_reduce.QUERY_ANNOTATION = old


def test_short_name_keeps_program_op_and_result_type():
    hlo = ("%fusion.24 = u32[786432]{0:T(1024)S(1)} fusion(u32[786432]"
           "{0:T(1024)} %copy-done.1), kind=kCustom, calls=%fused.4")
    assert trace_reduce.short_name(hlo, "jit__update_batch(123)") == \
        "jit__update_batch/fusion.24 u32[786432]"
    assert trace_reduce.short_name("Some host event") == "Some host event"


# Read from record_trace.py's own output on the chip (my chip run, PR 24).
EXPECT = {"window_s": 0.0120869, "busy_s": 0.000125455,
          "top_op": "jit__lambda/sort.6 (f32[65536], s32[65536])"}
