"""``scripts/trace_ops.py``: device time by XLA operation (PR 34).

The script reads a kept profiler trace; its reduction is pure functions
over (name, start, end) events, held here on a small synthetic device
line shaped like the ``XLA Ops`` line of a v5e: HLO text as event names,
the operations inside a ``conditional`` and a ``while`` nested in time
under it.
"""

import importlib.util
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def T():
    spec = importlib.util.spec_from_file_location(
        "trace_ops", os.path.join(ROOT, "scripts", "trace_ops.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


N = 786432
GATHER = (f"%fusion.24 = u32[{N}]{{0:T(1024)}} fusion(u32[{N}]{{0:T(1024)"
          f"S(1)}} %a, s32[{N}]{{0:T(1024)}} %perm), kind=kLoop, "
          f"calls=%fused_computation.3")
GATHER_2 = GATHER.replace("fusion.24", "fusion.31")
PACKED = (f"%fusion.14 = u32[{N},16]{{1,0:T(8,128)}} fusion(u32[{N},16]"
          f"{{1,0:T(8,128)}} %slab, s32[{N}]{{0:T(1024)}} %perm), "
          f"kind=kLoop, calls=%fused_computation.9")
SORT = (f"%sort.34 = (u32[{N}]{{0:T(1024)}}, u32[{N}]{{0:T(1024)}}, "
        f"s32[{N}]{{0:T(1024)}}) sort(u32[{N}]{{0}} %k, u32[{N}]{{0}} %w, "
        f"s32[{N}]{{0}} %p), dimensions={{0}}, is_stable=true, "
        f"to_apply=%region_0.1")
COND = (f"%conditional.1 = (u8[{N},16]{{1,0:T(8,128)(4,1)}}, pred[{N}]"
        f"{{0:T(1024)(128)(4,1)}}) conditional(pred[]{{:T(128)}} %p, "
        f"(u8[{N},16]{{1,0}}) %t, (u8[{N},16]{{1,0}}) %f), "
        f"true_computation=%a, false_computation=%b")
WHILE = ("%while.2 = (s32[]{:T(128)}, u32[193]{0:T(256)}) while((s32[]"
         "{:T(128)}, u32[193]{0:T(256)}) %tuple.1), condition=%c, body=%b")
BODY = ("%fusion.5 = u32[]{:T(128)} fusion(u32[786432]{0:T(1024)} %ha, "
        "u32[]{:T(128)} %la), kind=kLoop, calls=%fused_computation.1")
CUMSUM_FAST = (f"%fusion.7 = s32[{N}]{{0:T(1024)}} fusion(s32[{N}]"
               f"{{0:T(1024)}} %x), kind=kLoop, calls=%fused_computation.2")


def test_parse_op_reads_name_opcode_and_types(T):
    op = T.parse_op(GATHER, 10.0, 20.0)
    assert (op.name, op.opcode) == ("fusion.24", "fusion")
    assert op.result == f"u32[{N}]"
    assert op.operands == f"u32[{N}], s32[{N}]"
    assert op.signature == T.parse_op(GATHER_2).signature
    sort = T.parse_op(SORT)
    assert sort.opcode == "sort"
    assert sort.result == f"(u32[{N}], u32[{N}], s32[{N}])"
    assert T.rows_1d(sort.result) == N
    cond = T.parse_op(COND)
    assert cond.opcode == "conditional" and T.rows_1d(cond.result) == 0
    assert T.parse_op(WHILE).opcode == "while"
    assert T.rows_1d(T.parse_op(PACKED).result) == 0
    # A name that is no HLO text (the CPU backend's) stays an operation.
    assert T.parse_op("dot_general").opcode == "dot_general"


def one_run(T, t0=0.0):
    """A run of 100 ms: a conditional of 80 ms that holds two 1-D gathers
    (20 ms each: 25 ns an element; the chip's take 5.6), a packed gather,
    a sort and a while of three rounds; after it a fast 1-D pass (0.5 ms:
    0.6 ns an element)."""
    ev = [(COND, 5, 85), (GATHER, 6, 26), (GATHER_2, 26, 46),
          (PACKED, 46, 56), (SORT, 56, 66), (WHILE, 66, 84),
          (BODY, 67, 70), (BODY, 71, 74), (BODY, 75, 78),
          (CUMSUM_FAST, 86, 86.5)]
    return [T.parse_op(n, (t0 + s) * 1e6, (t0 + e) * 1e6)
            for n, s, e in ev]


def test_nest_gives_self_time_and_the_containers_around(T):
    rows = {(op.name, op.start): (depth, self_ns, path)
            for op, depth, self_ns, path in T.nest(one_run(T))}
    cond_sig = T.parse_op(COND).signature
    depth, self_ns, path = rows[("conditional.1", 5e6)]
    # 80 ms less the 78 its children cover (the while counts whole).
    assert (depth, path) == (0, ()) and self_ns == pytest.approx(2e6)
    depth, self_ns, path = rows[("while.2", 66e6)]
    assert depth == 1 and path == (cond_sig,)
    assert self_ns == pytest.approx(9e6)        # 18 ms less three bodies
    depth, _, path = rows[("fusion.5", 71e6)]
    assert depth == 2 and path == (cond_sig, T.parse_op(WHILE).signature)
    assert rows[("fusion.7", 86e6)][0] == 0


def test_slow_1d_is_a_column_moved_by_itself(T):
    ops = {op.name: (op, self_ns) for op, _, self_ns, _ in
           T.nest(one_run(T))}
    assert T.is_slow_1d(*ops["fusion.24"])          # 25 ns an element
    assert not T.is_slow_1d(*ops["fusion.14"])      # a packed row gather
    assert not T.is_slow_1d(*ops["sort.34"])        # counted as a sort
    assert not T.is_slow_1d(*ops["fusion.7"])       # 1-D but fast
    assert not T.is_slow_1d(*ops["conditional.1"])  # a container
    assert not T.is_slow_1d(*ops["fusion.5"])       # a scalar result


def test_summary_and_listing_over_two_runs(T):
    modules = [("jit__update_batch(123)", 0.0, 100e6),
               ("jit_iota(9)", 150e6, 151e6),
               ("jit__update_batch(123)", 200e6, 300e6)]
    ops = one_run(T) + one_run(T, 200.0)
    runs = T.runs_of(modules, ops)
    assert [len(c) for c in runs["jit__update_batch(123)"]] == [10, 10]
    assert runs["jit_iota(9)"] == [[]]
    table = {r["program"]: r for r in T.summary(runs)}
    row = table["jit__update_batch"]
    assert row["calls"] == 2
    assert row["ms"] == pytest.approx(2 * 80.5)         # 80 + 0.5 ms a run
    assert row["slow_1d_ms"] == pytest.approx(2 * 40.0)
    assert row["sort_ms"] == pytest.approx(2 * 10.0)
    rows = T.by_signature(runs["jit__update_batch(123)"])
    gathers = [r for r in rows if r["signature"] ==
               T.parse_op(GATHER).signature]
    assert len(gathers) == 1                    # fusion.24 and .31: one line
    assert gathers[0]["count_a_run"] == 2
    assert gathers[0]["ms_each"] == pytest.approx(20.0)
    assert gathers[0]["ms_a_run"] == pytest.approx(40.0)
    lines = [ln.split(" ms  ", 1) for ln in T.listing(rows)]
    # The conditional first, what ran inside it indented under it, the
    # while's body one step further; the fast pass after them.
    assert lines[0][0].split() == ["2.000", "ms", "a", "run", "1.0", "x",
                                   "2.000"]
    assert lines[0][1].startswith("conditional")
    assert lines[1][1].startswith("  fusion") and \
        lines[1][1].endswith("1-D slow")
    body = [what for _, what in lines if "u32[] <-" in what]
    assert body and body[0].startswith("    fusion")
    assert lines[-1][1].startswith("fusion s32[786432]")
