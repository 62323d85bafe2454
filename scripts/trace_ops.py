"""Device time of a kept benchmark trace by XLA operation (PR 34).

``benchmark/trace_reduce.py`` stops at the program: its ``breakdown`` names
``jit__update_batch/conditional`` as one operation of 112 ms. The profiler
wrote more: the ``XLA Ops`` line of a device lists the operations that ran
INSIDE a ``conditional`` or a ``while`` as events of their own, nested in
time under it. This prints them.

    python scripts/trace_ops.py <dir or .xplane.pb>              # by program
    python scripts/trace_ops.py <dir or .xplane.pb> _update_batch

Without a program: one line a program (``jit__update_batch``, every
compiled shape of it together) with its calls, its device time, and of
that the time in **slow 1-D operations** — an operation whose results are
all 1-D arrays of one length of at least ``MIN_ROWS`` and that takes
``SLOW_NS`` ns an element or more: a column moved by itself (a ``jnp.take``
by a batch-length index, an index scatter, a ``cumsum``), which costs the
chip as much as a packed row of sixteen words — and in ``sort``s.

With a program: every compiled shape of it that ran, and under each the
operations by signature (opcode, result and operand types; names differ,
``fusion.24`` and ``fusion.31`` are one line), calls a run of the program,
ms each and ms a run; what ran inside a ``conditional``, ``while`` or
``call`` is listed under it, indented, and the container's own line holds
only the time no operation inside it covers.

The trace of a ``--trace 1`` run, kept with ``--keep-trace DIR`` (or
``KEEP_TRACE=1 sh benchmark/prove.sh ...``). Reads files only
(``jax.profiler.ProfileData``): no device is touched, so it runs in the
sandbox over what a chip call brought back, like ``scripts/idle_owner.py``.
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re
import sys
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

MIN_ROWS = 65536        # a "batch's length": the smallest coalesced batch
SLOW_NS = 3.0           # ns an element: a copy at HBM speed is ~0.01
CONTAINERS = ("conditional", "while", "call")
SHOWN = 24

_LAYOUT = re.compile(r"\{[^{}]*\}")
_ARRAY = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")


class Op(NamedTuple):
    """One event of the ``XLA Ops`` line, read from its HLO text."""
    name: str               # fusion.24
    opcode: str             # fusion
    result: str             # u32[786432]  or  (u32[786432], s32[786432])
    operands: str           # u32[786432], s32[786432]
    start: float            # ns
    end: float

    @property
    def signature(self) -> str:
        return f"{self.opcode} {self.result} <- ({self.operands})"


def _balanced(text: str) -> int:
    """Index just past the parenthesis group that ``text`` starts with."""
    depth = 0
    for i, ch in enumerate(text):
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0:
            return i + 1
    return len(text)


def parse_op(hlo: str, start: float = 0.0, end: float = 0.0) -> Op:
    """``%fusion.24 = u32[786432]{0:T(1024)} fusion(u32[786432]{0} %a,
    s32[786432]{0} %p), kind=kLoop, calls=%f`` -> its name, opcode, result
    type and operand types, layouts dropped. A name that is no HLO text
    is an operation of that name with no types."""
    name, eq, rest = hlo.partition(" = ")
    name = name.strip().lstrip("%")
    if not eq:
        return Op(name, name, "", "", start, end)
    rest = _LAYOUT.sub("", rest)
    cut = _balanced(rest) if rest.startswith("(") else rest.find(" ")
    result, rest = rest[:cut].strip(), rest[cut:].strip()
    opcode, _, args = rest.partition("(")
    args = args[:_balanced("(" + args) - 2]
    operands = ", ".join(f"{t}[{dims}]" for t, dims in _ARRAY.findall(args))
    return Op(name, opcode.strip(), result, operands, start, end)


def rows_1d(result: str) -> int:
    """The common length of the result's arrays if all are 1-D and of one
    length, else 0 (``(u32[786432], s32[786432])`` -> 786432)."""
    dims = {d for _t, d in _ARRAY.findall(result)}
    if len(dims) != 1:
        return 0
    (d,) = dims
    return int(d) if d.isdigit() else 0


def nest(ops: Iterable[Op]) -> List[Tuple[Op, int, float, Tuple[str, ...]]]:
    """Each operation with its depth, its SELF time in ns (its duration
    less what the operations nested in it cover) and the signatures of
    the containers around it, outermost first. Operations of one device
    line nest or follow one another; they do not cross."""
    out: List[list] = []
    stack: List[int] = []
    for op in sorted(ops, key=lambda o: (o.start, -o.end)):
        while stack and op.start >= out[stack[-1]][0].end:
            stack.pop()
        if stack:
            out[stack[-1]][2] -= op.end - op.start
        path = tuple(out[i][0].signature for i in stack)
        out.append([op, len(stack), op.end - op.start, path])
        stack.append(len(out) - 1)
    return [(op, depth, max(self_ns, 0.0), path)
            for op, depth, self_ns, path in out]


def is_slow_1d(op: Op, self_ns: float) -> bool:
    n = rows_1d(op.result)
    return (op.opcode not in CONTAINERS and op.opcode != "sort"
            and n >= MIN_ROWS and self_ns / n >= SLOW_NS)


def program_of(module: str) -> str:
    """``jit__update_batch(123456)`` -> ``jit__update_batch``."""
    return module.split("(", 1)[0]


def runs_of(modules: List[Tuple[str, float, float]], ops: List[Op]
            ) -> Dict[str, List[List[Op]]]:
    """Module name (with its fingerprint: one compiled shape) -> the
    operations of each of its runs."""
    ops = sorted(ops, key=lambda o: o.start)
    starts = [o.start for o in ops]
    out: Dict[str, List[List[Op]]] = collections.defaultdict(list)
    for name, s, e in modules:
        lo = bisect.bisect_left(starts, s)
        hi = bisect.bisect_left(starts, e)
        out[name].append(ops[lo:hi])
    return out


def summary(runs: Dict[str, List[List[Op]]]) -> List[dict]:
    """One row a program: calls, device ms, slow 1-D ms, sort ms."""
    rows: Dict[str, dict] = {}
    for module, calls in runs.items():
        row = rows.setdefault(program_of(module), {
            "program": program_of(module), "calls": 0, "ms": 0.0,
            "slow_1d_ms": 0.0, "sort_ms": 0.0})
        row["calls"] += len(calls)
        for ops in calls:
            for op, _depth, self_ns, _path in nest(ops):
                row["ms"] += self_ns / 1e6
                if op.opcode == "sort":
                    row["sort_ms"] += self_ns / 1e6
                elif is_slow_1d(op, self_ns):
                    row["slow_1d_ms"] += self_ns / 1e6
    return sorted(rows.values(), key=lambda r: -r["ms"])


def by_signature(calls: List[List[Op]]) -> List[dict]:
    """The operations of one compiled program over its runs, grouped by
    (containers around it, signature): count and ms a run, ms each."""
    rows: Dict[tuple, dict] = {}
    for ops in calls:
        for op, _depth, self_ns, path in nest(ops):
            row = rows.setdefault((path, op.signature), {
                "path": path, "signature": op.signature, "count": 0, "ns": 0.0, "slow_1d": False,
                "container": op.opcode in CONTAINERS})
            row["count"] += 1
            row["ns"] += self_ns
            row["slow_1d"] |= is_slow_1d(op, self_ns)
    n = max(len(calls), 1)
    for row in rows.values():
        row["count_a_run"] = row["count"] / n
        row["ms_a_run"] = row["ns"] / n / 1e6
        row["ms_each"] = row["ns"] / row["count"] / 1e6
    return list(rows.values())


def listing(rows: List[dict], path: tuple = (), shown: int = SHOWN
            ) -> List[str]:
    """The rows under ``path`` by time, each container followed by what
    ran inside it."""
    mine = sorted((r for r in rows if r["path"] == path),
                  key=lambda r: -_total_ms(r, rows))
    lines = []
    for r in mine[:shown]:
        mark = "  1-D slow" if r["slow_1d"] else ""
        lines.append(
            f"{r['ms_a_run']:9.3f} ms a run  {r['count_a_run']:7.1f} x "
            f"{r['ms_each']:8.3f} ms  {'  ' * len(path)}"
            f"{r['signature'][:150]}{mark}")
        if r["container"]:
            lines += listing(rows, path + (r["signature"],), shown)
    rest = mine[shown:]
    if rest:
        lines.append(f"{sum(r['ms_a_run'] for r in rest):9.3f} ms a run  "
                     f"{'':20}{'  ' * len(path)}in {len(rest)} more kinds "
                     f"of operation")
    return lines


def _total_ms(row: dict, rows: List[dict]) -> float:
    """A container sorts by itself plus what it holds."""
    inside = row["path"] + (row["signature"],)
    return row["ms_a_run"] + sum(
        r["ms_a_run"] for r in rows
        if row["container"] and r["path"][:len(inside)] == inside)


def read(path: str):
    """(module events, operations) of the busiest device of a trace."""
    from jax.profiler import ProfileData
    import trace_reduce as tr
    files = [path] if os.path.isfile(path) else sorted(glob.glob(
        os.path.join(path, "**", "*.xplane.pb"), recursive=True))
    if not files:
        raise SystemExit(f"trace_ops: no .xplane.pb under {path}")
    found = files[-1]
    best: Optional[tuple] = None
    for plane in ProfileData.from_file(found).planes:
        if not plane.name.startswith(tr.DEVICE_PREFIX):
            continue
        lines = {ln.name: tr._events(ln) for ln in plane.lines
                 if ln.name in (tr.OPS_LINE, tr.MODULES_LINE)}
        modules = lines.get(tr.MODULES_LINE, [])
        busy = sum(e - s for _n, s, e in modules)
        if best is None or busy > best[0]:
            best = (busy, plane.name, modules,
                    [parse_op(n, s, e) for n, s, e in
                     lines.get(tr.OPS_LINE, [])])
    if best is None:
        raise SystemExit(f"trace_ops: {found} holds no device plane")
    return best[1:]


def main(argv: List[str]) -> int:
    if len(argv) not in (2, 3):
        print(__doc__.split("\n\n")[2], file=sys.stderr)
        return 2
    device, modules, ops = read(argv[1])
    runs = runs_of(modules, ops)
    if len(argv) == 2:
        rows = summary(runs)
        print(f"{device}: {len(modules)} program runs, "
              f"{sum(r['ms'] for r in rows):.1f} ms of device time; slow 1-D:"
              f" results 1-D of >= {MIN_ROWS} elements at >= {SLOW_NS} ns each")
        print(f"{'program':40} {'calls':>6} {'ms':>10} {'slow 1-D ms':>12} "
              f"{'sort ms':>9}")
        for r in rows[:SHOWN]:
            print(f"{r['program'][:40]:40} {r['calls']:6d} {r['ms']:10.1f} "
                  f"{r['slow_1d_ms']:12.1f} {r['sort_ms']:9.1f}")
        print(f"{'all':40} {sum(r['calls'] for r in rows):6d} "
              f"{sum(r['ms'] for r in rows):10.1f} "
              f"{sum(r['slow_1d_ms'] for r in rows):12.1f} "
              f"{sum(r['sort_ms'] for r in rows):9.1f}")
        return 0
    want = argv[2]
    chosen = {m: c for m, c in runs.items()
              if program_of(m) in (want, "jit_" + want)}
    if not chosen:
        print(f"trace_ops: no program {want!r}; the trace holds "
              f"{sorted({program_of(m) for m in runs})}", file=sys.stderr)
        return 1
    for module, calls in sorted(
            chosen.items(),
            key=lambda kv: -sum(o.end - o.start for c in kv[1] for o in c)):
        rows = by_signature(calls)
        total = sum(r["ms_a_run"] for r in rows)
        slow = sum(r["ms_a_run"] for r in rows if r["slow_1d"])
        print(f"{module}: {len(calls)} runs, {total:.3f} ms a run, "
              f"slow 1-D {slow:.3f}")
        print("\n".join(listing(rows)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
