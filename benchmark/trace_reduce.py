"""From a profiler trace to busy and idle time (PR 24).

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
``jax.profiler.ProfileData``. The traced window is the span of the
benchmark's own ``bench:query`` annotations (whole queries; what the
profiler spends starting and stopping lies outside). Per device, busy is
the union of the intervals in which an operation ran, clipped to that
window.

Which events are device operations (looked at by hand on a v5e trace, PR
24, ``python benchmark/trace_reduce.py <file>`` prints the same view):

- a plane named ``/device:TPU:<n>`` is a chip; its line ``XLA Ops`` holds
  one event per executed HLO operation, ``XLA Modules`` one per program,
  ``Steps`` groups of them. Busy time is read from ``XLA Ops`` alone: the
  other lines cover the same time again. An operation's name is its whole
  HLO text (``%fusion.24 = u32[786432]{..} fusion(..), kind=..``); the
  breakdown names it ``<program>/<op> <result type>``, the program being
  the ``XLA Modules`` event that covers it (``jit__update_batch``).
- the CPU backend (the rehearsal) has no device plane: its operations are
  the events of ``/host:CPU`` that carry an ``hlo_op`` stat, and the one
  "device" is the host. Such a reduction is named ``cpu`` and is no chip
  number.

Host annotations are the events of ``/host:CPU`` lines that hold a
``bench:query``: the benchmark's and the operators' ``TraceAnnotation``s
(``<Op>:<metric>``, ``ops/base.py``). An idle gap is labelled with the
innermost of them that covers its middle.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

QUERY_ANNOTATION = "bench:query"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
TOP = 10

Interval = Tuple[float, float]


def find_trace(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _events(line):
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def _has_stat(event, key: str) -> bool:
    return any(k == key for k, _ in event.stats)


def short_name(hlo: str, program: str = "") -> str:
    """``%fusion.24 = u32[786432]{0:T(1024)} fusion(...)`` of program
    ``jit__update_batch(123)`` -> ``jit__update_batch/fusion.24
    u32[786432]``. A name that is no HLO text stays as it is."""
    op, eq, rest = hlo.partition(" = ")
    op = op.lstrip("%")
    if eq:
        rest = re.sub(r"\{[^}]*\}", "", rest)           # tiling layouts
        end = rest.find(")") + 1 if rest.startswith("(") else rest.find(" ")
        op = f"{op} {rest[:end] if end > 0 else rest}"[:120]
    program = program.split("(", 1)[0]
    return f"{program}/{op}" if program else op


def _named(ops: list, modules: list) -> list:
    """The ops renamed by ``short_name``, each under the module event
    that covers its start (modules of one device do not overlap)."""
    modules = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]
    out = []
    for name, s, e in ops:
        i = bisect.bisect_right(starts, s) - 1
        inside = i >= 0 and s < modules[i][2]
        out.append((short_name(name, modules[i][0] if inside else ""),
                    s, e))
    return out


def _device_ops(data) -> Dict[str, list]:
    """Plane name -> [(op name, start, end)], one entry per device."""
    devices: Dict[str, list] = {}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            by_line = {ln.name: _events(ln) for ln in plane.lines
                       if ln.name in (OPS_LINE, MODULES_LINE)}
            devices[plane.name] = _named(by_line.get(OPS_LINE, []),
                                         by_line.get(MODULES_LINE, []))
    if devices:
        return devices
    for plane in data.planes:                   # CPU backend
        if plane.name == HOST_PLANE:
            devices["cpu"] = [
                (e.name, float(e.start_ns),
                 float(e.start_ns + e.duration_ns))
                for ln in plane.lines for e in ln.events
                if e.duration_ns > 0 and _has_stat(e, "hlo_op")]
    return devices


def _annotations(data) -> list:
    """The events of the client's thread: the host line with the most
    ``bench:query`` events. One thread, so they nest."""
    best: list = []
    for plane in data.planes:
        if plane.name == HOST_PLANE:
            for ln in plane.lines:
                evs = [ev for ev in _events(ln) if ev[2] > ev[1]]
                n = sum(name == QUERY_ANNOTATION for name, _, _ in evs)
                if n > sum(name == QUERY_ANNOTATION for name, _, _ in best):
                    best = evs
    return best


def _labels(annotations: list, points: List[float]) -> List[str]:
    """For each of the ascending ``points``, the innermost annotation
    that covers it: one sweep over the nested events of one thread."""
    anns = sorted(annotations, key=lambda a: (a[1], a[1] - a[2]))
    out, stack, i = [], [], 0
    for at in points:
        while i < len(anns) and anns[i][1] <= at:
            while stack and stack[-1][2] < anns[i][1]:
                stack.pop()
            stack.append(anns[i])
            i += 1
        while stack and stack[-1][2] < at:
            stack.pop()
        out.append(stack[-1][0] if stack
                   else "outside " + QUERY_ANNOTATION)
    return out


def reduce_trace(path: str) -> Optional[dict]:
    """``{"window_s", "queries", "devices": {plane: busy_s}, "busy_s"
    (mean over devices), "busiest", "device_ops": [[name, s]...],
    "idle_gaps": [[label, s]...]}``, the two lists for the busiest
    device; None where the trace holds no whole query or no device
    operation."""
    import jax.profiler
    data = jax.profiler.ProfileData.from_file(path)
    annotations = _annotations(data)
    queries = [(s, e) for n, s, e in annotations if n == QUERY_ANNOTATION]
    devices = _device_ops(data)
    if not queries or not any(devices.values()):
        return None
    w0, w1 = min(s for s, _ in queries), max(e for _, e in queries)
    busy: Dict[str, float] = {}
    merged: Dict[str, List[Interval]] = {}
    for name, ops in devices.items():
        merged[name] = union([(max(s, w0), min(e, w1)) for _, s, e in ops
                              if e > w0 and s < w1])
        busy[name] = sum(e - s for s, e in merged[name]) / 1e9
    busiest = max(busy, key=busy.get)
    by_op: Dict[str, float] = {}
    for name, s, e in devices[busiest]:
        if e > w0 and s < w1:
            by_op[name] = by_op.get(name, 0.0) + (min(e, w1) - max(s, w0))
    edges = [w0] + [t for iv in merged[busiest] for t in iv] + [w1]
    idle = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
    gaps: Dict[str, float] = {}
    for (s, e), label in zip(idle, _labels(
            annotations, [(s + e) / 2 for s, e in idle])):
        gaps[label] = gaps.get(label, 0.0) + (e - s)

    def top(d):
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
    return {"window_s": (w1 - w0) / 1e9, "queries": len(queries),
            "devices": busy, "busy_s": sum(busy.values()) / len(busy),
            "busiest": busiest, "device_ops": top(by_op),
            "idle_gaps": top(gaps)}


def describe(path: str) -> None:
    """What a trace holds, for the eye: planes, lines, first events."""
    import jax.profiler
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", line.name, len(events))
            for e in events[:4]:
                print("     ", e.name[:80], e.start_ns, e.duration_ns,
                      [k for k, _ in e.stats][:6])


if __name__ == "__main__":
    describe(sys.argv[1])
    print(reduce_trace(sys.argv[1]))
