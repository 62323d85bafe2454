"""Device idle time of a kept benchmark trace, by who owns it (PR 25).

``benchmark/trace_reduce.py`` labels an idle gap of the device with the
innermost host annotation over the gap's middle, whoever wrote it. Since
PR 25 every flight-recorder span is such an annotation, but jax's own
(``np.asarray(jax.Array)``, ``PjitFunction(_shrink)``) nest inside the
program's and win wherever the host waits inside jax: over half the idle
of the TPC-H cells. This prints the same gaps under four rules:

1. the innermost annotation of all (what ``breakdown.idle_gaps`` holds);
2. the innermost span of the program (``<category>:<name>`` or
   ``<Op>:<what>``; jax's and the runtime's own annotations skipped);
3. the owner: rule 2 with the ``sync:*`` funnel spans skipped as well,
   so a blocking read goes to the operator (or ``download``...) that
   asked for it. ``PERF.md`` quotes this one;
4. the owner again, each gap split over its whole extent among the
   spans it crosses, not given whole to the one over its middle: the
   gap between two queries (download, the collect's tail, the client's
   loop, DataFrame building, plan-bind, the first dispatch) shows its
   parts, and no label flips with where one middle falls.

Usage (the trace of a ``--trace 1`` run, kept with ``--keep-trace``)::

    python benchmark/run.py --workload tpch_sf1_resident_q3 --seed 7 \\
        --seconds 51 --trace 1 --keep-trace chiprun_out/traces/q3
    python scripts/idle_owner.py chiprun_out/traces/q3 [more ...]

Reads files only (``jax.profiler.ProfileData``): no device is touched,
so it runs in the sandbox over what a chip call brought back.
"""

from __future__ import annotations

import glob
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import trace_reduce as tr  # noqa: E402

# A span of the program: one colon, after a category or an operator's
# name. "PythonRefManager::CollectGarbage" and "PjitFunction(f)" are not.
PROGRAM = re.compile(r"^[^:()]+(\[.*\])?:[^:]")
SHOWN = 14


def idle_gaps(data):
    """(annotations of the client's thread, idle intervals of the busiest
    device inside the traced window): ``trace_reduce.reduce_trace``'s
    own reading of the file."""
    anns = tr._annotations(data)
    queries = [(s, e) for n, s, e in anns if n == tr.QUERY_ANNOTATION]
    devices = tr._device_ops(data)
    if not queries or not any(devices.values()):
        return anns, [], 0
    w0, w1 = min(s for s, _ in queries), max(e for _, e in queries)
    merged = {name: tr.union([(max(s, w0), min(e, w1))
                              for _, s, e in ops if e > w0 and s < w1])
              for name, ops in devices.items()}
    busiest = max(merged, key=lambda n: sum(e - s for s, e in merged[n]))
    edges = [w0] + [t for iv in merged[busiest] for t in iv] + [w1]
    idle = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
    return anns, idle, len(queries)


def by_label(annotations, idle):
    labels = tr._labels(annotations, [(s + e) / 2 for s, e in idle])
    out = {}
    for (s, e), label in zip(idle, labels):
        out[label] = out.get(label, 0.0) + (e - s)
    return sorted(out.items(), key=lambda kv: -kv[1])


def segments(annotations):
    """One thread's nested annotations flattened: [(start, end, label)]
    with the innermost label of every stretch, in time order; stretches
    under no annotation read as ``trace_reduce`` names them."""
    outside = "outside " + tr.QUERY_ANNOTATION
    out, stack, t = [], [], None

    def advance(upto):
        nonlocal t
        if t is not None and upto > t:
            out.append((t, upto, stack[-1][0] if stack else outside))
        t = upto if t is None else max(t, upto)

    for name, s, e in sorted(annotations, key=lambda a: (a[1], a[1] - a[2])):
        while stack and stack[-1][1] <= s:
            advance(stack[-1][1])
            stack.pop()
        advance(s)
        stack.append((name, e))
    while stack:
        advance(stack[-1][1])
        stack.pop()
    return out


def by_extent(annotations, idle):
    """Idle ns by label, each gap split among the stretches it crosses."""
    segs = segments(annotations)
    out, i = {}, 0
    for s, e in idle:
        while i < len(segs) and segs[i][1] <= s:
            i += 1
        j = i
        while j < len(segs) and segs[j][0] < e:
            a, b, label = segs[j]
            out[label] = out.get(label, 0.0) + (min(b, e) - max(a, s))
            j += 1
    return sorted(out.items(), key=lambda kv: -kv[1])


def report(path: str) -> None:
    import jax.profiler
    anns, idle, queries = idle_gaps(
        jax.profiler.ProfileData.from_file(path))
    if not idle:
        print(f"{path}: no whole query or no device operation")
        return
    total = sum(e - s for s, e in idle)
    print(f"{path}: {queries} queries, idle {total / 1e9:.4f} s, "
          f"{total / 1e6 / queries:.1f} ms a query")
    program = [a for a in anns if PROGRAM.match(a[0])]
    owners = [a for a in program if not a[0].startswith("sync:")]
    for title, table in (
            ("1. innermost annotation (breakdown.idle_gaps)",
             by_label(anns, idle)),
            ("2. innermost span of the program", by_label(program, idle)),
            ("3. owner (sync funnels skipped)", by_label(owners, idle)),
            ("4. owner, each gap split over its extent",
             by_extent(owners, idle))):
        print(" ", title)
        for label, ns in table[:SHOWN]:
            print(f"    {ns / 1e6 / queries:8.2f} ms/query "
                  f"{100 * ns / total:5.1f} %  {label}")


def main(argv) -> int:
    if not argv:
        print(__doc__)
        return 2
    for arg in argv:
        files = [arg] if os.path.isfile(arg) else sorted(
            glob.glob(os.path.join(arg, "**", "*.xplane.pb"),
                      recursive=True))
        if not files:
            print(f"{arg}: no .xplane.pb")
        for path in files:
            report(path)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
