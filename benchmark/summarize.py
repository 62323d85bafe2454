"""Table of a set of runs that ``prove.sh`` left in a directory (PR 24).

    python3 benchmark/summarize.py <dir> [<tag>]
    python3 benchmark/summarize.py <dir> <tag1> <tag2>     (two full sets)

Per run its result line's numbers; per metric the median and the spread
the bounds are set from: the distance between the first and the third
quartile (``statistics.quantiles(values, n=4)``) as a share of the
median. The exit code counts runs with no result line or ``correct``
false. Reads files only; touches neither JAX nor the program.
"""

from __future__ import annotations

import glob
import json
import os
import statistics
import sys


def last_json(path: str):
    try:
        with open(path) as f:
            lines = [ln for ln in f.read().splitlines() if ln.strip()]
        return json.loads(lines[-1]) if lines else None
    except (OSError, ValueError):
        return None


def spread(values) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    mid = statistics.median(values)
    return (q3 - q1) / mid if mid else float("nan")


def trimmed_spread(values) -> float:
    """The spread without the run farthest from the median (how the
    driver reads a set when it asks whether a bound is too tight)."""
    mid = statistics.median(values)
    rest = sorted(values, key=lambda v: abs(v - mid))[:-1]
    return spread(rest)


def results(directory: str, tag: str, trace: int):
    """(file name, result line or None) of a tag's runs, by seed."""
    for path in sorted(glob.glob(os.path.join(
            directory, f"{tag}*_t{trace}_s*.out"))):
        yield os.path.basename(path), last_json(path)


def two_sets(directory: str, tag1: str, tag2: str) -> None:
    """What a bound is set from: per end-to-end metric the spread of each
    set, the wider one, five times it, and how far the second set's median
    lies from the first's."""
    sets = []
    for tag in (tag1, tag2):
        columns: dict = {}
        for _, res in results(directory, tag, 0):
            if res and res.get("correct"):
                for k, m in res["metrics"].items():
                    columns.setdefault(k, []).append(m["value"])
        sets.append(columns)
    for k in sets[0]:
        a, b = sets[0][k], sets[1].get(k, [])
        if len(a) < 3 or len(b) < 3:
            continue
        wide = max(spread(a), spread(b))
        tight = (trimmed_spread(a) + trimmed_spread(b)) / 2
        shift = statistics.median(b) / statistics.median(a) - 1
        print(f"  sets {k}: n={len(a)}+{len(b)} medians="
              f"{statistics.median(a):.6g}/{statistics.median(b):.6g} "
              f"shift={shift:+.4%} spreads={spread(a):.4%}/{spread(b):.4%} "
              f"wider={wide:.4%} x5={5 * wide:.4%} "
              f"trimmed mean={tight:.4%}")


def main(argv) -> int:
    directory, tag = argv[1], (argv[2] if len(argv) > 2 else "")
    if len(argv) > 3:
        two_sets(directory, argv[2], argv[3])
        return 0
    bad = 0
    for trace in (0, 1):
        columns: dict = {}
        for name, res in results(directory, tag, trace):
            if not res or "correct" not in res:
                bad += 1
                print(f"{name}: NO RESULT LINE")
                continue
            bad += not res["correct"]
            vals = {k: m["value"] for k, m in res["metrics"].items()}
            for k, v in vals.items():
                columns.setdefault(k, []).append(v)
            dev = res["device"]
            print(name, "correct" if res["correct"] else "NOT CORRECT",
                  f"attempted={res['attempted']} failed={res['failed']}",
                  json.dumps(vals),
                  f"gap={res['checks']['max_rel_gap']['value']:.3g}",
                  f"peak={dev.get('memory_peak_bytes')}",
                  f"busy/window={dev.get('busy_s')}/{dev.get('window_s')}")
        for k, vs in columns.items():
            line = f"  trace={trace} {k}: n={len(vs)} " \
                   f"median={statistics.median(vs):.6g} " \
                   f"min={min(vs):.6g} max={max(vs):.6g}"
            if len(vs) >= 2:
                line += f" iqr/median={spread(vs):.4%}"
            if len(vs) >= 3:
                rest = vs[1:]
                line += f" | without the first run: median=" \
                        f"{statistics.median(rest):.6g}"
                if len(rest) >= 2:
                    line += f" iqr/median={spread(rest):.4%}"
            print(line)
    return bad


if __name__ == "__main__":
    sys.exit(main(sys.argv))
