"""The control of ``correct``: the reference in float32 (PR 24).

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 [--scale 1]

The configurations state float64. The step below it, the one that would
tempt a later PR on a chip whose float64 is emulated, is float32: this
puts the plain reference, computed in float32, in the program's place —
every float column cast to float32 as it is read, so products, sums and
means run in float32 — and holds its answers against the float64
reference's with the comparison of a run (``compare.judge``). It has to
come out as NOT correct, on every seed. It needs no chip and touches no
JAX: data, reference and control are host code of the benchmark's own.
Prints one JSON line per seed and query, ``control_gap`` being the number
the limit of ``max_rel_gap`` has to stay under. Exit code: how many came
out correct.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


def float32_query(suite, query: str, data_dir: str):
    """``suite.pandas_query`` with every float column read as float32."""
    import pyarrow as pa
    real_concat = suite.pa.concat_tables

    def concat32(tables, **kw):
        t = real_concat(tables, **kw)
        return t.cast(pa.schema(
            [pa.field(f.name, pa.float32()) if pa.types.is_floating(f.type)
             else f for f in t.schema]))

    class Arrow32:
        """``suite.pa`` for the length of one query."""
        concat_tables = staticmethod(concat32)

        def __getattr__(self, name):
            return getattr(pa, name)

    suite.pa = Arrow32()
    try:
        return suite.pandas_query(query, data_dir)
    finally:
        suite.pa = pa


def main(argv=None) -> int:
    import compare
    import run
    import traffic
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--scale", type=float)
    args = ap.parse_args(argv)
    _bench, cell, config, mix = run.load_cell(args.workload)
    suite = importlib.import_module(config["suite"])
    scale = args.scale if args.scale is not None else config["scale"]
    passed = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        data_dir = tempfile.mkdtemp(prefix="benchmark_control_")
        try:
            names = traffic.queries(mix)
            suite.generate(
                data_dir, scale=scale, seed=seed,
                files_per_table=config["files_per_table"],
                tables=sorted({t for q in names
                               for t in suite.QUERY_COLUMNS[q]}))
            for q in names:
                want = suite.pandas_query(q, data_dir)
                got = float32_query(suite, q, data_dir)
                v = compare.judge([{"query": q, "rows": got}], {q: want},
                                  suite.SET_COMPARE, sent=1)
                passed += v["correct"]
                print(json.dumps({
                    "workload": args.workload, "seed": seed, "query": q,
                    "scale": scale, "control": "reference in float32",
                    "correct": v["correct"],
                    "control_gap": v["checks"]["max_rel_gap"]["value"],
                    "answers_wrong": v["checks"]["answers_wrong"]["value"],
                    "limit": compare.LIMITS["max_rel_gap"]}), flush=True)
        finally:
            shutil.rmtree(data_dir, ignore_errors=True)
    return passed


if __name__ == "__main__":
    sys.exit(main())
