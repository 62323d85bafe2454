"""cell_counters.py — one cell of the benchmark, then the process totals
its ``counters`` line does not carry.

    python scripts/cell_counters.py --workload <cell> --seed <n> --seconds <s> --trace 0
                                    [--rehearse-cpu --scale <sf>]

The arguments are ``benchmark/run.py``'s, which runs in this process as it
would alone; afterwards one more JSON line gives ``columnar/batch.py
counters()`` (what ``coalesce_iter(shrink=True)`` decided, PR 32) for ALL
the collects of the process: two warm-ups of each query of the mix, the
window's ``queries`` (its ``window`` line) and, in a traced run, ten more;
and, in a traced run, a line with the flight recorder's ``counters()``
(PR 33: ``collects``, ``expandRowsIn`` / ``expandRowsOut`` /
``expandProjections``, ``aggUpdateRows``, ``aggConsolidateLevels``,
``windowRowsIn`` / ``windowBatches`` / ``windowOutOfCoreSplits``; counted
only while the recorder is on, so empty at ``--trace 0``; PR 35:
``joinBuildRows``, ``exchangeRows``, ``coalesceAloneRows``), and two lines that are there at
``--trace 0`` too (PR 35): the device scan cache's ``io/scan.py
counters()`` (units and bytes hit, missed, refilled, evicted, rejected,
resident) and ``plan/cost.py counters()`` (``replanChecks``,
``joinDemotions``: which plan the joins ran; ``replanObservedBytes``,
``replanFootprintBytes``, ``replanUncountedShards``: what the rule read,
what it would have read of padded shards, and shards without a row
count, each summed over the checks).
The counts follow from shapes and live counts alone, so a CPU rehearsal at
the cell's scale gives the chip's counts; its times are no device numbers.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "benchmark"), ROOT]


def main() -> int:
    import run
    rc = run.main(sys.argv[1:])
    from spark_rapids_tpu.columnar import batch
    from spark_rapids_tpu.monitoring import recorder
    print(json.dumps({"phase": "shrink_counters", **batch.counters()}),
          flush=True)
    print(json.dumps({"phase": "recorder_counters", **recorder.counters()}),
          flush=True)
    from spark_rapids_tpu.io import scan
    from spark_rapids_tpu.plan import cost
    print(json.dumps({"phase": "scan_cache_counters", **scan.counters()}),
          flush=True)
    print(json.dumps({"phase": "cost_counters", **cost.counters()}),
          flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
