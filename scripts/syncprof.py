"""Host-sync profiler: every device->host read makes the driver wait
(0.9 ms for a dispatch-and-read on the attached v5e, PR 21), so query
wall time ~= device compute + syncs * that floor.

Rebased on the flight recorder (spark_rapids_tpu/monitoring/): the sync
funnels (jax.device_get, ArrayImpl.__array__/__int__/__float__/__bool__)
are wrapped by monitoring/syncs.py, each blocking read records a ``sync``
span with its engine call sites, and this script aggregates the span
stream per site — so the sync attribution interleaves with the
operator/upload/shuffle spans on the same timeline (trace_export shows
each round trip INSIDE the operator that paid for it) instead of living
in a private ad-hoc timer table.

Usage: python scripts/syncprof.py [q1|q6|q3|q5|q67|xbb_q5|repart] [iters]
Env: TPCH_SF (default 1.0); JAX_PLATFORMS=cpu for the CPU backend.
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402,F401


def report(wall: float, query_id=None):
    from spark_rapids_tpu.monitoring.syncs import sync_stats
    stats = sync_stats(query_id)
    total = sum(secs for _, secs in stats.values())
    n = sum(cnt for cnt, _ in stats.values())
    print(f"\n  syncs: {n} totalling {total:.3f}s "
          f"({100 * total / max(wall, 1e-9):.0f}% of wall)")
    for site, (cnt, secs) in sorted(stats.items(), key=lambda kv: -kv[1][1]):
        print(f"  {secs:8.3f}s  x{cnt:<5d} {site}")


def main():
    qn = sys.argv[1] if len(sys.argv) > 1 else "q3"
    iters = int(sys.argv[2]) if len(sys.argv) > 2 else 1
    from spark_rapids_tpu import monitoring
    from spark_rapids_tpu.api.dataframe import TpuSession
    from spark_rapids_tpu.benchmarks import suites, tpch
    from spark_rapids_tpu.monitoring import syncs

    syncs.install()

    sf = float(os.environ.get("TPCH_SF", "1.0"))
    if qn in tpch.QUERIES:
        mod, ddir = tpch, os.environ.get("TPCH_DIR", f"/tmp/srt_tpch_sf{sf:g}")
    else:
        mod, ddir = suites, os.environ.get("SUITES_DIR",
                                           f"/tmp/srt_suites_sf{sf:g}")
    mod.generate(ddir, scale=sf)

    session = TpuSession()
    session.set("spark.rapids.sql.variableFloatAgg.enabled", True)
    session.set("spark.rapids.sql.hasNans", False)
    if os.environ.get("SRT_SHUFFLE_PARTS"):
        session.set("spark.rapids.sql.shuffle.partitions",
                    int(os.environ["SRT_SHUFFLE_PARTS"]))
    df = mod.QUERIES[qn](session, ddir)

    t0 = time.perf_counter()
    df.collect()
    print(f"warmup: {time.perf_counter() - t0:.2f}s")

    # Sync attribution needs the kernel level; the ring bound keeps even
    # a sync-storm run to a bounded window.
    session.set("spark.rapids.sql.trace.enabled", True)
    session.set("spark.rapids.sql.trace.level", "kernel")
    for it in range(iters):
        monitoring.reset()
        t0 = time.perf_counter()
        rows = df.collect()
        wall = time.perf_counter() - t0
        print(f"\n=== {qn} iter {it}: wall {wall:.3f}s, {len(rows)} rows ===")
        report(wall)


if __name__ == "__main__":
    main()
