"""Compile-warmup pack: pre-populate the plan cache + persistent kernel
cache from a recorded shape manifest, so a FRESH process serves its
first query without the 7-26s cold-compile cliff.

A shape manifest is a JSON list of entries::

    [{"module": "tpch",   "query": "q6", "dir": "/data/tpch"},
     {"module": "suites", "query": "q67", "dir": "/data/suites"}]

``module`` names a benchmarks module exposing ``QUERIES`` (tpch or
suites). With no --manifest, the default pack is the 11-query bench
suite over TPCH_DIR/SUITES_DIR (generated at WARMUP_SF if absent —
warmup compiles against the REAL data's batch capacities, which is what
makes the persistent-cache entries reusable by serving traffic).

Replaying a shape does one ``prepare()`` (template into the plan cache)
and one ``collect()`` (kernels traced + compiled + serialized into the
persistent compile cache: ``JAX_COMPILATION_CACHE_DIR``, else
``<checkout>/.jax_cache``). A process restarted with the same directory
then deserializes (~ms) instead of recompiling (~s), and its first
collect of each shape is bind-only.

Usage::

    python scripts/warmup.py [--manifest shapes.json]
        [--persistent-dir /var/cache/srt-kernels]
        [--dump-manifest shapes.json]

Prints one JSON line: per-shape seconds, plan-cache/kernel-cache/
persistent-cache counter deltas.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

DEFAULT_QUERIES = {
    "tpch": ["q1", "q6", "q3", "q5", "q12", "q14"],
    "suites": ["repart", "q67", "xbb_q5", "ds_q3", "xbb_q12"],
}


def default_manifest():
    sf = float(os.environ.get("WARMUP_SF", "0.01"))
    tpch_dir = os.environ.get("TPCH_DIR", f"/tmp/srt_tpch_sf{sf:g}")
    suites_dir = os.environ.get("SUITES_DIR", f"/tmp/srt_suites_sf{sf:g}")
    out = []
    for mod, queries in DEFAULT_QUERIES.items():
        d = tpch_dir if mod == "tpch" else suites_dir
        out.extend({"module": mod, "query": q, "dir": d} for q in queries)
    return out


def _ensure_data(manifest):
    """Generate any missing default data dirs (real serving deployments
    point the manifest at their own datasets)."""
    from spark_rapids_tpu.benchmarks import suites, tpch
    sf = float(os.environ.get("WARMUP_SF", "0.01"))
    for mod, gen in (("tpch", tpch.generate), ("suites", suites.generate)):
        dirs = {e["dir"] for e in manifest if e["module"] == mod}
        for d in dirs:
            if not os.path.isdir(d):
                gen(d, scale=sf)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--manifest", help="shape manifest JSON to replay")
    ap.add_argument("--dump-manifest",
                    help="write the default shape manifest here and exit")
    ap.add_argument("--persistent-dir", default="",
                    help="move the persistent compile cache here "
                         "(spark.rapids.sql.kernelCache.persistentDir); "
                         "default: keep JAX_COMPILATION_CACHE_DIR, else "
                         "<checkout>/.jax_cache. Ignored while "
                         "JAX_COMPILATION_CACHE_DIR is set")
    args = ap.parse_args(argv)

    if args.dump_manifest:
        with open(args.dump_manifest, "w") as f:
            json.dump(default_manifest(), f, indent=2)
        print(f"wrote {args.dump_manifest}")
        return 0

    if args.manifest:
        with open(args.manifest) as f:
            manifest = json.load(f)
    else:
        manifest = default_manifest()

    from spark_rapids_tpu.api.dataframe import TpuSession
    from spark_rapids_tpu.benchmarks import suites, tpch
    from spark_rapids_tpu.ops import kernel_cache as kc
    from spark_rapids_tpu.plan import plan_cache as pc

    mods = {"tpch": tpch, "suites": suites}
    _ensure_data(manifest)

    def session():
        s = TpuSession()
        s.set("spark.rapids.sql.variableFloatAgg.enabled", True)
        s.set("spark.rapids.sql.hasNans", False)
        if args.persistent_dir:
            s.set("spark.rapids.sql.kernelCache.persistentDir",
                  args.persistent_dir)
        return s

    kc0 = kc.cache().stats()
    pc0 = pc.counters()
    shapes = {}
    t0 = time.perf_counter()
    for entry in manifest:
        mod = mods[entry["module"]]
        qname = entry["query"]
        label = f"{entry['module']}:{qname}"
        t = time.perf_counter()
        try:
            df = mod.QUERIES[qname](session(), entry["dir"])
            df.prepare()            # template -> plan cache
            df.collect()            # kernels -> (persistent) compile cache
            shapes[label] = round(time.perf_counter() - t, 3)
        except Exception as e:      # one bad shape must not kill the pack
            shapes[label] = f"error: {type(e).__name__}: {e}"
    kc1 = kc.cache().stats()
    report = {
        "shapes": shapes,
        "total_s": round(time.perf_counter() - t0, 3),
        "plan_cache_entries": pc.cache().stats()["entries"],
        "plan_cache_counters": {
            k: pc.counters().get(k, 0) - pc0.get(k, 0)
            for k in ("planCacheHits", "planCacheMisses")},
        "kernel_compiles": kc1["misses"] - kc0["misses"],
        "persistent_dir": kc.persistent_stats()["dir"],
        "persistent_hits":
            kc1.get("persistentCacheHits", 0)
            - kc0.get("persistentCacheHits", 0),
        "persistent_misses":
            kc1.get("persistentCacheMisses", 0)
            - kc0.get("persistentCacheMisses", 0),
    }
    sys.stdout.write(json.dumps(report) + "\n")
    errs = [v for v in shapes.values() if isinstance(v, str)]
    return 1 if errs else 0


if __name__ == "__main__":
    sys.exit(main())
