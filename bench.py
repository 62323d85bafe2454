"""Benchmark: the five BASELINE.md target configs, device engine vs a CPU
columnar engine (pandas/pyarrow) on the same machine.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...} —
ALWAYS, even when the time budget expires mid-run: a watchdog thread
emits the JSON for whatever completed before the deadline and exits
(the r4 lesson: a benchmark that times out silently is worse than a slow
number; BenchUtils.scala:39-300 writes its report unconditionally).

Workloads (executed THROUGH the engine: parquet scan with pruned columns,
host->device upload, TPU kernels, collect — nothing pre-resident in HBM):
- TPC-H q1/q6 (scan+filter+agg) and q3/q5 (joins) — benchmarks/tpch.py
- TPC-DS q67-like (rollup + rank window + top-k)   — benchmarks/suites.py
- TPCxBB q5-like (conditional-sum pivot + joins)   — benchmarks/suites.py
- repartition-heavy (full hash shuffle + counts)   — benchmarks/suites.py

Per query, in budget order (cheap scans first, joins, then suites):
pandas oracle (result + wall time cached on disk keyed by the datagen
manifest + oracle source hash, so repeated runs skip the CPU rerun), one
first device run (compile + cold scan + correctness check), then
BENCH_ITERS hot runs against the device scan cache. q1/q6 additionally
get one post-compile cold run (scan cache cleared) for the scan-bandwidth
headline, comparable to earlier rounds' cold medians.

- ``value`` is the suite wall-clock (sum of per-query hot medians) over
  ``completed``; ``partial`` is true when not every selected query ran.
- ``vs_baseline`` is the speedup of this engine over the pandas/pyarrow
  implementation of the same queries at the same scale — the stand-in for
  the reference's GPU-vs-CPU-Spark headline (docs/FAQ.md:60-66 claims 3-4x
  typical; the repo publishes no absolute numbers, BASELINE.md).
- ``first_run_s`` holds the compile+cold time of the first device run;
  ``cold_s`` the post-compile cold runs (q1/q6).
- Every device result is checked against the pandas result before timing;
  a mismatch fails the benchmark (BenchUtils.compareResults analog).

Env knobs: TPCH_SF (default 1.0), TPCH_DIR, SUITES_DIR, BENCH_ITERS
(default 2), BENCH_QUERIES (comma list to subset), BENCH_BUDGET_S
(default 420 — hard deadline for the whole run including datagen).
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import statistics
import sys
import threading
import time


def _hbm_gb_per_sec() -> float:
    """Peak HBM bandwidth of the device the run is on, from the one
    table of published peaks (benchmarks/peaks.py). An accelerator that
    is not in the table raises; ask only where a device ran the work."""
    import jax
    from spark_rapids_tpu.benchmarks.peaks import peak
    return float(peak(jax.devices()[0].device_kind)["hbm_gb_per_sec"])


_START = time.perf_counter()
# _LOCK guards every read AND write of _STATE["out"] and its nested dicts:
# the watchdog json.dumps()es the same objects the main thread mutates.
_LOCK = threading.Lock()
_STATE = {"out": None, "done": False, "ok": {}}


def _emit(out):
    sys.stdout.write(json.dumps(out) + "\n")
    sys.stdout.flush()


def _watchdog(budget_s: float):
    """Print the partial report and hard-exit at the deadline. A thread
    (not SIGALRM): a signal handler can't preempt a blocked device
    round-trip, os._exit from a thread can. Exit code still reflects any
    correctness failure seen before the deadline."""
    deadline = _START + budget_s
    while True:
        now = time.perf_counter()
        if _STATE["done"]:
            return
        if now >= deadline:
            with _LOCK:
                if _STATE["done"]:      # main finished while we waited
                    return
                out = _STATE["out"] or {
                    "metric": "tpc_suite_wall_clock", "value": None,
                    "unit": "s", "vs_baseline": None, "completed": []}
                out["timed_out"] = True
                out["partial"] = True
                out["budget_s"] = budget_s
                _emit(out)
                ok = _STATE["ok"]
                code = 0 if ok and all(ok.values()) else 1
                # Exit while still holding the lock: main's own emit needs
                # it, so exactly one JSON line ever reaches stdout.
                os._exit(code)
        time.sleep(min(1.0, deadline - now))


def _remaining(budget_s):
    return budget_s - (time.perf_counter() - _START)


# The benchmark queries standing in for BASELINE.md's five target
# configurations (the headline shapes vs_baseline covers).
_TARGETS = {"q1", "q6", "q3", "q5", "q67", "xbb_q5", "repart"}


# The 11-query forced-host sweep (tests/test_host_engine.py runs the
# same set as a parity suite): numpy host-engine wall vs the pandas
# oracle on the same data.
_HOST_SWEEP = ("q1", "q6", "q3", "q5", "q12", "q14", "q22",
               "q67", "xbb_q5", "ds_q89", "ds_q98")


def _host_engine_probe(packs, pandas_s, budget):
    """Forced-host run per sweep query. ``vs_pandas`` > 1 means the
    vectorized numpy engine beat the pandas implementation of the same
    query; the perf gate asserts no query falls below 0.5 (2x slower
    than pandas)."""
    res = {}
    for qn in _HOST_SWEEP:
        if qn not in packs or qn not in pandas_s:
            continue
        if _remaining(budget) < 30:
            break
        mod, ddir = packs[qn]
        try:
            df = mod.QUERIES[qn](_session(), ddir)
            t0 = time.perf_counter()
            df.collect_host()
            hs = time.perf_counter() - t0
            entry = {"host_s": round(hs, 4), "pandas_s": pandas_s[qn]}
            if hs > 0:
                entry["vs_pandas"] = round(pandas_s[qn] / hs, 3)
            res[qn] = entry
        except Exception as e:  # the headline must survive a probe bug
            res[qn] = {"error": f"{type(e).__name__}: {e}"}
    return res


def _session(scan_cache: bool = True):
    from spark_rapids_tpu.api.dataframe import TpuSession
    s = TpuSession()
    s.set("spark.rapids.sql.variableFloatAgg.enabled", True)
    # TPC data is finite; the reference's benchmark setups make the same
    # assertion (spark.rapids.sql.hasNans=false) to unlock float fast paths.
    s.set("spark.rapids.sql.hasNans", False)
    # The persistent compile cache is where the package put it at import
    # (JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache): the bench
    # names no directory of its own.
    if not scan_cache:
        s.set("spark.rapids.sql.format.scanCache.maxBytes", 0)
    return s


def _oracle_cached(mod, qn, ddir, manifest):
    """Pandas oracle result + wall time, cached on disk. The key folds in
    the benchmark module's source hash so editing an oracle invalidates
    its cache. Cache hit skips the CPU rerun entirely (the budget saver);
    miss runs pandas once and stores both result and time."""
    src = hashlib.sha256()
    src.update(open(mod.__file__, "rb").read())
    key = f"{qn}:{manifest}:{src.hexdigest()[:16]}"
    # The cache lives inside the datagen dir: anyone who can write there
    # can already poison the parquet inputs (and thus the oracle result),
    # so the pickle adds no trust boundary beyond the data itself. Timing
    # is a single cached sample by design — the driver budget can't afford
    # fresh pandas medians every run (VERDICT r4 item 1).
    path = os.path.join(ddir, f"_oracle_{qn}.pkl")
    try:
        with open(path, "rb") as f:
            cached = pickle.load(f)
        if cached.get("key") == key:
            return cached["want"], cached["secs"]
    except Exception:       # stale pickle: unpickling can raise anything
        pass
    t0 = time.perf_counter()
    want = mod.pandas_query(qn, ddir)
    secs = time.perf_counter() - t0
    try:
        with open(path, "wb") as f:
            pickle.dump({"key": key, "want": want, "secs": secs}, f)
    except (OSError, pickle.PickleError):
        pass
    return want, secs


def _scan_probe(tpch_dir: str) -> dict:
    """Scan-bandwidth microbench measured from the INGEST FAST PATH:
    post-compile cold q1+q6 runs (scan cache cleared, pipeline +
    codec v2 + coalesced uploads all active) with the wire-counter
    deltas for exactly those runs. The gb_per_sec here is the
    scan_gb_per_sec headline (bytes = uncompressed pruned columns the
    queries read, the same denominator prior rounds used)."""
    from spark_rapids_tpu.benchmarks import tpch
    from spark_rapids_tpu.columnar import wire
    from spark_rapids_tpu.io.scan import DEVICE_SCAN_CACHE

    s = _session()
    dfs = [tpch.QUERIES[qn](s, tpch_dir) for qn in ("q1", "q6")]
    for df in dfs:
        df.collect()                # warm: compile + plan cache
    DEVICE_SCAN_CACHE.clear()
    w0 = wire.counters()
    t0 = time.perf_counter()
    for df in dfs:
        df.collect()
    secs = time.perf_counter() - t0
    w1 = wire.counters()
    nbytes = tpch.bytes_scanned("q1", tpch_dir) + \
        tpch.bytes_scanned("q6", tpch_dir)
    wd = {k: round(w1.get(k, 0) - w0.get(k, 0), 4)
          for k in ("rawBytes", "encodedBytes", "stagingBytes",
                    "uploadCalls", "uploadTransfers", "uploadedBatches",
                    "groupedUploads")}
    if wd.get("rawBytes", 0) > 0:
        wd["wireCompressionRatio"] = round(
            wd["rawBytes"] / max(wd["encodedBytes"], 1), 4)
    if wd.get("uploadedBatches", 0) > 0:
        wd["stagingHitRate"] = round(
            1.0 - wd["uploadCalls"] / wd["uploadedBatches"], 4)
    return {
        "queries": ["q1", "q6"],
        "seconds": round(secs, 4),
        "bytes": nbytes,
        "gb_per_sec": round(nbytes / secs / 1e9, 3) if secs > 0 else None,
        "wire": wd,
    }


def _trace_probe(tpch_dir: str, trace_path: str) -> dict:
    """One traced q3 run through the flight recorder: where the
    wall-clock went by span category, plus the Chrome trace JSON written
    as the benchmark's artifact (tier1.yml uploads it)."""
    from spark_rapids_tpu import monitoring
    from spark_rapids_tpu.benchmarks import tpch
    from spark_rapids_tpu.io.scan import DEVICE_SCAN_CACHE

    s = _session()
    s.set("spark.rapids.sql.trace.enabled", True)
    df = tpch.QUERIES["q3"](s, tpch_dir)
    monitoring.reset()
    DEVICE_SCAN_CACHE.clear()   # the upload funnel must actually run
    t0 = time.perf_counter()
    df.collect()
    secs = time.perf_counter() - t0
    df.trace_export(trace_path)
    snap = monitoring.snapshot()
    breakdown = {cat: agg["ms"]
                 for cat, agg in snap["categories"].items()}
    monitoring.configure(False)
    monitoring.reset()
    return {
        "query": "q3",
        "seconds": round(secs, 4),
        "category_ms": breakdown,
        "instants": snap["instants"],
        "dropped_events": snap["droppedEvents"],
        "artifact": trace_path,
    }


def _sustained_probe(tpch_dir: str, total: int, clients: int) -> dict:
    """Sustained serving load (ROADMAP item 2): ``total`` parameterized
    queries — mixed q6-class/aggregate/limit shapes with NEW literals
    every call — submitted from ``clients`` worker threads through the
    admission scheduler at maxConcurrentQueries=4. Every call would
    re-plan AND re-trace without the plan cache (literal values key the
    kernel fingerprints); with it, steady state is bind-only dispatch.
    Reports p50/p99 latency, queries/sec, the plan-cache hit rate, the
    mean plan+bind wall, and the q6-class bind-only speedup vs a
    planCache.enabled=false control (the ISSUE 10 acceptance ratio)."""
    import statistics as _st

    from spark_rapids_tpu.benchmarks import tpch
    from spark_rapids_tpu.plan import plan_cache as _pc
    from spark_rapids_tpu.plan.logical import agg_sum, col, lit_col

    def sess(cache=True):
        s = _session()
        s.set("spark.rapids.sql.scheduler.maxConcurrentQueries", 4)
        s.set("spark.rapids.sql.planCache.enabled", bool(cache))
        # The sustained block doubles as the live-telemetry acceptance
        # probe: metrics on, and the block's own JSON is reconciled
        # against an HTTP scrape taken right after the load drains.
        s.set("spark.rapids.sql.metrics.enabled", True)
        return s

    day0 = tpch.days("1994-01-01")

    def shape_q6(s, i):
        li = tpch._read(s, tpch_dir, "lineitem")
        lo = day0 + (i % 330)
        f = li.filter(
            (col("l_shipdate") >= lit_col(lo))
            & (col("l_shipdate") < lit_col(lo + 30))
            & (col("l_discount") >= 0.05) & (col("l_quantity") < 24.0))
        return f.agg(agg_sum(col("l_extendedprice") * col("l_discount"))
                     .alias("rev"))

    def shape_sum(s, i):
        li = tpch._read(s, tpch_dir, "lineitem")
        return li.filter(col("l_quantity") < float(5 + i % 40)) \
            .agg(agg_sum(col("l_extendedprice")).alias("s"))

    def shape_limit(s, i):
        li = tpch._read(s, tpch_dir, "lineitem")
        return li.select("l_orderkey", "l_extendedprice") \
            .limit(10 + i % 50)

    shapes = [shape_q6, shape_sum, shape_limit]
    s = sess()
    t0 = time.perf_counter()
    for i, sh in enumerate(shapes):         # cold: template + compile
        sh(s, i).collect()
    warmup_s = time.perf_counter() - t0

    from spark_rapids_tpu.monitoring import telemetry as _tm

    def _queries_total(text: str) -> float:
        return sum(float(ln.rsplit(" ", 1)[1])
                   for ln in text.splitlines()
                   if ln.startswith("srt_queries_total"))

    tm_base = _queries_total(_tm.render_text()) if _tm.enabled() else None
    c0 = _pc.counters()
    from spark_rapids_tpu.parallel import qos as _qos
    from spark_rapids_tpu.parallel import scheduler as _sc
    q0c = _qos.counters()
    s0c = _sc.counters()
    lock = threading.Lock()
    lat: list = []
    idx = {"i": 0}
    errors = [0]

    def client(k):
        # Each client is a distinct serving tenant: the per-tenant
        # plan-cache counters (parallel/qos/) attribute every hit/miss
        # even with the QoS scheduler off. Obedient-client contract
        # (ISSUE 18): rejections with a retry_after_ms hint back off
        # and resubmit through collect_with_retry (deterministic
        # per-client jitter, seed=k) instead of counting as errors.
        tenant = f"client{k}"
        while True:
            with lock:
                i = idx["i"]
                if i >= total:
                    return
                idx["i"] = i + 1
            q0 = time.perf_counter()
            try:
                shapes[i % len(shapes)](s, i).collect_with_retry(
                    tenant=tenant, seed=k)
            except Exception:
                with lock:
                    errors[0] += 1
                continue
            took = time.perf_counter() - q0
            with lock:
                lat.append(took)

    t0 = time.perf_counter()
    workers = [threading.Thread(target=client, args=(k,), daemon=True,
                                name=f"srt-sustained-{k}")
               for k in range(clients)]
    for w in workers:
        w.start()
    for w in workers:
        w.join()
    wall = time.perf_counter() - t0
    # Scrape reconciliation: a REAL OpenMetrics HTTP scrape, taken the
    # instant the load drains, must agree (±1 for an in-flight
    # straggler) with this block's own completion count — the proof the
    # exposition path reports the same world the bench JSON does.
    telemetry_js = None
    if _tm.enabled() and tm_base is not None:
        try:
            import urllib.request
            from spark_rapids_tpu.monitoring import exporter as _exp
            port = _exp.ensure_started(0)
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
                scraped = r.read().decode()
            delta = _queries_total(scraped) - tm_base
            expect = len(lat) + errors[0]
            telemetry_js = {
                "scrape_port": port,
                "scraped_queries_total_delta": delta,
                "bench_completions": expect,
                "reconciles": abs(delta - expect) <= 1,
            }
        except Exception as e:
            telemetry_js = {"error": f"{type(e).__name__}: {e}"}
    c1 = _pc.counters()
    q1c = _qos.counters()
    hits = c1.get("planCacheHits", 0) - c0.get("planCacheHits", 0)
    misses = c1.get("planCacheMisses", 0) - c0.get("planCacheMisses", 0)
    bind_ns = c1.get("planBindNs", 0) - c0.get("planBindNs", 0)
    lat.sort()

    def pct(q):
        return round(lat[min(int(q * len(lat)), len(lat) - 1)] * 1000, 2) \
            if lat else None

    # q6-class cold-vs-warm acceptance ratio: fresh literals every call,
    # plan cache on vs off (off re-plans AND re-traces per call).
    def serial(cache, n, off):
        ss = sess(cache)
        shape_q6(ss, off - 1).collect()     # conf-specific warm
        t = time.perf_counter()
        for i in range(n):
            shape_q6(ss, off + i).collect()
        return (time.perf_counter() - t) / n
    on_s = serial(True, 6, 500)
    off_s = serial(False, 6, 600)
    s1c = _sc.counters()
    return {
        "queries": total, "clients": clients, "errors": errors[0],
        "client_retries": int(s1c.get("clientRetries", 0)
                              - s0c.get("clientRetries", 0)),
        "max_concurrent": 4,
        "warmup_s": round(warmup_s, 3),
        "wall_s": round(wall, 3),
        "qps": round(len(lat) / wall, 2) if wall > 0 else None,
        "p50_ms": pct(0.50), "p99_ms": pct(0.99),
        "mean_ms": round(_st.mean(lat) * 1000, 2) if lat else None,
        "plan_cache_hits": hits, "plan_cache_misses": misses,
        "plan_cache_hit_rate": round(hits / max(hits + misses, 1), 4),
        "plan_bind_ms_mean": round(
            bind_ns / 1e6 / max(hits + misses, 1), 3),
        "q6_bind_only_s": round(on_s, 4),
        "q6_replan_retrace_s": round(off_s, 4),
        "q6_speedup_vs_plan_cache_off": round(off_s / on_s, 2)
        if on_s > 0 else None,
        "telemetry": telemetry_js,
        "tenants": {
            f"client{k}": {
                "plan_cache_hits": int(
                    q1c.get(f"planCacheHit.client{k}", 0)
                    - q0c.get(f"planCacheHit.client{k}", 0)),
                "plan_cache_misses": int(
                    q1c.get(f"planCacheMiss.client{k}", 0)
                    - q0c.get(f"planCacheMiss.client{k}", 0)),
            } for k in range(clients)
        },
    }


def _qos_probe(tpch_dir: str, total: int) -> dict:
    """Serving QoS block (ISSUE 14; parallel/qos/): mixed-class
    parameterized load through the WFQ scheduler at a deliberately
    tight maxConcurrentQueries=2 with a lopsided weight vector and a
    small starvation bound, plus a 2-client tenant capped at ONE
    in-flight query. Reports per-class p50/p99 latency, rejections by
    kind (the capped tenant produces real tenant-quota rejections),
    starvation-bound engagements, and kernel-quota evictions."""
    from spark_rapids_tpu.benchmarks import tpch
    from spark_rapids_tpu.parallel import qos as _qos
    from spark_rapids_tpu.parallel import scheduler as _sched
    from spark_rapids_tpu.plan.logical import agg_sum, col

    weights = "8,3,1"

    def sess():
        s = _session()
        s.set("spark.rapids.sql.scheduler.maxConcurrentQueries", 2)
        s.set("spark.rapids.sql.scheduler.qos.enabled", True)
        s.set("spark.rapids.sql.scheduler.qos.weights", weights)
        s.set("spark.rapids.sql.scheduler.qos.starvationBound", 2)
        return s

    def shape(s, i):
        li = tpch._read(s, tpch_dir, "lineitem")
        return li.filter(col("l_quantity") < float(5 + i % 8)) \
            .agg(agg_sum(col("l_extendedprice")).alias("s"))

    s = sess()
    shape(s, 0).collect()                   # warm: template + kernels
    c0 = _qos.counters()
    lock = threading.Lock()
    lat = {cls: [] for cls in _qos.CLASSES}
    rejected = [0]
    errors = [0]
    classes = [("interactive", None), ("batch", None),
               ("background", None), ("batch", "capped"),
               ("batch", "capped")]
    per_client = max(total // len(classes), 1)
    capped = sess()
    capped.set("spark.rapids.sql.scheduler.qos.tenantMaxInFlight", 1)

    def client(k, cls, tenant):
        cs = capped if tenant else s
        for j in range(per_client):
            i = k * per_client + j
            q0 = time.perf_counter()
            try:
                shape(cs, i).collect(priority=cls,
                                     tenant=tenant or f"t{k}")
            except _sched.QueryRejectedError:
                with lock:
                    rejected[0] += 1
                continue
            except Exception:
                with lock:
                    errors[0] += 1
                continue
            took = time.perf_counter() - q0
            with lock:
                lat[cls].append(took)

    t0 = time.perf_counter()
    workers = [threading.Thread(target=client, args=(k, cls, tenant),
                                daemon=True, name=f"srt-qos-{k}")
               for k, (cls, tenant) in enumerate(classes)]
    for w in workers:
        w.start()
    for w in workers:
        w.join()
    wall = time.perf_counter() - t0
    c1 = _qos.counters()

    def diff(name):
        return int(c1.get(name, 0) - c0.get(name, 0))

    def pct(xs, q):
        if not xs:
            return None
        xs = sorted(xs)
        return round(xs[min(int(q * len(xs)), len(xs) - 1)] * 1000, 2)

    return {
        "queries": per_client * len(classes), "clients": len(classes),
        "max_concurrent": 2, "weights": weights,
        "starvation_bound": 2,
        "wall_s": round(wall, 3), "errors": errors[0],
        "per_class": {
            cls: {"count": len(lat[cls]), "p50_ms": pct(lat[cls], 0.50),
                  "p99_ms": pct(lat[cls], 0.99)}
            for cls in _qos.CLASSES
        },
        "rejections": {
            kind: diff(f"rejected.{kind}")
            for kind in ("queue-full", "admission-timeout",
                         "tenant-quota", "deadline-unmeetable")
        },
        "rejected_total": rejected[0],
        "starvation_bound_engagements": diff(
            "starvationBoundEngagements"),
        "quota_evictions": diff("quotaEvictions"),
    }


def _concurrency_probe(tpch_dir: str, n: int) -> dict:
    """N-query throughput: N fresh sessions run hot q6 serially, then
    the same N concurrently through the scheduler (each on its own
    thread). Kernels are already compiled (the main loop ran q6), so
    this measures admission + isolation overhead and device sharing,
    not compilation."""
    from spark_rapids_tpu.benchmarks import tpch

    dfs = [tpch.QUERIES["q6"](_session(), tpch_dir) for _ in range(n)]
    for df in dfs:
        df.collect()            # warm: plan cache + device scan cache
    t0 = time.perf_counter()
    for df in dfs:
        df.collect()
    serial_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    handles = [df.submit() for df in dfs]
    errors = 0
    for h in handles:
        try:
            h.result(300)
        except Exception:
            errors += 1
    concurrent_s = time.perf_counter() - t0
    return {
        "query": "q6", "queries": n, "errors": errors,
        "serial_s": round(serial_s, 4),
        "concurrent_s": round(concurrent_s, 4),
        "speedup": round(serial_s / concurrent_s, 3)
        if concurrent_s > 0 else None,
    }


def main():
    budget = float(os.environ.get("BENCH_BUDGET_S", "420"))
    threading.Thread(target=_watchdog, args=(budget,), daemon=True).start()

    from spark_rapids_tpu import faults as _faults
    from spark_rapids_tpu.benchmarks import suites, tpch
    from spark_rapids_tpu.io.scan import DEVICE_SCAN_CACHE
    from spark_rapids_tpu.ops import kernel_cache as _kc
    from spark_rapids_tpu.parallel import pipeline as _pl

    import jax
    devs = jax.devices()
    # A CPU backend has no HBM: its runs carry no share of HBM bandwidth.
    on_cpu = devs[0].platform == "cpu"
    sf = float(os.environ.get("TPCH_SF", "1.0"))
    iters = int(os.environ.get("BENCH_ITERS", "2"))
    tpch_dir = os.environ.get("TPCH_DIR", f"/tmp/srt_tpch_sf{sf:g}")
    suites_dir = os.environ.get("SUITES_DIR", f"/tmp/srt_suites_sf{sf:g}")
    t0 = time.perf_counter()
    rows = tpch.generate(tpch_dir, scale=sf)
    rows.update(suites.generate(suites_dir, scale=sf))
    gen_s = time.perf_counter() - t0
    manifest = f"sf{sf:g}:" + ",".join(
        f"{k}={v}" for k, v in sorted(rows.items()))

    # Budget order: ALL the BASELINE.md target configs first — q67
    # included — so the 420s budget can only truncate the NON-target
    # tail; a partial JSON always contains every target the budget
    # could possibly fit (the r5 lesson: a headline that ships without
    # a q67 number is a hole, not a speedup). q67 runs THIRD, right
    # after the cheap q1/q6 scans: r5 ran it last among the targets and
    # the budget cut it (timed_out with q67 absent — VERDICT weak #1);
    # its rollup+window compile cost is also the biggest winner of the
    # persistent kernel cache the session now warms. The remaining
    # TPC-H/TPC-DS coverage queries run cheapest-first.
    packs = {
        "q1": (tpch, tpch_dir), "q6": (tpch, tpch_dir),
        "q67": (suites, suites_dir),
        "q3": (tpch, tpch_dir), "q5": (tpch, tpch_dir),
        "xbb_q5": (suites, suites_dir), "repart": (suites, suites_dir),
    }
    for qn in ("q14", "q19", "q12", "q22", "q11", "q15", "q16", "q2",
               "q4", "q17", "q20", "q10", "q13", "q7", "q8", "q9",
               "q18", "q21"):
        packs[qn] = (tpch, tpch_dir)
    for qn in ("ds_q3", "ds_q42", "ds_q89", "ds_q55", "ds_q98",
               "xbb_q12"):
        packs[qn] = (suites, suites_dir)
    sel = os.environ.get("BENCH_QUERIES", ",".join(packs)).split(",")
    qnames = [q for q in packs if q in sel]

    device_s = {}       # hot / steady-state (post-warmup medians)
    first_s = {}        # first device run: compile + cold scan + check
    cold_s = {}         # post-compile cold runs (q1/q6 scan headline)
    compile_s = {}      # first-minus-steady: the compile-ish overhead
    cache_q = {}        # per-query kernel-cache hit/miss deltas
    pandas_s = {}
    ok = _STATE["ok"]
    out = {
        "metric": f"tpc_sf{sf:g}_suite{len(qnames)}_wall_clock",
        "value": None, "unit": "s", "vs_baseline": None,
        "baseline": "pandas/pyarrow CPU engine, same queries+data+machine",
        # Where "device_s" ran, as JAX reports it: a CPU run names the
        # CPU (BENCH_r06 did not, and was read as a chip record).
        "device": {"platform": devs[0].platform,
                   "kind": devs[0].device_kind, "count": len(devs)},
        "correct": ok, "device_s": device_s, "first_run_s": first_s,
        "cold_s": cold_s, "compile_s": compile_s, "pandas_s": pandas_s,
        "kernel_cache": {}, "kernel_cache_per_query": cache_q,
        "completed": [], "timed_out": False, "partial": True,
        "rows": rows, "datagen_s": round(gen_s, 2),
        # Recovery machinery counters (memory/oom.py ladder, planner
        # transient retry, host degradation, fault injection): all zero
        # on a healthy run — nonzero values say the run survived real
        # pressure (or an SRT_FAULTS chaos schedule).
        "recovery": {},
        # Pipelined-executor counters (parallel/pipeline.py): overlap of
        # host decode/encode with device dispatch. overlapRatio > 0 says
        # the overlap is actually happening; 0/absent says the pipeline
        # degenerated (or SRT_PIPELINE=0).
        "pipeline": {},
        # Multi-query scheduler (parallel/scheduler.py): admission
        # counters for the whole run plus the N-query-vs-serial
        # throughput measurement (filled after the per-query loop when
        # the budget allows).
        "scheduler": {},
        "concurrency": {},
        # Parameterized plan cache (plan/plan_cache.py): template
        # hits/misses + bind-only executions for the whole run, and the
        # sustained-load serving block (N clients x mixed parameterized
        # shapes at maxConcurrentQueries=4 — p50/p99, qps, hit rate,
        # and the q6-class bind-only-vs-replan speedup).
        "plan_cache": {},
        "sustained": {},
        # Serving QoS subsystem (parallel/qos/): per-class latency
        # under weighted fair queueing, rejections by kind, starvation
        # -bound engagements, and per-tenant quota evictions.
        "qos": {},
        # Shuffle transport SPI (parallel/transport/): which transport
        # served the run plus its byte/shard counters — nonzero
        # remoteShardRefetches/remoteShardsLost say the run recovered
        # from data-at-rest damage.
        "transport": {},
        # Cost-based placement + runtime adaptive re-planning
        # (plan/cost.py, parallel/replan.py): how many queries were
        # host-placed by the static model and how many shuffled joins
        # demoted to broadcast from observed shuffle sizes.
        "cost": {},
        # Ingest fast path (columnar/wire.py): raw vs encoded wire
        # bytes, per-codec column counts, transfer counts and the
        # staging-buffer grouping rate; `scan` is the fast-path
        # microbench that produces the scan_gb_per_sec headline.
        "wire": {},
        "scan_bench": {},
        # Vectorized host engine (numpy fallback path): per-query
        # forced-host wall vs the pandas oracle — vs_pandas > 1 means
        # the host engine wins; the perf gate holds the floor at 0.5.
        "host_engine": {},
        # Native Pallas kernel layer (ops/native.py): the enabled
        # kernel set (empty on CPU — the layer no-ops to the jax.numpy
        # fallback there), per-kernel trace counts, and the cost
        # model's self-calibrated effective constants.
        "native": {},
        # Query flight recorder (spark_rapids_tpu/monitoring/): one
        # TRACED q3 run after the timing loop — the span-category wall
        # breakdown (queued/host-prefetch/device-compute/upload/
        # shuffle/recovery) plus the Chrome trace JSON artifact path
        # (loads in Perfetto / chrome://tracing).
        "trace": {},
    }
    with _LOCK:
        _STATE["out"] = out

    for qn in qnames:
        # Skip a query we clearly can't finish: leave headroom for the
        # report instead of letting the watchdog cut mid-query.
        if _remaining(budget) < 20:
            break
        mod, ddir = packs[qn]
        want, psecs = _oracle_cached(mod, qn, ddir, manifest)
        df = mod.QUERIES[qn](_session(), ddir)
        kc0 = _kc.cache().stats()
        t0 = time.perf_counter()
        got = df.collect()          # compile + cold scan + cache populate
        fsecs = time.perf_counter() - t0
        qok = bool(mod.check_result(qn, got, want))
        with _LOCK:
            # Record the verdict BEFORE the timing runs: a deadline hit
            # during them must still surface this query's failure.
            ok[qn] = qok
        times = []
        for _ in range(iters):
            if times and _remaining(budget) < times[-1] + 10:
                break               # keep what we have; report it
            t0 = time.perf_counter()
            df.collect()
            times.append(time.perf_counter() - t0)
        csecs = None
        if qn in ("q1", "q6") and \
                _remaining(budget) > fsecs + 10:
            # Post-compile cold run: decode + upload + kernels, no
            # compile — the scan-bandwidth denominator prior rounds used.
            DEVICE_SCAN_CACHE.clear()
            t0 = time.perf_counter()
            df.collect()
            csecs = time.perf_counter() - t0
        kc1 = _kc.cache().stats()
        with _LOCK:
            pandas_s[qn] = round(psecs, 4)
            first_s[qn] = round(fsecs, 4)
            if csecs is not None:
                cold_s[qn] = round(csecs, 4)
            device_s[qn] = round(statistics.median(times) if times
                                 else fsecs, 4)
            # Compile-inclusive first run minus the steady-state median:
            # the retrace cost a warm process (serving, later iterations)
            # no longer pays thanks to the process-global kernel cache.
            compile_s[qn] = round(max(fsecs - device_s[qn], 0.0), 4)
            cache_q[qn] = {
                "hits": kc1["hits"] - kc0["hits"],
                "misses": kc1["misses"] - kc0["misses"]}
            out["kernel_cache"] = kc1
            out["recovery"] = _faults.counters()
            out["pipeline"] = _pl.counters()
            out["completed"].append(qn)
            done = out["completed"]
            out["metric"] = f"tpc_sf{sf:g}_suite{len(done)}_wall_clock"
            out["partial"] = len(done) < len(qnames)
            dev_total = sum(device_s[q] for q in done)
            cpu_total = sum(pandas_s[q] for q in done)
            out["value"] = round(dev_total, 4)
            # Headline ratio covers the five BASELINE.md target configs;
            # the full completed set reports separately (the extra TPC-H
            # coverage queries are correctness surface first).
            tgt = [q for q in done if q in _TARGETS]
            tdev = sum(device_s[q] for q in tgt)
            tcpu = sum(pandas_s[q] for q in tgt)
            if tdev > 0:
                out["vs_baseline"] = round(tcpu / tdev, 3)
            if dev_total > 0:
                out["vs_baseline_all"] = round(cpu_total / dev_total, 3)
            if "q1" in cold_s and "q6" in cold_s:
                scan_bytes = tpch.bytes_scanned("q1", tpch_dir) + \
                    tpch.bytes_scanned("q6", tpch_dir)
                denom = cold_s["q1"] + cold_s["q6"]
                out["scan_gb_per_sec"] = round(scan_bytes / denom / 1e9, 3)
                if not on_cpu:
                    out["scan_frac_of_hbm_bw"] = round(
                        out["scan_gb_per_sec"] / _hbm_gb_per_sec(), 5)
        DEVICE_SCAN_CACHE.clear()

    # Scan-bandwidth microbench from the ingest fast path: the
    # scan_gb_per_sec headline is measured HERE (post-compile cold runs
    # through codec v2 + coalesced uploads); the q1/q6 cold_s derivation
    # above remains as scan_gb_per_sec_q1q6 for cross-round comparison.
    if "q1" in _STATE["ok"] and "q6" in _STATE["ok"] and \
            _remaining(budget) > 30:
        probe = _scan_probe(packs["q1"][1])
        with _LOCK:
            out["scan_bench"] = probe
            if "scan_gb_per_sec" in out:
                out["scan_gb_per_sec_q1q6"] = out["scan_gb_per_sec"]
            if probe.get("gb_per_sec"):
                out["scan_gb_per_sec"] = probe["gb_per_sec"]
                if not on_cpu:
                    out["scan_frac_of_hbm_bw"] = round(
                        probe["gb_per_sec"] / _hbm_gb_per_sec(), 5)

    # One TRACED q3 run (outside the timing loop — tracing costs ~µs per
    # span but the timed medians stay untouched): exports the Chrome
    # trace artifact and the span-category wall breakdown.
    if "q3" in _STATE["ok"] and _remaining(budget) > 30:
        trace_path = os.environ.get("BENCH_TRACE_PATH",
                                    "/tmp/srt_bench_q3_trace.json")
        try:
            probe = _trace_probe(packs["q3"][1], trace_path)
            with _LOCK:
                out["trace"] = probe
        except Exception as e:     # the headline must survive a probe bug
            with _LOCK:
                out["trace"] = {"error": f"{type(e).__name__}: {e}"}

    # Forced-host engine sweep: the host-path headline (the 30x gap vs
    # pandas this round closed). No compile step, so it is cheap next to
    # the device loop; still budget-gated.
    if _remaining(budget) > 60:
        he = _host_engine_probe(packs, pandas_s, budget)
        with _LOCK:
            out["host_engine"] = he

    # N-query concurrent throughput vs serial (the scheduler's reason to
    # exist): N fresh sessions run the same hot query back-to-back and
    # then simultaneously — speedup > 1 says admission + isolation let
    # concurrent queries share the device productively.
    if "q6" in _STATE["ok"] and _remaining(budget) > 30:
        conc = _concurrency_probe(packs["q6"][1],
                                  int(os.environ.get(
                                      "BENCH_CONCURRENCY", "2")))
        with _LOCK:
            out["concurrency"] = conc

    # Sustained serving load through the plan cache: the "millions of
    # users" block — mixed parameterized shapes, new literals per call.
    if "q6" in _STATE["ok"] and _remaining(budget) > 60:
        try:
            sus = _sustained_probe(
                packs["q6"][1],
                int(os.environ.get("BENCH_SUSTAINED_QUERIES", "200")),
                int(os.environ.get("BENCH_SUSTAINED_CLIENTS", "4")))
        except Exception as e:  # the headline must survive a probe bug
            sus = {"error": f"{type(e).__name__}: {e}"}
        with _LOCK:
            out["sustained"] = sus

    # Serving QoS: mixed-class WFQ load with a capped tenant (the
    # tenant-quota rejections and starvation-bound engagements the
    # subsystem exists to produce under pressure).
    if "q6" in _STATE["ok"] and _remaining(budget) > 45:
        try:
            qjs = _qos_probe(packs["q6"][1],
                             int(os.environ.get("BENCH_QOS_QUERIES",
                                                "100")))
        except Exception as e:  # the headline must survive a probe bug
            qjs = {"error": f"{type(e).__name__}: {e}"}
        with _LOCK:
            out["qos"] = qjs

    from spark_rapids_tpu.parallel import scheduler as _sched
    with _LOCK:
        sch = _sched.counters()
        for name in ("admitted", "rejected", "cancelled", "deadlineKills",
                     "crossQueryEvictions", "queuedMs"):
            sch.setdefault(name, 0)
        out["scheduler"] = sch
        rec = _faults.counters()
        # Headline recovery counters always present (zero on a healthy
        # run); the per-stage detail (stageRecomputes.stage<N>) and
        # per-site injection detail ride along from the counter map.
        for name in ("faultsInjected", "retriesAttempted",
                     "spillEscalations", "hostFallbacks",
                     "corruptionsDetected", "stageRecomputes",
                     "partitionRetries", "watchdogKills", "meshDegrades",
                     "meshCollectiveSkipped", "crossQueryEvictions",
                     "graceJoinPartitions", "graceJoinEngaged"):
            rec.setdefault(name, 0)
        out["recovery"] = rec
        from spark_rapids_tpu.columnar import wire as _wire
        w = _wire.counters()
        for name in ("rawBytes", "encodedBytes", "stagingBytes",
                     "uploadCalls", "uploadTransfers", "uploadedBatches",
                     "groupedUploads", "wireCompressionRatio",
                     "stagingHitRate"):
            w.setdefault(name, 0)
        w["codec"] = _wire.codec_mode()
        out["wire"] = w
        pl = _pl.counters()
        for name in ("hostPrefetchMs", "consumerWaitMs", "pipelineStalls",
                     "prefetchedPartitions", "concurrentStages",
                     "overlapRatio"):
            pl.setdefault(name, 0)
        out["pipeline"] = pl
        from spark_rapids_tpu import config as _C
        from spark_rapids_tpu.parallel import transport as _tp
        tp = _tp.counters()
        for name in ("transportBytesWritten", "transportBytesFetched",
                     "transportShardsWritten", "transportShardsFetched",
                     "remoteShardRefetches", "remoteShardsLost"):
            tp.setdefault(name, 0)
        tp["selected"] = _tp.transport_name(_C.TpuConf())
        out["transport"] = tp
        from spark_rapids_tpu.plan import cost as _cost
        cs = _cost.counters()
        for name in ("costPlanningRuns", "costHostPlacements",
                     "costHostPlacedNodes", "replanChecks",
                     "joinDemotions"):
            cs.setdefault(name, 0)
        cs["enabled"] = _cost.cost_enabled(_C.TpuConf())
        out["cost"] = cs
        from spark_rapids_tpu.plan import plan_cache as _plc
        plc = _plc.counters()
        for name in ("planCacheHits", "planCacheMisses",
                     "bindOnlyExecutions", "planCacheBypasses",
                     "planCacheUncacheable", "planBindNs"):
            plc.setdefault(name, 0)
        plc["entries"] = _plc.cache().stats()["entries"]
        plc["enabled"] = _plc.plan_cache_enabled(_C.TpuConf())
        out["plan_cache"] = plc
        from spark_rapids_tpu.ops import native as _native
        nt = _native.counters()
        for name in ("nativeRadixSortTraces", "nativeJoinProbeTraces",
                     "nativeRleDecodeTraces",
                     "nativeSegmentReduceTraces"):
            nt.setdefault(name, 0)
        nt["calibration"] = _cost.calibration_state()
        out["native"] = nt
        from spark_rapids_tpu.monitoring import telemetry as _tm
        if _tm.enabled():
            # Compact registry rollup (the sustained block flips metrics
            # on, so a full bench run always carries this): the query
            # counter series plus how many series/metrics exist at exit.
            snap = _tm.snapshot()["metrics"]
            out["telemetry"] = {
                "enabled": True,
                "metrics": len(snap),
                "series": sum(len(m["series"]) for m in snap.values()),
                "queries_by_series": {
                    ",".join(f"{k}={v}" for k, v in
                             sorted(s["labels"].items())) or "-":
                    s["value"]
                    for s in snap.get("srt_queries",
                                      {}).get("series", [])},
            }
        else:
            out["telemetry"] = {"enabled": False}
        _STATE["done"] = True
        _emit(out)
    # No completed query = nothing measured: that is a failure signal even
    # though no individual check failed (vacuous all() must not pass).
    sys.exit(0 if out["completed"] and all(ok.values()) else 1)


if __name__ == "__main__":
    main()
