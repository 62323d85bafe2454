"""benchmark/run.py — one cell of BENCHMARK.json, measured (PR 24).

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, JAX touched once, no child process, no network. Without a TPU
(or with fewer chips than the cell asks for) it exits non-zero and prints
no result. ``--rehearse-cpu [--scale 0.01]`` (flags the driver never
passes) runs the same control flow on the CPU backend, on as many virtual
devices as the cell has chips, and its result line says so: it proves the
control flow and nothing about a chip.

What a cell is comes from files found by the names in ``BENCHMARK.json``:
``configs/<config>.json`` (suite, scale, ``conf``), ``traffic/<traffic>
.json`` (the mix), ``metrics/<name>.py`` (one reader per per-layer
metric; ``<name>.<suffix>`` is ``<name>`` as another group of cells reads
it), ``<suite>.py`` (generator, query builders, plain reference).
Nothing about a cell, query, scale, ``conf`` or chip count is written
here.

A run: make the cell's tables as parquet from ``--seed``; one
``TpuSession`` with the configuration's ``conf``; every query of the mix
collected twice (set-up: loads or compiles every program, fills the scan
cache where the configuration has one); then the window, a closed loop of
one client that builds the DataFrame anew and calls ``collect()`` until
``--seconds`` are over (the query in flight is finished and counted).
After the window: the peak of device memory is read, the plain float64
reference is computed over the same files, and EVERY answer the window
received is held against it (``compare.py``). Facts are printed as one
JSON object per line while it runs; the last line of standard output is
the result, the last lines of standard error the numbers compared, each
beside its limit.

``--trace 1`` turns on the flight recorder (kernel level), the sync
attribution and, for the first seconds of the window, ``jax.profiler``;
its metrics are the per-layer ones. ``--trace 0`` turns on none of them.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()         # set-up is counted from here

import argparse                       # noqa: E402
import contextlib                     # noqa: E402
import importlib                      # noqa: E402
import importlib.util                 # noqa: E402
import json                           # noqa: E402
import math                           # noqa: E402
import os                             # noqa: E402
import shutil                         # noqa: E402
import statistics                     # noqa: E402
import sys                            # noqa: E402
import tempfile                       # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_SECONDS = 4.0                   # of the window, under jax.profiler
FRESH_VS_REUSED = 5                   # collects a side, traced run only


def emit(phase: str, **facts) -> None:
    print(json.dumps({"phase": phase, **facts}), flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str):
    """The cell's entry, its configuration and its traffic mix."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json "
                         f"(known: {sorted(cells)})")
    cell = cells[name]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    config = load_json(ROOT, files[cell["config"]])
    import traffic
    return bench, cell, config, traffic.load(cell["traffic"])


def base(name: str) -> str:
    """``device_busy_ms.parquet`` is ``device_busy_ms`` as the cells that
    report ``query_s.parquet`` read it: a quantity whose cells report
    different end-to-end metrics is split by a suffix after a dot, and
    every part is the one reader's (or the one window statistic's)."""
    return name.split(".", 1)[0]


def metric_reader(name: str):
    """``benchmark/metrics/<base(name)>.py``'s ``read``."""
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + base(name).replace("-", "_"),
        os.path.join(HERE, "metrics", base(name) + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def applies(metric: dict, cell: str, bench: dict) -> bool:
    """Does ``cell`` report ``metric``? Its ``workloads`` say; without
    them an end-to-end metric is every cell's, and a per-layer metric
    goes where the end-to-end metric it ``moves`` goes."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:
        moved = [m for m in bench["end_to_end"]
                 if m["name"] == metric["moves"]]
        return bool(moved) and applies(moved[0], cell, bench)
    return True


class CompileClock:
    """Seconds the backend spent compiling and the programs it compiled,
    from jax's own monitoring events: eager ops and every jit, not only
    the program's cached kernels (copied from ``chip_smoke.py``)."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.programs = 0
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def close(self) -> None:
        self._jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.seconds += duration
            self.programs += 1


def plan_host_nodes(df) -> int:
    """Plan nodes that do not run on the device: fallen back to the host
    engine, or placed there by the cost model."""
    phys = df._physical()
    placed = getattr(phys.cost_report, "nodes_host_placed", 0) or 0
    return len(phys.host_fallback_nodes()) + int(placed)


class RecorderTotals:
    """The flight recorder's spans, drained after each query (its rings
    keep 64 queries): milliseconds by category, and the count of syncs."""

    def __init__(self):
        self.category_ms = {}
        self.syncs = 0
        self.queries = 0

    def drain(self, recorder) -> None:
        for ph, _name, cat, _ts, dur, *_ in recorder.events():
            if ph != "X":
                continue
            self.category_ms[cat] = self.category_ms.get(cat, 0.0) + dur / 1e6
            self.syncs += cat == "sync"
        recorder.reset()
        self.queries += 1


def nearest_rank(xs, q: float) -> float:
    xs = sorted(xs)
    return xs[max(math.ceil(q * len(xs)), 1) - 1]


def run_cell(args, jax, devs) -> dict:
    """Everything after the look for a chip. Returns the result line."""
    import jax.profiler as profiler
    import spark_rapids_tpu  # noqa: F401  (x64, the compile cache's rule)
    from spark_rapids_tpu.api.dataframe import TpuSession
    from spark_rapids_tpu.monitoring import recorder, syncs
    import compare
    import trace_reduce
    import traffic
    import work

    bench, cell, config, mix = args.cell
    suite = importlib.import_module(config["suite"])
    names = traffic.queries(mix)
    scale = args.scale if args.rehearse_cpu else config["scale"]
    tracing = args.trace == 1
    emit("cell", workload=cell["name"], config=cell["config"],
         traffic=cell["traffic"], chips=cell["chips"], queries=names,
         scale=scale, seed=args.seed, seconds=args.seconds,
         trace=args.trace, conf=config["conf"],
         platform=devs[0].platform, device_kind=devs[0].device_kind,
         device_count=len(devs), host_cpus=os.cpu_count(),
         compile_cache_dir=jax.config.jax_compilation_cache_dir,
         rehearsal=bool(args.rehearse_cpu))

    workdir = tempfile.mkdtemp(prefix="benchmark_")
    clock = CompileClock(jax)
    try:
        # -- set-up ------------------------------------------------------
        data_dir = os.path.join(workdir, "data")
        tables = sorted({t for q in names for t in suite.QUERY_COLUMNS[q]})
        t0 = time.perf_counter()
        rows = suite.generate(data_dir, scale=scale, seed=args.seed,
                              files_per_table=config["files_per_table"],
                              tables=tables)
        emit("datagen", seconds=time.perf_counter() - t0, rows=rows)

        session = TpuSession()
        for k, v in config["conf"].items():
            session.set(k, v)
        if tracing:
            session.set("spark.rapids.sql.trace.enabled", True)
            session.set("spark.rapids.sql.trace.level", "kernel")
            syncs.install()

        def collect(q):
            # What a client does to submit the query again: API -> plan
            # cache/bind -> execs -> rows on the host.
            return suite.QUERIES[q](session, data_dir).collect()

        answers = []                  # every answer received, in order
        host_nodes = 0
        first_collect_s = 0.0
        for q in names:
            host_nodes += plan_host_nodes(suite.QUERIES[q](session,
                                                           data_dir))
            for i in range(2):
                t0 = time.perf_counter()
                got = collect(q)
                secs = time.perf_counter() - t0
                if i == 0:
                    first_collect_s += secs
                answers.append({"query": q, "rows": got})
                emit("warm_up", query=q, collect=i + 1, seconds=secs,
                     backend_compile_s=clock.seconds,
                     programs_compiled=clock.programs)
        warm_ups = len(answers)
        compile_s, compiled_before = clock.seconds, clock.programs
        if tracing:
            recorder.reset()
            trace_dir = os.path.join(workdir, "trace")
            opts = profiler.ProfileOptions()
            opts.python_tracer_level = 0
            profiler.start_trace(trace_dir, profiler_options=opts)
        totals = RecorderTotals()
        order = traffic.schedule(mix, args.seed)

        # -- the window --------------------------------------------------
        times, failed, profiling, errors = [], 0, tracing, {}
        t_window = t_end = time.perf_counter()
        setup_s = t_window - T_START
        while t_end - t_window < args.seconds:
            q = next(order)
            t0 = time.perf_counter()
            try:
                with (profiler.TraceAnnotation(trace_reduce.QUERY_ANNOTATION)
                      if profiling else contextlib.nullcontext()):
                    got = collect(q)
            except Exception as e:          # counted, and the run goes on
                got, failed = None, failed + 1
                errors[len(answers)] = repr(e)[:200]
                emit("query_failed", query=q, error=repr(e)[:500])
            t_end = time.perf_counter()
            times.append(t_end - t0)
            answers.append({"query": q, "rows": got})
            if tracing:
                totals.drain(recorder)
                if profiling and t_end - t_window >= min(
                        TRACE_SECONDS, args.seconds):
                    profiler.stop_trace()
                    profiling = False
        if profiling:
            profiler.stop_trace()
        window_s = t_end - t_window
        attempted = len(times)
        emit("window", queries=attempted, failed=failed, window_s=window_s,
             query_s=window_s / attempted, median_s=statistics.median(times),
             p95_s=nearest_rank(times, 0.95), max_s=max(times),
             min_s=min(times), setup_s=setup_s,
             programs_compiled_in_window=clock.programs - compiled_before)
        emit("query_times", seconds=[round(t, 5) for t in times])

        # -- after the window ---------------------------------------------
        stats = [d.memory_stats() or {} for d in devs]
        peaks = [s.get("peak_bytes_in_use") for s in stats]
        memory_peak = max(peaks) if all(p is not None for p in peaks) \
            else None
        if tracing:
            fresh_vs_reused(suite, session, data_dir, names[0])
        report_counters()
        del session

        references = {}
        t0 = time.perf_counter()
        for q in names:
            references[q] = suite.pandas_query(q, data_dir)
        emit("reference", engine="pandas float64",
             seconds=time.perf_counter() - t0,
             rows={q: len(r) for q, r in references.items()})
        verdict = compare.judge(answers, references, suite.SET_COMPARE,
                                sent=warm_ups + attempted)
        wrong_in_window = sum(i >= warm_ups for i in verdict["wrong"])
        # What was wrong, for whoever reads the end of a refused run (the
        # driver keeps the last 2,000 characters of the result line): the
        # first two answers at fault, as received, beside the reference's.
        shown = [{"answer": i, "in_window": i >= warm_ups,
                  "query": answers[i]["query"],
                  "seconds": (round(times[i - warm_ups], 5)
                              if i >= warm_ups else None),
                  "error": errors.get(i),
                  "got": repr(answers[i]["rows"])[:200],
                  "want": repr(references[answers[i]["query"]])[:200]}
                 for i in verdict["wrong"][:2]]
        for w in shown:
            emit("wrong_answer", **w)

        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs), "memory_peak_bytes": memory_peak}
        result = {"correct": verdict["correct"] and failed == 0,
                  "attempted": attempted,
                  "failed": max(failed, wrong_in_window)}
        if not tracing:
            window = {"query_s": window_s / attempted,
                      "query_p95_s": nearest_rank(times, 0.95),
                      "setup_s": setup_s}
            wanted = bench["end_to_end"]
            values = {m["name"]: window[base(m["name"])] for m in wanted
                      if applies(m, cell["name"], bench)}
        else:
            reduced = None
            path = trace_reduce.find_trace(trace_dir)
            if path:
                reduced = trace_reduce.reduce_trace(path)
            if path and args.keep_trace:
                os.makedirs(args.keep_trace, exist_ok=True)
                shutil.copy(path, args.keep_trace)
            emit("trace", platform=devs[0].platform,
                 file_bytes=os.path.getsize(path) if path else None,
                 reduction=reduced)
            on_chip = devs[0].platform == "tpu"
            query_bytes = {q: work.query_bytes(suite, q, data_dir)
                           for q in names}         # reads the files: once
            ctx = {
                "cell": cell, "config": config, "queries": attempted,
                "recorder": totals,
                # A CPU trace reduces too (the rehearsal), but what it
                # gives is no device number and fills no device metric.
                "trace": reduced if on_chip else None,
                "setup": {"compile_s": compile_s,
                          "first_collect_s": first_collect_s},
                "plan_host_nodes": host_nodes,
                "memory_peak_bytes": memory_peak,
                "work_bytes_per_query": statistics.fmean(
                    query_bytes[a["query"]] for a in answers[warm_ups:]),
                "device_kind": devs[0].device_kind,
            }
            values = {m["name"]: metric_reader(m["name"])(ctx)
                      for m in bench["per_layer"]
                      if applies(m, cell["name"], bench)}
            wanted = bench["per_layer"]
            if reduced and on_chip:
                device["busy_s"] = reduced["busy_s"]
                device["window_s"] = reduced["window_s"]
                result["breakdown"] = {"device_ops": reduced["device_ops"],
                                       "idle_gaps": reduced["idle_gaps"]}
        units = {m["name"]: m["unit"] for m in wanted
                 if applies(m, cell["name"], bench)}
        result["metrics"] = {k: {"value": v, "unit": units[k]}
                             for k, v in values.items()
                             if k in units and v is not None}
        result["device"] = device
        if args.rehearse_cpu:
            result["rehearsal"] = ("CPU backend at scale %g: control flow "
                                   "only, no number here is a chip's"
                                   % scale)
        if shown:
            result["wrong_answers"] = shown
        result["checks"] = verdict["checks"]          # last, by contract
        return result
    finally:
        clock.close()
        shutil.rmtree(workdir, ignore_errors=True)


def fresh_vs_reused(suite, session, data_dir, q) -> None:
    """Does a DataFrame built anew for every query (what the window does)
    cost more than a bind? Compared once, after the traced window, with
    ``chip_smoke.py``'s way: one DataFrame collected again and again."""
    def timed(fn):
        t0 = time.perf_counter()
        fn()
        return time.perf_counter() - t0
    fresh = [timed(lambda: suite.QUERIES[q](session, data_dir).collect())
             for _ in range(FRESH_VS_REUSED)]
    df = suite.QUERIES[q](session, data_dir)
    reused = [timed(df.collect) for _ in range(FRESH_VS_REUSED)]
    emit("fresh_vs_reused", query=q, n=FRESH_VS_REUSED,
         fresh_median_s=statistics.median(fresh),
         reused_median_s=statistics.median(reused), tracing=True)


def report_counters() -> None:
    """The program's own counts, for the reader of a run's lines."""
    from spark_rapids_tpu import faults
    from spark_rapids_tpu.columnar import wire
    from spark_rapids_tpu.ops import kernel_cache as kc
    from spark_rapids_tpu.parallel import pipeline
    from spark_rapids_tpu.plan import plan_cache
    emit("counters", wire=wire.counters(), pipeline=pipeline.counters(),
         plan_cache=plan_cache.counters(), kernel_cache=kc.cache().stats(),
         persistent_cache=kc.persistent_stats(),
         recovery={k: v for k, v in faults.counters().items() if v})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="the control flow on the CPU backend; the result "
                         "names the CPU")
    ap.add_argument("--scale", type=float, default=0.01,
                    help="scale factor of a rehearsal (a measured run "
                         "takes the configuration's)")
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="copy the traced run's .xplane.pb there")
    args = ap.parse_args(argv)
    if HERE not in sys.path:
        sys.path.insert(0, HERE)
    if ROOT not in sys.path:
        sys.path.insert(1, ROOT)
    args.cell = load_cell(args.workload)
    need = args.cell[1]["chips"]
    # The compile cache at a fixed path inside the checkout: the program
    # takes JAX_COMPILATION_CACHE_DIR where it is set, and two checkouts
    # on one machine then share nothing.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                           ".jax_cache")
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if "jax" not in sys.modules:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={need}").strip()
    import jax
    devs = jax.devices()
    want = "cpu" if args.rehearse_cpu else "tpu"
    if devs[0].platform != want:
        print(f"run.py: need a {want} backend, JAX found "
              f"{devs[0].platform} ({devs[0].device_kind})", file=sys.stderr)
        return 2
    if len(devs) < need:
        print(f"run.py: the cell needs {need} device(s), JAX found "
              f"{len(devs)}", file=sys.stderr)
        return 2
    result = run_cell(args, jax, devs)
    for w in result.get("wrong_answers", ()):
        print(f"wrong answer: {json.dumps(w)}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: value={c['value']!r} limit={c['limit']!r} "
              f"{'ok' if c['value'] <= c['limit'] else 'OVER'}",
              file=sys.stderr)
    print(f"correct={result['correct']} attempted={result['attempted']} "
          f"failed={result['failed']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
