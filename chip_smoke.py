"""chip_smoke.py — the quickest proof that the engine still starts on the chip.

Drives the main path once, through the entry points a user calls —
``TpuSession`` -> planner -> parquet scan -> wire upload -> device execs ->
``collect()`` — on TPC-H SF1 generated from ``--seed``, with default
configuration, and checks every result against the pandas oracle
(``tpch.pandas_query`` / ``tpch.check_result``).

    python chip_smoke.py            one TPU chip: q6, q1, q3, three collects each
    python chip_smoke.py --mesh     four chips: repart + q5 over the mesh
                                    transport vs the in-process transport
    python chip_smoke.py --cpu-rehearsal [--mesh] [--scale 0.01]
                                    the same control flow on the CPU backend,
                                    named as such; proves nothing about a chip

Without ``--cpu-rehearsal`` it fails at once, non-zero and without a
result, unless ``jax.devices()[0].platform == "tpu"``. One process, JAX
touched once, no child processes, no network. Facts are printed as one
JSON object per line while it runs; any failed phase exits non-zero; the
last line is printed only on success and is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

It asserts that the DEVICE did the work: no host-placed or fallen-back
plan node (without ``spark.rapids.sql.test.enabled``, which would stand
cost placement down and hide a wrong placement), zero recovery counters
(``hostFallbacks``, ``spillEscalations``, ``retriesAttempted``), a
``to_jax()`` leaf that lives on the accelerator, and no kernel-cache miss
and no program compiled on the third collect of a query.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

QUERIES = ("q6", "q1", "q3")          # scan+filter+agg / group-by+sort /
#                                       two joins+agg+sort+limit
MESH_DEVICES = 4


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def emit(phase: str, **facts) -> None:
    print(json.dumps({"phase": phase, **facts}), flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def quartiles(xs):
    xs = sorted(xs)
    return {"n": len(xs), "min": xs[0], "p25": xs[len(xs) // 4],
            "median": statistics.median(xs), "p75": xs[(3 * len(xs)) // 4],
            "max": xs[-1]}


# -- phases -------------------------------------------------------------------

def phase_environment(jax, devs, args) -> None:
    import jaxlib
    import numpy
    import pandas
    import pyarrow
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:                       # not installed: CPU-only image
        libtpu = None
    d = devs[0]
    stats = d.memory_stats() or {}
    emit("environment",
         python=sys.version.split()[0], jax=jax.__version__,
         jaxlib=jaxlib.__version__, libtpu=libtpu,
         numpy=numpy.__version__, pandas=pandas.__version__,
         pyarrow=pyarrow.__version__,
         platform=d.platform, device_kind=d.device_kind,
         device_count=len(devs), bytes_limit=stats.get("bytes_limit"),
         host_cpus=os.cpu_count(),
         JAX_COMPILATION_CACHE_DIR=os.environ.get(
             "JAX_COMPILATION_CACHE_DIR"),
         compile_cache_dir=jax.config.jax_compilation_cache_dir,
         scale=args.scale, seed=args.seed,
         cpu_rehearsal=bool(args.cpu_rehearsal))


def phase_sync_round_trip(jax) -> None:
    """One blocking host read of a device scalar: what
    ``spark.rapids.sql.cost.deviceSyncFloorMs`` stands for."""
    import jax.numpy as jnp
    import numpy as np
    f = jax.jit(lambda x: x + 1)
    x = jnp.asarray(0, jnp.int32)
    f(x).block_until_ready()
    ready, dispatch = [], []
    for _ in range(200):
        y = f(x)
        y.block_until_ready()
        t0 = time.perf_counter_ns()
        np.asarray(y)
        ready.append((time.perf_counter_ns() - t0) / 1e3)
    for _ in range(200):
        t0 = time.perf_counter_ns()
        np.asarray(f(x))
        dispatch.append((time.perf_counter_ns() - t0) / 1e3)
    from spark_rapids_tpu import config as C
    emit("sync_round_trip",
         ready_scalar_read_us=quartiles(ready),
         dispatch_and_read_us=quartiles(dispatch),
         cost_deviceSyncFloorMs_default=C.COST_SYNC_FLOOR_MS.default)


def phase_native_build(workdir: str) -> None:
    """Which spill store this tree gives: the C++ one (built now, from
    source, by g++) or the pure-Python one."""
    from spark_rapids_tpu.memory import native as mn
    f = mn.open_spill_file(os.path.join(workdir, "spill_probe"))
    try:
        bid = f.write(b"chip-smoke")
        check(f.read(bid) == b"chip-smoke", "spill store read-back differs")
    finally:
        f.close()
    emit("native_build", spill_store=type(f).__name__,
         built=sorted(os.path.basename(p) for p in
                      glob.glob(os.path.join(mn._BUILD_DIR, "*.so"))))


def session(**conf):
    """A default session plus two assertions about the DATA (TPC data
    is finite; float sums may reassociate).
    Only the mesh phase passes ``conf``, and prints what it passed."""
    from spark_rapids_tpu.api.dataframe import TpuSession
    s = TpuSession()
    s.set("spark.rapids.sql.variableFloatAgg.enabled", True)
    s.set("spark.rapids.sql.hasNans", False)
    for k, v in conf.items():
        s.set(k, v)
    return s


class CompileClock:
    """Seconds the backend spent compiling, from jax's own monitoring
    events (covers eager ops and every jit, not just cached kernels)."""

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

    def mark(self):
        return (self.seconds, self.programs)

    def since(self, mark):
        return {"backend_compile_s": round(self.seconds - mark[0], 3),
                "programs_compiled": self.programs - mark[1]}


def assert_device_plan(df, label: str) -> None:
    phys = df._physical()
    fallen = phys.host_fallback_nodes()
    check(not fallen, f"{label}: plan nodes on the host engine: {fallen}\n"
          + phys.explain())
    rep = phys.cost_report
    check(rep is not None and rep.skipped is None,
          f"{label}: cost placement did not run "
          f"({getattr(rep, 'skipped', None)!r}) — the placement check "
          f"would be vacuous")
    check(rep.nodes_host_placed == 0 and rep.placements == 0,
          f"{label}: cost model host-placed {rep.nodes_host_placed} "
          f"node(s): {rep.explain_lines()}")
    check(phys.root_on_device, f"{label}: plan root is not on the device")


def phase_queries(clock, data_dir: str, oracle: dict,
                  platform: str) -> None:
    from spark_rapids_tpu import faults
    from spark_rapids_tpu.benchmarks import tpch
    from spark_rapids_tpu.ops import kernel_cache as kc
    for qn in QUERIES:
        df = tpch.QUERIES[qn](session(), data_dir)
        assert_device_plan(df, qn)
        runs = []
        for i in range(3):
            k0, c0 = kc.cache().stats(), clock.mark()
            t0 = time.perf_counter()
            got = df.collect()
            secs = time.perf_counter() - t0
            k1 = kc.cache().stats()
            check(tpch.check_result(qn, got, oracle[qn]),
                  f"{qn} collect #{i + 1} diverges from the pandas oracle: "
                  f"got[:3]={got[:3]} want[:3]={oracle[qn][:3]}")
            runs.append({"seconds": round(secs, 4),
                         "kernel_cache_misses": k1["misses"] - k0["misses"],
                         "kernel_cache_hits": k1["hits"] - k0["hits"],
                         **clock.since(c0)})
            # Said at once: a run cut at its time limit still shows how
            # far it got.
            emit("collect", query=qn, run=i + 1, **runs[-1])
        m = df.metrics()
        cost = m.get("Cost@query", {})
        check(cost.get("hostPlacedNodes", 0) == 0,
              f"{qn}: Cost@query.hostPlacedNodes = {cost}")
        check(runs[2]["kernel_cache_misses"] == 0,
              f"{qn}: third collect missed the kernel cache "
              f"{runs[2]['kernel_cache_misses']} time(s)")
        # A retrace for a new shape INSIDE a cached jit never misses the
        # kernel cache; the backend's own compile events see it.
        check(runs[2]["programs_compiled"] == 0,
              f"{qn}: third collect compiled "
              f"{runs[2]['programs_compiled']} program(s)")
        emit("query", query=qn, rows=len(got), correct=True,
             first_s=runs[0]["seconds"], second_s=runs[1]["seconds"],
             third_s=runs[2]["seconds"],
             first_run_backend_compile_s=runs[0]["backend_compile_s"],
             first_run_programs_compiled=runs[0]["programs_compiled"],
             first_run_kernel_cache_misses=runs[0]["kernel_cache_misses"],
             bytes_scanned=tpch.bytes_scanned(qn, data_dir),
             est_syncs=cost.get("estSyncs"))
    rec = faults.counters()
    for name in ("hostFallbacks", "spillEscalations", "retriesAttempted"):
        check(rec.get(name, 0) == 0,
              f"recovery counter {name} = {rec.get(name)}: the device "
              f"path needed rescuing")
    emit("recovery", **{k: rec.get(k, 0) for k in (
        "hostFallbacks", "spillEscalations", "retriesAttempted",
        "faultsInjected", "stageRecomputes", "meshDegrades")})

    # A to_jax() leaf lives where the engine computed it.
    out = tpch.QUERIES["q6"](session(), data_dir).to_jax()
    leaf = out["revenue"]
    where = sorted({d.platform for d in leaf.devices()})
    check(where == [platform],
          f"to_jax leaf lives on {where}, the backend is {platform}")
    want = oracle["q6"][0][0]
    got = float(leaf[0])
    check(abs(got - want) <= 1e-6 * abs(want),
          f"to_jax q6 revenue {got} vs oracle {want}")
    emit("to_jax", devices=[str(d) for d in leaf.devices()],
         dtype=str(leaf.dtype), shape=list(leaf.shape))


def phase_report(devs, clock) -> None:
    from spark_rapids_tpu.columnar import wire
    from spark_rapids_tpu.ops import kernel_cache as kc
    from spark_rapids_tpu.plan import cost
    emit("compile_cache", **kc.persistent_stats(),
         kernel_cache=kc.cache().stats(),
         backend_compile_s_total=round(clock.seconds, 3),
         programs_compiled_total=clock.programs)
    emit("cost", counters=cost.counters(),
         calibration=cost.calibration_state())
    emit("wire", **{k: v for k, v in wire.counters().items()
                    if k in ("rawBytes", "encodedBytes", "uploadCalls",
                             "uploadTransfers", "uploadedBatches",
                             "wireCompressionRatio")})
    stats = devs[0].memory_stats() or {}
    emit("device_memory", peak_bytes_in_use=stats.get("peak_bytes_in_use"),
         bytes_in_use=stats.get("bytes_in_use"),
         bytes_limit=stats.get("bytes_limit"))


# -- the two runs ---------------------------------------------------------------

def run_single_chip(jax, devs, args, workdir: str) -> None:
    from spark_rapids_tpu.benchmarks import tpch
    phase_sync_round_trip(jax)
    phase_native_build(workdir)
    data_dir = os.path.join(workdir, "tpch")
    t0 = time.perf_counter()
    rows = tpch.generate(data_dir, scale=args.scale, seed=args.seed)
    emit("datagen", seconds=round(time.perf_counter() - t0, 2), rows=rows)
    oracle, secs = {}, {}
    for qn in QUERIES:
        t0 = time.perf_counter()
        oracle[qn] = tpch.pandas_query(qn, data_dir)
        secs[qn] = round(time.perf_counter() - t0, 3)
    emit("oracle", engine="pandas", seconds=secs)
    emit("conf", non_default={})
    clock = CompileClock(jax)
    try:
        phase_queries(clock, data_dir, oracle, devs[0].platform)
        phase_report(devs, clock)
    finally:
        clock.close()


def _mesh_exchanges(df):
    from spark_rapids_tpu.parallel.mesh_exchange import MeshExchangeExec
    found = []

    def walk(node):
        if isinstance(node, MeshExchangeExec):
            found.append(node)
        for c in node.children:
            walk(c)

    walk(df._physical().root)
    return found


# The mesh phase's two sides: transport -> (conf, why cost placement says
# it stood down). Broadcast off on both: the joins then really shuffle,
# and the transport under test really carries them. The mesh side sets
# nothing else — cost placement stands down by itself on a
# non-inprocess transport. It is turned off by
# hand on the in-process side and only there: left on it (rightly) sends
# q5's nation/region subtree to the host engine, and the comparison is
# between two device exchanges, not between two placements.
_SHUFFLED = {"spark.rapids.sql.autoBroadcastJoinThreshold": -1}
MESH_SIDES = {
    "inprocess": ({**_SHUFFLED,
                   "spark.rapids.sql.shuffle.transport": "inprocess",
                   "spark.rapids.sql.cost.enabled": False},
                  "disabled"),
    "mesh": ({**_SHUFFLED, "spark.rapids.sql.shuffle.transport": "mesh"},
             "non-inprocess shuffle transport"),
}


def run_mesh(jax, devs, args, workdir: str) -> None:
    """repart and q5 with every join shuffled, over the mesh transport
    (one process, a 4-device mesh, all_to_all), against the same queries
    on the in-process transport and against the oracle."""
    from spark_rapids_tpu.benchmarks import suites, tpch
    tpch_dir = os.path.join(workdir, "tpch")
    suites_dir = os.path.join(workdir, "suites")
    t0 = time.perf_counter()
    tpch.generate(tpch_dir, scale=args.scale, seed=args.seed)
    suites.generate(suites_dir, scale=args.scale, seed=args.seed)
    emit("datagen", seconds=round(time.perf_counter() - t0, 2))
    cases = (("repart", suites, suites_dir), ("q5", tpch, tpch_dir))
    emit("conf", non_default={t: c for t, (c, _) in MESH_SIDES.items()})
    clock = CompileClock(jax)
    try:
        for qn, mod, ddir in cases:
            mesh_case(clock, qn, mod, ddir, len(devs))
        phase_report(devs, clock)
    finally:
        clock.close()


def mesh_case(clock, qn: str, mod, ddir: str, n_devices: int) -> None:
    from spark_rapids_tpu import faults
    from spark_rapids_tpu.benchmarks import tpch
    t0 = time.perf_counter()
    want = mod.pandas_query(qn, ddir)
    oracle_s = time.perf_counter() - t0
    results, timing = {}, {}
    for transport, (conf, stood_down) in MESH_SIDES.items():
        faults.reset_counters()
        df = mod.QUERIES[qn](session(**conf), ddir)
        phys = df._physical()
        fallen = phys.host_fallback_nodes()
        check(not fallen, f"{qn}/{transport}: host nodes {fallen}")
        skipped = getattr(phys.cost_report, "skipped", None)
        check(skipped == stood_down,
              f"{qn}/{transport}: cost placement should have stood down "
              f"({stood_down!r}), the planner says {skipped!r}")
        if transport == "mesh":
            check(len(_mesh_exchanges(df)) >= 1,
                  f"{qn}: the mesh plan holds no MeshExchangeExec")
        secs = []
        for _ in range(2):
            c0 = clock.mark()
            t0 = time.perf_counter()
            got = df.collect()
            secs.append({"seconds": round(time.perf_counter() - t0, 4),
                         **clock.since(c0)})
        check(mod.check_result(qn, got, want),
              f"{qn}/{transport} diverges from the pandas oracle: "
              f"got[:3]={got[:3]} want[:3]={want[:3]}")
        results[transport], timing[transport] = got, secs
        rec = faults.counters()
        for name in ("meshDegrades", "meshCollectiveSkipped",
                     "hostFallbacks", "spillEscalations",
                     "retriesAttempted"):
            check(rec.get(name, 0) == 0,
                  f"{qn}/{transport}: {name} = {rec.get(name)}")
        if transport == "mesh":
            ex = [v for v in df.metrics().values()
                  if v.get("meshExchanges")]
            check(ex, f"{qn}: no exchange ran the collective")
            for v in ex:
                check(v["meshShardDevices"] ==
                      n_devices * v["meshExchanges"],
                      f"{qn}: exchanged shards sat on "
                      f"{v['meshShardDevices'] / v['meshExchanges']} "
                      f"device(s), the mesh has {n_devices}")
            timing["mesh_exchanges"] = len(ex)
            timing["shard_devices_per_exchange"] = n_devices
    check(tpch.rows_close(sorted(results["mesh"]),
                          sorted(results["inprocess"])),
          f"{qn}: mesh transport diverges from the in-process one")
    emit("mesh_query", query=qn, rows=len(want), correct=True,
         oracle_s=round(oracle_s, 3), **timing)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mesh", action="store_true",
                    help=f"the {MESH_DEVICES}-chip mesh-transport phase, "
                         f"and only it")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run the control flow on the CPU backend; the "
                         "result line then names the CPU")
    ap.add_argument("--scale", type=float, default=1.0,
                    help="TPC-H scale factor (the chip run is SF1)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    need = MESH_DEVICES if args.mesh else 1
    if args.cpu_rehearsal:
        # Before jax starts: the CPU by name, with the devices the phase
        # needs as virtual ones.
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={need}").strip()
    import jax
    devs = jax.devices()
    want_platform = "cpu" if args.cpu_rehearsal else "tpu"
    if devs[0].platform != want_platform:
        print(f"chip_smoke: need a {want_platform} backend, JAX found "
              f"{devs[0].platform} ({devs[0].device_kind})",
              file=sys.stderr)
        return 2
    if len(devs) < need:
        print(f"chip_smoke: need {need} device(s), JAX found {len(devs)}",
              file=sys.stderr)
        return 2
    import spark_rapids_tpu  # noqa: F401  (x64, compile cache directory)
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        phase_environment(jax, devs, args)
        (run_mesh if args.mesh else run_single_chip)(jax, devs, args,
                                                     workdir)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
