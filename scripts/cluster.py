"""Launch a pool of cluster worker processes (ISSUE 13 tentpole).

The distributed runtime has two halves: the driver-side coordinator
(started implicitly by any session with
``spark.rapids.sql.cluster.enabled=true``) and N worker processes that
register with it, poll for stage tasks and publish their outputs
through the shuffle transport. This script is the worker half's
launcher:

  * ``--coordinator HOST:PORT`` joins workers to a driver that is
    already running (the driver prints its address, or read it from
    ``get_coordinator(conf).addr``). The script forwards SIGINT/SIGTERM
    to the pool and exits with the first non-zero worker status.
  * ``--demo`` is the self-contained smoke path: generate a small TPC-H
    dataset, start a coordinator in-process, spawn the pool, run one
    query distributed and check it bit-identical against the local run.
  * ``--supervise`` runs the pool under the self-healing supervisor
    (ISSUE 20): dead workers restart with exponential backoff,
    crash-loopers are quarantined, stragglers demoted and scale-down
    drains cleanly. Add ``--autoscale`` to let the SLO loop size the
    pool between ``cluster.autoscale.minWorkers``/``maxWorkers``
    instead of holding ``--workers`` fixed.

Run: python scripts/cluster.py --workers 3 --coordinator 127.0.0.1:41234
     python scripts/cluster.py --supervise --workers 3 \
         --coordinator 127.0.0.1:41234
     python scripts/cluster.py --demo --workers 3 --query q3
"""

import argparse
import os
import signal
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def spawn_workers(addr, n, heartbeat_ms=None, prefix="w", devices="cpu"):
    """Spawn n worker subprocesses against coordinator ``addr``, each
    told its device: ``devices`` is ``"cpu"`` (all on the CPU) or
    ``"tpu"`` (worker i holds chip i of this host). This launcher never
    initializes a JAX backend itself: a chip belongs to one process."""
    from spark_rapids_tpu.parallel.cluster.worker import worker_env
    procs = []
    for i in range(n):
        cmd = [sys.executable, "-m",
               "spark_rapids_tpu.parallel.cluster.worker",
               "--coordinator", addr, "--worker-id", f"{prefix}{i}"]
        if heartbeat_ms:
            cmd += ["--heartbeat-ms", str(heartbeat_ms)]
        env = worker_env("cpu" if devices == "cpu" else f"tpu:{i}")
        procs.append(subprocess.Popen(cmd, env=env, cwd=ROOT))
    return procs


def reap(procs, timeout_s=15):
    for p in procs:
        if p.poll() is None:
            p.terminate()
    for p in procs:
        try:
            p.wait(timeout=timeout_s)
        except Exception:
            p.kill()


def run_pool(args):
    procs = spawn_workers(args.coordinator, args.workers,
                          args.heartbeat_ms, args.prefix,
                          args.worker_devices)
    stop = []

    def on_signal(signum, frame):
        stop.append(signum)

    signal.signal(signal.SIGINT, on_signal)
    signal.signal(signal.SIGTERM, on_signal)
    print(f"cluster.py: {args.workers} worker(s) -> {args.coordinator} "
          f"(pids {[p.pid for p in procs]})")
    rc = 0
    while not stop:
        done = [p for p in procs if p.poll() is not None]
        if done:
            rc = max(abs(p.returncode) for p in done)
            break
        time.sleep(0.25)
    reap(procs)
    return rc


def run_supervised(args):
    """Run the pool under the self-healing supervisor (and optionally
    the SLO autoscaler) instead of bare subprocesses."""
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.parallel.cluster.autoscaler import Autoscaler
    from spark_rapids_tpu.parallel.cluster.supervisor import Supervisor

    conf = C.TpuConf({})
    sup = Supervisor(args.coordinator, conf=conf, prefix=args.prefix,
                     heartbeat_ms=args.heartbeat_ms)
    scaler = None
    if args.autoscale or conf.get(C.CLUSTER_AUTOSCALE_ENABLED):
        scaler = Autoscaler(sup, conf=conf)
        start_n = scaler.min_workers
    else:
        start_n = args.workers
    for _ in range(start_n):
        sup.add_worker()

    stop = []

    def on_signal(signum, frame):
        stop.append(signum)

    signal.signal(signal.SIGINT, on_signal)
    signal.signal(signal.SIGTERM, on_signal)
    mode = "autoscaled" if scaler else "supervised"
    print(f"cluster.py: {start_n} {mode} worker(s) -> "
          f"{args.coordinator}")
    sup.start()
    if scaler:
        scaler.start()
    try:
        while not stop:
            time.sleep(0.25)
    finally:
        if scaler:
            scaler.stop()
        sup.close()
    return 0


def run_demo(args):
    from spark_rapids_tpu.api.dataframe import TpuSession
    from spark_rapids_tpu.benchmarks import tpch
    from spark_rapids_tpu.parallel import cluster as CL

    d = args.data_dir or tempfile.mkdtemp(prefix="tpch_cluster_demo_")
    if not os.path.exists(os.path.join(d, "lineitem")):
        print(f"cluster.py: generating TPC-H scale={args.scale} in {d}")
        tpch.generate(d, scale=args.scale, files_per_table=3, seed=7)

    def session(cluster=False):
        s = TpuSession()
        s.set("spark.rapids.sql.variableFloatAgg.enabled", True)
        # Shuffle-forced plans have independent leaf stages — the demo
        # should show work actually spreading across the pool.
        s.set("spark.rapids.sql.autoBroadcastJoinThreshold", -1)
        if cluster:
            s.set("spark.rapids.sql.cluster.enabled", True)
            s.set("spark.rapids.sql.cluster.minWorkers", args.workers)
        return s

    t0 = time.perf_counter()
    want = tpch.QUERIES[args.query](session(), d).collect()
    local_s = time.perf_counter() - t0

    s = session(cluster=True)
    co = CL.get_coordinator(s.conf)
    addr = f"{co.addr[0]}:{co.addr[1]}"
    # The demo's driver runs queries itself, so it holds whatever
    # accelerator JAX found: its workers go to the CPU whatever
    # --worker-devices says.
    procs = spawn_workers(addr, args.workers, args.heartbeat_ms,
                          args.prefix, "cpu")
    try:
        df = tpch.QUERIES[args.query](s, d)
        t0 = time.perf_counter()
        got = df.collect()
        cold_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        got = df.collect()
        warm_s = time.perf_counter() - t0
        st = co.stats()
        print(f"cluster.py demo: {args.query} x{args.workers} workers")
        print(f"  local       {local_s:8.3f}s")
        print(f"  distributed {cold_s:8.3f}s cold (worker JIT), "
              f"{warm_s:.3f}s warm")
        print(f"  bit-identical: {got == want}")
        for wid, w in sorted(st["workers"].items()):
            print(f"  {wid}: alive={w['alive']} "
                  f"completed={w['completed']}")
        return 0 if got == want else 1
    finally:
        reap(procs)
        CL.shutdown_coordinator()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--coordinator",
                    help="host:port of a running driver coordinator")
    ap.add_argument("--heartbeat-ms", type=int, default=None)
    ap.add_argument("--prefix", default="w",
                    help="worker-id prefix (ids are <prefix>0..N-1)")
    ap.add_argument("--worker-devices", choices=("cpu", "tpu"),
                    default="cpu",
                    help="cpu: every worker on the CPU; tpu: worker i "
                         "holds chip i of this host (the driver must "
                         "then run elsewhere or on the CPU)")
    ap.add_argument("--demo", action="store_true",
                    help="self-contained: coordinator + pool + one query")
    ap.add_argument("--supervise", action="store_true",
                    help="run the pool under the self-healing "
                         "supervisor (restart/quarantine/drain)")
    ap.add_argument("--autoscale", action="store_true",
                    help="with --supervise: SLO autoscaler sizes the "
                         "pool (cluster.autoscale.* knobs)")
    ap.add_argument("--query", default="q3",
                    help="TPC-H query for --demo")
    ap.add_argument("--scale", type=float, default=0.01,
                    help="TPC-H scale factor for --demo datagen")
    ap.add_argument("--data-dir", default=None,
                    help="reuse an existing TPC-H dataset for --demo")
    args = ap.parse_args(argv)
    if not args.demo and not args.coordinator:
        ap.error("--coordinator is required unless --demo")
    if args.demo:
        return run_demo(args)
    if args.supervise or args.autoscale:
        return run_supervised(args)
    return run_pool(args)


if __name__ == "__main__":
    sys.exit(main())
