"""Self-healing worker supervision (ISSUE 20 tentpole).

The cluster runtime could already *tolerate* a worker death (the
heartbeat sweep requeues its RUNNING stage, coordinator.py) — but
nothing ever brought the worker back, so a SIGKILL'd pool shrank
monotonically and a crash-looping worker flapped forever. The
:class:`Supervisor` owns the worker pool and closes that loop:

- **restart with exponential backoff**: a dead worker respawns after
  ``restartBackoffBaseMs * 2**(deaths-1)`` (capped), under the SAME
  worker id and environment, so locality/HRW placement re-converges;
- **crash-loop quarantine**: ``crashLoopThreshold`` deaths inside
  ``crashLoopWindowMs`` quarantine the worker — held out with a typed
  reason, surfaced as the ``srt_quarantined_workers`` gauge, a
  ``worker-quarantined`` event-log instant and a fleet record —
  instead of being respawned forever;
- **straggler demotion**: per-worker CBEAT heartbeat gaps and
  per-stage walls (coordinator CSTATS) feed a median-outlier detector;
  a worker whose medians exceed ``stragglerFactor`` × the fleet median
  is demoted below steal-delay placement preference (``CDEMO`` — the
  same tier pressure shedding uses) and promoted back on recovery;
- **clean drain on scale-down**: ``drain(wid)`` sends ``CDRAIN``; the
  coordinator stops dispatching to the worker, its in-flight stages
  commit their manifests, its next idle poll answers ``CRETIRE`` and
  the process exits 0 — scale-down never costs a stage recompute.

The policy arithmetic (backoff schedule, quarantine window, outlier
detection, drain ordering) is pure functions so tests/test_supervisor.py
pins it without processes. Everything here is inert unless a
supervisor is actually constructed (``scripts/cluster.py --supervise``
or the autoscaler): the default pool behaviour is byte-identical.
"""

from __future__ import annotations

import base64
import json
import logging
import os
import subprocess
import sys
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Set

from spark_rapids_tpu import config as C

_LOG = logging.getLogger("spark_rapids_tpu.cluster.supervisor")

# Managed-worker states.
RUNNING = "running"          # process alive (or believed alive)
BACKOFF = "backoff"          # died; restart scheduled
QUARANTINED = "quarantined"  # crash-looped; held out, never respawned
DRAINING = "draining"        # CDRAIN sent; waiting for clean exit
RETIRED = "retired"          # drained and exited 0 — a non-death


# -- policy units (pure; pinned by tests/test_supervisor.py) -----------------

def restart_backoff_ms(deaths: int, base_ms: float,
                       cap_ms: float) -> float:
    """Delay before restart number ``deaths`` (1-based): deterministic
    exponential ``base * 2**(deaths-1)`` capped at ``cap_ms``. No
    jitter on purpose — one supervisor restarts its own pool, there is
    no thundering herd to spread, and determinism keeps the schedule
    assertable."""
    if deaths <= 0:
        return 0.0
    return min(float(base_ms) * (2.0 ** (min(deaths, 63) - 1)),
               float(cap_ms))


def is_crash_looping(death_ts: Sequence[float], now: float,
                     window_ms: float, threshold: int) -> bool:
    """Quarantine arithmetic: ``threshold`` deaths whose timestamps
    fall inside the trailing ``window_ms`` window ending at ``now``."""
    if threshold <= 0:
        return True
    cutoff = now - window_ms / 1000.0
    return sum(1 for t in death_ts if t >= cutoff) >= int(threshold)


def _median(xs: Sequence[float]) -> float:
    s = sorted(xs)
    n = len(s)
    if n == 0:
        return 0.0
    mid = n // 2
    return s[mid] if n % 2 else (s[mid - 1] + s[mid]) / 2.0


def straggler_verdicts(samples: Dict[str, Sequence[float]],
                       factor: float, min_samples: int,
                       demoted: Optional[Set[str]] = None
                       ) -> Dict[str, bool]:
    """Median-outlier straggler detection over per-worker latency
    samples (CBEAT gaps or stage walls, ms). A worker is judged only
    once it has ``min_samples`` observations and at least one OTHER
    worker is judgeable (an outlier needs a fleet to be an outlier
    of). Returns wid -> should-be-demoted; hysteresis: an
    already-demoted worker (``demoted``) is only promoted back once
    its median drops under ``factor/2`` × the fleet median, so a
    worker hovering at the threshold doesn't flap."""
    demoted = demoted or set()
    meds = {w: _median(v) for w, v in samples.items()
            if len(v) >= max(int(min_samples), 1)}
    if len(meds) < 2:
        return {w: (w in demoted) for w in samples}
    fleet = _median([m for w, m in sorted(meds.items())])
    out: Dict[str, bool] = {}
    for w in samples:
        m = meds.get(w)
        if m is None or fleet <= 0:
            out[w] = w in demoted
        elif w in demoted:
            out[w] = m > (factor / 2.0) * fleet
        else:
            out[w] = m > factor * fleet
    return out


def drain_order(stats_workers: Dict[str, dict]) -> List[str]:
    """Which worker to drain first on scale-down: demoted stragglers,
    then the least useful (fewest completed stages), idlest last-seen
    breaking ties — deterministic by wid at the end."""
    def key(item):
        wid, w = item
        return (0 if w.get("demoted") else 1,
                int(w.get("completed", 0)),
                -int(w.get("idle_ms", 0)),
                wid)
    return [wid for wid, _ in sorted(stats_workers.items(), key=key)]


# -- the supervisor proper ---------------------------------------------------

class ManagedWorker:
    """One supervised worker: the live process handle plus the policy
    state the restart/quarantine machinery folds over."""

    __slots__ = ("wid", "proc", "state", "extra_env", "deaths",
                 "death_ts", "restarts", "next_restart_at",
                 "drain_deadline", "reason")

    def __init__(self, wid: str, proc=None, extra_env=None):
        self.wid = wid
        self.proc = proc
        self.state = RUNNING if proc is not None else BACKOFF
        self.extra_env = dict(extra_env or {})
        self.deaths = 0
        self.death_ts: List[float] = []
        self.restarts = 0
        self.next_restart_at = 0.0
        self.drain_deadline = 0.0
        self.reason = ""


class Supervisor:
    """Owns a pool of worker processes against one coordinator address
    and drives the observe→decide→act loop: reap deaths, restart with
    backoff, quarantine crash-loopers, demote stragglers, drain on
    scale-down. Usable in-process (tests, the autoscaler, bench) or
    standalone via ``scripts/cluster.py --supervise``."""

    def __init__(self, addr: str, conf=None, prefix: str = "sw",
                 heartbeat_ms: Optional[int] = None,
                 spawn_fn: Optional[Callable] = None,
                 stats_fn: Optional[Callable[[], dict]] = None,
                 verb_fn: Optional[Callable[[str], str]] = None):
        conf = conf if conf is not None else C.TpuConf({})
        self.addr = addr
        self.prefix = prefix
        self.heartbeat_ms = heartbeat_ms
        self.poll_ms = max(int(conf.get(C.CLUSTER_SUPERVISOR_POLL_MS)),
                           10)
        self.backoff_base_ms = float(
            conf.get(C.CLUSTER_SUPERVISOR_BACKOFF_BASE_MS))
        self.backoff_cap_ms = float(
            conf.get(C.CLUSTER_SUPERVISOR_BACKOFF_CAP_MS))
        self.crash_window_ms = float(
            conf.get(C.CLUSTER_SUPERVISOR_CRASH_LOOP_WINDOW_MS))
        self.crash_threshold = int(
            conf.get(C.CLUSTER_SUPERVISOR_CRASH_LOOP_THRESHOLD))
        self.straggler_factor = float(
            conf.get(C.CLUSTER_SUPERVISOR_STRAGGLER_FACTOR))
        self.straggler_min_samples = int(
            conf.get(C.CLUSTER_SUPERVISOR_STRAGGLER_MIN_SAMPLES))
        self.drain_timeout_ms = float(
            conf.get(C.CLUSTER_SUPERVISOR_DRAIN_TIMEOUT_MS))
        self._spawn_fn = spawn_fn or self._spawn_proc
        self._stats_fn = stats_fn
        self._verb_fn = verb_fn
        self._lock = threading.RLock()
        self.workers: Dict[str, ManagedWorker] = {}
        self._demoted: Set[str] = set()
        self._next_idx = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # Decision/action counters.
        self.counters = {"restarts": 0, "quarantines": 0, "drains": 0,
                         "retirements": 0, "demotions": 0,
                         "promotions": 0}

    # -- plumbing ------------------------------------------------------------
    def _spawn_proc(self, wid: str, extra_env: Dict[str, str]):
        import spark_rapids_tpu
        root = os.path.dirname(os.path.dirname(
            os.path.abspath(spark_rapids_tpu.__file__)))
        cmd = [sys.executable, "-m",
               "spark_rapids_tpu.parallel.cluster.worker",
               "--coordinator", self.addr, "--worker-id", wid]
        if self.heartbeat_ms:
            cmd += ["--heartbeat-ms", str(self.heartbeat_ms)]
        # Each worker is told its device (the CPU unless extra_env
        # carries a worker_env("tpu:<i>")), and never inherits a fault
        # schedule: a seeded crash-looper gets its schedule EXPLICITLY
        # via extra_env (and keeps it across restarts, which is what
        # makes it loop).
        from spark_rapids_tpu.parallel.cluster.worker import worker_env
        env = worker_env("cpu")
        env.update(extra_env)
        return subprocess.Popen(cmd, env=env, cwd=root)

    def _verb(self, line: str) -> Optional[str]:
        """One control-plane verb to the coordinator (CDRAIN/CDEMO/
        CSTATS); best-effort — a coordinator outage degrades a tick,
        never kills the supervisor."""
        try:
            if self._verb_fn is not None:
                return self._verb_fn(line)
            from spark_rapids_tpu.parallel.transport import \
                rendezvous as RV
            host, _, port = self.addr.rpartition(":")
            return RV._roundtrip((host or "127.0.0.1", int(port)),
                                 line + "\n", timeout_s=5.0, retries=1,
                                 backoff_ms=50)
        except Exception:
            _LOG.warning("supervisor: verb %r failed",
                         line.split()[0], exc_info=True)
            return None

    def _coordinator_stats(self) -> Optional[dict]:
        if self._stats_fn is not None:
            try:
                return self._stats_fn()
            except Exception:
                return None
        resp = self._verb("CSTATS")
        if not resp or not resp.startswith("OK "):
            return None
        try:
            return json.loads(base64.b64decode(resp[3:]).decode())
        except Exception:
            return None

    # -- pool management ------------------------------------------------------
    def add_worker(self, wid: Optional[str] = None,
                   extra_env: Optional[Dict[str, str]] = None) -> str:
        with self._lock:
            if wid is None:
                wid = f"{self.prefix}{self._next_idx}"
                self._next_idx += 1
            mw = ManagedWorker(wid, extra_env=extra_env)
            mw.proc = self._spawn_fn(wid, mw.extra_env)
            mw.state = RUNNING
            self.workers[wid] = mw
        from spark_rapids_tpu import monitoring
        monitoring.instant("worker-spawn", "cluster",
                           args={"worker": wid})
        self._log_fleet("worker-spawn", worker=wid)
        return wid

    def active_count(self) -> int:
        """Workers the pool can count on: running or pending restart.
        Draining/retired are on their way out, quarantined are out."""
        with self._lock:
            return sum(1 for w in self.workers.values()
                       if w.state in (RUNNING, BACKOFF))

    def scale_to(self, target: int) -> int:
        """Spawn or drain towards ``target`` active workers; returns
        the delta actually requested (positive = spawned)."""
        target = max(int(target), 0)
        with self._lock:
            active = [w for w in self.workers.values()
                      if w.state in (RUNNING, BACKOFF)]
            delta = target - len(active)
        if delta > 0:
            for _ in range(delta):
                self.add_worker()
        elif delta < 0:
            stats = self._coordinator_stats() or {}
            order = drain_order(stats.get("workers", {}))
            now = time.monotonic()
            with self._lock:
                # Capacity scale-down only picks STABLE workers: one
                # with a death inside the crash-loop window belongs to
                # the supervision plane (restart-or-quarantine), and
                # draining it would launder a crash-looper into a
                # clean-looking retirement before it burns its budget.
                drainable = [w.wid for w in self.workers.values()
                             if w.state == RUNNING
                             and not any(now - t <
                                         self.crash_window_ms / 1000.0
                                         for t in w.death_ts)]
            ranked = [w for w in order if w in drainable] + \
                [w for w in sorted(drainable) if w not in order]
            for wid in ranked[:-delta]:
                self.drain(wid)
        return delta

    def drain(self, wid: str) -> bool:
        """Clean scale-down of one worker: CDRAIN at the coordinator,
        then wait (in tick) for the process to exit 0."""
        with self._lock:
            mw = self.workers.get(wid)
            if mw is None or mw.state not in (RUNNING,):
                return False
            mw.state = DRAINING
            mw.drain_deadline = time.monotonic() + \
                self.drain_timeout_ms / 1000.0
            self.counters["drains"] += 1
        self._verb(f"CDRAIN {wid}")
        from spark_rapids_tpu import monitoring
        from spark_rapids_tpu.monitoring import telemetry
        monitoring.instant("worker-drain-request", "cluster",
                           args={"worker": wid})
        if telemetry.enabled():
            telemetry.inc("srt_worker_drains")
        self._log_fleet("worker-drain", worker=wid)
        return True

    def quarantined(self) -> Dict[str, str]:
        with self._lock:
            return {w.wid: w.reason for w in self.workers.values()
                    if w.state == QUARANTINED}

    # -- the control loop -----------------------------------------------------
    def tick(self, now: Optional[float] = None) -> None:
        """One observe→decide→act pass. Deterministic given process
        exits and coordinator stats; the run() loop just repeats it."""
        now = time.monotonic() if now is None else now
        self._reap_and_restart(now)
        self._scan_stragglers()
        self._publish_gauges()

    def _reap_and_restart(self, now: float) -> None:
        from spark_rapids_tpu import monitoring
        from spark_rapids_tpu.monitoring import telemetry
        with self._lock:
            items = list(self.workers.values())
        for mw in items:
            rc = mw.proc.poll() if mw.proc is not None else None
            if mw.state == RUNNING and rc is not None:
                if rc == 0:
                    # Self-retirement (--max-idle-s drain handshake):
                    # a clean exit is not a death.
                    with self._lock:
                        mw.state = RETIRED
                        self.counters["retirements"] += 1
                    self._log_fleet("worker-retired", worker=mw.wid,
                                    clean=True)
                    continue
                with self._lock:
                    mw.deaths += 1
                    mw.death_ts.append(now)
                    del mw.death_ts[:-16]
                    looping = is_crash_looping(
                        mw.death_ts, now, self.crash_window_ms,
                        self.crash_threshold)
                    if looping:
                        mw.state = QUARANTINED
                        mw.reason = (
                            f"crash-loop: {len(mw.death_ts)} deaths, "
                            f"last {self.crash_threshold} within "
                            f"{int(self.crash_window_ms)}ms "
                            f"(rc={rc})")
                        self.counters["quarantines"] += 1
                    else:
                        mw.state = BACKOFF
                        backoff = restart_backoff_ms(
                            mw.deaths, self.backoff_base_ms,
                            self.backoff_cap_ms)
                        mw.next_restart_at = now + backoff / 1000.0
                if looping:
                    _LOG.warning("supervisor: worker %s QUARANTINED "
                                 "(%s)", mw.wid, mw.reason)
                    monitoring.instant(
                        "worker-quarantined", "recovery",
                        args={"worker": mw.wid, "reason": mw.reason})
                    if telemetry.enabled():
                        telemetry.inc("srt_worker_quarantines")
                    self._log_fleet("worker-quarantined",
                                    worker=mw.wid, reason=mw.reason)
                else:
                    _LOG.warning(
                        "supervisor: worker %s died (rc=%s, death "
                        "%d) — restart in %.0fms", mw.wid, rc,
                        mw.deaths,
                        (mw.next_restart_at - now) * 1000.0)
                    self._log_fleet("worker-death", worker=mw.wid,
                                    rc=rc, deaths=mw.deaths)
            elif mw.state == BACKOFF and now >= mw.next_restart_at:
                with self._lock:
                    mw.proc = self._spawn_fn(mw.wid, mw.extra_env)
                    mw.state = RUNNING
                    mw.restarts += 1
                    self.counters["restarts"] += 1
                monitoring.instant("worker-restart", "recovery",
                                   args={"worker": mw.wid,
                                         "restarts": mw.restarts})
                if telemetry.enabled():
                    telemetry.inc("srt_worker_restarts")
                self._log_fleet("worker-restart", worker=mw.wid,
                                restarts=mw.restarts)
            elif mw.state == DRAINING:
                if rc is not None:
                    with self._lock:
                        mw.state = RETIRED
                        self.counters["retirements"] += 1
                    self._log_fleet("worker-retired", worker=mw.wid,
                                    clean=rc == 0)
                elif now > mw.drain_deadline:
                    # The drain never completed (stuck stage?): the
                    # heartbeat sweep will requeue whatever it held.
                    _LOG.warning("supervisor: drain of %s timed out "
                                 "— terminating", mw.wid)
                    try:
                        mw.proc.terminate()
                    except Exception:
                        pass
                    with self._lock:
                        mw.drain_deadline = now + 5.0

    def _scan_stragglers(self) -> None:
        stats = self._coordinator_stats()
        if not stats:
            return
        from spark_rapids_tpu import monitoring
        from spark_rapids_tpu.monitoring import telemetry
        workers = stats.get("workers", {})
        with self._lock:
            managed = {wid for wid, w in self.workers.items()
                       if w.state == RUNNING}
        eligible = {wid: w for wid, w in workers.items()
                    if wid in managed and w.get("alive")}
        for kind in ("beat_ms", "stage_wall_ms"):
            samples = {wid: w.get(kind) or []
                       for wid, w in eligible.items()}
            verdicts = straggler_verdicts(
                samples, self.straggler_factor,
                self.straggler_min_samples, demoted=self._demoted)
            for wid, slow in sorted(verdicts.items()):
                if slow and wid not in self._demoted:
                    self._demoted.add(wid)
                    self.counters["demotions"] += 1
                    self._verb(f"CDEMO {wid} 1")
                    monitoring.instant(
                        "worker-straggler-demoted", "recovery",
                        args={"worker": wid, "signal": kind})
                    if telemetry.enabled():
                        telemetry.inc("srt_stragglers_demoted")
                    self._log_fleet("worker-straggler", worker=wid,
                                    signal=kind)
                elif not slow and wid in self._demoted and \
                        kind == "stage_wall_ms":
                    # Promotion needs BOTH signals healthy; checking on
                    # the second kind keeps one pass per tick simple.
                    beats = straggler_verdicts(
                        {w: eligible[w].get("beat_ms") or []
                         for w in eligible},
                        self.straggler_factor,
                        self.straggler_min_samples,
                        demoted=self._demoted)
                    if not beats.get(wid, False):
                        self._demoted.discard(wid)
                        self.counters["promotions"] += 1
                        self._verb(f"CDEMO {wid} 0")
                        self._log_fleet("worker-promoted", worker=wid)

    def _publish_gauges(self) -> None:
        from spark_rapids_tpu.monitoring import telemetry
        if not telemetry.enabled():
            return
        with self._lock:
            states: Dict[str, int] = {}
            for w in self.workers.values():
                states[w.state] = states.get(w.state, 0) + 1
        telemetry.set_gauge("srt_fleet_workers",
                            states.get(RUNNING, 0) +
                            states.get(BACKOFF, 0))
        telemetry.set_gauge("srt_quarantined_workers",
                            states.get(QUARANTINED, 0))

    def _log_fleet(self, event: str, **fields) -> None:
        from spark_rapids_tpu.monitoring import history
        with self._lock:
            workers = sum(1 for w in self.workers.values()
                          if w.state in (RUNNING, BACKOFF))
            quarantined = sum(1 for w in self.workers.values()
                              if w.state == QUARANTINED)
        history.log_fleet(event, workers=workers,
                          quarantined=quarantined, **fields)

    # -- lifecycle ------------------------------------------------------------
    def start(self) -> None:
        if self._thread is not None:
            return
        self._thread = threading.Thread(
            target=self.run, name="srt-supervisor", daemon=True)
        self._thread.start()

    def run(self) -> None:
        while not self._stop.wait(self.poll_ms / 1000.0):
            try:
                self.tick()
            except Exception:      # the loop must survive any tick
                _LOG.exception("supervisor tick failed")

    def stop(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=5.0)
            self._thread = None

    def close(self, timeout_s: float = 15.0) -> None:
        """Stop the loop and reap every managed process."""
        self.stop()
        with self._lock:
            procs = [w.proc for w in self.workers.values()
                     if w.proc is not None]
        for p in procs:
            if p.poll() is None:
                try:
                    p.terminate()
                except Exception:
                    pass
        for p in procs:
            try:
                p.wait(timeout=timeout_s)
            except Exception:
                try:
                    p.kill()
                except Exception:
                    pass
