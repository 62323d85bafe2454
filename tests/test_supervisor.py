"""Self-healing supervision tests (ISSUE 20).

Three layers, cheapest first:

- pure policy arithmetic (backoff schedule, crash-loop window,
  straggler outlier detection, drain ordering) with no processes;
- the :class:`Supervisor` state machine against FAKE worker processes
  (an injected spawn_fn returning scriptable handles), so restart /
  quarantine / drain transitions are deterministic and instant;
- coordinator verb-level drain semantics (CDRAIN vs in-flight stages,
  CDEMO placement demotion) via ``co.dispatch`` — no sockets;
- one real-process regression: ``--max-idle-s`` self-retirement now
  deregisters through the CDRAIN→CRETIRE handshake instead of
  silently exiting and waiting out the heartbeat sweep.
"""

import base64
import os
import subprocess
import sys
import time

import pytest

import spark_rapids_tpu
from spark_rapids_tpu import config as C
from spark_rapids_tpu import faults
from spark_rapids_tpu.api.dataframe import TpuSession
from spark_rapids_tpu.benchmarks import tpch
from spark_rapids_tpu.memory.oom import is_transient_error
from spark_rapids_tpu.parallel import cluster as CL
from spark_rapids_tpu.parallel.cluster import coordinator as CO
from spark_rapids_tpu.parallel.cluster.supervisor import (
    BACKOFF, DRAINING, QUARANTINED, RETIRED, RUNNING, Supervisor,
    drain_order, is_crash_looping, restart_backoff_ms,
    straggler_verdicts)

REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.abspath(spark_rapids_tpu.__file__)))


@pytest.fixture(autouse=True)
def clean_cluster_state():
    faults.configure("")
    faults.reset_counters()
    yield
    CL.shutdown_coordinator()
    faults.configure("")
    faults.reset_counters()


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("tpch_supervisor"))
    tpch.generate(d, scale=0.003, files_per_table=3, seed=7)
    return d


def _cluster_session(**over) -> TpuSession:
    s = TpuSession()
    s.set("spark.rapids.sql.variableFloatAgg.enabled", True)
    s.set("spark.rapids.sql.cluster.enabled", True)
    for k, v in over.items():
        s.set(k, v)
    return s


def _submit_q3(data_dir, **over):
    s = _cluster_session(**over)
    s.set("spark.rapids.sql.autoBroadcastJoinThreshold", -1)
    phys = tpch.QUERIES["q3"](s, data_dir)._physical()
    co = CL.get_coordinator(s.conf)
    q = co.submit(phys, s.conf)
    assert q is not None
    return co, q


# ---------------------------------------------------------------------------
# Policy units (pure, no processes)
# ---------------------------------------------------------------------------

class TestWorkerEnv:
    """A chip belongs to one process: launchers tell every worker its
    device instead of letting it inherit whatever JAX would find."""

    def test_cpu_worker_is_pinned_to_the_cpu(self):
        from spark_rapids_tpu.parallel.cluster.worker import worker_env
        env = worker_env("cpu", base={"JAX_PLATFORMS": "tpu,cpu",
                                      "SRT_FAULTS": "oom@kernel:1"})
        assert env["JAX_PLATFORMS"] == "cpu"
        assert "SRT_FAULTS" not in env      # never inherited into a pool
        assert "TPU_VISIBLE_CHIPS" not in env

    def test_tpu_worker_gets_one_chip(self):
        from spark_rapids_tpu.parallel.cluster.worker import worker_env
        env = worker_env("tpu:2", base={})
        assert env["JAX_PLATFORMS"] == "tpu"
        assert env["TPU_VISIBLE_CHIPS"] == "2"
        assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"

    @pytest.mark.parametrize("bad", ["gpu", "tpu", "tpu:x", ""])
    def test_unknown_device_is_an_error(self, bad):
        from spark_rapids_tpu.parallel.cluster.worker import worker_env
        with pytest.raises(ValueError):
            worker_env(bad, base={})

    def test_supervisor_spawns_cpu_workers_by_default(self, monkeypatch):
        seen = {}

        def fake_popen(cmd, env=None, cwd=None):
            seen["env"] = env
            return FakeProc()

        monkeypatch.setattr(subprocess, "Popen", fake_popen)
        monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
        sup = Supervisor("127.0.0.1:1", conf=C.TpuConf({}))
        sup._spawn_proc("w0", {})
        assert seen["env"]["JAX_PLATFORMS"] == "cpu"


class TestBackoffSchedule:
    def test_deterministic_exponential_with_cap(self):
        sched = [restart_backoff_ms(n, 250, 10000) for n in range(1, 9)]
        assert sched == [250.0, 500.0, 1000.0, 2000.0, 4000.0,
                         8000.0, 10000.0, 10000.0]
        # Determinism: same inputs, same schedule, no jitter.
        assert sched == [restart_backoff_ms(n, 250, 10000)
                         for n in range(1, 9)]

    def test_zero_deaths_no_wait_and_huge_counts_stay_capped(self):
        assert restart_backoff_ms(0, 250, 10000) == 0.0
        # 2**(n-1) overflow guard: the cap holds at absurd counts.
        assert restart_backoff_ms(10_000, 250, 10000) == 10000.0


class TestCrashLoopWindow:
    def test_threshold_inside_window_quarantines(self):
        # 3 deaths within 30s of "now" -> looping.
        assert is_crash_looping([70.0, 80.0, 90.0], 100.0, 30000, 3)

    def test_old_deaths_age_out(self):
        # Only 2 of 3 deaths inside the trailing window: not looping.
        assert not is_crash_looping([60.0, 80.0, 90.0], 100.0,
                                    30000, 3)
        # The SAME history judged earlier (window ends sooner) loops:
        # the window is trailing from ``now``, not absolute.
        assert is_crash_looping([60.0, 80.0, 90.0], 90.0, 30000, 3)

    def test_exact_boundary_counts(self):
        # A death exactly window_ms ago is still inside (>= cutoff).
        assert is_crash_looping([70.0, 85.0, 100.0], 100.0, 30000, 3)


class TestStragglerDetection:
    def test_outlier_demoted_healthy_not(self):
        v = straggler_verdicts(
            {"a": [10.0] * 6, "b": [12.0] * 6, "c": [95.0] * 6},
            factor=3.0, min_samples=5)
        assert v == {"a": False, "b": False, "c": True}

    def test_min_samples_gate(self):
        # c is 10x slower but has too few samples to judge; a fleet of
        # one judgeable worker can't have outliers either.
        v = straggler_verdicts(
            {"a": [10.0] * 6, "c": [100.0] * 2},
            factor=3.0, min_samples=5)
        assert v == {"a": False, "c": False}

    def test_promote_back_hysteresis(self):
        # A demoted worker at 2.5x fleet median stays demoted (above
        # factor/2 = 1.5x) — no flapping at the threshold...
        v = straggler_verdicts(
            {"a": [10.0] * 6, "b": [10.0] * 6, "c": [25.0] * 6},
            factor=3.0, min_samples=5, demoted={"c"})
        assert v["c"] is True
        # ...and only promotes once clearly recovered (under 1.5x).
        v = straggler_verdicts(
            {"a": [10.0] * 6, "b": [10.0] * 6, "c": [12.0] * 6},
            factor=3.0, min_samples=5, demoted={"c"})
        assert v["c"] is False

    def test_synthetic_trace_with_noise(self):
        # Realistic shape: jittery healthy workers, one 5x straggler.
        healthy = [48.0, 52.0, 50.0, 47.0, 55.0, 51.0, 49.0]
        slow = [x * 5 for x in healthy]
        v = straggler_verdicts(
            {"w0": healthy, "w1": list(reversed(healthy)),
             "w2": healthy[1:] + healthy[:1], "w3": slow},
            factor=3.0, min_samples=5)
        assert v == {"w0": False, "w1": False, "w2": False,
                     "w3": True}


class TestDrainOrder:
    def test_demoted_then_least_useful(self):
        order = drain_order({
            "a": {"demoted": False, "completed": 9, "idle_ms": 0},
            "b": {"demoted": True, "completed": 50, "idle_ms": 0},
            "c": {"demoted": False, "completed": 2, "idle_ms": 500},
        })
        assert order == ["b", "c", "a"]

    def test_idle_breaks_ties(self):
        order = drain_order({
            "a": {"demoted": False, "completed": 5, "idle_ms": 10},
            "b": {"demoted": False, "completed": 5, "idle_ms": 900},
        })
        assert order == ["b", "a"]


# ---------------------------------------------------------------------------
# Supervisor state machine against fake processes
# ---------------------------------------------------------------------------

class FakeProc:
    """Scriptable stand-in for subprocess.Popen: tests flip ``rc``."""

    def __init__(self):
        self.rc = None
        self.terminated = False

    def poll(self):
        return self.rc

    def terminate(self):
        self.terminated = True
        if self.rc is None:
            self.rc = -15

    def wait(self, timeout=None):
        return self.rc

    def kill(self):
        self.rc = -9


def _conf(**over):
    s = TpuSession()
    for k, v in over.items():
        s.set(k, v)
    return s.conf


def _fake_supervisor(verbs=None, stats=None, **conf_over):
    spawned = []

    def spawn(wid, env):
        p = FakeProc()
        spawned.append((wid, dict(env)))
        return p

    if verbs is None:
        def verb_fn(line):
            return "OK"
    else:
        def verb_fn(line):
            verbs.append(line)
            return "OK"
    sup = Supervisor(
        "127.0.0.1:1", conf=_conf(**conf_over), prefix="t",
        spawn_fn=spawn,
        stats_fn=(lambda: stats) if stats is not None
        else (lambda: {"workers": {}}),
        verb_fn=verb_fn)
    return sup, spawned


class TestSupervisorRestarts:
    def test_death_restarts_after_backoff_same_wid_same_env(self):
        sup, spawned = _fake_supervisor(**{
            "spark.rapids.sql.cluster.supervisor.restartBackoffBaseMs": 200})
        wid = sup.add_worker(extra_env={"MARKER": "x"})
        now = 100.0
        sup.workers[wid].proc.rc = 1          # dies
        sup.tick(now)
        mw = sup.workers[wid]
        assert mw.state == BACKOFF and mw.deaths == 1
        assert mw.next_restart_at == pytest.approx(now + 0.2)
        sup.tick(now + 0.1)                   # still inside backoff
        assert mw.state == BACKOFF
        sup.tick(now + 0.25)                  # past it: respawned
        assert mw.state == RUNNING and mw.restarts == 1
        assert sup.counters["restarts"] == 1
        # restarted under the SAME wid, with the seeded env preserved
        assert [w for w, _ in spawned] == [wid, wid]
        assert spawned[1][1]["MARKER"] == "x"

    def test_second_death_doubles_backoff(self):
        sup, _ = _fake_supervisor(**{
            "spark.rapids.sql.cluster.supervisor.restartBackoffBaseMs": 200,
            "spark.rapids.sql.cluster.supervisor.crashLoopWindowMs":
                1000})
        wid = sup.add_worker()
        mw = sup.workers[wid]
        mw.proc.rc = 1
        sup.tick(10.0)
        sup.tick(10.3)
        mw.proc.rc = 1                        # dies again at 20s —
        sup.tick(20.0)                        # outside the loop window
        assert mw.state == BACKOFF
        assert mw.next_restart_at == pytest.approx(20.0 + 0.4)

    def test_clean_exit_is_retirement_not_death(self):
        sup, _ = _fake_supervisor()
        wid = sup.add_worker()
        sup.workers[wid].proc.rc = 0
        sup.tick(1.0)
        mw = sup.workers[wid]
        assert mw.state == RETIRED and mw.deaths == 0
        assert sup.counters["retirements"] == 1
        sup.tick(2.0)                         # and stays retired
        assert mw.state == RETIRED


class TestSupervisorQuarantine:
    def test_crash_loop_quarantines_and_never_respawns(self):
        sup, spawned = _fake_supervisor(**{
            "spark.rapids.sql.cluster.supervisor.restartBackoffBaseMs": 1,
            "spark.rapids.sql.cluster.supervisor.crashLoopWindowMs":
                30000,
            "spark.rapids.sql.cluster.supervisor.crashLoopThreshold":
                3})
        wid = sup.add_worker(extra_env={"SRT_FAULTS": "boom"})
        mw = sup.workers[wid]
        now = 50.0
        for _ in range(2):                    # deaths 1 and 2: backoff
            mw.proc.rc = 1
            sup.tick(now)
            assert mw.state == BACKOFF
            now += 1.0
            sup.tick(now)                     # respawn
            assert mw.state == RUNNING
            now += 1.0
        mw.proc.rc = 1                        # death 3 inside window
        sup.tick(now)
        assert mw.state == QUARANTINED
        assert "crash-loop" in mw.reason
        assert sup.counters["quarantines"] == 1
        assert wid in sup.quarantined()
        n_spawns = len(spawned)
        sup.tick(now + 100.0)                 # held out forever
        assert mw.state == QUARANTINED and len(spawned) == n_spawns
        assert sup.active_count() == 0

    def test_slow_deaths_outside_window_keep_restarting(self):
        sup, spawned = _fake_supervisor(**{
            "spark.rapids.sql.cluster.supervisor.restartBackoffBaseMs": 1,
            "spark.rapids.sql.cluster.supervisor.crashLoopWindowMs":
                10000,
            "spark.rapids.sql.cluster.supervisor.crashLoopThreshold":
                3})
        wid = sup.add_worker()
        mw = sup.workers[wid]
        now = 0.0
        for _ in range(5):                    # one death per minute
            mw.proc.rc = 1
            sup.tick(now)
            assert mw.state == BACKOFF
            sup.tick(now + 11.0)
            assert mw.state == RUNNING
            now += 60.0
        assert mw.deaths == 5 and mw.state == RUNNING
        assert len(spawned) == 6              # initial + 5 restarts


class TestSupervisorDrain:
    def test_drain_sends_cdrain_and_reaps_clean_exit(self):
        verbs = []
        sup, _ = _fake_supervisor(verbs=verbs)
        wid = sup.add_worker()
        assert sup.drain(wid)
        assert f"CDRAIN {wid}" in verbs
        mw = sup.workers[wid]
        assert mw.state == DRAINING
        assert sup.active_count() == 0        # leaving: not counted
        mw.proc.rc = 0                        # worker got CRETIRE
        sup.tick(1.0)
        assert mw.state == RETIRED
        assert sup.counters["drains"] == 1
        assert not sup.drain(wid)             # idempotent-ish: no-op

    def test_drain_timeout_escalates_to_terminate(self):
        sup, _ = _fake_supervisor(**{
            "spark.rapids.sql.cluster.supervisor.drainTimeoutMs": 100})
        wid = sup.add_worker()
        t0 = time.monotonic()
        sup.drain(wid)
        mw = sup.workers[wid]
        sup.tick(t0 + 0.05)                   # inside the window
        assert not mw.proc.terminated
        sup.tick(t0 + 0.5)                    # past it
        assert mw.proc.terminated
        sup.tick(t0 + 0.6)
        assert mw.state == RETIRED            # reaped after terminate

    def test_scale_to_prefers_draining_demoted(self):
        stats = {"workers": {
            "t0": {"alive": True, "demoted": False, "completed": 9,
                   "idle_ms": 0},
            "t1": {"alive": True, "demoted": True, "completed": 9,
                   "idle_ms": 0},
            "t2": {"alive": True, "demoted": False, "completed": 1,
                   "idle_ms": 0},
        }}
        sup, _ = _fake_supervisor(stats=stats)
        for _ in range(3):
            sup.add_worker()
        assert sup.scale_to(2) == -1
        assert sup.workers["t1"].state == DRAINING   # the straggler
        assert {w.wid for w in sup.workers.values()
                if w.state == RUNNING} == {"t0", "t2"}

    def test_scale_to_skips_recently_dead_workers(self):
        """Capacity scale-down never drains a worker with a death
        inside the crash-loop window — draining a flapper would
        launder a crash-looper into a clean retirement before it can
        burn its restart budget into quarantine."""
        stats = {"workers": {
            "t0": {"alive": True, "demoted": False, "completed": 9,
                   "idle_ms": 0},
            "t1": {"alive": True, "demoted": False, "completed": 0,
                   "idle_ms": 500},
        }}
        sup, _ = _fake_supervisor(stats=stats)
        for _ in range(2):
            sup.add_worker()
        # t1 ranks first in drain_order (fewest completed, most idle)
        # but just died once: scale-down must pick t0 instead.
        sup.workers["t1"].death_ts.append(time.monotonic())
        assert sup.scale_to(1) == -1
        assert sup.workers["t0"].state == DRAINING
        assert sup.workers["t1"].state == RUNNING

    def test_scale_to_spawns_up(self):
        sup, spawned = _fake_supervisor()
        sup.add_worker()
        assert sup.scale_to(3) == 2
        assert sup.active_count() == 3 and len(spawned) == 3


class TestSupervisorStragglerScan:
    def test_demotes_then_promotes_via_cdemo(self):
        stats = {"workers": {
            "t0": {"alive": True, "beat_ms": [10.0] * 6,
                   "stage_wall_ms": [100.0] * 6},
            "t1": {"alive": True, "beat_ms": [11.0] * 6,
                   "stage_wall_ms": [110.0] * 6},
            "t2": {"alive": True, "beat_ms": [12.0] * 6,
                   "stage_wall_ms": [900.0] * 6},
        }}
        verbs = []
        sup, _ = _fake_supervisor(verbs=verbs, stats=stats)
        for _ in range(3):
            sup.add_worker()
        sup.tick(1.0)
        assert "CDEMO t2 1" in verbs
        assert sup.counters["demotions"] == 1
        sup.tick(2.0)                         # still slow: no re-send
        assert verbs.count("CDEMO t2 1") == 1
        stats["workers"]["t2"]["stage_wall_ms"] = [115.0] * 6
        sup.tick(3.0)                         # recovered on BOTH axes
        assert "CDEMO t2 0" in verbs
        assert sup.counters["promotions"] == 1


# ---------------------------------------------------------------------------
# Coordinator verb-level drain semantics (no worker processes)
# ---------------------------------------------------------------------------

class TestDrainVerbOrdering:
    def test_drain_waits_for_inflight_stage_then_retires(self, data_dir):
        """CDRAIN ordering: stop dispatching immediately, let the
        in-flight stage COMMIT, only then answer CRETIRE — scale-down
        never costs a recompute."""
        co, q = _submit_q3(data_dir)
        co.dispatch(["CREG", "wA"])
        resp = co.dispatch(["CPOLL", "wA", "-"]).decode().split()
        assert resp[0] == "CTASK"
        qid, sid, gen = int(resp[1]), int(resp[2]), int(resp[3])
        assert co.dispatch(["CDRAIN", "wA"]) == b"OK\n"
        # In-flight stage not yet committed: poll must NOT retire the
        # worker (that would orphan the stage) and must NOT hand out
        # new work either.
        assert co.dispatch(["CPOLL", "wA", "-"]) == b"CIDLE -\n"
        assert q.tasks[sid].status == "running"
        assert co.dispatch(
            ["CDONE", "wA", str(qid), str(sid), str(gen),
             "50"]) == b"OK\n"
        # Committed: the next poll retires.
        assert co.dispatch(["CPOLL", "wA", "-"]) == b"CRETIRE\n"
        assert "wA" not in co.stats()["workers"]
        assert "wA" in co.stats()["retired"]
        assert q.tasks[sid].status == "done"          # no recompute
        assert faults.counters().get("clusterWorkerDeaths", 0) == 0
        assert faults.counters().get(
            "clusterWorkerRetirements", 0) == 1

    def test_drained_work_reroutes_to_peers(self, data_dir):
        co, q = _submit_q3(data_dir)
        co.dispatch(["CREG", "wA"])
        co.dispatch(["CREG", "wB"])
        co.dispatch(["CDRAIN", "wA"])
        # wA holds nothing: retires on its next poll; the whole query
        # drains through wB.
        assert co.dispatch(["CPOLL", "wA", "-"]) == b"CRETIRE\n"
        while True:
            resp = co.dispatch(["CPOLL", "wB", "-"]).decode().split()
            if resp[0] == "CIDLE":
                break
            co.dispatch(["CDONE", "wB", resp[1], resp[2], resp[3],
                         "10"])
        assert all(t.status == "done" and t.producer == "wB"
                   for t in q.tasks.values())

    def test_cretire_idempotent_and_stale_beat_swallowed(self, data_dir):
        co, _ = _submit_q3(data_dir)
        co.dispatch(["CREG", "wA"])
        co.dispatch(["CDRAIN", "wA"])
        assert co.dispatch(["CPOLL", "wA", "-"]) == b"CRETIRE\n"
        # The worker's daemon heartbeat may land once more, and a
        # duplicate poll may race the exit: neither resurrects it.
        assert co.dispatch(["CBEAT", "wA"]) == b"OK\n"
        assert co.dispatch(["CPOLL", "wA", "-"]) == b"CRETIRE\n"
        assert "wA" not in co.stats()["workers"]

    def test_fast_restart_requeues_orphaned_stage(self, data_dir):
        """Incarnation tokens: a supervisor restart re-registers the
        SAME wid, and on a loaded host that CREG can land BEFORE the
        heartbeat sweep notices the old process went silent. The new
        token is proof of death — the dead incarnation's RUNNING stage
        requeues immediately instead of staying assigned to a wid that
        keeps beating (a permanent dispatch stall)."""
        co, q = _submit_q3(data_dir)
        co.dispatch(["CREG", "wA", "pid1"])
        resp = co.dispatch(["CPOLL", "wA", "-"]).decode().split()
        assert resp[0] == "CTASK"
        sid, gen = int(resp[2]), int(resp[3])
        assert q.tasks[sid].status == "running"
        # SIGKILL + instant respawn under the same wid, new process.
        assert co.dispatch(["CREG", "wA", "pid2"]) == b"OK\n"
        t = q.tasks[sid]
        assert t.status == "pending"
        assert t.gen == gen + 1
        assert faults.counters().get("clusterWorkerDeaths", 0) == 1
        # The replacement immediately wins work again.
        resp = co.dispatch(["CPOLL", "wA", "-"]).decode().split()
        assert resp[0] == "CTASK"

    def test_same_token_reconnect_keeps_inflight_stage(self, data_dir):
        """A live worker re-registering after a coordinator hiccup
        (same process, same token) is NOT a death — its in-flight
        stage keeps running and no requeue happens."""
        co, q = _submit_q3(data_dir)
        co.dispatch(["CREG", "wA", "pid1"])
        resp = co.dispatch(["CPOLL", "wA", "-"]).decode().split()
        sid = int(resp[2])
        assert co.dispatch(["CREG", "wA", "pid1"]) == b"OK\n"
        assert q.tasks[sid].status == "running"
        # Tokenless CREG (legacy form) is a plain touch too.
        assert co.dispatch(["CREG", "wA"]) == b"OK\n"
        assert q.tasks[sid].status == "running"
        assert faults.counters().get("clusterWorkerDeaths", 0) == 0

    def test_cdemo_deprioritizes_placement(self, data_dir):
        """A demoted worker ranks below every undemoted peer in
        _pick_locked — it only receives work when it is the sole
        eligible candidate."""
        co, q = _submit_q3(data_dir, **{
            "spark.rapids.sql.cluster.stealDelayMs": 60000})
        co.dispatch(["CREG", "wFast"])
        co.dispatch(["CREG", "wSlow"])
        assert co.dispatch(["CDEMO", "wSlow", "1"]) == b"OK\n"
        assert co.stats()["workers"]["wSlow"]["demoted"] is True
        # With the fast worker mid-steal-delay-free (both idle), the
        # demoted one polls first yet gets nothing while wFast exists
        # and work remains unreserved for it... the cheap invariant to
        # pin without timing games: wFast drains the DAG solo even
        # though wSlow polls eagerly, because every pick prefers it.
        done = 0
        for _ in range(200):
            r = co.dispatch(["CPOLL", "wSlow", "-"]).decode().split()
            if r[0] == "CTASK":
                # demoted may still serve as fallback-of-last-resort
                # for tasks wFast can't take (none here: requeue path)
                co.dispatch(["CDONE", "wSlow", r[1], r[2], r[3], "5"])
            r = co.dispatch(["CPOLL", "wFast", "-"]).decode().split()
            if r[0] == "CTASK":
                co.dispatch(["CDONE", "wFast", r[1], r[2], r[3], "5"])
                done += 1
            if all(t.status == "done" for t in q.tasks.values()):
                break
        assert all(t.status == "done" for t in q.tasks.values())
        producers = {t.producer for t in q.tasks.values()}
        assert producers == {"wFast"}
        assert co.dispatch(["CDEMO", "wSlow", "0"]) == b"OK\n"
        assert co.stats()["workers"]["wSlow"]["demoted"] is False


# ---------------------------------------------------------------------------
# Dispatch-timeout rejection carries the retry contract (satellite)
# ---------------------------------------------------------------------------

class TestDispatchTimeoutHint:
    def test_barrier_timeout_is_typed_hinted_and_transient(self, data_dir):
        from spark_rapids_tpu.parallel.scheduler import (
            QueryRejectedError)
        co, q = _submit_q3(data_dir, **{
            "spark.rapids.sql.cluster.dispatchTimeoutMs": 120})
        co.dispatch(["CREG", "wA"])           # min-workers gate opens
        with pytest.raises(QueryRejectedError) as ei:
            q.run(None)                       # nobody ever polls
        e = ei.value
        assert isinstance(e, CO.ClusterDispatchError)
        assert e.kind == "dispatch-timeout"
        assert e.retry_after_ms is not None and e.retry_after_ms > 0
        assert e.queue_depth == len(q.tasks)
        assert "UNAVAILABLE" in str(e)
        assert is_transient_error(e)          # recovery-ladder eligible


# ---------------------------------------------------------------------------
# Real-process regression: --max-idle-s self-retirement deregisters
# ---------------------------------------------------------------------------

def _spawn_worker(addr, wid, extra_args=(), extra_env=None):
    cmd = [sys.executable, "-m",
           "spark_rapids_tpu.parallel.cluster.worker",
           "--coordinator", addr, "--worker-id", wid,
           "--heartbeat-ms", "200"] + list(extra_args)
    env = dict(os.environ)
    env.pop("SRT_FAULTS", None)
    if extra_env:
        env.update(extra_env)
    return subprocess.Popen(cmd, env=env, cwd=REPO_ROOT)


class TestMaxIdleSelfRetirement:
    @pytest.mark.slow  # real worker process; runs in the CI
    # `autoscaler` chaos entry (no `-m 'not slow'` filter there).
    def test_idle_worker_deregisters_instead_of_silent_exit(self, data_dir):
        """Pre-ISSUE-20, --max-idle-s expiry just exited: membership
        lingered until the heartbeat sweep timed out and counted a
        DEATH. Now the worker drains itself (CDRAIN → CRETIRE): clean
        exit 0, immediate membership drop, a retirement — zero deaths
        — even with the heartbeat timeout cranked to a minute."""
        s = TpuSession()
        s.set("spark.rapids.sql.variableFloatAgg.enabled", True)
        want = tpch.QUERIES["q3"](s, data_dir).collect()
        sc = _cluster_session(**{
            "spark.rapids.sql.cluster.heartbeatTimeoutMs": 60000})
        co = CL.get_coordinator(sc.conf)
        addr = f"{co.addr[0]}:{co.addr[1]}"
        p = _spawn_worker(addr, "solo", ["--max-idle-s", "1.0",
                                        "--poll-ms", "25"])
        try:
            # 1-task pool runs the whole query, then idles out.
            got = tpch.QUERIES["q3"](sc, data_dir).collect()
            assert got == want
            rc = p.wait(timeout=30)
            assert rc == 0
            st = co.stats()
            assert "solo" not in st["workers"]
            assert "solo" in st["retired"]
            cnt = faults.counters()
            assert cnt.get("clusterWorkerDeaths", 0) == 0
            assert cnt.get("clusterWorkerRetirements", 0) == 1
        finally:
            if p.poll() is None:
                p.kill()
                p.wait(timeout=10)
