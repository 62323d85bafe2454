"""Deterministic fault-injection registry (the chaos half of the
robustness story).

The reference's headline guarantee is that nothing failing ever corrupts
a query: RMM's alloc-failure callback spills and retries
(DeviceMemoryEventHandler.scala:42-69) and CPU fallback is always
available. This engine has the same machinery (memory/oom.py ladder,
planner transient retry, host degradation) — but recovery code that is
never exercised is recovery code that cannot be trusted. This module
makes every dispatch funnel *injectable* so tests/test_chaos.py can run
real queries under seeded fault schedules and assert bit-identical
results.

Spec grammar (``spark.rapids.sql.test.faults`` config or ``SRT_FAULTS``
env)::

    kind@site[/query=N][:arg][,kind@site[/query=N][:arg]...]

- ``kind``: ``oom`` (raises a synthetic RESOURCE_EXHAUSTED, recovered by
  the OOM escalation ladder), ``transient`` (raises a synthetic
  UNAVAILABLE, recovered by the planner's retry ladder), ``corrupt``
  (flips one byte of a serialized frame at a corruption site; detected
  by the CRC32 frame checksum and re-read), ``lostoutput`` (simulates a
  lost durable stage output at an exchange site; recovered by the
  lineage-scoped stage recompute, parallel/stages.py), ``stall``
  (hangs the dispatch until the execution watchdog kills and
  re-dispatches the partition, ops/base.py), or ``workerdeath``
  (SIGKILLs the cluster worker process at the ``cluster.stage`` site,
  parallel/cluster/worker.py — the coordinator's heartbeat monitor
  detects the death and requeues the stage task on a survivor: one
  stage recompute, never a dead query), ``slowput`` (injects latency
  into a shuffle-transport shard write at the ``transport`` site —
  exercises slow-writer overlap, never an error), or ``unavailable``
  (one backend request at the ``objectstore`` site fails with a
  synthetic 5xx/UNAVAILABLE; absorbed by the transport's bounded
  retry with exponential backoff + deterministic jitter, counter
  ``objectstoreRetries``).
- ``site``: a named injection point woven into the dispatch funnels:
  ``upload`` (wire codec device_put), ``download`` (result device_get),
  ``concat`` (batch coalescing), ``kernel`` (cached-kernel dispatch),
  ``scan`` (host-side scan-unit decode — fires on prefetch/reader
  threads and is re-raised at the ordered consumption point under the
  pipelined executor), ``exchange.flush`` / ``exchange.serve`` (shuffle
  map/reduce sides), ``mesh.exchange`` (collective shuffle),
  ``transport`` / ``transport.write`` (shuffle-transport SPI fetch and
  write funnels, parallel/transport/ — ``lostshard`` deletes the shard
  at rest and raises owner-tagged, so recovery MUST recompute the
  owning stage; ``corrupt`` flips a byte of the fetched frame, detected
  by the CRC and refetched once, counter ``remoteShardRefetches``;
  ``slowput`` delays the shard write), ``objectstore`` (one HTTP
  request to the object-store backend — ``unavailable`` only),
  ``spill.write`` / ``spill.read`` (disk tier I/O), ``wire``
  (serialized spill frames — corrupt only), ``cluster.stage``
  (cluster worker stage-task execution — workerdeath only).
- ``arg``: an integer N fires on the first N hits of the site (default
  1); a float p in (0, 1) fires per-hit with probability p from a
  deterministic per-site PRNG seeded by
  ``spark.rapids.sql.test.faults.seed`` / ``SRT_FAULTS_SEED``.
- ``/query=N``: query-scoped arming — the entry fires only on hits made
  by the query whose fault tag is ``N`` (the explicit
  ``spark.rapids.sql.test.faults.queryTag`` conf, falling back to the
  scheduler admission ordinal). Cross-query chaos tests inject a fault
  into query A and assert query B's results and counters are
  bit-identical to a solo run (parallel/scheduler.py, ISSUE 5).

This module also carries the per-thread QUERY TOKEN — the cooperative
cancellation/deadline handle the QueryManager (parallel/scheduler.py)
issues at admission. Every dispatch funnel already calls
:func:`fault_point`, so the same funnels double as cancellation
checkpoints: a cancelled or deadline-expired query unwinds with
:class:`QueryCancelledError` at its next dispatch, releasing the TPU
semaphore and every owned buffer on the way out. The token lives here
(not in the scheduler) because deep dispatch code may import faults but
must not import the scheduler.

The registry is process-global and ARMED only while a non-empty spec is
configured; a disarmed ``fault_point`` is two attribute loads (the
cancellation checkpoint + the injector), so production dispatch pays
almost nothing. Every injection/recovery event bumps
the process-global counters (``faultsInjected``, ``retriesAttempted``,
``spillEscalations``, ``hostFallbacks``, ``corruptionsDetected``) and,
when a query is running, the per-query ``Recovery`` Metrics sink —
surfaced through ``DataFrame.metrics()`` and :func:`counters`.

Deliberately imports nothing beyond stdlib: oom/stores/wire/ops all
import this module from deep dispatch code.
"""

from __future__ import annotations

import os
import random
import threading
import time
from typing import Dict, List, Optional, Tuple


class InjectedOomError(RuntimeError):
    """Synthetic device allocation failure: ``is_oom_error`` routes the
    type into the spill/retry ladder exactly like the real thing."""

    def __init__(self, site: str):
        super().__init__(
            f"RESOURCE_EXHAUSTED: injected fault at {site!r} "
            f"(spark.rapids.sql.test.faults)")
        self.site = site


class InjectedTransientError(RuntimeError):
    """Synthetic backend failure. Carries the UNAVAILABLE marker
    so ``is_transient_error`` routes it into the whole-query retry."""

    def __init__(self, site: str):
        super().__init__(
            f"UNAVAILABLE: injected transient fault at {site!r} "
            f"(spark.rapids.sql.test.faults)")
        self.site = site


class InjectedLostOutputError(RuntimeError):
    """Synthetic loss of a durable stage output (a shuffle/broadcast
    materialization that vanished or failed its checksum). Carries the
    UNAVAILABLE marker so, when lineage-scoped recovery is disabled or
    cannot attribute the loss, the whole-query retry still recovers it.
    ``fault_owner`` (``id()`` of the owning exchange exec, set by the
    injection site) lets parallel/stages.py invalidate and recompute
    just the owning stage instead."""

    def __init__(self, site: str):
        super().__init__(
            f"UNAVAILABLE: injected lost stage output at {site!r} "
            f"(spark.rapids.sql.test.faults)")
        self.site = site
        self.fault_owner: Optional[int] = None


class InjectedStallError(RuntimeError):
    """Raised when an injected stall is cancelled by the execution
    watchdog (the killed attempt's thread unwinds on it) or when its
    safety timeout expires with no watchdog armed. The message carries
    the DEADLINE_EXCEEDED marker so an escaped stall routes into the
    transient retry instead of failing the query."""

    def __init__(self, site: str):
        super().__init__(
            f"DEADLINE_EXCEEDED: injected stall at {site!r} "
            f"(spark.rapids.sql.test.faults)")
        self.site = site


class QueryCancelledError(RuntimeError):
    """The query was cancelled (explicit ``cancel()``) or its deadline
    expired (``collect(timeout_ms=...)``). The message deliberately
    carries NO transient/OOM marker: a cancelled query must unwind
    through every retry ladder — not be lovingly retried by one."""

    def __init__(self, query_id: int, reason: str):
        super().__init__(
            f"CANCELLED: query {query_id} {reason} "
            "(spark.rapids.sql.scheduler.*)")
        self.query_id = query_id
        self.reason = reason


class QueryPreemptedError(RuntimeError):
    """Control-flow only: the query was asked to yield the device to a
    higher-priority class and unwound at a partition boundary. The
    planner's ladder catches it, spills the query's catalog, waits for
    the preemptor to drain, and resumes on the SAME context — durable
    stage outputs make the suspension invisible in the results. Like
    cancellation, the message carries NO transient/OOM marker: no other
    retry rung may consume a preemption."""

    def __init__(self, query_id: int, preemptor: Optional[str] = None):
        super().__init__(
            f"PREEMPTED: query {query_id} yielded the device to a "
            f"{preemptor or 'higher-priority'} query "
            "(spark.rapids.sql.scheduler.preemption.*)")
        self.query_id = query_id
        self.preemptor = preemptor


class QueryToken:
    """Per-query cooperative cancellation/deadline handle, issued by the
    QueryManager at admission and registered thread-locally on every
    thread that works for the query (the collect thread itself, watchdog
    attempt workers, pipeline prefetchers, concurrent stage threads).

    ``cancel`` is a plain Event so blocking waits (semaphore admission,
    pipeline ``_take``, injected stalls) can wake on it; ``reason`` is
    set before the event so the unwinding error names why. The deadline
    is enforced by the scheduler's timer arm (it sets the same event),
    so checkpoints only ever test one flag.

    ``preempt`` is the overload survival plane's second, gentler signal
    (scheduler.preemption.enabled): set by the class-ranked device gate
    when a higher-priority query is queued behind this one. Unlike
    cancel it is only honored at partition boundaries
    (:func:`check_preempted`) and the query RESUMES afterwards — it
    never changes results, only when the device is held."""

    __slots__ = ("query_id", "fault_tag", "cancel", "reason", "tenant",
                 "qos_class", "preempt", "preemptor_class",
                 "preempt_enabled")

    def __init__(self, query_id: int, fault_tag: Optional[int] = None,
                 tenant: Optional[str] = None,
                 qos_class: Optional[str] = None):
        self.query_id = query_id
        # The tag query-scoped fault entries (kind@site/query=N) match.
        self.fault_tag = fault_tag if fault_tag is not None else query_id
        self.cancel = threading.Event()
        self.reason = "cancelled"
        # Serving-tier identity (parallel/qos/): owner attribution for
        # per-tenant quotas and plan-cache stats. None = untagged.
        self.tenant = tenant
        # Priority class (parallel/qos/) — the class-ranked device gate
        # orders acquisition and picks preemption victims by it. None =
        # FIFO admission (ranks as the default class).
        self.qos_class = qos_class
        self.preempt = threading.Event()
        self.preemptor_class: Optional[str] = None
        # Cleared by the planner once preemption.maxPerQuery is spent:
        # further requests are ignored and the query runs to completion.
        self.preempt_enabled = True

    def request_cancel(self, reason: str = "cancelled") -> None:
        self.reason = reason
        self.cancel.set()

    def cancelled(self) -> bool:
        return self.cancel.is_set()

    def error(self) -> QueryCancelledError:
        return QueryCancelledError(self.query_id, self.reason)

    def request_preempt(self, preemptor_class: Optional[str] = None) -> None:
        """Ask this query to yield the device at its next partition
        boundary (the class-ranked gate calls this; honoring it is
        cooperative and bounded by preemption.maxPerQuery)."""
        self.preemptor_class = preemptor_class
        self.preempt.set()

    def preempt_requested(self) -> bool:
        return self.preempt_enabled and self.preempt.is_set()

    def clear_preempt(self) -> None:
        self.preempt.clear()
        self.preemptor_class = None


def set_query_token(token: Optional[QueryToken]) -> None:
    """Register the active query's token for the calling thread. Helper
    threads (watchdog attempts, prefetch pool, stage pool) propagate it
    exactly like the recovery sink — thread-locals don't inherit."""
    _TL.query = token


def get_query_token() -> Optional[QueryToken]:
    return getattr(_TL, "query", None)


def check_cancelled() -> None:
    """Cancellation checkpoint: raise :class:`QueryCancelledError` when
    the calling thread's query was cancelled or deadlined. A single
    thread-local load + event test when a token is registered; a single
    attribute load when not — cheap enough for every dispatch funnel
    (:func:`fault_point` calls it first)."""
    tok = getattr(_TL, "query", None)
    if tok is not None and tok.cancel.is_set():
        raise tok.error()


def check_preempted() -> None:
    """Partition-boundary preemption checkpoint: raise
    :class:`QueryPreemptedError` when the class-ranked device gate asked
    the calling thread's query to yield. Separate from
    :func:`check_cancelled` on purpose — preemption is only honored
    where suspending is safe (between partitions, where every live
    intermediate is catalog-registered data at rest), never inside the
    deep dispatch funnels. One thread-local load + two attribute tests
    when a token is registered; a no-op whenever preemption is off
    (the gate never sets the event)."""
    tok = getattr(_TL, "query", None)
    if tok is not None and tok.preempt_enabled and tok.preempt.is_set():
        raise QueryPreemptedError(tok.query_id, tok.preemptor_class)


def current_query_id() -> Optional[int]:
    """The calling thread's query id (owner tag for catalog buffers and
    kernel-cache reservations), or None outside a managed query."""
    tok = getattr(_TL, "query", None)
    return None if tok is None else tok.query_id


class FaultSpec:
    """One parsed ``kind@site[/query=N]:arg`` entry."""

    __slots__ = ("kind", "site", "count", "probability", "fired", "query")

    def __init__(self, kind: str, site: str, count: Optional[int],
                 probability: Optional[float],
                 query: Optional[int] = None):
        self.kind = kind
        self.site = site
        self.count = count              # fire on the first N hits
        self.probability = probability  # or per-hit Bernoulli(p)
        self.query = query              # only for this query tag (None=any)
        self.fired = 0

    def __repr__(self):  # pragma: no cover - debug
        arg = self.probability if self.count is None else self.count
        q = "" if self.query is None else f"/query={self.query}"
        return f"FaultSpec({self.kind}@{self.site}{q}:{arg})"


_KINDS = ("oom", "transient", "corrupt", "lostoutput", "stall",
          "lostshard", "workerdeath", "slowput", "unavailable")


class FaultParseError(ValueError):
    pass


def parse_spec(spec: str) -> List[FaultSpec]:
    out: List[FaultSpec] = []
    for entry in spec.split(","):
        entry = entry.strip()
        if not entry:
            continue
        if "@" not in entry:
            raise FaultParseError(
                f"bad fault entry {entry!r}: expected kind@site[:arg]")
        kind, rest = entry.split("@", 1)
        kind = kind.strip().lower()
        if kind not in _KINDS:
            raise FaultParseError(
                f"unknown fault kind {kind!r} (want one of {_KINDS})")
        if ":" in rest:
            site, arg = rest.rsplit(":", 1)
        else:
            site, arg = rest, "1"
        site = site.strip()
        query: Optional[int] = None
        if "/" in site:
            site, qpart = site.split("/", 1)
            site = site.strip()
            qpart = qpart.strip()
            if not qpart.startswith("query="):
                raise FaultParseError(
                    f"bad fault entry {entry!r}: expected /query=N")
            try:
                query = int(qpart[len("query="):])
            except ValueError:
                raise FaultParseError(
                    f"bad fault entry {entry!r}: query tag must be an int")
        if not site:
            raise FaultParseError(f"bad fault entry {entry!r}: empty site")
        arg = arg.strip()
        try:
            if "." in arg:
                p = float(arg)
                if not 0.0 < p <= 1.0:
                    raise FaultParseError(
                        f"fault probability out of (0, 1]: {entry!r}")
                out.append(FaultSpec(kind, site, None, p, query))
            else:
                n = int(arg)
                if n < 1:
                    raise FaultParseError(
                        f"fault count must be >= 1: {entry!r}")
                out.append(FaultSpec(kind, site, n, None, query))
        except ValueError as e:
            if isinstance(e, FaultParseError):
                raise
            raise FaultParseError(f"bad fault arg in {entry!r}") from e
    return out


class FaultInjector:
    """Armed schedule: per-site hit counters + deterministic PRNGs."""

    def __init__(self, spec: str, seed: int = 0):
        self.spec = spec
        self.seed = int(seed)
        self.entries = parse_spec(spec)
        self._lock = threading.Lock()
        self._hits: Dict[str, int] = {}
        self._rngs: Dict[str, random.Random] = {}

    def _rng(self, site: str) -> random.Random:
        rng = self._rngs.get(site)
        if rng is None:
            # Seeded per (seed, site): the roll sequence at a site is a
            # pure function of the schedule, never of thread timing at
            # OTHER sites.
            rng = self._rngs[site] = random.Random(f"{self.seed}:{site}")
        return rng

    def should_fire(self, site: str, kinds,
                    query: Optional[int] = None) -> Optional[FaultSpec]:
        """One hit of ``site``; returns the spec entry that fires (first
        match wins) or None. Thread-safe and deterministic for count
        faults; probability faults are deterministic given a
        deterministic hit order. ``query`` is the hitting query's fault
        tag — query-scoped entries fire only on matching hits, so chaos
        in query A is invisible to query B."""
        with self._lock:
            hit = self._hits.get(site, 0) + 1
            self._hits[site] = hit
            for e in self.entries:
                if e.site != site or e.kind not in kinds:
                    continue
                if e.query is not None and e.query != query:
                    continue
                if e.count is not None:
                    if e.fired < e.count:
                        e.fired += 1
                        return e
                elif self._rng(site).random() < e.probability:
                    e.fired += 1
                    return e
        return None


_LOCK = threading.Lock()
_INJECTOR: Optional[FaultInjector] = None
_COUNTERS: Dict[str, float] = {}
_TL = threading.local()


def _env_injector() -> Optional[FaultInjector]:
    spec = os.environ.get("SRT_FAULTS", "").strip()
    if not spec:
        return None
    return FaultInjector(spec, int(os.environ.get("SRT_FAULTS_SEED", "0")))


with _LOCK:
    _INJECTOR = _env_injector()


def configure(spec: str, seed: int = 0) -> Optional[FaultInjector]:
    """(Re-)arm the process-global schedule; empty spec disarms. Count
    faults reset to unfired — callers arm once per query so a retried
    attempt sees the REMAINING schedule, not a fresh one."""
    global _INJECTOR
    with _LOCK:
        _INJECTOR = FaultInjector(spec, seed) if spec.strip() else None
        return _INJECTOR


def maybe_configure(conf) -> None:
    """Arm from ``spark.rapids.sql.test.faults`` when the query's conf
    sets it explicitly (the config wins over SRT_FAULTS); called once
    per query by PhysicalPlan.collect, BEFORE the attempt loop, so
    transient retries run against the remaining schedule.

    Idempotent against the ARMED schedule: a second collect() with the
    same (spec, seed) keeps the current injector — and therefore its
    consumed count-fault state — instead of re-arming a fresh one. A
    repeated collect after a fault-recovered run must not re-fire
    already-consumed faults; tests that want a fresh schedule call
    :func:`configure` directly."""
    from spark_rapids_tpu import config as C
    if C.TEST_FAULTS.key in conf.raw:
        spec = str(conf.get(C.TEST_FAULTS))
        seed = int(conf.get(C.TEST_FAULTS_SEED))
        with _LOCK:
            cur = _INJECTOR
            if cur is not None and cur.spec == spec and cur.seed == seed:
                return
        configure(spec, seed)


def injector() -> Optional[FaultInjector]:
    return _INJECTOR


def snapshot() -> Tuple[Optional[FaultInjector], Dict[str, float]]:
    """Capture the process-global fault state (armed injector + recovery
    counters) so a test harness can restore it afterwards — chaos tests
    must never bleed armed schedules or counter state into later tests
    (tests/conftest.py's autouse fixture)."""
    with _LOCK:
        return _INJECTOR, dict(_COUNTERS)


def restore(state: Tuple[Optional[FaultInjector], Dict[str, float]]) -> None:
    """Restore a :func:`snapshot` (the exact injector object, with its
    consumed-fault state, and the counter values as of the snapshot)."""
    global _INJECTOR
    inj, counters = state
    with _LOCK:
        _INJECTOR = inj
        _COUNTERS.clear()
        _COUNTERS.update(counters)


def set_recovery_sink(metrics) -> None:
    """Per-query Metrics object that mirrors the process-global recovery
    counters (set around a collect by ops/base.py)."""
    _TL.sink = metrics


def get_recovery_sink():
    """The calling thread's recovery sink (ops/base.py's watchdog hands
    it to partition worker threads — thread-locals don't inherit)."""
    return getattr(_TL, "sink", None)


def set_cancel_event(event) -> None:
    """Register the watchdog's cancel event for the calling (partition
    worker) thread: an injected ``stall`` waits on it and unwinds with
    :class:`InjectedStallError` the moment the watchdog kills the
    attempt, so the abandoned thread exits instead of lingering."""
    _TL.cancel = event


def get_cancel_event():
    """The calling thread's registered cancel event (None outside a
    watchdog attempt). Pool fan-outs that dispatch work on helper
    threads (scan reader pool, pipeline prefetchers) propagate it so a
    stall on a helper thread still unwinds when the watchdog kills the
    consuming attempt."""
    return getattr(_TL, "cancel", None)


def record(name: str, amount: float = 1) -> None:
    """Bump a recovery counter: process-global (:func:`counters`) and the
    active query's Recovery metrics (DataFrame.metrics())."""
    with _LOCK:
        _COUNTERS[name] = _COUNTERS.get(name, 0) + amount
    sink = getattr(_TL, "sink", None)
    if sink is not None:
        sink.add(name, amount)


def counters() -> Dict[str, float]:
    with _LOCK:
        return dict(_COUNTERS)


def reset_counters() -> None:
    with _LOCK:
        _COUNTERS.clear()


# Safety net for a stall with no watchdog armed: wait at most this long
# before unwinding as DEADLINE_EXCEEDED (-> transient retry).
STALL_TIMEOUT_S = float(os.environ.get("SRT_STALL_TIMEOUT_S", "30"))


def _current_fault_tag() -> Optional[int]:
    """The calling thread's query fault tag (for kind@site/query=N
    matching), or None outside a managed query — query-scoped entries
    then never fire."""
    tok = getattr(_TL, "query", None)
    return None if tok is None else tok.fault_tag


def _stall(site: str) -> None:
    """Injected stall: hang this dispatch like a wedged device call.
    With a watchdog armed (worker thread registered a cancel event) the
    wait ends the instant the watchdog kills the attempt; a registered
    query token likewise ends it on cancel/deadline; without either, the
    bounded safety timeout expires. Either way the dispatch unwinds —
    with :class:`QueryCancelledError` on a query cancel, else
    :class:`InjectedStallError` — a stall never 'completes'."""
    cancel = getattr(_TL, "cancel", None)
    tok = getattr(_TL, "query", None)
    deadline = time.monotonic() + STALL_TIMEOUT_S
    while time.monotonic() < deadline:
        if cancel is not None and cancel.is_set():
            break
        if tok is not None:
            if tok.cancel.wait(0.02):
                raise tok.error()
        elif cancel is not None:
            cancel.wait(0.05)
        else:
            time.sleep(0.05)
    raise InjectedStallError(site)


def check_fault(site: str, kinds) -> Optional[FaultSpec]:
    """One hit of ``site`` against the armed schedule, restricted to
    ``kinds``: returns the firing entry (recording the injection
    counters) or None. The raw half of :func:`fault_point` for callers
    that must act on the fired kind themselves — the shuffle-transport
    fetch funnel uses it to delete the shard at rest before raising a
    ``lostshard``, so recovery provably rewrites data instead of
    re-reading it."""
    inj = _INJECTOR
    if inj is None:
        return None
    e = inj.should_fire(site, kinds, _current_fault_tag())
    if e is None:
        return None
    record("faultsInjected")
    record(f"faultsInjected.{e.kind}@{site}")
    # Flight-recorder instant (lazy import: this module stays
    # stdlib-only at load; monitoring is itself stdlib-only).
    from spark_rapids_tpu import monitoring
    monitoring.instant("fault-injected", "recovery",
                       args={"kind": e.kind, "site": site})
    return e


def fault_point(site: str, owner: Optional[int] = None) -> None:
    """Named injection site AND cancellation checkpoint. Checks the
    calling thread's query token first (a cancelled/deadlined query
    unwinds here with :class:`QueryCancelledError`); beyond that it is a
    no-op unless a schedule is armed — raising the synthetic error when
    an ``oom``/``transient``/``lostoutput`` entry fires, or hanging
    (then unwinding) on a ``stall``. ``owner`` tags a lostoutput with
    the owning exchange exec's id so lineage recovery can invalidate
    exactly that stage's output."""
    check_cancelled()
    e = check_fault(site, ("oom", "transient", "lostoutput", "stall"))
    if e is None:
        return
    if e.kind == "oom":
        raise InjectedOomError(site)
    if e.kind == "transient":
        raise InjectedTransientError(site)
    if e.kind == "lostoutput":
        err = InjectedLostOutputError(site)
        err.fault_owner = owner
        raise err
    _stall(site)


def corrupt_blob(site: str, blob: bytes) -> bytes:
    """Corruption site: returns ``blob`` with one byte flipped when a
    ``corrupt`` entry fires (deterministic offset from the site PRNG),
    else the blob unchanged. Used on READ paths so the underlying data
    survives — detection + one re-read recovers; real (persistent)
    corruption still fails loudly at the checksum."""
    inj = _INJECTOR
    if inj is None or not blob:
        return blob
    e = inj.should_fire(site, ("corrupt",), _current_fault_tag())
    if e is None:
        return blob
    record("faultsInjected")
    record(f"faultsInjected.corrupt@{site}")
    from spark_rapids_tpu import monitoring
    monitoring.instant("fault-injected", "recovery",
                       args={"kind": "corrupt", "site": site})
    off = inj._rng(site).randrange(len(blob))
    out = bytearray(blob)
    out[off] ^= 0xFF
    return bytes(out)
