"""Physical operator base (ref: GpuExec.scala:65).

Execution model: a plan is a tree of ``Exec`` nodes; each node, per
partition, produces an iterator of batches. Two engines exist, mirroring the
reference's CPU-Spark vs GPU split:

- device: iterators of ``DeviceBatch``; per-batch kernels are pure jnp
  functions (jittable). The Python generator layer is only orchestration —
  the same division the reference has between JVM iterators and cuDF kernels.
- host: iterators of ``HostBatch`` (numpy) — the CPU fallback engine and the
  comparison oracle.

Metrics mirror GpuMetricNames (GpuExec.scala:27-56): numOutputRows,
numOutputBatches, totalTime (ns).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from spark_rapids_tpu.columnar.dtypes import DataType
from spark_rapids_tpu.columnar.batch import DeviceBatch
from spark_rapids_tpu.columnar.host import (
    HostBatch, device_to_host, host_to_device)
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.monitoring import recorder as _recorder

Schema = Tuple[Tuple[str, DataType], ...]


class Metrics:
    """Per-operator metric registry (NvtxWithMetrics analog — with the
    flight recorder on, ``timed`` sections are also profiler
    annotations, so a profile of a query shows per-operator ranges,
    NvtxWithMetrics.scala:21-44)."""

    def __init__(self, owner: str = ""):
        self.owner = owner
        self.values: Dict[str, float] = {}
        # add() is a read-modify-write reached from prefetch/stage
        # threads under the pipelined executor — lock it so two
        # concurrent collects can never lose counter increments.
        self._lock = threading.Lock()
        self._deferred: list = []

    def add(self, name: str, amount: float):
        with self._lock:
            self.values[name] = self.values.get(name, 0) + amount

    def defer(self, count) -> None:
        """A count that only a device read can give: ``count(self)``
        makes the read and its ``add`` calls when the metrics are next
        read (:meth:`settle`), after the query and not inside it. It is
        handed the registry so that it need not hold it: no cycle."""
        with self._lock:
            self._deferred.append(count)

    def settle(self) -> "Metrics":
        """Run what :meth:`defer` holds; ``DataFrame.metrics()`` and
        ``explain_analyze`` read through here."""
        with self._lock:
            todo, self._deferred = self._deferred, []
        for count in todo:
            count(self)
        return self

    def __repr__(self):  # pragma: no cover - cosmetic
        return f"Metrics({self.values})"


# -- audit metric groups ------------------------------------------------------
# THE registry of per-query audit entries (<Owner>@query) that the
# metrics verbosity filter (spark.rapids.sql.metrics.level) must never
# drop: they are recovery/scheduling audit trails, not operator
# telemetry. Every subsystem creates its entry through
# query_metrics_entry(), which registers the owner here — replacing the
# ad-hoc per-call-site exemptions DataFrame.metrics() used to hardcode.
_AUDIT_METRIC_GROUPS = {"Recovery", "Pipeline", "Scheduler", "Transport",
                        "Cost", "Cluster"}
_AUDIT_LOCK = threading.Lock()


def register_audit_metric_group(owner: str) -> None:
    """Mark ``owner`` as a level-filter-exempt audit group (idempotent).
    Third-party subsystems get the same never-filtered treatment as the
    built-in Recovery/Pipeline/Scheduler/Transport/Cost entries."""
    with _AUDIT_LOCK:
        _AUDIT_METRIC_GROUPS.add(owner)


def audit_metric_groups() -> frozenset:
    with _AUDIT_LOCK:
        return frozenset(_AUDIT_METRIC_GROUPS)


def query_metrics_entry(ctx: "ExecContext", owner: str) -> Metrics:
    """The per-query ``<owner>@query`` audit Metrics entry, created on
    first use and registered as level-filter exempt. All subsystems
    (scheduler, pipeline, transport, cost/replan, recovery) route
    through here so the exemption set has exactly one source."""
    register_audit_metric_group(owner)
    return ctx.metrics.setdefault(f"{owner}@query", Metrics(owner=owner))


def record_batch(m: Metrics, batch) -> None:
    """Record one output batch's observable size: always
    ``numOutputBatches``; ``numOutputRows``/``numOutputBytes`` when a
    HOST-KNOWN row count exists (``rows_hint`` on device batches, exact
    ``num_rows`` on host batches). Never forces a device sync — an
    unknown count stays unknown (explain_analyze renders ``?``) rather
    than costing a ~70ms round trip per batch."""
    m.add("numOutputBatches", 1)
    rows = getattr(batch, "rows_hint", None)
    if rows is None:
        nr = getattr(batch, "num_rows", None)
        if type(nr) is int:
            rows = nr
    if rows is None:
        return
    m.add("numOutputRows", int(rows))
    try:
        width = 0
        for c in batch.columns:
            if c.dtype.is_string:
                width += int(c.data.shape[1]) + 5
            else:
                width += int(c.dtype.np_dtype.itemsize) + 1
        if width:
            m.add("numOutputBytes", int(rows) * width)
    except Exception:
        pass        # exotic column layout: rows recorded, bytes skipped


@dataclasses.dataclass
class ExecContext:
    """Per-query execution context: conf + metrics sink + materialization
    cache (shuffle buckets, broadcast batches, built join sides — the role
    the reference's RapidsBufferCatalog/device store plays for shuffle
    data, SURVEY.md §2.6)."""

    conf: TpuConf = dataclasses.field(default_factory=TpuConf)
    metrics: Dict[str, Metrics] = dataclasses.field(default_factory=dict)
    cache: Dict[str, object] = dataclasses.field(default_factory=dict)
    # The admitting QueryManager ticket (parallel/scheduler.py): carries
    # the query id (catalog owner tag), the fair-share memory fraction,
    # and the cancellation token. None = unmanaged context (unit tests,
    # host oracle runs) — full budget, no owner, today's behavior.
    query: Optional[object] = None
    # Catalog leak report captured at close() AFTER owned handles were
    # released: [] proves query teardown freed everything it owned.
    last_leak_report: Optional[list] = None
    _catalog: Optional[object] = None
    _lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False, compare=False)

    def metrics_for(self, op: "Exec") -> Metrics:
        # Keyed/owned by op.name (not the bare class name) so fused
        # stages report as FusedStageExec[Project->Filter->...] and the
        # per-node metrics owner stays readable after fusion. Locked:
        # concurrent stage/prefetch threads registering the same op must
        # share ONE Metrics object (a lost entry loses its counts).
        key = f"{op.name}@{id(op):x}"
        m = self.metrics.get(key)
        if m is None:
            with self._lock:
                m = self.metrics.get(key)
                if m is None:
                    m = self.metrics[key] = Metrics(owner=op.name)
        return m

    @property
    def catalog(self):
        """Lazily-built spill catalog: every held batch (shuffle buckets,
        broadcast tables, buffered build sides) registers here so HBM
        pressure spills device->host->disk instead of OOMing
        (RapidsBufferCatalog.init wiring, RapidsBufferCatalog.scala:128).
        Built under the context lock: concurrent stage threads must
        never race two catalogs into existence (one would leak)."""
        if self._catalog is None:
            with self._lock:
                if self._catalog is not None:
                    return self._catalog
                from spark_rapids_tpu import config as C
                from spark_rapids_tpu.memory.stores import BufferCatalog
                budget = int(self.conf.get(C.DEVICE_BUDGET_BYTES))
                if budget <= 0:
                    visible = _visible_device_bytes()
                    budget = int(visible * float(
                        self.conf.get(C.HBM_POOL_FRACTION)))
                    # Ceiling + runtime reserve (maxAllocFraction /
                    # reserve, RapidsConf's RMM pool bounds).
                    ceiling = int(visible * float(
                        self.conf.get(C.MAX_ALLOC_FRACTION))) \
                        - int(self.conf.get(C.RESERVE_BYTES))
                    budget = max(min(budget, ceiling), 1 << 20)
                owner = None
                if self.query is not None:
                    # Managed query: fair-share budget + owner tagging
                    # (scheduler.queryMemoryFraction; GpuSemaphore +
                    # owner-tagged RapidsBufferCatalog analog).
                    from spark_rapids_tpu.parallel import scheduler as SC
                    frac = SC.query_memory_fraction(
                        self.conf, SC.get_query_manager(self.conf))
                    budget = max(int(budget * frac), 1 << 20)
                    owner = self.query.query_id
                self._catalog = BufferCatalog(
                    device_budget_bytes=budget,
                    host_budget_bytes=int(
                        self.conf.get(C.HOST_SPILL_STORAGE_SIZE)),
                    spill_dir=str(self.conf.get(C.SPILL_DIR)),
                    compression_codec=str(
                        self.conf.get(C.SHUFFLE_COMPRESSION_CODEC)),
                    debug=bool(self.conf.get(C.MEMORY_DEBUG)),
                    owner=owner)
        return self._catalog

    def release_owned(self):
        """Close every durable handle this context still holds (shuffle
        buckets, broadcast singles, mesh shards — SpillableBatch handles
        parked in ``cache``): query teardown must free everything the
        query owned whether it succeeded, failed, or was cancelled."""
        from spark_rapids_tpu.memory.stores import SpillableBatch
        from spark_rapids_tpu.parallel.transport.base import \
            ShuffleSession

        def close_in(obj, depth: int = 0):
            if isinstance(obj, SpillableBatch):
                obj.close()
            elif isinstance(obj, ShuffleSession):
                # Transport sessions (parallel/transport/) own their
                # shards — catalog handles or spool files; teardown
                # releases both.
                obj.close()
            elif depth < 3 and isinstance(obj, (list, tuple)):
                for x in obj:
                    close_in(x, depth + 1)
            elif depth < 3 and isinstance(obj, dict):
                for x in obj.values():
                    close_in(x, depth + 1)

        for v in list(self.cache.values()):
            close_in(v)

    def close(self):
        if self._catalog is not None:
            self.release_owned()
            # The leak report AFTER releasing owned handles: non-empty
            # means a buffer escaped its owner's teardown — the
            # scheduler's isolation tests assert this is [].
            self.last_leak_report = self._catalog.leak_report()
            self._catalog.close()
            self._catalog = None


# The CPU backend reports no memory stats: its "device" memory is host
# RAM, and the suite's budgets are sized against this figure.
_CPU_BACKEND_DEVICE_BYTES = 8 << 30


def _visible_device_bytes() -> int:
    """Memory of device 0 as the device reports it. An accelerator that
    reports no ``bytes_limit`` is an error, not 8 GiB: every budget
    below would be sized for hardware that is not there."""
    import jax
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return _CPU_BACKEND_DEVICE_BYTES
    stats = dev.memory_stats() or {}
    limit = stats.get("bytes_limit")
    if not limit:
        raise RuntimeError(
            f"{dev.platform} device {dev.device_kind!r} reports no "
            f"bytes_limit in memory_stats() ({sorted(stats)}); set "
            f"spark.rapids.memory.tpu.budgetBytes explicitly")
    return int(limit)


class WatchdogTimeoutError(RuntimeError):
    """Every watchdog attempt at a partition exceeded its deadline. The
    message carries the DEADLINE_EXCEEDED marker so the planner's
    transient retry is the next demotion rung (partition retry -> stage
    recompute -> whole-query retry)."""

    def __init__(self, op: str, label: str, timeout_ms: int,
                 attempts: int):
        super().__init__(
            f"DEADLINE_EXCEEDED: watchdog killed {op} {label} on all "
            f"{attempts} attempt(s) of {timeout_ms}ms "
            "(spark.rapids.sql.watchdog.*)")
        self.label = label


@dataclasses.dataclass
class _WatchdogParams:
    timeout_ms: int
    max_attempts: int


def _watchdog_params(conf: TpuConf) -> Optional[_WatchdogParams]:
    from spark_rapids_tpu import config as C
    if not bool(conf.get(C.WATCHDOG_ENABLED)):
        return None
    return _WatchdogParams(
        timeout_ms=max(int(conf.get(C.WATCHDOG_TASK_TIMEOUT_MS)), 1),
        max_attempts=max(int(conf.get(C.WATCHDOG_MAX_ATTEMPTS)), 1))


class Exec:
    """A physical operator. Subclasses implement the per-partition device
    and host paths. ``schema`` is the output schema."""

    def __init__(self, *children: "Exec"):
        self.children: Tuple["Exec", ...] = tuple(children)

    @property
    def schema(self) -> Schema:
        raise NotImplementedError

    @property
    def name(self) -> str:
        return type(self).__name__

    # Number of output partitions (defaults to the first child's).
    def num_partitions(self, ctx: ExecContext) -> int:
        return self.children[0].num_partitions(ctx)

    # -- device engine -------------------------------------------------------
    def execute_device(self, ctx: ExecContext,
                       partition: int) -> Iterator[DeviceBatch]:
        raise NotImplementedError

    # -- host engine ---------------------------------------------------------
    def execute_host(self, ctx: ExecContext,
                     partition: int) -> Iterator[HostBatch]:
        raise NotImplementedError

    # -- pipelined execution (parallel/pipeline.py) --------------------------
    def host_prefetchable(self) -> bool:
        """True when this subtree exposes a separable host half worth
        prefetching (a scan below, without crossing a stage boundary —
        a boundary exchange pipelines its own materialization loop)."""
        from spark_rapids_tpu.parallel.stages import is_stage_boundary
        return any(c.host_prefetchable() for c in self.children
                   if not is_stage_boundary(c))

    def prefetch_host(self, ctx: ExecContext, partition: int) -> None:
        """Run the host half of ``partition`` ahead of device dispatch
        (decode, stats pruning, wire encode — everything before
        ``device_put``). Called on pipeline prefetch threads; the
        results land in ``ctx.cache`` keyed by (node, partition) and the
        ordered consumer's ``execute_device`` pops them, so a mistimed
        or never-consumed prefetch costs only wasted CPU, never wrong
        rows. Recursion stops at stage boundaries: partition numbering
        changes there, and the boundary pipelines its own loop."""
        from spark_rapids_tpu.parallel.stages import is_stage_boundary
        for c in self.children:
            if not is_stage_boundary(c):
                c.prefetch_host(ctx, partition)

    def _grace_retry(self, ctx: ExecContext, partition: int):
        """Operator-specific on-device OOM rung ABOVE host fallback:
        return a replacement device iterator (e.g. the hash join's
        grace-partitioned path, ops/join.py) or None. Only consulted
        when the spill/shrink ladder is exhausted before the first
        output batch."""
        return None

    # -- recovery ------------------------------------------------------------
    def execute_device_recovering(self, ctx: ExecContext,
                                  partition: int) -> Iterator[DeviceBatch]:
        """Device stream with the FINAL OOM escalation rungs: when the
        device path dies on an exhausted spill/shrink ladder
        (memory/oom.py OomRetryExhausted) BEFORE producing its first
        batch, first offer the operator its on-device degraded mode
        (``_grace_retry`` — the hash join's spill-partitioned grace
        path), and only if that is unavailable or also OOMs re-run this
        operator subtree on the host engine and upload the results —
        the reference's operator-by-operator CPU fallback, applied at
        the dispatch funnels that pull child streams (collect,
        exchanges, broadcasts). After the first batch is out, consumers
        have already observed device output, so a mid-stream failure
        propagates instead of duplicating rows."""
        from spark_rapids_tpu import config as C, faults
        from spark_rapids_tpu.memory.oom import OomRetryExhausted
        it = self.execute_device(ctx, partition)
        try:
            first = next(it)
        except StopIteration:
            return
        except OomRetryExhausted as e:
            from spark_rapids_tpu import monitoring
            grace_it = self._grace_retry(ctx, partition)
            if grace_it is not None:
                import logging
                logging.getLogger("spark_rapids_tpu").warning(
                    "OOM ladder exhausted in %s partition %d; retrying "
                    "on-device via the grace-partitioned path: %s",
                    self.name, partition, e)
                monitoring.instant(
                    "grace-join-engaged", "recovery",
                    args={"op": self.name, "partition": partition})
                try:
                    first = next(grace_it)
                except StopIteration:
                    return
                except OomRetryExhausted as e2:
                    e = e2      # grace also OOMed: host fallback next
                else:
                    yield first
                    yield from grace_it
                    return
            if not bool(ctx.conf.get(C.OOM_HOST_FALLBACK)):
                raise e
            try:
                host_iter = self.execute_host(ctx, partition)
            except (NotImplementedError, AssertionError):
                raise e     # no host path (bridge nodes): nothing to do
            import logging
            logging.getLogger("spark_rapids_tpu").warning(
                "OOM ladder exhausted in %s partition %d; degrading the "
                "operator subtree to the host engine: %s",
                self.name, partition, e)
            faults.record("hostFallbacks")
            ctx.metrics_for(self).add("hostFallbacks", 1)
            monitoring.instant(
                "host-fallback", "recovery",
                args={"op": self.name, "partition": partition})
            for hb in host_iter:
                yield host_to_device(hb)
            return
        yield first
        yield from it

    def _watchdog_run(self, ctx: ExecContext, wd: "_WatchdogParams",
                      label: str, fn):
        """Execution watchdog (spark.rapids.sql.watchdog.*): run one unit
        of device work (a partition's stream, or the partition-count /
        AQE materialization step) under a deadline with bounded
        re-dispatch — the speculative-re-execution half of the fault
        story (Dean & Ghemawat, MapReduce, OSDI 2004), scoped to a
        partition instead of the query.

        Deterministic first-winner semantics: attempts run strictly
        serially, the first attempt to COMPLETE within its deadline wins,
        and a killed attempt's partial output is discarded whole — the
        computation is pure batch->batch, so whichever attempt wins, the
        result is bit-identical. Kills are cooperative: the attempt
        thread gets a cancel event that injected stalls (and any future
        cancellation-aware dispatch) unwind on; a truly wedged device
        call is abandoned to its daemon thread."""
        import threading

        from spark_rapids_tpu import faults
        from spark_rapids_tpu.memory.oom import (get_active_catalog,
                                                 set_active_catalog)
        timeout_s = wd.timeout_ms / 1000.0
        catalog = get_active_catalog()
        sink = faults.get_recovery_sink()
        token = faults.get_query_token()
        parent_span = _recorder.current()
        for attempt in range(wd.max_attempts):
            cancel = threading.Event()
            box: Dict[str, object] = {}

            def work():
                # Thread-locals don't inherit: the worker needs the
                # query's spill catalog (OOM ladder), recovery sink,
                # query token (cancellation/owner/fault tag), its
                # attempt's cancel event, and the span it works for.
                set_active_catalog(catalog)
                faults.set_recovery_sink(sink)
                faults.set_query_token(token)
                faults.set_cancel_event(cancel)
                _recorder.adopt(parent_span)
                try:
                    box["out"] = fn()
                except BaseException as e:
                    box["err"] = e

            t = threading.Thread(
                target=work, daemon=True,
                name=f"srt-watchdog-{label}-a{attempt}")
            t.start()
            t.join(timeout_s)
            if not t.is_alive():
                err = box.get("err")
                if err is not None:
                    raise err
                return box["out"]
            cancel.set()
            faults.record("watchdogKills")
            ctx.metrics_for(self).add("watchdogKills", 1)
            from spark_rapids_tpu import monitoring
            monitoring.instant(
                "watchdog-kill", "recovery",
                args={"op": self.name, "label": label,
                      "attempt": attempt + 1})
            import logging
            logging.getLogger("spark_rapids_tpu").warning(
                "watchdog: %s %s exceeded %dms (attempt %d/%d)"
                "; killing and %s", self.name, label, wd.timeout_ms,
                attempt + 1, wd.max_attempts,
                "re-dispatching" if attempt + 1 < wd.max_attempts
                else "giving up")
            # Grace join: a cooperatively-cancelled attempt (injected
            # stall) unwinds immediately, so the re-dispatch rarely
            # overlaps the old thread.
            t.join(0.2)
            if attempt + 1 < wd.max_attempts:
                faults.record("partitionRetries")
        raise WatchdogTimeoutError(self.name, label, wd.timeout_ms,
                                   wd.max_attempts)

    # -- helpers -------------------------------------------------------------
    @staticmethod
    def _recovery_metrics(ctx: ExecContext) -> Metrics:
        """The per-query Recovery metrics entry (retriesAttempted /
        spillEscalations / hostFallbacks / faultsInjected...), surfaced
        by DataFrame.metrics() next to the per-operator entries."""
        return query_metrics_entry(ctx, "Recovery")

    def collect(self, ctx: Optional[ExecContext] = None,
                device: bool = True) -> List[tuple]:
        """Run all partitions and collect rows (driver collect analog).

        The device path dispatches EVERY partition before downloading
        anything, then fetches all result batches in one two-phase
        ``download_batches`` call — two host syncs for the whole query
        instead of O(batches)."""
        ctx = ctx or ExecContext()
        # Engine marker: runtime-adaptive pieces (AQE partition coalescing)
        # must only trigger device materialization on the device engine.
        ctx.cache.setdefault("engine", "device" if device else "host")
        rows: List[tuple] = []
        names = tuple(n for n, _ in self.schema)
        if device:
            from spark_rapids_tpu import config as C, monitoring
            from spark_rapids_tpu.columnar import wire
            from spark_rapids_tpu.columnar.host import download_batches
            from spark_rapids_tpu.memory import stores
            from spark_rapids_tpu.memory.stores import get_tpu_semaphore
            # Adopt this query's process-globals (last writer wins):
            # its wire codec (spark.rapids.sql.wire.codec) before any
            # upload happens, its flight-recorder and telemetry
            # configuration before any span site runs
            # (spark.rapids.sql.trace.*), and its preemption policy.
            from spark_rapids_tpu.monitoring import telemetry
            wire.maybe_configure(ctx.conf)
            monitoring.maybe_configure(ctx.conf)
            telemetry.maybe_configure(ctx.conf)
            stores.preemption_configure(ctx.conf)
            # Task admission (GpuSemaphore.scala:74-87): at most
            # concurrentTpuTasks collects issue device work at once, so
            # concurrent queries can't oversubscribe HBM.
            sem = get_tpu_semaphore(
                max(int(ctx.conf.get(C.CONCURRENT_TPU_TASKS)), 1))
            # The query-level span covers EVERYTHING the device path
            # pays for: semaphore wait, adaptive re-planning, stage
            # prematerialization, the partition loop, and the download.
            collect_span = monitoring.span(
                "collect", "query", level=monitoring.LEVEL_QUERY,
                args={"op": self.name})
            t0_collect = time.perf_counter()
            collect_span.__enter__()
            try:
                with sem:
                    # OOM->spill->retry needs the catalog reachable from
                    # dispatch sites deep in the kernel layer (memory/oom.py);
                    # the recovery sink mirrors ladder/fallback/injection
                    # counters into this query's Metrics.
                    from spark_rapids_tpu import faults
                    from spark_rapids_tpu.memory.oom import set_active_catalog
                    set_active_catalog(ctx.catalog)
                    faults.set_recovery_sink(self._recovery_metrics(ctx))
                    try:
                        from spark_rapids_tpu.parallel import pipeline as PL
                        from spark_rapids_tpu.parallel import replan as RP
                        # Runtime adaptive re-planning BEFORE stage
                        # prematerialization: build-side exchanges
                        # materialize now, observed sizes demote shuffled
                        # joins to broadcast, and the skipped probe
                        # exchanges are flagged so the stage pass does not
                        # shuffle them anyway (parallel/replan.py).
                        with monitoring.span("replan", "planning"):
                            RP.plan_adaptive(ctx, self)
                        # Independent stages (join build/probe sides...)
                        # materialize their exchange outputs concurrently
                        # before the ordered partition loop; a no-op when
                        # the pipeline is off or the plan is single-stage.
                        PL.prematerialize_stages(ctx, self)
                        wd = _watchdog_params(ctx.conf)
                        batches: List[DeviceBatch] = []
                        if wd is None:
                            nparts = self.num_partitions(ctx)
                            pipe = PL.open_pipeline(ctx, self, nparts)
                            try:
                                for p in range(nparts):
                                    # Per-partition cancellation +
                                    # preemption checkpoint (the deep
                                    # funnels check cancellation too,
                                    # via fault_point; preemption only
                                    # ever fires at this boundary).
                                    faults.check_cancelled()
                                    faults.check_preempted()
                                    # consume() waits for p's host half
                                    # then returns the device stream
                                    # verbatim, so the serial path keeps
                                    # streaming exactly as before.
                                    with monitoring.span(
                                            "partition", "device-compute",
                                            args={"partition": p,
                                                  "op": self.name}):
                                        batches.extend(pipe.consume(
                                            p, lambda p=p:
                                            self.execute_device_recovering(
                                                ctx, p)))
                            finally:
                                pipe.close()
                        else:
                            # The partition count itself can trigger
                            # device work (AQE coalescing materializes
                            # the exchange to learn exact bucket sizes),
                            # so it runs under the watchdog too; the
                            # pipeline's per-partition wait then happens
                            # INSIDE the watchdog deadline (a stalled
                            # prefetch is killed with the attempt).
                            nparts = self._watchdog_run(
                                ctx, wd, "partition-count",
                                lambda: self.num_partitions(ctx))
                            pipe = PL.open_pipeline(ctx, self, nparts)
                            try:
                                for p in range(nparts):
                                    # Same partition-boundary preemption
                                    # checkpoint as the serial loop (the
                                    # watchdog handles cancellation).
                                    faults.check_preempted()
                                    with monitoring.span(
                                            "partition", "device-compute",
                                            args={"partition": p,
                                                  "op": self.name}):
                                        batches.extend(self._watchdog_run(
                                            ctx, wd, f"partition {p}",
                                            lambda p=p: pipe.consume(
                                                p, lambda: list(
                                                    self
                                                    .execute_device_recovering(
                                                        ctx, p)))))
                            finally:
                                pipe.close()
                        with monitoring.span(
                                "download", "download",
                                args={"batches": len(batches)}):
                            host_batches = download_batches(batches, names)
                    finally:
                        set_active_catalog(None)
                        faults.set_recovery_sink(None)
                # Row materialization is pure host CPU — outside the permit,
                # like the reference releasing GpuSemaphore once the task
                # leaves the device.
                with monitoring.span("to-rows", "download"):
                    for hb in host_batches:
                        rows.extend(hb.to_pylist())
            finally:
                collect_span.__exit__(None, None, None)
                # Live telemetry: one counter inc +
                # one histogram observe per collect, plus the spill
                # ladder's tier occupancy and device high watermark —
                # read off the catalog only if this query built one.
                telemetry.inc("srt_collects")
                monitoring.count("collects")
                telemetry.observe(
                    "srt_collect_ms",
                    (time.perf_counter() - t0_collect) * 1e3)
                cat = ctx._catalog
                if cat is not None:
                    # Memory-pressure plane: one scalar score per
                    # collect teardown feeds the admission brownout
                    # state machine and (via the worker heartbeat) the
                    # coordinator's shed-aware placement.
                    score = stores.pressure_score(cat)
                    if telemetry.enabled():
                        telemetry.set_gauge("srt_pressure_score", score)
                    from spark_rapids_tpu.parallel import scheduler as SC
                    SC.note_pressure(score, ctx.conf)
                if cat is not None and telemetry.enabled():
                    telemetry.set_gauge("srt_memory_bytes",
                                        cat.device_bytes, tier="device")
                    telemetry.set_gauge("srt_memory_bytes",
                                        cat.host_bytes, tier="host")
                    telemetry.set_gauge("srt_memory_bytes",
                                        cat.disk_bytes, tier="disk")
                    telemetry.set_gauge("srt_device_budget_bytes",
                                        cat.device_budget)
                    telemetry.max_gauge("srt_device_watermark_bytes",
                                        cat.device_bytes)
            # Cost-model self-calibration: feed this query's observed
            # sync-span mean and upload throughput (plus the Cost@query
            # estimateErrorPct as a trust dampener) back into the
            # placement model's effective constants (plan/cost.py). A
            # no-op when tracing is off or calibration is disabled.
            try:
                from spark_rapids_tpu.plan import cost as COST
                with monitoring.span("calibrate", "query"):
                    COST.observe_query(ctx)
            except Exception:   # calibration must never fail a query
                pass
        else:
            from spark_rapids_tpu import monitoring
            from spark_rapids_tpu.monitoring import telemetry
            monitoring.maybe_configure(ctx.conf)
            telemetry.maybe_configure(ctx.conf)
            with monitoring.span("collect", "query",
                                 level=monitoring.LEVEL_QUERY,
                                 args={"op": self.name,
                                       "engine": "host"}):
                for p in range(self.num_partitions(ctx)):
                    with monitoring.span("partition", "host-compute",
                                         args={"partition": p,
                                               "op": self.name}):
                        for b in self.execute_host(ctx, p):
                            rows.extend(b.to_pylist())
        return rows

    def pretty_tree(self, indent: int = 0) -> str:
        out = "  " * indent + self.name + "\n"
        for c in self.children:
            out += c.pretty_tree(indent + 1)
        return out


class LeafExec(Exec):
    """Base for source nodes (scans, in-memory sources)."""

    def num_partitions(self, ctx: ExecContext) -> int:
        raise NotImplementedError


class InMemorySourceExec(LeafExec):
    """In-memory host-batch source, pre-partitioned (test/bench currency;
    the DataFrame frontend's createDataFrame lands here)."""

    def __init__(self, schema: Schema,
                 partitions: Sequence[Sequence[HostBatch]]):
        super().__init__()
        self._schema = tuple(schema)
        self._partitions = [list(p) for p in partitions]

    @property
    def schema(self) -> Schema:
        return self._schema

    def num_partitions(self, ctx: ExecContext) -> int:
        return len(self._partitions)

    def execute_device(self, ctx, partition):
        for hb in self._partitions[partition]:
            yield host_to_device(hb)

    def execute_host(self, ctx, partition):
        yield from iter(self._partitions[partition])


class DeviceToHostExec(Exec):
    """Explicit device->host transition (GpuColumnarToRowExec analog): runs
    the child on the device engine, downloads each batch."""

    def __init__(self, child: Exec):
        super().__init__(child)

    @property
    def schema(self) -> Schema:
        return self.children[0].schema

    def execute_host(self, ctx, partition):
        names = tuple(n for n, _ in self.schema)
        for b in self.children[0].execute_device(ctx, partition):
            yield device_to_host(b, names)

    def execute_device(self, ctx, partition):  # pragma: no cover
        raise AssertionError("DeviceToHostExec is a host-side node")


class HostToDeviceExec(Exec):
    """Explicit host->device transition (GpuRowToColumnarExec analog)."""

    def __init__(self, child: Exec):
        super().__init__(child)

    @property
    def schema(self) -> Schema:
        return self.children[0].schema

    def execute_device(self, ctx, partition):
        for hb in self.children[0].execute_host(ctx, partition):
            yield host_to_device(hb)

    def execute_host(self, ctx, partition):  # pragma: no cover
        raise AssertionError("HostToDeviceExec is a device-side node")


# Flight-recorder category per timed() metric: operator dispatch is
# device-compute; scan decode/buffer work is host-side; shuffle and
# sizes-pull syncs label themselves.
_TIMED_CATS = {"bufferTime": "host-prefetch", "shuffleTime": "shuffle",
               "sizesPullTime": "sync"}


class _Timer:
    __slots__ = ("_metrics", "_name", "_span", "_t0")

    def __init__(self, metrics: Metrics, name: str):
        self._metrics = metrics
        self._name = name

    def __enter__(self):
        span = None
        if _recorder.enabled():     # no label or args built when off
            owner, name = self._metrics.owner or "op", self._name
            span = _recorder.span(
                owner, _TIMED_CATS.get(name, "device-compute"),
                _recorder.LEVEL_OPERATOR,
                args=None if name == "totalTime" else {"metric": name},
                label=f"{owner}:{name}")
            span.__enter__()
        self._span = span
        self._t0 = time.perf_counter_ns()

    def __exit__(self, *exc):
        self._metrics.add(self._name, time.perf_counter_ns() - self._t0)
        if self._span is not None:
            self._span.__exit__(None, None, None)
        return False


def timed(metrics: Metrics, name: str = "totalTime"):
    """Context manager adding elapsed ns to a metric. The same interval
    is a flight-recorder span (monitoring/recorder.py) and so, with the
    recorder on, a profiler annotation ``<Op>:<metric>``: a captured
    profile (jax.profiler.trace) shows every operator's dispatch ranges
    (NvtxWithMetrics.scala:21-44 analog). With the recorder off it
    annotates nothing."""
    return _Timer(metrics, name)
