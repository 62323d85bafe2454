"""Pipelined partition executor: overlap host decode/encode with device
dispatch (ISSUE 4).

The reference wins much of its throughput from *overlap*, not kernels:
the MULTITHREADED reader (GpuParquetScan.scala:1144) decodes files on a
host thread pool while the device consumes earlier batches, and
GpuSemaphore admits a bounded number of tasks so the device stays
saturated without oversubscribing HBM. This engine's partition loops
(``Exec.collect``, exchange map-side materialization, broadcast collect)
used to run strictly serially: host Arrow decode, filter-stat pruning,
wire encode, ``device_put`` and TPU compute never overlapped.

Two cooperating pieces fix that:

1. **Partition pipeline** (:func:`open_pipeline`): a bounded host thread
   pool runs the *separable host half* of each partition — everything an
   ``Exec.prefetch_host`` hook can do before ``device_put`` (scan-unit
   decode, stats pruning, wire encode; columnar/wire.py documents the
   encode half as thread-safe CPU-only work) — ``prefetchPartitions``
   ahead of a single ordered consumer that performs all device dispatch.
   Results therefore stay deterministically ordered, upload of partition
   p+1 overlaps compute of p, and faults raised on prefetch threads are
   captured and re-raised at the ordered consumption point, so the OOM
   ladder / stage recompute / transient retry demotion order (PR 2-3) is
   unchanged. Watchdog deadlines wrap the consumer's per-partition wait:
   ``_take`` polls the attempt's cancel event, and a killed attempt
   cancels its partition's prefetch so injected stalls unwind instead of
   lingering.

2. **Concurrent independent stages** (:func:`prematerialize_stages`):
   PR 3's stage DAG (parallel/stages.py) names the plan's exchange
   boundaries; stages whose parents are all materialized are independent,
   so e.g. the build- and probe-side scans of a join materialize their
   exchange outputs in parallel (bounded by
   ``pipeline.maxConcurrentStages``; device dispatch stays inside the
   query's TPU-semaphore permit). Waves run bottom-up with a barrier per
   wave, and a wave's first error (smallest stage id — deterministic) is
   re-raised to the planner ladder exactly as the serial pull would have
   raised it.

``spark.rapids.sql.pipeline.enabled=false`` or ``SRT_PIPELINE=0``
restores today's serial dispatch byte-for-byte: :func:`open_pipeline`
then returns the no-op serial pipeline and no thread is ever created.

Counters (process-global here + the per-query ``Pipeline@query`` metrics
entry, surfaced by ``DataFrame.metrics()`` and :func:`counters`):
``hostPrefetchMs``, ``consumerWaitMs``, ``pipelineStalls``,
``prefetchedPartitions``, ``concurrentStages`` and the derived
``overlapRatio`` (fraction of host-prefetch time the consumer did NOT
wait for — 0 means the pipeline degenerated to serial, 1 means decode
was entirely hidden behind device work).
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import logging
import os
import threading
import time
from typing import Dict, List, Optional

_LOG = logging.getLogger("spark_rapids_tpu.pipeline")

_COUNTER_LOCK = threading.Lock()
_COUNTERS: Dict[str, float] = {}


def _record(ctx, name: str, amount: float) -> None:
    with _COUNTER_LOCK:
        _COUNTERS[name] = _COUNTERS.get(name, 0) + amount
    if ctx is not None:
        metrics_entry(ctx).add(name, amount)


def record(ctx, name: str, amount: float) -> None:
    """Public counter hook for prefetch-side producers (the scan's
    wire-encode + staging-buffer pack, io/scan.py): counts land in both
    the process-global pipeline counters and the per-query
    ``Pipeline@query`` metrics entry. With the ingest fast path the
    prefetch pool stages fully-packed upload buffers
    (``stagingBytesPrefetched``), so the ordered consumer's only work
    per partition is device_put transfers + jitted decode dispatches."""
    _record(ctx, name, amount)


def counters() -> Dict[str, float]:
    """Process-global pipeline counters, with the derived overlapRatio
    folded in."""
    with _COUNTER_LOCK:
        out = dict(_COUNTERS)
    return _with_overlap_ratio(out)


def reset_counters() -> None:
    with _COUNTER_LOCK:
        _COUNTERS.clear()


def _with_overlap_ratio(vals: Dict[str, float]) -> Dict[str, float]:
    prefetch = vals.get("hostPrefetchMs", 0.0)
    if prefetch > 0:
        waited = min(vals.get("consumerWaitMs", 0.0), prefetch)
        vals["overlapRatio"] = round(1.0 - waited / prefetch, 4)
    return vals


def metrics_entry(ctx):
    """The per-query Pipeline metrics entry (next to Recovery@query;
    registered level-filter exempt through the ops/base.py audit
    registry)."""
    from spark_rapids_tpu.ops.base import query_metrics_entry
    return query_metrics_entry(ctx, "Pipeline")


def finalize_metrics(ctx) -> None:
    """Recompute the query-scoped overlapRatio from the entry's
    cumulative ms counters (a ratio cannot accumulate additively across
    the query's pipelines)."""
    m = ctx.metrics.get("Pipeline@query")
    if m is not None:
        with m._lock:
            _with_overlap_ratio(m.values)


@dataclasses.dataclass(frozen=True)
class PipelineParams:
    prefetch_partitions: int
    host_threads: int
    max_concurrent_stages: int


def params_of(conf) -> Optional[PipelineParams]:
    """Resolved pipeline parameters, or None when the pipeline is off
    (conf or the SRT_PIPELINE=0 escape hatch — the serial path then runs
    exactly as before)."""
    from spark_rapids_tpu import config as C
    if os.environ.get("SRT_PIPELINE", "").strip() == "0":
        return None
    if not bool(conf.get(C.PIPELINE_ENABLED)):
        return None
    return PipelineParams(
        prefetch_partitions=max(
            int(conf.get(C.PIPELINE_PREFETCH_PARTITIONS)), 1),
        host_threads=max(int(conf.get(C.PIPELINE_HOST_THREADS)), 1),
        max_concurrent_stages=max(
            int(conf.get(C.PIPELINE_MAX_CONCURRENT_STAGES)), 1))


# ---------------------------------------------------------------------------
# Partition pipeline
# ---------------------------------------------------------------------------

class _ConsumeCancelled(RuntimeError):
    """The watchdog killed the consuming attempt while it waited on a
    prefetch; the abandoned attempt thread unwinds on this (the watchdog
    already discarded the attempt, so nobody observes it)."""


class _Slot:
    __slots__ = ("future", "cancel", "consumed")

    def __init__(self, future, cancel):
        self.future = future
        self.cancel = cancel
        self.consumed = False


class _SerialPipeline:
    """The disabled pipeline: ``consume`` runs the partition inline with
    zero threads, zero buffering, zero counters — today's serial path."""

    def consume(self, partition: int, fn):
        return fn()

    def close(self):
        pass


class PartitionPipeline:
    """Bounded producer/consumer over one partition loop.

    Producers run ``source.prefetch_host(ctx, p)`` for partitions up to
    ``prefetch_partitions`` ahead of the consumer; the consumer calls
    :meth:`consume` in strict partition order from ONE thread (the
    calling/watchdog thread), so device dispatch order — and therefore
    result order — is identical to the serial path."""

    def __init__(self, ctx, source, nparts: int, params: PipelineParams):
        from spark_rapids_tpu import faults
        self._ctx = ctx
        self._source = source
        self._nparts = nparts
        self._depth = params.prefetch_partitions
        self._sink = faults.get_recovery_sink()
        self._token = faults.get_query_token()
        self._pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=min(params.host_threads, max(nparts, 1)),
            thread_name_prefix="srt-prefetch")
        self._slots: Dict[int, _Slot] = {}
        self._submitted = -1
        self._closed = False
        # Whether a consume finds its prefetch unfinished is the host's
        # load's to say: publish the count, 0 included, so that the
        # query's Pipeline entry has one shape on every run.
        _record(ctx, "pipelineStalls", 0)

    # -- producers -----------------------------------------------------------
    def _prefetch_task(self, partition: int, cancel,
                       parent_span: int) -> None:
        from spark_rapids_tpu import faults, monitoring
        faults.set_recovery_sink(self._sink)
        faults.set_query_token(self._token)
        faults.set_cancel_event(cancel)
        monitoring.adopt(parent_span)
        t0 = time.perf_counter()
        try:
            if not cancel.is_set():
                with monitoring.span("prefetch", "host-prefetch",
                                     args={"partition": partition}):
                    self._source.prefetch_host(self._ctx, partition)
        finally:
            monitoring.adopt(0)
            faults.set_cancel_event(None)
            faults.set_query_token(None)
            faults.set_recovery_sink(None)
            _record(self._ctx, "hostPrefetchMs",
                    (time.perf_counter() - t0) * 1000.0)
            _record(self._ctx, "prefetchedPartitions", 1)

    def _ensure_submitted(self, upto: int) -> None:
        from spark_rapids_tpu import monitoring
        upto = min(upto, self._nparts - 1)
        # The prefetch's cause: the consumer's span open now (the
        # partition that asks for it, ``prefetchPartitions`` ahead).
        parent_span = monitoring.current()
        while self._submitted < upto:
            self._submitted += 1
            p = self._submitted
            cancel = threading.Event()
            fut = self._pool.submit(self._prefetch_task, p, cancel,
                                    parent_span)
            self._slots[p] = _Slot(fut, cancel)

    # -- the ordered consumer ------------------------------------------------
    def _take(self, partition: int) -> None:
        """Block (cancellably) until partition's host half is done;
        re-raise any prefetch-thread fault HERE — the ordered consumption
        point — so recovery sees it exactly where the serial path would
        have raised it."""
        from spark_rapids_tpu import faults
        self._ensure_submitted(partition + self._depth)
        slot = self._slots.get(partition)
        if slot is None or slot.consumed:
            return                      # re-dispatch after a kill: inline
        slot.consumed = True
        fut = slot.future
        wait_span = None
        if not fut.done():
            _record(self._ctx, "pipelineStalls", 1)
            # The ordered consumer actually blocked on this partition's
            # host half: that wait is queue time, on the trace timeline.
            from spark_rapids_tpu import monitoring
            wait_span = monitoring.span("pipeline-wait", "queued",
                                        args={"partition": partition})
            wait_span.__enter__()
        t0 = time.perf_counter()
        try:
            while True:
                try:
                    fut.result(timeout=0.05)
                    return
                except concurrent.futures.TimeoutError:
                    if fut.done():
                        # Either the TASK raised TimeoutError, or it
                        # ended just after the poll gave up: asking
                        # again returns, or raises the task's own error,
                        # never the poll's.
                        fut.result()
                        return
                    # Query cancel/deadline: stop waiting, cancel the
                    # prefetch, and unwind at this ordered point — the
                    # same place a prefetch fault would have surfaced.
                    tok = faults.get_query_token()
                    if tok is not None and tok.cancelled():
                        slot.cancel.set()
                        raise tok.error()
                    wd_cancel = faults.get_cancel_event()
                    if wd_cancel is not None and wd_cancel.is_set():
                        # Watchdog killed this attempt: cancel the
                        # partition's prefetch (unwinds injected stalls)
                        # and unwind the abandoned attempt thread.
                        slot.cancel.set()
                        raise _ConsumeCancelled(
                            f"partition {partition} consume cancelled")
        except (_ConsumeCancelled, faults.QueryCancelledError):
            raise
        except BaseException:
            if slot.cancel.is_set():
                # The error is the prefetch unwinding on OUR cancel (a
                # killed stall): the re-dispatched attempt recomputes
                # inline, matching the serial watchdog-retry semantics.
                return
            raise
        finally:
            if wait_span is not None:
                wait_span.__exit__(None, None, None)
            waited = (time.perf_counter() - t0) * 1000.0
            if waited > 0:
                _record(self._ctx, "consumerWaitMs", waited)

    def consume(self, partition: int, fn):
        """Wait for partition's prefetch (if any), then run ``fn`` — the
        device half — on the calling thread."""
        self._take(partition)
        return fn()

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        for slot in self._slots.values():
            slot.cancel.set()
        self._pool.shutdown(wait=False, cancel_futures=True)
        # Drop unconsumed prefetch buffers (a failed/cancelled collect
        # must not leave encoded partitions pinned in the context).
        stale = {str(p) for p, s in self._slots.items()
                 if not s.consumed and s.future.done()}
        if stale:
            for key in [k for k in list(self._ctx.cache)
                        if isinstance(k, str)
                        and k.startswith("scan-prefetch:")
                        and k.rsplit(":", 1)[-1] in stale]:
                self._ctx.cache.pop(key, None)
        finalize_metrics(self._ctx)


def open_pipeline(ctx, source, nparts: int):
    """A :class:`PartitionPipeline` for this partition loop, or the
    serial no-op when the pipeline is disabled, the loop is trivial
    (``nparts <= 1`` gives nothing to overlap), or the subtree exposes no
    separable host half."""
    params = params_of(ctx.conf)
    if params is None or nparts <= 1 or not source.host_prefetchable():
        return _SerialPipeline()
    return PartitionPipeline(ctx, source, nparts, params)


# ---------------------------------------------------------------------------
# Concurrent independent stages
# ---------------------------------------------------------------------------

def prematerialize_stages(ctx, root) -> None:
    """Materialize independent stages' exchange outputs concurrently.

    Stages run in bottom-up waves: a stage is ready when every parent
    (upstream) stage's output is materialized. Waves of one run inline
    (zero overhead — the lazy pull would do the same work); larger waves
    fan out on threads bounded by ``pipeline.maxConcurrentStages``.
    Every materialization is idempotent against the context cache, so a
    ladder-recovered re-collect re-runs only what was invalidated."""
    params = params_of(ctx.conf)
    if params is None or params.max_concurrent_stages <= 1:
        return
    from spark_rapids_tpu import faults
    from spark_rapids_tpu.memory.oom import (get_active_catalog,
                                             set_active_catalog)
    from spark_rapids_tpu.ops.base import _watchdog_params
    from spark_rapids_tpu.parallel import stages as S
    graph = S.build_stage_graph(root)
    runnable = {st.stage_id: st for st in graph.stages.values()
                if st.boundary is not None
                and callable(getattr(st.boundary, "stage_prematerialize",
                                     None))}
    if len(runnable) < 2:
        return
    from spark_rapids_tpu import monitoring
    wd = _watchdog_params(ctx.conf)
    catalog = get_active_catalog()
    sink = faults.get_recovery_sink()
    token = faults.get_query_token()
    parent_span = monitoring.current()

    def run_stage(st):

        def materialize():
            st.boundary.stage_prematerialize(ctx)
        with monitoring.span(st.name, "stage",
                             level=monitoring.LEVEL_QUERY):
            if wd is None:
                materialize()
            else:
                st.boundary._watchdog_run(ctx, wd, st.name,
                                          materialize)

    def run_stage_threaded(st):
        set_active_catalog(catalog)
        faults.set_recovery_sink(sink)
        faults.set_query_token(token)
        monitoring.adopt(parent_span)
        try:
            run_stage(st)
        finally:
            monitoring.adopt(0)
            faults.set_query_token(None)
            faults.set_recovery_sink(None)

    done: set = set()
    pending = dict(runnable)
    while pending:
        # Ready = every parent stage's output already materialized. A
        # stage with a non-prematerializable parent (e.g. a mesh
        # exchange) never becomes ready and materializes lazily in the
        # consumer instead — running it here could double-materialize
        # the shared lazy parent from two threads.
        wave = sorted((st for st in pending.values()
                       if all(pid in done for pid in st.parents)),
                      key=lambda st: st.stage_id)
        if not wave:
            break
        for st in wave:
            pending.pop(st.stage_id)
        if len(wave) == 1:
            run_stage(wave[0])
        else:
            _record(ctx, "concurrentStages", len(wave))
            errors: Dict[int, BaseException] = {}
            nworkers = min(params.max_concurrent_stages, len(wave))
            with concurrent.futures.ThreadPoolExecutor(
                    max_workers=nworkers,
                    thread_name_prefix="srt-stage") as pool:
                futs = {st.stage_id: pool.submit(run_stage_threaded, st)
                        for st in wave}
                for sid, fut in futs.items():
                    try:
                        fut.result()
                    except BaseException as e:
                        errors[sid] = e
            if errors:
                # Deterministic choice: the smallest stage id is the one
                # the serial pull order would have hit first.
                raise errors[min(errors)]
        done.update(st.stage_id for st in wave)
