"""OOM -> tiered recovery at dispatch boundaries
(DeviceMemoryEventHandler.scala:42-69 re-imagined for XLA).

The reference installs a cuDF alloc-failure callback that spills the
RapidsBufferCatalog and lets RMM retry the SAME allocation. XLA exposes no
allocator hook, so the equivalent lives at the dispatch sites instead:
the funnels that issue large device allocations (uploads, concats/shrinks,
cached-kernel dispatches, downloads) run through :func:`retry_on_oom`.

Recovery is a bounded ESCALATION LADDER, not a single retry — each rung
frees (or will free) more memory than the last, and the dispatch retries
after every rung:

1. ``spill-some``: spill lowest-priority catalog buffers until about half
   the registered device bytes are freed (the cheap rung — most OOMs are
   transient headroom misses).
2. ``spill-all``: spill EVERY spillable device buffer (the reference's
   alloc-failure callback behavior).
3. ``evict-neighbors``: under the multi-query scheduler
   (parallel/scheduler.py) with concurrent queries in flight, spill the
   OTHER active queries' device buffers to host — the offending query's
   own buffers always go first (rungs 1-2), so isolation holds until
   the device is truly full (``crossQueryEvictions``).
4. ``shrink``: halve the process-wide degraded batch target
   (:func:`effective_batch_target`) so every SUBSEQUENT coalesce/serve
   dispatch issues smaller batches, then retry once more.

If the ladder is exhausted the dispatch raises :class:`OomRetryExhausted`
— whose message deliberately does NOT carry the OOM markers, so nested
``retry_on_oom`` frames propagate it instead of re-running the ladder.
The operator layer (ops/base.py ``execute_device_recovering``) catches it
and demotes in two further rungs: first the operator's ON-DEVICE degraded
mode (``Exec._grace_retry`` — the hash join's spill-partitioned grace
path, ops/join.py, which keeps a build side far past the device budget on
the accelerator), and only then host-engine degradation of the operator
subtree — mirroring the reference's always-available CPU fallback, but
one rung later than it.

The wrapped operations are pure batch->batch (no consumed iterator
state), so every retry is safe. The active catalog is registered
per-collect (ops/base.py) — dispatch sites deep in the kernel layer never
thread an ExecContext through. Every rung records through
spark_rapids_tpu.faults' recovery counters (``retriesAttempted``,
``spillEscalations``...), which is also how tests/test_chaos.py proves
the ladder actually fires.
"""

from __future__ import annotations

import logging
import random
import threading
from typing import Callable, List, Optional, TypeVar

from spark_rapids_tpu import faults

_LOG = logging.getLogger("spark_rapids_tpu.memory")

T = TypeVar("T")

_local = threading.local()


def set_active_catalog(catalog) -> None:
    _local.catalog = catalog


def get_active_catalog():
    return getattr(_local, "catalog", None)


class OomRetryExhausted(RuntimeError):
    """Device OOM persisted through the whole escalation ladder. The
    message carries NO OOM marker on purpose: an enclosing retry_on_oom
    must propagate this (its own ladder would just repeat the failed
    rungs), and the operator layer host-degrades on it instead."""

    def __init__(self, original: BaseException, rungs: List[str]):
        super().__init__(
            f"device memory exhausted after escalation ladder "
            f"{rungs!r}; original: {type(original).__name__}")
        self.original = original
        self.rungs = rungs


# What a backend says when an allocation fails WHILE a program or a
# transfer runs. First two: the TPU runtime, as the attached v5e printed
# them (PR 21: "RESOURCE_EXHAUSTED: Error allocating device buffer:
# Attempting to allocate 3.00G. That was not possible. There are 693.97M
# free.; (0x0x0_HBM0)"; loading a program whose scratch does not fit what
# is free says "Attempting to reserve"). The rest: XLA's BFC allocator on
# other backends. The compiler's refusals carry RESOURCE_EXHAUSTED too
# ("Allocation (size=..) would exceed memory (size=..) :: .. space=hbm" /
# "space=vmem", "Ran out of memory in memory space ..") and are NOT here:
# no amount of spilling makes a program that does not fit compile, and
# retrying it down to the host engine would hide that the device path is
# broken.
_RUNTIME_OOM_MARKERS = (
    "Attempting to allocate", "Attempting to reserve",
    "Out of memory while trying to allocate", "Out of memory allocating",
    "Failed to allocate")


def is_oom_error(e: BaseException) -> bool:
    """A run-time device allocation failure (or its injected stand-in),
    and nothing else: a compile-time or lowering error propagates."""
    if isinstance(e, OomRetryExhausted):
        return False
    if isinstance(e, faults.InjectedOomError):
        return True
    # Deliberately narrow: a spurious match triggers a full
    # spill-everything pass plus a duplicate dispatch of the failing op.
    s = str(e)
    return any(m in s for m in _RUNTIME_OOM_MARKERS)


# -- degraded batch target (rung 3) -----------------------------------------

_MAX_DEGRADE_FACTOR = 8
_MIN_TARGET_ROWS = 1 << 12
_degrade_lock = threading.Lock()
_degrade_factor = 1

RUNG_SPILL_SOME = "spill-some"
RUNG_SPILL_ALL = "spill-all"
RUNG_EVICT_NEIGHBORS = "evict-neighbors"
RUNG_SHRINK = "shrink"

# Rung names of the LAST completed ladder, in firing order (introspection
# for tests proving the escalation discipline).
last_ladder: List[str] = []


def degrade_factor() -> int:
    return _degrade_factor


def effective_batch_target(target_rows: int) -> int:
    """The batchSizeRows target after OOM degradation: once the shrink
    rung has fired, every consumer that coalesces toward the target
    (aggregate input coalescing, exchange reduce-side serving) dispatches
    proportionally smaller batches until :func:`reset_degradation`."""
    return max(int(target_rows) // _degrade_factor, _MIN_TARGET_ROWS)


def shrink_batch_target() -> bool:
    """Halve the process-wide batch target (bounded). True if the factor
    actually moved."""
    global _degrade_factor
    with _degrade_lock:
        if _degrade_factor >= _MAX_DEGRADE_FACTOR:
            return False
        _degrade_factor *= 2
        _LOG.warning("OOM escalation: batch target degraded to 1/%d",
                     _degrade_factor)
        return True


def reset_degradation() -> None:
    global _degrade_factor
    with _degrade_lock:
        _degrade_factor = 1


# -- the ladder ---------------------------------------------------------------

def _evict_neighbor_queries() -> int:
    """Cross-query eviction rung: after the offending query has spilled
    everything IT owns (the first two rungs walk its own catalog), ask
    the QueryManager to spill the other active queries' buffers to host
    — isolation means the offender pays first, not that neighbors are
    untouchable while the device is truly full. No-op (0 bytes) outside
    a managed query or with no concurrent neighbors."""
    tok = faults.get_query_token()
    if tok is None:
        return 0
    from spark_rapids_tpu.parallel import scheduler
    mgr = scheduler.get_query_manager()
    return mgr.evict_neighbors(tok.query_id)


def retry_on_oom(fn: Callable[..., T], *args, **kwargs) -> T:
    """Run ``fn``; on a device OOM walk the spill-some -> spill-all ->
    evict-neighbors -> shrink escalation ladder, retrying the dispatch
    after each rung (neighbor eviction only under the QueryManager with
    concurrent queries — the offender's own buffers always go first).
    Anything else propagates; a ladder that never frees or changes
    anything re-raises immediately (the retry would just fail again)."""
    try:
        return fn(*args, **kwargs)
    except Exception as e:                  # jaxlib.XlaRuntimeError etc.
        if not is_oom_error(e):
            raise
        first = e
    catalog = get_active_catalog()
    rungs: List[str] = []
    last = first

    def attempt():
        faults.record("retriesAttempted")
        return fn(*args, **kwargs)

    for rung in (RUNG_SPILL_SOME, RUNG_SPILL_ALL, RUNG_EVICT_NEIGHBORS,
                 RUNG_SHRINK):
        if rung == RUNG_SPILL_SOME:
            acted = catalog is not None and catalog.spill_some() > 0
        elif rung == RUNG_SPILL_ALL:
            acted = catalog is not None and catalog.handle_oom() > 0
        elif rung == RUNG_EVICT_NEIGHBORS:
            acted = _evict_neighbor_queries() > 0
        else:
            acted = shrink_batch_target()
        if not acted:
            # Nothing changed at this rung; the identical dispatch would
            # fail the same way — escalate without burning a retry.
            continue
        rungs.append(rung)
        last_ladder[:] = rungs
        faults.record("spillEscalations")
        from spark_rapids_tpu import monitoring
        monitoring.instant("oom-rung", "recovery", args={"rung": rung})
        _LOG.warning("device OOM: escalation rung %r (of %r), retrying "
                     "dispatch: %s", rung, rungs, last)
        try:
            return attempt()
        except Exception as e2:
            if not is_oom_error(e2):
                raise
            last = e2
    last_ladder[:] = rungs
    if not rungs:
        # No catalog / nothing spillable / already fully degraded:
        # preserve the original error verbatim (historical contract).
        raise last
    raise OomRetryExhausted(last, rungs)


# -- transient failures -------------------------------------------------------

def is_transient_error(e: BaseException) -> bool:
    """Backend failures worth retrying the whole query (SURVEY
    §5.3 failure detection: the reference leans on Spark task retry; this
    engine owns the retry itself — with exponential backoff and a
    per-query budget, plan/planner.py). Deliberately narrow —
    deterministic errors must not run twice."""
    s = f"{type(e).__name__}: {e}"
    return any(marker in s for marker in (
        "UNAVAILABLE", "DEADLINE_EXCEEDED", "connection reset",
        "Connection reset", "Socket closed", "ABORTED",
        "failed to connect", "stream terminated"))


def backoff_delay_ms(attempt: int, base_ms: int, max_ms: int,
                     seed: int = 0) -> float:
    """Exponential backoff with DETERMINISTIC jitter: attempt ``i``
    sleeps ``min(base * 2^i, max) * U(0.5, 1.0)`` where U comes from a
    PRNG seeded by (seed, attempt) — reproducible chaos runs stay
    reproducible down to their sleep schedule."""
    d = min(float(base_ms) * (2 ** int(attempt)), float(max_ms))
    jitter = random.Random(f"{seed}:backoff:{attempt}").uniform(0.5, 1.0)
    return d * jitter
