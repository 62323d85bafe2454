"""Priority classes + weighted fair queueing with a hard starvation
bound (ISSUE 14 tentpole part a/b).

Three fixed priority classes — ``interactive`` > ``batch`` >
``background`` — drain through :class:`WfqQueue`, a stride scheduler:
every class carries a virtual time that advances by ``1/weight`` each
time it is served, and the next run slot goes to the non-empty class
with the SMALLEST virtual time (ties break by class rank). Service is
therefore proportional to the weight vector over any window, and the
drain order is a pure function of the arrival schedule — no clocks, no
randomness — which is what the determinism tests pin.

On top of the stride ordering sits a HARD starvation bound: every time
a non-empty class is passed over for a dispatch its bypass counter
ticks; once any class has been bypassed ``starvation_bound`` times in a
row its head runs NEXT regardless of virtual time (the engagement is
counted). With weights like 100:1:1 the stride
schedule alone would make background wait ~100 grants between services;
the bound caps that wait absolutely.

Within a class, entries drain shortest-job-first by the plan/cost.py
estimate (``CostReport.est_device_ms + est_host_ms``; plan-cache hits
reuse the template's report so the lookup is free for repeat shapes).
Un-priced queries (cost model off or skipped) order after every priced
one, FIFO among themselves — the class-level starvation bound still
guarantees the class progresses.

Pure data structure: no locks (the QueryManager's lock covers it), no
engine imports.
"""

from __future__ import annotations

import heapq
from typing import Dict, List, Optional, Tuple

CLASSES: Tuple[str, ...] = ("interactive", "batch", "background")
CLASS_RANK: Dict[str, int] = {name: i for i, name in enumerate(CLASSES)}
DEFAULT_CLASS = "batch"
_UNPRICED = float("inf")


def resolve_class(name: Optional[str]) -> str:
    """Normalize a priority-class spec (submit kwarg or conf value) to
    one of :data:`CLASSES`; empty/None falls back to ``batch``."""
    if not name:
        return DEFAULT_CLASS
    v = str(name).strip().lower()
    if v not in CLASS_RANK:
        raise ValueError(
            f"unknown priority class {name!r} (expected one of {CLASSES})")
    return v


def parse_weights(spec: str) -> Dict[str, float]:
    """``"8,3,1"`` -> ``{interactive: 8, batch: 3, background: 1}``.
    Weights must be positive (a zero weight is a starvation machine the
    bound would have to carry alone)."""
    parts = [p.strip() for p in str(spec).split(",")]
    if len(parts) != len(CLASSES):
        raise ValueError(
            f"scheduler.qos.weights expects {len(CLASSES)} comma-separated "
            f"values (one per class {CLASSES}), got {spec!r}")
    out = {}
    for name, p in zip(CLASSES, parts):
        w = float(p)
        if w <= 0:
            raise ValueError(
                f"scheduler.qos.weights: weight for {name!r} must be > 0, "
                f"got {w}")
        out[name] = w
    return out


class QueueEntry:
    """One waiting query: its class, SJF cost key, arrival sequence, and
    the wake event the granted slot sets. ``granted``/``cancelled`` make
    removal race-free under the manager lock (lazy deletion: a cancelled
    entry is skipped at pop time)."""

    __slots__ = ("qos_class", "cost_ms", "seq", "event", "tenant",
                 "granted", "cancelled")

    def __init__(self, qos_class: str, cost_ms: Optional[float], seq: int,
                 event, tenant: Optional[str] = None):
        self.qos_class = qos_class
        self.cost_ms = float(cost_ms) if cost_ms is not None else _UNPRICED
        self.seq = seq
        self.event = event
        self.tenant = tenant
        self.granted = False
        self.cancelled = False

    def sort_key(self) -> Tuple[float, int]:
        return (self.cost_ms, self.seq)


class _ClassQueue:
    __slots__ = ("heap", "vtime", "bypass", "live")

    def __init__(self):
        self.heap: List[Tuple[Tuple[float, int], QueueEntry]] = []
        self.vtime = 0.0
        self.bypass = 0
        self.live = 0           # non-cancelled entries in the heap

    def push(self, entry: QueueEntry) -> None:
        heapq.heappush(self.heap, (entry.sort_key(), entry))
        self.live += 1

    def pop(self) -> Optional[QueueEntry]:
        while self.heap:
            _, e = heapq.heappop(self.heap)
            if e.cancelled:
                continue
            self.live -= 1
            return e
        return None


class WfqQueue:
    """The QoS run queue: one SJF heap per class, drained by stride
    scheduling with a hard starvation bound."""

    def __init__(self, weights: Dict[str, float], starvation_bound: int):
        self.weights = dict(weights)
        self.starvation_bound = max(int(starvation_bound), 1)
        self._classes = {name: _ClassQueue() for name in CLASSES}
        self._seq = 0
        self._global_vtime = 0.0

    def __len__(self) -> int:
        return sum(c.live for c in self._classes.values())

    def depth(self, qos_class: Optional[str] = None) -> int:
        if qos_class is None:
            return len(self)
        return self._classes[qos_class].live

    def push(self, qos_class: str, cost_ms: Optional[float], event,
             tenant: Optional[str] = None) -> QueueEntry:
        cq = self._classes[qos_class]
        if cq.live == 0:
            # Re-activation: a long-idle class joins at the CURRENT
            # virtual time instead of cashing in unbounded credit for
            # the time it had nothing to run (classic stride re-entry).
            cq.vtime = max(cq.vtime, self._global_vtime)
        self._seq += 1
        entry = QueueEntry(qos_class, cost_ms, self._seq, event, tenant)
        cq.push(entry)
        return entry

    def discard(self, entry: QueueEntry) -> None:
        """Remove a waiter that timed out / cancelled while queued.
        Lazy: the heap drops it at pop time; counts adjust now."""
        if not entry.cancelled and not entry.granted:
            entry.cancelled = True
            self._classes[entry.qos_class].live -= 1

    def pop_next(self) -> Tuple[Optional[QueueEntry], bool]:
        """The next query to grant a run slot: ``(entry, starved)``.
        ``starved`` is True when the hard starvation bound — not the
        stride order — picked the class (the engagement counter the
        soak asserts on). ``(None, False)`` when nothing is queued."""
        nonempty = [(name, cq) for name, cq in self._classes.items()
                    if cq.live > 0]
        if not nonempty:
            return None, False
        starved = [(name, cq) for name, cq in nonempty
                   if cq.bypass >= self.starvation_bound]
        engaged = False
        if starved:
            # Hard bound: the longest-bypassed class runs NEXT. Ties
            # break by bypass count then class rank.
            name, cq = max(
                starved,
                key=lambda nc: (nc[1].bypass, -CLASS_RANK[nc[0]]))
            engaged = True
        else:
            name, cq = min(
                nonempty,
                key=lambda nc: (nc[1].vtime, CLASS_RANK[nc[0]]))
        entry = cq.pop()
        assert entry is not None
        entry.granted = True
        # System virtual time = the vtime at which service happened;
        # classes re-activating later join here (no credit hoarding).
        self._global_vtime = max(self._global_vtime, cq.vtime)
        cq.vtime += 1.0 / self.weights[name]
        cq.bypass = 0
        for other, ocq in self._classes.items():
            if other != name and ocq.live > 0:
                ocq.bypass += 1
        return entry, engaged
