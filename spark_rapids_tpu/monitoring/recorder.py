"""Query flight recorder: structured trace spans + instant events.

The reference wraps every GPU operator in an NVTX range
(NvtxWithMetrics.scala:21-44) so an Nsight capture shows exactly where a
query's time went. This engine's analog must work WITHOUT an external
profiler attached — much of the interesting
time is host-side orchestration (scheduler queue, host prefetch, wire
pack, upload, device dispatch, shuffle spool, recovery rework) — so the
recorder lives in-process: a bounded per-query ring buffer of

- **spans** — named intervals with a category, monotonic start/duration
  (``time.perf_counter_ns``), the recording thread, the owning query
  id (the scheduler admission ordinal, resolved from the thread's
  ``faults.QueryToken``), a process-unique span id and the id of the
  span that caused it (its parent: the innermost span open on the
  thread, or the one a submitting thread handed over with
  :func:`current` / :func:`adopt`); and
- **instants** — point events for the things that are *decisions*, not
  durations: fault injected, OOM rung taken, stage recompute, join
  demotion, watchdog kill, cancellation, cross-query eviction.

One span, two sinks: every enabled span is also a
``jax.profiler.TraceAnnotation`` (``<category>:<name>``, or the label a
site gives), so a captured device profile shows the recorder's spans on
the profiler's own clock and an idle gap of the device can be put down
to the span the host was in. While enabled the recorder also listens to
jax's backend-compile events (``compile`` spans) and to ``gc.callbacks``
(``runtime`` spans); disabled, nothing is annotated and no listener is
registered.

Always cheap enough to leave on: the DISABLED path of :func:`span` /
:func:`instant` is one module-global load + a truthiness test returning
a shared no-op (no allocation, no lock, no clock read) — the tier-1
suite runs bit-identical with tracing off. Enabled, every ring is a
``collections.deque(maxlen=trace.maxEvents)``, so a runaway query can
never hold more than a bounded window of its own history (the flight
recorder discipline: you keep the tail, not the flight).

Config (process-global, last collect's conf wins — the same regime as
the wire codec): ``spark.rapids.sql.trace.enabled`` (``SRT_TRACE`` env
override), ``spark.rapids.sql.trace.maxEvents``,
``spark.rapids.sql.trace.level`` (``query`` < ``operator`` <
``kernel``).

Consumers: ``DataFrame.trace_export`` renders Chrome trace-event JSON
(chrome.py — loads in Perfetto / chrome://tracing, one track per query
and per worker thread), ``DataFrame.explain_analyze`` joins the span
stream with per-operator metrics and the cost model's estimates
(analyze.py), :func:`self_times` gives each category's self time (a
span's duration less what its children on the same thread cover), and
:func:`snapshot` aggregates the span-category time breakdown.

Deliberately imports nothing beyond stdlib at module level: faults.py
(itself stdlib-only) emits instants from injection sites; faults (for
the query id) and jax are imported when the recorder is first enabled.
"""

from __future__ import annotations

import collections
import gc
import itertools
import json
import os
import threading
import time
from typing import Dict, List, Optional, Tuple

# Verbosity levels: a span/instant records only when its level is at or
# below the configured one.
LEVEL_QUERY = 1      # query/stage lifecycle + every instant event
LEVEL_OPERATOR = 2   # + per-partition, per-operator, upload, shuffle
LEVEL_KERNEL = 3     # + per-batch wire encode/pack, sync attribution

_LEVEL_NAMES = {"query": LEVEL_QUERY, "operator": LEVEL_OPERATOR,
                "kernel": LEVEL_KERNEL}

# -- process-global state -----------------------------------------------------

# THE fast-path gate: the disabled span()/instant() path reads this one
# global and returns. Everything else hides behind it.
_ENABLED = False
_LEVEL = LEVEL_OPERATOR
_MAX_EVENTS = 65536
_MAX_QUERIES = 64           # oldest query rings evicted past this

_LOCK = threading.Lock()
# query id -> deque of event tuples, insertion-ordered so the oldest
# query is evicted first. Event tuples (kept flat for append cost):
#   ("X", name, cat, ts_ns, dur_ns, tid, qid, args_or_None, sid, parent)
#   ("i", name, cat, ts_ns, None,   tid, qid, args_or_None, 0,   parent)
# sid is the span's id, parent the sid of the span that caused it (0: a
# root). New fields go at the END: readers index or star-unpack.
_RINGS: "collections.OrderedDict[int, collections.deque]" = \
    collections.OrderedDict()
_THREAD_NAMES: Dict[int, str] = {}
_DROPPED: Dict[int, int] = {}       # per-query ring overflow count
_OPEN = itertools.count()           # spans entered
_CLOSED = itertools.count()         # spans exited (well-formedness probe)
_SID = itertools.count(1)           # span ids (0 means "no span")
_TLS = threading.local()            # .stack: sids of the open spans
# jax.profiler.TraceAnnotation while enabled (set by _hook), else None.
_ANNOTATION = None
# What _hook registered while enabled: "gc", "compile" (snapshot() says).
_LISTENERS: Tuple[str, ...] = ()
# Finished gc spans, (event, thread name): a collection can start inside
# _ring() with _LOCK held, so its callback touches no ring and no lock;
# the next ordinary record (or read) moves them into their rings.
_PENDING: collections.deque = collections.deque(maxlen=4096)

# Process-wide counts that operators add to while the recorder is on
# (:func:`count`): capacity rows and the like, known to the host without a
# read of the device. Totals of the process, kept through :func:`reset`.
_COUNTERS: Dict[str, int] = {}

# Epoch all timestamps are relative to (perf_counter_ns at import), so
# exported traces start near 0 instead of at an arbitrary boot offset.
_EPOCH_NS = time.perf_counter_ns()

_faults = None      # spark_rapids_tpu.faults, bound at the first enable

# Process identity for exported traces. Empty in the driver; cluster
# worker processes set "worker <wid>" so a worker-side trace export
# names its tracks "worker w0 query N" and a merged multi-process view
# stays attributable.
_PROCESS_TAG = ""


def _now_ns() -> int:
    return time.perf_counter_ns() - _EPOCH_NS


def _current_query_id() -> int:
    """The recording thread's query id (scheduler admission ordinal), or
    0 outside a managed query — unmanaged collects share ring 0."""
    qid = _faults.current_query_id()    # bound by _hook at first enable
    return 0 if qid is None else qid


def _ring(qid: int) -> collections.deque:
    ring = _RINGS.get(qid)
    if ring is None:
        with _LOCK:
            ring = _RINGS.get(qid)
            if ring is None:
                ring = _RINGS[qid] = collections.deque(maxlen=_MAX_EVENTS)
                while len(_RINGS) > _MAX_QUERIES:
                    old, _ = _RINGS.popitem(last=False)
                    _DROPPED.pop(old, None)
    return ring


def _append(event: tuple, qid: int, thread_name: Optional[str] = None
            ) -> None:
    ring = _ring(qid)
    if len(ring) == ring.maxlen:
        _DROPPED[qid] = _DROPPED.get(qid, 0) + 1
    ring.append(event)      # deque.append is atomic under the GIL
    tid = event[5]
    if tid not in _THREAD_NAMES:
        _THREAD_NAMES[tid] = thread_name or threading.current_thread().name


def _flush_pending() -> None:
    while _PENDING:
        try:
            event, thread_name = _PENDING.popleft()
        except IndexError:      # another thread took the last one
            return
        _append(event, event[6], thread_name)


def _record(event: tuple, qid: int) -> None:
    if _PENDING:
        _flush_pending()
    _append(event, qid)


# -- the recording API --------------------------------------------------------

class _NoopSpan:
    """Shared disabled span: __enter__/__exit__ do nothing. One instance
    for the whole process — the disabled path allocates nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def note(self, **args):
        pass


_NOOP = _NoopSpan()


def _stack() -> list:
    try:
        return _TLS.stack
    except AttributeError:
        stack = _TLS.stack = []
        return stack


def current() -> int:
    """The id of the innermost span open on this thread (0: none, or
    the recorder is off). Capture it where work is handed to another
    thread and :func:`adopt` it there."""
    if not _ENABLED:
        return 0
    stack = _stack()
    return stack[-1] if stack else 0


def adopt(parent: int) -> None:
    """Make ``parent`` (a :func:`current` of the submitting thread) the
    parent of the spans this thread opens from now on; 0 ends the
    adoption (pool threads are reused)."""
    if parent or getattr(_TLS, "stack", None):
        _TLS.stack = [parent] if parent else []


class _Span:
    __slots__ = ("name", "cat", "args", "qid", "label", "sid", "parent",
                 "_t0", "_ann", "_stack")

    def __init__(self, name: str, cat: str, args, qid, label):
        self.name = name
        self.cat = cat
        self.args = args
        self.qid = qid
        self.label = label

    def __enter__(self):
        next(_OPEN)
        stack = self._stack = _stack()
        self.parent = stack[-1] if stack else 0
        self.sid = sid = next(_SID)
        stack.append(sid)
        ann = _ANNOTATION
        if ann is not None:
            ann = ann(self.label or f"{self.cat}:{self.name}")
            ann.__enter__()
        self._ann = ann
        self._t0 = _now_ns()
        return self

    def note(self, **args):
        """Add args learnt while the span is open (recorded at exit)."""
        self.args = {**(self.args or {}), **args}

    def __exit__(self, *exc):
        t0 = self._t0
        dur = _now_ns() - t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        stack, sid = self._stack, self.sid
        if stack and stack[-1] == sid:
            stack.pop()
        elif sid in stack:      # exited out of order (a span held
            stack.remove(sid)   # across a generator's yield)
        qid = self.qid if self.qid is not None else _current_query_id()
        _record(("X", self.name, self.cat, t0, dur,
                 threading.get_ident(), qid, self.args, sid,
                 self.parent), qid)
        next(_CLOSED)
        return False


def span(name: str, cat: str, level: int = LEVEL_OPERATOR,
         args: Optional[dict] = None, qid: Optional[int] = None,
         label: Optional[str] = None):
    """A context manager recording one trace span. Disabled (or above
    the configured level) it returns the shared no-op — the caller's
    ``with`` costs two empty method calls and nothing else. Enabled, the
    span is also a profiler annotation: ``label``, or
    ``<cat>:<name>``."""
    if not _ENABLED or level > _LEVEL:
        return _NOOP
    return _Span(name, cat, args, qid, label)


def op_span(op: str, name: str, cat: str = "device-compute",
            level: int = LEVEL_OPERATOR):
    """A span around a step an operator takes for itself, labelled
    ``<op>:<name>`` on the profile. The label and the args are built
    only when the span records."""
    if not _ENABLED or level > _LEVEL:
        return _NOOP
    return _Span(name, cat, {"op": op}, None, f"{op}:{name}")


def now_ns() -> int:
    """Recorder-epoch-relative monotonic timestamp (for retro-recorded
    spans)."""
    return _now_ns()


def record_span(name: str, cat: str, t0_ns: int, dur_ns: int,
                qid: Optional[int] = None, args: Optional[dict] = None,
                level: int = LEVEL_OPERATOR) -> None:
    """Retro-record one completed span — for intervals whose owning
    query id only exists once they END (scheduler admission issues the
    id the admission wait was FOR) or that someone else timed (jax's
    backend compiles). Its parent is the span open now; it can be no
    profiler annotation."""
    if not _ENABLED or level > _LEVEL:
        return
    q = qid if qid is not None else _current_query_id()
    _record(("X", name, cat, t0_ns, max(int(dur_ns), 0),
             threading.get_ident(), q, args, next(_SID), current()), q)


def instant(name: str, cat: str, args: Optional[dict] = None,
            qid: Optional[int] = None, level: int = LEVEL_QUERY) -> None:
    """Record one instant event (fault injected, OOM rung, recompute,
    demotion, cancellation...). Instants default to LEVEL_QUERY: they
    are rare and they are the events the trace exists to explain."""
    if not _ENABLED or level > _LEVEL:
        return
    q = qid if qid is not None else _current_query_id()
    _record(("i", name, cat, _now_ns(), None,
             threading.get_ident(), q, args, 0, current()), q)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the process-wide counter ``name``; nothing while the
    recorder is off. For what an operator knows on the host as it
    dispatches (rows of capacity, batches, levels of a merge tree): a
    counter never reads the device. ``collects`` (one a device collect,
    from the funnel) is what a per-query mean divides by."""
    if not _ENABLED:
        return
    with _LOCK:
        _COUNTERS[name] = _COUNTERS.get(name, 0) + n


def counters() -> Dict[str, int]:
    """The counts of :func:`count` since the process began (or since
    :func:`reset_counters`): every collect the recorder was on for."""
    with _LOCK:
        return dict(_COUNTERS)


def reset_counters() -> None:
    with _LOCK:
        _COUNTERS.clear()


# -- what only an enabled recorder listens to ---------------------------------

_GC_OPEN = None     # (t0, annotation, generation, parent): gcs do not nest


def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks``: one ``runtime`` span per collection. A full
    collection is rare and can stall a query for seconds, so it records
    at query level; the young generations only at kernel level.

    A collection starts wherever the interpreter allocates, also inside
    :func:`_ring` with ``_LOCK`` held: this takes no lock and touches no
    ring. The finished span waits in ``_PENDING``."""
    global _GC_OPEN
    if phase == "start":
        gen = info["generation"]
        if not _ENABLED or \
                (LEVEL_QUERY if gen == 2 else LEVEL_KERNEL) > _LEVEL:
            return
        ann = _ANNOTATION
        if ann is not None:
            ann = ann("runtime:gc")
            ann.__enter__()
        _GC_OPEN = (_now_ns(), ann, gen, current())
    elif _GC_OPEN is not None:
        (t0, ann, gen, parent), _GC_OPEN = _GC_OPEN, None
        dur = _now_ns() - t0
        if ann is not None:
            ann.__exit__(None, None, None)
        _PENDING.append((
            ("X", "gc", "runtime", t0, dur, threading.get_ident(),
             _current_query_id(),
             {"generation": gen, "collected": info["collected"]},
             next(_SID), parent),
            threading.current_thread().name))


def _on_jax_duration(event: str, duration: float, **kw) -> None:
    """jax.monitoring: a backend compile that just ended becomes a
    ``compile`` span under the span that was open (which step
    recompiled), in that query's ring."""
    if event.endswith("backend_compile_duration"):
        dur = int(duration * 1e9)
        record_span("backend-compile", "compile", _now_ns() - dur, dur,
                    args={"fun_name": kw.get("fun_name")},
                    level=LEVEL_QUERY)


def _hook(on: bool) -> None:
    """Register (first enable) or drop (disable) the annotation class
    and the two listeners. Caller holds ``_LOCK``."""
    global _ANNOTATION, _LISTENERS, _faults
    if on == (_on_gc in gc.callbacks):
        return
    if on:
        from spark_rapids_tpu import faults     # _on_gc imports nothing
        _faults = faults
        gc.callbacks.append(_on_gc)
        _LISTENERS = ("gc",)
        try:
            import jax.monitoring
            import jax.profiler
        except ImportError:     # a stdlib-only process: spans, no sinks
            return
        _ANNOTATION = jax.profiler.TraceAnnotation
        jax.monitoring.register_event_duration_secs_listener(
            _on_jax_duration)
        _LISTENERS = ("gc", "compile")
    else:
        gc.callbacks.remove(_on_gc)
        _LISTENERS = ()
        if _ANNOTATION is not None:
            _ANNOTATION = None
            import jax.monitoring
            jax.monitoring.unregister_event_duration_listener(
                _on_jax_duration)


def set_process_tag(tag: str) -> None:
    """Name this process in exported traces (cluster workers pass
    ``worker <wid>``). Affects rendering only, never recording."""
    global _PROCESS_TAG
    _PROCESS_TAG = str(tag)


def process_tag() -> str:
    return _PROCESS_TAG


def enabled() -> bool:
    return _ENABLED


def level() -> int:
    return _LEVEL


# -- configuration ------------------------------------------------------------

def trace_enabled(conf) -> bool:
    """Conf key wins; else the SRT_TRACE env (the CI matrix hook); else
    the registered default (off)."""
    from spark_rapids_tpu import config as C
    if conf.raw.get(C.TRACE_ENABLED.key) is not None:
        return bool(conf.get(C.TRACE_ENABLED))
    env = os.environ.get("SRT_TRACE")
    if env is not None:
        return env.strip() not in ("", "0", "false", "no")
    return bool(C.TRACE_ENABLED.default)


def maybe_configure(conf) -> None:
    """Adopt this query's trace configuration (process-global, last
    writer wins — the wire-codec regime). Called once per collect from
    the dispatch funnel, BEFORE any span site runs."""
    global _ENABLED, _LEVEL, _MAX_EVENTS
    from spark_rapids_tpu import config as C
    want = trace_enabled(conf)
    lvl = _LEVEL_NAMES.get(
        str(conf.get(C.TRACE_LEVEL)).strip().lower(), LEVEL_OPERATOR)
    max_events = max(int(conf.get(C.TRACE_MAX_EVENTS)), 256)
    if want == _ENABLED and lvl == _LEVEL and max_events == _MAX_EVENTS:
        return
    with _LOCK:
        _LEVEL = lvl
        if max_events != _MAX_EVENTS:
            _MAX_EVENTS = max_events    # existing rings keep their bound
        _hook(want)
        _ENABLED = want


def configure(enabled_: bool, level_: int = LEVEL_OPERATOR,
              max_events: int = 65536) -> None:
    """Direct (test/bench) configuration, bypassing the conf plumbing."""
    global _ENABLED, _LEVEL, _MAX_EVENTS
    with _LOCK:
        _LEVEL = int(level_)
        _MAX_EVENTS = max(int(max_events), 256)
        _hook(bool(enabled_))
        _ENABLED = bool(enabled_)


def reset() -> None:
    """Drop every recorded event (test isolation; keeps configuration
    and the counters, which have :func:`reset_counters`)."""
    _PENDING.clear()
    with _LOCK:
        _RINGS.clear()
        _THREAD_NAMES.clear()
        _DROPPED.clear()


# -- consumers ----------------------------------------------------------------

def events(query_id: Optional[int] = None) -> List[tuple]:
    """Recorded events — one query's ring, or every ring interleaved in
    timestamp order."""
    _flush_pending()
    with _LOCK:
        if query_id is not None:
            ring = _RINGS.get(query_id)
            return list(ring) if ring is not None else []
        out: List[tuple] = []
        for ring in _RINGS.values():
            out.extend(ring)
    out.sort(key=lambda e: e[3])
    return out


def query_ids() -> List[int]:
    with _LOCK:
        return list(_RINGS.keys())


def thread_names() -> Dict[int, str]:
    with _LOCK:
        return dict(_THREAD_NAMES)


def open_span_count() -> int:
    """Spans entered minus spans exited — 0 when every begin got its
    end (the well-formedness probe the trace tests assert)."""
    # itertools.count has no read API; peek by advancing paired clones is
    # racy — instead derive from the repr ("count(N)").
    opened = int(repr(_OPEN)[6:-1])
    closed = int(repr(_CLOSED)[6:-1])
    return opened - closed


def self_ns(evs: List[tuple]) -> Dict[int, int]:
    """Span id -> self time in ns: the span's duration less the
    durations of its children on the same thread (they nest, so they are
    disjoint). A child on another thread ran beside its parent and takes
    nothing from it."""
    own = {e[8]: [e[4], e[5]] for e in evs if e[0] == "X"}
    for e in evs:
        if e[0] == "X":
            parent = own.get(e[9])
            if parent is not None and parent[1] == e[5]:
                parent[0] -= e[4]
    return {sid: max(v[0], 0) for sid, v in own.items()}


def self_times(query_id: Optional[int] = None) -> Dict[str, float]:
    """Category -> ms of self time over one query's ring (or all): what
    each layer spent in its own code, so the categories of one thread
    sum to no more than its outermost spans."""
    evs = events(query_id)
    own = self_ns(evs)
    out: Dict[str, float] = {}
    for e in evs:
        if e[0] == "X":
            out[e[2]] = out.get(e[2], 0.0) + own[e[8]] / 1e6
    return out


def snapshot() -> dict:
    """Aggregated process-wide view: per-category span counts and time
    (``ms``: the spans' durations summed, nested ones counted again;
    ``selfMs``: self time), instant counts by name, per-query event
    totals, ``listeners``: what an enabled recorder hears besides its
    span sites (``gc``, ``compile``) — the at-a-glance answer to "where
    did the wall-clock go" without exporting a full timeline."""
    cats: Dict[str, Dict[str, float]] = {}
    instants: Dict[str, int] = {}
    queries: Dict[str, Dict[str, float]] = {}
    evs = events()
    own = self_ns(evs)
    for e in evs:
        ph, name, cat, ts, dur, tid, qid, args, sid, parent = e
        q = queries.setdefault(str(qid), {"events": 0, "spanMs": 0.0})
        q["events"] += 1
        if ph == "X":
            c = cats.setdefault(cat, {"spans": 0, "ms": 0.0,
                                      "selfMs": 0.0})
            c["spans"] += 1
            c["ms"] += dur / 1e6
            c["selfMs"] += own[sid] / 1e6
            q["spanMs"] += dur / 1e6
        else:
            instants[name] = instants.get(name, 0) + 1
    for c in cats.values():
        c["ms"] = round(c["ms"], 3)
        c["selfMs"] = round(c["selfMs"], 3)
    for q in queries.values():
        q["spanMs"] = round(q["spanMs"], 3)
    with _LOCK:
        dropped = sum(_DROPPED.values())
    return {
        "enabled": _ENABLED,
        "level": {v: k for k, v in _LEVEL_NAMES.items()}[_LEVEL],
        "maxEvents": _MAX_EVENTS,
        "listeners": list(_LISTENERS),
        "categories": cats,
        "instants": instants,
        "queries": queries,
        "droppedEvents": dropped,
        "openSpans": open_span_count(),
    }


def category_breakdown() -> Dict[str, float]:
    """Span-category -> total ms, flat (the p50/p99 attribution story's
    denominator: queued / host-prefetch / device-compute / upload /
    shuffle / recovery ...)."""
    return {cat: agg["ms"]
            for cat, agg in snapshot()["categories"].items()}


def export_chrome(path: Optional[str] = None,
                  query_id: Optional[int] = None) -> dict:
    """Chrome trace-event JSON (loads in Perfetto / chrome://tracing):
    one process track per query, one thread track per worker thread.
    Writes ``path`` when given; returns the document either way."""
    from spark_rapids_tpu.monitoring.chrome import to_chrome
    doc = to_chrome(events(query_id), thread_names(), _PROCESS_TAG)
    if path:
        with open(path, "w") as f:
            json.dump(doc, f)
    return doc
