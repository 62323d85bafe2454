"""Parameterized plan cache: zero re-plan, zero re-trace repeated-query
serving (ROADMAP item 2; the serving half of the reference's
plan-once-per-query economics — the plugin rewrites Catalyst plans once
and Spark re-executes the cached physical plan per batch).

Every ``collect()`` today re-runs analysis -> pruning/pushdown ->
capability tagging -> cost placement -> conversion -> fusion from
scratch; only the jitted kernels are cached. For short queries that
host-side plan pipeline is a dominant share of latency (the flight
recorder makes it visible as non-device wall), and a serving tier
issuing the SAME query shape with new literals every call pays it —
plus, worse, a full kernel RE-TRACE, because literal values are
trace-time constants folded into the kernel-cache fingerprints.

This module fixes both with one mechanism:

1. :func:`parameterize` rewrites a logical plan's bindable literal
   leaves — numeric/bool/date operands of comparisons and arithmetic in
   filters and projections, plus ``limit(n)`` budgets — into positional
   BIND SLOTS (``("bindslot", i, dtype)`` Column nodes resolved to
   value-free :class:`~spark_rapids_tpu.exprs.bindslots.BindSlotExpr`
   leaves). Literals in structural positions (string widths, regex
   patterns, isin sets, pad/round/slice arguments, aggregate internals)
   are deliberately NOT hoisted: their values shape the traced program.
2. The parameterized shape keys a process-global LRU:
   ``(structural plan fingerprint incl. input schemas, conf snapshot)``.
   A hit returns the fully planned/fused/cost-placed
   :class:`~spark_rapids_tpu.plan.planner.PhysicalPlan` TEMPLATE.
3. :class:`BoundPlan` marries the shared template with THIS call's
   literal values. ``collect()`` installs them into the execution
   context, where kernel call sites (ops/basic.py, ops/fused.py) pass
   them as traced runtime inputs and host-side consumers (limit
   budgets, scan row-group pruning) resolve them as python values —
   so compiled executables are shared across bindings and a repeat
   execution goes straight to the execution funnel.

Correctness lines:

- Invalidation is conservative: ANY conf change misses (the snapshot
  keys the WHOLE raw conf — a superset of the cost/fusion/transport/
  wire keys that actually affect planning), schema/path/option changes
  miss structurally, and an armed fault schedule (conf or SRT_FAULTS
  env) BYPASSES the cache entirely — chaos targets per-plan state.
- Per-query state stays per-execution: ExecContext, owner tags, AQE
  replan decisions (parallel/replan.py keys them in ``ctx.cache``) and
  trace rings are fresh per collect; nothing writes back into the
  template.
- In-memory sources key by source-batch OBJECT identity; the key tuple
  holds strong references, so an id can never be recycled into a
  false hit (the LRU bound caps what that pins).
- Plans containing opaque callables (pandas UDF nodes, generate, etc.)
  raise :class:`Uncacheable` and plan fresh — correctness first.

``SRT_PLAN_CACHE=0`` (env) or ``spark.rapids.sql.planCache.enabled``
=false restores the plan-every-collect engine byte-for-byte (the CI
``plan-cache-off`` matrix entry runs the whole suite that way).
"""

from __future__ import annotations

import collections
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from spark_rapids_tpu import config as C
from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.exprs.bindslots import BindValue
from spark_rapids_tpu.ops.kernel_cache import schema_fingerprint
from spark_rapids_tpu.plan import logical as L
from spark_rapids_tpu.plan.logical import Column, LogicalPlan, canonical_node

# ---------------------------------------------------------------------------
# Process-global counters
# ---------------------------------------------------------------------------

_COUNTER_LOCK = threading.Lock()
_COUNTERS: Dict[str, float] = {}


def _record(name: str, amount: float = 1) -> None:
    with _COUNTER_LOCK:
        _COUNTERS[name] = _COUNTERS.get(name, 0) + amount


def counters() -> Dict[str, float]:
    with _COUNTER_LOCK:
        return dict(_COUNTERS)


def reset_counters() -> None:
    with _COUNTER_LOCK:
        _COUNTERS.clear()


def plan_cache_enabled(conf) -> bool:
    """Conf key wins; else the SRT_PLAN_CACHE env (CI matrix hook); else
    the registered default."""
    if conf.raw.get(C.PLAN_CACHE_ENABLED.key) is not None:
        return bool(conf.get(C.PLAN_CACHE_ENABLED))
    env = os.environ.get("SRT_PLAN_CACHE")
    if env is not None:
        return env.strip() not in ("0", "false", "no")
    return bool(C.PLAN_CACHE_ENABLED.default)


# ---------------------------------------------------------------------------
# Literal hoisting (parameterization)
# ---------------------------------------------------------------------------

# Expression kinds whose DIRECT literal operands flow as pure data —
# evaluation expands the scalar into a column and the kernel shape never
# depends on the value. Everything else (strings: width buckets; round
# scales, substr bounds, isin sets, like patterns, ...) keeps its
# literal inline as a trace constant.
_SAFE_BINARY = {"add", "sub", "mul", "div", "mod",
                "eq", "lt", "le", "gt", "ge"}


def _bindable_dtype(v) -> Optional[dt.DataType]:
    """The slot dtype for a hoistable literal value — EXACTLY the
    inference ``exprs.base.lit`` applies, so a template plans with the
    same types the unhoisted plan would."""
    if isinstance(v, bool):
        return dt.BOOL
    if isinstance(v, int):
        return dt.INT32 if -2**31 <= v < 2**31 else dt.INT64
    if isinstance(v, float):
        return dt.FLOAT64
    return None


class _Hoister:
    """Collects hoisted values/dtypes in deterministic DFS order (two
    equal-shaped plans allocate identical slot numbering)."""

    def __init__(self):
        self.values: List[Any] = []
        self.dtypes: List[dt.DataType] = []

    def _slot(self, value, dtype) -> int:
        self.values.append(value)
        self.dtypes.append(dtype)
        return len(self.values) - 1

    def rewrite(self, c: Column) -> Column:
        node = c.node
        kind = node[0]
        hoist_at = (1, 2) if kind in _SAFE_BINARY else ()
        out: List[Any] = [kind]
        changed = False
        for idx, x in enumerate(node[1:], start=1):
            if isinstance(x, Column):
                if idx in hoist_at and x.node[0] == "lit":
                    t = _bindable_dtype(x.node[1])
                    if t is not None:
                        out.append(Column(
                            ("bindslot", self._slot(x.node[1], t), t)))
                        changed = True
                        continue
                nx = self.rewrite(x)
                changed |= nx is not x
                out.append(nx)
            elif isinstance(x, tuple):
                nx, tchanged = self._rewrite_tuple(x)
                out.append(nx if tchanged else x)
                changed |= tchanged
            else:
                out.append(x)
        if not changed:
            return c
        return Column(tuple(out))

    def _rewrite_tuple(self, t: tuple) -> Tuple[tuple, bool]:
        out: List[Any] = []
        changed = False
        for y in t:
            if isinstance(y, Column):
                ny = self.rewrite(y)
                changed |= ny is not y
                out.append(ny)
            elif isinstance(y, tuple):
                ny, ychanged = self._rewrite_tuple(y)
                out.append(ny if ychanged else y)
                changed |= ychanged
            else:
                out.append(y)
        return tuple(out), changed


def parameterize(plan: LogicalPlan):
    """Rewrite ``plan`` with bindable literals hoisted into slots.
    Returns ``(parameterized_plan, values, dtypes)``; the plan is
    returned unchanged (identity) where nothing hoists."""
    h = _Hoister()
    new = _walk(plan, h)
    return new, tuple(h.values), tuple(h.dtypes)


def _walk(plan: LogicalPlan, h: _Hoister) -> LogicalPlan:
    kids = [_walk(c, h) for c in plan.children]
    same_kids = all(a is b for a, b in zip(kids, plan.children))
    if isinstance(plan, L.LogicalFilter):
        cond = h.rewrite(plan.condition)
        if cond is plan.condition and same_kids:
            return plan
        return L.LogicalFilter(kids[0], cond)
    if isinstance(plan, L.LogicalProject):
        projections = [(n, h.rewrite(c)) for n, c in plan.projections]
        if same_kids and all(a[1] is b[1] for a, b in
                             zip(projections, plan.projections)):
            return plan
        return L.LogicalProject(kids[0], projections)
    if isinstance(plan, L.LogicalLimit) and isinstance(plan.n, int):
        # Limit budgets are host-side python ints: hoisted as BindValue
        # markers the limit execs resolve per execution.
        return L.LogicalLimit(kids[0], BindValue(h._slot(
            int(plan.n), dt.INT64)))
    if same_kids:
        return plan
    import copy
    cp = copy.copy(plan)
    cp.children = tuple(kids)
    return cp


# ---------------------------------------------------------------------------
# Structural plan keys
# ---------------------------------------------------------------------------

class Uncacheable(Exception):
    """This plan shape cannot be keyed safely (opaque callables, unknown
    node types): plan fresh every time."""


class _IdKey:
    """Identity-hashed strong reference: keys an in-memory source batch
    by OBJECT identity while pinning the object, so a garbage-collected
    id can never be recycled into a false cache hit."""

    __slots__ = ("obj",)

    def __init__(self, obj):
        self.obj = obj

    def __hash__(self):
        return id(self.obj)

    def __eq__(self, other):
        return isinstance(other, _IdKey) and other.obj is self.obj


def _canon_cols(pairs) -> Tuple:
    return tuple((n, canonical_node(c)) for n, c in pairs)


def plan_key(plan: LogicalPlan) -> Tuple:
    """Hashable structural fingerprint of a (parameterized) logical
    plan: node types, schemas, canonical expression ASTs (bind slots are
    value-free), join/grouping shapes. Two plans with equal keys must
    plan to semantically identical templates — the cache correctness
    contract (literal VALUES are excluded exactly where bind slots
    carry them at runtime)."""
    kids = tuple(plan_key(c) for c in plan.children)
    if isinstance(plan, L.InMemoryScan):
        # Source-batch OBJECT identity: the key tuple strong-refs the
        # batches, so a recycled id can never produce a false hit.
        return ("mem", schema_fingerprint(plan.source_schema),
                tuple(tuple(_IdKey(hb) for hb in p)
                      for p in plan.partitions))
    if isinstance(plan, L.FileScan):
        return ("scan", plan.fmt, tuple(plan.paths),
                schema_fingerprint(plan.source_schema),
                tuple(sorted((str(k), repr(v))
                             for k, v in plan.options.items())),
                canonical_node(plan.predicates))
    if isinstance(plan, L.LogicalRange):
        return ("range", plan.start, plan.end, plan.step,
                plan.num_partitions)
    if isinstance(plan, L.LogicalFilter):
        return ("filter", canonical_node(plan.condition)) + kids
    if isinstance(plan, L.LogicalProject):
        return ("project", _canon_cols(plan.projections)) + kids
    if isinstance(plan, L.LogicalAggregate):
        return ("agg", plan.grouping, _canon_cols(plan.group_by),
                _canon_cols(plan.aggregates)) + kids
    if isinstance(plan, L.LogicalWindow):
        return ("window", _canon_cols(plan.exprs), plan.spec_key()) + kids
    if isinstance(plan, L.LogicalSort):
        return ("sort", tuple(canonical_node(o)
                              for o in plan.orders)) + kids
    if isinstance(plan, L.LogicalLimit):
        n = plan.n
        return ("limit",
                ("bindval", n.slot) if isinstance(n, BindValue)
                else int(n)) + kids
    if isinstance(plan, L.LogicalRepartition):
        return ("repart", plan.num_partitions,
                tuple(canonical_node(k) for k in (plan.keys or ()))) + kids
    if isinstance(plan, L.LogicalUnion):
        return ("union",) + kids
    if isinstance(plan, L.LogicalJoin):
        return ("join", plan.join_type, plan.strategy,
                tuple(canonical_node(k) for k in plan.left_keys),
                tuple(canonical_node(k) for k in plan.right_keys),
                None if plan.condition is None
                else canonical_node(plan.condition)) + kids
    # Generate / pandas-UDF / ingest-exotic nodes carry opaque callables
    # or shapes this keyer does not model — refuse rather than guess.
    raise Uncacheable(plan.name)


def _conf_key(conf) -> Tuple:
    return tuple(sorted((k, repr(v)) for k, v in conf.raw.items()))


def _faults_armed(conf) -> bool:
    from spark_rapids_tpu import faults
    if str(conf.get(C.TEST_FAULTS) or "").strip():
        return True
    if os.environ.get("SRT_FAULTS", "").strip():
        return True
    return faults.injector() is not None


# ---------------------------------------------------------------------------
# The cache
# ---------------------------------------------------------------------------

class PlanCacheEntry:
    __slots__ = ("template", "dtypes", "nbinds")

    def __init__(self, template, dtypes):
        self.template = template
        self.dtypes = tuple(dtypes)
        self.nbinds = len(self.dtypes)


class PlanCache:
    """Bounded LRU of physical plan templates keyed by parameterized
    structure + conf snapshot."""

    def __init__(self, max_entries: int = 256):
        self._entries: "collections.OrderedDict[Any, PlanCacheEntry]" = \
            collections.OrderedDict()
        self._lock = threading.Lock()
        self.max_entries = max_entries
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def configure(self, max_entries: int) -> None:
        with self._lock:
            self.max_entries = max(int(max_entries), 1)
            self._evict()

    def lookup(self, key) -> Optional[PlanCacheEntry]:
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                self._entries.move_to_end(key)
                self.hits += 1
                _record("planCacheHits")
            else:
                self.misses += 1
                _record("planCacheMisses")
            return entry

    def insert(self, key, entry: PlanCacheEntry) -> PlanCacheEntry:
        """First writer wins: a concurrent planner of the same key keeps
        the stored template so every caller shares one exec tree."""
        with self._lock:
            existing = self._entries.get(key)
            if existing is not None:
                return existing
            self._entries[key] = entry
            self._evict()
            return entry

    def _evict(self) -> None:
        while len(self._entries) > self.max_entries:
            self._entries.popitem(last=False)
            self.evictions += 1
            _record("planCacheEvictions")

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "evictions": self.evictions,
                    "entries": len(self._entries)}

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self.hits = self.misses = self.evictions = 0


_CACHE = PlanCache()


def cache() -> PlanCache:
    """The process-global plan cache."""
    return _CACHE


# ---------------------------------------------------------------------------
# Bound plans
# ---------------------------------------------------------------------------

class BoundPlan:
    """Execution view over a shared plan template plus THIS call's
    literal bindings — the ``df.prepare()`` prepared-statement handle.
    Attribute access falls through to the template (root, meta, conf,
    cost_report, last_ctx ...); ``collect`` threads the bindings into
    the execution context."""

    def __init__(self, template, values, dtypes, cache_hit: bool):
        self.template = template
        self.bind_values = tuple(values)
        self.bind_dtypes = tuple(dtypes)
        self.cache_hit = bool(cache_hit)

    @property
    def provenance(self) -> str:
        return "plan-cache hit, bind-only" if self.cache_hit \
            else "plan-cache miss, template planned"

    def __getattr__(self, name):
        return getattr(object.__getattribute__(self, "template"), name)

    def install(self, ctx) -> None:
        """Install the binding vector on a caller-built context (the
        non-collect funnels: to_jax)."""
        ctx.cache["plan_binds"] = self.bind_values
        ctx.cache["plan_bind_dtypes"] = self.bind_dtypes

    def collect(self, ctx=None, timeout_ms=None, cancel_event=None,
                priority=None, tenant=None):
        if self.cache_hit:
            _record("bindOnlyExecutions")
        return self.template.collect(
            ctx, timeout_ms=timeout_ms, cancel_event=cancel_event,
            bindings=(self.bind_values, self.bind_dtypes),
            plan_cache_hit=self.cache_hit, priority=priority,
            tenant=tenant)

    def explain(self, mode: str = "ALL") -> str:
        report = self.template.explain(mode)
        return (f"[{self.provenance}; "
                f"{len(self.bind_values)} bind slot(s)]\n{report}")


def plan_or_bind(conf, logical: LogicalPlan):
    """THE planning funnel behind ``DataFrame._physical``: parameterize,
    fingerprint, and either bind against a cached template (hit) or
    plan one and cache it (miss). Returns a :class:`BoundPlan`, or a
    plain :class:`PhysicalPlan` when the cache is disabled, bypassed
    (armed faults), or the shape is uncacheable."""
    from spark_rapids_tpu import monitoring
    from spark_rapids_tpu.plan.planner import Planner
    if not plan_cache_enabled(conf):
        return Planner(conf).plan(logical)
    if _faults_armed(conf):
        # Chaos schedules target per-plan state; a shared template would
        # couple independently-armed queries. Bypass, don't poison.
        _record("planCacheBypasses")
        return Planner(conf).plan(logical)
    # The acceptance probe: steady-state plan+bind must stay in the low
    # single-digit ms (vs tens-to-hundreds for a full plan).
    span_args: dict = {}
    with monitoring.span("plan-bind", "planning", args=span_args,
                         level=monitoring.LEVEL_QUERY):
        t0 = time.perf_counter_ns()
        try:
            param, values, dtypes = parameterize(logical)
            key = (plan_key(param), _conf_key(conf))
            hash(key)
        except (Uncacheable, TypeError):
            _record("planCacheUncacheable")
            span_args["uncacheable"] = True
            return Planner(conf).plan(logical)
        _CACHE.configure(int(conf.get(C.PLAN_CACHE_MAX_ENTRIES)))
        entry = _CACHE.lookup(key)
        hit = entry is not None
        if not hit:
            entry = _CACHE.insert(
                key, PlanCacheEntry(Planner(conf).plan(param), dtypes))
        _record("planBindNs", time.perf_counter_ns() - t0)
        span_args.update(planCacheHit=hit, bindSlots=len(values))
    monitoring.instant(
        "plan-cache-hit" if hit else "plan-cache-miss", "planning",
        args={"bindSlots": len(values)})
    return BoundPlan(entry.template, values, dtypes, hit)
