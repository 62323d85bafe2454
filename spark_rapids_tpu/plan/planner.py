"""Plan rewrite: wrap -> tag -> convert (ref: GpuOverrides.scala:1991,
RapidsMeta.scala:189, GpuTransitionOverrides.scala).

The reference's crown jewel, rebuilt for the standalone engine:
- every logical node and expression is wrapped in a Meta carrying
  fallback ``reasons`` (RapidsMeta.willNotWorkOnGpu analog);
- per-node kill-switch configs are auto-registered
  (``spark.rapids.sql.exec.<Node>`` / ``spark.rapids.sql.expression.<Kind>``
  — RapidsMeta confKey, SURVEY.md §5.6);
- incompat expressions (locale-sensitive case mapping, order-dependent
  float aggregation) fall back to the host engine unless
  ``spark.rapids.sql.incompatibleOps.enabled`` (GpuOverrides incompat
  flags);
- conversion emits the physical Exec tree with explicit
  HostToDevice/DeviceToHost transitions at placement changes
  (GpuTransitionOverrides insertColumnarToGpu/FromGpu), two-stage
  aggregation across hash exchanges, range exchanges under global sorts,
  and broadcast-vs-shuffle join planning;
- ``explain`` renders the will/will-not-run report
  (RapidsMeta.explain:291), and test mode
  ``spark.rapids.sql.test.enabled`` fails any query with a
  non-allowlisted host node (GpuTransitionOverrides.assertIsOnTheGpu:391).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

from spark_rapids_tpu import config as C
from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu import exprs as E
from spark_rapids_tpu.exprs.base import BoundReference, Expression
from spark_rapids_tpu.ops import (
    AggSpec, Average, Count, CountStar, ExpandExec, FilterExec, First,
    GlobalLimitExec, HashAggregateExec, Last, LocalLimitExec, Max, Min,
    ProjectExec, RangeExec, SortExec, SortOrder, Sum, UnionExec)
from spark_rapids_tpu.ops.base import (
    DeviceToHostExec, Exec, HostToDeviceExec, InMemorySourceExec)
from spark_rapids_tpu.ops.join import (
    BroadcastHashJoinExec, BroadcastNestedLoopJoinExec,
    ShuffledHashJoinExec)
from spark_rapids_tpu.parallel import (
    HashPartitioning, RangePartitioning, RoundRobinPartitioning,
    ShuffleExchangeExec, SinglePartitioning)
from spark_rapids_tpu.plan import logical as L
from spark_rapids_tpu.plan.logical import (
    Column, LogicalPlan, ResolutionError, resolve)


# ---------------------------------------------------------------------------
# Expression tagging rules (GpuOverrides expr registry analog)
# ---------------------------------------------------------------------------

# Kinds whose device implementation can differ from the JVM in corner cases.
_INCOMPAT_EXPRS = {
    "upper": "locale-sensitive case mapping is ASCII-only on TPU",
    "lower": "locale-sensitive case mapping is ASCII-only on TPU",
    "initcap": "locale-sensitive case mapping is ASCII-only on TPU",
}

# Kinds that execute on the host even inside the device plan (regex etc.).
_HOST_ROUNDTRIP_EXPRS = {"regexp_replace", "regexp_extract", "translate",
                         "lpad", "rpad", "replace"}

# Transcendentals whose XLA lowering can round differently from
# java.lang.Math (GpuOverrides marks the same family incompat); allowed by
# spark.rapids.sql.improvedFloatOps.enabled or incompatibleOps.enabled.
_IMPROVED_FLOAT_EXPRS = {
    "exp", "expm1", "log", "log10", "log2", "log1p", "sin", "cos", "tan",
    "asin", "acos", "atan", "sinh", "cosh", "tanh", "cbrt", "pow", "atan2",
}

# Kinds whose value depends on the task context rather than column inputs.
_CONTEXTUAL_EXPRS = {
    "rand": "nondeterministic (distribution-equal to Spark, not "
            "sequence-equal)",
    "input_file_name": "reads the per-batch host file path; disables "
                       "projection jit",
}

# All task-context kinds; only Project/Filter thread an EvalContext, so
# anywhere else these would silently evaluate with pid=0/row_base=0
# (Spark's CheckAnalysis draws the same line for nondeterministic exprs).
_CONTEXTUAL_KINDS = {"rand", "spark_partition_id",
                     "monotonically_increasing_id", "input_file_name"}


def _column_kinds(c: Column, out: set):
    out.add(c.node[0])
    for x in c.node[1:]:
        if isinstance(x, Column):
            _column_kinds(x, out)
        elif isinstance(x, tuple):
            for y in x:
                if isinstance(y, Column):
                    _column_kinds(y, out)
                elif isinstance(y, tuple):
                    for z in y:
                        if isinstance(z, Column):
                            _column_kinds(z, out)
    return out


def _uses_input_file(plan: LogicalPlan) -> bool:
    """True when any Project/Filter column references input_file_name():
    scans must then stay per-file (the reference's disableCoalesceUntilInput
    fence, GpuExpressions.scala:64-74) so the published path is exact."""
    cols: List[Column] = []
    if isinstance(plan, L.LogicalProject):
        cols = [c for _, c in plan.projections]
    elif isinstance(plan, L.LogicalFilter):
        cols = [plan.condition]
    for c in cols:
        if "input_file_name" in _column_kinds(c, set()):
            return True
    return any(_uses_input_file(ch) for ch in plan.children)


def _forbid_contextual(c: Column, where: str):
    """Analysis-time guard: contextual expressions are only valid where the
    evaluating operator threads an EvalContext (select/filter)."""
    bad = _column_kinds(c, set()) & _CONTEXTUAL_KINDS
    if bad:
        raise ResolutionError(
            f"nondeterministic/task-context expression(s) {sorted(bad)} are "
            f"only supported in select/filter/with_column, not in {where} "
            "(evaluate them into a column first)")


def _expr_conf_key(kind: str) -> str:
    return f"spark.rapids.sql.expression.{kind}"


def _exec_conf_key(name: str) -> str:
    return f"spark.rapids.sql.exec.{name}"


def tag_column(c: Column, conf: C.TpuConf, reasons: List[str],
               notes: List[str], schema=None):
    """Walk an untyped Column AST, collecting fallback reasons. ``schema``
    (when available) enables type-directed gates like the float<->string
    cast checks (GpuCast meta tagging, GpuOverrides.scala:442)."""
    kind = c.node[0]
    if not conf.is_op_enabled(_expr_conf_key(kind)):
        reasons.append(f"expression {kind} disabled by "
                       f"{_expr_conf_key(kind)}")
    if kind in _INCOMPAT_EXPRS and not conf.incompatible_ops:
        reasons.append(
            f"expression {kind} is incompatible ({_INCOMPAT_EXPRS[kind]}); "
            "enable spark.rapids.sql.incompatibleOps.enabled to allow")
    if kind in _IMPROVED_FLOAT_EXPRS and not conf.incompatible_ops and \
            not conf.get(C.IMPROVED_FLOAT_OPS):
        reasons.append(
            f"expression {kind} can round differently from java.lang.Math "
            "on TPU; enable spark.rapids.sql.improvedFloatOps.enabled")
    if kind == "cast" and schema is not None:
        try:
            src = resolve(c.node[1], schema).data_type()
        except Exception:
            src = None
        dst = c.node[2]
        if src is not None and src.is_floating and dst.is_string and \
                not conf.get(C.CAST_FLOAT_TO_STRING):
            reasons.append(
                "casting floats to string formats differently from Spark; "
                "enable spark.rapids.sql.castFloatToString.enabled")
        if src is not None and src.is_string and dst.is_floating and \
                not conf.get(C.CAST_STRING_TO_FLOAT):
            reasons.append(
                "casting strings to float differs in corner cases; "
                "enable spark.rapids.sql.castStringToFloat.enabled")
    if kind in _HOST_ROUNDTRIP_EXPRS:
        notes.append(f"expression {kind} runs via a host roundtrip")
    if kind == "pyudf":
        fname = getattr(c.node[1], "__name__", "udf")
        notes.append(
            f"python UDF {fname!r} could not be compiled to native "
            f"expressions ({c.node[4]}); runs via host roundtrip "
            "(GpuArrowEvalPythonExec-style fallback)")
    if kind in _CONTEXTUAL_EXPRS:
        notes.append(f"expression {kind}: {_CONTEXTUAL_EXPRS[kind]}")
    for x in c.node[1:]:
        if isinstance(x, Column):
            tag_column(x, conf, reasons, notes, schema)
        elif isinstance(x, tuple):
            for y in x:
                if isinstance(y, Column):
                    tag_column(y, conf, reasons, notes, schema)
                elif isinstance(y, tuple):
                    for z in y:
                        if isinstance(z, Column):
                            tag_column(z, conf, reasons, notes, schema)


def _float_agg_reasons(agg_col: Column, schema, conf: C.TpuConf,
                       reasons: List[str]):
    """Order-dependent float aggregation gate (GpuOverrides checks on
    variableFloatAgg, RapidsConf.scala:149 analog in config.py)."""
    kind = agg_col.node[1]
    child = agg_col.node[2]
    if kind in ("sum", "avg") and child is not None:
        try:
            t = resolve(child, schema).data_type()
        except Exception:
            return
        if t.is_floating and not conf.get(C.VARIABLE_FLOAT_AGG):
            reasons.append(
                f"{kind} over {t.name} can vary with evaluation order on "
                "TPU; enable spark.rapids.sql.variableFloatAgg.enabled")


# ---------------------------------------------------------------------------
# Node meta (RapidsMeta analog)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class NodeMeta:
    plan: LogicalPlan
    children: List["NodeMeta"]
    reasons: List[str] = dataclasses.field(default_factory=list)
    notes: List[str] = dataclasses.field(default_factory=list)
    # Cost-based placement (plan/cost.py): True flips this node to the
    # host engine as a PLACEMENT choice, not a capability fallback —
    # kept separate from ``reasons`` so explain reasons and test-mode
    # allowlists keep their capability meaning.
    cost_host: bool = False

    @property
    def on_device(self) -> bool:
        return not self.reasons and not self.cost_host

    def explain_lines(self, depth: int = 0, not_on_device_only=False):
        mark = "*" if self.on_device else "!"
        line = "  " * depth + f"{mark}Exec <{self.plan.name}>"
        if self.reasons:
            line += " cannot run on TPU because " + "; ".join(self.reasons)
        elif self.notes:
            line += " (" + "; ".join(self.notes) + ")"
        out = [] if (not_on_device_only and self.on_device and
                     not self.notes) else [line]
        for ch in self.children:
            out.extend(ch.explain_lines(depth + 1, not_on_device_only))
        return out


def wrap_and_tag(plan: LogicalPlan, conf: C.TpuConf) -> NodeMeta:
    meta = NodeMeta(plan, [wrap_and_tag(c, conf) for c in plan.children])
    reasons, notes = meta.reasons, meta.notes
    if not conf.sql_enabled:
        reasons.append("spark.rapids.sql.enabled is false")
    if not conf.is_op_enabled(_exec_conf_key(plan.name)):
        reasons.append(f"disabled by {_exec_conf_key(plan.name)}")

    if isinstance(plan, L.FileScan):
        fmt_gates = {
            "parquet": (C.ENABLE_PARQUET, C.ENABLE_PARQUET_READ),
            "orc": (C.ENABLE_ORC, C.ENABLE_ORC_READ),
            "csv": (C.ENABLE_CSV, C.ENABLE_CSV_READ),
        }
        for entry in fmt_gates.get(plan.fmt, ()):
            if not bool(conf.get(entry)):
                reasons.append(f"{plan.fmt} scan disabled by {entry.key}")
    elif isinstance(plan, L.LogicalFilter):
        tag_column(plan.condition, conf, reasons, notes,
                   plan.child.schema)
    elif isinstance(plan, L.LogicalProject):
        for _, c in plan.projections:
            tag_column(c, conf, reasons, notes, plan.child.schema)
    elif isinstance(plan, L.LogicalAggregate):
        for _, c in plan.group_by:
            _forbid_contextual(c, "group_by")
            tag_column(c, conf, reasons, notes, plan.child.schema)
        for _, c in plan.aggregates:
            _forbid_contextual(c, "aggregates")
            ac = _unalias(c)
            inner = ac.node[2] if ac.node[0] in ("agg", "aggd") else None
            if inner is not None:
                tag_column(inner, conf, reasons, notes, plan.child.schema)
            if ac.node[0] in ("agg", "aggd"):
                _float_agg_reasons(ac, plan.child.schema, conf, reasons)
    elif isinstance(plan, L.LogicalSort):
        for o in plan.orders:
            inner = o.node[1] if o.node[0] == "sortorder" else o
            _forbid_contextual(inner, "order_by")
            tag_column(inner, conf, reasons, notes, plan.child.schema)
    elif isinstance(plan, L.LogicalJoin):
        if plan.strategy == "shuffle" and plan.left_keys and \
                not conf.get(C.REPLACE_SORT_MERGE_JOIN):
            reasons.append(
                "co-partitioned (sort-merge-shaped) join replacement "
                "disabled by spark.rapids.sql.replaceSortMergeJoin.enabled")
        ls = plan.children[0].schema
        rs = plan.children[1].schema
        for k in plan.left_keys:
            _forbid_contextual(k, "join keys")
            tag_column(k, conf, reasons, notes, ls)
        for k in plan.right_keys:
            _forbid_contextual(k, "join keys")
            tag_column(k, conf, reasons, notes, rs)
        if plan.condition is not None:
            _forbid_contextual(plan.condition, "join condition")
            tag_column(plan.condition, conf, reasons, notes,
                       tuple(ls) + tuple(rs))
    elif isinstance(plan, L.LogicalRepartition):
        for k in (plan.keys or []):
            _forbid_contextual(k, "repartition keys")
            tag_column(k, conf, reasons, notes, plan.child.schema)
    elif isinstance(plan, L.LogicalGenerate):
        for c in plan.elements:
            _forbid_contextual(c, "explode elements")
            tag_column(c, conf, reasons, notes, plan.child.schema)
    elif isinstance(plan, L.LogicalWindow):
        for c in plan.window.partition_cols:
            _forbid_contextual(c, "window partition keys")
            tag_column(c, conf, reasons, notes, plan.child.schema)
        for o in plan.window.order_cols:
            inner = o.node[1] if o.node[0] == "sortorder" else o
            _forbid_contextual(inner, "window order keys")
            tag_column(inner, conf, reasons, notes, plan.child.schema)
        for _, fn_col in plan.exprs:
            node = fn_col.node
            if len(node) > 2 and isinstance(node[2], Column):
                tag_column(node[2], conf, reasons, notes,
                           plan.child.schema)
    return meta


def merge_windows(plan: LogicalPlan) -> LogicalPlan:
    """Collapse chains of LogicalWindow nodes with the SAME window spec
    into one multi-expression node: each node plans an exchange + a
    partition sort, so N window columns over one spec would otherwise
    shuffle and sort N times (Spark's ExtractWindowExpressions groups the
    same way before planning one Window operator)."""
    kids = [merge_windows(c) for c in plan.children]
    if not all(a is b for a, b in zip(kids, plan.children)):
        import copy
        plan = copy.copy(plan)
        plan.children = tuple(kids)
    if isinstance(plan, L.LogicalWindow) and \
            isinstance(plan.child, L.LogicalWindow) and \
            plan.spec_key() == plan.child.spec_key():
        inner = plan.child
        # Only merge when the outer expressions don't read the inner
        # node's outputs (a window fn over another window's result must
        # stay a separate pass).
        from spark_rapids_tpu.plan.pruning import refs_of
        refs: set = set()
        for _, fn_col in plan.exprs:
            refs_of(fn_col, refs)
        if not refs & {n for n, _ in inner.exprs}:
            return merge_windows(L.LogicalWindow(
                inner.child, list(inner.exprs) + list(plan.exprs),
                inner.window))
    return plan


# ---------------------------------------------------------------------------
# Aggregate resolution
# ---------------------------------------------------------------------------

def _unalias(c: Column) -> Column:
    while c.node[0] == "alias":
        c = c.node[1]
    return c


def resolve_agg(c: Column, schema) -> "AggFunctionLike":
    c = _unalias(c)
    assert c.node[0] in ("agg", "aggd"), f"not an aggregate: {c.node[0]}"
    distinct = c.node[0] == "aggd"
    kind = c.node[1]
    child_col = c.node[2]
    child = None if child_col is None else resolve(child_col, schema)
    if distinct and kind in ("first", "last"):
        raise L.ResolutionError(f"{kind}(DISTINCT) is not meaningful")
    if kind == "count":
        fn = CountStar(None) if child is None else Count(child)
    elif kind == "sum":
        fn = Sum(child)
    elif kind == "min":
        fn = Min(child)
    elif kind == "max":
        fn = Max(child)
    elif kind == "avg":
        fn = Average(child)
    elif kind == "first":
        fn = First(child, c.node[3] if len(c.node) > 3 else True)
    elif kind == "last":
        fn = Last(child, c.node[3] if len(c.node) > 3 else True)
    else:
        raise L.ResolutionError(f"unknown aggregate {kind!r}")
    # min/max(DISTINCT) == min/max: drop the flag so no rewrite happens.
    fn.is_distinct = distinct and kind not in ("min", "max")
    if fn.is_distinct:
        # Structural key of the (unresolved) input expression, for the
        # single-distinct-input restriction check.
        fn.distinct_key = L.canonical_node(child_col)
    return fn


AggFunctionLike = object


# ---------------------------------------------------------------------------
# Conversion (convertIfNeeded + transition insertion)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PhysicalPlan:
    """Planner output: root exec + which engine the root runs on + the
    tagged meta tree for explain/test-mode + the conf the query was
    planned with (runtime-read configs must see the same values)."""

    root: Exec
    root_on_device: bool
    meta: NodeMeta
    conf: "C.TpuConf" = dataclasses.field(default_factory=C.TpuConf)

    def explain(self, mode: str = "ALL") -> str:
        lines = self.meta.explain_lines(
            not_on_device_only=(mode.upper() == "NOT_ON_GPU"))
        from spark_rapids_tpu.plan.fusion import collect_fused
        fused = collect_fused(self.root)
        if fused:
            # Render fused stages with their member operator names so the
            # physical shape (and each stage's metrics owner) stays
            # readable next to the logical fallback report.
            lines.append(f"Fused stages: {len(fused)}")
            for i, f in enumerate(fused):
                members = ", ".join(type(o).__name__ for o in f.ops)
                lines.append(f"  *Stage #{i} <{f.name}> fuses [{members}]")
        report = getattr(self, "cost_report", None)
        if report is not None and (report.placements or report.lines or
                                   bool(self.conf.get(C.COST_EXPLAIN))):
            lines.extend(report.explain_lines())
        return "\n".join(lines)

    def collect(self, ctx=None, timeout_ms=None, cancel_event=None,
                bindings=None, plan_cache_hit=None, priority=None,
                tenant=None):
        """``bindings`` is the plan cache's ``(values, dtypes)`` pair for
        a parameterized template: installed into every execution
        context (including fresh-context retries) so bind slots, limit
        budgets and scan predicates resolve to THIS call's literals.
        ``plan_cache_hit`` (when not None) records the per-tenant
        plan-cache outcome on the Scheduler@query entry.

        ``priority``/``tenant`` feed the QoS subsystem (parallel/qos/):
        the priority class routes the query through the weighted-fair
        queue, the tenant tag enforces per-tenant quotas, and
        ``timeout_ms`` doubles as the deadline the cost estimate is
        tested against at admit time. With QoS off, both collapse to
        pure attribution on the ticket."""
        import time as _time

        from spark_rapids_tpu import faults, monitoring
        from spark_rapids_tpu.memory.oom import (
            backoff_delay_ms, is_transient_error, reset_degradation)
        from spark_rapids_tpu.ops.base import (ExecContext, Metrics,
                                               query_metrics_entry)
        from spark_rapids_tpu.parallel import scheduler as SC
        from spark_rapids_tpu.parallel import stages as S
        owned = ctx is None
        # Adopt the trace + telemetry configuration BEFORE admission so
        # the admission-queue span AND the rejection counters of THIS
        # query record (a shed query never reaches the dispatch funnel).
        monitoring.maybe_configure(self.conf)
        monitoring.telemetry.maybe_configure(self.conf)
        # Multi-query admission (parallel/scheduler.py): one ticket per
        # top-level collect. A thread already carrying a token (a nested
        # collect issued by this same query — e.g. a gated write) rides
        # the existing admission instead of deadlocking on a second
        # slot. Caller-provided contexts are the caller's query.
        ticket = None
        mgr = None
        if owned and faults.get_query_token() is None:
            mgr = SC.get_query_manager(self.conf)
            # The admission cost estimate: the plan's device+host
            # wall-clock projection (plan/cost.py). Plan-cache hits
            # reuse the template's CostReport, so repeat shapes carry
            # their SJF ordering key for free.
            est = getattr(self, "cost_report", None)
            est_ms = None
            if est is not None and est.skipped is None:
                est_ms = float(est.est_device_ms) + float(est.est_host_ms)
            ticket = mgr.admit(self.conf, cancel=cancel_event,
                               priority=priority, tenant=tenant,
                               cost_ms=est_ms, deadline_ms=timeout_ms)
            ticket.arm_deadline(timeout_ms)
            faults.set_query_token(ticket.token)
        ctx = ctx or ExecContext(self.conf, query=ticket)

        def install_bindings(c):
            if bindings is not None:
                c.cache["plan_binds"] = tuple(bindings[0])
                c.cache["plan_bind_dtypes"] = tuple(bindings[1])

        install_bindings(ctx)
        # The ring the flight recorder attributes this query's events to
        # (trace_export / explain_analyze read it off last_ctx).
        if ticket is not None:
            trace_qid = ticket.token.query_id
        else:
            tok = faults.get_query_token()
            trace_qid = tok.query_id if tok is not None else 0
        ctx.cache.setdefault("trace_query", trace_qid)
        if ticket is not None:
            mgr.register_context(ticket, ctx)
            sched = SC.metrics_entry(ctx)
            sched.add("admitted", 1)
            sched.add("queuedMs", ticket.queued_ms)
            if ticket.qos_class is not None:
                sched.add(f"class.{ticket.qos_class}", 1)
            if ticket.tenant is not None:
                sched.add(f"tenant.{ticket.tenant}", 1)
            if plan_cache_hit is not None:
                # Per-tenant plan-cache stats (plan/plan_cache.py): a
                # hit means this execution was bind-only — zero
                # re-plan, zero re-trace.
                SC.record_plan_cache(ctx, plan_cache_hit)
        # Cost@query audit trail: static placement decisions land here at
        # admission; runtime re-planning (parallel/replan.py) adds its
        # demotion counters to the same entry during execution.
        report = getattr(self, "cost_report", None)
        if report is not None and report.skipped is None:
            cm = query_metrics_entry(ctx, "Cost")
            cm.add("placements", report.placements)
            cm.add("hostPlacedNodes", report.nodes_host_placed)
            cm.add("estDeviceMs", report.est_device_ms)
            cm.add("estHostMs", report.est_host_ms)
            cm.add("estSyncs", report.est_syncs)
        # Arm the fault schedule ONCE per query (not per attempt: a
        # retried attempt must run against the REMAINING schedule, or a
        # count-based transient fault re-fires forever), and clear any
        # batch-target degradation a previous query's OOM ladder left.
        faults.maybe_configure(self.conf)
        reset_degradation()
        # Failure recovery ladder (SURVEY §5.3 + lineage-scoped recovery,
        # parallel/stages.py), scoped-smallest-first:
        #
        # 1. STAGE RECOMPUTE — a failure attributable to one stage's lost
        #    durable output (lostoutput injection, persistent checksum
        #    failure of a materialized exchange buffer) invalidates just
        #    that stage and re-runs the collect on the SAME context:
        #    every sibling stage serves its cached materialization, so
        #    only the lost lineage recomputes. Bounded by
        #    spark.rapids.sql.recovery.maxStageRecomputes.
        # 2. SAME-CONTEXT TRANSIENT RETRY — the first transient
        #    backend error also retries on the same context
        #    (materialized stage outputs are data at rest; discarding
        #    them re-runs work the failure never touched).
        # 3. WHOLE-QUERY RETRY — repeated transients (possibly poisoned
        #    device state) or an unattributable/budget-exhausted loss
        #    fall back to a fresh context, with exponential backoff +
        #    deterministic jitter, bounded by the per-query budget.
        #
        # Owned contexts only: a caller-provided context may hold state
        # the caller still needs.
        max_retries = max(int(self.conf.get(C.RETRY_TRANSIENT_MAX)), 0)
        base_ms = int(self.conf.get(C.RETRY_BACKOFF_MS))
        max_ms = int(self.conf.get(C.RETRY_MAX_BACKOFF_MS))
        seed = int(self.conf.get(C.TEST_FAULTS_SEED))
        graph = None
        if owned and bool(self.conf.get(C.STAGE_RECOVERY_ENABLED)):
            graph = S.build_stage_graph(self.root)
        # Cluster mode (parallel/cluster/, ISSUE 13): dispatch the
        # stage DAG to registered worker processes and fetch their
        # committed outputs locally. None (disabled, no dispatchable
        # stage, unpicklable plan, host fallback, mesh transport) =
        # execute locally exactly as before.
        qrun = None
        if owned and bool(self.conf.get(C.CLUSTER_ENABLED)):
            from spark_rapids_tpu.parallel import cluster as CL
            qrun = CL.maybe_prepare(self, ctx, graph)
        stage_budget = max(
            int(self.conf.get(C.RECOVERY_MAX_STAGE_RECOMPUTES)), 0)
        stage_recomputes = 0
        same_ctx_retry_used = False
        preempt_count = 0
        attempt = 0
        import logging
        log = logging.getLogger("spark_rapids_tpu")
        t0_query = _time.perf_counter()
        status = "ok"
        err_text = None
        try:
            while True:
                try:
                    if qrun is not None:
                        # Dispatch barrier: every remote stage task is
                        # committed to the spool before the local
                        # collect starts fetching. Dispatch failures
                        # (worker exhaustion, timeout) unwind through
                        # the same ladder below.
                        qrun.run(ctx)
                    return self.root.collect(ctx,
                                             device=self.root_on_device)
                except Exception as e:
                    if not owned:
                        raise
                    # Cancelled/deadlined queries unwind through every
                    # retry rung: whatever error the cancellation
                    # surfaced as (a killed stall, a poll raise, a torn
                    # stream), the query is done — converting here also
                    # stops the transient ladder from retrying it.
                    if ticket is not None and ticket.token.cancelled():
                        if not isinstance(e, faults.QueryCancelledError):
                            raise ticket.token.error() from e
                        raise
                    # Rung 0: class-aware preemption (ISSUE 18) — not a
                    # failure at all. The classed TPU gate asked this
                    # query to yield at a partition boundary; spill its
                    # live device buffers through the existing ladder,
                    # wait for the preemptor to drain, then re-collect
                    # on the SAME context: durable stage outputs serve
                    # from their materializations, so resumption loses
                    # no completed work and stays byte-identical.
                    if isinstance(e, faults.QueryPreemptedError) \
                            and ticket is not None:
                        preempt_count += 1
                        budget = max(int(self.conf.get(
                            C.PREEMPTION_MAX_PER_QUERY)), 0)
                        if preempt_count > budget:
                            # Budget spent: this query never yields
                            # again — starving a victim to death on
                            # repeated preemptions is worse than one
                            # slow interactive query.
                            ticket.token.preempt_enabled = False
                            ticket.token.clear_preempt()
                            continue
                        try:
                            # Chaos checkpoint: seeded faults can land
                            # exactly mid-preemption-spill (armed as
                            # kind@preempt.spill) — they re-enter the
                            # ladder below like any execution fault.
                            faults.fault_point("preempt.spill")
                            freed = 0
                            if bool(self.conf.get(
                                    C.PREEMPTION_SPILL_ENABLED)) \
                                    and ctx._catalog is not None:
                                # The victim vacates HBM for the
                                # preemptor via the same device->host
                                # ladder the OOM path uses (handles stay
                                # owned: nothing leaks, everything pages
                                # back on resume).
                                freed = ctx._catalog.handle_oom()
                            sched = SC.metrics_entry(ctx)
                            sched.add("preemptions", 1)
                            SC._record("preemptions")
                            monitoring.instant(
                                "query-preempted", "recovery",
                                qid=trace_qid,
                                args={"preemptor": e.preemptor or "-",
                                      "spilledBytes": freed,
                                      "count": preempt_count})
                            monitoring.telemetry.inc(
                                "srt_preemptions",
                                **{"class": str(ticket.qos_class
                                                or "-")})
                            log.warning(
                                "query %d preempted by a %s query "
                                "(%d/%d, spilled %d bytes); resuming "
                                "after the preemptor drains", trace_qid,
                                e.preemptor or "higher-priority",
                                preempt_count, budget, freed)
                            from spark_rapids_tpu.memory.stores import \
                                get_tpu_semaphore
                            sem = get_tpu_semaphore(max(
                                int(self.conf.get(
                                    C.CONCURRENT_TPU_TASKS)), 1))
                            t0_pre = _time.perf_counter()
                            # Blocks in class order until a permit
                            # would be ours again — i.e. the preemptor
                            # (and anything ranked ahead) drained.
                            # Cancellation/deadline aborts the wait via
                            # the token.
                            sem.wait_resume(ticket.token)
                            ticket.token.clear_preempt()
                            preempted_ms = (_time.perf_counter()
                                            - t0_pre) * 1e3
                            resumed = S.materialized_stage_count(
                                ctx, graph)
                            sched.add("preemptedMs", preempted_ms)
                            sched.add("resumedStages", resumed)
                            SC._record("preemptedMs", preempted_ms)
                            SC._record("resumedStages", resumed)
                            monitoring.instant(
                                "query-resumed", "recovery",
                                qid=trace_qid,
                                args={"preemptedMs":
                                      round(preempted_ms, 2),
                                      "resumedStages": resumed})
                            # Mid-resume chaos checkpoint
                            # (kind@preempt.resume).
                            faults.fault_point("preempt.resume")
                            continue
                        except faults.QueryCancelledError:
                            raise
                        except Exception as e2:
                            # A fault landed mid-spill or mid-resume:
                            # clear the preempt flag (the gate wait, if
                            # reached, already honored it) and re-enter
                            # the ladder with the NEW error — stage
                            # recompute / transient retry / fresh
                            # context apply exactly as for any
                            # execution-time fault.
                            ticket.token.clear_preempt()
                            e = e2
                    # Rung 1: lineage-scoped stage recompute.
                    st = S.stage_for_error(graph, e)
                    if st is not None and stage_recomputes < stage_budget:
                        S.invalidate_stage(ctx, st)
                        S.record_recompute(ctx, st)
                        if qrun is not None:
                            # The lost output is a REMOTE stage's spool:
                            # requeue its task so a worker rewrites it
                            # before the re-collect fetches again.
                            qrun.recompute(st.stage_id)
                        stage_recomputes += 1
                        log.warning(
                            "lost stage output (%s, recompute %d/%d); "
                            "recomputing only that stage: %s",
                            st.name, stage_recomputes, stage_budget, e)
                        continue
                    if not is_transient_error(e) or attempt >= max_retries:
                        raise
                    delay_ms = backoff_delay_ms(attempt, base_ms, max_ms,
                                                seed)
                    faults.record("retriesAttempted")
                    if graph is not None and not same_ctx_retry_used:
                        # Rung 2: retry on the same context — completed
                        # stages serve their durable outputs instead of
                        # recomputing.
                        same_ctx_retry_used = True
                        log.warning(
                            "transient device error (attempt %d/%d), "
                            "retrying on the same context in %.0fms "
                            "(materialized stage outputs are kept): %s",
                            attempt + 1, max_retries, delay_ms, e)
                        _time.sleep(delay_ms / 1000.0)
                    else:
                        # Rung 3: whole-query retry on a fresh context.
                        log.warning(
                            "transient device error (attempt %d/%d), "
                            "retrying query on a fresh context in "
                            "%.0fms: %s",
                            attempt + 1, max_retries, delay_ms, e)
                        _time.sleep(delay_ms / 1000.0)
                        if qrun is not None:
                            qrun.reset()
                        ctx.close()
                        ctx = ExecContext(self.conf, query=ticket)
                        install_bindings(ctx)
                        ctx.cache.setdefault("trace_query", trace_qid)
                        if ticket is not None:
                            mgr.register_context(ticket, ctx)
                        if qrun is not None:
                            qrun.install(ctx)
                    rec = query_metrics_entry(ctx, "Recovery")
                    rec.add("retriesAttempted", 1)
                    attempt += 1
        except BaseException as e:
            status = "error"
            err_text = f"{type(e).__name__}: {e}"
            raise
        finally:
            # The device idles through the teardown: a span of its own.
            with monitoring.span("finish", "query", qid=trace_qid):
                if ticket is not None:
                    # Teardown accounting BEFORE the context close captures
                    # the leak report: cancelled vs deadline-killed.
                    if ticket.token.cancelled():
                        sched = SC.metrics_entry(ctx)
                        if ticket.token.reason == "deadline exceeded":
                            sched.add("deadlineKills", 1)
                            SC._record("deadlineKills")
                            monitoring.instant(
                                "query-deadline-killed", "recovery",
                                qid=trace_qid)
                        else:
                            sched.add("cancelled", 1)
                            SC._record("cancelled")
                            monitoring.instant(
                                "query-cancelled", "recovery",
                                args={"reason": ticket.token.reason},
                                qid=trace_qid)
                    faults.set_query_token(None)
                    mgr.finish(ticket)
                if qrun is not None:
                    # Retire the dispatch state and the query's spool tree
                    # BEFORE the context close: sessions opened on it are
                    # keep_on_close, so the coordinator owns this cleanup.
                    qrun.finish()
                # Live telemetry + persistent event log, BEFORE the context
                # close (the record reads ctx.metrics and the trace ring).
                if ticket is not None and ticket.token.cancelled():
                    status = ("deadline"
                              if ticket.token.reason == "deadline exceeded"
                              else "cancelled")
                qos_class = ticket.qos_class if ticket is not None else None
                q_tenant = ticket.tenant if ticket is not None else None
                dur_ms = (_time.perf_counter() - t0_query) * 1e3
                lbls = {"class": str(qos_class or "-"),
                        "tenant": str(q_tenant or "-")}
                monitoring.telemetry.inc("srt_queries", status=status, **lbls)
                monitoring.telemetry.observe("srt_query_latency_ms", dur_ms,
                                             **lbls)
                monitoring.history.log_query(
                    self, ctx, query_id=trace_qid, status=status,
                    qos_class=qos_class, tenant=q_tenant,
                    duration_ms=dur_ms, error=err_text)
                # Metrics survive the collect for DataFrame.metrics().
                self.last_ctx = ctx
                if owned:
                    ctx.close()

    def host_fallback_nodes(self) -> List[str]:
        out = []

        def rec(m: NodeMeta):
            if not m.on_device:
                out.append(m.plan.name)
            for c in m.children:
                rec(c)
        rec(self.meta)
        return out


class Planner:
    """Converts a tagged logical plan into the physical Exec tree."""

    def __init__(self, conf: Optional[C.TpuConf] = None):
        self.conf = conf or C.TpuConf()

    # -- public --------------------------------------------------------------
    def plan(self, logical: LogicalPlan) -> PhysicalPlan:
        from spark_rapids_tpu.plan.pruning import (
            prune_columns, pushdown_filters)
        logical = pushdown_filters(prune_columns(merge_windows(logical)))
        self._force_perfile = _uses_input_file(logical)
        meta = wrap_and_tag(logical, self.conf)
        # Cost-based placement (plan/cost.py): flip whole maximal
        # subtrees to the host engine when the footer-stats estimate
        # says the sync floor can't amortize. Runs after tagging so
        # capability fallbacks already shaped ``on_device``.
        from spark_rapids_tpu.plan import cost as COST
        cost_report = COST.apply_placement(meta, self.conf)
        if self.conf.explain in ("ALL", "NOT_ON_GPU"):
            print("\n".join(meta.explain_lines(
                not_on_device_only=self.conf.explain == "NOT_ON_GPU")))
        root, side = self._convert(meta)
        # Process-global kernel cache: size it from this query's conf
        # (last writer wins — it is one process-wide pool, like the
        # reference's single RMM pool).
        from spark_rapids_tpu.ops import kernel_cache
        kernel_cache.cache().configure(
            int(self.conf.get(C.KERNEL_CACHE_MAX_ENTRIES)))
        # Persistent (on-disk) compilation cache: adopt an explicit
        # persistentDir (never over JAX_COMPILATION_CACHE_DIR) and
        # start counting its hits/misses; idempotent.
        kernel_cache.configure_persistent(
            str(self.conf.get(C.KERNEL_CACHE_PERSISTENT_DIR) or ""))
        num_fused = 0
        if bool(self.conf.get(C.STAGE_FUSION_ENABLED)):
            from spark_rapids_tpu.plan.fusion import fuse_stages
            root, num_fused = fuse_stages(root, side)
        phys = PhysicalPlan(root, side, meta, self.conf)
        phys.num_fused_stages = num_fused
        phys.cost_report = cost_report
        if self.conf.test_enabled:
            allowed = {s for s in str(self.conf.get(
                C.TEST_ALLOWED_NONTPU)).split(",") if s}
            bad = [n for n in phys.host_fallback_nodes()
                   if n not in allowed]
            if bad:
                raise AssertionError(
                    f"Query would execute on host: {bad} "
                    "(spark.rapids.sql.test.enabled)")
        return phys

    # -- helpers -------------------------------------------------------------
    def _bridge(self, child_exec: Exec, child_dev: bool,
                want_dev: bool) -> Exec:
        if child_dev == want_dev:
            return child_exec
        return HostToDeviceExec(child_exec) if want_dev \
            else DeviceToHostExec(child_exec)

    def _shuffle_partitions(self) -> int:
        if self._mesh_enabled():
            from spark_rapids_tpu.parallel.mesh_exchange import mesh_size
            # An explicit partition-count conf wins: the mesh exchange
            # folds/splits arbitrary logical partition counts onto the
            # device mesh (MeshExchangeExec fold pass), so the user's
            # fan-out no longer has to match the hardware shape.
            if self.conf.raw.get(C.SHUFFLE_PARTITIONS.key) is not None:
                return self.conf.get(C.SHUFFLE_PARTITIONS)
            return mesh_size()
        if self.conf.raw.get(C.SHUFFLE_PARTITIONS.key) is None:
            # Defaulted count on a single chip: a materialized exchange
            # only chunks work (all buckets run on device 0), and every
            # extra partition costs downstream per-partition host syncs
            # and dispatches.
            # One partition = one merge, fewest syncs. An explicit conf
            # value or a multi-device mesh keeps the configured fan-out.
            import jax
            if len(jax.devices()) == 1:
                return 1
        return self.conf.get(C.SHUFFLE_PARTITIONS)

    def _mesh_enabled(self) -> bool:
        # Transport SPI selection (parallel/transport/): the 'mesh'
        # transport lowers hash shuffles to MeshExchangeExec; everything
        # else plans the materialized exchange, which spools through the
        # selected transport at execution time.
        from spark_rapids_tpu.parallel import transport as T
        return T.transport_name(self.conf) == "mesh"

    def _hash_exchange(self, child: Exec, keys, n: int,
                       allow_coalesce: bool = False) -> Exec:
        """Hash shuffle: collective mesh exchange when a mesh is
        configured, else the materialized single-process exchange.
        ``allow_coalesce`` opts into AQE-lite partition merging — safe for
        aggregate/window exchanges, NOT for co-partitioned join inputs."""
        part = HashPartitioning(keys, n)
        if self._mesh_enabled():
            from spark_rapids_tpu.parallel.mesh_exchange import \
                MeshExchangeExec
            return MeshExchangeExec(child, part)
        return ShuffleExchangeExec(child, part,
                                   allow_coalesce=allow_coalesce)

    def _convert(self, meta: NodeMeta) -> Tuple[Exec, bool]:
        exec_, dev = self._convert_inner(meta)
        # Tag the physical root of every logical node's conversion with
        # the logical node's identity: explain_analyze joins observed
        # per-exec metrics to the cost model's per-logical-node
        # estimates through this (monitoring/analyze.py).
        exec_._logical_id = id(meta.plan)
        return exec_, dev

    def _convert_inner(self, meta: NodeMeta) -> Tuple[Exec, bool]:
        plan = meta.plan
        want_dev = meta.on_device
        kids = [self._convert(c) for c in meta.children]

        if isinstance(plan, L.InMemoryScan):
            return InMemorySourceExec(plan.schema, plan.partitions), want_dev
        if isinstance(plan, L.FileScan):
            from spark_rapids_tpu.io import make_scan_exec
            return make_scan_exec(
                plan, self.conf,
                force_perfile=getattr(self, "_force_perfile", False)
            ), want_dev
        if isinstance(plan, L.LogicalRange):
            return RangeExec(plan.start, plan.end, plan.step,
                             plan.num_partitions,
                             batch_rows=int(self.conf.get(
                                 C.BATCH_SIZE_ROWS))), want_dev
        if isinstance(plan, L.LogicalFilter):
            child, cdev = kids[0]
            cond = resolve(plan.condition, plan.child.schema)
            return FilterExec(self._bridge(child, cdev, want_dev),
                              cond), want_dev
        if isinstance(plan, L.LogicalProject):
            child, cdev = kids[0]
            projections = [(n, resolve(c, plan.child.schema))
                           for n, c in plan.projections]
            return ProjectExec(self._bridge(child, cdev, want_dev),
                               projections), want_dev
        if isinstance(plan, L.LogicalUnion):
            bridged = [self._bridge(ch, cdev, want_dev)
                       for ch, cdev in kids]
            return UnionExec(*bridged), want_dev
        if isinstance(plan, L.LogicalLimit):
            child, cdev = kids[0]
            child = self._bridge(child, cdev, want_dev)
            local = LocalLimitExec(child, plan.n)
            single = ShuffleExchangeExec(local, SinglePartitioning())
            return GlobalLimitExec(single, plan.n), want_dev
        if isinstance(plan, L.LogicalRepartition):
            child, cdev = kids[0]
            child = self._bridge(child, cdev, want_dev)
            if plan.keys:
                keys = [resolve(k, plan.child.schema) for k in plan.keys]
                if self._mesh_enabled():
                    # The mesh exchange folds the requested partition
                    # count onto the mesh, so the user's repartition
                    # fan-out is honored as-is.
                    from spark_rapids_tpu.parallel.mesh_exchange import \
                        MeshExchangeExec
                    return MeshExchangeExec(
                        child,
                        HashPartitioning(keys, plan.num_partitions)), \
                        want_dev
                part = HashPartitioning(keys, plan.num_partitions)
            else:
                part = RoundRobinPartitioning(plan.num_partitions)
            return ShuffleExchangeExec(child, part), want_dev
        if isinstance(plan, L.LogicalSort):
            child, cdev = kids[0]
            child = self._bridge(child, cdev, want_dev)
            orders = self._sort_orders(plan)
            # Global order: range-exchange into sorted partition ranges
            # first (Spark's requiredChildDistribution for global sort).
            ex = ShuffleExchangeExec(
                child, RangePartitioning(orders, self._shuffle_partitions()),
                allow_coalesce=want_dev)
            return SortExec(ex, orders), want_dev
        if isinstance(plan, L.LogicalAggregate):
            return self._convert_aggregate(plan, meta, kids[0], want_dev)
        if isinstance(plan, L.LogicalJoin):
            return self._convert_join(plan, meta, kids, want_dev)
        if isinstance(plan, L.LogicalWindow):
            return self._convert_window(plan, kids[0], want_dev)
        if isinstance(plan, L.LogicalGenerate):
            from spark_rapids_tpu.ops.generate import GenerateExec
            child, cdev = kids[0]
            child = self._bridge(child, cdev, want_dev)
            schema = plan.child.schema
            elements = [resolve(c, schema) for c in plan.elements]
            return GenerateExec(
                child, elements, position=plan.position, outer=plan.outer,
                element_name=plan.out_name,
                skip_nulls=plan.outer), want_dev
        if isinstance(plan, L.LogicalMapInPandas):
            from spark_rapids_tpu.ops.pandas_exec import MapInPandasExec
            child, cdev = kids[0]
            child = self._bridge(child, cdev, want_dev)
            return MapInPandasExec(child, plan.fn,
                                   plan.out_schema), want_dev
        if isinstance(plan, L.LogicalGroupedMapInPandas):
            from spark_rapids_tpu.ops.pandas_exec import \
                FlatMapGroupsInPandasExec
            child, cdev = kids[0]
            child = self._bridge(child, cdev, want_dev)
            child = self._pandas_group_exchange(child, plan.child.schema,
                                                plan.key_names, want_dev)
            return FlatMapGroupsInPandasExec(
                child, plan.key_names, plan.fn, plan.out_schema), want_dev
        if isinstance(plan, L.LogicalCoGroupedMapInPandas):
            from spark_rapids_tpu.ops.pandas_exec import \
                CoGroupedMapInPandasExec
            lch, ldev = kids[0]
            rch, rdev = kids[1]
            lch = self._bridge(lch, ldev, want_dev)
            rch = self._bridge(rch, rdev, want_dev)
            lch = self._pandas_group_exchange(
                lch, plan.children[0].schema, plan.left_keys, want_dev)
            rch = self._pandas_group_exchange(
                rch, plan.children[1].schema, plan.right_keys, want_dev)
            return CoGroupedMapInPandasExec(
                lch, rch, plan.left_keys, plan.right_keys, plan.fn,
                plan.out_schema), want_dev
        if isinstance(plan, L.LogicalAggInPandas):
            from spark_rapids_tpu.ops.pandas_exec import \
                AggregateInPandasExec
            child, cdev = kids[0]
            child = self._bridge(child, cdev, want_dev)
            child = self._pandas_group_exchange(child, plan.child.schema,
                                                plan.key_names, want_dev)
            return AggregateInPandasExec(child, plan.key_names,
                                         plan.aggs), want_dev
        raise NotImplementedError(f"cannot convert {plan.name}")

    def _pandas_group_exchange(self, child: Exec, schema, key_names,
                               want_dev: bool) -> Exec:
        """Co-partition a pandas-UDF child by its grouping keys so each
        partition holds whole groups (requiredChildDistribution of the
        grouped python execs). Host-engine children skip the exchange —
        the oracle runs single-partition."""
        if not want_dev:
            return child
        names = [n for n, _ in schema]
        keys = []
        for k in key_names:
            if k not in names:
                raise L.ResolutionError(f"unknown grouping key {k!r}")
            i = names.index(k)
            keys.append(BoundReference(i, schema[i][1]))
        return self._hash_exchange(child, keys, self._shuffle_partitions())

    def _convert_window(self, plan: "L.LogicalWindow", kid,
                        want_dev: bool) -> Tuple[Exec, bool]:
        """Window exec with its required distribution underneath
        (GpuWindowExec.scala:92: hash-partition by the PARTITION BY keys,
        or a single partition for empty PARTITION BY; ordering happens
        inside the kernel's frame sort)."""
        from spark_rapids_tpu.ops.window import (
            DenseRank, Lag, Lead, Rank, RowNumber, WindowAgg, WindowExec,
            WindowExprSpec, WindowFrame, WindowSpec)
        child, cdev = kid
        child = self._bridge(child, cdev, want_dev)
        schema = plan.child.schema
        win = plan.window
        pcols = [resolve(c, schema) for c in win.partition_cols]
        orders = []
        for o in win.order_cols:
            if o.node[0] == "sortorder":
                inner, asc, nf = o.node[1], o.node[2], o.node[3]
            else:
                inner, asc, nf = o, True, True
            from spark_rapids_tpu.ops.sort import SortOrder
            orders.append(SortOrder(resolve(inner, schema), asc, nf))
        spec = WindowSpec(pcols, orders)
        wx_specs = []
        for out_name, fn_col in plan.exprs:
            node = fn_col.node
            if node[0] == "winfn":
                kind, child_col, offset = node[1], node[2], node[3]
                if kind in ("rank", "dense_rank", "row_number") \
                        and not orders:
                    raise L.ResolutionError(f"{kind}() requires ORDER BY")
                if kind == "row_number":
                    fn = RowNumber()
                elif kind == "rank":
                    fn = Rank()
                elif kind == "dense_rank":
                    fn = DenseRank()
                elif kind == "lead":
                    fn = Lead(resolve(child_col, schema), offset)
                elif kind == "lag":
                    fn = Lag(resolve(child_col, schema), offset)
                else:
                    raise L.ResolutionError(f"unknown window fn {kind!r}")
            else:   # ("agg", kind, child)
                kind, child_col = node[1], node[2]
                agg_child = None if child_col is None \
                    else resolve(child_col, schema)
                if win.frame is not None:
                    _, start, end = win.frame
                    if (start is not None and start > 0) or \
                            (end is not None and end < 0):
                        raise L.ResolutionError(
                            "rows_between bounds must straddle the "
                            "current row")
                    frame = WindowFrame(
                        None if start is None else -start, end)
                elif orders:
                    # Spark default: RANGE UNBOUNDED..CURRENT ROW.
                    frame = WindowFrame(None, 0, running_with_peers=True)
                else:
                    frame = WindowFrame(None, None)   # whole partition
                fn = WindowAgg(kind, agg_child, frame)
            wx_specs.append(WindowExprSpec(out_name, fn, spec))
        if pcols:
            ex = self._hash_exchange(child, pcols,
                                     self._shuffle_partitions(),
                                     allow_coalesce=want_dev)
        else:
            ex = ShuffleExchangeExec(child, SinglePartitioning())
        return WindowExec(ex, wx_specs), want_dev

    def _sort_orders(self, plan: L.LogicalSort) -> List[SortOrder]:
        orders = []
        for o in plan.orders:
            if o.node[0] == "sortorder":
                inner, asc, nf = o.node[1], o.node[2], o.node[3]
            else:
                inner, asc, nf = o, True, True
            orders.append(SortOrder(resolve(inner, plan.child.schema),
                                    asc, nf))
        return orders

    def _convert_aggregate(self, plan: L.LogicalAggregate, meta: NodeMeta,
                           kid, want_dev: bool) -> Tuple[Exec, bool]:
        child, cdev = kid
        child = self._bridge(child, cdev, want_dev)
        schema = plan.child.schema
        group_by = [(n, resolve(c, schema)) for n, c in plan.group_by]
        aggs = [AggSpec(n, fn, distinct=getattr(fn, "is_distinct", False))
                for n, fn in ((n, resolve_agg(c, schema))
                              for n, c in plan.aggregates)]
        if plan.grouping is not None:
            if any(s.distinct for s in aggs):
                raise L.ResolutionError(
                    "DISTINCT aggregates under rollup/cube are unsupported")
            return self._convert_grouping_sets(
                plan.grouping, group_by, aggs, child, want_dev)
        if any(s.distinct for s in aggs):
            return self._convert_distinct_aggregate(
                group_by, aggs, child, want_dev)
        # Two-stage: partial -> exchange on group keys -> final
        # (aggregate.scala partial/final mode pair across the shuffle).
        return self._two_stage(group_by, aggs, child, want_dev)

    def _convert_grouping_sets(self, kind: str, group_by, aggs, child,
                               want_dev: bool) -> Tuple[Exec, bool]:
        """ROLLUP/CUBE via ExpandExec (GpuExpandExec.scala; Spark lowers
        grouping sets to Expand + Aggregate keyed by (keys...,
        grouping_id)): each input row is emitted once per grouping set,
        with aggregated-out keys NULLed and a grouping-id literal so a
        data NULL never merges with a subtotal NULL. A final projection
        drops the grouping id."""
        from spark_rapids_tpu.exprs.base import Literal
        nk = len(group_by)
        if kind == "rollup":
            # Set i keeps the first nk-i keys; gid bit per dropped key.
            masks = [(1 << i) - 1 for i in range(nk + 1)]
        else:
            masks = list(range(1 << nk))
        agg_children = []
        for s in aggs:
            agg_children.append(s.fn.child)
        names = [n for n, _ in group_by] + \
            [f"__agg_in{i}" for i in range(len(agg_children))] + \
            ["__grouping_id"]
        projections = []
        for mask in masks:
            proj = []
            for i, (_, e) in enumerate(group_by):
                dropped = mask & (1 << (nk - 1 - i)) if kind == "cube" \
                    else (i >= nk - bin(mask).count("1"))
                proj.append(Literal(e.data_type(), None) if dropped else e)
            for ce in agg_children:
                proj.append(ce if ce is not None
                            else Literal(dt.INT32, 1))
            proj.append(Literal(dt.INT64, mask))
            projections.append(proj)
        expand = ExpandExec(child, projections, names)
        # Re-key everything by ordinal over the expand output.
        ex_group = [(n, BoundReference(i, e.data_type()))
                    for i, (n, e) in enumerate(group_by)]
        ex_group.append(("__grouping_id", BoundReference(
            nk + len(agg_children), dt.INT64)))
        ex_aggs = []
        for i, s in enumerate(aggs):
            if s.fn.child is None:
                ex_aggs.append(s)
                continue
            ref = BoundReference(nk + i, s.fn.child.data_type())
            if isinstance(s.fn, (First, Last)):
                fn = type(s.fn)(ref, s.fn.ignore_nulls)
            else:
                fn = type(s.fn)(ref)
            ex_aggs.append(AggSpec(s.name, fn))
        final, dev = self._two_stage(ex_group, ex_aggs, expand, want_dev,
                                     allow_partial_skip=False)
        # Drop the grouping id from the output.
        out = [(n, BoundReference(i, e.data_type()))
               for i, (n, e) in enumerate(ex_group[:nk])]
        out += [(s.name, BoundReference(nk + 1 + i, s.fn.result_type))
                for i, s in enumerate(ex_aggs)]
        return ProjectExec(final, out), dev

    def _two_stage(self, group_by, aggs, child, want_dev: bool,
                   allow_partial_skip: bool = True) -> Tuple[Exec, bool]:
        """partial -> hash exchange -> final (shared by plain and
        grouping-set aggregates). Grouping-set plans keep the partial
        pass unconditionally: the expand multiplies rows N-fold, and the
        coarse rollup levels reduce massively even when the finest level
        does not — skipping would shuffle the whole expansion."""
        partial = HashAggregateExec(child, group_by, aggs, mode="partial")
        partial.allow_partial_skip = allow_partial_skip
        nkeys = len(group_by)
        if nkeys:
            keys = [BoundReference(i, e.data_type())
                    for i, (_, e) in enumerate(group_by)]
            ex = self._hash_exchange(partial, keys,
                                     self._shuffle_partitions(),
                                     allow_coalesce=want_dev)
        else:
            ex = ShuffleExchangeExec(partial, SinglePartitioning())
        final_groups = [
            (n, BoundReference(i, e.data_type()))
            for i, (n, e) in enumerate(group_by)]
        final = HashAggregateExec(ex, final_groups, aggs, mode="final")
        return final, want_dev

    def _convert_distinct_aggregate(self, group_by, aggs, child,
                                    want_dev: bool) -> Tuple[Exec, bool]:
        """DISTINCT aggregates via the reference's partial-merge mode
        combos (aggregate.scala:305 distinct handling):

          partial  group by (keys..., x) w/ partial non-distinct aggs
          -> hash exchange on keys (x rides along; co-location by keys
             suffices since dedup completes in the merge stage)
          -> merge  group by (keys..., x): dedup complete, buffers merged
          -> mixed_final group by keys: distinct aggs UPDATE over the
             now-unique x values, non-distinct aggs MERGE their buffers

        All distinct aggregates must share one input expression (Spark's
        planner has the same single-distinct-column restriction before
        falling back to expand-based rewrites)."""
        d_specs = [s for s in aggs if s.distinct]
        nd_specs = [s for s in aggs if not s.distinct]
        x_exprs = {s.fn.distinct_key for s in d_specs}
        if len(x_exprs) > 1:
            raise L.ResolutionError(
                "multiple DISTINCT aggregates must share the same input "
                f"expression; got {len(x_exprs)} different ones")
        x = d_specs[0].fn.child
        xt = x.data_type()
        nkeys = len(group_by)
        # Stage A: partial, keyed by (keys..., x).
        gb_a = list(group_by) + [("__distinct_x", x)]
        stage_a = HashAggregateExec(child, gb_a, nd_specs, mode="partial")
        # Exchange on the group keys only (zero keys -> single partition).
        if nkeys:
            keys = [BoundReference(i, e.data_type())
                    for i, (_, e) in enumerate(group_by)]
            ex = self._hash_exchange(stage_a, keys,
                                     self._shuffle_partitions(),
                                     allow_coalesce=want_dev)
        else:
            ex = ShuffleExchangeExec(stage_a, SinglePartitioning())
        # Stage B: merge, still keyed by (keys..., x) over the buffer
        # layout [keys..., x, nd buffers...].
        gb_b = [(n, BoundReference(i, e.data_type()))
                for i, (n, e) in enumerate(group_by)]
        gb_b.append(("__distinct_x", BoundReference(nkeys, xt)))
        stage_b = HashAggregateExec(ex, gb_b, nd_specs, mode="merge")
        # Stage C: mixed final keyed by keys; distinct fns read x at
        # ordinal nkeys of stage B's output.
        final_groups = [(n, BoundReference(i, e.data_type()))
                        for i, (n, e) in enumerate(group_by)]
        specs_c = []
        for s in aggs:
            if s.distinct:
                fn = type(s.fn)(BoundReference(nkeys, xt))
                specs_c.append(AggSpec(s.name, fn, distinct=True))
            else:
                specs_c.append(s)
        final = HashAggregateExec(stage_b, final_groups, specs_c,
                                  mode="mixed_final")
        return final, want_dev

    def _convert_join(self, plan: L.LogicalJoin, meta: NodeMeta, kids,
                      want_dev: bool) -> Tuple[Exec, bool]:
        (lch, ldev), (rch, rdev) = kids
        lch = self._bridge(lch, ldev, want_dev)
        rch = self._bridge(rch, rdev, want_dev)
        ls, rs = plan.children[0].schema, plan.children[1].schema
        lkeys = [resolve(k, ls) for k in plan.left_keys]
        rkeys = [resolve(k, rs) for k in plan.right_keys]
        cond = None
        if plan.condition is not None:
            cond = resolve(plan.condition, tuple(ls) + tuple(rs))
        if not lkeys:
            return BroadcastNestedLoopJoinExec(
                lch, rch, plan.join_type, cond), want_dev
        strategy = plan.strategy
        est = None
        if strategy == "auto":
            # Stats-driven choice (autoBroadcastJoinThreshold): broadcast
            # when the build side's estimated bytes fit the threshold,
            # else hash-shuffle both sides. Full outer always needs
            # co-partitioning.
            if plan.join_type == "full":
                strategy = "shuffle"
            else:
                threshold = int(self.conf.get(C.AUTO_BROADCAST_THRESHOLD))
                from spark_rapids_tpu.plan.pruning import estimate_bytes
                build_plan = plan.children[1] \
                    if plan.join_type != "right" else plan.children[0]
                est = estimate_bytes(build_plan)
                # Spark semantics: -1 disables auto-broadcast.
                strategy = "broadcast" \
                    if threshold >= 0 and est is not None \
                    and est <= threshold else "shuffle"
                meta.notes.append(
                    f"auto join strategy -> {strategy} (build side "
                    f"~{est if est is not None else '?'} bytes, "
                    f"threshold {threshold})")
        if strategy == "broadcast":
            return BroadcastHashJoinExec(
                lch, rch, lkeys, rkeys, plan.join_type, cond), want_dev
        n = self._shuffle_partitions()
        lex = self._hash_exchange(lch, lkeys, n)
        rex = self._hash_exchange(rch, rkeys, n)
        shj = ShuffledHashJoinExec(
            lex, rex, lkeys, rkeys, plan.join_type, cond)
        # Planning-time build estimate, kept for runtime re-planning's
        # estimate-vs-actual error metric (parallel/replan.py).
        shj.est_build_bytes = est
        return shj, want_dev
