"""Runtime adaptive re-planning (ISSUE 7 tentpole, part b).

The stats-only AQE-lite (autoBroadcastJoinThreshold over footer
estimates, post-shuffle partition coalescing) plans from ESTIMATES; this
module re-plans mid-query from EXACT materialized sizes, the way the
reference's GpuCustomShuffleReaderExec.scala:132 reader rebuilds the
remaining plan once a shuffle's map output statistics exist.

Flow (driven from the top-level device collect funnel, ops/base.py,
before stage prematerialization):

1. Walk the physical plan's device regions for shuffled hash joins whose
   both inputs are materialized exchanges (the stage-DAG boundaries of
   parallel/stages.py), bottom-up so inner joins decide first.
2. For each candidate, materialize ONLY the build-side exchange — its
   transport session records the exact byte sizes of what it wrote
   (`ShuffleSession.record_shard_bytes`, the size-observation hook), and
   of those the bytes of LIVE rows: the threshold is stated in bytes of
   data, as the planner's estimate is, and the padding of a shard's
   capacity bucket (up to a third) is none.
3. When the observed build size fits ``autoBroadcastJoinThreshold``, the
   join DEMOTES to a broadcast hash join: a rewritten subtree whose
   build input is the already-materialized exchange (served as broadcast
   shards) and whose probe input is the probe exchange's CHILD — the
   probe side never shuffles at all, which is the win. The fusion pass
   re-runs over the rewritten subtree (idempotent where nothing new
   fuses), and the skipped probe exchange is flagged so stage
   prematerialization does not shuffle it anyway.
4. Decisions are per-query (keyed in ``ctx.cache``), so the cached
   physical plan is untouched, the host oracle path never sees them, and
   lineage recovery still maps a lost build shard to the ORIGINAL
   exchange's stage: a recompute after ``stage_invalidate`` re-observes
   the sizes and re-derives the same demotion deterministically.

Counters land in the query's ``Cost@query`` metrics entry
(``replanChecks`` / ``joinDemotions`` / ``replanObservedBytes`` (live
rows, what the rule reads) / ``replanFootprintBytes`` (the same shards
with their padding, what it read until PR 35) / ``replanUncountedShards``
(shards in the first at their footprint, for want of a row count: 0
where the rule read no padding) / ``estimateErrorPct``) and, but for the
last, in the process-global cost counters (plan/cost.py ``counters()``).
"""

from __future__ import annotations

import logging
from typing import List, Tuple

from spark_rapids_tpu import config as C

_LOG = logging.getLogger("spark_rapids_tpu.replan")


def _metrics(ctx):
    from spark_rapids_tpu.ops.base import query_metrics_entry
    return query_metrics_entry(ctx, "Cost")


def decision_key(join) -> str:
    return f"replan:{id(join):x}"


def _candidates(root) -> List[Tuple[object, bool]]:
    """(join, on_device) for every shuffled-hash-join over two
    materialized exchanges, bottom-up (inner joins first), restricted to
    device regions — host islands run the oracle engine verbatim."""
    from spark_rapids_tpu.ops.base import (DeviceToHostExec,
                                           HostToDeviceExec)
    from spark_rapids_tpu.ops.join import ShuffledHashJoinExec
    from spark_rapids_tpu.parallel.exchange import ShuffleExchangeExec
    out: List[Tuple[object, bool]] = []

    def walk(op, device: bool):
        if isinstance(op, DeviceToHostExec):
            kid_dev = [True]
        elif isinstance(op, HostToDeviceExec):
            kid_dev = [False]
        else:
            kid_dev = [device] * len(op.children)
        for c, d in zip(op.children, kid_dev):
            walk(c, d)
        if device and type(op) is ShuffledHashJoinExec and \
                op.join_type != "full" and \
                all(isinstance(c, ShuffleExchangeExec)
                    for c in op.children):
            out.append((op, device))

    walk(root, True)
    return out


def plan_adaptive(ctx, root) -> None:
    """Decide demotions for this query. Idempotent per context: re-runs
    after a lineage-scoped stage recompute re-use cached decisions (and
    a recomputed build exchange re-derives the same one)."""
    from spark_rapids_tpu.plan import cost as COST
    if ctx.cache.get("engine") != "device":
        return
    if not bool(ctx.conf.get(C.AQE_REPLAN)):
        return
    threshold = int(ctx.conf.get(C.AUTO_BROADCAST_THRESHOLD))
    if threshold < 0:       # Spark semantics: -1 disables auto-broadcast
        return
    for join, _ in _candidates(root):
        key = decision_key(join)
        if key in ctx.cache:
            continue
        m = _metrics(ctx)
        m.add("replanChecks", 1)
        COST._record("replanChecks")
        build_right = join.join_type != "right"
        build_ex = join.children[1] if build_right else join.children[0]
        probe_ex = join.children[0] if build_right else join.children[1]
        observed, footprint, uncounted = build_ex.observed_sizes(ctx)
        for name, n in (("replanObservedBytes", observed),
                        ("replanFootprintBytes", footprint),
                        ("replanUncountedShards", uncounted)):
            m.add(name, n)
            COST._record(name, n)
        est = getattr(join, "est_build_bytes", None)
        if est is not None and observed > 0:
            m.add("estimateErrorPct",
                  abs(est - observed) * 100.0 / observed)
        if observed > threshold:
            ctx.cache[key] = None
            continue
        delegate = _demote(ctx, join, build_ex, probe_ex, build_right)
        ctx.cache[key] = delegate
        ctx.cache[f"replan-skip:{id(probe_ex):x}"] = True
        m.add("joinDemotions", 1)
        COST._record("joinDemotions")
        from spark_rapids_tpu import monitoring
        monitoring.instant(
            "join-demotion", "replan",
            args={"join": join.name, "observedBytes": observed,
                  "threshold": threshold})
        _LOG.warning(
            "runtime re-plan: demoting %s to broadcast (observed build "
            "side %d bytes <= threshold %d; probe shuffle skipped)",
            join.name, observed, threshold)


def _demote(ctx, join, build_ex, probe_ex, build_right: bool):
    """Rewritten subtree for one demotion: a BroadcastHashJoinExec whose
    build child is the ALREADY-MATERIALIZED exchange (its reduce buckets
    stream as broadcast shards, zero re-shuffling) and whose probe child
    is the probe exchange's unshuffled input. Keys/condition carry over —
    both sides' schemas are unchanged."""
    from spark_rapids_tpu.ops.join import BroadcastHashJoinExec
    probe_child = probe_ex.children[0]
    if build_right:
        left, right = probe_child, build_ex
    else:
        left, right = build_ex, probe_child
    delegate = BroadcastHashJoinExec(
        left, right, join.left_keys, join.right_keys, join.join_type,
        join.condition)
    # Re-run the fusion pass over the rewritten subtree (the ISSUE 7
    # contract): already-fused runs below are fixed points, so this only
    # fuses shapes the exchange removal newly exposed.
    if bool(ctx.conf.get(C.STAGE_FUSION_ENABLED)):
        from spark_rapids_tpu.plan.fusion import fuse_stages
        delegate, refused = fuse_stages(delegate, True)
        if refused:
            _metrics(ctx).add("replanRefusions", refused)
    return delegate


def demoted(ctx, join):
    """The delegate for ``join`` in this query, or None (no demotion /
    replan never ran / host engine)."""
    return ctx.cache.get(decision_key(join))
