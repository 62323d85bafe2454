"""Stage-graph recovery: lineage-scoped fault tolerance (ISSUE 3).

Spark's core resilience story is *lineage*: when a task's input shuffle
data is lost, only the stages that produced the lost partitions recompute
— never the whole job (Zaharia et al., RDDs, NSDI 2012). This engine's
per-query materializations (shuffle buckets, broadcast singles, mesh
shards) already live in the ExecContext, so the same story falls out of
two pieces:

1. **A stage DAG over the physical plan.** :func:`build_stage_graph`
   splits the Exec tree at exchange/broadcast boundaries (any exec with a
   ``stage_invalidate`` method is a boundary). Each :class:`Stage` owns
   the operators between its boundary exchange and the next boundaries
   below; ``parents`` point at the stages whose durable outputs feed it —
   the lineage edges recovery walks.

2. **Durable, invalidatable stage outputs.** Every exchange registers its
   materialization through the shuffle-transport SPI
   (``parallel/transport/``): spillable catalog handles on the
   ``inprocess``/``mesh`` transports (``memory/stores.py``
   SpillableBatch — bounded by the memory ladder, CRC-framed via
   ``wire.frame_blob`` once spilled to disk), CRC-framed spool files on
   the cross-process ``hostfile`` transport — and exposes
   ``stage_invalidate(ctx)`` to drop it. Because re-running a collect on
   the SAME context serves every still-cached materialization instead of
   recomputing it, *invalidate-one-stage + re-collect* IS partition-scoped
   recovery: only the lost stage (and the never-materialized result
   stage above it) re-executes; sibling stages' scans never run again.
   A lost or persistently-corrupt REMOTE shard behaves identically: the
   transport fetch raises owner-tagged (``ShardLostError`` /
   ``WireCorruptionError`` with ``fault_owner``), :func:`stage_for_error`
   maps it to the owning exchange's stage, and the recompute REWRITES
   the shard at rest — one stage, never a whole-query retry.

The same DAG also powers the pipelined executor (parallel/pipeline.py,
ISSUE 4): stages whose parents are all materialized are *independent*,
so their boundary exchanges' ``stage_prematerialize`` hooks run
concurrently (the build- and probe-side scans of a shuffled join
materialize in parallel), bounded by
``spark.rapids.sql.pipeline.maxConcurrentStages``.

The planner's retry ladder (plan/planner.py) demotes through:
watchdog partition retry (ops/base.py) -> stage recompute (this module)
-> whole-query retry on a fresh context (only when the loss cannot be
attributed to a stage — "a root stage is gone" — or the recompute budget
is spent). Every recompute bumps the ``stageRecomputes`` counter (plus a
per-stage ``stageRecomputes.stage<N>`` detail) through
spark_rapids_tpu.faults, surfacing in ``DataFrame.metrics()`` and
``faults.counters()``.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Dict, List, Optional

_LOG = logging.getLogger("spark_rapids_tpu.stages")


def is_stage_boundary(op) -> bool:
    """An exec whose materialized output is a durable stage output —
    shuffle, broadcast and mesh exchanges all expose stage_invalidate."""
    return callable(getattr(op, "stage_invalidate", None))


@dataclasses.dataclass
class Stage:
    """One stage: the subtree between a boundary exchange (whose
    materialization is this stage's output; None for the result stage)
    and the child boundaries feeding it."""

    stage_id: int
    boundary: Optional[object]
    ops: List[object] = dataclasses.field(default_factory=list)
    parents: List[int] = dataclasses.field(default_factory=list)

    @property
    def name(self) -> str:
        root = "result" if self.boundary is None else \
            type(self.boundary).__name__
        return f"Stage#{self.stage_id}<{root}>"


class StageGraph:
    """Stage DAG of one physical plan: stages keyed by id, plus the
    exchange-exec-id -> stage index recovery uses to map a lost-output
    error back to the stage that owns the lost materialization."""

    def __init__(self):
        self.stages: Dict[int, Stage] = {}
        self.by_exchange: Dict[int, int] = {}
        self.root_stage_id: int = 0

    def __len__(self) -> int:
        return len(self.stages)

    def stage_of_exchange(self, exchange_id: int) -> Optional[Stage]:
        sid = self.by_exchange.get(exchange_id)
        return None if sid is None else self.stages.get(sid)

    def pretty(self) -> str:  # pragma: no cover - debug/explain aid
        lines = []
        for st in self.stages.values():
            members = ", ".join(type(o).__name__ for o in st.ops)
            lines.append(f"{st.name} parents={st.parents} [{members}]")
        return "\n".join(lines)


def build_stage_graph(root) -> StageGraph:
    """Split the physical plan at exchange boundaries into the stage DAG
    (Spark DAGScheduler's stage cut, applied to this engine's tree)."""
    g = StageGraph()

    def new_stage(boundary) -> Stage:
        st = Stage(len(g.stages), boundary)
        g.stages[st.stage_id] = st
        if boundary is not None:
            g.by_exchange[id(boundary)] = st.stage_id
        return st

    def walk(op, stage: Stage):
        stage.ops.append(op)
        for ch in op.children:
            if is_stage_boundary(ch):
                child = new_stage(ch)
                stage.parents.append(child.stage_id)
                walk(ch, child)
            else:
                walk(ch, stage)

    result = new_stage(None)
    g.root_stage_id = result.stage_id
    if is_stage_boundary(root):
        # Degenerate plan rooted at an exchange: the result stage is
        # empty and the root exchange owns its own (recoverable) stage.
        child = new_stage(root)
        result.parents.append(child.stage_id)
        walk(root, child)
    else:
        walk(root, result)
    return g


def stage_for_error(graph: Optional[StageGraph], e) -> Optional[Stage]:
    """Map a failure to the stage whose durable output is gone. Only
    errors tagged with a ``fault_owner`` (the owning exchange's id — set
    by injection sites and by the checksum-failure wrappers on durable
    reads) are attributable; anything else means a root/unattributable
    loss and the caller falls back to the whole-query retry."""
    if graph is None:
        return None
    owner = getattr(e, "fault_owner", None)
    if owner is None:
        return None
    return graph.stage_of_exchange(owner)


def invalidate_stage(ctx, stage: Stage) -> None:
    """Drop the stage's durable output from the context (cache entries +
    catalog registrations) so the next execution recomputes it from its
    parents' still-materialized outputs."""
    if stage.boundary is not None:
        stage.boundary.stage_invalidate(ctx)
    _LOG.warning("lineage recovery: invalidated %s; recomputing it from "
                 "its parent stages on the next attempt", stage.name)


def record_recompute(ctx, stage: Stage) -> None:
    """Bump the recovery counters for one stage recompute: the global
    aggregate, the per-stage detail, the
    query's Recovery metrics entry, and a flight-recorder instant so
    the rework shows on the trace timeline."""
    from spark_rapids_tpu import faults, monitoring
    from spark_rapids_tpu.ops.base import query_metrics_entry
    faults.record("stageRecomputes")
    faults.record(f"stageRecomputes.stage{stage.stage_id}")
    query_metrics_entry(ctx, "Recovery").add("stageRecomputes", 1)
    monitoring.instant("stage-recompute", "recovery",
                       args={"stage": stage.name})


def materialized_stage_count(ctx, graph: Optional[StageGraph]) -> int:
    """How many boundary stages still hold a durable, context-cached
    output right now. Class-aware preemption (plan/planner.py) reads
    this when a preempted query resumes: every stage counted here is
    served from its materialization instead of recomputing — the
    ``resumedStages`` counter that proves a suspension lost no work."""
    if graph is None or ctx is None:
        return 0
    n = 0
    for st in graph.stages.values():
        b = st.boundary
        if b is None:
            continue                    # the result stage is never durable
        key_fn = getattr(b, "_cache_key", None)
        if callable(key_fn):
            keys = (key_fn(True), key_fn(False))
        else:                           # mesh exchanges key by exec id
            keys = (f"meshx:{id(b):x}", f"meshx-host:{id(b):x}")
        if any(k in ctx.cache for k in keys):
            n += 1
    return n
