"""Whole-stage kernel fusion (the WholeStageCodegen / GpuCoalesceBatches
analog for this engine).

A ``FusedStageExec`` replaces a maximal run of contiguous, row-local,
jittable device operators (Project, Filter, LocalLimit, Expand — see
plan/fusion.py for the stage-break rules) with ONE exec whose per-batch
body is the composition of the member kernels, compiled as a single XLA
program through the process-global kernel cache. A Project->Filter->Project
chain is one dispatch instead of three, and nothing materializes between
the steps — the filter's selection vector flows straight into the next
projection inside the fused program.

LocalLimit is stateful across batches (a per-partition row budget); the
fused kernel threads the remaining budgets through as TRACED int32 scalars,
so one compilation serves the whole partition stream with no host sync.
Expand is 1->K: the fused body flat-maps, so a stage containing an Expand
returns K output batches per input batch (all from the same dispatch).

The member execs keep their original child links: the host-engine path and
``explain`` fallback reporting still see the unfused chain, and disabling
``spark.rapids.sql.stageFusion.enabled`` restores the original plan shape
exactly.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar.batch import DeviceBatch
from spark_rapids_tpu.exprs.base import as_device_column, eval_exprs
from spark_rapids_tpu.exprs.bindslots import (
    bound_literals, device_bind_args, has_bind_slots, resolve_bound)
from spark_rapids_tpu.ops import kernel_cache as kc
from spark_rapids_tpu.ops.base import (Exec, ExecContext, Schema,
    record_batch, timed)
from spark_rapids_tpu.ops.basic import count_expand


def _stage_specs(ops: Sequence[Exec]) -> List[Tuple[str, object]]:
    """Extract pure kernel descriptors from the member execs. The fused
    kernel closes over these (expression lists, limits), never over the
    exec objects — a cached kernel must not pin the plan subtree."""
    from spark_rapids_tpu.ops.basic import (
        ExpandExec, FilterExec, LocalLimitExec, ProjectExec)
    specs: List[Tuple[str, object]] = []
    nlimits = 0
    for op in ops:
        if isinstance(op, ProjectExec):
            specs.append(("project", tuple(op.exprs)))
        elif isinstance(op, FilterExec):
            specs.append(("filter", op.condition))
        elif isinstance(op, LocalLimitExec):
            specs.append(("limit", nlimits))
            nlimits += 1
        elif isinstance(op, ExpandExec):
            specs.append(("expand", tuple(tuple(p)
                                          for p in op.projections)))
        else:  # pragma: no cover - planner guards the member set
            raise TypeError(f"unfusible op {type(op).__name__}")
    return specs


def _spec_exprs(specs: Sequence[Tuple[str, object]]):
    """Every expression the fused stage evaluates (bind-slot probe)."""
    out = []
    for kind, payload in specs:
        if kind == "project":
            out.extend(payload)
        elif kind == "filter":
            out.append(payload)
        elif kind == "expand":
            out.extend(e for proj in payload for e in proj)
    return out


def _build_fused(specs: Sequence[Tuple[str, object]]):
    """Compose the member kernels into one batch->batches function.

    Signature: ``fused(batch, rems, binds) -> (outputs, rems_out)``
    where ``rems`` is a tuple of int32 scalars — one remaining-row
    budget per LocalLimit member — and ``binds`` the execution's bound
    literals (empty when the stage has no bind slots), both threaded
    through the trace as runtime inputs so one compilation serves the
    whole partition stream AND every literal binding."""

    def fused(batch: DeviceBatch, rems, binds=()):
        with bound_literals(binds):
            return _fused_body(batch, rems)

    def _fused_body(batch: DeviceBatch, rems):
        outs = [batch]
        rems = list(rems)
        for kind, payload in specs:
            if kind == "project":
                outs = [eval_exprs(payload, b) for b in outs]
            elif kind == "filter":
                nxt = []
                for b in outs:
                    cond = as_device_column(payload.eval(b), b)
                    nxt.append(b.with_sel(cond.data & cond.validity))
                outs = nxt
            elif kind == "expand":
                outs = [eval_exprs(proj, b)
                        for b in outs for proj in payload]
            else:  # limit
                i = payload
                r = rems[i]
                nxt = []
                for b in outs:
                    ob = b.head(r)
                    r = r - ob.live_count()
                    nxt.append(ob)
                rems[i] = r
                outs = nxt
        return tuple(outs), tuple(rems)

    return fused


class FusedStageExec(Exec):
    """One fused device stage. ``ops`` are the member execs in execution
    order (ops[0] innermost / applied first); ``source`` feeds the stage
    and is also ops[0]'s (original) child."""

    def __init__(self, ops: Sequence[Exec], source: Exec):
        super().__init__(source)
        self.ops = list(ops)
        self._specs = _stage_specs(self.ops)
        from spark_rapids_tpu.ops.basic import LocalLimitExec
        self._limits = [op.limit for op in self.ops
                        if isinstance(op, LocalLimitExec)]
        self._pure_project = all(k == "project" for k, _ in self._specs)
        self._expand_fanouts = [len(payload) for k, payload in self._specs
                                if k == "expand"]
        self._fp = kc.fingerprint(tuple(self._specs))
        self._has_binds = has_bind_slots(_spec_exprs(self._specs))

    @property
    def schema(self) -> Schema:
        return self.ops[-1].schema

    @property
    def name(self) -> str:
        inner = "->".join(type(o).__name__ for o in self.ops)
        return f"FusedStageExec[{inner}]"

    def execute_device(self, ctx: ExecContext, partition: int):
        m = ctx.metrics_for(self)
        m.values.setdefault("numFusedStages", 1)
        m.values.setdefault("numFusedOps", len(self.ops))
        schema_fp = kc.schema_fingerprint(self.children[0].schema)
        rems = tuple(jnp.asarray(int(resolve_bound(n, ctx)), jnp.int32)
                     for n in self._limits)
        binds = device_bind_args(ctx) if self._has_binds else ()
        specs = self._specs
        for batch in self.children[0].execute_device(ctx, partition):
            entry = kc.lookup(
                "fused-stage",
                (self._fp, schema_fp, batch.capacity, len(binds)),
                lambda: jax.jit(_build_fused(specs)), m)
            with timed(m):
                outs, rems = kc.call(entry, m, batch, rems, binds)
            if self._expand_fanouts:
                count_expand(batch.capacity, self._expand_fanouts)
            for out in outs:
                if self._pure_project:
                    # Row count unchanged by pure projection chains —
                    # keep the host-known hint so downstream size
                    # consumers skip their device sync.
                    out.rows_hint = batch.rows_hint
                record_batch(m, out)
                yield out

    def execute_host(self, ctx: ExecContext, partition: int):
        # The member chain is intact (fusion never rewires the originals'
        # links beyond the stage's source), so the host engine just runs
        # the outermost original op.
        yield from self.ops[-1].execute_host(ctx, partition)
