"""Basic physical operators (ref: basicPhysicalOperators.scala, limit.scala,
GpuExpandExec.scala).

Project/Filter/Union/Coalesce/Range/Limits/Expand. Per-batch device kernels
are jitted once per (expression list, batch shape) via jax.jit closure
caching; the generator layer stays in Python (orchestration only).
"""

from __future__ import annotations

from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.batch import (
    DeviceBatch, DeviceColumn, bucket_capacity)
from spark_rapids_tpu.columnar.host import HostBatch, HostColumn, \
    all_valid as host_all_valid
from spark_rapids_tpu.exprs.base import (
    Expression, as_device_column, as_host_column, eval_exprs,
    eval_exprs_host)
from spark_rapids_tpu.exprs.bindslots import (
    bound_literals, device_bind_args, has_bind_slots, host_bind_args,
    resolve_bound)
from spark_rapids_tpu.exprs.nondeterministic import (
    EvalContext, eval_context, needs_eval_context)
from spark_rapids_tpu.ops import kernel_cache as kc
from spark_rapids_tpu.ops.base import (Exec, ExecContext, Schema,
    record_batch, timed)


def _project_host_closure(exprs, names):
    """Build the compiled host closure for a projection: one numpy ufunc
    pipeline pass per batch, bound literals riding as arguments."""
    def closure(hb: HostBatch, binds) -> HostBatch:
        if binds is not None:
            with bound_literals(binds):
                return eval_exprs_host(exprs, hb, names)
        return eval_exprs_host(exprs, hb, names)
    return closure


def _filter_host_closure(condition):
    """Build the compiled host closure for a filter: fused mask-then-
    gather — evaluate the condition once, AND in validity, and gather
    every column through the matrix-preserving HostColumn.filter (string
    columns keep their dense byte-matrix layout instead of decaying to
    per-row object arrays)."""
    def closure(hb: HostBatch, binds) -> HostBatch:
        if binds is not None:
            with bound_literals(binds):
                cond = as_host_column(condition.eval_host(hb), hb)
        else:
            cond = as_host_column(condition.eval_host(hb), hb)
        keep = np.asarray(cond.data, np.bool_) \
            & np.asarray(cond.validity, np.bool_)
        return hb.filter(keep)
    return closure


def _host_closure(ctx, op, kind, exprs, builder, binds):
    """Fetch the operator's compiled host closure through the host
    closure cache (ops/host_cache.py) — same fingerprint + bind-slot
    normalization as the device kernel cache, so bind-only plan-cache
    executions hit. Non-jittable expression trees (nondeterministic
    state) skip the cache like the device path does."""
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.ops import host_cache as hc
    if not all(e.jittable for e in exprs):
        return builder()
    fp = kc.fingerprint(tuple(exprs))
    schema_fp = kc.schema_fingerprint(op.children[0].schema)
    nbinds = 0 if binds is None else len(binds)
    return hc.lookup(kind, (fp, schema_fp, nbinds), builder,
                     ctx.metrics_for(op),
                     ctx.conf.get(C.HOST_CLOSURE_CACHE_MAX_ENTRIES))


def _input_file_key(op: Exec, partition: int, host: bool = False
                    ) -> Optional[str]:
    """Cache key under which this operator's (unique) descendant file scan
    publishes the current file path. Scans scope their keys by instance so
    two scans sharing a partition can't clobber each other; if this subtree
    has zero or multiple scans there is no well-defined "current input
    file" and input_file_name() yields '' (reference behavior for
    non-scan inputs, GpuInputFileBlock.scala)."""
    scans = []

    def walk(node):
        if type(node).__name__ == "FileScanExec":
            scans.append(node)
            return
        # An exchange breaks the batch<->file association: rows in a
        # post-shuffle batch mix every map partition's files, so
        # input_file_name() above one is '' (Spark behavior).
        if "Exchange" in type(node).__name__:
            return
        for ch in getattr(node, "children", ()):
            walk(ch)

    walk(op)
    if len(scans) != 1:
        return None
    prefix = "input_file_host" if host else "input_file"
    return f"{prefix}:{id(scans[0])}:{partition}"


def _contextual_device_loop(op: Exec, exprs: Sequence[Expression],
                            kernel, ctx: ExecContext, partition: int):
    """Drive ``kernel(batch)`` over the child's batches with an EvalContext
    (partition id / row base / input file) attached around each call.

    When every expression is jittable the compiled program takes the
    partition id and row base as *traced* int scalars — one compilation
    serves all partitions; the row base is carried as a device scalar with
    no host sync. Non-jittable trees run eagerly so per-batch host values
    (input_file_name) can be read at eval time.
    """
    m = ctx.metrics_for(op)
    jittable = all(e.jittable for e in exprs)
    binds = device_bind_args(ctx) if has_bind_slots(exprs) else None
    if jittable:
        def build():
            def kfn(b, pid, base, bv=()):
                with eval_context(EvalContext(pid, base)), \
                        bound_literals(bv):
                    out = kernel(b)
                return out, base + b.num_rows.astype(jnp.int64)
            return jax.jit(kfn)
        fp = kc.fingerprint(tuple(exprs))
        schema_fp = kc.schema_fingerprint(op.children[0].schema)
        pid = jnp.asarray(partition, jnp.int32)
        base = jnp.asarray(0, jnp.int64)
        for batch in op.children[0].execute_device(ctx, partition):
            entry = kc.lookup(
                "ctx-" + type(op).__name__,
                (fp, schema_fp, batch.capacity,
                 len(binds) if binds else 0), build, m)
            with timed(m):
                out, base = kc.call(entry, m, batch, pid, base,
                                    binds or ())
            record_batch(m, out)
            yield out
    else:
        base = 0
        key = _input_file_key(op, partition)
        for batch in op.children[0].execute_device(ctx, partition):
            ec = EvalContext(partition, base,
                             ctx.cache.get(key) if key else None)
            with timed(m), eval_context(ec), \
                    bound_literals(binds or ()):
                out = kernel(batch)
            base = base + batch.num_rows.astype(jnp.int64)
            record_batch(m, out)
            yield out


def _contextual_host_loop(op: Exec, kernel, ctx: ExecContext,
                          partition: int, exprs=()):
    base = 0
    key = _input_file_key(op, partition, host=True)
    binds = host_bind_args(ctx) if has_bind_slots(exprs) else ()
    for hb in op.children[0].execute_host(ctx, partition):
        ec = EvalContext(partition, base,
                         ctx.cache.get(key) if key else None)
        with eval_context(ec), bound_literals(binds):
            out = kernel(hb)
        yield out
        base += hb.num_rows


class ProjectExec(Exec):
    """Evaluate named expressions per batch (GpuProjectExec,
    basicPhysicalOperators.scala:66)."""

    def __init__(self, child: Exec,
                 projections: Sequence[Tuple[str, Expression]]):
        super().__init__(child)
        self.names = tuple(n for n, _ in projections)
        self.exprs = [e for _, e in projections]

    @property
    def schema(self) -> Schema:
        return tuple((n, e.data_type())
                     for n, e in zip(self.names, self.exprs))

    def execute_device(self, ctx, partition):
        exprs = list(self.exprs)
        if needs_eval_context(exprs):
            yield from _contextual_device_loop(
                self, exprs, lambda b: eval_exprs(exprs, b),
                ctx, partition)
            return
        m = ctx.metrics_for(self)
        jittable = all(e.jittable for e in exprs)
        fp = kc.fingerprint(tuple(exprs)) if jittable else None
        schema_fp = kc.schema_fingerprint(self.children[0].schema)
        binds = device_bind_args(ctx) if has_bind_slots(exprs) else None
        for batch in self.children[0].execute_device(ctx, partition):
            if jittable and binds is not None:
                # Bound literals ride as traced runtime inputs: one
                # compiled kernel serves every binding of these dtypes.
                def build():
                    def kfn(b, bv):
                        with bound_literals(bv):
                            return eval_exprs(exprs, b)
                    return jax.jit(kfn)
                entry = kc.lookup(
                    "project",
                    (fp, schema_fp, batch.capacity, len(binds)),
                    build, m)
                with timed(m):
                    out = kc.call(entry, m, batch, binds)
            elif jittable:
                entry = kc.lookup(
                    "project", (fp, schema_fp, batch.capacity),
                    lambda: jax.jit(lambda b: eval_exprs(exprs, b)), m)
                with timed(m):
                    out = kc.call(entry, m, batch)
            else:
                with timed(m), bound_literals(binds or ()):
                    out = eval_exprs(exprs, batch)
            # Projection preserves row count — keep the host-known hint so
            # downstream size consumers skip their device sync.
            out.rows_hint = batch.rows_hint
            record_batch(m, out)
            yield out

    def execute_host(self, ctx, partition):
        if needs_eval_context(self.exprs):
            yield from _contextual_host_loop(
                self, lambda hb: eval_exprs_host(self.exprs, hb, self.names),
                ctx, partition, self.exprs)
            return
        binds = host_bind_args(ctx) if has_bind_slots(self.exprs) else None
        fn = _host_closure(
            ctx, self, "project", self.exprs,
            lambda: _project_host_closure(list(self.exprs),
                                          tuple(self.names)),
            binds)
        for hb in self.children[0].execute_host(ctx, partition):
            yield fn(hb, binds)


class FilterExec(Exec):
    """Row filter via SELECTION VECTOR (GpuFilterExec analog).

    Rows are never moved: the condition mask ANDs into the batch's ``sel``
    (batch.py), costing one fused elementwise kernel instead of a packed
    compaction (~100-400ms/1M rows on the target chip). Downstream
    operators read liveness through ``row_mask()``; materialization
    happens at exchanges/concats/downloads."""

    def __init__(self, child: Exec, condition: Expression):
        super().__init__(child)
        self.condition = condition

    @property
    def schema(self) -> Schema:
        return self.children[0].schema

    def _kernel(self, batch: DeviceBatch) -> DeviceBatch:
        cond = as_device_column(self.condition.eval(batch), batch)
        keep = cond.data & cond.validity
        return batch.with_sel(keep)

    def _host_kernel(self, hb: HostBatch) -> HostBatch:
        return _filter_host_closure(self.condition)(hb, None)

    def execute_device(self, ctx, partition):
        condition = self.condition

        def kernel(b: DeviceBatch) -> DeviceBatch:
            cond = as_device_column(condition.eval(b), b)
            return b.with_sel(cond.data & cond.validity)

        if needs_eval_context([condition]):
            yield from _contextual_device_loop(
                self, [condition], kernel, ctx, partition)
            return
        m = ctx.metrics_for(self)
        jittable = condition.jittable
        fp = kc.fingerprint(condition) if jittable else None
        schema_fp = kc.schema_fingerprint(self.children[0].schema)
        binds = device_bind_args(ctx) \
            if has_bind_slots([condition]) else None
        for batch in self.children[0].execute_device(ctx, partition):
            if jittable and binds is not None:
                def build():
                    def kfn(b, bv):
                        with bound_literals(bv):
                            return kernel(b)
                    return jax.jit(kfn)
                entry = kc.lookup(
                    "filter",
                    (fp, schema_fp, batch.capacity, len(binds)),
                    build, m)
                with timed(m):
                    out = kc.call(entry, m, batch, binds)
            elif jittable:
                entry = kc.lookup(
                    "filter", (fp, schema_fp, batch.capacity),
                    lambda: jax.jit(kernel), m)
                with timed(m):
                    out = kc.call(entry, m, batch)
            else:
                with timed(m), bound_literals(binds or ()):
                    out = kernel(batch)
            record_batch(m, out)
            yield out

    def execute_host(self, ctx, partition):
        if needs_eval_context([self.condition]):
            yield from _contextual_host_loop(
                self, self._host_kernel, ctx, partition,
                [self.condition])
            return
        binds = host_bind_args(ctx) \
            if has_bind_slots([self.condition]) else None
        fn = _host_closure(
            ctx, self, "filter", [self.condition],
            lambda: _filter_host_closure(self.condition), binds)
        for hb in self.children[0].execute_host(ctx, partition):
            yield fn(hb, binds)


class UnionExec(Exec):
    """Concatenation of children's partitions (GpuUnionExec)."""

    def __init__(self, *children: Exec):
        super().__init__(*children)

    @property
    def schema(self) -> Schema:
        return self.children[0].schema

    def num_partitions(self, ctx) -> int:
        return sum(c.num_partitions(ctx) for c in self.children)

    def _locate(self, ctx, partition: int):
        for c in self.children:
            n = c.num_partitions(ctx)
            if partition < n:
                return c, partition
            partition -= n
        raise IndexError(partition)

    def execute_device(self, ctx, partition):
        child, p = self._locate(ctx, partition)
        yield from child.execute_device(ctx, p)

    def execute_host(self, ctx, partition):
        child, p = self._locate(ctx, partition)
        yield from child.execute_host(ctx, p)

    def prefetch_host(self, ctx, partition):
        # Union concatenates child partition spaces, so the prefetch must
        # translate the partition index before descending. Subtrees that
        # contain a stage boundary are skipped entirely: _locate's
        # num_partitions probe could otherwise trigger an exchange
        # materialization (AQE sizing) on a prefetch thread.
        from spark_rapids_tpu.parallel.stages import is_stage_boundary

        def boundary_free(op):
            return not is_stage_boundary(op) and \
                all(boundary_free(c) for c in op.children)

        if not all(boundary_free(c) for c in self.children):
            return
        child, p = self._locate(ctx, partition)
        child.prefetch_host(ctx, p)


class CoalescePartitionsExec(Exec):
    """Reduce partition count by concatenating streams (GpuCoalesceExec)."""

    def __init__(self, child: Exec, num_partitions: int = 1):
        super().__init__(child)
        self._n = num_partitions

    @property
    def schema(self) -> Schema:
        return self.children[0].schema

    def num_partitions(self, ctx) -> int:
        return min(self._n, self.children[0].num_partitions(ctx))

    def _sources(self, ctx, partition: int) -> List[int]:
        child_n = self.children[0].num_partitions(ctx)
        mine = self.num_partitions(ctx)
        return [p for p in range(child_n) if p % mine == partition]

    def execute_device(self, ctx, partition):
        for p in self._sources(ctx, partition):
            yield from self.children[0].execute_device(ctx, p)

    def execute_host(self, ctx, partition):
        for p in self._sources(ctx, partition):
            yield from self.children[0].execute_host(ctx, p)


class RangeExec(Exec):
    """range(start, end, step) source (GpuRangeExec,
    basicPhysicalOperators.scala:190)."""

    def __init__(self, start: int, end: int, step: int = 1,
                 num_partitions: int = 1, batch_rows: int = 1 << 20,
                 name: str = "id"):
        super().__init__()
        assert step != 0
        self.start, self.end, self.step = start, end, step
        self._parts = num_partitions
        self.batch_rows = batch_rows
        self._name = name

    @property
    def schema(self) -> Schema:
        return ((self._name, dt.INT64),)

    def num_partitions(self, ctx) -> int:
        return self._parts

    def _bounds(self, partition: int) -> Tuple[int, int]:
        total = max(0, -(-(self.end - self.start) // self.step))
        per = -(-total // self._parts)
        lo = min(per * partition, total)
        hi = min(lo + per, total)
        return lo, hi

    def execute_device(self, ctx, partition):
        lo, hi = self._bounds(partition)
        cap = bucket_capacity(min(self.batch_rows, max(hi - lo, 1)))
        idx = lo
        while idx < hi:
            n = min(cap, hi - idx)
            base = self.start + idx * self.step
            data = base + jnp.arange(cap, dtype=jnp.int64) * self.step
            validity = jnp.arange(cap, dtype=jnp.int32) < n
            data = jnp.where(validity, data, 0)
            col = DeviceColumn(dt.INT64, data, validity)
            yield DeviceBatch((col,), jnp.asarray(n, jnp.int32))
            idx += n

    def execute_host(self, ctx, partition):
        lo, hi = self._bounds(partition)
        idx = lo
        while idx < hi:
            n = min(self.batch_rows, hi - idx)
            base = self.start + idx * self.step
            data = base + np.arange(n, dtype=np.int64) * self.step
            col = HostColumn(dt.INT64, data, host_all_valid(n))
            yield HostBatch((self._name,), [col])
            idx += n


class LocalLimitExec(Exec):
    """Per-partition head(n) (GpuLocalLimitExec, limit.scala)."""

    def __init__(self, child: Exec, limit: int):
        super().__init__(child)
        # A plain int, or a bindslots.BindValue slot the plan cache
        # hoisted: resolved per execution against ctx's binding vector.
        self.limit = limit

    @property
    def schema(self) -> Schema:
        return self.children[0].schema

    def execute_device(self, ctx, partition):
        remaining = int(resolve_bound(self.limit, ctx))
        for batch in self.children[0].execute_device(ctx, partition):
            if remaining <= 0:
                break
            out = batch.head(remaining)
            # Advance the python-side budget. A host-known live count
            # (sort/shuffle outputs carry rows_hint) avoids the device
            # scalar pull the reference's limit pays per batch.
            if batch.rows_hint is not None:
                taken = min(batch.rows_hint, remaining)
                out.rows_hint = taken
            else:
                # A host sync; without a span of the limit's own the
                # trace charges it to the exchange that pulls this chain.
                from spark_rapids_tpu import monitoring
                with monitoring.op_span(self.name, "limit-count"):
                    taken = int(out.live_count())
            remaining -= taken
            yield out

    def execute_host(self, ctx, partition):
        remaining = int(resolve_bound(self.limit, ctx))
        for hb in self.children[0].execute_host(ctx, partition):
            if remaining <= 0:
                break
            n = min(remaining, hb.num_rows)
            cols = [HostColumn(c.dtype, c.data[:n], c.validity[:n])
                    for c in hb.columns]
            remaining -= n
            yield HostBatch(hb.names, cols)


class GlobalLimitExec(Exec):
    """Single-partition global limit; expects a 1-partition child
    (GpuGlobalLimitExec)."""

    def __init__(self, child: Exec, limit: int):
        super().__init__(child)
        self.limit = limit

    @property
    def schema(self) -> Schema:
        return self.children[0].schema

    def execute_device(self, ctx, partition):
        inner = LocalLimitExec(self.children[0], self.limit)
        yield from inner.execute_device(ctx, partition)

    def execute_host(self, ctx, partition):
        inner = LocalLimitExec(self.children[0], self.limit)
        yield from inner.execute_host(ctx, partition)


class ExpandExec(Exec):
    """GROUPING SETS expansion (GpuExpandExec.scala): each input row is
    emitted once per projection list."""

    def __init__(self, child: Exec,
                 projections: Sequence[Sequence[Expression]],
                 names: Sequence[str]):
        super().__init__(child)
        self.projections = [list(p) for p in projections]
        self.names = tuple(names)

    @property
    def schema(self) -> Schema:
        return tuple((n, e.data_type())
                     for n, e in zip(self.names, self.projections[0]))

    def execute_device(self, ctx, partition):
        m = ctx.metrics_for(self)
        for batch in self.children[0].execute_device(ctx, partition):
            # Every projection of a batch at once, as the fused stage
            # gives them: one span an input batch.
            with timed(m):
                outs = [eval_exprs(proj, batch) for proj in self.projections]
            count_expand(batch.capacity, [len(self.projections)])
            yield from outs

    def execute_host(self, ctx, partition):
        for hb in self.children[0].execute_host(ctx, partition):
            for proj in self.projections:
                yield eval_exprs_host(proj, hb, self.names)


def count_expand(capacity: int, fanouts: Sequence[int]) -> None:
    """The recorder's counters of one input batch of ``capacity`` rows
    going through Expands of ``fanouts`` projections, one after the
    other (``ExpandExec`` alone, or the members of a fused stage): every
    projection is at its input's capacity, so the counts are rows of
    capacity, known without a read of the device."""
    from spark_rapids_tpu import monitoring
    if not monitoring.enabled():
        return
    batches = 1
    for k in fanouts:
        monitoring.count("expandRowsIn", batches * capacity)
        monitoring.count("expandProjections", batches * k)
        batches *= k
        monitoring.count("expandRowsOut", batches * capacity)
