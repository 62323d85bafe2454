"""Sort operator (ref: GpuSortExec.scala + SortUtils.scala).

Full sort requires the whole partition as one batch (same RequireSingleBatch
restriction the reference has in v0.3); the device kernel is an LSD radix of
stable sorts over orderable uint32 words, each carrying the words of the
passes to come (ops/kernels.py ``radix_sort``) — the TPU replacement for
cuDF Table.orderBy.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import struct
from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.columnar.batch import (
    DeviceBatch, bucket_capacity, concat_batches)
from spark_rapids_tpu.columnar.host import HostBatch, HostColumn
from spark_rapids_tpu.exprs.base import Expression, as_device_column, \
    as_host_column
from spark_rapids_tpu.ops.base import (Exec, ExecContext, Schema,
    record_batch, timed)
from spark_rapids_tpu.ops import kernels


@dataclasses.dataclass
class SortOrder:
    """One sort key (Spark SortOrder analog). Defaults: asc, nulls first —
    Spark's ASC NULLS FIRST."""

    child: Expression
    ascending: bool = True
    nulls_first: bool = True


def coalesce_to_single_batch(batches: List[DeviceBatch]) -> DeviceBatch:
    """Concatenate a partition's batches into one (RequireSingleBatch goal,
    GpuCoalesceBatches.scala:120). Jitted so the scatter storm fuses."""
    from spark_rapids_tpu.columnar.batch import jit_concat_batches
    if len(batches) == 1:
        return batches[0]
    total_cap = sum(b.capacity for b in batches)
    return jit_concat_batches(batches, bucket_capacity(total_cap))


def sort_batch(batch: DeviceBatch, orders: Sequence[SortOrder],
               stable: bool = True) -> DeviceBatch:
    """Device kernel: fully sort one batch by the sort orders. Selected
    (live) rows sort to the front, so the output is dense (sel discharged
    by the gather)."""
    passes: List[jnp.ndarray] = []
    for o in orders:
        col = as_device_column(o.child.eval(batch), batch)
        passes.extend(kernels.sort_key_passes(col, o.ascending,
                                              o.nulls_first))
    perm = kernels.lex_sort_perm(passes, batch.row_mask(), batch.capacity,
                                 stable=stable)
    return batch.gather(perm, batch.live_count())


class _SpillableListSource(Exec):
    """Leaf serving an already-buffered list of catalog-registered batches
    (the sort's out-of-core staging area)."""

    def __init__(self, schema: Schema, spillables):
        super().__init__()
        self._schema = tuple(schema)
        self._spillables = spillables

    @property
    def schema(self) -> Schema:
        return self._schema

    def num_partitions(self, ctx) -> int:
        # One partition per buffered batch: the exchange's range-bounds
        # sampler reads 64 rows from EVERY partition's first batch, so
        # this shape samples the whole staged input, not just its head.
        return len(self._spillables)

    def execute_device(self, ctx, partition):
        from spark_rapids_tpu.memory.stores import PRIORITY_SHUFFLE_OUTPUT
        sb = self._spillables[partition]
        try:
            yield sb.get()
        finally:
            # Consumers abandon this generator mid-stream (the range
            # bounds sampler breaks after one batch); the staged entry
            # must drop back to spillable either way, or the whole
            # larger-than-HBM input ends up pinned ACTIVE.
            sb.release(PRIORITY_SHUFFLE_OUTPUT)

    def execute_host(self, ctx, partition):    # pragma: no cover
        raise AssertionError("device-only staging source")


def stage_spillables(ctx, child_iter):
    """Register a batch stream as catalog spillables (the out-of-core
    staging step shared by sort/window bucketing and grace joins).
    Returns (spillables, total device bytes)."""
    from spark_rapids_tpu.memory.stores import (
        PRIORITY_SHUFFLE_OUTPUT, SpillableBatch)
    spillables = []
    total_bytes = 0
    for b in child_iter:
        total_bytes += b.device_size_bytes()
        spillables.append(SpillableBatch(ctx.catalog, b,
                                         PRIORITY_SHUFFLE_OUTPUT))
    return spillables, total_bytes


def staged_exchange(spillables, schema, partitioning):
    """An exchange over already-staged spillables: the generic bucketing
    device for out-of-core operators. Sort/window feed it a
    RangePartitioning (equal keys share a bucket, buckets stream in
    range order); grace hash joins feed it a HashPartitioning over the
    join keys so BOTH sides bucket by the same key fingerprints
    (ops/join.py). ``allow_coalesce`` stays off — bucket identity is
    load-bearing for every caller."""
    from spark_rapids_tpu.parallel.exchange import ShuffleExchangeExec
    return ShuffleExchangeExec(_SpillableListSource(schema, spillables),
                               partitioning)


def out_of_core_partition(ctx, metrics, child_iter, schema,
                          split_orders: Sequence[SortOrder], batch_fn,
                          trace_cat: Optional[str] = None):
    """Shared out-of-core scaffold (SortExec's sample-sort shape, also
    used by partition-chunked windows): stage the partition's batches as
    catalog spillables; small partitions run ``batch_fn`` over one
    coalesced batch, larger ones range-split by ``split_orders`` through
    the exchange into bounded spillable buckets and run ``batch_fn`` per
    bucket (equal keys always share a bucket). Yields output batches.

    With ``trace_cat`` the steps after the child's pull are spans of
    that category (``gather``: the staged batches into one; ``split``:
    the range exchange of the out-of-core path; ``compute``: the
    dispatch of ``batch_fn``), never nested in one another and never
    held across a yield, so their sum is a time; a split also counts
    ``<trace_cat>OutOfCoreSplits``."""
    from spark_rapids_tpu import monitoring
    from spark_rapids_tpu.memory.oom import retry_on_oom
    from spark_rapids_tpu.parallel.partitioning import RangePartitioning
    m = metrics

    def phase(name):
        if trace_cat is None:
            return contextlib.nullcontext()
        return monitoring.span(name, trace_cat)

    spillables, total_bytes = stage_spillables(ctx, child_iter)
    if not spillables:
        return
    bucket_budget = max(ctx.catalog.device_budget // 3, 1 << 16)
    if total_bytes <= bucket_budget or not split_orders:
        with phase("gather"):
            batches = [sb.get() for sb in spillables]
            single = coalesce_to_single_batch(batches)
            for sb in spillables:
                sb.close()
        with phase("compute"), timed(m):
            out = retry_on_oom(batch_fn, single)
        record_batch(m, out)
        yield out
        return
    nb = max(2, -(-total_bytes // bucket_budget))
    m.add("outOfCoreBuckets", nb)
    if trace_cat is not None:
        monitoring.count(trace_cat + "OutOfCoreSplits")
    ex = staged_exchange(spillables, schema,
                         RangePartitioning(list(split_orders), nb))
    try:
        for p in range(nb):
            # The first bucket's pull materializes the whole exchange.
            with phase("split"):
                bucket = list(ex.execute_device(ctx, p))
            if not bucket:
                continue
            with phase("gather"):
                single = coalesce_to_single_batch(bucket)
            with phase("compute"), timed(m):
                out = retry_on_oom(batch_fn, single)
            record_batch(m, out)
            yield out
    finally:
        for sb in spillables:
            sb.close()


class SortExec(Exec):
    """Per-partition full sort (global order requires a range exchange
    upstream, as in Spark).

    OUT-OF-CORE (beyond the reference's v0.3 RequireSingleBatch,
    GpuSortExec.scala:50 — SURVEY §5.7's "thing to beat"): when the
    partition exceeds a fraction of the device budget the sort becomes a
    device sample-sort via :func:`out_of_core_partition` — bounded
    buckets sort independently and stream in range order. Peak HBM is
    one bucket + one in-flight batch; the rest rides the spill tiers."""

    def __init__(self, child: Exec, orders: Sequence[SortOrder]):
        super().__init__(child)
        self.orders = list(orders)

    @property
    def schema(self) -> Schema:
        return self.children[0].schema

    def _sort_fn(self, ctx):
        from spark_rapids_tpu import config as C
        from spark_rapids_tpu.ops import kernel_cache as kc
        stable = bool(ctx.conf.get(C.STABLE_SORT))
        orders = list(self.orders)
        if not all(o.child.jittable for o in orders):
            return lambda b: sort_batch(b, orders, stable=stable)
        m = ctx.metrics_for(self)
        fp = kc.fingerprint(tuple(orders))
        schema_fp = kc.schema_fingerprint(self.schema)

        def fn(b: DeviceBatch) -> DeviceBatch:
            entry = kc.lookup(
                "sort", (fp, stable, schema_fp, b.capacity),
                lambda: jax.jit(
                    lambda bb: sort_batch(bb, orders, stable=stable)), m)
            return kc.call(entry, m, b)
        return fn

    def execute_device(self, ctx, partition):
        yield from out_of_core_partition(
            ctx, ctx.metrics_for(self),
            self.children[0].execute_device(ctx, partition),
            self.schema, self.orders, self._sort_fn(ctx))

    def execute_host(self, ctx, partition):
        hbs = list(self.children[0].execute_host(ctx, partition))
        if not hbs:
            return
        from spark_rapids_tpu.columnar.host import concat_host_batches
        yield sort_host_batch(concat_host_batches(hbs), self.orders)


def host_sort_indices(hb: HostBatch,
                      orders: Sequence[SortOrder]) -> np.ndarray:
    """Stable row permutation sorting ``hb`` under Spark semantics
    (float total order via sign-flipped raw bits — every NaN canonical
    and greatest, -0.0 < 0.0 — plus per-key null ordering).

    Vectorized: each order key becomes two np.lexsort planes — the
    null-rank plane (always ascending: null placement never flips with
    the key direction, matching the row-oracle this replaced) and the
    type-aware int64 code from encode_sort_key, bit-inverted for descending
    (~x reverses int64 order with no INT64_MIN overflow). np.lexsort is
    stable, so ties keep input order exactly like the python sort."""
    from spark_rapids_tpu.columnar.host import encode_sort_key
    planes = []
    for o in orders:
        col = as_host_column(o.child.eval_host(hb), hb)
        valid = np.asarray(col.validity, np.bool_)
        null_rank = (valid if o.nulls_first else ~valid).astype(np.int8)
        code = encode_sort_key(col)
        if not o.ascending:
            code = np.where(valid, ~code, np.int64(0))
        planes.append((null_rank, code))
    # np.lexsort keys run last-to-first, so emit least-significant first.
    lex = []
    for null_rank, code in reversed(planes):
        lex.append(code)
        lex.append(null_rank)
    return np.lexsort(lex)


def sort_host_batch(hb: HostBatch, orders: Sequence[SortOrder]) -> HostBatch:
    """Host sort with Spark semantics (NaN greatest, null ordering)."""
    order = host_sort_indices(hb, orders)
    return hb.take(order)


@functools.total_ordering
class _Rev:
    """Reverses comparison for descending host sort keys."""

    def __init__(self, v):
        self.v = v

    def __eq__(self, other):
        return self.v == other.v

    def __lt__(self, other):
        return other.v < self.v
