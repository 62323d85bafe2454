"""Device-resident columnar batches as JAX pytrees.

This is the TPU re-design of the reference's columnar runtime
(sql-plugin GpuColumnVector.java / ColumnarBatch over cuDF device columns).
cuDF allocates exact-size device buffers per kernel result; XLA instead wants
static shapes, so a batch here is a *fixed-capacity* set of device arrays plus
a runtime ``num_rows`` scalar — rows past ``num_rows`` are padding. Capacities
come from a power-of-two bucket ladder so the number of distinct compiled
programs stays bounded (SURVEY.md §7 "hard parts" #1).

Layout per column:
- fixed-width type T: ``data (capacity,) T`` + ``validity (capacity,) bool``
- string: ``data (capacity, width) uint8`` (zero-padded) +
  ``lengths (capacity,) int32`` + validity. Fixed-width padded bytes are the
  TPU-first answer to cuDF's offsets+chars: every string op becomes a dense
  (N, W) vector op on the VPU instead of a gather over a ragged buffer.

Null semantics: ``validity[i]`` True means non-null. Padding rows have
validity False and zeroed data so results stay deterministic.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar.dtypes import DataType

MIN_CAPACITY = 8


def bucket_capacity(n: int) -> int:
    """Round row count up to the capacity bucket ladder.

    Rungs at 2^k and 3*2^(k-1) (8, 12, 16, 24, 32, ...): every row-movement
    kernel's cost scales with CAPACITY on this chip, so the plain
    power-of-two ladder's worst case (~2x padding) costs real wall time —
    e.g. a 750k-row parquet row group padded to 1M pays 33% on every op.
    Mid rungs cap the waste at ~33% for 2x the compiled-program count
    (amortized by the persistent compilation cache)."""
    cap = MIN_CAPACITY
    while cap < n:
        if cap * 3 // 2 >= n:
            return cap * 3 // 2
        cap *= 2
    return cap


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DeviceColumn:
    """One column of a device batch. A pytree: arrays are leaves, dtype is aux."""

    dtype: DataType
    data: jax.Array            # (capacity,) or (capacity, width) uint8 for strings
    validity: jax.Array        # (capacity,) bool, True = non-null
    lengths: Optional[jax.Array] = None   # (capacity,) int32, strings only

    # -- pytree protocol -----------------------------------------------------
    def tree_flatten(self):
        if self.dtype.is_string:
            return (self.data, self.validity, self.lengths), self.dtype
        return (self.data, self.validity), self.dtype

    @classmethod
    def tree_unflatten(cls, dtype, leaves):
        if dtype.is_string:
            data, validity, lengths = leaves
            return cls(dtype, data, validity, lengths)
        data, validity = leaves
        return cls(dtype, data, validity)

    # -- shape info ----------------------------------------------------------
    @property
    def capacity(self) -> int:
        return self.data.shape[0]

    @property
    def string_width(self) -> int:
        assert self.dtype.is_string
        return self.data.shape[1]

    # -- construction --------------------------------------------------------
    @classmethod
    def full_null(cls, dtype: DataType, capacity: int,
                  string_width: int = 8) -> "DeviceColumn":
        if dtype.is_string:
            return cls(dtype,
                       jnp.zeros((capacity, string_width), jnp.uint8),
                       jnp.zeros((capacity,), jnp.bool_),
                       jnp.zeros((capacity,), jnp.int32))
        return cls(dtype,
                   jnp.zeros((capacity,), dtype.np_dtype),
                   jnp.zeros((capacity,), jnp.bool_))

    # -- row movement primitives --------------------------------------------
    def gather(self, indices: jax.Array, valid_dst: jax.Array) -> "DeviceColumn":
        """Take rows at ``indices``; ``valid_dst`` masks live destination rows."""
        data = jnp.take(self.data, indices, axis=0, mode="clip")
        validity = jnp.take(self.validity, indices, axis=0, mode="clip") & valid_dst
        data = _zero_dead(data, validity)
        if self.dtype.is_string:
            lengths = jnp.take(self.lengths, indices, axis=0, mode="clip")
            lengths = jnp.where(validity, lengths, 0)
            return DeviceColumn(self.dtype, data, validity, lengths)
        return DeviceColumn(self.dtype, data, validity)

    def with_validity(self, validity: jax.Array) -> "DeviceColumn":
        data = _zero_dead(self.data, validity)
        if self.dtype.is_string:
            return DeviceColumn(self.dtype, data, validity,
                                jnp.where(validity, self.lengths, 0))
        return DeviceColumn(self.dtype, data, validity)


def _zero_dead(data: jax.Array, validity: jax.Array) -> jax.Array:
    """Zero data where validity is False (keeps padding deterministic)."""
    if data.ndim == 2:
        return jnp.where(validity[:, None], data, jnp.zeros_like(data))
    return jnp.where(validity, data, jnp.zeros_like(data))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DeviceBatch:
    """A fixed-capacity columnar batch in HBM: the unit all operators consume.

    ``num_rows`` is a device int32 scalar so that data-dependent row counts
    (filter/join/groupby outputs) never force a recompile; ``capacity`` is
    static. Mirrors the role of the reference's ColumnarBatch of
    GpuColumnVectors (GpuColumnVector.java:from(Table)).

    ``sel`` is an optional (capacity,) bool SELECTION VECTOR: rows inside
    the ``num_rows`` prefix with sel False are deleted. Filters and join
    emits produce sel-batches instead of compacting (a 1M-row packed
    compaction costs ~100-400ms of device time on the target chip; a mask
    costs nothing) — the Velox/DuckDB selection-vector idea applied at
    batch granularity. Compaction happens only at materialization points
    (exchange, concat, sort output, download) via columnar/rowmove.py.
    """

    columns: Tuple[DeviceColumn, ...]
    num_rows: jax.Array          # int32 scalar
    # Host-known exact LIVE row count, when the producer knows it (uploads
    # do). NOT a pytree leaf: jit-produced batches lose it (None = unknown).
    # Lets consumers (exchange shrink, downloads) skip a device->host sync.
    rows_hint: Optional[int] = dataclasses.field(
        default=None, compare=False)
    sel: Optional[jax.Array] = None   # (capacity,) bool; None = all prefix

    def tree_flatten(self):
        if self.sel is not None:
            return (tuple(self.columns), self.num_rows, self.sel), True
        return (tuple(self.columns), self.num_rows), False

    @classmethod
    def tree_unflatten(cls, has_sel, leaves):
        if has_sel:
            columns, num_rows, sel = leaves
            return cls(tuple(columns), num_rows, sel=sel)
        columns, num_rows = leaves
        return cls(tuple(columns), num_rows)

    @property
    def capacity(self) -> int:
        if self.columns:
            return self.columns[0].capacity
        # A zero-column batch (count(*) over fully-pruned input) still
        # carries liveness in its selection vector; its capacity is the
        # sel length, not 0, or row_mask breaks against sel.
        if self.sel is not None:
            return int(self.sel.shape[0])
        return 0

    @property
    def num_columns(self) -> int:
        return len(self.columns)

    def row_mask(self) -> jax.Array:
        """(capacity,) bool — True for live (non-padding, selected) rows."""
        mask = jnp.arange(self.capacity, dtype=jnp.int32) < self.num_rows
        if self.sel is not None:
            mask = mask & self.sel
        return mask

    def live_count(self) -> jax.Array:
        """int32 scalar: number of live rows (== num_rows when no sel)."""
        if self.sel is None:
            return jnp.asarray(self.num_rows, jnp.int32)
        return jnp.sum(self.row_mask().astype(jnp.int32))

    def with_sel(self, keep: jax.Array) -> "DeviceBatch":
        """Restrict live rows by ``keep`` without moving data (lazy
        filter). rows_hint is dropped — the live count changed."""
        sel = keep if self.sel is None else (self.sel & keep)
        return DeviceBatch(self.columns, self.num_rows, sel=sel)

    # -- row movement --------------------------------------------------------
    def gather(self, indices: jax.Array, new_num_rows: jax.Array) -> "DeviceBatch":
        from spark_rapids_tpu.columnar.rowmove import gather_rows
        return gather_rows(self, indices,
                           jnp.asarray(new_num_rows, jnp.int32))

    def compact(self, keep: jax.Array) -> "DeviceBatch":
        """Materialize rows where ``keep`` (ANDed with row_mask) as a packed
        prefix — the cuDF ``Table.filter`` analog, via one index scatter
        and one packed gather per slab (columnar/rowmove.py)."""
        from spark_rapids_tpu.columnar.rowmove import compact_batch
        return compact_batch(self, keep)

    def head(self, n: jax.Array) -> "DeviceBatch":
        """First min(n, live) rows (GpuLocalLimit analog) — selection-only,
        no data movement."""
        live = self.row_mask()
        keep = jnp.cumsum(live.astype(jnp.int32)) <= jnp.asarray(n, jnp.int32)
        return self.with_sel(keep & live)

    def select(self, indices: Sequence[int]) -> "DeviceBatch":
        return DeviceBatch(tuple(self.columns[i] for i in indices),
                           self.num_rows, sel=self.sel)

    @property
    def dtypes(self) -> Tuple[DataType, ...]:
        return tuple(c.dtype for c in self.columns)

    def device_size_bytes(self) -> int:
        """Approximate HBM footprint (for the spill framework's accounting)."""
        total = 4
        for c in self.columns:
            total += c.data.size * c.data.dtype.itemsize
            total += c.validity.size  # bool = 1 byte
            if c.lengths is not None:
                total += c.lengths.size * 4
        if self.sel is not None:
            total += self.sel.size
        return total

    def live_size_bytes(self) -> Optional[int]:
        """What the rows known to be live hold, ESTIMATED from
        ``rows_hint`` (where a sizes pull left one): the footprint scaled
        by live rows over capacity, so without the padding of the
        capacity bucket. Exact for fixed-width columns; a string column's
        bytes are taken to be spread evenly over the rows. None where no
        count is known: the caller decides what stands for it."""
        if self.rows_hint is None or self.capacity <= 0:
            return None
        return self.device_size_bytes() * min(self.rows_hint,
                                              self.capacity) // self.capacity


def concat_batches(batches: Sequence[DeviceBatch], capacity: int) -> DeviceBatch:
    """Concatenate the live rows of ``batches`` into one dense batch of
    ``capacity`` rows.

    The cuDF ``Table.concatenate`` analog used by GpuCoalesceBatches
    (GpuCoalesceBatches.scala:643), via one index scatter and one packed
    gather per slab (columnar/rowmove.py) — selection vectors compact away
    here.
    Capacities are static, so overflow is checked at trace time.
    """
    assert batches, "concat of zero batches"
    total_cap = sum(b.capacity for b in batches)
    assert total_cap <= capacity, (
        f"concat overflow: member capacities sum to {total_cap} > {capacity}")
    from spark_rapids_tpu.columnar.rowmove import concat_compact
    return concat_compact(batches, capacity)


def _kernel_lookup(kind: str, key_parts, builder):
    """Process-global kernel cache access (lazy import: ops.kernel_cache
    must stay import-cycle-free with this module)."""
    from spark_rapids_tpu.ops import kernel_cache as kc
    return kc.lookup(kind, key_parts, builder)


def jit_concat_batches(batches: Sequence[DeviceBatch],
                       capacity: int) -> DeviceBatch:
    """``concat_batches`` under jit. Cached per target capacity in the
    process-global kernel cache; jax's own cache handles distinct input
    pytree structures. Eager concat is a per-column scatter storm — under
    jit XLA fuses it into a few copies."""
    fn = _kernel_lookup("concat", (capacity,),
                        lambda: jax.jit(
                            lambda bs: concat_batches(bs, capacity)))
    from spark_rapids_tpu import faults
    from spark_rapids_tpu.memory.oom import retry_on_oom

    def dispatch(bs):
        faults.fault_point("concat")
        return fn(bs)

    return retry_on_oom(dispatch, list(batches))


# Below this device size a shrink/compaction is taken not to repay its
# sizes-pull host sync (shared by coalescing, broadcasts and downloads).
# Sized when a sync cost ~70 ms; ~1 ms on an attached chip, not
# re-measured (ROADMAP A3).
MIN_SHRINK_BYTES = 4 << 20

# A join probe reads its input through ``row_mask()``, so a member with a
# selection vector is compacted in front of it only where its live bucket
# is at most 1/R of its capacity. Measured on a v5e at 786,432 rows of
# TPC-H q3's lineitem (scripts/chip_probe.py shrinkrule, PR 32; ms a call,
# compact + probe at the bucket + the output's compaction, against probe
# at capacity + that compaction): bucket 2/3 27.3 / 19.4, 1/2 22.8 / 19.4,
# 1/3 19.2 / 19.2, 1/8 11.8 / 18.7 — the probe alone breaks even at a
# third. But a kept batch's capacity rides on in the join's output: with
# R = 3 q3's orders batches (bucket 1/2) stayed at 196,608 rows, the next
# join's build side sorted 1,572,864 rows where it had sorted 786,432, and
# the query went 0.281 -> 0.340 s. R = 2 is the largest the cell bears.
PROBE_SHRINK_RATIO = 2

# ``coalesce_iter`` concatenates batches to spare their consumer a round
# of dispatches and pulls a batch. Moving a member costs an index pass and
# a packed gather a slab over the OUTPUT capacity, and that is never cheap.
# Measured on a v5e (scripts/chip_probe.py coalescealone, PR 35; ms, concat
# then one consumer call against a consumer call a member, with a blocking
# read of each output's count behind it; q1's layout and slot update / q3's
# lineitem layout and dense probe), a PAIR of members: 262,144 rows 13.5
# against 7.0 / 18.5 against 10.8; 524,288 rows 25.4 against 7.5 / 34.8
# against 16.6; 786,432 rows 37.3 against 8.8 / 94.5 against 22.0;
# 1,048,576 rows 53.6 against 10.5 / 124.9 against 27.9; in the group the
# 4-Mi goal makes (16, 8, 5, 4 members) 184-188 against 20-55 / 151-268
# against 54-85. A concat is 21-23 ns a row of output up to 1,048,576 rows
# and 42-96 above; the slot update 3.3 ms a call and 1.7 ns a row, the
# probe 19.5 ns a row: the break-even is ~150,000-250,000 rows. In the
# cells (kept traces, TPC-H SF10): four 1,048,576-row scan batches into
# 4,194,304 took 304 ms to save three slot updates of 2.7 ms, 4.29 of q1's
# 4.48 s of device time a query. The constant sits above the break-even,
# where it changes no program of a cell measured before it (no one-chip
# SF1 cell holds a member of even 262,144 rows in a group of several, the
# mesh cell none of 524,288; CPU rehearsals at scale 1).
COALESCE_ALONE_ROWS = 1 << 19

# -- counters -----------------------------------------------------------------
# Process-wide totals of what ``coalesce_iter(shrink=True)`` decided, in
# the manner of ``mesh_exchange.counters()``: ``shrinkMembers`` (members
# a shrinking flush saw), ``shrinkCompacted`` (rewritten at a smaller
# capacity), ``shrinkKeptSameBucket`` and ``shrinkKeptBelowRatio``
# (selection-vector members passed on as they were: the live bucket is
# the capacity, or is above ``1 / keep_ratio`` of it) and
# ``shrinkRowsKept`` (rows of capacity those two did not rewrite).
_COUNTER_LOCK = threading.Lock()
_COUNTERS: collections.Counter = collections.Counter()
_COUNTS = ("shrinkMembers", "shrinkCompacted", "shrinkKeptSameBucket",
           "shrinkKeptBelowRatio", "shrinkRowsKept")
_DECISIONS = _COUNTS[1:4]


def counters() -> Dict[str, int]:
    with _COUNTER_LOCK:
        return {k: _COUNTERS[k] for k in _COUNTS}


def reset_counters() -> None:
    with _COUNTER_LOCK:
        _COUNTERS.clear()


def group_by_goal(batches, target_rows: int, target_bytes: int,
                  size_of=None):
    """The stream in lists, as ``coalesce_iter`` groups it: a list is
    closed as its capacities reach ``target_rows`` or its bytes
    (``size_of(batch)``, by default the batch's device size)
    ``target_bytes``, or before the member that would carry it past
    either. A producer that wants to act once per group its consumer will
    make (the join's late probe: one count pull a group) cuts here too."""
    size_of = size_of or DeviceBatch.device_size_bytes
    group: List[DeviceBatch] = []
    cap = nbytes = 0
    for b in batches:
        nb = size_of(b)
        if group and (cap + b.capacity > target_rows
                      or nbytes + nb > target_bytes):
            yield group
            group, cap, nbytes = [], 0, 0
        group.append(b)
        cap += b.capacity
        nbytes += nb
        if cap >= target_rows or nbytes >= target_bytes:
            yield group
            group, cap, nbytes = [], 0, 0
    if group:
        yield group


def coalesce_iter(batches, target_rows: int, shrink: bool = False,
                  target_bytes: int = 512 * 1024 * 1024,
                  owner: Optional[str] = None, keep_ratio: int = 1):
    """Group a batch stream into ~``target_rows``-capacity batches with
    minimal host syncs (grouping keys off static capacities, the exchange
    serving idiom — GpuCoalesceBatches.scala:115 done the TPU way).

    Every batch costs its consumer a round of dispatches and, further
    on, sizes pulls (a few ms of host time), so many SMALL batches
    through a join probe or partial aggregate cost that many rounds where
    one coalesced batch costs one and a packed concat gather. A member of
    ``COALESCE_ALONE_ROWS`` rows of capacity or more is not moved for
    that: it goes on as it is, in its place among the runs of smaller
    members, which are concatenated as ever.

    ``shrink=True`` additionally re-buckets sparse members first (one
    batched sizes pull per group, skipped where rows_hint is known and
    below ``MIN_SHRINK_BYTES``): consumers whose kernels scale with
    CAPACITY (sort-based aggregation) must not pay 4M-row sorts for a
    selective join's 30k live rows. Both consumers (the keyed
    aggregate's update, the join probe) read selection vectors, so a
    member is compacted only where it gets smaller: never into its own
    capacity, and with ``keep_ratio`` R > 1 only where its live bucket
    is at most 1/R of its capacity (``shrink_all`` has the rule). A
    member passed on as it was keeps its selection vector and carries
    the pulled count as ``rows_hint``.

    ``target_bytes`` bounds the coalesced device size as well — wide
    (many-string-column) rows must not ride the row target into
    multi-GB batches (the batchSizeBytes bound, GpuCoalesceBatches'
    byte goal).

    ``owner`` names the operator whose input this is, for the trace
    (the generator runs when that operator pulls, so only here can the
    compaction be told from the child's own work).
    """
    def flush(g: List[DeviceBatch]):
        if shrink:
            # Only batches worth compacting pay a sizes pull (below the
            # threshold the kernel-time saved can't repay a round trip).
            # The pull is a host sync, and the shrinks are dispatched
            # behind it into a queue run dry: one span for the idiom.
            from spark_rapids_tpu import monitoring
            tally = collections.Counter(shrinkMembers=len(g))
            with monitoring.op_span(owner or "coalesce", "shrink-all",
                                    level=monitoring.LEVEL_KERNEL) as sp:
                g, _ = shrink_all(g, min_bytes=MIN_SHRINK_BYTES,
                                  keep_ratio=keep_ratio, tally=tally)
                sp.note(**{k: tally[k] for k in _DECISIONS})
            with _COUNTER_LOCK:
                _COUNTERS.update(tally)
        run: List[DeviceBatch] = []
        for b in g:
            if b.capacity < COALESCE_ALONE_ROWS:
                run.append(b)
                continue
            if run:
                yield _concat_run(run)
                run = []
            if len(g) > 1:
                from spark_rapids_tpu import monitoring
                monitoring.count("coalesceAloneRows", b.capacity)
            yield b
        if run:
            yield _concat_run(run)

    for group in group_by_goal(batches, target_rows, target_bytes):
        yield from flush(group)


def _concat_run(run: List[DeviceBatch]) -> DeviceBatch:
    """The members of ``run`` as one batch (itself, where it is one)."""
    if len(run) == 1:
        return run[0]
    out = jit_concat_batches(run, bucket_capacity(sum(b.capacity
                                                      for b in run)))
    hints = [b.rows_hint for b in run]
    if all(h is not None for h in hints):
        out.rows_hint = sum(hints)
    return out


def shrink_to_capacity(batch: DeviceBatch, capacity: int) -> DeviceBatch:
    """Re-bucket a batch whose live rows fit ``capacity`` as a DENSE
    batch (after a groupby/filter the packed prefix is all that matters).
    Jitted; requires ``live_count <= capacity``. A selection vector
    always compacts away, at the batch's own capacity too (cost: an
    index scatter over the INPUT capacity and a packed gather per slab
    over the OUTPUT capacity — rowmove.compact_batch); a batch without
    one at its own capacity comes back as it is. Whether a
    selection-vector batch is worth compacting is the caller's question:
    ``shrink_all(keep_ratio=...)`` asks it for consumers that mask."""
    if capacity >= batch.capacity and batch.sel is None:
        return batch
    hint = batch.rows_hint

    def _build():
        def _shrink(b: DeviceBatch) -> DeviceBatch:
            from spark_rapids_tpu.columnar.rowmove import compact_batch
            if b.sel is not None:
                return compact_batch(b, capacity=capacity)
            # The live rows are the prefix: slice it. (A gather by
            # arange packs the INPUT's capacity into slabs first: 0.5 ms
            # a call to take 4 rows of a 786,432-row partial, PR 32.)
            # Slots past num_rows come out zeroed, as the gather left them.
            live = jnp.arange(capacity, dtype=jnp.int32) < b.num_rows
            heads = [jax.tree.map(lambda x: x[:capacity], c)
                     for c in b.columns]
            return DeviceBatch(tuple(c.with_validity(c.validity & live)
                                     for c in heads), b.num_rows)
        return jax.jit(_shrink)

    out = _kernel_lookup("shrink", (capacity,), _build)(batch)
    out.rows_hint = hint
    return out


def _shrink_decision(batch: DeviceBatch, cap: int,
                     keep_ratio: Optional[int]) -> Optional[str]:
    """What ``shrink_all`` does with a counted member whose live bucket
    is ``cap``, as the name it has in ``counters()``; None where there
    is nothing to do (dense, and at its bucket already)."""
    if batch.sel is None:
        return "shrinkCompacted" if cap < batch.capacity else None
    if keep_ratio is None:
        return "shrinkCompacted"
    if cap >= batch.capacity:
        return "shrinkKeptSameBucket"
    if cap * keep_ratio > batch.capacity:
        return "shrinkKeptBelowRatio"
    return "shrinkCompacted"


def shrink_all(batches: Sequence[DeviceBatch],
               min_bytes: int = 0,
               keep_ratio: Optional[int] = None,
               tally: Optional[collections.Counter] = None,
               ) -> Tuple[List[DeviceBatch], List[Optional[int]]]:
    """Two-phase sizes-then-shrink over a batch list (SURVEY §7): pull
    every unknown live count in ONE batched ``jax.device_get`` (one host
    sync instead of one per batch), then re-bucket
    each batch to its live capacity. ``min_bytes`` skips the pull for
    batches too small for the saved transfer/compute to repay the sync —
    including selection-vector batches (every consumer handles sel);
    callers that NEED exact counts (the exchange's bucket accounting)
    keep the default 0. Returns (shrunk batches, live counts — None
    where the pull was skipped). The one shared implementation of this
    idiom for aggregates, exchanges, broadcasts and downloads.

    ``keep_ratio`` is what the caller says of its consumer. None (shard
    writers, serialisers, ``to_pylist``): the consumer wants dense
    batches, every counted selection vector compacts away. An int R >= 1:
    the consumer masks (``row_mask()``, ``live_count()``), so a counted
    member with a selection vector is compacted only where that makes it
    smaller — never when ``bucket_capacity(live)`` is its own capacity
    (the rewrite would hand the same capacity to a consumer that masks
    either way), and only when the bucket is at most ``capacity // R``.
    R = 1 is "any smaller bucket", for a consumer that may sort. A member
    kept goes on as the same object with ``rows_hint`` set to its count,
    so that no later ``shrink_all`` pulls it again. A member without a
    selection vector is cut to any smaller bucket as ever (its prefix).
    ``tally``, if given, counts the decisions under the names of
    ``counters()``."""
    import jax
    batches = list(batches)
    counts: List[Optional[int]] = [b.rows_hint for b in batches]
    unknown = [i for i, b in enumerate(batches)
               if counts[i] is None
               and b.device_size_bytes() > min_bytes]
    if unknown:
        pulled = jax.device_get([batches[i].live_count() for i in unknown])
        for i, c in zip(unknown, pulled):
            counts[i] = int(c)
    out = []
    for b, c in zip(batches, counts):
        if c is not None:
            cap = bucket_capacity(max(c, 1))
            what = _shrink_decision(b, cap, keep_ratio)
            if what == "shrinkCompacted":
                b = shrink_to_capacity(b, cap)
            if tally is not None and what is not None:
                tally[what] += 1
                if what != "shrinkCompacted":
                    tally["shrinkRowsKept"] += b.capacity
            b.rows_hint = c
        out.append(b)
    return out, counts


def sample_rows(batch: DeviceBatch, k: int) -> DeviceBatch:
    """Up to ``k`` evenly spaced live rows, as a k-capacity batch — the
    device-side half of range-bounds sampling (GpuRangePartitioner's
    reservoir sample): sample BEFORE downloading so a bounds probe moves
    k rows over the link instead of a whole batch."""
    def _build():
        def _sample(b: DeviceBatch) -> DeviceBatch:
            if b.sel is not None:
                from spark_rapids_tpu.columnar.rowmove import compact_batch
                b = compact_batch(b)
            n = jnp.maximum(b.num_rows, 1).astype(jnp.int64)
            slots = jnp.arange(k, dtype=jnp.int64)
            strided = ((slots * (n - 1)) // jnp.maximum(
                jnp.asarray(k - 1, jnp.int64), 1)).astype(jnp.int32)
            slots = slots.astype(jnp.int32)
            n = n.astype(jnp.int32)
            # With fewer live rows than slots the stride collapses to
            # mostly row 0; take the first n rows verbatim instead so
            # range-bound probes see distinct rows.
            idx = jnp.where(n > k, strided, jnp.minimum(slots, n - 1))
            take = jnp.minimum(jnp.asarray(k, jnp.int32), b.num_rows)
            return b.gather(idx, take)
        return jax.jit(_sample)

    return _kernel_lookup("sample", (k,), _build)(batch)


def string_repad(col: DeviceColumn, width: int) -> DeviceColumn:
    """Re-pad a string column's byte matrix to ``width`` (static)."""
    assert col.dtype.is_string
    cur = col.string_width
    if cur == width:
        return col
    if cur < width:
        pad = jnp.zeros((col.capacity, width - cur), jnp.uint8)
        return DeviceColumn(col.dtype, jnp.concatenate([col.data, pad], axis=1),
                            col.validity, col.lengths)
    # Narrowing: only legal when all lengths fit — caller's responsibility
    # (used by ops like substring that provably shrink strings). Lengths are
    # clamped so the column stays internally consistent either way.
    return DeviceColumn(col.dtype, col.data[:, :width], col.validity,
                        jnp.minimum(col.lengths, width))
