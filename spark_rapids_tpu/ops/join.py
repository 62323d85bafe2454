"""Hash joins (ref: shims/spark300 GpuHashJoin.scala:50,195,
GpuShuffledHashJoinExec, GpuBroadcastHashJoinExec,
GpuBroadcastNestedLoopJoinExec.scala, GpuCartesianProductExec.scala).

TPU-first design — no hash table with chained buckets (pointer chasing is
poison on the VPU). Instead, a sort-probe join over key fingerprints:

  build side: fingerprint build keys (two murmur3 streams, ops/kernels.py),
      sort build rows by fingerprint -> contiguous key groups, plus a
      sorted fingerprint array for searching.
  probe side: fingerprint probe keys, double binary search (searchsorted
      left/right) into the sorted build fingerprints -> per-probe match
      range [lo, hi).
  expansion: total pairs = sum(hi - lo) is reduced on device, synced once,
      and rounded up to a capacity bucket (the one host sync a join costs —
      matching cuDF's join output-size computation). The expansion kernel
      maps each output slot back to its (probe, build) pair with a
      searchsorted over the running offsets — all dense vector ops.

Join sides: inner, left/right outer, full outer, left semi, left anti, plus
cross (nested loop) joins. An optional residual condition filters pairs
post-expansion (non-equi predicates), with outer-join match bookkeeping done
after the filter, like the reference's conditional join handling.
"""

from __future__ import annotations

import dataclasses
from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import monitoring
from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.batch import (
    DeviceBatch, DeviceColumn, bucket_capacity, concat_batches)
from spark_rapids_tpu.columnar.host import HostBatch, HostColumn
from spark_rapids_tpu.exprs.base import Expression, as_device_column, \
    as_host_column
from spark_rapids_tpu.ops.base import Exec, ExecContext, Schema, timed
from spark_rapids_tpu.ops import kernels
from spark_rapids_tpu.ops.sort import coalesce_to_single_batch

JOIN_TYPES = ("inner", "left", "right", "full", "semi", "anti", "cross")


# ---------------------------------------------------------------------------
# Device join kernels
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class BuiltSide:
    """Build side prepared for probing: rows sorted by key fingerprint.

    Registered as a jax pytree so whole probe steps can be jitted with the
    built side passed as a traced argument (one compile serves every
    partition).

    ``stats`` is a small int64 device vector pulled to the host ONCE per
    build (one round trip): [max_run, int_keys_ok, kmin..., kmax...].
    It powers both the FK fast path (max_run bounds the output size with
    no per-probe-batch sync) and the dense direct-address table decision.
    ``table`` (set lazily) maps dense key offsets -> build row index — the
    TPU-first replacement for a hash-table probe: one gather instead of a
    double binary search (which costs ~190ms/1M probes on this chip)."""

    batch: DeviceBatch          # rows in fingerprint-sorted order
    fp: jnp.ndarray             # (cap,) uint64 sorted fingerprints
    matchable: jnp.ndarray      # (cap,) bool: live AND non-null keys
    row_live: jnp.ndarray       # (cap,) bool: live (incl. null-key rows)
    num_rows: jnp.ndarray       # int32
    key_ordinals: Optional[List[int]] = None  # for post-match verification
    null_safe: bool = False
    max_run: Optional[jnp.ndarray] = None     # kept for mesh path compat
    stats: Optional[jnp.ndarray] = None       # int64 stats vector
    table: Optional[jnp.ndarray] = None       # dense key -> row, or None
    # kmin and span per key: (k,) int64 DEVICE vectors, pytree leaves — as
    # host constants they made every probe program data-dependent, so a
    # process over new data compiled each anew (9 programs of 4-8 s in the
    # mesh q5 cell: my chip run, PR 30).
    table_base: Optional[jnp.ndarray] = None
    table_spans: Optional[jnp.ndarray] = None
    host_stats: Optional[List[int]] = None    # stats pulled once (aux)

    def stats_host(self) -> Optional[List[int]]:
        """The stats vector on the host, pulled at most ONCE per build.
        A broadcast BuiltSide is shared across every probe partition, and
        a per-partition ``np.asarray(stats)`` re-read is a blocking host
        sync each time."""
        if self.host_stats is None and self.stats is not None:
            self.host_stats = [int(x) for x in np.asarray(self.stats)]
        return self.host_stats


def _builtside_flatten(bs: "BuiltSide"):
    children = (bs.batch, bs.fp, bs.matchable, bs.row_live, bs.num_rows,
                bs.max_run, bs.stats, bs.table, bs.table_base,
                bs.table_spans)
    aux = (tuple(bs.key_ordinals) if bs.key_ordinals is not None else None,
           bs.null_safe)
    return children, aux


def _builtside_unflatten(aux, children):
    ko, ns = aux
    (batch, fp, matchable, row_live, num_rows, max_run, stats, table,
     tb, tsp) = children
    return BuiltSide(batch, fp, matchable, row_live, num_rows,
                     list(ko) if ko is not None else None, ns, max_run,
                     stats, table, tb, tsp)


jax.tree_util.register_pytree_node(
    BuiltSide, _builtside_flatten, _builtside_unflatten)


def _fingerprint64(batch: DeviceBatch, key_ordinals) -> jnp.ndarray:
    ha, hb = kernels.key_fingerprint(
        [batch.columns[i] for i in key_ordinals], batch.capacity)
    return (ha.astype(jnp.uint64) << jnp.uint64(32)) | hb.astype(jnp.uint64)


def build_side(batch: DeviceBatch, key_ordinals: Sequence[int],
               null_safe: bool = False, metrics=None) -> BuiltSide:
    """Sort build rows by fingerprint. Rows with null keys never match (SQL
    equi-join), but stay alive for full-outer emission.

    ONE jitted program per (keys, batch shape). Run op by op it was ~240
    one-op programs per shape — its associative scan alone a slice, a
    pad, a maximum and a concatenate per level — and q3's first run on
    the chip compiled 342 such programs at ~0.6 s each (PR 21).

    The build side is the largest single allocation of a join, so it
    dispatches through ``kernel_cache.call`` like every other cached
    kernel: OOM ladder, ``kernel`` fault site, ``compileTime`` on
    ``metrics``. Under a trace (the mesh step builds inside its own
    program) it inlines instead."""
    statics = (tuple(key_ordinals), bool(null_safe))
    if any(isinstance(x, jax.core.Tracer)
           for x in jax.tree_util.tree_leaves(batch)):
        return _build_side(batch, *statics)
    from spark_rapids_tpu.ops import kernel_cache as kc
    entry = kc.lookup("join-build-side", statics,
                      lambda: jax.jit(_build_side, static_argnums=(1, 2)),
                      metrics)
    built = kc.call(entry, metrics, batch, *statics)
    # Start the device->host copy of the stats now: the stream loop reads
    # them before the first probe batch, and overlapping the pull with
    # probe-side startup hides a host sync.
    built.stats.copy_to_host_async()
    return built


def _build_side(batch: DeviceBatch, key_ordinals: Tuple[int, ...],
                null_safe: bool) -> BuiltSide:
    from spark_rapids_tpu.columnar.rowmove import gather_rows
    fp = _fingerprint64(batch, key_ordinals)
    row_live = batch.row_mask()
    matchable = row_live
    if not null_safe:
        for i in key_ordinals:
            matchable = matchable & batch.columns[i].validity
    # Unmatchable rows sort to the end with the max fingerprint sentinel
    # (padding after null-key rows). One packed gather moves every column
    # (rowmove.py); liveness is per-sorted-row, not a prefix.
    sentinel = jnp.uint64(0xFFFFFFFFFFFFFFFF)
    key = jnp.where(matchable, fp, sentinel)
    perm = jnp.argsort(key, stable=True)
    s_live = jnp.take(row_live, perm, axis=0)
    sorted_batch = gather_rows(batch, perm.astype(jnp.int32),
                               batch.num_rows, valid_dst=s_live)
    s_fp = jnp.take(key, perm, axis=0)
    s_match = jnp.take(matchable, perm, axis=0)
    # Longest run of equal sorted fingerprints among matchable rows (the
    # sentinel run at the end is excluded via s_match).
    cap = batch.capacity
    idx = jnp.arange(cap, dtype=jnp.int32)
    starts = jnp.concatenate([jnp.ones((1,), jnp.bool_),
                              s_fp[1:] != s_fp[:-1]])
    last_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(starts, idx, 0))
    run_pos = idx - last_start
    max_run = jnp.max(jnp.where(s_match, run_pos + 1, 0))
    # Key range stats for the dense direct-address decision: all-integral
    # keys with a small combined span get a direct table (gather probe).
    int_ok = not null_safe
    mins: List[jnp.ndarray] = []
    maxs: List[jnp.ndarray] = []
    for i in key_ordinals:
        c = batch.columns[i]
        if not (c.dtype.is_integral or c.dtype.name == "date"):
            int_ok = False
            break
        v = c.data.astype(jnp.int64)
        ok = matchable & c.validity
        mins.append(jnp.min(jnp.where(ok, v, jnp.int64(2 ** 62))))
        maxs.append(jnp.max(jnp.where(ok, v, jnp.int64(-2 ** 62))))
    if not int_ok:
        mins, maxs = [], []
    stats = jnp.stack([max_run.astype(jnp.int64),
                       jnp.asarray(1 if int_ok else 0, jnp.int64)]
                      + mins + maxs) if key_ordinals else None
    return BuiltSide(sorted_batch, s_fp, s_match, s_live,
                     batch.num_rows, list(key_ordinals), null_safe,
                     max_run, stats)


# Dense tables beyond this many entries are not worth the HBM (64 MB int32)
_DENSE_TABLE_MAX = 1 << 24


def _maybe_build_dense(built: BuiltSide, batch: DeviceBatch,
                       key_ordinals: Sequence[int]) -> None:
    """Attach a direct-address table when the (integral) build keys are
    unique and span a small dense range — every TPC-style FK dimension
    join qualifies. Probe then costs ONE gather + compare instead of a
    sorted binary search + expansion. Idempotent: a broadcast BuiltSide is
    shared across probe partitions and must build its table once."""
    if built.stats is None or built.table is not None:
        return
    st = built.stats_host()
    max_run, int_ok = st[0], st[1]
    if not int_ok or max_run > 1:
        return
    k = len(key_ordinals)
    mins, maxs = st[2:2 + k], st[2 + k:2 + 2 * k]
    if any(mx < mn for mn, mx in zip(mins, maxs)):
        return          # no matchable rows
    spans = [mx - mn + 1 for mn, mx in zip(mins, maxs)]
    total = 1
    for s in spans:
        total *= s
        if total > _DENSE_TABLE_MAX:
            return
    size = 1
    while size < total:
        size *= 2
    from spark_rapids_tpu.ops import kernel_cache as kc

    def _builder():
        def build_table(batch_, matchable, mins_, spans_, ords):
            combined = jnp.zeros((batch_.capacity,), jnp.int64)
            for i, o in enumerate(ords):
                v = batch_.columns[o].data.astype(jnp.int64) - mins_[i]
                combined = combined * spans_[i] + v
            pos = jnp.where(matchable, combined, size)
            rows = jnp.arange(batch_.capacity, dtype=jnp.int32)
            return jnp.full((size,), -1, jnp.int32).at[pos].set(
                rows, mode="drop")
        return jax.jit(build_table, static_argnames=("ords",))

    fn = kc.lookup("join-dense-build", (size,), _builder)
    # The table indexes the fingerprint-SORTED batch (built.batch) — the
    # same rows every other join path gathers from.
    built.table_base = jnp.asarray(mins, jnp.int64)
    built.table_spans = jnp.asarray(spans, jnp.int64)
    built.table = fn(built.batch, built.matchable, built.table_base,
                     built.table_spans, tuple(key_ordinals))


def _settle(x) -> None:
    """Where the flight recorder is on, wait for ``x`` on the device, so
    that a ``join-build`` span ends when the work it dispatched is done
    and its time is the build's and not the dispatch's. The wait would
    otherwise land wherever the host next reads the device (the first
    probe batch's sizes pull, as a rule): the span would read a few ms of
    a build that holds the chip for 200 (PR 35, q3 at SF10). With the
    recorder off nothing waits: the build overlaps the host's dispatch
    of the first probe batch, as ``_device_join_stream`` arranges."""
    if monitoring.enabled():
        jax.block_until_ready(x)


def _pair_keys_equal(built: BuiltSide, b_idx: jnp.ndarray,
                     probe: DeviceBatch, p_idx: jnp.ndarray,
                     probe_ordinals: Sequence[int],
                     base: jnp.ndarray) -> jnp.ndarray:
    """Verify ACTUAL key equality for candidate (probe, build) pairs.

    Fingerprint ranges are candidates only — a 64-bit collision (or a true
    fingerprint landing on the sort sentinel) would otherwise silently join
    wrong rows. The reference's cuDF hash join compares real keys after
    hashing; this is that check, vectorized over the expanded pairs.
    Float keys follow Spark join-key semantics (NaN==NaN, -0.0==0.0);
    null-safe (<=>) joins treat NULL==NULL as a match.
    """
    from spark_rapids_tpu.columnar.batch import string_repad
    eq = base
    for bo, po in zip(built.key_ordinals, probe_ordinals):
        bc = built.batch.columns[bo]
        pc = probe.columns[po]
        bv = jnp.take(bc.validity, b_idx, axis=0, mode="clip")
        pv = jnp.take(pc.validity, p_idx, axis=0, mode="clip")
        if bc.dtype.is_string:
            w = max(bc.string_width, pc.string_width)
            bcw, pcw = string_repad(bc, w), string_repad(pc, w)
            bd = jnp.take(bcw.data, b_idx, axis=0, mode="clip")
            pd = jnp.take(pcw.data, p_idx, axis=0, mode="clip")
            bl = jnp.take(bcw.lengths, b_idx, axis=0, mode="clip")
            pl = jnp.take(pcw.lengths, p_idx, axis=0, mode="clip")
            data_eq = (bl == pl) & jnp.all(bd == pd, axis=1)
        else:
            bd = jnp.take(bc.data, b_idx, axis=0, mode="clip")
            pd = jnp.take(pc.data, p_idx, axis=0, mode="clip")
            data_eq = bd == pd
            if jnp.issubdtype(bd.dtype, jnp.floating):
                data_eq = data_eq | (jnp.isnan(bd) & jnp.isnan(pd))
        if built.null_safe:
            eq = eq & ((bv & pv & data_eq) | (~bv & ~pv))
        else:
            eq = eq & bv & pv & data_eq
    return eq


def probe_ranges(built: BuiltSide, probe: DeviceBatch,
                 key_ordinals: Sequence[int], null_safe: bool = False):
    """Per-probe-row match range [lo, hi) in the sorted build side."""
    fp = _fingerprint64(probe, key_ordinals)
    plive = probe.row_mask()
    if not null_safe:
        for i in key_ordinals:
            plive = plive & probe.columns[i].validity
    lo = jnp.searchsorted(built.fp, fp, side="left").astype(jnp.int32)
    hi = jnp.searchsorted(built.fp, fp, side="right").astype(jnp.int32)
    counts = jnp.where(plive, hi - lo, 0)
    return lo, counts, plive


def expand_pairs(lo: jnp.ndarray, counts: jnp.ndarray, out_cap: int,
                 probe_cap: int):
    """Map output slots to (probe_row, build_row) pairs.

    offsets = exclusive cumsum(counts); slot s belongs to probe row
    p = upper_bound(offsets, s) - 1 and build row lo[p] + (s - offsets[p]).

    Returns (p, b, valid, num_rows, overflowed): when the true pair count
    exceeds ``out_cap`` (callers that cannot sync the exact total, e.g. the
    mesh-collective join), num_rows clamps to out_cap and ``overflowed``
    flags the truncation so callers can surface it instead of silently
    dropping pairs.
    """
    offsets = jnp.cumsum(counts) - counts          # exclusive
    total = jnp.sum(counts)
    num_rows = jnp.minimum(total, out_cap).astype(jnp.int32)
    slots = jnp.arange(out_cap, dtype=jnp.int32)
    p = (jnp.searchsorted(offsets, slots, side="right") - 1).astype(jnp.int32)
    p = jnp.clip(p, 0, probe_cap - 1)
    within = slots - jnp.take(offsets, p, axis=0)
    b = jnp.take(lo, p, axis=0) + within.astype(jnp.int32)
    valid = slots < num_rows
    return p, b, valid, num_rows, total > out_cap


def _gather_cols(batch: DeviceBatch, rows: jnp.ndarray,
                 valid: jnp.ndarray, null_out: jnp.ndarray = None):
    """Gather columns at ``rows`` (out-capacity positions); ``null_out``
    marks slots that must become NULL (outer-join no-match sides)."""
    cols = []
    for c in batch.columns:
        dst_valid = jnp.take(c.validity, rows, axis=0, mode="clip") & valid
        if null_out is not None:
            dst_valid = dst_valid & ~null_out
        cols.append(c.gather(rows, dst_valid))
    return cols


def _dense_lookup(built: BuiltSide, pbatch: DeviceBatch, probe_keys):
    """The direct-address table lookup: ``(pos, found, plive)``, each of
    the probe's capacity — the build row a probe row's keys address (-1
    where the slot is empty), whether the row is live, non-NULL, in range
    and addressed a build row, and the probe's ``row_mask()``."""
    base, spans = built.table_base, built.table_spans
    size = built.table.shape[0]
    plive = pbatch.row_mask()
    combined = jnp.zeros((pbatch.capacity,), jnp.int64)
    inrange = plive
    for i, o in enumerate(probe_keys):
        c = pbatch.columns[o]
        v = c.data.astype(jnp.int64)
        inrange = inrange & c.validity & (v >= base[i]) & \
            (v < base[i] + spans[i])
        combined = combined * spans[i] + (v - base[i])
    idx = jnp.clip(combined, 0, size - 1)
    pos = jnp.take(built.table, idx, axis=0)
    return pos, inrange & (pos >= 0), plive


def _late_lookup(built: BuiltSide, pbatch: DeviceBatch, probe_keys):
    """First half of the late dense probe of an inner join without a
    condition: the lookup, and the count of rows that found a build row
    as a device scalar. Nothing of the payload moves yet."""
    pos, found, _ = _dense_lookup(built, pbatch, probe_keys)
    return pos, found, jnp.sum(found.astype(jnp.int32))


def _late_emit(build: DeviceBatch, pbatch: DeviceBatch, pos, found, count,
               out_cap: Optional[int], build_is_right: bool) -> DeviceBatch:
    """Second half, once ``count`` is known on the host. With ``out_cap``
    (a capacity bucket that holds ``count``): the matched rows alone, in
    the probe's order, as a dense batch — one index pass over the probe's
    capacity, then the probe's rows and the build side's rows gathered at
    ``out_cap``: what ``_dense_step`` followed by ``shrink_to_capacity``
    gives, row for row, without having gathered the build side's rows of
    the probe rows that are dropped. ``out_cap`` None (the bucket is no
    smaller than the consumer would keep): ``_dense_step``'s own output,
    at the probe's capacity under a selection vector, no index pass."""
    from spark_rapids_tpu.columnar.rowmove import _live_sources, gather_rows
    if out_cap is None:
        probe_out, at, valid = pbatch, pos, found
    else:
        src = _live_sources(found, out_cap)
        valid = jnp.arange(out_cap, dtype=jnp.int32) < count
        probe_out = gather_rows(pbatch, src, count, valid_dst=valid)
        at = jnp.take(pos, src, axis=0)
    build_out = gather_rows(build, jnp.clip(at, 0, build.capacity - 1),
                            probe_out.num_rows, valid_dst=valid)
    if build_is_right:
        cols = tuple(probe_out.columns) + tuple(build_out.columns)
    else:
        cols = tuple(build_out.columns) + tuple(probe_out.columns)
    out = DeviceBatch(cols, probe_out.num_rows)
    return out.with_sel(found) if out_cap is None else out


def _late_jit_fns():
    """The two programs of the late dense probe, from the process-global
    cache. Neither reads a join's type or condition (the caller has
    checked both), so every join shares them."""
    from spark_rapids_tpu.ops import kernel_cache as kc
    return (kc.lookup("join-late-lookup", (),
                      lambda: jax.jit(_late_lookup,
                                      static_argnames=("probe_keys",))),
            kc.lookup("join-late-emit", (),
                      lambda: jax.jit(_late_emit,
                                      static_argnames=("out_cap",
                                                       "build_is_right"))))


def _join_schema(left: Schema, right: Schema, join_type: str) -> Schema:
    if join_type in ("semi", "anti"):
        return left
    return tuple(left) + tuple(right)


class _JoinKernelMixin:
    """Shared device join logic over a built (single-batch) build side and a
    streamed probe side. Subclasses decide which input is which."""

    # Fast path bound: with max_run <= this, output capacity is taken as
    # probe_cap * max_run with NO per-probe-batch size sync. Beyond it the
    # padding waste outweighs the saved round trip.
    _FAST_PATH_MAX_RUN = 4

    def _join_fp(self):
        """Structural identity of this join's emit semantics: everything
        ``_emit_expanded`` reads off ``self`` (join type + condition).
        Execs with equal fingerprints share one compiled probe/emit
        program through the process-global kernel cache."""
        from spark_rapids_tpu.ops import kernel_cache as kc
        fp = getattr(self, "_join_fp_cache", None)
        if fp is None:
            fp = self._join_fp_cache = (
                type(self).__name__, self.join_type,
                kc.fingerprint(self.condition))
        return fp

    def _build(self, ctx, bbatches, key_ordinals) -> BuiltSide:
        """One build side, from its child's last batch to the sorted
        side READY on the device: the concat into one batch and
        ``_build_side``'s fingerprint sort, as one ``join-build`` span
        (``_device_join_stream`` adds the second, the dense table).
        Never around the child's pull."""
        with monitoring.span(
                "build-side", "join-build",
                args={"op": self.name, "batches": len(bbatches),
                      "capacities": [b.capacity for b in bbatches]}
                if monitoring.enabled() else None):
            single = coalesce_to_single_batch(bbatches)
            monitoring.count("joinBuildRows", single.capacity)
            built = build_side(single, key_ordinals,
                               metrics=ctx.metrics_for(self))
            _settle(built.fp)
        return built

    def _probe_span(self, pbatch: DeviceBatch, path: str):
        """One ``join-probe`` span a probe batch: the dispatch of its
        probe and emit programs (and, off the fast paths, the pull of the
        pair count), not the pull of the batch from the child."""
        return monitoring.span(
            "probe", "join-probe",
            args={"op": self.name, "path": path,
                  "capacity": pbatch.capacity}
            if monitoring.enabled() else None)

    def _probe_jit_fn(self):
        """Jitted probe step from the process-global cache: fingerprint
        search + expansion + gathers fused into a single device program
        (one dispatch per probe batch instead of dozens of eager
        primitives). BuiltSide is a pytree argument, so all partitions —
        and all execs with the same join shape — share the compile."""
        from spark_rapids_tpu.ops import kernel_cache as kc

        def build():
            clone = kc.detached_clone(self)

            def step(built, pbatch, out_cap, build_is_right, probe_keys):
                lo, counts, plive = probe_ranges(built, pbatch,
                                                 list(probe_keys),
                                                 built.null_safe)
                return clone._emit_expanded(
                    built, pbatch, lo, counts, plive, out_cap,
                    build_is_right, list(probe_keys))
            return jax.jit(
                step, static_argnames=("out_cap", "build_is_right",
                                       "probe_keys"))
        return kc.lookup("join-probe", self._join_fp(), build)

    def _emit_jit_fn(self):
        """Jitted expansion for the synced (max_run > fast bound) path: the
        ranges were already computed eagerly to size the output, so this
        variant takes them as traced arguments instead of re-hashing the
        probe keys and re-searching the build fingerprints."""
        from spark_rapids_tpu.ops import kernel_cache as kc

        def build():
            clone = kc.detached_clone(self)

            def step(built, pbatch, lo, counts, plive, out_cap,
                     build_is_right, probe_keys):
                return clone._emit_expanded(
                    built, pbatch, lo, counts, plive, out_cap,
                    build_is_right, list(probe_keys))
            return jax.jit(
                step, static_argnames=("out_cap", "build_is_right",
                                       "probe_keys"))
        return kc.lookup("join-emit", self._join_fp(), build)

    def _dense_step(self, built: BuiltSide, pbatch: DeviceBatch,
                    probe_keys, build_is_right: bool):
        """Direct-address probe: ONE table gather decides every probe row's
        build match (unique integral build keys — the FK dimension join).
        Emits a selection-vector batch: no expansion, no output-size sync,
        no compaction. ~45ms per 1M-row probe batch on this chip vs ~1.2s
        through the sorted-search path."""
        from spark_rapids_tpu.columnar.rowmove import gather_rows
        jt = self.join_type
        cond = self.condition
        pos, found, plive = _dense_lookup(built, pbatch, probe_keys)
        if jt in ("semi", "anti") and cond is None:
            keep = found if jt == "semi" else ~found
            return pbatch.with_sel(keep & plive)
        bsafe = jnp.clip(pos, 0, built.batch.capacity - 1)
        build_out = gather_rows(built.batch, bsafe, pbatch.num_rows,
                                valid_dst=found)
        if build_is_right:
            cols = tuple(pbatch.columns) + tuple(build_out.columns)
        else:
            cols = tuple(build_out.columns) + tuple(pbatch.columns)
        pairs = DeviceBatch(cols, pbatch.num_rows)
        matched = found
        if cond is not None:
            c = as_device_column(cond.eval(pairs), pairs)
            matched = matched & c.data & c.validity
        if jt == "inner":
            return pairs.with_sel(matched & plive)
        if jt in ("semi", "anti"):
            keep = matched if jt == "semi" else ~matched
            return pbatch.with_sel(keep & plive)
        # left/right outer: every live probe row survives; the build side
        # shows NULLs where unmatched (gather valid_dst already nulled
        # not-found rows; a failed condition re-nulls here).
        if cond is not None:
            nulled = tuple(
                c.with_validity(c.validity & matched)
                for c in build_out.columns)
            if build_is_right:
                cols = tuple(pbatch.columns) + nulled
            else:
                cols = nulled + tuple(pbatch.columns)
            pairs = DeviceBatch(cols, pbatch.num_rows)
        return pairs.with_sel(plive)

    def _dense_jit_fn(self):
        from spark_rapids_tpu.ops import kernel_cache as kc
        return kc.lookup(
            "join-dense", self._join_fp(),
            lambda: jax.jit(kc.detached_clone(self)._dense_step,
                            static_argnames=("probe_keys",
                                             "build_is_right")))

    def _device_join_stream(self, ctx, built: BuiltSide, probe_iter,
                            probe_keys, build_is_right: bool):
        import itertools
        from spark_rapids_tpu import config as C
        from spark_rapids_tpu.columnar.batch import (
            PROBE_SHRINK_RATIO, coalesce_iter)
        jt = self.join_type
        cond = self.condition
        build_cap = built.batch.capacity
        # Full outer: build-side coverage accumulates over the whole probe
        # stream and unmatched build rows are emitted once at the end.
        covered_acc = jnp.zeros((build_cap,), jnp.bool_) \
            if jt == "full" else None
        # Coalesce the probe stream: per-batch probe work has a fixed
        # device-latency floor, so 8 scan-file batches cost 8 floors where
        # 1-2 coalesced batches cost 1-2. shrink=True compacts sparse
        # members first (an upstream selective join's output would
        # otherwise make EVERY downstream probe gather pay its full
        # capacity); the sizes pull is batched per group and skipped
        # where rows_hint is known (scans). Every probe path below reads
        # its input through row_mask(), so a member whose live bucket is
        # over 1/PROBE_SHRINK_RATIO of its capacity comes as it is.
        probe_iter = coalesce_iter(
            probe_iter, int(ctx.conf.get(C.BATCH_SIZE_ROWS)),
            shrink=True,
            target_bytes=int(ctx.conf.get(C.BATCH_SIZE_BYTES)),
            owner=self.name, keep_ratio=PROBE_SHRINK_RATIO)
        # Dispatch the FIRST probe batch's upstream work before blocking on
        # the build stats: the async stats copy then overlaps probe-side
        # scan/decode instead of serializing ahead of it.
        first = next(iter(probe_iter), None)
        if first is not None:
            probe_iter = itertools.chain([first], probe_iter)
        else:
            probe_iter = iter(())
        # One sync per BUILD (not per probe batch): the stats pull powers
        # both the FK fast path (max_run sizes every probe batch's output
        # with no further syncs) and the dense direct-address table.
        jittable = cond is None or getattr(cond, "jittable", False)
        mr = None
        with monitoring.span(
                "table", "join-build",
                args={"op": self.name, "capacity": build_cap}
                if monitoring.enabled() else None):
            if built.stats is not None:
                mr = built.stats_host()[0]
            elif built.max_run is not None:
                mr = int(built.max_run)
            if mr is not None and jt in ("inner", "left", "right", "semi",
                                         "anti") and jittable:
                _maybe_build_dense(built, built.batch, built.key_ordinals)
                _settle(built.table)
        if built.table is not None:
            yield from self._dense_stream(ctx, built, probe_iter,
                                          tuple(probe_keys), build_is_right)
            return
        fast = mr is not None and 0 < mr <= self._FAST_PATH_MAX_RUN
        for pbatch in probe_iter:
            with self._probe_span(pbatch, "fast" if fast else "expand"):
                out, covered = self._probe_batch(
                    built, pbatch, mr if fast else None, jittable,
                    build_is_right, probe_keys)
            if covered_acc is not None and covered is not None:
                covered_acc = covered_acc | covered
            yield out
        if covered_acc is not None:
            build_unmatched = ~covered_acc & built.row_live
            # A fake empty probe batch supplies the null side's schema.
            yield self._null_extend_build(
                built, build_unmatched, self._probe_schema_batch(),
                build_is_right)

    def _dense_stream(self, ctx, built: BuiltSide, probe_iter,
                      probe_keys: Tuple[int, ...], build_is_right: bool):
        """The direct-address probe over a probe stream. One algorithm
        whose gather site follows what it observes.

        An inner join without a condition is the one shape in which a
        probe row can vanish with no build column needed to decide it, and
        there the build side's rows are gathered LATE: a window of probe
        batches (the group the consumer's ``coalesce_iter`` would make of
        their outputs) has its lookups dispatched, its match counts pulled
        in ONE ``device_get`` — the pull the consumer pays today, which it
        now finds as ``rows_hint`` — and then its emits: at the count's
        capacity bucket where the consumer's own rule would have compacted
        the output (``bucket * PROBE_SHRINK_RATIO <= capacity``), else at
        the probe's capacity under a selection vector. A 0.5 %-match probe
        of 1,048,576 rows thus gathers 6,144 build rows, not 1,048,576 of
        which ``_shrink`` drops all but those (20.7 of 36.7 ms a batch on
        a v5e: PERF.md, PR 37).

        Everything else takes ``_dense_step``'s single program as ever
        (``joinEagerBatches``): outer and semi/anti joins, a condition; a
        batch whose output is too small for any consumer to count
        (``MIN_SHRINK_BYTES``); and a join whose last window compacted
        nothing — there the count buys nothing, and a consumer that never
        shrinks (an Expand, a repartitioning exchange) would pay a pull a
        window for it, so the join stops asking for the rest of the
        query."""
        from spark_rapids_tpu import config as C
        from spark_rapids_tpu.columnar.batch import (
            MIN_SHRINK_BYTES, PROBE_SHRINK_RATIO, group_by_goal)
        from spark_rapids_tpu.memory.oom import (effective_batch_target,
                                                 retry_on_oom)
        dense = self._dense_jit_fn()

        def eager(pbatch):
            monitoring.count("joinEagerBatches")
            with self._probe_span(pbatch, "dense"):
                return retry_on_oom(dense, built, pbatch,
                                    probe_keys=probe_keys,
                                    build_is_right=build_is_right)

        if self.join_type != "inner" or self.condition is not None:
            for pbatch in probe_iter:
                yield eager(pbatch)
            return
        lookup, emit = _late_jit_fns()
        build_row_bytes = -(-built.batch.device_size_bytes()
                            // max(built.batch.capacity, 1))
        quiet_key = f"join-late-quiet:{id(self):x}"

        def out_bytes(pbatch):
            return (pbatch.device_size_bytes()
                    + pbatch.capacity * build_row_bytes)

        for window in group_by_goal(
                probe_iter,
                effective_batch_target(int(ctx.conf.get(C.BATCH_SIZE_ROWS))),
                int(ctx.conf.get(C.BATCH_SIZE_BYTES)), out_bytes):
            quiet = ctx.cache.get(quiet_key)
            late = [not quiet and out_bytes(pbatch) > MIN_SHRINK_BYTES
                    for pbatch in window]
            if not any(late):
                for pbatch in window:
                    yield eager(pbatch)
                continue
            outs: List = []
            for pbatch, ask in zip(window, late):
                if not ask:
                    outs.append(eager(pbatch))
                    continue
                with self._probe_span(pbatch, "late-lookup"):
                    outs.append(retry_on_oom(lookup, built, pbatch,
                                             probe_keys=probe_keys))
            asked = [i for i, ask in enumerate(late) if ask]
            monitoring.count("joinLateWindows")
            with monitoring.span(
                    "counts", "join-probe",
                    args={"op": self.name, "path": "late-counts",
                          "batches": len(asked)}
                    if monitoring.enabled() else None):
                counts = jax.device_get([outs[i][2] for i in asked])
            compacted = False
            for i, c in zip(asked, counts):
                pbatch, (pos, found, count) = window[i], outs[i]
                cap = bucket_capacity(max(int(c), 1))
                if cap * PROBE_SHRINK_RATIO > pbatch.capacity:
                    cap = None
                compacted |= cap is not None
                monitoring.count("joinLateEmitCapacity" if cap is None
                                 else "joinLateEmitBucket")
                with self._probe_span(pbatch, "late-emit"):
                    out = retry_on_oom(emit, built.batch, pbatch, pos,
                                       found, count, out_cap=cap,
                                       build_is_right=build_is_right)
                out.rows_hint = int(c)
                outs[i] = out
            if not compacted:
                ctx.cache[quiet_key] = True
            yield from outs

    def _probe_batch(self, built: BuiltSide, pbatch: DeviceBatch,
                     fast_run: Optional[int], jittable: bool,
                     build_is_right: bool, probe_keys):
        """One probe batch off the dense path: ``(out, covered)``. With
        ``fast_run`` (the build side's longest key run, small) the output
        is sized from the probe's capacity and nothing is pulled; without
        it the pair count is."""
        from spark_rapids_tpu.memory.oom import retry_on_oom
        if fast_run is not None:
            out_cap = bucket_capacity(max(pbatch.capacity * fast_run, 1))
            if jittable:
                return retry_on_oom(
                    self._probe_jit_fn(),
                    built, pbatch, out_cap=out_cap,
                    build_is_right=build_is_right,
                    probe_keys=tuple(probe_keys))
            lo, counts, plive = probe_ranges(
                built, pbatch, probe_keys, built.null_safe)
            return self._emit_expanded(
                built, pbatch, lo, counts, plive, out_cap,
                build_is_right, probe_keys)
        # (Semi/anti also go through expansion: candidate fingerprint
        # ranges must be key-verified before deciding hit/miss.) The
        # eagerly-computed ranges are reused by the emit step — probe
        # keys are hashed once per batch.
        lo, counts, plive = probe_ranges(built, pbatch, probe_keys,
                                         built.null_safe)
        total = int(jnp.sum(counts))
        out_cap = bucket_capacity(max(total, 1))
        if jittable:
            return self._emit_jit_fn()(
                built, pbatch, lo, counts, plive, out_cap=out_cap,
                build_is_right=build_is_right,
                probe_keys=tuple(probe_keys))
        return self._emit_expanded(
            built, pbatch, lo, counts, plive, out_cap,
            build_is_right, probe_keys)

    def _probe_schema_batch(self) -> DeviceBatch:
        build_right = self.join_type != "right"
        probe_child = self.children[0] if build_right else self.children[1]
        return _empty_like(probe_child.schema)

    def _emit_expanded(self, built: BuiltSide, pbatch: DeviceBatch,
                       lo, counts, plive, out_cap: int,
                       build_is_right: bool, probe_keys=None):
        """Expand matches for one probe batch. Returns (out_batch,
        covered_build_rows_or_None)."""
        from spark_rapids_tpu.columnar.rowmove import gather_rows
        jt = self.join_type
        cond = self.condition
        probe_cap = pbatch.capacity
        p, b, valid, total, _overflow = expand_pairs(lo, counts, out_cap,
                                                     probe_cap)
        if built.key_ordinals is not None and probe_keys is not None:
            valid = _pair_keys_equal(built, b, pbatch, p, probe_keys, valid)
        probe_out = gather_rows(pbatch, p, total, valid_dst=valid)
        build_out = gather_rows(built.batch, b, total, valid_dst=valid)
        if build_is_right:
            cols = tuple(probe_out.columns) + tuple(build_out.columns)
        else:
            cols = tuple(build_out.columns) + tuple(probe_out.columns)
        pairs = DeviceBatch(cols, total)

        if cond is not None:
            c = as_device_column(cond.eval(pairs), pairs)
            cond_keep = c.data & c.validity & valid
        else:
            cond_keep = valid

        if jt in ("inner", "cross"):
            return pairs.with_sel(cond_keep), None
        if jt in ("semi", "anti"):
            hit = jax.ops.segment_max(
                cond_keep.astype(jnp.int32), p, num_segments=probe_cap) > 0
            keep = (hit if jt == "semi" else ~hit) & pbatch.row_mask()
            return pbatch.with_sel(keep), None
        # Outer joins: survivors + unmatched probe rows with NULL side.
        survivors = pairs.with_sel(cond_keep)
        probe_hit = jax.ops.segment_max(
            cond_keep.astype(jnp.int32), p, num_segments=probe_cap) > 0
        probe_unmatched = ~probe_hit & pbatch.row_mask()
        extra = self._null_extend(pbatch, probe_unmatched, built,
                                  build_is_right)
        out = concat_batches(
            [survivors, extra],
            bucket_capacity(survivors.capacity + extra.capacity))
        if jt == "full":
            build_cap = built.batch.capacity
            covered = jax.ops.segment_max(
                (cond_keep & valid).astype(jnp.int32),
                jnp.clip(b, 0, build_cap - 1), num_segments=build_cap) > 0
            return out, covered
        return out, None

    def _null_extend(self, pbatch: DeviceBatch, keep, built: BuiltSide,
                     build_is_right: bool) -> DeviceBatch:
        """Probe rows with a NULL build side (selection-vector, no move)."""
        kept = pbatch.with_sel(keep & pbatch.row_mask())
        nulls = [DeviceColumn.full_null(
            c.dtype, kept.capacity,
            c.string_width if c.dtype.is_string else 8)
            for c in built.batch.columns]
        if build_is_right:
            cols = tuple(kept.columns) + tuple(nulls)
        else:
            cols = tuple(nulls) + tuple(kept.columns)
        return DeviceBatch(cols, kept.num_rows, sel=kept.sel)

    def _null_extend_build(self, built: BuiltSide, keep, pbatch: DeviceBatch,
                           build_is_right: bool) -> DeviceBatch:
        # built.batch's live rows are NOT a prefix (fingerprint-sorted with
        # null-key rows at the end): num_rows=capacity makes row_mask read
        # the selection vector alone.
        keep = keep & built.row_live
        kept = DeviceBatch(built.batch.columns,
                           jnp.asarray(built.batch.capacity, jnp.int32),
                           sel=keep)
        nulls = [DeviceColumn.full_null(
            c.dtype, kept.capacity,
            c.string_width if c.dtype.is_string else 8)
            for c in pbatch.columns]
        if build_is_right:
            cols = tuple(nulls) + tuple(kept.columns)
        else:
            cols = tuple(kept.columns) + tuple(nulls)
        return DeviceBatch(cols, kept.num_rows, sel=kept.sel)


# ---------------------------------------------------------------------------
# Execs
# ---------------------------------------------------------------------------

class ShuffledHashJoinExec(Exec, _JoinKernelMixin):
    """Both sides co-partitioned by key (GpuShuffledHashJoinExec). The build
    side (right for left/inner/..., left for 'right' joins) is coalesced to
    a single batch per partition — RequireSingleBatch, as in the reference.
    """

    def __init__(self, left: Exec, right: Exec,
                 left_keys: Sequence[Expression],
                 right_keys: Sequence[Expression],
                 join_type: str = "inner",
                 condition: Optional[Expression] = None):
        super().__init__(left, right)
        assert join_type in JOIN_TYPES
        self.left_keys = list(left_keys)
        self.right_keys = list(right_keys)
        self.join_type = join_type
        self.condition = condition

    @property
    def schema(self) -> Schema:
        return _join_schema(self.children[0].schema,
                            self.children[1].schema, self.join_type)

    def num_partitions(self, ctx) -> int:
        delegate = self._replan_delegate(ctx)
        if delegate is not None:
            return delegate.num_partitions(ctx)
        return self.children[0].num_partitions(ctx)

    def _replan_delegate(self, ctx) -> Optional[Exec]:
        """The broadcast delegate a runtime re-plan swapped in for this
        query (parallel/replan.py), or None. Decisions are per-context:
        the cached physical plan and the host oracle never see them.
        BroadcastHashJoinExec overrides every consulting method, so a
        delegate can never consult itself."""
        from spark_rapids_tpu.parallel import replan as RP
        return RP.demoted(ctx, self)

    def _key_ordinals(self, side: Exec, keys) -> List[int]:
        # Keys must be bound references for the kernel; project otherwise.
        from spark_rapids_tpu.exprs.base import BoundReference
        ords = []
        for k in keys:
            assert isinstance(k, BoundReference), \
                "join keys must be pre-projected BoundReferences"
            ords.append(k.ordinal)
        return ords

    def execute_device(self, ctx, partition):
        delegate = self._replan_delegate(ctx)
        if delegate is not None:
            # Runtime demotion: stream the rewritten broadcast subtree —
            # the build side serves the already-materialized exchange,
            # the probe side reads its child UNSHUFFLED.
            yield from delegate.execute_device(ctx, partition)
            return
        # 'right' join probes with the right side preserved: build LEFT.
        build_right = self.join_type != "right"
        build_child = self.children[1] if build_right else self.children[0]
        probe_child = self.children[0] if build_right else self.children[1]
        build_keys = self.right_keys if build_right else self.left_keys
        probe_keys = self.left_keys if build_right else self.right_keys
        bbatches = list(build_child.execute_device(ctx, partition))
        if not bbatches:
            if self.join_type in ("inner", "semi", "cross"):
                return
            bbatches = []
        probe_iter = probe_child.execute_device(ctx, partition)
        if not bbatches:
            # Outer/anti with empty build: every probe row is unmatched.
            for pbatch in probe_iter:
                if self.join_type == "anti":
                    yield pbatch
                elif self.join_type in ("left", "right", "full"):
                    empty = _empty_like(build_child.schema)
                    built = build_side(empty, list(range(
                        len(self._key_ordinals(build_child, build_keys)))))
                    yield self._null_extend(
                        pbatch, pbatch.row_mask(), built, build_right)
            return
        total_bytes = sum(b.device_size_bytes() for b in bbatches)
        grace_budget = self._grace_bucket_budget(ctx)
        forced = bool(ctx.cache.get(self._grace_force_key()))
        if grace_budget is not None and (forced
                                         or total_bytes > grace_budget):
            yield from self._grace_join(
                ctx, bbatches, probe_iter, build_child, probe_child,
                build_keys, probe_keys, build_right, total_bytes,
                grace_budget)
            return
        built = self._build(ctx, bbatches,
                            self._key_ordinals(build_child, build_keys))
        yield from self._device_join_stream(
            ctx, built, probe_iter,
            self._key_ordinals(probe_child, probe_keys), build_right)

    # -- out-of-core grace hash join -----------------------------------------
    def _grace_force_key(self) -> str:
        return f"grace-join-force:{id(self):x}"

    def _grace_bucket_budget(self, ctx) -> Optional[int]:
        """Per-bucket byte budget when the grace path is available for
        this join, else None. The same number is the build-side size
        past which grace engages proactively."""
        from spark_rapids_tpu import config as C
        if not bool(ctx.conf.get(C.JOIN_GRACE_ENABLED)):
            return None
        if self.join_type == "cross" or not self.left_keys:
            return None
        frac = float(ctx.conf.get(C.JOIN_GRACE_BUILD_FRACTION))
        return max(int(ctx.catalog.device_budget * frac), 1 << 16)

    def _grace_retry(self, ctx, partition):
        """The OOM-ladder rung ABOVE host fallback (ops/base.py calls
        this when the device path dies on an exhausted spill/shrink
        ladder): force the grace-partitioned path for this join and
        re-run on device. Returns the retry iterator, or None when
        grace is unavailable / already forced (then host fallback is
        next, as before)."""
        from spark_rapids_tpu import faults
        if self._grace_bucket_budget(ctx) is None:
            return None
        key = self._grace_force_key()
        if ctx.cache.get(key):
            return None                 # grace itself OOMed: demote on
        ctx.cache[key] = True
        faults.record("graceJoinEngaged")
        ctx.metrics_for(self).add("graceJoinEngaged", 1)
        return self.execute_device(ctx, partition)

    def _grace_join(self, ctx, bbatches, probe_iter, build_child,
                    probe_child, build_keys, probe_keys,
                    build_right: bool, total_bytes: int,
                    bucket_budget: int):
        """Spill-partitioned grace hash join (the Grace/hybrid-hash
        classic, TPU-shaped): BOTH sides partition by the murmur3 key
        fingerprint through the staged exchange into spillable buckets
        (equal keys land in the same bucket on both sides by
        construction), then co-partitioned bucket pairs run the normal
        build/probe kernel one at a time. Peak HBM is one bucket's
        build side + one probe batch; everything else rides the spill
        tiers. Runs build sides FAR past the device budget on-device —
        beating the reference's RequireSingleBatch build restriction
        (GpuShuffledHashJoinExec / SURVEY §5.7)."""
        from spark_rapids_tpu import config as C, faults
        from spark_rapids_tpu.memory.stores import PRIORITY_SHUFFLE_OUTPUT
        from spark_rapids_tpu.ops.sort import (stage_spillables,
                                               staged_exchange)
        from spark_rapids_tpu.parallel.partitioning import HashPartitioning
        m = ctx.metrics_for(self)
        nb = max(2, -(-total_bytes // bucket_budget))
        nb = min(nb, max(int(ctx.conf.get(C.JOIN_GRACE_MAX_PARTITIONS)),
                         2))
        m.add("graceJoinPartitions", nb)
        faults.record("graceJoinPartitions", nb)
        bords = self._key_ordinals(build_child, build_keys)
        pords = self._key_ordinals(probe_child, probe_keys)
        bspill, _ = stage_spillables(ctx, iter(bbatches))
        pspill, _ = stage_spillables(ctx, probe_iter)
        bex = staged_exchange(bspill, build_child.schema,
                              HashPartitioning(list(build_keys), nb))
        pex = staged_exchange(pspill, probe_child.schema,
                              HashPartitioning(list(probe_keys), nb))
        try:
            for p in range(nb):
                bucket = list(bex.execute_device(ctx, p))
                probe_bucket = pex.execute_device(ctx, p)
                if not bucket:
                    # Empty build bucket: mirror the empty-build-side
                    # semantics per bucket (each probe row lives in
                    # exactly one bucket, so emitting here is exact).
                    if self.join_type == "anti":
                        yield from probe_bucket
                    elif self.join_type in ("left", "right", "full"):
                        empty = _empty_like(build_child.schema)
                        built = build_side(empty,
                                           list(range(len(bords))))
                        for pbatch in probe_bucket:
                            yield self._null_extend(
                                pbatch, pbatch.row_mask(), built,
                                build_right)
                    continue
                built = self._build(ctx, bucket, bords)
                yield from self._device_join_stream(
                    ctx, built, probe_bucket, pords, build_right)
        finally:
            for sb in bspill + pspill:
                sb.close()

    # -- host oracle ---------------------------------------------------------
    def execute_host(self, ctx, partition):
        yield from _host_join(self, ctx, partition)


class BroadcastHashJoinExec(ShuffledHashJoinExec):
    """Build side pre-broadcast (wrapped in BroadcastExchangeExec); probe
    side streams its partitions (GpuBroadcastHashJoinExec)."""

    def _grace_retry(self, ctx, partition):
        # A broadcast build side is shared across every probe partition;
        # grace-partitioning it per partition would rebuild the table N
        # times. OOM here demotes straight to host fallback (the planner
        # picked broadcast because the build side was SMALL — an OOM is
        # device pressure, not build-side size).
        return None

    def num_partitions(self, ctx) -> int:
        probe = self.children[0] if self.join_type != "right" else \
            self.children[1]
        return probe.num_partitions(ctx)

    def _probe_child(self):
        return self.children[0] if self.join_type != "right" else \
            self.children[1]

    def host_prefetchable(self) -> bool:
        # Only the PROBE side streams by this node's partition numbering;
        # the build side materializes once (builtside cache) — prefetching
        # it per probe partition would re-encode the whole build table
        # N times for nothing.
        from spark_rapids_tpu.parallel.stages import is_stage_boundary
        probe = self._probe_child()
        return not is_stage_boundary(probe) and probe.host_prefetchable()

    def prefetch_host(self, ctx, partition):
        from spark_rapids_tpu.parallel.stages import is_stage_boundary
        probe = self._probe_child()
        if not is_stage_boundary(probe):
            probe.prefetch_host(ctx, partition)

    def execute_device(self, ctx, partition):
        build_right = self.join_type != "right"
        build_child = self.children[1] if build_right else self.children[0]
        probe_child = self.children[0] if build_right else self.children[1]
        build_keys = self.right_keys if build_right else self.left_keys
        probe_keys = self.left_keys if build_right else self.right_keys
        # Full outer over a broadcast build would emit build-unmatched rows
        # once per probe partition; Spark never plans that shape either.
        assert self.join_type != "full" or \
            probe_child.num_partitions(ctx) == 1, \
            "full outer join requires a shuffled (co-partitioned) plan"
        probe_iter = probe_child.execute_device(ctx, partition)
        # The BuiltSide (collection + fingerprint sort of the broadcast
        # table) is built once and shared across probe partitions.
        cache_key = f"builtside:{id(self):x}"
        built = ctx.cache.get(cache_key)
        if built is None:
            bbatches = []
            # In cluster mode the broadcast child may ADOPT its single
            # from the transport-backed broadcast artifact cache
            # (parallel/broadcast_cache.py) instead of re-collecting —
            # this loop is the consumer of that hit; only the
            # fingerprint sort below is always process-local.
            for cp in range(build_child.num_partitions(ctx)):
                bbatches.extend(build_child.execute_device(ctx, cp))
            if bbatches:
                built = self._build(ctx, bbatches, self._key_ordinals(
                    build_child, build_keys))
            else:
                built = "EMPTY"
            ctx.cache[cache_key] = built
            ctx.metrics_for(self).add("buildSideBuilds", 1)
        if built == "EMPTY":
            for pbatch in probe_iter:
                if self.join_type == "anti":
                    yield pbatch
                elif self.join_type in ("left", "right", "full"):
                    empty = _empty_like(build_child.schema)
                    eb = build_side(empty, [0] if build_keys else [])
                    yield self._null_extend(pbatch, pbatch.row_mask(),
                                            eb, build_right)
            return
        yield from self._device_join_stream(
            ctx, built, probe_iter,
            self._key_ordinals(probe_child, probe_keys), build_right)


class BroadcastNestedLoopJoinExec(Exec, _JoinKernelMixin):
    """Cross / conditional nested-loop join: every probe (left) row pairs
    with every build (right/broadcast) row
    (GpuBroadcastNestedLoopJoinExec.scala). Output capacity is
    probe_cap * build_cap per batch pair — keep the build side small.

    'right' preserves the build side, 'left'/'full' the usual semantics;
    right/full require a single probe partition (build-unmatched rows are
    emitted once), matching how Spark plans these only when viable."""

    def __init__(self, left: Exec, right: Exec,
                 join_type: str = "cross",
                 condition: Optional[Expression] = None):
        super().__init__(left, right)
        assert join_type in JOIN_TYPES
        self.join_type = join_type
        self.condition = condition

    @property
    def schema(self) -> Schema:
        return _join_schema(self.children[0].schema,
                            self.children[1].schema, self.join_type)

    def num_partitions(self, ctx) -> int:
        return self.children[0].num_partitions(ctx)

    def host_prefetchable(self) -> bool:
        # Probe (left) side only — the broadcast build side is pulled
        # whole per partition, not by this node's partition numbering.
        from spark_rapids_tpu.parallel.stages import is_stage_boundary
        return not is_stage_boundary(self.children[0]) and \
            self.children[0].host_prefetchable()

    def prefetch_host(self, ctx, partition):
        from spark_rapids_tpu.parallel.stages import is_stage_boundary
        if not is_stage_boundary(self.children[0]):
            self.children[0].prefetch_host(ctx, partition)

    def execute_device(self, ctx, partition):
        jt = self.join_type
        assert jt not in ("right", "full") or \
            self.num_partitions(ctx) == 1, \
            f"nested-loop {jt} join needs a single probe partition"
        bbatches = []
        for cp in range(self.children[1].num_partitions(ctx)):
            bbatches.extend(self.children[1].execute_device(ctx, cp))
        probe_iter = self.children[0].execute_device(ctx, partition)
        if not bbatches:
            # Empty build side: left/full keep probes null-extended, anti
            # keeps all probes, inner/cross/semi/right emit nothing.
            empty = _empty_like(self.children[1].schema)
            built = BuiltSide(empty, None, empty.row_mask(),
                              empty.row_mask(), empty.num_rows)
            for pbatch in probe_iter:
                if jt == "anti":
                    yield pbatch
                elif jt in ("left", "full"):
                    yield self._null_extend(pbatch, pbatch.row_mask(),
                                            built, True)
            return
        build = coalesce_to_single_batch(bbatches)
        if build.sel is not None:
            # The NLJ pairs every probe row with build positions
            # 0..num_rows-1; a selection vector (small filtered build that
            # skipped the broadcast shrink) must compact first or deleted
            # rows would join as live.
            from spark_rapids_tpu.columnar.rowmove import compact_batch
            from spark_rapids_tpu.ops import kernel_cache as kc
            build = kc.lookup("compact-batch", (),
                              lambda: jax.jit(compact_batch))(build)
        built = BuiltSide(build, None, build.row_mask(),
                          build.row_mask(), build.num_rows)
        bcap = build.capacity
        covered_acc = jnp.zeros((bcap,), jnp.bool_) \
            if jt in ("right", "full") else None
        for pbatch in probe_iter:
            pcap = pbatch.capacity
            # lo=0, count=num_build_rows for every live probe row.
            lo = jnp.zeros((pcap,), jnp.int32)
            counts = jnp.where(pbatch.row_mask(),
                               build.num_rows.astype(jnp.int32), 0)
            out_cap = bucket_capacity(
                max(int(pbatch.num_rows) * int(build.num_rows), 1))
            out, covered = self._nlj_emit(built, pbatch, lo, counts,
                                          out_cap)
            if covered_acc is not None and covered is not None:
                covered_acc = covered_acc | covered
            if out is not None:
                yield out
        if covered_acc is not None:
            build_unmatched = ~covered_acc & built.row_live
            yield self._null_extend_build(
                built, build_unmatched,
                _empty_like(self.children[0].schema), True)

    def _nlj_emit(self, built, pbatch, lo, counts, out_cap):
        """Like _emit_expanded but with nested-loop join-type semantics:
        the probe is always the LEFT side; 'right' preserves the build."""
        jt = self.join_type
        cond = self.condition
        probe_cap = pbatch.capacity
        bcap = built.batch.capacity
        p, b, valid, total, _overflow = expand_pairs(lo, counts, out_cap,
                                                     probe_cap)
        left_cols = _gather_cols(pbatch, p, valid)
        right_cols = _gather_cols(built.batch, b, valid)
        pairs = DeviceBatch(tuple(left_cols) + tuple(right_cols), total)
        if cond is not None:
            c = as_device_column(cond.eval(pairs), pairs)
            cond_keep = c.data & c.validity & valid
        else:
            cond_keep = valid
        covered = None
        if jt in ("right", "full"):
            covered = jax.ops.segment_max(
                (cond_keep & valid).astype(jnp.int32),
                jnp.clip(b, 0, bcap - 1), num_segments=bcap) > 0
        if jt in ("inner", "cross"):
            return pairs.with_sel(cond_keep), covered
        if jt in ("semi", "anti"):
            hit = jax.ops.segment_max(
                cond_keep.astype(jnp.int32), p, num_segments=probe_cap) > 0
            keep = (hit if jt == "semi" else ~hit) & pbatch.row_mask()
            return pbatch.with_sel(keep), covered
        if jt == "right":
            # Only matched pairs here; unmatched build rows come at end.
            return pairs.with_sel(cond_keep), covered
        # left / full: survivors + probe-unmatched null-extended.
        survivors = pairs.with_sel(cond_keep)
        probe_hit = jax.ops.segment_max(
            cond_keep.astype(jnp.int32), p, num_segments=probe_cap) > 0
        probe_unmatched = ~probe_hit & pbatch.row_mask()
        extra = self._null_extend(pbatch, probe_unmatched, built, True)
        return concat_batches(
            [survivors, extra],
            bucket_capacity(survivors.capacity + extra.capacity)), covered

    def execute_host(self, ctx, partition):
        yield from _host_join(self, ctx, partition, nested_loop=True)


# ---------------------------------------------------------------------------
# Helpers
# ---------------------------------------------------------------------------

def _empty_like(schema: Schema) -> DeviceBatch:
    cols = []
    for _, t in schema:
        cols.append(DeviceColumn.full_null(t, 8))
    return DeviceBatch(tuple(cols), jnp.asarray(0, jnp.int32))


def _empty_host_batch(schema: Schema) -> HostBatch:
    cols = []
    for _, t in schema:
        if t.is_string:
            cols.append(HostColumn(t, None, np.zeros(0, np.bool_),
                                   str_matrix=np.zeros((0, 1), np.uint8),
                                   str_lengths=np.zeros(0, np.int32)))
        else:
            cols.append(HostColumn(t, np.zeros(0, t.np_dtype),
                                   np.zeros(0, np.bool_)))
    return HostBatch(tuple(n for n, _ in schema), cols)


def _host_join(op, ctx, partition, nested_loop: bool = False):
    """Vectorized host join with SQL equi-join null semantics.

    Equi-joins reduce each key tuple to one int64 code per row (shared
    code space across sides, NaN==NaN and -0.0==0.0 canonical —
    columnar/host.py encode_key_pair), sort the build side by code, and
    probe every left row with two searchsorted calls; pair expansion is
    one repeat+gather, conditions evaluate ONCE over the gathered pair
    batch, and every emission mode is an index-array gather (negative
    index = null extension). That keeps the exact emission order of the
    row loop this replaced: pairs per left row with build rows
    ascending, unmatched right rows appended at the end. Nested-loop
    joins expand the cross product in bounded chunks with the same
    vectorized condition eval."""
    from spark_rapids_tpu.columnar.host import (
        concat_host_batches, encode_key_pair, stable_code_argsort)

    def _collect(child, parts, cache_tag=None):
        # Broadcast sides span EVERY child partition; without a cache
        # each probe partition would re-execute the whole build subtree
        # (scans, upstream joins and all) — collect once per query like
        # the device path's broadcast collection.
        key = None
        if cache_tag is not None:
            key = f"bcast-host:{id(op):x}:{cache_tag}"
            hit = ctx.cache.get(key)
            if hit is not None:
                return hit
        hbs = []
        for cp in parts:
            hbs.extend(child.execute_host(ctx, cp))
        out = (concat_host_batches(hbs) if hbs
               else _empty_host_batch(child.schema))
        if key is not None:
            ctx.cache[key] = out
        return out

    # For shuffled joins the oracle joins per partition; for broadcast the
    # build side is global. Simplest correct oracle: join THIS partition's
    # probe rows against the appropriate build rows.
    lchild, rchild = op.children
    if isinstance(op, BroadcastNestedLoopJoinExec):
        lb = _collect(lchild, [partition])
        rb = _collect(rchild, range(rchild.num_partitions(ctx)), "build")
        lkeys = rkeys = None
    elif isinstance(op, BroadcastHashJoinExec):
        if op.join_type != "right":
            lb = _collect(lchild, [partition])
            rb = _collect(rchild, range(rchild.num_partitions(ctx)),
                          "build")
        else:
            lb = _collect(lchild, range(lchild.num_partitions(ctx)),
                          "build")
            rb = _collect(rchild, [partition])
        lkeys, rkeys = op.left_keys, op.right_keys
    else:
        lb = _collect(lchild, [partition])
        rb = _collect(rchild, [partition])
        lkeys, rkeys = op.left_keys, op.right_keys

    nl, nr = lb.num_rows, rb.num_rows
    lschema, rschema = lchild.schema, rchild.schema
    jt = op.join_type
    cond = op.condition

    def eval_cond(li_p, ri_p):
        if cond is None:
            return np.ones(len(li_p), np.bool_)
        if not len(li_p):
            return np.zeros(0, np.bool_)
        hb = HostBatch(
            tuple(n for n, _ in tuple(lschema) + tuple(rschema)),
            [c.take(li_p) for c in lb.columns]
            + [c.take(ri_p) for c in rb.columns])
        c = as_host_column(cond.eval_host(hb), hb)
        return np.asarray(c.data, np.bool_) & np.asarray(c.validity,
                                                         np.bool_)

    if nested_loop:
        li_parts, ri_parts = [], []
        step = max(1, (1 << 20) // max(1, nr))
        ridx = np.arange(nr, dtype=np.int64)
        for blo in range(0, nl, step):
            bhi = min(nl, blo + step)
            li_p = np.repeat(np.arange(blo, bhi, dtype=np.int64), nr)
            ri_p = np.tile(ridx, bhi - blo)
            ok = eval_cond(li_p, ri_p)
            li_parts.append(li_p[ok])
            ri_parts.append(ri_p[ok])
        li_f = (np.concatenate(li_parts) if li_parts
                else np.zeros(0, np.int64))
        ri_f = (np.concatenate(ri_parts) if ri_parts
                else np.zeros(0, np.int64))
    else:
        lval = np.ones(nl, np.bool_)
        rval = np.ones(nr, np.bool_)
        cl_parts, cr_parts = [], []
        for lk, rk in zip(lkeys, rkeys):
            a, b = lb.columns[lk.ordinal], rb.columns[rk.ordinal]
            ca, cb = encode_key_pair(a, b)
            cl_parts.append(ca)
            cr_parts.append(cb)
            lval &= np.asarray(a.validity, np.bool_)
            rval &= np.asarray(b.validity, np.bool_)
        if len(cl_parts) == 1:
            cl, cr = cl_parts[0], cr_parts[0]
        else:
            allc = np.ascontiguousarray(np.concatenate(
                [np.stack(cl_parts, 1), np.stack(cr_parts, 1)]))
            v = allc.view(np.dtype((np.void, allc.shape[1] * 8))).ravel()
            _, inv = np.unique(v, return_inverse=True)
            inv = inv.astype(np.int64)
            cl, cr = inv[:nl], inv[nl:]
        # The build-side sort order and its equal-run boundaries are
        # invariant across probe partitions: every key (re)encoding is
        # order-preserving and equality-exact over the same build rows,
        # so per-partition codes permute and segment identically. Cache
        # them per (join, build batch) — a broadcast build (one shared
        # batch) then sorts ONCE per query instead of once per probe
        # partition; only the d-sized unique-code gather is per-call.
        skey = f"hjoin-order:{id(op):x}"
        cached = ctx.cache.get(skey)
        if cached is not None and cached[0] is rb:
            rs_order, rstart, rend = cached[1], cached[2], cached[3]
        else:
            rsel = np.flatnonzero(rval)
            rs_order = rsel[stable_code_argsort(cr[rsel])]
            cr_sorted = cr[rs_order]
            if len(cr_sorted):
                rstart = np.flatnonzero(np.concatenate(
                    [np.ones(1, np.bool_),
                     cr_sorted[1:] != cr_sorted[:-1]]))
                rend = np.concatenate(
                    [rstart[1:], np.array([len(cr_sorted)], np.int64)])
            else:
                rstart = rend = np.zeros(0, np.int64)
            ctx.cache[skey] = (rb, rs_order, rstart, rend)
        # One binary search per probe row into the UNIQUE build codes,
        # not two over the full build: a probe's [lo, hi) run bounds
        # come from the run-length table of the sorted codes.
        if len(rs_order):
            uniq = cr[rs_order[rstart]]
            base = int(uniq[0])
            spread = int(uniq[-1]) - base + 1
            if spread <= max(1 << 20, 8 * len(uniq)):
                # Dense build codes (string ranks always are; int keys
                # usually): a direct [lo, hi) lookup table turns the
                # per-probe-row binary search into one O(1) gather.
                lut_lo = np.zeros(spread, np.int64)
                lut_hi = np.zeros(spread, np.int64)
                lut_lo[uniq - base] = rstart
                lut_hi[uniq - base] = rend
                idx = cl - base
                inb = (idx >= 0) & (idx < spread) & lval
                idx = np.where(inb, idx, 0)
                plo = np.where(inb, lut_lo[idx], 0)
                phi = np.where(inb, lut_hi[idx], 0)
            else:
                pos = np.minimum(np.searchsorted(uniq, cl, "left"),
                                 len(uniq) - 1)
                hit = (uniq[pos] == cl) & lval
                plo = np.where(hit, rstart[pos], 0)
                phi = np.where(hit, rend[pos], 0)
        else:
            plo = phi = np.zeros(nl, np.int64)
        if len(rstart) == len(rs_order):
            # Every build key is unique (dimension tables): each probe
            # row has 0 or 1 match, so pair expansion is a masked
            # gather — no repeat/cumsum machinery.
            mask = phi > plo
            li_p = np.flatnonzero(mask)
            ri_p = rs_order[plo[li_p]]
        else:
            cnt = (phi - plo).astype(np.int64)
            tot = int(cnt.sum())
            li_p = np.repeat(np.arange(nl, dtype=np.int64), cnt)
            offs = np.arange(tot, dtype=np.int64) \
                - np.repeat(np.cumsum(cnt) - cnt, cnt)
            ri_p = rs_order[np.repeat(plo, cnt) + offs]
        ok = eval_cond(li_p, ri_p)
        li_f, ri_f = li_p[ok], ri_p[ok]

    names = tuple(n for n, _ in op.schema)
    lmatch = np.bincount(li_f, minlength=nl)
    if jt in ("semi", "anti"):
        keep = lmatch > 0 if jt == "semi" else lmatch == 0
        yield HostBatch(names, [c.filter(keep) for c in lb.columns])
        return
    if jt in ("left", "full"):
        unm = np.flatnonzero(lmatch == 0)
        li_all = np.concatenate([li_f, unm])
        ri_all = np.concatenate([ri_f, np.full(len(unm), -1, np.int64)])
        order = np.argsort(li_all, kind="stable")
        li_all, ri_all = li_all[order], ri_all[order]
    else:                                    # inner / cross / right pairs
        li_all, ri_all = li_f, ri_f
    if jt in ("right", "full"):
        rmatched = np.zeros(nr, np.bool_)
        rmatched[ri_f] = True
        runm = np.flatnonzero(~rmatched)
        li_all = np.concatenate([li_all,
                                 np.full(len(runm), -1, np.int64)])
        ri_all = np.concatenate([ri_all, runm])
    cols = [c.take(li_all, null_on_negative=True) for c in lb.columns] \
        + [c.take(ri_all, null_on_negative=True) for c in rb.columns]
    yield HostBatch(names, cols)


def _rows_to_hb(rows, schema) -> HostBatch:
    names = tuple(n for n, _ in schema)
    cols = []
    for ci, (_, t) in enumerate(schema):
        cols.append(HostColumn.from_values(t, [r[ci] for r in rows]))
    return HostBatch(names, cols)
