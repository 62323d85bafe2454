"""Window functions (ref: GpuWindowExec.scala:92 + GpuWindowExpression.scala
823 LoC — partition/order windows via cuDF rolling aggs, re-designed as
sorted segmented scans for TPU).

Device kernel per batch (whole partition required single-batch, like the
reference's window exec):
  1. radix-sort rows by (partition fingerprint, order keys) — reuses
     ops/kernels.py passes, so partitions become contiguous segments with
     rows in frame order;
  2. segment/peer boundary masks drive everything else:
     - row_number/rank/dense_rank from boundary cumsums,
     - lead/lag as global shifts masked at partition edges,
     - aggregates as segment reductions broadcast back, segmented running
       scans (cumsum minus segment-start), or rows-frame sliding windows
       (cumsum differences clamped to the segment);
  3. results scatter back to the original row order.

Frames supported (matching the v0.3 reference's envelope,
GpuWindowExpression.scala:100-151): whole-partition (no order), RANGE
UNBOUNDED PRECEDING..CURRENT ROW with peer (tie) semantics — Spark's
default frame — and ROWS frames with bounded preceding/following.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.batch import DeviceBatch, DeviceColumn
from spark_rapids_tpu.columnar.host import HostBatch, HostColumn, \
    all_valid as host_all_valid
from spark_rapids_tpu.exprs.base import Expression, as_device_column, \
    as_host_column
from spark_rapids_tpu.ops.base import Exec, ExecContext, Schema, timed
from spark_rapids_tpu.ops import kernels
from spark_rapids_tpu.ops.sort import SortOrder, coalesce_to_single_batch

UNBOUNDED = None


@dataclasses.dataclass
class WindowFrame:
    """Frame bounds; None = unbounded. Spark's default (RANGE
    UNBOUNDED..CURRENT with peers) is ``running=True``.

    ``range_interval=True`` makes preceding/following VALUE offsets over
    the single integer-typed order column (date days / timestamp micros)
    instead of row counts — the reference's RANGE-interval frame envelope
    (GpuWindowExpression.scala:114-151: one non-null date/time order
    column, ascending, day intervals)."""

    preceding: Optional[int] = UNBOUNDED
    following: Optional[int] = 0
    running_with_peers: bool = False
    range_interval: bool = False


@dataclasses.dataclass
class WindowSpec:
    partition_by: List[Expression]
    order_by: List[SortOrder]


class WindowFunction:
    """One window expression: fn(sorted ctx) -> (data, validity)."""

    def result_type(self) -> dt.DataType:
        raise NotImplementedError


@dataclasses.dataclass
class RowNumber(WindowFunction):
    def result_type(self):
        return dt.INT32


@dataclasses.dataclass
class Rank(WindowFunction):
    def result_type(self):
        return dt.INT32


@dataclasses.dataclass
class DenseRank(WindowFunction):
    def result_type(self):
        return dt.INT32


@dataclasses.dataclass
class Lead(WindowFunction):
    child: Expression
    offset: int = 1

    def result_type(self):
        return self.child.data_type()


@dataclasses.dataclass
class Lag(WindowFunction):
    child: Expression
    offset: int = 1

    def result_type(self):
        return self.child.data_type()


@dataclasses.dataclass
class WindowAgg(WindowFunction):
    """sum/count/min/max/avg over the window frame."""

    kind: str                   # sum | count | min | max | avg
    child: Optional[Expression]
    frame: WindowFrame = dataclasses.field(default_factory=WindowFrame)

    def result_type(self):
        if self.kind == "count":
            return dt.INT64
        if self.kind == "avg":
            return dt.FLOAT64
        t = self.child.data_type()
        if self.kind == "sum":
            return dt.FLOAT64 if t.is_floating else dt.INT64
        return t


@dataclasses.dataclass
class WindowExprSpec:
    name: str
    fn: WindowFunction
    spec: WindowSpec


# ---------------------------------------------------------------------------
# Device kernel
# ---------------------------------------------------------------------------

def _sorted_frame(batch: DeviceBatch, spec: WindowSpec):
    """Sort rows into (partition, order) frame; return sort context."""
    cap = batch.capacity
    live = batch.row_mask()
    passes = [jnp.where(live, jnp.uint32(0), jnp.uint32(0xFFFFFFFF))]
    pcols = [as_device_column(e.eval(batch), batch)
             for e in spec.partition_by]
    if pcols:
        passes.extend(kernels.key_fingerprint(pcols, cap))
    order_from = len(passes)
    for o in spec.order_by:
        col = as_device_column(o.child.eval(batch), batch)
        passes.extend(kernels.sort_key_passes(col, o.ascending,
                                              o.nulls_first))
    perm, sorted_passes = kernels.radix_sort(passes, cap)
    s_live = sorted_passes[0] == 0

    def changes(sw):
        return sw != jnp.concatenate([sw[:1], sw[:-1]])

    # Partition boundary at sorted position i (first row of a partition).
    new_part = jnp.arange(cap) == 0
    for sw in sorted_passes[1:order_from]:
        new_part = new_part | changes(sw)
    new_part = new_part & s_live
    # Peer boundary: partition boundary OR any order key differs.
    new_peer = new_part
    for sw in sorted_passes[order_from:]:
        new_peer = new_peer | (changes(sw) & s_live)
    return perm, s_live, new_part, new_peer


def _segment_starts(new_part, cap):
    idx = jnp.arange(cap, dtype=jnp.int32)
    # Start index of the segment containing each row = cummax of boundary
    # positions.
    return jax.lax.cummax(jnp.where(new_part, idx, 0))


def _run_ends(boundary_next, cap):
    """For each row, the index of the last row of its run, where
    ``boundary_next[i]`` marks i as a run's last row."""
    idx = jnp.arange(cap, dtype=jnp.int32)
    marked = jnp.where(boundary_next, idx, cap)
    rev = jnp.flip(marked)
    ends = jnp.flip(jax.lax.cummin(rev))
    return jnp.clip(ends, 0, cap - 1)


def _seg_id(new_part):
    return jnp.cumsum(new_part.astype(jnp.int32)) - 1


def compute_window(batch: DeviceBatch, exprs: Sequence[WindowExprSpec]):
    """Evaluate all window expressions; returns new columns appended to the
    original batch (original row order)."""
    cap = batch.capacity
    out_cols = list(batch.columns)
    # Group specs by identical WindowSpec object to share the sort.
    for wx in exprs:
        perm, s_live, new_part, new_peer = _sorted_frame(batch, wx.spec)
        inv = jnp.zeros((cap,), jnp.int32).at[perm].set(
            jnp.arange(cap, dtype=jnp.int32))
        seg_start = _segment_starts(new_part, cap)
        idx = jnp.arange(cap, dtype=jnp.int32)
        gid = _seg_id(new_part)
        gid = jnp.where(s_live, gid, jnp.int32(max(cap - 1, 0)))
        t = wx.fn.result_type()
        if t.is_string:
            out_cols.append(_eval_one_string(batch, wx, perm, inv, s_live,
                                             new_part, gid, idx, cap))
            continue
        data, valid = _eval_one(batch, wx, perm, s_live, new_part,
                                new_peer, seg_start, gid, idx, cap)
        # Scatter back to original order: sorted position p holds original
        # row perm[p]; result for original row r is at sorted pos inv[r].
        data_orig = jnp.take(data, inv, axis=0)
        valid_orig = jnp.take(valid, inv, axis=0) & batch.row_mask()
        data_orig = jnp.where(valid_orig, data_orig.astype(t.np_dtype),
                              jnp.zeros((), t.np_dtype))
        out_cols.append(DeviceColumn(t, data_orig, valid_orig))
    return DeviceBatch(tuple(out_cols), batch.num_rows)


def _eval_one_string(batch, wx, perm, inv, s_live, new_part, gid, idx, cap):
    """String-typed window results. The variable-width payload never flows
    through the numeric window arithmetic: each branch computes, per output
    row, the ORIGINAL row index whose string is the answer, and a single
    ``DeviceColumn.gather`` moves the (bytes, lengths) rows."""
    fn = wx.fn
    col = as_device_column(fn.child.eval(batch), batch)
    if isinstance(fn, (Lead, Lag)):
        off = fn.offset if isinstance(fn, Lead) else -fn.offset
        src = idx + off
        ok = (src >= 0) & (src < cap)
        src_c = jnp.clip(src, 0, cap - 1)
        same = jnp.take(gid, src_c, axis=0) == gid
        struct = ok & same & s_live & jnp.take(s_live, src_c, axis=0)
        src_orig = jnp.take(jnp.take(perm, src_c, axis=0), inv, axis=0)
        struct_orig = jnp.take(struct, inv, axis=0)
    elif isinstance(fn, WindowAgg) and fn.kind in ("min", "max"):
        frame = fn.frame
        if not (frame.preceding is UNBOUNDED and
                frame.following is UNBOUNDED and
                not frame.running_with_peers):
            raise NotImplementedError(
                "string min/max window: whole-partition frames only")
        # Second radix sort by (partition keys, child bytes) makes each
        # partition's winner the first live row of its segment; nulls sort
        # last, so an all-null partition's head is itself null.
        spec2 = WindowSpec(wx.spec.partition_by,
                           [SortOrder(fn.child, ascending=fn.kind == "min",
                                      nulls_first=False)])
        perm2, s_live2, new_part2, _ = _sorted_frame(batch, spec2)
        inv2 = jnp.zeros((cap,), jnp.int32).at[perm2].set(
            jnp.arange(cap, dtype=jnp.int32))
        head = _segment_starts(new_part2, cap)
        src_orig = jnp.take(jnp.take(perm2, head, axis=0), inv2, axis=0)
        struct_orig = jnp.take(s_live2, inv2, axis=0)
    else:
        raise NotImplementedError(
            "string window results for %s" % type(fn).__name__)
    return col.gather(src_orig, struct_orig & batch.row_mask())


def _eval_one(batch, wx, perm, s_live, new_part, new_peer, seg_start, gid,
              idx, cap):
    fn = wx.fn
    if isinstance(fn, RowNumber):
        return (idx - seg_start + 1), s_live
    if isinstance(fn, Rank):
        # First row index of the peer run, relative to segment start.
        peer_start = jax.lax.cummax(jnp.where(new_peer, idx, 0))
        return (peer_start - seg_start + 1), s_live
    if isinstance(fn, DenseRank):
        # Count of peer boundaries within the segment up to current row.
        pb = jnp.cumsum(new_peer.astype(jnp.int32))
        pb_at_start = jnp.take(pb, seg_start, axis=0)
        return (pb - pb_at_start + 1), s_live
    if isinstance(fn, (Lead, Lag)):
        col = as_device_column(fn.child.eval(batch), batch)
        sdata = jnp.take(col.data, perm, axis=0)
        svalid = jnp.take(col.validity, perm, axis=0) & s_live
        off = fn.offset if isinstance(fn, Lead) else -fn.offset
        src = idx + off
        ok = (src >= 0) & (src < cap)
        src_c = jnp.clip(src, 0, cap - 1)
        data = jnp.take(sdata, src_c, axis=0)
        valid = jnp.take(svalid, src_c, axis=0) & ok
        # Must stay inside the same partition.
        same = jnp.take(gid, src_c, axis=0) == gid
        valid = valid & same & s_live
        return data, valid
    if isinstance(fn, WindowAgg):
        return _eval_window_agg(batch, fn, perm, s_live, new_part,
                                new_peer, seg_start, gid, idx, cap,
                                wx.spec)
    raise NotImplementedError(type(fn).__name__)


def _seg_lower_bound(oval, lo0, hi0, target, cap, inclusive):
    """Vectorized per-row binary search within [lo0, hi0): first index j
    with oval[j] >= target (inclusive=False: > target). oval is ascending
    inside each segment; bounds confine the search to the row's segment."""
    lo, hi = lo0, hi0
    for _ in range(int(np.ceil(np.log2(max(cap, 2)))) + 1):
        mid = (lo + hi) // 2
        v = jnp.take(oval, jnp.clip(mid, 0, cap - 1), axis=0)
        go_right = (v <= target) if inclusive else (v < target)
        active = lo < hi
        lo = jnp.where(active & go_right, mid + 1, lo)
        hi = jnp.where(active & ~go_right, mid, hi)
    return lo


def _eval_window_agg(batch, fn: WindowAgg, perm, s_live, new_part, new_peer,
                     seg_start, gid, idx, cap, spec=None):
    if fn.child is not None:
        col = as_device_column(fn.child.eval(batch), batch)
        sdata = jnp.take(col.data, perm, axis=0)
        svalid = jnp.take(col.validity, perm, axis=0) & s_live
    else:
        sdata = jnp.ones((cap,), jnp.int64)
        svalid = s_live
    frame = fn.frame
    t = fn.result_type()

    if frame.preceding is UNBOUNDED and frame.following is UNBOUNDED and \
            not frame.running_with_peers:
        # Whole partition: segment reduce, broadcast back by gid.
        return _whole_partition(fn, sdata, svalid, gid, cap)

    if frame.range_interval:
        return _eval_range_interval(batch, fn, sdata, svalid, perm,
                                    s_live, new_part, seg_start, idx,
                                    cap, spec)

    # Running / ROWS frames via cumulative sums.
    if fn.kind in ("sum", "avg", "count"):
        acc_t = jnp.float64 if t.is_floating or fn.kind == "avg" \
            else jnp.int64
        vals = jnp.where(svalid, sdata.astype(acc_t),
                         jnp.zeros((), acc_t))
        if fn.kind == "count":
            vals = svalid.astype(jnp.int64)
        cum = jnp.cumsum(vals)
        cnt = jnp.cumsum(svalid.astype(jnp.int64))

        def upto(i):     # inclusive prefix inside segment
            c = jnp.take(cum, jnp.clip(i, 0, cap - 1), axis=0)
            n = jnp.take(cnt, jnp.clip(i, 0, cap - 1), axis=0)
            zero = i < 0
            return jnp.where(zero, 0, c), jnp.where(zero, 0, n)

        if frame.running_with_peers:
            # Spark default RANGE frame: end at the LAST peer of each row.
            last_of_run = jnp.concatenate(
                [new_peer[1:], jnp.ones((1,), jnp.bool_)])
            end = _run_ends(last_of_run, cap)
        elif frame.following is UNBOUNDED:
            # to segment end
            last_of_seg = jnp.concatenate(
                [new_part[1:], jnp.ones((1,), jnp.bool_)])
            end = _run_ends(last_of_seg, cap)
        else:
            seg_end = _run_ends(jnp.concatenate(
                [new_part[1:], jnp.ones((1,), jnp.bool_)]), cap)
            end = jnp.minimum(idx + frame.following, seg_end)
        if frame.preceding is UNBOUNDED:
            start = seg_start
        else:
            start = jnp.maximum(idx - frame.preceding, seg_start)
        c_end, n_end = upto(end)
        c_before, n_before = upto(start - 1)
        # start-1 could cross into previous segment; clamp via seg_start.
        c_start0, n_start0 = upto(seg_start - 1)
        c_before = jnp.where(start - 1 < seg_start, c_start0, c_before)
        n_before = jnp.where(start - 1 < seg_start, n_start0, n_before)
        s = c_end - c_before
        n = n_end - n_before
        if fn.kind == "count":
            return s.astype(jnp.int64), s_live
        if fn.kind == "avg":
            safe = jnp.where(n > 0, n, 1)
            return s / safe.astype(jnp.float64), s_live & (n > 0)
        return s.astype(t.np_dtype), s_live & (n > 0)

    if fn.kind in ("min", "max"):
        # Segmented running min/max via associative scan with reset flag.
        if frame.preceding is not UNBOUNDED or \
                frame.following not in (0, UNBOUNDED):
            raise NotImplementedError(
                "bounded-preceding min/max window frames")
        fill = kernels._identity_for(sdata.dtype, fn.kind)
        vals = jnp.where(svalid, sdata, fill)
        if frame.following is UNBOUNDED and not frame.running_with_peers:
            return _whole_partition(fn, sdata, svalid, gid, cap)

        def combine(a, b):
            a_flag, a_val, a_n = a
            b_flag, b_val, b_n = b
            op = jnp.minimum if fn.kind == "min" else jnp.maximum
            val = jnp.where(b_flag, b_val, op(a_val, b_val))
            n = jnp.where(b_flag, b_n, a_n + b_n)
            return a_flag | b_flag, val, n

        flags = new_part
        counts = svalid.astype(jnp.int64)
        _, scanned, ns = jax.lax.associative_scan(
            combine, (flags, vals, counts))
        if frame.running_with_peers:
            last_of_run = jnp.concatenate(
                [new_peer[1:], jnp.ones((1,), jnp.bool_)])
            end = _run_ends(last_of_run, cap)
            scanned = jnp.take(scanned, end, axis=0)
            ns = jnp.take(ns, end, axis=0)
        return scanned, s_live & (ns > 0)
    raise NotImplementedError(fn.kind)


def _eval_range_interval(batch, fn: WindowAgg, sdata, svalid, perm,
                         s_live, new_part, seg_start, idx, cap, spec):
    """RANGE BETWEEN (val - preceding) AND (val + following): frame bounds
    found by per-row segment-confined binary search over the (sorted)
    order-column values, then cumsum prefix differences — the TPU
    replacement for cuDF's range rolling windows
    (GpuWindowExpression.scala:114-151's envelope: ONE non-null integer
    date/time order column, ascending)."""
    assert spec is not None and len(spec.order_by) == 1, \
        "range-interval frames require exactly one order column"
    o = spec.order_by[0]
    assert o.ascending, "range-interval frames require ascending order"
    if fn.kind not in ("sum", "avg", "count"):
        raise NotImplementedError(
            "range-interval min/max window frames")
    ocol = as_device_column(o.child.eval(batch), batch)
    oval = jnp.take(ocol.data, perm, axis=0).astype(jnp.int64)
    seg_end = _run_ends(jnp.concatenate(
        [new_part[1:], jnp.ones((1,), jnp.bool_)]), cap)
    cur = oval
    if fn.frame.preceding is UNBOUNDED:
        start = seg_start
    else:
        # first index in segment with oval >= cur - preceding
        start = _seg_lower_bound(oval, seg_start, seg_end + 1,
                                 cur - fn.frame.preceding, cap,
                                 inclusive=False)
    if fn.frame.following is UNBOUNDED:
        end = seg_end
    else:
        # last index in segment with oval <= cur + following
        end = _seg_lower_bound(oval, seg_start, seg_end + 1,
                               cur + fn.frame.following, cap,
                               inclusive=True) - 1
    t = fn.result_type()
    acc_t = jnp.float64 if t.is_floating or fn.kind == "avg" else jnp.int64
    vals = svalid.astype(jnp.int64) if fn.kind == "count" else \
        jnp.where(svalid, sdata.astype(acc_t), jnp.zeros((), acc_t))
    cum = jnp.cumsum(vals)
    cnt = jnp.cumsum(svalid.astype(jnp.int64))

    def upto(i):
        c = jnp.take(cum, jnp.clip(i, 0, cap - 1), axis=0)
        n = jnp.take(cnt, jnp.clip(i, 0, cap - 1), axis=0)
        return jnp.where(i < 0, 0, c), jnp.where(i < 0, 0, n)

    c_end, n_end = upto(end)
    c_before, n_before = upto(start - 1)
    s = c_end - c_before
    n = n_end - n_before
    empty = end < start
    s = jnp.where(empty, 0, s)
    n = jnp.where(empty, 0, n)
    if fn.kind == "count":
        return s.astype(jnp.int64), s_live
    if fn.kind == "avg":
        safe = jnp.where(n > 0, n, 1)
        return s / safe.astype(jnp.float64), s_live & (n > 0)
    return s.astype(t.np_dtype), s_live & (n > 0)


def _whole_partition(fn: WindowAgg, sdata, svalid, gid, cap):
    t = fn.result_type()
    if fn.kind == "count":
        agg = jax.ops.segment_sum(svalid.astype(jnp.int64), gid,
                                  num_segments=cap)
        return jnp.take(agg, gid, axis=0), jnp.ones((cap,), jnp.bool_)
    if fn.kind in ("sum", "avg"):
        acc_t = jnp.float64 if fn.kind == "avg" or t.is_floating \
            else jnp.int64
        agg, counts = kernels.segment_reduce(
            sdata.astype(acc_t), svalid, gid, cap, "sum")
        n = jnp.take(counts, gid, axis=0)
        s = jnp.take(agg, gid, axis=0)
        if fn.kind == "avg":
            safe = jnp.where(n > 0, n, 1)
            return s / safe.astype(jnp.float64), n > 0
        return s.astype(t.np_dtype), n > 0
    agg, counts = kernels.segment_reduce(sdata, svalid, gid, cap, fn.kind)
    return (jnp.take(agg, gid, axis=0),
            jnp.take(counts, gid, axis=0) > 0)


# ---------------------------------------------------------------------------
# Exec
# ---------------------------------------------------------------------------

class WindowExec(Exec):
    """Appends window expression columns.

    OUT-OF-CORE (beyond GpuWindowExec v0.3's RequireSingleBatch): when a
    partitioned window's input exceeds a fraction of the device budget,
    the input range-splits by the window PARTITION KEYS into bounded
    spillable buckets — equal keys always land in one bucket, so each
    bucket's windows compute independently (the partition-chunked shape
    of SURVEY §5.7). Unpartitioned (whole-table frame) windows cannot
    chunk and keep the single-batch requirement."""

    def __init__(self, child: Exec, exprs: Sequence[WindowExprSpec]):
        super().__init__(child)
        self.exprs = list(exprs)

    @property
    def schema(self) -> Schema:
        base = list(self.children[0].schema)
        for wx in self.exprs:
            base.append((wx.name, wx.fn.result_type()))
        return tuple(base)

    def _window_fn(self, ctx):
        from spark_rapids_tpu import monitoring
        from spark_rapids_tpu.ops import kernel_cache as kc
        m = ctx.metrics_for(self)
        exprs = list(self.exprs)
        fp = kc.fingerprint(tuple(exprs))
        schema_fp = kc.schema_fingerprint(self.children[0].schema)

        def fn(b):
            entry = kc.lookup(
                "window", (fp, schema_fp, b.capacity),
                lambda: jax.jit(
                    lambda bb: compute_window(bb, exprs)), m)
            monitoring.count("windowBatches")
            monitoring.count("windowRowsIn", b.capacity)
            return kc.call(entry, m, b)
        return fn

    def execute_device(self, ctx, partition):
        from spark_rapids_tpu.ops.sort import out_of_core_partition
        # Chunking splits on the window PARTITION KEYS (equal keys share
        # a bucket); unpartitioned windows pass no orders and stay
        # single-batch.
        pcols = self.exprs[0].spec.partition_by if self.exprs else []
        orders = [SortOrder(c) for c in pcols]
        yield from out_of_core_partition(
            ctx, ctx.metrics_for(self),
            self.children[0].execute_device(ctx, partition),
            self.children[0].schema, orders, self._window_fn(ctx),
            trace_cat="window")

    # -- host engine ---------------------------------------------------------
    def execute_host(self, ctx, partition):
        hbs = list(self.children[0].execute_host(ctx, partition))
        if not hbs:
            return
        from spark_rapids_tpu.columnar.host import concat_host_batches
        hb = concat_host_batches(hbs)
        yield _host_window(hb, self.exprs, self.schema)


def _host_window_vectorized(hb: HostBatch, wx) -> "HostColumn":
    """One window expression evaluated with the lexsort/segment-boundary
    machinery of the vectorized host group-by: one stable lexsort over
    (partition codes, order-key codes), partition/peer boundary flags,
    then ranks as positions-in-segment, Lead/Lag as clamped shifted
    gathers, and frame aggregates as prefix-sum differences (the same
    cumsum-minus-segment-start shape the device kernels use). Results
    come back through the inverse permutation so output rows keep input
    order. Returns None for shapes the python oracle below still owns
    (min/max over bounded frames, string agg inputs, descending or
    null-bearing range frames)."""
    from spark_rapids_tpu.columnar.host import (encode_key,
                                                encode_sort_key)
    n = hb.num_rows
    fn = wx.fn
    if n == 0:
        return None
    pcols = [as_host_column(e.eval_host(hb), hb)
             for e in wx.spec.partition_by]
    ocols = [(as_host_column(o.child.eval_host(hb), hb), o)
             for o in wx.spec.order_by]
    ccol = None
    if isinstance(fn, (Lead, Lag, WindowAgg)) and \
            getattr(fn, "child", None) is not None:
        ccol = as_host_column(fn.child.eval_host(hb), hb)

    part_planes = []
    for c in pcols:
        part_planes.append((encode_key(c),
                            np.asarray(c.validity, np.int8)))
    okey_planes = []
    for c, o in ocols:
        valid = np.asarray(c.validity, np.bool_)
        null_rank = (valid if o.nulls_first else ~valid).astype(np.int8)
        code = encode_sort_key(c)
        if not o.ascending:
            code = np.where(valid, ~code, np.int64(0))
        okey_planes.append((null_rank, code))

    # Most-significant first; np.lexsort takes least-significant first.
    sig = []
    for code, val in part_planes:
        sig.append(code)
        sig.append(val)
    for null_rank, code in okey_planes:
        sig.append(null_rank)
        sig.append(code)
    if sig:
        order_idx = np.lexsort(tuple(reversed(sig)))
    else:
        order_idx = np.arange(n, dtype=np.int64)

    pos = np.arange(n, dtype=np.int64)
    seg_flags = np.zeros(n, np.bool_)
    seg_flags[0] = True
    for code, val in part_planes:
        sc, sv = code[order_idx], val[order_idx]
        seg_flags[1:] |= (sc[1:] != sc[:-1]) | (sv[1:] != sv[:-1])
    starts = np.flatnonzero(seg_flags).astype(np.int64)
    seg_len = np.diff(np.append(starts, n))
    seg_start = np.repeat(starts, seg_len)
    seg_end = np.repeat(starts + seg_len - 1, seg_len)
    r_local = pos - seg_start

    change = seg_flags.copy()
    for null_rank, code in okey_planes:
        snr, sc = null_rank[order_idx], code[order_idx]
        change[1:] |= (snr[1:] != snr[:-1]) | (sc[1:] != sc[:-1])
    rb = np.flatnonzero(change).astype(np.int64)
    run_len = np.diff(np.append(rb, n))
    peer_start = np.repeat(rb, run_len)
    peer_end = np.repeat(rb + run_len - 1, run_len)

    inv = np.empty(n, np.int64)
    inv[order_idx] = pos

    def out_numeric(t, data, validity):
        return HostColumn(t, np.where(validity, data, 0)
                          .astype(t.np_dtype),
                          np.asarray(validity, np.bool_)).take(inv)

    t = fn.result_type()
    if isinstance(fn, RowNumber):
        return out_numeric(t, r_local + 1, host_all_valid(n))
    if isinstance(fn, DenseRank):
        d = np.cumsum(change)
        dense = d - np.repeat(d[starts], seg_len) + 1
        return out_numeric(t, dense, host_all_valid(n))
    if isinstance(fn, Rank):
        return out_numeric(t, peer_start - seg_start + 1,
                           host_all_valid(n))
    if isinstance(fn, (Lead, Lag)):
        off = fn.offset if isinstance(fn, Lead) else -fn.offset
        tgt = pos + off
        inrange = (tgt >= seg_start) & (tgt <= seg_end)
        idx = np.where(inrange, order_idx[np.clip(tgt, 0, n - 1)],
                       np.int64(-1))
        return ccol.take(idx, null_on_negative=True).take(inv)
    if not isinstance(fn, WindowAgg):
        return None

    frame = fn.frame
    kind = fn.kind
    if ccol is not None and ccol.dtype.is_string and kind != "count":
        return None
    # Frame bounds as global [lo, hi] row ranges per row.
    if frame.running_with_peers:
        lo, hi = seg_start, peer_end
    elif frame.preceding is UNBOUNDED and frame.following is UNBOUNDED:
        lo, hi = seg_start, seg_end
    elif frame.range_interval:
        if not ocols:
            return None
        oc, oo = ocols[0]
        if (not oo.ascending or oc.dtype.is_string
                or not np.asarray(oc.validity, np.bool_).all()):
            return None
        ov = np.asarray(oc.data, np.float64)[order_idx]
        cur = ov                                  # current row's value
        lo = seg_start.copy()
        hi = seg_end.copy()
        for s0, sl in zip(starts.tolist(), seg_len.tolist()):
            s1 = s0 + sl
            vals_seg = ov[s0:s1]
            if frame.preceding is not UNBOUNDED:
                lo[s0:s1] = s0 + np.searchsorted(
                    vals_seg, cur[s0:s1] - frame.preceding, "left")
            if frame.following is not UNBOUNDED:
                hi[s0:s1] = s0 + np.searchsorted(
                    vals_seg, cur[s0:s1] + frame.following, "right") - 1
    else:
        lo = seg_start if frame.preceding is UNBOUNDED else \
            np.maximum(seg_start, pos - frame.preceding)
        hi = seg_end if frame.following is UNBOUNDED else \
            np.minimum(seg_end, pos + frame.following)

    empty = hi < lo
    loc = np.clip(lo, 0, n)
    hic = np.clip(hi + 1, 0, n)

    def prefix(x):
        return np.concatenate([np.zeros(1, x.dtype), np.cumsum(x)])

    if ccol is not None:
        cvalid = np.asarray(ccol.validity, np.bool_)[order_idx]
    else:
        cvalid = host_all_valid(n)
    Pc = prefix(cvalid.astype(np.int64))
    cnt = np.where(empty, 0, Pc[hic] - Pc[loc])

    if kind == "count":
        total = np.where(empty, 0, hi - lo + 1)
        data = cnt if ccol is not None else total
        return out_numeric(t, data, host_all_valid(n))

    if kind in ("sum", "avg"):
        x = np.asarray(ccol.data)[order_idx]
        if t.is_floating or kind == "avg":
            xf = np.where(cvalid, x.astype(np.float64), 0.0)
            if np.isnan(xf).any():
                # A prefix-sum difference leaks NaN into every frame
                # after the NaN (cumsum is global); the oracle sums
                # only the frame's own rows.
                return None
            P = prefix(xf)
        else:
            with np.errstate(over="ignore"):
                P = prefix(np.where(cvalid, x.astype(np.int64),
                                    np.int64(0)))
        s = np.where(empty, 0, P[hic] - P[loc])
        ok = cnt > 0
        if kind == "avg":
            data = np.where(ok, s / np.where(ok, cnt, 1), 0.0)
        else:
            data = np.where(ok, s, 0)
        return out_numeric(t, data, ok)

    # min/max: only the whole-segment frame vectorizes (a prefix trick
    # does not exist for range min); bounded frames stay on the oracle.
    if not (np.array_equal(lo, seg_start) and np.array_equal(hi, seg_end)):
        return None
    x = np.asarray(ccol.data)[order_idx]
    ok = np.add.reduceat(cvalid.astype(np.int64), starts) > 0
    if ccol.dtype.is_floating:
        f = x.astype(np.float64)
        nanm = cvalid & np.isnan(f)
        nonnan = cvalid & ~np.isnan(f)
        if kind == "max":
            m = np.maximum.reduceat(np.where(nonnan, f, -np.inf), starts)
            hasnan = np.add.reduceat(nanm.astype(np.int64), starts) > 0
            data_g = np.where(hasnan, np.nan, m)
        else:
            m = np.minimum.reduceat(np.where(nonnan, f, np.inf), starts)
            nncnt = np.add.reduceat(nonnan.astype(np.int64), starts)
            data_g = np.where(nncnt > 0, m, np.nan)
        data_g = np.where(ok, data_g, 0.0)
    else:
        xi64 = x.astype(np.int64)
        if kind == "max":
            data_g = np.maximum.reduceat(
                np.where(cvalid, xi64, np.iinfo(np.int64).min), starts)
        else:
            data_g = np.minimum.reduceat(
                np.where(cvalid, xi64, np.iinfo(np.int64).max), starts)
        data_g = np.where(ok, data_g, 0)
    data = np.repeat(data_g, seg_len)
    validity = np.repeat(ok, seg_len)
    return out_numeric(t, data, validity)


def _host_window(hb: HostBatch, exprs, schema) -> HostBatch:
    """Host window: vectorized per expression, python oracle fallback."""
    n = hb.num_rows
    out_cols = {i: [None] * n for i in range(len(exprs))}
    for xi, wx in enumerate(exprs):
        fast = _host_window_vectorized(hb, wx)
        if fast is not None:
            out_cols[xi] = fast
            continue
        pcols = [as_host_column(e.eval_host(hb), hb).to_list()
                 for e in wx.spec.partition_by]
        ocols = [(as_host_column(o.child.eval_host(hb), hb).to_list(), o)
                 for o in wx.spec.order_by]
        ccol = None
        if isinstance(wx.fn, (Lead, Lag, WindowAgg)) and \
                getattr(wx.fn, "child", None) is not None:
            ccol = as_host_column(wx.fn.child.eval_host(hb), hb).to_list()

        def canon(v):
            if isinstance(v, float):
                if np.isnan(v):
                    return "NaN"
                if v == 0:
                    return 0.0
            return v

        def order_key(i):
            parts = []
            for vals, o in ocols:
                v = vals[i]
                null_rank = 0 if (v is None) == o.nulls_first else 1
                if v is None:
                    parts.append((null_rank, 0))
                else:
                    k = v
                    if isinstance(v, float):
                        k = (1, 0.0) if np.isnan(v) else (0, v)
                    from spark_rapids_tpu.ops.sort import _Rev
                    parts.append((null_rank,
                                  k if o.ascending else _Rev(k)))
            return tuple(parts)

        groups = {}
        for i in range(n):
            key = tuple(canon(pc[i]) for pc in pcols)
            groups.setdefault(key, []).append(i)
        for key, idxs in groups.items():
            idxs = sorted(idxs, key=order_key)
            peers = []
            prev = object()
            for rank_i, i in enumerate(idxs):
                ok = order_key(i)
                if ok != prev:
                    peers.append(rank_i)
                    prev = ok
                else:
                    peers.append(peers[-1])
            ovals = ocols[0][0] if ocols else None
            out_cols[xi] = _host_eval_fn(
                wx.fn, idxs, peers, ccol, out_cols[xi], ovals)
    cols = list(hb.columns)
    for xi, wx in enumerate(exprs):
        if isinstance(out_cols[xi], HostColumn):
            cols.append(out_cols[xi])
        else:
            t = wx.fn.result_type()
            cols.append(HostColumn.from_values(t, out_cols[xi]))
    return HostBatch(tuple(n_ for n_, _ in schema), cols)


def _host_eval_fn(fn, idxs, peers, ccol, out, ovals=None):
    npart = len(idxs)
    if isinstance(fn, RowNumber):
        for r, i in enumerate(idxs):
            out[i] = r + 1
    elif isinstance(fn, Rank):
        for r, i in enumerate(idxs):
            out[i] = peers[r] + 1
    elif isinstance(fn, DenseRank):
        dense = []
        d = 0
        for r in range(npart):
            if r == 0 or peers[r] != peers[r - 1]:
                d += 1
            dense.append(d)
        for r, i in enumerate(idxs):
            out[i] = dense[r]
    elif isinstance(fn, (Lead, Lag)):
        off = fn.offset if isinstance(fn, Lead) else -fn.offset
        for r, i in enumerate(idxs):
            s = r + off
            out[i] = ccol[idxs[s]] if 0 <= s < npart else None
    elif isinstance(fn, WindowAgg):
        for r, i in enumerate(idxs):
            frame = fn.frame
            if frame.running_with_peers:
                hi = r
                while hi + 1 < npart and peers[hi + 1] == peers[r]:
                    hi += 1
                lo = 0
            elif frame.preceding is UNBOUNDED and \
                    frame.following is UNBOUNDED:
                lo, hi = 0, npart - 1
            elif frame.range_interval:
                cur = ovals[i]
                lo, hi = 0, npart - 1
                if frame.preceding is not UNBOUNDED:
                    lo = npart
                    for s in range(npart):
                        if ovals[idxs[s]] >= cur - frame.preceding:
                            lo = s
                            break
                if frame.following is not UNBOUNDED:
                    hi = -1
                    for s in range(npart - 1, -1, -1):
                        if ovals[idxs[s]] <= cur + frame.following:
                            hi = s
                            break
            else:
                lo = 0 if frame.preceding is UNBOUNDED else \
                    max(0, r - frame.preceding)
                hi = npart - 1 if frame.following is UNBOUNDED else \
                    min(npart - 1, r + frame.following)
            vals = [1 if ccol is None else ccol[idxs[s]]
                    for s in range(lo, hi + 1)]
            nn = [v for v in vals if v is not None]
            if fn.kind == "count":
                out[i] = len(nn) if ccol is not None else len(vals)
            elif not nn:
                out[i] = None
            elif fn.kind == "sum":
                out[i] = float(np.sum(np.asarray(nn, np.float64))) \
                    if fn.result_type().is_floating else int(sum(nn))
            elif fn.kind == "avg":
                out[i] = float(np.sum(np.asarray(nn, np.float64)) / len(nn))
            elif fn.kind == "min":
                non_nan = [v for v in nn if not (
                    isinstance(v, float) and np.isnan(v))]
                out[i] = min(non_nan) if non_nan else float("nan")
            elif fn.kind == "max":
                has_nan = any(isinstance(v, float) and np.isnan(v)
                              for v in nn)
                out[i] = float("nan") if has_nan else max(nn)
    return out
