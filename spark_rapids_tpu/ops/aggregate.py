"""Hash aggregate (ref: aggregate.scala:305 — the 4-stage pipeline
documented at aggregate.scala:397-425, re-designed for TPU).

Device algorithm per partition (mirrors the reference's iterative loop at
aggregate.scala:427-480):

  for each input batch:
      project grouping keys + aggregate inputs
      group_ids (fingerprint sort) + segmented update aggregation
      -> partial buffer batch [keys..., buffers...]
      concat with the running partial; when the concat grows past the
      merge threshold, re-merge (group again with merge aggregates)
  final merge once at end; in final/complete mode run the result
  projection (finalize avg, rename columns)

All kernels are fixed-capacity jnp programs; the number of groups is a
device scalar so data-dependent group counts never recompile. Buffers are
(data, validity, lengths-or-None) triples so string aggregates (min/max/
first/last over strings) flow through the same machinery.

Aggregate functions (ref: AggregateFunctions.scala as CudfAggregate
update/merge pairs): Count, Sum, Min, Max, Average, First, Last. Each also
carries a host-side update/merge/finalize so the host oracle engine runs
real partial/final plans, not just single-stage ones.
"""

from __future__ import annotations

import dataclasses
from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.batch import (
    DeviceBatch, DeviceColumn, bucket_capacity, concat_batches)
from spark_rapids_tpu.columnar.host import HostBatch, HostColumn
from spark_rapids_tpu.exprs.base import (
    Expression, as_device_column, as_host_column)
from spark_rapids_tpu.ops.base import (Exec, ExecContext, Schema,
    record_batch, timed)
from spark_rapids_tpu.ops import kernels


@dataclasses.dataclass
class SortedCol:
    """One column's arrays permuted to group-sorted order."""

    data: jnp.ndarray
    validity: jnp.ndarray
    lengths: Optional[jnp.ndarray] = None   # strings only


Buf = Tuple[jnp.ndarray, jnp.ndarray, Optional[jnp.ndarray]]


# ---------------------------------------------------------------------------
# Aggregate function descriptors
# ---------------------------------------------------------------------------

class AggFunction:
    """One aggregate: an input expression plus update/merge/finalize logic
    over segmented reductions. ``buffer_types`` is the partial-buffer schema
    this function contributes."""

    def __init__(self, child: Optional[Expression]):
        self.child = child

    @property
    def buffer_types(self) -> Tuple[dt.DataType, ...]:
        raise NotImplementedError

    @property
    def result_type(self) -> dt.DataType:
        raise NotImplementedError

    # -- device ---------------------------------------------------------
    def update(self, col: SortedCol, gid, capacity,
               row_index) -> List[Buf]:
        raise NotImplementedError

    def merge(self, bufs: List[SortedCol], gid, capacity) -> List[Buf]:
        raise NotImplementedError

    def finalize(self, bufs: List[SortedCol]) -> Buf:
        raise NotImplementedError

    # -- fast segmented-sum plan (cumsum path) ---------------------------
    # Sum-decomposable aggregates (Sum/Count/Average) expose their work as
    # masked value streams; HashAggregateExec stacks every stream of the
    # whole spec list into per-dtype 2D arrays and computes ALL group sums
    # with ONE prefix sum + boundary-diff per dtype on the sorted path
    # (q1's fifteen streams over 786,432 rows: 104-113 ms a batch with
    # the sort, on one v5e), or reduces each stream group by group where
    # the batch holds few groups (4.3 ms at four groups; my chip run, PR
    # 26, _SLOT_MAX_GROUPS has the table). None = not sum-decomposable
    # (min/max/first/last keep the per-fn segment path, sorted).
    # ``has_nans`` mirrors spark.rapids.sql.hasNans: when the user asserts
    # float data is finite, the out-of-band NaN/inf occurrence streams (3
    # extra i32 cumsum columns per f64 sum) are skipped entirely.
    def sum_terms_update(self, col: SortedCol,
                         has_nans: bool = True) -> Optional[List[Tuple]]:
        return None

    def sum_terms_merge(self, bufs: List[SortedCol],
                        has_nans: bool = True) -> Optional[List[Tuple]]:
        return None

    def bufs_from_sums(self, sums: List, capacity: int,
                       has_nans: bool = True) -> List[Buf]:
        raise NotImplementedError

    # -- global (zero-key) fast path -------------------------------------
    # Whole-batch masked reductions — no sort, no segments. Returns one
    # value per buffer as (scalar_data, scalar_valid, lengths_or_None).
    def update_global(self, col: SortedCol, row_index=None,
                      live=None) -> Optional[List[Tuple]]:
        return None

    def merge_global(self, bufs: List[SortedCol]) -> Optional[List[Tuple]]:
        return None

    # -- partial-skip passthrough ----------------------------------------
    # Each input ROW becomes its own single-element group buffer — a pure
    # elementwise projection into the buffer layout, used when the partial
    # stage's measured reduction ratio is poor (the reference's later
    # skipAggPassReductionRatio idea): grouping then happens once, after
    # the exchange, instead of twice. None = unsupported.
    def update_row(self, col: SortedCol, row_index) -> Optional[List[Buf]]:
        return None

    # -- host oracle ----------------------------------------------------
    def host_update(self, values: list) -> tuple:
        """Group's python values (None=null) -> buffer value tuple."""
        raise NotImplementedError

    def host_merge(self, buf_tuples: List[tuple]) -> tuple:
        raise NotImplementedError

    def host_finalize(self, buf: tuple):
        raise NotImplementedError

    def host_agg(self, values: list):
        return self.host_finalize(self.host_merge([self.host_update(values)]))


class Count(AggFunction):
    """count(x): non-null count; see CountStar for count(*)."""

    @property
    def buffer_types(self):
        return (dt.INT64,)

    @property
    def result_type(self):
        return dt.INT64

    def update(self, col, gid, capacity, row_index):
        cnt = jax.ops.segment_sum(col.validity.astype(jnp.int64), gid,
                                  num_segments=capacity)
        return [(cnt, jnp.ones((capacity,), jnp.bool_), None)]

    def merge(self, bufs, gid, capacity):
        b, = bufs
        s = jax.ops.segment_sum(jnp.where(b.validity, b.data, 0), gid,
                                num_segments=capacity)
        return [(s, jnp.ones((capacity,), jnp.bool_), None)]

    def finalize(self, bufs):
        b, = bufs
        return b.data, b.validity, None

    # -- fast paths ------------------------------------------------------
    def sum_terms_update(self, col, has_nans=True):
        return [("i32", col.validity.astype(jnp.int32))]

    def sum_terms_merge(self, bufs, has_nans=True):
        b, = bufs
        return [("i64", jnp.where(b.validity, b.data, 0))]

    def bufs_from_sums(self, sums, capacity, has_nans=True):
        s, = sums
        return [(s.astype(jnp.int64), jnp.ones((capacity,), jnp.bool_),
                 None)]

    def update_global(self, col, row_index=None, live=None):
        return [(jnp.sum(col.validity.astype(jnp.int64)), True, None)]

    def update_row(self, col, row_index):
        ones = jnp.ones_like(col.validity)
        return [(col.validity.astype(jnp.int64), ones, None)]

    def merge_global(self, bufs):
        b, = bufs
        return [(jnp.sum(jnp.where(b.validity, b.data, 0)), True, None)]

    def host_update(self, values):
        return (sum(1 for v in values if v is not None),)

    def host_merge(self, buf_tuples):
        return (sum(b[0] for b in buf_tuples if b[0] is not None),)

    def host_finalize(self, buf):
        return buf[0]


class CountStar(Count):
    def update_row(self, col, row_index):
        ones = jnp.ones_like(col.validity)
        return [(jnp.ones(col.validity.shape, jnp.int64), ones, None)]

    def host_update(self, values):
        return (len(values),)


def _sum_result_type(t: dt.DataType) -> dt.DataType:
    return dt.FLOAT64 if t.is_floating else dt.INT64


def _reapply_nonfinite(s, nan_cnt, pinf_cnt, ninf_cnt):
    """Reconstruct IEEE sum semantics from a finite-only sum plus per-group
    NaN/±inf occurrence counts (cumsum path carries non-finites out of
    band)."""
    bad = (nan_cnt > 0) | ((pinf_cnt > 0) & (ninf_cnt > 0))
    s = jnp.where(pinf_cnt > 0, jnp.inf, s)
    s = jnp.where(ninf_cnt > 0, -jnp.inf, s)
    return jnp.where(bad, jnp.nan, s)


class Sum(AggFunction):
    @property
    def buffer_types(self):
        return (_sum_result_type(self.child.data_type()),)

    @property
    def result_type(self):
        return _sum_result_type(self.child.data_type())

    def update(self, col, gid, capacity, row_index):
        t = self.result_type.np_dtype
        agg, counts = kernels.segment_reduce(
            col.data.astype(t), col.validity, gid, capacity, "sum")
        return [(agg, counts > 0, None)]

    def merge(self, bufs, gid, capacity):
        b, = bufs
        agg, counts = kernels.segment_reduce(b.data, b.validity, gid,
                                             capacity, "sum")
        return [(agg, counts > 0, None)]

    def finalize(self, bufs):
        b, = bufs
        return b.data, b.validity, None

    # -- fast paths ------------------------------------------------------
    @property
    def _cls(self) -> str:
        return "f64" if self.result_type.is_floating else "i64"

    def _terms(self, data, validity, has_nans):
        """Masked value stream + count; float streams also carry NaN/inf
        occurrence counts (unless hasNans=false asserts finiteness) — the
        cumsum prefix-diff would otherwise let one group's NaN poison
        every later group's sum."""
        t = self.result_type.np_dtype
        v = jnp.where(validity, data.astype(t), jnp.zeros((), t))
        if self._cls != "f64":
            return [("i64", v), ("i32", validity.astype(jnp.int32))]
        if not has_nans:
            return [("f64", v), ("i32", validity.astype(jnp.int32))]
        finite = jnp.isfinite(v)
        clean = jnp.where(finite, v, 0.0)
        return [("f64", clean), ("i32", validity.astype(jnp.int32)),
                ("i32", (validity & jnp.isnan(v)).astype(jnp.int32)),
                ("i32", (v == jnp.inf).astype(jnp.int32)),
                ("i32", (v == -jnp.inf).astype(jnp.int32))]

    def sum_terms_update(self, col, has_nans=True):
        return self._terms(col.data, col.validity, has_nans)

    def sum_terms_merge(self, bufs, has_nans=True):
        b, = bufs
        return self._terms(b.data, b.validity, has_nans)

    def bufs_from_sums(self, sums, capacity, has_nans=True):
        if self._cls != "f64" or not has_nans:
            s, c = sums
            return [(s, c > 0, None)]
        s, c, nan, pinf, ninf = sums
        s = _reapply_nonfinite(s, nan, pinf, ninf)
        return [(s, c > 0, None)]

    def update_global(self, col, row_index=None, live=None):
        t = self.result_type.np_dtype
        v = jnp.where(col.validity, col.data.astype(t), jnp.zeros((), t))
        return [(jnp.sum(v), jnp.sum(col.validity.astype(jnp.int32)) > 0,
                 None)]

    def update_row(self, col, row_index):
        t = self.result_type.np_dtype
        return [(col.data.astype(t), col.validity, None)]

    def merge_global(self, bufs):
        b, = bufs
        t = self.result_type.np_dtype
        v = jnp.where(b.validity, b.data.astype(t), jnp.zeros((), t))
        return [(jnp.sum(v), jnp.sum(b.validity.astype(jnp.int32)) > 0,
                 None)]

    def host_update(self, values):
        vs = [v for v in values if v is not None]
        if not vs:
            return (None,)
        if self.result_type.is_floating:
            return (float(np.sum(np.asarray(vs, np.float64))),)
        acc = np.int64(0)
        with np.errstate(over="ignore"):
            for v in vs:
                acc = np.int64(acc + np.int64(v))   # JVM wrap
        return (int(acc),)

    def host_merge(self, buf_tuples):
        return self.host_update([b[0] for b in buf_tuples])

    def host_finalize(self, buf):
        return buf[0]


class Min(AggFunction):
    kind = "min"

    @property
    def buffer_types(self):
        return (self.child.data_type(),)

    @property
    def result_type(self):
        return self.child.data_type()

    def update(self, col, gid, capacity, row_index):
        if col.lengths is not None:
            return [kernels.segment_minmax_string(
                col.data, col.lengths, col.validity, gid, capacity,
                want_max=self.kind == "max")]
        agg, counts = kernels.segment_reduce(col.data, col.validity, gid,
                                             capacity, self.kind)
        return [(agg, counts > 0, None)]

    def merge(self, bufs, gid, capacity):
        return self.update(bufs[0], gid, capacity, None)

    def finalize(self, bufs):
        b, = bufs
        return b.data, b.validity, b.lengths

    def _global(self, col):
        if col.lengths is not None:
            return None       # string min/max: sorted path
        v, val = col.data, col.validity
        if jnp.issubdtype(v.dtype, jnp.floating):
            isnan = jnp.isnan(v)
            real = val & ~isnan
            nanv = jnp.asarray(jnp.nan, v.dtype)
            if self.kind == "min":
                m = jnp.min(jnp.where(real, v,
                                      jnp.asarray(jnp.inf, v.dtype)))
                m = jnp.where(jnp.sum(real.astype(jnp.int32)) > 0, m, nanv)
            else:
                m = jnp.max(jnp.where(real, v,
                                      jnp.asarray(-jnp.inf, v.dtype)))
                m = jnp.where(jnp.sum((val & isnan).astype(jnp.int32)) > 0,
                              nanv, m)
        else:
            ident = kernels._identity_for(v.dtype, self.kind)
            masked = jnp.where(val, v, ident)
            m = jnp.min(masked) if self.kind == "min" else jnp.max(masked)
        ok = jnp.sum(val.astype(jnp.int32)) > 0
        return [(m, ok, None)]

    def update_global(self, col, row_index=None, live=None):
        return self._global(col)

    def update_row(self, col, row_index):
        return [(col.data, col.validity, col.lengths)]

    def merge_global(self, bufs):
        return self._global(bufs[0])

    def host_update(self, values):
        vs = [v for v in values if v is not None]
        if not vs:
            return (None,)
        t = self.child.data_type()
        if t.is_floating:
            non_nan = [v for v in vs if not np.isnan(v)]
            if self.kind == "min":
                return (min(non_nan) if non_nan else float("nan"),)
            return (float("nan") if len(non_nan) < len(vs)
                    else max(vs),)
        return (min(vs) if self.kind == "min" else max(vs),)

    def host_merge(self, buf_tuples):
        return self.host_update([b[0] for b in buf_tuples])

    def host_finalize(self, buf):
        return buf[0]


class Max(Min):
    kind = "max"


class Average(AggFunction):
    """avg: partial buffer = (sum double, count long); result double."""

    @property
    def buffer_types(self):
        return (dt.FLOAT64, dt.INT64)

    @property
    def result_type(self):
        return dt.FLOAT64

    def update(self, col, gid, capacity, row_index):
        s, counts = kernels.segment_reduce(
            col.data.astype(jnp.float64), col.validity, gid, capacity, "sum")
        return [(s, counts > 0, None),
                (counts, jnp.ones((capacity,), jnp.bool_), None)]

    def merge(self, bufs, gid, capacity):
        sb, cb = bufs
        s, _ = kernels.segment_reduce(sb.data, sb.validity, gid, capacity,
                                      "sum")
        c = jax.ops.segment_sum(jnp.where(cb.validity, cb.data, 0), gid,
                                num_segments=capacity)
        return [(s, c > 0, None),
                (c, jnp.ones((capacity,), jnp.bool_), None)]

    def finalize(self, bufs):
        sb, cb = bufs
        safe = jnp.where(cb.data > 0, cb.data, 1)
        return sb.data / safe.astype(jnp.float64), cb.data > 0, None

    # -- fast paths ------------------------------------------------------
    @staticmethod
    def _f64_terms(v, has_nans):
        if not has_nans:
            return [("f64", v)]
        finite = jnp.isfinite(v)
        return [("f64", jnp.where(finite, v, 0.0)),
                ("i32", jnp.isnan(v).astype(jnp.int32)),
                ("i32", (v == jnp.inf).astype(jnp.int32)),
                ("i32", (v == -jnp.inf).astype(jnp.int32))]

    def sum_terms_update(self, col, has_nans=True):
        masked = jnp.where(col.validity, col.data.astype(jnp.float64), 0.0)
        return self._f64_terms(masked, has_nans) + \
            [("i32", col.validity.astype(jnp.int32))]

    def sum_terms_merge(self, bufs, has_nans=True):
        sb, cb = bufs
        return self._f64_terms(jnp.where(sb.validity, sb.data, 0.0),
                               has_nans) + \
            [("i64", jnp.where(cb.validity, cb.data, 0))]

    def bufs_from_sums(self, sums, capacity, has_nans=True):
        if has_nans:
            s, nan, pinf, ninf, c = sums
            s = _reapply_nonfinite(s, nan, pinf, ninf)
        else:
            s, c = sums
        c = c.astype(jnp.int64)
        return [(s, c > 0, None),
                (c, jnp.ones((capacity,), jnp.bool_), None)]

    def update_global(self, col, row_index=None, live=None):
        s = jnp.sum(jnp.where(col.validity, col.data.astype(jnp.float64),
                              0.0))
        c = jnp.sum(col.validity.astype(jnp.int64))
        return [(s, c > 0, None), (c, True, None)]

    def update_row(self, col, row_index):
        ones = jnp.ones_like(col.validity)
        return [(col.data.astype(jnp.float64), col.validity, None),
                (col.validity.astype(jnp.int64), ones, None)]

    def merge_global(self, bufs):
        sb, cb = bufs
        s = jnp.sum(jnp.where(sb.validity, sb.data, 0.0))
        c = jnp.sum(jnp.where(cb.validity, cb.data, 0))
        return [(s, c > 0, None), (c, True, None)]

    def host_update(self, values):
        vs = [v for v in values if v is not None]
        if not vs:
            return (None, 0)
        return (float(np.sum(np.asarray(vs, np.float64))), len(vs))

    def host_merge(self, buf_tuples):
        s = [b[0] for b in buf_tuples if b[0] is not None]
        c = sum(b[1] for b in buf_tuples)
        return (float(np.sum(s)) if s else None, c)

    def host_finalize(self, buf):
        s, c = buf
        return None if c == 0 else s / c


class First(AggFunction):
    """first(x[, ignoreNulls]) — order = arrival order within the partition
    stream, same determinism caveat as the reference's GpuFirst."""

    pick = "min"

    def __init__(self, child, ignore_nulls: bool = True):
        super().__init__(child)
        self.ignore_nulls = ignore_nulls

    @property
    def buffer_types(self):
        return (self.child.data_type(), dt.INT64)

    @property
    def result_type(self):
        return self.child.data_type()

    def _gather(self, col: SortedCol, pos, ok):
        safe = jnp.clip(pos, 0, pos.shape[0] - 1).astype(jnp.int32)
        val = jnp.take(col.data, safe, axis=0)
        vval = jnp.take(col.validity, safe, axis=0) & ok
        if col.lengths is not None:
            lens = jnp.where(vval, jnp.take(col.lengths, safe, axis=0), 0)
            val = jnp.where(vval[:, None], val, 0)
            return val, vval, lens
        val = jnp.where(vval, val, jnp.zeros_like(val))
        return val, vval, None

    def update(self, col, gid, capacity, row_index):
        # Pick by GLOBAL arrival index (monotone across the batch stream, so
        # first/last stays correct through concat+merge), but gather the
        # value by sorted position: the stable fingerprint sort preserves
        # arrival order within a group, so min/max global index coincides
        # with min/max sorted position.
        pos = jnp.arange(capacity, dtype=jnp.int64)
        gidx = pos if row_index is None else row_index.astype(jnp.int64)
        eligible = col.validity if self.ignore_nulls else \
            jnp.ones_like(col.validity)
        bad_pos = jnp.int64(capacity if self.pick == "min" else -1)
        bad_idx = jnp.int64(2 ** 62 if self.pick == "min" else -1)
        red = jax.ops.segment_min if self.pick == "min" else \
            jax.ops.segment_max
        picked_pos = red(jnp.where(eligible, pos, bad_pos), gid,
                         num_segments=capacity)
        picked_idx = red(jnp.where(eligible, gidx, bad_idx), gid,
                         num_segments=capacity)
        ok = (picked_pos < capacity) & (picked_pos >= 0)
        val, vval, lens = self._gather(col, picked_pos, ok)
        return [(val, vval, lens),
                (jnp.where(ok, picked_idx, bad_idx), ok, None)]

    def merge(self, bufs, gid, capacity):
        vcol, icol = bufs
        bad = jnp.int64(2 ** 62 if self.pick == "min" else -1)
        keyed = jnp.where(icol.validity, icol.data, bad)
        red = jax.ops.segment_min if self.pick == "min" else \
            jax.ops.segment_max
        picked_val = red(keyed, gid, num_segments=capacity)
        # Winner = the row holding the reduced index; tie-break by min row.
        row = jnp.arange(capacity, dtype=jnp.int64)
        winner = keyed == jnp.take(picked_val, gid, axis=0)
        wrow = jnp.where(winner & icol.validity, row, capacity)
        first_row = jax.ops.segment_min(wrow, gid, num_segments=capacity)
        ok = first_row < capacity
        val, vval, lens = self._gather(vcol, first_row, ok)
        iv = jnp.take(icol.data, jnp.clip(first_row, 0, capacity - 1)
                      .astype(jnp.int32), axis=0)
        return [(val, vval, lens), (jnp.where(ok, iv, bad), ok, None)]

    def finalize(self, bufs):
        vcol, _ = bufs
        return vcol.data, vcol.validity, vcol.lengths

    def update_row(self, col, row_index):
        eligible = col.validity if self.ignore_nulls else \
            jnp.ones_like(col.validity)
        bad = jnp.int64(2 ** 62 if self.pick == "min" else -1)
        idx = jnp.where(eligible, row_index.astype(jnp.int64), bad)
        return [(col.data, col.validity, col.lengths),
                (idx, eligible, None)]

    def update_global(self, col, row_index=None, live=None):
        cap = col.validity.shape[0]
        pos = jnp.arange(cap, dtype=jnp.int64)
        # With ignore_nulls=False a NULL row still wins, but dead rows
        # (padding / sel-deselected) never do.
        eligible = col.validity if self.ignore_nulls else \
            (live if live is not None else jnp.ones_like(col.validity))
        if self.pick == "min":
            picked = jnp.min(jnp.where(eligible, pos, cap))
            ok = picked < cap
        else:
            picked = jnp.max(jnp.where(eligible, pos, -1))
            ok = picked >= 0
        safe = jnp.clip(picked, 0, cap - 1).astype(jnp.int32)
        val = jnp.take(col.data, safe, axis=0)
        gidx = jnp.take(row_index, safe, axis=0) \
            if row_index is not None else picked
        bad = jnp.int64(2 ** 62 if self.pick == "min" else -1)
        length = jnp.take(col.lengths, safe, axis=0) \
            if col.lengths is not None else None
        vval = ok & jnp.take(col.validity, safe, axis=0)
        return [(val, vval, length), (jnp.where(ok, gidx, bad), ok, None)]

    def merge_global(self, bufs):
        vcol, icol = bufs
        cap = icol.validity.shape[0]
        bad = jnp.int64(2 ** 62 if self.pick == "min" else -1)
        keyed = jnp.where(icol.validity, icol.data, bad)
        best = jnp.min(keyed) if self.pick == "min" else jnp.max(keyed)
        row = jnp.min(jnp.where(icol.validity & (keyed == best),
                                jnp.arange(cap, dtype=jnp.int64), cap))
        ok = (row < cap) & (best != bad)
        safe = jnp.clip(row, 0, cap - 1).astype(jnp.int32)
        val = jnp.take(vcol.data, safe, axis=0)
        length = jnp.take(vcol.lengths, safe, axis=0) \
            if vcol.lengths is not None else None
        iv = jnp.take(icol.data, safe, axis=0)
        return [(val, ok & jnp.take(vcol.validity, safe, axis=0), length),
                (jnp.where(ok, iv, bad), ok, None)]

    def host_update(self, values):
        seq = [(i, v) for i, v in enumerate(values)
               if not (self.ignore_nulls and v is None)]
        if not seq:
            return (None, None)
        i, v = seq[0] if self.pick == "min" else seq[-1]
        return (v, i)

    def host_merge(self, buf_tuples):
        cands = [b for b in buf_tuples if b[1] is not None]
        if not cands:
            return (None, None)
        pickf = min if self.pick == "min" else max
        return pickf(cands, key=lambda b: b[1])

    def host_finalize(self, buf):
        return buf[0]


class Last(First):
    pick = "max"


@dataclasses.dataclass
class AggSpec:
    """A named aggregate in the output (result column). ``distinct`` is
    consumed by mixed_final mode: the fn runs UPDATE over the deduped
    distinct input instead of MERGE over partial buffers."""

    name: str
    fn: AggFunction
    distinct: bool = False


# ---------------------------------------------------------------------------
# The exec
# ---------------------------------------------------------------------------

# Rows per block of the two-level float prefix sum. Every capacity bucket
# above it (2^k and 3*2^(k-1)) is a multiple of it.
_SCAN_BLOCK = 512


def _prefix_sums(M: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix sums down axis 0 of an (n, k) stack.

    Integer stacks use ``jnp.cumsum``. Float stacks do not: a float64
    ``cumsum`` lowers to a reduce_window that the v5e compiler (libtpu
    0.0.34) takes ~150 s over at ANY length (147 s at 4,096 rows, 154 s
    at 256; described-chip compile, PR 21) — one such program per grouped
    float aggregate. Two levels of associative scans — within blocks of
    ``_SCAN_BLOCK`` rows, then across the block totals — compile in 2.5 s
    at 786,432 x 4 and run in 3.4 ms there on the chip against the
    cumsum's 19.9 ms (PR 21); one flat associative scan does not scale
    either (122 s, 272 MB of code at that size).

    The dtype fork is measured, not inherited: integer add is associative
    and the same two levels would be bit-identical, but for integers they
    compile no better. Described-chip compile, PR 21, two levels against
    ``jnp.cumsum`` — int64 786,432 x 1: 52.8 s / 62.7 MB of code against
    47.3 s / 1.8 MB; x 4: 1.2 s against 50.3 s; int32 786,432 x 1: 53.2 s
    against 21.3 s; x 4: 26.3 s against 20.5 s; 2^20 x 1: 95.8 / 92.8 s
    against 47.7 / 17.2 s. Neither is good (ROADMAP A1); their run times
    on the chip are ``scripts/chip_probe.py``'s to give."""
    if not jnp.issubdtype(M.dtype, jnp.floating):
        return jnp.cumsum(M, axis=0)
    return _two_level_prefix_sums(M)


def _two_level_prefix_sums(M: jnp.ndarray) -> jnp.ndarray:
    """Prefix sums within blocks of ``_SCAN_BLOCK`` rows, then across the
    block totals; any dtype whose add is associative enough."""
    n, k = M.shape
    if n <= _SCAN_BLOCK or n % _SCAN_BLOCK:
        return jax.lax.associative_scan(jnp.add, M, axis=0)
    inner = jax.lax.associative_scan(
        jnp.add, M.reshape(n // _SCAN_BLOCK, _SCAN_BLOCK, k), axis=1)
    totals = jax.lax.associative_scan(jnp.add, inner[:, -1, :], axis=0)
    before = jnp.concatenate([jnp.zeros_like(totals[:1]), totals[:-1]])
    return (inner + before[:, None, :]).reshape(n, k)


# The most distinct key fingerprints a batch may show and still be grouped
# without a sort (HashAggregateExec._slot_update), fixed where slots still
# win by 2x with room to spare. q1's eight aggregates over 786,432 rows on
# one v5e, ms a batch, median of 20 (my chip run, PR 26:
# ``scripts/chip_probe.py slots``):
#
#   groups in the batch      4     16     32     64    128    256    512   1024
#   sorted path alone    113.3  119.1  106.8  113.9  104.2  103.7  103.5  103.3
#   probe, cond, slots     4.3    5.4    6.9    9.8   15.7   27.3   50.8   97.3
#
# The slot update costs ~3.7 ms + 0.09 ms a group FOUND (a loop over the
# groups, not over the limit), so 512 groups are the edge (2.04x). What
# a larger limit costs is paid by the batches beyond it: the probe gives
# up after ``limit + 1`` rounds of ~10 us each (4,096 groups behind a
# limit of 128: 99.3 ms against 103.2 sorted alone, the fingerprints are
# shared; behind 1,024: 108.6), 2.5 % of the sort that follows at 256.
_SLOT_MAX_GROUPS = 256
# ... and the rows a batch must hold for each group it is asked about. A
# round of the probe is bound by its launches up to ~1 M rows, so a small
# batch pays as much for it as a large one, for a sort that costs ~0.13 us
# a row: q3's four update batches a query are of 4,096 rows and thousands
# of groups, and 129 rounds each cost it 4.7 ms a query (`device_busy_ms`
# 490.9 against 486.2) before this rule, 0.2 ms after (486.3; my chip
# runs, PR 26). One round per 4,096 rows keeps the probe under 2 % of the
# sort it may precede; a batch under 4,096 rows is not asked at all.
_SLOT_ROWS_PER_GROUP = 4096


# The most 32-bit words of prefix sums that ride ONE sort to their groups'
# slots (``HashAggregateExec._segment_sums``); more are gathered in slabs.
_SUMS_RIDE_WORDS = 4


def _slot_limit(capacity: int) -> int:
    """The most groups a batch of ``capacity`` rows may show and be
    grouped without a sort; 0: the batch is too small to ask."""
    return min(_SLOT_MAX_GROUPS, capacity // _SLOT_ROWS_PER_GROUP)


def _count_updates(m, partials) -> None:
    """Operator metrics ``aggSlotBatches`` / ``aggSortedBatches`` from
    update partials' ``(capacity, groups)``: ``_update_batch`` groups
    without a sort exactly where it finds at most ``_slot_limit``
    groups."""
    for capacity, groups in partials:
        limit = _slot_limit(capacity)
        m.add("aggSlotBatches" if limit and int(groups) <= limit
              else "aggSortedBatches", 1)


class HashAggregateExec(Exec):
    """Groupby aggregate. ``mode``:
    - 'partial': emits [keys..., buffers...] for a downstream exchange
    - 'final': consumes partial buffers, emits finalized results
    - 'complete': update+merge+finalize in one node (single-stage plans)
    """

    def __init__(self, child: Exec,
                 group_by: Sequence[Tuple[str, Expression]],
                 aggregates: Sequence[AggSpec],
                 mode: str = "complete"):
        super().__init__(child)
        # 'merge' = final minus the result projection (emits buffers);
        # 'mixed_final' = the distinct combo stage: input layout is
        # [keys..., distinct_x, nd buffers...]; distinct specs UPDATE over
        # x, the rest MERGE their buffers (aggregate.scala:305 distinct
        # partial-merge mode combos).
        assert mode in ("partial", "final", "complete", "merge",
                        "mixed_final")
        self.group_names = tuple(n for n, _ in group_by)
        self.group_exprs = [e for _, e in group_by]
        self.aggs = list(aggregates)
        self.mode = mode

    # -- schemas -------------------------------------------------------------
    @property
    def buffer_schema(self) -> Schema:
        cols: List[Tuple[str, dt.DataType]] = []
        for n, e in zip(self.group_names, self.group_exprs):
            cols.append((n, e.data_type()))
        for spec in self.aggs:
            for bi, bt in enumerate(spec.fn.buffer_types):
                cols.append((f"{spec.name}#buf{bi}", bt))
        return tuple(cols)

    @property
    def schema(self) -> Schema:
        if self.mode in ("partial", "merge"):
            return self.buffer_schema
        cols = [(n, e.data_type())
                for n, e in zip(self.group_names, self.group_exprs)]
        cols += [(s.name, s.fn.result_type) for s in self.aggs]
        return tuple(cols)

    @property
    def _nkeys(self) -> int:
        return len(self.group_exprs)

    # -- device path ---------------------------------------------------------
    def _project_inputs(self, batch: DeviceBatch) -> Tuple[DeviceBatch, list]:
        """[keys..., agg inputs...] working batch + per-agg input ordinal."""
        cols = [as_device_column(e.eval(batch), batch)
                for e in self.group_exprs]
        ords = []
        for spec in self.aggs:
            if spec.fn.child is None:   # count(*)
                ords.append(None)
            else:
                cols.append(as_device_column(spec.fn.child.eval(batch),
                                             batch))
                ords.append(len(cols) - 1)
        from spark_rapids_tpu.exprs.base import project_batch
        return project_batch(cols, batch), ords

    @staticmethod
    def _input_col(work: DeviceBatch, ord_, mask) -> SortedCol:
        """An aggregate's input column of the working batch, in place,
        valid only under ``mask``; ``ord_`` None is count(*)'s."""
        if ord_ is None:
            return SortedCol(jnp.zeros((work.capacity,), jnp.int64), mask)
        c = work.columns[ord_]
        return SortedCol(c.data, c.validity & mask, c.lengths)

    @staticmethod
    def _sorted_col(col: DeviceColumn, perm, slive) -> SortedCol:
        data = jnp.take(col.data, perm, axis=0)
        validity = jnp.take(col.validity, perm, axis=0) & slive
        lens = None
        if col.dtype.is_string:
            lens = jnp.where(validity, jnp.take(col.lengths, perm, axis=0),
                             0)
        return SortedCol(data, validity, lens)

    @staticmethod
    def _buf_column(buf: Buf, bt: dt.DataType, gmask) -> DeviceColumn:
        data, valid, lens = buf
        valid = valid & gmask
        if bt.is_string:
            data = jnp.where(valid[:, None], data.astype(jnp.uint8), 0)
            lens = jnp.where(valid, lens, 0)
            return DeviceColumn(bt, data, valid, lens)
        data = jnp.where(valid, data.astype(bt.np_dtype),
                         jnp.zeros((), bt.np_dtype))
        return DeviceColumn(bt, data, valid)

    # -- sorted-path machinery ----------------------------------------------
    def _group_sorted(self, work: DeviceBatch, fingerprints=None):
        """Group + ONE packed gather of the whole batch to group-sorted
        order (rowmove.py): per-column takes cost ~40-60ms each at 1M rows
        on this chip; the packed 2D form moves every column at once."""
        from spark_rapids_tpu.columnar.rowmove import gather_rows
        g = kernels.group_ids(work, range(self._nkeys), fingerprints)
        live = work.live_count()
        sorted_b = gather_rows(work, g.perm, live)
        slive = jnp.arange(work.capacity, dtype=jnp.int32) < live
        return g, sorted_b, slive

    @staticmethod
    def _segment_sums(stacks, gid, slive, capacity):
        """ALL group sums with one cumsum + boundary shift-diff per dtype
        class. Values arrive pre-masked (dead/null rows contribute 0).
        Groups are contiguous ascending runs of ``gid`` in sorted order, so
        group g's sum = prefix(end_g) - prefix(end_{g-1}).

        How the prefix sums at the groups' last rows reach the groups'
        slots follows from how many 32-bit words they are (static):

        - up to ``_SUMS_RIDE_WORDS``: the groups' last rows, in order, are
          the groups in order, so ONE stable sort keyed by "not a group's
          last row" with the prefix sums riding leaves group g's at slot
          g. No scatter of the ends, no gather: a sum and its count (q67)
          are three words and three 1-D gathers, a float64 having no words
          to share a slab with — 48 ms of a 786,432-row update's 128 on
          one v5e (PERF.md, PR 34). Slots past the last group hold other
          rows' prefix sums, masked by ``_buf_column`` as every slot past
          ``num_groups`` is;
        - more: the ends' indices by one scatter and the sums by packed
          gathers (``rowmove.take_columns``): every operand of a sort
          costs the chip's compiler ~12 s, a slab gather's column nothing.

        Returns class -> its sums, one (N,) array a stream, by group."""
        idx = jnp.arange(capacity, dtype=jnp.int32)
        nxt_gid = jnp.concatenate([gid[1:], gid[-1:]])
        nxt_live = jnp.concatenate([slive[1:], jnp.zeros((1,), jnp.bool_)])
        last = slive & ((idx == capacity - 1) | (nxt_gid != gid)
                        | ~nxt_live)
        prefix = []
        for arrs in stacks.values():
            S = _prefix_sums(jnp.stack(arrs, axis=1))
            prefix.extend(S[:, j] for j in range(len(arrs)))
        if sum(p.dtype.itemsize // 4 for p in prefix) <= _SUMS_RIDE_WORDS:
            at_ends = jax.lax.sort([(~last).astype(jnp.uint32)] + prefix,
                                   num_keys=1, is_stable=True)[1:]
        else:
            from spark_rapids_tpu.columnar.rowmove import take_columns
            ends = jnp.zeros((capacity,), jnp.int32).at[
                jnp.where(last, gid, capacity)].set(idx, mode="drop")
            at_ends = take_columns(prefix, ends)
        out, off = {}, 0
        for cls, arrs in stacks.items():
            out[cls] = [jnp.concatenate([e[:1], e[1:] - e[:-1]])
                        for e in at_ends[off:off + len(arrs)]]
            off += len(arrs)
        return out

    def _run_specs(self, spec_inputs, gid, slive, capacity, row_index,
                   has_nans: bool = True):
        """Shared spec-evaluation core: ``spec_inputs`` yields per spec
        ("update", SortedCol) or ("merge", [SortedCol...]). Sum-decomposable
        specs ride the stacked-cumsum path; the rest use their segment
        kernels. Returns the flat buffer list (per spec, per buffer)."""
        stacks: dict = {}
        plans = []          # per spec: ("sum", [(cls, pos)...]) | ("raw", bufs)
        for spec, (kind, arg) in zip(self.aggs, spec_inputs):
            terms = spec.fn.sum_terms_update(arg, has_nans) \
                if kind == "update" \
                else spec.fn.sum_terms_merge(arg, has_nans)
            if terms is not None:
                slots = []
                for cls, values in terms:
                    stacks.setdefault(cls, []).append(values)
                    slots.append((cls, len(stacks[cls]) - 1))
                plans.append(("sum", slots))
            elif kind == "update":
                plans.append(("raw", spec.fn.update(arg, gid, capacity,
                                                    row_index)))
            else:
                plans.append(("raw", spec.fn.merge(arg, gid, capacity)))
        sums = self._segment_sums(stacks, gid, slive, capacity) \
            if stacks else {}
        out = []
        for spec, plan in zip(self.aggs, plans):
            if plan[0] == "sum":
                vals = [sums[cls][pos] for cls, pos in plan[1]]
                out.append(spec.fn.bufs_from_sums(vals, capacity,
                                                  has_nans))
            else:
                out.append(plan[1])
        return out

    def _assemble(self, work: DeviceBatch, leader, num_groups,
                  all_bufs) -> DeviceBatch:
        """Key columns at group leaders (one small packed gather) + buffer
        columns -> the output buffer batch, of ``leader``'s length."""
        from spark_rapids_tpu.columnar.rowmove import gather_rows
        gmask = jnp.arange(leader.shape[0], dtype=jnp.int32) < num_groups
        out_cols: List[DeviceColumn] = []
        if self._nkeys:
            keys = gather_rows(work.select(range(self._nkeys)),
                               leader, num_groups)
            out_cols.extend(keys.columns)
        for spec, bufs in zip(self.aggs, all_bufs):
            for buf, bt in zip(bufs, spec.fn.buffer_types):
                out_cols.append(self._buf_column(buf, bt, gmask))
        return DeviceBatch(tuple(out_cols), num_groups)

    def _sorted_view(self, sorted_b: DeviceBatch, ord_: int) -> SortedCol:
        c = sorted_b.columns[ord_]
        return SortedCol(c.data, c.validity, c.lengths)

    def _update_batch(self, batch: DeviceBatch,
                      offset: jnp.ndarray) -> DeviceBatch:
        """One input batch -> partial buffer batch. ``offset`` is the global
        arrival index of this batch's row 0 (orders First/Last across the
        stream).

        How rows find their groups is chosen on the device from what the
        batch shows: at most ``_slot_limit(capacity)`` distinct key
        fingerprints -> :meth:`_slot_update` (no sort); more ->
        :meth:`_sorted_update`. Both give the same groups in the same
        order with the same leaders; a batch of at most that many groups
        is one whose output holds at most that many rows, which is how
        the host learns the choice where it reads a count anyway
        (``_count_updates``)."""
        work, ords = self._project_inputs(batch)
        if self._global_ok:
            return self._global_stage(work, ords, offset, update=True)
        limit = _slot_limit(work.capacity) if self._slot_ok else 0
        if not limit:
            return self._sorted_update(work, ords, offset)
        fp = kernels.key_fingerprint(work.columns[:self._nkeys],
                                     work.capacity)
        live = work.row_mask()
        pa, pb, found = kernels.smallest_fingerprints(*fp, live, limit)
        return jax.lax.cond(
            found > limit,
            lambda: self._sorted_update(work, ords, offset, fp),
            lambda: self._slot_update(work, ords, fp, live, pa[:limit],
                                      pb[:limit], found))

    def _sorted_update(self, work: DeviceBatch, ords, offset,
                       fingerprints=None) -> DeviceBatch:
        """Group by a stable sort of the key fingerprints, then segment
        sums over the sorted batch: any number of groups, any spec."""
        cap = work.capacity
        g, sorted_b, slive = self._group_sorted(work, fingerprints)
        row_index = offset.astype(jnp.int64) + g.perm.astype(jnp.int64)
        inputs = []
        for spec, ord_ in zip(self.aggs, ords):
            if ord_ is None:
                inputs.append(("update",
                               SortedCol(jnp.zeros((cap,), jnp.int64),
                                         slive)))
            else:
                inputs.append(("update", self._sorted_view(sorted_b, ord_)))
        bufs = self._run_specs(inputs, g.group_of_sorted, slive, cap,
                               row_index, self._has_nans)
        return self._assemble(work, g.group_leader, g.num_groups, bufs)

    @property
    def _slot_ok(self) -> bool:
        """Grouped, and every spec is sum-decomposable: what
        :meth:`_slot_update` can answer (First, Last, Min and Max keep
        the sorted path alone, with no ``cond`` in the program)."""
        return self._nkeys > 0 and all(
            type(s.fn).sum_terms_update is not AggFunction.sum_terms_update
            for s in self.aggs)

    def _slot_update(self, work: DeviceBatch, ords, fingerprints, live,
                     pa, pb, num_groups) -> DeviceBatch:
        """The update for a batch of few groups, whose fingerprint pairs
        ``(pa[i], pb[i])``, ``i < num_groups``, are known in ascending
        order (``kernels.smallest_fingerprints``): one pass of masked
        reductions over the batch per group found. Group i's leader is
        its first row and its sums are, per value stream of
        ``sum_terms_update``, the sum over the rows whose pair is the
        i-th, each in the stream's own dtype.

        The rule that makes it cheap: nothing here sorts, gathers,
        scatters or prefix-scans an array of the batch's capacity
        (``tests/test_agg_slots.py`` reads the jaxpr). Elementwise work
        and reductions only; the one gather takes the keys at the
        ``len(pa)`` leaders. The result is padded to the batch's capacity,
        as the sorted path's is."""
        cap = work.capacity
        slots = pa.shape[0]
        ha, hb = fingerprints
        rows = jnp.arange(cap, dtype=jnp.int32)

        def sums_where(mask):
            """Per spec, the sum of each value stream over ``mask``: a
            stream is zero where its column is not valid, so the mask
            goes in as validity."""
            out = []
            for spec, ord_ in zip(self.aggs, ords):
                terms = spec.fn.sum_terms_update(
                    self._input_col(work, ord_, mask), self._has_nans)
                out.append([jnp.sum(values, dtype=values.dtype)
                            for _cls, values in terms])
            return out

        def one_group(i, acc):
            mask = live & (ha == pa[i]) & (hb == pb[i])
            first = jnp.min(jnp.where(mask, rows, cap))
            return jax.tree.map(
                lambda a, v: jax.lax.dynamic_update_index_in_dim(a, v, i, 0),
                acc, (first, sums_where(mask)))

        empty = jax.tree.map(
            lambda v: jnp.zeros((slots,), v.dtype),
            jax.eval_shape(lambda: (jnp.int32(0), sums_where(live))))
        leader, sums = jax.lax.fori_loop(0, num_groups, one_group, empty)
        bufs = [spec.fn.bufs_from_sums(s, slots, self._has_nans)
                for spec, s in zip(self.aggs, sums)]
        return jax.tree.map(
            lambda x: x if x.ndim == 0 else jnp.pad(
                x, [(0, cap - slots)] + [(0, 0)] * (x.ndim - 1)),
            self._assemble(work, leader, num_groups, bufs))

    def _merge_batch(self, batch: DeviceBatch) -> DeviceBatch:
        """Merge a buffer batch (re-group by keys, merge buffers)."""
        if self._global_ok:
            return self._global_stage(batch, None, None, update=False)
        cap = batch.capacity
        g, sorted_b, slive = self._group_sorted(batch)
        ci = self._nkeys
        inputs = []
        for spec in self.aggs:
            nbuf = len(spec.fn.buffer_types)
            inputs.append(("merge",
                           [self._sorted_view(sorted_b, ci + b)
                            for b in range(nbuf)]))
            ci += nbuf
        bufs = self._run_specs(inputs, g.group_of_sorted, slive, cap, None,
                               self._has_nans)
        return self._assemble(batch, g.group_leader, g.num_groups, bufs)

    def _mixed_batch(self, batch: DeviceBatch) -> DeviceBatch:
        """Distinct combo stage: input [keys..., x, nd buffers...] with
        (keys, x) already unique; group by keys only; distinct specs
        update over x, others merge buffers. Output is the standard
        buffer layout [keys..., all buffers...]."""
        cap = batch.capacity
        g, sorted_b, slive = self._group_sorted(batch)
        x_ord = self._nkeys
        ci = self._nkeys + 1            # nd buffers follow the x column
        row_index = g.perm.astype(jnp.int64)
        inputs = []
        for spec in self.aggs:
            if spec.distinct:
                inputs.append(("update", self._sorted_view(sorted_b,
                                                           x_ord)))
            else:
                nbuf = len(spec.fn.buffer_types)
                inputs.append(("merge",
                               [self._sorted_view(sorted_b, ci + b)
                                for b in range(nbuf)]))
                ci += nbuf
        bufs = self._run_specs(inputs, g.group_of_sorted, slive, cap,
                               row_index)
        return self._assemble(batch, g.group_leader, g.num_groups, bufs)

    # -- zero-key fast path ---------------------------------------------------
    @property
    def _global_ok(self) -> bool:
        """Zero grouping keys and every fn supports whole-batch masked
        reductions (no sort, no segment scatters: q1's update as masked
        reductions over four groups takes 4.3 ms a batch of 786,432 rows
        against 113.3 ms through the sorted path; my chip run, PR 26)."""
        if self._nkeys != 0 or self.mode == "mixed_final":
            return False
        for spec in self.aggs:
            fn = spec.fn
            if isinstance(fn, Min) and fn.child.data_type().is_string:
                return False
        return True

    def _global_stage(self, work: DeviceBatch, ords, offset,
                      update: bool) -> DeviceBatch:
        live = work.row_mask()
        all_bufs = []
        if update:
            cap = work.capacity
            row_index = offset.astype(jnp.int64) + \
                jnp.arange(cap, dtype=jnp.int64)
            for spec, ord_ in zip(self.aggs, ords):
                all_bufs.append(spec.fn.update_global(
                    self._input_col(work, ord_, live), row_index,
                    live=live))
        else:
            ci = self._nkeys
            for spec in self.aggs:
                nbuf = len(spec.fn.buffer_types)
                bufs = []
                for b in range(nbuf):
                    c = work.columns[ci + b]
                    bufs.append(SortedCol(c.data, c.validity & live,
                                          c.lengths))
                ci += nbuf
                all_bufs.append(spec.fn.merge_global(bufs))
        return self._global_assemble(all_bufs)

    def _global_assemble(self, all_bufs) -> DeviceBatch:
        cap = 8
        first = jnp.arange(cap, dtype=jnp.int32) < 1
        out_cols: List[DeviceColumn] = []
        for spec, bufs in zip(self.aggs, all_bufs):
            for (val, ok, length), bt in zip(bufs, spec.fn.buffer_types):
                valid = first & jnp.asarray(ok, jnp.bool_)
                if bt.is_string:
                    w = val.shape[-1]
                    data = jnp.zeros((cap, w), jnp.uint8).at[0].set(
                        val.astype(jnp.uint8))
                    lens = jnp.zeros((cap,), jnp.int32).at[0].set(
                        jnp.asarray(length, jnp.int32))
                    out_cols.append(self._buf_column((data, valid, lens),
                                                     bt, first))
                else:
                    data = jnp.zeros((cap,), bt.np_dtype).at[0].set(
                        jnp.asarray(val).astype(bt.np_dtype))
                    out_cols.append(self._buf_column((data, valid, None),
                                                     bt, first))
        return DeviceBatch(tuple(out_cols), jnp.asarray(1, jnp.int32))

    def _finalize_batch(self, batch: DeviceBatch) -> DeviceBatch:
        out_cols = list(batch.columns[:self._nkeys])
        ci = self._nkeys
        gmask = batch.row_mask()
        for spec in self.aggs:
            nbuf = len(spec.fn.buffer_types)
            bufs = [SortedCol(batch.columns[ci + b].data,
                              batch.columns[ci + b].validity,
                              batch.columns[ci + b].lengths)
                    for b in range(nbuf)]
            data, valid, lens = spec.fn.finalize(bufs)
            out_cols.append(self._buf_column((data, valid, lens),
                                             spec.fn.result_type, gmask))
            ci += nbuf
        return DeviceBatch(tuple(out_cols), batch.num_rows)

    def _passthrough_batch(self, batch: DeviceBatch,
                           offset: jnp.ndarray) -> DeviceBatch:
        """Partial-skip path: project each ROW into the buffer layout with
        no grouping at all (pure elementwise — the measured reduction ratio
        said grouping here would not pay for itself)."""
        work, ords = self._project_inputs(batch)
        cap = work.capacity
        live = work.row_mask()
        row_index = offset.astype(jnp.int64) + \
            jnp.arange(cap, dtype=jnp.int64)
        out_cols = list(work.columns[:self._nkeys])
        for spec, ord_ in zip(self.aggs, ords):
            bufs = spec.fn.update_row(self._input_col(work, ord_, live),
                                      row_index)
            for buf, bt in zip(bufs, spec.fn.buffer_types):
                out_cols.append(self._buf_column(buf, bt, live))
        return DeviceBatch(tuple(out_cols), work.num_rows, sel=work.sel)

    @property
    def _rowskip_capable(self) -> bool:
        return self._nkeys > 0 and all(
            type(s.fn).update_row is not AggFunction.update_row
            for s in self.aggs)

    _has_nans = True    # set from conf before the jits are built

    def _jits(self):
        """Aggregation-stage kernels from the PROCESS-GLOBAL kernel cache,
        keyed by the structural identity of the aggregation (mode, group
        expressions, agg specs, hasNans term layout): a fresh query — a
        new bench iteration, a re-planned DataFrame — reuses the compiled
        update/merge/finalize programs instead of re-tracing them per
        exec instance. The jitted bound methods belong to a child-severed
        clone so a cache entry never pins the plan subtree."""
        from spark_rapids_tpu.ops import kernel_cache as kc
        key = ("agg-fns", type(self).__name__, self.mode, self._has_nans,
               _SLOT_MAX_GROUPS, _SLOT_ROWS_PER_GROUP,
               kc.fingerprint(tuple(self.group_names)),
               kc.fingerprint(tuple(self.group_exprs)),
               kc.fingerprint(tuple(self.aggs)))

        def build():
            clone = kc.detached_clone(self)
            clone._has_nans = self._has_nans
            return (jax.jit(clone._update_batch),
                    jax.jit(clone._merge_batch),
                    jax.jit(clone._finalize_batch),
                    jax.jit(clone._mixed_batch),
                    jax.jit(clone._passthrough_batch))

        fns, _ = kc.cache().get(key, build)
        return fns

    # Max batches concatenated per merge step: bounds the transient HBM of
    # a consolidation to CHUNK x batch-capacity (a 70-wide concat of
    # high-cardinality partials OOMed the chip on TPC-DS q67's rollup).
    _CONSOLIDATE_CHUNK = 12

    def _consolidate(self, ctx, m, pending: List[DeviceBatch],
                     final_stage: bool = False,
                     fresh: int = 0) -> DeviceBatch:
        """Chunked tree of shrink + concat + merge over the pending list.
        The last ``fresh`` of them are update partials not yet counted
        as slot or sorted batches: the first sizes pull says which.

        Each level does ONE batched sizes pull for its hint-less batches
        (every host sync stalls the dispatch queue; exchange
        pieces carry ``rows_hint`` so the final stage's first level
        usually needs no sync), concats chunks of at most
        ``_CONSOLIDATE_CHUNK`` members, and runs the grouping stage on
        each chunk — grouping shrinks the data level by level, so peak
        HBM stays bounded regardless of how many partials a partition
        accumulated. mixed_final's distinct-update kernel is chunk-safe:
        its distinct inputs are globally unique rows, so chunk updates
        followed by plain merges count each value exactly once."""
        from spark_rapids_tpu import monitoring
        from spark_rapids_tpu.columnar.batch import (
            jit_concat_batches, shrink_all)
        _, merge, finalize, mixed, _pt = self._jits()
        first_stage = {"final": merge, "merge": merge,
                       "mixed_final": mixed}.get(self.mode)
        level = 0
        batches = pending
        while True:
            # One span a level (its sizes pull, concats and merge
            # dispatches): a host clock over asynchronous dispatch, so
            # the pull holds the wait for the level before.
            monitoring.count("aggConsolidateLevels")
            with monitoring.span(
                    "level", "agg-consolidate",
                    args={"op": self.name, "level": level,
                          "members": len(batches),
                          "capacities": [b.capacity for b in batches]}
                    if monitoring.enabled() else None):
                with timed(m, "sizesPullTime"):
                    batches, counts = shrink_all(batches)
                if level == 0 and fresh:
                    # An update's output has its input's capacity.
                    _count_updates(
                        m, zip((b.capacity for b in pending[-fresh:]),
                               counts[-fresh:]))
                if len(batches) == 1:
                    single = batches[0]
                    if level == 0 and first_stage is not None:
                        single = first_stage(single)
                    break
                stage = first_stage if (level == 0 and
                                        first_stage is not None) else merge
                nxt = []
                for i in range(0, len(batches), self._CONSOLIDATE_CHUNK):
                    grp = batches[i:i + self._CONSOLIDATE_CHUNK]
                    if len(grp) == 1:
                        # Level >= 1 singletons are already merge outputs.
                        nxt.append(stage(grp[0]) if level == 0 else grp[0])
                        continue
                    cap = bucket_capacity(sum(b.capacity for b in grp))
                    nxt.append(stage(jit_concat_batches(grp, cap)))
                batches = nxt
                level += 1
                if len(batches) == 1:
                    single = batches[0]
                    break
        if final_stage and self.mode in ("final", "complete",
                                         "mixed_final"):
            single = finalize(single)
        return single

    def execute_device(self, ctx, partition):
        import jax as _jax
        from spark_rapids_tpu import config as _C, monitoring
        m = ctx.metrics_for(self)
        self._has_nans = bool(ctx.conf.get(_C.HAS_NANS))
        update, merge, finalize, mixed, passthrough = self._jits()

        from spark_rapids_tpu import config as C
        pending: List[DeviceBatch] = []
        pending_cap = 0
        saw_input = False
        offset = 0
        update_stage = self.mode in ("partial", "complete")
        # Update partials whose choice of grouping (aggSlotBatches /
        # aggSortedBatches) no read has told yet. Complete mode counts
        # them at the sizes pull of the next consolidation. Partial mode
        # has no read of its own after the skip probe, and adds none: it
        # leaves their group counts, device scalars, to whoever reads the
        # metrics.
        counting = update_stage and self._slot_ok
        uncounted = 0
        unread: list = []
        # Adaptive partial-skip (skipAggPassReductionRatio): measure the
        # FIRST partial batch's reduction; if grouping barely reduced it,
        # later batches project rows straight into the buffer layout and
        # the post-exchange stage does all grouping once. One decision per
        # query (cached in ctx), one small device sync to make it.
        skip_key = f"aggskip:{id(self):x}"
        skip_ratio = float(ctx.conf.get(C.AGG_SKIP_PARTIAL_RATIO))
        can_skip = (self.mode == "partial" and skip_ratio < 1.0
                    and getattr(self, "allow_partial_skip", True)
                    and self._rowskip_capable)
        # Memory guard: when buffered partials exceed this many rows of
        # capacity, consolidate early (mirrors the reference's iterative
        # re-merge loop, aggregate.scala:427 — but amortized, not
        # per-batch). Deliberately NOT tied to batchSizeRows: that knob
        # tunes coalescing, this one bounds buffered-state high water.
        consolidate_at = max(8 << 20,
                             2 * int(ctx.conf.get(C.BATCH_SIZE_ROWS)))
        child_iter = self.children[0].execute_device(ctx, partition)
        if update_stage and not self._global_ok:
            # Coalesce the input stream: one sort-based update kernel over
            # a 4M-row batch beats 8 over 512k (fixed per-dispatch floor),
            # and sparse join outputs compact before the capacity-scaled
            # sort (any smaller bucket: keep_ratio 1). A member whose live
            # rows fill its own bucket comes as it is, selection vector and
            # all: the update masks. Zero-key aggregates skip this: their
            # masked reductions don't sort, so the concat gather would be
            # pure overhead.
            from spark_rapids_tpu.columnar.batch import coalesce_iter
            from spark_rapids_tpu.memory.oom import effective_batch_target
            child_iter = coalesce_iter(
                child_iter,
                effective_batch_target(
                    int(ctx.conf.get(C.BATCH_SIZE_ROWS))),
                shrink=True,
                target_bytes=int(ctx.conf.get(C.BATCH_SIZE_BYTES)),
                owner=self.name)
        for batch in child_iter:
            saw_input = True
            if update_stage:
                from spark_rapids_tpu.memory.oom import retry_on_oom
                skipping = can_skip and ctx.cache.get(skip_key, False)
                monitoring.count("aggUpdateRows", batch.capacity)
                with timed(m):
                    if skipping:
                        partial = retry_on_oom(
                            passthrough,
                            batch, jnp.asarray(offset, jnp.int64))
                    else:
                        partial = retry_on_oom(
                            update, batch, jnp.asarray(offset, jnp.int64))
                if can_skip and skip_key not in ctx.cache:
                    # A host sync that waits for the first update.
                    with monitoring.op_span(self.name, "agg-skip-probe"):
                        groups, live = _jax.device_get(
                            [partial.num_rows, batch.live_count()])
                    ctx.cache[skip_key] = \
                        int(groups) >= skip_ratio * max(int(live), 1)
                    if counting:
                        _count_updates(m, [(partial.capacity, groups)])
                elif counting and not skipping:
                    if self.mode == "complete":
                        uncounted += 1
                    else:
                        unread.append((partial.capacity, partial.num_rows))
                offset += batch.capacity
                if self.mode == "partial":
                    # Partial stage feeds an exchange, which batches its
                    # own sizes pull across every partition — emit the
                    # per-batch partial as-is, no sync here.
                    record_batch(m, partial)
                    yield partial
                    continue
                pending.append(partial)
                pending_cap += partial.capacity
            else:
                # final/merge/mixed_final: defer ALL grouping to one
                # consolidated pass over the partition's batches.
                pending.append(batch)
                pending_cap += batch.capacity
            # mixed_final's kernel is NOT idempotent (it reads a raw x
            # column that its own output no longer has) — never consolidate
            # it mid-stream, only once at the end.
            if pending_cap > consolidate_at and len(pending) > 1 \
                    and self.mode != "mixed_final":
                with timed(m):
                    merged = self._consolidate(ctx, m, pending,
                                               fresh=uncounted)
                uncounted = 0
                pending = [merged]
                pending_cap = merged.capacity
        if unread:
            name = self.name

            def read_flags(metrics):
                with monitoring.op_span(name, "slot-flags"):
                    _count_updates(metrics, _jax.device_get(unread))
            m.defer(read_flags)
        if self.mode == "partial":
            return
        if not saw_input:
            if self._nkeys == 0 and self.mode in ("final", "complete",
                                                  "mixed_final"):
                yield self._empty_result()
            return
        with timed(m):
            acc = self._consolidate(ctx, m, pending, final_stage=True,
                                    fresh=uncounted)
        record_batch(m, acc)
        yield acc

    def _empty_result(self) -> DeviceBatch:
        cap = 8
        cols = []
        for spec in self.aggs:
            t = spec.fn.result_type
            if isinstance(spec.fn, (Count, CountStar)):
                data = jnp.zeros((cap,), t.np_dtype)
                valid = jnp.arange(cap) < 1
            else:
                data = jnp.zeros((cap,), t.np_dtype)
                valid = jnp.zeros((cap,), jnp.bool_)
            if t.is_string:
                cols.append(DeviceColumn(t, jnp.zeros((cap, 8), jnp.uint8),
                                         valid, jnp.zeros((cap,), jnp.int32)))
            else:
                cols.append(DeviceColumn(t, data, valid))
        return DeviceBatch(tuple(cols), jnp.asarray(1, jnp.int32))

    # -- host oracle ---------------------------------------------------------
    def _host_groups(self, hbs, key_evaluator, input_lists):
        """Shared host grouping: returns (order, key_values, groups) where
        groups[key][ai] is the list of python values for aggregate ai.

        Primitive (non-string) keys take a vectorized path — one stable
        lexsort over canonicalized key arrays instead of a per-row python
        dict walk. Host placement (plan/cost.py) made the host engine a
        first-class executor, so grouping millions of rows here must run
        at numpy speed, not interpreter speed (~50x). Semantics are
        identical: first-seen group order, within-group row order (First/
        Last), NaN==NaN and -0.0==0.0 canonical grouping, null keys group
        together."""
        fast = self._host_groups_vectorized(hbs, key_evaluator,
                                            input_lists)
        if fast is not None:
            return fast
        groups = {}
        key_values = {}
        order = []
        for hb, keycols, inlists in zip(hbs, key_evaluator, input_lists):
            for i in range(hb.num_rows):
                triples = [self._host_key(kc, i) for kc in keycols]
                # Canonical key only — raw floats break NaN equality.
                key = tuple((t[0], t[1]) for t in triples)
                if key not in groups:
                    groups[key] = [[] for _ in self.aggs]
                    key_values[key] = [t[2] if t[0] else None
                                       for t in triples]
                    order.append(key)
                for ai, vals in enumerate(inlists):
                    groups[key][ai].append(vals[i] if vals is not None
                                           else 1)
        return order, key_values, groups

    def _host_groups_vectorized(self, hbs, key_evaluator, input_lists):
        """The numpy fast path of :meth:`_host_groups`, or None when the
        shape doesn't qualify (string keys keep the exact python-loop
        canonicalization)."""
        nrows = [hb.num_rows for hb in hbs]
        total = sum(nrows)
        if total == 0:
            return [], {}, {}
        keycols0 = key_evaluator[0] if key_evaluator else []
        if any(kc.dtype.is_string for kc in keycols0):
            return None
        nkeys = len(keycols0)
        nags = len(self.aggs)

        def group_lists(idx_groups):
            out_per_agg = []
            for ai in range(nags):
                parts = [il[ai] for il in input_lists]
                if any(p is None for p in parts):
                    out_per_agg.append([[1] * len(idx)
                                        for idx in idx_groups])
                    continue
                merged = parts[0] if len(parts) == 1 else \
                    [v for p in parts for v in p]
                arr = np.empty(len(merged), dtype=object)
                try:
                    arr[:] = merged          # scalars: one C-level copy
                    ok = True
                except (ValueError, TypeError):
                    ok = False               # tuple rows (merge buffers)
                if ok:
                    out_per_agg.append([arr[idx].tolist()
                                        for idx in idx_groups])
                else:
                    out_per_agg.append([[merged[i] for i in idx.tolist()]
                                        for idx in idx_groups])
            return out_per_agg

        if nkeys == 0:
            idx_all = np.arange(total, dtype=np.int64)
            per_agg = group_lists([idx_all])
            key = ()
            return [key], {key: []}, {key: [per_agg[ai][0]
                                            for ai in range(nags)]}

        # Canonicalize each key column across batches: an exact-equality
        # uint64/int64 view where NaNs share one bit pattern, -0.0 == 0.0
        # and invalid rows compare equal regardless of payload.
        views = []
        valids = []
        raws = []
        for ki in range(nkeys):
            cols = [ke[ki] for ke in key_evaluator]
            data = np.concatenate([np.asarray(c.data) for c in cols]) \
                if len(cols) > 1 else np.asarray(cols[0].data)
            valid = np.concatenate([np.asarray(c.validity)
                                    for c in cols]) \
                if len(cols) > 1 else np.asarray(cols[0].validity)
            dtype = cols[0].dtype
            if dtype.is_floating:
                d = data.astype(np.float64) + 0.0     # -0.0 -> +0.0
                nanmask = np.isnan(d)
                if nanmask.any():
                    d = d.copy()
                    d[nanmask] = np.nan               # canonical NaN bits
                view = d.view(np.uint64).astype(np.int64, copy=False)
            elif dtype.is_boolean:
                view = data.astype(np.int64)
            else:
                view = data.astype(np.int64, copy=False)
            view = np.where(valid, view, np.int64(0))
            views.append(view)
            valids.append(valid.astype(np.int8))
            raws.append((dtype, data, valid))
        order_idx = np.lexsort(tuple(
            a for ki in range(nkeys - 1, -1, -1)
            for a in (views[ki], valids[ki])))
        new_flags = np.zeros(total, dtype=bool)
        new_flags[0] = True
        for ki in range(nkeys):
            sv = views[ki][order_idx]
            sa = valids[ki][order_idx]
            new_flags[1:] |= (sv[1:] != sv[:-1]) | (sa[1:] != sa[:-1])
        starts = np.flatnonzero(new_flags)
        ends = np.append(starts[1:], total)
        # First-seen emission order: lexsort is stable, so order_idx at a
        # group's start IS its first original row.
        emit = np.argsort(order_idx[starts], kind="stable")
        # Within a group, order_idx is already ascending (stable sort
        # keeps equal keys in original row order — First/Last depend on
        # it).
        idx_groups = [order_idx[starts[g]:ends[g]] for g in emit]
        per_agg = group_lists(idx_groups)
        order = []
        key_values = {}
        groups = {}
        for gi, g in enumerate(emit):
            rep = int(order_idx[starts[g]])
            key = []
            vals = []
            for ki in range(nkeys):
                v_ok = bool(valids[ki][rep])
                key.append((v_ok, int(views[ki][rep])))
                if not v_ok:
                    vals.append(None)
                    continue
                dtype, data, _ = raws[ki]
                if dtype.is_floating:
                    f = float(data[rep])
                    vals.append(0.0 if f == 0.0 else f)
                elif dtype.is_boolean:
                    vals.append(bool(data[rep]))
                else:
                    vals.append(int(data[rep]))
            key = tuple(key)
            order.append(key)
            key_values[key] = vals
            groups[key] = [per_agg[ai][gi] for ai in range(nags)]
        return order, key_values, groups

    # -- vectorized host engine ---------------------------------------------
    def _host_segments(self, key_pieces, total):
        """Group segmentation over per-batch key column pieces: one stable
        lexsort over (encode_key_concat, validity) planes per key. Returns
        ``(order_idx, starts, ends, emit, rep_idx, key_enc)`` where
        starts/ends are ascending (reduceat currency), ``emit`` permutes
        sorted-group order into first-seen emission order, ``rep_idx``
        is each group's first original row in emission order, and
        ``key_enc`` is the per-key ``(codes, space)`` list — the caller
        stamps these onto the concatenated key columns so the encoding
        survives into this aggregate's OUTPUT and the next consumer
        (shuffle -> final agg) merges dictionaries instead of
        re-ranking rows.

        Keys arrive as the UNCONCATENATED per-batch pieces so encoding
        can dedupe repeated column instances (grouping-set expansion)
        instead of re-ranking the materialized concat."""
        from spark_rapids_tpu.columnar.host import encode_key_concat
        nkeys = len(key_pieces)
        if nkeys == 0:
            order_idx = np.arange(total, dtype=np.int64)
            one = np.zeros(1, np.int64)
            return (order_idx, one, np.asarray([total], np.int64), one,
                    one.copy(), [])
        codes, valids, spaces = [], [], []
        for pieces in key_pieces:
            c, v, space = encode_key_concat(pieces)
            codes.append(c)
            valids.append(v.view(np.int8))
            spaces.append(space)
        # Pack (valid, code) pairs into as few int64 planes as their
        # value ranges allow: a 9-key rollup that would lexsort and
        # diff-scan 18 planes usually fits in one packed word (string
        # codes are dense ranks, int keys span small ranges). Packing is
        # injective per key, so segment contiguity and the stable
        # within-group order are exactly those of the unpacked sort —
        # only the (irrelevant, emit-normalized) group order changes.
        planes: list = []
        acc = None
        acc_range = 1
        _cap = 1 << 62
        for ki in range(nkeys):
            c, v = codes[ki], valids[ki].astype(np.int64)
            cmin = int(c.min())
            crange = int(c.max()) - cmin + 1
            r = 2 * crange
            if r > _cap:
                if acc is not None:
                    planes.append(acc)
                    acc, acc_range = None, 1
                planes.append(v)        # valid outranks code (null group)
                planes.append(c)
                continue
            local = v * crange + (c - cmin)
            if acc is None:
                acc, acc_range = local, r
            elif acc_range * r <= _cap:
                acc = acc * r + local
                acc_range *= r
            else:
                planes.append(acc)
                acc, acc_range = local, r
        if acc is not None:
            planes.append(acc)
        from spark_rapids_tpu.columnar.host import stable_code_argsort
        order_idx = stable_code_argsort(planes[0]) if len(planes) == 1 \
            else np.lexsort(tuple(planes[::-1]))
        new_flags = np.zeros(total, dtype=bool)
        new_flags[0] = True
        for p in planes:
            sp = p[order_idx]
            new_flags[1:] |= sp[1:] != sp[:-1]
        starts = np.flatnonzero(new_flags).astype(np.int64)
        ends = np.append(starts[1:], total)
        emit = np.argsort(order_idx[starts], kind="stable").astype(np.int64)
        rep_idx = order_idx[starts][emit]
        return (order_idx, starts, ends, emit, rep_idx,
                list(zip(codes, spaces)))

    def _host_exec_vectorized(self, hbs):
        """One vectorized pass covering every host aggregation mode
        (update/complete over inputs, merge/final over buffers,
        mixed_final), or None when the shape doesn't qualify (empty
        input, string min/max, an agg without a segment kernel) — the
        per-row python grouping below stays as the oracle fallback."""
        from spark_rapids_tpu.columnar.host import concat_host_batches
        total = sum(hb.num_rows for hb in hbs)
        if total == 0:
            return None
        for spec in self.aggs:
            fn = spec.fn
            if isinstance(fn, (Count, Average, Sum, First)):
                continue
            if isinstance(fn, Min):
                if fn.child.data_type().is_string:
                    return None
                continue
            return None

        def concat_col(cols):
            if len(cols) == 1:
                return cols[0]
            return concat_host_batches(
                [HostBatch(("c",), [c]) for c in cols]).columns[0]

        mode = self.mode
        agg_inputs = []
        if mode in ("partial", "complete"):
            kind = "update" if mode == "partial" else "agg"
            keysrc = [[as_host_column(e.eval_host(hb), hb)
                       for e in self.group_exprs] for hb in hbs]
            for spec in self.aggs:
                if spec.fn.child is None:
                    agg_inputs.append((kind, [None]))
                else:
                    agg_inputs.append((kind, [concat_col(
                        [as_host_column(spec.fn.child.eval_host(hb), hb)
                         for hb in hbs])]))
        elif mode in ("final", "merge"):
            kind = "final" if mode == "final" else "merge"
            keysrc = [list(hb.columns[:self._nkeys]) for hb in hbs]
            ci = self._nkeys
            for spec in self.aggs:
                nbuf = len(spec.fn.buffer_types)
                agg_inputs.append((kind, [
                    concat_col([hb.columns[ci + b] for hb in hbs])
                    for b in range(nbuf)]))
                ci += nbuf
        else:                                   # mixed_final
            keysrc = [list(hb.columns[:self._nkeys]) for hb in hbs]
            xcol = concat_col([hb.columns[self._nkeys] for hb in hbs])
            ci = self._nkeys + 1
            for spec in self.aggs:
                if spec.distinct:
                    agg_inputs.append(("agg", [xcol]))
                else:
                    nbuf = len(spec.fn.buffer_types)
                    agg_inputs.append(("final", [
                        concat_col([hb.columns[ci + b] for hb in hbs])
                        for b in range(nbuf)]))
                    ci += nbuf
        key_cols = [concat_col([ks[ki] for ks in keysrc])
                    for ki in range(self._nkeys)]
        (order_idx, starts, ends, emit, rep_idx,
         key_enc) = self._host_segments(
            [[ks[ki] for ks in keysrc] for ki in range(self._nkeys)],
            total)
        for kc, (codes, space) in zip(key_cols, key_enc):
            # The concat rows ARE the rows these codes were computed
            # for; stamping lets take(rep_idx) below propagate them.
            if kc._key_codes is None:
                kc._key_codes = codes
                kc._key_uniq = space
        out_cols = []
        for kc in key_cols:
            oc = kc.take(rep_idx)
            if oc.dtype.is_floating:
                # Canonical zero on output: -0.0 group reps emit as 0.0
                # (grouping already treats them equal).
                oc = HostColumn(oc.dtype,
                                oc.data + oc.dtype.np_dtype.type(0),
                                oc.validity)
            out_cols.append(oc)
        for (kind, cols), spec in zip(agg_inputs, self.aggs):
            res = _host_seg_agg(spec.fn, kind, cols, order_idx, starts,
                                ends, total)
            if res is None:
                return None
            out_cols.extend(rc.take(emit) for rc in res)
        return HostBatch(tuple(n for n, _ in self.schema), out_cols)

    def execute_host(self, ctx, partition):
        hbs = list(self.children[0].execute_host(ctx, partition))
        fast = self._host_exec_vectorized(hbs)
        if fast is not None:
            yield fast
            return
        if self.mode in ("final", "merge"):
            yield from self._execute_host_final(
                hbs, do_finalize=self.mode == "final")
            return
        if self.mode == "mixed_final":
            yield from self._execute_host_mixed(hbs)
            return
        key_evaluator = []
        input_lists = []
        for hb in hbs:
            key_evaluator.append([as_host_column(e.eval_host(hb), hb)
                                  for e in self.group_exprs])
            inlists = []
            for spec in self.aggs:
                if spec.fn.child is None:
                    inlists.append(None)
                else:
                    inlists.append(as_host_column(
                        spec.fn.child.eval_host(hb), hb).to_list())
            input_lists.append(inlists)
        order, key_values, groups = self._host_groups(hbs, key_evaluator,
                                                      input_lists)
        rows: List[tuple] = []
        for key in order:
            vals = list(key_values[key])
            for ai, spec in enumerate(self.aggs):
                if self.mode == "partial":
                    vals.extend(spec.fn.host_update(groups[key][ai]))
                else:
                    vals.append(spec.fn.host_agg(groups[key][ai]))
            rows.append(tuple(vals))
        if not rows and self._nkeys == 0:
            vals = []
            for spec in self.aggs:
                if self.mode == "partial":
                    vals.extend(spec.fn.host_update([]))
                else:
                    vals.append(spec.fn.host_agg([]))
            rows = [tuple(vals)]
        yield _rows_to_host_batch(rows, self.schema)

    def _execute_host_final(self, hbs, do_finalize: bool = True):
        """Host final/merge mode: group buffer rows by key, merge buffer
        tuples; 'merge' emits the merged buffers unfinalized."""
        key_evaluator = []
        buf_lists = []
        for hb in hbs:
            key_evaluator.append(list(hb.columns[:self._nkeys]))
            # One pseudo-input per aggregate: the tuple of its buffer values.
            ci = self._nkeys
            per_agg = []
            for spec in self.aggs:
                nbuf = len(spec.fn.buffer_types)
                cols = [hb.columns[ci + b].to_list() for b in range(nbuf)]
                per_agg.append(list(zip(*cols)) if cols else [])
                ci += nbuf
            buf_lists.append(per_agg)
        order, key_values, groups = self._host_groups(hbs, key_evaluator,
                                                      buf_lists)
        rows = []
        for key in order:
            vals = list(key_values[key])
            for ai, spec in enumerate(self.aggs):
                merged = spec.fn.host_merge(groups[key][ai])
                if do_finalize:
                    vals.append(spec.fn.host_finalize(merged))
                else:
                    vals.extend(merged)
            rows.append(tuple(vals))
        yield _rows_to_host_batch(rows, self.schema)

    def _execute_host_mixed(self, hbs):
        """Host mixed_final: input rows are unique by (keys, x); distinct
        specs aggregate the x values, others merge their buffers."""
        key_evaluator = []
        input_lists = []
        x_ord = self._nkeys
        for hb in hbs:
            key_evaluator.append(list(hb.columns[:self._nkeys]))
            xvals = hb.columns[x_ord].to_list()
            ci = self._nkeys + 1
            per_agg = []
            for spec in self.aggs:
                if spec.distinct:
                    per_agg.append(xvals)
                else:
                    nbuf = len(spec.fn.buffer_types)
                    cols = [hb.columns[ci + b].to_list()
                            for b in range(nbuf)]
                    per_agg.append(list(zip(*cols)) if cols else [])
                    ci += nbuf
            input_lists.append(per_agg)
        order, key_values, groups = self._host_groups(hbs, key_evaluator,
                                                      input_lists)
        rows = []
        for key in order:
            vals = list(key_values[key])
            for ai, spec in enumerate(self.aggs):
                if spec.distinct:
                    vals.append(spec.fn.host_agg(groups[key][ai]))
                else:
                    merged = spec.fn.host_merge(groups[key][ai])
                    vals.append(spec.fn.host_finalize(merged))
            rows.append(tuple(vals))
        if not rows and self._nkeys == 0:
            vals = []
            for spec in self.aggs:
                if spec.distinct:
                    vals.append(spec.fn.host_agg([]))
                else:
                    vals.append(spec.fn.host_finalize(
                        spec.fn.host_merge([])))
            rows = [tuple(vals)]
        yield _rows_to_host_batch(rows, self.schema)

    @staticmethod
    def _host_key(col: HostColumn, i: int):
        """(valid, canonical-group-key, output-value) triple for one key."""
        if not col.validity[i]:
            return (False, None, None)
        v = col.data[i]
        if col.dtype.is_string:
            s = bytes(v).decode("utf-8", "replace")
            return (True, s, s)
        if col.dtype.is_floating:
            f = float(v)
            if np.isnan(f):
                return (True, "NaN", f)   # NaN == NaN for grouping
            if f == 0.0:
                return (True, 0.0, 0.0)   # -0.0 == 0.0 for grouping
            return (True, f, f)
        if col.dtype.is_boolean:
            return (True, bool(v), bool(v))
        return (True, int(v), int(v))


def _rows_to_host_batch(rows: List[tuple], schema: Schema) -> HostBatch:
    names = tuple(n for n, _ in schema)
    cols = []
    for ci, (_, t) in enumerate(schema):
        vals = [r[ci] for r in rows]
        cols.append(HostColumn.from_values(t, vals))
    return HostBatch(names, cols)


def _host_seg_agg(fn: AggFunction, kind: str, cols, order_idx, starts,
                  ends, total) -> Optional[List[HostColumn]]:
    """Vectorized per-group evaluation of one aggregate over sorted
    segments — the numpy mirror of the fn's host_update/host_agg/
    host_merge/host_finalize contract, one reduceat per group set
    instead of one python call per group.

    ``kind``: 'agg' (complete result), 'update' (partial buffers),
    'merge' (merged buffers, unfinalized), 'final' (merge + finalize).
    ``cols`` holds the concatenated input column ('agg'/'update'; None
    for count(*)) or the buffer columns ('merge'/'final'). Results come
    back in SORTED-group order (the caller permutes by its emission
    order). None = no segment kernel for this fn/dtype (caller falls
    back to the python path)."""
    ngroups = len(starts)

    def v_of(c):
        return np.asarray(c.validity, np.bool_)[order_idx]

    def d_of(c):
        return np.asarray(c.data)[order_idx]

    def cnt_of(v):
        return np.add.reduceat(v.astype(np.int64), starts)

    def masked_sum(c, out_float):
        v = v_of(c)
        if out_float:
            return np.add.reduceat(
                np.where(v, d_of(c).astype(np.float64), 0.0), starts), v
        with np.errstate(over="ignore"):
            s = np.add.reduceat(
                np.where(v, d_of(c).astype(np.int64), np.int64(0)), starts)
        return s, v

    if isinstance(fn, CountStar) and kind in ("agg", "update"):
        return [HostColumn(dt.INT64, (ends - starts).astype(np.int64),
                           np.ones(ngroups, np.bool_))]
    if isinstance(fn, Count):           # Count + CountStar merge/final
        if kind in ("agg", "update"):
            data = cnt_of(v_of(cols[0]))
        else:
            data, _ = masked_sum(cols[0], out_float=False)
        return [HostColumn(dt.INT64, data, np.ones(ngroups, np.bool_))]

    if isinstance(fn, Sum):
        t = fn.result_type
        s, v = masked_sum(cols[0], out_float=t.is_floating)
        ok = cnt_of(v) > 0
        data = np.where(ok, s, 0).astype(t.np_dtype)
        return [HostColumn(t, data, ok)]

    if isinstance(fn, Average):
        if kind in ("agg", "update"):
            s, v = masked_sum(cols[0], out_float=True)
            n = cnt_of(v)
            sv = n > 0
        else:
            s, v0 = masked_sum(cols[0], out_float=True)
            n, _ = masked_sum(cols[1], out_float=False)
            sv = cnt_of(v0) > 0
        if kind in ("agg", "final"):
            ok = n > 0
            data = np.where(ok, s / np.where(ok, n, 1), 0.0)
            return [HostColumn(dt.FLOAT64, data, ok)]
        return [HostColumn(dt.FLOAT64, np.where(sv, s, 0.0), sv),
                HostColumn(dt.INT64, n, np.ones(ngroups, np.bool_))]

    if isinstance(fn, Min):             # Min + Max, numeric only
        c = cols[0]
        t = c.dtype
        if t.is_string:
            return None
        v = v_of(c)
        ok = cnt_of(v) > 0
        is_max = fn.kind == "max"
        if t.is_floating:
            f = d_of(c).astype(np.float64)
            nanm = v & np.isnan(f)
            nonnan = v & ~np.isnan(f)
            if is_max:
                # Spark max: NaN is greatest — any NaN wins the group.
                m = np.maximum.reduceat(np.where(nonnan, f, -np.inf),
                                        starts)
                data = np.where(cnt_of(nanm) > 0, np.nan, m)
            else:
                # Spark min: NaN only when the group is all-NaN.
                m = np.minimum.reduceat(np.where(nonnan, f, np.inf),
                                        starts)
                data = np.where(cnt_of(nonnan) > 0, m, np.nan)
            data = np.where(ok, data, 0.0).astype(t.np_dtype)
        else:
            x = d_of(c).astype(np.int64)
            if is_max:
                m = np.maximum.reduceat(
                    np.where(v, x, np.iinfo(np.int64).min), starts)
            else:
                m = np.minimum.reduceat(
                    np.where(v, x, np.iinfo(np.int64).max), starts)
            data = np.where(ok, m, 0).astype(t.np_dtype)
        return [HostColumn(t, data, ok)]

    if isinstance(fn, First):           # First + Last
        last = fn.pick == "max"
        pos = np.arange(total, dtype=np.int64)
        if kind in ("agg", "update"):
            c = cols[0]
            v = v_of(c)
            if fn.ignore_nulls:
                if last:
                    p = np.maximum.reduceat(np.where(v, pos, np.int64(-1)),
                                            starts)
                    ok = p >= 0
                else:
                    big = np.int64(total)
                    p = np.minimum.reduceat(np.where(v, pos, big), starts)
                    ok = p < big
            else:
                p = (ends - 1 if last else starts).astype(np.int64)
                ok = np.ones(ngroups, np.bool_)
            safe = np.where(ok, p, 0)
            idx = np.where(ok, order_idx[safe], np.int64(-1))
            vcol = c.take(idx, null_on_negative=True)
            if kind == "agg":
                return [vcol]
            return [vcol, HostColumn(dt.INT64, np.where(ok, safe - starts, 0),
                                     ok)]
        # merge/final over (value, within-group-index) buffers: pick the
        # min (First) / max (Last) index, first-wins on ties like the
        # stable python min/max — encoded as index*T + tiebreak so one
        # reduceat does argmin with stability.
        vb, ib = cols
        iv = v_of(ib)
        ix = d_of(ib).astype(np.int64)
        localpos = pos - np.repeat(starts, ends - starts)
        T = np.int64(total + 1)
        if last:
            enc = np.where(iv, ix * T + (T - 1 - localpos), np.int64(-1))
            best = np.maximum.reduceat(enc, starts)
            ok = best >= 0
        else:
            imax = np.iinfo(np.int64).max
            enc = np.where(iv, ix * T + localpos, imax)
            best = np.minimum.reduceat(enc, starts)
            ok = best < imax
        safe = np.where(ok, best, 0)
        lp = (T - 1) - (safe % T) if last else safe % T
        p = starts + lp
        idx = np.where(ok, order_idx[np.where(ok, p, 0)], np.int64(-1))
        vcol = vb.take(idx, null_on_negative=True)
        if kind == "final":
            return [vcol]
        return [vcol, HostColumn(dt.INT64, np.where(ok, safe // T, 0), ok)]

    return None
