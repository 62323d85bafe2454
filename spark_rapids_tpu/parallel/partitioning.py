"""Partitioning strategies (ref: GpuHashPartitioning.scala,
GpuRangePartitioning.scala + GpuRangePartitioner.scala,
GpuRoundRobinPartitioning.scala, GpuSinglePartitioning.scala,
GpuPartitioning.scala:44-124).

Each strategy maps rows to partition ids on device; ``split_batch``
(columnar/rowmove.py, re-exported here) is the ``Table.contiguousSplit``
analog — it packs each destination's rows into its own fixed-capacity piece,
all pieces in one pass, so every piece keeps a static shape for XLA.

Hash partitioning uses the bit-exact Spark murmur3 (exprs/hash.py) with
``pmod(hash, n)`` — TPU shuffle partitions line up with CPU Spark's, the
parity requirement SURVEY.md §7 calls out.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.batch import DeviceBatch, DeviceColumn
from spark_rapids_tpu.columnar.host import HostBatch, HostColumn
from spark_rapids_tpu.columnar.rowmove import split_batch  # noqa: F401
from spark_rapids_tpu.exprs.base import Expression, as_device_column, \
    as_host_column
from spark_rapids_tpu.exprs.hash import Murmur3Hash
from spark_rapids_tpu.ops import kernels
from spark_rapids_tpu.ops.sort import SortOrder


class Partitioning:
    """Maps each row to a partition id in [0, num_partitions)."""

    num_partitions: int

    @property
    def jittable(self) -> bool:
        """False when any key expression needs a host roundtrip."""
        return True

    def partition_ids(self, batch: DeviceBatch) -> jnp.ndarray:
        raise NotImplementedError

    def partition_ids_host(self, hb: HostBatch) -> np.ndarray:
        raise NotImplementedError


@dataclasses.dataclass
class SinglePartitioning(Partitioning):
    num_partitions: int = 1

    def partition_ids(self, batch):
        return jnp.zeros((batch.capacity,), jnp.int32)

    def partition_ids_host(self, hb):
        return np.zeros(hb.num_rows, np.int32)


class HashPartitioning(Partitioning):
    """pmod(murmur3(keys), n) — exactly Spark's HashPartitioning."""

    def __init__(self, keys: Sequence[Expression], num_partitions: int):
        self.keys = list(keys)
        self.num_partitions = num_partitions
        self._hash = Murmur3Hash(self.keys)

    @property
    def jittable(self) -> bool:
        return all(k.jittable for k in self.keys)

    def partition_ids(self, batch):
        h = as_device_column(self._hash.eval(batch), batch).data
        n = jnp.int32(self.num_partitions)
        return jnp.remainder(jnp.remainder(h, n) + n, n).astype(jnp.int32)

    def partition_ids_host(self, hb):
        h = as_host_column(self._hash.eval_host(hb), hb).data
        n = self.num_partitions
        return (((h.astype(np.int64) % n) + n) % n).astype(np.int32)


class RoundRobinPartitioning(Partitioning):
    """Position-based distribution (GpuRoundRobinPartitioning — the
    reference starts at a random partition per task; we start at 0 for
    determinism, which only shifts which partition gets which rows)."""

    def __init__(self, num_partitions: int, start: int = 0):
        self.num_partitions = num_partitions
        self.start = start

    def partition_ids(self, batch):
        return jnp.remainder(self.start +
                             jnp.arange(batch.capacity, dtype=jnp.int32),
                             self.num_partitions).astype(jnp.int32)

    def partition_ids_host(self, hb):
        return ((self.start + np.arange(hb.num_rows)) %
                self.num_partitions).astype(np.int32)


class RangePartitioning(Partitioning):
    """Range partitioning by sort orders against sampled bounds
    (GpuRangePartitioning.scala: CPU reservoir sample picks bounds, device
    does the upper-bound search). ``bounds`` is a HostBatch of the key
    columns with num_partitions-1 rows, ascending."""

    def __init__(self, orders: Sequence[SortOrder], num_partitions: int,
                 bounds: Optional[HostBatch] = None):
        self.orders = list(orders)
        self.num_partitions = num_partitions
        self.bounds = bounds

    @property
    def jittable(self) -> bool:
        return all(o.child.jittable for o in self.orders)

    @staticmethod
    def compute_bounds(sample: HostBatch, orders,
                       num_partitions: int) -> HostBatch:
        """Pick num_partitions-1 bounds from a host sample of the keys
        (the reservoir-sample half of GpuRangePartitioner.scala:33)."""
        from spark_rapids_tpu.ops.sort import sort_host_batch
        sorted_sample = sort_host_batch(sample, orders)
        n = sorted_sample.num_rows
        idxs = [min(n - 1, max(0, (i + 1) * n // num_partitions))
                for i in range(num_partitions - 1)] if n else []
        cols = []
        for c in sorted_sample.columns:
            cols.append(HostColumn(c.dtype, c.data[idxs],
                                   c.validity[idxs]))
        return HostBatch(sorted_sample.names, cols)

    def _key_words(self, batch_like, device: bool):
        """Orderable word arrays for the key exprs over a data batch."""
        words = []
        for o in self.orders:
            if device:
                col = as_device_column(o.child.eval(batch_like), batch_like)
            else:
                hc = as_host_column(o.child.eval_host(batch_like),
                                    batch_like)
                col = _host_as_device_like(hc)
            words.extend(kernels.sort_key_passes(col, o.ascending,
                                                 o.nulls_first))
        return words

    def _bound_words(self):
        """Orderable words of the bounds rows — the bounds batch holds the
        key columns positionally (k0, k1, ...), no exprs involved."""
        words = []
        for i, o in enumerate(self.orders):
            col = _host_as_device_like(self.bounds.columns[i])
            words.extend(kernels.sort_key_passes(col, o.ascending,
                                                 o.nulls_first))
        return words

    def partition_ids(self, batch):
        assert self.bounds is not None, "range bounds not computed"
        row_words = self._key_words(batch, device=True)
        bound_words = self._bound_words()
        nb = self.bounds.num_rows
        cap = batch.capacity
        pid = jnp.zeros((cap,), jnp.int32)
        for bi in range(nb):
            # row > bound  <=> lexicographic compare over word passes.
            gt = jnp.zeros((cap,), jnp.bool_)
            eq = jnp.ones((cap,), jnp.bool_)
            for rw, bw in zip(row_words, bound_words):
                b = bw[bi]
                gt = gt | (eq & (rw > b))
                eq = eq & (rw == b)
            # Spark RangePartitioner: keys equal to a bound stay in the
            # lower partition (bounds are inclusive upper bounds).
            pid = pid + gt.astype(jnp.int32)
        return jnp.minimum(pid, self.num_partitions - 1)

    def partition_ids_host(self, hb):
        assert self.bounds is not None
        row_words = [np.asarray(w) for w in self._key_words(hb, device=False)]
        bound_words = [np.asarray(w) for w in self._bound_words()]
        n = hb.num_rows
        pid = np.zeros(n, np.int32)
        for bi in range(self.bounds.num_rows):
            gt = np.zeros(n, np.bool_)
            eq = np.ones(n, np.bool_)
            for rw, bw in zip(row_words, bound_words):
                b = bw[bi]
                gt = gt | (eq & (rw > b))
                eq = eq & (rw == b)
            pid += gt.astype(np.int32)
        return np.minimum(pid, self.num_partitions - 1)


def _host_as_device_like(hc: HostColumn):
    """View a host column with jnp-compatible arrays for the shared kernels
    (numpy arrays duck-type fine through sort_key_passes)."""
    from spark_rapids_tpu.columnar.host import StringMatrixView
    if hc.dtype.is_string:
        v = StringMatrixView.of(hc)
        return DeviceColumn(hc.dtype, jnp.asarray(v.data),
                            jnp.asarray(v.validity), jnp.asarray(v.lengths))
    return DeviceColumn(hc.dtype, jnp.asarray(hc.data),
                        jnp.asarray(hc.validity))


# ---------------------------------------------------------------------------
# Splitting (Table.contiguousSplit analog)
# ---------------------------------------------------------------------------

def split_host_batch(hb: HostBatch, pids: np.ndarray,
                     num_partitions: int) -> List[HostBatch]:
    """ONE stable argsort + one gather per column, then zero-copy slices
    per destination — instead of a boolean-mask scan of the whole batch
    per partition (O(n) x num_partitions). The host engine is a
    first-class placement target now (plan/cost.py), so its shuffle
    split runs the same move-all-rows-once shape as the device split."""
    from spark_rapids_tpu.columnar.host import stable_code_argsort
    order = stable_code_argsort(np.asarray(pids, np.int64))
    counts = np.bincount(pids[order], minlength=num_partitions)
    offsets = np.concatenate([[0], np.cumsum(counts)])
    # take() keeps dense string layouts dense — slicing .data here would
    # materialize object arrays and force every downstream string kernel
    # back through a strings_to_matrix re-encode.
    gathered = [c.take(order) for c in hb.columns]
    out = []
    for p in range(num_partitions):
        lo, hi = int(offsets[p]), int(offsets[p + 1])
        cols = []
        for g in gathered:
            if g.dtype.is_string and g._data is None:
                c = HostColumn(
                    g.dtype, None, g.validity[lo:hi],
                    str_matrix=g.str_matrix[lo:hi],
                    str_lengths=g.str_lengths[lo:hi])
            else:
                c = HostColumn(g.dtype, g.data[lo:hi],
                               g.validity[lo:hi])
            if g._key_codes is not None:
                # Key-code propagation through the shuffle: the reduce
                # side merges per-map-shard code dictionaries instead of
                # re-ranking every received row (columnar/host.py).
                c._key_codes = g._key_codes[lo:hi]
                c._key_uniq = g._key_uniq
            elif g.dtype.is_string:
                c._key_src = (g, slice(lo, hi), None)
            cols.append(c)
        out.append(HostBatch(hb.names, cols))
    return out
