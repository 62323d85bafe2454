"""Device-mesh collectives: the ICI/DCN distribution layer
(ref: SURVEY.md §2.6 TPU mapping — the UCX client/server pull protocol of
shuffle-plugin/.../ucx/UCX.scala becomes a *planned collective exchange*).

Design (scaling-book recipe): pick a mesh, annotate shardings, let XLA
insert collectives.
- One logical table = one DeviceBatch per device, sharded over the ``data``
  mesh axis (per-partition data parallelism, SURVEY.md §2.5).
- Hash shuffle = ``jax.lax.all_to_all`` over ICI: each device splits its
  batch into per-destination pieces (the contiguousSplit analog), the
  collective transposes piece ownership, receivers concatenate.
- Broadcast join build = ``all_gather`` once (GpuBroadcastExchangeExec).
- Partial->final aggregation crosses the exchange exactly like the
  reference's partial/final GpuHashAggregate pair.

Everything here is shape-static and runs under ``shard_map`` + ``jit``; the
driver validates it on an N-virtual-device CPU mesh
(xla_force_host_platform_device_count) exactly like tests/conftest.py.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_rapids_tpu.shims import (shard_map, tree_flatten,
                                    tree_map, tree_unflatten)

from spark_rapids_tpu.columnar.batch import DeviceBatch, bucket_capacity
from spark_rapids_tpu.columnar.rowmove import concat_stacked
from spark_rapids_tpu.parallel.partitioning import Partitioning, split_batch

DATA_AXIS = "data"


def make_mesh(n_devices: Optional[int] = None,
              axis: str = DATA_AXIS) -> Mesh:
    """A one-axis mesh over ``jax.devices()``, which lists the process's
    devices in id order: all of them, or the first ``n_devices``. The
    four chips of one 2x2 v5e host give a ``data`` axis of 4 (ids 0-3);
    the host's 2x2 ICI layout is not an axis of its own, the one
    collective here (``all_to_all``) runs over all four."""
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis,))


def batch_sharding(mesh: Mesh, axis: str = DATA_AXIS):
    """Sharding that splits every batch leaf's leading (row) axis across the
    mesh — used to lay out a logical table as one shard per device."""
    return NamedSharding(mesh, P(axis))


# ---------------------------------------------------------------------------
# Collective shuffle (inside shard_map)
# ---------------------------------------------------------------------------

def all_to_all_exchange(batch: DeviceBatch, pids: jnp.ndarray,
                        n_devices: int,
                        axis: str = DATA_AXIS,
                        piece_capacity: Optional[int] = None
                        ) -> DeviceBatch:
    """ICI hash-shuffle step for one device's shard (call under shard_map).

    Splits the local batch into per-destination pieces (one index scatter
    and one gather per slab: a row moves once), exchanges piece ownership
    with ``all_to_all`` (one fused ICI collective, not a peer pull
    protocol), and concatenates the received pieces.

    ``piece_capacity`` is the static per-destination piece size. Default
    (None) is the worst case — every piece at the full shard capacity, an
    n_devices-fold wire inflation. The planner's two-phase path
    (SURVEY §7 sizes-then-data) exchanges COUNTS first and passes the
    observed max, so the collective moves ~the real data volume.
    """
    # One pass, straight into the collective's layout: leaves
    # (n_devices, piece_capacity, ...), leading axis = destination device.
    stacked = split_batch(batch, pids, n_devices, piece_capacity)
    received = jax.lax.all_to_all(stacked, axis, split_axis=0,
                                  concat_axis=0, tiled=False)
    # received leaf shape == stacked leaf shape; index i = piece from peer i.
    return concat_stacked(received, bucket_capacity(
        n_devices * (piece_capacity or batch.capacity)))


def exchange_counts(batch: DeviceBatch, pids: jnp.ndarray,
                    n_devices: int, axis: str = DATA_AXIS) -> jnp.ndarray:
    """Phase 1 of the two-phase shuffle: this device's per-destination
    live-row counts, all_to_all'd so every device holds the counts of the
    pieces it WILL receive — a (n_devices,) int32 collective, the
    metadata exchange that replaces the reference's UCX metadata round
    (SURVEY §2.6)."""
    live = batch.row_mask()
    key = jnp.where(live, pids, n_devices)
    counts = jax.ops.segment_sum(
        jnp.ones((batch.capacity,), jnp.int32), key,
        num_segments=n_devices + 1)[:n_devices]
    return jax.lax.all_to_all(counts[:, None], axis, split_axis=0,
                              concat_axis=0, tiled=False).reshape(-1)


def all_gather_batch(batch: DeviceBatch, n_devices: int,
                     axis: str = DATA_AXIS) -> DeviceBatch:
    """Replicate every device's shard to all devices (broadcast build side:
    the one-time all-gather replacing collect+torrent-broadcast+re-upload).
    """
    gathered = jax.lax.all_gather(batch, axis, axis=0, tiled=False)
    return concat_stacked(gathered,
                          bucket_capacity(n_devices * batch.capacity))


# ---------------------------------------------------------------------------
# Distributed plan step: shard_map over a q1-shaped pipeline
# ---------------------------------------------------------------------------

def distributed_aggregate_step(mesh: Mesh, agg_exec,
                               partitioning: Partitioning,
                               axis: str = DATA_AXIS):
    """Build a jitted distributed aggregation step over ``mesh``.

    Per device (under shard_map):
      partial = local groupby update of the device's shard
      exchanged = all_to_all by hash(key) pmod n  (ICI shuffle)
      final = merge + finalize of the received partials

    ``agg_exec`` is a HashAggregateExec used purely for its kernels
    (update/merge/finalize are pure batch->batch functions).
    """
    n = mesh.devices.size

    def step(local_batch: DeviceBatch) -> DeviceBatch:
        partial = agg_exec._update_batch(local_batch,
                                         jnp.asarray(0, jnp.int64))
        pids = partitioning.partition_ids(partial)
        exchanged = all_to_all_exchange(partial, pids, n, axis)
        merged = agg_exec._merge_batch(exchanged)
        return agg_exec._finalize_batch(merged)

    def wrapped(stacked_local):
        # in_specs P(axis) leaves a unit device axis on each leaf locally.
        local = tree_map(lambda x: x[0], stacked_local)
        out = step(local)
        return tree_map(lambda x: x[None], out)

    sharded = shard_map(wrapped, mesh, in_specs=(P(axis),),
                        out_specs=P(axis))
    return jax.jit(sharded)


def distributed_join_agg_step(mesh: Mesh, join_exec, agg_exec,
                              join_partitioning_left,
                              join_partitioning_right,
                              agg_partitioning,
                              axis: str = DATA_AXIS,
                              join_out_capacity: Optional[int] = None):
    """Distributed join + aggregate step (TPC-H q3-shaped):

    per device: all_to_all both sides by join key -> local hash join ->
    partial agg -> all_to_all by group key -> final agg.

    Returns (result, overflowed): a join can emit up to |L|x|R| pairs per
    device; ``join_out_capacity`` bounds the static expansion buffer
    (default: the exact |L|x|R| product when small, else 4x the input).
    ``overflowed`` is a per-device bool — callers must check it, since
    pairs beyond the capacity are truncated.
    """
    from spark_rapids_tpu.ops import join as J
    n = mesh.devices.size

    def step(left: DeviceBatch, right: DeviceBatch):
        lex = all_to_all_exchange(
            left, join_partitioning_left.partition_ids(left), n, axis)
        rex = all_to_all_exchange(
            right, join_partitioning_right.partition_ids(right), n, axis)
        built = J.build_side(rex, [k.ordinal
                                   for k in join_exec.right_keys])
        lo, counts, plive = J.probe_ranges(
            built, lex, [k.ordinal for k in join_exec.left_keys])
        if join_out_capacity is not None:
            out_cap = bucket_capacity(join_out_capacity)
        elif lex.capacity * rex.capacity <= (1 << 20):
            out_cap = bucket_capacity(lex.capacity * rex.capacity)
        else:
            out_cap = bucket_capacity(4 * (lex.capacity + rex.capacity))
        p, b, valid, num_rows, overflow = J.expand_pairs(
            lo, counts, out_cap, lex.capacity)
        valid = J._pair_keys_equal(
            built, b, lex, p, [k.ordinal for k in join_exec.left_keys],
            valid)
        probe_cols = J._gather_cols(lex, p, valid)
        build_cols = J._gather_cols(built.batch, b, valid)
        pairs = DeviceBatch(
            tuple(probe_cols) + tuple(build_cols), num_rows).compact(valid)
        partial = agg_exec._update_batch(pairs, jnp.asarray(0, jnp.int64))
        pids = agg_partitioning.partition_ids(partial)
        exchanged = all_to_all_exchange(partial, pids, n, axis)
        merged = agg_exec._merge_batch(exchanged)
        return agg_exec._finalize_batch(merged), overflow

    def wrapped(l_stacked, r_stacked):
        left = tree_map(lambda x: x[0], l_stacked)
        right = tree_map(lambda x: x[0], r_stacked)
        out, overflow = step(left, right)
        return (tree_map(lambda x: x[None], out), overflow[None])

    sharded = shard_map(wrapped, mesh, in_specs=(P(axis), P(axis)),
                        out_specs=P(axis))
    return jax.jit(sharded)


def _lead_axis(tree):
    return tree_map(lambda x: x[None], tree)


def shard_batches(mesh: Mesh, per_device: List[DeviceBatch],
                  axis: str = DATA_AXIS) -> DeviceBatch:
    """Assemble per-device shards into one globally-sharded DeviceBatch:
    every leaf gets a leading device axis mapped onto the mesh, row i
    being ``per_device[i]`` (one shard per mesh device).

    Whole trees, not leaf by leaf: one cached program a shard gives its
    leaves their leading axis of 1, ONE batched ``device_put`` sends the
    n trees to the devices that hold rows 0..n-1, and each global leaf is
    assembled from its n single-device pieces on the host
    (``make_array_from_single_device_arrays``: no device work)."""
    from spark_rapids_tpu.ops import kernel_cache as kc
    n = len(per_device)
    if n != mesh.devices.size:
        raise ValueError(f"{n} shards for a mesh of {mesh.devices.size}")
    sharding = NamedSharding(mesh, P(axis))
    lead = kc.lookup("mesh-lead", (), lambda: jax.jit(_lead_axis))
    rows = [tree_flatten(lead(b)) for b in per_device]
    treedef = rows[0][1]
    first = rows[0][0]
    # the device of row i, as the sharding lays the leading axis out
    devices = [None] * n
    for d, idx in sharding.addressable_devices_indices_map(
            (n,) + first[0].shape[1:]).items():
        devices[idx[0].start or 0] = d
    placed = jax.device_put([leaves for leaves, _ in rows], devices)
    return tree_unflatten(treedef, [
        jax.make_array_from_single_device_arrays(
            (n,) + x.shape[1:], sharding, [p[j] for p in placed])
        for j, x in enumerate(first)])
