"""Shuffle transport SPI (ISSUE 6).

The reference treats shuffle transport as a swappable layer: the
columnar serializer fallback (GpuColumnarBatchSerializer.scala:38) works
everywhere, and the UCX/RDMA plugin (shuffle-plugin/.../ucx/UCX.scala)
slots in behind the same RapidsShuffleInternalManager interface when the
fabric supports it. This package mirrors that split for the TPU engine:
every exchange funnel talks to a :class:`ShuffleTransport` chosen by
``spark.rapids.sql.shuffle.transport`` instead of hard-coding where
shuffle shards live.

Contract (see docs/shuffle.md for the full narrative):

- ``Transport.open(conf, tag, ...)`` starts ONE map/reduce session for
  one exchange materialization. ``tag`` identifies the exchange's
  durable output (stable across a recompute of the same exchange).
- ``session.write_shard(partition, batch)`` appends one map-side piece
  to a reduce partition's shard list. Shards are owner-tagged with the
  exchange id, so a loss detected at fetch time flows through
  lineage-scoped stage recompute (parallel/stages.py), not whole-query
  retry.
- ``session.commit()`` publishes the map output atomically: fetches
  must never observe a half-written shard set.
- ``session.fetch_shards(partition)`` returns the partition's shard
  handles (``.capacity``, ``.get() -> DeviceBatch``, ``.release()``,
  ``.close()`` — the SpillableBatch protocol, memory/stores.py), in
  deterministic map order.
- ``session.invalidate()`` drops the durable output (the
  ``stage_invalidate`` boundary contract) so a recompute rewrites it;
  ``session.abort()`` cleans up a partial materialization;
  ``session.close()`` is query teardown.

Serialized shards are CRC-framed via the existing ``wire.frame_blob``
format, so a flipped bit on any transport's at-rest data is DETECTED at
fetch (one refetch, counter ``remoteShardRefetches``) instead of
decoding into silently wrong rows.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence


class ShardLostError(RuntimeError):
    """A durable shuffle shard is gone (missing spool file, vanished
    manifest, injected ``lostshard``). Carries the UNAVAILABLE marker so
    an unattributable loss still lands in the whole-query retry, and
    ``fault_owner`` (the owning exchange exec's id) so lineage recovery
    (parallel/stages.py) can invalidate and recompute exactly the owning
    stage instead."""

    def __init__(self, what: str, owner: Optional[int] = None):
        super().__init__(
            f"UNAVAILABLE: lost shuffle shard: {what}")
        self.fault_owner = owner


class TransportError(RuntimeError):
    """Non-recoverable transport misconfiguration (unknown transport
    name, unreachable spool directory, rendezvous timeout)."""


class ShuffleSession:
    """One exchange materialization through one transport. Subclasses
    implement the five SPI verbs; the base class only carries the
    identity fields every implementation needs."""

    def __init__(self, tag: str, owner: Optional[int]):
        # ``tag`` names the durable output; ``owner`` is the owning
        # exchange exec's id() — the lineage attribution every
        # loss/corruption error must carry.
        self.tag = tag
        self.owner = owner
        # Observed per-partition byte sizes (the size-observation hook
        # runtime adaptive re-planning and byte-aware partition
        # coalescing read, parallel/replan.py / exchange._groups): every
        # implementation records what it actually wrote, in its own
        # units (device bytes inprocess/mesh, framed blob bytes
        # hostfile) — EXACT sizes, the GpuCustomShuffleReaderExec
        # materialized-stats analog.
        self.shard_bytes: Dict[int, int] = {}
        # The same without the padding of capacity buckets, where the
        # transport knows the live rows of what it wrote (a device shard
        # that carries a ``rows_hint``: an estimate, the footprint scaled
        # by it; a framed blob holds live rows alone): what a runtime
        # re-plan holds against a threshold stated in bytes of data
        # (parallel/replan.py). A device shard WITHOUT a count goes in at
        # its footprint and is counted, so that a plan which hung on
        # whether a sizes pull happened upstream says so.
        self.live_bytes = 0
        self.uncounted_shards = 0

    def record_shard_bytes(self, partition: int, nbytes: int,
                          live_bytes: Optional[int] = None) -> None:
        self.shard_bytes[partition] = \
            self.shard_bytes.get(partition, 0) + int(nbytes)
        self.live_bytes += int(nbytes if live_bytes is None
                               else live_bytes)

    def record_device_shard(self, partition: int, batch) -> None:
        """A device batch written as a shard: its footprint, and its live
        bytes as ``DeviceBatch.live_size_bytes()`` estimates them."""
        live = batch.live_size_bytes()
        if live is None:
            self.uncounted_shards += 1
        self.record_shard_bytes(partition, batch.device_size_bytes(), live)

    def observed_bytes(self, partition: Optional[int] = None) -> int:
        """Total observed bytes of one partition, or of the whole map
        output (partition=None). Only meaningful after commit()."""
        if partition is not None:
            return self.shard_bytes.get(partition, 0)
        return sum(self.shard_bytes.values())

    # -- map side ------------------------------------------------------------
    def write_shard(self, partition: int, batch) -> None:
        raise NotImplementedError

    def commit(self) -> None:
        raise NotImplementedError

    # -- reduce side ---------------------------------------------------------
    def fetch_shards(self, partition: int) -> Sequence:
        raise NotImplementedError

    # -- lifecycle -----------------------------------------------------------
    def abort(self) -> None:
        """Failed mid-materialization: release whatever was written (the
        retry ladder re-runs the materialization from scratch)."""
        self.invalidate()

    def invalidate(self) -> None:
        raise NotImplementedError

    def close(self) -> None:
        """Query teardown: release everything. Must be idempotent."""
        self.invalidate()


class ShuffleTransport:
    """Transport factory. Stateless; one session per exchange
    materialization."""

    name = "?"

    def open(self, conf, tag: str, num_partitions: int,
             owner: Optional[int] = None, catalog=None,
             metrics=None) -> ShuffleSession:
        raise NotImplementedError
