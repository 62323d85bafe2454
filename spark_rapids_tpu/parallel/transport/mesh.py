"""Mesh shuffle transport: the ICI collective path behind the SPI.

The data plane here is ``jax.lax.all_to_all`` inside MeshExchangeExec's
shard_map program — the fabric moves the bytes, not this module. What
the SPI contributes is the DURABLE half: each device's post-exchange
shard registers through ``write_shard`` as an owner-tagged spillable
catalog handle (memory/stores.py — bounded by the memory ladder,
CRC-framed once spilled), and ``fetch_shards``/``invalidate`` give the
collective output the same lineage-recovery contract as every other
transport: lose a shard, recompute one stage.

The transport does not fold partitions itself — MeshExchangeExec's
fold/split pass (partition count != mesh size) writes one shard per
LOGICAL partition, so consumers never see mesh geometry.
"""

from __future__ import annotations

from typing import List, Optional

from spark_rapids_tpu.parallel.transport.base import (
    ShuffleSession, ShuffleTransport)


class MeshSession(ShuffleSession):
    def __init__(self, tag: str, num_partitions: int,
                 owner: Optional[int], catalog):
        super().__init__(tag, owner)
        self._catalog = catalog
        self.buckets: List[list] = [[] for _ in range(num_partitions)]

    def write_shard(self, partition: int, batch) -> None:
        from spark_rapids_tpu.memory.stores import (
            PRIORITY_SHUFFLE_OUTPUT, SpillableBatch)
        self.record_device_shard(partition, batch)
        self.buckets[partition].append(SpillableBatch(
            self._catalog, batch, PRIORITY_SHUFFLE_OUTPUT))

    def commit(self) -> None:
        pass

    def fetch_shards(self, partition: int):
        return self.buckets[partition]

    def invalidate(self) -> None:
        for blist in self.buckets:
            for sb in blist:
                sb.close()
        self.buckets = [[] for _ in self.buckets]


class MeshTransport(ShuffleTransport):
    name = "mesh"

    def open(self, conf, tag: str, num_partitions: int,
             owner: Optional[int] = None, catalog=None,
             metrics=None) -> MeshSession:
        assert catalog is not None, \
            "mesh transport needs the query's buffer catalog"
        return MeshSession(tag, num_partitions, owner, catalog)
