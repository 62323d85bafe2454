"""In-process shuffle transport: today's BufferCatalog-backed exchange
path, refactored behind the SPI.

Shards stay on-device as SpillableBatch handles (memory/stores.py) —
spillable under the memory ladder, CRC-framed via ``wire.frame_blob``
once they reach the disk tier, owner-tagged by the per-query catalog.
This is the serializer-fallback half of the reference's transport split
(GpuColumnarBatchSerializer.scala:38): always available, zero copies,
single process.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from spark_rapids_tpu.parallel.transport.base import (
    ShuffleSession, ShuffleTransport)


class InProcessSession(ShuffleSession):
    def __init__(self, tag: str, num_partitions: int,
                 owner: Optional[int], catalog):
        super().__init__(tag, owner)
        self._catalog = catalog
        self.buckets: List[list] = [[] for _ in range(num_partitions)]
        self._committed = False

    def write_shard(self, partition: int, batch) -> None:
        from spark_rapids_tpu import faults
        from spark_rapids_tpu.memory.stores import (
            PRIORITY_SHUFFLE_OUTPUT, SpillableBatch)
        faults.fault_point("transport.write", owner=self.owner)
        self.record_device_shard(partition, batch)
        self.buckets[partition].append(SpillableBatch(
            self._catalog, batch, PRIORITY_SHUFFLE_OUTPUT))

    def commit(self) -> None:
        # Device handles are visible the moment they register; commit is
        # the SPI's publication barrier and a no-op here.
        self._committed = True

    def fetch_shards(self, partition: int):
        return self.buckets[partition]

    def invalidate(self) -> None:
        for blist in self.buckets:
            for sb in blist:
                sb.close()
        self.buckets = [[] for _ in self.buckets]
        self._committed = False


class InProcessTransport(ShuffleTransport):
    name = "inprocess"

    def open(self, conf, tag: str, num_partitions: int,
             owner: Optional[int] = None, catalog=None,
             metrics=None) -> InProcessSession:
        assert catalog is not None, \
            "inprocess transport needs the query's buffer catalog"
        return InProcessSession(tag, num_partitions, owner, catalog)
