"""Shuffle & broadcast exchanges (ref: GpuShuffleExchangeExec.scala:69,145,
GpuBroadcastExchangeExec.scala:237, ShuffledBatchRDD.scala).

The exchange materializes the child once per query context (the role
Spark's shuffle files / the reference's RapidsCachingWriter device-store
play — see RapidsShuffleInternalManager write path, SURVEY.md §3.4),
bucketing every batch by partition id. Reduce tasks then stream their
bucket. WHERE the buckets live is the shuffle transport SPI's business
(parallel/transport/, ISSUE 6): ``inprocess`` keeps them as spillable
catalog handles (single process), ``hostfile`` spools CRC-framed shard
blobs to a shared directory so independent worker processes can fetch
each other's map output, and the multi-chip path replaces this
materialization entirely with an ICI all-to-all collective
(parallel/mesh_exchange.py) — a planned collective exchange instead of a
pull protocol, per SURVEY.md §2.6's TPU mapping note.

A sampled range exchange computes bounds from a host sample first, like
GpuRangePartitioner's reservoir sample.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.columnar.batch import DeviceBatch, bucket_capacity, \
    concat_batches
from spark_rapids_tpu.columnar.host import HostBatch, HostColumn
from spark_rapids_tpu.ops.base import Exec, ExecContext, Schema, timed
from spark_rapids_tpu.parallel.partitioning import (
    Partitioning, RangePartitioning, split_batch, split_host_batch)


def _slice_rows(batch: DeviceBatch, start, size: int,
                num_rows) -> DeviceBatch:
    """Rows [start, start+size) of a dense batch as a new batch with
    ``num_rows`` live rows (traced start/num_rows; static size)."""
    from spark_rapids_tpu.columnar.batch import DeviceColumn
    cols = []
    for c in batch.columns:
        data = jax.lax.dynamic_slice_in_dim(c.data, start, size, axis=0)
        validity = jax.lax.dynamic_slice_in_dim(c.validity, start, size,
                                                axis=0)
        if c.dtype.is_string:
            lengths = jax.lax.dynamic_slice_in_dim(c.lengths, start, size,
                                                   axis=0)
            cols.append(DeviceColumn(c.dtype, data, validity, lengths))
        else:
            cols.append(DeviceColumn(c.dtype, data, validity))
    return DeviceBatch(tuple(cols), jnp.asarray(num_rows, jnp.int32))


class ShuffleExchangeExec(Exec):
    """Repartition the child by a Partitioning strategy.

    ``allow_coalesce`` opts this exchange into AQE-lite partition
    coalescing (GpuCustomShuffleReaderExec.scala:132 analog): once the
    map side materializes, the EXACT per-bucket row counts are known, and
    undersized adjacent reduce partitions merge up to the target. The
    planner enables it where partition identity is not load-bearing
    (aggregate/window/sort exchanges) and keeps it off for co-partitioned
    join inputs, whose two sides must stay aligned bucket-for-bucket."""

    def __init__(self, child: Exec, partitioning: Partitioning,
                 allow_coalesce: bool = False):
        super().__init__(child)
        self.partitioning = partitioning
        self.allow_coalesce = allow_coalesce

    @property
    def schema(self) -> Schema:
        return self.children[0].schema

    def _groups(self, ctx) -> Optional[List[List[int]]]:
        """Coalesced bucket groups (device engine only), or None."""
        from spark_rapids_tpu import config as C
        n = self.partitioning.num_partitions
        if not self.allow_coalesce or n <= 1 or \
                ctx.cache.get("engine") != "device" or \
                not bool(ctx.conf.get(C.AQE_COALESCE_PARTITIONS)):
            return None
        gkey = f"shuffle-groups:{id(self):x}"
        groups = ctx.cache.get(gkey)
        if groups is None:
            sess = self._materialize_device(ctx)
            sizes = ctx.cache.get(self._cache_key(True) + ":rows",
                                  [0] * n)
            target = int(ctx.conf.get(C.AQE_COALESCE_TARGET_ROWS))
            # Byte-aware merging from the OBSERVED shard bytes the
            # transport session recorded at materialization: partitions
            # merge while BOTH the row and the byte target hold, so a
            # few fat skewed buckets never collapse into one oversized
            # reduce partition just because their row counts are low.
            tbytes = int(ctx.conf.get(C.AQE_COALESCE_TARGET_BYTES))
            groups = []
            cur: List[int] = []
            cur_rows = 0
            cur_bytes = 0
            for b in range(n):
                b_bytes = sess.observed_bytes(b)
                if cur and (cur_rows + sizes[b] > target or
                            cur_bytes + b_bytes > tbytes):
                    groups.append(cur)
                    cur, cur_rows, cur_bytes = [], 0, 0
                cur.append(b)
                cur_rows += sizes[b]
                cur_bytes += b_bytes
            if cur:
                groups.append(cur)
            m = ctx.metrics_for(self)
            m.add("coalescedPartitions", n - len(groups))
            ctx.cache[gkey] = groups
        return groups

    def num_partitions(self, ctx) -> int:
        groups = self._groups(ctx)
        if groups is not None:
            return len(groups)
        return self.partitioning.num_partitions

    # -- materialization (the "map side") ------------------------------------
    def _cache_key(self, device: bool) -> str:
        return f"shuffle:{id(self):x}:{'dev' if device else 'host'}"

    def _ensure_bounds(self, ctx, device: bool):
        """Range partitioning needs bounds from a sample of the keys."""
        p = self.partitioning
        if not isinstance(p, RangePartitioning) or p.bounds is not None:
            return
        # Sample: pull up to 64 rows per child partition on the host engine
        # (CPU-side sampling, like the reference).
        from spark_rapids_tpu.columnar.batch import sample_rows
        from spark_rapids_tpu.columnar.host import device_to_host
        samples: List[HostBatch] = []
        for cp in range(self.children[0].num_partitions(ctx)):
            it = (self.children[0].execute_device(ctx, cp) if device
                  else self.children[0].execute_host(ctx, cp))
            for b in it:
                if device:
                    # Sample on device; download 64 rows, not the batch.
                    hb = device_to_host(sample_rows(b, 64))
                else:
                    hb = b
                keycols = []
                from spark_rapids_tpu.exprs.base import as_host_column
                for o in p.orders:
                    keycols.append(as_host_column(o.child.eval_host(hb), hb))
                n = min(64, hb.num_rows)
                idx = np.linspace(0, max(hb.num_rows - 1, 0), n,
                                  dtype=np.int64) if n else \
                    np.zeros(0, np.int64)
                cols = [HostColumn(c.dtype, c.data[idx], c.validity[idx])
                        for c in keycols]
                samples.append(HostBatch(
                    tuple(f"k{i}" for i in range(len(cols))), cols))
                break   # one batch per partition is enough for bounds
        if not samples:
            p.bounds = HostBatch((), [])
            return
        merged_cols = []
        for ci in range(samples[0].num_columns):
            data = np.concatenate([s.columns[ci].data for s in samples])
            val = np.concatenate([s.columns[ci].validity for s in samples])
            merged_cols.append(HostColumn(samples[0].columns[ci].dtype,
                                          data, val))
        merged = HostBatch(samples[0].names, merged_cols)
        # Bounds are picked over the key columns themselves, so the sort
        # orders must reference them by ordinal.
        from spark_rapids_tpu.exprs.base import BoundReference
        from spark_rapids_tpu.ops.sort import SortOrder
        bound_orders = [
            SortOrder(BoundReference(i, o.child.data_type()),
                      o.ascending, o.nulls_first)
            for i, o in enumerate(p.orders)]
        # The bounds batch holds the key columns positionally; see
        # RangePartitioning._bound_words.
        p.bounds = RangePartitioning.compute_bounds(
            merged, bound_orders, p.num_partitions)

    def _partitioning_fp(self):
        """Structural cache key for this exchange's partitioning. Range
        partitionings fold their sampled bounds in — bounds are DATA, so
        two queries share a split kernel only when their bounds match."""
        from spark_rapids_tpu.ops import kernel_cache as kc
        fp = getattr(self, "_part_fp", None)
        if fp is None:
            fp = self._part_fp = kc.fingerprint(self.partitioning)
        return fp

    def _pids_counts_fn(self, metrics=None):
        """Jitted (pids, per-partition live counts) for one child batch,
        from the process-global kernel cache."""
        partitioning = self.partitioning
        n = partitioning.num_partitions

        def fn(b: DeviceBatch):
            pids = partitioning.partition_ids(b)
            live = b.row_mask()
            key = jnp.where(live, pids, n)
            counts = jax.ops.segment_sum(
                jnp.ones((b.capacity,), jnp.int32), key,
                num_segments=n + 1)[:n]
            return pids, counts
        if not partitioning.jittable:
            return fn
        from spark_rapids_tpu.ops import kernel_cache as kc
        return kc.lookup("exchange-pids", (self._partitioning_fp(),),
                         lambda: jax.jit(fn), metrics)

    def _split_fn(self, piece_cap: int, metrics=None):
        """Jitted split: ONE pid-stable sort + ONE packed gather, then a
        dynamic slice per piece — replaces the per-partition compaction
        storm (contiguousSplit done the TPU way: gather/scatter cost on
        this chip scales with row-operations, so moving all columns once
        beats moving each partition separately ~n-fold)."""
        partitioning = self.partitioning
        n = partitioning.num_partitions

        def fn(b: DeviceBatch, pids, offsets, counts):
            from spark_rapids_tpu.columnar.rowmove import gather_rows
            live = b.row_mask()
            skey = jnp.where(live, pids, n)
            perm = jnp.argsort(skey, stable=True)
            # Pad the gather so a slice at offset near the end never
            # clamps (dynamic_slice adjusts out-of-range starts).
            idx = jnp.concatenate(
                [perm.astype(jnp.int32),
                 jnp.zeros((piece_cap,), jnp.int32)])
            sorted_b = gather_rows(b, idx, b.live_count())
            pieces = []
            for p in range(n):
                pieces.append(_slice_rows(sorted_b, offsets[p],
                                          piece_cap, counts[p]))
            return pieces
        if not partitioning.jittable:
            return fn
        from spark_rapids_tpu.ops import kernel_cache as kc
        return kc.lookup("exchange-split",
                         (self._partitioning_fp(), piece_cap),
                         lambda: jax.jit(fn), metrics)

    def _open_session(self, ctx):
        """Open this exchange's transport session (parallel/transport/):
        the SPI decides where map-side shards live — catalog handles for
        ``inprocess``, spool files for ``hostfile``. The session is the
        durable stage output; it parks in ctx.cache so re-executions
        serve the committed materialization and ctx.close tears it
        down."""
        import os

        from spark_rapids_tpu.parallel import transport as T
        info = ctx.cache.get("cluster")
        if info is not None:
            # Cluster mode (parallel/cluster/): a dispatchable stage's
            # output lives at its cross-process tag on the query spool,
            # shared by every process of the query. Untagged exchanges
            # (session_for -> None) open their configured transport
            # exactly as before.
            sess = info.session_for(ctx, self)
            if sess is not None:
                return sess
        transport = T.materialization_transport(ctx.conf)
        return transport.open(
            ctx.conf, f"x{os.getpid():x}-{id(self):x}",
            self.partitioning.num_partitions, owner=id(self),
            catalog=ctx.catalog, metrics=T.metrics_entry(ctx))

    def _materialize_device(self, ctx):
        key = self._cache_key(True)
        if key in ctx.cache:
            return ctx.cache[key]
        from spark_rapids_tpu import monitoring
        info = ctx.cache.get("cluster")
        if info is not None and info.is_remote(self):
            # Another process of this query produced (or is assigned)
            # this stage: adopt its committed spool instead of running
            # the map side. The dispatch barrier (QueryRun.run) and the
            # coordinator's deps-done gating guarantee the manifest is
            # committed before any consumer lands here.
            with monitoring.span("exchange-adopt", "shuffle",
                                 args={"op": self.name,
                                       "stage": info.sid_of(self)}):
                sess = info.session_for(ctx, self)
                rows = type(info).adopt_manifest(
                    sess, self.partitioning.num_partitions)
                ctx.cache[key] = sess
                ctx.cache[key + ":rows"] = rows
                return sess
        with monitoring.span("exchange-materialize", "shuffle",
                             args={"op": self.name,
                                   "partitions":
                                   self.partitioning.num_partitions}):
            return self._materialize_device_traced(ctx, key)

    def _materialize_device_traced(self, ctx, key):
        from spark_rapids_tpu import monitoring
        self._ensure_bounds(ctx, device=True)
        n = self.partitioning.num_partitions
        sess = self._open_session(ctx)
        bucket_rows = [0] * n           # exact counts (AQE coalescing)
        from spark_rapids_tpu.columnar.batch import shrink_to_capacity
        pids_fn = self._pids_counts_fn(metrics=ctx.metrics_for(self))
        # Two-phase sizes-then-data (SURVEY §7): dispatch per-batch
        # partition-id counts, pull the whole window's counts in ONE
        # batched device_get (one host sync, not one per batch), then
        # split each batch with host-known piece
        # sizes. The window is bounded so pre-split batches never
        # accumulate unboundedly in un-spillable HBM.
        _WINDOW = 32

        def flush_window(window: List[DeviceBatch]):
            from spark_rapids_tpu import faults
            faults.fault_point("exchange.flush", owner=id(self))
            # What the exchange does itself, apart from pulling its child.
            with monitoring.span("exchange-flush", "shuffle",
                                 args={"batches": len(window)}):
                _flush_window(window)

        def _flush_window(window: List[DeviceBatch]):
            if n == 1:
                # Single destination: no pids, no sort, no slices — shrink
                # each batch to its live bucket (using hints when known)
                # and bucket it directly.
                from spark_rapids_tpu.columnar.batch import shrink_all
                with monitoring.op_span(self.name, "shrink-all",
                                        level=monitoring.LEVEL_KERNEL):
                    pieces, counts1 = shrink_all(window)
                for piece, cnt in zip(pieces, counts1):
                    if cnt == 0:
                        continue
                    bucket_rows[0] += cnt
                    piece.rows_hint = cnt
                    sess.write_shard(0, piece)
                return
            metas = [(b,) + tuple(pids_fn(b)) for b in window]
            pulled = jax.device_get([m[2] for m in metas])
            for (batch, pids, _), counts in zip(metas, pulled):
                counts = [int(c) for c in counts]
                total = sum(counts)
                if total == 0:
                    continue
                # Mostly-dead batches (selective filters, tiny partial
                # aggregates) shrink to their live bucket first so the
                # split's gather moves live rows, not capacity.
                small = bucket_capacity(max(total, 1))
                if small < batch.capacity:
                    batch = shrink_to_capacity(batch, small)
                    pids, _ = pids_fn(batch)
                piece_cap = bucket_capacity(max(max(counts), 1))
                offsets = np.concatenate(
                    [[0], np.cumsum(counts[:-1])]).astype(np.int32)
                pieces = self._split_fn(
                    piece_cap, metrics=ctx.metrics_for(self))(
                    batch, pids, jnp.asarray(offsets),
                    jnp.asarray(counts, jnp.int32))
                for p, piece in enumerate(pieces):
                    if counts[p] == 0:
                        continue
                    piece.rows_hint = counts[p]
                    bucket_rows[p] += counts[p]
                    # Shuffle output is durable (RapidsCachingWriter
                    # inserts into the device store; shuffle spills FIRST
                    # per SpillPriorities) — the transport session holds
                    # a handle (spillable catalog entry or spool file),
                    # not a pinned device batch.
                    sess.write_shard(p, piece)

        # The window is bounded by BYTES as well as count: pre-split
        # batches are pinned un-spillable HBM, so a window must never
        # hold more than a fraction of the device budget (out-of-core
        # sorts/aggregations stream through here at multiples of HBM).
        max_window_bytes = max(ctx.catalog.device_budget // 4, 1 << 20)
        window: List[DeviceBatch] = []
        window_bytes = 0
        # Map-side partition loop through the pipelined executor: the
        # child's host half (scan decode + wire encode) runs
        # prefetchPartitions ahead on host threads while THIS (single,
        # ordered) consumer uploads and splits — the overlap that makes
        # scans below an exchange pipeline (parallel/pipeline.py). The
        # serial pipeline is a no-op passthrough, streaming exactly as
        # before.
        from spark_rapids_tpu.parallel import pipeline as PL
        nchild = self.children[0].num_partitions(ctx)
        pipe = PL.open_pipeline(ctx, self.children[0], nchild)
        try:
            for cp in range(nchild):
                # Child pull through the recovery wrapper: an
                # OOM-exhausted child subtree degrades to the host engine
                # per operator instead of failing the exchange.
                for b in pipe.consume(
                        cp, lambda cp=cp:
                        self.children[0].execute_device_recovering(
                            ctx, cp)):
                    window.append(b)
                    window_bytes += b.device_size_bytes()
                    if len(window) >= _WINDOW or \
                            window_bytes >= max_window_bytes:
                        flush_window(window)
                        window = []
                        window_bytes = 0
            if window:
                flush_window(window)
        except BaseException:
            # Partial materialization must not leak catalog entries or
            # spool files: the planner's retry ladder (stage recompute /
            # transient retry on the same context) re-runs this
            # materialization from scratch, so whatever was written so
            # far is garbage.
            sess.abort()
            raise
        finally:
            pipe.close()
        sess.commit()
        monitoring.count("exchangeRows", sum(bucket_rows))
        ctx.cache[key] = sess
        ctx.cache[key + ":rows"] = bucket_rows
        return sess

    def _materialize_host(self, ctx) -> List[List[HostBatch]]:
        key = self._cache_key(False)
        if key in ctx.cache:
            return ctx.cache[key]
        self._ensure_bounds(ctx, device=False)
        n = self.partitioning.num_partitions
        buckets: List[List[HostBatch]] = [[] for _ in range(n)]
        for cp in range(self.children[0].num_partitions(ctx)):
            for hb in self.children[0].execute_host(ctx, cp):
                pids = self.partitioning.partition_ids_host(hb)
                for p, piece in enumerate(split_host_batch(hb, pids, n)):
                    buckets[p].append(piece)
        ctx.cache[key] = buckets
        return buckets

    # -- serving (the "reduce side") -----------------------------------------
    def execute_device(self, ctx, partition):
        # Buckets stay registered (not freed) until ctx.close(): a plan can
        # legitimately re-execute a partition (range-bounds sampling,
        # broadcast probe re-runs). Consumed buckets carry the lowest spill
        # priority, so they are the first evicted under pressure.
        #
        # Post-shuffle COALESCE (GpuCoalesceBatches after an exchange,
        # GpuCoalesceBatches.scala:643): a reduce partition receives one
        # piece per map batch — typically many small batches. Serving them
        # individually makes every downstream per-batch host sync (agg
        # shrink, join size read) pay a device round trip PER PIECE; concat
        # groups of pieces up to batchSizeRows into one batch first. The
        # grouping keys off host-known static capacities — zero syncs.
        from spark_rapids_tpu import config as C
        from spark_rapids_tpu.columnar.batch import jit_concat_batches
        from spark_rapids_tpu.memory.stores import PRIORITY_SHUFFLE_OUTPUT
        sess = self._materialize_device(ctx)
        # Serve toward the (possibly OOM-degraded) batch target: after a
        # shrink escalation, reduce-side concats re-dispatch smaller.
        from spark_rapids_tpu.memory.oom import effective_batch_target
        target = effective_batch_target(int(ctx.conf.get(C.BATCH_SIZE_ROWS)))
        group: List = []
        group_cap = 0

        def flush(sbs):
            """Returns (batch_to_yield, handles_to_release_after_consume).
            A concat produces a NEW batch, so the source handles release
            immediately (jax keeps their buffers alive for the in-flight
            concat); a passed-through single batch IS the catalog-resident
            batch and must stay ACTIVE until the consumer is done with it,
            or it becomes the top spill victim mid-use."""
            if len(sbs) == 1:
                return sbs[0].get(), sbs
            batches = [sb.get() for sb in sbs]
            cap = bucket_capacity(sum(b.capacity for b in batches))
            out = jit_concat_batches(batches, cap)
            # Pieces carry exact live counts from the split's sizes pull;
            # their sum lets the consumer (final aggregate, download) skip
            # its own device sync entirely.
            hints = [b.rows_hint for b in batches]
            if all(h is not None for h in hints):
                out.rows_hint = sum(hints)
            for sb in sbs:
                sb.release(PRIORITY_SHUFFLE_OUTPUT)
            return out, []

        def serve(sbs):
            from spark_rapids_tpu import faults, monitoring
            from spark_rapids_tpu.columnar.wire import WireCorruptionError
            faults.fault_point("exchange.serve", owner=id(self))
            try:
                with monitoring.span("exchange-serve", "shuffle",
                                     args={"partition": partition,
                                           "shards": len(sbs)}):
                    out, pending = flush(sbs)
            except WireCorruptionError as err:
                # A durable stage output failed its CRC even after the
                # re-read: the data at rest is gone. Tag the loss with
                # this exchange so lineage recovery recomputes just this
                # stage instead of failing the query.
                err.fault_owner = id(self)
                raise
            try:
                yield out
            finally:
                # Runs when the consumer resumes (or abandons) the stream,
                # so the served batch is never evictable while in use.
                for sb in pending:
                    sb.release(PRIORITY_SHUFFLE_OUTPUT)

        from spark_rapids_tpu import monitoring
        groups = self._groups(ctx)
        mine = groups[partition] if groups is not None else [partition]
        try:
            for b in mine:
              with monitoring.span("fetch-shards", "shuffle",
                                   level=monitoring.LEVEL_KERNEL,
                                   args={"bucket": b}):
                  fetched = sess.fetch_shards(b)
              for sb in fetched:
                if group and group_cap + sb.capacity > target:
                    yield from serve(group)
                    group, group_cap = [], 0
                group.append(sb)
                group_cap += sb.capacity
            if group:
                yield from serve(group)
                group = []
        finally:
            # Early generator close before serve() ran: release anything
            # still grouped so no batch stays pinned ACTIVE.
            for sb in group:
                sb.release(PRIORITY_SHUFFLE_OUTPUT)

    def execute_host(self, ctx, partition):
        buckets = self._materialize_host(ctx)
        yield from iter(buckets[partition])

    # -- runtime adaptive re-planning ----------------------------------------
    def observed_sizes(self, ctx) -> Tuple[int, int, int]:
        """Materialize (idempotent) and return ``(live bytes, footprint
        bytes, shards without a row count)`` of the map side, as the
        transport session observed them across all shards. Runtime
        re-planning (parallel/replan.py) demotes joins on the FIRST: the
        bytes of the live rows, estimated from each shard's ``rows_hint``
        (``DeviceBatch.live_size_bytes()``; a shard written here has one:
        ``_materialize_device`` pulls the pieces' counts before it
        writes). Not on the shards' footprint, as until PR 35: a piece
        pads to its capacity bucket, up to a third more, and a plan must
        not hang on padding (q3 at SF10: 53.96 MB of live rows against a
        threshold of 67.1, in shards whose footprint was over it). The
        third says how many device shards went into the first at their
        footprint for want of a count."""
        sess = self._materialize_device(ctx)
        return sess.live_bytes, sess.observed_bytes(), sess.uncounted_shards

    # -- pipelined execution -------------------------------------------------
    def stage_prematerialize(self, ctx) -> None:
        """Materialize this stage's durable output now (idempotent vs
        the context cache) — the hook parallel/pipeline.py uses to run
        independent sibling stages concurrently. A runtime re-plan that
        demoted this exchange's join to a broadcast skips the probe-side
        materialization entirely (parallel/replan.py flags it): shuffling
        a side the demoted join will stream unshuffled is pure waste."""
        if ctx.cache.get(f"replan-skip:{id(self):x}"):
            return
        if ctx.cache.get("engine") == "device":
            self._materialize_device(ctx)

    # -- lineage recovery ----------------------------------------------------
    def stage_invalidate(self, ctx) -> None:
        """Drop this exchange's durable stage output (parallel/stages.py
        boundary contract): the transport session releases every shard
        it holds — catalog registrations, spool files — and the next
        execution recomputes this stage from its parents' still-cached
        outputs. Applies identically to a lost REMOTE shard: the
        hostfile fetch raises owner-tagged, the planner lands here, and
        the recompute rewrites the spool."""
        dev_key = self._cache_key(True)
        sess = ctx.cache.pop(dev_key, None)
        ctx.cache.pop(dev_key + ":rows", None)
        ctx.cache.pop(self._cache_key(False), None)
        ctx.cache.pop(f"shuffle-groups:{id(self):x}", None)
        if sess is not None:
            sess.invalidate()


class BroadcastExchangeExec(Exec):
    """Collect the whole child into ONE batch replicated to every consumer
    (GpuBroadcastExchangeExec: collect-to-driver + re-upload becomes, on a
    pod, a one-time all-gather; single-host it is a concat + cache)."""

    def __init__(self, child: Exec):
        super().__init__(child)

    @property
    def schema(self) -> Schema:
        return self.children[0].schema

    def num_partitions(self, ctx) -> int:
        return 1

    def _cache_key(self, device: bool) -> str:
        return f"broadcast:{id(self):x}:{'dev' if device else 'host'}"

    def collect_single_device(self, ctx) -> DeviceBatch:
        # The merged single is a durable stage output: registered with
        # the buffer catalog (spillable under the memory ladder, CRC
        # framed once it reaches disk) instead of pinned raw in
        # ctx.cache, and re-acquired from whatever tier it sits on.
        from spark_rapids_tpu.memory.stores import (PRIORITY_BROADCAST,
                                                    SpillableBatch)
        key = self._cache_key(True)
        handle = ctx.cache.get(key)
        if handle is not None:
            batch = handle.get()
            handle.release(PRIORITY_BROADCAST)
            return batch
        # Cluster broadcast artifact cache (parallel/broadcast_cache.py):
        # another process of this query may have already built and
        # published this single — adopt it instead of re-collecting the
        # child. The fetched handle satisfies the same get/release
        # protocol as the SpillableBatch below. No-op outside cluster
        # mode.
        from spark_rapids_tpu.parallel import broadcast_cache as BC
        hit = BC.maybe_fetch(ctx, self)
        if hit is not None:
            ctx.cache[key] = hit[0]
            return hit[1]
        from spark_rapids_tpu import monitoring
        from spark_rapids_tpu.parallel import pipeline as PL
        nchild = self.children[0].num_partitions(ctx)
        pipe = PL.open_pipeline(ctx, self.children[0], nchild)
        batches = []
        try:
            with monitoring.span("broadcast-collect", "shuffle",
                                 args={"partitions": nchild}):
                for cp in range(nchild):
                    batches.extend(pipe.consume(
                        cp, lambda cp=cp:
                        self.children[0].execute_device_recovering(ctx,
                                                                   cp)))
        finally:
            pipe.close()
        if not batches:
            raise ValueError("broadcast of empty child needs a schema batch")
        # One batched sizes pull, then shrink members to live scale: the
        # broadcast build side's capacity bounds the build-side sort and
        # (on the slow path) probe expansion. SMALL batches skip the pull
        # entirely — a dimension table's shrink can't repay a ~100ms
        # round trip, and the join kernels handle selection vectors.
        from spark_rapids_tpu.columnar.batch import (MIN_SHRINK_BYTES,
                                                      shrink_all)
        if any(b.device_size_bytes() >= MIN_SHRINK_BYTES
               for b in batches):
            with monitoring.op_span(self.name, "shrink-all",
                                    level=monitoring.LEVEL_KERNEL):
                batches, _ = shrink_all(batches)
        total = sum(b.capacity for b in batches)
        single = batches[0] if len(batches) == 1 else \
            concat_batches(batches, bucket_capacity(total))
        ctx.cache[key] = SpillableBatch(ctx.catalog, single,
                                        PRIORITY_BROADCAST)
        # Publish the freshly-built single for the query's OTHER
        # processes (best-effort; no-op outside cluster mode).
        BC.maybe_publish(ctx, self, single)
        return single

    def collect_single_host(self, ctx) -> HostBatch:
        key = self._cache_key(False)
        if key in ctx.cache:
            return ctx.cache[key]
        hbs = []
        for cp in range(self.children[0].num_partitions(ctx)):
            hbs.extend(self.children[0].execute_host(ctx, cp))
        assert hbs, "broadcast of empty child"
        from spark_rapids_tpu.columnar.host import concat_host_batches
        merged = concat_host_batches(hbs)
        ctx.cache[key] = merged
        # Host path while a device copy exists = the host-fallback rung
        # degraded an operator subtree over this broadcast. The degraded
        # consumer reads the host copy; keeping the device single too
        # would pin BOTH for the query's lifetime, so free the device
        # side (a later device consumer rebuilds it).
        dev = ctx.cache.pop(self._cache_key(True), None)
        if dev is not None:
            dev.close()
        return merged

    def stage_prematerialize(self, ctx) -> None:
        """Build the broadcast single now (idempotent) so sibling stages
        can materialize concurrently (parallel/pipeline.py)."""
        if ctx.cache.get("engine") == "device":
            self.collect_single_device(ctx)

    def stage_invalidate(self, ctx) -> None:
        """Drop the broadcast's durable output (stage boundary contract,
        parallel/stages.py)."""
        dev = ctx.cache.pop(self._cache_key(True), None)
        ctx.cache.pop(self._cache_key(False), None)
        if dev is not None:
            dev.close()

    def execute_device(self, ctx, partition):
        yield self.collect_single_device(ctx)

    def execute_host(self, ctx, partition):
        yield self.collect_single_host(ctx)
