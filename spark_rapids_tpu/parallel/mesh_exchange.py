"""Planner-integrated collective shuffle: ShuffleExchangeExec lowered onto
a jax.sharding.Mesh.

With ``spark.rapids.sql.shuffle.transport=mesh`` the planner emits
``MeshExchangeExec`` for hash shuffles instead of the single-process
materialized exchange: child partitions become one uniform-shape shard per
mesh device, ONE jitted ``shard_map`` program runs the split +
``jax.lax.all_to_all`` + concat (the ICI collective replacing the
reference's UCX pull protocol — SURVEY.md §2.6 TPU mapping,
GpuShuffleExchangeExec.scala:69,145), and each output partition serves its
device's post-exchange shard to the normal per-partition operator stream
above. Operators (aggregate final stage, shuffled join) compose unchanged.

Shards go out and come back as whole trees: ``mesh.shard_batches`` hands
the n shards to their devices in one batched ``device_put`` and
``_addressable_parts`` lands them in another; the programs either phase
dispatches follow the shards, never the leaves.
Today every post-exchange shard is moved to ``jax.devices()[0]``
(``_addressable_parts``) and every operator above an exchange runs there:
the other chips take part in the collectives alone (``meshLandedBytes``
counts what lands where; ROADMAP B1 is the change that leaves a partition
where its shard lies).

A single chip degenerates to n=1; the tests run the real collective path
on the virtual CPU devices they ask for (``tests/conftest.py``).

What one exchange costs is visible as spans of the category
``mesh-exchange`` (``shard``, ``pids``, ``counts``, ``collective``,
``land``, ``unfold``; docs/observability.md) and as the counters
``meshLiveBytes`` / ``meshWireBytes`` / ``meshLandedBytes.dev<i>`` and
``meshHandoutPuts`` / ``meshLandPuts``, on the operator's ``Metrics`` and
process-wide (:func:`counters`).
"""

from __future__ import annotations

import collections
import functools
import threading
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from spark_rapids_tpu import monitoring
from spark_rapids_tpu.columnar.batch import (
    DeviceBatch, DeviceColumn, bucket_capacity, string_repad)
from spark_rapids_tpu.ops.base import Exec, ExecContext, Schema, timed
from spark_rapids_tpu.parallel import mesh as M
from spark_rapids_tpu.shims import (shard_map, tree_flatten,
                                    tree_map, tree_unflatten)
from spark_rapids_tpu.parallel.partitioning import Partitioning


def mesh_for(ctx: ExecContext):
    """One mesh per query context (all visible devices)."""
    m = ctx.cache.get("mesh:singleton")
    if m is None:
        m = M.make_mesh()
        ctx.cache["mesh:singleton"] = m
    return m


def mesh_size() -> int:
    return len(jax.devices())


# Shards below this many rows skip the two-phase counts exchange: its
# blocking host pull is taken to cost more than the worst-case padding it
# would avoid. The threshold was sized when a pull cost ~70 ms; it is
# ~1 ms on an attached chip and has not been re-measured (ROADMAP A3).
# Module-level so tests can lower it.
TWO_PHASE_MIN_SHARD_ROWS = 1 << 18


# -- counters -----------------------------------------------------------------
# Process-wide totals beside each operator's ``Metrics``
# (benchmark/metrics/mesh_exchange_padding_pct.py reads the ratio of two).
# ``meshLiveBytes`` and ``meshWireBytes`` count an exchange together, once
# its live rows are known on the host: at once where the two-phase path
# pulled the counts matrix anyway, else when a counter is next read. Until
# then they are the landed shards' ``num_rows``, device scalars in
# ``_UNREAD``: no exchange makes a blocking read for a counter's sake. An
# exchange that falls off the end of ``_UNREAD`` unread is in neither
# total, so their ratio stays a ratio.
_COUNTER_LOCK = threading.Lock()
_COUNTERS: Dict[str, int] = {}
_UNREAD: collections.deque = collections.deque(maxlen=4096)


def _add_counters(counts: Dict[str, int]) -> None:
    with _COUNTER_LOCK:
        for k, v in counts.items():
            _COUNTERS[k] = _COUNTERS.get(k, 0) + v


def counters() -> Dict[str, int]:
    """``meshExchanges``, ``meshHandoutPuts``, ``meshLandPuts``,
    ``meshLiveBytes``, ``meshWireBytes`` and ``meshLandedBytes.dev<id>``
    of this process. Reads what the exchanges since the last call left on
    the device."""
    while _UNREAD:
        try:
            read = _UNREAD.popleft()
        except IndexError:          # another reader took it
            break
        _add_counters(read())
    with _COUNTER_LOCK:
        return dict(_COUNTERS)


def reset_counters() -> None:
    _UNREAD.clear()
    with _COUNTER_LOCK:
        _COUNTERS.clear()


def _row_width(shard: DeviceBatch) -> int:
    """Bytes one row takes in the collective's arrays: every column's
    data, validity and (strings) lengths, as decoded on the device."""
    return sum(x.dtype.itemsize * int(np.prod(x.shape[1:], dtype=np.int64))
               for x in tree_flatten(shard.columns)[0])


def _tally(rows, landed_on: List[int], row_width: int,
           wire_bytes: int) -> Dict[str, int]:
    """One exchange's counters from the rows of each landed shard and
    the device it lies on."""
    out = {"meshLiveBytes": 0, "meshWireBytes": wire_bytes}
    for r, d in zip(rows, landed_on):
        out["meshLiveBytes"] += int(r) * row_width
        key = f"meshLandedBytes.dev{d}"
        out[key] = out.get(key, 0) + int(r) * row_width
    return out


class _UnreadExchange:
    """An exchange whose live rows are still device scalars (no counts
    matrix was pulled): read once, under ``<Op>:landed-rows``, when the
    operator's metrics or the process's counters are first read, and the
    scalars are let go."""

    __slots__ = ("_rows", "_tally", "_op", "_counts")

    def __init__(self, rows, tally, op: str):
        self._rows, self._tally, self._op = rows, tally, op
        self._counts: Optional[Dict[str, int]] = None

    def __call__(self) -> Dict[str, int]:
        if self._counts is None:
            with monitoring.op_span(self._op, "landed-rows"):
                self._counts = self._tally(jax.device_get(self._rows))
            self._rows = None
        return self._counts

    def settle(self, metrics) -> None:
        for k, v in self().items():
            metrics.add(k, v)


def _count_exchange(m, landed_rows, landed_on: List[int], row_width: int,
                    wire_bytes: int) -> None:
    """One exchange into the operator's and the process's counters.
    ``landed_rows[i]`` are the rows of the shard that landed on device
    ``landed_on[i]``: ints where the counts matrix was pulled, else
    device scalars, read when a counter is next read and not before."""
    tally = functools.partial(_tally, landed_on=landed_on,
                              row_width=row_width, wire_bytes=wire_bytes)
    _add_counters({"meshExchanges": 1})
    if all(isinstance(r, int) for r in landed_rows):
        counts = tally(landed_rows)
        for k, v in counts.items():
            m.add(k, v)
        _add_counters(counts)
        return
    unread = _UnreadExchange(landed_rows, tally, m.owner or "MeshExchangeExec")
    m.defer(unread.settle)
    _UNREAD.append(unread)


def _count_put(m, name: str) -> None:
    """One batched transfer of a phase (``meshHandoutPuts``: the
    ``device_put`` of ``shard_batches``; ``meshLandPuts``: that of
    ``_addressable_parts``), on the operator and process-wide. Each phase
    issues one an exchange, whatever its leaves: the ratio of either to
    ``meshExchanges`` is 1 where the whole-tree hand-out and landing ran."""
    m.add(name, 1)
    _add_counters({name: 1})


def _phase(name: str):
    """A span of the category ``mesh-exchange`` (profiler annotation
    ``mesh-exchange:<name>``). The phases are never nested in one another
    and never enclose the pull of the child, so the category's sum is a
    time. It is a host clock over asynchronous dispatch: a phase that
    blocks (``counts``) also holds the wait for the child's device work."""
    return monitoring.span(name, "mesh-exchange")


def _uniform_shards(batches_per_dev: List[List[DeviceBatch]],
                    schema: Schema) -> List[DeviceBatch]:
    """Coalesce each device's batches and pad all shards to one common
    capacity + per-column string width (shard_map needs uniform shapes)."""
    from spark_rapids_tpu.ops import kernel_cache as kc
    from spark_rapids_tpu.ops.sort import coalesce_to_single_batch
    from spark_rapids_tpu.columnar.rowmove import compact_batch
    shards = []
    for blist in batches_per_dev:
        if blist:
            single = coalesce_to_single_batch(blist)
            if single.sel is not None:
                # A lone filtered batch passes through coalesce with its
                # selection vector; shard_map shards are sel-less, so
                # materialize the live rows first.
                single = kc.lookup("mesh-compact", (), lambda: jax.jit(
                    compact_batch))(single)
            shards.append(single)
        else:
            shards.append(None)
    caps = [s.capacity for s in shards if s is not None]
    cap = bucket_capacity(max(caps)) if caps else 8
    widths = []
    for ci, (_, t) in enumerate(schema):
        if t.is_string:
            ws = [s.columns[ci].string_width
                  for s in shards if s is not None]
            widths.append(max(ws) if ws else 8)
        else:
            widths.append(None)
    widths = tuple(widths)
    out = []
    for s in shards:
        if s is None:
            out.append(_empty_shard(schema, cap, widths))
        elif s.capacity != cap or any(
                w is not None and c.string_width != w
                for c, w in zip(s.columns, widths)):
            out.append(_fit_shard(s, cap, widths))
        else:
            out.append(s)
    return out


def _empty_shard(schema: Schema, capacity: int, widths) -> DeviceBatch:
    """A device's shard when no child partition was dealt to it: every
    column null, no row; ONE cached program, not a ``zeros`` per leaf."""
    from spark_rapids_tpu.ops import kernel_cache as kc

    def empty():
        return DeviceBatch(tuple(
            DeviceColumn.full_null(t, capacity, w or 8)
            for (_, t), w in zip(schema, widths)),
            jnp.asarray(0, jnp.int32))

    return kc.lookup("mesh-empty", (kc.schema_fingerprint(schema), capacity,
                                    widths), lambda: jax.jit(empty))()


def _fit_shard(shard: DeviceBatch, capacity: int, widths) -> DeviceBatch:
    """A dense shard at the exchange's common ``capacity`` and string
    ``widths`` (``_uniform_shards``): ONE program per capacity and
    widths. Run op by op, the re-pad of each string column and the pack,
    gather and unpack of columnar/rowmove.py are some forty one-op
    programs, each compiled anew in every process and dispatched one by
    one in every query."""
    from spark_rapids_tpu.ops import kernel_cache as kc

    def fit(b):
        b = DeviceBatch(tuple(c if w is None else string_repad(c, w)
                              for c, w in zip(b.columns, widths)),
                        b.num_rows)
        if b.capacity == capacity:
            return b
        return b.gather(jnp.arange(capacity, dtype=jnp.int32), b.num_rows)

    return kc.lookup("mesh-fit", (capacity, widths),
                     lambda: jax.jit(fit))(shard)


def _drop_lead(offsets):
    """The landing's one program: row ``offsets[i][j]`` of leaf j of part
    i, that is its leading axis dropped (0 for a leaf sharded one row a
    device; i for a replicated leaf, whose every shard holds all rows)."""
    def land(parts):
        return [[x[o] for x, o in zip(p, offs)]
                for p, offs in zip(parts, offsets)]
    return jax.jit(land)


def _addressable_parts(out, n: int):
    """Device i's post-exchange shard as an ordinary per-device batch.

    Takes each leaf's shards from ``addressable_shards`` as they are
    (device-local data, leading size 1, no indexing) instead of ``x[i]``
    gathers on the global sharded array — a cross-device lazy gather that
    XLA re-dispatches whenever a consumer (including the range-bounds
    sampling pass re-executing this tree) touches it, and the trigger of
    the r4 SIGABRT inside apply_primitive.

    The downstream operator stream is single-process and mixes partitions
    freely (concat across buckets), so every shard is ``device_put`` onto
    the default device — an explicit transfer now, not a lazy gather
    later: ONE batched transfer for every leaf of every part, then ONE
    cached program drops the leading axis of them all."""
    from spark_rapids_tpu.ops import kernel_cache as kc
    leaves, treedef = tree_flatten(out)
    per_dev = [[] for _ in range(n)]
    offsets = [[] for _ in range(n)]
    for leaf in leaves:
        shards = leaf.addressable_shards
        for i in range(n):
            # the shard whose leading slice holds row i (every shard of a
            # replicated leaf does: its row i is at offset i)
            s = next(s for s in shards
                     if (s.index[0].start or 0) <= i
                     < (s.index[0].stop or leaf.shape[0]))
            per_dev[i].append(s.data)
            offsets[i].append(i - (s.index[0].start or 0))
    per_dev = jax.device_put(per_dev, jax.devices()[0])
    offsets = tuple(map(tuple, offsets))
    land = kc.lookup("mesh-land", (offsets,), lambda: _drop_lead(offsets))
    return [tree_unflatten(treedef, ls) for ls in land(per_dev)]


class MeshExchangeExec(Exec):
    """Hash shuffle over the device mesh as one collective program."""

    def __init__(self, child: Exec, partitioning: Partitioning):
        super().__init__(child)
        self.partitioning = partitioning

    def _mesh_key(self, mesh):
        """Cache key part identifying this exchange's collective shape:
        the partitioning structure + the mesh's device layout. Collective
        programs from the process-global kernel cache are shared across
        exec instances (every fresh query otherwise re-traces the
        shard_map programs)."""
        from spark_rapids_tpu.ops import kernel_cache as kc
        fp = getattr(self, "_part_fp", None)
        if fp is None:
            fp = self._part_fp = kc.fingerprint(self.partitioning)
        devs = tuple(int(d.id) for d in mesh.devices.flat)
        return (fp, tuple(mesh.axis_names), devs)

    @property
    def schema(self) -> Schema:
        return self.children[0].schema

    def num_partitions(self, ctx) -> int:
        return self.partitioning.num_partitions

    def _pids_step(self, mesh):
        """Per-shard LOGICAL partition ids, computed ONCE and fed to
        both the counts and data collectives (murmur/bound-compare over
        every row is not free twice). The collectives fold logical ids
        onto device ids themselves (``pid // fold``)."""
        part = self.partitioning

        def local(stacked):
            b = tree_map(lambda x: x[0], stacked)
            return part.partition_ids(b)[None]

        return jax.jit(shard_map(local, mesh, in_specs=(P(M.DATA_AXIS),),
                                 out_specs=P(M.DATA_AXIS)))

    def _build_step(self, mesh, n: int, fold: int, piece_capacity=None):
        def local(stacked, pids):
            b = tree_map(lambda x: x[0], stacked)
            out = M.all_to_all_exchange(b, pids[0] // fold, n,
                                        piece_capacity=piece_capacity)
            return tree_map(lambda x: x[None], out)

        return jax.jit(shard_map(
            local, mesh, in_specs=(P(M.DATA_AXIS), P(M.DATA_AXIS)),
            out_specs=P(M.DATA_AXIS)))

    def _counts_step(self, mesh, n: int, fold: int):
        def local(stacked, pids):
            b = tree_map(lambda x: x[0], stacked)
            return M.exchange_counts(b, pids[0] // fold, n)[None]

        return jax.jit(shard_map(
            local, mesh, in_specs=(P(M.DATA_AXIS), P(M.DATA_AXIS)),
            out_specs=P(M.DATA_AXIS)))

    def _fallback(self):
        """Single-process materialized exchange over the same child and
        partitioning — the demotion target when the mesh collective
        fails. Built lazily, once per exec instance, so its per-context
        materialization caches key stably across retries."""
        fb = getattr(self, "_fallback_exec", None)
        if fb is None:
            from spark_rapids_tpu.parallel.exchange import \
                ShuffleExchangeExec
            fb = self._fallback_exec = ShuffleExchangeExec(
                self.children[0], self.partitioning)
        return fb

    def _degrade(self, ctx, err) -> None:
        """Mesh degrade: the collective failed, so demote THIS QUERY's
        mesh exchanges to the single-process ShuffleExchangeExec path
        instead of killing the query. The flag is context-scoped — every
        other MeshExchangeExec in the plan skips its collective too (a
        sick interconnect rarely fails just one exchange)."""
        import logging
        from spark_rapids_tpu import faults
        logging.getLogger("spark_rapids_tpu").warning(
            "mesh collective failed in %s; degrading this query's "
            "exchanges to the single-process shuffle path: %s",
            self.name, err)
        faults.record("meshDegrades")
        ctx.metrics_for(self).add("meshDegrades", 1)
        ctx.cache["mesh.degraded"] = True

    def _materialize(self, ctx):
        """Run the collective and register each LOGICAL partition's
        post-exchange shard as a durable stage output through the mesh
        transport session (parallel/transport/mesh.py — spillable
        catalog handles). Returns None after a graceful degrade — the
        caller serves from the single-process fallback exchange
        instead.

        Partition count != mesh size no longer degrades: logical
        partitions FOLD onto devices (``device = pid // ceil(np/n)``,
        counter ``meshPartitionFolds``) and each device's received
        shard splits back into its logical partitions after the
        collective, so co-partitioned consumers never see mesh
        geometry. ``meshCollectiveSkipped`` now fires only for
        genuinely unsupported shapes (a non-jittable partitioning —
        nothing the planner emits today)."""
        key = f"meshx:{id(self):x}"
        if key in ctx.cache:
            return ctx.cache[key]
        if ctx.cache.get(f"meshx-skip:{id(self):x}"):
            return None         # unsupported shape already diagnosed
        m = ctx.metrics_for(self)
        mesh = mesh_for(ctx)
        n = mesh.devices.size
        np_parts = self.partitioning.num_partitions
        if np_parts < 1 or not getattr(self.partitioning, "jittable",
                                       False):
            import logging
            from spark_rapids_tpu import faults
            logging.getLogger("spark_rapids_tpu").warning(
                "mesh collective skipped in %s: partitioning %r is not "
                "collective-capable; serving this exchange from the "
                "single-process shuffle path", self.name,
                type(self.partitioning).__name__)
            faults.record("meshCollectiveSkipped")
            m.add("meshCollectiveSkipped", 1)
            ctx.cache[f"meshx-skip:{id(self):x}"] = True
            return None
        fold = -(-np_parts // n)        # ceil: k logical pids per device
        if fold > 1 or np_parts != n:
            from spark_rapids_tpu import faults
            faults.record("meshPartitionFolds")
            m.add("meshPartitionFolds", 1)
        # Deal child partitions onto devices round-robin.
        per_dev: List[List[DeviceBatch]] = [[] for _ in range(n)]
        child = self.children[0]
        for cp in range(child.num_partitions(ctx)):
            for batch in child.execute_device_recovering(ctx, cp):
                per_dev[cp % n].append(batch)
        from spark_rapids_tpu import config as C
        with timed(m, "shuffleTime"):
            try:
                from spark_rapids_tpu import faults
                faults.fault_point("mesh.exchange", owner=id(self))
                with _phase("shard"):
                    shards = _uniform_shards(per_dev, self.schema)
                    stacked = M.shard_batches(mesh, shards)
                _count_put(m, "meshHandoutPuts")
                # Two-phase sizes-then-data (SURVEY §7 hard part 6):
                # exchange per-destination COUNTS first (a (n,n) int32
                # collective + one host pull), size the data collective's
                # static piece capacity to the observed max instead of
                # the worst case — the default padding is an n-fold wire
                # inflation at scale. n == 1 skips the phase: the
                # collective moves nothing, so the counts sync could
                # only cost.
                from spark_rapids_tpu.ops import kernel_cache as kc
                mkey = self._mesh_key(mesh)
                with _phase("pids"):
                    pids_fn = kc.lookup("mesh-pids", mkey,
                                        lambda: self._pids_step(mesh), m)
                    pids = pids_fn(stacked)
                piece_cap = landed_rows = None
                if n > 1 and shards[0].capacity >= \
                        TWO_PHASE_MIN_SHARD_ROWS:
                    with _phase("counts"):
                        counts_fn = kc.lookup(
                            "mesh-counts", mkey + (fold,),
                            lambda: self._counts_step(mesh, n, fold), m)
                        # row d: what device d receives from each peer
                        counts = np.asarray(counts_fn(stacked, pids))
                    landed_rows = counts.sum(axis=1).tolist()
                    piece_cap = bucket_capacity(max(int(counts.max()), 1))
                    if piece_cap >= shards[0].capacity:
                        piece_cap = None  # padding wouldn't shrink
                with _phase("collective"):
                    step = kc.lookup(
                        "mesh-exchange", mkey + (fold, piece_cap),
                        lambda: self._build_step(mesh, n, fold,
                                                 piece_capacity=piece_cap),
                        m)
                    out = step(stacked, pids)
                # Where the collective left its output: one shard per
                # mesh device — before _addressable_parts moves them all
                # to device 0 for the single-process operator stream
                # above. chip_smoke.py --mesh holds this to the mesh
                # size.
                m.add("meshExchanges", 1)
                m.add("meshShardDevices", len(
                    {s.device for s in
                     tree_flatten(out)[0][0].addressable_shards}))
                with _phase("land"):
                    parts = _addressable_parts(out, n)
                _count_put(m, "meshLandPuts")
                if landed_rows is None:     # no counts pulled: deferred
                    landed_rows = [p.num_rows for p in parts]
                row_width = _row_width(shards[0])
                _count_exchange(
                    m, landed_rows,
                    [next(iter(p.num_rows.devices())).id for p in parts],
                    row_width,
                    n * n * (piece_cap or shards[0].capacity) * row_width)
            except Exception as err:
                if not bool(ctx.conf.get(C.MESH_DEGRADE_ENABLED)):
                    raise
                self._degrade(ctx, err)
                return None
        # Durable stage outputs through the transport SPI: each logical
        # partition's shard registers with the buffer catalog (bounded
        # by the memory ladder; CRC-framed once spilled to disk)
        # instead of pinning raw HBM in ctx.cache.
        from spark_rapids_tpu.parallel import transport as T
        sess = T.get_transport("mesh").open(
            ctx.conf, f"meshx-{id(self):x}", np_parts, owner=id(self),
            catalog=ctx.catalog, metrics=T.metrics_entry(ctx))
        if fold == 1 and np_parts <= n:
            for p in range(np_parts):
                sess.write_shard(p, parts[p])
        else:
            # Unfold: split each device's received shard back into its
            # logical partitions (the pids recompute is one murmur pass
            # over the received rows — received shards are dense, so
            # this is row-proportional, not capacity-proportional).
            with _phase("unfold"):
                for d in range(n):
                    lo = d * fold
                    cnt = min(np_parts - lo, fold)
                    if cnt <= 0:
                        continue
                    shard = parts[d]
                    shard_pids = self.partitioning.partition_ids(shard)
                    live = shard.row_mask()
                    for j in range(cnt):
                        keep = (shard_pids == lo + j) & live
                        sess.write_shard(lo + j, shard.compact(keep))
        sess.commit()
        ctx.cache[key] = sess
        return sess

    def execute_device(self, ctx, partition):
        sess = None
        if not ctx.cache.get("mesh.degraded"):
            sess = self._materialize(ctx)
        if sess is None:          # degraded (now or by a prior exchange)
            yield from self._fallback().execute_device(ctx, partition)
            return
        from spark_rapids_tpu.memory.stores import \
            PRIORITY_SHUFFLE_OUTPUT
        for h in sess.fetch_shards(partition):
            batch = h.get()
            try:
                yield batch
            finally:
                h.release(PRIORITY_SHUFFLE_OUTPUT)

    # -- lineage recovery ----------------------------------------------------
    def stage_invalidate(self, ctx) -> None:
        """Drop this exchange's durable shards (stage boundary contract,
        parallel/stages.py)."""
        sess = ctx.cache.pop(f"meshx:{id(self):x}", None)
        ctx.cache.pop(f"meshx-host:{id(self):x}", None)
        ctx.cache.pop(f"meshx-skip:{id(self):x}", None)
        if sess is not None:
            sess.invalidate()
        fb = getattr(self, "_fallback_exec", None)
        if fb is not None:
            fb.stage_invalidate(ctx)

    def execute_host(self, ctx, partition):
        # Host engine has no mesh; fall back to the materialized exchange
        # semantics (same results, used only by the oracle).
        from spark_rapids_tpu.parallel.partitioning import split_host_batch
        key = f"meshx-host:{id(self):x}"
        if key not in ctx.cache:
            n = self.partitioning.num_partitions
            buckets = [[] for _ in range(n)]
            child = self.children[0]
            for cp in range(child.num_partitions(ctx)):
                for hb in child.execute_host(ctx, cp):
                    pids = self.partitioning.partition_ids_host(hb)
                    for p, piece in enumerate(
                            split_host_batch(hb, pids, n)):
                        buckets[p].append(piece)
            ctx.cache[key] = buckets
        yield from iter(ctx.cache[key][partition])

