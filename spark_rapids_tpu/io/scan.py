"""File scans: parquet / ORC / CSV (ref: GpuParquetScan.scala:84,
GpuOrcScan.scala, GpuBatchScanExec.scala CSV path).

Reader strategies mirror RapidsConf's
``spark.rapids.sql.format.parquet.reader.type`` (RapidsConf.scala:510):
- PERFILE: open and decode one file at a time, upload per batch.
- MULTITHREADED: a host thread pool prefetches+decodes files in the
  background while the device consumes earlier ones — the
  MultiFileCloudParquetPartitionReader overlap (GpuParquetScan.scala:1144).
- COALESCING: decode several units and concatenate their rows into fewer,
  larger device batches (MultiFileParquetPartitionReader:823's
  stitch-row-groups idea at the arrow level — small row groups from MANY
  files merge into one upload).
- AUTO: MULTITHREADED (the cloud default heuristic).

Partitioning is at **scan-unit** granularity: a unit is one parquet row
group / one ORC stripe / one CSV file (the footer parse that enumerates
them is CPU-side, exactly the reference's split — GpuParquetScan.scala:823
``populateCurrentBlockChunk``). Units are dealt round-robin over N
partitions, so one big parquet file parallelizes across partitions
instead of becoming a single giant host decode.

Predicate pushdown: pushed conjuncts (plan/pruning.pushdown_filters) are
checked against per-row-group min/max/null statistics; units whose stats
prove no row can match are skipped without reading data bytes
(GpuParquetScan filter pushdown / OrcFilters.scala analog).
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import os
import threading
from collections import OrderedDict
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from spark_rapids_tpu import config as C
from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.host import HostBatch, host_to_device
from spark_rapids_tpu.ops.base import Exec, ExecContext, LeafExec, Schema, \
    record_batch, timed
from spark_rapids_tpu.io.arrow_convert import (
    arrow_to_host_batch, schema_from_arrow)

import pyarrow as pa
import pyarrow.csv as pacsv
import pyarrow.orc as paorc
import pyarrow.parquet as papq


def infer_schema(fmt: str, paths: Sequence[str], options: Dict) -> Schema:
    """Footer/header-only schema inference (CPU-side footer parse, the
    GpuParquetScan footer-on-CPU half)."""
    path = paths[0]
    if fmt == "parquet":
        return schema_from_arrow(papq.ParquetFile(path).schema_arrow)
    if fmt == "orc":
        return schema_from_arrow(paorc.ORCFile(path).schema)
    if fmt == "csv":
        # Stream only the first block to infer types (no full-file parse).
        read_opts = _csv_read_options(options, sample=True)
        with pacsv.open_csv(path, **read_opts) as reader:
            return schema_from_arrow(reader.schema)
    raise ValueError(f"unknown format {fmt}")


def _csv_read_options(options: Dict, sample: bool = False):
    kwargs = {}
    parse = pacsv.ParseOptions(
        delimiter=options.get("sep", options.get("delimiter", ",")))
    has_header = str(options.get("header", "true")).lower() in (
        "true", "1", "yes")
    read_kwargs = {"autogenerate_column_names": not has_header}
    if sample:
        read_kwargs["block_size"] = 1 << 20   # schema from first 1MB only
    kwargs["parse_options"] = parse
    kwargs["read_options"] = pacsv.ReadOptions(**read_kwargs)
    return kwargs


@dataclasses.dataclass(frozen=True)
class ScanUnit:
    """One independently-readable slice of a file: a parquet row group,
    an ORC stripe, or a whole CSV file (``index is None``)."""

    path: str
    index: Optional[int]        # row group / stripe ordinal
    rows: int                   # 0 = unknown (csv)


# (path, mtime, size) -> parquet FileMetaData; footer parses are cheap but
# repeated across planning + N partitions, so memoize. Bounded: inserting a
# new entry evicts stale entries for the same path (rewritten files), and
# the whole cache is FIFO-capped so long sessions don't leak FileMetaData.
# Locked: pipeline prefetch threads probe partitions concurrently.
_PQ_META_CACHE: Dict[Tuple[str, float, int], Any] = {}
_PQ_META_CACHE_MAX = 1024
_PQ_META_LOCK = threading.Lock()


def _parquet_metadata(path: str):
    st = os.stat(path)
    key = (path, st.st_mtime, st.st_size)
    with _PQ_META_LOCK:
        md = _PQ_META_CACHE.get(key)
    if md is None:
        md = papq.ParquetFile(path).metadata
        with _PQ_META_LOCK:
            for stale in [k for k in _PQ_META_CACHE if k[0] == path]:
                del _PQ_META_CACHE[stale]
            while len(_PQ_META_CACHE) >= _PQ_META_CACHE_MAX:
                _PQ_META_CACHE.pop(next(iter(_PQ_META_CACHE)))
            _PQ_META_CACHE[key] = md
    return md


def enumerate_units(fmt: str, paths: Sequence[str]) -> List[ScanUnit]:
    """CPU-side footer/tail parse producing the scan's split units
    (GpuParquetScan.scala:823 block enumeration analog)."""
    units: List[ScanUnit] = []
    for path in paths:
        if fmt == "parquet":
            md = _parquet_metadata(path)
            for rg in range(md.num_row_groups):
                units.append(ScanUnit(path, rg, md.row_group(rg).num_rows))
        elif fmt == "orc":
            f = paorc.ORCFile(path)
            for si in range(f.nstripes):
                units.append(ScanUnit(path, si, 0))
        else:
            units.append(ScanUnit(path, None, 0))
    return units


# ORC stripe stats index (OrcFilters.scala:206 pushdown analog): pyarrow
# exposes no ORC column statistics, so the engine builds its own per-
# stripe min/max/null index on FIRST contact with a stripe (one decode of
# the predicate columns) and prunes every later scan from the cache.
# (stripe_key) -> {col: (min, max, null_count, rows)}. A true LRU:
# hits move-to-end, and eviction happens only when a genuinely NEW key
# is inserted at capacity — warm stripes survive a full cache, instead
# of FIFO-evicting the entries the workload keeps probing.
_ORC_STATS_CACHE: "OrderedDict[Tuple, Dict[str, tuple]]" = OrderedDict()
_ORC_STATS_CACHE_MAX = 4096
_ORC_STATS_LOCK = threading.Lock()


class _Stat:
    """Duck-typed stand-in for a parquet ColumnChunk statistics object."""

    def __init__(self, mn, mx, null_count):
        self.min, self.max = mn, mx
        self.null_count = null_count
        self.has_min_max = mn is not None


def _orc_stripe_stats(unit: ScanUnit, names: Sequence[str]
                      ) -> Tuple[Dict[str, "_Stat"], int]:
    """(per-column stats, stripe row count). Columns missing from the
    file cache a no-stats sentinel so they are never re-probed.
    Serialized by a lock: pipeline prefetch threads prune partitions
    concurrently and an OrderedDict must never interleave mutations."""
    st = os.stat(unit.path)
    key = (unit.path, st.st_mtime, st.st_size, unit.index)
    with _ORC_STATS_LOCK:
        cached = _ORC_STATS_CACHE.get(key)
        if cached is not None:
            _ORC_STATS_CACHE.move_to_end(key)
            cached = dict(cached)
    need = [n for n in names
            if cached is None or n not in cached]
    if need:
        f = paorc.ORCFile(unit.path)
        have = set(f.schema.names)
        cols = [n for n in need if n in have]
        entry = dict(cached or {})
        if cols:
            tab = f.read_stripe(unit.index, columns=cols)
            for n in cols:
                c = tab.column(n)
                nulls = c.null_count
                if nulls == len(c):
                    entry[n] = (None, None, nulls, len(c))
                else:
                    import pyarrow.compute as pc
                    mm = pc.min_max(c).as_py()
                    entry[n] = (mm["min"], mm["max"], nulls, len(c))
        for n in need:
            if n not in entry:      # absent column: unknown-stats marker
                entry[n] = (None, None, None, -1)
        with _ORC_STATS_LOCK:
            resident = _ORC_STATS_CACHE.get(key)
            if resident is not None:
                # A concurrent prober filled other columns meanwhile:
                # merge instead of clobbering its work.
                entry = {**resident, **entry}
            elif key not in _ORC_STATS_CACHE:
                # Evict only for a genuinely new key (an update of a
                # resident key must never push out a warm neighbor),
                # oldest first.
                while len(_ORC_STATS_CACHE) >= _ORC_STATS_CACHE_MAX:
                    _ORC_STATS_CACHE.popitem(last=False)
            _ORC_STATS_CACHE[key] = entry
            _ORC_STATS_CACHE.move_to_end(key)
        cached = entry
    num_rows = max((rows for (_, _, _, rows) in cached.values()
                    if rows >= 0), default=0)
    return ({n: _Stat(mn, mx, nulls)
             for n, (mn, mx, nulls, rows) in cached.items()
             if rows >= 0}, num_rows)


def _unit_survives(fmt: str, unit: ScanUnit,
                   predicates: Sequence[Tuple[str, str, Any]]) -> bool:
    """False when unit statistics prove no row can satisfy ALL pushed
    conjuncts (conservative: missing/odd stats keep the unit). SQL null
    semantics make this safe — a comparison is never true for NULL, so
    bounds over non-null values suffice. Parquet reads footer stats; ORC
    uses the engine's own first-contact stripe index."""
    if not predicates or fmt == "csv":
        return True
    if fmt == "orc":
        stats_by_name, num_rows = _orc_stripe_stats(
            unit, [name for name, _, _ in predicates])
        return _stats_survive(stats_by_name, num_rows, predicates)
    rg = _parquet_metadata(unit.path).row_group(unit.index)
    stats_by_name = {}
    for ci in range(rg.num_columns):
        col = rg.column(ci)
        stats_by_name[col.path_in_schema] = col.statistics
    return _stats_survive(stats_by_name, rg.num_rows, predicates)


def _stats_survive(stats_by_name, num_rows,
                   predicates: Sequence[Tuple[str, str, Any]]) -> bool:
    for name, op, value in predicates:
        st = stats_by_name.get(name)
        if st is None:
            continue
        try:
            if op == "isnotnull":
                if st.null_count is not None and \
                        st.null_count == num_rows:
                    return False
                continue
            if not st.has_min_max:
                # All-null pages carry no min/max: a comparison predicate
                # can never be true then.
                if st.null_count is not None and \
                        st.null_count == num_rows:
                    return False
                continue
            mn, mx = st.min, st.max
            v = value.decode() if isinstance(value, bytes) else value
            mn = mn.decode() if isinstance(mn, bytes) else mn
            mx = mx.decode() if isinstance(mx, bytes) else mx
            if op == "eq" and (v < mn or v > mx):
                return False
            if op == "lt" and mn >= v:
                return False
            if op == "le" and mn > v:
                return False
            if op == "gt" and mx <= v:
                return False
            if op == "ge" and mx < v:
                return False
        except TypeError:
            continue    # incomparable stat/value types: keep the unit
    return True


def _read_unit_batches(fmt: str, unit: ScanUnit, options: Dict,
                       batch_rows: int,
                       columns: Optional[List[str]] = None
                       ) -> Iterator[HostBatch]:
    """Decode one scan unit; ``columns`` restricts the read to a pruned
    schema (GpuParquetScan readDataSchema analog — unread columns are
    never decoded)."""
    if fmt == "parquet":
        pf = papq.ParquetFile(unit.path)
        for rb in pf.iter_batches(batch_size=batch_rows,
                                  row_groups=[unit.index],
                                  columns=columns):
            yield arrow_to_host_batch(rb)
    elif fmt == "orc":
        f = paorc.ORCFile(unit.path)
        yield arrow_to_host_batch(
            f.read_stripe(unit.index, columns=columns))
    elif fmt == "csv":
        kwargs = _csv_read_options(options)
        if columns:
            kwargs["convert_options"] = pacsv.ConvertOptions(
                include_columns=list(columns))
        tbl = pacsv.read_csv(unit.path, **kwargs)
        for rb in tbl.to_batches(max_chunksize=batch_rows):
            yield arrow_to_host_batch(rb)
    else:
        raise ValueError(fmt)


class DeviceScanCache:
    """Transparent device-resident cache of decoded scan units.

    The TPU analog of keeping Spark's columnar cache on the accelerator
    (InMemoryTableScanExec handling, GpuTransitionOverrides.scala:339) at
    scan-unit granularity: a unit's decoded DeviceBatches stay in HBM,
    keyed by file identity (path, mtime, size), unit ordinal and the
    pruned column set, so a repeated query serves them without touching
    the host->device link or the host decode in front of it. Rewritten
    files miss naturally via the mtime/size key.

    Kept to the configured byte budget by evicting from the least
    recently used end, with one exception (PR 35). A query reads a
    table's units in one order every time, and under such a cyclic scan
    a plain LRU has a cliff: a working set ONE unit over the budget
    evicts, for each unit it takes in, the unit the next step needs, and
    every unit misses in every query. So a unit whose admission would
    evict a unit of its OWN scan (same format, columns, options and
    batch size) is turned away and what is held stays, unless that
    unit has not been served since this one was last turned away: the
    part of the table that does not fit misses every query, the rest
    hits, and stale entries (an overwritten table's old files, which
    nothing serves any more) yield on the new units' second pass."""

    _COUNTS = ("scanCacheHitUnits", "scanCacheMissUnits",
               "scanCacheHitBytes", "scanCacheMissBytes",
               "scanCacheRefillBytes", "scanCacheEvictedBytes",
               "scanCacheRejectedBytes")
    # Keys remembered after they left or were turned away (the oldest
    # forgotten first): what makes a miss a REFILL and not a first fill.
    _GONE_KEYS = 1 << 16

    def __init__(self):
        self._entries: "dict" = {}     # key -> [DeviceBatch]
        self._bytes: Dict[Any, int] = {}
        self._total = 0
        self._tick = 0
        self._served: Dict[Any, int] = {}   # key -> tick of its last use
        self._gone: Dict[Any, int] = {}     # key -> tick it left at
        self._counts = dict.fromkeys(self._COUNTS, 0)
        # Probed/filled from pipeline prefetch threads and concurrent
        # consumers: LRU reorder + eviction accounting must be atomic.
        self._lock = threading.Lock()

    def get(self, key, probe: bool = False):
        """The unit's batches, or None. ``probe=True`` only asks (the
        prefetch thread deciding what to decode): the consumer's own
        ``get`` is the hit that counts and that marks the unit served."""
        with self._lock:
            e = self._entries.get(key)
            if e is not None and not probe:
                self._entries[key] = self._entries.pop(key)   # to MRU
                self._tick += 1
                self._served[key] = self._tick
                self._counts["scanCacheHitUnits"] += 1
                self._counts["scanCacheHitBytes"] += self._bytes[key]
            return e

    def put(self, key, batches, budget: int):
        """Offer a unit that was just decoded and uploaded because the
        cache did not hold it: counted as a miss whatever becomes of it."""
        size = sum(b.device_size_bytes() for b in batches)
        with self._lock:
            c = self._counts
            c["scanCacheMissUnits"] += 1
            c["scanCacheMissBytes"] += size
            if key in self._entries:
                return                     # concurrent filler won
            self._tick += 1
            since = self._gone.pop(key, None)   # it left, or was turned
            if since is not None:               # away, at this tick
                c["scanCacheRefillBytes"] += size
            if size > budget:
                c["scanCacheRejectedBytes"] += size
                self._forget(key)
                return
            victims, freed = [], 0
            for old in self._entries:
                if self._total - freed + size <= budget:
                    break
                if self._same_scan(old, key) and (
                        since is None or self._served[old] > since):
                    self._forget(key)
                    return
                victims.append(old)
                freed += self._bytes[old]
            for old in victims:
                self._entries.pop(old)
                self._served.pop(old)
                c["scanCacheEvictedBytes"] += self._bytes[old]
                self._total -= self._bytes.pop(old)
                self._forget(old)
            self._entries[key] = list(batches)
            self._bytes[key] = size
            self._served[key] = self._tick
            self._total += size

    @staticmethod
    def _same_scan(a, b) -> bool:
        """A key is ``(scan, unit)`` (``FileScanExec._unit_cache_key``):
        the units of one scan share the first."""
        return a[0] == b[0]

    def _forget(self, key) -> None:
        self._gone[key] = self._tick
        if len(self._gone) > self._GONE_KEYS:
            self._gone.pop(next(iter(self._gone)))

    def counters(self) -> Dict[str, int]:
        with self._lock:
            return {**self._counts, "scanCacheResidentBytes": self._total}

    def clear(self):
        with self._lock:
            self._entries.clear()
            self._bytes.clear()
            self._served.clear()
            self._gone.clear()
            self._total = 0


DEVICE_SCAN_CACHE = DeviceScanCache()


def counters() -> Dict[str, int]:
    """Process totals of the device scan cache, in the manner of
    ``columnar/batch.py counters()``: units and device bytes served from
    it (``scanCacheHitUnits`` / ``HitBytes``), decoded and uploaded
    because it did not hold them (``MissUnits`` / ``MissBytes``), the
    part of those bytes whose key it had met before — held and evicted,
    or turned away (``scanCacheRefillBytes``: 0 for ever where the
    working set is resident, warm-ups included), bytes evicted, bytes of
    units larger than the whole budget (``RejectedBytes``) and what it
    holds now (``scanCacheResidentBytes``)."""
    return DEVICE_SCAN_CACHE.counters()


class FileScanExec(LeafExec):
    """Leaf scan over N files in a format, with reader strategies.
    Splits at scan-unit (row-group/stripe) granularity and applies pushed
    predicates as row-group stats skips."""

    def __init__(self, fmt: str, paths: Sequence[str], schema: Schema,
                 options: Optional[Dict] = None,
                 num_partitions: Optional[int] = None,
                 force_perfile: bool = False,
                 predicates: Sequence[Tuple[str, str, Any]] = ()):
        super().__init__()
        self.fmt = fmt
        self.paths = list(paths)
        self._schema = tuple(schema)
        self.options = dict(options or {})
        self._columns = [n for n, _ in self._schema]
        self.predicates = tuple(predicates)
        self._opts_key = tuple(sorted((str(k), str(v))
                                      for k, v in self.options.items()))
        self._units = enumerate_units(fmt, self.paths)
        self._parts = num_partitions or min(len(self._units), 8) or 1
        # input_file_name() in the plan: batches must not span files.
        self.force_perfile = force_perfile

    @property
    def schema(self) -> Schema:
        return self._schema

    @property
    def name(self) -> str:
        return f"{type(self).__name__}[{self.fmt}]"

    def num_partitions(self, ctx) -> int:
        return self._parts

    def _resolved_predicates(self, ctx) -> Tuple:
        """Pushed conjuncts with plan-cache bind slots resolved against
        THIS execution's binding vector (``ctx.cache['plan_binds']``).
        A slot predicate with no bindings in scope is dropped — stats
        skipping is an optimization; the filter above still runs."""
        from spark_rapids_tpu.exprs.bindslots import BindValue
        if not any(isinstance(v, BindValue)
                   for _, _, v in self.predicates):
            return self.predicates
        binds = None if ctx is None else ctx.cache.get("plan_binds")
        out = []
        for name, op, value in self.predicates:
            if isinstance(value, BindValue):
                if binds is None or value.slot >= len(binds):
                    continue
                value = binds[value.slot]
            out.append((name, op, value))
        return tuple(out)

    def _units_of(self, partition: int, m=None, ctx=None) -> List[ScanUnit]:
        """This partition's units, minus stats-skipped ones."""
        mine = [u for i, u in enumerate(self._units)
                if i % self._parts == partition]
        if not self.predicates:
            return mine
        predicates = self._resolved_predicates(ctx)
        if not predicates:
            return mine
        kept = [u for u in mine
                if _unit_survives(self.fmt, u, predicates)]
        if m is not None and len(kept) < len(mine):
            m.add("numSkippedRowGroups", len(mine) - len(kept))
        return kept

    def _reader_type(self, ctx) -> str:
        if self.force_perfile:
            return "PERFILE"
        entry = {"parquet": C.PARQUET_READER_TYPE,
                 "orc": C.ORC_READER_TYPE,
                 "csv": C.CSV_READER_TYPE}[self.fmt]
        rt = str(ctx.conf.get(entry)).upper()
        if rt == "AUTO":
            return "MULTITHREADED"
        return rt

    def _batch_rows(self, ctx) -> int:
        return int(ctx.conf.get(C.MAX_READER_BATCH_SIZE_ROWS))

    def _publish_input_file(self, ctx, partition: int, path: str,
                            host: bool = False) -> None:
        """Publish the current file for input_file_name() downstream
        (GpuInputFileBlock analog; per-unit, pre-yield). Keys are scoped to
        this scan instance so two scans sharing a partition (join of two
        reads) never clobber each other; the consumer resolves the key via
        its unique descendant scan (ops/basic.py)."""
        prefix = "input_file_host" if host else "input_file"
        ctx.cache[f"{prefix}:{id(self)}:{partition}"] = path

    # -- host engine ---------------------------------------------------------
    def execute_host(self, ctx, partition):
        rows = self._batch_rows(ctx)
        for unit in self._units_of(partition, ctx=ctx):
            self._publish_input_file(ctx, partition, unit.path, host=True)
            yield from _read_unit_batches(self.fmt, unit, self.options,
                                          rows, self._columns)

    # -- pipelined prefetch (parallel/pipeline.py) ---------------------------
    def host_prefetchable(self) -> bool:
        return True

    def _prefetch_key(self, partition: int) -> str:
        return f"scan-prefetch:{id(self):x}:{partition}"

    def prefetch_host(self, ctx, partition) -> None:
        """The separable host half of one partition: stats pruning, unit
        decode, wire encode AND staging-buffer pack — everything before
        ``device_put``. Runs on a pipeline prefetch thread; the payload
        lands in ``ctx.cache`` and the ordered consumer's
        :meth:`execute_device` pops it and only dispatches transfers.
        Payload entries are ``(unit, [EncodedBatch...])`` /
        ``(unit, "cached")`` for device-cache hits / ``(None, encs)``
        for COALESCING merges (which have no per-unit identity)."""
        from spark_rapids_tpu import faults
        from spark_rapids_tpu.columnar import wire
        from spark_rapids_tpu.columnar.host import concat_host_batches
        from spark_rapids_tpu.parallel import pipeline as PL
        m = ctx.metrics_for(self)
        rt = self._reader_type(ctx)
        rows = self._batch_rows(ctx)
        units = self._units_of(partition, m, ctx=ctx)
        budget = int(ctx.conf.get(C.SCAN_CACHE_BYTES))
        use_cache = budget > 0 and rt != "COALESCING"
        payload: List[tuple] = []
        if rt == "COALESCING":
            pending: List[HostBatch] = []
            pending_rows = 0
            for unit in units:
                faults.fault_point("scan")
                for hb in _read_unit_batches(self.fmt, unit, self.options,
                                             rows, self._columns):
                    pending.append(hb)
                    pending_rows += hb.num_rows
                    if pending_rows >= rows:
                        payload.append((None, [wire.pack_batch(
                            concat_host_batches(pending))]))
                        pending, pending_rows = [], 0
            if pending:
                payload.append((None, [wire.pack_batch(
                    concat_host_batches(pending))]))
        else:
            for unit in units:
                if use_cache and DEVICE_SCAN_CACHE.get(
                        self._unit_cache_key(unit, rows),
                        probe=True) is not None:
                    payload.append((unit, "cached"))
                    continue
                faults.fault_point("scan")
                payload.append((unit, [
                    wire.pack_batch(hb)
                    for hb in _read_unit_batches(self.fmt, unit,
                                                 self.options, rows,
                                                 self._columns)]))
        staged = sum(e.nbytes for _, item in payload
                     if item != "cached" for e in item)
        PL.record(ctx, "stagingBytesPrefetched", staged)
        ctx.cache[self._prefetch_key(partition)] = payload

    def _upload_group_plan(self, ctx, encs):
        """Deterministic call grouping for a run of encoded batches:
        members below wire.minUploadBytes coalesce into one device_put
        call (columnar/wire.py plan_upload_groups)."""
        from spark_rapids_tpu.columnar import wire
        min_bytes = int(ctx.conf.get(C.WIRE_MIN_UPLOAD_BYTES))
        if min_bytes <= 0:
            return [[i] for i in range(len(encs))]
        return wire.plan_upload_groups([e.nbytes for e in encs],
                                       min_bytes)

    def _upload_run(self, ctx, m, run, rows, partition, budget):
        """Upload a run of consecutive non-cached payload entries
        ``(unit_or_None, [EncodedBatch...])`` with tiny members grouped
        into shared device_put calls. Yield order (and therefore every
        downstream bit) is identical to per-batch uploads — grouping
        changes only the call count."""
        from spark_rapids_tpu.columnar import wire
        flat = []                      # (entry_idx, EncodedBatch)
        for ei, (_unit, encs) in enumerate(run):
            for enc in encs:
                flat.append((ei, enc))
        # Groups are consecutive flat-index runs, so streaming them in
        # order preserves the serial yield order exactly.
        groups = self._upload_group_plan(ctx, [e for _, e in flat])
        entry_batches: List[List] = [[] for _ in run]
        started = set()
        for g in groups:
            with timed(m, "bufferTime"):
                outs = wire.upload_packed_group([flat[i][1] for i in g])
            for i, b in zip(g, outs):
                ei = flat[i][0]
                unit = run[ei][0]
                if ei not in started:
                    started.add(ei)
                    if unit is not None:
                        self._publish_input_file(ctx, partition,
                                                 unit.path)
                entry_batches[ei].append(b)
                record_batch(m, b)
                yield b
                last_of_entry = i + 1 >= len(flat) or \
                    flat[i + 1][0] != ei
                if last_of_entry and unit is not None and budget > 0:
                    key = self._unit_cache_key(unit, rows)
                    if key is not None:
                        DEVICE_SCAN_CACHE.put(key, entry_batches[ei],
                                              budget)

    def _device_prefetched(self, ctx, m, payload, rows, partition,
                           budget):
        """Consume a prefetched partition: dispatch-only, in payload
        order (identical to the serial decode order, so results match
        the serial path bit-for-bit). Consecutive tiny units share one
        device_put call (wire.minUploadBytes)."""
        run: List[tuple] = []
        for unit, item in payload:
            if unit is not None and item == "cached":
                if run:
                    yield from self._upload_run(ctx, m, run, rows,
                                                partition, budget)
                    run = []
                hit = DEVICE_SCAN_CACHE.get(
                    self._unit_cache_key(unit, rows)) \
                    if budget > 0 else None
                if hit is not None:
                    m.add("scanCacheHits", 1)
                    self._publish_input_file(ctx, partition, unit.path)
                    for b in hit:
                        record_batch(m, b)
                        yield b
                else:
                    # Evicted between prefetch and consume: decode inline.
                    yield from self._device_perfile(ctx, m, [unit], rows,
                                                    partition, budget)
                continue
            run.append((unit, item))
        if run:
            yield from self._upload_run(ctx, m, run, rows, partition,
                                        budget)

    # -- device engine -------------------------------------------------------
    def _unit_cache_key(self, unit: ScanUnit, rows: int):
        try:
            st = os.stat(unit.path)
        except OSError:
            return None
        # ``(scan, unit)``. Reader options and the user schema change how
        # the same bytes decode (CSV delimiter/header, imposed types):
        # they must key the cache or two differently-configured scans
        # would share entries. They are what one scan's units have in
        # common (``DeviceScanCache._same_scan``); the file's identity
        # and the unit's ordinal tell its units apart.
        return ((self.fmt, self._schema, self._opts_key, rows),
                (unit.path, st.st_mtime_ns, st.st_size, unit.index))

    def execute_device(self, ctx, partition):
        m = ctx.metrics_for(self)
        rt = self._reader_type(ctx)
        rows = self._batch_rows(ctx)
        pre = ctx.cache.pop(self._prefetch_key(partition), None)
        if pre is not None:
            # Pipeline prefetch already decoded+encoded this partition on
            # a host thread; this (ordered, single-consumer) call only
            # uploads. A watchdog-killed attempt popped the payload with
            # it, so a re-dispatch falls through to the inline path.
            yield from self._device_prefetched(
                ctx, m, pre, rows, partition,
                int(ctx.conf.get(C.SCAN_CACHE_BYTES)))
            return
        units = self._units_of(partition, m, ctx=ctx)
        budget = int(ctx.conf.get(C.SCAN_CACHE_BYTES))
        # COALESCING merges units into one upload, so its outputs have no
        # per-unit identity to cache under; the per-unit strategies cache.
        use_cache = budget > 0 and rt != "COALESCING"
        if not use_cache:
            if rt == "MULTITHREADED":
                yield from self._device_multithreaded(ctx, m, units, rows,
                                                      partition, 0)
            elif rt == "COALESCING":
                yield from self._device_coalescing(ctx, m, units, rows)
            else:
                yield from self._device_perfile(ctx, m, units, rows,
                                                partition, 0)
            return
        # Serve cache hits inline; read contiguous miss runs through the
        # configured reader strategy (which inserts them into the cache).
        read = self._device_multithreaded if rt == "MULTITHREADED" \
            else self._device_perfile
        run: List[ScanUnit] = []
        for unit in units:
            hit = DEVICE_SCAN_CACHE.get(self._unit_cache_key(unit, rows))
            if hit is None:
                run.append(unit)
                continue
            if run:
                yield from read(ctx, m, run, rows, partition, budget)
                run = []
            m.add("scanCacheHits", 1)
            self._publish_input_file(ctx, partition, unit.path)
            for b in hit:
                record_batch(m, b)
                yield b
        if run:
            yield from read(ctx, m, run, rows, partition, budget)

    def _device_perfile(self, ctx, m, units, rows, partition, budget):
        from spark_rapids_tpu import faults
        for unit in units:
            faults.fault_point("scan")
            self._publish_input_file(ctx, partition, unit.path)
            ubatches = []
            for hb in _read_unit_batches(self.fmt, unit, self.options,
                                         rows, self._columns):
                with timed(m, "bufferTime"):
                    batch = host_to_device(hb)
                record_batch(m, batch)
                ubatches.append(batch)
                yield batch
            if budget > 0:
                key = self._unit_cache_key(unit, rows)
                if key is not None:
                    DEVICE_SCAN_CACHE.put(key, ubatches, budget)

    def _device_multithreaded(self, ctx, m, units, rows, partition,
                              budget=0):
        """Background host decode overlapped with device consumption
        (MultiFileCloudParquetPartitionReader's thread-pool overlap,
        GpuParquetScan.scala:1144). Streaming: at most ``nthreads`` units
        are in flight at once and each finished unit's batches are yielded
        (uploaded) while later units keep decoding in the background —
        never the old whole-partition ``list(...)`` buffering."""
        from spark_rapids_tpu import faults
        nthreads = int(ctx.conf.get(
            C.PARQUET_MULTITHREADED_READ_NUM_THREADS))
        if not units:
            return
        window = min(nthreads, len(units))
        # Worker threads inherit this (consuming) thread's recovery sink
        # and watchdog cancel event, so injected faults on the pool count
        # into the query's Recovery metrics and a stalled decode unwinds
        # the moment the watchdog kills the consuming attempt.
        sink = faults.get_recovery_sink()
        cancel = faults.get_cancel_event()

        def read_unit(u):
            # Decode, wire-encode AND pack in the worker: the upload's
            # entire host half (narrowing analysis, padding, bit-packing,
            # staging-buffer assembly) is CPU work that overlaps with
            # device consumption of earlier units.
            from spark_rapids_tpu.columnar import wire
            faults.set_recovery_sink(sink)
            faults.set_cancel_event(cancel)
            try:
                faults.fault_point("scan")
                return [wire.pack_batch(hb)
                        for hb in _read_unit_batches(self.fmt, u,
                                                     self.options, rows,
                                                     self._columns)]
            finally:
                faults.set_cancel_event(None)
                faults.set_recovery_sink(None)

        with concurrent.futures.ThreadPoolExecutor(
                max_workers=window) as pool:
            inflight = []          # [(unit, future)] bounded by `window`
            it = iter(units)
            for u in it:
                inflight.append((u, pool.submit(read_unit, u)))
                if len(inflight) >= window:
                    break
            while inflight:
                unit, fut = inflight.pop(0)
                encoded = fut.result()
                nxt = next(it, None)
                if nxt is not None:
                    inflight.append((nxt, pool.submit(read_unit, nxt)))
                yield from self._upload_run(ctx, m, [(unit, encoded)],
                                            rows, partition, budget)

    def _device_coalescing(self, ctx, m, units, rows):
        """Concatenate small units' rows into fewer, larger uploads
        (MultiFileParquetPartitionReader:823 stitch idea)."""
        from spark_rapids_tpu import faults
        pending: List[HostBatch] = []
        pending_rows = 0
        for unit in units:
            faults.fault_point("scan")
            for hb in _read_unit_batches(self.fmt, unit, self.options,
                                         rows, self._columns):
                pending.append(hb)
                pending_rows += hb.num_rows
                if pending_rows >= rows:
                    yield self._upload_merged(m, pending)
                    pending, pending_rows = [], 0
        if pending:
            yield self._upload_merged(m, pending)

    def _upload_merged(self, m, hbs: List[HostBatch]):
        from spark_rapids_tpu.columnar.host import concat_host_batches
        merged = concat_host_batches(hbs)
        with timed(m, "bufferTime"):
            batch = host_to_device(merged)
        record_batch(m, batch)
        return batch


def make_scan_exec(file_scan, conf, force_perfile: bool = False
                   ) -> FileScanExec:
    """Planner hook for L.FileScan nodes."""
    return FileScanExec(file_scan.fmt, file_scan.paths,
                        file_scan.source_schema, file_scan.options,
                        force_perfile=force_perfile,
                        predicates=getattr(file_scan, "predicates", ()))
