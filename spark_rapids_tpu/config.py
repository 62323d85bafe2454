"""Typed, self-documenting configuration registry.

The analog of the reference's RapidsConf.scala (1049 LoC builder DSL producing
typed ConfEntry objects, a registry, and generated docs/configs.md). Same
design: ``conf("spark.rapids...").doc(...).boolean(default)`` builders append
to a module-level registry; ``TpuConf`` resolves values from a plain dict (the
stand-in for Spark SQL conf); ``generate_docs()`` renders the markdown table.

Per-operator kill-switch keys (``spark.rapids.sql.exec.*`` /
``spark.rapids.sql.expression.*``) are registered dynamically by the
plan-rewrite rules (plan/overrides.py), mirroring RapidsMeta's ``confKey``.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, Dict, List, Optional


@dataclasses.dataclass
class ConfEntry:
    key: str
    doc: str
    value_type: str            # "boolean" | "integer" | "long" | "double" | "string"
    default: Any
    converter: Callable[[str], Any]
    internal: bool = False

    def get(self, conf: "TpuConf") -> Any:
        raw = conf.raw.get(self.key)
        if raw is None:
            return self.default
        if isinstance(raw, str):
            return self.converter(raw)
        # Coerce non-string values to the declared type so typed accessors
        # never leak e.g. int 0 where a bool is expected.
        if self.value_type == "boolean":
            if not isinstance(raw, bool):
                raise ValueError(
                    f"{self.key} expects a boolean, got {raw!r}")
            return raw
        if self.value_type in ("integer", "long"):
            return int(raw)
        if self.value_type == "double":
            return float(raw)
        return raw


_REGISTRY: Dict[str, ConfEntry] = {}
_REGISTRY_LOCK = threading.Lock()


def _parse_bool(s: str) -> bool:
    v = s.strip().lower()
    if v in ("true", "1", "yes"):
        return True
    if v in ("false", "0", "no"):
        return False
    raise ValueError(f"not a boolean config value: {s!r}")


class _Builder:
    def __init__(self, key: str):
        self._key = key
        self._doc = ""
        self._internal = False

    def doc(self, text: str) -> "_Builder":
        self._doc = text
        return self

    def internal(self) -> "_Builder":
        self._internal = True
        return self

    def _register(self, value_type, default, converter) -> ConfEntry:
        entry = ConfEntry(self._key, self._doc, value_type, default, converter,
                          self._internal)
        with _REGISTRY_LOCK:
            if self._key in _REGISTRY:
                return _REGISTRY[self._key]   # idempotent re-registration
            _REGISTRY[self._key] = entry
        return entry

    def boolean(self, default: bool) -> ConfEntry:
        return self._register("boolean", default, _parse_bool)

    def integer(self, default: int) -> ConfEntry:
        return self._register("integer", default, int)

    def long(self, default: int) -> ConfEntry:
        return self._register("long", default, int)

    def double(self, default: float) -> ConfEntry:
        return self._register("double", default, float)

    def string(self, default: Optional[str]) -> ConfEntry:
        return self._register("string", default, str)


def conf(key: str) -> _Builder:
    return _Builder(key)


def registered_entries() -> List[ConfEntry]:
    with _REGISTRY_LOCK:
        return sorted(_REGISTRY.values(), key=lambda e: e.key)


# ---------------------------------------------------------------------------
# Core entries (ref: RapidsConf.scala:282-751; keys kept compatible where the
# concept carries over, with TPU-specific replacements where it does not).
# ---------------------------------------------------------------------------

SQL_ENABLED = conf("spark.rapids.sql.enabled").doc(
    "Enable or disable running SQL operators on the TPU.").boolean(True)

DEVICE = conf("spark.rapids.device").doc(
    "Accelerator backend to target: 'tpu' (jax default backend) or 'cpu' "
    "(host fallback everywhere; useful for debugging).").string("tpu")

EXPLAIN = conf("spark.rapids.sql.explain").doc(
    "Explain why parts of a query were or were not placed on the TPU: "
    "NONE, ALL, or NOT_ON_GPU (only print replacement failures).").string("NONE")

BATCH_SIZE_BYTES = conf("spark.rapids.sql.batchSizeBytes").doc(
    "Target size in bytes for coalesced TPU batches. Larger batches amortize "
    "kernel launch/compile overhead; bounded by HBM.").long(512 * 1024 * 1024)

BATCH_SIZE_ROWS = conf("spark.rapids.sql.batchSizeRows").doc(
    "Target row capacity bucket for coalesced TPU batches (power of two). "
    "TPU addition: row capacity, not just bytes, is what bounds XLA "
    "recompilation. Default favors few large batches: every batch "
    "pays its dispatches and host syncs again.").long(4 << 20)

AUTO_BROADCAST_THRESHOLD = conf(
    "spark.rapids.sql.autoBroadcastJoinThreshold").doc(
    "Joins with strategy 'auto' broadcast the build side when its "
    "estimated size (parquet footer stats propagated through the plan) "
    "is at most this many bytes, else hash-shuffle both sides — the "
    "stats-driven half of AQE-lite (ref GpuCustomShuffleReaderExec / "
    "Spark autoBroadcastJoinThreshold semantics: -1 disables "
    "auto-broadcast entirely).").long(64 * 1024 * 1024)

AQE_COALESCE_PARTITIONS = conf(
    "spark.rapids.sql.aqe.coalescePartitions.enabled").doc(
    "After a shuffle materializes, merge undersized reduce partitions "
    "using their now-exact row counts (GpuCustomShuffleReaderExec.scala:"
    "132 coalesced-partition reader analog).").boolean(True)

AQE_COALESCE_TARGET_ROWS = conf(
    "spark.rapids.sql.aqe.coalescePartitions.targetRows").doc(
    "Row target per post-shuffle partition when coalescing.").long(1 << 20)

AQE_COALESCE_TARGET_BYTES = conf(
    "spark.rapids.sql.aqe.coalescePartitions.targetBytes").doc(
    "Byte target per post-shuffle partition when coalescing, from the "
    "OBSERVED shard bytes the transport session recorded at "
    "materialization (the exact-size half of "
    "GpuCustomShuffleReaderExec's coalesced reader). Partitions merge "
    "while both the row and the byte target hold.").long(64 * 1024 * 1024)

AQE_REPLAN = conf("spark.rapids.sql.aqe.replan.enabled").doc(
    "Runtime adaptive re-planning (parallel/replan.py): before stage "
    "prematerialization, materialize each shuffled hash join's "
    "build-side exchange, read the OBSERVED partition byte sizes from "
    "its transport session, and when the build side fits "
    "autoBroadcastJoinThreshold demote the join to a broadcast hash "
    "join — the probe side then skips its shuffle entirely and the "
    "fusion pass re-runs over the rewritten subtree. Extends the "
    "stats-only AQE-lite into true mid-query re-planning "
    "(GpuCustomShuffleReaderExec.scala:132 analog driven by the stage "
    "DAG). Off keeps the statically planned joins.").boolean(True)

COST_ENABLED = conf("spark.rapids.sql.cost.enabled").doc(
    "Cost-based host/device placement (plan/cost.py): estimate every "
    "logical subtree's device time (sync floor x sync count + "
    "bytes over the device pipeline) and host time (bytes over the "
    "host engine) from parquet/ORC footer stats, and place whole "
    "maximal subtrees on the host engine when the host estimate wins — "
    "inputs of a few MB cannot amortize the device's per-sync floor "
    "(the reference's own 'worthwhile >=30s' economics, "
    "docs/FAQ.md:82-84). The SRT_COST env (0/1) overrides "
    "the default for a whole process. Placement is skipped in test "
    "mode, under an armed fault schedule, and on non-inprocess shuffle "
    "transports (chaos/mesh paths pin the device plan).").boolean(True)

COST_SYNC_FLOOR_MS = conf("spark.rapids.sql.cost.deviceSyncFloorMs").doc(
    "Cost of ONE device host sync: dispatch a small program, wait for "
    "it and read a scalar back. Measured on the locally attached v5e "
    "with the chip tool on 2026-09-26 (PR 21): median 0.90 ms over 300 "
    "readings (0.41 ms to read a scalar that is already computed). "
    "Every sync-bearing node (exchange, join build, aggregate shrink, "
    "sort sample, collect download) charges multiples of this. On a "
    "CPU-only backend the estimator charges zero instead "
    "(plan/cost.py _cpu_only_backend).").double(0.9)

COST_DEVICE_GBPS = conf("spark.rapids.sql.cost.deviceThroughputGBps").doc(
    "Steady-state device pipeline throughput (scanned bytes over warm "
    "seconds, scan cache resident) used for the bytes-proportional "
    "term of the device estimate. One figure stands for very different "
    "operators: on the attached v5e at TPC-H SF1 (PR 21) warm q6 moved "
    "2.17 GB/s, q3 0.16 and q1 0.065. The default keeps the scan+filter "
    "figure; a per-operator model waits for the benchmark's cells "
    "(ROADMAP A0/A3).").double(2.0)

COST_HOST_GBPS = conf("spark.rapids.sql.cost.hostThroughputGBps").doc(
    "Calibrated host (numpy) engine throughput per operator pass used "
    "for the bytes-proportional term of the host estimate.").double(0.6)

COST_MAX_HOST_BYTES = conf("spark.rapids.sql.cost.maxHostBytes").doc(
    "Safety ceiling: a subtree whose estimated input exceeds this many "
    "bytes is never host-placed regardless of the model (the host "
    "engine is single-process numpy; past this size the device always "
    "wins once syncs amortize).").long(256 * 1024 * 1024)

COST_EXPLAIN = conf("spark.rapids.sql.cost.explain").doc(
    "Render per-node cost estimates (rows/bytes, device-ms vs host-ms, "
    "sync counts) and the chosen placement in DataFrame.explain() "
    "output.").boolean(False)

AGG_SKIP_PARTIAL_RATIO = conf(
    "spark.rapids.sql.agg.skipAggPassReductionRatio").doc(
    "When the first partial-aggregation batch reduces its input by less "
    "than this ratio (groups/rows above the threshold), remaining batches "
    "skip pre-shuffle grouping and project rows straight into the buffer "
    "layout; all grouping then happens once, after the exchange. 1.0 "
    "disables skipping.").double(0.85)

CONCURRENT_TPU_TASKS = conf("spark.rapids.sql.concurrentTpuTasks").doc(
    "Number of tasks that may issue work to one TPU chip concurrently "
    "(ref: spark.rapids.sql.concurrentGpuTasks / GpuSemaphore).").integer(2)

INCOMPATIBLE_OPS = conf("spark.rapids.sql.incompatibleOps.enabled").doc(
    "Enable operators that produce results that differ from Spark CPU in "
    "corner cases (float aggregation order, locale-sensitive strings...)."
).boolean(False)

HAS_NANS = conf("spark.rapids.sql.hasNans").doc(
    "Assume floating point data may contain NaN/Infinity. When true (the "
    "safe default), sum/avg aggregation carries out-of-band non-finite "
    "occurrence streams through the cumsum fast path; setting it false "
    "(the reference's common benchmark setting) drops that work entirely."
).boolean(True)

VARIABLE_FLOAT_AGG = conf("spark.rapids.sql.variableFloatAgg.enabled").doc(
    "Allow float/double aggregations whose result can vary with evaluation "
    "order (parallel tree reductions on TPU).").boolean(False)

CAST_FLOAT_TO_STRING = conf(
    "spark.rapids.sql.castFloatToString.enabled").doc(
    "Allow float->string casts that may format differently from Spark."
).boolean(False)

CAST_STRING_TO_FLOAT = conf(
    "spark.rapids.sql.castStringToFloat.enabled").doc(
    "Allow string->float casts that may differ in corner cases."
).boolean(False)

IMPROVED_FLOAT_OPS = conf("spark.rapids.sql.improvedFloatOps.enabled").doc(
    "Use TPU-fused float paths that can round differently from the JVM."
).boolean(False)

TEST_ENABLED = conf("spark.rapids.sql.test.enabled").doc(
    "Test mode: fail any query that executes a non-allowlisted operator on "
    "the host (ref: GpuTransitionOverrides.assertIsOnTheGpu).").boolean(False)

TEST_ALLOWED_NONTPU = conf("spark.rapids.sql.test.allowedNonTpu").doc(
    "Comma-separated exec class names tolerated on host in test mode."
).string("")

MAX_READER_BATCH_SIZE_ROWS = conf(
    "spark.rapids.sql.reader.batchSizeRows").doc(
    "Soft cap on rows per batch produced by file readers.").long(1 << 20)

MAX_READER_BATCH_SIZE_BYTES = conf(
    "spark.rapids.sql.reader.batchSizeBytes").doc(
    "Soft cap on bytes per batch produced by file readers."
).long(512 * 1024 * 1024)

PARQUET_READER_TYPE = conf("spark.rapids.sql.format.parquet.reader.type").doc(
    "Parquet reader strategy: PERFILE, COALESCING, MULTITHREADED, or AUTO "
    "(ref: GpuParquetScan.scala reader selection).").string("AUTO")

PARQUET_MULTITHREADED_READ_NUM_THREADS = conf(
    "spark.rapids.sql.format.parquet.multiThreadedRead.numThreads").doc(
    "Host threads used to read parquet row groups in parallel.").integer(20)

ENABLE_PARQUET = conf("spark.rapids.sql.format.parquet.enabled").doc(
    "Enable parquet scan/write on TPU path.").boolean(True)

ENABLE_CSV = conf("spark.rapids.sql.format.csv.enabled").doc(
    "Enable CSV scan on TPU path.").boolean(True)

ENABLE_ORC = conf("spark.rapids.sql.format.orc.enabled").doc(
    "Enable ORC scan/write on TPU path.").boolean(True)

ENABLE_PARQUET_READ = conf(
    "spark.rapids.sql.format.parquet.read.enabled").doc(
    "Enable parquet reads on the TPU path (scan falls back to the host "
    "engine when off; finer grain than format.parquet.enabled)."
).boolean(True)

ENABLE_PARQUET_WRITE = conf(
    "spark.rapids.sql.format.parquet.write.enabled").doc(
    "Enable the device plan feeding parquet writes (off = the write job "
    "runs through the host fallback engine).").boolean(True)

ENABLE_ORC_READ = conf("spark.rapids.sql.format.orc.read.enabled").doc(
    "Enable ORC reads on the TPU path.").boolean(True)

ENABLE_ORC_WRITE = conf("spark.rapids.sql.format.orc.write.enabled").doc(
    "Enable the device plan feeding ORC writes.").boolean(True)

ENABLE_CSV_READ = conf("spark.rapids.sql.format.csv.read.enabled").doc(
    "Enable CSV reads on the TPU path.").boolean(True)

ORC_READER_TYPE = conf("spark.rapids.sql.format.orc.reader.type").doc(
    "ORC reader strategy: PERFILE, COALESCING, MULTITHREADED, or AUTO "
    "(GpuOrcScan multi-file reader selection analog).").string("AUTO")

CSV_READER_TYPE = conf("spark.rapids.sql.format.csv.reader.type").doc(
    "CSV reader strategy: PERFILE, COALESCING, MULTITHREADED, or AUTO."
).string("AUTO")

REPLACE_SORT_MERGE_JOIN = conf(
    "spark.rapids.sql.replaceSortMergeJoin.enabled").doc(
    "Replace sort-merge joins with TPU hash joins, dropping the sorts "
    "(ref: GpuSortMergeJoinExec meta).").boolean(True)

STABLE_SORT = conf("spark.rapids.sql.stableSort.enabled").doc(
    "Use stable sorting (matches Spark's sort for ties at a small cost)."
).boolean(True)

SHUFFLE_COMPRESSION_CODEC = conf(
    "spark.rapids.shuffle.compression.codec").doc(
    "Codec for spilled shuffle/buffer blobs: lz4 (native LZ4 block "
    "format, memory/compression.py + native/compress.cpp), copy "
    "(framing only, testing), or none. The reference compresses with "
    "nvcomp LZ4 on-GPU; the TPU path keeps live data in HBM, so the "
    "codec applies on the host at the disk-spill boundary.").string("lz4")

SCAN_CACHE_BYTES = conf(
    "spark.rapids.sql.format.scanCache.maxBytes").doc(
    "Device (HBM) budget for the transparent scan-unit cache: decoded "
    "batches of recently scanned parquet/orc/csv units stay resident and "
    "are served without re-decoding or re-crossing the host->device link "
    "(the TPU analog of serving Spark's columnar InMemoryTableScan from "
    "the device store, GpuTransitionOverrides.scala:339; same role as a "
    "transparent read cache in front of cold storage). 0 disables."
).long(4 * 1024 * 1024 * 1024)

WIRE_CODEC = conf("spark.rapids.sql.wire.codec").doc(
    "Host->device wire codec (columnar/wire.py): 'v2' (default — "
    "dictionary, narrow-int, RLE, delta and frame-of-reference "
    "encodings chosen per column by smallest wire size from one host "
    "stats pass), 'v1' (dictionary + narrow-int only, the pre-fast-path "
    "behavior), or 'plain' (logical dtypes ship untransformed — the "
    "transport-transparency baseline; every codec is lossless, so all "
    "three produce bit-identical query results). The SRT_WIRE_CODEC "
    "env seeds the process default; the conf key overrides it. "
    "Process-global, like the kernel cache.").string("v2")

WIRE_MIN_UPLOAD_BYTES = conf("spark.rapids.sql.wire.minUploadBytes").doc(
    "Upload call coalescing threshold: consecutive encoded scan "
    "batches whose packed staging buffers are each below this many "
    "bytes share ONE device_put call (each member still decodes "
    "through its own cached kernel off its own arrays, so results are "
    "bit-identical). Since PR 21 a call moves one host-to-device "
    "transfer per wire ARRAY, so grouping saves a call's overhead and "
    "no transfer; whether that still pays on the attached chip is not "
    "measured (ROADMAP A2; a 1 MB device_put reached 1.2 GB/s there, a "
    "256 MB one 4.7 GB/s). 0 disables grouping."
).long(1 << 20)

JOIN_GRACE_ENABLED = conf("spark.rapids.sql.join.grace.enabled").doc(
    "Out-of-core grace hash joins (ops/join.py): when a shuffled hash "
    "join's build side exceeds join.grace.buildFraction of the device "
    "budget, partition BOTH sides by key fingerprint (the same "
    "murmur3 hash partitioning the exchange uses) into spillable "
    "buckets and join the co-partitioned bucket pairs — so a build "
    "side far past the device budget still runs ON DEVICE instead of "
    "OOM-laddering to the host engine. Also registered as the OOM "
    "escalation rung directly ABOVE host fallback: a hash join whose "
    "single-batch build exhausts the spill/shrink ladder retries "
    "grace-partitioned before degrading to host. This beats the "
    "reference's RequireSingleBatch build-side restriction "
    "(GpuShuffledHashJoinExec).").boolean(True)

JOIN_GRACE_BUILD_FRACTION = conf(
    "spark.rapids.sql.join.grace.buildFraction").doc(
    "Fraction of the device budget a hash-join build side may occupy "
    "as a single coalesced batch before the grace path engages; it is "
    "also the per-bucket byte budget the grace partitioner targets."
).double(0.5)

JOIN_GRACE_MAX_PARTITIONS = conf(
    "spark.rapids.sql.join.grace.maxPartitions").doc(
    "Upper bound on grace-join fingerprint buckets per partition "
    "(graceJoinPartitions counts the buckets actually used)."
).integer(64)

SHUFFLE_PARTITIONS = conf("spark.rapids.sql.shuffle.partitions").doc(
    "Number of shuffle output partitions for exchanges (analog of "
    "spark.sql.shuffle.partitions).").integer(8)

HBM_POOL_FRACTION = conf("spark.rapids.memory.tpu.allocFraction").doc(
    "Fraction of visible HBM the engine budgets for batch storage; the "
    "watermark evictor starts spilling above it (ref: RMM pool + "
    "DeviceMemoryEventHandler). A real allocation failure past the "
    "watermark spills-and-retries at the dispatch site (memory/oom.py), "
    "so the budget can run close to full.").double(0.9)

CONCURRENT_PYTHON_WORKERS = conf(
    "spark.rapids.python.concurrentPythonWorkers").doc(
    "Max pandas-UDF group functions evaluated concurrently "
    "(PythonWorkerSemaphore analog; 0 or 1 = serial).").integer(4)

MEMORY_DEBUG = conf("spark.rapids.memory.tpu.debug").doc(
    "Log every catalog buffer add/acquire/spill/remove with sizes, record "
    "creation stacks, and emit a leak report (unfreed buffers + where "
    "they were allocated) when the query context closes (ref: "
    "spark.rapids.memory.gpu.debug, RapidsConf.scala:288 + cuDF "
    "MemoryCleaner leak callstacks).").boolean(False)

MAX_ALLOC_FRACTION = conf(
    "spark.rapids.memory.tpu.maxAllocFraction").doc(
    "Hard ceiling on the fraction of visible HBM the batch-storage "
    "budget may claim, regardless of allocFraction (RapidsConf's "
    "maxAllocFraction).").double(0.95)

RESERVE_BYTES = conf("spark.rapids.memory.tpu.reserve").doc(
    "HBM bytes held back from the batch-storage budget for compute "
    "transients and the XLA runtime (spark.rapids.memory.gpu.reserve "
    "analog).").long(512 * 1024 * 1024)

METRICS_LEVEL = conf("spark.rapids.sql.metrics.level").doc(
    "Operator metric verbosity reported by DataFrame.metrics(): "
    "ESSENTIAL (rows/time), MODERATE (+batches/shuffle), or DEBUG "
    "(everything the execs record). Audit groups registered in "
    "ops/base.py (Recovery/Pipeline/Scheduler/Transport/Cost @query) "
    "are never filtered.").string("DEBUG")

TRACE_ENABLED = conf("spark.rapids.sql.trace.enabled").doc(
    "Query flight recorder (spark_rapids_tpu/monitoring/): record "
    "structured trace spans (scheduler queue, host prefetch, wire "
    "pack/upload, per-operator device dispatch, shuffle write/fetch, "
    "stage materialization) and instant events (fault injected, OOM "
    "rung, stage recompute, join demotion, watchdog kill, "
    "cancellation, cross-query eviction) into a bounded per-query "
    "ring buffer. Consumed by DataFrame.trace_export (Chrome/Perfetto "
    "JSON), DataFrame.explain_analyze, monitoring.snapshot() and the "
    "benchmark's traced run. Off = a no-op recorder with near-zero "
    "per-call overhead (the NVTX-always-on analog, "
    "NvtxWithMetrics.scala:21-44). The SRT_TRACE env (0/1) overrides "
    "the default for a whole process.").boolean(False)

TRACE_MAX_EVENTS = conf("spark.rapids.sql.trace.maxEvents").doc(
    "Per-query ring-buffer bound for the flight recorder: once a "
    "query's ring is full the oldest events drop (droppedEvents in "
    "monitoring.snapshot() counts them), so tracing can stay on under "
    "sustained load without unbounded memory.").integer(65536)

TRACE_LEVEL = conf("spark.rapids.sql.trace.level").doc(
    "Flight-recorder verbosity: 'query' (query/stage lifecycle spans + "
    "every instant event), 'operator' (+ per-partition, per-operator, "
    "upload, shuffle spans), or 'kernel' (+ per-batch wire encode/pack "
    "and host-sync attribution spans).").string("operator")

HOST_SPILL_STORAGE_SIZE = conf("spark.rapids.memory.host.spillStorageSize").doc(
    "Bytes of host RAM for spilled device batches before going to disk."
).long(1024 * 1024 * 1024)

SPILL_DIR = conf("spark.rapids.memory.spill.dir").doc(
    "Directory for the disk spill tier.").string("/tmp/spark_rapids_tpu_spill")

UDF_COMPILER_ENABLED = conf("spark.rapids.sql.udfCompiler.enabled").doc(
    "Trace python UDFs with JAX into columnar expressions when possible "
    "(the TPU-native analog of the bytecode->Catalyst udf-compiler)."
).boolean(True)

METRICS_ENABLED = conf("spark.rapids.sql.metrics.enabled").doc(
    "Live telemetry plane (spark_rapids_tpu/monitoring/telemetry.py): "
    "a process-global typed metric registry — monotonic counters, "
    "gauges, sliding-window log-bucket histograms (p50/p95/p99) with "
    "labeled series (tenant/class/kind/tier/worker) — continuously "
    "scrapeable while queries run, bridged from every existing counter "
    "funnel (scheduler/QoS, plan+kernel caches, recovery ladder, "
    "transport, pipeline, spill watermark). Consumed by "
    "telemetry.snapshot()/render_text() and the OpenMetrics exporter "
    "(metrics.port). Off = a no-op "
    "registry whose per-call cost is one global load (the same "
    "discipline as trace.enabled). "
    "The SRT_METRICS env (0/1) overrides the default for a whole "
    "process.").boolean(False)

METRICS_PORT = conf("spark.rapids.sql.metrics.port").doc(
    "OpenMetrics/Prometheus exporter port (monitoring/exporter.py): "
    "with metrics.enabled, serve the text exposition on "
    "127.0.0.1:<port>/metrics from a daemon thread. 0 (default) = no "
    "socket — the registry stays readable in-process via "
    "telemetry.snapshot()/render_text().").integer(0)

EVENT_LOG_DIR = conf("spark.rapids.sql.eventLog.dir").doc(
    "Persistent per-query event log (monitoring/history.py): append "
    "one JSONL record per query at teardown — plan fingerprint, bind "
    "slots, per-node observed rows/bytes, span-category breakdown, "
    "recovery/QoS instants, final metrics — under this directory "
    "(one events-<pid>.jsonl per process). scripts/history.py "
    "reconstructs explain_analyze-style reports and a fleet summary "
    "from the log alone, after the process has exited (the history "
    "server analog). Empty (default) = off. The SRT_EVENT_LOG env "
    "overrides the default for a whole process.").string("")

MESH_ENABLED = conf("spark.rapids.sql.mesh.enabled").doc(
    "Lower hash shuffles to collective all_to_all exchanges over the "
    "jax.sharding.Mesh of all visible devices (ICI shuffle; ref: "
    "SURVEY.md §2.6 TPU mapping). Off = single-process materialized "
    "exchange.").boolean(False)

STAGE_FUSION_ENABLED = conf("spark.rapids.sql.stageFusion.enabled").doc(
    "Collapse maximal runs of contiguous row-local jittable device "
    "operators (Project, Filter, LocalLimit, Expand) into one fused "
    "kernel per stage — one XLA dispatch instead of one per operator, "
    "with no materialized batch between them (the WholeStageCodegen / "
    "GpuCoalesceBatches analog for this engine). A stage breaks at "
    "exchanges, aggregates, sorts, joins, host-roundtrip expressions "
    "and task-context expressions (rand, input_file_name...). Off "
    "restores the one-Exec-one-kernel plan shape.").boolean(True)

KERNEL_CACHE_MAX_ENTRIES = conf(
    "spark.rapids.sql.kernelCache.maxEntries").doc(
    "LRU bound on the process-global compiled-kernel cache keyed by "
    "(expression fingerprint, input schema, capacity bucket). Repeated "
    "queries — bench iterations, suite partitions, serving traffic — "
    "reuse compiled programs across planner/exec instances instead of "
    "re-tracing them; the bound caps host memory AND mmap regions held "
    "by cached executables. The latter is the binding constraint: a "
    "live XLA CPU executable for a real query kernel holds ~80 memory "
    "maps, and Linux caps a process at vm.max_map_count (65530 by "
    "default) — cross it and the next compile SIGSEGVs inside XLA. 512 "
    "keeps a fully-fat cache near ~40k maps; raise it only with a "
    "raised map ceiling.").integer(512)

HOST_CLOSURE_CACHE_MAX_ENTRIES = conf(
    "spark.rapids.sql.host.closureCache.maxEntries").doc(
    "LRU bound on the host engine's compiled-closure cache "
    "(ops/host_cache.py) — the numpy analog of the device kernel "
    "cache, keyed by the same structural expression fingerprint + "
    "bind-slot normalization so plan-cache bind-only executions walk "
    "no expression tree on host either. Entries are plain python "
    "closures (no XLA executables), so the bound only caps fingerprint "
    "bookkeeping memory.").integer(256)

DEVICE_BUDGET_BYTES = conf("spark.rapids.memory.tpu.budgetBytes").doc(
    "Explicit HBM budget for the buffer catalog in bytes; 0 derives it "
    "from allocFraction of the visible device memory (ref: RMM pool "
    "sizing, GpuDeviceManager.scala:159-230).").long(0)

TEST_FAULTS = conf("spark.rapids.sql.test.faults").doc(
    "Deterministic fault-injection schedule for chaos testing: "
    "comma-separated kind@site[:arg] entries (kinds oom/transient/"
    "corrupt; arg = fire-count or probability), e.g. "
    "'oom@upload:0.05,transient@exchange.flush:2,corrupt@wire:1'. "
    "Empty disarms. The SRT_FAULTS env var seeds the process-global "
    "schedule when this key is unset. See docs/robustness.md and "
    "spark_rapids_tpu/faults.py.").string("")

TEST_FAULTS_SEED = conf("spark.rapids.sql.test.faults.seed").doc(
    "Seed for the per-site fault-injection PRNGs and retry-backoff "
    "jitter: the same schedule + seed reproduces the same failures AND "
    "the same recovery timing (SRT_FAULTS_SEED env analog).").long(0)

RETRY_TRANSIENT_MAX = conf(
    "spark.rapids.sql.retry.transientMaxRetries").doc(
    "Per-query retry budget for transient backend failures "
    "(UNAVAILABLE, DEADLINE_EXCEEDED, connection resets): the whole "
    "query re-runs on a fresh context up to this many times, with "
    "exponential backoff between attempts. 0 disables the retry."
).integer(2)

RETRY_BACKOFF_MS = conf("spark.rapids.sql.retry.backoffMs").doc(
    "Base backoff before transient-retry attempt i: "
    "min(backoffMs * 2^i, maxBackoffMs) scaled by deterministic jitter "
    "in [0.5, 1.0) seeded from spark.rapids.sql.test.faults.seed."
).long(50)

RETRY_MAX_BACKOFF_MS = conf("spark.rapids.sql.retry.maxBackoffMs").doc(
    "Ceiling on the exponential transient-retry backoff.").long(2000)

OOM_HOST_FALLBACK = conf("spark.rapids.sql.oom.hostFallback.enabled").doc(
    "Final OOM escalation rung: when a device operator exhausts the "
    "spill-some -> spill-all -> shrink ladder before producing its "
    "first batch, re-run that operator subtree on the host engine and "
    "upload the results (the reference's CPU-fallback-always-available "
    "guarantee applied at the dispatch funnel).").boolean(True)

WATCHDOG_ENABLED = conf("spark.rapids.sql.watchdog.enabled").doc(
    "Execution watchdog: run each partition's device execution under a "
    "deadline (taskTimeoutMs) with bounded re-dispatch (maxAttempts) — "
    "the speculative-re-execution analog of Spark's task-level "
    "straggler handling, with deterministic first-winner semantics so "
    "chaos runs stay bit-identical. Off by default: the per-partition "
    "worker thread is pure overhead on a healthy single-tenant chip."
).boolean(False)

WATCHDOG_TASK_TIMEOUT_MS = conf(
    "spark.rapids.sql.watchdog.taskTimeoutMs").doc(
    "Deadline per watchdog partition attempt. An attempt still running "
    "at the deadline is killed (cooperative cancel; a wedged device "
    "call is abandoned to its daemon thread) and re-dispatched."
).long(600000)

WATCHDOG_MAX_ATTEMPTS = conf("spark.rapids.sql.watchdog.maxAttempts").doc(
    "Total watchdog attempts per partition (first dispatch + "
    "re-dispatches). Exhausting them raises DEADLINE_EXCEEDED, handing "
    "recovery to the transient whole-query retry rung.").integer(2)

STAGE_RECOVERY_ENABLED = conf(
    "spark.rapids.sql.recovery.stageRecompute.enabled").doc(
    "Lineage-scoped recovery (parallel/stages.py): split the physical "
    "plan into a stage DAG at exchange boundaries and, when a durable "
    "stage output is lost or fails its checksum, invalidate and "
    "recompute ONLY that stage on the same query context — sibling "
    "stages serve their still-materialized outputs. Off = every "
    "recoverable failure falls back to the whole-query retry."
).boolean(True)

RECOVERY_MAX_STAGE_RECOMPUTES = conf(
    "spark.rapids.sql.recovery.maxStageRecomputes").doc(
    "Per-query budget of lineage-scoped stage recomputes before "
    "recovery demotes to the whole-query retry (a stage that keeps "
    "losing its output is a sick backend, not a transient blip)."
).integer(4)

PIPELINE_ENABLED = conf("spark.rapids.sql.pipeline.enabled").doc(
    "Pipelined partition execution (parallel/pipeline.py): a host thread "
    "pool runs the separable host half of each partition (scan-unit "
    "decode, filter-stat pruning, wire encode) prefetchPartitions ahead "
    "while the consumer dispatches device work in strict partition order "
    "under the TPU semaphore — upload of partition p+1 overlaps compute "
    "of p (the MULTITHREADED-reader overlap, GpuParquetScan.scala:1144, "
    "applied at every partition-loop dispatch funnel). Independent "
    "stages of the plan DAG additionally materialize their exchange "
    "outputs concurrently. Off (or SRT_PIPELINE=0) restores the serial "
    "per-partition dispatch exactly.").boolean(True)

PIPELINE_PREFETCH_PARTITIONS = conf(
    "spark.rapids.sql.pipeline.prefetchPartitions").doc(
    "How many partitions ahead of the ordered consumer the host half may "
    "run. 1 keeps exactly one partition in flight beyond the one being "
    "consumed; larger values smooth uneven partition decode times at the "
    "cost of host memory for the buffered encodes.").integer(2)

PIPELINE_HOST_THREADS = conf("spark.rapids.sql.pipeline.hostThreads").doc(
    "Host threads shared by the pipeline's partition prefetchers "
    "(decode + wire encode are pure CPU work; the reference's "
    "multiThreadedRead.numThreads plays the same role inside one scan)."
).integer(4)

PIPELINE_MAX_CONCURRENT_STAGES = conf(
    "spark.rapids.sql.pipeline.maxConcurrentStages").doc(
    "Upper bound on plan stages (parallel/stages.py DAG nodes) whose "
    "exchange outputs materialize concurrently — e.g. the build and "
    "probe side scans of a join. Device dispatch stays bounded by the "
    "query's TPU semaphore permit; this caps only the thread fan-out. "
    "1 disables concurrent stage materialization.").integer(2)

KERNEL_CACHE_PERSISTENT_DIR = conf(
    "spark.rapids.sql.kernelCache.persistentDir").doc(
    "Directory for JAX's persistent compilation cache: compiled XLA "
    "executables serialize here and survive process restarts, so a "
    "fresh process pays deserialization (~ms) instead of recompilation "
    "(~s..min on a TPU) for every kernel it has ever compiled (the "
    "first_run_s tax). Hits surface as persistentCacheHits in the "
    "kernel-cache counters. Empty (default) keeps the directory chosen "
    "at import: JAX_COMPILATION_CACHE_DIR when set, else "
    "<checkout>/.jax_cache. With JAX_COMPILATION_CACHE_DIR set this "
    "key never moves the cache.").string("")

MESH_DEGRADE_ENABLED = conf("spark.rapids.sql.mesh.degrade.enabled").doc(
    "Graceful mesh degrade: when a mesh collective exchange fails, "
    "demote this query's exchanges to the single-process "
    "ShuffleExchangeExec path (counter meshDegrades) instead of killing "
    "the query. Off = collective failures propagate.").boolean(True)

SCHEDULER_MAX_CONCURRENT = conf(
    "spark.rapids.sql.scheduler.maxConcurrentQueries").doc(
    "Multi-query admission control (parallel/scheduler.py): at most this "
    "many collect()s execute at once; excess queries wait in the bounded "
    "run queue. 1 = strictly serial queries (byte-identical to the "
    "pre-scheduler engine); the SRT_SCHEDULER_MAX_CONCURRENT env "
    "overrides for a whole process.").integer(2)

SCHEDULER_QUEUE_DEPTH = conf("spark.rapids.sql.scheduler.queueDepth").doc(
    "Admission run-queue bound: queries beyond maxConcurrentQueries "
    "wait here, FIFO. A query arriving with the queue full is SHED with "
    "QueryRejectedError instead of letting unbounded concurrency OOM "
    "the device.").integer(16)

SCHEDULER_ADMISSION_TIMEOUT_MS = conf(
    "spark.rapids.sql.scheduler.admissionTimeoutMs").doc(
    "How long a queued query waits for a run slot before it is shed "
    "with QueryRejectedError (queuedMs reports the wait of admitted "
    "queries).").integer(60000)

SCHEDULER_QUERY_MEMORY_FRACTION = conf(
    "spark.rapids.sql.scheduler.queryMemoryFraction").doc(
    "Fair-share fraction of the device budget each admitted query's "
    "buffer catalog is charged against. 0 = auto "
    "(1/maxConcurrentQueries when queries can overlap, else the full "
    "budget); 1.0 = every query sees the full budget and isolation "
    "relies on admission + cross-query eviction.").double(1.0)

QOS_ENABLED = conf("spark.rapids.sql.scheduler.qos.enabled").doc(
    "Serving QoS subsystem (parallel/qos/): replaces the FIFO run queue "
    "with weighted fair queueing across priority classes, "
    "shortest-job-first ordering by the plan/cost.py estimate, "
    "per-tenant quotas, and deadline-aware admission. Default off: the "
    "scheduler is byte-for-byte the FIFO QueryManager. The SRT_QOS env "
    "enables it for a whole process (the CI matrix hook); the conf key "
    "wins when set.").boolean(False)

QOS_PRIORITY_CLASS = conf(
    "spark.rapids.sql.scheduler.qos.priorityClass").doc(
    "This session's default priority class: 'interactive', 'batch', or "
    "'background'. The priority= kwarg of DataFrame.collect/submit "
    "overrides per call. Ignored (recorded only) when qos.enabled is "
    "false.").string("batch")

QOS_WEIGHTS = conf("spark.rapids.sql.scheduler.qos.weights").doc(
    "WFQ weight vector 'interactive,batch,background' — run slots are "
    "granted proportionally to these weights over any window (stride "
    "scheduling; parallel/qos/policy.py). All weights must be > 0."
).string("8,3,1")

QOS_STARVATION_BOUND = conf(
    "spark.rapids.sql.scheduler.qos.starvationBound").doc(
    "Hard starvation bound: the max times a non-empty class may be "
    "bypassed for a run slot before its head query runs NEXT regardless "
    "of weights (counter starvationBoundEngagements).").integer(8)

QOS_TENANT = conf("spark.rapids.sql.scheduler.qos.tenant").doc(
    "Tenant identity for this session's queries (per-tenant quotas, "
    "plan-cache stats, chaos isolation). The tenant= kwarg of "
    "DataFrame.collect/submit overrides per call. Empty = 'default'."
).string("")

QOS_TENANT_MAX_IN_FLIGHT = conf(
    "spark.rapids.sql.scheduler.qos.tenantMaxInFlight").doc(
    "Per-tenant cap on in-flight (running + queued) queries; an "
    "over-cap tenant is rejected at admission with a typed "
    "QueryRejectedError (kind 'tenant-quota') carrying a retry-after "
    "hint. 0 = unlimited.").integer(0)

QOS_TENANT_MAX_CATALOG_BYTES = conf(
    "spark.rapids.sql.scheduler.qos.tenantMaxCatalogBytes").doc(
    "Per-tenant cap on owner-tagged catalog bytes "
    "(BufferCatalog.owned_bytes summed over the tenant's active "
    "queries) checked at admission. 0 = unlimited.").long(0)

QOS_TENANT_MAX_KERNEL_ENTRIES = conf(
    "spark.rapids.sql.scheduler.qos.tenantMaxKernelCacheEntries").doc(
    "Per-tenant compile budget: kernel-cache entries owned by the "
    "tenant's query ids (KernelCache.owners). Over the cap the "
    "tenant's OLDEST entries are evicted at its next admission "
    "(counter quotaEvictions) — never a rejection. 0 = unlimited."
).integer(0)

QOS_DEADLINE_ADMISSION = conf(
    "spark.rapids.sql.scheduler.qos.deadlineAdmission.enabled").doc(
    "Deadline-aware admission (qos.enabled only): a query whose "
    "plan/cost.py estimate cannot meet its collect(timeout_ms=...) "
    "deadline is rejected at admit time (kind 'deadline-unmeetable') "
    "instead of burning device time and dying to the kill timer. "
    "Un-priced queries always pass; the in-flight timer remains the "
    "backstop.").boolean(True)

QOS_DEADLINE_SLACK = conf(
    "spark.rapids.sql.scheduler.qos.deadlineSlack").doc(
    "Multiplier applied to the cost estimate before the deadline "
    "admission test (>1.0 rejects earlier — estimates are optimistic "
    "about queueing; <1.0 admits optimistically).").double(1.0)

PREEMPTION_ENABLED = conf(
    "spark.rapids.sql.scheduler.preemption.enabled").doc(
    "Class-aware device preemption (the overload survival plane): when "
    "a higher-priority query queues for the TPU semaphore behind a "
    "running lower-class query, the victim is asked to suspend at its "
    "next partition boundary — it spills its live catalog buffers "
    "through the existing memory ladder, releases the device permit, "
    "and resumes through the stage DAG after the preemptor drains "
    "(durable stage outputs are kept, so only unfinished work re-runs; "
    "results stay byte-identical for victim and preemptor). Default "
    "off: the device gate is the flat class-blind semaphore, "
    "byte-for-byte today's behavior. Counters preemptions/preemptedMs/"
    "resumedStages.").boolean(False)

PREEMPTION_MAX_PER_QUERY = conf(
    "spark.rapids.sql.scheduler.preemption.maxPerQuery").doc(
    "Upper bound on how many times one query may be preempted; past it "
    "the query ignores further preemption requests and runs to "
    "completion (livelock guard for a sustained interactive storm)."
).integer(4)

PREEMPTION_SPILL_ENABLED = conf(
    "spark.rapids.sql.scheduler.preemption.spill.enabled").doc(
    "Whether a preempted query spills its spillable device buffers to "
    "host while suspended (frees HBM for the preemptor). Off = suspend "
    "only releases the device permit and keeps buffers resident."
).boolean(True)

PRESSURE_ENABLED = conf(
    "spark.rapids.sql.scheduler.pressure.enabled").doc(
    "Memory-pressure shedding: each collect publishes a pressure score "
    "derived from its catalog's device/host/disk watermarks "
    "(srt_pressure_score; workers piggyback it on CBEAT heartbeats), "
    "the cluster coordinator demotes pressured workers below the "
    "steal-delay placement preference so they shed new stages instead "
    "of spilling, and sustained device pressure flips admission into "
    "brownout mode. Default off: no score is consulted anywhere."
).boolean(False)

PRESSURE_SHED_SCORE = conf(
    "spark.rapids.sql.scheduler.pressure.shedScore").doc(
    "Pressure score at or above which the coordinator demotes a worker "
    "in CPOLL placement (it loses steal-delay reservations and only "
    "receives a stage when every unpressured worker is busy or the "
    "reservation window expired). Scores are in [0, ~1.35]; the device "
    "fraction dominates.").double(0.75)

PRESSURE_BROWNOUT_SCORE = conf(
    "spark.rapids.sql.scheduler.pressure.brownout.enterScore").doc(
    "Device-pressure score at or above which (sustained for "
    "brownout.sustainMs) admission enters brownout: background-class "
    "queries are rejected with kind 'brownout' and a retry-after hint "
    "while interactive/batch admit normally — load is shed BEFORE the "
    "OOM ladders engage.").double(0.9)

PRESSURE_BROWNOUT_EXIT_SCORE = conf(
    "spark.rapids.sql.scheduler.pressure.brownout.exitScore").doc(
    "Pressure score below which brownout mode exits (hysteresis: must "
    "be below brownout.enterScore or brownout flaps).").double(0.7)

PRESSURE_BROWNOUT_SUSTAIN_MS = conf(
    "spark.rapids.sql.scheduler.pressure.brownout.sustainMs").doc(
    "How long the pressure score must stay at or above "
    "brownout.enterScore before admission browns out — one transient "
    "spike (a single large partition) must not shed a whole class."
).integer(200)

CLIENT_RETRY_MAX_ATTEMPTS = conf(
    "spark.rapids.sql.client.retry.maxAttempts").doc(
    "Default attempt budget for DataFrame.collect_with_retry: total "
    "admission attempts before the last QueryRejectedError propagates. "
    "Each retry honors the rejection's retry_after_ms hint with capped "
    "deterministic-jitter backoff (counter clientRetries / "
    "srt_client_retries_total).").integer(5)

CLIENT_RETRY_MAX_BACKOFF_MS = conf(
    "spark.rapids.sql.client.retry.maxBackoffMs").doc(
    "Cap on one collect_with_retry backoff sleep, applied after the "
    "retry_after_ms hint and the deterministic jitter (a rejection "
    "storm must converge, not sleep unboundedly).").integer(10000)

TEST_FAULTS_QUERY_TAG = conf(
    "spark.rapids.sql.test.faults.queryTag").doc(
    "Explicit fault tag for query-scoped chaos (kind@site/query=N "
    "entries fire only on the query whose tag is N). -1 = untagged: "
    "the scheduler admission ordinal is the tag.").integer(-1)

SHUFFLE_TRANSPORT = conf("spark.rapids.sql.shuffle.transport").doc(
    "Shuffle transport SPI selection (parallel/transport/): 'inprocess' "
    "(catalog-backed single-process exchange — today's default), 'mesh' "
    "(ICI collective all_to_all over the device mesh; implies what "
    "spark.rapids.sql.mesh.enabled used to select), or 'hostfile' "
    "(shards spooled to a shared directory with a manifest/socket "
    "rendezvous so independent worker processes can map-write and "
    "reduce-fetch each other's shards — the DCN multi-slice stand-in). "
    "Empty = inprocess unless SRT_SHUFFLE_TRANSPORT or the legacy "
    "mesh.enabled key says otherwise. The reference's analog is the "
    "RapidsShuffleInternalManager serializer fallback with the UCX "
    "plugin behind it (GpuColumnarBatchSerializer.scala:38).").string("")

SHUFFLE_TRANSPORT_HOSTFILE_DIR = conf(
    "spark.rapids.sql.shuffle.transport.hostfile.dir").doc(
    "Spool directory for the hostfile shuffle transport. All "
    "cooperating worker processes must see the same path (a shared "
    "filesystem is the stand-in for the DCN fabric). Empty = a "
    "per-process directory under the system temp dir — correct for "
    "single-process use, useless for cross-process rendezvous."
).string("")

SHUFFLE_TRANSPORT_HOSTFILE_WORKER_ID = conf(
    "spark.rapids.sql.shuffle.transport.hostfile.workerId").doc(
    "This process's worker identity in the hostfile spool (manifest "
    "name + shard subdirectory). Empty = 'w<pid>'.").string("")

SHUFFLE_TRANSPORT_HOSTFILE_EXPECTED_WORKERS = conf(
    "spark.rapids.sql.shuffle.transport.hostfile.expectedWorkers").doc(
    "How many worker manifests a reduce-side fetch waits for before "
    "serving shards (the membership half of the rendezvous). 1 = "
    "single-process (fetch only this worker's shards).").integer(1)

SHUFFLE_TRANSPORT_HOSTFILE_RENDEZVOUS = conf(
    "spark.rapids.sql.shuffle.transport.hostfile.rendezvous").doc(
    "Optional 'host:port' of the socket rendezvous "
    "(parallel/transport/rendezvous.py): committing workers announce "
    "their manifest over TCP and fetchers block on the commit barrier "
    "instead of polling the spool directory. Empty = manifest-file "
    "polling only.").string("")

SHUFFLE_TRANSPORT_HOSTFILE_FETCH_TIMEOUT_MS = conf(
    "spark.rapids.sql.shuffle.transport.hostfile.fetchTimeoutMs").doc(
    "How long a reduce-side fetch waits for the expected worker "
    "manifests before failing with a lost-shard error (which flows "
    "into the recovery ladder).").integer(30000)

SHUFFLE_TRANSPORT_HOSTFILE_EXCLUSIVE_MANIFEST = conf(
    "spark.rapids.sql.shuffle.transport.hostfile.exclusiveManifest").doc(
    "Single-writer manifest mode: the committing session publishes ONE "
    "tag-scoped 'exchange.manifest.json' (atomic rename) instead of a "
    "per-worker manifest, so a stage recompute on a DIFFERENT worker "
    "atomically REPLACES the dead worker's manifest — a late fetcher "
    "sees the old complete shard set or the new complete shard set, "
    "never a mix. The cluster runtime (parallel/cluster/) opens every "
    "stage-output session in this mode; expectedWorkers is forced to 1 "
    "(one committed manifest IS the stage output).").boolean(False)

SHUFFLE_TRANSPORT_HOSTFILE_RV_CONNECT_TIMEOUT_MS = conf(
    "spark.rapids.sql.shuffle.transport.hostfile.rendezvous."
    "connectTimeoutMs").doc(
    "Socket connect/read timeout for one rendezvous round trip "
    "(parallel/transport/rendezvous.py). A dead rendezvous peer fails "
    "the round trip within this bound instead of hanging the fetch "
    "indefinitely.").integer(5000)

SHUFFLE_TRANSPORT_HOSTFILE_RV_RETRIES = conf(
    "spark.rapids.sql.shuffle.transport.hostfile.rendezvous."
    "retries").doc(
    "Bounded retry count for one rendezvous round trip, with "
    "deterministic exponential backoff between attempts "
    "(rendezvous.backoffMs * 2^attempt, capped at 2s). Exhausted "
    "retries raise RendezvousUnavailableError — typed 'UNAVAILABLE:' "
    "so it maps onto the transient rung of the recovery ladder; the "
    "hostfile transport additionally DEGRADES to manifest-file polling "
    "instead of failing the fetch.").integer(3)

SHUFFLE_TRANSPORT_HOSTFILE_RV_BACKOFF_MS = conf(
    "spark.rapids.sql.shuffle.transport.hostfile.rendezvous."
    "backoffMs").doc(
    "Base backoff between rendezvous round-trip retries; attempt i "
    "sleeps backoffMs * 2^i (deterministic, capped at 2s).").integer(50)

SHUFFLE_TRANSPORT_OBJECTSTORE_ENDPOINT = conf(
    "spark.rapids.sql.shuffle.transport.objectstore.endpoint").doc(
    "Base URL of the object-store backend for the objectstore shuffle "
    "transport (parallel/transport/objectstore.py), e.g. "
    "'http://127.0.0.1:9000'. Empty = SRT_OBJECTSTORE_ENDPOINT, else an "
    "in-process localhost stub server is started once per process "
    "(single-machine stand-in for S3/GCS; the cluster coordinator pins "
    "the resolved endpoint into dispatched worker confs so every "
    "process shares one store).").string("")

SHUFFLE_TRANSPORT_OBJECTSTORE_PREFIX = conf(
    "spark.rapids.sql.shuffle.transport.objectstore.prefix").doc(
    "Key-namespace prefix prepended to every object this session "
    "reads or writes ('<prefix>/<tag>/<worker>/pNNNNN-SSSS.shard'). "
    "The cluster runtime sets '<cluster-ns>/q<qid>' per query so "
    "concurrent queries and clusters can share one store. Empty = "
    "keys rooted at the tag.").string("")

SHUFFLE_TRANSPORT_OBJECTSTORE_WORKER_ID = conf(
    "spark.rapids.sql.shuffle.transport.objectstore.workerId").doc(
    "This process's worker identity in the object store (manifest "
    "name + shard key segment). Empty = 'w<pid>'.").string("")

SHUFFLE_TRANSPORT_OBJECTSTORE_EXPECTED_WORKERS = conf(
    "spark.rapids.sql.shuffle.transport.objectstore.expectedWorkers"
).doc(
    "How many worker manifests a reduce-side fetch waits for before "
    "serving shards (same membership contract as "
    "hostfile.expectedWorkers). 1 = single-process.").integer(1)

SHUFFLE_TRANSPORT_OBJECTSTORE_EXCLUSIVE_MANIFEST = conf(
    "spark.rapids.sql.shuffle.transport.objectstore.exclusiveManifest"
).doc(
    "Single-writer manifest mode: commit publishes ONE tag-scoped "
    "'exchange.manifest.json' object (a whole-object PUT is the atomic "
    "publication barrier — readers see the old manifest or the new "
    "one, never a torn mix), mirroring "
    "hostfile.exclusiveManifest for the cluster runtime.").boolean(
    False)

SHUFFLE_TRANSPORT_OBJECTSTORE_FETCH_TIMEOUT_MS = conf(
    "spark.rapids.sql.shuffle.transport.objectstore.fetchTimeoutMs"
).doc(
    "How long a reduce-side fetch polls for the expected worker "
    "manifests before failing with a lost-shard error (which flows "
    "into the recovery ladder).").integer(30000)

SHUFFLE_TRANSPORT_OBJECTSTORE_RETRIES = conf(
    "spark.rapids.sql.shuffle.transport.objectstore.retries").doc(
    "Bounded retry count for one backend request (put/get/list/"
    "delete) on TRANSIENT errors — 5xx responses, refused/reset "
    "connections, socket timeouts. Attempt i sleeps backoffMs * "
    "2^(i-1) (capped at 2s) plus a deterministic jitter derived from "
    "the object key, so a fleet of fetchers retrying the same outage "
    "does not stampede in lockstep. Exhausted retries raise a typed "
    "'UNAVAILABLE:' error onto the transient rung of the recovery "
    "ladder. A 404 on a manifest-listed shard is NOT retried — that "
    "is shard loss and goes to stage recompute instead.").integer(4)

SHUFFLE_TRANSPORT_OBJECTSTORE_BACKOFF_MS = conf(
    "spark.rapids.sql.shuffle.transport.objectstore.backoffMs").doc(
    "Base backoff between backend-request retries (see "
    "objectstore.retries for the schedule).").integer(25)

SHUFFLE_TRANSPORT_OBJECTSTORE_TIMEOUT_MS = conf(
    "spark.rapids.sql.shuffle.transport.objectstore.timeoutMs").doc(
    "Socket connect/read timeout for one HTTP request to the object "
    "store backend.").integer(5000)

CLUSTER_ENABLED = conf("spark.rapids.sql.cluster.enabled").doc(
    "Distributed worker runtime (parallel/cluster/): the driver "
    "partitions each query's stage DAG into stage tasks and dispatches "
    "them to registered worker processes, which publish stage outputs "
    "as owner-tagged shards through the hostfile shuffle transport. "
    "false (the default) leaves every existing single-process code "
    "path byte-for-byte unchanged. Queries ineligible for dispatch "
    "(host-fallback nodes, mesh transport, no dispatchable stage, "
    "caller-provided context) stand down to local execution even when "
    "enabled.").boolean(False)

CLUSTER_COORDINATOR = conf("spark.rapids.sql.cluster.coordinator").doc(
    "host:port the driver-side coordinator binds its control-plane "
    "socket on (the rendezvous protocol extended with stage-task "
    "verbs). Workers register against this address. Empty = "
    "127.0.0.1 with an OS-assigned port (tests; read the bound "
    "address off the coordinator object).").string("")

CLUSTER_DIR = conf("spark.rapids.sql.cluster.dir").doc(
    "Shared spool directory for cluster stage outputs (the hostfile "
    "transport's DCN stand-in). All workers and the driver must see "
    "the same path. Empty = a per-process directory under the system "
    "temp dir — single-machine clusters only.").string("")

CLUSTER_MIN_WORKERS = conf("spark.rapids.sql.cluster.minWorkers").doc(
    "Dispatch gate: stage tasks are held until this many workers have "
    "registered (elastic membership — a worker joining later picks up "
    "queued tasks immediately).").integer(1)

CLUSTER_HEARTBEAT_TIMEOUT_MS = conf(
    "spark.rapids.sql.cluster.heartbeatTimeoutMs").doc(
    "A worker whose last heartbeat (or any control-plane traffic) is "
    "older than this is declared dead: its RUNNING stage task is "
    "requeued onto a survivor (one stage recompute — the partial spool "
    "is cleared first), and its membership is dropped.").integer(10000)

CLUSTER_POLL_MS = conf("spark.rapids.sql.cluster.pollMs").doc(
    "Worker task-poll interval and the driver's dispatch-loop tick. "
    "Workers heartbeat at a third of heartbeatTimeoutMs independently "
    "of this.").integer(25)

CLUSTER_DISPATCH_TIMEOUT_MS = conf(
    "spark.rapids.sql.cluster.dispatchTimeoutMs").doc(
    "How long the driver waits for the full stage-task set of one "
    "query (including requeues after worker death) before failing the "
    "dispatch with a typed UNAVAILABLE error that flows into the "
    "recovery ladder.").integer(300000)

CLUSTER_MAX_TASK_RETRIES = conf(
    "spark.rapids.sql.cluster.maxTaskRetries").doc(
    "Per-stage-task requeue budget (worker deaths + reported stage "
    "failures). A task exhausting it fails the query dispatch instead "
    "of requeueing forever.").integer(3)

CLUSTER_STEAL_DELAY_MS = conf(
    "spark.rapids.sql.cluster.stealDelayMs").doc(
    "Delay scheduling: how long a ready stage task is reserved for its "
    "preferred worker (most input-shard bytes, then rendezvous-hash "
    "owner) before any polling worker may steal it. Keeps repeat-query "
    "placement deterministic — a momentarily busy worker keeps its "
    "stages instead of paying a fresh kernel trace on whichever "
    "process grabbed them first. 0 disables the reservation.").integer(
    200)

CLUSTER_COORDINATOR_REMOTE = conf(
    "spark.rapids.sql.cluster.coordinator.remote").doc(
    "Treat cluster.coordinator as an ALREADY-RUNNING standalone "
    "coordinator process (python -m "
    "spark_rapids_tpu.parallel.cluster.coordinator) instead of "
    "hosting one in the driver. The driver submits stage plans over "
    "the control socket and polls for completion, riding out "
    "coordinator outages up to dispatchTimeoutMs — combined with the "
    "journal this is what makes a coordinator SIGKILL + restart "
    "mid-query survivable. Requires cluster.dir to be a path shared "
    "with the coordinator and workers.").boolean(False)

CLUSTER_JOURNAL_ENABLED = conf(
    "spark.rapids.sql.cluster.journal.enabled").doc(
    "Write-ahead journal for coordinator failover: registration and "
    "per-query stage state (submit/dispatch/done/requeue, with stage "
    "generations) are appended as torn-line-tolerant JSONL under "
    "<cluster.dir>/journal/ — the same event-log machinery as "
    "monitoring/history.py. A restarted coordinator replays the "
    "journal, re-adopts stage outputs whose transport manifests are "
    "still committed, and requeues only the tasks that were in "
    "flight, bounding a coordinator crash at ≤1 recompute per "
    "affected stage.").boolean(True)

CLUSTER_JOURNAL_FSYNC = conf(
    "spark.rapids.sql.cluster.journal.fsync").doc(
    "fsync the journal after every append. Off by default: the "
    "failover contract tolerates a torn tail (an unflushed 'done' "
    "record costs at most the one recompute the crash already "
    "budgeted), so the default buys dispatch latency instead of "
    "durability theater.").boolean(False)

CLUSTER_AUTOSCALE_ENABLED = conf(
    "spark.rapids.sql.cluster.autoscale.enabled").doc(
    "SLO-driven autoscaling of the worker pool "
    "(parallel/cluster/autoscaler.py): the autoscaler loop watches "
    "admission queueing (srt_admission_queued_ms), run-queue depth and "
    "fleet pressure (srt_pressure_score) against targetQueuedMs and "
    "spawns or cleanly drains workers through the supervisor, within "
    "[minWorkers, maxWorkers] and subject to cooldownMs hysteresis. "
    "Default off: the pool size is whatever was launched and no "
    "scaling decision is ever taken.").boolean(False)

CLUSTER_AUTOSCALE_MIN_WORKERS = conf(
    "spark.rapids.sql.cluster.autoscale.minWorkers").doc(
    "Autoscaler floor: the pool never drains below this many "
    "supervised workers, regardless of how idle the fleet is."
).integer(1)

CLUSTER_AUTOSCALE_MAX_WORKERS = conf(
    "spark.rapids.sql.cluster.autoscale.maxWorkers").doc(
    "Autoscaler ceiling: scale-up stops here. When the fleet is at "
    "the ceiling AND pressure stays sustained, brownout admission "
    "shedding engages (scale-up is tried FIRST — see "
    "scheduler.pressure.brownout.*).").integer(4)

CLUSTER_AUTOSCALE_TARGET_QUEUED_MS = conf(
    "spark.rapids.sql.cluster.autoscale.targetQueuedMs").doc(
    "Per-class admission-wait SLO the scale-up rule defends: when the "
    "observed queued-ms signal (worst class) exceeds this target, or "
    "the run queue is non-empty with every worker busy, the "
    "autoscaler requests scaleUpStep more workers.").integer(500)

CLUSTER_AUTOSCALE_SCALE_UP_STEP = conf(
    "spark.rapids.sql.cluster.autoscale.scaleUpStep").doc(
    "How many workers one scale-up decision adds (bounded by "
    "maxWorkers). Scale-down always retires exactly one worker per "
    "decision — draining is deliberately slower than spawning."
).integer(1)

CLUSTER_AUTOSCALE_SCALE_DOWN_IDLE_S = conf(
    "spark.rapids.sql.cluster.autoscale.scaleDownIdleS").doc(
    "How long the load signals must stay below target (no queueing, "
    "spare workers idle) before one worker is drained. Drains use "
    "CDRAIN: the coordinator stops dispatching to the worker, waits "
    "for its in-flight stages to commit their manifests, then "
    "retires it — scale-down never costs a stage recompute."
).integer(10)

CLUSTER_AUTOSCALE_COOLDOWN_MS = conf(
    "spark.rapids.sql.cluster.autoscale.cooldownMs").doc(
    "Minimum wall time between two autoscaling decisions (either "
    "direction). With the scaleDownIdleS dwell this is the "
    "hysteresis that makes the loop converge instead of flapping "
    "around the target.").integer(5000)

CLUSTER_SUPERVISOR_POLL_MS = conf(
    "spark.rapids.sql.cluster.supervisor.pollMs").doc(
    "Supervisor control-loop tick (parallel/cluster/supervisor.py): "
    "how often worker processes are reaped, restart backoffs "
    "re-evaluated, and straggler statistics pulled from the "
    "coordinator.").integer(250)

CLUSTER_SUPERVISOR_BACKOFF_BASE_MS = conf(
    "spark.rapids.sql.cluster.supervisor.restartBackoffBaseMs").doc(
    "First restart delay after a supervised worker dies; each "
    "consecutive death doubles it (deterministic exponential "
    "schedule) up to restartBackoffCapMs. A worker that completes a "
    "task resets its schedule.").integer(250)

CLUSTER_SUPERVISOR_BACKOFF_CAP_MS = conf(
    "spark.rapids.sql.cluster.supervisor.restartBackoffCapMs").doc(
    "Upper bound on the exponential restart backoff.").integer(10000)

CLUSTER_SUPERVISOR_CRASH_LOOP_WINDOW_MS = conf(
    "spark.rapids.sql.cluster.supervisor.crashLoopWindowMs").doc(
    "Crash-loop detection window: a worker that dies "
    "crashLoopThreshold times within this window is QUARANTINED — "
    "held out of the pool with a typed reason "
    "(srt_quarantined_workers gauge + worker-quarantined event-log "
    "instant) instead of being respawned forever.").integer(30000)

CLUSTER_SUPERVISOR_CRASH_LOOP_THRESHOLD = conf(
    "spark.rapids.sql.cluster.supervisor.crashLoopThreshold").doc(
    "Deaths within crashLoopWindowMs that quarantine a worker."
).integer(3)

CLUSTER_SUPERVISOR_STRAGGLER_FACTOR = conf(
    "spark.rapids.sql.cluster.supervisor.stragglerFactor").doc(
    "Straggler demotion threshold: a worker whose median CBEAT "
    "heartbeat interval or per-stage wall exceeds this multiple of "
    "the fleet median is demoted below steal-delay placement "
    "preference (CDEMO — the same pressure-shed tier as "
    "scheduler.pressure.shedScore), and promoted back once it "
    "recovers under factor*0.5.").double(3.0)

CLUSTER_SUPERVISOR_STRAGGLER_MIN_SAMPLES = conf(
    "spark.rapids.sql.cluster.supervisor.stragglerMinSamples").doc(
    "Minimum per-worker samples (heartbeat intervals or stage walls) "
    "before the straggler detector may judge it — outlier math on "
    "two points demotes noise.").integer(5)

CLUSTER_SUPERVISOR_DRAIN_TIMEOUT_MS = conf(
    "spark.rapids.sql.cluster.supervisor.drainTimeoutMs").doc(
    "How long a drain (CDRAIN) may wait for the worker's in-flight "
    "stages to commit before the supervisor escalates to terminating "
    "the process anyway (the heartbeat sweep then requeues whatever "
    "was left RUNNING).").integer(30000)

BROADCAST_CACHE_ENABLED = conf(
    "spark.rapids.sql.broadcast.cache.enabled").doc(
    "Cluster-wide broadcast artifact cache: the first process to "
    "build a broadcast build-side publishes the built batch through "
    "the shuffle transport (keyed by plan fingerprint + upstream "
    "stage generations, same CRC-framed blob + "
    "manifest-as-publication-barrier + refetch-once contract as "
    "stage outputs), and every other worker fetches it instead of "
    "re-collecting and re-building the same table. Only active when "
    "a query runs under the cluster runtime; any cache miss or "
    "corruption falls back to the local build, never to a query "
    "error.").boolean(True)

BROADCAST_CACHE_FETCH_TIMEOUT_MS = conf(
    "spark.rapids.sql.broadcast.cache.fetchTimeoutMs").doc(
    "How long a broadcast-cache probe waits for a published manifest "
    "before declaring a miss and building locally. Deliberately "
    "short — the cache is an optimization, and the local build is "
    "always correct.").integer(50)

COST_CALIBRATION = conf("spark.rapids.sql.cost.calibration.enabled").doc(
    "Cost-model self-calibration (plan/cost.py): feed flight-recorder "
    "span timings (sync-category span means -> deviceSyncFloorMs, "
    "upload span bytes/wall -> deviceThroughputGBps) and the "
    "Cost@query estimateErrorPct back into the placement model as "
    "EWMA-updated effective constants, clamped to [1/4x, 4x] of the "
    "configured values — so placement tracks the machine it runs on "
    "instead of hand constants. An explicitly-set cost.* key always "
    "wins over the calibrated value. The SRT_COST_CALIBRATION env "
    "(0/1) overrides the default.").boolean(True)

COST_CALIBRATION_ALPHA = conf(
    "spark.rapids.sql.cost.calibration.alpha").doc(
    "EWMA weight of one query's observation when calibrating "
    "cost.{deviceSyncFloorMs,deviceThroughputGBps}.").double(0.2)

PLAN_CACHE_ENABLED = conf("spark.rapids.sql.planCache.enabled").doc(
    "Parameterized plan cache (plan/plan_cache.py): keep fully "
    "planned/fused/cost-placed physical plan templates in a "
    "process-global LRU keyed by the logical plan's structural "
    "fingerprint (literal VALUES hoisted into bind slots) + input "
    "schemas + the conf snapshot. A repeat execution with the same "
    "shape and new literals (filter constants, date ranges, limits) "
    "skips analysis/planning/fusion/cost placement entirely and binds "
    "its literals as runtime scalar kernel inputs, so compiled "
    "executables are shared across bindings too. Armed fault schedules "
    "bypass the cache; any conf change misses it. The SRT_PLAN_CACHE "
    "env (0/1) overrides the default for a whole process.").boolean(True)

PLAN_CACHE_MAX_ENTRIES = conf("spark.rapids.sql.planCache.maxEntries").doc(
    "LRU bound on the parameterized plan cache. Each entry pins one "
    "physical plan template (exec tree + tagged meta — no compiled "
    "kernels; those live in the kernel cache) plus, for in-memory "
    "sources, the source batches its key identifies.").integer(256)


class TpuConf:
    """Resolved view over a raw key->value dict (Spark SQL conf stand-in)."""

    def __init__(self, raw: Optional[Dict[str, Any]] = None):
        self.raw = dict(raw or {})
        self._version = 0

    @property
    def version(self) -> int:
        """Bumped on every set(); planners cache against it."""
        return self._version

    def get(self, entry: ConfEntry) -> Any:
        return entry.get(self)

    def get_key(self, key: str, default: Any = None) -> Any:
        entry = _REGISTRY.get(key)
        if entry is not None:
            return entry.get(self)
        return self.raw.get(key, default)

    def set(self, key: str, value: Any) -> "TpuConf":
        self.raw[key] = value
        self._version += 1
        return self

    def is_op_enabled(self, conf_key: str) -> bool:
        """Per-rule kill switch lookup; default True (ref: RapidsMeta confKey)."""
        raw = self.raw.get(conf_key)
        if raw is None:
            return True
        return raw if isinstance(raw, bool) else _parse_bool(str(raw))

    # Convenience accessors used widely.
    @property
    def sql_enabled(self) -> bool:
        return self.get(SQL_ENABLED)

    @property
    def explain(self) -> str:
        return str(self.get(EXPLAIN)).upper()

    @property
    def batch_size_rows(self) -> int:
        return self.get(BATCH_SIZE_ROWS)

    @property
    def batch_size_bytes(self) -> int:
        return self.get(BATCH_SIZE_BYTES)

    @property
    def incompatible_ops(self) -> bool:
        return self.get(INCOMPATIBLE_OPS)

    @property
    def test_enabled(self) -> bool:
        return self.get(TEST_ENABLED)


def generate_docs() -> str:
    """Render configs.md, same shape as the reference's generated docs."""
    lines = [
        "# spark-rapids-tpu Configuration",
        "",
        "Generated from spark_rapids_tpu.config — do not edit by hand.",
        "",
        "| Name | Description | Default |",
        "|---|---|---|",
    ]
    for e in registered_entries():
        if e.internal:
            continue
        default = "None" if e.default is None else str(e.default)
        lines.append(f"| {e.key} | {e.doc} | {default} |")
    lines += [
        "",
        "## Stage fusion",
        "",
        "With `spark.rapids.sql.stageFusion.enabled` (default true) the",
        "planner collapses maximal runs of contiguous, row-local, jittable",
        "device operators into a single `FusedStageExec` whose body is one",
        "composed batch->batch function compiled as ONE kernel — a",
        "Project->Filter->Project chain costs one XLA dispatch instead of",
        "three, with no materialized batch between the steps.",
        "",
        "What fuses: `ProjectExec`, `FilterExec`, `LocalLimitExec`,",
        "`ExpandExec` — operators whose device kernel is a pure",
        "batch-in/batch-out function.",
        "",
        "What breaks a stage: exchanges (shuffle/broadcast), aggregates,",
        "sorts, joins, windows, generate, scans, engine transitions",
        "(host<->device bridges), host-roundtrip expressions (regexp,",
        "pad/replace, python UDF fallbacks), and task-context expressions",
        "(`rand`, `spark_partition_id`, `monotonically_increasing_id`,",
        "`input_file_name`), which need the per-batch EvalContext the",
        "unfused operator threads.",
        "",
        "Fused kernels (and every other operator kernel) are compiled",
        "through the process-global kernel cache bounded by",
        "`spark.rapids.sql.kernelCache.maxEntries`, so re-running a query",
        "— every bench iteration, every serving request — re-traces",
        "nothing. Cache behavior is observable per operator via the",
        "`kernelCacheHits`/`kernelCacheMisses`/`compileTime` metrics and",
        "fused stages are rendered in `explain`/`pretty_tree` output with",
        "their member operator names.",
        "",
        "## Pipelined execution",
        "",
        "With `spark.rapids.sql.pipeline.enabled` (default true) every",
        "partition-loop dispatch funnel (driver collect, exchange",
        "map-side materialization, broadcast collection) runs through a",
        "bounded producer/consumer pipeline: a host thread pool",
        "(`pipeline.hostThreads`) executes the separable host half of",
        "each partition — scan-unit decode, filter-stat pruning, wire",
        "encode — up to `pipeline.prefetchPartitions` ahead, while a",
        "single ordered consumer performs all device dispatch under the",
        "TPU semaphore. Upload of partition p+1 overlaps compute of p;",
        "results are deterministically ordered and bit-identical to the",
        "serial path. Independent plan stages (e.g. the two exchange",
        "inputs of a shuffled join) additionally materialize",
        "concurrently, bounded by `pipeline.maxConcurrentStages`.",
        "`SRT_PIPELINE=0` (or the conf) restores the serial dispatch",
        "exactly. Overlap is observable via the `Pipeline@query` metrics",
        "entry and `parallel/pipeline.py counters()` (`hostPrefetchMs`,",
        "`consumerWaitMs`, `pipelineStalls`, `concurrentStages`,",
        "`overlapRatio`). See docs/performance.md for the overlap model",
        "and the interaction with the watchdog/recovery demotion ladder.",
        "",
        "## Ingest fast path: wire codec v2 & coalesced uploads",
        "",
        "`spark.rapids.sql.wire.codec` (default `v2`) selects the",
        "host->device wire codec (columnar/wire.py): per column, one",
        "cheap host stats pass picks the smallest LOSSLESS encoding",
        "among narrow-int / dictionary (v1's set), run-length (sorted or",
        "low-run-count columns), delta (monotone/smooth integers: int64",
        "base + narrow deltas, decoded by an exact jitted cumsum) and",
        "frame-of-reference (clustered ids: base + narrow unsigned",
        "offsets). Decodes are gathers, bitcasts and exact integer",
        "arithmetic only — never emulated-f64 math — so every mode is",
        "transport-transparent: `plain`, `v1` and `v2` produce",
        "bit-identical query results (the dual-engine parity suite and",
        "the SRT_WIRE_CODEC=plain CI matrix entry pin this).",
        "",
        "All of a batch's wire arrays pack into ONE contiguous",
        "8-byte-aligned staging buffer with a static offset table; an",
        "upload is one device_put call over that buffer's typed views",
        "(one transfer per wire array) plus one jitted decode program;",
        "consecutive encoded batches below",
        "`spark.rapids.sql.wire.minUploadBytes` share a call. The",
        "pack half runs on pipeline prefetch threads, so the ordered",
        "consumer only dispatches. `columnar/wire.py counters()` reports",
        "raw vs encoded bytes, per-codec column counts, call and",
        "transfer counts and the staging hit rate. See",
        "docs/performance.md.",
        "",
        "## Out-of-core grace hash joins",
        "",
        "`spark.rapids.sql.join.grace.enabled` (default true): a",
        "shuffled hash join whose build side exceeds",
        "`join.grace.buildFraction` of the device budget partitions",
        "BOTH sides by key fingerprint (the exchange's murmur3 hash",
        "partitioning) into spillable buckets and joins co-partitioned",
        "bucket pairs — peak HBM is one bucket's build side plus one",
        "probe batch, so a build side 2x+ the device budget runs",
        "ON-DEVICE instead of OOM-laddering to the host engine (beating",
        "the reference's RequireSingleBatch build restriction). Grace is",
        "also the OOM escalation rung directly ABOVE host fallback: a",
        "join whose single-batch build exhausts the spill/shrink ladder",
        "retries grace-partitioned first (`graceJoinEngaged`), and only",
        "a grace OOM demotes to host. `graceJoinPartitions` counts the",
        "buckets used, in per-operator metrics and the recovery block.",
        "",
        "## Robustness: fault injection & the recovery ladder",
        "",
        "Device OOMs at any dispatch funnel (upload, concat, cached",
        "kernel, download) walk a bounded escalation ladder instead of",
        "failing: spill-some -> spill-all -> shrink the batch target ->",
        "the operator's on-device degraded mode (a hash join retries",
        "grace-partitioned, `spark.rapids.sql.join.grace.enabled`) ->",
        "degrade the operator subtree to the host engine",
        "(`spark.rapids.sql.oom.hostFallback.enabled`). Execution-side",
        "failures demote through partition-scoped, then stage-scoped,",
        "then query-scoped recovery: the execution watchdog",
        "(`spark.rapids.sql.watchdog.*`) kills and re-dispatches a",
        "stalled partition; lineage-scoped stage recovery",
        "(`spark.rapids.sql.recovery.stageRecompute.enabled`) recomputes",
        "only the stage whose durable exchange output was lost or failed",
        "its checksum; transient backend errors retry first on",
        "the same context (materialized stages are reused) and only then",
        "re-run the whole query on a fresh context with exponential",
        "backoff, bounded by `spark.rapids.sql.retry.transientMaxRetries`.",
        "A failed mesh collective demotes that query's exchanges to the",
        "single-process shuffle path",
        "(`spark.rapids.sql.mesh.degrade.enabled`). Spilled frames",
        "carry a CRC32 checksum verified at deserialize, so corruption",
        "is detected (and re-read once) instead of decoding into wrong",
        "rows. The whole machinery is continuously exercised by",
        "deterministic fault injection (`spark.rapids.sql.test.faults` /",
        "`SRT_FAULTS`) — see docs/robustness.md, tests/test_chaos.py and",
        "tests/test_stage_recovery.py. Recovery counters",
        "(retriesAttempted, spillEscalations, hostFallbacks,",
        "faultsInjected, corruptionsDetected, stageRecomputes,",
        "partitionRetries, watchdogKills, meshDegrades,",
        "meshCollectiveSkipped, crossQueryEvictions) surface",
        "through `DataFrame.metrics()` and `faults.counters()`.",
        "",
        "## Shuffle transport SPI",
        "",
        "`spark.rapids.sql.shuffle.transport` selects where shuffle",
        "shards live (parallel/transport/, docs/shuffle.md):",
        "",
        "- `inprocess` (default) — the BufferCatalog-backed",
        "  single-process exchange: shards are spillable catalog",
        "  handles under the memory ladder.",
        "- `mesh` — hash shuffles lower to `jax.lax.all_to_all`",
        "  collectives over the device mesh (the ICI path; the legacy",
        "  `spark.rapids.sql.mesh.enabled` key still selects it).",
        "  Logical partition counts that differ from the mesh size FOLD",
        "  onto devices (`meshPartitionFolds`) instead of degrading.",
        "- `hostfile` — shards spool to a shared directory as",
        "  CRC-framed blobs with a manifest/socket rendezvous",
        "  (`shuffle.transport.hostfile.*` keys), so N independent",
        "  worker processes can map-write and reduce-fetch each",
        "  other's shards — the DCN multi-slice stand-in.",
        "",
        "All transports share the recovery contract: a lost or",
        "persistently-corrupt shard raises owner-tagged and costs ONE",
        "lineage-scoped stage recompute; a transiently-corrupt fetch",
        "refetches once (`remoteShardRefetches`). The",
        "`SRT_SHUFFLE_TRANSPORT` env overrides the default for a whole",
        "process (the CI matrix hook), and `Transport@query` metrics +",
        "`parallel/transport counters()` carry",
        "`transportBytesWritten/Fetched` and the recovery counters.",
        "",
        "## Multi-query admission, isolation & cancellation",
        "",
        "Concurrent `collect()`s from multiple threads run through the",
        "process-wide QueryManager (parallel/scheduler.py): at most",
        "`spark.rapids.sql.scheduler.maxConcurrentQueries` queries",
        "execute at once, excess queries wait FIFO in a run queue of",
        "`scheduler.queueDepth`, and a query arriving with the queue",
        "full — or waiting past `scheduler.admissionTimeoutMs` — is",
        "SHED with `QueryRejectedError` instead of oversubscribing the",
        "device. Each admitted query gets an owner id that tags every",
        "catalog buffer and kernel-cache reservation it creates, a",
        "fair-share device budget (`scheduler.queryMemoryFraction`),",
        "and a cooperative cancellation token:",
        "`DataFrame.collect(timeout_ms=...)` arms a deadline and",
        "`DataFrame.submit().cancel()` stops a query mid-flight — both",
        "unwind with `QueryCancelledError` at the next dispatch",
        "checkpoint, releasing the TPU semaphore and every owned buffer",
        "(the catalog leak report proves teardown freed everything).",
        "The OOM ladder spills the offending query's own buffers",
        "through two rungs before evicting neighbors",
        "(`crossQueryEvictions`), and query-scoped fault arming",
        "(`kind@site/query=N` with",
        "`spark.rapids.sql.test.faults.queryTag`) lets chaos tests",
        "prove a fault injected into one query is invisible to its",
        "neighbors. `SRT_SCHEDULER_MAX_CONCURRENT=1` degenerates to",
        "strictly serial queries, byte-identical to the pre-scheduler",
        "engine. See docs/robustness.md and tests/test_scheduler.py.",
        "",
        "## Serving QoS: priority classes, fair queueing, tenant quotas",
        "",
        "With `spark.rapids.sql.scheduler.qos.enabled` (default FALSE;",
        "`SRT_QOS=1` enables for a whole process) the QueryManager's",
        "FIFO run queue is replaced by the cost-aware QoS scheduler",
        "(parallel/qos/): queries carry a priority class —",
        "`interactive` / `batch` / `background`, from",
        "`scheduler.qos.priorityClass` or the `priority=` kwarg of",
        "`DataFrame.collect/submit` — and run slots are granted by",
        "weighted fair queueing over `scheduler.qos.weights` with a",
        "HARD starvation bound (`scheduler.qos.starvationBound`: after",
        "that many bypasses a starved class's head runs next,",
        "counter `starvationBoundEngagements`). Within a class,",
        "queries drain shortest-job-first by the plan/cost.py estimate",
        "(plan-cache hits reuse the template's CostReport, so ordering",
        "is free for repeat shapes). Tenants",
        "(`scheduler.qos.tenant` / the `tenant=` kwarg) get",
        "admission-time quotas: in-flight query caps",
        "(`tenantMaxInFlight`), owner-tagged catalog bytes",
        "(`tenantMaxCatalogBytes`), and a kernel-cache compile budget",
        "(`tenantMaxKernelCacheEntries`, enforced by evicting the",
        "tenant's oldest entries — `quotaEvictions`). A deadline armed",
        "via `collect(timeout_ms=...)` is additionally tested against",
        "the cost estimate AT ADMISSION",
        "(`qos.deadlineAdmission.enabled`): an unmeetable deadline",
        "rejects immediately instead of burning device time. Every",
        "rejection is a structured `QueryRejectedError` carrying",
        "`kind` (`queue-full` / `admission-timeout` / `tenant-quota` /",
        "`deadline-unmeetable`), a `queue_depth` snapshot, and a",
        "`retry_after_ms` hint derived from observed service times.",
        "Disabled, the scheduler is byte-for-byte the FIFO",
        "QueryManager (the `qos-on` tier-1 matrix entry proves the",
        "whole suite passes identically either way). See",
        "docs/serving.md and tests/test_qos.py for the model and the",
        "1000-query x 4-tenant soak contract.",
        "",
        "## Cost-based placement & adaptive re-planning",
        "",
        "With `spark.rapids.sql.cost.enabled` (default true) the planner",
        "estimates every logical subtree's device time (per-dispatch sync",
        "floor x sync count + bytes over the device pipeline) and host",
        "time (bytes over the host engine) from parquet/ORC footer stats",
        "and places whole maximal subtrees on the HOST engine when the",
        "host estimate strictly wins — inputs of a few MB cannot amortize",
        "the per-sync floor (0.9 ms measured on the attached v5e, PR 21;",
        "charged as zero on a CPU-only backend). The",
        "constants (`cost.deviceSyncFloorMs`, `cost.deviceThroughputGBps`,",
        "`cost.hostThroughputGBps`, `cost.maxHostBytes`) are",
        "conf-overridable; `cost.explain` renders per-node estimates;",
        "`SRT_COST=0` restores the legacy all-device planner for a whole",
        "process. Placement stands down in test mode, under an armed",
        "fault schedule, on non-inprocess transports, and for plans",
        "without a file scan.",
        "",
        "At runtime, `spark.rapids.sql.aqe.replan.enabled` (default true)",
        "re-plans mid-query from OBSERVED shuffle sizes: each shuffled",
        "hash join's build-side exchange materializes first, its",
        "transport session records exact per-partition bytes, and a build",
        "side within `autoBroadcastJoinThreshold` demotes the join to a",
        "broadcast hash join — the probe side never shuffles, the fusion",
        "pass re-runs over the rewritten subtree, and lineage recovery",
        "still covers the re-planned stages. Post-shuffle coalescing",
        "merges partitions while BOTH `aqe.coalescePartitions.targetRows`",
        "and `aqe.coalescePartitions.targetBytes` hold. Decisions and",
        "estimate-vs-actual error surface in the `Cost@query` metrics",
        "entry and `plan/cost.py counters()`. See docs/performance.md.",
        "",
        "## Parameterized plan cache",
        "",
        "With `spark.rapids.sql.planCache.enabled` (default true;",
        "`SRT_PLAN_CACHE=0` disables for a whole process) every",
        "`collect()` first rewrites its logical plan's bindable literal",
        "leaves (numeric/bool/date operands of comparisons and",
        "arithmetic in filters and projections, plus `limit(n)` values)",
        "into positional BIND SLOTS, then looks the parameterized shape",
        "up in a process-global LRU keyed by (structural plan",
        "fingerprint, input schemas, conf snapshot). A hit skips",
        "analysis, planning, capability tagging, fusion and cost",
        "placement entirely — the cached physical template executes with",
        "this call's literals bound as runtime scalar kernel inputs, so",
        "kernel-cache fingerprints (and compiled XLA executables) are",
        "shared across bindings and a re-parameterized query re-traces",
        "nothing. Per-query state (ExecContext, owner tags, AQE replan",
        "decisions, trace rings) stays per-execution. Invalidation is",
        "conservative: ANY conf change, schema change, or armed fault",
        "schedule misses or bypasses the cache. `explain()` annotates",
        "provenance (`[plan-cache hit, bind-only]`), `DataFrame.prepare()`",
        "returns the bound template as an explicit prepared-statement",
        "handle, and `scripts/warmup.py` replays a shape manifest so a",
        "fresh process serves its first query without the cold-compile",
        "cliff. Counters (planCacheHits/Misses/bindOnlyExecutions) land",
        "in `plan/plan_cache.py counters()` and per-tenant on the",
        "`Scheduler@query` metrics entry. See docs/performance.md.",
        "",
        "## Query flight recorder",
        "",
        "With `spark.rapids.sql.trace.enabled` (or `SRT_TRACE=1`) every",
        "execution funnel records structured spans — scheduler admission",
        "queue, TPU-semaphore acquire, host prefetch, wire pack, upload,",
        "per-operator device dispatch, shuffle materialize/serve, stage",
        "prematerialization, result download — and instant events (fault",
        "injected, OOM rung, stage recompute, join demotion, watchdog",
        "kill, cancellation, cross-query eviction) into a bounded",
        "per-query ring buffer (`trace.maxEvents`; `trace.level` picks",
        "query < operator < kernel verbosity). Consumers:",
        "`DataFrame.trace_export(path)` writes Chrome trace-event JSON",
        "(Perfetto / chrome://tracing, one track per query and worker",
        "thread), `DataFrame.explain_analyze()` renders the plan tree",
        "with observed rows/bytes/wall next to the cost model's",
        "estimates, `monitoring.snapshot()` aggregates the span-category",
        "breakdown.",
        "Disabled, the recorder is a shared no-op costing nanoseconds",
        "per call site — results and metrics are byte-identical either",
        "way. See docs/observability.md.",
        "",
        "## Live telemetry & history",
        "",
        "With `spark.rapids.sql.metrics.enabled` (default false;",
        "`SRT_METRICS=1` env override) every process keeps a typed",
        "metric registry — counters, gauges and sliding-window",
        "histograms with p50/p95/p99 — fed from the existing",
        "scheduler/memory/cache/shuffle counter funnels plus per-query",
        "labeled series (status, QoS class, tenant, rejection kind).",
        "`spark.rapids.sql.metrics.port` (default 0 = off) additionally",
        "serves the registry in OpenMetrics text format on a",
        "localhost-only HTTP endpoint (`/metrics`, `/healthz`) for",
        "Prometheus-style scraping; `telemetry.snapshot()` and",
        "`telemetry.render_text()` expose the same view in-process",
        "with zero dependencies. In cluster mode workers piggyback",
        "metric deltas on their heartbeats, so the coordinator process",
        "scrapes a fleet view with per-worker labels.",
        "",
        "`spark.rapids.sql.eventLog.dir` (default empty = off;",
        "`SRT_EVENT_LOG` env override) appends one JSONL record per",
        "query at teardown — status, class, tenant, plan fingerprint,",
        "per-node observed rows/bytes/wall, span-category breakdown and",
        "recovery instants. `scripts/history.py` reconstructs",
        "explain_analyze-style reports and a fleet summary from the log",
        "alone after every process has exited. Both gates are",
        "exposition-only: disabled (the default) the hot paths reduce",
        "to a single global load, and results are byte-identical either",
        "way. See docs/observability.md.",
        "",
        "## Dynamic per-rule kill switches",
        "",
        "Beyond the registered keys, every planner rule accepts a boolean",
        "kill switch (RapidsMeta confKey analog, default true):",
        "",
        "- `spark.rapids.sql.exec.<ExecName>` — disable one physical",
        "  operator (e.g. `spark.rapids.sql.exec.LogicalJoin`); the plan",
        "  falls back to the host engine there with an explain reason.",
        "- `spark.rapids.sql.expression.<kind>` — disable one expression",
        "  kind (e.g. `spark.rapids.sql.expression.upper`); the enclosing",
        "  operator falls back with a reason naming the expression.",
    ]
    return "\n".join(lines) + "\n"
