"""DataFrame API frontend (SURVEY.md §7 "accept ... a direct DataFrame API
for standalone benchmarking"; shapes mirror pyspark.sql).

``TpuSession`` is the SparkSession analog: holds the conf, builds
DataFrames from memory/files/range, and plans queries through the
tag->convert rewrite (plan/planner.py). ``DataFrame.collect`` executes on
the device engine with host islands where the planner tagged fallbacks;
``DataFrame.explain`` prints the will/will-not-run-on-TPU report.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple, Union

from spark_rapids_tpu import config as C
from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.host import HostBatch
from spark_rapids_tpu.plan import logical as L
from spark_rapids_tpu.plan.logical import Column, col, lit_col
from spark_rapids_tpu.plan.planner import Planner


class TpuSession:
    """Session: conf + DataFrame builders (SparkSession analog)."""

    def __init__(self, conf: Optional[Dict] = None):
        self.conf = C.TpuConf(conf)

    # -- conf ----------------------------------------------------------------
    def set(self, key: str, value) -> "TpuSession":
        self.conf.set(key, value)
        return self

    # -- builders ------------------------------------------------------------
    def create_dataframe(self, data: Union[Dict, List[tuple]],
                         schema: Sequence[Tuple[str, dt.DataType]],
                         num_partitions: int = 1) -> "DataFrame":
        schema = tuple(schema)
        if isinstance(data, dict):
            rows = list(zip(*[data[n] for n, _ in schema])) \
                if data else []
        else:
            rows = list(data)
        per = max(1, -(-len(rows) // num_partitions)) if rows else 1
        parts = []
        for i in range(num_partitions):
            chunk = rows[i * per:(i + 1) * per]
            cols = {n: [r[ci] for r in chunk]
                    for ci, (n, _) in enumerate(schema)}
            parts.append([HostBatch.from_pydict(schema, cols)])
        return DataFrame(self, L.InMemoryScan(schema, parts))

    def range(self, start: int, end: Optional[int] = None, step: int = 1,
              num_partitions: int = 1) -> "DataFrame":
        if end is None:
            start, end = 0, start
        return DataFrame(self, L.LogicalRange(start, end, step,
                                              num_partitions))

    def ingest_spark_plan(self, plan_text: str, table_paths):
        """Plugin mode: parse a CAPTURED Spark physical plan (the text of
        ``df.explain()`` from a real cluster) and run it on this engine.
        ``table_paths`` maps table names (matched against the captured
        scan locations) to local data paths. See plan/spark_ingest.py."""
        from spark_rapids_tpu.plan.spark_ingest import ingest_spark_plan
        return ingest_spark_plan(plan_text, self, table_paths)

    @property
    def read(self) -> "DataFrameReader":
        return DataFrameReader(self)


class DataFrameReader:
    def __init__(self, session: TpuSession):
        self._session = session
        self._options: Dict = {}

    def option(self, key: str, value) -> "DataFrameReader":
        self._options[key] = value
        return self

    def _scan(self, fmt: str, paths) -> "DataFrame":
        from spark_rapids_tpu import monitoring
        from spark_rapids_tpu.io import infer_schema
        if isinstance(paths, str):
            paths = [paths]
        # Opens a footer per table, each time a query is built anew.
        with monitoring.span("infer-schema", "planning"):
            schema = infer_schema(fmt, paths, self._options)
        return DataFrame(self._session,
                         L.FileScan(fmt, list(paths), schema,
                                    dict(self._options)))

    def parquet(self, *paths) -> "DataFrame":
        return self._scan("parquet", list(paths))

    def csv(self, *paths) -> "DataFrame":
        return self._scan("csv", list(paths))

    def orc(self, *paths) -> "DataFrame":
        return self._scan("orc", list(paths))


class GroupedData:
    def __init__(self, df: "DataFrame", keys: Sequence[Union[str, Column]],
                 grouping: Optional[str] = None):
        self._df = df
        self._keys = [(k, col(k)) if isinstance(k, str)
                      else (k.name_hint, k) for k in keys]
        self._grouping = grouping

    def agg(self, *aggs: Column, **named: Column) -> "DataFrame":
        specs = []
        for a in aggs:
            specs.append((self._agg_name(a), a))
        for name, a in named.items():
            specs.append((name, a))
        plan = L.LogicalAggregate(self._df._plan, self._keys, specs,
                                  grouping=self._grouping)
        return DataFrame(self._df._session, plan)

    @staticmethod
    def _agg_name(a: Column) -> str:
        node = a.node
        if node[0] == "alias":
            return node[2]
        if node[0] == "agg":
            kind = node[1]
            child = node[2]
            base = child.name_hint if child is not None else "1"
            return f"{kind}({base})"
        return node[0]

    def count(self) -> "DataFrame":
        from spark_rapids_tpu.plan.logical import agg_count
        return self.agg(agg_count().alias("count"))

    # -- pandas-UDF flavors (GpuFlatMapGroupsInPandasExec family) ---------
    def _key_names(self) -> List[str]:
        names = []
        for hint, c in self._keys:
            if c.node[0] != "ref":
                raise ValueError(
                    "pandas group flavors need plain column-name keys")
            names.append(c.node[1])
        return names

    def apply_in_pandas(self, fn, schema) -> "DataFrame":
        """fn(group: pandas.DataFrame) -> pandas.DataFrame, one call per
        group (Spark applyInPandas; GpuFlatMapGroupsInPandasExec)."""
        plan = L.LogicalGroupedMapInPandas(
            self._df._plan, self._key_names(), fn, tuple(schema))
        return DataFrame(self._df._session, plan)

    applyInPandas = apply_in_pandas

    def agg_in_pandas(self, **named) -> "DataFrame":
        """GROUPED_AGG pandas UDFs: each kwarg is
        ``out_name=(input_column, series_fn, result_type)`` where
        series_fn(pandas.Series) -> scalar (GpuAggregateInPandasExec)."""
        aggs = [(out, colname, fn, t)
                for out, (colname, fn, t) in named.items()]
        plan = L.LogicalAggInPandas(self._df._plan, self._key_names(),
                                    aggs)
        return DataFrame(self._df._session, plan)

    def cogroup(self, other: "GroupedData") -> "CoGroupedData":
        return CoGroupedData(self, other)


class CoGroupedData:
    """Pair of grouped frames for cogrouped pandas application
    (Spark's PandasCogroupedOps; GpuCoGroupedMapInPandasExec)."""

    def __init__(self, left: GroupedData, right: GroupedData):
        self._left = left
        self._right = right

    def apply_in_pandas(self, fn, schema) -> "DataFrame":
        """fn(left_group: pdf, right_group: pdf) -> pdf per key in the
        union of both sides' key sets (absent side = empty frame)."""
        plan = L.LogicalCoGroupedMapInPandas(
            self._left._df._plan, self._right._df._plan,
            self._left._key_names(), self._right._key_names(),
            fn, tuple(schema))
        return DataFrame(self._left._df._session, plan)

    applyInPandas = apply_in_pandas


class DataFrame:
    def __init__(self, session: TpuSession, plan: L.LogicalPlan):
        self._session = session
        self._plan = plan

    # -- schema ---------------------------------------------------------------
    @property
    def schema(self):
        return self._plan.schema

    @property
    def columns(self) -> List[str]:
        return [n for n, _ in self.schema]

    # -- transformations ------------------------------------------------------
    def filter(self, condition: Column) -> "DataFrame":
        return DataFrame(self._session,
                         L.LogicalFilter(self._plan, condition))

    where = filter

    def _project(self, projections) -> "DataFrame":
        """Build a projection, extracting window expressions into a chain
        of LogicalWindow nodes first (ExtractWindowExpressions analog)."""
        plan = self._plan
        out = []
        for i, (name, c) in enumerate(projections):
            if L.is_window_column(c):
                node = c.node
                while node[0] == "alias":
                    node = node[1].node
                _, fn_col, windef = node
                tmp = f"__window_{i}_{name}"
                plan = L.LogicalWindow(plan, [(tmp, fn_col)], windef)
                out.append((name, col(tmp)))
            elif L.is_generate_column(c):
                node = c.node
                while node[0] == "alias":
                    node = node[1].node
                _, elements, position, outer = node
                plan = L.LogicalGenerate(plan, name, list(elements),
                                         position, outer)
                if position:
                    out.append((f"{name}__pos", col(f"{name}__pos")))
                out.append((name, col(name)))
            else:
                out.append((name, c))
        return DataFrame(self._session, L.LogicalProject(plan, out))

    def select(self, *cols_: Union[str, Column]) -> "DataFrame":
        projections = []
        for c in cols_:
            if isinstance(c, str):
                projections.append((c, col(c)))
            else:
                projections.append((c.name_hint, c))
        return self._project(projections)

    def with_column(self, name: str, c: Column) -> "DataFrame":
        # Replace in place like pyspark's withColumn; append when new.
        if name in self.columns:
            projections = [(n, c if n == name else col(n))
                           for n in self.columns]
        else:
            projections = [(n, col(n)) for n in self.columns]
            projections.append((name, c))
        return self._project(projections)

    withColumn = with_column

    def map_in_pandas(self, fn, schema) -> "DataFrame":
        """fn(iterator of pandas DataFrames) -> iterator of DataFrames
        (Spark mapInPandas; GpuMapInPandasExec analog)."""
        plan = L.LogicalMapInPandas(self._plan, fn, tuple(schema))
        return DataFrame(self._session, plan)

    mapInPandas = map_in_pandas

    def group_by(self, *keys: Union[str, Column]) -> GroupedData:
        return GroupedData(self, keys)

    groupBy = group_by

    def rollup(self, *keys: Union[str, Column]) -> GroupedData:
        """GROUP BY ROLLUP: hierarchical subtotals via ExpandExec
        (GpuExpandExec.scala)."""
        return GroupedData(self, keys, grouping="rollup")

    def cube(self, *keys: Union[str, Column]) -> GroupedData:
        """GROUP BY CUBE: all key-subset subtotals via ExpandExec."""
        return GroupedData(self, keys, grouping="cube")

    def agg(self, *aggs: Column, **named: Column) -> "DataFrame":
        return GroupedData(self, []).agg(*aggs, **named)

    def order_by(self, *orders: Union[str, Column]) -> "DataFrame":
        os_ = [col(o) if isinstance(o, str) else o for o in orders]
        return DataFrame(self._session, L.LogicalSort(self._plan, os_))

    orderBy = order_by
    sort = order_by

    def limit(self, n: int) -> "DataFrame":
        return DataFrame(self._session, L.LogicalLimit(self._plan, n))

    def union(self, other: "DataFrame") -> "DataFrame":
        return DataFrame(self._session,
                         L.LogicalUnion(self._plan, other._plan))

    unionAll = union

    def repartition(self, n: int, *keys: Union[str, Column]) -> "DataFrame":
        ks = [col(k) if isinstance(k, str) else k for k in keys] or None
        return DataFrame(self._session,
                         L.LogicalRepartition(self._plan, n, ks))

    def join(self, other: "DataFrame", on: Union[str, Sequence[str], tuple],
             how: str = "inner", condition: Optional[Column] = None,
             strategy: str = "auto") -> "DataFrame":
        if isinstance(on, str):
            on = [on]
        lkeys = [col(k) if isinstance(k, str) else k for k in on]
        rkeys = list(lkeys)
        plan = L.LogicalJoin(self._plan, other._plan, lkeys, rkeys,
                             how, condition, strategy)
        return DataFrame(self._session, plan)

    def join_on(self, other: "DataFrame",
                left_on: Sequence[Union[str, Column]],
                right_on: Sequence[Union[str, Column]],
                how: str = "inner", condition: Optional[Column] = None,
                strategy: str = "auto") -> "DataFrame":
        lkeys = [col(k) if isinstance(k, str) else k for k in left_on]
        rkeys = [col(k) if isinstance(k, str) else k for k in right_on]
        plan = L.LogicalJoin(self._plan, other._plan, lkeys, rkeys,
                             how, condition, strategy)
        return DataFrame(self._session, plan)

    def cross_join(self, other: "DataFrame") -> "DataFrame":
        plan = L.LogicalJoin(self._plan, other._plan, [], [], "cross")
        return DataFrame(self._session, plan)

    crossJoin = cross_join

    # -- actions --------------------------------------------------------------
    def _physical(self):
        # Plan once per (DataFrame, conf version); the process-global
        # parameterized plan cache (plan/plan_cache.py) additionally
        # shares fully planned templates ACROSS DataFrames of the same
        # shape — a repeat query with new literals binds against the
        # cached template instead of re-planning and re-tracing.
        key = self._session.conf.version
        cached = getattr(self, "_phys_cache", None)
        if cached is not None and cached[0] == key:
            return cached[1]
        from spark_rapids_tpu.plan.plan_cache import plan_or_bind
        phys = plan_or_bind(self._session.conf, self._plan)
        self._phys_cache = (key, phys)
        return phys

    def prepare(self):
        """Explicit prepared-statement handle: plan NOW (or bind
        against the process-global plan cache) and return the bound
        plan — its ``collect()``/``explain()`` skip all planning work,
        and ``cache_hit``/``bind_values`` expose the plan-cache
        provenance. Useful for warming a serving tier's shapes before
        traffic arrives (scripts/warmup.py drives this)."""
        return self._physical()

    def collect(self, timeout_ms: Optional[float] = None,
                priority: Optional[str] = None,
                tenant: Optional[str] = None) -> List[tuple]:
        """Run the query through the multi-query scheduler
        (parallel/scheduler.py). ``timeout_ms`` arms a deadline: a query
        still running when it expires unwinds cooperatively at its next
        dispatch checkpoint with ``QueryCancelledError`` (reason
        "deadline exceeded"), releasing the TPU semaphore and every
        owned buffer. Raises ``QueryRejectedError`` when the scheduler's
        run queue is full (load shed) or admission times out.

        With the QoS subsystem enabled (scheduler.qos.enabled),
        ``priority`` picks the query's class ("interactive" / "batch" /
        "background"), ``tenant`` tags it for per-tenant quotas, and
        ``timeout_ms`` additionally acts as a deadline tested against
        the cost estimate at admit time (kind "deadline-unmeetable").
        Both default from conf (qos.priorityClass / qos.tenant)."""
        return self._physical().collect(timeout_ms=timeout_ms,
                                        priority=priority, tenant=tenant)

    def collect_with_retry(self, timeout_ms: Optional[float] = None,
                           priority: Optional[str] = None,
                           tenant: Optional[str] = None,
                           max_attempts: Optional[int] = None,
                           max_backoff_ms: Optional[float] = None,
                           seed: int = 0) -> List[tuple]:
        """:meth:`collect` behind the obedient-client backpressure loop
        (parallel/scheduler.collect_with_retry): a
        ``QueryRejectedError`` carrying a ``retry_after_ms`` hint backs
        off for the hinted interval (deterministic per-``seed`` jitter,
        capped at ``client.retry.maxBackoffMs``) and resubmits, up to
        ``client.retry.maxAttempts`` attempts; hintless rejections
        re-raise immediately. This is the call a sustained serving
        client should make — a herd of them converges onto the
        scheduler's observed service rate instead of hammering a full
        queue."""
        from spark_rapids_tpu.parallel import scheduler as SC
        return SC.collect_with_retry(
            lambda: self.collect(timeout_ms=timeout_ms,
                                 priority=priority, tenant=tenant),
            conf=self._session.conf, max_attempts=max_attempts,
            max_backoff_ms=max_backoff_ms, seed=seed)

    def submit(self, timeout_ms: Optional[float] = None,
               priority: Optional[str] = None,
               tenant: Optional[str] = None):
        """Async collect: returns a ``QueryHandle`` whose ``cancel()``
        stops the query cooperatively — while it is still queued for
        admission or mid-flight — and whose ``result()`` returns the
        rows or re-raises the query's error. ``priority``/``tenant``
        feed QoS scheduling exactly as in :meth:`collect`."""
        from spark_rapids_tpu.parallel.scheduler import QueryHandle
        phys = self._physical()

        def run(cancel_event, tmo):
            return phys.collect(timeout_ms=tmo, cancel_event=cancel_event,
                                priority=priority, tenant=tenant)

        return QueryHandle(run, timeout_ms)

    def _host_physical(self):
        """Re-plan with sql.enabled off (the host fallback engine — no
        device bridges). Shared by collect_host and gated writes."""
        import spark_rapids_tpu.config as C
        host_conf = C.TpuConf(dict(self._session.conf.raw))
        host_conf.set("spark.rapids.sql.enabled", False)
        return Planner(host_conf).plan(self._plan)

    def collect_host(self) -> List[tuple]:
        """Run entirely on the host oracle engine (CPU-Spark stand-in)."""
        phys = self._host_physical()
        from spark_rapids_tpu.ops.base import ExecContext
        return phys.root.collect(ExecContext(phys.conf), device=False)

    def count_rows(self) -> int:
        return len(self.collect())

    def explain(self, mode: str = "ALL") -> str:
        report = self._physical().explain(mode)
        print(report)
        return report

    def explain_analyze(self) -> str:
        """The plan tree annotated with OBSERVED per-operator
        rows/bytes/wall-ms next to the cost model's per-node estimates
        and the estimate error — the estimate-vs-actual feedback the
        cost calibration needs (monitoring/analyze.py). Reads the LAST
        collect() on this DataFrame; collects once if none ran yet."""
        phys = self._physical()
        if getattr(phys, "last_ctx", None) is None:
            self.collect()
        from spark_rapids_tpu.monitoring.analyze import render
        report = render(phys, getattr(phys, "last_ctx", None))
        # Plan provenance: a cache-hit (bind-only) execution must not
        # silently look identical to a freshly planned one.
        prov = getattr(phys, "provenance", None)
        if prov:
            report = f"[{prov}]\n{report}"
        print(report)
        return report

    def trace_export(self, path: Optional[str] = None) -> dict:
        """Export the flight recorder's Chrome trace-event JSON (loads
        in Perfetto / chrome://tracing): one track per query — this
        DataFrame's last collect AND whatever ran concurrently — and
        one per worker thread. Requires ``spark.rapids.sql.trace.enabled``
        (or SRT_TRACE=1) during the collect; returns the trace document
        and writes it to ``path`` when given.

        After a cluster collect, the workers' trace rings (shipped back
        on stage completion) merge into this SAME document under their
        own per-worker process tracks — one file shows the driver's
        dispatch wait next to each worker's stage execution."""
        from spark_rapids_tpu import monitoring
        phys = self._physical()
        ctx = getattr(phys, "last_ctx", None)
        workers = ctx.cache.get("cluster_worker_events") \
            if ctx is not None else None
        if not workers:
            return monitoring.export_chrome(path)
        from spark_rapids_tpu.monitoring.chrome import to_chrome_cluster
        doc = to_chrome_cluster(monitoring.events(),
                                monitoring.thread_names(), workers,
                                monitoring.process_tag())
        if path:
            import json
            with open(path, "w") as f:
                json.dump(doc, f)
        return doc

    def to_pandas(self):
        import pandas as pd
        rows = self.collect()
        return pd.DataFrame(rows, columns=self.columns)

    def to_jax(self):
        """ML hand-off: run the plan on the device engine and return the
        result as ``{column_name: jax.Array}`` WITHOUT a host round trip
        (ColumnarRdd.scala:41-49 / InternalColumnarRddConverter analog —
        the reference exports cuDF device tables to GPU ML; here the
        arrays stay resident in HBM for jax models to consume).

        Numeric/bool/date columns come back as 1-D arrays of exactly the
        live row count; strings as (rows, width) uint8 byte matrices
        under ``name`` plus ``name + '__len'`` length vectors. Nulls are
        not representable in a raw array — columns with any null raise
        (fill or drop them in the query first)."""
        import jax as _jax
        import jax.numpy as jnp
        import spark_rapids_tpu.config as C
        from spark_rapids_tpu.columnar.batch import (
            bucket_capacity, concat_batches)
        from spark_rapids_tpu.memory.oom import set_active_catalog
        from spark_rapids_tpu.memory.stores import get_tpu_semaphore
        from spark_rapids_tpu.ops.base import ExecContext
        phys = self._physical()
        assert phys.root_on_device, \
            "to_jax needs a device plan (sql.enabled off?)"
        ctx = ExecContext(phys.conf)
        ctx.cache.setdefault("engine", "device")
        install = getattr(phys, "install", None)
        if install is not None:     # bound plan: thread the literals in
            install(ctx)
        root = phys.root
        # Same device-admission + OOM-recovery regime as collect():
        # the semaphore bounds concurrent device users, the registered
        # catalog lets dispatch sites spill-and-retry.
        sem = get_tpu_semaphore(
            max(int(phys.conf.get(C.CONCURRENT_TPU_TASKS)), 1))
        try:
            with sem:
                set_active_catalog(ctx.catalog)
                try:
                    batches = []
                    for p in range(root.num_partitions(ctx)):
                        batches.extend(
                            root.execute_device_recovering(ctx, p))
                    if not batches:
                        return self._empty_jax(root.schema)
                    single = batches[0] if len(batches) == 1 else \
                        concat_batches(
                            batches, bucket_capacity(
                                sum(b.capacity for b in batches)))
                    from spark_rapids_tpu.columnar.rowmove import \
                        compact_batch
                    from spark_rapids_tpu.ops import kernel_cache as kc
                    fn = kc.lookup("compact-batch", (),
                                   lambda: _jax.jit(compact_batch))
                    single = fn(single)
                    n = int(single.live_count())
                finally:
                    set_active_catalog(None)
        finally:
            phys.last_ctx = ctx
            ctx.close()
        out = {}
        for (name, t), c in zip(root.schema, single.columns):
            if not bool(jnp.all(c.validity[:n])):
                raise ValueError(
                    f"to_jax: column {name!r} contains nulls; fill or "
                    f"filter them before exporting")
            if t.is_string:
                out[name] = c.data[:n]
                out[name + "__len"] = c.lengths[:n]
            else:
                out[name] = c.data[:n]
        return out

    @staticmethod
    def _empty_jax(schema):
        """Typed empty export: dtypes and the string matrix/length layout
        must match the non-empty contract."""
        import jax.numpy as jnp
        out = {}
        for name, t in schema:
            if t.is_string:
                out[name] = jnp.zeros((0, 8), jnp.uint8)
                out[name + "__len"] = jnp.zeros((0,), jnp.int32)
            else:
                out[name] = jnp.zeros((0,), t.np_dtype)
        return out

    _METRIC_LEVELS = {
        "ESSENTIAL": {"numOutputRows", "totalTime"},
        "MODERATE": {"numOutputRows", "totalTime", "numOutputBatches",
                     "shuffleTime", "bufferTime"},
    }

    def metrics(self):
        """Per-operator metrics of the LAST collect() on this DataFrame
        (GpuExec.scala:27-56 registry; empty before any action).
        ``spark.rapids.sql.metrics.level`` filters verbosity."""
        import spark_rapids_tpu.config as C
        phys = self._physical()
        ctx = getattr(phys, "last_ctx", None)
        if ctx is None:
            return {}
        level = str(self._session.conf.get(C.METRICS_LEVEL)).upper()
        keep = self._METRIC_LEVELS.get(level)
        # Audit-group entries (Recovery/Pipeline/Scheduler/Transport/
        # Cost @query — stageRecomputes, overlapRatio, queuedMs,
        # remoteShardRefetches, joinDemotions...) are audit trails,
        # never filtered by verbosity level. The exemption set lives in
        # ONE registry (ops/base.py audit_metric_groups) that every
        # subsystem's query_metrics_entry() feeds — not in per-call-site
        # tuples here.
        from spark_rapids_tpu.ops.base import audit_metric_groups
        exempt = audit_metric_groups()
        return {k: {name: v for name, v in m.settle().values.items()
                    if keep is None or name in keep
                    or m.owner in exempt}
                for k, m in ctx.metrics.items()}

    # -- writes ---------------------------------------------------------------
    @property
    def write(self):
        from spark_rapids_tpu.io.writer import DataFrameWriter
        return DataFrameWriter(self)
