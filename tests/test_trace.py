"""Query flight recorder (ISSUE 9): span well-formedness, concurrent
attribution, chaos instants, trace-off bit-identity, Chrome export, and
explain_analyze.

Contract under test:
- every span begin has an end (open_span_count == 0 after a collect),
  durations are non-negative, and same-thread spans nest properly;
- a query's events land in ITS ring (the scheduler admission id), both
  serial and for two concurrent queries;
- injected oom/transient/lostshard schedules appear as ``fault-injected``
  / ``stage-recompute`` instants in the owning query's ring while the
  results stay bit-identical to the fault-free run;
- ``trace.enabled=false`` leaves results and metrics byte-identical and
  the recorder records nothing (the no-op path);
- ``trace_export`` emits Chrome trace-event JSON with the
  scheduler-queue / host-prefetch / device-compute / upload / shuffle
  categories on per-query, per-thread tracks;
- ``explain_analyze`` renders observed rows/bytes/wall next to the cost
  model's estimates with a per-node error;
- (PR 25) every span has an id and a parent: a collect's events form one
  tree, work handed to a pool thread keeps its cause, ``self_times``
  counts each ms once, and every enabled span is also a profiler
  annotation on the device trace's clock (none with the recorder off).
"""

import json

import pytest

from spark_rapids_tpu import faults, monitoring
from spark_rapids_tpu.api.dataframe import TpuSession
from spark_rapids_tpu.benchmarks import suites, tpch


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("tpch_trace"))
    tpch.generate(d, scale=0.003, files_per_table=3, seed=7)
    return d


@pytest.fixture(scope="module")
def suites_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("suites_trace"))
    suites.generate(d, scale=0.01, files_per_table=2)
    return d


@pytest.fixture(autouse=True)
def clean_state():
    faults.configure("")
    faults.reset_counters()
    monitoring.reset()
    yield
    monitoring.configure(False)
    monitoring.reset()


def _session(trace: bool = True, chaos: str = "", scan_cache: bool = True):
    s = TpuSession()
    s.set("spark.rapids.sql.variableFloatAgg.enabled", True)
    s.set("spark.rapids.sql.trace.enabled", trace)
    s.set("spark.rapids.sql.test.faults", chaos)
    s.set("spark.rapids.sql.test.faults.seed", 7)
    s.set("spark.rapids.sql.retry.backoffMs", 1)
    if chaos or not scan_cache:
        s.set("spark.rapids.sql.format.scanCache.maxBytes", 0)
    return s


def _query_events(df):
    """The traced query's own ring (attribution by admission id)."""
    ctx = df._physical().last_ctx
    qid = ctx.cache["trace_query"]
    return qid, monitoring.events(qid)


def _spans(evs):
    return [e for e in evs if e[0] == "X"]


def _instants(evs):
    return [e for e in evs if e[0] == "i"]


def _assert_well_formed(evs):
    assert monitoring.open_span_count() == 0, "unclosed span(s)"
    spans = _spans(evs)
    assert spans, "no spans recorded"
    for e in spans:
        assert e[3] >= 0 and e[4] >= 0, f"bad interval in {e!r}"
    # Same-thread spans must nest like a call stack: sort by (start,
    # -duration) and check each span closes within its enclosing one.
    by_tid = {}
    for e in spans:
        by_tid.setdefault(e[5], []).append(e)
    for tid, ss in by_tid.items():
        stack = []
        for e in sorted(ss, key=lambda e: (e[3], -e[4])):
            t0, t1 = e[3], e[3] + e[4]
            while stack and stack[-1] <= t0:
                stack.pop()
            if stack:
                assert t1 <= stack[-1], \
                    f"span {e[1]!r} partially overlaps its parent " \
                    f"(tid {tid})"
            stack.append(t1)


# ---------------------------------------------------------------------------
# Well-formedness: serial queries
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("qname", ["q1", "q6", "q3"])
def test_spans_well_formed_serial(qname, data_dir):
    df = tpch.QUERIES[qname](_session(), data_dir)
    df.collect()
    qid, evs = _query_events(df)
    assert qid > 0        # managed query: admission issued an id
    _assert_well_formed(evs)
    # Exactly one top-level collect span, and it brackets every
    # partition span of this query.
    collects = [e for e in _spans(evs)
                if e[1] == "collect" and e[2] == "query"]
    assert len(collects) == 1
    c0, c1 = collects[0][3], collects[0][3] + collects[0][4]
    parts = [e for e in _spans(evs) if e[1] == "partition"]
    assert parts
    for e in parts:
        assert c0 <= e[3] and e[3] + e[4] <= c1
    # Every event in the ring is attributed to this query.
    assert {e[6] for e in evs} == {qid}


def test_disabled_recorder_records_nothing(data_dir):
    df = tpch.QUERIES["q1"](_session(trace=False), data_dir)
    df.collect()
    assert monitoring.events() == []
    assert not monitoring.enabled()
    # The disabled span path returns the shared no-op (no allocation).
    s1 = monitoring.span("a", "b")
    s2 = monitoring.span("c", "d")
    assert s1 is s2


# ---------------------------------------------------------------------------
# Concurrent queries: per-query attribution
# ---------------------------------------------------------------------------

def test_two_concurrent_queries_attributed(data_dir):
    df_a = tpch.QUERIES["q6"](_session(), data_dir)
    df_b = tpch.QUERIES["q1"](_session(), data_dir)
    want_a = df_a.collect()
    want_b = df_b.collect()
    monitoring.reset()
    ha, hb = df_a.submit(), df_b.submit()
    assert ha.result(120) == want_a
    assert hb.result(120) == want_b
    qa, evs_a = _query_events(df_a)
    qb, evs_b = _query_events(df_b)
    assert qa != qb
    _assert_well_formed(evs_a)
    _assert_well_formed(evs_b)
    for qid, evs in ((qa, evs_a), (qb, evs_b)):
        assert {e[6] for e in evs} == {qid}
        assert sum(1 for e in _spans(evs)
                   if e[1] == "collect" and e[2] == "query") == 1


# ---------------------------------------------------------------------------
# Chaos: injected faults appear as instants, results bit-identical
# ---------------------------------------------------------------------------

def test_chaos_instants_oom_transient(data_dir):
    want = tpch.QUERIES["q3"](_session(chaos=""), data_dir).collect()
    monitoring.reset()
    df = tpch.QUERIES["q3"](
        _session(chaos="oom@upload:1,transient@download:1"), data_dir)
    got = df.collect()
    assert got == want       # bit-identical under the schedule
    qid, evs = _query_events(df)
    _assert_well_formed(monitoring.events())
    kinds = {(e[7] or {}).get("kind") for e in _instants(evs)
             if e[1] == "fault-injected"}
    assert {"oom", "transient"} <= kinds
    # OOM ladder rungs are instants too, attributed to the same query.
    assert any(e[1] == "oom-rung" for e in _instants(evs))


def test_chaos_instants_lostshard(data_dir, tmp_path):
    want = tpch.QUERIES["q3"](_session(chaos=""), data_dir).collect()
    monitoring.reset()
    s = _session(chaos="lostshard@transport:1")
    s.set("spark.rapids.sql.shuffle.transport", "hostfile")
    s.set("spark.rapids.sql.shuffle.transport.hostfile.dir",
          str(tmp_path))
    df = tpch.QUERIES["q3"](s, data_dir)
    got = df.collect()
    assert got == want
    qid, evs = _query_events(df)
    inst = _instants(evs)
    assert any(e[1] == "fault-injected"
               and (e[7] or {}).get("kind") == "lostshard" for e in inst)
    # The lineage-scoped recompute shows on the same timeline.
    assert any(e[1] == "stage-recompute" for e in inst)


def test_chaos_scoped_to_one_of_two_queries(data_dir):
    """Cross-query attribution: chaos scoped to query A must not leave
    instants in concurrent query B's ring."""
    df_a = tpch.QUERIES["q6"](_session(), data_dir)
    df_b = tpch.QUERIES["q1"](_session(), data_dir)
    want_a, want_b = df_a.collect(), df_b.collect()
    monitoring.reset()
    faults.configure("oom@upload/query=1:1", seed=7)
    ha, hb = df_a.submit(), df_b.submit()
    ra, rb = ha.result(120), hb.result(120)
    assert ra == want_a and rb == want_b
    qa, evs_a = _query_events(df_a)
    qb, evs_b = _query_events(df_b)
    tagged = {qid for qid in (qa, qb)
              if any(e[1] == "fault-injected"
                     for e in _instants(monitoring.events(qid)))}
    # The schedule names fault tag 1: at most that one query's ring
    # carries injection instants; the other stays clean.
    other = {qa, qb} - tagged
    for qid in other:
        assert not any(e[1] == "fault-injected"
                       for e in _instants(monitoring.events(qid)))


# ---------------------------------------------------------------------------
# trace.enabled=false: byte-identical results/metrics, no-op recorder
# ---------------------------------------------------------------------------

_TPCH_FAST = ["q1", "q6"]
_TPCH_SLOW = ["q3", "q5", "q12", "q14"]
_SUITES_FAST = ["repart"]
_SUITES_SLOW = ["q67", "xbb_q5", "ds_q3", "xbb_q12"]


# Counters keyed to PROCESS-GLOBAL cache state (kernel/scan caches warm
# monotonically across collects) — legitimately run-order-dependent,
# excluded from the trace-on/off shape comparison.
_CACHE_COUNTERS = {"kernelCacheHits", "kernelCacheMisses", "compileTime",
                   "scanCacheHits", "persistentCacheHits",
                   "planCacheMiss", "planCacheBindOnly"}


def _metric_shape(metrics: dict):
    """Instance-address-free metric shape: a sorted multiset of
    (operator name, counter names) — comparable across separately
    planned DataFrames."""
    return sorted((k.split("@")[0],
                   tuple(sorted(n for n in v
                                if n not in _CACHE_COUNTERS)))
                  for k, v in metrics.items())


def _identity_check(qname, mod, ddir):
    off = mod.QUERIES[qname](_session(trace=False, scan_cache=False),
                             ddir)
    rows_off = off.collect()
    metrics_off = off.metrics()
    assert monitoring.events() == []
    on = mod.QUERIES[qname](_session(trace=True, scan_cache=False), ddir)
    rows_on = on.collect()
    assert rows_on == rows_off
    assert monitoring.events() != []
    # The recorder observes the plan and never shapes it.
    assert on.explain() == off.explain()
    off2 = mod.QUERIES[qname](_session(trace=False, scan_cache=False),
                              ddir)
    assert off2.collect() == rows_off
    # Metric SHAPE is unchanged by a traced run in between (values are
    # timings): same operator entries, same counter names.
    assert _metric_shape(metrics_off) == _metric_shape(off2.metrics())
    assert _metric_shape(metrics_off) == _metric_shape(on.metrics())


@pytest.mark.parametrize("qname", _TPCH_FAST + [
    pytest.param(q, marks=pytest.mark.slow) for q in _TPCH_SLOW])
def test_trace_off_identity_tpch(qname, data_dir):
    _identity_check(qname, tpch, data_dir)


@pytest.mark.parametrize("qname", _SUITES_FAST + [
    pytest.param(q, marks=pytest.mark.slow) for q in _SUITES_SLOW])
def test_trace_off_identity_suites(qname, suites_dir):
    _identity_check(qname, suites, suites_dir)


# ---------------------------------------------------------------------------
# Chrome export (the Perfetto acceptance artifact)
# ---------------------------------------------------------------------------

def test_trace_export_chrome_q3(data_dir, tmp_path):
    # Scan cache off so the upload funnel actually runs (a cache hit
    # would serve device batches without crossing the wire).
    df = tpch.QUERIES["q3"](_session(scan_cache=False), data_dir)
    df.collect()
    path = str(tmp_path / "q3_trace.json")
    doc = df.trace_export(path)
    on_disk = json.load(open(path))
    assert on_disk == doc
    evs = doc["traceEvents"]
    assert evs
    # The acceptance categories, each on a real track.
    cats = {e.get("cat") for e in evs if e.get("ph") == "X"}
    assert {"queued", "host-prefetch", "device-compute", "upload",
            "shuffle"} <= cats, cats
    # One process track per query with a name; thread tracks named.
    pnames = [e for e in evs
              if e.get("ph") == "M" and e["name"] == "process_name"]
    assert pnames and all(
        a["args"]["name"].startswith("query ") for a in pnames)
    tnames = [e for e in evs
              if e.get("ph") == "M" and e["name"] == "thread_name"]
    assert tnames
    # Worker threads (prefetch pool) appear as their own tracks.
    tids = {e["tid"] for e in evs if e.get("ph") == "X"}
    assert len(tids) >= 2
    # Complete events carry microsecond ts/dur as the format requires.
    for e in evs:
        if e.get("ph") == "X":
            assert e["dur"] >= 0 and e["ts"] >= 0


def test_process_tag_prefixes_exported_tracks():
    # Cluster worker processes tag themselves (worker.py run()) so their
    # per-process trace exports render "worker <wid> query N" tracks;
    # the untagged driver keeps the plain "query N" names.
    from spark_rapids_tpu.monitoring.chrome import to_chrome
    evs = [("X", "stage", "cluster", 1_000, 2_000, 1, 3, None, 1, 0)]
    try:
        monitoring.set_process_tag("worker w7")
        doc = to_chrome(evs, {1: "t"}, monitoring.process_tag())
        names = [e["args"]["name"] for e in doc["traceEvents"]
                 if e.get("ph") == "M" and e["name"] == "process_name"]
        assert names == ["worker w7 query 3"]
    finally:
        monitoring.set_process_tag("")
    doc = to_chrome(evs, {1: "t"}, monitoring.process_tag())
    names = [e["args"]["name"] for e in doc["traceEvents"]
             if e.get("ph") == "M" and e["name"] == "process_name"]
    assert names == ["query 3"]


def test_snapshot_category_breakdown(data_dir):
    tpch.QUERIES["q6"](_session(), data_dir).collect()
    snap = monitoring.snapshot()
    assert snap["enabled"] and snap["openSpans"] == 0
    cats = snap["categories"]
    assert "device-compute" in cats and cats["device-compute"]["ms"] > 0
    assert "queued" in cats
    bd = monitoring.category_breakdown()
    assert bd.keys() == cats.keys()


# ---------------------------------------------------------------------------
# explain_analyze
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("qname", ["q1", "q6", "q3"])
def test_explain_analyze_tpch(qname, data_dir, capsys):
    s = _session()
    # Keep placement off (explicitly — estimates in explain_analyze come
    # from estimate_plan directly, independent of placement) so the
    # device engine runs and leaf operators record observed rows.
    s.set("spark.rapids.sql.cost.enabled", False)
    df = tpch.QUERIES[qname](s, data_dir)
    df.collect()
    out = df.explain_analyze()
    assert "rows=" in out and "wall=" in out and "bytes=" in out
    assert "est " in out and "err=" in out and "syncs" in out
    # Observed leaf rows are real numbers, not all '?'.
    assert any("rows=" in ln and "rows=?" not in ln
               for ln in out.splitlines())
    # The audit entries + the per-query category breakdown land in the
    # footer.
    assert "Scheduler@query" in out
    assert "Trace@query" in out and "device-compute=" in out


@pytest.mark.slow
@pytest.mark.parametrize("qname,pack", [(q, "tpch") for q in
                                        ["q1", "q6", "q3", "q5", "q12",
                                         "q14"]] +
                         [(q, "suites") for q in
                          ["repart", "q67", "xbb_q5", "ds_q3",
                           "xbb_q12"]])
def test_explain_analyze_full_suite(qname, pack, data_dir, suites_dir):
    """The 11-query acceptance sweep: explain_analyze renders observed
    numbers and estimate errors for every bench query."""
    mod, ddir = (tpch, data_dir) if pack == "tpch" else \
        (suites, suites_dir)
    df = mod.QUERIES[qname](_session(), ddir)
    df.collect()
    out = df.explain_analyze()
    assert "wall=" in out and "rows=" in out
    assert "est " in out and "err=" in out


# ---------------------------------------------------------------------------
# Span ids and parents (PR 25)
# ---------------------------------------------------------------------------

def _kernel_session(**kw):
    from spark_rapids_tpu.monitoring import syncs
    syncs.install()     # resident wrappers; silent below kernel level
    s = _session(**kw)
    s.set("spark.rapids.sql.trace.level", "kernel")
    return s


def _chain(e, by_sid):
    """The spans above ``e``, innermost first."""
    out, seen = [], set()
    while e[9]:
        assert e[9] not in seen, "cycle in the parent chain"
        seen.add(e[9])
        e = by_sid[e[9]]
        out.append(e)
    return out


@pytest.mark.parametrize("qname", ["q1", "q3"])
def test_parent_chain_is_a_tree_rooted_at_collect(qname, data_dir):
    tpch.QUERIES[qname](_kernel_session(), data_dir).collect()
    monitoring.reset()
    df = tpch.QUERIES[qname](_kernel_session(), data_dir)
    df.collect()
    qid, evs = _query_events(df)
    by_sid = {e[8]: e for e in _spans(evs)}
    assert len(by_sid) == len(_spans(evs)), "span ids repeat"
    collect, = [e for e in _spans(evs) if e[1] == "collect"]
    syncs = [e for e in _spans(evs) if e[2] == "sync"]
    assert syncs, "no sync recorded at kernel level"
    for e in evs:
        if e is collect or e[1] in ("admission-queue", "calibrate",
                                    "finish"):
            assert e[9] == 0    # the admission before the collect, the
            continue            # calibration and the teardown after it
        chain = _chain(e, by_sid)
        assert chain and chain[-1] is collect, f"{e[1]!r} hangs loose"
        if e[0] == "X":                 # a child lies inside its parent
            up = chain[0]
            if up[5] == e[5]:
                assert up[3] <= e[3] and e[3] + e[4] <= up[3] + up[4]
    # Every blocking read is paid for by an operator's own span (a
    # timed() section, or the span around the operator's pull) or by the
    # result download: none is left to a container.
    from spark_rapids_tpu.monitoring.syncs import owner
    owners = {owner(e, by_sid) for e in syncs}
    assert "download" in owners
    assert owners <= {"download", "exchange-flush",
                      "ShuffleExchangeExec:shrink-all",
                      "HashAggregateExec:shrink-all",
                      "HashAggregateExec:agg-skip-probe",
                      "HashAggregateExec:sizesPullTime",
                      "LocalLimitExec:limit-count"}, owners


def test_prefetch_keeps_its_cause_across_threads(data_dir):
    """A prefetch runs on a pool thread; its parent is the consumer's
    `partition` span that asked for it (the first partition asks for
    ``prefetchPartitions`` ahead, so it is also the one that consumes
    partition 0's)."""
    import glob
    from spark_rapids_tpu.plan.logical import col
    paths = sorted(glob.glob(f"{data_dir}/lineitem/*.parquet"))
    df = _kernel_session(scan_cache=False).read.parquet(*paths) \
        .filter(col("l_quantity") < 10).select("l_orderkey")
    df.collect()
    qid, evs = _query_events(df)
    by_sid = {e[8]: e for e in _spans(evs)}
    collect, = [e for e in _spans(evs) if e[1] == "collect"]
    prefetches = {e[7]["partition"]: e for e in _spans(evs)
                  if e[1] == "prefetch"}
    assert len(prefetches) == len(paths)
    for p, e in prefetches.items():
        up = by_sid[e[9]]
        assert e[5] != collect[5], "prefetch ran on the collect thread"
        assert up[1] == "partition" and up[5] == collect[5]
        assert up[7]["partition"] <= p
        assert _chain(e, by_sid)[-1] is collect
    assert by_sid[prefetches[0][9]][7]["partition"] == 0
    # what the worker does for the prefetch hangs under it
    packs = [e for e in _spans(evs) if e[1] == "wire-pack"]
    assert packs and all(by_sid[e[9]][1] == "prefetch" for e in packs)
    # the pool threads carry nothing over to their next task
    assert monitoring.current() == 0


@pytest.mark.parametrize("qname", ["q1", "q3"])
def test_self_times_count_each_ms_once(qname, data_dir):
    df = tpch.QUERIES[qname](_kernel_session(), data_dir)
    df.collect()
    qid, evs = _query_events(df)
    own = monitoring.self_ns(evs)
    collect, = [e for e in _spans(evs) if e[1] == "collect"]
    by_tid = {}
    for e in _spans(evs):
        assert 0 <= own[e[8]] <= e[4]
        by_tid[e[5]] = by_tid.get(e[5], 0) + own[e[8]]
    # one thread's self times add up to no more than the time it was
    # inside the query (durations summed would pass it several times):
    # the collect, and on its thread the spans before and after it
    roots = sum(e[4] for e in _spans(evs)
                if e[9] == 0 and e[5] == collect[5])
    for tid, ns in by_tid.items():
        inside = roots if tid == collect[5] else collect[4]
        assert ns <= inside * 1.001, (tid, ns, inside)
    assert sum(e[4] for e in _spans(evs) if e[5] == collect[5]) \
        > collect[4]
    cats = monitoring.self_times(qid)
    assert sum(cats.values()) == pytest.approx(
        sum(own.values()) / 1e6, rel=1e-9)
    assert cats["query"] < collect[4] / 1e6


@pytest.mark.parametrize("qname", ["q1", "q3"])
def test_span_metric_categories_never_nest(qname, data_dir):
    """`planning`, `download` and `compile` spans never enclose one
    another on a thread: a category's durations summed are a time (all
    that the benchmark's per-category totals can carry)."""
    s = _kernel_session()
    tpch.QUERIES[qname](s, data_dir).collect()
    monitoring.reset()
    tpch.QUERIES[qname](s, data_dir).collect()
    spans = _spans(monitoring.events())     # ring 0 (planning) included
    seen = set()
    for cat in ("planning", "download", "compile"):
        by_tid = {}
        for e in spans:
            if e[2] == cat:
                by_tid.setdefault(e[5], []).append((e[3], e[3] + e[4]))
                seen.add(e[1])
        for ivs in by_tid.values():
            ivs.sort()
            for (_, end), (start, _) in zip(ivs, ivs[1:]):
                assert end <= start, f"{cat} spans overlap"
    assert {"infer-schema", "plan-bind", "replan", "download",
            "to-rows"} <= seen


def test_sync_stats_name_the_owner(data_dir):
    from spark_rapids_tpu.monitoring.syncs import sync_stats
    df = tpch.QUERIES["q1"](_kernel_session(), data_dir)
    df.collect()
    qid, evs = _query_events(df)
    stats = sync_stats(qid)
    assert any(k.endswith("@ download") for k in stats), stats
    assert not any("<unknown>" in k or ".py:" in k for k in stats), stats
    # a funnel inside a funnel (device_get -> __array__) counts once
    leaves = [e for e in _spans(evs) if e[2] == "sync"
              and e[8] not in {c[9] for c in _spans(evs)
                               if c[2] == "sync"}]
    assert sum(n for n, _ in stats.values()) == len(leaves)


# ---------------------------------------------------------------------------
# One span, two sinks: the profiler trace (PR 25)
# ---------------------------------------------------------------------------

def _host_annotations(trace_dir):
    """line name -> [(name, start, end)] of the host plane."""
    import glob
    import jax.profiler
    path, = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(path)
    out = {}
    for plane in data.planes:
        if plane.name == "/host:CPU":
            for i, ln in enumerate(plane.lines):
                out[f"{ln.name}#{i}"] = [
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in ln.events]
    return out


def _profiled_collect(make_df, trace_dir, name="test:query"):
    import jax.profiler
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(name):
            rows = make_df().collect()
    finally:
        jax.profiler.stop_trace()
    return rows


def test_enabled_spans_annotate_the_profile(data_dir, tmp_path):
    s = _session()
    tpch.QUERIES["q1"](s, data_dir).collect()       # warm, configured
    got = _profiled_collect(lambda: tpch.QUERIES["q1"](s, data_dir),
                            tmp_path)
    assert got
    lines = _host_annotations(tmp_path)
    line, = [evs for evs in lines.values()
             if any(n == "test:query" for n, _, _ in evs)]
    names = {n for n, _, _ in line}
    assert {"query:collect", "download:download", "download:to-rows",
            "planning:plan-bind", "planning:infer-schema"} <= names
    ops = [n for n in names if n.endswith(":totalTime")]
    assert ops and all(":" in n and "Exec" in n for n in ops), names
    # nested on the one line, on the profiler's clock
    (_, q0, q1), = [e for e in line if e[0] == "test:query"]
    (_, c0, c1), = [e for e in line if e[0] == "query:collect"]
    assert q0 <= c0 and c1 <= q1
    for n, s0, s1 in line:
        if n == "download:download" or n.endswith(":totalTime"):
            assert c0 <= s0 and s1 <= c1, n
        if n.startswith("planning:plan-bind"):
            assert q0 <= s0 and s1 <= c0    # before the funnel


def test_idle_owner_script_names_the_program(data_dir, tmp_path):
    """scripts/idle_owner.py over a kept trace: the same idle gaps under
    the three rules; the owner rule names spans of the program only."""
    import importlib.util
    import os
    import jax.profiler
    spec = importlib.util.spec_from_file_location(
        "idle_owner", os.path.join(os.path.dirname(__file__), "..",
                                   "scripts", "idle_owner.py"))
    idle_owner = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(idle_owner)
    s = _kernel_session()
    tpch.QUERIES["q3"](s, data_dir).collect()
    _profiled_collect(lambda: tpch.QUERIES["q3"](s, data_dir), tmp_path,
                      name="bench:query")
    path = idle_owner.tr.find_trace(str(tmp_path))
    anns, idle, queries = idle_owner.idle_gaps(
        jax.profiler.ProfileData.from_file(path))
    assert queries == 1 and idle
    program = [a for a in anns if idle_owner.PROGRAM.match(a[0])]
    names = {a[0] for a in program}
    assert {"bench:query", "query:collect", "sync:device_get",
            "download:download"} <= names
    assert not any("(" in n.split("[")[0] or "::" in n for n in names)
    owners = [a for a in program if not a[0].startswith("sync:")]
    total = sum(e - s for s, e in idle)
    for annotations in (anns, program, owners):
        table = idle_owner.by_label(annotations, idle)
        assert sum(ns for _, ns in table) == pytest.approx(total)
    assert all(idle_owner.PROGRAM.match(label) and
               not label.startswith("sync:")
               for label, _ in idle_owner.by_label(owners, idle))
    # a gap split over its extent: the same idle, and the stretches of
    # the flattened thread neither overlap nor leave the query
    split = idle_owner.by_extent(owners, idle)
    assert sum(ns for _, ns in split) == pytest.approx(total)
    segs = idle_owner.segments(owners)
    assert all(a[1] <= b[0] for a, b in zip(segs, segs[1:]))
    (_, q0, q1), = [a for a in owners if a[0] == "bench:query"]
    assert segs[0][0] == q0 and segs[-1][1] == q1
    assert "query:finish" in {label for _, _, label in segs}
    assert idle_owner.main([str(tmp_path)]) == 0


def test_disabled_recorder_annotates_nothing(data_dir, tmp_path):
    import gc
    from spark_rapids_tpu.monitoring import recorder
    s = _session(trace=False)
    tpch.QUERIES["q1"](s, data_dir).collect()
    assert not monitoring.enabled()
    assert recorder._ANNOTATION is None
    assert recorder._on_gc not in gc.callbacks
    from jax._src import monitoring as jm
    assert recorder._on_jax_duration not in \
        jm.get_event_duration_listeners()
    assert monitoring.span("a", "b") is monitoring.span("c", "d")
    _profiled_collect(lambda: tpch.QUERIES["q1"](s, data_dir), tmp_path)
    line, = [evs for evs in _host_annotations(tmp_path).values()
             if any(n == "test:query" for n, _, _ in evs)]
    ours = [n for n, _, _ in line
            if n.split(":")[0] in ("query", "download", "planning",
                                   "device-compute", "sync", "queued")
            or n.endswith(":totalTime")]
    assert ours == []


def test_recorder_module_imports_only_the_standard_library():
    import ast
    import sys
    from spark_rapids_tpu.monitoring import recorder
    tree = ast.parse(open(recorder.__file__).read())
    for node in tree.body:
        if isinstance(node, ast.Import):
            mods = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            mods = [node.module]
        else:
            continue
        for m in mods:
            assert m.split(".")[0] in sys.stdlib_module_names, m


def test_gc_inside_the_recorder_lock_cannot_deadlock():
    """A collection can start inside ``_ring()``, with ``_LOCK`` held and
    no ring yet for the thread's query: the callback takes no lock, and
    the span reaches its ring with the next ordinary record."""
    import gc
    import threading
    from spark_rapids_tpu.monitoring import recorder
    monitoring.configure(True, monitoring.LEVEL_KERNEL)
    gc.disable()        # no collection of the interpreter's own in between
    try:
        monitoring.reset()                      # no ring for query 0

        def collection():
            recorder._on_gc("start", {"generation": 0})
            recorder._on_gc("stop", {"generation": 0, "collected": 7,
                                     "uncollectable": 0})
        with recorder._LOCK:
            assert not recorder._RINGS
            t = threading.Thread(target=collection, name="gc-here")
            t.start()
            t.join(10)
            assert not t.is_alive(), "the gc callback waits for _LOCK"
            recorder._on_gc("start", {"generation": 2})   # same thread
            recorder._on_gc("stop", {"generation": 2, "collected": 1,
                                     "uncollectable": 0})
            assert not recorder._RINGS          # nothing touched a ring
        with monitoring.span("after", "device-compute"):
            pass
        evs = monitoring.events()
        gcs = [e for e in _spans(evs) if e[2] == "runtime"]
        assert [e[7] for e in gcs] == [
            {"generation": 0, "collected": 7},
            {"generation": 2, "collected": 1}]
        assert gcs[0][5] == t.ident and gcs[1][5] == threading.get_ident()
        assert monitoring.thread_names()[t.ident] == "gc-here"
        assert len({e[8] for e in _spans(evs)}) == 3
    finally:
        gc.enable()
        monitoring.configure(False)
        monitoring.reset()


def test_compile_and_gc_record_as_spans():
    import gc
    import jax
    import jax.numpy as jnp
    monitoring.configure(True, monitoring.LEVEL_QUERY)
    with monitoring.span("step", "device-compute",
                         level=monitoring.LEVEL_QUERY) as step:
        # never compiled before, and too small for the persistent cache
        jax.jit(lambda x: x * 3.25 + 41.5)(jnp.arange(7)).block_until_ready()
        gc.collect()
    monitoring.configure(False)
    spans = _spans(monitoring.events())
    compiled = [e for e in spans if e[2] == "compile"]
    assert compiled and all(e[1] == "backend-compile"
                            and e[9] == step.sid for e in compiled)
    assert all(e[4] > 0 and e[7]["fun_name"] for e in compiled)
    full = [e for e in spans if e[2] == "runtime" and e[1] == "gc"]
    # gc.collect()'s, and any full collection the interpreter ran itself
    assert full and all(e[9] == step.sid for e in full)
    assert all(e[7]["generation"] == 2 and e[7]["collected"] >= 0
               for e in full)
    _assert_well_formed(monitoring.events())
    # disabled again: the listeners are gone with it
    assert monitoring.recorder._on_gc not in gc.callbacks
