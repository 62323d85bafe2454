"""TPC-H q1 and q3 with the SHAPE of SF10 at a small scale (PR 35): eight
scan units a partition (small row groups, a small reader batch), the
second join's estimate over ``autoBroadcastJoinThreshold`` and the first's
under it — forced from outside the program, held against the benchmark's
plain pandas reference by its own comparison. With it what the deployment
made the program say of itself: the device scan cache's counters (and the
cliff of a working set one unit over its budget), the ``join-build`` /
``join-probe`` spans and the ``joinBuildRows`` / ``exchangeRows``
counters.
"""

import os
import sys

import pyarrow.parquet as papq
import pytest

from spark_rapids_tpu.api.dataframe import TpuSession
from spark_rapids_tpu.io import scan
from spark_rapids_tpu.monitoring import recorder
from spark_rapids_tpu.ops.join import (
    BroadcastHashJoinExec, ShuffledHashJoinExec)

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
ROW_GROUP = 2048
SCAN_CACHE = "spark.rapids.sql.format.scanCache.maxBytes"
THRESHOLD = "spark.rapids.sql.autoBroadcastJoinThreshold"


def _bench(name):
    """A module of ``benchmark/`` (they import one another by bare name)."""
    if BENCH not in sys.path:
        sys.path.append(BENCH)
    return __import__(name)


@pytest.fixture(scope="module")
def sf10(tmp_path_factory):
    """The suite's three tables at SF0.02, rewritten with row groups of
    2,048 rows: lineitem 8 files x 8 units, orders 8 x 2 (SF10: 8 x 8 of
    1,048,576 and 8 x 2), and the plain reference's answers."""
    suite = _bench("tpch_sf10_data")
    d = str(tmp_path_factory.mktemp("sf10_shape"))
    suite.generate(d, scale=0.02, seed=2147483935, files_per_table=8,
                   tables=["customer", "lineitem", "orders"])
    for table in ("lineitem", "orders"):
        for p in suite._paths(d, table):
            papq.write_table(papq.read_table(p), p,
                             row_group_size=ROW_GROUP)
    groups = [papq.ParquetFile(p).metadata.num_row_groups
              for p in suite._paths(d, "lineitem")]
    assert groups == [8] * 8
    return {"suite": suite, "dir": d,
            "want": {q: suite.pandas_query(q, d) for q in ("q1", "q3")}}


def _session(trace=False, **conf):
    s = TpuSession()
    s.set("spark.rapids.sql.variableFloatAgg.enabled", True)
    s.set("spark.rapids.sql.hasNans", False)
    s.set("spark.rapids.sql.reader.batchSizeRows", ROW_GROUP)
    s.set("spark.rapids.sql.trace.enabled", trace)
    s.set("spark.rapids.sql.trace.level", "kernel")
    for k, v in conf.items():
        s.set(k, v)
    return s


def _judge(sf10, query, rows):
    compare = _bench("compare")
    return compare.judge([{"query": query, "rows": rows}], sf10["want"],
                         sf10["suite"].SET_COMPARE, sent=1)


def _joins(root):
    out = []

    def walk(n):
        if isinstance(n, ShuffledHashJoinExec):
            out.append(n)
        for c in n.children:
            walk(c)

    walk(root)
    return out


# -- (a) the plans of q3, each equal to the reference --------------------------

@pytest.fixture(scope="module")
def q3_sizes(sf10):
    """With nothing broadcast, what the planner estimates for the two
    build sides, and what the second join's exchange observed: the live
    rows' bytes (what the re-plan reads) and the shards' footprint."""
    df = sf10["suite"].QUERIES["q3"](_session(**{THRESHOLD: -1}),
                                     sf10["dir"])
    phys = df._physical()
    second, first = _joins(phys.root)
    assert type(first) is type(second) is ShuffledHashJoinExec
    phys.collect()
    sess = phys.last_ctx.cache[f"shuffle:{id(second.children[1]):x}:dev"]
    return {"est_first": first.est_build_bytes,
            "est_second": second.est_build_bytes,
            "live": sess.live_bytes, "footprint": sess.observed_bytes()}


def test_sizes_stand_as_at_sf10(q3_sizes):
    """customer under the live build side under the shards' footprint
    under the planner's estimate (SF10: ~35 < 60.6 < 63-75 < 272 MB)."""
    s = q3_sizes
    assert s["est_first"] < s["live"] < s["footprint"] < s["est_second"]


@pytest.mark.parametrize("case", [
    "shuffled_demoted", "demoted_at_live_bytes", "stays_shuffled",
    "broadcast"])
def test_q3_plans_equal_the_reference(sf10, q3_sizes, case):
    s = q3_sizes
    # (the first join broadcast, the second's shards are cut a little
    # otherwise than in ``q3_sizes``: a few bytes a shard of slack)
    slack = s["live"] // 100
    threshold = {"shuffled_demoted": s["est_second"] - 1,
                 # the shards' padding must not decide: just over the
                 # live bytes the join is demoted, though its shards hold
                 # more than the threshold
                 "demoted_at_live_bytes": s["live"] + slack,
                 "stays_shuffled": s["live"] - slack,
                 "broadcast": s["est_second"]}[case]
    assert s["est_first"] <= threshold
    assert case != "demoted_at_live_bytes" or threshold < s["footprint"]
    df = sf10["suite"].QUERIES["q3"](_session(**{THRESHOLD: threshold}),
                                     sf10["dir"])
    phys = df._physical()
    second, first = _joins(phys.root)
    assert type(first) is BroadcastHashJoinExec
    rows = df.collect()
    verdict = _judge(sf10, "q3", rows)
    assert verdict["correct"], verdict["checks"]
    cost = df.metrics().get("Cost@query", {})
    if case == "broadcast":
        assert type(second) is BroadcastHashJoinExec
        assert "replanChecks" not in cost
        return
    assert type(second) is ShuffledHashJoinExec
    assert cost["replanChecks"] == 1
    assert abs(cost["replanObservedBytes"] - s["live"]) < slack
    # beside it what the rule read until PR 35, and that no shard went
    # into the live bytes at its footprint for want of a row count
    assert cost["replanFootprintBytes"] > cost["replanObservedBytes"]
    assert cost["replanUncountedShards"] == 0
    assert cost.get("joinDemotions", 0) == (case != "stays_shuffled")


def test_a_shard_without_a_row_count_is_counted_at_its_footprint():
    """``live_bytes`` is an estimate from ``rows_hint``; a device shard
    that carries none goes in at its footprint, and the session says how
    many did (``replanUncountedShards``)."""
    from spark_rapids_tpu.parallel.transport.base import ShuffleSession

    class _Shard:
        def __init__(self, live):
            self.live = live

        def device_size_bytes(self):
            return 1000

        def live_size_bytes(self):
            return self.live

    sess = ShuffleSession.__new__(ShuffleSession)
    sess.shard_bytes, sess.live_bytes, sess.uncounted_shards = {}, 0, 0
    sess.record_device_shard(0, _Shard(600))
    sess.record_device_shard(1, _Shard(None))
    assert sess.observed_bytes() == 2000
    assert (sess.live_bytes, sess.uncounted_shards) == (1600, 1)


def test_live_size_bytes_is_the_footprint_scaled_by_the_row_hint():
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.columnar.batch import DeviceBatch, DeviceColumn
    cap = 4096
    b = DeviceBatch((DeviceColumn(dt.INT64, jnp.zeros((cap,), jnp.int64),
                                  jnp.ones((cap,), jnp.bool_)),),
                    jnp.asarray(cap, jnp.int32))
    assert b.live_size_bytes() is None          # no count: no estimate
    b.rows_hint = cap // 4
    assert b.live_size_bytes() == b.device_size_bytes() // 4


def test_q1_over_eight_units_a_partition(sf10):
    df = sf10["suite"].QUERIES["q1"](_session(), sf10["dir"])
    verdict = _judge(sf10, "q1", df.collect())
    assert verdict["correct"], verdict["checks"]
    scans = [m for name, m in df.metrics().items()
             if name.startswith("FileScanExec")]
    assert sum(m["numOutputBatches"] for m in scans) == 64


# -- (b) the device scan cache's counters, and its cliff -----------------------

def _delta(before, after):
    return {k: after[k] - before[k] for k in after}


@pytest.fixture()
def clean_cache():
    scan.DEVICE_SCAN_CACHE.clear()
    yield
    scan.DEVICE_SCAN_CACHE.clear()


def test_resident_window_misses_nothing(sf10, clean_cache):
    session = _session()

    def collect():
        return sf10["suite"].QUERIES["q1"](session, sf10["dir"]).collect()

    start = scan.counters()
    collect(), collect()                       # the warm-ups fill it
    warm = scan.counters()
    filled = _delta(start, warm)
    assert filled["scanCacheMissUnits"] == filled["scanCacheHitUnits"] == 64
    assert filled["scanCacheMissBytes"] == warm["scanCacheResidentBytes"]
    rows = [collect() for _ in range(3)]       # the window
    window = _delta(warm, scan.counters())
    assert window["scanCacheHitUnits"] == 3 * 64
    assert window["scanCacheHitBytes"] == 3 * warm["scanCacheResidentBytes"]
    assert window["scanCacheResidentBytes"] == 0
    for k in ("scanCacheMissUnits", "scanCacheMissBytes",
              "scanCacheRefillBytes", "scanCacheEvictedBytes",
              "scanCacheRejectedBytes"):
        assert window[k] == 0, k
    assert rows[0] == rows[1] == rows[2]


def test_one_unit_over_the_budget_misses_one_unit_a_query(sf10,
                                                          clean_cache):
    """The cliff, as repaired: a cyclic scan whose working set is one
    unit over the budget keeps what it holds and misses that unit, where
    a plain LRU would evict, for each unit, the one the next step needs
    and miss all 64 in every query."""
    q1 = sf10["suite"].QUERIES["q1"]
    q1(_session(), sf10["dir"]).collect()
    whole = scan.counters()["scanCacheResidentBytes"]
    scan.DEVICE_SCAN_CACHE.clear()
    session = _session(**{SCAN_CACHE: whole - 1})
    start = scan.counters()
    want = q1(session, sf10["dir"]).collect()
    q1(session, sf10["dir"]).collect()
    warm = scan.counters()
    assert _delta(start, warm)["scanCacheMissUnits"] == 64 + 1
    assert 0 < warm["scanCacheResidentBytes"] < whole
    left_out = whole - warm["scanCacheResidentBytes"]
    for _ in range(3):
        assert q1(session, sf10["dir"]).collect() == want
    window = _delta(warm, scan.counters())
    assert window["scanCacheMissUnits"] == 3
    assert window["scanCacheHitUnits"] == 3 * 63
    assert window["scanCacheMissBytes"] == 3 * left_out
    assert window["scanCacheRefillBytes"] == 3 * left_out
    assert window["scanCacheEvictedBytes"] == 0
    assert window["scanCacheResidentBytes"] == 0


class _Unit:
    """What ``DeviceScanCache`` asks of a batch."""

    def __init__(self, nbytes):
        self.nbytes = nbytes

    def device_size_bytes(self):
        return self.nbytes


def _key(path, index, columns=("a",)):
    # ``FileScanExec._unit_cache_key``'s form: (scan, unit)
    return (("parquet", columns, (), 1 << 20), (path, 1, 100, index))


def test_the_keys_of_one_scan_share_their_first_half(sf10, clean_cache):
    """What ``DeviceScanCache._same_scan`` rests on: the keys the scan
    makes are ``(scan, unit)``; q1's 64 units share one ``scan`` and
    differ in ``unit``, and another column set is another scan."""
    suite = sf10["suite"]
    suite.QUERIES["q1"](_session(), sf10["dir"]).collect()
    q1_keys = list(scan.DEVICE_SCAN_CACHE._entries)
    assert all(len(k) == 2 for k in q1_keys)
    assert len({k[0] for k in q1_keys}) == 1
    assert len({k[1] for k in q1_keys}) == len(q1_keys) == 64
    assert {len(k[1]) for k in q1_keys} == {4}     # path, mtime, size, index
    suite.QUERIES["q3"](_session(), sf10["dir"]).collect()
    q3_keys = [k for k in scan.DEVICE_SCAN_CACHE._entries
               if k not in set(q1_keys)]
    lineitem = set(suite._paths(sf10["dir"], "lineitem"))
    again = [k for k in q3_keys if k[1][0] in lineitem]
    assert again and all(
        not scan.DeviceScanCache._same_scan(k, q1_keys[0]) for k in again)
    assert all(scan.DeviceScanCache._same_scan(k, again[0]) for k in again)


def _scan_once(cache, keys, budget, size=10):
    """One pass of a scan: serve what is held, decode and offer the rest."""
    for k in keys:
        if cache.get(k) is None:
            cache.put(k, [_Unit(size)], budget)


@pytest.mark.parametrize("over", [1, 3])
def test_cyclic_scan_keeps_what_it_holds(over):
    cache = scan.DeviceScanCache()
    keys = [_key("t/part-0", i) for i in range(8 + over)]
    for _ in range(4):
        _scan_once(cache, keys, budget=80)
    c = cache.counters()
    assert c["scanCacheResidentBytes"] == 80
    assert c["scanCacheHitUnits"] == 3 * 8
    assert c["scanCacheMissUnits"] == 8 + 4 * over
    assert c["scanCacheRefillBytes"] == 3 * over * 10
    assert c["scanCacheEvictedBytes"] == 0


def test_another_scans_units_are_evicted_oldest_first():
    cache = scan.DeviceScanCache()
    old = [_key("t/part-0", i) for i in range(8)]
    new = [_key("u/part-0", i, columns=("b",)) for i in range(4)]
    _scan_once(cache, old, budget=80)
    _scan_once(cache, new, budget=80)
    c = cache.counters()
    assert c["scanCacheEvictedBytes"] == 40
    assert c["scanCacheResidentBytes"] == 80
    assert [cache.get(k, probe=True) is not None for k in old] == \
        [False] * 4 + [True] * 4
    # what was evicted comes back as a refill
    _scan_once(cache, old[:1], budget=80)
    assert cache.counters()["scanCacheRefillBytes"] == 10


def test_stale_units_yield_on_the_second_pass():
    """An overwritten table: new files under new names, the old files'
    units still held and served by nothing. The first pass over the new
    files is turned away, the second takes their place."""
    cache = scan.DeviceScanCache()
    _scan_once(cache, [_key("t/old", i) for i in range(8)], budget=80)
    fresh = [_key("t/new", i) for i in range(8)]
    _scan_once(cache, fresh, budget=80)
    assert all(cache.get(k, probe=True) is None for k in fresh)
    _scan_once(cache, fresh, budget=80)
    assert all(cache.get(k, probe=True) is not None for k in fresh)
    before = cache.counters()
    _scan_once(cache, fresh, budget=80)
    assert _delta(before, cache.counters())["scanCacheMissUnits"] == 0


def test_a_unit_larger_than_the_budget_is_rejected():
    cache = scan.DeviceScanCache()
    cache.put(_key("t/p", 0), [_Unit(100)], 80)
    cache.put(_key("t/p", 0), [_Unit(100)], 80)
    c = cache.counters()
    assert c["scanCacheRejectedBytes"] == 200
    assert c["scanCacheRefillBytes"] == 100
    assert c["scanCacheResidentBytes"] == 0


# -- (c) the join's spans and counters -----------------------------------------

@pytest.fixture(scope="module")
def q3_traced(sf10, q3_sizes):
    """q3 untraced, traced with the second join demoted, and traced with
    it shuffled: rows, events, counters."""
    out = {}
    for name, trace, threshold in (
            ("off", False, q3_sizes["est_second"] - 1),
            ("demoted", True, q3_sizes["est_second"] - 1),
            ("shuffled", True, q3_sizes["live"] - 1)):
        recorder.reset()
        recorder.reset_counters()
        df = sf10["suite"].QUERIES["q3"](
            _session(trace, **{THRESHOLD: threshold}), sf10["dir"])
        out[name] = {"rows": df.collect(), "events": recorder.events(),
                     "counters": recorder.counters()}
    recorder.configure(False)
    recorder.reset()
    recorder.reset_counters()
    return out


def _spans(events, cat=None):
    return [e for e in events
            if e[0] == "X" and (cat is None or e[2] == cat)]


def test_untraced_records_nothing_and_answers_the_same(sf10, q3_traced):
    off = q3_traced["off"]
    assert off["events"] == [] and off["counters"] == {}
    # the same plan traced: the same bits; the other plan sums in another
    # order: equal to the reference
    assert off["rows"] == q3_traced["demoted"]["rows"]
    assert _judge(sf10, "q3", q3_traced["shuffled"]["rows"])["correct"]


@pytest.mark.parametrize("run", ["demoted", "shuffled"])
def test_join_spans_and_counters(q3_traced, run):
    events, c = q3_traced[run]["events"], q3_traced[run]["counters"]
    builds = _spans(events, "join-build")
    probes = _spans(events, "join-probe")
    sides = [e for e in builds if e[1] == "build-side"]
    # shuffled: one build side a reduce partition of the second join;
    # demoted or broadcast: one for the whole join
    assert {e[1] for e in builds} == {"build-side", "table"}
    assert len(sides) >= 2 and (run == "shuffled" or len(sides) == 2)
    # the one batch a build side is made of holds its members' rows
    assert c["joinBuildRows"] >= sum(max(e[7]["capacities"])
                                     for e in sides)
    assert all(e[7]["batches"] == len(e[7]["capacities"]) for e in sides)
    assert probes and all(e[7]["capacity"] > 0 for e in probes)
    # only a shuffled join sends its probe side through the exchange
    moved = c["exchangeRows"]
    assert moved > 0
    if run == "shuffled":
        assert moved > q3_traced["demoted"]["counters"]["exchangeRows"]
    # never nested within their own or each other's category, and spans
    # of one thread follow one another
    by_sid = {e[8]: e for e in _spans(events)}
    for e in builds + probes:
        p = by_sid.get(e[9])
        while p is not None:
            assert p[2] not in ("join-build", "join-probe"), (e[1], p[1])
            p = by_sid.get(p[9])
    for tid in {e[5] for e in builds + probes}:
        mine = sorted((e for e in builds + probes if e[5] == tid),
                      key=lambda e: e[3])
        for a, b in zip(mine, mine[1:]):
            assert a[3] + a[4] <= b[3], (a[1], b[1])


@pytest.mark.parametrize("trace", [False, True])
def test_a_build_span_ends_when_the_device_has_the_build(sf10, q3_sizes,
                                                         trace, monkeypatch):
    """``join_build_ms`` is to read the build and not its dispatch: with
    the recorder on each ``join-build`` span waits for what it dispatched
    (the sorted side, the dense table) before it closes; with it off
    nothing waits, and the build overlaps the first probe batch."""
    from spark_rapids_tpu.ops import join as J
    waited = []
    real = J.jax.block_until_ready
    monkeypatch.setattr(J.jax, "block_until_ready",
                        lambda x: (waited.append(x), real(x))[1])
    recorder.reset()
    df = sf10["suite"].QUERIES["q3"](
        _session(trace, **{THRESHOLD: q3_sizes["est_second"] - 1}),
        sf10["dir"])
    try:
        rows = df.collect()
        builds = _spans(recorder.events(), "join-build")
    finally:
        recorder.configure(False)
        recorder.reset()
        recorder.reset_counters()
    assert _judge(sf10, "q3", rows)["correct"]
    if not trace:
        assert waited == [] and builds == []
        return
    sides = [e for e in builds if e[1] == "build-side"]
    tables = [e for e in builds if e[1] == "table"]
    assert len(waited) >= len(sides) == 2
    assert len(waited) <= len(sides) + len(tables)


@pytest.mark.parametrize("run", ["demoted", "shuffled"])
def test_join_spans_hold_no_childs_work(q3_traced, run):
    """Under a join's span: its own dispatch, its reads, what the runtime
    interposes — no scan, no exchange, no other operator."""
    events = _spans(q3_traced[run]["events"])
    by_sid = {e[8]: e for e in events}
    inside = set()
    for e in events:
        p = by_sid.get(e[9])
        while p is not None and p[2] not in ("join-build", "join-probe"):
            p = by_sid.get(p[9])
        if p is not None:
            inside.add(e[2])
    assert inside <= {"sync", "runtime", "compile"}, inside
