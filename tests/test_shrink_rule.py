"""The rule by which ``coalesce_iter(shrink=True)`` compacts a member (PR 32).

Both of its consumers, the keyed aggregate's update and the join probe,
read selection vectors. So a member with one is compacted only where that
makes it smaller: never into its own capacity, and in front of a probe
(``keep_ratio`` = ``PROBE_SHRINK_RATIO``) only where its live bucket is at
most half its capacity. Here: the rule member by member, with the counts
it keeps; that the other callers of ``shrink_all`` still get dense batches;
and whole queries whose aggregate and probe inputs stay uncompacted.
"""

import jax.numpy as jnp
import numpy as np
import pytest

import spark_rapids_tpu  # noqa: F401  (x64)
from spark_rapids_tpu import exprs as E
from spark_rapids_tpu.api.dataframe import TpuSession
from spark_rapids_tpu.benchmarks import tpch
from spark_rapids_tpu.columnar import batch as B
from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.host import device_to_host
from spark_rapids_tpu.exprs.base import BoundReference as Ref, lit
from spark_rapids_tpu.monitoring import recorder
from spark_rapids_tpu.ops import FilterExec, kernel_cache as kc
from spark_rapids_tpu.ops.join import (
    BroadcastHashJoinExec, ShuffledHashJoinExec)

from test_ops import compare_engines, source

CAP = 786_432           # q1's and q3's scan batches; 7.9 MB with one int64
AGG, PROBE = 1, B.PROBE_SHRINK_RATIO
KEPT_SAME, KEPT_RATIO, COMPACTED = (
    "shrinkKeptSameBucket", "shrinkKeptBelowRatio", "shrinkCompacted")
# live share -> (bucket of the live rows, what each consumer's flush does)
CASES = {
    98: (786_432, {AGG: KEPT_SAME, PROBE: KEPT_SAME}),
    54: (524_288, {AGG: COMPACTED, PROBE: KEPT_RATIO}),
    25: (196_608, {AGG: COMPACTED, PROBE: COMPACTED}),
    1: (8_192, {AGG: COMPACTED, PROBE: COMPACTED}),
}


def member(live_pct, cap=CAP, sel=True):
    """A batch of ``cap`` rows, ``live_pct`` % of them live: scattered
    under a selection vector, or as a dense prefix."""
    live = cap * live_pct // 100
    col = B.DeviceColumn(dt.INT64, jnp.arange(cap, dtype=jnp.int64),
                         jnp.ones((cap,), jnp.bool_))
    if not sel:
        return B.DeviceBatch((col,), jnp.asarray(live, jnp.int32)), live
    keep = np.zeros(cap, bool)
    keep[np.random.default_rng(live_pct).choice(cap, live, False)] = True
    return B.DeviceBatch((col,), jnp.asarray(cap, jnp.int32),
                         sel=jnp.asarray(keep)), live


def live_values(batch):
    return np.asarray(batch.columns[0].data)[np.asarray(batch.row_mask())]


@pytest.fixture(autouse=True)
def fresh_counts():
    B.reset_counters()
    yield
    B.reset_counters()


@pytest.mark.parametrize("keep_ratio", [AGG, PROBE], ids=["agg", "probe"])
@pytest.mark.parametrize("live_pct", sorted(CASES))
def test_member_is_compacted_only_where_the_rule_says(live_pct, keep_ratio):
    bucket, outcomes = CASES[live_pct]
    what = outcomes[keep_ratio]
    b, live = member(live_pct)
    assert b.device_size_bytes() >= B.MIN_SHRINK_BYTES
    want = live_values(b)
    kc.cache().clear()
    (out,) = list(B.coalesce_iter([b], 1 << 20, shrink=True,
                                  keep_ratio=keep_ratio))
    assert out.rows_hint == live
    if what == COMPACTED:
        assert out.capacity == bucket and out.sel is None
        assert int(out.num_rows) == live
    else:
        assert out is b and out.sel is b.sel and out.capacity == CAP
    np.testing.assert_array_equal(live_values(out), want)
    assert B.counters() == {
        "shrinkMembers": 1, KEPT_SAME: 0, KEPT_RATIO: 0, COMPACTED: 0,
        "shrinkRowsKept": 0 if what == COMPACTED else CAP, what: 1}
    # Nothing rewrote the member into its own capacity.
    assert ("shrink", CAP) not in kc.cache().keys()
    assert (("shrink", bucket) in kc.cache().keys()) == (what == COMPACTED)


@pytest.mark.parametrize("keep_ratio", [AGG, PROBE], ids=["agg", "probe"])
@pytest.mark.parametrize("live_pct,bucket", [(98, CAP), (54, 524_288)])
def test_dense_member_keeps_its_prefix_as_ever(live_pct, bucket, keep_ratio):
    b, live = member(live_pct, sel=False)
    want = live_values(b)
    (out,) = list(B.coalesce_iter([b], 1 << 20, shrink=True,
                                  keep_ratio=keep_ratio))
    assert out.capacity == bucket and out.sel is None
    assert (out is b) == (bucket == CAP) and out.rows_hint == live
    np.testing.assert_array_equal(live_values(out), want)
    counts = B.counters()
    assert counts[COMPACTED] == (bucket < CAP)
    assert counts[KEPT_SAME] == counts[KEPT_RATIO] == 0
    assert counts["shrinkRowsKept"] == 0


def test_prefix_shrink_moves_nothing_and_zeroes_the_padding():
    """A dense batch shrinks by a slice of its prefix: no slab is packed
    over the input's capacity and nothing is gathered. Slots past
    ``num_rows`` come out null and zeroed whatever they held."""
    import jax
    cap, live = 4_096, 100
    text = np.full((cap, 8), ord("x"), np.uint8)
    b = B.DeviceBatch((
        B.DeviceColumn(dt.FLOAT64, jnp.arange(cap, dtype=jnp.float64) + 1,
                       jnp.ones((cap,), jnp.bool_)),
        B.DeviceColumn(dt.STRING, jnp.asarray(text),
                       jnp.ones((cap,), jnp.bool_),
                       jnp.full((cap,), 8, jnp.int32))),
        jnp.asarray(live, jnp.int32))
    out = B.shrink_to_capacity(b, 128)
    assert out.capacity == 128 and int(out.num_rows) == live
    for c, dirty in zip(out.columns, b.columns):
        valid = np.asarray(c.validity)
        assert valid[:live].all() and not valid[live:].any()
        np.testing.assert_array_equal(np.asarray(c.data)[:live],
                                      np.asarray(dirty.data)[:live])
        assert not np.asarray(c.data)[live:].any()
    assert not np.asarray(out.columns[1].lengths)[live:].any()
    jaxpr = str(jax.make_jaxpr(
        lambda x: kc.cache().get(("shrink", 128), None)[0].fn(x))(b))
    assert "gather" not in jaxpr and "concatenate" not in jaxpr


def test_member_under_the_byte_floor_is_neither_pulled_nor_counted():
    b, _ = member(50, cap=4_096)
    (out,) = list(B.coalesce_iter([b], 1 << 20, shrink=True))
    assert out is b and out.rows_hint is None
    assert B.counters() == {"shrinkMembers": 1, KEPT_SAME: 0, KEPT_RATIO: 0,
                            COMPACTED: 0, "shrinkRowsKept": 0}


@pytest.mark.parametrize("live_pct", sorted(CASES))
def test_other_callers_of_shrink_all_still_get_dense_batches(live_pct):
    """The exchange's flush, the broadcast, the aggregate's merge loop and
    the download pass no ``keep_ratio``: a selection vector compacts away
    at any live share, and nothing is counted."""
    b, live = member(live_pct)
    want = live_values(b)
    (out,), (count,) = B.shrink_all([b])
    assert count == live and out.rows_hint == live
    assert out.sel is None and out.capacity == CASES[live_pct][0]
    np.testing.assert_array_equal(live_values(out), want)
    assert not any(B.counters().values())


def test_kept_member_is_not_pulled_again_and_compacts_for_a_dense_consumer(
        monkeypatch):
    b, live = member(98)
    (kept,) = list(B.coalesce_iter([b], 1 << 20, shrink=True))
    assert kept is b and kept.rows_hint == live
    import jax
    monkeypatch.setattr(jax, "device_get", lambda *_: pytest.fail("pulled"))
    (out,), (count,) = B.shrink_all([kept])
    assert count == live and out.sel is None and out.capacity == CAP


def test_grouped_members_concatenate_dense_whatever_was_kept(
        rule_engages_at_small_sizes):
    (a, na), (b, nb) = member(98, cap=CAP // 2), member(54, cap=CAP // 2)
    want = np.concatenate([live_values(a), live_values(b)])
    (out,) = list(B.coalesce_iter([a, b], CAP, shrink=True,
                                  keep_ratio=PROBE))
    assert out.sel is None and out.rows_hint == na + nb
    np.testing.assert_array_equal(live_values(out), want)
    assert B.counters()[KEPT_SAME] == 1 and B.counters()[KEPT_RATIO] == 1


def test_span_carries_the_three_counts():
    recorder.configure(True, recorder.LEVEL_KERNEL)
    try:
        recorder.reset()
        b, _ = member(54)
        list(B.coalesce_iter([b], 1 << 20, shrink=True, owner="SomeExec",
                             keep_ratio=PROBE))
        (args,) = [e[7] for e in recorder.events() if e[1] == "shrink-all"]
    finally:
        recorder.configure(False)
    assert args == {"op": "SomeExec", KEPT_SAME: 0, KEPT_RATIO: 1,
                    COMPACTED: 0}


def test_process_totals_lose_no_update_under_threads():
    import sys
    import threading
    b, _ = member(50, cap=64)
    flushes, workers = 200, 16

    def work():
        for _ in range(flushes):
            list(B.coalesce_iter([b], 64, shrink=True))
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert B.counters()["shrinkMembers"] == flushes * workers


# -- whole queries whose inputs stay uncompacted ------------------------------

@pytest.fixture
def rule_engages_at_small_sizes(monkeypatch):
    monkeypatch.setattr(B, "MIN_SHRINK_BYTES", 0)


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("tpch"))
    tpch.generate(d, scale=0.003, files_per_table=3, seed=7)
    return d


@pytest.mark.parametrize("qname,kept", [("q1", KEPT_SAME),
                                        ("q3", KEPT_RATIO)])
def test_tpch_equals_pandas_with_the_rule_engaged(
        qname, kept, data_dir, rule_engages_at_small_sizes):
    s = TpuSession()
    s.set("spark.rapids.sql.variableFloatAgg.enabled", True)
    got = tpch.QUERIES[qname](s, data_dir).collect()
    want = tpch.pandas_query(qname, data_dir)
    assert tpch.check_result(qname, got, want), (got, want)
    assert B.counters()[kept] > 0, B.counters()


def test_q3_probes_late_and_the_aggregate_pulls_nothing(
        data_dir, rule_engages_at_small_sizes):
    """PR 37: q3's inner joins over direct-address tables gather the
    build side's rows after the compaction. The answer is the single
    program's to the bit; the aggregate's ``coalesce_iter`` finds the
    counts on the batches and pulls nothing; and the only reads the
    query gained are the joins' own count pulls, one a window."""
    from spark_rapids_tpu.monitoring import syncs
    from spark_rapids_tpu.ops import join as J

    def run(late):
        real = J._JoinKernelMixin._dense_stream
        if not late:
            # the parent's loop: every batch through the single program
            def eager_only(self, ctx, built, probe_iter, keys, right):
                dense = self._dense_jit_fn()
                for p in probe_iter:
                    yield dense(built, p, probe_keys=keys,
                                build_is_right=right)
            J._JoinKernelMixin._dense_stream = eager_only
        syncs.install()
        recorder.reset()
        recorder.reset_counters()
        try:
            s = TpuSession()
            s.set("spark.rapids.sql.variableFloatAgg.enabled", True)
            s.set("spark.rapids.sql.trace.enabled", True)
            s.set("spark.rapids.sql.trace.level", "kernel")
            got = tpch.QUERIES["q3"](s, data_dir).collect()
            return (got, recorder.counters(),
                    {k: n for k, (n, _) in syncs.sync_stats().items()})
        finally:
            J._JoinKernelMixin._dense_stream = real
            recorder.configure(False)
            recorder.reset()
            recorder.reset_counters()

    def owned(stats, owner):
        return sum(n for k, n in stats.items() if k.endswith(owner))

    want = tpch.pandas_query("q3", data_dir)
    run(late=True)      # a process's first collect reads more (calibration)
    got, counts, late = run(late=True)
    assert tpch.check_result("q3", got, want), (got, want)
    assert counts.get("joinLateEmitBucket", 0) > 0, counts
    eager_rows, _, eager = run(late=False)
    assert eager_rows == got
    agg = "HashAggregateExec:shrink-all"
    assert owned(eager, agg) > 0 and owned(late, agg) == 0, (eager, late)
    pulls = owned(late, "BroadcastHashJoinExec:counts")
    assert pulls == counts["joinLateWindows"]
    assert sum(late.values()) - pulls == \
        sum(eager.values()) - owned(eager, agg), (late, eager)


PROBE_ROWS = 200
BUILD = {"b": [k for k in range(0, 260, 2) for _ in (0, 1)],
         "w": list(range(260))}        # every build key twice: not dense


@pytest.mark.parametrize("join_type", ["inner", "left", "full", "semi",
                                       "anti"])
@pytest.mark.parametrize("live,kept", [(196, KEPT_SAME), (140, KEPT_RATIO)])
@pytest.mark.parametrize("join", [BroadcastHashJoinExec,
                                  ShuffledHashJoinExec],
                         ids=["broadcast", "shuffled"])
def test_join_over_uncompacted_probe_batches(
        join, live, kept, join_type, rule_engages_at_small_sizes):
    """Duplicate build keys keep the probe off the direct-address table:
    the sorted-search paths and the ``full`` join's coverage accumulator
    see a selection-vector probe batch at its full capacity."""
    probe = FilterExec(
        source([("a", dt.INT32), ("v", dt.INT32)],
               {"a": [(7 * i) % 300 for i in range(PROBE_ROWS)],
                "v": list(range(PROBE_ROWS))}),
        E.LessThan(Ref(1, dt.INT32), lit(live)))
    plan = join(probe, source([("b", dt.INT32), ("w", dt.INT32)], BUILD),
                [Ref(0, dt.INT32)], [Ref(0, dt.INT32)], join_type)
    compare_engines(plan, sort_result=True)
    counts = B.counters()
    assert counts[kept] == 1 and counts[COMPACTED] == 0, counts


def test_dense_probe_and_partial_skip_over_uncompacted_batches(
        rule_engages_at_small_sizes):
    """Unique build keys take the direct-address probe, whose output (a
    selection vector over the probe's capacity) feeds a keyed aggregate
    that skips its partial pass: both read the mask."""
    s = TpuSession()
    s.set("spark.rapids.sql.variableFloatAgg.enabled", True)
    s.set("spark.rapids.sql.agg.skipAggPassReductionRatio", 0.0)
    n = 3_000
    left = s.create_dataframe(
        {"k": [i % 1_500 for i in range(n)], "v": list(range(n))},
        [("k", dt.INT32), ("v", dt.INT64)], num_partitions=2)
    right = s.create_dataframe(
        {"k2": list(range(0, 1_500, 2)), "w": list(range(750))},
        [("k2", dt.INT32), ("w", dt.INT64)])
    from spark_rapids_tpu.api import agg_sum, col
    df = left.filter(col("v") < 2_900).join_on(right, ["k"], ["k2"]) \
        .group_by("k").agg(agg_sum(col("w")).alias("sw"),
                           agg_sum(col("v")).alias("sv"))
    got = sorted(df.collect())
    assert got == sorted(df.collect_host())
    assert B.counters()[KEPT_SAME] > 0, B.counters()


def test_download_of_a_kept_batch_is_dense():
    b, live = member(98)
    (kept,) = list(B.coalesce_iter([b], 1 << 20, shrink=True))
    assert device_to_host(kept).num_rows == live


@pytest.mark.parametrize("shrink", [False, True])
def test_a_large_member_goes_on_alone_among_the_runs_of_small_ones(shrink):
    """PR 35: a member of ``COALESCE_ALONE_ROWS`` rows or more is not
    moved to spare its consumer a round of dispatches; the smaller ones
    around it are concatenated as ever, and the order stays."""
    big_cap, small_cap = B.COALESCE_ALONE_ROWS, B.COALESCE_ALONE_ROWS // 4
    (s1, n1), (s2, n2), (s3, n3) = (member(98, small_cap, sel=False),
                                    member(98, small_cap, sel=False),
                                    member(98, small_cap, sel=False))
    (b1, m1), (b2, m2) = member(98, big_cap), member(98, big_cap)
    recorder.configure(True)
    recorder.reset_counters()
    try:
        out = list(B.coalesce_iter([s1, s2, b1, b2, s3], 4 << 20,
                                   shrink=shrink, keep_ratio=PROBE))
        alone = recorder.counters().get("coalesceAloneRows")
    finally:
        recorder.configure(False)
        recorder.reset_counters()
    assert [o.capacity for o in out] == [2 * small_cap, big_cap, big_cap,
                                         small_cap]
    assert out[1] is b1 and out[2] is b2        # not rewritten
    assert alone == 2 * big_cap
    got = [sorted(live_values(o).tolist()) for o in out]
    assert got[0] == sorted(live_values(s1).tolist()
                            + live_values(s2).tolist())
    assert len(got[1]) == m1 and len(got[2]) == m2 and len(got[3]) == n3
    # a group of one is no decision: nothing counted
    recorder.configure(True)
    try:
        (only,) = list(B.coalesce_iter([b1], 4 << 20, shrink=shrink))
        assert only is b1
        assert "coalesceAloneRows" not in recorder.counters()
    finally:
        recorder.configure(False)
        recorder.reset_counters()
