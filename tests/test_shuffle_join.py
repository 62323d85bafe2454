"""Shuffle exchange, partitioning, and join tests (device vs host oracle).

Ref test models: GpuPartitioningSuite, HashAggregatesSuite join-side tests,
integration_tests join/repartition pytest files.
"""

import math

import numpy as np
import pytest

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.host import HostBatch
from spark_rapids_tpu import exprs as E
from spark_rapids_tpu.exprs.base import BoundReference as Ref, lit
from spark_rapids_tpu.ops import (
    AggSpec, CountStar, HashAggregateExec, SortExec, SortOrder, Sum)
from spark_rapids_tpu.ops.join import (
    BroadcastHashJoinExec, BroadcastNestedLoopJoinExec, ShuffledHashJoinExec)
from spark_rapids_tpu.parallel import (
    BroadcastExchangeExec, HashPartitioning, RangePartitioning,
    RoundRobinPartitioning, ShuffleExchangeExec, SinglePartitioning)

from harness import assert_rows_equal
from test_ops import compare_engines, source


ORDERS_SCHEMA = [("o_key", dt.INT32), ("o_cust", dt.INT32),
                 ("o_total", dt.FLOAT64)]
ORDERS = {
    "o_key": [1, 2, 3, 4, 5, 6],
    "o_cust": [10, 20, 10, None, 30, 20],
    "o_total": [100.0, 200.0, 150.0, 50.0, 300.0, 250.0],
}
CUST_SCHEMA = [("c_key", dt.INT32), ("c_name", dt.STRING)]
CUST = {
    "c_key": [10, 20, 40, None],
    "c_name": ["alice", "bob", "dan", "ghost"],
}


class TestShuffleExchange:
    def test_hash_partition_preserves_rows(self):
        src = source(ORDERS_SCHEMA, ORDERS, num_partitions=2,
                     batches_per_partition=2)
        ex = ShuffleExchangeExec(src, HashPartitioning(
            [Ref(1, dt.INT32)], 4))
        dev = compare_engines(ex, sort_result=True)
        assert len(dev) == 6

    def test_hash_partition_device_host_same_buckets(self):
        # Same partition id per row on both engines (murmur3 parity).
        src = source(ORDERS_SCHEMA, ORDERS)
        ex = ShuffleExchangeExec(src, HashPartitioning(
            [Ref(1, dt.INT32)], 3))
        from spark_rapids_tpu.ops.base import ExecContext
        for p in range(3):
            ctx_d = ExecContext()
            ctx_h = ExecContext()
            dev_rows = []
            for b in ex.execute_device(ctx_d, p):
                from spark_rapids_tpu.columnar.host import device_to_host
                dev_rows.extend(device_to_host(b).to_pylist())
            host_rows = []
            for hb in ex.execute_host(ctx_h, p):
                host_rows.extend(hb.to_pylist())
            assert_rows_equal(dev_rows, host_rows, msg=f"partition {p}")

    def test_same_key_same_partition(self):
        src = source(ORDERS_SCHEMA, ORDERS)
        ex = ShuffleExchangeExec(src, HashPartitioning([Ref(1, dt.INT32)],
                                                       3))
        from spark_rapids_tpu.ops.base import ExecContext
        from spark_rapids_tpu.columnar.host import device_to_host
        ctx = ExecContext()
        seen = {}
        for p in range(3):
            for b in ex.execute_device(ctx, p):
                for row in device_to_host(b).to_pylist():
                    seen.setdefault(row[1], set()).add(p)
        for k, parts in seen.items():
            assert len(parts) == 1, f"key {k} split across {parts}"

    def test_round_robin(self):
        src = source(ORDERS_SCHEMA, ORDERS)
        ex = ShuffleExchangeExec(src, RoundRobinPartitioning(4))
        dev = compare_engines(ex, sort_result=True)
        assert len(dev) == 6

    def test_single(self):
        from spark_rapids_tpu.ops.base import ExecContext
        src = source(ORDERS_SCHEMA, ORDERS, num_partitions=3)
        ex = ShuffleExchangeExec(src, SinglePartitioning())
        assert ex.num_partitions(ExecContext()) == 1
        dev = compare_engines(ex, sort_result=True)
        assert len(dev) == 6

    def test_range_partition_orders_between_partitions(self):
        src = source(ORDERS_SCHEMA, ORDERS, num_partitions=2)
        ex = ShuffleExchangeExec(src, RangePartitioning(
            [SortOrder(Ref(0, dt.INT32))], 3))
        from spark_rapids_tpu.ops.base import ExecContext
        from spark_rapids_tpu.columnar.host import device_to_host
        ctx = ExecContext()
        maxes = []
        all_rows = []
        for p in range(3):
            vals = []
            for b in ex.execute_device(ctx, p):
                vals.extend(r[0] for r in device_to_host(b).to_pylist())
            all_rows.extend(vals)
            if vals:
                maxes.append((min(vals), max(vals)))
        assert sorted(all_rows) == [1, 2, 3, 4, 5, 6]
        for (lo1, hi1), (lo2, hi2) in zip(maxes, maxes[1:]):
            assert hi1 <= lo2

    def test_shuffle_then_two_stage_agg(self):
        # partial agg -> hash exchange on key -> final agg: the real
        # distributed aggregation plan shape.
        src = source(ORDERS_SCHEMA, ORDERS, num_partitions=2)
        partial = HashAggregateExec(
            src, [("cust", Ref(1, dt.INT32))],
            [AggSpec("total", Sum(Ref(2, dt.FLOAT64))),
             AggSpec("n", CountStar(None))], mode="partial")
        ex = ShuffleExchangeExec(partial,
                                 HashPartitioning([Ref(0, dt.INT32)], 3))
        final = HashAggregateExec(
            ex, [("cust", Ref(0, dt.INT32))],
            [AggSpec("total", Sum(Ref(2, dt.FLOAT64))),
             AggSpec("n", CountStar(None))], mode="final")
        compare_engines(final,
                        [(10, 250.0, 2), (20, 450.0, 2), (None, 50.0, 1),
                         (30, 300.0, 1)],
                        approx_float=True, sort_result=True)


def join_sources():
    left = source(ORDERS_SCHEMA, ORDERS, batches_per_partition=2)
    right = source(CUST_SCHEMA, CUST)
    return left, right


class TestJoins:
    def _expected_inner(self):
        out = []
        for ok, oc, ot in zip(ORDERS["o_key"], ORDERS["o_cust"],
                              ORDERS["o_total"]):
            for ck, cn in zip(CUST["c_key"], CUST["c_name"]):
                if oc is not None and ck is not None and oc == ck:
                    out.append((ok, oc, ot, ck, cn))
        return out

    def test_inner_broadcast(self):
        left, right = join_sources()
        plan = BroadcastHashJoinExec(
            left, right, [Ref(1, dt.INT32)], [Ref(0, dt.INT32)], "inner")
        compare_engines(plan, self._expected_inner(), sort_result=True)

    @pytest.mark.parametrize("join_type", ["inner", "left", "semi"])
    def test_dense_probe_program_is_not_traced_per_key_range(self,
                                                             join_type):
        """The direct-address probe takes the table's key minimum and span
        as device vectors: build sides over other keys (another seed,
        another partition) run the SAME program. As host constants in the
        pytree they made every new build side a new trace, and a new
        compile in every process (PR 30)."""
        traces = []
        for base in (10, 5000, 70000):
            left = source(ORDERS_SCHEMA, {
                "o_key": [1, 2, 3, 4],
                "o_cust": [base, base + 2, None, base + 9],
                "o_total": [1.0, 2.0, 3.0, 4.0]})
            right = source([("c_key", dt.INT32), ("c_acct", dt.INT64)], {
                "c_key": [base + 2, base, base + 5],
                "c_acct": [7, 8, 9]})
            plan = BroadcastHashJoinExec(
                left, right, [Ref(1, dt.INT32)], [Ref(0, dt.INT32)],
                join_type)
            dev = compare_engines(plan, sort_result=True)
            assert len(dev) == {"inner": 2, "left": 4, "semi": 2}[join_type]
            traces.append(plan._dense_jit_fn().fn._cache_size())
        # (other tests' joins of this shape may have traced it before)
        assert traces[0] >= 1 and traces[1:] == traces[:1] * 2

    @pytest.mark.parametrize("join", [BroadcastHashJoinExec,
                                      ShuffledHashJoinExec],
                             ids=["broadcast", "shuffled"])
    @pytest.mark.parametrize("match_every", [1, 3, 40])
    def test_inner_dense_join_gathers_late(self, join, match_every,
                                           monkeypatch):
        """PR 37: an inner join over a direct-address table looks up,
        pulls its window's match counts and emits at their buckets (or,
        where nearly every row matches, at the probe's capacity): the
        rows are the host engine's either way, through the join's own
        input coalescing, several batches a partition, NULL and absent
        keys and a string payload."""
        from spark_rapids_tpu.columnar import batch as B
        from spark_rapids_tpu.monitoring import recorder
        monkeypatch.setattr(B, "MIN_SHRINK_BYTES", 0)
        monkeypatch.setattr(B, "COALESCE_ALONE_ROWS", 64)
        n = 600
        left = source(
            [("o_key", dt.INT32), ("o_cust", dt.INT64),
             ("o_note", dt.STRING)],
            {"o_key": list(range(n)),
             "o_cust": [None if i % 11 == 0 else
                        (i % 97 if i % match_every == 0 else 5_000 + i)
                        for i in range(n)],
             "o_note": [f"n{i % 13}" for i in range(n)]},
            num_partitions=2, batches_per_partition=3)
        right = source([("c_key", dt.INT64), ("c_name", dt.STRING)],
                       {"c_key": list(range(97)),
                        "c_name": [f"c{i}" for i in range(97)]})
        if join is ShuffledHashJoinExec:     # co-partition both sides
            left = ShuffleExchangeExec(
                left, HashPartitioning([Ref(1, dt.INT64)], 2))
            right = ShuffleExchangeExec(
                right, HashPartitioning([Ref(0, dt.INT64)], 2))
        plan = join(left, right, [Ref(1, dt.INT64)], [Ref(0, dt.INT64)],
                    "inner")
        monkeypatch.setenv("SRT_TRACE", "1")    # every collect adopts it
        recorder.reset_counters()
        try:
            dev = compare_engines(plan, sort_result=True)
            counts = recorder.counters()
        finally:
            recorder.configure(False)
            recorder.reset_counters()
        assert len(dev) == sum(1 for i in range(n)
                               if i % 11 and i % match_every == 0)
        bucket = counts.get("joinLateEmitBucket", 0)
        capacity = counts.get("joinLateEmitCapacity", 0)
        assert counts["joinLateWindows"] >= 1 and bucket + capacity >= 1
        assert (bucket > 0) == (match_every > 1), counts

    def test_inner_shuffled(self):
        # Co-partition both sides by key first.
        left, right = join_sources()
        lex = ShuffleExchangeExec(left,
                                  HashPartitioning([Ref(1, dt.INT32)], 3))
        rex = ShuffleExchangeExec(right,
                                  HashPartitioning([Ref(0, dt.INT32)], 3))
        plan = ShuffledHashJoinExec(
            lex, rex, [Ref(1, dt.INT32)], [Ref(0, dt.INT32)], "inner")
        compare_engines(plan, self._expected_inner(), sort_result=True)

    def test_left_outer(self):
        left, right = join_sources()
        plan = BroadcastHashJoinExec(
            left, right, [Ref(1, dt.INT32)], [Ref(0, dt.INT32)], "left")
        inner = self._expected_inner()
        matched = {r[0] for r in inner}
        expected = inner + [
            (ok, oc, ot, None, None)
            for ok, oc, ot in zip(ORDERS["o_key"], ORDERS["o_cust"],
                                  ORDERS["o_total"]) if ok not in matched]
        compare_engines(plan, expected, sort_result=True)

    def test_right_outer(self):
        left, right = join_sources()
        plan = BroadcastHashJoinExec(
            left, right, [Ref(1, dt.INT32)], [Ref(0, dt.INT32)], "right")
        inner = self._expected_inner()
        matched_c = {r[3] for r in inner}
        expected = inner + [
            (None, None, None, ck, cn)
            for ck, cn in zip(CUST["c_key"], CUST["c_name"])
            if ck not in matched_c]
        compare_engines(plan, expected, sort_result=True)

    def test_full_outer(self):
        left, right = join_sources()
        plan = BroadcastHashJoinExec(
            left, right, [Ref(1, dt.INT32)], [Ref(0, dt.INT32)], "full")
        inner = self._expected_inner()
        matched_o = {r[0] for r in inner}
        matched_c = {r[3] for r in inner}
        expected = inner + [
            (ok, oc, ot, None, None)
            for ok, oc, ot in zip(ORDERS["o_key"], ORDERS["o_cust"],
                                  ORDERS["o_total"])
            if ok not in matched_o] + [
            (None, None, None, ck, cn)
            for ck, cn in zip(CUST["c_key"], CUST["c_name"])
            if ck not in matched_c]
        compare_engines(plan, expected, sort_result=True)

    def test_semi_anti(self):
        left, right = join_sources()
        semi = BroadcastHashJoinExec(
            left, right, [Ref(1, dt.INT32)], [Ref(0, dt.INT32)], "semi")
        inner_keys = {r[0] for r in self._expected_inner()}
        expected = [(ok, oc, ot) for ok, oc, ot in
                    zip(ORDERS["o_key"], ORDERS["o_cust"],
                        ORDERS["o_total"]) if ok in inner_keys]
        compare_engines(semi, expected, sort_result=True)
        left2, right2 = join_sources()
        anti = BroadcastHashJoinExec(
            left2, right2, [Ref(1, dt.INT32)], [Ref(0, dt.INT32)], "anti")
        expected = [(ok, oc, ot) for ok, oc, ot in
                    zip(ORDERS["o_key"], ORDERS["o_cust"],
                        ORDERS["o_total"]) if ok not in inner_keys]
        compare_engines(anti, expected, sort_result=True)

    def test_inner_with_condition(self):
        left, right = join_sources()
        # join on key AND o_total > 150
        plan = BroadcastHashJoinExec(
            left, right, [Ref(1, dt.INT32)], [Ref(0, dt.INT32)], "inner",
            condition=E.GreaterThan(Ref(2, dt.FLOAT64), lit(150.0)))
        expected = [r for r in self._expected_inner() if r[2] > 150.0]
        compare_engines(plan, expected, sort_result=True)

    def test_left_with_condition(self):
        left, right = join_sources()
        plan = BroadcastHashJoinExec(
            left, right, [Ref(1, dt.INT32)], [Ref(0, dt.INT32)], "left",
            condition=E.GreaterThan(Ref(2, dt.FLOAT64), lit(150.0)))
        inner = [r for r in self._expected_inner() if r[2] > 150.0]
        matched = {r[0] for r in inner}
        expected = inner + [
            (ok, oc, ot, None, None)
            for ok, oc, ot in zip(ORDERS["o_key"], ORDERS["o_cust"],
                                  ORDERS["o_total"]) if ok not in matched]
        compare_engines(plan, expected, sort_result=True)

    def test_cross_join(self):
        left = source([("a", dt.INT32)], {"a": [1, 2, 3]})
        right = source([("b", dt.STRING)], {"b": ["x", "y"]})
        plan = BroadcastNestedLoopJoinExec(left, right, "cross")
        expected = [(a, b) for a in [1, 2, 3] for b in ["x", "y"]]
        compare_engines(plan, expected, sort_result=True)

    def test_string_join_keys(self):
        left = source([("k", dt.STRING), ("v", dt.INT32)],
                      {"k": ["a", "b", None, "c"], "v": [1, 2, 3, 4]})
        right = source([("k2", dt.STRING), ("w", dt.INT32)],
                       {"k2": ["a", "c", "d", None], "w": [10, 30, 40, 50]})
        plan = BroadcastHashJoinExec(
            left, right, [Ref(0, dt.STRING)], [Ref(0, dt.STRING)], "inner")
        compare_engines(plan, [("a", 1, "a", 10), ("c", 4, "c", 30)],
                        sort_result=True)

    def test_join_duplicate_build_keys(self):
        left = source([("k", dt.INT32)], {"k": [1, 1, 2]})
        right = source([("k2", dt.INT32), ("w", dt.STRING)],
                       {"k2": [1, 1, 1, 2], "w": ["a", "b", "c", "d"]})
        plan = BroadcastHashJoinExec(
            left, right, [Ref(0, dt.INT32)], [Ref(0, dt.INT32)], "inner")
        dev = compare_engines(plan, sort_result=True)
        assert len(dev) == 7  # 2 left rows x 3 matches + 1 x 1


class TestJoinReviewRegressions:
    def test_nested_loop_right_and_full(self):
        left = source([("a", dt.INT32)], {"a": [5]})
        right = source([("b", dt.INT32)], {"b": [1, 9]})
        # b > a condition: (5,9) matches; b=1 unmatched.
        plan = BroadcastNestedLoopJoinExec(
            left, right, "right",
            condition=E.GreaterThan(Ref(1, dt.INT32), Ref(0, dt.INT32)))
        compare_engines(plan, [(5, 9), (None, 1)], sort_result=True)
        plan = BroadcastNestedLoopJoinExec(
            source([("a", dt.INT32)], {"a": [5]}),
            source([("b", dt.INT32)], {"b": [1, 9]}), "full",
            condition=E.GreaterThan(Ref(1, dt.INT32), Ref(0, dt.INT32)))
        compare_engines(plan, [(5, 9), (None, 1)], sort_result=True)
        plan = BroadcastNestedLoopJoinExec(
            source([("a", dt.INT32)], {"a": [5, 99]}),
            source([("b", dt.INT32)], {"b": [1, 9]}), "left",
            condition=E.GreaterThan(Ref(1, dt.INT32), Ref(0, dt.INT32)))
        compare_engines(plan, [(5, 9), (99, None)], sort_result=True)

    def test_nested_loop_empty_build(self):
        left = source([("a", dt.INT32)], {"a": [1, 2]})
        right = source([("b", dt.INT32)], {"b": []})
        plan = BroadcastNestedLoopJoinExec(left, right, "left")
        compare_engines(plan, [(1, None), (2, None)], sort_result=True)
        plan = BroadcastNestedLoopJoinExec(
            source([("a", dt.INT32)], {"a": [1, 2]}),
            source([("b", dt.INT32)], {"b": []}), "cross")
        compare_engines(plan, [])

    def test_range_partition_host_engine(self):
        src = source(ORDERS_SCHEMA, ORDERS, num_partitions=2)
        ex = ShuffleExchangeExec(src, RangePartitioning(
            [SortOrder(Ref(0, dt.INT32))], 3))
        dev = compare_engines(ex, sort_result=True)
        assert len(dev) == 6


def test_nested_loop_with_filtered_small_build():
    """A small filtered build side keeps its selection vector past the
    broadcast (no shrink pull) — the NLJ must not pair probe rows with
    sel-deleted build rows."""
    from spark_rapids_tpu import FLOAT64, INT64
    from spark_rapids_tpu.api.dataframe import TpuSession
    from spark_rapids_tpu.plan.logical import col
    s = TpuSession()
    left = s.create_dataframe({"a": [1, 2, 3]}, [("a", INT64)])
    right = s.create_dataframe({"b": [10, 20, 30, 40]}, [("b", INT64)]) \
        .filter(col("b") >= 30)
    j = left.cross_join(right)
    got = sorted(j.collect())
    want = sorted(j.collect_host())
    assert got == want
    assert len(got) == 6        # 3 x 2, not 3 x 4


def test_build_side_is_one_program_per_shape():
    """The join build side dispatches ONE jitted program per (keys, batch
    shape): op by op it was ~240 one-op programs per shape, each a
    compile of its own on the chip (PR 21: 342 of the 366 programs q3's
    first run compiled there)."""
    import jax
    from spark_rapids_tpu.columnar.host import host_to_device
    from spark_rapids_tpu.ops.join import build_side

    def batch(seed):
        rng = np.random.default_rng(seed)
        return host_to_device(HostBatch.from_pydict(
            [("k", dt.INT64), ("v", dt.FLOAT64)],
            {"k": rng.integers(0, 50, 1000).tolist(),
             "v": rng.random(1000).tolist()}), capacity=1536)

    first, second = batch(1), batch(2)
    compiled = []

    def on_compile(event, duration, **kw):
        if event.endswith("backend_compile_duration"):
            compiled.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        built = build_side(first, [0])
        n_first = len(compiled)
        build_side(second, [0])             # same shape: nothing new
        n_second = len(compiled) - n_first
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
    assert n_first <= 1 and n_second == 0, (n_first, n_second)
    fp = np.asarray(built.fp)
    live = int(np.asarray(built.matchable).sum())
    assert live == 1000 and (np.diff(fp[:live].astype(np.float64)) >= 0).all()


def test_build_side_dispatches_through_the_kernel_choke_point():
    """The build side is a join's largest single allocation: it goes
    through ``kernel_cache.call`` like every other cached kernel — the
    op's metrics see the cache and the compile, and an OOM at the
    ``kernel`` site walks the ladder instead of killing the query. Under
    a trace it inlines and leaves the cache alone."""
    import jax
    from spark_rapids_tpu import faults
    from spark_rapids_tpu.columnar.host import host_to_device
    from spark_rapids_tpu.ops import kernel_cache as kc
    from spark_rapids_tpu.ops.base import Metrics
    from spark_rapids_tpu.ops.join import build_side

    batch = host_to_device(HostBatch.from_pydict(
        [("k", dt.INT64)], {"k": list(range(40, 0, -1))}), capacity=96)
    m = Metrics("join")
    # null_safe: a cache key no other test of this process has built.
    build_side(batch, [0], null_safe=True, metrics=m)
    assert m.values["kernelCacheMisses"] == 1 and m.values["compileTime"] > 0
    build_side(batch, [0], null_safe=True, metrics=m)
    assert m.values["kernelCacheHits"] == 1

    faults.reset_counters()
    faults.configure("oom@kernel:1")
    built = build_side(batch, [0], metrics=m)
    assert faults.counters().get("retriesAttempted", 0) >= 1
    assert sorted(np.asarray(built.batch.columns[0].data)[:40].tolist()) \
        == list(range(1, 41))
    assert (np.diff(np.asarray(built.fp)[:40].astype(np.float64)) >= 0).all()

    before = kc.cache().stats()
    traced = jax.jit(lambda b: build_side(b, [0]).fp)(batch)
    after = kc.cache().stats()
    assert (after["hits"], after["misses"]) == \
        (before["hits"], before["misses"])
    assert np.array_equal(np.asarray(traced), np.asarray(built.fp))
