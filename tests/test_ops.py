"""Physical operator tests: device engine vs host oracle
(SparkQueryCompareTestSuite analog at operator level)."""

import math

import numpy as np
import pytest

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.host import HostBatch
from spark_rapids_tpu import exprs as E
from spark_rapids_tpu.exprs.base import BoundReference as Ref, lit
from spark_rapids_tpu import ops
from spark_rapids_tpu.ops import (
    AggSpec, Average, Count, CountStar, ExecContext, FilterExec, First,
    GlobalLimitExec, HashAggregateExec, InMemorySourceExec, Last,
    LocalLimitExec, Max, Min, ProjectExec, RangeExec, SortExec, SortOrder,
    Sum, UnionExec)

from harness import assert_rows_equal


def source(schema, data, num_partitions=1, batches_per_partition=1):
    """Build an InMemorySourceExec, optionally splitting rows."""
    hb = HostBatch.from_pydict(schema, data)
    rows = hb.to_pylist()
    names = tuple(n for n, _ in schema)
    parts = []
    per = max(1, -(-len(rows) // num_partitions))
    chunks = [rows[i:i + per] for i in range(0, len(rows), per)] or [[]]
    while len(chunks) < num_partitions:
        chunks.append([])
    for chunk in chunks[:num_partitions]:
        bper = max(1, -(-len(chunk) // batches_per_partition))
        bs = []
        for j in range(0, max(len(chunk), 1), bper):
            sub = chunk[j:j + bper]
            cols = {n: [r[ci] for r in sub] for ci, n in enumerate(names)}
            bs.append(HostBatch.from_pydict(schema, cols))
        parts.append(bs)
    return InMemorySourceExec(tuple(schema), parts)


def compare_engines(plan, expected=None, approx_float=False,
                    sort_result=False):
    dev = plan.collect(device=True)
    host = plan.collect(device=False)
    expected = list(expected) if expected is not None else None
    if sort_result:
        keyf = lambda r: tuple((v is None, str(v)) for v in r)
        dev = sorted(dev, key=keyf)
        host = sorted(host, key=keyf)
        if expected is not None:
            expected = sorted(expected, key=keyf)
    assert_rows_equal(dev, host, approx_float, "device vs host engine")
    if expected is not None:
        assert_rows_equal(dev, expected, approx_float, "device vs oracle")
    return dev


SCHEMA = [("k", dt.STRING), ("v", dt.INT32), ("x", dt.FLOAT64)]
DATA = {
    "k": ["a", "b", "a", None, "b", "a", "c", None],
    "v": [1, 2, 3, 4, None, 6, 7, 8],
    "x": [1.0, 2.5, float("nan"), 4.0, 5.0, None, 7.5, 8.0],
}


class TestBasicOps:
    def test_project(self):
        plan = ProjectExec(source(SCHEMA, DATA),
                           [("v2", E.Multiply(Ref(1, dt.INT32), lit(2))),
                            ("up", E.Upper(Ref(0, dt.STRING)))])
        compare_engines(plan,
                        [(2, "A"), (4, "B"), (6, "A"), (8, None), (None, "B"),
                         (12, "A"), (14, "C"), (16, None)])

    def test_filter(self):
        plan = FilterExec(source(SCHEMA, DATA),
                          E.GreaterThan(Ref(1, dt.INT32), lit(3)))
        compare_engines(plan, [(None, 4, 4.0), ("a", 6, None),
                               ("c", 7, 7.5), (None, 8, 8.0)])

    def test_filter_multibatch(self):
        plan = FilterExec(source(SCHEMA, DATA, batches_per_partition=3),
                          E.IsNotNull(Ref(0, dt.STRING)))
        dev = compare_engines(plan)
        assert len(dev) == 6

    def test_union(self):
        s1 = source(SCHEMA, DATA)
        s2 = source(SCHEMA, DATA)
        plan = UnionExec(s1, s2)
        dev = compare_engines(plan)
        assert len(dev) == 16

    def test_limits(self):
        plan = LocalLimitExec(source(SCHEMA, DATA, batches_per_partition=4),
                              3)
        dev = compare_engines(plan)
        assert len(dev) == 3
        plan = GlobalLimitExec(source(SCHEMA, DATA), 5)
        assert len(compare_engines(plan)) == 5

    def test_range(self):
        plan = RangeExec(0, 100, 7, num_partitions=3, batch_rows=8)
        dev = compare_engines(plan)
        assert [r[0] for r in dev] == list(range(0, 100, 7))

    def test_range_negative_step(self):
        plan = RangeExec(10, -10, -3, num_partitions=2, batch_rows=4)
        dev = compare_engines(plan)
        assert [r[0] for r in dev] == list(range(10, -10, -3))


class TestSort:
    def test_sort_int_asc_desc(self):
        plan = SortExec(source(SCHEMA, DATA, batches_per_partition=2),
                        [SortOrder(Ref(1, dt.INT32))])
        dev = compare_engines(plan)
        assert [r[1] for r in dev] == [None, 1, 2, 3, 4, 6, 7, 8]
        plan = SortExec(source(SCHEMA, DATA),
                        [SortOrder(Ref(1, dt.INT32), ascending=False,
                                   nulls_first=False)])
        dev = compare_engines(plan)
        assert [r[1] for r in dev] == [8, 7, 6, 4, 3, 2, 1, None]

    def test_sort_string_then_int(self):
        plan = SortExec(source(SCHEMA, DATA),
                        [SortOrder(Ref(0, dt.STRING)),
                         SortOrder(Ref(1, dt.INT32), ascending=False,
                                   nulls_first=False)])
        dev = compare_engines(plan)
        assert [(r[0], r[1]) for r in dev] == [
            (None, 8), (None, 4), ("a", 6), ("a", 3), ("a", 1),
            ("b", 2), ("b", None), ("c", 7)]

    def test_sort_float_nan_greatest(self):
        plan = SortExec(source(SCHEMA, DATA),
                        [SortOrder(Ref(2, dt.FLOAT64), nulls_first=False)])
        dev = compare_engines(plan)
        xs = [r[2] for r in dev]
        assert xs[:5] == [1.0, 2.5, 4.0, 5.0, 7.5]
        assert xs[5] == 8.0
        assert math.isnan(xs[6]) and xs[7] is None

    def test_host_sort_negative_nan_greatest(self):
        # Sign-bit NaN must sort greatest on the host oracle too, matching
        # the device kernel's nan_word handling (Java Double.compare).
        import struct as _struct
        from spark_rapids_tpu.columnar.host import HostBatch, HostColumn
        from spark_rapids_tpu.ops.sort import sort_host_batch
        neg_nan = _struct.unpack("<d", _struct.pack("<Q",
                                                    0xFFF8000000000000))[0]
        vals = np.array([neg_nan, 1.0, -2.0, float("inf")], np.float64)
        hb = HostBatch(("x",), [HostColumn(dt.FLOAT64, vals,
                                           np.ones(4, np.bool_))])
        out = sort_host_batch(hb, [SortOrder(Ref(0, dt.FLOAT64))])
        xs = out.columns[0].data
        assert list(xs[:3]) == [-2.0, 1.0, float("inf")]
        assert math.isnan(xs[3])

    def test_sort_stable_ties(self):
        schema = [("a", dt.INT32), ("b", dt.INT32)]
        data = {"a": [1, 1, 1, 0, 0], "b": [10, 20, 30, 40, 50]}
        plan = SortExec(source(schema, data),
                        [SortOrder(Ref(0, dt.INT32))])
        dev = compare_engines(plan)
        assert [r[1] for r in dev] == [40, 50, 10, 20, 30]


class TestAggregate:
    def test_global_agg(self):
        plan = HashAggregateExec(
            source(SCHEMA, DATA, batches_per_partition=3), [],
            [AggSpec("cnt", CountStar(None)),
             AggSpec("cv", Count(Ref(1, dt.INT32))),
             AggSpec("sv", Sum(Ref(1, dt.INT32))),
             AggSpec("mn", Min(Ref(1, dt.INT32))),
             AggSpec("mx", Max(Ref(1, dt.INT32))),
             AggSpec("av", Average(Ref(1, dt.INT32)))])
        compare_engines(plan, [(8, 7, 31, 1, 8, 31 / 7)],
                        approx_float=True)

    def test_group_by_string_key(self):
        plan = HashAggregateExec(
            source(SCHEMA, DATA, batches_per_partition=2),
            [("k", Ref(0, dt.STRING))],
            [AggSpec("cnt", CountStar(None)),
             AggSpec("s", Sum(Ref(1, dt.INT32)))])
        compare_engines(plan,
                        [("a", 3, 10), ("b", 2, 2), (None, 2, 12),
                         ("c", 1, 7)], sort_result=True)

    def test_group_by_min_max_float_nan(self):
        plan = HashAggregateExec(
            source(SCHEMA, DATA), [("k", Ref(0, dt.STRING))],
            [AggSpec("mn", Min(Ref(2, dt.FLOAT64))),
             AggSpec("mx", Max(Ref(2, dt.FLOAT64)))])
        dev = compare_engines(plan, sort_result=True)
        bykey = {r[0]: r[1:] for r in dev}
        # group a: [1.0, nan, null] -> min 1.0, max NaN (NaN greatest)
        assert bykey["a"][0] == 1.0 and math.isnan(bykey["a"][1])
        assert bykey["b"] == (2.5, 5.0)

    def test_first_last(self):
        plan = HashAggregateExec(
            source(SCHEMA, DATA, batches_per_partition=2),
            [("k", Ref(0, dt.STRING))],
            [AggSpec("f", First(Ref(1, dt.INT32))),
             AggSpec("l", Last(Ref(1, dt.INT32)))])
        compare_engines(plan,
                        [("a", 1, 6), ("b", 2, 2), (None, 4, 8),
                         ("c", 7, 7)], sort_result=True)

    def test_avg_all_null_group(self):
        schema = [("k", dt.INT32), ("v", dt.INT32)]
        data = {"k": [1, 1, 2], "v": [None, None, 5]}
        plan = HashAggregateExec(
            source(schema, data), [("k", Ref(0, dt.INT32))],
            [AggSpec("s", Sum(Ref(1, dt.INT32))),
             AggSpec("a", Average(Ref(1, dt.INT32)))])
        compare_engines(plan, [(1, None, None), (2, 5, 5.0)],
                        sort_result=True)

    def test_partial_final_roundtrip(self):
        # Two-stage aggregation through buffer batches (shuffle-shaped).
        src = source(SCHEMA, DATA, batches_per_partition=2)
        partial = HashAggregateExec(
            src, [("k", Ref(0, dt.STRING))],
            [AggSpec("s", Sum(Ref(1, dt.INT32))),
             AggSpec("a", Average(Ref(1, dt.INT32)))], mode="partial")
        bufschema = partial.buffer_schema
        final = HashAggregateExec(
            partial, [("k", Ref(0, dt.STRING))],
            [AggSpec("s", Sum(Ref(1, dt.INT32))),
             AggSpec("a", Average(Ref(1, dt.INT32)))], mode="final")
        # In final mode buffers are read positionally from the child's
        # buffer schema; the agg children only define types.
        dev = final.collect(device=True)
        keyf = lambda r: tuple((v is None, str(v)) for v in r)
        expected = [("a", 10, 10 / 3), ("b", 2, 2.0), (None, 12, 6.0),
                    ("c", 7, 7.0)]
        assert_rows_equal(sorted(dev, key=keyf), sorted(expected, key=keyf),
                          True, "partial+final vs oracle")

    def test_group_by_float_key_normalization(self):
        schema = [("k", dt.FLOAT64), ("v", dt.INT32)]
        data = {"k": [0.0, -0.0, float("nan"), float("nan"), 1.5],
                "v": [1, 2, 3, 4, 5]}
        plan = HashAggregateExec(
            source(schema, data), [("k", Ref(0, dt.FLOAT64))],
            [AggSpec("s", Sum(Ref(1, dt.INT32)))])
        dev = compare_engines(plan, sort_result=True)
        # -0.0 groups with 0.0; NaN groups with NaN => 3 groups.
        assert len(dev) == 3


class TestAggReviewRegressions:
    """Regressions for the ops-layer code-review findings."""

    def test_string_min_max(self):
        schema = [("k", dt.INT32), ("s", dt.STRING)]
        data = {"k": [1, 1, 1, 2, 2, 3],
                "s": ["banana", "apple", None, "zz", "aa", None]}
        plan = HashAggregateExec(
            source(schema, data, batches_per_partition=2),
            [("k", Ref(0, dt.INT32))],
            [AggSpec("mn", Min(Ref(1, dt.STRING))),
             AggSpec("mx", Max(Ref(1, dt.STRING)))])
        compare_engines(plan,
                        [(1, "apple", "banana"), (2, "aa", "zz"),
                         (3, None, None)], sort_result=True)

    def test_string_min_max_prefix_ties(self):
        schema = [("k", dt.INT32), ("s", dt.STRING)]
        data = {"k": [1, 1, 1], "s": ["ab", "abc", "a"]}
        plan = HashAggregateExec(
            source(schema, data), [("k", Ref(0, dt.INT32))],
            [AggSpec("mn", Min(Ref(1, dt.STRING))),
             AggSpec("mx", Max(Ref(1, dt.STRING)))])
        compare_engines(plan, [(1, "a", "abc")])

    def test_string_first_last(self):
        schema = [("k", dt.INT32), ("s", dt.STRING)]
        data = {"k": [1, 1, 2, 1], "s": ["x", None, "mid", "y"]}
        plan = HashAggregateExec(
            source(schema, data, batches_per_partition=2),
            [("k", Ref(0, dt.INT32))],
            [AggSpec("f", First(Ref(1, dt.STRING))),
             AggSpec("l", Last(Ref(1, dt.STRING)))])
        compare_engines(plan, [(1, "x", "y"), (2, "mid", "mid")],
                        sort_result=True)

    def test_partial_final_host_engine(self):
        # The host oracle must run real two-stage plans too.
        src = source(SCHEMA, DATA, batches_per_partition=2)
        partial = HashAggregateExec(
            src, [("k", Ref(0, dt.STRING))],
            [AggSpec("s", Sum(Ref(1, dt.INT32))),
             AggSpec("a", Average(Ref(1, dt.INT32))),
             AggSpec("f", First(Ref(1, dt.INT32)))], mode="partial")
        final = HashAggregateExec(
            partial, [("k", Ref(0, dt.STRING))],
            [AggSpec("s", Sum(Ref(1, dt.INT32))),
             AggSpec("a", Average(Ref(1, dt.INT32))),
             AggSpec("f", First(Ref(1, dt.INT32)))], mode="final")
        compare_engines(final,
                        [("a", 10, 10 / 3, 1), ("b", 2, 2.0, 2),
                         (None, 12, 6.0, 4), ("c", 7, 7.0, 7)],
                        approx_float=True, sort_result=True)

    def test_cast_date_trailing_garbage_null(self):
        from harness import check_expr
        from spark_rapids_tpu.columnar.host import HostBatch
        b = HostBatch.from_pydict(
            [("s", dt.STRING)],
            {"s": ["2020-01-01", "2020-01-01garbage", "2020-1-2", "2020",
                   "2020-13-01", None]})
        check_expr(E.Cast(Ref(0, dt.STRING), dt.DATE), b,
                   [18262, None, 18263, 18262, None, None])


@pytest.mark.parametrize("dtype", ["float64", "int64", "int32"])
@pytest.mark.parametrize("rows", [2048, 700])
def test_grouped_aggregate_prefix_sums(dtype, rows):
    """The grouped aggregate's prefix sums: floats go through two levels
    of associative scans (a float64 cumsum costs the v5e compiler ~150 s),
    integers through jnp.cumsum — and the two levels are bit-identical to
    it for integers, which is what lets a measurement choose between
    them (``scripts/chip_probe.py prefix``)."""
    import jax.numpy as jnp
    from spark_rapids_tpu.ops.aggregate import (_prefix_sums,
                                                _two_level_prefix_sums)
    rng = np.random.default_rng(rows)
    if dtype == "float64":
        host = rng.uniform(0, 1e5, (rows, 3))
    else:
        host = rng.integers(-1000, 1000, (rows, 3)).astype(dtype)
    want = np.cumsum(host, axis=0, dtype=host.dtype)
    for fn in (_prefix_sums, _two_level_prefix_sums):
        got = np.asarray(fn(jnp.asarray(host)))
        assert got.dtype == host.dtype and got.shape == host.shape
        if dtype == "float64":
            np.testing.assert_allclose(got, want, rtol=1e-12)
        else:
            assert np.array_equal(got, want)
