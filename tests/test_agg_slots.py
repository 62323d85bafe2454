"""The grouped aggregate's sort-free update (PR 26).

``HashAggregateExec._update_batch`` looks for a batch's distinct key
fingerprints with masked min-reductions; at most ``_SLOT_MAX_GROUPS`` of
them and the batch is reduced group by group with masked sums
(``_slot_update``), more and it takes the sorted path
(``_sorted_update``). Here: both give the same groups in the same order
with the same leaders; the host oracle agrees; the choice shows in the
operator's metrics without a blocking read in the query; an operator
that cannot use slots traces no ``cond``; and the slot branch moves
nothing of the batch's size.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import spark_rapids_tpu  # noqa: F401  (x64)
from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.host import (
    HostBatch, device_to_host, host_to_device)
from spark_rapids_tpu.exprs.base import BoundReference as Ref
from spark_rapids_tpu.ops import (
    AggSpec, Average, Count, CountStar, ExecContext, First,
    HashAggregateExec, InMemorySourceExec, Max, Sum, aggregate, kernels)

from harness import assert_rows_equal

K = aggregate._SLOT_MAX_GROUPS
ROWS, CAP = 5 * (K + 1), 8 * (K + 1)
OFF = jnp.asarray(0, jnp.int64)


@pytest.fixture(autouse=True)
def small_batches_may_ask(monkeypatch):
    """As shipped a batch is asked about one group per
    ``_SLOT_ROWS_PER_GROUP`` rows (a round of the probe costs what
    sorting that many rows costs), so ``K`` groups take half a million
    rows: here, one group a row."""
    monkeypatch.setattr(aggregate, "_SLOT_ROWS_PER_GROUP", 1)

KEY_TYPES = {"string": dt.STRING, "int": dt.INT64, "float": dt.FLOAT64}


def schema_of(key):
    return (("k", KEY_TYPES[key]), ("v", dt.INT64), ("x", dt.FLOAT64))


def keys_of(key, groups, rng):
    """``ROWS`` keys in ``groups`` groups, one of them the null key."""
    g = rng.permutation(np.arange(ROWS) % groups)
    if key == "string":
        named = [f"key-{i:03d}" for i in g]
    elif key == "int":
        named = [int(i) * 7919 - 400 for i in g]
    else:
        # -0.0 and 0.0 are one group: which of them comes out is the
        # leader's, so the leaders are compared too.
        named = [(-0.0 if j % 2 else 0.0) if i == 0 else float(i) / 3
                 for j, i in enumerate(g)]
    if groups > 1:
        named = [None if i == 1 else k for i, k in zip(g, named)]
    return named


def host_batch(key, groups, seed=0, nonfinite=False):
    rng = np.random.default_rng(seed)
    keys = keys_of(key, groups, rng)
    v = [None if i % 7 == 3 else int(rng.integers(-10**12, 10**12))
         for i in range(ROWS)]
    x = [None if i % 5 == 2 else float(rng.normal(0, 1e3))
         for i in range(ROWS)]
    if nonfinite:
        # NaN, +inf, -inf and both infinities, each in one group only.
        firsts = {}
        for i, k in enumerate(keys):
            firsts.setdefault(k, []).append(i)
        rows = [r for r in firsts.values() if len(r) >= 2][:4]
        x[rows[0][0]] = float("nan")
        x[rows[1][0]] = float("inf")
        x[rows[2][0]] = float("-inf")
        x[rows[3][0]], x[rows[3][1]] = float("inf"), float("-inf")
    return HostBatch.from_pydict(schema_of(key),
                                 {"k": keys, "v": v, "x": x})


def device_batch(hb):
    """Padding rows behind the live ones, and a selection vector that
    deletes every eleventh row."""
    batch = host_to_device(hb, capacity=CAP)
    return batch.with_sel(jnp.arange(CAP) % 11 != 10)


def aggregate_of(key, mode="partial", has_nans=True, child=None):
    agg = HashAggregateExec(
        child or InMemorySourceExec(schema_of(key), [[]]),
        [("k", Ref(0, KEY_TYPES[key]))],
        [AggSpec("sum_v", Sum(Ref(1, dt.INT64))),
         AggSpec("sum_x", Sum(Ref(2, dt.FLOAT64))),
         AggSpec("avg_x", Average(Ref(2, dt.FLOAT64))),
         AggSpec("avg_v", Average(Ref(1, dt.INT64))),
         AggSpec("n_x", Count(Ref(2, dt.FLOAT64))),
         AggSpec("n", CountStar(None))], mode=mode)
    agg._has_nans = has_nans
    return agg


def sorted_update(agg, batch, off=OFF):
    return agg._sorted_update(*agg._project_inputs(batch), off)


def assert_same_batch(got, want):
    """Every leaf of the two buffer batches, padding included: ints,
    bools and bytes exactly (keys at the leaders, -0.0 told from 0.0),
    float sums to the rounding of another order of additions: the
    sorted path's sum is a difference of prefix sums and carries the
    rounding of the prefix, ~1e6 here, whatever the group's own size."""
    assert int(got.num_rows) == int(want.num_rows)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g, w = np.asarray(g), np.asarray(w)
        assert g.shape == w.shape and g.dtype == w.dtype
        if g.dtype == np.float64:
            np.testing.assert_array_equal(np.signbit(g), np.signbit(w))
            np.testing.assert_allclose(g, w, rtol=1e-12, atol=1e-9,
                                       equal_nan=True)
        else:
            np.testing.assert_array_equal(g, w)


# -- the device's choice, against the sorted path ------------------------------

@pytest.mark.parametrize("groups", [1, 4, K, K + 1])
@pytest.mark.parametrize("key", ["string", "int", "float"])
def test_update_equals_sorted_path(key, groups):
    agg = aggregate_of(key)
    batch = device_batch(host_batch(key, groups, seed=groups))
    got = jax.jit(agg._update_batch)(batch, OFF)
    want = jax.jit(lambda b: sorted_update(agg, b))(batch)
    assert int(want.num_rows) == groups
    assert got.capacity == want.capacity == CAP
    assert_same_batch(got, want)


@pytest.mark.parametrize("has_nans", [True, False])
def test_nonfinite_values_stay_in_their_group(has_nans):
    agg = aggregate_of("string", has_nans=has_nans)
    batch = device_batch(host_batch("string", 6, seed=9,
                                    nonfinite=has_nans))
    got = jax.jit(agg._update_batch)(batch, OFF)
    want = jax.jit(lambda b: sorted_update(agg, b))(batch)
    assert_same_batch(got, want)
    sums = np.asarray(got.columns[2].data)[:6]
    if has_nans:
        assert np.isnan(sums).sum() == 2 and np.isposinf(sums).sum() == 1 \
            and np.isneginf(sums).sum() == 1
    else:
        assert np.isfinite(sums).all()


def test_empty_batch_is_no_group():
    agg = aggregate_of("int")
    batch = device_batch(host_batch("int", 4)).with_sel(
        jnp.zeros((CAP,), jnp.bool_))
    got = jax.jit(agg._update_batch)(batch, OFF)
    assert int(got.num_rows) == 0
    assert_same_batch(got, jax.jit(lambda b: sorted_update(agg, b))(batch))


# -- the whole operator, against the host oracle, and what its metrics say ------

def run_operator(agg, ctx):
    out = []
    for b in agg.execute_device(ctx, 0):
        out.extend(device_to_host(b).to_pylist())
    return out


def by_key(rows):
    return sorted(rows, key=lambda r: tuple((v is None, str(v)) for v in r))


@pytest.mark.parametrize("groups", [1, 4, K, K + 1])
@pytest.mark.parametrize("key", ["string", "int"])
def test_complete_mode_equals_host_oracle_and_counts_its_batches(key,
                                                                 groups):
    hbs = [host_batch(key, groups, seed=s) for s in (1, 2, 3)]
    agg = aggregate_of(key, mode="complete",
                       child=InMemorySourceExec(schema_of(key), [hbs]))
    ctx = ExecContext()
    dev = run_operator(agg, ctx)
    host = agg.collect(device=False)
    assert len(dev) == groups
    assert_rows_equal(by_key(dev), by_key(host), approx_float=True,
                      msg="device vs host engine")
    # The input is coalesced into one update batch, counted at the
    # consolidation's sizes pull: no read was added for it.
    counts = ctx.metrics_for(agg).values
    slot, sort = ("aggSlotBatches", "aggSortedBatches")
    if groups <= K:
        assert counts.get(slot) == 1 and sort not in counts
    else:
        assert counts.get(sort) == 1 and slot not in counts


@pytest.mark.parametrize("groups", [4, K + 1])
def test_partial_mode_counts_without_a_read_of_its_own(groups):
    """The first batch is counted by the skip probe's read; the others'
    group counts wait, as device scalars, for whoever reads the metrics."""
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.config import TpuConf
    hbs = [[host_batch("int", groups, seed=s)] for s in (1, 2, 3)]
    agg = aggregate_of("int", mode="partial",
                       child=InMemorySourceExec(schema_of("int"), hbs))
    ctx = ExecContext(conf=TpuConf({C.AGG_SKIP_PARTIAL_RATIO.key: 0.9}))
    for p in range(3):
        assert len(list(agg.execute_device(ctx, p))) == 1
    name = "aggSlotBatches" if groups <= K else "aggSortedBatches"
    m = ctx.metrics_for(agg)
    assert m.values.get(name) == 1
    assert m.settle().values.get(name) == 3
    assert m.settle().values.get(name) == 3         # settled once


def test_explain_analyze_shows_the_choice_and_the_query_reads_nothing():
    """Traced at kernel level too, the query makes no read for the
    counters: ``slot-flags`` is a span of whoever asks for the metrics."""
    from spark_rapids_tpu import monitoring
    from spark_rapids_tpu.api.dataframe import TpuSession
    from spark_rapids_tpu.monitoring import syncs
    from spark_rapids_tpu.plan.logical import agg_sum, col
    syncs.install()
    session = TpuSession()
    session.set("spark.rapids.sql.variableFloatAgg.enabled", True)
    session.set("spark.rapids.sql.cost.enabled", False)
    session.set("spark.rapids.sql.trace.enabled", True)
    session.set("spark.rapids.sql.trace.level", "kernel")
    df = session.create_dataframe(
        {"k": [i % 3 for i in range(90)],
         "x": [float(i) for i in range(90)]},
        [("k", dt.INT64), ("x", dt.FLOAT64)], num_partitions=3) \
        .group_by("k").agg(agg_sum(col("x")).alias("s"))
    spans = lambda: [e[1] for e in monitoring.events() if e[0] == "X"]
    try:
        monitoring.reset()
        assert sorted(df.collect()) == [(0, 1305.0), (1, 1335.0),
                                        (2, 1365.0)]
        assert "agg-skip-probe" in spans() and "slot-flags" not in spans()
        report = df.explain_analyze()
        assert "slot=3" in report and "sorted=" not in report
        # One read per partition whose batches the probe did not count.
        assert spans().count("slot-flags") == 2
    finally:
        monitoring.configure(False)
        monitoring.reset()


# -- what is traced --------------------------------------------------------------

def test_slot_limit_grows_with_the_batch(monkeypatch):
    monkeypatch.undo()                          # the rule as shipped
    per = aggregate._SLOT_ROWS_PER_GROUP
    assert aggregate._slot_limit(per - 1) == 0
    assert aggregate._slot_limit(4 * per) == 4
    assert aggregate._slot_limit(3 << 18) == 192    # SF1's reader batch
    assert aggregate._slot_limit(1 << 30) == K
    # A batch too small to ask traces the sorted path alone.
    agg = aggregate_of("int")
    batch = device_batch(host_batch("int", 4))
    assert CAP < per
    names = {e.primitive.name for e in primitives(
        jax.make_jaxpr(agg._update_batch)(batch, OFF).jaxpr)}
    assert "cond" not in names and "sort" in names


def primitives(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from primitives(sub)


@pytest.mark.parametrize("fn, has_cond", [
    (Sum(Ref(2, dt.FLOAT64)), True),
    (First(Ref(2, dt.FLOAT64)), False),
    (Max(Ref(2, dt.FLOAT64)), False)],
    ids=["sum", "first", "max"])
def test_only_sum_decomposable_operators_trace_a_cond(fn, has_cond):
    agg = HashAggregateExec(
        InMemorySourceExec(schema_of("int"), [[]]),
        [("k", Ref(0, dt.INT64))],
        [AggSpec("n", CountStar(None)), AggSpec("a", fn)], mode="partial")
    batch = device_batch(host_batch("int", 4))
    traced = jax.make_jaxpr(agg._update_batch)(batch, OFF)
    names = {e.primitive.name for e in primitives(traced.jaxpr)}
    assert ("cond" in names) == has_cond
    assert "sort" in names                      # the sorted path is there
    got = jax.jit(agg._update_batch)(batch, OFF)
    assert_same_batch(got, jax.jit(lambda b: sorted_update(agg, b))(batch))


MOVERS = ("sort", "gather", "scatter", "scatter-add", "scatter_add",
          "cumsum", "cumprod", "cummax", "cummin", "cumlogsumexp",
          "reduce_window", "reduce_window_sum", "reduce_window_max",
          "reduce_window_min", "select_and_scatter_add",
          "select_and_gather_add")


def batch_sized_movers(jaxpr, cap):
    """Equations that sort, gather, scatter or prefix-scan something of
    the batch's capacity. A gather's first operand is what it reads from
    (the key columns, at the leaders): what must be small is its index
    vector and its result; of every other mover, each operand."""
    found = []
    for eqn in primitives(jaxpr):
        if eqn.primitive.name not in MOVERS:
            continue
        avals = [v.aval for v in eqn.invars] + [v.aval for v in eqn.outvars]
        if eqn.primitive.name == "gather":
            avals = avals[1:]
        if any(getattr(a, "shape", ())[:1] == (cap,) for a in avals):
            found.append(eqn.primitive.name)
    return found


@pytest.mark.parametrize("key", ["string", "int"])
def test_slot_branch_moves_nothing_of_the_batchs_size(key):
    agg = aggregate_of(key)
    batch = device_batch(host_batch(key, 4))

    def slots(batch):
        work, ords = agg._project_inputs(batch)
        fp = kernels.key_fingerprint(work.columns[:1], CAP)
        live = work.row_mask()
        pa, pb, found = kernels.smallest_fingerprints(*fp, live, K)
        return agg._slot_update(work, ords, fp, live, pa[:K], pb[:K], found)

    assert batch_sized_movers(jax.make_jaxpr(slots)(batch).jaxpr, CAP) == []
    # The walker does see them where they are: the sorted path.
    seen = batch_sized_movers(
        jax.make_jaxpr(lambda b: sorted_update(agg, b))(batch).jaxpr, CAP)
    assert "sort" in seen and "gather" in seen


# -- the probe -------------------------------------------------------------------

TOP = 0xFFFFFFFF


@pytest.mark.parametrize("pairs, live, limit, want", [
    ([(5, 1), (5, 0), (2, 9), (5, 1), (2, 9)], None, 4,
     [(2, 9), (5, 0), (5, 1)]),
    ([(TOP, TOP), (0, 0), (TOP, 0), (TOP, TOP)], None, 3,
     [(0, 0), (TOP, 0), (TOP, TOP)]),
    ([(3, 3), (1, 1), (2, 2), (4, 4)], None, 2, [(1, 1), (2, 2), (3, 3)]),
    ([(3, 3), (1, 1), (2, 2), (4, 4)], [1, 0, 0, 1], 2, [(3, 3), (4, 4)]),
    ([(3, 3), (1, 1)], [0, 0], 2, []),
], ids=["ascending", "top-values", "over-the-limit", "live-only", "empty"])
def test_smallest_fingerprints(pairs, live, limit, want):
    ha = jnp.asarray([p[0] for p in pairs], jnp.uint32)
    hb = jnp.asarray([p[1] for p in pairs], jnp.uint32)
    mask = jnp.asarray([True] * len(pairs) if live is None else live,
                       jnp.bool_)
    pa, pb, found = jax.jit(
        lambda a, b, m: kernels.smallest_fingerprints(a, b, m, limit))(
        ha, hb, mask)
    # ``limit + 1`` found says "more than ``limit``": the pairs beyond
    # are not looked for.
    assert int(found) == len(want) <= limit + 1
    assert list(zip(np.asarray(pa)[:len(want)].tolist(),
                    np.asarray(pb)[:len(want)].tolist())) == want


# -- under shard_map -------------------------------------------------------------

@pytest.mark.parametrize("groups", [4, K + 1])
def test_update_batch_under_shard_map(groups):
    from jax.sharding import PartitionSpec as P
    from spark_rapids_tpu.parallel import mesh as M
    from spark_rapids_tpu.shims import shard_map
    n_dev = 4
    assert len(jax.devices()) >= n_dev
    mesh = M.make_mesh(n_dev)
    agg = aggregate_of("int")
    shards = [host_to_device(host_batch("int", groups, seed=d),
                             capacity=CAP) for d in range(n_dev)]

    def local_update(stacked):
        local = jax.tree.map(lambda x: x[0], stacked)
        out = agg._update_batch(local, OFF)
        return jax.tree.map(lambda x: x[None], out)

    step = jax.jit(shard_map(local_update, mesh, in_specs=(P(M.DATA_AXIS),),
                             out_specs=P(M.DATA_AXIS)))
    out = step(M.shard_batches(mesh, shards))
    for d in range(n_dev):
        got = jax.tree.map(lambda x: np.asarray(x)[d], out)
        assert int(got.num_rows) == groups
        assert_same_batch(got, sorted_update(agg, shards[d]))
