"""The sort core moves its words inside the sort (PR 34).

``kernels.radix_sort`` is the one multi-pass sort of the engine: each LSD
pass is one ``lax.sort`` with every other word and the permutation riding
as operands, so nothing is gathered by ``perm`` inside it, and its callers
(``group_ids``, ``window._sorted_frame``, ``segment_minmax_string``,
``lex_sort_perm``) read the sorted words from its outputs. Held here:

- against the loop it replaced, kept in this file as the reference
  (``take``, ``argsort(stable=True)``, ``take``): the permutation bit for
  bit, the sorted passes equal to ``take(pass, perm)``;
- in the jaxprs of the sorted grouping path at a capacity of 4,096 with
  q67-like keys: no ``gather`` of a 1-D operand by an index of the
  batch's length in ``group_ids``, ``_sorted_frame``, ``_segment_sums``
  and ``sort_batch``'s permutation; the sorted aggregate gathers through
  the packed row movers alone.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import spark_rapids_tpu  # noqa: F401  (x64)
from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar import rowmove
from spark_rapids_tpu.columnar.host import HostBatch, host_to_device
from spark_rapids_tpu.exprs.base import BoundReference as Ref
from spark_rapids_tpu.ops import (
    AggSpec, Average, CountStar, HashAggregateExec, InMemorySourceExec,
    Sum, kernels)
from spark_rapids_tpu.ops import window as W
from spark_rapids_tpu.ops.sort import SortOrder, sort_batch


# -- the reference: the loop that radix_sort replaced ------------------------------

def take_argsort_take(passes, capacity, unstable_first=False):
    perm = jnp.arange(capacity, dtype=jnp.int32)
    first = True
    for words in reversed(passes):
        keyed = jnp.take(words, perm, axis=0)
        order = jnp.argsort(keyed, stable=not (unstable_first and first))
        perm = jnp.take(perm, order, axis=0)
        first = False
    return perm


def words_of(kind, n_passes, cap, seed):
    """``n_passes`` word arrays: the first says which rows are dead (as
    every caller's first pass does), one in the middle is a float64 pass
    where there is room (and the second, so that a long sort holds one in
    its last chunk too), the rest uint32 of the given kind."""
    rng = np.random.default_rng(seed)
    dead = np.where(rng.random(cap) < 0.2, 0xFFFFFFFF, 0).astype(np.uint32)
    out = [dead]
    for i in range(1, n_passes):
        if kind == "all-equal":
            w = np.full(cap, 7, np.uint32)
        elif kind == "long-ties":
            # runs of equal words a sixth of the batch long
            w = (np.arange(cap) * 6 // cap).astype(np.uint32)[
                rng.permutation(cap)] if i % 2 else \
                rng.integers(0, 3, cap).astype(np.uint32)
        else:
            w = rng.integers(0, 2 ** 32, cap, dtype=np.uint32)
        if kind == "full-u32" and n_passes > 3 and i in (n_passes // 2, 1):
            w = rng.choice(np.asarray([-1.5, -0.0, 0.0, 0.5, np.inf]), cap)
        out.append(w)
    return out[:n_passes]


# One to seven passes at 8 and 4,096 rows; at 98,304 the pass counts the
# engine sorts with: one word, group_ids' three, the window's seven. Past
# ``kernels._RIDE_PASSES`` the sort goes in chunks: two of them, three, and
# chunks of one pass each.
SHAPES = [(n, cap) for cap in (8, 4096) for n in range(1, 8)] + \
         [(n, 98304) for n in (1, 3, 7)] + \
         [(n, 4096) for n in (kernels._RIDE_PASSES + 1,
                              2 * kernels._RIDE_PASSES + 3)]


@pytest.mark.parametrize("unstable_first", [False, True],
                         ids=["stable", "unstable-first"])
@pytest.mark.parametrize("kind", ["long-ties", "all-equal", "full-u32"])
@pytest.mark.parametrize("n_passes, cap", SHAPES)
def test_radix_sort_against_the_loop_it_replaced(n_passes, cap, kind,
                                                 unstable_first):
    passes = [jnp.asarray(w) for w in words_of(kind, n_passes, cap,
                                               seed=n_passes * 31 + cap)]
    perm, sorted_passes = jax.jit(
        lambda p: kernels.radix_sort(p, cap, unstable_first))(passes)
    want = jax.jit(
        lambda p: take_argsort_take(p, cap, unstable_first))(passes)
    perm = np.asarray(perm)
    if unstable_first:
        # No unique answer: a permutation under which the pass tuples
        # come out as the reference's do.
        assert np.array_equal(np.sort(perm), np.arange(cap))
        for p in passes:
            assert np.array_equal(np.asarray(p)[perm],
                                  np.asarray(p)[np.asarray(want)])
    else:
        assert np.array_equal(perm, np.asarray(want))
    assert len(sorted_passes) == n_passes
    for p, s in zip(passes, sorted_passes):
        assert s.dtype == p.dtype
        assert np.array_equal(np.asarray(p)[perm].view(np.uint8),
                              np.asarray(s).view(np.uint8))


def test_take_columns_equals_the_takes_it_packs():
    rng = np.random.default_rng(2)
    n = 96
    cols = [rng.integers(-2 ** 62, 2 ** 62, n),
            rng.normal(0, 1e6, n),
            rng.integers(-2 ** 31, 2 ** 31, n).astype(np.int32),
            rng.normal(0, 1, n),
            rng.integers(0, 2 ** 32, n, dtype=np.uint32)] + \
           [rng.integers(-2 ** 62, 2 ** 62, n) for _ in range(9)]
    idx = rng.integers(0, n, n).astype(np.int32)
    got = jax.jit(rowmove.take_columns)([jnp.asarray(c) for c in cols],
                                        jnp.asarray(idx))
    for c, g in zip(cols, got):
        assert g.dtype == c.dtype
        assert np.array_equal(c[idx].view(np.uint8),
                              np.asarray(g).view(np.uint8))


# -- structure: what the sorted path gathers ---------------------------------------

CAP = 4096
ROWS = 3000
SCHEMA = (("category", dt.STRING), ("brand", dt.STRING),
          ("year", dt.INT32), ("store", dt.INT64),
          ("sales", dt.FLOAT64), ("qty", dt.INT64))


def q67_like_batch(seed=0):
    """Strings, an int64 and an int32 key, NULLs in every key (a rollup
    level's and the data's), a float64 and an int64 measure; padding rows
    behind the live ones and a selection vector."""
    rng = np.random.default_rng(seed)

    def nulled(vals, share):
        return [None if rng.random() < share else v for v in vals]
    hb = HostBatch.from_pydict(SCHEMA, {
        "category": nulled([f"cat-{i % 10}" for i in
                            rng.integers(0, 50, ROWS)], 0.1),
        "brand": nulled([f"brand number {i:05d}" for i in
                         rng.integers(0, 400, ROWS)], 0.2),
        "year": nulled([int(i) for i in rng.integers(1998, 2003, ROWS)],
                       0.3),
        "store": nulled([int(i) * 2 ** 33 for i in
                         rng.integers(0, 12, ROWS)], 0.3),
        "sales": nulled([float(i) for i in rng.integers(0, 10 ** 6, ROWS)],
                        0.04),
        "qty": nulled([int(i) for i in rng.integers(1, 100, ROWS)], 0.04)})
    batch = host_to_device(hb, capacity=CAP)
    return batch.with_sel(jnp.arange(CAP) % 13 != 12)


def rollup_aggregate(mode="partial"):
    agg = HashAggregateExec(
        InMemorySourceExec(SCHEMA, [[]]),
        [(n, Ref(i, t)) for i, (n, t) in enumerate(SCHEMA[:4])],
        [AggSpec("sumsales", Sum(Ref(4, dt.FLOAT64))),
         AggSpec("avg_qty", Average(Ref(5, dt.INT64))),
         AggSpec("sum_qty", Sum(Ref(5, dt.INT64))),
         AggSpec("n", CountStar(None))], mode=mode)
    agg._has_nans = False
    return agg


def equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from equations(sub)


def batch_length_gathers(fn, *args):
    """``(column_gathers, packed_gathers)`` of ``fn``'s jaxpr: the
    ``gather`` equations whose index vector is of the batch's length,
    split by what they read from — one column (a 1-D operand, or (N, 1):
    XLA drops the unit axis) or rows of several words."""
    columns, packed = [], []
    for eqn in equations(jax.make_jaxpr(fn)(*args).jaxpr):
        if eqn.primitive.name != "gather":
            continue
        operand, index = (v.aval for v in eqn.invars[:2])
        if index.shape[:1] != (CAP,):
            continue
        wide = [d for d in operand.shape[1:] if d != 1]
        (packed if wide else columns).append(
            f"{operand.dtype}{list(operand.shape)}")
    return columns, packed


def test_group_ids_gathers_no_column():
    batch = q67_like_batch()
    assert batch_length_gathers(
        lambda b: dataclasses.astuple(kernels.group_ids(b, range(4))),
        batch) == ([], [])


@pytest.mark.parametrize("partitioned", [True, False])
def test_sorted_frame_gathers_no_column(partitioned):
    """q67's window: rank() over a string partition key by a float64
    descending — seven passes in two chunks: the partition's words enter
    theirs in one packed gather, the order's uint32 words are read back
    in another, and the float64 one rides."""
    spec = W.WindowSpec(
        [Ref(0, dt.STRING)] if partitioned else [],
        [SortOrder(Ref(4, dt.FLOAT64), ascending=False)])
    columns, packed = batch_length_gathers(
        lambda b: W._sorted_frame(b, spec), q67_like_batch())
    assert columns == []
    assert sorted(packed) == [f"uint32[{CAP}, {2 + partitioned}]"] * 2


def only_packed(columns, packed, float64_columns):
    """Packed rows (uint32 words, a float64 stack), and of single columns
    at most the float64 ones: a float64 has no words to pack
    (rowmove.py), so a batch's only float64 column moves by itself."""
    assert packed and all(p.startswith(("uint32", "float64"))
                          for p in packed), packed
    assert sorted(columns) == [f"float64[{CAP}, 1]"] * float64_columns


def test_a_long_frame_sort_gathers_packed_rows():
    """Past ``_RIDE_PASSES`` passes the words enter their chunk in one
    packed gather, and the peers' words of the lower chunk are read in
    one more; the float64 pass of the upper chunk is the one column."""
    spec = W.WindowSpec(
        [Ref(0, dt.STRING)],
        [SortOrder(Ref(4, dt.FLOAT64), ascending=False),
         SortOrder(Ref(1, dt.STRING))])
    only_packed(*batch_length_gathers(
        lambda b: W._sorted_frame(b, spec), q67_like_batch()),
        float64_columns=1)


def test_sort_batch_gathers_only_packed_rows():
    orders = [SortOrder(Ref(0, dt.STRING)), SortOrder(Ref(3, dt.INT64)),
              SortOrder(Ref(4, dt.FLOAT64), ascending=False)]
    # The payload moves once, packed (``batch.gather``): its float64 slab
    # holds the batch's one float64 column. The float64 pass lies in the
    # least significant chunk, which starts from the rows as they are.
    only_packed(*batch_length_gathers(
        lambda b: sort_batch(b, orders), q67_like_batch()),
        float64_columns=1)


@pytest.mark.parametrize("layout, sorts, scatters, columns, packed", [
    # q67's sum and its count: three words ride one sort to their slots
    ({"f64": 1, "i32": 1}, 1, 0, [], []),
    ({"i64": 1, "i32": 2}, 1, 0, [], []),
    # more than ``_SUMS_RIDE_WORDS``: the ends' indices by one scatter, the
    # integer sums in one slab of words, the float64 ones in one stack
    ({"f64": 2, "i64": 2, "i32": 3}, 0, 1, [],
     [f"uint32[{CAP}, 7]", f"float64[{CAP}, 2]"]),
    ({"f64": 1, "i32": 4}, 0, 1, [f"float64[{CAP}, 1]"],
     [f"uint32[{CAP}, 4]"])],
    ids=["sum-and-count", "int-sum-and-counts", "many-streams",
         "lone-float64"])
def test_segment_sums_ride_a_sort_or_gather_in_slabs(layout, sorts, scatters,
                                                     columns, packed):
    agg = rollup_aggregate()
    rng = np.random.default_rng(1)
    make = {"f64": lambda: jnp.asarray(rng.integers(-9, 9, CAP).astype(float)),
            "i64": lambda: jnp.asarray(rng.integers(-9, 9, CAP) * 2 ** 40),
            "i32": lambda: jnp.asarray(rng.integers(0, 2, CAP), jnp.int32)}
    stacks = {cls: [make[cls]() for _ in range(n)]
              for cls, n in layout.items()}
    groups = 500
    gid = jnp.asarray(np.sort(rng.integers(0, groups, CAP)).astype(np.int32))
    slive = jnp.arange(CAP) < ROWS

    def sums(s):
        return agg._segment_sums(s, gid, slive, CAP)
    got_columns, got_packed = batch_length_gathers(sums, stacks)
    assert (got_columns, sorted(got_packed)) == (columns, sorted(packed))
    names = [e.primitive.name for e in
             equations(jax.make_jaxpr(sums)(stacks).jaxpr)]
    assert (names.count("sort"), names.count("scatter")) == (sorts, scatters)
    # ... and they are the sums, numpy's, of the groups present in order
    # (whole numbers, so float64 sums are exact in any order).
    got = jax.jit(sums)(stacks)
    g = np.asarray(gid)[:ROWS]
    present = np.unique(g)
    for cls, arrs in stacks.items():
        for a, d in zip(arrs, got[cls]):
            want = np.zeros(groups, np.asarray(a).dtype)
            np.add.at(want, g, np.asarray(a)[:ROWS])
            assert np.array_equal(np.asarray(d)[:len(present)],
                                  want[present]), cls


@pytest.mark.parametrize("stage, float64_columns", [
    ("_sorted_update", 1), ("_merge_batch", 0), ("_mixed_batch", 1)])
def test_the_sorted_aggregate_gathers_rows_not_columns(stage,
                                                       float64_columns):
    """The sorted update, the merge and the distinct combo stage gather
    through the packed row movers alone (the batch to group order, the
    keys at the leaders) and ``_segment_sums``' slabs; the update's input
    has one float64 column and the buffer batches two, the distinct stage
    sums one float64 stream among five words."""
    batch = q67_like_batch()
    off = jnp.asarray(0, jnp.int64)
    if stage == "_sorted_update":
        agg = rollup_aggregate()

        def fn(b):
            return agg._sorted_update(*agg._project_inputs(b), off)
    else:
        partial = rollup_aggregate()
        batch = jax.jit(lambda b: partial._sorted_update(
            *partial._project_inputs(b), off))(batch)
        if stage == "_merge_batch":
            fn = rollup_aggregate("final")._merge_batch
        else:
            mixed = HashAggregateExec(
                InMemorySourceExec(partial.buffer_schema, [[]]),
                [(n, Ref(i, t)) for i, (n, t) in enumerate(SCHEMA[:3])],
                [AggSpec("stores", Sum(Ref(3, dt.INT64)), distinct=True),
                 AggSpec("sumsales", Sum(Ref(4, dt.FLOAT64)))],
                mode="mixed_final")
            fn = mixed._mixed_batch
    only_packed(*batch_length_gathers(fn, batch),
                float64_columns=float64_columns)
