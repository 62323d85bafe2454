"""The four hot kernels against numpy.

The stable radix permutation (ops/kernels.py ``radix_sort``), the join
probe's double search (ops/join.py ``probe_ranges``), the wire's RLE
decode (columnar/wire.py, through the real decode program) and the
sorted-segment reduce (ops/kernels.py ``segment_reduce``) each have one
implementation. Every case here holds it against a reference written in
numpy or plain Python over the same inputs — never against another
function of the package. The inputs are the hard ones: heavy ties and
the full u32 range, the all-ones sentinel run a real build side ends in,
``-0.0`` / ``NaN`` / ``inf`` runs compared as bits, integer wraparound,
null keys and all-null groups.
"""

import collections

import numpy as np
import pytest

import jax.numpy as jnp

import spark_rapids_tpu  # noqa: F401  (x64)
from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar import wire
from spark_rapids_tpu.columnar.host import HostBatch
from spark_rapids_tpu.ops import kernels
from spark_rapids_tpu.ops.join import (_fingerprint64, build_side,
                                       probe_ranges)


def _bits(a) -> np.ndarray:
    """Bit view for exact comparison (tells -0.0 from 0.0 and one NaN
    payload from another)."""
    a = np.asarray(a)
    return a if a.dtype == np.bool_ else a.view(np.uint8)


def assert_bit_equal(want, got, msg=None):
    w, g = np.asarray(want), np.asarray(got)
    assert w.dtype == g.dtype and w.shape == g.shape, (msg, w.dtype,
                                                      g.dtype)
    assert np.array_equal(_bits(w), _bits(g)), (msg, w[:8], g[:8])


# ---------------------------------------------------------------------------
# radix_sort: a stable sort's permutation is unique, so numpy's is THE answer
# ---------------------------------------------------------------------------

def _lexsort(passes) -> np.ndarray:
    """numpy's stable permutation by ``passes``, most significant first
    (np.lexsort reads its keys last-is-primary)."""
    return np.lexsort(tuple(np.asarray(p) for p in reversed(passes)))


@pytest.mark.parametrize("hi", [8, 2 ** 32], ids=["ties", "full-u32"])
@pytest.mark.parametrize("cap", [8, 12, 96])
def test_radix_perm_one_pass(cap, hi):
    rng = np.random.default_rng(cap)
    keys = rng.integers(0, hi, cap, dtype=np.uint32)
    got = kernels.radix_sort([jnp.asarray(keys)], cap)[0]
    assert np.array_equal(np.argsort(keys, kind="stable"), np.asarray(got))


def test_radix_perm_three_word_passes():
    rng = np.random.default_rng(3)
    cap = 384
    passes = [rng.integers(0, 9, cap, dtype=np.uint32) for _ in range(3)]
    got = kernels.radix_sort([jnp.asarray(p) for p in passes], cap)[0]
    assert np.array_equal(_lexsort(passes), np.asarray(got))


def test_radix_perm_float64_pass_between_word_passes():
    """The TPU's f64 sort keys stay in the float domain: a float pass
    between two word passes sorts like any other."""
    rng = np.random.default_rng(5)
    cap = 24
    passes = [rng.integers(0, 3, cap, dtype=np.uint32),
              rng.choice(np.asarray([-1.5, -0.25, 0.5, 2.0, np.inf]), cap),
              rng.integers(0, 3, cap, dtype=np.uint32)]
    got = kernels.radix_sort([jnp.asarray(p) for p in passes], cap)[0]
    assert np.array_equal(_lexsort(passes), np.asarray(got))


def test_radix_perm_unstable_first_gives_a_valid_order():
    """stableSort off relaxes the tie order of the least significant
    pass: no unique answer, so hold what every answer has — a
    permutation under which the key tuples are nondecreasing."""
    rng = np.random.default_rng(4)
    cap = 96
    passes = [rng.integers(0, 4, cap, dtype=np.uint32),
              rng.integers(0, 5, cap, dtype=np.uint32)]
    got = np.asarray(kernels.radix_sort(
        [jnp.asarray(p) for p in passes], cap, unstable_first=True)[0])
    assert np.array_equal(np.sort(got), np.arange(cap))
    ordered = list(zip(passes[0][got].tolist(), passes[1][got].tolist()))
    assert ordered == sorted(ordered)


# ---------------------------------------------------------------------------
# probe_ranges: insertion points are uniquely defined
# ---------------------------------------------------------------------------

def _key_batch(vals):
    return wire.upload(HostBatch.from_pydict([("k", dt.INT64)],
                                             {"k": list(vals)}))


@pytest.mark.parametrize("cap_b,cap_p", [(8, 8), (16, 24), (96, 12)])
def test_probe_ranges_against_searchsorted(cap_b, cap_p):
    """lo and counts over the fingerprints the two sides really carry,
    the build side ending in its run of all-ones sentinels (null keys
    and padding)."""
    rng = np.random.default_rng(cap_b + cap_p)
    bvals = [int(x) for x in rng.integers(0, 7, cap_b - 2)]
    bvals[1] = None
    pvals = [int(x) for x in rng.integers(0, 10, cap_p)]
    pvals[0] = None
    db, dp = _key_batch(bvals), _key_batch(pvals)
    assert (db.capacity, dp.capacity) == (cap_b, cap_p)
    built = build_side(db, [0])
    lo, counts, plive = probe_ranges(built, dp, [0])

    fp = np.asarray(built.fp)
    assert np.all(fp[-3:] == np.uint64(0xFFFFFFFFFFFFFFFF))
    assert np.all(fp[:-1] <= fp[1:])
    q = np.asarray(_fingerprint64(dp, [0]))
    want_live = np.asarray([v is not None for v in pvals])
    want_lo = np.searchsorted(fp, q, side="left")
    want_hi = np.searchsorted(fp, q, side="right")
    assert np.array_equal(want_live, np.asarray(plive))
    assert np.array_equal(want_lo, np.asarray(lo))
    assert np.array_equal(np.where(want_live, want_hi - want_lo, 0),
                          np.asarray(counts))


def test_probe_ranges_against_a_dict_join():
    """Through real built sides with duplicate and null keys: each probe
    row's range holds exactly the build rows a Python dict join pairs it
    with, and a null key on either side pairs with nothing."""
    rng = np.random.default_rng(11)
    bvals = [int(x) for x in rng.integers(0, 6, 40)]
    bvals[5] = bvals[17] = None
    pvals = [int(x) for x in rng.integers(0, 9, 64)]
    pvals[3] = None
    by_key = collections.Counter(v for v in bvals if v is not None)
    built = build_side(_key_batch(bvals), [0])
    lo, counts, _plive = probe_ranges(built, _key_batch(pvals), [0])
    lo, counts = np.asarray(lo), np.asarray(counts)
    sorted_keys = np.asarray(built.batch.columns[0].data)
    sorted_valid = np.asarray(built.batch.columns[0].validity)
    for p, key in enumerate(pvals):
        want = 0 if key is None else by_key.get(key, 0)
        assert counts[p] == want, (p, key)
        rows = slice(int(lo[p]), int(lo[p]) + want)
        assert np.all(sorted_valid[rows]) and \
            np.all(sorted_keys[rows] == key), (p, key)


# ---------------------------------------------------------------------------
# RLE decode, through the wire's real decode program
# ---------------------------------------------------------------------------

RLE_POOLS = [
    ("int8", np.int8, [1, 2, -3]),
    ("int16", np.int16, [100, -2000]),
    ("int32", np.int32, [7, -9, 2 ** 30]),
    ("int64", np.int64, [2 ** 40, -5, 0]),
    ("float32", np.float32, [1.5, -0.0, np.nan, 0.0]),
    ("float64", np.float64, [np.nan, -0.0, 0.0, 3.25, np.inf]),
]


def _rle_decode(run_vals, lengths, cap, run_cap, logical=None):
    """Decode the run table of (run_vals, lengths) — laid out as the
    wire ships one: values zero-padded to ``run_cap``, ascending
    exclusive run ends padded with ``cap`` — and return it beside
    np.repeat's answer, zero beyond the rows."""
    run_vals = np.asarray(run_vals)
    lengths = np.asarray(lengths)
    runs, n = len(run_vals), int(lengths.sum())
    table = np.zeros(run_cap, run_vals.dtype)
    table[:runs] = run_vals
    ends = np.full(run_cap, cap, np.int32)
    ends[:runs] = np.cumsum(lengths)
    logical = logical or run_vals.dtype.name
    spec = ("rle", logical, run_vals.dtype.name, run_cap, "all")
    out = wire._decode_fn(cap, (spec,))([table, ends],
                                        np.asarray(n, np.int32))
    want = np.zeros(cap, dt.type_named(logical).np_dtype)
    want[:n] = np.repeat(run_vals, lengths)
    return want, out.columns[0]


@pytest.mark.parametrize("name,dtype,pool", RLE_POOLS,
                         ids=[p[0] for p in RLE_POOLS])
def test_rle_decode_against_repeat(name, dtype, pool):
    """Every value of the dtype's pool as a run, forwards then
    backwards; -0.0 next to 0.0 and NaN runs must come back as the bits
    that went in."""
    run_vals = np.asarray(pool + pool[::-1], dtype)
    lengths = 1 + np.arange(len(run_vals)) % 4
    want, col = _rle_decode(run_vals, lengths, cap=64, run_cap=16)
    assert_bit_equal(want, col.data, name)
    assert np.array_equal(np.arange(64) < lengths.sum(),
                          np.asarray(col.validity))


def test_rle_decode_widens_a_narrowed_wire_type():
    """An int64 column whose values fit int8 ships int8 runs; the decode
    ends in a pure cast."""
    want, col = _rle_decode(np.asarray([-128, 127, 0, -1], np.int8),
                            [3, 20, 1, 6], cap=32, run_cap=8,
                            logical="int64")
    assert_bit_equal(want, col.data)


def test_rle_decode_full_run_table_pads_with_zero():
    """With as many runs as the table holds there is no zeroed slot for
    the padding rows to index: they must read zero all the same."""
    want, col = _rle_decode(np.asarray([5, 6, 7, 9, 11, 13, 17, 19],
                                       np.int32),
                            [2, 3, 1, 4, 2, 5, 1, 2], cap=32, run_cap=8)
    assert_bit_equal(want, col.data)
    assert not np.asarray(col.data)[20:].any()


# ---------------------------------------------------------------------------
# segment_reduce on sorted group ids, with Spark's rules
# ---------------------------------------------------------------------------

SEG_DTYPES = [np.bool_, np.int8, np.int16, np.int32, np.int64,
              np.float32, np.float64]
# A boolean SUM is the one pairing left out: Spark has none, the engine
# never forms one (Sum casts to int64 or float64 first) and jax refuses it.
SEG_CASES = [(d, k) for d in SEG_DTYPES for k in ("sum", "min", "max")
             if not (d == np.bool_ and k == "sum")]


def _reference_reduce(vals, validity, gid, cap, kind):
    """(agg, non-null count) per group by numpy's unbuffered ufunc.at,
    under Spark's rules: nulls take no part, NaN is the greatest value.
    ``agg`` means something only where the count is positive."""
    counts = np.zeros(cap, np.int64)
    np.add.at(counts, gid, validity.astype(np.int64))
    floating = np.issubdtype(vals.dtype, np.floating)
    if kind == "sum":
        agg = np.zeros(cap, vals.dtype)
        with np.errstate(invalid="ignore"):         # inf - inf is NaN
            np.add.at(agg, gid[validity], vals[validity])
        return agg, counts
    real = validity & ~np.isnan(vals) if floating else validity
    if floating:
        start = np.inf if kind == "min" else -np.inf
    elif vals.dtype == np.bool_:
        start = kind == "min"
    else:
        info = np.iinfo(vals.dtype)
        start = info.max if kind == "min" else info.min
    agg = np.full(cap, start, vals.dtype)
    (np.minimum if kind == "min" else np.maximum).at(
        agg, gid[real], vals[real])
    if floating:
        nans = np.zeros(cap, np.int64)
        np.add.at(nans, gid, (validity & np.isnan(vals)).astype(np.int64))
        # NaN greatest: max is NaN where any is; min only where all are.
        agg[(nans > 0) if kind == "max" else (nans == counts)] = np.nan
    return agg, counts


def _assert_groups_equal(want, got, want_counts, got_counts, msg):
    """Equal counts everywhere; equal aggregates (NaN equal to NaN, and
    -0.0 to 0.0 as Spark compares them) in every group that has a
    non-null value. A group without one is null, its buffer unspecified."""
    got, got_counts = np.asarray(got), np.asarray(got_counts)
    assert got.dtype == want.dtype, (msg, got.dtype)
    assert got_counts.dtype == np.int64
    assert np.array_equal(want_counts, got_counts), msg
    some = want_counts > 0
    assert some.any() and not some.all(), "want null and non-null groups"
    np.testing.assert_array_equal(got[some], want[some], err_msg=str(msg))


@pytest.mark.parametrize(
    "dtype,kind", SEG_CASES,
    ids=[f"{np.dtype(d).name}-{k}" for d, k in SEG_CASES])
def test_segment_reduce_dtype_ladder(dtype, kind):
    cap = 48
    rng = np.random.default_rng(cap + SEG_DTYPES.index(dtype))
    gid = np.sort(rng.integers(0, 12, cap)).astype(np.int32)
    if dtype == np.bool_:
        vals = rng.integers(0, 2, cap).astype(np.bool_)
    elif np.issubdtype(dtype, np.integer):
        # The full range: narrow sums wrap, in numpy as on the device.
        info = np.iinfo(dtype)
        vals = rng.integers(info.min, info.max, cap).astype(dtype)
    elif kind == "sum":
        # Sums of these are exact in any order, so the order of the
        # reduction is not under test; inf - inf and NaN are.
        vals = rng.choice(np.asarray(
            [1.5, -0.0, 0.0, -2.25, 1024.0, np.nan], dtype), cap)
        vals[gid == gid[7]] = np.asarray([np.inf, -np.inf], dtype)[
            np.arange(int((gid == gid[7]).sum())) % 2]
    else:
        vals = rng.choice(np.asarray(
            [1.5, -0.0, 0.0, np.inf, -np.inf, 3.7, np.nan], dtype), cap)
        vals[gid == gid[7]] = np.nan            # an all-NaN group
    validity = rng.integers(0, 4, cap) > 0
    validity[gid == gid[20]] = False            # an all-null group
    want, want_counts = _reference_reduce(vals, validity, gid, cap, kind)
    agg, counts = kernels.segment_reduce(
        jnp.asarray(vals), jnp.asarray(validity), jnp.asarray(gid), cap,
        kind)
    _assert_groups_equal(want, agg, want_counts, counts, (dtype, kind))


def test_segment_reduce_int64_sum_wraps():
    """Spark's long sum overflows silently: two's complement."""
    cap = 12
    vals = np.full(cap, 2 ** 62, np.int64)
    gid = np.asarray([0] * 3 + [1] * 9, np.int32)
    agg, counts = kernels.segment_reduce(
        jnp.asarray(vals), jnp.ones(cap, jnp.bool_), jnp.asarray(gid),
        cap, "sum")

    def wrapped(total):
        return (total + 2 ** 63) % 2 ** 64 - 2 ** 63
    assert [int(x) for x in np.asarray(agg)[:2]] == \
        [wrapped(3 * 2 ** 62), wrapped(9 * 2 ** 62)] == [-2 ** 62, 2 ** 62]
    assert [int(x) for x in np.asarray(counts)[:2]] == [3, 9]


@pytest.mark.parametrize("kind", ["sum", "min", "max"])
def test_segment_reduce_null_discipline(kind):
    """NaN, both zeros, infinities and nulls at once, the count asked
    for: the count is of non-null rows, NaN rows among them."""
    cap = 48
    rng = np.random.default_rng(9)
    vals = rng.choice(np.asarray([1.5, -0.0, 0.0, np.nan, np.inf, -2.25]),
                      cap)
    validity = rng.integers(0, 4, cap) > 0
    gid = np.sort(rng.integers(0, 12, cap)).astype(np.int32)
    want, want_counts = _reference_reduce(vals, validity, gid, cap, kind)
    agg, counts = kernels.segment_reduce(
        jnp.asarray(vals), jnp.asarray(validity), jnp.asarray(gid), cap,
        kind, count_also=True)
    _assert_groups_equal(want, agg, want_counts, counts, kind)
