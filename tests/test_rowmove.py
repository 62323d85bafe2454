"""The packed row movers of columnar/rowmove.py against a plain numpy
reference: ``compact_batch``, ``concat_compact`` (behind
``concat_batches``), the mesh's ``split_batch`` and the collective step
``all_to_all_exchange`` built on it.

Every case asserts rows and their order, ``num_rows``, dead slots zeroed
whole, and no selection vector left on the result. The last tests read the
collective step's jaxpr: a row moves by ONE gather per slab on the way out
and one on the way in, and the only scatters are the 1-D int32 index
scatters."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.batch import (
    DeviceBatch, bucket_capacity, concat_batches)
from spark_rapids_tpu.columnar.host import (
    HostBatch, device_to_host, host_to_device)
from spark_rapids_tpu.columnar.rowmove import (
    compact_batch, concat_stacked, pack_batch)
from spark_rapids_tpu.parallel import mesh as M
from spark_rapids_tpu.parallel.partitioning import (
    split_batch, split_host_batch)
from spark_rapids_tpu.shims import shard_map, tree_map

# Every way a column rides in a slab (columnar/rowmove.py): flag bits
# (bool data and every validity), one uint32 word (int8/16/32, date,
# float32), two (int64, timestamp), string bytes + a length word, and the
# float64 slab; "wide" needs two flag words and three word slabs.
_T = {"b": dt.BOOL, "c": dt.INT8, "h": dt.INT16, "i": dt.INT32, "d": dt.DATE,
      "e": dt.FLOAT32, "l": dt.INT64, "t": dt.TIMESTAMP, "f": dt.FLOAT64,
      "g": dt.FLOAT64, "s": dt.STRING, "u": dt.STRING}
SCHEMAS = {
    name: [(c, _T[c]) for c in cols] for name, cols in {
        "words": "bchide", "i64": "lt", "f64": "fg", "strings": "su",
        "all": "bchideltfgsu"}.items()}
SCHEMAS["wide"] = ([(f"i{k}", dt.INT32) for k in range(20)]
                   + [(f"b{k}", dt.BOOL) for k in range(20)]
                   + [("f", dt.FLOAT64)])


def _values(rng, t, n, long_strings):
    if t is dt.BOOL:
        vals = (rng.random(n) < 0.5).tolist()
    elif t is dt.STRING:
        top = 14 if long_strings else 6       # widths 16 and 8
        vals = ["".join(rng.choice(list("abcxyz"), rng.integers(0, top + 1)))
                for _ in range(n)]
    elif t is dt.FLOAT64:
        vals = np.round(rng.uniform(-1e6, 1e6, n), 3).tolist()
    elif t in (dt.INT64, dt.TIMESTAMP):
        vals = rng.integers(-2**62, 2**62, n).tolist()
    elif t is dt.INT16:
        vals = rng.integers(-2**15, 2**15, n).tolist()
    elif t is dt.INT8:
        vals = rng.integers(-2**7, 2**7, n).tolist()
    elif t is dt.FLOAT32:
        vals = rng.uniform(-1e6, 1e6, n).astype(np.float32).tolist()
    else:
        vals = rng.integers(-2**31, 2**31, n).tolist()
    nulls = rng.random(n) < 0.2
    return [None if z else v for v, z in zip(vals, nulls)]


def make_batch(rng, schema, n, capacity, sel_share=None,
               long_strings=False):
    """(device batch, its rows as python tuples, its live mask over the
    first ``n`` rows). ``sel_share`` adds a selection vector."""
    hb = HostBatch.from_pydict(
        SCHEMAS[schema],
        {c: _values(rng, t, n, long_strings) for c, t in SCHEMAS[schema]})
    db = host_to_device(hb, capacity=capacity)
    live = np.ones(n, bool)
    if sel_share is not None:
        sel = rng.random(db.capacity) < sel_share
        db = db.with_sel(jnp.asarray(sel))
        live = sel[:n]
    return db, hb.to_pylist(), live


def rows_of(batch: DeviceBatch):
    return device_to_host(batch).to_pylist()


def assert_dense(batch: DeviceBatch, want_rows):
    """``batch`` is exactly ``want_rows`` as a packed prefix."""
    assert batch.sel is None
    assert int(batch.num_rows) == len(want_rows)
    assert rows_of(batch) == want_rows
    k = len(want_rows)
    for leaf in jax.tree_util.tree_leaves(batch.columns):
        assert not np.asarray(leaf)[k:].any(), "a dead slot is not zeroed"


# ---------------------------------------------------------------------------
# compact_batch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("schema", list(SCHEMAS))
@pytest.mark.parametrize("case", ["prefix", "sel", "keep", "sel+keep",
                                  "none_live", "all_live", "shrink",
                                  "grow"])
def test_compact_batch(rng, schema, case):
    n, cap = 41, 64
    if case == "all_live":
        n = cap
    db, rows, live = make_batch(
        rng, schema, n, cap,
        sel_share={"sel": 0.6, "sel+keep": 0.6, "shrink": 0.3,
                   "grow": 0.6}.get(case))
    keep = None
    if case in ("keep", "sel+keep"):
        keep = rng.random(cap) < 0.5
        live = live & keep[:n]
    elif case == "none_live":
        keep = np.zeros(cap, bool)
        live = live & False
    # "shrink" is shrink_to_capacity's case (live rows fit the smaller
    # capacity); "grow" is concat_stacked's (a bucket above the input).
    out_cap = {"shrink": 32, "grow": 96}.get(case)
    if case == "shrink":
        assert live.sum() <= out_cap
    out = jax.jit(compact_batch, static_argnames="capacity")(
        db, None if keep is None else jnp.asarray(keep), capacity=out_cap)
    assert out.capacity == (out_cap or cap)
    assert_dense(out, [r for r, ok in zip(rows, live) if ok])


def test_compact_method_and_eager(rng):
    """``DeviceBatch.compact`` is the same mover, outside jit too."""
    db, rows, live = make_batch(rng, "all", 20, 32, sel_share=0.7)
    keep = rng.random(32) < 0.5
    out = db.compact(jnp.asarray(keep))
    assert_dense(out, [r for r, ok in zip(rows, live & keep[:20]) if ok])


# ---------------------------------------------------------------------------
# concat_compact (concat_batches)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("schema", list(SCHEMAS))
@pytest.mark.parametrize("case", ["equal", "unequal", "sel", "one_empty",
                                  "all_empty", "single", "full"])
def test_concat_compact(rng, schema, case):
    # (rows, capacity, sel share, long strings) per member: unequal
    # capacities AND string widths (8 and 16 bytes) wherever they differ.
    members = {
        "equal": [(10, 16, None, False), (16, 16, None, False)],
        "unequal": [(5, 8, None, False), (30, 64, None, True),
                    (9, 16, None, False)],
        "sel": [(14, 16, 0.5, True), (7, 8, None, False),
                (40, 64, 0.3, False)],
        "one_empty": [(0, 8, None, False), (11, 16, 0.8, True)],
        "all_empty": [(0, 8, None, False), (6, 8, 0.0, False)],
        "single": [(13, 16, 0.6, True)],
        "full": [(16, 16, None, True), (8, 8, None, False)],
    }[case]
    batches, want = [], []
    for n, cap, share, long_strings in members:
        db, rows, live = make_batch(rng, schema, n, cap, share, long_strings)
        batches.append(db)
        want += [r for r, ok in zip(rows, live) if ok]
    cap = bucket_capacity(sum(b.capacity for b in batches))
    out = jax.jit(lambda bs: concat_batches(bs, cap))(batches)
    assert out.capacity == cap
    assert_dense(out, want)
    for ci, (_, t) in enumerate(SCHEMAS[schema]):
        if t.is_string:
            assert out.columns[ci].string_width == max(
                b.columns[ci].string_width for b in batches)


# ---------------------------------------------------------------------------
# split_batch
# ---------------------------------------------------------------------------

def _piece(stacked: DeviceBatch, p: int) -> DeviceBatch:
    return tree_map(lambda x: x[p], stacked)


@pytest.mark.parametrize("schema", ["all", "words", "wide", "f64", "strings"])
@pytest.mark.parametrize("n_parts", [1, 2, 4])
@pytest.mark.parametrize("piece", ["none", "exact", "bucket", "short"])
def test_split_batch(rng, schema, n_parts, piece):
    n, cap = 50, 64
    db, rows, live = make_batch(rng, schema, n, cap, sel_share=0.8)
    pids = rng.integers(0, n_parts, cap).astype(np.int32)
    want = [[r for r, ok, p in zip(rows, live, pids) if ok and p == d]
            for d in range(n_parts)]
    longest = max(len(w) for w in want)
    pc = {"none": None, "exact": longest,
          "bucket": bucket_capacity(longest + 1),
          # below the longest piece: a piece keeps its first pc rows, as
          # the old truncating slice did (the two-phase path never asks)
          "short": max(longest - 3, 1)}[piece]
    out = jax.jit(lambda b, p: split_batch(b, p, n_parts, pc))(
        db, jnp.asarray(pids))
    assert out.sel is None
    assert out.num_rows.shape == (n_parts,)
    for leaf in jax.tree_util.tree_leaves(out.columns):
        assert leaf.shape[:2] == (n_parts, pc or cap)
    for d in range(n_parts):
        assert_dense(_piece(out, d), want[d][:pc])


def test_split_batch_drops_stray_pids(rng):
    """A pid outside [0, n) moves no row (dead rows carry any pid)."""
    db, rows, live = make_batch(rng, "all", 30, 32)
    pids = rng.integers(-1, 3, 32).astype(np.int32)     # -1 and 2 stray
    out = split_batch(db, jnp.asarray(pids), 2)
    for d in range(2):
        assert_dense(_piece(out, d),
                     [r for r, p in zip(rows, pids) if p == d])


def test_concat_stacked_undoes_split(rng):
    """Receive side: the pieces of one shard, concatenated again, are its
    live rows grouped by destination in stable order."""
    db, rows, live = make_batch(rng, "all", 50, 64, sel_share=0.7)
    pids = rng.integers(0, 4, 64).astype(np.int32)
    out = jax.jit(lambda b, p: concat_stacked(split_batch(b, p, 4, 24), 96))(
        db, jnp.asarray(pids))
    assert out.capacity == 96
    assert_dense(out, [r for d in range(4)
                       for r, ok, p in zip(rows, live, pids)
                       if ok and p == d])


# ---------------------------------------------------------------------------
# The collective step on four virtual devices
# ---------------------------------------------------------------------------

N_DEV = 4


def _exchange_step(mesh, piece_capacity):
    def local(stacked, pids):
        b = tree_map(lambda x: x[0], stacked)
        out = M.all_to_all_exchange(b, pids[0], N_DEV,
                                    piece_capacity=piece_capacity)
        return tree_map(lambda x: x[None], out)
    spec = M.P(M.DATA_AXIS)
    return jax.jit(shard_map(local, mesh, in_specs=(spec, spec),
                             out_specs=spec))


@pytest.mark.parametrize("piece", ["none", "bucket"])
def test_all_to_all_exchange_equals_host_split(rng, piece):
    assert len(jax.devices()) >= N_DEV
    mesh = M.make_mesh(N_DEV)
    schema = SCHEMAS["all"]
    cap = 32
    hosts, shards, pids = [], [], []
    for d in range(N_DEV):
        n = int(rng.integers(cap // 2, cap + 1))
        hb = HostBatch.from_pydict(
            schema, {c: _values(rng, t, n, False) for c, t in schema})
        hosts.append(hb)
        shards.append(host_to_device(hb, capacity=cap,
                                     string_widths={"s": 8, "u": 8}))
        pids.append(rng.integers(0, N_DEV, cap).astype(np.int32))
    # The host's answer: every shard split by the same pids, destination
    # d receives piece d of shard 0, then of shard 1, ...
    split = [split_host_batch(hb, p[:hb.num_rows], N_DEV)
             for hb, p in zip(hosts, pids)]
    want = [[r for s in range(N_DEV) for r in split[s][d].to_pylist()]
            for d in range(N_DEV)]
    pc = None if piece == "none" else bucket_capacity(
        max(split[s][d].num_rows for s in range(N_DEV)
            for d in range(N_DEV)))
    step = _exchange_step(mesh, pc)
    out = step(M.shard_batches(mesh, shards),
               jax.device_put(jnp.asarray(np.stack(pids)),
                              M.batch_sharding(mesh)))
    for d in range(N_DEV):
        got = tree_map(lambda x: np.asarray(x)[d], out)
        assert got.capacity == bucket_capacity(N_DEV * (pc or cap))
        assert_dense(got, want[d])


def _primitives(jaxpr, found):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        found.append(eqn)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _primitives(inner, found)
    return found


@pytest.mark.parametrize("piece_capacity", [None, 24])
def test_exchange_moves_a_row_once(rng, piece_capacity):
    """The collective step of one shard: one gather per slab into the
    all_to_all's operand, one per slab out of what it returns, and no
    scatter but the two 1-D int32 index scatters."""
    db, _, _ = make_batch(rng, "wide", 50, 64)
    slabs = pack_batch(db)
    assert sorted(slabs) == ["f64", "w0", "w1"]
    mesh = M.make_mesh(N_DEV)
    step = _exchange_step(mesh, piece_capacity)
    stacked = M.shard_batches(mesh, [db] * N_DEV)
    pids = jnp.zeros((N_DEV, 64), jnp.int32)
    eqns = _primitives(jax.make_jaxpr(step)(stacked, pids).jaxpr, [])
    names = [e.primitive.name for e in eqns]
    assert names.count("all_to_all") >= 1
    gathers = [e for e in eqns if e.primitive.name == "gather"]
    assert len(gathers) == 2 * len(slabs)
    pc = piece_capacity or 64
    # send side: n x piece_capacity output rows; receive side: the bucket
    assert sorted(e.outvars[0].aval.shape[0] for e in gathers) == sorted(
        [N_DEV * pc] * len(slabs)
        + [bucket_capacity(N_DEV * pc)] * len(slabs))
    scatters = [e for e in eqns if e.primitive.name.startswith("scatter")]
    assert len(scatters) == 2
    for e in scatters:
        out = e.outvars[0].aval
        assert out.ndim == 1 and out.dtype == jnp.int32
