"""The mesh exchange's hand-out (``mesh.shard_batches``) and landing
(``mesh_exchange._addressable_parts``) on four of the suite's virtual CPU
devices: whole trees in one batched transfer a side, equal leaf for leaf
to the stacked, leaf-by-leaf form they replace, and compiling a number
of programs that does not grow with the leaves."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.batch import DeviceBatch
from spark_rapids_tpu.columnar.host import HostBatch, host_to_device
from spark_rapids_tpu.parallel import mesh as M
from spark_rapids_tpu.parallel import mesh_exchange as MX
from spark_rapids_tpu.shims import tree_flatten, tree_map

N_DEV = 4


def _schema(n_int):
    return ([("k", dt.INT64), ("x", dt.FLOAT64), ("s", dt.STRING)]
            + [(f"i{j}", dt.INT32) for j in range(n_int)])


def _batch(rng, schema, rows, capacity, width):
    data = {}
    for name, t in schema:
        if t.is_string:
            data[name] = ["".join(rng.choice(list("abcdefgh"),
                                             rng.integers(0, width + 1)))
                          if rng.random() > 0.2 else None
                          for _ in range(rows)]
        elif t == dt.FLOAT64:
            data[name] = rng.normal(size=rows).tolist()
        else:
            data[name] = rng.integers(-50, 50, rows).tolist()
    return host_to_device(HostBatch.from_pydict(schema, data),
                          capacity=capacity, string_widths={"s": width})


def _dealt(rng, schema, cap=32):
    """What ``MeshExchangeExec`` deals to four devices: shards of two
    capacities and two string widths, one device with two batches, one
    with none."""
    return [[_batch(rng, schema, 20, cap, 8)],
            [_batch(rng, schema, 30, 2 * cap, 16)],
            [],
            [_batch(rng, schema, 5, cap, 8), _batch(rng, schema, 7, cap, 8)]]


def _stacked_form(mesh, per_device):
    """The hand-out as it was: an eager stack of every leaf, then a put of
    each to the mesh's sharding."""
    stacked = tree_map(lambda *xs: jnp.stack(xs), *per_device)
    sharding = NamedSharding(mesh, P(M.DATA_AXIS))
    return tree_map(lambda x: jax.device_put(x, sharding), stacked)


def _indexed_landing(out, n):
    """The landing as it was: ``[0]`` of every leaf's shard on every
    device, then one batched put to the first device."""
    leaves, treedef = tree_flatten(out)
    per_dev = [[] for _ in range(n)]
    for leaf in leaves:
        by_row = {(s.index[0].start or 0): s.data
                  for s in leaf.addressable_shards}
        for i in range(n):
            per_dev[i].append(by_row[i][0] if i in by_row else leaf[i])
    per_dev = jax.device_put(per_dev, jax.devices()[0])
    return [jax.tree_util.tree_unflatten(treedef, ls) for ls in per_dev]


def _leaves_equal(a, b):
    la, ta = tree_flatten(a)
    lb, tb = tree_flatten(b)
    assert ta == tb
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert x.shape == y.shape and x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < N_DEV:
        pytest.skip(f"needs {N_DEV} devices")
    return M.make_mesh(N_DEV)


def _puts(monkeypatch):
    calls = []
    real = jax.device_put

    def counted(*a, **k):
        calls.append(a[1] if len(a) > 1 else k.get("device"))
        return real(*a, **k)

    monkeypatch.setattr(jax, "device_put", counted)
    return calls


@pytest.mark.parametrize("n_int", [0, 12])
def test_shard_batches_equals_the_stacked_form(rng, mesh, n_int):
    schema = _schema(n_int)
    shards = MX._uniform_shards(_dealt(rng, schema), schema)
    # the empty shard, the repadded strings, one capacity
    assert int(shards[2].num_rows) == 0
    assert {s.columns[2].string_width for s in shards} == {16}
    assert {s.capacity for s in shards} == {64}
    got = M.shard_batches(mesh, shards)
    want = _stacked_form(mesh, shards)
    _leaves_equal(got, want)
    sharding = NamedSharding(mesh, P(M.DATA_AXIS))
    for g, w in zip(tree_flatten(got)[0], tree_flatten(want)[0]):
        assert g.shape == (N_DEV,) + w.shape[1:]
        assert g.sharding.is_equivalent_to(sharding, g.ndim)
        assert g.sharding.is_equivalent_to(w.sharding, g.ndim)
    # device i holds row i, and row i is per_device[i] (num_rows too)
    leaves = tree_flatten(got)[0]
    for i, shard in enumerate(shards):
        for leaf, x in zip(leaves, tree_flatten(shard)[0]):
            s, = [s for s in leaf.addressable_shards
                  if (s.index[0].start or 0) == i]
            assert s.device == mesh.devices.flat[i]
            assert s.data.shape == (1,) + x.shape
            np.testing.assert_array_equal(np.asarray(s.data)[0],
                                          np.asarray(x))


@pytest.mark.parametrize("n_int", [0, 12])
def test_shard_batches_is_one_batched_put(rng, mesh, monkeypatch, n_int):
    schema = _schema(n_int)
    shards = MX._uniform_shards(_dealt(rng, schema), schema)
    calls = _puts(monkeypatch)
    M.shard_batches(mesh, shards)
    # one put of the n shard trees to the n devices, never to a sharding
    assert len(calls) == 1
    assert list(calls[0]) == list(mesh.devices.flat)


def test_shard_batches_wants_one_shard_a_device(rng, mesh):
    schema = _schema(0)
    shard = _batch(rng, schema, 3, 8, 8)
    with pytest.raises(ValueError):
        M.shard_batches(mesh, [shard] * (N_DEV - 1))


@pytest.mark.parametrize("n_int", [0, 12])
def test_addressable_parts_equals_the_indexed_landing(rng, mesh, monkeypatch,
                                                      n_int):
    schema = _schema(n_int)
    shards = MX._uniform_shards(_dealt(rng, schema), schema)
    out = M.shard_batches(mesh, shards)
    want = _indexed_landing(out, N_DEV)
    calls = _puts(monkeypatch)
    got = MX._addressable_parts(out, N_DEV)
    assert calls == [jax.devices()[0]]
    assert len(got) == N_DEV
    for g, w, shard in zip(got, want, shards):
        _leaves_equal(g, w)
        _leaves_equal(g, shard)
        for leaf in tree_flatten(g)[0]:
            assert leaf.devices() == {jax.devices()[0]}


def test_addressable_parts_replicated_leaf(rng, mesh):
    sharded = M.shard_batches(
        mesh, [DeviceBatch((), jnp.asarray(i, jnp.int32))
               for i in range(N_DEV)])
    replicated = jax.device_put(jnp.arange(N_DEV * 3).reshape(N_DEV, 3),
                                NamedSharding(mesh, P()))
    parts = MX._addressable_parts((sharded.num_rows, replicated), N_DEV)
    for i, (rows, rep) in enumerate(parts):
        assert int(rows) == i
        np.testing.assert_array_equal(np.asarray(rep),
                                      np.arange(3 * i, 3 * i + 3))
        assert rep.devices() == {jax.devices()[0]}


class _Compiles:
    def __init__(self):
        self.programs = 0

    def __call__(self, event, duration, **kw):
        if event.endswith("backend_compile_duration"):
            self.programs += 1


def _phase_compiles(rng, mesh, schema, cap):
    """Programs compiled by an exchange's ``shard`` phase (the deal made
    uniform, then handed out) and its ``land`` phase, at a capacity no
    test compiled before."""
    dealt = _dealt(rng, schema, cap)
    clock = _Compiles()
    jax.monitoring.register_event_duration_secs_listener(clock)
    try:
        shards = MX._uniform_shards(dealt, schema)
        out = M.shard_batches(mesh, shards)
        jax.block_until_ready(out)
        shard_phase = clock.programs
        parts = MX._addressable_parts(out, N_DEV)
        jax.block_until_ready(parts)
        land_phase = clock.programs - shard_phase
    finally:
        jax.monitoring.unregister_event_duration_listener(clock)
    return shard_phase, land_phase


def test_phases_compile_a_bounded_count_whatever_the_leaves(rng, mesh):
    # shard phase: the concat of device 3's two batches, one fit a shard
    # shape (40 rows at width 8, 80 at 16, the concat's 96 at 8), the
    # empty shard, the leading axis: a count of shards, not of leaves;
    # then the landing's one program
    narrow = _phase_compiles(rng, mesh, _schema(0), 40)
    wide = _phase_compiles(rng, mesh, _schema(12), 40)
    assert narrow == wide
    shard_phase, land_phase = wide
    assert shard_phase <= N_DEV + 2
    assert land_phase <= 1
