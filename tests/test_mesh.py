"""Multi-chip collective tests on the 8-virtual-device CPU mesh
(conftest.py forces xla_force_host_platform_device_count=8 — SURVEY.md §4's
"distributed without a cluster" strategy)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.host import HostBatch, host_to_device, \
    device_to_host
from spark_rapids_tpu.exprs.base import BoundReference as Ref
from spark_rapids_tpu.ops import AggSpec, CountStar, HashAggregateExec, Sum
from spark_rapids_tpu.parallel import HashPartitioning
from spark_rapids_tpu.parallel import mesh as M


N_DEV = 8


def make_shards(rng, rows_per_dev=64, n_dev=N_DEV):
    shards = []
    all_rows = []
    for d in range(n_dev):
        keys = rng.integers(0, 37, rows_per_dev).tolist()
        vals = rng.integers(0, 1000, rows_per_dev).tolist()
        all_rows.extend(zip(keys, vals))
        hb = HostBatch.from_pydict(
            [("k", dt.INT64), ("v", dt.INT64)],
            {"k": keys, "v": vals})
        shards.append(host_to_device(hb, capacity=rows_per_dev))
    return shards, all_rows


def test_distributed_aggregate_step(rng):
    assert len(jax.devices()) >= N_DEV
    mesh = M.make_mesh(N_DEV)
    shards, all_rows = make_shards(rng)
    agg = HashAggregateExec.__new__(HashAggregateExec)
    # Build the exec without a child: only its kernels are used.
    HashAggregateExec.__init__(
        agg, _DummyChild(), [("k", Ref(0, dt.INT64))],
        [AggSpec("s", Sum(Ref(1, dt.INT64))),
         AggSpec("n", CountStar(None))])
    part = HashPartitioning([Ref(0, dt.INT64)], N_DEV)
    step = M.distributed_aggregate_step(mesh, agg, part)
    global_batch = M.shard_batches(mesh, shards)
    out = step(global_batch)
    # Collect per-device results and compare against a python oracle.
    got = {}
    for d in range(N_DEV):
        local = jax.tree.map(lambda x: np.asarray(x)[d], out)
        from spark_rapids_tpu.columnar.batch import DeviceBatch
        hb = device_to_host(local)
        for k, s, n in hb.to_pylist():
            assert k not in got, f"group {k} on two devices"
            got[k] = (s, n)
    expected = {}
    for k, v in all_rows:
        s, n = expected.get(k, (0, 0))
        expected[k] = (s + v, n + 1)
    assert got == expected


def test_all_gather_batch(rng):
    mesh = M.make_mesh(N_DEV)
    shards, all_rows = make_shards(rng, rows_per_dev=16)
    global_batch = M.shard_batches(mesh, shards)

    from jax.sharding import PartitionSpec as P
    from spark_rapids_tpu.shims import shard_map

    def inner(stacked):
        local = jax.tree.map(lambda x: x[0], stacked)
        full = M.all_gather_batch(local, N_DEV)
        return jax.tree.map(lambda x: x[None], full)

    fn = jax.jit(shard_map(inner, mesh, in_specs=(P("data"),),
                           out_specs=P("data")))
    out = fn(global_batch)
    # Every device should now hold all rows.
    for d in range(N_DEV):
        local = jax.tree.map(lambda x: np.asarray(x)[d], out)
        hb = device_to_host(local)
        assert sorted(hb.to_pylist()) == sorted(all_rows)


class _DummyChild:
    """Placeholder child for kernel-only HashAggregateExec use."""

    schema = ()
    children = ()

    def num_partitions(self, ctx):
        return 1


def test_dryrun_multichip_raises_on_too_few_devices():
    """Fewer devices than asked for is an error — never a quiet re-run on
    virtual CPU devices that reports a mesh nobody has."""
    import __graft_entry__ as g
    with pytest.raises(RuntimeError, match="device"):
        g.dryrun_multichip(len(jax.devices()) + 1)
