"""Compile the main path's kernels for the chip, without the chip.

The TPU's compiler is installed next to the CPU backend and compiles for a
chip that is described and not attached (guide: on-chip-measurement §2).
These tests hold what no interpret-mode or CPU test can see: that the
programs TPC-H SF1 really dispatches lower for a v5e at the capacities it
dispatches them at (reader batches 3*2^18..2^20 rows, coalesced ones
above).

PR 21 found the packed wire unpack program compiling for 698 s; it is gone
(columnar/wire.py). A compile that passes is not a chip run:
``chip_smoke.py`` is.

The topology is described inside a module-scoped fixture, never at import:
one process at a time may load libtpu, and every xdist worker imports this
file. Keep every chip-compile test in THIS file for the same reason.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import spark_rapids_tpu  # noqa: F401  (x64)
from spark_rapids_tpu.columnar import dtypes as dt

CAPS = (1 << 20, 3 << 19)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip (the next run warns and
    compiles again): keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _shapes(tree, sharding):
    """``tree`` with every array leaf replaced by its shape on the chip."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _compile(fn, sharding, *args):
    return jax.jit(fn).lower(*_shapes(args, sharding)).compile()


def _lineitem_like(cap):
    """A q1/q6-shaped batch: two float64 measures, a date, an int64 key."""
    from spark_rapids_tpu.columnar.batch import DeviceBatch, DeviceColumn
    ones = np.ones((cap,), np.bool_)
    cols = (DeviceColumn(dt.FLOAT64, np.zeros((cap,), np.float64), ones),
            DeviceColumn(dt.FLOAT64, np.zeros((cap,), np.float64), ones),
            DeviceColumn(dt.DATE, np.zeros((cap,), np.int32), ones),
            DeviceColumn(dt.INT64, np.zeros((cap,), np.int64), ones))
    return DeviceBatch(cols, np.asarray(cap, np.int32))


# -- main-path jax.numpy kernels ------------------------------------------------

@pytest.mark.parametrize("cap", CAPS)
def test_wire_decode_program(cap, one_chip):
    """q1's scan batch as the wire ships it: narrowed ints, a plain
    float64, numeric and string dictionaries — decoded from typed arrays
    (the device-side byte unpack this replaced took 698 s here)."""
    from spark_rapids_tpu.columnar import wire
    specs = (("num", "float64", "int8", "all"),
             ("num", "float64", "float64", "all"),
             ("dnum", "float64", "int8", 16, "all"),
             ("dstr", 8, "int8", 8, "all"),
             ("rle", "int32", "int8", 8, "all"),
             ("num", "date", "int16", "all"))
    entries, _total = wire._batch_layout(cap, specs)
    arrays = [np.zeros(shape, np.bool_ if name == "bool" else name)
              for _off, name, shape, _nbytes in entries]
    _compile(wire._decode_fn(cap, specs), one_chip, arrays[:-1], arrays[-1])


@pytest.mark.parametrize("cap", CAPS)
def test_filter_and_global_aggregate(cap, one_chip):
    """q6's device half: the filter predicate and the ungrouped
    hash-aggregate update/merge/finalize."""
    from spark_rapids_tpu import exprs as E
    from spark_rapids_tpu.exprs import base as eb
    from spark_rapids_tpu.exprs.base import BoundReference as Ref, lit
    from spark_rapids_tpu.ops import AggSpec, HashAggregateExec, Sum
    from spark_rapids_tpu.ops.base import InMemorySourceExec
    schema = (("price", dt.FLOAT64), ("disc", dt.FLOAT64),
              ("ship", dt.DATE), ("key", dt.INT64))
    agg = HashAggregateExec(
        InMemorySourceExec(schema, [[]]), [],
        [AggSpec("revenue", Sum(E.Multiply(Ref(0, dt.FLOAT64),
                                           Ref(1, dt.FLOAT64))))])

    def step(batch):
        cond = eb.as_device_column(
            E.LessThan(Ref(1, dt.FLOAT64), lit(0.07)).eval(batch), batch)
        kept = batch.compact(cond.data & cond.validity)
        partial = agg._update_batch(kept, jnp.asarray(0, jnp.int64))
        return agg._finalize_batch(agg._merge_batch(partial))

    _compile(step, one_chip, _lineitem_like(cap))


@pytest.mark.parametrize("sel", [False, True],
                         ids=["dense", "selection-vector"])
def test_grouped_aggregate_update(sel, one_chip):
    """q1's grouped update at a reader batch's 786,432 rows: the probe
    for the batch's distinct keys, the ``cond``, and both branches (the
    slot loops and the sort) in one program. One capacity: the sort in
    it takes the chip's compiler half a minute. Since PR 32 the filtered
    scan batch reaches it uncompacted, under its selection vector."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "scripts"))
    from chip_probe import q1_like_aggregate
    agg, make = q1_like_aggregate()
    cap = 3 << 18
    batch = jax.tree.map(
        lambda x: np.zeros((cap,) + x.shape[1:], x.dtype) if x.ndim
        else np.asarray(cap, x.dtype), make(8, 4))
    if sel:
        batch = batch.with_sel(np.ones((cap,), np.bool_))
    out = _compile(lambda b: agg._update_batch(b, jnp.asarray(0, jnp.int64)),
                   one_chip, batch)
    text = out.as_text()
    assert "conditional(" in text and "while(" in text


def test_radix_permutation(one_chip):
    """The LSD radix sort every sort and grouping shares (ops/kernels.py
    radix_sort) as ``group_ids`` runs it: three word passes, each ONE
    stable sort with the other words and the permutation riding, and no
    gather in the compiled program. One capacity only — the chip's
    compiler takes ~12 s an operand over each distinct sort at any size
    from 32,768 rows up (PR 34; the three passes here are one to it),
    which is ROADMAP A5's business."""
    from spark_rapids_tpu.ops import kernels
    cap = CAPS[1]
    word = np.zeros((cap,), np.uint32)
    out = _compile(lambda *p: kernels.radix_sort(p, cap), one_chip,
                   word, word, word)
    text = out.as_text()
    assert text.count(" sort(") == 3 and " gather(" not in text


@pytest.mark.parametrize("cap", CAPS)
def test_sorted_segment_reduce(cap, one_chip):
    from spark_rapids_tpu.ops import kernels
    valid = np.ones((cap,), np.bool_)
    gid = np.zeros((cap,), np.int32)
    for values, kind in ((np.zeros((cap,), np.float64), "sum"),
                         (np.zeros((cap,), np.int64), "sum"),
                         (np.zeros((cap,), np.int32), "min")):
        _compile(lambda v, ok, g: kernels.segment_reduce(v, ok, g, cap,
                                                         kind),
                 one_chip, values, valid, gid)


@pytest.mark.parametrize("cap", CAPS)
def test_join_probe(cap, one_chip):
    """The sorted-fingerprint probe: per-probe-row match ranges in a
    built side (two searchsorted passes over uint64 fingerprints)."""
    from spark_rapids_tpu.ops import join
    probe = _lineitem_like(cap)
    built = jax.eval_shape(lambda b: join.build_side(b, [3]),
                           _lineitem_like(1 << 17))
    _compile(lambda bs, p: join.probe_ranges(bs, p, [3]), one_chip,
             built, probe)


@pytest.mark.parametrize("cap,build_cap", [(1 << 20, 1 << 21),
                                           (3 << 18, 3 << 18)],
                         ids=["sf10-q3", "sf1-q3"])
def test_late_dense_probe(cap, build_cap, one_chip):
    """The late dense probe's two programs (PR 37) at q3's shapes: the
    lookup over a selection-vector lineitem batch against a 2^24-slot
    table, and the emit at the count's bucket (0.5 % match: 6,144 rows)
    and at the probe's capacity, out of a nine-word build side."""
    import dataclasses
    from spark_rapids_tpu.columnar.batch import DeviceBatch, DeviceColumn
    from spark_rapids_tpu.ops import join
    ones = np.ones((build_cap,), np.bool_)
    kinds = (dt.INT64, dt.DATE, dt.INT32, dt.INT64, dt.INT64)
    build = DeviceBatch(tuple(
        DeviceColumn(t, np.zeros((build_cap,), t.np_dtype), ones)
        for t in kinds), np.asarray(build_cap, np.int32))
    built = dataclasses.replace(
        jax.eval_shape(lambda b: join.build_side(b, [0]), build),
        table=np.zeros((1 << 24,), np.int32),
        table_base=np.zeros((1,), np.int64),
        table_spans=np.ones((1,), np.int64))
    probe = _lineitem_like(cap)
    probe = DeviceBatch(probe.columns, probe.num_rows,
                        sel=np.ones((cap,), np.bool_))
    pos, found, count = jax.eval_shape(
        lambda bs, p: join._late_lookup(bs, p, (3,)), built, probe)
    _compile(lambda bs, p: join._late_lookup(bs, p, (3,)), one_chip,
             built, probe)
    for out_cap in (6_144, None):
        hlo = _compile(
            lambda b, p, at, f, n: join._late_emit(b, p, at, f, n, out_cap,
                                                   True),
            one_chip, built.batch, probe, pos, found, count).as_text()
        assert ("scatter" in hlo) == (out_cap is not None)


# -- packed row movers (columnar/rowmove.py) ------------------------------------

MESH_SHARD = 3 << 19        # TPC-H SF1 q5's big mesh shards: 1,572,864 rows


def _mesh_shard_like(cap, sel=False):
    """A batch with every kind of slab column: q5's shard (int32, int16,
    int64, float64) plus a bool and a 16-byte string."""
    from spark_rapids_tpu.columnar.batch import DeviceBatch, DeviceColumn
    ones = np.ones((cap,), np.bool_)
    kinds = ([dt.INT32] * 3 + [dt.INT16] * 2 + [dt.INT64] * 4
             + [dt.FLOAT64] * 2 + [dt.BOOL])
    cols = [DeviceColumn(t, np.zeros((cap,), t.np_dtype), ones)
            for t in kinds]
    cols.append(DeviceColumn(dt.STRING, np.zeros((cap, 16), np.uint8), ones,
                             np.zeros((cap,), np.int32)))
    return DeviceBatch(tuple(cols), np.asarray(cap, np.int32),
                       sel=ones if sel else None)


def test_compact_and_concat_at_a_mesh_shard(one_chip):
    """The selection-vector discharge of one shard and the concat of four
    received pieces, at the sizes the mesh cell dispatches them."""
    from spark_rapids_tpu.columnar.batch import concat_batches
    from spark_rapids_tpu.columnar.rowmove import compact_batch
    hlo = _compile(compact_batch, one_chip,
                   _mesh_shard_like(MESH_SHARD, sel=True)).as_text()
    assert "scatter" in hlo and "gather" in hlo
    pieces = [_mesh_shard_like(MESH_SHARD // 4) for _ in range(4)]
    _compile(lambda bs: concat_batches(bs, MESH_SHARD), one_chip, pieces)


def test_mesh_collective_step_for_four_chips(topo):
    """``all_to_all_exchange`` under ``shard_map`` over the 2x2 host, at
    a big shard with a two-phase piece capacity: one program across four
    chips, a collective in it, and no slab scatter (the only scatters
    are the send and the receive side's 1-D int32 index lists)."""
    import re
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from spark_rapids_tpu.parallel import mesh as M
    from spark_rapids_tpu.shims import shard_map, tree_map
    n = len(topo.devices)
    assert n == 4
    mesh = Mesh(np.array(topo.devices), (M.DATA_AXIS,))
    rows = NamedSharding(mesh, P(M.DATA_AXIS))
    shard = _mesh_shard_like(MESH_SHARD)
    stacked = jax.tree.map(lambda x: np.broadcast_to(x, (n,) + x.shape),
                           shard)
    pids = np.zeros((n, MESH_SHARD), np.int32)

    def local(st, pids):
        out = M.all_to_all_exchange(tree_map(lambda x: x[0], st), pids[0],
                                    n, piece_capacity=9 << 16)
        return tree_map(lambda x: x[None], out)

    spec = P(M.DATA_AXIS)
    hlo = _compile(shard_map(local, mesh, in_specs=(spec, spec),
                             out_specs=spec), rows, stacked, pids).as_text()
    assert "all-to-all" in hlo
    scattered = re.findall(r"= (\w+)\[[\d,]*\]\S* scatter\(", hlo)
    assert scattered and set(scattered) == {"s32"}, scattered
