"""chip_probe.py — the small on-chip measurements the defaults and notes quote.

    python scripts/chip_probe.py [sync] [link] [upload] [prefix] [slots] [rowmove]
                                 [shrinkrule] [sortpath] [sortgrid] [coalescealone]
                                 [lategather] [--rows N,N,...]

With no section named it runs all eleven. One process, one chip, one JSON
object per line, every reading on the host's clock around a
``block_until_ready`` (on an attached chip that waits for completion):

- ``sync``   one blocking host read of a device scalar — what
             ``spark.rapids.sql.cost.deviceSyncFloorMs`` stands for;
- ``link``   ``device_put`` and read-back bandwidth at 1, 16 and 256 MB;
- ``upload`` the wire upload's shapes on a lineitem-like batch of 2^20
             rows: the host pack copy, ``device_put`` of the encoded
             arrays as they are, of the staging buffer's typed views (what
             ``wire.upload_packed`` does) and of the staging buffer whole
             (what crossed the link until PR 21); then eight tiny batches
             through ``upload_packed`` one by one against
             ``upload_packed_group`` (ROADMAP A2: do the pack and the
             grouping still pay?);
- ``prefix`` the grouped aggregate's prefix sums at 786,432 x 4, float64,
             int64 and int32, each through ``aggregate._prefix_sums`` and
             through the formulation it forks away from (first call with
             compile, then the median of 20);
- ``slots``  the grouped aggregate's update on a q1-shaped batch of
             786,432 rows (two string keys, q1's eight aggregates) holding
             4 to 4,096 groups: the sorted path alone, the update as
             shipped (probe, ``cond``, then slots or sort), and the update
             with the slot limit raised to 1,024 — the table behind
             ``aggregate._SLOT_MAX_GROUPS``; the two paths' sums are
             compared on the way;
- ``rowmove`` the packed row movers of ``columnar/rowmove.py`` on a batch
             shaped like TPC-H Q5's big mesh shards (three int32, two
             int16, four int64, two float64 columns: 59 bytes + validity
             a row) at N = 16,384, 262,144 and 1,572,864 rows, ~98 % and
             ~25 % live: the compaction as a slab SCATTER (the form the
             movers had until PR 30, kept here alone), the same with
             ``unique_indices``, and as shipped (index scatter + slab
             GATHER); each kind of column alone at the largest N; what
             ONE gather costs by dtype and width (why the slabs are uint32
             words, 16 a slab); then the mesh's split into four pieces
             (four compactions + stack against ``split_batch``'s one pass)
             and the concat of four members, at the shard and at a
             two-phase piece capacity — the table behind the module's
             docstring;
- ``shrinkrule`` what ``coalesce_iter(shrink=True)`` buys its two
             consumers at 786,432 rows and 98 / 67 / 50 / 45 / 33 / 25 /
             12 % live under a selection vector (live buckets of 1, 1,
             2/3, 1/2, 1/3, 1/4 and 1/8 of the capacity): (a)
             ``shrink_to_capacity`` to the live bucket and then the
             consumer at that bucket, against
             (b) the consumer at the full capacity reading the selection
             vector. On q3's lineitem layout (an int64 key, two float64)
             the consumer is the dense join probe over a build side of
             ~147,000 unique keys in 6,000,000, with the compaction of
             its output to 4,096 rows that the aggregate above makes
             either way; on q1's layout it is the aggregate's update, four
             groups (slots) and sorted. The table behind
             ``batch.PROBE_SHRINK_RATIO`` and the rule of ``shrink_all``.
- ``sortpath`` what the sorted grouping path is made of (PR 34), first call
             with compile and steady ms. (a) Primitives at 98,304 / 262,144
             / 393,216 / 524,288 / 786,432 / 1,048,576 / 1,572,864 rows
             (``--rows`` picks among them; three are powers of two, to see
             whether those are slow by themselves): one 1-D ``take`` of a
             uint32 and of a float64 column, a float64 stack of two, one
             packed gather of 4 and of 16 words, an index scatter,
             ``lax.sort`` of four operands keyed by one and, at 262,144
             and 786,432 rows, a stable ``argsort``. (b) The operators on
             q67's key layout (five string keys, three int32, one float64
             sum, ~2 rows a group) at 4,096, 98,304 and 786,432 rows — a
             q3 update, an Expand projection, q67's coalesced batch; the
             merge's 1,572,864 is read from the cell's trace — as shipped
             and as they were (``old_loop``: ``take``, ``argsort``,
             ``take``, and the per-class ``take`` of ``_segment_sums``):
             ``group_ids``, the sorted update, and (not at 98,304: two more
             long compiles) the window's frame, rank by the float64
             descending within the first key. At 4,096 and 786,432 rows
             also the candidates that lost: ``group_ids`` over ONE sort of
             three keys (``lax.sort(passes + [iota], num_keys=all)``), and
             the sorted update with its group sums read at ``ends`` by
             packed gathers (``_segment_sums`` with no word allowed to
             ride: what it does for more than ``_SUMS_RIDE_WORDS``) in
             place of the sort that brings q67's three to their slots. The forms' answers are
             compared on the way.
- ``sortgrid`` ``lax.sort`` at 786,432 rows with 2 / 4 / 8 operands and
             1 / 3 / 8 keys, stable: what a rider costs and what a key
             costs, to run and to COMPILE (a sort of eight keys compiles
             for six minutes: 393.6 s for a described v5e in the sandbox).

- ``coalescealone`` what ``coalesce_iter`` buys its consumers by moving
             a member into a concatenated batch (PR 35): members of
             262,144 / 524,288 / 786,432 / 1,048,576 rows of capacity
             (``--rows`` picks others), in a group of two and in the
             group that ``batchSizeRows`` = 4 Mi makes of them (16, 8, 5,
             4). (a) TOGETHER: ``jit_concat_batches`` into one batch of
             the group's capacity, the consumer once; (b) ALONE: the
             consumer once a member, and the same with a blocking read
             of each output's row count behind it (the sizes pull a round
             costs further on, at its dearest: one a member). Each form
             is timed whole, dispatch and device, to its last output
             ready. On q1's layout (two string keys, four float64, ~98 %
             live under a selection vector) the consumer is the
             aggregate's slot update of four groups; on q3's lineitem
             layout (~54 % live) the dense join probe over SF10's build
             side (1.46 M unique keys in 15 M). Also the concat and one
             consumer call by themselves. The table behind
             ``batch.COALESCE_ALONE_ROWS``.

- ``lategather`` where the dense join probe gathers the build side's
             rows (PR 37): q3's lineitem probe, 1,048,576 rows against
             SF10's build side (1.46 M keys, capacity 2,097,152, nine
             words a row) and 786,432 rows against SF1's (147,000 keys,
             capacity 786,432), 0.5 / 10 / 40 / 60 / 100 % of the probe's
             rows matching. EAGER (the parent): ``_dense_step``, and the
             consumer's ``shrink_to_capacity`` of its output where
             ``shrink_all``'s rule for a probe compacts it. LATE: the
             lookup program, then the emit at the count's bucket (index
             pass, probe rows and build rows gathered there) and the emit
             at the probe's capacity under a selection vector; ``shipped``
             names the one ``_dense_stream``'s rule takes. The forms'
             outputs are compared on the way.

Like ``chip_smoke.py`` it refuses any backend but a TPU unless
``--cpu-rehearsal`` is given, which runs the control flow at a tiny size
on the CPU and says so on every line: a CPU reading is not a device number.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SECTIONS = ("sync", "link", "upload", "prefix", "slots", "rowmove",
            "shrinkrule", "sortpath", "sortgrid", "coalescealone",
            "lategather")
SORTPATH_ROWS = (98304, 262144, 393216, 524288, 786432, 1048576, 1572864)
COALESCE_ROWS = (262144, 524288, 786432, 1048576)
LABEL = {}
OPTIONS = {"rows": None}


def emit(section: str, **facts) -> None:
    print(json.dumps({"probe": section, **LABEL, **facts}), flush=True)


def quartiles(xs):
    xs = sorted(xs)
    return {"n": len(xs), "min": xs[0], "p25": xs[len(xs) // 4],
            "median": statistics.median(xs), "p75": xs[(3 * len(xs)) // 4],
            "max": xs[-1]}


def ms(secs):
    """Quartiles of timings in milliseconds."""
    return quartiles([s * 1e3 for s in secs])


def timed(fn, n: int):
    """Seconds of ``n`` calls of ``fn``, each waited for."""
    import jax
    out = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        out.append(time.perf_counter() - t0)
    return out


def probe_sync(jax, small: bool) -> None:
    import jax.numpy as jnp
    import numpy as np
    f = jax.jit(lambda x: x + 1)
    x = jnp.asarray(0, jnp.int32)
    f(x).block_until_ready()
    n = 20 if small else 300
    ready, dispatch = [], []
    for _ in range(n):
        y = f(x)
        y.block_until_ready()
        t0 = time.perf_counter_ns()
        np.asarray(y)
        ready.append((time.perf_counter_ns() - t0) / 1e3)
    for _ in range(n):
        t0 = time.perf_counter_ns()
        np.asarray(f(x))
        dispatch.append((time.perf_counter_ns() - t0) / 1e3)
    from spark_rapids_tpu import config as C
    emit("sync", ready_scalar_read_us=quartiles(ready),
         dispatch_and_read_us=quartiles(dispatch),
         cost_deviceSyncFloorMs_default=C.COST_SYNC_FLOOR_MS.default)


def probe_link(jax, small: bool) -> None:
    import numpy as np
    for mb in ((1, 2) if small else (1, 16, 256)):
        host = np.random.default_rng(mb).integers(
            0, 255, mb << 20, dtype=np.uint8)
        dev = jax.device_put(host)
        dev.block_until_ready()
        n = 3 if small else (5 if mb == 256 else 20)
        up = timed(lambda: jax.device_put(host), n)
        down = []
        for _ in range(n):
            # A fresh device array each time: np.asarray caches the host
            # copy on the array it read.
            d = jax.device_put(host)
            d.block_until_ready()
            t0 = time.perf_counter()
            np.asarray(d)
            down.append(time.perf_counter() - t0)
        gbps = lambda secs: [host.nbytes / s / 1e9 for s in secs]
        emit("link", mb=mb, up_GBps=quartiles(gbps(up)),
             down_GBps=quartiles(gbps(down)),
             down_GBps_in_order=[round(g, 3) for g in gbps(down)])


def _lineitem_like(rows: int, seed: int):
    """A scan batch shaped like q1/q6's pruned lineitem: dictionary-coded
    flags and small decimals, a date, a wide float, an id."""
    import numpy as np
    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.columnar.host import HostBatch, HostColumn
    rng = np.random.default_rng(seed)
    cols = [
        ("l_orderkey", dt.INT64, np.sort(rng.integers(1, 6_000_000, rows))),
        ("l_quantity", dt.FLOAT64,
         rng.integers(1, 51, rows).astype(np.float64)),
        ("l_extendedprice", dt.FLOAT64,
         np.round(rng.uniform(900, 105_000, rows), 2)),
        ("l_discount", dt.FLOAT64, rng.integers(0, 11, rows) / 100.0),
        ("l_shipdate", dt.INT32,
         rng.integers(8036, 10_562, rows).astype(np.int32)),
        ("l_linenumber", dt.INT32,
         rng.integers(1, 8, rows).astype(np.int32))]
    valid = np.ones(rows, np.bool_)
    return HostBatch(tuple(n for n, _, _ in cols),
                     [HostColumn(t, v, valid) for _, t, v in cols])


def probe_upload(jax, small: bool) -> None:
    import numpy as np
    from spark_rapids_tpu.columnar import wire
    rows = 1 << (12 if small else 20)
    n = 3 if small else 20
    hb = _lineitem_like(rows, 0)
    arrays, specs, nrows, cap = wire.encode_batch(hb)
    arrays = [np.asarray(a) for a in arrays]
    pack = []
    for _ in range(n):
        t0 = time.perf_counter()
        enc = wire.pack_encoded(arrays, specs, nrows, cap)
        pack.append(time.perf_counter() - t0)
    views = wire._staged_views(enc)
    nbytes = sum(a.nbytes for a in arrays)
    shapes = {
        "encoded_arrays_as_they_are": lambda: jax.device_put(arrays),
        "staging_views__upload_packed": lambda: jax.device_put(views),
        "staging_buffer_whole__until_pr21": lambda: jax.device_put(
            enc.staging),
    }
    for fn in shapes.values():
        jax.block_until_ready(fn())
    emit("upload", rows=rows, wire_arrays=len(arrays),
         array_bytes=sorted(a.nbytes for a in arrays),
         encoded_bytes=nbytes, staging_bytes=int(enc.nbytes),
         host_pack_copy_ms=ms(pack),
         device_put_ms={k: ms(timed(fn, n)) for k, fn in shapes.items()})
    wire.upload_packed(enc)                 # compile the decode program
    emit("upload_and_decode", rows=rows,
         upload_packed_ms=ms(timed(
             lambda: wire.upload_packed(enc).columns[0].data, n)))

    tiny_rows = 1 << (6 if small else 13)
    tiny = [wire.pack_batch(_lineitem_like(tiny_rows, i + 1))
            for i in range(8)]
    last = lambda batches: [b.columns[0].data for b in batches]
    solo = lambda: last([wire.upload_packed(e) for e in tiny])
    grouped = lambda: last(wire.upload_packed_group(tiny))
    jax.block_until_ready(solo())
    jax.block_until_ready(grouped())
    emit("upload_grouping", batches=len(tiny), rows_each=tiny_rows,
         staging_bytes_each=sorted(int(e.nbytes) for e in tiny),
         one_call_each_ms=ms(timed(solo, n)),
         one_call_for_all_ms=ms(timed(grouped, n)))


def probe_prefix(jax, small: bool) -> None:
    import jax.numpy as jnp
    import numpy as np
    from spark_rapids_tpu.ops.aggregate import (_prefix_sums,
                                                _two_level_prefix_sums)
    rows, k = (4096 if small else 786432), 4
    rng = np.random.default_rng(0)
    for dtn in ("float64", "int64", "int32"):
        if dtn == "float64":
            host = rng.uniform(0, 1e5, (rows, k))
        else:
            host = rng.integers(0, 1000, (rows, k)).astype(dtn)
        M = jnp.asarray(host)
        want = np.cumsum(host, axis=0)
        # What the engine runs for this dtype, then what it forks away
        # from: jnp.cumsum for floats, the two-level scan for ints.
        if dtn == "float64":
            other = ("jnp_cumsum", lambda m: jnp.cumsum(m, axis=0))
        else:
            other = ("two_level_assoc_scan", _two_level_prefix_sums)
        for name, fn in (("engine__prefix_sums", _prefix_sums), other):
            f = jax.jit(fn)
            t0 = time.perf_counter()
            out = f(M)
            out.block_until_ready()
            first = time.perf_counter() - t0
            steady = timed(lambda: f(M), 3 if small else 20)
            got = np.asarray(out)
            if dtn == "float64":
                err = float(np.max(np.abs(got - want) / want))
            else:
                err = int(np.max(np.abs(got - want)))
            emit("prefix", dtype=dtn, shape=[rows, k], formulation=name,
                 first_call_s=round(first, 3),
                 steady_ms=quartiles([s * 1e3 for s in steady]),
                 max_err_vs_numpy=err)


def q1_like_aggregate():
    """q1's grouped aggregate over (flag, status, qty, price, disc, tax):
    the operator (``hasNans`` false, as the benchmark's cells run it) and
    a maker of batches of ``rows`` rows in ``groups`` groups."""
    import numpy as np
    from spark_rapids_tpu import exprs as E
    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.columnar.host import HostBatch, host_to_device
    from spark_rapids_tpu.exprs.base import BoundReference as Ref, lit
    from spark_rapids_tpu.ops import (AggSpec, Average, CountStar,
                                      HashAggregateExec,
                                      InMemorySourceExec, Sum)
    schema = (("flag", dt.STRING), ("status", dt.STRING),
              ("qty", dt.FLOAT64), ("price", dt.FLOAT64),
              ("disc", dt.FLOAT64), ("tax", dt.FLOAT64))
    qty, price, disc, tax = (Ref(i, dt.FLOAT64) for i in range(2, 6))
    disc_price = E.Multiply(price, E.Subtract(lit(1.0), disc))
    charge = E.Multiply(disc_price, E.Add(lit(1.0), tax))
    agg = HashAggregateExec(
        InMemorySourceExec(schema, [[]]),
        [("flag", Ref(0, dt.STRING)), ("status", Ref(1, dt.STRING))],
        [AggSpec("sum_qty", Sum(qty)), AggSpec("sum_base", Sum(price)),
         AggSpec("sum_disc_price", Sum(disc_price)),
         AggSpec("sum_charge", Sum(charge)),
         AggSpec("avg_qty", Average(qty)),
         AggSpec("avg_price", Average(price)),
         AggSpec("avg_disc", Average(disc)),
         AggSpec("count_order", CountStar(None))], mode="partial")
    agg._has_nans = False

    def batch(rows: int, groups: int, seed: int = 0):
        rng = np.random.default_rng(seed)
        g = rng.integers(0, groups, rows)
        return host_to_device(HostBatch.from_pydict(schema, {
            "flag": [f"F{i:04d}" for i in g // 2],
            "status": ["O" if i % 2 else "F" for i in g],
            "qty": rng.integers(1, 51, rows).astype(np.float64),
            "price": np.round(rng.uniform(900, 105_000, rows), 2),
            "disc": rng.integers(0, 11, rows) / 100.0,
            "tax": rng.integers(0, 9, rows) / 100.0}))
    return agg, batch


def probe_slots(jax, small: bool) -> None:
    import jax.numpy as jnp
    import numpy as np
    from spark_rapids_tpu.ops import aggregate
    rows = 3 << (8 if small else 18)
    n = 3 if small else 20
    shipped = aggregate._slot_limit(rows)
    agg, make = q1_like_aggregate()
    off = jnp.asarray(0, jnp.int64)

    def update(limit):
        # The limit is read while tracing: one jit per value of it.
        def fn(b):
            rule, aggregate._slot_limit = aggregate._slot_limit, \
                lambda capacity: min(limit, capacity)
            try:
                return agg._update_batch(b, off)
            finally:
                aggregate._slot_limit = rule
        return jax.jit(fn)

    paths = {"sorted_alone": jax.jit(lambda b: agg._sorted_update(
                 *agg._project_inputs(b), off)),
             "update_as_shipped": update(shipped),
             "update_limit_1024": update(1024)}
    for groups in ((4, 64) if small else
                   (4, 16, 32, 64, 128, 256, 512, 1024, 4096)):
        b = make(rows, groups)
        facts, outs = {}, {}
        for name, f in paths.items():
            t0 = time.perf_counter()
            outs[name] = jax.block_until_ready(f(b))
            first = time.perf_counter() - t0
            facts[name] = {"first_call_s": round(first, 3),
                           "steady_ms": ms(timed(lambda: f(b), n))}
        want = outs["sorted_alone"]
        found = int(want.num_rows)
        gap = 0.0
        for name, got in outs.items():
            assert int(got.num_rows) == found, (name, got.num_rows)
            for cw, cg in zip(want.columns, got.columns):
                w, g = np.asarray(cw.data)[:found], np.asarray(cg.data)[:found]
                assert (np.asarray(cw.validity) ==
                        np.asarray(cg.validity)).all(), name
                if cw.dtype.is_floating:
                    gap = max(gap, float(np.max(np.abs(g - w) / np.abs(w))))
                else:
                    assert (w == g).all(), name   # keys in order, counts
        emit("slots", rows=rows, groups=found, slot_limit_shipped=shipped,
             max_rel_gap_vs_sorted=gap, **facts)


def q5_shard_like(rows: int, live_share: float, classes=("w8", "i64", "f64"),
                  seed: int = 0):
    """A device batch of ``rows`` capacity with the columns of q5's big
    mesh shards (until PR 30 the slabs ``u8[N,27]`` = three int32 and two
    int16 columns and the eleven validity bytes, ``i64[N,4]``,
    ``f64[N,2]``), all rows in the prefix and ``live_share`` of them
    selected; ``classes`` keeps one kind of column alone."""
    import jax.numpy as jnp
    import numpy as np
    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.columnar.batch import DeviceBatch, DeviceColumn
    rng = np.random.default_rng(seed)
    kinds = {"i64": [dt.INT64] * 4, "f64": [dt.FLOAT64] * 2,
             "w8": [dt.INT32] * 3 + [dt.INT16] * 2}
    cols = []
    for cls in classes:
        for t in kinds[cls]:
            if t is dt.FLOAT64:
                data = rng.uniform(0, 1e5, rows)
            else:
                data = rng.integers(0, 30000, rows).astype(t.np_dtype)
            cols.append(DeviceColumn(t, jnp.asarray(data),
                                     jnp.asarray(rng.random(rows) < 0.97)))
    sel = jnp.asarray(rng.random(rows) < live_share)
    return DeviceBatch(tuple(cols), jnp.asarray(rows, jnp.int32), sel=sel)


def scatter_compact(batch, keep=None, unique: bool = False):
    """``compact_batch`` as it was until PR 30: the slabs themselves are
    scattered to the live rows' ranks. ``unique`` promises XLA distinct
    indices, for which the dead rows get distinct out-of-range targets."""
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar.rowmove import pack_batch, unpack_batch
    cap = batch.capacity
    live = batch.row_mask() if keep is None else (keep & batch.row_mask())
    rank = jnp.cumsum(live.astype(jnp.int32)) - 1
    dead = cap + jnp.arange(cap, dtype=jnp.int32) if unique else cap
    positions = jnp.where(live, rank, dead)
    out = {k: jnp.zeros_like(slab).at[positions].set(
               slab, mode="drop", unique_indices=unique)
           for k, slab in pack_batch(batch).items()}
    return unpack_batch(out, batch, jnp.sum(live.astype(jnp.int32)))


def gather_by_shape(jax, small: bool, top: int, n: int) -> None:
    """What ONE slab gather costs by dtype, width and the layout the
    compiler gives it (read from the compiled HLO), at ``top`` rows and at
    half of that, for an index list as a 98 % live compaction makes it."""
    import re
    import jax.numpy as jnp
    import numpy as np
    take = jax.jit(lambda slab, idx: jnp.take(slab, idx, axis=0,
                                              mode="clip"))
    for rows in (top // 2, top):
        live = np.random.default_rng(2).random(rows) < 0.98
        idx = jnp.asarray(np.resize(np.flatnonzero(live), rows), jnp.int32)
        for dtn, widths in (("uint8", (27, 31, 59, 64, 128)),
                            ("uint32", (4, 7, 8, 15, 16, 32, 128)),
                            ("int64", (4,)), ("float64", (0, 2, 5, 8, 16))):
            for w in widths[:2] if small else widths:
                slab = jnp.ones((rows, w) if w else (rows,), dtn)
                hlo = take.lower(slab, idx).compile().as_text()
                layouts = sorted({m for line in hlo.splitlines()
                                  if "gather" in line
                                  for m in re.findall(
                                      r"= (\S+\[%d[,\]]\S*) \w+\(" % rows, line)})
                jax.block_until_ready(take(slab, idx))
                emit("rowmove", mover="gather_by_shape", rows=rows,
                     dtype=dtn, width=w, layouts=layouts,
                     steady_ms=ms(timed(lambda: take(slab, idx), n)))
                del slab


def probe_rowmove(jax, small: bool) -> None:
    import jax.numpy as jnp
    import numpy as np
    from spark_rapids_tpu.columnar.batch import (DeviceBatch,
                                                 bucket_capacity,
                                                 concat_batches)
    from spark_rapids_tpu.columnar.rowmove import (compact_batch,
                                                   pack_batch, unpack_batch)
    from spark_rapids_tpu.parallel.partitioning import split_batch
    from spark_rapids_tpu.shims import tree_map
    n = 3 if small else 10
    sizes = (256, 1024) if small else (16384, 262144, 1572864)

    def race(what, forms, *args, **facts):
        """First call (with compile) and steady times of each form on the
        same arguments; every form's answer must equal the first's."""
        outs, read = {}, {}
        for name, fn in forms.items():
            f = jax.jit(fn)
            t0 = time.perf_counter()
            outs[name] = jax.block_until_ready(f(*args))
            first = time.perf_counter() - t0
            read[name] = {"first_call_s": round(first, 3),
                          "steady_ms": ms(timed(lambda: f(*args), n))}
        want = jax.tree_util.tree_leaves(next(iter(outs.values())))
        for name, got in outs.items():      # num_rows is a leaf too
            for x, y in zip(want, jax.tree_util.tree_leaves(got)):
                assert (np.asarray(x) == np.asarray(y)).all(), (what, name)
        emit("rowmove", mover=what, **facts, **read)

    compacts = {"scatter": scatter_compact,
                "scatter_unique": lambda b: scatter_compact(b, unique=True),
                "gather_as_shipped": compact_batch}
    for rows in sizes:
        for share in (0.98, 0.25):
            race("compact", compacts, q5_shard_like(rows, share),
                 rows=rows, live_share=share, columns="w8+i64+f64")
    for cls in ("w8", "i64", "f64"):
        race("compact", compacts, q5_shard_like(sizes[-1], 0.98, (cls,)),
             rows=sizes[-1], live_share=0.98, columns=cls)

    gather_by_shape(jax, small, sizes[-1], n)

    # The mesh's send side (four destinations) and its receive side.
    rows, parts = sizes[-1], 4
    shard = compact_batch(q5_shard_like(rows, 0.98))
    pids = jnp.asarray(np.random.default_rng(1).integers(0, parts, rows),
                       jnp.int32)
    for pc in (None, bucket_capacity(int(rows * 0.98 / parts * 1.02))):
        def four_passes(b, pids, pc=pc):
            pieces = [scatter_compact(b, pids == p) for p in range(parts)]
            if pc is not None:
                pieces = [DeviceBatch(
                    tree_map(lambda x: x[:pc], p.columns),
                    jnp.minimum(p.num_rows, pc)) for p in pieces]
            return tree_map(lambda *xs: jnp.stack(xs), *pieces)
        race("split", {"four_scatter_passes": four_passes,
                       "one_pass_as_shipped":
                           lambda b, pids, pc=pc: split_batch(b, pids, parts,
                                                              pc)},
             shard, pids, rows=rows, pieces=parts, piece_capacity=pc or rows)
        members = [tree_map(lambda x, i=i: x[i],
                            split_batch(shard, pids, parts, pc))
                   for i in range(parts)]
        cap = bucket_capacity(sum(m.capacity for m in members))

        def scatter_concat(bs, cap=cap):
            out, off = {}, jnp.asarray(0, jnp.int32)
            for b in bs:
                live = b.row_mask()
                pos = jnp.where(live, jnp.cumsum(live.astype(jnp.int32))
                                - 1 + off, cap)
                for k, slab in pack_batch(b).items():
                    acc = out.get(k)
                    if acc is None:
                        acc = jnp.zeros((cap,) + slab.shape[1:], slab.dtype)
                    out[k] = acc.at[pos].set(slab, mode="drop")
                off = off + jnp.sum(live.astype(jnp.int32))
            return unpack_batch(out, bs[0], off)
        race("concat", {"scatter_per_member": scatter_concat,
                        "gather_as_shipped":
                            lambda bs, cap=cap: concat_batches(bs, cap)},
             members, members=parts, member_capacity=members[0].capacity,
             capacity=cap)


def q3_like_probe(rows: int, build_rows: int, key_span: int):
    """q3's second join, lineitem against the filtered orders: a jitted
    direct-address probe (the join's own ``_dense_step``) over a build
    side of ``build_rows`` unique int64 keys out of ``key_span`` carrying a
    date and an int32, and a lineitem-like probe batch of ``rows`` rows
    (the key, two float64), ``4 * build_rows / key_span`` of whose keys are
    in the build side (q3 at SF1: a tenth)."""
    import jax.numpy as jnp
    import numpy as np
    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.columnar.batch import DeviceBatch, DeviceColumn
    from spark_rapids_tpu.exprs.base import BoundReference as Ref
    from spark_rapids_tpu.ops import InMemorySourceExec
    from spark_rapids_tpu.ops import join as J
    rng = np.random.default_rng(3)
    yes = lambda n: jnp.ones((n,), jnp.bool_)            # noqa: E731

    def batch(cols, n):
        return DeviceBatch(tuple(DeviceColumn(t, jnp.asarray(d), yes(n))
                                 for t, d in cols), jnp.asarray(n, jnp.int32))
    # Order keys are sparse (a quarter of the span exists), and a probe
    # key is one of those that exist.
    orders = rng.choice(key_span, key_span // 4, replace=False) \
        .astype(np.int64)
    keys = orders[:build_rows]
    build = batch([(dt.INT64, keys),
                   (dt.DATE, rng.integers(8000, 9200, build_rows)
                    .astype(np.int32)),
                   (dt.INT32, np.zeros(build_rows, np.int32))], build_rows)
    probe = batch([(dt.INT64, rng.choice(orders, rows)),
                   (dt.FLOAT64, np.round(rng.uniform(900, 105_000, rows), 2)),
                   (dt.FLOAT64, rng.integers(0, 11, rows) / 100.0)], rows)
    op = J.BroadcastHashJoinExec(
        InMemorySourceExec((("l_orderkey", dt.INT64), ("price", dt.FLOAT64),
                            ("disc", dt.FLOAT64)), [[]]),
        InMemorySourceExec((("o_orderkey", dt.INT64), ("o_date", dt.DATE),
                            ("o_prio", dt.INT32)), [[]]),
        [Ref(0, dt.INT64)], [Ref(0, dt.INT64)], "inner")
    built = J.build_side(build, [0])
    J._maybe_build_dense(built, built.batch, built.key_ordinals)
    assert built.table is not None, "the build side took no dense table"
    dense = op._dense_jit_fn()
    return probe, lambda b: dense(built, b, probe_keys=(0,),
                                  build_is_right=True)


def probe_shrinkrule(jax, small: bool) -> None:
    import jax.numpy as jnp
    import numpy as np
    from spark_rapids_tpu.columnar.batch import (bucket_capacity,
                                                 shrink_to_capacity)
    rows = 3 << (8 if small else 18)
    n = 3 if small else 10
    shares = (98, 50) if small else (98, 67, 50, 45, 33, 25, 12)
    off = jnp.asarray(0, jnp.int64)

    def steady(fn, *args):
        """Median ms of a call after its first, which compiles."""
        jax.block_until_ready(fn(*args))
        return ms(timed(lambda: fn(*args), n))["median"]

    def live_rows(b):
        return int(jax.device_get(b.live_count()))

    def selected(b, pct):
        """``b`` with ``pct`` % of its rows selected, its live count and
        bucket, and the same rows compacted to that bucket."""
        keep = np.random.default_rng(pct).random(b.capacity) < pct / 100
        b = b.with_sel(jnp.asarray(keep))
        live = live_rows(b)
        bucket = bucket_capacity(live)
        return b, live, bucket, shrink_to_capacity(b, bucket)

    lineitem, dense = q3_like_probe(
        rows, *((96, 4096) if small else (147_000, 6_000_000)))
    agg, make = q1_like_aggregate()
    slot = jax.jit(lambda b: agg._update_batch(b, off))
    srt = jax.jit(lambda b: agg._sorted_update(*agg._project_inputs(b), off))
    q1_batch = make(rows, 4)
    for pct in shares:
        b, live, bucket, small_b = selected(lineitem, pct)
        out_full, out_small = dense(b), dense(small_b)
        matched = live_rows(out_full)
        assert matched == live_rows(out_small)
        out_cap = bucket_capacity(matched)
        emit("shrinkrule", layout="q3_lineitem", consumer="dense_probe",
             rows=rows, live_pct=pct, live=live, bucket=bucket,
             matched=matched,
             compact_ms=steady(shrink_to_capacity, b, bucket),
             probe_at_bucket_ms=steady(dense, small_b),
             probe_at_capacity_ms=steady(dense, b),
             out_compact_from_bucket_ms=steady(
                 shrink_to_capacity, out_small, out_cap),
             out_compact_from_capacity_ms=steady(
                 shrink_to_capacity, out_full, out_cap))
        b, live, bucket, small_b = selected(q1_batch, pct)
        assert int(slot(b).num_rows) == int(slot(small_b).num_rows) == 4
        emit("shrinkrule", layout="q1_lineitem", consumer="update",
             rows=rows, live_pct=pct, live=live, bucket=bucket,
             compact_ms=steady(shrink_to_capacity, b, bucket),
             slot_at_bucket_ms=steady(slot, small_b),
             slot_at_capacity_ms=steady(slot, b),
             sorted_at_bucket_ms=steady(srt, small_b),
             sorted_at_capacity_ms=steady(srt, b))


def old_radix(passes, capacity, unstable_first=False):
    """``kernels.radix_sort`` as it was until PR 34 (kept here alone, and
    in ``tests/test_sort_core.py`` as the reference): two 1-D gathers a
    pass, and one more for every pass the caller reads back."""
    import jax.numpy as jnp
    perm = jnp.arange(capacity, dtype=jnp.int32)
    first = True
    for words in reversed(passes):
        keyed = jnp.take(words, perm, axis=0)
        order = jnp.argsort(keyed, stable=not (unstable_first and first))
        perm = jnp.take(perm, order, axis=0)
        first = False
    return perm, [jnp.take(p, perm, axis=0) for p in passes]


def onesort_radix(passes, capacity, unstable_first=False):
    """The form ``radix_sort`` did not take: ONE sort keyed by every
    pass; stable, so ties keep the original order as the LSD passes do."""
    import jax
    import jax.numpy as jnp
    out = jax.lax.sort(
        list(passes) + [jnp.arange(capacity, dtype=jnp.int32)],
        num_keys=len(passes), is_stable=not unstable_first)
    return out[-1], list(out[:-1])


def old_segment_sums(stacks, gid, slive, capacity):
    """``HashAggregateExec._segment_sums`` as it was until PR 34: the
    prefix sums of each dtype class read at the groups' ends by a
    ``take`` of their own."""
    import jax.numpy as jnp
    from spark_rapids_tpu.ops import aggregate
    idx = jnp.arange(capacity, dtype=jnp.int32)
    nxt_gid = jnp.concatenate([gid[1:], gid[-1:]])
    nxt_live = jnp.concatenate([slive[1:], jnp.zeros((1,), jnp.bool_)])
    last = slive & ((idx == capacity - 1) | (nxt_gid != gid) | ~nxt_live)
    ends = jnp.zeros((capacity,), jnp.int32).at[
        jnp.where(last, gid, capacity)].set(idx, mode="drop")
    out = {}
    for cls, arrs in stacks.items():
        se = jnp.take(aggregate._prefix_sums(jnp.stack(arrs, axis=1)),
                      ends, axis=0)
        d = jnp.concatenate([se[:1], se[1:] - se[:-1]], axis=0)
        out[cls] = [d[:, j] for j in range(len(arrs))]
    return out


def q67_like(rows: int, seed: int = 0):
    """q67's rollup aggregate (nine keys, five of them strings, one
    float64 sum) and a batch of ``rows`` rows of it, ~2 rows a group,
    NULLs where a rollup level and the data put them; ~98 % live under a
    selection vector."""
    import jax.numpy as jnp
    import numpy as np
    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.columnar.batch import DeviceBatch, DeviceColumn
    from spark_rapids_tpu.exprs.base import BoundReference as Ref
    from spark_rapids_tpu.ops import (AggSpec, HashAggregateExec,
                                      InMemorySourceExec, Sum)
    rng = np.random.default_rng(seed)
    widths = {"i_category": 16, "i_class": 16, "i_brand": 32,
              "i_product_name": 32, "s_store_id": 16}
    schema, cols = [], []
    item = rng.integers(0, max(rows // 24, 4), rows)
    for name in ("i_category", "i_class", "i_brand", "i_product_name",
                 "d_year", "d_qoy", "d_moy", "s_store_id"):
        valid = jnp.asarray(rng.random(rows) < 0.8)
        if name in widths:
            w = widths[name]
            key = item if name.startswith("i_") else rng.integers(0, 12, rows)
            text = np.zeros((rows, w), np.uint8)
            digits = 8
            for d in range(digits):
                text[:, d] = 48 + (key // 10 ** (digits - 1 - d)) % 10
            text[:, digits:w - 2] = 97 + len(name) % 26
            schema.append((name, dt.STRING))
            cols.append(DeviceColumn(dt.STRING, jnp.asarray(text), valid,
                                     jnp.full((rows,), w - 2, jnp.int32)))
        else:
            schema.append((name, dt.INT32))
            cols.append(DeviceColumn(
                dt.INT32, jnp.asarray(rng.integers(1, 13, rows), jnp.int32),
                valid))
    schema.append(("sales", dt.FLOAT64))
    cols.append(DeviceColumn(
        dt.FLOAT64, jnp.asarray(rng.integers(0, 20000, rows).astype(float)),
        jnp.asarray(rng.random(rows) < 0.96)))
    agg = HashAggregateExec(
        InMemorySourceExec(tuple(schema), [[]]),
        [(n, Ref(i, t)) for i, (n, t) in enumerate(schema[:-1])],
        [AggSpec("sumsales", Sum(Ref(len(schema) - 1, dt.FLOAT64)))],
        mode="partial")
    agg._has_nans = False
    batch = DeviceBatch(tuple(cols), jnp.asarray(rows, jnp.int32),
                        sel=jnp.asarray(rng.random(rows) < 0.98))
    return agg, batch


def probe_sortpath(jax, small: bool) -> None:
    import contextlib
    import dataclasses
    import jax.numpy as jnp
    import numpy as np
    from spark_rapids_tpu.exprs.base import BoundReference as Ref
    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.ops import aggregate, kernels, window
    from spark_rapids_tpu.ops.sort import SortOrder
    n = 3 if small else 10
    sizes = (256, 1024) if small else OPTIONS["rows"] or SORTPATH_ROWS
    argsort_at = sizes if small else (262144, 786432)
    operators_at = (256, 1024) if small else tuple(
        r for r in (4096, 98304, 786432) if r == 4096 or r in sizes)
    off = jnp.asarray(0, jnp.int64)

    def read(what, fn, *args, **facts):
        """First call (with compile) and steady ms of ``jit(fn)``."""
        f = jax.jit(fn)
        t0 = time.perf_counter()
        out = jax.block_until_ready(f(*args))
        first = time.perf_counter() - t0
        emit("sortpath", what=what, first_call_s=round(first, 3),
             steady_ms=ms(timed(lambda: f(*args), n)), **facts)
        return out

    @contextlib.contextmanager
    def sort_core(form):
        """The operators traced with another sort core, another rule for
        the group sums or, with the old loop, the old ``_segment_sums``:
        all are read while tracing, so one jit a form."""
        shipped = (kernels.radix_sort,
                   aggregate.HashAggregateExec.__dict__["_segment_sums"],
                   aggregate._SUMS_RIDE_WORDS)
        if form == "one_sort":
            kernels.radix_sort = onesort_radix
        elif form == "sums_by_take":
            aggregate._SUMS_RIDE_WORDS = 0
        elif form == "old_loop":
            kernels.radix_sort = old_radix
            aggregate.HashAggregateExec._segment_sums = staticmethod(
                old_segment_sums)
        try:
            yield
        finally:
            (kernels.radix_sort,
             aggregate.HashAggregateExec._segment_sums,
             aggregate._SUMS_RIDE_WORDS) = shipped

    def with_core(form, fn):
        def traced(*args):
            with sort_core(form):
                return fn(*args)
        return traced

    for rows in sizes:
        rng = np.random.default_rng(rows)
        idx = jnp.asarray(rng.permutation(rows), jnp.int32)
        u32 = [jnp.asarray(rng.integers(0, 2 ** 32, rows, dtype=np.uint32))
               for _ in range(8)]
        f64 = jnp.asarray(rng.normal(0, 1, rows))
        take = lambda x, i: jnp.take(x, i, axis=0, mode="clip")  # noqa: E731
        read("take_1d_u32", take, u32[0], idx, rows=rows)
        read("take_1d_f64", take, f64, idx, rows=rows)
        read("take_f64_x2", take, jnp.stack([f64, f64 + 1], 1), idx,
             rows=rows)
        read("take_u32_x4", take, jnp.stack(u32[:4], 1), idx, rows=rows)
        read("take_u32_x16", take, jnp.stack(u32 + u32, 1), idx, rows=rows)
        read("scatter_index", lambda i: jnp.zeros((rows,), jnp.int32).at[
            i].set(jnp.arange(rows, dtype=jnp.int32), mode="drop"), idx,
            rows=rows)
        read("lax_sort", lambda *xs: jax.lax.sort(
            list(xs), num_keys=1, is_stable=True), *u32[:3], idx, rows=rows,
            operands=4, num_keys=1)
        if rows in argsort_at:
            read("argsort_stable", lambda x: jnp.argsort(x, stable=True),
                 u32[0], rows=rows)
    for rows in operators_at:
        agg, batch = q67_like(rows)
        nkeys = len(agg.group_exprs)
        fp = jax.jit(lambda b: kernels.key_fingerprint(
            b.columns[:nkeys], rows))(batch)          # noqa: B023
        spec = window.WindowSpec(
            [Ref(0, dt.STRING)],
            [SortOrder(Ref(nkeys, dt.FLOAT64), ascending=False)])
        # The candidates that lost and the frame (two long compiles more)
        # are not read at 98,304 rows.
        full = rows != 98304
        want = {}
        for what, fn, args, forms in (
                ("group_ids", lambda b, a, c: dataclasses.astuple(
                    kernels.group_ids(b, (), (a, c))), (batch, *fp),
                 ("shipped", "one_sort", "old_loop") if full
                 else ("shipped", "old_loop")),
                ("sorted_update", lambda b: agg._sorted_update(  # noqa: B023
                    *agg._project_inputs(b), off), (batch,),     # noqa: B023
                 ("shipped", "sums_by_take", "old_loop") if full
                 else ("shipped", "old_loop")),
                ("window_frame", lambda b: window._sorted_frame(
                    b, spec), (batch,),                          # noqa: B023
                 ("shipped", "old_loop") if full else ())):
            for form in forms:
                got = jax.tree.leaves(read(what, with_core(form, fn), *args,
                                           rows=rows, sort_core=form))
                for x, y in zip(want.setdefault(what, got), got):
                    assert (np.asarray(x) == np.asarray(y)).all(), (
                        what, rows, form)
        del batch, want, got


def probe_sortgrid(jax, small: bool) -> None:
    import jax.numpy as jnp
    import numpy as np
    rows = 1024 if small else 786432
    n = 3 if small else 10
    rng = np.random.default_rng(rows)
    xs = [jnp.asarray(rng.integers(0, 2 ** 32, rows, dtype=np.uint32))
          for _ in range(7)] + [jnp.arange(rows, dtype=jnp.int32)]
    for operands, keys in ((2, 1), (4, 1), (8, 1), (4, 3), (8, 3), (8, 8)):
        f = jax.jit(lambda *ops: jax.lax.sort(
            list(ops), num_keys=keys, is_stable=True))          # noqa: B023
        args = xs[:operands - 1] + xs[-1:]
        t0 = time.perf_counter()
        jax.block_until_ready(f(*args))
        first = time.perf_counter() - t0
        emit("sortgrid", rows=rows, operands=operands, num_keys=keys,
             first_call_s=round(first, 3),
             steady_ms=ms(timed(lambda: f(*args), n)))            # noqa: B023

def probe_coalescealone(jax, small: bool) -> None:
    import jax.numpy as jnp
    import numpy as np
    from spark_rapids_tpu.columnar.batch import (bucket_capacity,
                                                 jit_concat_batches)
    goal = 1 << (12 if small else 22)           # batchSizeRows
    sizes = tuple(r >> 10 for r in COALESCE_ROWS) if small \
        else OPTIONS["rows"] or COALESCE_ROWS
    n = 3 if small else 10
    off = jnp.asarray(0, jnp.int64)
    agg, make = q1_like_aggregate()
    slot = jax.jit(lambda b: agg._update_batch(b, off))

    def whole(fn):
        """Median ms of ``fn`` to its last output ready, after a first
        call that compiles."""
        jax.block_until_ready(fn())
        return ms(timed(fn, n))["median"]

    def selected(b, pct):
        keep = np.random.default_rng(pct).random(b.capacity) < pct / 100
        return b.with_sel(jnp.asarray(keep))

    def pulled(consumer, members):
        outs = []
        for m in members:
            outs.append(consumer(m))
            jax.device_get(outs[-1].num_rows)
        return outs

    for rows in sizes:
        lineitem, dense = q3_like_probe(
            rows, *((96, 4096) if small else (1_460_000, 15_000_000)))
        layouts = (("q1_lineitem", "slot_update", slot,
                    selected(make(rows, 4), 98)),
                   ("q3_lineitem", "dense_probe", dense,
                    selected(lineitem, 54)))
        for layout, name, consumer, member in layouts:
            for k in sorted({2, max(goal // rows, 2)}):
                members = [member] * k
                cap = bucket_capacity(k * rows)
                concat = lambda: jit_concat_batches(members, cap)  # noqa: E731
                big = concat()
                emit("coalescealone", layout=layout, consumer=name,
                     rows=rows, members=k, out_capacity=cap,
                     concat_ms=whole(concat),
                     consumer_at_member_ms=whole(lambda: consumer(member)),
                     consumer_at_group_ms=whole(lambda: consumer(big)),
                     together_ms=whole(lambda: consumer(concat())),
                     together_pulled_ms=whole(
                         lambda: pulled(consumer, [concat()])),
                     alone_ms=whole(
                         lambda: [consumer(m) for m in members]),
                     alone_pulled_ms=whole(
                         lambda: pulled(consumer, members)))
                del big


def q3_like_build(build_rows: int, capacity: int, key_span: int, rng):
    """SF10 q3's second build side (orders x customer, nine words a row:
    the order key, a date, an int32, two more int64) of ``build_rows``
    unique EVEN keys below ``key_span`` in a batch of ``capacity`` rows,
    with its dense table, and the keys."""
    import jax.numpy as jnp
    import numpy as np
    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.columnar.batch import DeviceBatch, DeviceColumn
    from spark_rapids_tpu.ops import join as J
    keys = 2 * rng.choice(key_span // 2, build_rows, replace=False) \
        .astype(np.int64)
    live = np.arange(capacity) < build_rows

    def col(t, values):
        data = np.zeros(capacity, t.np_dtype)
        data[:build_rows] = values
        return DeviceColumn(t, jnp.asarray(data), jnp.asarray(live))
    build = DeviceBatch(
        (col(dt.INT64, keys),
         col(dt.DATE, rng.integers(8000, 9200, build_rows)),
         col(dt.INT32, 0), col(dt.INT64, keys // 4), col(dt.INT64, keys % 97)),
        jnp.asarray(build_rows, jnp.int32))
    built = J.build_side(build, [0])
    J._maybe_build_dense(built, built.batch, built.key_ordinals)
    assert built.table is not None, "the build side took no dense table"
    return built, keys


def probe_lategather(jax, small: bool) -> None:
    import jax.numpy as jnp
    import numpy as np
    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.columnar.batch import (
        PROBE_SHRINK_RATIO, DeviceBatch, DeviceColumn, bucket_capacity,
        shrink_to_capacity)
    from spark_rapids_tpu.columnar.rowmove import pack_batch
    from spark_rapids_tpu.exprs.base import BoundReference as Ref
    from spark_rapids_tpu.ops import InMemorySourceExec
    from spark_rapids_tpu.ops import join as J
    n = 3 if small else 10
    shapes = ((1 << 10, 1_400, 1 << 11, 15_000),
              (3 << 8, 140, 3 << 8, 6_000)) if small else (
        (1 << 20, 1_460_000, 1 << 21, 15_000_000),
        (3 << 18, 147_000, 3 << 18, 6_000_000))
    op = J.BroadcastHashJoinExec(
        InMemorySourceExec((("l_orderkey", dt.INT64), ("price", dt.FLOAT64),
                            ("disc", dt.FLOAT64)), [[]]),
        InMemorySourceExec((("o_orderkey", dt.INT64), ("o_date", dt.DATE),
                            ("o_prio", dt.INT32), ("a", dt.INT64),
                            ("b", dt.INT64)), [[]]),
        [Ref(0, dt.INT64)], [Ref(0, dt.INT64)], "inner")
    dense_fn = op._dense_jit_fn()
    lookup_fn, emit_fn = J._late_jit_fns()

    def steady(fn):
        jax.block_until_ready(fn())
        return ms(timed(fn, n))["median"]

    for rows, build_rows, build_cap, key_span in shapes:
        rng = np.random.default_rng(rows)
        built, keys = q3_like_build(build_rows, build_cap, key_span, rng)
        dense = lambda b: dense_fn(built, b, probe_keys=(0,),  # noqa: E731
                                   build_is_right=True)
        lookup = lambda b: lookup_fn(built, b, probe_keys=(0,))  # noqa: E731
        slabs = {k: list(v.shape) for k, v in pack_batch(built.batch).items()}
        for pct in (0.5, 10, 40, 60, 100):
            hit = rng.random(rows) * 100 < pct
            # a miss is an odd key: in the table's range, held by no order
            k = np.where(hit, rng.choice(keys, rows),
                         2 * rng.integers(0, key_span // 2, rows) + 1)
            ones = jnp.ones((rows,), jnp.bool_)
            pbatch = DeviceBatch(
                (DeviceColumn(dt.INT64, jnp.asarray(k), ones),
                 DeviceColumn(dt.FLOAT64, jnp.asarray(np.round(
                     rng.uniform(900, 105_000, rows), 2)), ones),
                 DeviceColumn(dt.FLOAT64, jnp.asarray(
                     rng.integers(0, 11, rows) / 100.0), ones)),
                jnp.asarray(rows, jnp.int32))
            pos, found, count = lookup(pbatch)
            matched = int(jax.device_get(count))
            bucket = bucket_capacity(max(matched, 1))
            compacts = bucket * PROBE_SHRINK_RATIO <= rows
            emit_at = lambda cap: emit_fn(  # noqa: E731
                built.batch, pbatch, pos, found, count, out_cap=cap,
                build_is_right=True)
            eager = (lambda: shrink_to_capacity(dense(pbatch), bucket)) \
                if compacts else (lambda: dense(pbatch))
            late = lambda cap: emit_fn(  # noqa: E731
                built.batch, pbatch, *lookup(pbatch), out_cap=cap,
                build_is_right=True)
            want = eager()
            got = late(bucket if compacts else None)
            same = all(bool((a == b).all()) for a, b in zip(
                jax.tree_util.tree_leaves(got),
                jax.tree_util.tree_leaves(want)))
            assert same, f"late and eager differ at {rows} rows, {pct} %"
            facts = {"dense_step_ms": steady(lambda: dense(pbatch)),
                     "lookup_ms": steady(lambda: lookup(pbatch)),
                     "emit_at_capacity_ms": steady(lambda: emit_at(None)),
                     "eager_whole_ms": steady(eager),
                     "late_at_capacity_ms": steady(lambda: late(None))}
            if bucket < rows:
                out = dense(pbatch)
                facts.update(
                    shrink_ms=steady(
                        lambda: shrink_to_capacity(out, bucket)),
                    emit_at_bucket_ms=steady(lambda: emit_at(bucket)),
                    late_at_bucket_ms=steady(lambda: late(bucket)))
                del out
            emit("lategather", rows=rows, build_capacity=build_cap,
                 build_keys=build_rows, slabs=slabs, match_pct=pct,
                 matched=matched, bucket=bucket,
                 shipped="late_at_bucket" if compacts
                 else "late_at_capacity", same_output=same, **facts)
        del built


PROBES = {"sync": probe_sync, "link": probe_link, "upload": probe_upload,
          "prefix": probe_prefix, "slots": probe_slots,
          "rowmove": probe_rowmove, "shrinkrule": probe_shrinkrule,
          "sortpath": probe_sortpath, "sortgrid": probe_sortgrid,
          "coalescealone": probe_coalescealone,
          "lategather": probe_lategather}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("sections", nargs="*", metavar="section",
                    help=f"which of {', '.join(SECTIONS)} to run "
                         f"(default: all)")
    ap.add_argument("--rows", type=lambda v: tuple(map(int, v.split(","))),
                    default=None,
                    help="sortpath: the sizes to read, of "
                         f"{','.join(map(str, SORTPATH_ROWS))}; "
                         "coalescealone: the members' capacities "
                         f"(default {','.join(map(str, COALESCE_ROWS))})")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="control flow at a tiny size on the CPU backend")
    args = ap.parse_args(argv)
    unknown = set(args.sections) - set(SECTIONS)
    if unknown:
        ap.error(f"unknown section(s) {sorted(unknown)}")
    OPTIONS["rows"] = args.rows
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        LABEL["cpu_rehearsal"] = True
    import jax
    d = jax.devices()[0]
    want = "cpu" if args.cpu_rehearsal else "tpu"
    if d.platform != want:
        print(f"chip_probe: need a {want} backend, JAX found "
              f"{d.platform} ({d.device_kind})", file=sys.stderr)
        return 2
    import spark_rapids_tpu  # noqa: F401  (x64, compile cache directory)
    emit("device", platform=d.platform, kind=d.device_kind,
         count=len(jax.devices()), host_cpus=os.cpu_count(),
         jax=jax.__version__)
    for name in args.sections or SECTIONS:
        PROBES[name](jax, args.cpu_rehearsal)
    return 0


if __name__ == "__main__":
    sys.exit(main())
