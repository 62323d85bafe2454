"""Microbenchmark engine kernels on the real device: where do q1's 14s go?"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp


def _sync(out):
    jax.block_until_ready(out)


def timeit(name, fn, *args, n=3):
    # First iteration is compile-inclusive (trace + XLA compile + run);
    # steady-state is the post-warmup min — report both so compile cost
    # and hot-path cost read separately (the kernel-cache story: a second
    # query pays only the steady-state number).
    t0 = time.perf_counter()
    out = fn(*args)
    _sync(out)
    first = time.perf_counter() - t0
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        out = fn(*args)
        _sync(out)
        ts.append(time.perf_counter() - t0)
    steady = min(ts)
    print(f"{name}: first={first*1000:.1f} ms (compile-inclusive) "
          f"steady={steady*1000:.1f} ms")
    return out


def main():
    import spark_rapids_tpu  # noqa: F401  (x64 config)
    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.columnar.batch import DeviceBatch, DeviceColumn
    from spark_rapids_tpu.ops import kernels

    cap = 1 << 20
    rng = np.random.default_rng(0)

    # Columns shaped like q1's lineitem batch
    f64 = lambda: jnp.asarray(rng.uniform(0, 1e5, cap))
    i32 = lambda: jnp.asarray(rng.integers(8000, 11000, cap), jnp.int32)
    s1 = jnp.asarray(rng.integers(65, 68, (cap, 8)), jnp.uint8)
    ones = jnp.ones((cap,), jnp.bool_)
    lens = jnp.full((cap,), 1, jnp.int32)

    nrows = jnp.asarray(cap - 7, jnp.int32)

    cols = [
        DeviceColumn(dt.STRING, s1, ones, lens),          # returnflag
        DeviceColumn(dt.STRING, s1, ones, lens),          # linestatus
        DeviceColumn(dt.FLOAT64, f64(), ones),            # quantity
        DeviceColumn(dt.FLOAT64, f64(), ones),            # extendedprice
        DeviceColumn(dt.FLOAT64, f64(), ones),            # discount
        DeviceColumn(dt.FLOAT64, f64(), ones),            # tax
        DeviceColumn(dt.DATE, i32(), ones),               # shipdate
    ]
    batch = DeviceBatch(tuple(cols), nrows)
    jax.block_until_ready(batch)

    # 1. fingerprint
    fp = jax.jit(lambda b: kernels.key_fingerprint(
        [b.columns[0], b.columns[1]], cap))
    timeit("fingerprint 2 str cols", fp, batch)

    # 2. single stable argsort u32
    keys = jnp.asarray(rng.integers(0, 2**32, cap, dtype=np.uint32))
    timeit("argsort u32 1M", jax.jit(lambda k: jnp.argsort(k, stable=True)),
           keys)

    # 3. group_ids (3 argsorts via fingerprint)
    def _gi(b):
        g_ = kernels.group_ids(b, [0, 1])
        return (g_.perm, g_.group_of_sorted, g_.num_groups, g_.group_leader)
    gi = jax.jit(_gi)
    gt = timeit("group_ids (2 str keys)", gi, batch)
    import types
    g = types.SimpleNamespace(perm=gt[0], group_of_sorted=gt[1],
                              num_groups=gt[2], group_leader=gt[3])

    # 4. segment_sum f64 1M
    gid = g.group_of_sorted
    vals = batch.columns[2].data
    timeit("segment_sum f64 1M->1M segs",
           jax.jit(lambda v, g_: jax.ops.segment_sum(v, g_,
                                                     num_segments=cap)),
           vals, gid)
    vals32 = vals.astype(jnp.float32)
    timeit("segment_sum f32 1M",
           jax.jit(lambda v, g_: jax.ops.segment_sum(v, g_,
                                                     num_segments=cap)),
           vals32, gid)

    # 5. filter compact on the 7-col batch
    keep = batch.columns[6].data <= 10000
    timeit("compact 7col 1M",
           jax.jit(lambda b, k: b.compact(k)), batch, keep)

    # 6. f64 multiply + sum (q1 projections)
    timeit("f64 mul x3 1M", jax.jit(
        lambda a, b, c: a * (1.0 - b) * (1.0 + c)),
        vals, batch.columns[4].data, batch.columns[5].data)

    # 7. gather 7 cols by perm
    perm = jnp.asarray(rng.permutation(cap), jnp.int32)
    timeit("gather 7col 1M", jax.jit(
        lambda b, p: b.gather(p, b.num_rows)), batch, perm)

    # 8. f64 argsort (join/sort path)
    timeit("argsort f64 1M", jax.jit(
        lambda v: jnp.argsort(v, stable=True)), vals)

    # 9. searchsorted 1M into 1M (join probe)
    sk = jnp.sort(keys)
    timeit("searchsorted 1M/1M", jax.jit(
        lambda s, q: jnp.searchsorted(s, q)), sk, keys)

    # 10. full agg update_batch (q1 partial agg analog)
    from spark_rapids_tpu.ops.aggregate import (
        AggSpec, Average, Count, HashAggregateExec, Sum)
    from spark_rapids_tpu.exprs.base import BoundReference as BR
    agg = HashAggregateExec.__new__(HashAggregateExec)
    agg.group_names = ("rf", "ls")
    agg.group_exprs = [BR(0, dt.STRING), BR(1, dt.STRING)]
    agg.aggs = [AggSpec("s1", Sum(BR(2, dt.FLOAT64))),
                AggSpec("s2", Sum(BR(3, dt.FLOAT64))),
                AggSpec("a1", Average(BR(2, dt.FLOAT64))),
                AggSpec("c", Count(BR(2, dt.FLOAT64)))]
    agg.mode = "partial"
    upd = jax.jit(agg._update_batch)
    timeit("q1-like update_batch 1M", upd, batch,
           jnp.asarray(0, jnp.int64))

    from spark_rapids_tpu.ops import kernel_cache as kc
    print("kernel cache:", kc.cache().stats())

    native_bench()
    trace_overhead()
    telemetry_overhead()


def native_bench():
    """Native Pallas kernels vs their jax.numpy twins on a TPU backend
    (ROADMAP A5). A kernel Mosaic refuses raises here at trace time —
    as of PR 21 that is all four; there is no interpreter timing, a
    CPU number says nothing about the chip."""
    import jax.ops
    from spark_rapids_tpu.ops import kernel_cache as kc
    from spark_rapids_tpu.ops import native

    if not native.available():
        print("native kernels: not on a TPU backend, nothing to time")
        return
    cap = 1 << 20
    rng = np.random.default_rng(7)
    print(f"native kernels vs jax.numpy twins (cap={cap}, mosaic):")

    def duel(name, twin_fn, native_fn, *args):
        # Both sides compile through the kernel-cache interface, so the
        # bench measures exactly what serving traffic dispatches.
        twin = kc.lookup(f"microbench-{name}", ("twin", cap),
                         lambda: jax.jit(twin_fn))
        nat = kc.lookup(f"microbench-{name}", ("native", cap),
                        lambda: jax.jit(native_fn))
        timeit(f"  {name} twin", twin, *args)
        timeit(f"  {name} native", nat, *args)

    # 1. radix rank pass (one stable u32 argsort)
    keys = jnp.asarray(rng.integers(0, 2 ** 32, cap, dtype=np.uint32))
    duel("radix-pass",
         lambda k: jnp.argsort(k, stable=True),
         native.stable_argsort_u32, keys)

    # 2. join probe (double binary search over sorted u64 fingerprints)
    fp = jnp.sort(jnp.asarray(rng.integers(0, 2 ** 63, cap)
                              .astype(np.uint64)))
    q = jnp.asarray(rng.integers(0, 2 ** 63, cap).astype(np.uint64))
    duel("join-probe",
         lambda b, x: (jnp.searchsorted(b, x, side="left"),
                       jnp.searchsorted(b, x, side="right")),
         native.searchsorted_u64_pair, fp, q)

    # 3. RLE decode (sorted low-cardinality column)
    runs = 256
    run_vals = jnp.asarray(rng.normal(size=runs))
    ends = jnp.asarray(np.sort(rng.choice(
        np.arange(1, cap), runs - 1, replace=False)).astype(np.int32))
    run_ends = jnp.concatenate([ends, jnp.asarray([cap], jnp.int32)])
    nrows = jnp.asarray(cap, jnp.int32)

    def rle_twin(rv, re_, n):
        rows = jnp.arange(cap, dtype=jnp.int32)
        ridx = jnp.searchsorted(re_, rows, side="right").astype(jnp.int32)
        data = jnp.take(rv, ridx, mode="clip")
        return jnp.where(rows < n, data, jnp.zeros_like(data))

    duel("rle-decode", rle_twin,
         lambda rv, re_, n: native.rle_decode(rv, re_, cap, n),
         run_vals, run_ends, nrows)

    # 4. segment reduce (sorted gids, int64 sum + f64 min)
    gid = jnp.asarray(np.sort(rng.integers(0, cap // 4, cap))
                      .astype(np.int32))
    vals = jnp.asarray(rng.integers(-1000, 1000, cap).astype(np.int64))
    duel("segment-sum-i64",
         lambda v, g: jax.ops.segment_sum(v, g, num_segments=cap),
         lambda v, g: native.segment_sum_sorted(v, g, cap), vals, gid)
    # f32 so the duel also runs on a real TPU (f64 min/max falls back
    # there — the emulated f64 cannot bitcast into the total-order
    # domain).
    fvals = jnp.asarray(rng.normal(size=cap).astype(np.float32))
    duel("segment-min-f32",
         lambda v, g: jax.ops.segment_min(v, g, num_segments=cap),
         lambda v, g: native.segment_minmax_sorted(v, g, cap, "min"),
         fvals, gid)
    print("native counters:", native.counters())


def trace_overhead(calls: int = 200_000, budget_ns: float = 3000.0):
    """Bound the flight recorder's DISABLED span cost: the no-op path is
    one global load + a shared no-op context manager, so a per-partition
    dispatch wearing a span must cost nanoseconds when tracing is off.
    Prints ns/call for disabled vs enabled and asserts the disabled path
    stays under ``budget_ns`` (generous — real cost is tens of ns; the
    bound only exists to catch an accidental allocation/lock creeping
    into the hot path)."""
    from spark_rapids_tpu import monitoring

    def loop():
        t0 = time.perf_counter_ns()
        for _ in range(calls):
            with monitoring.span("bench", "device-compute"):
                pass
        return (time.perf_counter_ns() - t0) / calls

    monitoring.configure(False)
    disabled = min(loop() for _ in range(3))
    monitoring.configure(True, monitoring.LEVEL_OPERATOR)
    enabled = min(loop() for _ in range(3))
    monitoring.configure(False)
    monitoring.reset()
    print(f"trace span: disabled={disabled:.0f} ns/call "
          f"enabled={enabled:.0f} ns/call")
    assert disabled < budget_ns, \
        f"no-op trace span costs {disabled:.0f} ns/call (> {budget_ns})"


def telemetry_overhead(calls: int = 200_000, budget_ns: float = 3000.0):
    """Bound the metric registry's DISABLED cost: ``inc``/``observe``
    with metrics off is a single module-global load and return, so the
    instrumentation sites (collect funnel, scheduler admit/reject,
    query teardown) must cost nanoseconds in the default-off
    configuration. Same budget philosophy as :func:`trace_overhead` —
    generous vs the tens-of-ns real cost, present to catch a lock or
    allocation creeping ahead of the enabled check."""
    from spark_rapids_tpu.monitoring import telemetry

    def loop():
        t0 = time.perf_counter_ns()
        for _ in range(calls):
            telemetry.inc("srt_bench_counter")
            telemetry.observe("srt_bench_latency_ms", 1.0)
        return (time.perf_counter_ns() - t0) / calls

    telemetry.configure(False)
    disabled = min(loop() for _ in range(3))
    telemetry.configure(True)
    enabled = min(loop() for _ in range(3))
    telemetry.configure(False)
    telemetry.reset()
    print(f"telemetry inc+observe: disabled={disabled:.0f} ns/call "
          f"enabled={enabled:.0f} ns/call")
    assert disabled < budget_ns, \
        f"no-op telemetry costs {disabled:.0f} ns/call (> {budget_ns})"


def host_bench(n: int = 200_000, iters: int = 3):
    """Duel the vectorized host-engine kernels against the per-row
    python loops they replaced (the r06 host path). Each pair computes
    the same result; the loop twin is the removed implementation kept
    here as a benchmark fossil so the speedup stays measurable."""
    import spark_rapids_tpu  # noqa: F401
    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.columnar.host import (
        HostBatch, HostColumn, encode_key, strings_to_matrix)
    from spark_rapids_tpu.ops.sort import SortOrder, host_sort_indices
    from spark_rapids_tpu.exprs.base import BoundReference as Ref

    rng = np.random.default_rng(7)
    keys = rng.integers(0, n // 50, n)
    vals = rng.uniform(0, 1e4, n)
    words = np.array([b"alpha", b"bravo", b"charlie", b"delta", b"echo"],
                     dtype=object)
    svals = words[rng.integers(0, 5, n)]

    def duel(name, vec, loop):
        tv = min(_wall(vec) for _ in range(iters))
        tl = _wall(loop)    # once is enough, it's the slow one
        print(f"host {name}: vectorized={tv*1000:.1f} ms "
              f"loop={tl*1000:.1f} ms speedup={tl/max(tv,1e-9):.1f}x")

    # 1. string column -> byte matrix (scan/shuffle boundary).
    def enc_vec():
        col = HostColumn(dt.STRING, svals.copy(),
                         np.ones(n, np.bool_))
        return strings_to_matrix(col)

    def enc_loop():
        lens = np.zeros(n, np.int32)
        w = max(len(v) for v in svals)
        m = np.zeros((n, w), np.uint8)
        for i, v in enumerate(svals):
            lens[i] = len(v)
            m[i, :len(v)] = np.frombuffer(v, np.uint8)
        return m, lens

    duel("string-encode", enc_vec, enc_loop)

    # 2. order-preserving sort keys: lexsort vs python sorted.
    hb = HostBatch(("k", "v"), [
        HostColumn(dt.INT64, keys.astype(np.int64), np.ones(n, np.bool_)),
        HostColumn(dt.FLOAT64, vals, np.ones(n, np.bool_))])
    orders = [SortOrder(Ref(1, dt.FLOAT64), ascending=False),
              SortOrder(Ref(0, dt.INT64))]

    def sort_vec():
        return host_sort_indices(hb, orders)

    def sort_loop():
        rows = list(zip(vals.tolist(), keys.tolist(), range(n)))
        rows.sort(key=lambda r: (-r[0], r[1]))
        return [r[2] for r in rows]

    duel("sort-keys", sort_vec, sort_loop)

    # 3. grouped sum: encode+lexsort+reduceat vs dict accumulate.
    def agg_vec():
        kc = HostColumn(dt.INT64, keys.astype(np.int64),
                        np.ones(n, np.bool_))
        code = encode_key(kc)
        order = np.argsort(code, kind="stable")
        sc = code[order]
        flags = np.ones(n, np.bool_)
        flags[1:] = sc[1:] != sc[:-1]
        starts = np.flatnonzero(flags)
        return np.add.reduceat(vals[order], starts)

    def agg_loop():
        acc = {}
        for k, v in zip(keys.tolist(), vals.tolist()):
            acc[k] = acc.get(k, 0.0) + v
        return acc

    duel("group-sum", agg_vec, agg_loop)

    # 4. hash-join probe: sorted build + searchsorted vs dict probe.
    bk = np.unique(keys)[: max(1, len(np.unique(keys)) // 2)]

    def join_vec():
        order = np.argsort(bk, kind="stable")
        blo = np.searchsorted(bk[order], keys, "left")
        bhi = np.searchsorted(bk[order], keys, "right")
        return np.flatnonzero(bhi > blo)

    def join_loop():
        bset = set(bk.tolist())
        return [i for i, k in enumerate(keys.tolist()) if k in bset]

    duel("join-probe", join_vec, join_loop)

    # 5. fused filter mask-then-gather vs per-row append.
    def filt_vec():
        keep = vals < 5e3
        return vals[keep], keys[keep]

    def filt_loop():
        ov, ok_ = [], []
        for i in range(n):
            if vals[i] < 5e3:
                ov.append(vals[i])
                ok_.append(keys[i])
        return ov, ok_

    duel("filter-gather", filt_vec, filt_loop)


def _wall(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "host":
        host_bench()
    else:
        main()
