"""Native Pallas kernel layer (ISSUE 11 tentpole): bit-identity parity,
gating, and fallback chaos.

Every native kernel (ops/native.py) must be BIT-IDENTICAL to its
jax.numpy twin across the dtype ladder — including -0.0/NaN float edge
cases — individually gateable, and `native.enabled=false` must restore
the jax.numpy code paths byte-for-byte. On this CPU backend the kernels
run through the Pallas interpreter, reachable only inside
``native.forced``. The Mosaic lowering for the chip is a separate matter:
tests/test_chip_compile.py holds it for every kernel that is default-on
(none today — ROADMAP A5).
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import jax.ops

from spark_rapids_tpu.api.dataframe import TpuSession
from spark_rapids_tpu.ops import kernel_cache as kc
from spark_rapids_tpu.ops import kernels, native


def _bits(a: np.ndarray) -> np.ndarray:
    """Bit view for exact comparison (distinguishes -0.0 and NaN
    payloads)."""
    a = np.asarray(a)
    return a if a.dtype == np.bool_ else a.view(np.uint8)


def assert_bit_equal(twin, got, msg=None):
    t, g = np.asarray(twin), np.asarray(got)
    assert t.dtype == g.dtype and t.shape == g.shape, (msg, t.dtype,
                                                      g.dtype)
    assert np.array_equal(_bits(t), _bits(g)), (msg, t[:8], g[:8])


# ---------------------------------------------------------------------------
# Kernel 1: stable radix rank
# ---------------------------------------------------------------------------

class TestRadixSort:
    @pytest.mark.parametrize("cap", [
        8, 12, 96,
        pytest.param(768, marks=pytest.mark.slow),
        pytest.param(1024, marks=pytest.mark.slow)])
    def test_stable_argsort_u32_bit_identical(self, cap):
        rng = np.random.default_rng(cap)
        with native.forced():
            for hi in (8, 2 ** 32):     # heavy ties and full range
                keys = jnp.asarray(rng.integers(0, hi, cap,
                                                dtype=np.uint32))
                # argsort returns int64 under x64; both are pure gather
                # indices at the call site, so compare values as i32.
                assert_bit_equal(
                    jnp.argsort(keys, stable=True).astype(jnp.int32),
                    native.stable_argsort_u32(keys),
                    f"cap={cap} hi={hi}")

    def test_radix_perm_multi_pass_parity(self):
        """The real call site: _radix_perm over several word passes
        (the multi-key LSD sort) native vs fallback."""
        rng = np.random.default_rng(3)
        cap = 384
        passes = [jnp.asarray(rng.integers(0, 9, cap, dtype=np.uint32))
                  for _ in range(3)]
        with native.forced():
            on = kernels._radix_perm(passes, cap)
        with native.forced(master=False):
            off = kernels._radix_perm(passes, cap)
        assert_bit_equal(off, on)

    def test_unstable_first_pass_keeps_twin(self):
        """The relaxed-tie unstable first pass has no unique answer, so
        the native path must not engage for it (later passes still
        may)."""
        rng = np.random.default_rng(4)
        cap = 96
        passes = [jnp.asarray(rng.integers(0, 5, cap, dtype=np.uint32))]
        native.reset_counters()
        with native.forced():
            kernels._radix_perm(passes, cap, unstable_first=True)
            assert native.counters().get("nativeRadixSortTraces", 0) == 0
            kernels._radix_perm(passes, cap, unstable_first=False)
            assert native.counters().get("nativeRadixSortTraces", 0) == 1

    def test_float_domain_passes_keep_twin(self):
        """TPU f64 sort keys stay in the float domain — only u32 word
        passes go native; the mixed-pass sort still matches."""
        rng = np.random.default_rng(5)
        cap = 24
        passes = [jnp.asarray(rng.integers(0, 3, cap, dtype=np.uint32)),
                  jnp.asarray(rng.normal(size=cap)),
                  jnp.asarray(rng.integers(0, 3, cap, dtype=np.uint32))]
        with native.forced():
            on = kernels._radix_perm(passes, cap)
        with native.forced(master=False):
            off = kernels._radix_perm(passes, cap)
        assert_bit_equal(off, on)


# ---------------------------------------------------------------------------
# Kernel 2: join probe
# ---------------------------------------------------------------------------

class TestJoinProbe:
    @pytest.mark.parametrize("cap_b,cap_p", [
        (8, 8), (16, 24), (96, 12),
        pytest.param(512, 768, marks=pytest.mark.slow)])
    def test_searchsorted_pair_bit_identical(self, cap_b, cap_p):
        rng = np.random.default_rng(cap_b + cap_p)
        b = np.sort(rng.integers(0, 2 ** 63, cap_b).astype(np.uint64))
        # The sort sentinel run every real build side carries.
        b[-2:] = np.uint64(0xFFFFFFFFFFFFFFFF)
        q = rng.choice(np.concatenate(
            [b, rng.integers(0, 2 ** 63, cap_p).astype(np.uint64)]),
            cap_p)
        bj, qj = jnp.asarray(b), jnp.asarray(q)
        with native.forced():
            lo_n, hi_n = native.searchsorted_u64_pair(bj, qj)
        assert_bit_equal(
            jnp.searchsorted(bj, qj, side="left").astype(jnp.int32), lo_n)
        assert_bit_equal(
            jnp.searchsorted(bj, qj, side="right").astype(jnp.int32),
            hi_n)

    def test_probe_ranges_end_to_end(self):
        """probe_ranges through real built sides (duplicate + null keys)
        native vs fallback."""
        from spark_rapids_tpu.columnar import dtypes as dt
        from spark_rapids_tpu.columnar.host import HostBatch
        from spark_rapids_tpu.columnar.wire import upload
        from spark_rapids_tpu.ops.join import build_side, probe_ranges
        rng = np.random.default_rng(11)
        build = HostBatch.from_pydict(
            [("k", dt.INT64)],
            {"k": [int(x) for x in rng.integers(0, 6, 40)]})
        pvals = [int(x) for x in rng.integers(0, 9, 64)]
        pvals[3] = None
        probe = HostBatch.from_pydict([("k", dt.INT64)], {"k": pvals})
        db, dp = upload(build), upload(probe)

        def run():
            built = build_side(db, [0])
            lo, counts, plive = probe_ranges(built, dp, [0])
            return (np.asarray(lo), np.asarray(counts),
                    np.asarray(plive))
        with native.forced():
            on = run()
        with native.forced(master=False):
            off = run()
        for a, b_ in zip(off, on):
            assert_bit_equal(a, b_)


# ---------------------------------------------------------------------------
# Kernel 3: RLE decode
# ---------------------------------------------------------------------------

RLE_POOLS = [
    ("int8", np.int8, [1, 2, -3]),
    ("int16", np.int16, [100, -2000]),
    ("int32", np.int32, [7, -9, 2 ** 30]),
    ("int64", np.int64, [2 ** 40, -5, 0]),
    ("float32", np.float32, [1.5, -0.0, np.nan, 0.0]),
    ("float64", np.float64, [np.nan, -0.0, 0.0, 3.25, np.inf]),
]


class TestRleDecode:
    @pytest.mark.parametrize("name,dtype,pool", RLE_POOLS,
                             ids=[p[0] for p in RLE_POOLS])
    def test_decode_bit_identical(self, name, dtype, pool):
        """Run tables built exactly like wire._try_rle (bit-view run
        detection), decoded native vs the searchsorted+gather twin —
        including -0.0 vs 0.0 and NaN-payload runs."""
        from spark_rapids_tpu.columnar.batch import bucket_capacity
        rng = np.random.default_rng(hash(name) % 2 ** 31)
        n = 50
        cap = bucket_capacity(n)
        v = np.asarray([pool[i] for i in
                        np.repeat(rng.choice(len(pool), 5), 10)], dtype)
        bits = v.view(np.int32 if dtype == np.float32 else np.int64) \
            if dtype in (np.float32, np.float64) else v
        st = np.empty(n, bool)
        st[0] = True
        np.not_equal(bits[1:], bits[:-1], out=st[1:])
        runs = int(st.sum())
        run_cap = bucket_capacity(max(runs, 1))
        sidx = np.flatnonzero(st)
        run_vals = np.zeros(run_cap, dtype)
        run_vals[:runs] = v[sidx]
        ends = np.full(run_cap, cap, np.int32)
        if runs > 1:
            ends[:runs - 1] = sidx[1:]
        ends[runs - 1] = n
        rv, re_ = jnp.asarray(run_vals), jnp.asarray(ends)
        rows = jnp.arange(cap, dtype=jnp.int32)
        ridx = jnp.searchsorted(re_, rows, side="right").astype(jnp.int32)
        twin = jnp.take(rv, ridx, mode="clip")
        twin = jnp.where(rows < n, twin, jnp.zeros_like(twin))
        with native.forced():
            got = native.rle_decode(rv, re_, cap,
                                    jnp.asarray(n, jnp.int32))
        assert_bit_equal(twin, got, name)

    def test_upload_path_engages_and_matches(self):
        """A sorted low-cardinality column through the REAL wire v2
        upload funnel: native decode on vs off, bit-identical device
        batches."""
        from spark_rapids_tpu.columnar import dtypes as dt
        from spark_rapids_tpu.columnar.host import HostBatch
        from spark_rapids_tpu.columnar import wire
        vals = [float(x) for x in np.repeat([1.5, 2.5, 3.5], 40)]
        hb = HostBatch.from_pydict([("v", dt.FLOAT64)], {"v": vals})

        def run():
            return np.asarray(wire.upload(hb).columns[0].data)
        native.reset_counters()
        with native.forced():
            on = run()
            assert native.counters().get("nativeRleDecodeTraces", 0) >= 1
        with native.forced(master=False):
            off = run()
        assert_bit_equal(off, on)

    def test_run_cap_bound_falls_back(self):
        """Run tables past native.rleDecode.maxRuns keep the twin."""
        from spark_rapids_tpu.config import TpuConf
        native.maybe_configure(TpuConf(
            {"spark.rapids.sql.native.rleDecode.maxRuns": 4}))
        try:
            assert native.rle_max_runs() == 4
        finally:
            native.maybe_configure(TpuConf())
        assert native.rle_max_runs() > 4


# ---------------------------------------------------------------------------
# Kernel 4: sorted-segment reduction
# ---------------------------------------------------------------------------

SEG_DTYPES = [np.bool_, np.int8, np.int16, np.int32, np.int64,
              np.float32, np.float64]


class TestSegmentReduce:
    @pytest.mark.parametrize("dtype", SEG_DTYPES,
                             ids=[np.dtype(d).name for d in SEG_DTYPES])
    @pytest.mark.parametrize("cap", [
        24,
        pytest.param(8, marks=pytest.mark.slow),
        pytest.param(768, marks=pytest.mark.slow)])
    def test_raw_reduce_bit_identical(self, dtype, cap):
        rng = np.random.default_rng(cap)
        gid = np.sort(rng.integers(0, max(cap // 3, 1), cap)) \
            .astype(np.int32)
        if dtype == np.bool_:
            vals = rng.integers(0, 2, cap).astype(np.bool_)
        elif np.issubdtype(dtype, np.integer):
            info = np.iinfo(dtype)
            vals = rng.integers(info.min, info.max, cap).astype(dtype)
        else:
            vals = rng.choice(np.asarray(
                [1.5, -0.0, 0.0, np.inf, -np.inf, 3.7], dtype), cap)
        vj, gj = jnp.asarray(vals), jnp.asarray(gid)
        with native.forced():
            if dtype != np.bool_:
                got = native.segment_sum_sorted(vj, gj, cap)
                if np.issubdtype(dtype, np.integer):
                    assert got is not None, "int sums must be native"
                    assert_bit_equal(jax.ops.segment_sum(
                        vj, gj, num_segments=cap), got, "sum")
                else:
                    assert got is None, \
                        "float sums must keep the twin (order changes " \
                        "rounding)"
            for kind, red in (("min", jax.ops.segment_min),
                              ("max", jax.ops.segment_max)):
                got = native.segment_minmax_sorted(vj, gj, cap, kind)
                assert got is not None
                assert_bit_equal(red(vj, gj, num_segments=cap), got, kind)

    @pytest.mark.parametrize("kind", ["sum", "min", "max"])
    def test_segment_reduce_null_discipline(self, kind):
        """The full kernels.segment_reduce wrapper (Spark null/NaN
        discipline) native vs fallback, with NaN + -0.0 + nulls."""
        cap = 48
        rng = np.random.default_rng(9)
        vals = rng.choice(np.asarray(
            [1.5, -0.0, 0.0, np.nan, np.inf, -2.25]), cap)
        validity = rng.integers(0, 4, cap) > 0
        gid = np.sort(rng.integers(0, 12, cap)).astype(np.int32)
        args = (jnp.asarray(vals), jnp.asarray(validity),
                jnp.asarray(gid), cap, kind)
        with native.forced():
            agg_on, cnt_on = kernels.segment_reduce(*args)
        with native.forced(master=False):
            agg_off, cnt_off = kernels.segment_reduce(*args)
        assert_bit_equal(agg_off, agg_on, kind)
        assert_bit_equal(cnt_off, cnt_on, "counts")

    def test_int_sum_wraparound_parity(self):
        """int32 overflow wraps identically (two's complement)."""
        cap = 12
        vals = jnp.asarray(np.full(cap, 2 ** 30, np.int32))
        gid = jnp.zeros(cap, jnp.int32)
        with native.forced():
            got = native.segment_sum_sorted(vals, gid, cap)
        assert_bit_equal(jax.ops.segment_sum(vals, gid, num_segments=cap),
                         got)


# ---------------------------------------------------------------------------
# Gating, cache coherence, and the kill-switch contract
# ---------------------------------------------------------------------------

class TestGating:
    def test_cpu_defaults_to_fallback(self):
        """Outside forced() a CPU backend never engages native kernels
        and never reaches the interpreter — whatever the gates say."""
        if jax.default_backend() == "tpu":
            pytest.skip("TPU backend: native is genuinely available")
        from spark_rapids_tpu.config import TpuConf
        native.maybe_configure(TpuConf(
            {"spark.rapids.sql.native.radixSort.enabled": True}))
        try:
            assert native.gate_enabled("radixSort")
            assert not native.available()
            assert not native._interpret()
            assert not native.kernel_enabled("radixSort")
            assert native.fingerprint() == ()
        finally:
            native.maybe_configure(TpuConf())

    def test_every_kernel_gate_defaults_off(self):
        """PR 21: Mosaic refuses all four kernels for v5e as written, so
        no gate is default-on (a gate flips together with its compile
        case in tests/test_chip_compile.py); forced() still turns them
        all on for the parity suite."""
        from spark_rapids_tpu.config import TpuConf
        native.maybe_configure(TpuConf())
        assert native.master_enabled()
        assert not any(native.gate_enabled(k) for k in native.KERNELS)
        with native.forced():
            assert all(native.kernel_enabled(k) for k in native.KERNELS)

    def test_conf_keys_gate_individually(self):
        from spark_rapids_tpu.config import TpuConf
        native.maybe_configure(TpuConf(
            {"spark.rapids.sql.native.radixSort.enabled": True}))
        try:
            assert native.gate_enabled("radixSort")
            assert not native.gate_enabled("joinProbe")
        finally:
            native.maybe_configure(TpuConf())

    def test_master_kill_switch(self):
        from spark_rapids_tpu.config import TpuConf
        native.maybe_configure(TpuConf(
            {"spark.rapids.sql.native.enabled": False,
             "spark.rapids.sql.native.radixSort.enabled": True}))
        try:
            assert not native.master_enabled()
            assert not any(native.kernel_enabled(k)
                           for k in native.KERNELS)
            assert native.fingerprint() == ()
        finally:
            native.maybe_configure(TpuConf())

    def test_env_kill_switch(self, monkeypatch):
        monkeypatch.setenv("SRT_NATIVE", "0")
        assert not native.master_enabled()
        assert native.fingerprint() == ()

    def test_interpreter_only_under_forced(self, monkeypatch):
        """The Pallas interpreter is reachable through forced() and
        nothing else: no env var turns it on."""
        if jax.default_backend() == "tpu":
            pytest.skip("TPU backend compiles through Mosaic")
        monkeypatch.setenv("SRT_NATIVE_INTERPRET", "1")  # retired knob
        assert not native._interpret()
        with native.forced():
            assert native._interpret()
        assert not native._interpret()

    def test_fingerprint_keys_kernel_cache(self):
        """Toggling a native gate must MISS the kernel cache, never
        serve a program traced under the other setting."""
        calls = []

        def builder():
            calls.append(1)
            return lambda: None
        key = ("native-gate-test", id(calls))
        with native.forced():
            kc.lookup("t", key, builder)
        with native.forced(master=False):
            kc.lookup("t", key, builder)
        assert len(calls) == 2, "same key served across a gate toggle"


# ---------------------------------------------------------------------------
# End-to-end: the 11-query sweep + chaos (fallback matrix green on CPU)
# ---------------------------------------------------------------------------

def _session(native_on: bool, chaos: str = "") -> TpuSession:
    s = TpuSession()
    s.set("spark.rapids.sql.variableFloatAgg.enabled", True)
    s.set("spark.rapids.sql.native.enabled", native_on)
    # Cold scans so the upload/decode funnel (the RLE kernel's call
    # site) actually runs.
    s.set("spark.rapids.sql.format.scanCache.maxBytes", 0)
    if chaos:
        s.set("spark.rapids.sql.test.faults", chaos)
        s.set("spark.rapids.sql.test.faults.seed", 7)
        s.set("spark.rapids.sql.retry.backoffMs", 1)
    return s


def _tpch_dir(tmp_path_factory):
    from spark_rapids_tpu.benchmarks import tpch
    d = getattr(_tpch_dir, "_dir", None)
    if d is None:
        d = str(tmp_path_factory.mktemp("native_tpch"))
        tpch.generate(d, scale=0.003, files_per_table=3, seed=7)
        _tpch_dir._dir = d
    return d


def _suites_dir(tmp_path_factory):
    from spark_rapids_tpu.benchmarks import suites
    d = getattr(_suites_dir, "_dir", None)
    if d is None:
        d = str(tmp_path_factory.mktemp("native_suites"))
        suites.generate(d, scale=0.01, files_per_table=2)
        _suites_dir._dir = d
    return d


_TPCH = ["q1",
         pytest.param("q6", marks=pytest.mark.slow),
         pytest.param("q3", marks=pytest.mark.slow),
         pytest.param("q5", marks=pytest.mark.slow),
         pytest.param("q12", marks=pytest.mark.slow),
         pytest.param("q14", marks=pytest.mark.slow)]
_SUITES = [pytest.param("repart", marks=pytest.mark.slow),
           pytest.param("q67", marks=pytest.mark.slow),
           pytest.param("xbb_q5", marks=pytest.mark.slow),
           pytest.param("ds_q3", marks=pytest.mark.slow),
           pytest.param("xbb_q12", marks=pytest.mark.slow)]


class TestEndToEnd:
    @pytest.mark.parametrize("qname", _TPCH)
    def test_tpch_native_on_off_bit_identical(self, qname,
                                              tmp_path_factory):
        from spark_rapids_tpu.benchmarks import tpch
        d = _tpch_dir(tmp_path_factory)
        native.reset_counters()
        with native.forced():
            on = tpch.QUERIES[qname](_session(True), d).collect()
            if qname == "q1":
                # The sweep must not pass vacuously: q1's grouping
                # sorts trace the radix kernel at minimum. (The native
                # fingerprint is part of every kernel-cache key, so the
                # first native-on q1 in a process always traces fresh —
                # non-native runs of q1 elsewhere in the suite cannot
                # have seeded these entries.)
                assert native.counters().get(
                    "nativeRadixSortTraces", 0) > 0
        off = tpch.QUERIES[qname](_session(False), d).collect()
        assert on == off

    @pytest.mark.parametrize("qname", _SUITES)
    def test_suites_native_on_off_bit_identical(self, qname,
                                                tmp_path_factory):
        from spark_rapids_tpu.benchmarks import suites
        d = _suites_dir(tmp_path_factory)
        with native.forced():
            on = suites.QUERIES[qname](_session(True), d).collect()
        off = suites.QUERIES[qname](_session(False), d).collect()
        assert on == off

    def test_chaos_native_fallback_matrix_green(self, tmp_path_factory):
        """Seeded oom+transient schedule under native kernels: the
        recovery ladder runs THROUGH the native dispatch funnel and the
        result stays bit-identical to the clean native-off run."""
        from spark_rapids_tpu.benchmarks import tpch
        d = _tpch_dir(tmp_path_factory)
        clean = tpch.QUERIES["q1"](_session(False), d).collect()
        chaos = "oom@kernel:1,transient@upload:1"
        with native.forced():
            df = tpch.QUERIES["q1"](_session(True, chaos), d)
            got = df.collect()
            m = df.metrics().get("Recovery@query", {})
            assert m.get("faultsInjected", 0) >= 1, m
        assert got == clean
