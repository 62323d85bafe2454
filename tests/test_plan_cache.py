"""Parameterized plan cache (ISSUE 10): zero re-plan, zero re-trace
repeated-query serving.

Parity contract: cached-vs-fresh execution is BIT-IDENTICAL across the
11-query bench suite, including rebinding with different literals, with
``planCache.enabled=false`` as the control and armed chaos schedules
proving the bypass. Mechanism contracts: a rebind of the same shape is
a plan-cache hit with ZERO kernel-cache misses (literals travel as
traced runtime inputs, satellite #1), pushed-down scan predicates
resolve against the EXECUTION's binding (row-group skipping can never
reuse the template's first literals), invalidation covers conf and
schema changes, and explain/explain_analyze annotate provenance
(satellite #2).
"""

import pytest

from spark_rapids_tpu.api.dataframe import TpuSession
from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.ops import kernel_cache as kc
from spark_rapids_tpu.plan import plan_cache as pc
from spark_rapids_tpu.plan.logical import agg_sum, col, lit_col


def _session(plan_cache=True, chaos="", **extra):
    s = TpuSession()
    s.set("spark.rapids.sql.variableFloatAgg.enabled", True)
    s.set("spark.rapids.sql.planCache.enabled", plan_cache)
    if chaos:
        s.set("spark.rapids.sql.test.faults", chaos)
        s.set("spark.rapids.sql.test.faults.seed", 7)
    for k, v in extra.items():
        s.set(k, v)
    return s


@pytest.fixture(scope="module")
def tpch_dir(tmp_path_factory):
    from spark_rapids_tpu.benchmarks import tpch
    d = str(tmp_path_factory.mktemp("plan_cache_tpch"))
    tpch.generate(d, scale=0.003, files_per_table=3, seed=7)
    return d


@pytest.fixture(scope="module")
def suites_dir(tmp_path_factory):
    from spark_rapids_tpu.benchmarks import suites
    d = str(tmp_path_factory.mktemp("plan_cache_suites"))
    suites.generate(d, scale=0.01, files_per_table=2)
    return d


def _q6(session, data_dir, lo="1994-01-01", hi="1995-01-01"):
    """Parameterized q6: the date range is the binding."""
    from spark_rapids_tpu.benchmarks import tpch
    li = tpch._read(session, data_dir, "lineitem")
    f = li.filter(
        (col("l_shipdate") >= lit_col(tpch.days(lo)))
        & (col("l_shipdate") < lit_col(tpch.days(hi)))
        & (col("l_discount") >= 0.05) & (col("l_discount") <= 0.07)
        & (col("l_quantity") < 24.0))
    return f.agg(agg_sum(col("l_extendedprice") * col("l_discount"))
                 .alias("revenue"))


# ---------------------------------------------------------------------------
# The serving fast path: hit + bind-only + zero retrace
# ---------------------------------------------------------------------------

def test_rebind_hits_with_zero_kernel_misses(tpch_dir):
    """Satellite #1 acceptance: two different literal bindings of the
    same shape share ONE template and ONE set of compiled kernels —
    the second collect re-traces NOTHING."""
    s = _session()
    _q6(s, tpch_dir).collect()                      # template + compile
    st0 = pc.cache().stats()
    k0 = kc.cache().stats()
    got = _q6(s, tpch_dir, "1995-01-01", "1996-01-01").collect()
    st1 = pc.cache().stats()
    k1 = kc.cache().stats()
    assert st1["hits"] == st0["hits"] + 1, (st0, st1)
    assert k1["misses"] == k0["misses"], \
        f"rebinding re-traced kernels: {k0} -> {k1}"
    # Bit-identical to a fresh, cache-off plan of the same binding.
    control = _q6(_session(plan_cache=False), tpch_dir,
                  "1995-01-01", "1996-01-01").collect()
    assert got == control


def test_same_literals_rebuild_is_a_hit(tpch_dir):
    from spark_rapids_tpu.benchmarks import tpch
    s = _session()
    a = tpch.QUERIES["q1"](s, tpch_dir).collect()
    st0 = pc.cache().stats()
    b = tpch.QUERIES["q1"](s, tpch_dir).collect()
    st1 = pc.cache().stats()
    assert st1["hits"] == st0["hits"] + 1
    assert a == b


def test_limit_values_bind(tpch_dir):
    from spark_rapids_tpu.benchmarks import tpch
    s = _session()
    li = tpch._read(s, tpch_dir, "lineitem")
    base = li.select("l_orderkey", "l_quantity")
    a = base.limit(3).collect()
    st0 = pc.cache().stats()
    b = base.limit(9).collect()
    st1 = pc.cache().stats()
    assert len(a) == 3 and len(b) == 9
    assert st1["hits"] == st0["hits"] + 1, (st0, st1)


def test_pushdown_predicates_resolve_per_binding(tmp_path):
    """THE row-group pruning trap: a template cached with binding A's
    pushed predicates must skip row groups according to binding B's
    literals on the rebound run — never A's."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as papq
    path = str(tmp_path / "t.parquet")
    tab = pa.table({"x": pa.array(np.arange(400, dtype=np.int64)),
                    "y": pa.array(np.arange(400.0))})
    papq.write_table(tab, path, row_group_size=100)
    s = _session()
    base = s.read.parquet(path)

    def q(lo, hi):
        return base.filter((col("x") >= lit_col(lo))
                           & (col("x") < lit_col(hi)))

    a = q(10, 20).collect()
    assert [r[0] for r in a] == list(range(10, 20))
    st0 = pc.cache().stats()
    # Binding B lives entirely in the LAST row group: a stale-predicate
    # skip would return zero rows.
    df = q(350, 360)
    b = df.collect()
    assert pc.cache().stats()["hits"] == st0["hits"] + 1
    assert [r[0] for r in b] == list(range(350, 360))
    skipped = sum(v.get("numSkippedRowGroups", 0)
                  for v in df.metrics().values())
    assert skipped >= 3, "stats skipping stopped working under binding"


# ---------------------------------------------------------------------------
# Invalidation & bypass
# ---------------------------------------------------------------------------

def test_conf_change_invalidates(tpch_dir):
    s = _session()
    _q6(s, tpch_dir).collect()
    st0 = pc.cache().stats()
    s.set("spark.rapids.sql.shuffle.partitions", 3)
    _q6(s, tpch_dir).collect()
    st1 = pc.cache().stats()
    assert st1["misses"] == st0["misses"] + 1, (st0, st1)


def test_schema_change_misses():
    s = _session()
    data = {"a": [1, 2, 3]}
    d32 = s.create_dataframe(data, [("a", dt.INT32)])
    d64 = s.create_dataframe(data, [("a", dt.INT64)])
    r32 = d32.filter(col("a") > lit_col(1)).collect()
    st0 = pc.cache().stats()
    r64 = d64.filter(col("a") > lit_col(1)).collect()
    st1 = pc.cache().stats()
    assert st1["misses"] == st0["misses"] + 1
    assert r32 == r64 == [(2,), (3,)]


def test_armed_faults_bypass_and_stay_bit_identical(tpch_dir):
    from spark_rapids_tpu import faults
    want = _q6(_session(), tpch_dir).collect()
    c0 = pc.counters().get("planCacheBypasses", 0)
    chaos = "oom@upload:1,oom@kernel:1,transient@download:1"
    got = _q6(_session(chaos=chaos), tpch_dir).collect()
    c1 = pc.counters().get("planCacheBypasses", 0)
    assert c1 == c0 + 1, "armed fault schedule must bypass the cache"
    assert got == want
    assert faults.counters().get("faultsInjected", 0) > 0


def test_disabled_control_returns_plain_physical_plan(tpch_dir):
    df = _q6(_session(plan_cache=False), tpch_dir)
    phys = df._physical()
    assert not hasattr(phys, "provenance")
    assert "plan-cache" not in df.explain("ALL")


# ---------------------------------------------------------------------------
# Provenance & handles (satellite #2)
# ---------------------------------------------------------------------------

def test_explain_annotates_provenance(tpch_dir):
    pc.cache().clear()      # earlier tests cached this shape
    s = _session()
    first = _q6(s, tpch_dir)
    rep0 = first.explain("ALL")
    assert "plan-cache miss, template planned" in rep0
    rebound = _q6(s, tpch_dir, "1995-01-01", "1996-01-01")
    rep1 = rebound.explain("ALL")
    assert "plan-cache hit, bind-only" in rep1


def test_explain_analyze_annotates_provenance(tpch_dir):
    s = _session()
    _q6(s, tpch_dir).collect()
    rebound = _q6(s, tpch_dir, "1993-01-01", "1994-01-01")
    report = rebound.explain_analyze()
    assert "plan-cache hit, bind-only" in report


def test_prepare_returns_bound_handle(tpch_dir):
    s = _session()
    _q6(s, tpch_dir).collect()
    handle = _q6(s, tpch_dir, "1995-01-01", "1996-01-01").prepare()
    assert handle.cache_hit
    assert len(handle.bind_values) >= 2
    rows = handle.collect()
    control = _q6(_session(plan_cache=False), tpch_dir,
                  "1995-01-01", "1996-01-01").collect()
    assert rows == control


def test_scheduler_per_tenant_stats(tpch_dir):
    s = _session()
    _q6(s, tpch_dir).collect()
    df = _q6(s, tpch_dir, "1996-01-01", "1997-01-01")
    df.collect()
    sched = df.metrics().get("Scheduler@query", {})
    assert sched.get("planCacheBindOnly") == 1, sched


def test_plan_bind_span_under_budget(tpch_dir):
    """Acceptance: steady-state plan+bind < 5ms, measured via the trace
    span (generous 50ms CI bound)."""
    from spark_rapids_tpu import monitoring
    s = _session()
    s.set("spark.rapids.sql.trace.enabled", True)
    _q6(s, tpch_dir).collect()
    monitoring.reset()
    _q6(s, tpch_dir, "1995-06-01", "1995-12-01").collect()
    spans = [e for events in
             (monitoring.events(q) for q in monitoring.query_ids())
             for e in events if e[1] == "plan-bind"]
    assert spans, "plan-bind span missing"
    dur_ms = spans[-1][4] / 1e6
    args = spans[-1][7]
    assert args and args.get("planCacheHit") is True, args
    assert dur_ms < 50.0, f"plan+bind took {dur_ms:.1f}ms"
    monitoring.configure(False)
    monitoring.reset()


# ---------------------------------------------------------------------------
# Parity suite: 11 bench queries cached-vs-fresh, rebind, chaos control
# ---------------------------------------------------------------------------

# Fast tier runs q6 only (the serving shape the mechanism tests above
# already exercise end to end); the CI plan-cache chaos entry runs the
# full 11-query sweep without the slow filter.
_TPCH = ["q6",
         pytest.param("q1", marks=pytest.mark.slow),
         pytest.param("q3", marks=pytest.mark.slow),
         pytest.param("q5", marks=pytest.mark.slow),
         pytest.param("q12", marks=pytest.mark.slow),
         pytest.param("q14", marks=pytest.mark.slow)]
_SUITES = [pytest.param("repart", marks=pytest.mark.slow),
           pytest.param("q67", marks=pytest.mark.slow),
           pytest.param("xbb_q5", marks=pytest.mark.slow),
           pytest.param("ds_q3", marks=pytest.mark.slow),
           pytest.param("xbb_q12", marks=pytest.mark.slow)]

_CHAOS = "oom@kernel:1,transient@exchange.flush:1"


def _parity_check(mod, qname, ddir):
    """cached (miss) == cached (rebind hit) == cache-off control ==
    chaos-bypass run, bit for bit."""
    fresh = mod.QUERIES[qname](_session(plan_cache=False), ddir).collect()
    cached = mod.QUERIES[qname](_session(), ddir).collect()
    st0 = pc.cache().stats()
    rebound = mod.QUERIES[qname](_session(), ddir).collect()
    assert pc.cache().stats()["hits"] > st0["hits"]
    assert cached == fresh
    assert rebound == fresh
    chaos = mod.QUERIES[qname](_session(chaos=_CHAOS), ddir).collect()
    assert chaos == fresh


@pytest.mark.parametrize("qname", _TPCH)
def test_parity_tpch(qname, tpch_dir):
    from spark_rapids_tpu.benchmarks import tpch
    _parity_check(tpch, qname, tpch_dir)


@pytest.mark.parametrize("qname", _SUITES)
def test_parity_suites(qname, suites_dir):
    from spark_rapids_tpu.benchmarks import suites
    _parity_check(suites, qname, suites_dir)


def test_parity_two_bindings_q6(tpch_dir):
    """Two genuinely different literal bindings, each checked against
    its own cache-off control."""
    s = _session()
    for lo, hi in (("1994-01-01", "1995-01-01"),
                   ("1995-01-01", "1996-01-01")):
        got = _q6(s, tpch_dir, lo, hi).collect()
        want = _q6(_session(plan_cache=False), tpch_dir, lo, hi).collect()
        assert got == want, (lo, hi)


@pytest.mark.slow
def test_parity_two_bindings_q1(tpch_dir):
    from spark_rapids_tpu.benchmarks import tpch
    from spark_rapids_tpu.plan.logical import agg_avg, agg_count

    def q1(session, cutoff):
        li = tpch._read(session, tpch_dir, "lineitem")
        disc = li.filter(col("l_shipdate") <= lit_col(tpch.days(cutoff))) \
            .with_column("disc_price",
                         col("l_extendedprice") * (1.0 - col("l_discount")))
        return disc.group_by("l_returnflag", "l_linestatus").agg(
            agg_sum(col("disc_price")).alias("sum_disc_price"),
            agg_avg(col("l_quantity")).alias("avg_qty"),
            agg_count().alias("n"),
        ).order_by("l_returnflag", "l_linestatus")

    s = _session()
    for cutoff in ("1998-09-02", "1995-06-17"):
        got = q1(s, cutoff).collect()
        want = q1(_session(plan_cache=False), cutoff).collect()
        assert got == want, cutoff


# ---------------------------------------------------------------------------
# Unit: parameterization rules
# ---------------------------------------------------------------------------

def test_parameterize_hoists_only_safe_positions():
    from spark_rapids_tpu.plan import logical as L
    s = TpuSession()
    df = s.create_dataframe({"a": [1], "s": ["xy"]},
                            [("a", dt.INT64), ("s", dt.STRING)])
    shaped = df.filter((col("a") > lit_col(5))
                       & (col("s") == lit_col("xy"))
                       & col("s").isin("p", "q"))
    param, values, dtypes = pc.parameterize(shaped._plan)
    # The int comparison hoists; the string literal and the isin set are
    # structural (width buckets / set membership) and stay inline.
    assert values == (5,)
    assert dtypes == (dt.INT32,)


def test_parameterize_slot_order_deterministic():
    s = TpuSession()
    df = s.create_dataframe({"a": [1]}, [("a", dt.INT64)])
    shaped = df.filter(col("a") > lit_col(3)) \
        .with_column("b", col("a") * 2) \
        .limit(4)
    _, v1, t1 = pc.parameterize(shaped._plan)
    _, v2, t2 = pc.parameterize(shaped._plan)
    assert v1 == v2 == (3, 2, 4)
    assert t1 == t2


def test_uncacheable_shapes_plan_fresh():
    """Opaque shapes (pandas UDF nodes) bypass rather than mis-key."""
    s = _session()
    df = s.create_dataframe({"a": [1, 2]}, [("a", dt.INT64)])
    out = df.map_in_pandas(lambda it: it, [("a", dt.INT64)])
    c0 = pc.counters().get("planCacheUncacheable", 0)
    rows = out.collect()
    assert sorted(rows) == [(1,), (2,)]
    assert pc.counters().get("planCacheUncacheable", 0) == c0 + 1


def test_int64_literal_gets_wide_slot():
    s = _session()
    df = s.create_dataframe({"a": [2**40, 5]}, [("a", dt.INT64)])
    got = df.filter(col("a") > lit_col(2**35)).collect()
    assert got == [(2**40,)]
    _, values, dtypes = pc.parameterize(
        df.filter(col("a") > lit_col(2**35))._plan)
    assert dtypes == (dt.INT64,)
