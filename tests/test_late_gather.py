"""The late dense probe (PR 37): an inner join without a condition over a
direct-address table gathers the build side's rows AFTER its match count
is known, at the output's capacity bucket.

Here: that the late path's output equals, leaf for leaf, what
``_dense_step`` followed by the consumer's ``shrink_to_capacity`` gave;
which joins and batches still take the single program; that the count
comes in one pull a window and the consumer, finding ``rows_hint``, pulls
nothing; that both programs go through ``retry_on_oom``; the counters and
the spans.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import spark_rapids_tpu  # noqa: F401  (x64)
from spark_rapids_tpu import config as C, exprs as E
from spark_rapids_tpu.columnar import batch as B
from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.exprs.base import BoundReference as Ref
from spark_rapids_tpu.monitoring import recorder, syncs
from spark_rapids_tpu.ops import join as J
from spark_rapids_tpu.ops.base import ExecContext

from test_ops import source

CAP = 4_096
BUILD_KEYS = 3_000          # build keys 100 .. 3,099, each once
LATE = ("joinLateWindows", "joinLateEmitBucket", "joinLateEmitCapacity",
        "joinEagerBatches")
PROBE_SCHEMA = [("k", dt.INT64), ("v", dt.INT32), ("x", dt.FLOAT64),
                ("s", dt.STRING)]
BUILD_SCHEMA = [("bk", dt.INT64), ("w", dt.INT64), ("t", dt.STRING),
                ("y", dt.FLOAT64)]


def _col(dtype, values, valid=None):
    values = np.asarray(values)
    valid = np.ones(len(values), bool) if valid is None else valid
    if dtype.is_string:
        data = np.zeros((len(values), 8), np.uint8)
        lengths = np.zeros(len(values), np.int32)
        for i, v in enumerate(values):
            raw = str(v).encode()[:8]
            data[i, :len(raw)] = list(raw)
            lengths[i] = len(raw)
        return B.DeviceColumn(dtype, jnp.asarray(data), jnp.asarray(valid),
                              jnp.asarray(lengths))
    return B.DeviceColumn(dtype, jnp.asarray(values.astype(dtype.np_dtype)),
                          jnp.asarray(valid))


def built_side():
    keys = np.arange(100, 100 + BUILD_KEYS)
    order = np.random.default_rng(5).permutation(BUILD_KEYS)
    keys = keys[order]
    build = B.DeviceBatch(
        (_col(dt.INT64, keys), _col(dt.INT64, keys * 7),
         _col(dt.STRING, [f"b{k}" for k in keys]),
         _col(dt.FLOAT64, keys / 4)),
        jnp.asarray(BUILD_KEYS, jnp.int32))
    built = J.build_side(build, [0])
    J._maybe_build_dense(built, built.batch, built.key_ordinals)
    assert built.table is not None
    return built


def probe(match_pct, seed=0, rows=CAP - 96, sel=False):
    """``rows`` probe rows of which ``match_pct`` % address a build key;
    the others are NULL, below the table's base, above its span, or in
    range and absent. ``sel``: a third of the rows deleted by a selection
    vector on top."""
    rng = np.random.default_rng([seed, int(match_pct * 10)])
    k = np.empty(CAP, np.int64)
    hit = rng.random(CAP) * 100 < match_pct
    k[hit] = rng.integers(100, 100 + BUILD_KEYS, hit.sum())
    miss = rng.integers(0, 4, CAP)
    k[~hit] = np.where(miss[~hit] == 0, -5, np.where(
        miss[~hit] == 1, 10 ** 12, np.where(miss[~hit] == 2, 7, 99)))
    valid = ~((miss == 2) & ~hit)           # the 7s are NULL keys
    cols = (_col(dt.INT64, k, valid), _col(dt.INT32, np.arange(CAP)),
            _col(dt.FLOAT64, np.arange(CAP) / 8),
            _col(dt.STRING, [f"p{i}" for i in range(CAP)]))
    keep = jnp.asarray(rng.random(CAP) < 2 / 3) if sel else None
    return B.DeviceBatch(cols, jnp.asarray(rows, jnp.int32), sel=keep)


def join_exec(join_type="inner", condition=None, cls=J.BroadcastHashJoinExec):
    empty = {n: [] for n, _ in PROBE_SCHEMA}
    return cls(source(PROBE_SCHEMA, empty),
               source(BUILD_SCHEMA, {n: [] for n, _ in BUILD_SCHEMA}),
               [Ref(0, dt.INT64)], [Ref(0, dt.INT64)], join_type, condition)


def stream(ex, built, batches, build_is_right=True, ctx=None, **conf):
    """The dense probe over ``batches`` as they are: what
    ``_device_join_stream`` runs behind its input's ``coalesce_iter``."""
    ctx = ctx or ExecContext(TpuConf(conf))
    return list(ex._dense_stream(ctx, built, iter(batches), (0,),
                                 build_is_right))


def eager_then_shrink(ex, built, pbatch, build_is_right=True):
    """What the parent gave the consumer: ``_dense_step``, and the
    consumer's compaction where ``shrink_all``'s rule for a probe asks
    for it."""
    out = ex._dense_jit_fn()(built, pbatch, probe_keys=(0,),
                             build_is_right=build_is_right)
    live = int(out.live_count())
    cap = B.bucket_capacity(max(live, 1))
    if cap * B.PROBE_SHRINK_RATIO <= pbatch.capacity:
        out = B.shrink_to_capacity(out, cap)
    return out, live


def assert_same_batch(got, want):
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.fixture
def counted():
    """The recorder on (counters count only then), syncs wrapped."""
    syncs.install()
    recorder.configure(True, recorder.LEVEL_KERNEL)
    recorder.reset()
    recorder.reset_counters()
    yield lambda: {k: recorder.counters().get(k, 0) for k in LATE}
    recorder.configure(False)
    recorder.reset()
    recorder.reset_counters()


@pytest.fixture
def small_batches_count(monkeypatch):
    monkeypatch.setattr(B, "MIN_SHRINK_BYTES", 0)


@pytest.fixture(scope="module")
def built():
    return built_side()


# -- the same rows, the same bits ---------------------------------------------

@pytest.mark.parametrize("build_is_right", [True, False],
                         ids=["build-right", "build-left"])
@pytest.mark.parametrize("sel", [False, True], ids=["prefix", "sel"])
@pytest.mark.parametrize("match_pct", [0, 0.5, 10, 60, 100])
def test_late_equals_dense_step_then_shrink(built, match_pct, sel,
                                            build_is_right, counted,
                                            small_batches_count):
    ex = join_exec()
    p = probe(match_pct, sel=sel)
    want, live = eager_then_shrink(ex, built, p, build_is_right)
    (got,) = stream(ex, built, [p], build_is_right)
    assert_same_batch(got, want)
    assert got.rows_hint == live
    at_bucket = B.bucket_capacity(max(live, 1)) * 2 <= CAP
    assert (got.sel is None) == at_bucket
    assert got.capacity == (B.bucket_capacity(max(live, 1)) if at_bucket
                            else CAP)
    assert counted() == {"joinLateWindows": 1, "joinEagerBatches": 0,
                         "joinLateEmitBucket": int(at_bucket),
                         "joinLateEmitCapacity": int(not at_bucket)}
    if match_pct in (10, 60):
        assert 0 < live < int(p.live_count())    # the fixture is no fake


def test_window_of_several_batches_with_an_empty_last_one(
        built, counted, small_batches_count, monkeypatch):
    """One pull for the window's counts; order kept; the empty batch
    comes out at the smallest bucket with ``rows_hint`` 0."""
    ex = join_exec()
    window = [probe(0.5, seed=1), probe(10, seed=2, sel=True),
              probe(60, seed=3), probe(10, seed=4, rows=0)]
    want = [eager_then_shrink(ex, built, p) for p in window]
    pulls = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: (pulls.append(len(x)), real(x))[1])
    got = stream(ex, built, window)
    assert pulls == [4]
    assert len(got) == 4
    for g, (w, live) in zip(got, want):
        assert_same_batch(g, w)
        assert g.rows_hint == live
    assert got[3].rows_hint == 0 and got[3].capacity == B.MIN_CAPACITY
    assert counted() == {"joinLateWindows": 1, "joinEagerBatches": 0,
                         "joinLateEmitBucket": 3, "joinLateEmitCapacity": 1}


def test_windows_are_cut_where_the_consumer_would_group(
        built, counted, small_batches_count):
    """The row goal of ``coalesce_iter`` cuts the windows: five batches
    of 4,096 rows under a goal of 8,192 are three windows, three pulls."""
    ex = join_exec()
    batches = [probe(10, seed=i) for i in range(5)]
    got = stream(ex, built, batches, **{C.BATCH_SIZE_ROWS.key: 2 * CAP})
    assert len(got) == 5 and counted()["joinLateWindows"] == 3
    assert [len(w) for w in B.group_by_goal(batches, 2 * CAP, 1 << 40,
                                            lambda b: 1)] == [2, 2, 1]
    assert [len(w) for w in B.group_by_goal(batches, 1 << 40, 250,
                                            lambda b: 100)] == [2, 2, 1]


# -- what still takes the single program --------------------------------------

@pytest.mark.parametrize("join_type,condition", [
    ("left", None), ("right", None), ("semi", None), ("anti", None),
    ("inner", "w>v"), ("left", "w>v"), ("semi", "w>v")])
def test_outer_semi_and_conditional_joins_stay_eager(
        built, join_type, condition, counted, small_batches_count,
        monkeypatch):
    cond = None
    if condition:
        # build is right: the pairs are (k, v, x, s, bk, w, t, y)
        cond = E.GreaterThan(Ref(5, dt.INT64),
                             E.Cast(Ref(1, dt.INT32), dt.INT64))
    ex = join_exec(join_type, cond)
    batches = [probe(10, seed=1), probe(0.5, seed=2, sel=True)]
    want = [ex._dense_jit_fn()(built, p, probe_keys=(0,),
                               build_is_right=join_type != "right")
            for p in batches]
    monkeypatch.setattr(jax, "device_get",
                        lambda *_: pytest.fail("an eager probe pulled"))
    got = stream(ex, built, batches, build_is_right=join_type != "right")
    for g, w in zip(got, want):
        assert_same_batch(g, w)
        assert g.rows_hint is None
    assert counted() == {"joinLateWindows": 0, "joinEagerBatches": 2,
                         "joinLateEmitBucket": 0, "joinLateEmitCapacity": 0}


def test_a_batch_no_consumer_would_count_stays_eager(built, counted):
    """Under ``MIN_SHRINK_BYTES`` of output no ``shrink_all`` pulls a
    count, so the join does not either."""
    ex = join_exec()
    p = probe(0.5)
    assert p.device_size_bytes() < B.MIN_SHRINK_BYTES
    (got,) = stream(ex, built, [p])
    assert got.capacity == CAP and got.sel is not None
    assert got.rows_hint is None
    assert counted() == {"joinLateWindows": 0, "joinEagerBatches": 1,
                         "joinLateEmitBucket": 0, "joinLateEmitCapacity": 0}


def test_a_join_that_compacts_nothing_stops_asking(built, counted,
                                                   small_batches_count):
    """After a window whose every emit stayed at the probe's capacity the
    join takes the single program for the rest of the query (one
    ``ExecContext``): the count bought nothing, and a consumer that never
    shrinks would pay a pull a window for it. A new query asks again."""
    ex = join_exec()
    ctx = ExecContext(TpuConf({C.BATCH_SIZE_ROWS.key: 2 * CAP}))
    dense = [probe(100, seed=i) for i in range(6)]
    got = stream(ex, built, dense, ctx=ctx)
    assert counted() == {"joinLateWindows": 1, "joinLateEmitCapacity": 2,
                         "joinLateEmitBucket": 0, "joinEagerBatches": 4}
    assert [g.rows_hint is not None for g in got] == [True] * 2 + [False] * 4
    for g, p in zip(got, dense):
        assert_same_batch(g, eager_then_shrink(ex, built, p)[0])
    # a sparse join keeps asking, window after window
    recorder.reset_counters()
    stream(ex, built, [probe(10, seed=i) for i in range(6)],
           **{C.BATCH_SIZE_ROWS.key: 2 * CAP})
    assert counted() == {"joinLateWindows": 3, "joinLateEmitBucket": 6,
                         "joinLateEmitCapacity": 0, "joinEagerBatches": 0}
    # the same exec in another query starts anew
    recorder.reset_counters()
    stream(ex, built, dense[:2])
    assert counted()["joinLateWindows"] == 1


# -- the pull is the consumer's, moved ----------------------------------------

def _sync_count():
    return sum(n for n, _ in syncs.sync_stats().values())


def test_consumer_finds_rows_hint_and_pulls_nothing(built, counted,
                                                    small_batches_count):
    """Join and consumer together read the device once a window, as the
    parent's consumer did alone; the consumer's ``coalesce_iter`` finds
    the counts and rewrites nothing (the late emit is at the bucket
    already)."""
    ex = join_exec()
    window = [probe(0.5, seed=1), probe(10, seed=2), probe(60, seed=3)]
    # the parent: eager outputs, the consumer pulls the counts
    eager = [ex._dense_jit_fn()(built, p, probe_keys=(0,),
                                build_is_right=True) for p in window]
    recorder.reset()
    want = list(B.coalesce_iter(eager, 1 << 22, shrink=True, keep_ratio=1))
    parent_syncs = _sync_count()
    assert parent_syncs >= 1
    # the change
    recorder.reset()
    outs = stream(ex, built, window)
    join_syncs = _sync_count()
    assert 1 <= join_syncs <= parent_syncs
    assert all(o.rows_hint is not None for o in outs)
    recorder.reset()
    B.reset_counters()
    got = list(B.coalesce_iter(outs, 1 << 22, shrink=True, keep_ratio=1))
    assert _sync_count() == 0
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.rows_hint == w.rows_hint
        np.testing.assert_array_equal(
            np.asarray(g.columns[1].data)[np.asarray(g.row_mask())],
            np.asarray(w.columns[1].data)[np.asarray(w.row_mask())])
    B.reset_counters()


def test_both_programs_go_through_retry_on_oom(built, small_batches_count,
                                               monkeypatch):
    from spark_rapids_tpu.memory import oom
    seen = []
    real = oom.retry_on_oom

    def spy(fn, *a, **k):
        seen.append(fn)
        return real(fn, *a, **k)
    monkeypatch.setattr(oom, "retry_on_oom", spy)
    stream(join_exec(), built, [probe(10)])
    lookup, emit = J._late_jit_fns()
    assert seen == [lookup, emit]
    # and an injected OOM at either program is ridden out by the ladder
    from spark_rapids_tpu import faults
    monkeypatch.undo()
    calls = {"n": 0}

    def flaky(kernel):
        def run(*a, **k):
            calls["n"] += 1
            if calls["n"] % 2:
                raise faults.InjectedOomError("injected")
            return kernel(*a, **k)
        return run
    monkeypatch.setattr(J, "_late_jit_fns",
                        lambda: (flaky(lookup), flaky(emit)))
    monkeypatch.setattr(B, "MIN_SHRINK_BYTES", 0)
    p = probe(10, seed=9)
    (got,) = stream(join_exec(), built, [p])
    assert calls["n"] == 4
    assert_same_batch(got, eager_then_shrink(join_exec(), built, p)[0])


def test_spans_one_a_batch_and_phase_and_never_nested(
        built, counted, small_batches_count):
    ex = join_exec()
    stream(ex, built, [probe(0.5, seed=1), probe(60, seed=2)])
    spans = [e for e in recorder.events()
             if e[0] == "X" and e[2] == "join-probe"]
    assert [e[7]["path"] for e in sorted(spans, key=lambda e: e[3])] == [
        "late-lookup", "late-lookup", "late-counts", "late-emit",
        "late-emit"]
    assert all(e[7]["op"] == ex.name for e in spans)
    by_sid = {e[8]: e for e in recorder.events() if e[0] == "X"}
    assert all(by_sid.get(e[9], (0, 0, ""))[2] != "join-probe"
               for e in spans)
    # the one read of the device sits under the window's counts span
    pulls = [e for e in by_sid.values() if e[2] == "sync"]
    assert pulls and all(by_sid[e[9]][7].get("path") == "late-counts"
                         or by_sid[e[9]][2] == "sync" for e in pulls)


def test_chip_probe_lategather_rehearses_on_the_cpu(capsys):
    """``scripts/chip_probe.py lategather --cpu-rehearsal``: the probe's
    control flow at a tiny size; every line says it is no device number,
    the two forms agree, and the rule named is ``_dense_stream``'s."""
    import json
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts"))
    import chip_probe
    assert chip_probe.main(["lategather", "--cpu-rehearsal"]) == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    assert all(d["cpu_rehearsal"] for d in lines)
    rows = [d for d in lines if d["probe"] == "lategather"]
    assert len(rows) == 10 and all(d["same_output"] for d in rows)
    assert {d["match_pct"] for d in rows} == {0.5, 10, 40, 60, 100}
    for d in rows:
        compacts = d["bucket"] * B.PROBE_SHRINK_RATIO <= d["rows"]
        assert d["shipped"] == ("late_at_bucket" if compacts
                                else "late_at_capacity")
        assert ("late_at_bucket_ms" in d) == (d["bucket"] < d["rows"])
