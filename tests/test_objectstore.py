"""Object-store shuffle transport (ISSUE 17): localhost stub, bounded
retry against 5xx bursts, shard loss at rest, injected fault kinds, the
manifest publication barrier, and the cluster chaos scenario — a
driver + 3 workers surviving shard loss and an availability burst
mid-query with at most one stage recompute and zero whole-query
retries.
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

import spark_rapids_tpu
from spark_rapids_tpu import config as C
from spark_rapids_tpu import faults
from spark_rapids_tpu.api.dataframe import TpuSession
from spark_rapids_tpu.benchmarks import tpch
from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.host import (HostBatch, HostColumn,
                                            device_to_host, host_to_device)
from spark_rapids_tpu.ops.base import ExecContext
from spark_rapids_tpu.parallel import broadcast_cache as BC
from spark_rapids_tpu.parallel import cluster as CL
from spark_rapids_tpu.parallel import transport as T
from spark_rapids_tpu.parallel.cluster.coordinator import ClusterExecInfo
from spark_rapids_tpu.parallel.transport.base import ShardLostError
from spark_rapids_tpu.parallel.transport.objectstore import (
    HttpObjectStoreBackend, ObjectMissingError, ObjectStoreStub,
    ObjectStoreTransport, ObjectStoreUnavailableError, make_backend)

REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.abspath(spark_rapids_tpu.__file__)))


@pytest.fixture(autouse=True)
def _clean():
    faults.configure("")
    faults.reset_counters()
    T.reset_counters()
    yield
    CL.shutdown_coordinator()
    faults.configure("")
    faults.reset_counters()


@pytest.fixture()
def stub():
    s = ObjectStoreStub()
    yield s
    s.close()


def _batch(keys, vals):
    hb = HostBatch(
        ("k", "v"),
        [HostColumn(dt.INT64, np.asarray(keys, np.int64),
                    np.ones(len(keys), bool)),
         HostColumn(dt.INT64, np.asarray(vals, np.int64),
                    np.ones(len(vals), bool))])
    return host_to_device(hb)


def _rows(batch):
    return device_to_host(batch).to_pylist()


def _conf(stub, prefix="t", **over):
    raw = {C.SHUFFLE_TRANSPORT_OBJECTSTORE_ENDPOINT.key: stub.endpoint,
           C.SHUFFLE_TRANSPORT_OBJECTSTORE_PREFIX.key: prefix,
           C.SHUFFLE_TRANSPORT_OBJECTSTORE_BACKOFF_MS.key: 5}
    raw.update({getattr(C, k).key: v for k, v in over.items()})
    return C.TpuConf(raw)


# ---------------------------------------------------------------------------
# Backend + stub
# ---------------------------------------------------------------------------

def test_stub_backend_put_get_list_delete(stub):
    b = make_backend(stub.endpoint, timeout_s=2.0)
    assert isinstance(b, HttpObjectStoreBackend)
    b.put("a/x", b"one")
    b.put("a/y", b"two")
    b.put("b/z", b"three")
    assert b.get("a/y") == b"two"
    assert b.list_keys("a/") == ["a/x", "a/y"]
    b.delete("a/x")
    assert b.list_keys("a/") == ["a/y"]
    with pytest.raises(ObjectMissingError):
        b.get("a/x")


def test_stub_5xx_surfaces_typed_unavailable(stub):
    b = make_backend(stub.endpoint, timeout_s=2.0)
    b.put("k", b"v")
    stub.fail_next(1)
    with pytest.raises(ObjectStoreUnavailableError):
        b.get("k")
    assert b.get("k") == b"v"      # burst over: healthy again


def test_stub_http_admin_surface_steers_chaos(stub):
    """The same chaos the in-process setters drive must be reachable
    over HTTP — that is what out-of-process CI workers use."""
    b = make_backend(stub.endpoint, timeout_s=2.0)
    b.put("c/s1", b"x")
    b.put("c/s2", b"y")

    def admin(path):
        req = urllib.request.Request(f"{stub.endpoint}{path}",
                                     method="POST")
        with urllib.request.urlopen(req, timeout=2.0) as r:
            return r.read()

    dropped = json.loads(admin("/admin/drop?prefix=c/s1"))
    assert dropped == ["c/s1"]
    admin("/admin/fail?n=1&code=503")
    with pytest.raises(ObjectStoreUnavailableError):
        b.get("c/s2")
    stats = json.loads(urllib.request.urlopen(
        f"{stub.endpoint}/admin/stats", timeout=2.0).read())
    assert stats["failed"] >= 1


# ---------------------------------------------------------------------------
# Session SPI: roundtrip, publication barrier, retry, loss
# ---------------------------------------------------------------------------

def test_objectstore_write_commit_fetch_roundtrip(stub):
    conf = _conf(stub)
    w = ObjectStoreTransport().open(conf, "xround", 2, owner=123)
    w.write_shard(0, _batch([1, 2], [3, 4]))
    w.write_shard(1, _batch([5], [6]))
    w.write_shard(0, _batch([7], [8]))
    w.commit()
    r = ObjectStoreTransport().open(conf, "xround", 2)
    got0 = [row for h in r.fetch_shards(0) for row in _rows(h.get())]
    got1 = [row for h in r.fetch_shards(1) for row in _rows(h.get())]
    assert got0 == [(1, 3), (2, 4), (7, 8)]    # (worker, seq) order
    assert got1 == [(5, 6)]
    assert r.fetch_shards(1)[0].capacity >= 1  # manifest-known, no I/O
    r.close()
    w.close()
    assert stub.keys("t/xround") == []         # last owner cleaned up


def test_objectstore_fetch_waits_for_manifest(stub):
    conf = _conf(
        stub, SHUFFLE_TRANSPORT_OBJECTSTORE_FETCH_TIMEOUT_MS=200)
    w = ObjectStoreTransport().open(conf, "xbarrier", 1, owner=9)
    w.write_shard(0, _batch([1], [2]))
    # No commit: shard objects are durable but INVISIBLE — the manifest
    # PUT is the publication barrier.
    r = ObjectStoreTransport().open(conf, "xbarrier", 1, owner=9)
    with pytest.raises(ShardLostError) as ei:
        r.fetch_shards(0)
    assert ei.value.fault_owner == 9
    w.invalidate()


def test_objectstore_torn_manifest_reads_as_unpublished(stub):
    """Same regression contract as the hostfile spool: a torn or
    schema-incomplete manifest object is 'not yet published', never a
    crash inside fetch_shards."""
    conf = _conf(
        stub, SHUFFLE_TRANSPORT_OBJECTSTORE_FETCH_TIMEOUT_MS=200)
    b = make_backend(stub.endpoint, timeout_s=2.0)
    w = ObjectStoreTransport().open(conf, "xtorn", 1, owner=4)
    w.write_shard(0, _batch([1], [2]))
    w.commit()
    mkey = w._manifest_key()
    full = b.get(mkey)
    for torn in (full[: len(full) // 2],
                 json.dumps({"worker": "w", "shards": "torn"}).encode()):
        b.put(mkey, torn)
        r = ObjectStoreTransport().open(conf, "xtorn", 1, owner=4)
        with pytest.raises(ShardLostError) as ei:
            r.fetch_shards(0)
        assert ei.value.fault_owner == 4
    b.put(mkey, full)                          # restored: published
    r = ObjectStoreTransport().open(conf, "xtorn", 1, owner=4)
    assert _rows(r.fetch_shards(0)[0].get()) == [(1, 2)]
    w.invalidate()


def test_5xx_burst_absorbed_by_bounded_retry(stub):
    conf = _conf(stub, SHUFFLE_TRANSPORT_OBJECTSTORE_RETRIES=4)
    w = ObjectStoreTransport().open(conf, "xburst", 1, owner=1)
    w.write_shard(0, _batch([1], [2]))
    w.commit()
    stub.fail_next(3)                          # every op retries past it
    r = ObjectStoreTransport().open(conf, "xburst", 1, owner=1)
    assert _rows(r.fetch_shards(0)[0].get()) == [(1, 2)]
    assert T.counters().get("objectstoreRetries", 0) >= 1
    w.invalidate()


def test_retry_exhaustion_surfaces_typed_unavailable(stub):
    conf = _conf(stub, SHUFFLE_TRANSPORT_OBJECTSTORE_RETRIES=1)
    w = ObjectStoreTransport().open(conf, "xdown", 1, owner=1)
    stub.fail_next(10)
    with pytest.raises(ObjectStoreUnavailableError):
        w.write_shard(0, _batch([1], [2]))


def test_shard_loss_at_rest_raises_owner_tagged(stub):
    conf = _conf(stub)
    w = ObjectStoreTransport().open(conf, "xloss", 1, owner=42)
    w.write_shard(0, _batch([1], [2]))
    w.commit()
    r = ObjectStoreTransport().open(conf, "xloss", 1, owner=42)
    handles = r.fetch_shards(0)
    stub.drop("t/xloss/")                      # the chaos matrix verb
    # the manifest is gone too, but the handle already points at its key
    with pytest.raises(ShardLostError) as ei:
        handles[0].get()
    assert ei.value.fault_owner == 42          # lineage recompute target
    assert T.counters().get("remoteShardsLost", 0) == 1


def test_corrupt_at_rest_refetches_once(stub):
    conf = _conf(stub)
    w = ObjectStoreTransport().open(conf, "xcorrupt", 1, owner=7)
    w.write_shard(0, _batch([1, 2, 3], [4, 5, 6]))
    w.commit()
    faults.configure("corrupt@transport:1", seed=3)
    try:
        r = ObjectStoreTransport().open(conf, "xcorrupt", 1, owner=7)
        got = _rows(r.fetch_shards(0)[0].get())
        assert got == [(1, 4), (2, 5), (3, 6)]
        assert T.counters().get("remoteShardRefetches") == 1
    finally:
        faults.configure("")
        w.invalidate()


# ---------------------------------------------------------------------------
# Injected fault kinds (chaos matrix verbs)
# ---------------------------------------------------------------------------

def test_fault_unavailable_objectstore_absorbed_by_retry(stub):
    conf = _conf(stub, SHUFFLE_TRANSPORT_OBJECTSTORE_RETRIES=3)
    faults.configure("unavailable@objectstore:1", seed=5)
    try:
        w = ObjectStoreTransport().open(conf, "xfault", 1, owner=1)
        w.write_shard(0, _batch([1], [2]))
        w.commit()
        r = ObjectStoreTransport().open(conf, "xfault", 1, owner=1)
        assert _rows(r.fetch_shards(0)[0].get()) == [(1, 2)]
        assert T.counters().get("objectstoreRetries", 0) >= 1
    finally:
        faults.configure("")
        w.invalidate()


def test_fault_slowput_transport_is_latency_not_error(stub):
    conf = _conf(stub)
    faults.configure("slowput@transport:1", seed=5)
    try:
        w = ObjectStoreTransport().open(conf, "xslow", 1, owner=1)
        t0 = time.monotonic()
        w.write_shard(0, _batch([1], [2]))
        assert time.monotonic() - t0 >= 0.2    # injected latency
        w.commit()
        r = ObjectStoreTransport().open(conf, "xslow", 1, owner=1)
        assert _rows(r.fetch_shards(0)[0].get()) == [(1, 2)]
        assert T.counters().get("slowPuts", 0) == 1
    finally:
        faults.configure("")
        w.invalidate()


def test_injected_lostshard_deletes_at_rest_first(stub):
    conf = _conf(stub)
    w = ObjectStoreTransport().open(conf, "xdel", 1, owner=3)
    w.write_shard(0, _batch([1], [2]))
    w.commit()
    faults.configure("lostshard@transport:1", seed=2)
    try:
        r = ObjectStoreTransport().open(conf, "xdel", 1, owner=3)
        with pytest.raises(ShardLostError):
            r.fetch_shards(0)[0].get()
        # recovery must REWRITE, not re-read a survivor
        assert not any(k.endswith(".shard") for k in stub.keys("t/xdel"))
    finally:
        faults.configure("")
        w.invalidate()


# ---------------------------------------------------------------------------
# Broadcast artifact cache (tentpole leg c) through the objectstore
# ---------------------------------------------------------------------------

def _bcast_ctx(stub, wid, exchange, gens=None, **over):
    """One simulated cluster process: an ExecContext whose installed
    ClusterExecInfo tags ``exchange`` as broadcast stage 4 of a query
    with plan fingerprint ``feedface`` on the objectstore store."""
    ctx = ExecContext(conf=_conf(stub, prefix="bc", **over))
    ctx.cache["cluster"] = ClusterExecInfo(
        "", wid, {}, store_kind="objectstore",
        store_endpoint=stub.endpoint, store_prefix="bc",
        bcast_tags={id(exchange): 4}, bcast_deps={4: [1, 2]},
        plan_fp="feedface",
        gen_source=(lambda: gens) if gens is not None else None)
    return ctx


def test_broadcast_cache_publish_then_adopted_by_peer(stub):
    """The first process to build a broadcast single publishes it; a
    peer process of the same query adopts the committed blob instead of
    re-collecting — and the transport counters prove it."""
    ex = object()
    single = _batch([1, 2, 3], [10, 20, 30])
    BC.maybe_publish(_bcast_ctx(stub, "w0", ex), ex, single)
    assert T.counters().get("broadcastCachePublishes") == 1
    assert stub.keys("bc/bc-feedface-s4-g0/")      # content-addressed key
    hit = BC.maybe_fetch(_bcast_ctx(stub, "w1", ex), ex)
    assert hit is not None
    _handle, got = hit
    assert _rows(got) == _rows(single)
    assert T.counters().get("broadcastCacheHits") == 1


def test_broadcast_cache_unpublished_and_loss_degrade_to_miss(stub):
    """Not-yet-published and lost-at-rest both mean: build locally.
    Never an error, never a recompute."""
    ex = object()
    dst = _bcast_ctx(stub, "w1", ex)
    assert BC.maybe_fetch(dst, ex) is None          # nobody published yet
    BC.maybe_publish(_bcast_ctx(stub, "w0", ex), ex, _batch([1], [2]))
    stub.drop("bc/")                 # blobs AND manifest lost at rest
    assert BC.maybe_fetch(dst, ex) is None          # loss = miss
    assert T.counters().get("broadcastCacheMisses") >= 2
    assert faults.counters().get("stageRecomputes", 0) == 0


def test_broadcast_cache_generation_bump_invalidates(stub):
    """A recomputed upstream stage bumps its generation, which changes
    the cache tag — a cached build of pre-recompute inputs is simply
    never found."""
    ex = object()
    BC.maybe_publish(_bcast_ctx(stub, "w0", ex, gens={1: 0, 2: 0}),
                     ex, _batch([7], [8]))
    assert BC.maybe_fetch(
        _bcast_ctx(stub, "w1", ex, gens={1: 0, 2: 0}), ex) is not None
    assert BC.maybe_fetch(
        _bcast_ctx(stub, "w2", ex, gens={1: 1, 2: 0}), ex) is None


def test_broadcast_cache_disabled_is_inert(stub):
    ex = object()
    ctx = _bcast_ctx(stub, "w0", ex, BROADCAST_CACHE_ENABLED=False)
    BC.maybe_publish(ctx, ex, _batch([1], [2]))
    assert stub.keys("bc/") == []
    assert BC.maybe_fetch(ctx, ex) is None
    assert T.counters().get("broadcastCachePublishes", 0) == 0


# ---------------------------------------------------------------------------
# Cluster chaos (acceptance scenario 2): shard loss + 5xx burst
# ---------------------------------------------------------------------------

def _spawn_worker(addr, wid, extra_env=None):
    env = dict(os.environ)
    env.pop("SRT_FAULTS", None)
    if extra_env:
        env.update(extra_env)
    return subprocess.Popen(
        [sys.executable, "-m",
         "spark_rapids_tpu.parallel.cluster.worker",
         "--coordinator", addr, "--worker-id", wid],
        env=env, cwd=REPO_ROOT)


def _stop(procs):
    for p in procs:
        if p.poll() is None:
            p.terminate()
    for p in procs:
        try:
            p.wait(timeout=20)
        except Exception:
            p.kill()


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("tpch_objstore"))
    tpch.generate(d, scale=0.003, files_per_table=3, seed=7)
    return d


@pytest.mark.slow      # CI runs this via the objectstore-loss entry
def test_cluster_survives_shard_loss_and_5xx_burst(data_dir, stub):
    """Driver + 3 workers on the objectstore transport. Mid-query chaos:
    one worker loses a fetched dep shard at rest (lostshard fires inside
    its transport fetch) while the store serves a 5xx burst. The query
    must finish bit-identical with EXACTLY one stage recompute and zero
    whole-query retries — loss is repaired by lineage, bursts by the
    bounded retry loop, never by rerunning the query."""
    s = TpuSession()
    s.set("spark.rapids.sql.variableFloatAgg.enabled", True)
    want = tpch.QUERIES["q3"](s, data_dir).collect()

    sc = TpuSession()
    sc.set("spark.rapids.sql.variableFloatAgg.enabled", True)
    sc.set("spark.rapids.sql.cluster.enabled", True)
    sc.set("spark.rapids.sql.shuffle.transport", "objectstore")
    sc.set(C.SHUFFLE_TRANSPORT_OBJECTSTORE_ENDPOINT.key, stub.endpoint)
    sc.set("spark.rapids.sql.cluster.minWorkers", 3)
    co = CL.get_coordinator(sc.conf)
    addr = f"{co.addr[0]}:{co.addr[1]}"
    procs = [
        _spawn_worker(addr, "w0",
                      extra_env={"SRT_FAULTS": "lostshard@transport:1"}),
        _spawn_worker(addr, "w1"),
        _spawn_worker(addr, "w2"),
    ]
    stub.fail_next(5)                          # availability burst
    try:
        c0 = dict(faults.counters())
        got = tpch.QUERIES["q3"](sc, data_dir).collect()
        c1 = faults.counters()
        delta = lambda k: c1.get(k, 0) - c0.get(k, 0)
        assert got == want                       # bit-identical
        assert delta("stageRecomputes") <= 1     # at most ONE per loss
        assert delta("retriesAttempted") == 0    # never a dead query
    finally:
        _stop(procs)
