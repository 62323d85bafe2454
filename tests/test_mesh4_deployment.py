"""The four-chip deployment ``tpch_sf1_mesh4`` on the CPU (PR 27): TPC-H
Q5 with every join shuffled over a mesh of FOUR virtual devices, at SF
0.01 from a seed, with the ``conf`` of the committed configuration file
(``benchmark/configs/tpch_sf1_mesh4_2x2.json``) and nothing else set.

What the cell ``tpch_sf1_mesh4_q5`` is held to on the chip is held here
at a small size: the rows against the benchmark's plain float64 reference
under ``compare.judge``'s rule and against the in-process transport, the
plan's shape, the plan cache, the ``mesh-exchange`` spans (never nested,
never around a child's work, each a profiler annotation) and the byte
counters, on both branches of the exchange (counts pulled / skipped),
with no blocking read added for a counter's sake.
"""

import collections
import glob
import importlib.util
import json
import os
import time

import pytest

from spark_rapids_tpu import faults
from spark_rapids_tpu.api.dataframe import TpuSession
from spark_rapids_tpu.monitoring import recorder, syncs
from spark_rapids_tpu.parallel import mesh as M
from spark_rapids_tpu.parallel import mesh_exchange as MX
from spark_rapids_tpu.plan import plan_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
MESH = 4
SEED = 2147483927                 # over 31 bits, as the driver's are
PHASES = ("shard", "pids", "counts", "collective", "land", "unfold")


def _bench_module(name):
    """A module of ``benchmark/`` by path: the harness's generator,
    reference and comparison, with nothing of it put on ``sys.path``."""
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{name}", os.path.join(BENCH, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _committed():
    """(configuration entry, its file's contents) of ``tpch_sf1_mesh4``
    as committed."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [c for c in bench["configs"] if c["name"] == "tpch_sf1_mesh4"]
    with open(os.path.join(ROOT, entry["file"])) as f:
        return entry, json.load(f)


def _execs(df):
    out = []

    def walk(node):
        out.append(node)
        for c in node.children:
            walk(c)
    walk(df._physical().root)
    return out


def _annotations(trace_dir):
    import jax.profiler
    path, = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(path)
    return [e.name for plane in data.planes if plane.name == "/host:CPU"
            for ln in plane.lines for e in ln.events]


def _spans(events):
    return [e for e in events if e[0] == "X"]


def _exchange_metrics(df):
    return [v for k, v in df.metrics().items()
            if k.startswith("MeshExchangeExec")]


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    """Everything the tests below look at, from one session: the query is
    compiled once per branch of the exchange."""
    import jax
    import jax.profiler
    if len(jax.devices()) < MESH:
        pytest.skip(f"needs {MESH} devices")
    tpch_data, compare = _bench_module("tpch_data"), _bench_module("compare")
    run = _bench_module("run")      # its compile clock and host-node count
    entry, config = _committed()
    data_dir = str(tmp_path_factory.mktemp("mesh4_data"))
    tpch_data.generate(data_dir, scale=0.01, seed=SEED,
                       files_per_table=config["files_per_table"],
                       tables=sorted(tpch_data.QUERY_COLUMNS["q5"]))

    def four(ctx):
        m = ctx.cache.get("mesh:singleton")
        if m is None:
            m = ctx.cache["mesh:singleton"] = M.make_mesh(MESH)
        return m

    mp = pytest.MonkeyPatch()
    # the deployment's mesh on this process's eight virtual devices: the
    # planner asks mesh_size() for the partition count, the exchange asks
    # mesh_for() for the mesh
    mp.setattr(MX, "mesh_size", lambda: MESH)
    mp.setattr(MX, "mesh_for", four)
    out = {"entry": entry, "config": config, "compare": compare,
           "tpch_data": tpch_data}
    clock = run.CompileClock(jax)
    try:
        s = TpuSession()
        for k, v in config["conf"].items():
            s.set(k, v)
        s.set("spark.rapids.sql.trace.enabled", True)
        s.set("spark.rapids.sql.trace.level", "kernel")
        syncs.install()
        faults.reset_counters()
        MX.reset_counters()

        def q5():
            return tpch_data.QUERIES["q5"](s, data_dir)

        def traced_collect(read_counters, trace_dir=None):
            """One collect of a DataFrame built anew: its rows, spans,
            wall time, what compiled and what the plan cache said. The
            spans are taken before any counter is read."""
            recorder.reset()
            before = (clock.programs, plan_cache.counters())
            if trace_dir:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
            try:
                df = q5()
                t0 = time.perf_counter()
                rows = df.collect()
                wall_ns = (time.perf_counter() - t0) * 1e9
            finally:
                if trace_dir:
                    jax.profiler.stop_trace()
            run = {"rows": rows, "events": recorder.events(),
                   "wall_ns": wall_ns,
                   "compiled": clock.programs - before[0],
                   "plan_cache": {
                       k: plan_cache.counters().get(k, 0) - before[1].get(k, 0)
                       for k in ("planCacheHits", "planCacheMisses")}}
            if read_counters:
                recorder.reset()
                run["exchanges"] = _exchange_metrics(df)
                run["process"] = MX.counters()
                run["read_events"] = recorder.events()
            return run

        first = q5()
        out["host_nodes"] = run.plan_host_nodes(first)
        out["execs"] = _execs(first)
        out["first_rows"] = first.collect()             # compiles
        trace_dir = str(tmp_path_factory.mktemp("mesh4_trace"))
        out["skipped"] = traced_collect(True, trace_dir)
        out["annotations"] = _annotations(trace_dir)
        out["unread"] = traced_collect(False)
        # the same query with no counter at all: what a blocking read for
        # a counter's sake would show against
        with pytest.MonkeyPatch.context() as off:
            off.setattr(MX, "_count_exchange", lambda *a, **k: None)
            out["uncounted"] = traced_collect(False)
        # the other branch: every exchange pulls its counts matrix
        mp.setattr(MX, "TWO_PHASE_MIN_SHARD_ROWS", 8)
        q5().collect()                                  # compiles
        out["two_phase"] = traced_collect(True)
        out["faults"] = faults.counters()

        inproc = TpuSession()
        for k, v in config["conf"].items():
            inproc.set(k, v)
        inproc.set("spark.rapids.sql.shuffle.transport", "inprocess")
        out["inprocess_rows"] = tpch_data.QUERIES["q5"](
            inproc, data_dir).collect()
        out["reference"] = tpch_data.pandas_query("q5", data_dir)
        yield out
    finally:
        clock.close()
        mp.undo()
        recorder.configure(False)


def test_configuration_file_is_the_deployment(cell):
    entry, config = cell["entry"], cell["config"]
    assert entry["reduced"] == ["scale"] == config["reduced"]
    assert config["conf"] == {
        "spark.rapids.sql.variableFloatAgg.enabled": True,
        "spark.rapids.sql.hasNans": False,
        "spark.rapids.sql.shuffle.transport": "mesh",
        "spark.rapids.sql.autoBroadcastJoinThreshold": -1}
    assert config["chips"] == MESH == config["partitions"]
    assert set(config["tables"]) == set(
        cell["tpch_data"].QUERY_COLUMNS["q5"])
    assert "autoBroadcastJoinThreshold" in config["assumed"]
    assert "scale" in config["reduced_why"]


def test_rows_equal_the_plain_reference(cell):
    compare, tpch_data = cell["compare"], cell["tpch_data"]
    answers = [{"query": "q5", "rows": cell[k]["rows"]}
               for k in ("skipped", "unread", "uncounted", "two_phase")]
    answers.append({"query": "q5", "rows": cell["first_rows"]})
    verdict = compare.judge(answers, {"q5": cell["reference"]},
                            tpch_data.SET_COMPARE, sent=len(answers))
    assert verdict["correct"], verdict
    assert len(cell["reference"]) > 0


def test_rows_equal_the_inprocess_transport(cell):
    gap = cell["compare"].answer_gap(
        cell["skipped"]["rows"], cell["inprocess_rows"], as_set=True)
    assert gap is not None and gap <= 1e-9


def test_plan_every_join_shuffled_every_exchange_on_the_mesh(cell):
    from spark_rapids_tpu.parallel.exchange import ShuffleExchangeExec
    names = collections.Counter(type(e).__name__ for e in cell["execs"])
    assert not [n for n in names if "Broadcast" in n], names
    assert names["ShuffledHashJoinExec"] == 5, names
    meshed = [e for e in cell["execs"] if isinstance(e, MX.MeshExchangeExec)]
    assert len(meshed) == 11
    assert all(e.partitioning.num_partitions == MESH for e in meshed)
    # what is left to the single-process exchange hashes nothing
    for e in cell["execs"]:
        if isinstance(e, ShuffleExchangeExec):
            assert "Hash" not in type(e.partitioning).__name__
    assert cell["host_nodes"] == 0      # default placement conf


def test_no_degrade_no_skip_no_fold(cell):
    for name in ("meshDegrades", "meshCollectiveSkipped",
                 "meshPartitionFolds"):
        assert not cell["faults"].get(name), cell["faults"]
    for branch in ("skipped", "two_phase"):
        ex = cell[branch]["exchanges"]
        assert len(ex) == 11
        for v in ex:
            assert v["meshExchanges"] == 1
            assert v["meshShardDevices"] == MESH
            assert not v.get("meshDegrades")


@pytest.mark.parametrize("run", ["skipped", "unread", "uncounted",
                                 "two_phase"])
def test_dataframe_built_anew_hits_the_plan_cache(cell, run):
    got = cell[run]
    assert got["compiled"] == 0
    assert got["plan_cache"] == {"planCacheHits": 1, "planCacheMisses": 0}


@pytest.mark.parametrize("branch,absent", [("skipped", {"counts", "unfold"}),
                                           ("two_phase", {"unfold"})])
def test_every_phase_once_per_exchange(cell, branch, absent):
    spans = [e for e in _spans(cell[branch]["events"])
             if e[2] == "mesh-exchange"]
    per_phase = collections.Counter(e[1] for e in spans)
    assert set(per_phase) == set(PHASES) - absent
    assert set(per_phase.values()) == {11}
    # under the operator's own timed section, one exchange each
    by_sid = {e[8]: e for e in _spans(cell[branch]["events"])}
    parents = collections.Counter(e[9] for e in spans)
    assert len(parents) == 11
    for sid, n in parents.items():
        assert n == len(per_phase)
        assert by_sid[sid][1:3] == ("MeshExchangeExec", "shuffle")


@pytest.mark.parametrize("branch", ["skipped", "two_phase"])
def test_phases_are_never_nested_and_hold_no_childs_work(cell, branch):
    events = _spans(cell[branch]["events"])
    by_sid = {e[8]: e for e in events}
    phases = sorted((e for e in events if e[2] == "mesh-exchange"),
                    key=lambda e: e[3])
    # one thread, one after the other: no overlap in time
    assert len({e[5] for e in phases}) == 1
    for a, b in zip(phases, phases[1:]):
        assert a[3] + a[4] <= b[3], (a[1], b[1])
    # nothing the program does for a child hangs under a phase: only the
    # blocking read of the counts matrix, and what the runtime interposes
    inside = collections.Counter()
    for e in events:
        p = by_sid.get(e[9])
        while p is not None and p[2] != "mesh-exchange":
            p = by_sid.get(p[9])
        if p is not None:
            inside[(p[1], e[2])] += 1
    assert {cat for _, cat in inside} <= {"sync", "runtime", "compile"}
    assert inside.get(("counts", "sync"), 0) == \
        (11 if branch == "two_phase" else 0)
    assert not any(ph != "counts" and cat == "sync" for ph, cat in inside)
    # so the category's sum is a time, and less than the query's
    assert sum(e[4] for e in phases) <= cell[branch]["wall_ns"]


def test_each_phase_is_a_profiler_annotation(cell):
    names = collections.Counter(cell["annotations"])
    for phase in ("shard", "pids", "collective", "land"):
        assert names[f"mesh-exchange:{phase}"] == 11
    assert names["MeshExchangeExec:shuffleTime"] == 11


@pytest.mark.parametrize("branch", ["skipped", "two_phase"])
def test_byte_counters(cell, branch):
    ex = cell[branch]["exchanges"]
    for v in ex:
        landed = {k: b for k, b in v.items()
                  if k.startswith("meshLandedBytes.dev")}
        assert 0 < v["meshLiveBytes"] <= v["meshWireBytes"]
        assert sum(landed.values()) == v["meshLiveBytes"]
        # today every shard lands on the first device (ROADMAP B1)
        assert set(landed) == {"meshLandedBytes.dev0"}
    if branch == "two_phase":
        # the pulled counts size the pieces: the same live bytes, less wire
        skipped = cell["skipped"]["exchanges"]
        assert sorted(v["meshLiveBytes"] for v in ex) == \
            sorted(v["meshLiveBytes"] for v in skipped)
        assert sum(v["meshWireBytes"] for v in ex) < \
            sum(v["meshWireBytes"] for v in skipped)


def test_process_counters_are_the_operators_totals(cell):
    # before the first reading: the first collect and ``skipped``; before
    # the second: ``unread`` (``uncounted`` counts nothing) and two
    # collects on the other branch
    one, two = cell["skipped"]["exchanges"], cell["two_phase"]["exchanges"]
    first, second = cell["skipped"]["process"], cell["two_phase"]["process"]
    assert first["meshExchanges"] == 2 * 11
    assert second["meshExchanges"] == 5 * 11
    for name in ("meshLiveBytes", "meshWireBytes", "meshLandedBytes.dev0"):
        assert first[name] == 2 * sum(v[name] for v in one)
        assert second[name] - first[name] == \
            sum(v[name] for v in one) + 2 * sum(v[name] for v in two)
    assert second["meshLiveBytes"] == second["meshLandedBytes.dev0"]


@pytest.mark.parametrize("branch", ["skipped", "two_phase"])
def test_one_batched_put_a_side_an_exchange(cell, branch):
    for v in cell[branch]["exchanges"]:
        assert v["meshHandoutPuts"] == v["meshLandPuts"] == \
            v["meshExchanges"] == 1
    first, second = cell["skipped"]["process"], cell["two_phase"]["process"]
    assert first["meshHandoutPuts"] == first["meshLandPuts"] == \
        first["meshExchanges"] == 2 * 11
    # ``uncounted`` stubs out ``_count_exchange`` (``meshExchanges``) alone
    assert second["meshHandoutPuts"] == second["meshLandPuts"] == \
        second["meshExchanges"] + 11


def test_counters_add_no_blocking_read(cell):
    def reads(events):
        return sum(e[2] == "sync" for e in _spans(events))
    n = reads(cell["uncounted"]["events"])
    assert n > 0
    assert reads(cell["skipped"]["events"]) == n    # counters read after
    assert reads(cell["unread"]["events"]) == n     # counters never read
    # where the counts phase is skipped the live rows wait on the device:
    # read when the counters are, once an exchange, outside any query
    late = _spans(cell["skipped"]["read_events"])
    by_sid = {e[8]: e for e in late}
    gets = collections.Counter(
        by_sid[e[9]][1] for e in late
        if e[2] == "sync" and e[1] == "device_get")
    # the first collect's exchanges and this one's (the rest: the
    # aggregate's own deferred flags)
    assert gets["landed-rows"] == 2 * 11
    assert set(gets) <= {"landed-rows", "slot-flags"}
    assert not any(e[1] == "collect" for e in late)
    # where the counts matrix is pulled anyway nothing is left to read:
    # the second reading finds the ``unread`` collect's exchanges alone
    assert sum(e[1] == "landed-rows" for e in
               _spans(cell["two_phase"]["read_events"])) == 11


def test_rows_in_are_rows_out(monkeypatch):
    """Per exchange: the live bytes counted are the rows that went in,
    and every destination's landed rows come out of it."""
    import numpy as np
    from spark_rapids_tpu.columnar import dtypes as dt
    monkeypatch.setattr(MX, "mesh_size", lambda: MESH)
    monkeypatch.setattr(
        MX, "mesh_for", lambda ctx: ctx.cache.setdefault(
            "mesh:singleton", M.make_mesh(MESH)))
    rng = np.random.default_rng(SEED)
    n = 3000
    data = {"k": rng.integers(0, 1000, n).tolist(),
            "v": rng.normal(size=n).tolist(),
            "tag": [f"t{i % 13}" for i in range(n)]}
    for min_rows in (MX.TWO_PHASE_MIN_SHARD_ROWS, 8):
        monkeypatch.setattr(MX, "TWO_PHASE_MIN_SHARD_ROWS", min_rows)
        s = TpuSession()
        s.set("spark.rapids.sql.shuffle.transport", "mesh")
        df = s.create_dataframe(
            data, [("k", dt.INT64), ("v", dt.FLOAT64), ("tag", dt.STRING)],
            num_partitions=3).repartition(MESH, "k")
        rows = df.collect()
        assert sorted(rows) == sorted(zip(data["k"], data["v"], data["tag"]))
        v, = _exchange_metrics(df)
        # int64 + float64 + their validity bytes, string bytes + length +
        # validity: the decoded row as the device holds it
        width = v["meshLiveBytes"] // n
        assert v["meshLiveBytes"] == n * width and width >= 8 + 8 + 2
        assert v["meshLandedBytes.dev0"] == v["meshLiveBytes"]
        assert v["meshWireBytes"] % (MESH * MESH * width) == 0
        assert v["meshWireBytes"] >= v["meshLiveBytes"]


def test_process_counters_lose_no_update_under_threads():
    """Exchanges of concurrent queries count from their own threads while
    a reader drains: more workers than cores, a short switch interval,
    and totals that a lost update would break."""
    import sys
    import threading
    from spark_rapids_tpu.ops.base import Metrics
    workers, each = 4 * (os.cpu_count() or 4), 200
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    MX.reset_counters()
    stop = threading.Event()

    def count():
        m = Metrics("MeshExchangeExec")
        for _ in range(each):
            MX._count_exchange(m, [1, 2, 3, 4], [0, 0, 0, 0], 10, 1000)
        assert m.values["meshLiveBytes"] == each * 100

    def read():
        while not stop.is_set():
            c = MX.counters()
            assert c.get("meshLiveBytes", 0) <= c.get("meshWireBytes", 0)

    try:
        reader = threading.Thread(target=read)
        threads = [threading.Thread(target=count) for _ in range(workers)]
        reader.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        stop.set()
        reader.join(timeout=60)
        assert not reader.is_alive() and not any(t.is_alive() for t in threads)
        total = MX.counters()
        assert total["meshExchanges"] == workers * each
        assert total["meshLiveBytes"] == workers * each * 100
        assert total["meshWireBytes"] == workers * each * 1000
        assert total["meshLandedBytes.dev0"] == total["meshLiveBytes"]
    finally:
        stop.set()
        sys.setswitchinterval(old)
        MX.reset_counters()
