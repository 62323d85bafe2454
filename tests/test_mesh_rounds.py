"""The mesh exchange in rounds (``mesh_exchange.MESH_ROUND_ROWS``) on four
of the suite's virtual CPU devices: a device's dealt batches go over in
rounds of shards of a bounded capacity, each landing one more batch of
every partition, and every output partition holds the rows the
single-process ``ShuffleExchangeExec`` gives it over the same child."""

import collections
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.host import (HostBatch, device_to_host,
                                            host_to_device)
from spark_rapids_tpu.exprs.base import BoundReference
from spark_rapids_tpu.monitoring import recorder
from spark_rapids_tpu.ops.base import Exec, ExecContext
from spark_rapids_tpu.parallel import mesh as M
from spark_rapids_tpu.parallel import mesh_exchange as MX
from spark_rapids_tpu.parallel.exchange import ShuffleExchangeExec
from spark_rapids_tpu.parallel.partitioning import HashPartitioning

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_DEV = 4
BOUND = 96                  # a rung of bucket_capacity's ladder
SEED = 2147483943
PHASES = {"shard", "pids", "counts", "collective", "land", "unfold"}
NUM = [("k", dt.INT64), ("x", dt.FLOAT64)]
STR = NUM + [("s", dt.STRING)]


class Dealt(Exec):
    """A child whose partitions are the given device batches."""

    def __init__(self, schema, parts):
        super().__init__()
        self._schema, self.parts = tuple(schema), parts

    @property
    def schema(self):
        return self._schema

    def num_partitions(self, ctx):
        return len(self.parts)

    def execute_device(self, ctx, partition):
        yield from self.parts[partition]


def _batch(rng, schema, rows, capacity, width=8, live_share=None):
    data = {"k": rng.integers(0, 500, rows).tolist(),
            "x": [None if rng.random() < 0.1 else float(v)
                  for v in rng.normal(size=rows)]}
    if any(t.is_string for _, t in schema):
        data["s"] = ["".join(rng.choice(list("abcdefgh"),
                                        rng.integers(0, width + 1)))
                     for _ in range(rows)]
    b = host_to_device(HostBatch.from_pydict(schema, data),
                       capacity=capacity, string_widths={"s": width})
    if live_share is not None:
        b = b.with_sel(jnp.asarray(rng.random(capacity) < live_share))
    return b


def _case(name, rng):
    """(schema, child partitions, output partitions) of each case."""
    caps = (8, 16, 24, 32, 48, 64)
    if name == "one_batch_a_device":
        return NUM, [[_batch(rng, NUM, 40, 64)] for _ in range(N_DEV)], N_DEV
    if name == "sixteen_uneven_batches_a_device":
        parts = []
        for _ in range(2 * N_DEV):
            parts.append([_batch(rng, NUM, int(rng.integers(1, c + 1)), c)
                          for c in rng.choice(caps, 8)])
        return NUM, parts, N_DEV
    if name == "one_device_with_none":
        parts = [[_batch(rng, NUM, 30, 32) for _ in range(5)]
                 for _ in range(N_DEV - 1)]
        return NUM, parts, N_DEV
    if name == "string_column":
        parts = [[_batch(rng, STR, 20, 32, width=w) for w in (4, 8, 16, 8)]
                 for _ in range(N_DEV)]
        return STR, parts, N_DEV
    if name == "selection_vectors":
        parts = [[_batch(rng, NUM, 48, 48, live_share=0.4)
                  for _ in range(4)] for _ in range(N_DEV)]
        return NUM, parts, N_DEV
    if name == "fold":
        parts = [[_batch(rng, STR, 30, 32) for _ in range(4)]
                 for _ in range(N_DEV)]
        return STR, parts, 2 * N_DEV + 1
    if name == "batch_larger_than_the_bound":
        parts = [[_batch(rng, NUM, 20, 32), _batch(rng, NUM, 200, 256),
                  _batch(rng, NUM, 20, 32)] for _ in range(N_DEV)]
        return NUM, parts, N_DEV
    raise KeyError(name)


CASES = ("one_batch_a_device", "sixteen_uneven_batches_a_device",
         "one_device_with_none", "string_column", "selection_vectors",
         "fold", "batch_larger_than_the_bound")


def _rows(batches, names):
    out = []
    for b in batches:
        out += device_to_host(b, names).to_pylist()
    return sorted(out, key=repr)


def _exchange(schema, parts, num_out):
    """Both exchanges over one child: (mesh rows by partition, single-
    process rows by partition, the mesh's landed batches by partition,
    the process counters' change, the recorded spans)."""
    names = [n for n, _ in schema]
    child = Dealt(schema, parts)
    part = HashPartitioning([BoundReference(0, dt.INT64)], num_out)
    ctx = ExecContext()
    ctx.cache["mesh:singleton"] = M.make_mesh(N_DEV)
    MX.reset_counters()
    recorder.configure(True)
    recorder.reset()
    try:
        mesh_ex = MX.MeshExchangeExec(child, part)
        landed = [list(mesh_ex.execute_device(ctx, p))
                  for p in range(num_out)]
        events = recorder.events()
    finally:
        recorder.configure(False)
    counts = MX.counters()
    single = ShuffleExchangeExec(child, part)
    sctx = ExecContext()
    want = [_rows(single.execute_device(sctx, p), names)
            for p in range(num_out)]
    got = [_rows(bs, names) for bs in landed]
    return got, want, landed, counts, [e for e in events if e[0] == "X"]


@pytest.fixture(autouse=True)
def _bound(monkeypatch):
    if len(jax.devices()) < N_DEV:
        pytest.skip(f"needs {N_DEV} devices")
    monkeypatch.setattr(MX, "MESH_ROUND_ROWS", BOUND)
    yield
    MX.reset_counters()


@pytest.mark.parametrize("case", CASES)
def test_each_partition_holds_the_single_process_exchanges_rows(case):
    rng = np.random.default_rng(SEED + CASES.index(case))
    schema, parts, num_out = _case(case, rng)
    got, want, landed, counts, spans = _exchange(schema, parts, num_out)
    assert got == want
    assert sum(map(len, got)) == sum(
        int(jnp.sum(b.row_mask())) for p in parts for b in p)
    # the rounds ``_rounds`` cuts the deal into, each landing one more
    # batch of every partition
    per_dev = [[] for _ in range(N_DEV)]
    for cp, p in enumerate(parts):
        per_dev[cp % N_DEV] += p
    rounds = MX._rounds(per_dev, BOUND)
    assert counts["meshRounds"] == len(rounds)
    assert counts["meshExchanges"] == 1
    assert [len(bs) for bs in landed] == [len(rounds)] * num_out
    if case == "one_batch_a_device":
        assert len(rounds) == 1
    else:
        assert len(rounds) > 1
    biggest = max(b.capacity for p in parts for b in p)
    if case == "batch_larger_than_the_bound":
        assert counts["meshRoundRowsMax"] == biggest > BOUND
    else:
        assert counts["meshRoundRowsMax"] <= BOUND
    # the counters keep their ratios across rounds
    assert counts["meshHandoutPuts"] == counts["meshLandPuts"] == \
        counts["meshRounds"]
    assert 0 < counts["meshLiveBytes"] <= counts["meshWireBytes"]
    assert counts["meshLandedBytes.dev0"] == counts["meshLiveBytes"]
    # every phase span names its round, once a round at most
    phases = [e for e in spans if e[2] == "mesh-exchange"]
    by_round = collections.Counter((e[7]["round"], e[1]) for e in phases)
    assert {r for r, _ in by_round} == set(range(len(rounds)))
    assert set(by_round.values()) == {1}
    assert {ph for _, ph in by_round} <= PHASES
    assert ("unfold" in {ph for _, ph in by_round}) == (case == "fold")


def test_phases_are_never_nested_and_no_span_of_theirs_encloses_a_round():
    rng = np.random.default_rng(SEED)
    schema, parts, num_out = _case("sixteen_uneven_batches_a_device", rng)
    *_, spans = _exchange(schema, parts, num_out)
    phases = sorted((e for e in spans if e[2] == "mesh-exchange"),
                    key=lambda e: e[3])
    assert len({e[1] for e in phases}) >= 4
    for a, b in zip(phases, phases[1:]):
        assert a[3] + a[4] <= b[3], (a[1], b[1])
    by_sid = {e[8]: e for e in spans}
    for e in phases:
        p = by_sid.get(e[9])
        while p is not None:
            assert p[2] != "mesh-exchange", (e[1], p[1])
            p = by_sid.get(p[9])


class _Cap:
    def __init__(self, capacity):
        self.capacity = capacity


@pytest.mark.parametrize("deal,rounds,largest", [
    # TPC-H Q5 at SF1: two 786,432-row scan batches a device, one round
    ([786_432] * 2, 1, 1_572_864),
    # at SF10: two partitions of seven 1,048,576-row units and a tail of
    # 196,608 a device: a unit a round, the tail with the unit before it
    (([1 << 20] * 7 + [196_608]) * 2, 14, 1_245_184),
])
def test_the_bound_as_shipped(monkeypatch, deal, rounds, largest):
    monkeypatch.undo()
    dealt = [[_Cap(c) for c in deal] for _ in range(N_DEV)]
    cut = MX._rounds(dealt, MX.MESH_ROUND_ROWS)
    assert len(cut) == rounds
    sums = [sum(b.capacity for b in r[0]) for r in cut]
    assert max(sums) == largest <= MX.MESH_ROUND_ROWS
    # every batch goes over once, in the order it was dealt
    assert [b for r in cut for b in r[0]] == dealt[0]


def test_one_round_an_exchange_under_the_sf1_mesh_conf(monkeypatch,
                                                      tmp_path):
    """Q5 under ``tpch_sf1_mesh4_2x2.json``'s conf, with the bound as
    shipped: every exchange is one round."""
    import importlib.util
    from spark_rapids_tpu.api.dataframe import TpuSession
    monkeypatch.undo()
    spec = importlib.util.spec_from_file_location(
        "benchmark_tpch_data", os.path.join(ROOT, "benchmark", "tpch_data.py"))
    tpch_data = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tpch_data)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "tpch_sf1_mesh4_2x2.json")) as f:
        config = json.load(f)
    tpch_data.generate(str(tmp_path), scale=0.01, seed=SEED,
                       files_per_table=config["files_per_table"],
                       tables=sorted(tpch_data.QUERY_COLUMNS["q5"]))
    monkeypatch.setattr(MX, "mesh_size", lambda: N_DEV)
    monkeypatch.setattr(MX, "mesh_for", lambda ctx: ctx.cache.setdefault(
        "mesh:singleton", M.make_mesh(N_DEV)))
    s = TpuSession()
    for k, v in config["conf"].items():
        s.set(k, v)
    MX.reset_counters()
    rows = tpch_data.QUERIES["q5"](s, str(tmp_path)).collect()
    assert rows
    c = MX.counters()
    assert c["meshExchanges"] == 11
    assert c["meshRounds"] == c["meshExchanges"]
    assert c["meshRoundRowsMax"] <= MX.MESH_ROUND_ROWS
