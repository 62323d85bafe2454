"""Planner-lowered collective shuffle: full DataFrame queries execute over
the 8-virtual-CPU-device mesh (conftest) with mesh.enabled, and results
match the single-process exchange and the host oracle.
"""

import numpy as np
import pytest

from spark_rapids_tpu.api.dataframe import TpuSession
from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.plan.logical import agg_count, agg_sum, col


def _session(mesh: bool):
    s = TpuSession()
    s.set("spark.rapids.sql.mesh.enabled", mesh)
    return s


def _tables(s, n=800, parts=5):
    rng = np.random.default_rng(11)
    facts = s.create_dataframe(
        {"k": rng.integers(0, 37, n).tolist(),
         "v": rng.integers(-100, 100, n).tolist(),
         "tag": [f"t{i % 7}" for i in range(n)]},
        [("k", dt.INT64), ("v", dt.INT64), ("tag", dt.STRING)],
        num_partitions=parts)
    dims = s.create_dataframe(
        {"dk": list(range(37)), "w": [i * 10 for i in range(37)]},
        [("dk", dt.INT64), ("w", dt.INT64)], num_partitions=2)
    return facts, dims


def _q_groupby(s):
    facts, _ = _tables(s)
    return facts.group_by("k").agg(
        agg_sum(col("v")).alias("sv"), agg_count().alias("n")) \
        .order_by("k")


def _q_join_agg(s):
    facts, dims = _tables(s)
    j = facts.join_on(dims, ["k"], ["dk"], strategy="shuffle")
    return j.group_by("tag").agg(
        agg_sum(col("v") + col("w")).alias("s"),
        agg_count().alias("n")).order_by("tag")


@pytest.mark.parametrize("qf", [_q_groupby, _q_join_agg],
                         ids=["groupby", "join_agg"])
def test_mesh_matches_single_process(qf):
    mesh_rows = qf(_session(True)).collect()
    single_rows = qf(_session(False)).collect()
    host_rows = qf(_session(False)).collect_host()
    assert mesh_rows == single_rows
    assert mesh_rows == host_rows


def test_mesh_exchange_in_plan():
    from spark_rapids_tpu.parallel.mesh_exchange import MeshExchangeExec
    q = _q_groupby(_session(True))
    phys = q._physical()

    def find(e):
        if isinstance(e, MeshExchangeExec):
            return True
        return any(find(c) for c in e.children)
    assert find(phys.root), "mesh exchange not planned"


def test_mesh_repartition():
    s = _session(True)
    facts, _ = _tables(s)
    got = sorted(facts.repartition(8, "k").collect())
    want = sorted(facts.collect())
    assert got == want


def test_mesh_shape_mismatch_folds_onto_mesh():
    """A mesh exchange whose partition count != mesh size FOLDS the
    logical partitions onto the devices (ISSUE 6 satellite — counter
    meshPartitionFolds) instead of degrading to the single-process
    shuffle; results stay correct partition-for-partition."""
    from spark_rapids_tpu import faults
    from spark_rapids_tpu.parallel.mesh_exchange import MeshExchangeExec
    from spark_rapids_tpu.parallel.partitioning import HashPartitioning

    s = _session(True)
    q = _q_groupby(s)
    phys = q._physical()

    def rewrite(e):
        # Force the shape mismatch: re-point every planned mesh
        # exchange at a 3-way partitioning on the 8-device mesh.
        if isinstance(e, MeshExchangeExec):
            e.partitioning = HashPartitioning(
                e.partitioning.keys, 3)
        for c in e.children:
            rewrite(c)
    rewrite(phys.root)
    faults.reset_counters()
    got = phys.collect()
    want = _q_groupby(_session(False)).collect()
    assert got == want
    c = faults.counters()
    assert c.get("meshPartitionFolds", 0) >= 1
    assert not c.get("meshCollectiveSkipped")
    assert not c.get("meshDegrades")


def test_mesh_unsupported_partitioning_degrades_observably(caplog):
    """Shapes the collective genuinely cannot run (a non-jittable
    partitioning) still degrade OBSERVABLY — warning +
    meshCollectiveSkipped counter + single-process fallback — never a
    silent skip or an assert."""
    import logging

    from spark_rapids_tpu import faults
    from spark_rapids_tpu.parallel.mesh_exchange import MeshExchangeExec
    from spark_rapids_tpu.parallel.partitioning import HashPartitioning

    class HostBoundPartitioning(HashPartitioning):
        @property
        def jittable(self):
            return False

    s = _session(True)
    q = _q_groupby(s)
    phys = q._physical()

    def rewrite(e):
        if isinstance(e, MeshExchangeExec):
            e.partitioning = HostBoundPartitioning(
                e.partitioning.keys, e.partitioning.num_partitions)
        for c in e.children:
            rewrite(c)
    rewrite(phys.root)
    faults.reset_counters()
    with caplog.at_level(logging.WARNING, "spark_rapids_tpu"):
        got = phys.collect()
    want = _q_groupby(_session(False)).collect()
    assert got == want
    assert faults.counters().get("meshCollectiveSkipped", 0) >= 1
    assert any("mesh collective skipped" in r.message
               for r in caplog.records)


def test_two_phase_sized_exchange(monkeypatch):
    """The sizes-then-data mesh shuffle (SURVEY 7 hard part 6): with the
    threshold lowered, the counts collective sizes the data all_to_all's
    piece capacity below the worst case and results stay correct."""
    import spark_rapids_tpu.parallel.mesh_exchange as MX
    from spark_rapids_tpu import FLOAT64, INT64
    from spark_rapids_tpu.api.dataframe import TpuSession
    from spark_rapids_tpu.plan.logical import agg_sum, col
    monkeypatch.setattr(MX, "TWO_PHASE_MIN_SHARD_ROWS", 8)
    import numpy as np
    rng = np.random.default_rng(5)
    n = 4096
    data = {"k": rng.integers(0, 97, n).tolist(),
            "v": rng.normal(size=n).tolist()}
    s = TpuSession()
    s.set("spark.rapids.sql.mesh.enabled", True)
    s.set("spark.rapids.sql.variableFloatAgg.enabled", True)
    df = s.create_dataframe(data, [("k", INT64), ("v", FLOAT64)],
                            num_partitions=8) \
        .group_by("k").agg(agg_sum(col("v")).alias("sv"))
    got = sorted(df.collect())
    want = sorted(df.collect_host())
    assert len(got) == 97
    for a, b in zip(got, want):
        assert a[0] == b[0] and abs(a[1] - b[1]) < 1e-9
