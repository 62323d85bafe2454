"""Engine-integrated spill: a real DataFrame query under a deliberately
tiny device budget completes correctly BY spilling shuffle buckets
(ref: RapidsCachingWriter inserting shuffle buffers
into the spillable device store, RapidsShuffleInternalManager.scala:57)."""

import numpy as np

from spark_rapids_tpu.api.dataframe import TpuSession
from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.ops.base import ExecContext
from spark_rapids_tpu.plan.logical import agg_count, agg_sum, col


def _df(session, n=4000, parts=4):
    rng = np.random.default_rng(3)
    return session.create_dataframe(
        {"k": rng.integers(0, 50, n).tolist(),
         "v": rng.integers(0, 1000, n).tolist()},
        [("k", dt.INT64), ("v", dt.INT64)], num_partitions=parts)


def test_groupby_spills_and_stays_correct():
    s = TpuSession()
    # ~8 KiB budget: below even one exchange's bucket total, so buckets
    # spill host-ward DURING materialization and must restore on read.
    s.set("spark.rapids.memory.tpu.budgetBytes", 8 * 1024)
    # This asserts the IN-PROCESS transport's map-side spill behavior
    # (hostfile map shards live in spool files, not the catalog), so
    # pin the transport against the SRT_SHUFFLE_TRANSPORT matrix env.
    s.set("spark.rapids.sql.shuffle.transport", "inprocess")
    q = _df(s).group_by("k").agg(agg_sum(col("v")).alias("sv"),
                                 agg_count().alias("n")).order_by("k")
    phys = q._physical()
    ctx = ExecContext(phys.conf)
    got = phys.root.collect(ctx, device=True)
    spills = ctx.catalog.metrics["spill_to_host"]
    restores = ctx.catalog.metrics["restore_from_host"]
    ctx.close()
    assert spills > 0, "tiny budget must force shuffle-bucket spills"
    assert restores > 0
    assert got == q.collect_host()


def test_no_raw_batches_in_cache():
    """ctx.cache holds transport sessions whose shards are spillable
    handles, not pinned device batches."""
    from spark_rapids_tpu.memory.stores import SpillableBatch
    from spark_rapids_tpu.parallel.transport.base import ShuffleSession
    s = TpuSession()
    q = _df(s).group_by("k").agg(agg_count().alias("n"))
    phys = q._physical()
    ctx = ExecContext(phys.conf)
    phys.root.collect(ctx, device=True)
    seen = 0
    for key, val in ctx.cache.items():
        if key.startswith("shuffle:") and not key.endswith(":rows"):
            assert isinstance(val, ShuffleSession), \
                f"raw materialization hoarded in {key}"
            seen += 1
            for bucket in getattr(val, "buckets", []):
                for item in bucket:
                    assert isinstance(item, SpillableBatch), \
                        f"raw batch hoarded in {key}"
    assert seen >= 1
    ctx.close()
