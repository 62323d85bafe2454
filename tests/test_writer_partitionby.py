"""Dynamic-partition writes + write stats
(GpuFileFormatWriter.scala:338, BasicColumnarWriteStatsTracker.scala:180)."""

import os

import pytest

from spark_rapids_tpu import FLOAT64, INT64, STRING
from spark_rapids_tpu.api.dataframe import TpuSession


def _df(s):
    return s.create_dataframe(
        {"k": ["a", "b", "a", "c", "b", "a"],
         "n": [1, 2, 3, 4, 5, 6],
         "v": [1.0, 2.0, 3.0, 4.0, 5.0, 6.0]},
        [("k", STRING), ("n", INT64), ("v", FLOAT64)],
        num_partitions=2)


def test_partition_by_layout_and_stats(tmp_path):
    s = TpuSession()
    out = str(tmp_path / "out")
    w = _df(s).write
    stats = w.partition_by("k").parquet(out)
    dirs = sorted(d for d in os.listdir(out) if d.startswith("k="))
    assert dirs == ["k=a", "k=b", "k=c"]
    # Partition column is NOT in the files (Hive layout). Read the bare
    # file (ParquetFile), not read_table: pyarrow >= 22 re-infers the
    # hive partition column from the k=a path segment even for a single
    # file, which would mask a writer that wrongly kept the column.
    import pyarrow.parquet as papq
    files = [os.path.join(out, "k=a", f)
             for f in os.listdir(os.path.join(out, "k=a"))]
    t = papq.ParquetFile(files[0]).read()
    assert t.schema.names == ["n", "v"]
    assert stats["numOutputRows"] == 6
    assert stats["numParts"] == 3
    assert stats["numFiles"] >= 3
    assert stats["numOutputBytes"] > 0
    # Values routed to the right directory.
    rows_a = sum(papq.read_table(os.path.join(out, "k=a", f)).num_rows
                 for f in os.listdir(os.path.join(out, "k=a")))
    assert rows_a == 3


def test_partition_by_roundtrip_read(tmp_path):
    s = TpuSession()
    out = str(tmp_path / "rt")
    _df(s).write.partition_by("k").parquet(out)
    parts = []
    for d in sorted(os.listdir(out)):
        full = os.path.join(out, d)
        if not os.path.isdir(full):
            continue
        for f in sorted(os.listdir(full)):
            parts.append(os.path.join(full, f))
    back = s.read.parquet(*parts).collect()
    assert sorted(r[0] for r in back) == [1, 2, 3, 4, 5, 6]


def test_plain_write_stats(tmp_path):
    s = TpuSession()
    out = str(tmp_path / "plain")
    w = _df(s).write
    stats = w.parquet(out)
    assert stats["numOutputRows"] == 6
    assert stats["numFiles"] == 2          # one per engine partition
    assert stats["numParts"] == 0
