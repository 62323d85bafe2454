"""The benchmark's generator draws what the program's draws."""

import os

import pyarrow.parquet as papq
import pytest

import tpch_data


@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_same_tables_as_the_original(tmp_path, seed):
    orig = pytest.importorskip("spark_rapids_tpu.benchmarks.tpch")
    mine, theirs = str(tmp_path / "mine"), str(tmp_path / "theirs")
    rows = tpch_data.generate(mine, scale=0.01, seed=seed)
    assert rows == orig.generate(theirs, scale=0.01, seed=seed)
    for table in rows:
        for f in sorted(os.listdir(os.path.join(theirs, table))):
            a = papq.read_table(os.path.join(mine, table, f))
            b = papq.read_table(os.path.join(theirs, table, f))
            assert a.schema.equals(b.schema), (table, f)
            assert a.equals(b), (table, f)


def test_a_cell_gets_only_its_tables(tmp_path):
    whole, part = str(tmp_path / "whole"), str(tmp_path / "part")
    tpch_data.generate(whole, scale=0.01, seed=3)
    rows = tpch_data.generate(part, scale=0.01, seed=3,
                              tables=["lineitem", "customer"])
    assert sorted(os.listdir(part)) == ["customer", "lineitem"]
    assert set(rows) == {"customer", "lineitem"}
    for table in rows:      # and they are the whole set's tables
        assert papq.read_table(os.path.join(part, table)).equals(
            papq.read_table(os.path.join(whole, table)))


def test_every_listed_query_has_a_reference(tmp_path):
    d = str(tmp_path)
    tpch_data.generate(d, scale=0.01, seed=1)
    for q in tpch_data.QUERY_COLUMNS:
        assert q in tpch_data.QUERIES
        assert tpch_data.pandas_query(q, d)
