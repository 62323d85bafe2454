"""All 22 TPC-H queries, device engine vs the pandas oracle
(TpchLikeSpark.scala:293-onward parity).

Each query runs through the full planner/device pipeline on the CPU
backend at a small scale factor and must match the independent pandas
implementation (ordered compare unless the query sorts by a computed
float — benchmarks/tpch.py check_result)."""

import pytest

from spark_rapids_tpu.api.dataframe import TpuSession
from spark_rapids_tpu.benchmarks import tpch


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("tpch22")
    tpch.generate(str(d), scale=0.01, files_per_table=2)
    return str(d)


@pytest.mark.parametrize("qn", sorted(tpch.QUERIES,
                                      key=lambda q: int(q[1:])))
def test_query_matches_pandas(qn, data_dir):
    s = TpuSession()
    s.set("spark.rapids.sql.variableFloatAgg.enabled", True)
    s.set("spark.rapids.sql.hasNans", False)
    got = tpch.QUERIES[qn](s, data_dir).collect()
    want = tpch.pandas_query(qn, data_dir)
    assert tpch.check_result(qn, got, want), (
        f"{qn}: device result diverges from pandas oracle\n"
        f"  got[:3]={got[:3]}\n  want[:3]={want[:3]}")
