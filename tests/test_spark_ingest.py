"""Plugin-mode slice: ingest CAPTURED Spark physical
plans — the text a user's real cluster prints from df.explain() — and
execute them on this engine with results matching the pandas oracle
(SQLPlugin.scala:28 / GpuOverrides.scala:1991 identity, via plan capture
instead of an in-JVM hook)."""

import os

import pytest

from spark_rapids_tpu.api.dataframe import TpuSession
from spark_rapids_tpu.benchmarks import tpch
from spark_rapids_tpu.plan.spark_ingest import (
    SparkPlanParseError, ingest_spark_plan)

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures",
                        "spark_plans")


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("tpch_ingest")
    tpch.generate(str(d), scale=0.01, files_per_table=2)
    return str(d)


def _tables(data_dir):
    return {t: tpch._paths(data_dir, t)
            for t in ("lineitem", "orders", "customer")}


def _session():
    s = TpuSession()
    s.set("spark.rapids.sql.variableFloatAgg.enabled", True)
    s.set("spark.rapids.sql.hasNans", False)
    # These tests assert the ingested plan lands ON the device; the cost
    # model would (correctly) host-place the mini-scale fixtures.
    s.set("spark.rapids.sql.cost.enabled", False)
    return s


@pytest.mark.parametrize("qn", ["q6", "q3"])
def test_captured_plan_matches_pandas(qn, data_dir):
    text = open(os.path.join(FIXTURES, f"{qn}.txt")).read()
    df = ingest_spark_plan(text, _session(), _tables(data_dir))
    got = df.collect()
    want = tpch.pandas_query(qn, data_dir)
    assert tpch.check_result(qn, got, want), (
        f"ingested {qn} diverges\n got[:3]={got[:3]}\nwant[:3]={want[:3]}")


def test_ingested_plan_runs_on_device(data_dir):
    text = open(os.path.join(FIXTURES, "q3.txt")).read()
    df = ingest_spark_plan(text, _session(), _tables(data_dir))
    report = df._physical().explain()
    assert "!Exec" not in report, report   # nothing fell off the TPU


def test_unknown_operator_raises():
    with pytest.raises(SparkPlanParseError):
        ingest_spark_plan("*(1) FancyNewExec [x#1]\n", _session(), {})


def test_host_oracle_agrees(data_dir):
    text = open(os.path.join(FIXTURES, "q6.txt")).read()
    df = ingest_spark_plan(text, _session(), _tables(data_dir))
    got = df.collect()
    want = df.collect_host()
    assert len(got) == len(want) == 1
    assert abs(got[0][0] - want[0][0]) < 1e-6 * abs(want[0][0])


def test_misaligned_operator_line_raises():
    """ISSUE 2 satellite: a line that looks like an operator but fails
    the multiple-of-3 indentation check must raise, not silently drop
    the operator (a vanished Filter = silently wrong results)."""
    bad = ("*(1) Project [x#1]\n"
           "  +- Filter (x#1 > 2)\n"           # 2-space indent: malformed
           "      +- FileScan parquet [x#1]\n")
    with pytest.raises(SparkPlanParseError, match="indentation"):
        ingest_spark_plan(bad, _session(), {})


def test_scan_missing_columns_raises(data_dir):
    """ISSUE 2 satellite: a captured scan that wants columns the local
    file lacks must raise naming them, instead of silently narrowing
    the scan to a DIFFERENT query."""
    text = ("*(1) FileScan parquet [l_shipdate#26,no_such_col#99] "
            "Batched: true, Format: Parquet, Location: "
            "InMemoryFileIndex[file:/data/tpch/lineitem], "
            "ReadSchema: struct<l_shipdate:date>\n")
    with pytest.raises(SparkPlanParseError, match="no_such_col"):
        ingest_spark_plan(text, _session(), _tables(data_dir))
