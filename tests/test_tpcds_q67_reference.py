"""The deployment ``tpcds_sf1_resident`` on the CPU (PR 33): TPC-DS q67
over ``benchmark/tpcds_data.generate`` at a small scale, the engine's rows
against the benchmark's plain pandas reference, row for row, under the
comparison that decides a cell's ``correct`` — with what the source's data
holds and the program's own suite never had: NULL foreign keys, NULL
prices, NULL item attributes beside the NULLs a rollup level writes, sums
that tie across rollup levels inside a category's top 100 (so that
``rank()``'s ties are really compared), and dates keyed from the Julian
day number 2,415,022.
"""

import importlib.util
import os

import pytest

from spark_rapids_tpu.api.dataframe import TpuSession

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
SCALE = 0.03
SEEDS = [3, 2147483931]           # one over 31 bits, as the driver's are


def _bench_module(name):
    """A module of ``benchmark/`` by path, nothing put on ``sys.path``."""
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{name}", os.path.join(BENCH, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


tpcds = _bench_module("tpcds_data")
compare = _bench_module("compare")


@pytest.fixture(scope="module", params=SEEDS)
def data(request, tmp_path_factory):
    d = str(tmp_path_factory.mktemp("tpcds_q67"))
    rows = tpcds.generate(d, scale=SCALE, seed=request.param,
                          files_per_table=2)
    return {"dir": d, "rows": rows, "want": tpcds.pandas_query("q67", d)}


def _session():
    s = TpuSession()
    s.set("spark.rapids.sql.variableFloatAgg.enabled", True)
    s.set("spark.rapids.sql.hasNans", False)
    return s


def _read(data, table):
    import pyarrow.parquet as papq
    return papq.read_table(os.path.join(data["dir"], table)).to_pandas()


@pytest.mark.parametrize("engine", ["device", "host"])
def test_q67_equals_the_plain_reference(data, engine):
    df = tpcds.QUERIES["q67"](_session(), data["dir"])
    got = df.collect() if engine == "device" else df.collect_host()
    assert len(got) == 100
    v = compare.judge([{"query": "q67", "rows": got}],
                      {"q67": data["want"]}, tpcds.SET_COMPARE, sent=1)
    assert v["correct"], (got[:3], data["want"][:3])
    # whole currency units: every sum is exact, so is the comparison
    assert v["checks"]["max_rel_gap"]["value"] == 0.0
    assert [tuple(r) for r in got] == data["want"]


def test_the_data_has_the_sources_shapes(data):
    assert data["rows"] == tpcds.table_rows(SCALE)
    dd = _read(data, "date_dim")
    assert len(dd) == 73_049 and dd.d_date_sk.min() == 2_415_022
    jan2000 = dd[(dd.d_year == 2000) & (dd.d_moy == 1)]
    assert set(jan2000.d_month_seq) == {1200} and len(jan2000) == 31
    assert dd.d_date_sk.is_monotonic_increasing
    ss = _read(data, "store_sales")
    for c in ss.columns:          # every fact column NULL in a few per cent
        assert 0.02 < ss[c].isna().mean() < 0.06, c
    assert ss.ss_sold_date_sk.min() >= 2_415_022 + 35_794   # 1998-01-02
    it = _read(data, "item")
    for c in ("i_category", "i_class", "i_brand"):
        assert it[c].isna().any(), c
    assert it.i_category.nunique() == 10
    assert it.i_product_name.nunique() > 0.95 * len(it)
    assert it.i_brand.str.len().max() > 16      # not shortened
    st = _read(data, "store")
    assert len(st) == 12 and st.s_store_id.nunique() == 6
    assert set(st.s_store_id.str.len()) == {16}


def test_the_nine_group_bys_add_up_to_the_grand_total(data):
    levels = tpcds.rollup_levels(data["dir"])
    assert len(levels) == 9 and len(levels[-1]) == 1
    total = float(levels[-1].sumsales.iloc[0])
    assert total > 2 ** 24          # a float32 sum could not hold it
    for lv in levels:
        assert float(lv.sumsales.sum()) == total
    # finest first: every level has at most the groups of the one before
    sizes = [len(lv) for lv in levels]
    assert sizes == sorted(sizes, reverse=True)


def test_ties_inside_a_top_100_and_data_nulls_beside_rollup_nulls(data):
    import pandas as pd
    dw1 = pd.concat(tpcds.rollup_levels(data["dir"]), ignore_index=True)
    dw1["rk"] = dw1.groupby("i_category", dropna=False)["sumsales"] \
        .rank(method="min", ascending=False)
    top = dw1[dw1.rk <= 100]
    # a product sold in one year ties with itself one level up: rank()
    # gives both one rank, and the answer holds them
    tied = top.groupby(["i_category", "rk"], dropna=False).size()
    assert (tied > 1).any()
    want = data["want"]
    assert len({(r[8], r[9]) for r in want}) < len(want)
    # the grand total and the items without a category both have eight
    # NULL keys: two groups, which only the grouping id tells apart
    all_null = [r for r in want if all(k is None for k in r[:8])]
    assert len(all_null) == 2 and all_null[0][8] != all_null[1][8]
    assert all_null[1][9] == 1 and all_null[0][9] > 1
