"""The benchmark's TPC-DS suite (PR 33): generator, query builder and plain
pandas reference for query 67, the one TPC-DS query a cell runs.

The deployment is TPC-DS specification v2 at scale factor 1, query 67 as
``Query("q67")`` of ``integration_tests/.../tests/tpcds/TpcdsLikeSpark
.scala`` of spark-rapids v0.3 writes it: ``store_sales`` joined to
``date_dim``, ``store`` and ``item``, one year of months, a ROLLUP over
eight columns (nine grouping sets), ``rank()`` over the category, the top
100 of each, ordered, the first 100. Started from the q67 parts of
``spark_rapids_tpu/benchmarks/suites.py`` and given the source's shapes;
it imports the program only inside the query builder (the system under
test's DataFrame API), and the reference imports nothing of it.

What the source fixes and this file keeps (``benchmark/configs/
tpcds_sf1_resident.json`` lists every departure under ``assumed``):
- rows at SF1: ``store_sales`` 2,880,404, ``item`` 18,000, ``store`` 12,
  ``date_dim`` 73,049 (1900-01-02 .. 2100-01-01; ``d_date_sk`` is the
  Julian day number, 2,415,022 upward; ``d_month_seq`` counts months from
  1900-01, so 1200 is January 2000). Only the columns q67 reads exist.
- every fact column is NULL in a few per cent of the rows, independently
  (why the query says ``coalesce(..., 0)`` and why the joins meet NULL
  keys); ``s_store_id`` is a 16-character business key, two versions a
  store; the item hierarchy is category (10) > class (~100) > brand
  (several hundred) > product name (nearly one an item) in dsdgen's words
  and lengths, the three upper attributes NULL in a small share of items.
- ``DoubleType`` prices, int64 surrogate keys, int32 calendar columns and
  quantity.

What a suite module gives the harness: ``generate``, ``QUERIES``,
``pandas_query``, ``SET_COMPARE``, ``QUERY_COLUMNS``, ``table_rows``,
``_paths``.
"""

from __future__ import annotations

import os
from typing import Dict, List

import numpy as np

import pyarrow as pa
import pyarrow.parquet as papq

# -- the calendar -------------------------------------------------------------
DATE_DIM_ROWS = 73_049                  # 1900-01-02 .. 2100-01-01
FIRST_DATE = np.datetime64("1900-01-02")
FIRST_DATE_SK = 2_415_022               # its Julian day number
SALES_FIRST = np.datetime64("1998-01-02")   # the five sales years
SALES_LAST = np.datetime64("2003-01-02")

# -- the fact table -----------------------------------------------------------
STORE_SALES_ROWS_SF1 = 2_880_404
ITEM_ROWS_SF1 = 18_000
STORE_ROWS = 12
FACT_NULL_SHARE = 0.04                  # each fact column, independently
ITEM_NULL_SHARE = 0.005                 # category, class, brand

# -- the item hierarchy, in dsdgen's words ------------------------------------
# Ten categories, each with its classes (100 in all).
CLASSES = {
    "Women": ["dresses", "fragrances", "maternity", "swimwear"],
    "Men": ["accessories", "pants", "shirts", "sports-apparel"],
    "Children": ["infants", "newborn", "school-uniforms", "toddlers"],
    "Shoes": ["athletic", "kids", "mens", "womens"],
    "Music": ["classical", "country", "pop", "rock"],
    "Jewelry": ["birdal", "bracelets", "consignment", "costume", "custom",
                "diamonds", "earings", "estate", "gold", "jewelry boxes",
                "loose stones", "mens watch", "pendants", "rings",
                "semi-precious", "womens watch"],
    "Home": ["accent", "bathroom", "bedding", "blinds/shades", "curtains/"
             "drapes", "decor", "flatware", "furniture", "glassware",
             "kids", "lighting", "mattresses", "paint", "rugs", "tables",
             "wallpaper"],
    "Sports": ["archery", "athletic shoes", "baseball", "basketball",
               "camping", "fishing", "fitness", "football", "golf",
               "guns", "hockey", "optics", "outdoor", "pools", "sailing",
               "tennis"],
    "Books": ["arts", "business", "computers", "cooking", "entertainments",
              "fiction", "history", "home repair", "mystery", "parenting",
              "reference", "romance", "science", "self-help", "sports",
              "travel"],
    "Electronics": ["audio", "automotive", "cameras", "camcorders",
                    "disk drives", "dvd/vcr players", "karoke",
                    "memory", "monitors", "musical", "personal",
                    "portable", "scanners", "stereo", "televisions",
                    "wireless"],
}
CATEGORIES = list(CLASSES)
# A brand is two or three of dsdgen's syllables and a number: "amalgimporto
# #1", "exportiunivamalg #12", "edu packscholar #2" (12 to 24 characters).
BRAND_SYLLABLES = ["amalg", "importo", "exporti", "edu pack", "scholar",
                   "brand", "corp", "maxi", "univ", "nameless"]
BRANDS_PER_CLASS = 7
# A product name spells the digits of a number in dsdgen's syllables:
# 17,042 is "oughtationbareseable".
DIGIT_SYLLABLES = ["bar", "ought", "able", "pri", "ese", "anti", "cally",
                   "ation", "eing", "n st"]
PRODUCT_NAME_REUSE = 0.02               # items named as another item is


def _write_parts(table: pa.Table, out_dir: str, n_files: int):
    os.makedirs(out_dir, exist_ok=True)
    n = table.num_rows
    per = max(1, -(-n // n_files))
    parts = []
    for i in range(n_files):
        part = table.slice(i * per, per)
        if part.num_rows == 0 and i > 0:
            break
        parts.append((part, os.path.join(out_dir, f"part-{i:03d}.parquet")))
    # The files of a table are written side by side (arrow releases the
    # interpreter lock): every run pays this in its set-up.
    from concurrent.futures import ThreadPoolExecutor
    with ThreadPoolExecutor(max_workers=min(len(parts), 8)) as pool:
        for f in [pool.submit(papq.write_table, part, path,
                              compression="snappy")
                  for part, path in parts]:
            f.result()


def _take(pool, idx, null=None) -> pa.Array:
    """``pool[idx]`` as an arrow string array, without a Python list;
    NULL where ``null`` is set."""
    return pa.DictionaryArray.from_arrays(
        pa.array(np.asarray(idx, dtype=np.int32), mask=null),
        pa.array(list(pool), pa.string())).cast(pa.string())


def business_key(n: int) -> str:
    """dsdgen's 16-character business key of the number ``n``: eight
    ``A`` and the number in letters ``A``..``P``, lowest digit first
    (``AAAAAAAABAAAAAAA`` is 1)."""
    digits = []
    for _ in range(8):
        digits.append("ABCDEFGHIJKLMNOP"[n % 16])
        n //= 16
    return "A" * 8 + "".join(digits)


def product_name(n: int) -> str:
    return "".join(DIGIT_SYLLABLES[int(d)] for d in str(n))


def brand_pool() -> List[str]:
    """Every brand, class by class: ``BRANDS_PER_CLASS`` to a class, the
    syllables drawn from the class's place in the hierarchy and not from
    the seed (the pool is data about the schema, as the categories are)."""
    out = []
    n_class = sum(len(v) for v in CLASSES.values())
    s = BRAND_SYLLABLES
    for c in range(n_class):
        for b in range(BRANDS_PER_CLASS):
            k = c * BRANDS_PER_CLASS + b
            name = s[k % 10] + s[(k // 10 + b) % 10]
            if k % 3 == 0:
                name += s[(k // 7) % 10]
            out.append(f"{name} #{b + 1}")
    return out


def table_rows(scale: float) -> Dict[str, int]:
    """Rows of every table at ``scale``, without generating. ``date_dim``
    is the calendar at any scale; ``store`` is the specification's 12 at
    SF1 and below (larger factors are not this file's yet)."""
    return {"store_sales": max(int(STORE_SALES_ROWS_SF1 * scale), 1000),
            "item": max(int(ITEM_ROWS_SF1 * scale), 100),
            "store": STORE_ROWS, "date_dim": DATE_DIM_ROWS}


def generate(data_dir: str, scale: float = 1.0, files_per_table: int = 8,
             seed: int = 0, tables=None) -> Dict[str, int]:
    """The four tables q67 reads, from ``seed``; only ``tables`` where
    given. Each table draws from a generator of its own (``[seed, i]``),
    so a table is the same whatever else is skipped."""
    def want(t):
        return tables is None or t in tables
    n = table_rows(scale)
    rows: Dict[str, int] = {}

    if want("date_dim"):
        dates = FIRST_DATE + np.arange(DATE_DIM_ROWS)
        year = dates.astype("datetime64[Y]").astype(np.int64) + 1970
        month = dates.astype("datetime64[M]").astype(np.int64) % 12 + 1
        date_dim = pa.table({
            "d_date_sk": FIRST_DATE_SK + np.arange(DATE_DIM_ROWS,
                                                   dtype=np.int64),
            "d_month_seq": ((year - 1900) * 12 + month - 1)
            .astype(np.int32),
            "d_year": year.astype(np.int32),
            "d_moy": month.astype(np.int32),
            "d_qoy": ((month - 1) // 3 + 1).astype(np.int32),
        })
        _write_parts(date_dim, os.path.join(data_dir, "date_dim"), 1)
        rows["date_dim"] = DATE_DIM_ROWS

    if want("store"):
        # Two versions a store (the slowly changing dimension's history):
        # twelve rows, six business keys.
        store = pa.table({
            "s_store_sk": np.arange(1, STORE_ROWS + 1, dtype=np.int64),
            "s_store_id": pa.array([business_key(i // 2 + 1)
                                    for i in range(STORE_ROWS)],
                                   pa.string()),
        })
        _write_parts(store, os.path.join(data_dir, "store"), 1)
        rows["store"] = STORE_ROWS

    if want("item"):
        rng = np.random.default_rng([seed, 1])
        n_item = n["item"]
        class_names = [c for cat in CATEGORIES for c in CLASSES[cat]]
        class_category = np.repeat(np.arange(len(CATEGORIES)),
                                   [len(CLASSES[c]) for c in CATEGORIES])
        cls = rng.integers(0, len(class_names), n_item)
        brand = cls * BRANDS_PER_CLASS + rng.integers(0, BRANDS_PER_CLASS,
                                                      n_item)
        named_as = np.arange(1, n_item + 1)
        reuse = rng.random(n_item) < PRODUCT_NAME_REUSE
        named_as[reuse] = rng.integers(1, n_item + 1, int(reuse.sum()))
        null = rng.random((3, n_item)) < ITEM_NULL_SHARE
        item = pa.table({
            "i_item_sk": np.arange(1, n_item + 1, dtype=np.int64),
            "i_category": _take(CATEGORIES, class_category[cls], null[0]),
            "i_class": _take(class_names, cls, null[1]),
            "i_brand": _take(brand_pool(), brand, null[2]),
            "i_product_name": _take(
                [product_name(i) for i in range(1, n_item + 1)],
                named_as - 1),
        })
        _write_parts(item, os.path.join(data_dir, "item"), 1)
        rows["item"] = n_item

    if want("store_sales"):
        rng = np.random.default_rng([seed, 2])
        n_ss = n["store_sales"]
        first = FIRST_DATE_SK + int((SALES_FIRST - FIRST_DATE)
                                    / np.timedelta64(1, "D"))
        last = FIRST_DATE_SK + int((SALES_LAST - FIRST_DATE)
                                   / np.timedelta64(1, "D"))
        null = rng.random((5, n_ss)) < FACT_NULL_SHARE
        store_sales = pa.table({
            "ss_sold_date_sk": pa.array(
                rng.integers(first, last + 1, n_ss, dtype=np.int64),
                mask=null[0]),
            "ss_item_sk": pa.array(
                rng.integers(1, n["item"] + 1, n_ss, dtype=np.int64),
                mask=null[1]),
            "ss_store_sk": pa.array(
                rng.integers(1, STORE_ROWS + 1, n_ss, dtype=np.int64),
                mask=null[2]),
            "ss_quantity": pa.array(
                rng.integers(1, 101, n_ss).astype(np.int32), mask=null[3]),
            # Whole currency units: many rollup levels hold the same rows
            # (a product sold in one year), their true sums tie, rank()
            # gives ties one rank, and a reassociated float64 sum of cents
            # would break a tie that the reference keeps. Whole units keep
            # every sum exact in float64 (configs/tpcds_sf1_resident.json,
            # ``assumed``).
            "ss_sales_price": pa.array(
                rng.integers(1, 201, n_ss).astype(np.float64),
                mask=null[4]),
        })
        _write_parts(store_sales, os.path.join(data_dir, "store_sales"),
                     files_per_table)
        rows["store_sales"] = n_ss
    return rows


def _paths(data_dir: str, table: str) -> List[str]:
    d = os.path.join(data_dir, table)
    return sorted(os.path.join(d, f) for f in os.listdir(d)
                  if f.endswith(".parquet"))


def _read(session, data_dir: str, table: str):
    return session.read.parquet(*_paths(data_dir, table))


# ---------------------------------------------------------------------------
# The query (TpcdsLikeSpark.scala Query("q67"), through the DataFrame API)
# ---------------------------------------------------------------------------

Q67_KEYS = ["i_category", "i_class", "i_brand", "i_product_name",
            "d_year", "d_qoy", "d_moy", "s_store_id"]


def q67(session, data_dir: str):
    """Three joins, one year of months, a rollup of eight columns,
    rank() over the category, the top 100 of each, the first 100."""
    from spark_rapids_tpu.plan.logical import (
        Window, agg_sum, coalesce_cols, col, lit_col, rank)
    ss = _read(session, data_dir, "store_sales")
    dd = _read(session, data_dir, "date_dim") \
        .filter((col("d_month_seq") >= 1200)
                & (col("d_month_seq") <= 1200 + 11))
    st = _read(session, data_dir, "store")
    it = _read(session, data_dir, "item")
    j = ss.join_on(dd, ["ss_sold_date_sk"], ["d_date_sk"]) \
        .join_on(st, ["ss_store_sk"], ["s_store_sk"]) \
        .join_on(it, ["ss_item_sk"], ["i_item_sk"]) \
        .with_column("sales",
                     coalesce_cols(col("ss_sales_price")
                                   * col("ss_quantity").cast("double"),
                                   lit_col(0.0)))
    dw1 = j.rollup(*Q67_KEYS).agg(agg_sum(col("sales")).alias("sumsales"))
    w = Window.partition_by("i_category").order_by(col("sumsales").desc())
    dw2 = dw1.with_column("rk", rank().over(w)).filter(col("rk") <= 100)
    return dw2.order_by(*[col(k).asc() for k in Q67_KEYS],
                        col("sumsales").asc(), col("rk").asc()) \
        .limit(100)


QUERIES = {"q67": q67}


# ---------------------------------------------------------------------------
# The plain reference: pandas, float64, written from the query text
# ---------------------------------------------------------------------------

def rollup_levels(data_dir: str):
    """The nine group-bys of q67's rollup, finest first, each a pandas
    frame of ``Q67_KEYS`` (the rolled-up ones all NULL) and ``sumsales``."""
    import pandas as pd

    def read(table, columns):
        return pa.concat_tables(
            [papq.read_table(p, columns=columns)
             for p in _paths(data_dir, table)]).to_pandas()

    ss = read("store_sales", QUERY_COLUMNS["q67"]["store_sales"])
    dd = read("date_dim", QUERY_COLUMNS["q67"]["date_dim"])
    dd = dd[(dd.d_month_seq >= 1200) & (dd.d_month_seq <= 1200 + 11)]
    st = read("store", QUERY_COLUMNS["q67"]["store"])
    it = read("item", QUERY_COLUMNS["q67"]["item"])
    # A NULL key joins nothing (pandas would match NULL with NULL).
    ss = ss.dropna(subset=["ss_sold_date_sk", "ss_item_sk", "ss_store_sk"])
    j = ss.merge(dd, left_on="ss_sold_date_sk", right_on="d_date_sk") \
        .merge(st, left_on="ss_store_sk", right_on="s_store_sk") \
        .merge(it, left_on="ss_item_sk", right_on="i_item_sk")
    price = j.ss_sales_price
    j["sales"] = (price * j.ss_quantity.astype(price.dtype)).fillna(0)
    levels = []
    for n_keys in range(len(Q67_KEYS), -1, -1):
        keys = Q67_KEYS[:n_keys]
        if keys:
            # dropna=False: a NULL category, class or brand is a group.
            g = j.groupby(keys, dropna=False, sort=False)["sales"].sum() \
                .reset_index()
        else:
            g = pd.DataFrame({"sales": [j.sales.sum()]})
        for k in Q67_KEYS[n_keys:]:
            g[k] = None
        levels.append(g[Q67_KEYS + ["sales"]]
                      .rename(columns={"sales": "sumsales"}))
    return levels


def pandas_query(name: str, data_dir: str):
    """Query ``name`` in plain pandas and float64, as a list of row
    tuples in the DataFrame version's column order."""
    import pandas as pd
    if name != "q67":
        raise KeyError(name)
    dw1 = pd.concat(rollup_levels(data_dir), ignore_index=True)
    for k in ("d_year", "d_qoy", "d_moy"):
        dw1[k] = dw1[k].astype("Int32")
    # NULL is a partition like any other: the grand total and the items
    # without a category are ranked together.
    dw1["rk"] = dw1.groupby("i_category", dropna=False)["sumsales"] \
        .rank(method="min", ascending=False).astype("int32")
    dw2 = dw1[dw1.rk <= 100]
    # Spark's ascending order puts NULLs first.
    dw2 = dw2.sort_values(Q67_KEYS + ["sumsales", "rk"], ascending=True,
                          na_position="first", kind="stable").head(100)
    return [tuple(None if pd.isna(v) else v for v in r)
            for r in dw2[Q67_KEYS + ["sumsales", "rk"]]
            .itertuples(index=False)]


# The order is total up to rows that are equal in every column.
SET_COMPARE: set = set()

# The columns each query reads, by table: what ``work.py`` counts a
# query's input bytes from, and what ``generate(tables=...)`` is asked
# for. A cell may only name a query that is listed here.
QUERY_COLUMNS = {
    "q67": {"store_sales": ["ss_sold_date_sk", "ss_item_sk", "ss_store_sk",
                            "ss_quantity", "ss_sales_price"],
            "date_dim": ["d_date_sk", "d_month_seq", "d_year", "d_moy",
                         "d_qoy"],
            "store": ["s_store_sk", "s_store_id"],
            "item": ["i_item_sk", "i_category", "i_class", "i_brand",
                     "i_product_name"]},
}
