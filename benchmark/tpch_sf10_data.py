"""The TPC-H-like suite at scales past SF1 (PR 35): ``tpch_data``'s
statements and plain reference, and a generator of its own.

``QUERIES``, ``SET_COMPARE``, ``QUERY_COLUMNS``, ``table_rows`` and
``_paths`` ARE ``tpch_data``'s: the same statements over the same schema,
and the same plain float64 pandas reference (``pandas_query`` hands it
this module's ``pa``, which ``control.py`` swaps for its float32 reader).
What differs is ``generate``: ``tpch_data.generate`` draws every column of
a table in one piece on one core and keeps the whole of lineitem in
memory (SF10: 78 s and 8.8 GB on 8 CPUs), and every run of a cell pays
that in its set-up. This one works

- **file by file**: chunk ``k`` of a table's ``files_per_table`` is a
  contiguous range of order keys, the lineitem rows of those orders, and
  draws of its own from ``numpy.random.default_rng([seed, k])``, so a
  chunk is a function of ``(seed, k)`` whatever else runs, and chunks run
  side by side on the host's cores (numpy's draws and arrow's writers
  release the interpreter lock; no child process, so a parent that holds
  a chip can call it);
- **column-pruned**: a table gets the columns some entry of
  ``QUERY_COLUMNS`` reads and no other (``COLUMNS``); a draw that only an
  unwritten column needed is not made;
- with ``tpch_data.generate``'s distributions, key relations and row
  counts: orders ``1_500_000 x scale`` with keys 1..n, 1..7 lines an
  order, customers drawn from the first two thirds (a third have no
  order), order dates uniform over 1992-01-01..1998-08-01, ship date 1..121
  days after the order, receipt 1..30 after that, ``l_returnflag`` by the
  receipt date and ``l_linestatus`` by the ship date, a line's supplier one
  of its part's four, uniform keys, float64 / int64 / int32 / date32 / string
  as there. The draws are other draws: for one seed the two generators
  give different rows of the same distribution;
- pyarrow's default row groups (1,048,576 rows), snappy.

Tables: customer, orders, lineitem, and the three small ones that q5
reads beside them (supplier, nation, region).
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict

import numpy as np

import pyarrow as pa
import pyarrow.parquet as papq

import tpch_data
from tpch_data import (NATIONS, QUERIES, QUERY_COLUMNS, REGIONS,  # noqa: F401
                       SEGMENTS, SET_COMPARE, _paths, _take, days,
                       table_rows)

# What a table is written with: the columns some query of the suite reads.
COLUMNS = {t: sorted({c for q in QUERY_COLUMNS.values()
                      for c in q.get(t, ())})
           for t in sorted({t for q in QUERY_COLUMNS.values() for t in q})}


def pandas_query(name: str, data_dir: str):
    """``tpch_data.pandas_query`` reading through THIS module's ``pa``
    (``control.py`` replaces a suite's ``pa`` to read floats as
    float32)."""
    held = tpch_data.pa
    tpch_data.pa = pa
    try:
        return tpch_data.pandas_query(name, data_dir)
    finally:
        tpch_data.pa = held


def _date32(a) -> pa.Array:
    return pa.array(a, pa.int32()).cast(pa.date32())


def _small(rng, lo, hi, n, over=None) -> np.ndarray:
    """``n`` whole numbers of ``lo..hi-1`` as float64, divided by
    ``over`` where given, with no 8-byte temporary beside the result."""
    a = rng.integers(lo, hi, n, dtype=np.int8).astype(np.float64)
    if over is not None:
        a /= over
    return a


def _cents(a: np.ndarray) -> np.ndarray:
    return np.round(a, 2, out=a)


def _write(data_dir: str, table: str, k: int, columns: dict) -> int:
    """File ``k`` of ``table``, with the listed columns alone."""
    out_dir = os.path.join(data_dir, table)
    os.makedirs(out_dir, exist_ok=True)
    t = pa.table({c: columns[c] for c in COLUMNS[table]})
    papq.write_table(t, os.path.join(out_dir, f"part-{k:03d}.parquet"),
                     compression="snappy")
    return t.num_rows


def _chunk(data_dir, seed, k, first, last, n_cust, n_part, n_supp, want):
    """Orders ``first..last-1`` (keys from 1) and their lines."""
    rng = np.random.default_rng([seed, k])
    n = last - first
    o_orderkey = np.arange(first + 1, last + 1, dtype=np.int64)
    o_custkey = rng.integers(1, max(n_cust * 2 // 3, 2), n, dtype=np.int64)
    o_orderdate = rng.integers(days("1992-01-01"), days("1998-08-02"), n,
                               dtype=np.int32)
    per_order = rng.integers(1, 8, n)
    rows = {}
    if "orders" in want:
        rows["orders"] = _write(data_dir, "orders", k, {
            "o_orderkey": o_orderkey, "o_custkey": o_custkey,
            "o_orderdate": _date32(o_orderdate),
            "o_shippriority": np.zeros(n, dtype=np.int32)})
    if "lineitem" not in want:
        return rows
    del o_custkey
    l_orderkey = np.repeat(o_orderkey, per_order)
    l_shipdate = np.repeat(o_orderdate, per_order)
    del o_orderkey, o_orderdate, per_order
    m = len(l_orderkey)
    l_shipdate += rng.integers(1, 122, m, dtype=np.int32)
    l_receiptdate = l_shipdate + rng.integers(1, 31, m, dtype=np.int32)
    cutoff = days("1995-06-17")
    # 'R' or 'A' for what was received by the cutoff, 'N' after it; 'F'
    # for what shipped by it, 'O' after (dbgen's correlation: q1's groups).
    l_returnflag = np.where(l_receiptdate <= cutoff,
                            rng.integers(0, 2, m, dtype=np.int8),
                            np.int8(2))
    l_linestatus = (l_shipdate <= cutoff).astype(np.int8)
    del l_receiptdate
    l_partkey = rng.integers(1, n_part + 1, m, dtype=np.int64)
    l_suppkey = (l_partkey + rng.integers(0, 4, m, dtype=np.int64)
                 * (n_supp // 4 + 1)) % n_supp + 1
    del l_partkey
    rows["lineitem"] = _write(data_dir, "lineitem", k, {
        "l_orderkey": l_orderkey, "l_suppkey": l_suppkey,
        "l_quantity": _small(rng, 1, 51, m),
        "l_extendedprice": _cents(rng.uniform(900.0, 105_000.0, m)),
        "l_discount": _small(rng, 0, 11, m, 100.0),
        "l_tax": _small(rng, 0, 9, m, 100.0),
        "l_returnflag": _take("ARN", l_returnflag),
        "l_linestatus": _take("OF", l_linestatus),
        "l_shipdate": _date32(l_shipdate)})
    return rows


def _customer(data_dir, seed, k, first, last):
    rng = np.random.default_rng([seed, 1 << 20, k])
    n = last - first
    return {"customer": _write(data_dir, "customer", k, {
        "c_custkey": np.arange(first + 1, last + 1, dtype=np.int64),
        "c_nationkey": rng.integers(0, 25, n, dtype=np.int64),
        "c_mktsegment": _take(SEGMENTS,
                              rng.integers(0, len(SEGMENTS), n))})}


def _ranges(n: int, files: int):
    per = max(1, -(-n // files))
    return [(k, k * per, min((k + 1) * per, n)) for k in range(files)
            if k * per < n]


def generate(data_dir: str, scale: float = 1.0, files_per_table: int = 8,
             seed: int = 0, tables=None) -> Dict[str, int]:
    """The tables of ``tables`` (all six where None) as parquet under
    ``data_dir``, from ``seed``; rows written, by table."""
    want = set(COLUMNS) if tables is None else set(tables)
    unknown = want - set(COLUMNS)
    if unknown:
        raise KeyError(f"no query of the suite reads {sorted(unknown)}")
    counts = table_rows(scale)
    n_cust, n_supp = counts["customer"], counts["supplier"]
    rows: Dict[str, int] = {}
    jobs = []
    with ThreadPoolExecutor(max_workers=min(os.cpu_count() or 1,
                                            files_per_table)) as pool:
        if want & {"orders", "lineitem"}:
            jobs += [pool.submit(_chunk, data_dir, seed, k, a, b, n_cust,
                                 counts["part"], n_supp, want)
                     for k, a, b in _ranges(counts["orders"],
                                            files_per_table)]
        if "customer" in want:
            jobs += [pool.submit(_customer, data_dir, seed, k, a, b)
                     for k, a, b in _ranges(n_cust,
                                            max(files_per_table // 2, 1))]
        for job in jobs:
            for t, n in job.result().items():
                rows[t] = rows.get(t, 0) + n
    if "supplier" in want:
        rng = np.random.default_rng([seed, 2 << 20])
        rows["supplier"] = _write(data_dir, "supplier", 0, {
            "s_suppkey": np.arange(1, n_supp + 1, dtype=np.int64),
            "s_nationkey": rng.integers(0, 25, n_supp, dtype=np.int64)})
    if "nation" in want:
        rows["nation"] = _write(data_dir, "nation", 0, {
            "n_nationkey": np.arange(25, dtype=np.int64),
            "n_name": pa.array([n for n, _ in NATIONS], pa.string()),
            "n_regionkey": np.array([r for _, r in NATIONS], np.int64)})
    if "region" in want:
        rows["region"] = _write(data_dir, "region", 0, {
            "r_regionkey": np.arange(5, dtype=np.int64),
            "r_name": pa.array(REGIONS, pa.string())})
    return rows
