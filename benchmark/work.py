"""The bytes a query has to read, from table shapes alone (PR 24).

The numerator of ``scan_hbm_roofline``: rows x width of the columns the
query reads, each read once. It is the same number whatever implements the
operators, which is the point: a roofline share that counted the bytes an
implementation happens to move would rise when the implementation wastes
more.

Widths are those of the decoded column, not of the parquet file or the
wire: 8 bytes for int64 and float64, 4 for int32 and date32, 1 for bool,
and for a string its bytes plus a 4-byte offset (arrow's layout).
"""

from __future__ import annotations

from typing import Dict, Iterable

import pyarrow as pa
import pyarrow.parquet as papq


def _column_bytes(col: pa.ChunkedArray) -> int:
    t = col.type
    if pa.types.is_string(t) or pa.types.is_large_string(t) \
            or pa.types.is_binary(t):
        import pyarrow.compute as pc
        data = pc.sum(pc.binary_length(col)).as_py() or 0
        return int(data) + 4 * len(col)
    if pa.types.is_boolean(t):
        return len(col)
    return len(col) * (t.bit_width // 8)


def table_bytes(paths: Iterable[str], columns) -> int:
    """Decoded bytes of ``columns`` over a table's parquet files."""
    total = 0
    for p in paths:
        t = papq.read_table(p, columns=list(columns))
        total += sum(_column_bytes(t.column(c)) for c in columns)
    return total


def query_bytes(suite, query: str, data_dir: str) -> int:
    """Bytes of the columns ``query`` reads (``suite.QUERY_COLUMNS``),
    each counted once, from the tables as generated."""
    cols: Dict[str, list] = suite.QUERY_COLUMNS[query]
    return sum(table_bytes(suite._paths(data_dir, table), names)
               for table, names in cols.items())
