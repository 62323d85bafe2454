"""Vectorized row materialization (ISSUE 4 satellite): the one-pass
``HostColumn.to_list`` / ``HostBatch.to_pylist`` must produce values
IDENTICAL (types included) to the reference per-row loop it replaced:
this file pins semantics.
"""

import math

import numpy as np

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.host import (HostBatch, HostColumn,
                                            matrix_to_strings)


def _reference_to_list(col):
    """The pre-vectorization implementation, verbatim."""
    out = []
    for i in range(col.num_rows):
        if not col.validity[i]:
            out.append(None)
        elif col.dtype.is_string:
            out.append(bytes(col.data[i]).decode("utf-8", "replace"))
        elif col.dtype.is_boolean:
            out.append(bool(col.data[i]))
        elif col.dtype.is_floating:
            out.append(float(col.data[i]))
        else:
            out.append(int(col.data[i]))
    return out


def _check(col):
    got = col.to_list()
    want = _reference_to_list(col)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert type(g) is type(w), (g, w)
        if isinstance(w, float) and math.isnan(w):
            assert math.isnan(g)
        else:
            assert g == w, (g, w)
    return got


def test_ints_with_nulls():
    col = HostColumn.from_values(dt.INT64, [1, None, -5, 2 ** 40, None])
    assert _check(col) == [1, None, -5, 2 ** 40, None]


def test_int32_all_valid():
    col = HostColumn.from_values(dt.INT32, list(range(-3, 4)))
    _check(col)


def test_floats_including_nan_and_nulls():
    col = HostColumn.from_values(
        dt.FLOAT64, [1.5, None, float("nan"), -0.0, float("inf")])
    got = _check(col)
    assert got[3] == 0.0 and math.copysign(1.0, got[3]) == -1.0


def test_float32_widens_identically():
    col = HostColumn.from_values(dt.FLOAT32, [0.1, None, 3.25])
    _check(col)


def test_booleans():
    col = HostColumn.from_values(dt.BOOL, [True, None, False])
    assert _check(col) == [True, None, False]


def test_strings_object_array():
    col = HostColumn.from_values(dt.STRING, ["ab", None, "", "Ω≈ç"])
    assert _check(col) == ["ab", None, "", "Ω≈ç"]


def test_strings_matrix_layout():
    m = np.zeros((4, 3), np.uint8)
    m[0, :2] = list(b"hi")
    m[2, :3] = list(b"xyz")
    lens = np.array([2, 0, 3, 1], np.int32)
    val = np.array([True, False, True, True])
    col = matrix_to_strings(m, lens, val)
    assert col._data is None            # still lazy before to_list
    got = col.to_list()                 # must not materialize the object
    assert col._data is None            # array — it decodes the matrix
    assert got == ["hi", None, "xyz", "\x00"]
    assert _check(col) == got           # reference agrees (materializes)


def test_empty_column():
    col = HostColumn.from_values(dt.INT64, [])
    assert _check(col) == []


def test_batch_to_pylist_zip():
    hb = HostBatch.from_pydict(
        (("a", dt.INT64), ("s", dt.STRING)),
        {"a": [1, None, 3], "s": ["x", "y", None]})
    assert hb.to_pylist() == [(1, "x"), (None, "y"), (3, None)]
