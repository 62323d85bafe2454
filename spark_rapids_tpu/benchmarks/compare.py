"""Generalized oracle result comparison (BenchUtils.compareResults
analog — BenchUtils.scala's sorted/epsilon compare, ISSUE 5 satellite).

One comparator for every harness that checks engine output against an
oracle (chip_smoke.py, tests/test_suites.py, tests/test_tpch*.py, the
scheduler's bit-identity tests): dtype-aware epsilon on floats, date
normalization, None-aware exact compare on everything else, and an
optional type-aware row sort for queries whose ORDER BY is computed from
epsilon-different floats (the two engines may legitimately order such
rows differently, so only the row SET is comparable).

Replaces the hand-rolled per-query ``check_result`` comparisons that
used bare ``sorted(...)`` (which throws on None and mixed types) —
``tests/harness.py`` re-exports these helpers for test use.
"""

from __future__ import annotations

import datetime
import math
from typing import Sequence

_EPOCH = datetime.date(1970, 1, 1)


def sort_key(row: Sequence) -> tuple:
    """Total order over heterogeneous rows: None sorts first within a
    column, then by type name (so int/str mixes never raise), then by
    value — deterministic for any oracle row set."""
    return tuple((v is None, str(type(v)), v if v is not None else 0)
                 for v in row)


def values_close(va, vb, rel_tol: float = 1e-6,
                 abs_tol: float = 1e-9) -> bool:
    """Dtype-aware scalar compare: dates normalize to days-since-epoch
    (pandas oracles yield datetime.date, the engine yields ints), floats
    compare with relative+absolute epsilon (NaN == NaN — an oracle
    emitting NaN means the engine must too), everything else exactly."""
    if va is None or vb is None:
        return va is None and vb is None
    if isinstance(va, datetime.date):
        va = (va - _EPOCH).days
    if isinstance(vb, datetime.date):
        vb = (vb - _EPOCH).days
    if isinstance(va, float) or isinstance(vb, float):
        fa, fb = float(va), float(vb)
        if math.isnan(fa) or math.isnan(fb):
            return math.isnan(fa) and math.isnan(fb)
        return math.isclose(fa, fb, rel_tol=rel_tol, abs_tol=abs_tol)
    return va == vb


def compare_results(got, want, sort: bool = False,
                    rel_tol: float = 1e-6,
                    abs_tol: float = 1e-9) -> bool:
    """Row-list compare. ``sort=True`` compares the row SETS under the
    type-aware total order (for computed-float ORDER BY); default keeps
    order significant (ORDER BY included in the contract)."""
    if len(got) != len(want):
        return False
    if sort:
        got = sorted(got, key=sort_key)
        want = sorted(want, key=sort_key)
    for ra, rb in zip(got, want):
        if len(ra) != len(rb):
            return False
        for va, vb in zip(ra, rb):
            if not values_close(va, vb, rel_tol, abs_tol):
                return False
    return True


def first_mismatch(got, want, sort: bool = False,
                   rel_tol: float = 1e-6, abs_tol: float = 1e-9):
    """(row, col, got_value, want_value) of the first divergence, or a
    (row-count) tuple when lengths differ, or None when equal — the
    assertion-message half of the harness."""
    if len(got) != len(want):
        return ("rows", len(got), len(want))
    if sort:
        got = sorted(got, key=sort_key)
        want = sorted(want, key=sort_key)
    for r, (ra, rb) in enumerate(zip(got, want)):
        if len(ra) != len(rb):
            return (r, "width", len(ra), len(rb))
        for c, (va, vb) in enumerate(zip(ra, rb)):
            if not values_close(va, vb, rel_tol, abs_tol):
                return (r, c, va, vb)
    return None
