"""The comparison that decides ``correct`` (PR 24).

A copy of ``spark_rapids_tpu/benchmarks/compare.py``'s row comparator
(type-aware row sort, dates normalised to days, None-aware exact compare
on everything that is no float), changed in one thing: it does not answer
yes or no against a fixed epsilon, it MEASURES. Every float of every row
gives a relative gap to the reference's; everything else either equals the
reference's or makes the answer wrong. ``run.py`` holds the two numbers
against their limits (``LIMITS``), and prints both beside them.

It imports nothing of the program.
"""

from __future__ import annotations

import datetime
import math
from typing import Dict, List, Optional, Sequence

_EPOCH = datetime.date(1970, 1, 1)

# Name -> limit. How each was set, from which readings, is in PERF.md
# ("How correct is decided").
#   answers_wrong   answers whose row count, keys, order or any value that
#                   is no float differs from the reference's: exact, so 0.
#   answers_missing queries sent whose answer never came: 0.
#   max_rel_gap     the widest relative gap of any float of any answer
#                   from the float64 reference's. The program (float64,
#                   sums reassociated) read 1.7e-11 at the most (q3, whose
#                   grouped sum is a difference of prefix sums; q1 9.7e-13,
#                   q6 2.3e-15) on the v5e; the float32 control 5.2e-9 at
#                   the least (q6, one float an answer; q1 5.3e-8, q3
#                   2.0e-8; SF1, 15 seeds a query). The limit lies a factor
#                   of ~60 above the one and 5 below the other.
LIMITS: Dict[str, float] = {
    "answers_wrong": 0,
    "answers_missing": 0,
    "max_rel_gap": 1e-9,
}


def sort_key(row: Sequence) -> tuple:
    """Total order over heterogeneous rows: None sorts first within a
    column, then by type name (so int/str mixes never raise), then by
    value — deterministic for any reference row set."""
    return tuple((v is None, str(type(v)), v if v is not None else 0)
                 for v in row)


def _plain(v):
    """numpy scalars and dates to plain Python, dates to days."""
    if isinstance(v, datetime.datetime):
        return v
    if isinstance(v, datetime.date):
        return (v - _EPOCH).days
    item = getattr(v, "item", None)
    return item() if callable(item) else v


def value_gap(got, want) -> Optional[float]:
    """Relative gap of two floats; for anything else 0.0 when they are
    equal and None when they differ (the answer is then wrong, however
    small a limit on gaps is)."""
    got, want = _plain(got), _plain(want)
    if got is None or want is None:
        return 0.0 if got is None and want is None else None
    if isinstance(got, bool) or isinstance(want, bool):
        return 0.0 if got == want else None
    if isinstance(got, float) or isinstance(want, float):
        if not isinstance(got, (int, float)) or \
                not isinstance(want, (int, float)):
            return None
        a, b = float(got), float(want)
        if math.isnan(a) or math.isnan(b):
            return 0.0 if math.isnan(a) and math.isnan(b) else None
        if a == b:
            return 0.0
        if math.isinf(a) or math.isinf(b):
            return None
        return abs(a - b) / max(abs(a), abs(b))
    return 0.0 if got == want else None


def answer_gap(got: Sequence[Sequence], want: Sequence[Sequence],
               as_set: bool = False) -> Optional[float]:
    """One answer against the reference's: the widest float gap, or None
    where the answer is wrong in a way no tolerance covers (row count,
    width, a key, the order). ``as_set`` compares the row SETS under the
    type-aware order, for queries ordered by a computed float."""
    if got is None or len(got) != len(want):
        return None
    if as_set:
        got = sorted(got, key=sort_key)
        want = sorted(want, key=sort_key)
    worst = 0.0
    for ra, rb in zip(got, want):
        if len(ra) != len(rb):
            return None
        for va, vb in zip(ra, rb):
            gap = value_gap(va, vb)
            if gap is None:
                return None
            worst = max(worst, gap)
    return worst


def judge(answers: List[dict], references: Dict[str, list],
          set_compare, sent: int) -> dict:
    """All the answers of a run against the references.

    ``answers``: ``{"query": name, "rows": rows or None}`` in the order
    they came; ``sent``: how many queries were sent in all. Returns
    ``{"correct": bool, "checks": {name: {"value": v, "limit": l}},
    "wrong": [indices of the answers that are wrong or over the limit]}``.
    """
    wrong, over, worst = [], [], 0.0
    for i, a in enumerate(answers):
        gap = answer_gap(a["rows"], references[a["query"]],
                         as_set=a["query"] in set_compare)
        if gap is None:
            wrong.append(i)
        else:
            worst = max(worst, gap)
            if gap > LIMITS["max_rel_gap"]:
                over.append(i)
    values = {"answers_wrong": len(wrong),
              "answers_missing": sent - len(answers),
              "max_rel_gap": worst}
    checks = {k: {"value": values[k], "limit": LIMITS[k]} for k in LIMITS}
    correct = bool(answers) and all(
        c["value"] <= c["limit"] for c in checks.values())
    return {"correct": correct, "checks": checks,
            "wrong": sorted(wrong + over)}
