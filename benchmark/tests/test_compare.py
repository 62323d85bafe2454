"""``compare.py``: what counts as a gap, and what as a wrong answer."""

import datetime

import pytest

import compare

WANT = [(1, datetime.date(1995, 3, 1), "A", 100.0),
        (2, datetime.date(1995, 3, 2), "B", 200.0)]


def rows(change=None):
    out = [list(r) for r in WANT]
    for (r, c), v in (change or {}).items():
        out[r][c] = v
    return [tuple(r) for r in out]


def test_equal_rows_have_no_gap():
    got = rows()
    got[0] = (1, 9190, "A", 100.0)            # the engine gives days
    assert compare.answer_gap(got, WANT) == 0.0


def test_a_float_gives_its_relative_gap():
    got = [(1, 9190, "A", 100.0), (2, 9191, "B", 200.0 * (1 + 1e-7))]
    assert compare.answer_gap(got, WANT) == pytest.approx(1e-7, rel=1e-3)


@pytest.mark.parametrize("got", [
    WANT[:1],                                         # a row short
    [(1, 9190, "A", 100.0), (3, 9191, "B", 200.0)],   # a key
    [(1, 9190, "A", 100.0), (2, 9191, "C", 200.0)],   # a string
    [(1, 9190, "A", 100.0), (2, 9192, "B", 200.0)],   # a date
    [(1, 9190, "A", 100.0), (2, 9191, "B", None)],    # a null
    [(1, 9190, "A", 100.0), (2, 9191, "B", float("nan"))],
    [(1, 9190, "A"), (2, 9191, "B")],                 # a column short
    list(reversed([(1, 9190, "A", 100.0), (2, 9191, "B", 200.0)])),
    None,                                             # the query failed
], ids=["row", "key", "string", "date", "null", "nan", "width", "order",
        "failed"])
def test_what_no_tolerance_covers(got):
    assert compare.answer_gap(got, WANT) is None


def test_set_compare_forgives_the_order_only():
    got = [(2, 9191, "B", 200.0), (1, 9190, "A", 100.0)]
    assert compare.answer_gap(got, WANT, as_set=True) == 0.0
    got[0] = (3, 9191, "B", 200.0)
    assert compare.answer_gap(got, WANT, as_set=True) is None


def test_judge_counts_and_limits():
    good = {"query": "q", "rows": list(WANT)}
    near = {"query": "q", "rows": rows({(1, 3): 200.0 * (1 + 1e-12)})}
    far = {"query": "q", "rows": rows({(1, 3): 200.0 * (1 + 1e-7)})}
    bad = {"query": "q", "rows": rows({(1, 0): 7})}
    ref = {"q": WANT}
    v = compare.judge([good, near], ref, set(), sent=2)
    assert v["correct"] and v["checks"]["max_rel_gap"]["value"] < 1e-11
    v = compare.judge([good, far], ref, set(), sent=2)
    assert not v["correct"] and v["wrong"] == [1]
    assert v["checks"]["max_rel_gap"]["value"] > \
        v["checks"]["max_rel_gap"]["limit"]
    v = compare.judge([good, bad], ref, set(), sent=2)
    assert not v["correct"] and v["wrong"] == [1]
    assert v["checks"]["answers_wrong"] == {"value": 1, "limit": 0}
    v = compare.judge([good], ref, set(), sent=2)       # one never came
    assert not v["correct"]
    assert v["checks"]["answers_missing"]["value"] == 1
    assert not compare.judge([], ref, set(), sent=0)["correct"]
