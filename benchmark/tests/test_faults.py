"""``correct`` comes out false when the timed path is broken underneath.

Of the faults a cell can have, these cells have one: an answer altered
where it is produced (no state is stepped, no batch averaged, no chip
exchanges). The harness's look for a chip is skipped (the CPU rehearsal)
and the rest of a run is driven as it is, with ``DataFrame.collect``
altered underneath, in the window only: the set-up's answers stay true.
"""

import json

import pytest

import run


def broken_collect(real, fault, after=3):
    calls = {"n": 0}

    def collect(self, *a, **kw):
        rows = real(self, *a, **kw)
        calls["n"] += 1
        if calls["n"] != after:              # one answer, in the window
            return rows
        if fault == "raises":
            raise RuntimeError("planted")
        rows = [list(r) for r in rows]
        f = [isinstance(v, float) for v in rows[0]].index(True)
        if fault == "float_off_by_1e-7":
            rows[0][f] = rows[0][f] * (1 + 1e-7)
        elif fault == "row_dropped":
            rows = rows[1:]
        elif fault == "float_zeroed":
            rows[0][f] = 0.0
        return [tuple(r) for r in rows]
    return collect


@pytest.mark.parametrize("fault", ["float_off_by_1e-7", "float_zeroed",
                                   "row_dropped", "raises", None])
def test_an_altered_answer_is_not_correct(fault, monkeypatch, capsys):
    from spark_rapids_tpu.api.dataframe import DataFrame
    if fault:
        monkeypatch.setattr(DataFrame, "collect",
                            broken_collect(DataFrame.collect, fault))
    rc = run.main(["--workload", "tpch_sf1_resident_q1", "--seed", "77",
                   "--seconds", "0.5", "--trace", "0", "--rehearse-cpu",
                   "--scale", "0.01"])
    assert rc == 0
    out, err = capsys.readouterr()
    res = json.loads(out.strip().splitlines()[-1])
    assert res["correct"] is (fault is None)
    checks = res["checks"]
    if fault is None:
        assert res["failed"] == 0
        return
    assert res["failed"] == 1
    # the answer at fault is shown, as received, beside the reference's
    shown = res["wrong_answers"]
    assert len(shown) == 1 and shown[0]["in_window"]
    assert (shown[0]["error"] is not None) is (fault == "raises")
    assert "wrong answer: " in err and list(res)[-1] == "checks"
    if fault == "float_off_by_1e-7":
        assert checks["max_rel_gap"]["value"] > checks["max_rel_gap"]["limit"]
        assert checks["answers_wrong"]["value"] == 0
        assert "OVER" in err
    elif fault == "float_zeroed":
        assert checks["max_rel_gap"]["value"] == 1.0
    else:
        assert checks["answers_wrong"]["value"] == 1
