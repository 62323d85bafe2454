"""The control of ``correct`` comes out as not correct: the reference
computed in float32, put in the program's place, at a size a test can
hold (the chip-size readings are in PERF.md)."""

import json

import pytest

import control


@pytest.mark.parametrize("cell", ["tpch_sf1_resident_q1",
                                  "tpch_sf1_parquet_q6",
                                  "tpch_sf1_resident_q3"])
def test_float32_reference_is_not_correct(cell, bench, capsys):
    if cell not in {w["name"] for w in bench["workloads"]}:
        pytest.skip(f"{cell} is not in BENCHMARK.json")
    # q6's answer is ONE float: its float32 gap lies anywhere under 6e-8,
    # so one seed in some hundreds lands under the limit by chance (seed
    # 1 at this scale reads 3.3e-10). PERF.md, "How correct is decided".
    passed = control.main(["--workload", cell, "--seeds", "2,3,4",
                           "--scale", "0.01"])
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()]
    assert passed == 0 and len(lines) == 3
    for ln in lines:
        assert not ln["correct"]
        assert ln["control_gap"] > 3 * ln["limit"]
