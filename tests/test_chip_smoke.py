"""chip_smoke.py's contract, as far as a machine without a chip can hold
it: no chip -> non-zero exit and no result; the CPU rehearsal walks every
phase and names the CPU; a failure injected into any phase surfaces as a
failure, never as the result line."""

import json
import os
import subprocess
import sys

import pytest

import chip_smoke

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARGS = ["--cpu-rehearsal", "--scale", "0.003"]


@pytest.fixture(autouse=True)
def _restore_env(monkeypatch):
    """main() names the CPU in the environment before jax starts; in this
    (already started) process that must not leak into later tests'
    subprocesses."""
    for k in ("JAX_PLATFORMS", "XLA_FLAGS"):
        monkeypatch.setenv(k, os.environ.get(k, ""))


def test_no_chip_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == "", "printed something without a chip"
    assert "need a tpu backend" in out.stderr


def test_cpu_rehearsal_walks_every_phase_and_names_the_cpu(capsys):
    assert chip_smoke.main(ARGS) == 0
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()]
    last = lines[-1]
    assert last["ok"] is True and set(last) == {"ok", "device"}
    assert last["device"]["platform"] == "cpu"       # truthfully named
    phases = [ln.get("phase") for ln in lines[:-1]]
    for want in ("environment", "sync_round_trip", "native_build",
                 "datagen", "oracle", "collect", "query", "recovery",
                 "to_jax", "compile_cache", "device_memory"):
        assert want in phases, f"phase {want} printed nothing"
    queries = {ln["query"]: ln for ln in lines if ln.get("phase") == "query"}
    assert set(queries) == set(chip_smoke.QUERIES)
    assert all(q["correct"] for q in queries.values())
    third = [ln for ln in lines
             if ln.get("phase") == "collect" and ln["run"] == 3]
    assert len(third) == len(chip_smoke.QUERIES)
    assert all(c["kernel_cache_misses"] == 0 for c in third)
    assert all(c["programs_compiled"] == 0 for c in third)
    conf = [ln for ln in lines if ln.get("phase") == "conf"]
    assert conf == [{"phase": "conf", "non_default": {}}]   # default conf


def test_single_chip_session_is_the_default_plus_two_data_assertions():
    # The smoke proves the DEFAULT path: its session carries two
    # assertions about the data and not one engine setting.
    assert chip_smoke.session().conf.raw == {
        "spark.rapids.sql.variableFloatAgg.enabled": True,
        "spark.rapids.sql.hasNans": False}


def test_mesh_phase_turns_cost_placement_off_only_on_the_inprocess_side():
    cost = "spark.rapids.sql.cost.enabled"
    mesh_conf, mesh_why = chip_smoke.MESH_SIDES["mesh"]
    inproc_conf, inproc_why = chip_smoke.MESH_SIDES["inprocess"]
    assert cost not in mesh_conf and inproc_conf[cost] is False
    # The reasons mesh_case holds the planner to are the planner's own.
    from spark_rapids_tpu.config import TpuConf
    from spark_rapids_tpu.plan import cost as C
    assert C._placement_gates(TpuConf(inproc_conf), None) == inproc_why
    assert C._placement_gates(TpuConf(mesh_conf), None) == mesh_why


def _oracle_mismatch(monkeypatch):
    from spark_rapids_tpu.benchmarks import tpch
    monkeypatch.setattr(tpch, "check_result", lambda *a, **k: False)


def _host_fallback_counted(monkeypatch):
    from spark_rapids_tpu import faults
    real = faults.counters
    monkeypatch.setattr(faults, "counters",
                        lambda: dict(real(), hostFallbacks=1))


def _host_placed_plan(monkeypatch):
    # A floor that prices every fixture cheaper on the host: the smoke
    # must refuse a plan the cost model moved off the device.
    from spark_rapids_tpu.plan import cost
    monkeypatch.setattr(cost, "effective_sync_floor_ms", lambda conf: 1e6)


def _retrace_inside_a_cached_kernel(monkeypatch):
    # A new shape inside a cached jit never misses the kernel cache; only
    # the backend's compile events show it.
    real = chip_smoke.CompileClock.since
    monkeypatch.setattr(
        chip_smoke.CompileClock, "since",
        lambda self, mark: dict(real(self, mark), programs_compiled=1))


@pytest.mark.parametrize("inject", [
    _oracle_mismatch, _host_fallback_counted, _host_placed_plan,
    _retrace_inside_a_cached_kernel], ids=lambda f: f.__name__.strip("_"))
def test_injected_failure_in_any_phase_fails_the_smoke(inject, monkeypatch,
                                                       capsys):
    inject(monkeypatch)
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.main(ARGS)
    assert '"ok"' not in capsys.readouterr().out
