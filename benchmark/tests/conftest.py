"""Tests of the benchmark's own files (PR 24): ``python -m pytest
benchmark/tests -q``. They run on the CPU backend and prove control flow,
the comparison and the reductions; no number they see is a chip's."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
if ROOT not in sys.path:
    sys.path.insert(1, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def rehearse(cell, *more, root=ROOT, seconds="1.5", seed="2147484001",
             trace="0", pythonpath=None):
    """``run.py --rehearse-cpu`` in a process of its own (it sets the
    number of virtual devices before JAX starts). Returns the result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    if pythonpath:
        env["PYTHONPATH"] = pythonpath
    p = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", cell, "--seed", seed, "--seconds", seconds,
         "--trace", trace, "--rehearse-cpu", "--scale", "0.01", *more],
        cwd=root, env=env, capture_output=True, text=True, timeout=1500)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p


@pytest.fixture(scope="session")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)
