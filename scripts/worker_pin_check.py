"""worker_pin_check.py — does ``worker_env("tpu:<i>")`` give a child exactly one chip?

    python scripts/worker_pin_check.py        (on a host with four chips)

Starts two children AT ONCE, pinned to chips 1 and 3, and prints what
each one's ``jax.devices()`` holds. The parent never initializes a JAX
backend — the rule every launcher of this repo follows
(``scripts/cluster.py``, ``parallel/cluster/supervisor.py``): a process
that has touched JAX holds the chip, and a child that needs it then
fails or hangs. Exit 0 only if each child saw exactly one TPU device.
"""

import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CHILD = ("import jax, json; d = jax.devices(); "
         "print(json.dumps({'n': len(d), 'platform': d[0].platform, "
         "'kind': d[0].device_kind, "
         "'coords': [list(getattr(x, 'coords', [])) for x in d]}))")


def main() -> int:
    from spark_rapids_tpu.parallel.cluster.worker import worker_env
    procs = {i: subprocess.Popen(
        [sys.executable, "-c", CHILD], env=worker_env(f"tpu:{i}"),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for i in (1, 3)}
    ok = True
    for i, p in procs.items():
        try:
            out, err = p.communicate(timeout=150)
        except subprocess.TimeoutExpired:
            p.kill()
            p.communicate()
            out, err = "", "timed out waiting for the chip"
        seen = json.loads(out) if p.returncode == 0 and out.strip() else None
        ok &= bool(seen) and seen["n"] == 1 and seen["platform"] == "tpu"
        print(json.dumps({"worker_pin_check": f"tpu:{i}", "rc": p.returncode,
                          "devices": seen,
                          "stderr_tail": "" if seen else err.strip()[-300:]}),
              flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
