"""Records the tiny trace that ``test_trace_reduce.py`` reduces (PR 24).

    chiprun --chips 1 -- python3 benchmark/tests/record_trace.py chiprun_out/tiny_trace

Three "queries" on a TPU: each a ``bench:query`` annotation around an
operator's annotation around one small jitted program, with a sleep
between them so that the chip is idle for a known share. The file it
leaves (a few KB) is committed as ``data/tpu_v5e_tiny.xplane.pb``; the
numbers the test expects were read from this script's own output.
"""

import glob
import json
import os
import shutil
import sys
import time


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp
    import jax.profiler as profiler
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    f = jax.jit(lambda x: jnp.sort(x * 2.0).cumsum())
    x = jnp.arange(1 << 16, dtype=jnp.float32)
    f(x).block_until_ready()
    tmp = os.path.join(out_dir, "raw")
    opts = profiler.ProfileOptions()
    opts.python_tracer_level = 0
    profiler.start_trace(tmp, profiler_options=opts)
    for _ in range(3):
        with profiler.TraceAnnotation("bench:query"):
            with profiler.TraceAnnotation("SortExec:totalTime"):
                f(x).block_until_ready()
            time.sleep(0.002)
        time.sleep(0.001)
    profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    kept = os.path.join(out_dir, "tpu_v5e_tiny.xplane.pb")
    shutil.copy(path, kept)
    shutil.rmtree(tmp)
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import trace_reduce
    trace_reduce.describe(kept)
    print(json.dumps({"bytes": os.path.getsize(kept),
                      "reduction": trace_reduce.reduce_trace(kept)}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
