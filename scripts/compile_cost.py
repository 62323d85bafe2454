"""Compile seconds of a cell's join-probe programs for a v5e, without one (PR 30).

A cold process is a compiler benchmark (PERF.md, PR 21), and how rows are
packed (``columnar/rowmove.py``) is in every program that gathers a batch.
This runs a cell's CPU rehearsal at scale 1 in a checkout, records the
argument shapes of every ``_dense_step`` program (the dense join probe,
``ops/join.py``; since PR 37 its late forms ``_late_lookup`` and
``_late_emit`` too) the cell dispatches, then lowers and compiles each for a
DESCRIBED v5e chip with the persistent cache off, and prints seconds per
program. Point it at two checkouts to compare them on one host::

    git archive HEAD | tar -x -C .scratch/parent
    python scripts/compile_cost.py .scratch/parent
    python scripts/compile_cost.py .

The TPU's compiler runs on this host (guide on-chip-measurement, section
2), so the seconds are this host's, not the chip machine's: compare trees,
not machines. PR 30's readings are in PERF.md, section 6.
"""

import json
import os
import sys
import time


def main(argv) -> int:
    root = os.path.abspath(argv[1] if len(argv) > 1 else ".")
    cell = argv[2] if len(argv) > 2 else "tpch_sf1_mesh4_q5"
    os.chdir(root)
    sys.path[:0] = [root, os.path.join(root, "benchmark")]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    import spark_rapids_tpu  # noqa: F401  (x64)
    from spark_rapids_tpu.ops import join

    seen = {}

    def recording(name, kernel):
        def call(*args, **static):
            avals = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args)
            key = (name, str(jax.tree.structure(avals)),
                   str(jax.tree.leaves(avals)), str(sorted(static.items())))
            seen.setdefault(key, (name, kernel.fn, avals, static))
            return kernel(*args, **static)
        return call

    dense = join._JoinKernelMixin._dense_jit_fn
    join._JoinKernelMixin._dense_jit_fn = \
        lambda self: recording("_dense_step", dense(self))
    # the late dense probe's two programs (PR 37; absent before)
    late = getattr(join, "_late_jit_fns", None)
    if late is not None:
        join._late_jit_fns = lambda: tuple(
            recording(name, kernel)
            for name, kernel in zip(("_late_lookup", "_late_emit"), late()))
    import run as bench_run
    rc = bench_run.main(["--workload", cell, "--seed", "1", "--seconds", "1",
                         "--trace", "0", "--rehearse-cpu", "--scale", "1"])
    if rc:
        return rc

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    total = 0.0
    for name, fn, avals, static in seen.values():
        avals = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=chip), avals)
        lowered = fn.lower(*avals, **static)
        t0 = time.perf_counter()
        lowered.compile()
        seconds = time.perf_counter() - t0
        total += seconds
        print(json.dumps({
            "program": name, "compile_s": round(seconds, 2),
            "static": {k: v for k, v in static.items() if k == "out_cap"},
            "leaves": sorted({f"{x.dtype}{list(x.shape)}"
                              for x in jax.tree.leaves(avals)})}),
              flush=True)
    print(json.dumps({"tree": root, "cell": cell, "programs": len(seen),
                      "total_compile_s": round(total, 2)}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
