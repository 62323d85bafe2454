#!/bin/sh
# benchmark/prove.sh <cell> <seconds> <tag> "<seeds for --trace 0>" ["<seeds for --trace 1>"]
#
# One set of a cell's runs in ONE chip call: every run is a process of
# its own, all share the compile cache inside the checkout, each run's
# standard output and error go to chiprun_out/prove/<cell>/, and the end
# of the call is summarize.py's table (per run, then median and quartile
# spread per metric). Exit code: the number of runs that printed no
# result or were not correct.
#
#   chiprun --chips 1 --timeout 1800 -- sh benchmark/prove.sh \
#       tpch_sf1_resident_q1 30 set1 "11 12 13 14 15 16" "17"
set -u
cell=$1; seconds=$2; tag=$3; seeds0=$4; seeds1=${5:-}
out=chiprun_out/prove/$cell
mkdir -p "$out"
# run.py keeps the compile cache at <checkout>/.jax_cache, whatever the
# environment says. Where the machine brings a cache directory that
# outlives the call, let that path lead there, so that the builder's next
# call finds this one's programs (same path, so the same keys).
# A .jax_cache that came with the copy holds the sandbox's CPU programs,
# written without the "-atime" files that a machine with a bounded cache
# asks of every entry: every write then fails (my first chip call).
if [ -n "${JAX_COMPILATION_CACHE_DIR:-}" ] && [ ! -L .jax_cache ]; then
    rm -rf .jax_cache
    mkdir -p "$JAX_COMPILATION_CACHE_DIR" && ln -s "$JAX_COMPILATION_CACHE_DIR" .jax_cache
    echo "compile cache: .jax_cache -> $JAX_COMPILATION_CACHE_DIR ($(ls .jax_cache | wc -l) entries)"
fi
run() {
    trace=$1; seed=$2
    base=$out/${tag}_t${trace}_s${seed}
    t0=$(date +%s)
    python3 benchmark/run.py --workload "$cell" --seed "$seed" \
        --seconds "$seconds" --trace "$trace" ${KEEP_TRACE:+--keep-trace "$out/trace"} \
        > "$base.out" 2> "$base.err"
    echo "run $cell tag=$tag trace=$trace seed=$seed rc=$? wall=$(( $(date +%s) - t0 ))s"
}
for s in $seeds0; do run 0 "$s"; done
for s in $seeds1; do run 1 "$s"; done
python3 benchmark/summarize.py "$out" "$tag"
