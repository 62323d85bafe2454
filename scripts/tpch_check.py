"""Dev loop: run TPC-H queries on the CPU backend vs the pandas oracle.

Usage: python scripts/tpch_check.py [q2 q4 ... | all] — SF 0.02 data in
/tmp/tpch_check (regenerated when the datagen version bumps).
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ["JAX_PLATFORMS"] = "cpu"


def main():
    from spark_rapids_tpu.api.dataframe import TpuSession
    from spark_rapids_tpu.benchmarks import tpch
    d = os.environ.get("TPCH_CHECK_DIR", "/tmp/tpch_check")
    tpch.generate(d, scale=float(os.environ.get("TPCH_CHECK_SF", "0.02")),
                  files_per_table=4)
    names = sys.argv[1:] or ["all"]
    if names == ["all"]:
        names = list(tpch.QUERIES)
    failed = []
    for qn in names:
        s = TpuSession()
        s.set("spark.rapids.sql.variableFloatAgg.enabled", True)
        s.set("spark.rapids.sql.hasNans", False)
        t0 = time.perf_counter()
        try:
            got = tpch.QUERIES[qn](s, d).collect()
            want = tpch.pandas_query(qn, d)
            ok = tpch.check_result(qn, got, want)
        except Exception as e:
            print(f"{qn}: EXCEPTION {type(e).__name__}: {e}")
            failed.append(qn)
            continue
        status = "ok" if ok else "MISMATCH"
        print(f"{qn}: {status} rows={len(got)} "
              f"({time.perf_counter() - t0:.1f}s)")
        if not ok:
            failed.append(qn)
            for r in got[:3]:
                print("   got ", r)
            for r in want[:3]:
                print("   want", r)
    if failed:
        print("FAILED:", ",".join(failed))
        sys.exit(1)
    print("all ok")


if __name__ == "__main__":
    main()
