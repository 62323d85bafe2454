"""``scan_cache_resident_gb`` (GB): what the device scan cache holds
after the window (``io/scan.py counters()``'s ``scanCacheResidentBytes``:
the device bytes of every decoded unit it keeps), against its budget of
4 GiB = 4.29 GB by default: how near the deployment is to the point where
units start to miss. Nothing, and no zero, where the program has no such
counter (the parent of PR 35) or the cache holds nothing (a configuration
without one). Layer: ingest, scan cache."""


def read(ctx):
    from spark_rapids_tpu.io import scan
    counters = getattr(scan, "counters", None)
    if counters is None:
        return None
    return counters()["scanCacheResidentBytes"] / 1e9 or None
