"""``scan_cache_refill_mb`` (MB/query): device bytes of scan units that
were decoded and uploaded AGAIN — the device scan cache had met their key
before and did not hold it (evicted, or turned away for want of room) —
in megabytes a query: ``io/scan.py counters()``'s ``scanCacheRefillBytes``
over the recorder's ``collects``, both totals of the process. A ratio, and
a unit's first upload is no refill, so the fill of the warm-ups does no
harm. 0.0 where the deployment is resident, as its configuration says;
anything else says the cell measures ingest. Nothing, and no zero, where
the program has no such counter (the parent of PR 35) or the recorder
counted no collect. Layer: ingest, scan cache."""


def read(ctx):
    from spark_rapids_tpu.io import scan
    from spark_rapids_tpu.monitoring import recorder
    counters = getattr(scan, "counters", None)
    collects = getattr(recorder, "counters", dict)().get("collects")
    if counters is None or not collects:
        return None
    return counters()["scanCacheRefillBytes"] / 1e6 / collects
