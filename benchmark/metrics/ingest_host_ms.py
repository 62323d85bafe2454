"""``ingest_host_ms`` (ms/query): the flight recorder's ``host-prefetch``
and ``upload`` spans (parquet decode, wire encode, pack, ``device_put``)
over the whole traced window, per query. Host clock, spans of several
threads summed, so it can pass ``query_s``. Layer: ingest."""


def read(ctx):
    rec = ctx["recorder"]
    if not rec.queries:
        return None
    ms = rec.category_ms.get("host-prefetch", 0.0) \
        + rec.category_ms.get("upload", 0.0)
    return ms / rec.queries
