"""``download_ms`` (ms/query): the flight recorder's ``download`` spans
over the traced window, per query: ``download`` (``download_batches``:
the result's device-to-host copies and the wait for them) and ``to-rows``
(the host batches made Python rows), one after the other. Layer: result
download. A program without the category (before PR 25) reports
nothing."""


def read(ctx):
    rec = ctx["recorder"]
    if not rec.queries or "download" not in rec.category_ms:
        return None
    return rec.category_ms["download"] / rec.queries
