"""``join_build_ms`` (ms/query): the flight recorder's ``join-build``
spans, one a build side of a hash join (from its child's last batch to
the table ready: the concat into one batch, the fingerprint sort of
``_build_side``, the dense table), over the queries traced. Build sides
follow one another, so the sum is a time. Each span ends when the device
has what it dispatched (with the recorder on the program waits for the
sorted side and for the table before it closes them), so the time is the
build's on the device and the host's dispatch in front of it, not the
dispatch alone. Nothing, and no zero, where the program has no such span
(the parent of PR 35) or no join ran. Layer: device execs, join."""


def read(ctx):
    rec = ctx["recorder"]
    if not rec.queries or "join-build" not in rec.category_ms:
        return None
    return rec.category_ms["join-build"] / rec.queries
