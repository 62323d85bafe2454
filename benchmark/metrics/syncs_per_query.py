"""``syncs_per_query`` (syncs/query): blocking device-to-host reads
(``monitoring/syncs.py``'s ``sync`` spans) in the traced window, per
query. Each costs a round trip (0.9 ms on the v5e, PR 21) and drains the
dispatch queue. Layer: host syncs."""


def read(ctx):
    rec = ctx["recorder"]
    return rec.syncs / rec.queries if rec.queries else None
