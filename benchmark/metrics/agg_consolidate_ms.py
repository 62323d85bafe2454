"""``agg_consolidate_ms`` (ms/query): the flight recorder's
``agg-consolidate`` spans, one a level of ``HashAggregateExec
._consolidate``'s merge tree (the level's sizes pull, its concats and the
dispatch of its merges), over the queries traced. Levels follow one
another, so the sum is a time; it is a host clock over asynchronous
dispatch, and a level's sizes pull holds the wait for the device work of
the level before it (and of the updates before the first). Nothing, and no
zero, where the program has no such span (the parent of PR 33). Layer:
device execs, aggregate merge tree."""


def read(ctx):
    rec = ctx["recorder"]
    if not rec.queries or "agg-consolidate" not in rec.category_ms:
        return None
    return rec.category_ms["agg-consolidate"] / rec.queries
