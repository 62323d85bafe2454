"""``mesh_exchange_ms`` (ms/query): the flight recorder's
``mesh-exchange`` spans (``MeshExchangeExec._materialize``'s phases:
shard, pids, counts, collective, land, unfold) over the queries traced.
The spans are never nested in one another and never enclose the pull of
the child, so their sum is a time; it is a host clock over asynchronous
dispatch, and the phase that blocks (``counts``) also holds the wait for
the child's device work. Nothing, and no zero, where the program has no
such span (the parent of PR 27). Layer: shuffle, mesh exchange."""


def read(ctx):
    rec = ctx["recorder"]
    if not rec.queries or "mesh-exchange" not in rec.category_ms:
        return None
    return rec.category_ms["mesh-exchange"] / rec.queries
