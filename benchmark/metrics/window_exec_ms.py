"""``window_exec_ms`` (ms/query): the flight recorder's ``window`` spans
(``WindowExec.execute_device`` through ``ops/sort.py
out_of_core_partition``: ``gather`` the staged child batches into one,
``split`` on the out-of-core path, ``compute`` the dispatch of
``compute_window``) over the queries traced. The spans are never nested in
one another and never enclose the pull of the child, so their sum is a
time; it is a host clock over asynchronous dispatch. Nothing, and no zero,
where the program has no such span (the parent of PR 33). Layer: device
execs, window."""


def read(ctx):
    rec = ctx["recorder"]
    if not rec.queries or "window" not in rec.category_ms:
        return None
    return rec.category_ms["window"] / rec.queries
