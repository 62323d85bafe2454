"""``idle_unattributed_ms`` (ms/query): device idle time whose innermost
host annotation is the benchmark's own ``bench:query``: Python inside the
query under no span of the program, so no layer can be charged with it.
From the same trace as ``device_idle_pct``; 0.0 where the label is not
among the ten longest that ``trace_reduce`` keeps. Layer: device. Nothing
from a CPU trace."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["queries"]:
        return None
    gaps = dict(tr["idle_gaps"])
    return 1e3 * gaps.get("bench:query", 0.0) / tr["queries"]
