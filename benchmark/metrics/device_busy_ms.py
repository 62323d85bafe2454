"""``device_busy_ms`` (ms/query): the union of the intervals in which an
operation ran on the busiest chip, from the profiler trace, over the
whole queries the trace holds. Layer: device execs. Nothing from a CPU
trace."""


def read(ctx):
    tr = ctx["trace"]
    if not tr:
        return None
    return 1e3 * tr["devices"][tr["busiest"]] / tr["queries"]
