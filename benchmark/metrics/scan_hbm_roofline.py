"""``scan_hbm_roofline`` (%): the least time the chip could take to read
the query's input columns once (``work.py``'s bytes over the HBM peak of
``peaks.py``) over the time it was busy per query in the profiler trace.
The bound is memory bandwidth: TPC-H's arithmetic per byte is far under
the chip's. Layer: device execs. It reads nothing where there is no
device trace, and never 0."""

import peaks


def read(ctx):
    tr = ctx["trace"]
    if not tr:
        return None
    busy_s = tr["devices"][tr["busiest"]] / tr["queries"]
    if busy_s <= 0:
        return None
    peak = peaks.peak(ctx["device_kind"])["hbm_gb_per_sec"] * 1e9
    return 100.0 * (ctx["work_bytes_per_query"] / peak) / busy_s
