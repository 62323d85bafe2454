"""``device_idle_pct`` (%): 1 - busy / traced window on the busiest chip,
same trace as ``device_busy_ms``. Layer: device. What the host keeps the
chip waiting for is in the result's ``breakdown.idle_gaps``."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["devices"][tr["busiest"]] / tr["window_s"])
