"""``mesh_chip_balance_pct`` (%): busy time of the least busy chip over
that of the busiest, from the same trace and the same whole queries as
``device_busy_ms``. 100 is a mesh whose chips work alike; a few per cent
is one chip doing every operator above the exchanges while the others
take part in the collectives alone. Nothing with fewer than two devices
in the trace, or none busy, or from a CPU trace. Layer: shuffle, mesh
exchange."""


def read(ctx):
    tr = ctx["trace"]
    if not tr:
        return None
    busy = list(tr["devices"].values())
    if len(busy) < 2 or max(busy) <= 0:
        return None
    return 100.0 * min(busy) / max(busy)
