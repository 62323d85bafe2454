"""``mesh_exchange_padding_pct`` (%): of the bytes the mesh collectives
were shaped to move (``meshWireBytes``: n x n pieces of one static
capacity each), the share that was no live row (``meshLiveBytes``).
Process-wide totals of ``parallel/mesh_exchange.counters()``: a ratio,
so the warm-ups' exchanges and those after the window do no harm.
Nothing where the program has no such counter (the parent of PR 27) or
no mesh exchange ran. Layer: shuffle, mesh exchange."""


def read(ctx):
    from spark_rapids_tpu.parallel import mesh_exchange
    counters = getattr(mesh_exchange, "counters", None)
    if counters is None:
        return None
    c = counters()
    if not c.get("meshWireBytes"):
        return None
    return 100.0 * (1.0 - c.get("meshLiveBytes", 0) / c["meshWireBytes"])
