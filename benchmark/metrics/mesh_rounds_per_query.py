"""``mesh_rounds_per_query`` (rounds/query): the rounds the mesh
exchanges went over in, a query: ``parallel/mesh_exchange.counters()``'s
``meshRounds`` over the flight recorder's ``collects``, both totals of
the process. A ratio, so the warm-ups and the collects after the window
do no harm (every collect of one query deals the same batches). Equal to
the exchanges a query where each moves a device's share in one round; a
child whose partitions are many batches goes over in more. Nothing, and
no zero, where the program has no such counter (one from before the
exchange went in rounds) or the recorder counted no collect. Layer:
shuffle, mesh exchange."""


def read(ctx):
    from spark_rapids_tpu.monitoring import recorder
    from spark_rapids_tpu.parallel import mesh_exchange
    counters = getattr(mesh_exchange, "counters", None)
    collects = getattr(recorder, "counters", dict)().get("collects")
    if counters is None or not collects:
        return None
    rounds = counters().get("meshRounds")
    if not rounds:
        return None
    return rounds / collects
