"""``agg_update_mrows`` (Mrows/query): rows of CAPACITY handed to the
grouped aggregate's update (``HashAggregateExec._update_batch``), in
millions a query: the recorder's counter ``aggUpdateRows`` over its
counter ``collects``, both totals of the process while the recorder was
on. A ratio, so the warm-ups and the collects after the window do no harm
(every collect of one query is handed the same capacities). In q67 it is
nine times what the joins give: what an aggregate pushed under the Expand
would cut. Nothing, and no zero, where the program has no such counter
(the parent of PR 33) or no update ran. Layer: device execs, aggregate
merge tree."""


def read(ctx):
    from spark_rapids_tpu.monitoring import recorder
    counters = getattr(recorder, "counters", None)
    if counters is None:
        return None
    c = counters()
    if not c.get("aggUpdateRows") or not c.get("collects"):
        return None
    return c["aggUpdateRows"] / 1e6 / c["collects"]
