"""``plan_host_nodes`` (nodes): plan nodes of the cell's queries that do
not run on the device — fallen back to the host engine
(``phys.host_fallback_nodes()``) or placed there by the cost model
(``phys.cost_report.nodes_host_placed``). Layer: API, plan cache, planner
and cost placement. A node that leaves the device moves ``query_s``."""


def read(ctx):
    return ctx["plan_host_nodes"]
