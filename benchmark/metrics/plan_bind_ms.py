"""``plan_bind_ms`` (ms/query): the flight recorder's ``planning`` spans
over the traced window, per query: ``infer-schema`` (the footers a
DataFrame built anew opens), ``plan-bind`` (plan cache lookup and bind,
or a whole plan on a miss) and ``replan`` (the adaptive pass in the
collect funnel). Spans of this category never enclose one another, so
the sum is a time. Layer: API, plan cache, planner and cost placement."""


def read(ctx):
    rec = ctx["recorder"]
    if not rec.queries or "planning" not in rec.category_ms:
        return None
    return rec.category_ms["planning"] / rec.queries
