"""``window_compile_ms`` (ms/query): the flight recorder's ``compile``
spans (jax's ``backend_compile_duration`` events, recorded by a listener
the recorder registers when it is enabled) over the traced window, per
query. 0.0 where nothing compiled: every program was warmed in set-up.
Layer: compile. The recorder says itself whether it listens
(``snapshot()["listeners"]``, PR 25): a program whose recorder does not
reports nothing, not a zero."""


def read(ctx):
    from spark_rapids_tpu.monitoring import recorder
    rec = ctx["recorder"]
    if not rec.queries or \
            "compile" not in recorder.snapshot().get("listeners", ()):
        return None
    return rec.category_ms.get("compile", 0.0) / rec.queries
