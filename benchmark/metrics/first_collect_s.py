"""``first_collect_s`` (s): host clock around the first ``collect()`` of
each query of the mix, summed: tracing, loading or compiling every
program, the first scan and upload. Layer: compile."""


def read(ctx):
    return ctx["setup"]["first_collect_s"]
