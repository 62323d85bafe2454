"""``compile_s`` (s): seconds the backend spent compiling during set-up,
from jax's ``backend_compile_duration`` events (eager programs and every
jit). Layer: compile. With every program in the persistent cache it is
what the cache does not keep (programs under 0.3 s)."""


def read(ctx):
    return ctx["setup"]["compile_s"]
