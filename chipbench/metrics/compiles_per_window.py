"""Programs JAX builds inside the measured window, compiled or loaded
from the persistent cache (the ``backend_compile_duration`` monitoring
event), per admission window."""


def read(ctx):
    if not ctx.get("windows"):
        return None
    return ctx["compiles"] / ctx["windows"]
