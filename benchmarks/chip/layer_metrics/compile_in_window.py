"""Runtime (jit and the compile cache): backend compiles inside the window,
from ``jax.monitoring``.  Warm-up should leave none."""
NAME = "compile.in_window"


def read(ctx: dict):
    return ctx["compiles"]
