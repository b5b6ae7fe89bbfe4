"""Programs built inside the window (backend compiles and persistent-cache
loads, from ``jax.monitoring``).  Compiles belong in set-up."""


def read(ctx):
    return ctx.get("window_compiles")
