"""Backend compiles inside the window (jax.monitoring)."""


def read(ctx):
    return ctx.compiles
