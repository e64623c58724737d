"""JAX compile: backend compiles inside the window (jax.monitoring's
backend-compile events), with the persistent cache off there."""


def read(ctx):
    return ctx.compiles
