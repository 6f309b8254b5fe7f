"""Programs compiled inside the window, loads from the persistent compile
cache included: JAX's own compile events, counted by the harness."""


def read(ctx):
    return ctx.compiles
