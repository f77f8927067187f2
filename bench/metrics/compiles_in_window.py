"""Device runtime: programs JAX built or loaded (a persistent-cache hit
counts) between the window's open and its close. Should read 0."""


def read(ctx):
    return ctx.compiles_in_window
