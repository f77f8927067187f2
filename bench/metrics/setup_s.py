"""Seconds from process start to the window's open: imports, weights,
engine, warm-up, and any compilation or cache loading."""


def read(ctx):
    return ctx.setup_s
