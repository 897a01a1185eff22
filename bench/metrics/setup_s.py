"""Seconds from process start to the start of the window: imports,
weights, engine build, compiling or loading every program, and the
mix's warm phase (host clock)."""


def read(ctx):
    return ctx.setup_s
