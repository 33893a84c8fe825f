"""Seconds from the start of the process to the start of the measured
window: imports, data, compilation (or loading it from the cache) and one
warm call."""


def read(ctx):
    return ctx.setup_s
