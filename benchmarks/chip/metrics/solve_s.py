"""Seconds per solve: the window's length over the whole calls in it."""


def read(ctx):
    return ctx.window_s / ctx.calls
