"""Idle share of the device in the traced window, in percent: 1 - busy /
window, busy being the union of the device's operation intervals, averaged
over the chips the cell uses."""


def read(ctx):
    return ctx.idle_share_pct()
