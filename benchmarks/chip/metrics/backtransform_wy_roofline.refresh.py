"""Share of its roofline that ``backtransform_wy`` (the Q2 back-transform)
reaches, in percent: the work of each traced call at its shapes
(``work/backtransform_wy.py``) at the chip's published peaks, over the
kernel's summed device time."""


def read(ctx):
    return ctx.roofline_pct("backtransform_wy")
