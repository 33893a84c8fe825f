"""Share of its roofline that ``syr2k_lower`` reaches, in percent: the work
of each traced call at its shapes (``work/syr2k_lower.py``) at the chip's
published peaks, over the kernel's summed device time."""


def read(ctx):
    return ctx.roofline_pct("syr2k_lower")
