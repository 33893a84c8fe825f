"""Device milliseconds of inverse iteration per refresh: from the end of
``evd_mark_bisection`` to the start of ``evd_mark_inverse_iteration``,
averaged over the traced refreshes that hold both marks (``stages.py``).
Under ``vmap`` one mark stands for the whole stack."""
import stages


def read(ctx):
    if ctx.trace is None:
        return None
    spans = stages.intervals(ctx.trace, "bisection", "inverse_iteration")
    if not spans:
        return None
    return 1e-6 * sum(e - s for _, s, e in spans) / len(spans)
