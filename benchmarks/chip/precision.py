"""The program's own precision switch, for the controls.

``repro.solver.plan.MATMUL_PRECISION`` is the precision of every dot the
solver traces (``repro.solver.executor`` keeps a copy of it); the
configurations state ``highest``.  A control is the program with this switch
one step lower.
"""
from __future__ import annotations

import contextlib
import importlib

MODULES = ("repro.solver.plan", "repro.solver.executor")


@contextlib.contextmanager
def switched(precision: str):
    """Every dot of the solver's programs traced inside at ``precision``."""
    import jax

    mods = [importlib.import_module(m) for m in MODULES]
    stated = [m.MATMUL_PRECISION for m in mods]
    jax.clear_caches()  # the solver's jitted stages retrace at ``precision``
    for m in mods:
        m.MATMUL_PRECISION = precision
    try:
        yield
    finally:
        for m, p in zip(mods, stated):
            m.MATMUL_PRECISION = p
        jax.clear_caches()
