"""Pallas TPU kernel: a named mark on the device timeline.

The solver's executable runs as one compiled program, so no Python code of
the program runs while it executes and a host-side span cannot say where
one stage ends and the next begins.  A mark is the smallest operation that
can: a kernel named ``evd_mark_<stage>`` that copies one (8, 128) float32
tile, placed by ``repro.solver.plan`` between the last operation of a stage
and the first of the next.  A profiler trace then shows it on the device's
own clock, under its name, and the compiled HLO lists it as a
``tpu_custom_call`` in stage order.
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["MARK_PREFIX", "MARK_TILE", "stage_mark_pallas"]

MARK_PREFIX = "evd_mark_"
MARK_TILE = (8, 128)    # one float32 vreg


def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def stage_mark_pallas(tile: jax.Array, stage: str, *, interpret: bool = False) -> jax.Array:
    """``tile`` unchanged, through a kernel named ``evd_mark_<stage>``.

    ``stage`` is a name of letters, digits and underscores: a trace reader
    strips a trailing ``.<digits>`` from instruction names, so a dot in it
    would be lost.
    """
    if not stage.replace("_", "").isalnum():
        raise ValueError(f"stage names are letters, digits and '_', got {stage!r}")
    return pl.pallas_call(
        _copy_kernel,
        out_shape=jax.ShapeDtypeStruct(tile.shape, tile.dtype),
        # Side effects keep XLA from removing a kernel whose output no
        # computation reads.
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
        interpret=interpret,
        name=MARK_PREFIX + stage,
    )(tile)
