"""Pallas TPU kernels for the paper's compute hot spots.

  syr2k         — lower-triangular-tile symmetric rank-2k update (paper §5.2)
  fused_panel   — fused panel QR + compact-WY trailing update with the
                  factors VMEM-resident across the trailing sweep (§5.1/§5.2)
  bulge         — VMEM-resident grouped-wavefront bulge chasing, dense or
                  band-strip layout, with optional reflector-log emission
                  (paper §4.2/§5.3)
  panel         — fused Householder panel QR in WY form (paper §5.1)
  backtransform — VMEM-resident blocked compact-WY eigenvector
                  back-transform (DESIGN.md §6)
  mark          — ``evd_mark_<stage>``: a named no-op that marks a stage
                  boundary of a solve on the device timeline

The framework resolves these through ``repro.backend.registry`` (which also
owns the interpret-mode decision and tile defaults); oracles live in
``repro.kernels.ref`` and the dispatch ceilings in ``repro.kernels.limits``.
Kernels execute with ``interpret=True`` off-TPU (validation) and compile on
real TPUs.
"""
from .ops import (
    syr2k,
    trailing_update,
    fused_panel_update,
    bulge_wavefront,
    panel_qr,
    backtransform_wy,
)

__all__ = [
    "syr2k",
    "trailing_update",
    "fused_panel_update",
    "bulge_wavefront",
    "panel_qr",
    "backtransform_wy",
]
