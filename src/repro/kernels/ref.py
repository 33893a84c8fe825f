"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

Each function is the mathematically transparent version of its kernel; the
per-kernel tests sweep shapes/dtypes and assert_allclose kernel vs oracle.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

__all__ = [
    "syr2k_ref",
    "trailing_update_ref",
    "fused_panel_update_ref",
    "symm_ref",
    "panel_qr_ref",
    "bulge_sweep_ref",
]


def syr2k_ref(
    A: jax.Array,
    B: jax.Array,
    C: Optional[jax.Array] = None,
    *,
    alpha: float = 1.0,
) -> jax.Array:
    """C + alpha * (A B^T + B A^T), full symmetric."""
    S = alpha * (A @ B.T + B @ A.T)
    return S if C is None else C + S


def trailing_update_ref(C: jax.Array, Y: jax.Array, Z: jax.Array) -> jax.Array:
    """The DBR trailing update: C - Z Y^T - Y Z^T."""
    return C - Z @ Y.T - Y @ Z.T


def fused_panel_update_ref(Bv: jax.Array, b: int, w: int):
    """Oracle for the fused panel+trailing kernel: ``_reduce_block``.

    geqrf panel QRs + the jnp trailing update — the jnp registry op, which
    the Pallas kernel is tested against allclose.
    Returns ``(new_view, Vbuf (m, w), Ts (w//b, b, b))``.
    """
    from repro.core.band_reduction import _reduce_block
    from repro.core.panel_qr import panel_qr_geqrf

    return _reduce_block(Bv, b, w, panel_qr_geqrf, trailing_update_ref)


def symm_ref(A: jax.Array, V: jax.Array) -> jax.Array:
    """A @ V with A symmetric (oracle ignores the symmetry)."""
    return A @ V


def panel_qr_ref(panel: jax.Array):
    """Oracle for the panel-QR kernel: the scan-based Householder QR."""
    from repro.core.panel_qr import panel_qr_householder

    return panel_qr_householder(panel)


def bulge_sweep_ref(B: jax.Array, b: int):
    """Oracle for the bulge-chasing kernel: the sequential executor."""
    from repro.core.bulge_chasing import chase_sequential

    return chase_sequential(B, b)
