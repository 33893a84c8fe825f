"""Pallas TPU kernel: Householder panel QR in WY form (DBR's panel factor).

The scan-based reference (`repro.core.panel_qr.panel_qr_householder`) issues
one XLA op sequence per column; for the b-wide panels DBR factors thousands
of times that launch/loop overhead dominates.  This kernel keeps the whole
panel in VMEM and unrolls the b column steps inside one kernel invocation —
the TPU equivalent of the fused TSQR panel kernels the paper leverages
([2, 3, 42] in its bibliography).

The panel is held TRANSPOSED, (b, m): a b-wide column panel would fill b
of the 128 lanes of every vector register, its transpose fills them all.

Outputs: V (m, b) unit-lower-trapezoidal, T (b, b) upper-triangular compact
WY factor, taus (b,), R (b, b).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

__all__ = ["panel_qr_pallas", "panel_qr_rows"]


def panel_qr_rows(PT: jax.Array, b: int, *, p0=0, lapack_sign: bool = False):
    """The in-kernel panel-QR math on a TRANSPOSED (b, m) panel VALUE.

    Row ``j`` of ``PT`` is panel column ``j``; the panel's rows are lanes
    ``[p0, m)`` (``p0`` may be traced), so column ``j`` pivots at lane
    ``p0 + j`` and lanes below ``p0`` are carried through untouched.  Unrolls
    the b Householder column steps and the larft T recurrence with masked
    whole-array updates and reductions only (no dynamic gathers, no 1-D
    values), so it lowers both as a standalone Pallas kernel body
    (:func:`panel_qr_pallas`) and inlined inside larger fused kernels
    (``repro.kernels.fused_panel``).

    Returns ``(VT, T, taus, RT)``: the reflectors as rows (b, m), the
    compact-WY T (b, b), the taus as a (b, 1) column, and the factored
    panel, transposed — lanes below ``p0`` as given, R^T in lanes
    ``[p0, p0 + b)``, zeros below.  With ``lapack_sign=False`` the reflector
    signs follow ``repro.core.panel_qr.panel_qr_householder`` (beta =
    +|x|, this kernel's historical convention); with ``lapack_sign=True``
    they follow LAPACK ``larfg`` / ``panel_qr_geqrf`` (beta =
    -sign(alpha)·|x|), which the fused first-stage kernel uses so its output
    is comparable to the geqrf-based ``_reduce_block``.
    """
    m = PT.shape[1]
    dtype = PT.dtype
    lanes = lax.broadcasted_iota(jnp.int32, (1, m), 1)
    rows = lax.broadcasted_iota(jnp.int32, (b, 1), 0)
    cols = lax.broadcasted_iota(jnp.int32, (1, b), 1)

    def row(X, j):  # row j of X as a (1, width) value
        return jnp.sum(jnp.where(rows == j, X, 0.0), axis=0, keepdims=True)

    VT = jnp.zeros((b, m), dtype)
    taus = jnp.zeros((b, 1), dtype)

    for j in range(b):  # static unroll: the column recurrence is sequential
        p = p0 + j
        x = row(PT, j)
        alpha = jnp.sum(jnp.where(lanes == p, x, 0.0))
        sigma = jnp.sum(jnp.where(lanes > p, x * x, 0.0))
        mu = jnp.sqrt(alpha * alpha + sigma)
        degenerate = sigma == 0
        if lapack_sign:
            sign_a = jnp.where(alpha >= 0, 1.0, -1.0)
            beta_nd = -sign_a * mu
            safe_beta = jnp.where(beta_nd == 0, jnp.ones((), dtype), beta_nd)
            tau = jnp.where(degenerate, 0.0, (beta_nd - alpha) / safe_beta)
            beta = jnp.where(degenerate, alpha, beta_nd)
            # alpha - beta = sign(alpha)(|alpha| + mu): no cancellation.
            denom = alpha - beta_nd
            v0_safe = jnp.where(denom == 0, jnp.ones((), dtype), denom)
        else:
            safe_denom = jnp.where(alpha + mu == 0, jnp.ones((), dtype), alpha + mu)
            v0 = jnp.where(alpha <= 0, alpha - mu, -sigma / safe_denom)
            v0_safe = jnp.where(degenerate, jnp.ones((), dtype), v0)
            tau = jnp.where(
                degenerate, 0.0, 2.0 * v0_safe * v0_safe / (sigma + v0_safe * v0_safe)
            )
            beta = jnp.where(degenerate, alpha, mu)
        v = jnp.where(lanes == p, 1.0, jnp.where(lanes > p, x / v0_safe, 0.0))
        # Apply H to the remaining columns.
        w = jnp.sum(PT * v, axis=1, keepdims=True)  # (b, 1)
        w = jnp.where(rows >= j, w, 0.0)
        PT = PT - tau * w * v
        # Column j: exact (beta above-diagonal part preserved).
        newrow = jnp.where(lanes == p, beta, jnp.where(lanes < p, row(PT, j), 0.0))
        PT = jnp.where(rows == j, newrow, PT)
        VT = jnp.where(rows == j, v, VT)
        taus = jnp.where(rows == j, tau, taus)

    # T = larft(V, taus), unrolled.
    VtV = lax.dot_general(
        VT, VT, (((1,), (1,)), ((), ())),
        precision=lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    ).astype(dtype)
    T = jnp.zeros((b, b), dtype)
    for j in range(b):
        tau_j = jnp.sum(jnp.where(rows == j, taus, 0.0))
        rhs = jnp.where(cols < j, row(VtV, j), 0.0)  # V_j^T V_k, k < j
        tcol = -tau_j * jnp.sum(T * rhs, axis=1, keepdims=True)
        tcol = jnp.where(rows < j, tcol, 0.0)
        tcol = jnp.where(rows == j, tau_j, tcol)
        T = jnp.where(cols == j, tcol, T)

    return VT, T, taus, PT


def _panel_qr_kernel(p_ref, v_ref, t_ref, tau_ref, r_ref, *, b: int):
    VT, T, taus, RT = panel_qr_rows(p_ref[...], b)
    v_ref[...] = VT
    t_ref[...] = T
    tau_ref[...] = taus
    r_ref[...] = RT


@functools.partial(jax.jit, static_argnames=("interpret",))
def panel_qr_pallas(panel: jax.Array, *, interpret: bool = False):
    """Panel QR in WY form, one fused kernel.  Returns (V, T, taus, R)."""
    m, b = panel.shape
    kernel = functools.partial(_panel_qr_kernel, b=b)
    VT, T, taus, RT = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((b, m), panel.dtype),
            jax.ShapeDtypeStruct((b, b), panel.dtype),
            jax.ShapeDtypeStruct((b, 1), panel.dtype),
            jax.ShapeDtypeStruct((b, m), panel.dtype),
        ),
        interpret=interpret,
        name="panel_qr_wy",
    )(panel.T)
    return VT.T, T, taus[:, 0], RT[:, :b].T
