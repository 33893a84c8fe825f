"""jit-facing wrappers around the Pallas kernels.

Responsibilities:
* interpret-mode dispatch: anywhere that is not a real TPU the kernels
  execute with ``interpret=True`` (the brief's validation mode); on TPU they
  compile.  The decision lives in ``repro.backend.probe``.
* shape normalization: pad to tile multiples, slice back.
* symmetrization: the syr2k kernel writes lower tiles only; wrappers
  reconstruct the full symmetric result.

Nothing outside ``repro.kernels`` calls ``pl.pallas_call`` directly, and
the pipeline resolves these wrappers through ``repro.backend.registry``
(``syr2k`` and ``panel_qr`` are reached through ``trailing_update`` and the
tests).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.backend import probe

from .limits import fits_vmem, limit
from .syr2k import syr2k_lower_pallas
from .bulge import (
    BULGE_DENSE,
    BULGE_STRIP,
    bulge_strip_vmem_bytes,
    bulge_vmem_bytes,
    bulge_wavefront_pallas,
    strip_fits_tile,
)
from .panel import panel_qr_pallas
from .fused_panel import fused_panel_update_pallas, fused_tpu_aligned, fused_vmem_bytes
from .backtransform import backtransform_wy_pallas, column_block
from .mark import stage_mark_pallas

__all__ = [
    "syr2k",
    "trailing_update",
    "fused_panel_update",
    "fused_uses_kernel",
    "bulge_wavefront",
    "bulge_uses_kernel",
    "bulge_kernel",
    "BULGE_DENSE",
    "BULGE_STRIP",
    "panel_qr",
    "backtransform_wy",
    "backtransform_uses_kernel",
    "stage_mark",
]

# The VMEM budget and the interpret-mode ceilings live in repro.kernels.limits;
# each kernel module counts its own VMEM bytes.  The *_uses_kernel functions
# below are the single source of truth for kernel-versus-XLA dispatch.


def _pad_to(x: jax.Array, mult0: int, mult1: int) -> jax.Array:
    p0 = (-x.shape[0]) % mult0
    p1 = (-x.shape[1]) % mult1
    if p0 == 0 and p1 == 0:
        return x
    return jnp.pad(x, ((0, p0), (0, p1)))


def _pick_tile(n: int, pref: int) -> int:
    """Largest power-of-two tile <= pref that keeps padding waste < 2x."""
    t = pref
    while t > 8 and n % t and (n % t) < t // 2 and n < t:
        t //= 2
    return max(min(t, pref), 8)


@functools.partial(jax.jit, static_argnames=("alpha", "bm", "bk", "interpret"))
def syr2k(
    A: jax.Array,
    B: jax.Array,
    C: Optional[jax.Array] = None,
    *,
    alpha: float = 1.0,
    bm: int = 256,
    bk: int = 256,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Full symmetric ``C + alpha (A B^T + B A^T)`` via the lower-tile kernel."""
    interpret = probe.interpret_mode() if interpret is None else interpret
    n, k = A.shape
    bm = min(bm, max(8, 1 << (n - 1).bit_length()))
    bk = min(bk, max(8, 1 << (k - 1).bit_length()))
    C_in = jnp.zeros((n, n), A.dtype) if C is None else C
    Ap = _pad_to(A, bm, bk)
    Bp = _pad_to(B, bm, bk)
    Cp = _pad_to(C_in, bm, bm)
    low = syr2k_lower_pallas(Ap, Bp, Cp, alpha=alpha, bm=bm, bk=bk, interpret=interpret)
    low = low[:n, :n]
    # Symmetrize from the lower triangle only (upper tiles are undefined).
    full = jnp.tril(low) + jnp.tril(low, -1).T
    return full


def trailing_update(
    C: jax.Array, Y: jax.Array, Z: jax.Array, **kw
) -> jax.Array:
    """The DBR trailing update ``C - Z Y^T - Y Z^T`` (paper Alg. 1 line 10),
    fused into one syr2k kernel invocation with alpha = -1."""
    return syr2k(Z, Y, C, alpha=-1.0, **kw)


def fused_uses_kernel(
    m: int, w: int, b: int, *, bm: int = 128, interpret: Optional[bool] = None
) -> bool:
    """Whether :func:`fused_panel_update` on an (m, m) trailing view runs the
    fused Pallas kernel (True) or ``_reduce_block``'s panel QRs and syr2k
    trailing update (False).  Single source of truth for the dispatch
    decision."""
    explicit = interpret is not None
    interp = probe.interpret_mode() if interpret is None else interpret
    if interp and not explicit:
        return m <= limit("FUSED_PANEL_INTERPRET_MAX_M")
    if not interp and not fused_tpu_aligned(m, w, b, bm):
        return False
    return fits_vmem(fused_vmem_bytes(m, w, b, bm))


def fused_panel_update(
    Bv: jax.Array,
    b: int,
    w: int,
    *,
    bm: int = 128,
    interpret: Optional[bool] = None,
):
    """One fused first-stage block step on a trailing view (m, m): q = w/b
    panel QRs + the rank-2w two-sided trailing update, factors VMEM-resident.

    Returns ``(new_view, Vbuf (m, w), Ts (q, b, b))`` with the contract of
    ``repro.core.band_reduction._reduce_block``.  Above the VMEM/interpret
    ceilings it falls back to ``_reduce_block`` on the active backend's
    trailing update (same math, streamed).
    """
    m = Bv.shape[0]
    if not fused_uses_kernel(m, w, b, bm=bm, interpret=interpret):
        from repro.backend import registry
        from repro.core.band_reduction import _reduce_block
        from repro.core.panel_qr import panel_qr_geqrf

        return _reduce_block(Bv, b, w, panel_qr_geqrf, registry.resolve("trailing_update"))
    interpret = probe.interpret_mode() if interpret is None else interpret
    C_low, V, F, Ts = fused_panel_update_pallas(Bv, b=b, w=w, bm=bm, interpret=interpret)
    mt = m - w
    low = C_low[:mt, :mt]
    # Symmetrize from the lower tiles only (upper tiles are undefined).
    trailing = jnp.tril(low) + jnp.tril(low, -1).T
    new_view = Bv.at[w:, w:].set(trailing)
    new_view = new_view.at[:, :w].set(F[:m])
    new_view = new_view.at[:w, w:].set(F[w:m, :].T)
    return new_view, V[:m], Ts


def bulge_uses_kernel(
    n: int,
    b: int,
    *,
    group: Optional[int] = None,
    return_log: bool = False,
    interpret: Optional[bool] = None,
) -> bool:
    """Whether the dense-resident kernel runs at size ``n``: the first
    choice of :func:`bulge_kernel`.  Diagnostics must use these rather than
    re-deriving the ceilings.
    """
    if n < 3 or b <= 1:
        return False
    explicit = interpret is not None
    interp = probe.interpret_mode() if interpret is None else interpret
    if interp and not explicit:
        return n <= limit("BULGE_INTERPRET_MAX_N")
    group = _wavefront_group(n, b) if group is None else group
    return fits_vmem(bulge_vmem_bytes(n, b, group=group, return_log=return_log))


def bulge_kernel(
    n: int,
    b: int,
    *,
    group: Optional[int] = None,
    return_log: bool = False,
    interpret: Optional[bool] = None,
) -> Optional[str]:
    """The kernel :func:`bulge_wavefront` runs at size ``n``:
    :data:`BULGE_DENSE` when the dense-resident matrix fits the VMEM budget,
    else :data:`BULGE_STRIP` when the band strip does, else None (the XLA
    wavefront executor).  Single source of truth for that choice.
    """
    group = _wavefront_group(n, b) if (group is None and n >= 3) else group
    if bulge_uses_kernel(n, b, group=group, return_log=return_log, interpret=interpret):
        return BULGE_DENSE
    if n < 3 or b <= 1 or not strip_fits_tile(b):
        return None
    if interpret is None and probe.interpret_mode():
        return None  # implied interpretation: above the interpret ceiling
    nbytes = bulge_strip_vmem_bytes(n, b, group=group, return_log=return_log)
    return BULGE_STRIP if fits_vmem(nbytes) else None


def _wavefront_group(n: int, b: int) -> int:
    from repro.solver.autotune import wavefront_group

    return wavefront_group(n, b)


def bulge_wavefront(
    B: jax.Array,
    b: int,
    *,
    return_log: bool = False,
    group: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """Grouped wavefront bulge chase, optionally emitting the reflector log.

    The ``bulge_wavefront`` registry op: the kernel chases ``group`` bulges
    per loop iteration (default: the per-platform
    ``repro.solver.autotune.wavefront_group``) and can emit the sweep-major
    ``ChaseLog`` directly, so eigenvector runs stay on the kernel path.  It
    holds the dense matrix in VMEM where that fits and the band strip where
    only the strip does (:func:`bulge_kernel`).  Above both VMEM counts or the interpret
    ceiling — or for trivial sizes — it falls back to the XLA wavefront
    executor.
    """
    n = B.shape[0]
    from repro.core.bulge_chasing import ChaseLog, chase_wavefront

    group = _wavefront_group(n, b) if (group is None and n >= 3) else group
    kernel = bulge_kernel(n, b, group=group, return_log=return_log, interpret=interpret)
    if kernel is None:
        return chase_wavefront(B, b, return_log)
    interpret = probe.interpret_mode() if interpret is None else interpret
    kw = dict(group=int(group), strip=kernel == BULGE_STRIP, interpret=interpret)
    if not return_log:
        return bulge_wavefront_pallas(B, b, **kw)
    out, (vs, taus, row0) = bulge_wavefront_pallas(B, b, return_log=True, **kw)
    return out, ChaseLog(vs=vs, taus=taus, row0=row0, n=n, b=b)


def panel_qr(panel: jax.Array, *, interpret: Optional[bool] = None):
    """Fused panel QR (V, T, taus, R)."""
    interpret = probe.interpret_mode() if interpret is None else interpret
    return panel_qr_pallas(panel, interpret=interpret)


def backtransform_uses_kernel(
    n: int,
    m: int,
    b: int,
    *,
    group: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> bool:
    """Whether the blocked back-transform at panel shape (n, m) runs the
    Pallas kernel (True) or the XLA scan fallback (False).  Single source of
    truth for the dispatch decision, like :func:`bulge_uses_kernel`.
    ``group`` None means one group per sweep, as in :func:`backtransform_wy`.
    """
    explicit = interpret is not None
    interp = probe.interpret_mode() if interpret is None else interpret
    if interp and not explicit:
        return n <= limit("BACKTRANSFORM_INTERPRET_MAX_N")
    return _bt_column_block(n, m, b, group) > 0


def _bt_column_block(n: int, m: int, b: int, group: Optional[int]) -> int:
    from repro.core.backtransform import _sweep_shape

    S, K = _sweep_shape(n, b)
    if S == 0:
        return 0
    group = K if group is None else group
    return column_block(n, m, K, b, group, fits_vmem)


def backtransform_wy(
    X: jax.Array,
    vs: jax.Array,
    taus: jax.Array,
    *,
    b: int,
    group: Optional[int] = None,
    transpose: bool = False,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Blocked Q2 back-transform via the VMEM-resident kernel; falls back to
    the XLA scan implementation above the VMEM/interpret ceilings.

    An EXPLICIT ``interpret=True`` (validating the kernel itself) runs the
    kernel regardless of the implied-interpret size ceiling.
    """
    n, m = X.shape
    if not backtransform_uses_kernel(n, m, b, group=group, interpret=interpret):
        from repro.core.backtransform import backtransform_wy_xla

        return backtransform_wy_xla(
            X, vs, taus, b=b, group=group, transpose=transpose
        )
    interpret = probe.interpret_mode() if interpret is None else interpret
    K = vs.shape[1]
    group = K if group is None else group
    return backtransform_wy_pallas(
        X, vs, taus, b=b, group=int(group), mb=_bt_column_block(n, m, b, group),
        transpose=transpose, interpret=interpret,
    )


def stage_mark(tile: jax.Array, stage: str, *, interpret: Optional[bool] = None) -> jax.Array:
    """``tile`` (8, 128) unchanged, through the kernel ``evd_mark_<stage>``:
    a mark of the stage boundary on the device timeline."""
    interpret = probe.interpret_mode() if interpret is None else interpret
    return stage_mark_pallas(tile, stage, interpret=interpret)
