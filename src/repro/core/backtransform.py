"""Blocked compact-WY back-transformation: V = Q1 Q2 V_T as GEMMs.

The two-stage pipeline recovers eigenvectors by applying the accumulated
orthogonal factors of both reduction stages to the tridiagonal eigenvector
panel X (n, k).  The straightforward appliers are skinny-update loops — the
exact antipattern the paper's thesis targets:

* ``apply_q_left``  walks P panels of Q1, each a rank-b update;
* ``apply_q2``      scans ~3n wavefronts of Q2, each a batched rank-1
  gather/scatter update.

This module replaces both with blocked, GEMM-based equivalents (the
standard cure — LAPACK ``ormtr``-style aggregation; see also the pipelined
multi-GPU back-transform literature in PAPERS.md):

**Q1 — T-merge.**  A DBR block factors q = nb/b panels back-to-back.  Their
compact-WY factors merge exactly:

    (I - V1 T1 V1^T)(I - V2 T2 V2^T) = I - [V1 V2] Tm [V1 V2]^T,
    Tm = [[T1, -T1 (V1^T V2) T2], [0, T2]]

so each block becomes ONE rank-q·b reflector and ``apply_q_left_blocked``
performs P·b/nb wide GEMMs instead of P skinny ones — same FLOPs (the V
panels are stored dense either way), a fraction of the launches/passes.

**Q2 — sweep-major regroup.**  Reflector (s, k) of the bulge chase has row
support [s+1+k·b, s+1+(k+1)·b): within one sweep ``s`` the supports are
DISJOINT across k, so sweep s's reflectors commute pairwise and their
compact-WY T factor is exactly diag(taus) — groups of G consecutive k's
apply as one (b·G)-row-panel update with no cross terms.  Reordering the
wavefront-interleaved execution log into sweep-major order is exact: every
non-commuting (overlapping-support) pair (s, k), (s+d, k') appears in the
same relative order in both schedules (overlap forces k - k' < d/b + 1
<= 3d, which is the wavefront-order condition).  See DESIGN.md.

The grouped application is the registry op ``backtransform_wy``: the jnp
reference (:func:`backtransform_wy_xla`) scans sweeps with contiguous
dynamic-slice row panels; the Pallas kernel (``repro.kernels.backtransform``)
keeps X VMEM-resident across the whole schedule.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .band_reduction import BandReflectors, apply_q_left
from .bulge_chasing import ChaseLog, _kmax_table, apply_q2

__all__ = [
    "merge_band_reflectors",
    "apply_q_left_blocked",
    "sweep_major_log",
    "backtransform_wy_xla",
    "apply_q2_blocked",
    "sweep_group_count",
]


# ------------------------------------------------------------------ Q1 merge
def _merge_block_ts(Vg: jax.Array, Ts: jax.Array, b: int) -> jax.Array:
    """Fuse q per-panel T factors into one (q·b, q·b) block-reflector T.

    Vg: (n, q·b) — the block's panels side by side; Ts: (q, b, b).  One
    ``lax.scan`` over panels with static shapes: at step j the rows and
    columns of Tm from j·b on are still zero, so the full-width products
    equal the prefix products of the recurrence.
    """
    q = Ts.shape[0]
    w = q * b
    Tm = jnp.zeros((w, w), Vg.dtype)
    Tm = Tm.at[:b, :b].set(Ts[0])

    def merge(Tm, j):
        c0 = j * b
        Vj = lax.dynamic_slice_in_dim(Vg, c0, b, axis=1)
        Tj = Ts[j]
        cross = -Tm @ ((Vg.T @ Vj) @ Tj)  # rows from c0 on are zero
        Tm = lax.dynamic_update_slice_in_dim(Tm, cross, c0, axis=1)
        Tm = lax.dynamic_update_slice(Tm, Tj, (c0, c0))
        return Tm, None

    Tm, _ = lax.scan(merge, Tm, jnp.arange(1, q))
    return Tm


def merge_band_reflectors(refl: BandReflectors) -> BandReflectors:
    """Return ``refl`` with per-block merged T factors (``Tm``) populated.

    Requires the block structure recorded by :func:`band_reduce`
    (``refl.blocks``); a no-op when ``Tm`` is already present.
    """
    if refl.Tm is not None:
        return refl
    if not refl.blocks:
        if refl.T.shape[0] == 0:  # n <= b: no panels, Q1 == I
            return BandReflectors(
                V=refl.V, T=refl.T, b=refl.b, blocks=(), Tm=()
            )
        raise ValueError(
            "BandReflectors carries no block structure; rebuild it via "
            "band_reduce(..., return_reflectors=True)"
        )
    b = refl.b
    Tms = []
    for p0, q in refl.blocks:
        Vg = refl.V[:, p0 * b : (p0 + q) * b]
        Tms.append(_merge_block_ts(Vg, refl.T[p0 : p0 + q], b))
    return BandReflectors(
        V=refl.V, T=refl.T, b=b, blocks=refl.blocks, Tm=tuple(Tms)
    )


def apply_q_left_blocked(
    refl: BandReflectors, X: jax.Array, transpose: bool = False
) -> jax.Array:
    """Q1 @ X (or Q1^T @ X) via one rank-q·b GEMM update per DBR block.

    Numerically equivalent to :func:`apply_q_left` (exact in exact
    arithmetic); falls back to it when no merged factors are available.
    """
    if refl.Tm is None:
        if refl.blocks:
            refl = merge_band_reflectors(refl)
        else:
            return apply_q_left(refl, X, transpose)
    b = refl.b
    order = range(len(refl.blocks))
    if not transpose:
        order = reversed(order)
    for g in order:
        p0, q = refl.blocks[g]
        V = refl.V[:, p0 * b : (p0 + q) * b]
        T = refl.Tm[g]
        Tg = T.T if transpose else T
        X = X - V @ (Tg @ (V.T @ X))
    return X


# --------------------------------------------------------------- Q2 regroup
def _sweep_shape(n: int, b: int) -> Tuple[int, int]:
    """(S, K): sweep count and max reflectors per sweep."""
    S = max(n - 2, 0)
    K = (n - 3) // b + 1 if n >= 3 else 0
    return S, K


def sweep_major_log(log: ChaseLog) -> Tuple[jax.Array, jax.Array]:
    """Reindex a :class:`ChaseLog` into sweep-major order.

    Returns ``(vs, taus)`` of shapes (S, K, b) / (S, K): entry (s, k) is the
    reflector eliminating column ``s+1+(k-1)b`` with row support
    ``[s+1+k·b, s+1+(k+1)·b)``.  Slots past ``kmax(s)`` carry tau == 0
    (exact no-ops).  Works for both wavefront logs (W, A, b) — entry (s, k)
    lives at wavefront ``3s+k``, slot ``k//3`` — and sequential logs (L, b).
    """
    n, b = log.n, log.b
    S, K = _sweep_shape(n, b)
    if S == 0 or K == 0:
        raise ValueError(f"no bulge-chase reflectors for n={n}")
    kmax = _kmax_table(n, b)

    vs, taus = log.vs, log.taus
    if vs.ndim == 2:  # sequential log: entries in (s-major, k-minor) order
        i_idx = np.zeros((S, K), np.int64)
        valid = np.zeros((S, K), bool)
        i = 0
        for s in range(S):
            for k in range(kmax[s] + 1):
                i_idx[s, k] = i
                valid[s, k] = True
                i += 1
        vs_sw = vs[i_idx]
        taus_sw = taus[i_idx]
    else:  # wavefront log
        w_idx = np.zeros((S, K), np.int64)
        a_idx = np.zeros((S, K), np.int64)
        valid = np.zeros((S, K), bool)
        for s in range(S):
            for k in range(kmax[s] + 1):
                w_idx[s, k] = 3 * s + k
                a_idx[s, k] = k // 3
                valid[s, k] = True
        vs_sw = vs[w_idx, a_idx]
        taus_sw = taus[w_idx, a_idx]
    mask = jnp.asarray(valid)
    return jnp.where(mask[..., None], vs_sw, 0.0), jnp.where(mask, taus_sw, 0.0)


def sweep_group_count(n: int, b: int, group: int) -> int:
    """Number of (b·group)-row panels per sweep at the given group size."""
    _, K = _sweep_shape(n, b)
    group = max(1, min(int(group), K)) if K else 1
    return -(-K // group) if K else 0


def backtransform_wy_xla(
    X: jax.Array,
    vs: jax.Array,
    taus: jax.Array,
    *,
    b: int,
    group: Optional[int] = None,
    transpose: bool = False,
) -> jax.Array:
    """jnp/XLA reference for the ``backtransform_wy`` op.

    ``vs`` (S, K, b) / ``taus`` (S, K) in sweep-major order (see
    :func:`sweep_major_log`); applies Q2 @ X (or Q2^T @ X) as a
    ``lax.scan`` over sweeps.  Within a sweep the reflectors have disjoint
    contiguous row supports, so each group of ``group`` consecutive
    reflectors is one (b·group)-row contiguous panel update — a pair of
    (group, b)·(b, m)-shaped contractions instead of rank-1 gather/scatter.
    Sweep s's panel starts at row s+1; group boundaries never interact
    (disjoint supports commute), so only the sweep order is direction-aware.
    """
    S, K, _ = vs.shape
    n, m = X.shape
    group = K if group is None else max(1, min(int(group), K))

    # Pad so every (s, group) panel slice is in bounds; masked reflectors
    # (tau == 0) make the pad rows exact no-ops.
    Xp = jnp.zeros((n + K * b, m), X.dtype).at[:n, :].set(X)
    s_order = jnp.arange(S, dtype=jnp.int32)
    if not transpose:
        s_order = s_order[::-1]
        vs, taus = vs[::-1], taus[::-1]

    n_groups = -(-K // group)

    def body(Xp, xs):
        V, t, s = xs  # (K, b), (K,), ()
        for g in range(n_groups):
            k0 = g * group
            gk = min(group, K - k0)
            r0 = s + 1 + k0 * b
            P = lax.dynamic_slice(Xp, (r0, 0), (gk * b, m)).reshape(gk, b, m)
            Vg = V[k0 : k0 + gk]
            proj = jnp.einsum("kb,kbm->km", Vg, P)
            P = P - t[k0 : k0 + gk, None, None] * Vg[:, :, None] * proj[:, None, :]
            Xp = lax.dynamic_update_slice(Xp, P.reshape(gk * b, m), (r0, 0))
        return Xp, None

    Xp, _ = lax.scan(body, Xp, (vs, taus, s_order))
    return Xp[:n, :]


def apply_q2_blocked(
    log: ChaseLog,
    X: jax.Array,
    transpose: bool = False,
    *,
    group: Optional[int] = None,
    backend: Optional[str] = None,
) -> jax.Array:
    """Q2 @ X (or Q2^T @ X) through the blocked ``backtransform_wy`` op.

    Regroups the chase log sweep-major and dispatches through
    ``repro.backend.registry`` (Pallas VMEM-resident kernel by default, jnp
    reference as fallback/oracle).  Matches :func:`apply_q2` to fp rounding.
    Degenerate logs (n < 3 or b <= 1: no reflectors) fall back to the scan
    applier, which handles their masked sentinel entries.
    """
    n, b = log.n, log.b
    S, K = _sweep_shape(n, b)
    if S == 0 or K == 0 or b <= 1:
        return apply_q2(log, X, transpose)
    from repro.backend import registry

    vs, taus = sweep_major_log(log)
    fn = registry.resolve("backtransform_wy", backend)
    return fn(X, vs, taus, b=b, group=group, transpose=transpose)
