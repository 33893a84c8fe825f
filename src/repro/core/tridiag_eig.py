"""Eigensolvers for symmetric tridiagonal matrices.

The paper hands the tridiagonal matrix to cuSOLVER's iterative methods (QR /
divide-and-conquer).  On TPU the natural massively-parallel iterative method
is **Sturm-sequence bisection** (related-work §7.2.2 of the paper): every
eigenvalue is an independent lane, so the whole spectrum converges in ~40
batched scans — no sequential deflation like the QR algorithm.  Eigenvectors
come from **simultaneous pivoted inverse iteration** (one independent
tridiagonal solve per eigenvalue, vmapped) with a QR after every step that
keeps clustered eigenvectors independent.

All routines are shape-static, jit- and vmap-friendly.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

__all__ = [
    "sturm_count",
    "eigvalsh_tridiag",
    "eigvalsh_tridiag_range",
    "eigvecs_inverse_iteration",
    "eigh_tridiag",
    "INVERSE_ITERATION_STEPS",
]

# Shifted solves (each followed by a QR) per eigenvector solve.  Two reach
# the n·eps residual bound on the spectra the tests hold; a third costs one
# more O(n k^2) QR per solve.
INVERSE_ITERATION_STEPS = 2


def sturm_count(d: jax.Array, e: jax.Array, x: jax.Array) -> jax.Array:
    """Number of eigenvalues of tridiag(d, e) strictly below each x.

    d: (n,) diagonal; e: (n-1,) subdiagonal; x: (m,) query points.
    Returns (m,) int32 counts.  Uses the safeguarded LDL^T sign-count
    recurrence (LAPACK dstebz style).
    """
    n = d.shape[0]
    m = x.shape[0]
    e2 = jnp.concatenate([jnp.zeros((1,), d.dtype), e * e])  # e2[i] = e_{i-1}^2
    eps = jnp.finfo(d.dtype).tiny
    pivmin = jnp.maximum(jnp.max(e2) * eps, eps)

    def body(carry, de):
        q_prev, count = carry
        d_i, e2_i = de
        q = (d_i - x) - e2_i / q_prev
        q = jnp.where(jnp.abs(q) < pivmin, -pivmin, q)
        count = count + (q < 0).astype(jnp.int32)
        return (q, count), None

    q0 = jnp.full((m,), 1.0, d.dtype)
    (q, count), _ = lax.scan(body, (q0, jnp.zeros((m,), jnp.int32)), (d, e2))
    return count


def _bisect_indices(d: jax.Array, e: jax.Array, ks: jax.Array, max_iter: int):
    """Bisection lanes for eigenvalue indices ``ks`` (ascending order)."""
    m = ks.shape[0]
    e_abs = jnp.concatenate([jnp.zeros((1,), d.dtype), jnp.abs(e)])
    r = e_abs + jnp.concatenate([jnp.abs(e), jnp.zeros((1,), d.dtype)])
    lo0 = jnp.min(d - r)
    hi0 = jnp.max(d + r)
    span = jnp.maximum(hi0 - lo0, jnp.finfo(d.dtype).eps)
    lo0 = lo0 - 0.001 * span
    hi0 = hi0 + 0.001 * span

    lo = jnp.full((m,), lo0, d.dtype)
    hi = jnp.full((m,), hi0, d.dtype)

    def body(carry, _):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        cnt = sturm_count(d, e, mid)
        go_up = cnt <= ks  # lambda_k >= mid
        lo = jnp.where(go_up, mid, lo)
        hi = jnp.where(go_up, hi, mid)
        return (lo, hi), None

    (lo, hi), _ = lax.scan(body, (lo, hi), None, length=max_iter)
    return 0.5 * (lo + hi)


@partial(jax.jit, static_argnames=("max_iter",))
def eigvalsh_tridiag(d: jax.Array, e: jax.Array, max_iter: int = 48) -> jax.Array:
    """All eigenvalues of tridiag(d, e), ascending, via parallel bisection."""
    n = d.shape[0]
    return _bisect_indices(d, e, jnp.arange(n, dtype=jnp.int32), max_iter)


@partial(jax.jit, static_argnames=("start", "count", "max_iter"))
def eigvalsh_tridiag_range(
    d: jax.Array,
    e: jax.Array,
    *,
    start: int = 0,
    count: Optional[int] = None,
    max_iter: int = 48,
) -> jax.Array:
    """Eigenvalues ``start .. start+count-1`` (ascending index) of
    tridiag(d, e) — the partial-spectrum entry point (LAPACK ``RANGE='I'``).

    Bisection runs one lane per REQUESTED eigenvalue: a ``count``-sized
    selection costs ``count`` Sturm lanes regardless of n.
    """
    n = d.shape[0]
    count = n - start if count is None else count
    if not (0 <= start and start + count <= n and count >= 1):
        raise ValueError(
            f"invalid spectrum window [start={start}, count={count}) for n={n}"
        )
    ks = start + jnp.arange(count, dtype=jnp.int32)
    return _bisect_indices(d, e, ks, max_iter)


def _tridiag_solve_pivoted(dl: jax.Array, d: jax.Array, du: jax.Array, rhs: jax.Array):
    """Solve a (possibly nearly singular) tridiagonal system with partial
    pivoting (Gaussian elimination, dgtsv-style), shape-static via two scans.

    dl: (n-1,) subdiagonal; d: (n,) diagonal; du: (n-1,) superdiagonal.
    """
    n = d.shape[0]
    dtype = d.dtype
    tiny = jnp.finfo(dtype).tiny * 16

    a_next = jnp.concatenate([dl, jnp.zeros((1,), dtype)])  # a_next[i] = A[i+1, i]
    b_next = jnp.concatenate([d[1:], jnp.zeros((1,), dtype)])
    c_next = jnp.concatenate([du[1:], jnp.zeros((2,), dtype)])  # A[i+1, i+2]
    r_next = jnp.concatenate([rhs[1:], jnp.zeros((1,), dtype)])
    c_cur0 = jnp.concatenate([du, jnp.zeros((1,), dtype)])

    def fwd(carry, row):
        b_cur, c_cur, r_cur = carry
        a_n, b_n, c_n, r_n = row
        swap = jnp.abs(a_n) > jnp.abs(b_cur)
        # pivot row (goes to output), in columns (i, i+1, i+2)
        p1 = jnp.where(swap, a_n, b_cur)
        p2 = jnp.where(swap, b_n, c_cur)
        p3 = jnp.where(swap, c_n, 0.0)
        pr = jnp.where(swap, r_n, r_cur)
        # eliminated row, columns (i, i+1, i+2)
        e1 = jnp.where(swap, b_cur, a_n)
        e2 = jnp.where(swap, c_cur, b_n)
        e3 = jnp.where(swap, 0.0, c_n)
        er = jnp.where(swap, r_cur, r_n)
        p1_safe = jnp.where(jnp.abs(p1) < tiny, jnp.where(p1 < 0, -tiny, tiny), p1)
        mfac = e1 / p1_safe
        nb = e2 - mfac * p2
        nc = e3 - mfac * p3
        nr = er - mfac * pr
        return (nb, nc, nr), (p1_safe, p2, p3, pr)

    (b_last, _c_last, r_last), rows = lax.scan(
        fwd, (d[0], c_cur0[0], rhs[0]), (a_next[:-1], b_next[:-1], c_next[:-1], r_next[:-1])
    ) if n > 1 else ((d[0], 0.0, rhs[0]), tuple(jnp.zeros((0,), dtype) for _ in range(4)))

    u1, u2, u3, ur = rows
    b_safe = jnp.where(jnp.abs(b_last) < tiny, jnp.where(b_last < 0, -tiny, tiny), b_last)
    x_last = r_last / b_safe

    def bwd(carry, row):
        x1, x2 = carry  # x_{i+1}, x_{i+2}
        p1, p2, p3, pr = row
        x0 = (pr - p2 * x1 - p3 * x2) / p1
        return (x0, x1), x0

    if n > 1:
        (_, _), xs = lax.scan(bwd, (x_last, jnp.zeros((), dtype)), (u1, u2, u3, ur), reverse=True)
        x = jnp.concatenate([xs, x_last[None]])
    else:
        x = x_last[None]
    return x


def _segment_cummax(x: jax.Array, starts: jax.Array) -> jax.Array:
    """Running maximum of ``x``, restarted where ``starts`` is True."""

    def op(a, b):
        return a[0] | b[0], jnp.where(b[0], b[1], jnp.maximum(a[1], b[1]))

    return lax.associative_scan(op, (starts, x))[1]


@partial(jax.jit, static_argnames=("n_iter",))
def eigvecs_inverse_iteration(
    d: jax.Array, e: jax.Array, lams: jax.Array, n_iter: int = INVERSE_ITERATION_STEPS
) -> jax.Array:
    """Eigenvectors of tridiag(d, e) for precomputed eigenvalues ``lams``.

    Simultaneous inverse iteration: each step solves one shifted system per
    eigenvalue (vmapped lanes, each from its own fixed pseudo-random
    start), then a thin QR re-orthogonalizes the block (columns arrive
    eigenvalue-sorted, so it only mixes near-degenerate neighbours) and
    keeps a cluster's lanes spanning its invariant subspace.  With
    ``u = eps ||T||``, eigenvalues closer than ``10 u`` to their neighbour
    form a group, and the shifts are:

    * for a group whose members lie less than ``u`` apart on average,
      which this precision cannot tell apart, one shift for all its lanes:
      ``w + 3u`` beyond the group's edge (``w`` its width), on the side of
      the larger gap to the next group and at most half that gap away.
      The lanes run subspace iteration that scales every member alike
      (within about 2x, rounding included) and the rest of the spectrum
      by ``(2w + 3u) / gap`` per step.  A shift inside the group would
      scale its members by factors that differ by orders of magnitude:
      the lanes collapse onto the members nearest the shift, and the QR
      rebuilds the group's last columns from rounding noise that reaches
      the far spectrum (up to ~70 n·eps on low-rank-plus-ridge
      statistics);
    * for every other lane, its own eigenvalue, raised where needed to at
      least ``u`` above the shift of the lane before it in its group
      (LAPACK ``xSTEIN``'s perturbation of close shifts), so that it
      converges onto its own member.  A group's mean as the shift would
      converge first onto the members nearest the mean, handing the
      ascending columns their neighbours' vectors (up to ~0.6 n·eps at
      n = 4096 on a geometric spectrum).

    After the default two steps every column's residual is within
    ``n eps ||T||`` on tight clusters beside a well-separated spectrum and
    on chains of resolved neighbours.  ``lams`` may be any ascending subset
    of the spectrum (partial-spectrum plans pass k < n values); the gaps
    at its ends count as unbounded.  Returns (n, k) with column j the
    eigenvector for lams[j].
    """
    n = d.shape[0]
    m = lams.shape[0]
    dtype = d.dtype
    e_abs = jnp.abs(e)
    zero = jnp.zeros((1,), dtype)
    t_norm = jnp.max(jnp.abs(d) + jnp.concatenate([zero, e_abs]) + jnp.concatenate([e_abs, zero]))
    u = jnp.finfo(dtype).eps * t_norm
    starts = jnp.concatenate([jnp.ones((1,), bool), jnp.diff(lams) > 10 * u])
    group = jnp.cumsum(starts) - 1
    size = jax.ops.segment_sum(jnp.ones_like(lams), group, num_segments=m)
    lo = jax.ops.segment_min(lams, group, num_segments=m)
    hi = jax.ops.segment_max(lams, group, num_segments=m)
    lane = jnp.arange(m)
    inf = jnp.full((1,), jnp.inf, dtype)
    last = lane == group[-1]  # the last group: no gap above
    gap_lo = lo - jnp.concatenate([-inf, hi[:-1]])
    gap_hi = jnp.where(last, jnp.inf, jnp.concatenate([lo[1:], inf]) - hi)
    width = hi - lo
    offset = jnp.minimum(width + 3 * u, jnp.maximum(gap_lo, gap_hi) / 2)
    outside = jnp.where(gap_lo >= gap_hi, lo - offset, hi + offset)
    unresolved = (size > 1) & (width <= (size - 1) * u)
    # shift_j = max(lams_j, shift_{j-1} + u) within a group: a running max
    # of lams_j - k u, restarted at each group, with k the lane's index in it.
    k = (lane - lax.cummax(jnp.where(starts, lane, 0))).astype(dtype) * u
    own = k + _segment_cummax(lams - k, starts)
    shifts = jnp.where(unresolved[group], outside[group], own)

    solve = jax.vmap(
        lambda lam, v: _tridiag_solve_pivoted(e, d - lam, e, v),
        in_axes=(0, 1), out_axes=1,
    )
    V = jax.random.normal(jax.random.key(0), (n, m), dtype)
    for _ in range(n_iter):
        V = solve(shifts, V)
        V = V / jnp.maximum(jnp.linalg.norm(V, axis=0, keepdims=True), jnp.finfo(dtype).tiny)
        # Fix column signs (positive R diagonal) so the result is deterministic.
        Q, R = jnp.linalg.qr(V)
        signs = jnp.sign(jnp.diagonal(R))
        V = Q * jnp.where(signs == 0, 1.0, signs)[None, :]
    return V


@partial(jax.jit, static_argnames=("eigenvectors", "max_iter"))
def eigh_tridiag(
    d: jax.Array,
    e: jax.Array,
    *,
    eigenvectors: bool = True,
    max_iter: int = 48,
):
    """Full symmetric tridiagonal eigendecomposition (ascending)."""
    lams = eigvalsh_tridiag(d, e, max_iter=max_iter)
    if not eigenvectors:
        return lams
    V = eigvecs_inverse_iteration(d, e, lams)
    return lams, V
