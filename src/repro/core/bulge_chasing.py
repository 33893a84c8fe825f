"""Bulge chasing: symmetric band matrix -> tridiagonal.

The paper's Algorithm 2 runs one GPU thread block per sweep and pipelines
sweeps with spin-lock flags: sweep ``i+1`` may proceed once sweep ``i`` is
three Householder "cycles" (2b columns) ahead.  TPUs are bulk-synchronous,
so we make that schedule *static* (DESIGN.md §2): the dependence

    op (s, k) may run at wavefront  w = 3*s + k

is affine, every op executable at wavefront ``w`` touches a window disjoint
from every other op at ``w`` (they share at most one untouched corner
diagonal entry), so each wavefront is executed as ONE batched two-sided
Householder update over all active sweeps.  This is the paper's inter-kernel
parallelism with the synchronization cost compiled away, and the batched
window update is its intra-kernel parallelism.

Geometry (0-based, bandwidth ``b``; sweep ``s`` makes column ``s``
tridiagonal):

* op (s, 0):  rows I_0 = [s+1, s+1+b)   eliminate column  s   below row s+1
* op (s, k):  rows I_k = [s+1+kb, s+1+(k+1)b)
              eliminate column  c_k = s+1+(k-1)b  below row s+1+kb
* every op touches only the symmetric window
      reg_k = [minI_k - b, minI_k + 2b)   (3b wide)
* op count: k = 0 .. kmax(s),  kmax(s) = (n-3-s) // b
* sweeps: s = 0 .. n-3

Two executors over a zero-padded dense matrix:

* ``chase_sequential`` — one op at a time (oracle; order = paper's serial
  algorithm).
* ``chase_wavefront``  — batched wavefronts (the accelerated schedule).

Both can log their reflectors so Q2 (for eigenvectors) can be applied with
``apply_q2``.  A Pallas kernel version of the wavefront executor lives in
``repro.kernels.bulge``.
"""
from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .householder import house

__all__ = [
    "ChaseLog",
    "chase_sequential",
    "chase_wavefront",
    "band_to_tridiag",
    "apply_q2",
    "extract_tridiag",
    "num_wavefronts",
    "max_active_sweeps",
]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ChaseLog:
    """Reflector log for the bulge-chasing orthogonal factor Q2.

    B = Q2 T Q2^T with Q2 = H_1 H_2 ... H_L in execution order.  ``vs`` holds
    the Householder vectors (zero-padded), ``row0`` the global start row of
    each reflector's support (sentinel ``n`` when masked/inactive).

    Shapes: sequential log -> (L, b) / (L,); wavefront log -> (W, A, b) etc.
    ``n`` and ``b`` are static pytree metadata (shape parameters).
    """

    vs: jax.Array
    taus: jax.Array
    row0: jax.Array
    n: int
    b: int

    def tree_flatten(self):
        return (self.vs, self.taus, self.row0), (self.n, self.b)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


def _kmax_table(n: int, b: int) -> np.ndarray:
    return np.array([(n - 3 - s) // b for s in range(max(n - 2, 1))], np.int32)


def num_wavefronts(n: int, b: int) -> int:
    if n < 3:
        return 0
    return 3 * (n - 3) + 1  # max over s of (3s + kmax(s)) + 1; kmax(n-3) = 0


def max_active_sweeps(n: int, b: int) -> int:
    # Active slots at any wavefront: ceil((kmax(0)+1)/3) + 1 is a safe bound.
    return int((_kmax_table(n, b)[0] + 1 + 2) // 3 + 1) if n >= 3 else 1


def _pad_sizes(n: int, b: int):
    off = b                       # margin before the matrix (k=0 windows)
    scratch0 = off + n + 2 * b    # masked ops read/write a zero scratch block
    total = scratch0 + 3 * b
    return off, scratch0, total


def _embed(B: jax.Array, b: int) -> jax.Array:
    n = B.shape[0]
    off, _, total = _pad_sizes(n, b)
    Bp = jnp.zeros((total, total), B.dtype)
    return lax.dynamic_update_slice(Bp, B, (off, off))


def _window_op(W: jax.Array, k, b: int):
    """Apply one chase op to a (3b, 3b) symmetric window.

    Rows I = [b, 2b) locally; the eliminated column is local ``b-1`` for the
    sweep-starting op (k == 0) and local ``0`` for chase ops (k >= 1).
    Degenerate windows (all zeros — masked slots / ragged tails) are no-ops.
    Returns (W_new, v (b,), tau).
    """
    w3 = 3 * b
    li = jnp.arange(w3)
    elim = jnp.where(k == 0, b - 1, 0)
    # x = W[b:2b, elim]  (dynamic column index)
    x = jnp.take_along_axis(
        W[b : 2 * b, :], jnp.full((b, 1), elim, jnp.int32), axis=1
    )[:, 0]
    v, tau, beta = house(x)
    u = jnp.zeros((w3,), W.dtype).at[b : 2 * b].set(v)
    Mv = W @ u
    vMv = u @ Mv
    wvec = tau * (Mv - 0.5 * tau * vMv * u)
    Wn = W - jnp.outer(u, wvec) - jnp.outer(wvec, u)
    # Exact zeros in the eliminated column/row (cleans rounding fuzz).
    in_rows = (li >= b) & (li < 2 * b)
    exact = jnp.where(li == b, beta, 0.0)
    col_mask = in_rows[:, None] & (li[None, :] == elim)
    Wn = jnp.where(col_mask, exact[:, None], Wn)
    Wn = jnp.where(col_mask.T, exact[None, :], Wn)
    return Wn, v, tau


def chase_sequential(B: jax.Array, b: int, return_log: bool = False):
    """Oracle executor: ops run one at a time in the paper's serial order."""
    n = B.shape[0]
    if n < 3 or b <= 1:
        log = ChaseLog(
            vs=jnp.zeros((1, max(b, 1)), B.dtype),
            taus=jnp.zeros((1,), B.dtype),
            row0=jnp.full((1,), n, jnp.int32),
            n=n,
            b=max(b, 1),
        )
        return (B, log) if return_log else B

    kmax = _kmax_table(n, b)
    s_list, k_list = [], []
    for s in range(n - 2):
        for k in range(kmax[s] + 1):
            s_list.append(s)
            k_list.append(k)
    ss = jnp.asarray(np.array(s_list, np.int32))
    ks = jnp.asarray(np.array(k_list, np.int32))

    off, _, _ = _pad_sizes(n, b)
    Bp = _embed(B, b)

    def body(Bp, sk):
        s, k = sk
        r0 = off + s + 1 + (k - 1) * b
        W = lax.dynamic_slice(Bp, (r0, r0), (3 * b, 3 * b))
        Wn, v, tau = _window_op(W, k, b)
        Bp = lax.dynamic_update_slice(Bp, Wn, (r0, r0))
        return Bp, (v, tau, s + 1 + k * b)

    Bp, (vs, taus, row0) = lax.scan(body, Bp, (ss, ks))
    out = lax.dynamic_slice(Bp, (off, off), (n, n))
    log = ChaseLog(vs=vs, taus=taus, row0=row0.astype(jnp.int32), n=n, b=b)
    return (out, log) if return_log else out


def chase_wavefront(B: jax.Array, b: int, return_log: bool = False):
    """Accelerated executor: one batched update per wavefront.

    Per wavefront ``w`` the active ops are {(s, w - 3s)}; their windows are
    gathered with a vmapped dynamic slice and updated in parallel, then
    written back by a fori loop of ``dynamic_update_slice`` writes (masked
    slots target a shared zero scratch block and write zeros).  Windows
    within a wavefront are disjoint, so the sequential write-back commutes
    and the loop carries no cross-slot dependence.
    """
    n = B.shape[0]
    if n < 3 or b <= 1:
        return chase_sequential(B, b, return_log)

    kmax = jnp.asarray(_kmax_table(n, b))
    A = max_active_sweeps(n, b)
    W_total = num_wavefronts(n, b)
    off, scratch0, _ = _pad_sizes(n, b)
    w3 = 3 * b

    Bp = _embed(B, b)
    slot = jnp.arange(A, dtype=jnp.int32)

    def body(Bp, w):
        s = w // 3 - slot
        k = w - 3 * s
        s_safe = jnp.clip(s, 0, n - 3)
        active = (s >= 0) & (s <= n - 3) & (k >= 0) & (k <= kmax[s_safe])
        r0 = jnp.where(active, off + s + 1 + (k - 1) * b, scratch0)
        Ws = jax.vmap(lambda r: lax.dynamic_slice(Bp, (r, r), (w3, w3)))(r0)
        Wn, vs, taus = jax.vmap(lambda Wi, ki: _window_op(Wi, ki, b))(Ws, k)
        Bp = lax.fori_loop(
            0,
            A,
            lambda a, Bc: lax.dynamic_update_slice(Bc, Wn[a], (r0[a], r0[a])),
            Bp,
        )
        row0 = jnp.where(active, s + 1 + k * b, n).astype(jnp.int32)
        return Bp, (vs, taus, row0)

    Bp, (vs, taus, row0) = lax.scan(body, Bp, jnp.arange(W_total, dtype=jnp.int32))
    out = lax.dynamic_slice(Bp, (off, off), (n, n))
    log = ChaseLog(vs=vs, taus=taus, row0=row0, n=n, b=b)
    return (out, log) if return_log else out


def band_to_tridiag(B: jax.Array, b: int, *, return_log: bool = False):
    """Reduce a symmetric band matrix (dense storage) to tridiagonal form.

    Runs the ``bulge_wavefront`` registry op: the grouped wavefront kernel
    (or, off its ceilings, :func:`chase_wavefront`), which emits the
    reflector log directly, so eigenvector runs stay on the kernel too.
    """
    from repro.backend import registry

    return registry.resolve("bulge_wavefront")(B, b, return_log=return_log)


def extract_tridiag(T: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(diagonal, subdiagonal) of a (numerically) tridiagonal matrix."""
    d = jnp.diagonal(T)
    e = jnp.diagonal(T, offset=-1)
    return d, e


def apply_q2(log: ChaseLog, X: jax.Array, transpose: bool = False) -> jax.Array:
    """Q2 @ X (or Q2^T @ X) from a reflector log.

    Q2 = H_1 ... H_L in execution order, so Q2 @ X applies the LAST reflector
    first (reversed log) and Q2^T @ X runs the log forward.  Wavefront logs
    (rank-3 ``vs``) apply each wavefront's reflectors as one batched update —
    their row supports are disjoint, so they commute.
    """
    n, b = log.n, log.b
    m = X.shape[1]
    # Pad with b zero rows: masked reflectors (row0 == n) land here.
    Xp = jnp.zeros((n + b, m), X.dtype).at[:n, :].set(X)

    vs, taus, row0 = log.vs, log.taus, log.row0
    if vs.ndim == 2:  # sequential log -> treat as wavefronts of size 1
        vs = vs[:, None, :]
        taus = taus[:, None]
        row0 = row0[:, None]

    if not transpose:
        vs, taus, row0 = vs[::-1], taus[::-1], row0[::-1]

    def body(Xp, wf):
        v, tau, r0 = wf  # (A, b), (A,), (A,)
        rows = jnp.minimum(r0[:, None] + jnp.arange(b)[None, :], n + b - 1)
        Xg = Xp[rows]  # (A, b, m)
        proj = jnp.einsum("ab,abm->am", v, Xg)
        upd = tau[:, None, None] * v[:, :, None] * proj[:, None, :]
        Xp = Xp.at[rows].add(-upd)
        return Xp, None

    Xp, _ = lax.scan(body, Xp, (vs, taus, row0))
    return Xp[:n, :]
