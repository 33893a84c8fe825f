"""Band reduction: dense symmetric -> banded symmetric.

This module implements the paper's stage-1 algorithms:

* ``band_reduce(..., nb=b)``  — conventional **SBR** (successive band
  reduction): every panel QR is immediately followed by a rank-2b trailing
  update, so the trailing ``syr2k`` has k == b (tall-skinny, memory-bound on
  modern accelerators — the paper's Table 1 bottleneck).

* ``band_reduce(..., nb>b)``  — the paper's **DBR** (Detached Band
  Reduction, Algorithm 1): the bandwidth ``b`` is decoupled from the update
  block size ``nb``.  ``nb/b`` panels are factored back-to-back, their WY
  factors (Y=V, Z) are accumulated, and ONE rank-2·nb trailing update is
  applied with k == nb (square-ish, compute-bound).

Inside a block we use LAPACK-``latrd``-style *compensation* instead of
physically updating panel columns: panel j's columns and its `A @ V` product
are corrected against the accumulated (V, Z) of panels < j with a single
GEMM pair of k = w (over buffers whose later columns are still zero).  This
is the same FLOP-reaggregation idea as the paper's recursive panel-update
schedule (§5.1) — both exist to make the intra-block updates large GEMMs
instead of many skinny ones — expressed in the form that maps best onto
XLA/TPU (one k = w GEMM instead of a recursion tree of launches).  See
DESIGN.md §2.

Shapes are static per block (Python loop over blocks with shrinking trailing
views), so everything jits and vmaps.  Each block step is the registry's
``fused_panel_update`` op, resolved at trace time: the fused Pallas kernel
where it fits (interpret-mode on CPU, compiled on TPU), else
:func:`_reduce_block` with the registry ``trailing_update``; the jnp
backend runs :func:`_reduce_block` with the jnp trailing update.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.backend import registry


__all__ = [
    "band_reduce",
    "BandReflectors",
    "StageEntry",
    "StageSchedule",
    "build_stage_schedule",
    "apply_q_left",
    "form_q",
]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class BandReflectors:
    """Householder data for the orthogonal factor Q1 of the band reduction.

    A = Q1 B Q1^T with Q1 = H_1 H_2 ... H_P (one block reflector per panel).

    V: (n, P*b) unit-lower-trapezoidal columns in FULL-matrix coordinates
       (panel p occupies columns [p*b, (p+1)*b), rows below its elimination
       point; zero elsewhere).
    T: (P, b, b) upper-triangular compact-WY factors.
    b: panel width (the bandwidth) — static pytree metadata.
    blocks: ((panel0, q), ...) — the DBR block structure: block g covers the
       q consecutive panels starting at ``panel0`` (static metadata; the
       blocked back-transform merges each block into one rank-q·b reflector).
    Tm: optional per-block merged compact-WY factors, one (q·b, q·b) upper
       triangular per block, so H_{p0} .. H_{p0+q-1} = I - V_g Tm_g V_g^T.
       Populated by ``band_reduce(..., merge_ts=True)`` or
       :func:`repro.core.backtransform.merge_band_reflectors`.
    """

    V: jax.Array
    T: jax.Array
    b: int
    blocks: Tuple[Tuple[int, int], ...] = ()
    Tm: Optional[Tuple[jax.Array, ...]] = None

    def tree_flatten(self):
        return (self.V, self.T, self.Tm), (self.b, self.blocks)

    @classmethod
    def tree_unflatten(cls, aux, children):
        V, T, Tm = children
        b, blocks = aux
        return cls(V=V, T=T, b=b, blocks=blocks, Tm=Tm)


@dataclasses.dataclass(frozen=True)
class StageEntry:
    """One block step of the first stage (static shapes — jit-safe).

    ``ci``: start column of the block in full-matrix coordinates; ``m``: side
    of the trailing view the block operates on; ``w``: columns factored by
    the block (= q·b); ``panel0``/``q``: the block's panel range in the
    global panel numbering (matches ``BandReflectors.blocks``).
    """

    ci: int
    m: int
    w: int
    panel0: int
    q: int


@dataclasses.dataclass(frozen=True)
class StageSchedule:
    """The static first-stage schedule: panel/block index -> fused-op call.

    Invariants (relied on by the back-transform and pinned by tests):

    * entries are in execution order with ``ci`` strictly increasing by
      ``w``; the final entry leaves a trailing view of side <= ``b`` + last
      ``w`` (the loop stops when ``m <= b``).
    * ``panel0``/``q`` tile the global panel numbering contiguously —
      ``entries[g].panel0 == sum(q of entries[:g])`` — so
      ``BandReflectors.blocks == ((e.panel0, e.q) for e in entries)``
      regardless of which executor (the fused kernel or
      ``_reduce_block``) runs the entries.
    * every ``w`` is a multiple of ``b`` and ``b <= m - w``, the
      preconditions of both the fused kernel and ``_reduce_block``.

    The schedule depends only on (n, b, nb) — never on values — so it is
    built once per plan and baked into the traced program.
    """

    n: int
    b: int
    nb: int
    entries: Tuple[StageEntry, ...]

    @property
    def num_panels(self) -> int:
        return sum(e.q for e in self.entries)

    @property
    def blocks(self) -> Tuple[Tuple[int, int], ...]:
        return tuple((e.panel0, e.q) for e in self.entries)


def build_stage_schedule(n: int, b: int, nb: int) -> StageSchedule:
    """The static block schedule of ``band_reduce`` for sizes (n, b, nb)."""
    entries = []
    ci = 0
    p = 0
    while n - ci > b:
        m = n - ci
        w = min(nb, m - b)
        q = w // b
        entries.append(StageEntry(ci=ci, m=m, w=w, panel0=p, q=q))
        p += q
        ci += w
    return StageSchedule(n=n, b=b, nb=nb, entries=tuple(entries))


def _reduce_block(
    Bv: jax.Array,
    b: int,
    w: int,
    panel_qr_fn: Callable,
    trailing_update_fn: Callable,
):
    """Reduce the first ``w`` columns of the trailing view ``Bv`` (m, m) to
    bandwidth ``b`` and apply one rank-2w trailing update.

    The q = w/b panels run as one ``lax.scan`` with static shapes, so a block
    compiles once however many panels it has: the factor buffers are full
    width (columns of panels not yet factored are zero, so full-width GEMMs
    equal the prefix GEMMs), and panel j's rows [r0, m) are rotated to the
    top of a zero-padded (m, b) panel for the QR.

    Returns (new_view, Vbuf (m, w), Ts (w//b, b, b)).
    """
    m = Bv.shape[0]
    q = w // b
    dtype = Bv.dtype
    rows = jnp.arange(m)[:, None]
    cols = jnp.arange(b)[None, :]

    def panel(carry, j):
        Vbuf, Zbuf, F = carry
        c0 = j * b
        r0 = c0 + b  # elimination starts below this row
        # --- compensated panel: P = (B - Z V^T - V Z^T)[:, c0:c0+b] --------
        P = lax.dynamic_slice_in_dim(Bv, c0, b, axis=1)
        Vrow = lax.dynamic_slice_in_dim(Vbuf, c0, b, axis=0)
        Zrow = lax.dynamic_slice_in_dim(Zbuf, c0, b, axis=0)
        P = P - Zbuf @ Vrow.T - Vbuf @ Zrow.T
        # --- panel QR of rows [r0, m) ---------------------------------------
        # Zero rows below the rotated panel leave V, T and R unchanged.
        low = jnp.roll(jnp.where(rows >= r0, P, 0.0), -r0, axis=0)
        V_j, T_j, _taus, R_j = panel_qr_fn(low)
        Vhat = jnp.roll(V_j, r0, axis=0)
        # --- exact final column values (band structure) ---------------------
        R_embed = lax.dynamic_update_slice(jnp.zeros((m, b), dtype), R_j, (r0, 0))
        fcol = jnp.where(rows < r0, P, R_embed)
        # Structurally-banded write-back: entries above the band are exact
        # zeros in exact arithmetic; mask out their rounding fuzz.
        in_band = rows >= c0 + cols - b
        F = lax.dynamic_update_slice_in_dim(F, jnp.where(in_band, fcol, 0.0), c0, axis=1)
        # --- Z_j = A_cur Vhat T  - 1/2 Vhat T^T (Vhat^T A_cur Vhat) T --------
        M = Bv @ Vhat - Zbuf @ (Vbuf.T @ Vhat) - Vbuf @ (Zbuf.T @ Vhat)
        MT = M @ T_j
        Z_j = MT - 0.5 * Vhat @ (T_j.T @ (Vhat.T @ MT))
        Vbuf = lax.dynamic_update_slice_in_dim(Vbuf, Vhat, c0, axis=1)
        Zbuf = lax.dynamic_update_slice_in_dim(Zbuf, Z_j, c0, axis=1)
        return (Vbuf, Zbuf, F), T_j

    zeros = jnp.zeros((m, w), dtype)
    (Vbuf, Zbuf, F), Ts = lax.scan(panel, (zeros, zeros, zeros), jnp.arange(q))

    # --- one rank-2w trailing update with k = w (the paper's big syr2k) -----
    trailing = trailing_update_fn(Bv[w:, w:], Vbuf[w:, :], Zbuf[w:, :])
    new_view = Bv
    new_view = new_view.at[w:, w:].set(trailing)
    new_view = new_view.at[:, :w].set(F)
    new_view = new_view.at[:w, w:].set(F[w:, :].T)
    return new_view, Vbuf, Ts


def band_reduce(
    A: jax.Array,
    b: int,
    nb: Optional[int] = None,
    *,
    return_reflectors: bool = False,
    merge_ts: bool = False,
):
    """Reduce a symmetric matrix to band form with bandwidth ``b``.

    ``nb == b`` is conventional SBR; ``nb > b`` is the paper's DBR.  Each
    :class:`StageSchedule` entry runs as ONE ``fused_panel_update``
    registry op (panel QRs + trailing update).

    Args:
      A: (n, n) symmetric.  ``n`` must be a multiple of ``b``.
      b: target bandwidth (panel width).
      nb: update block size (multiple of ``b``); defaults to ``b`` (SBR).
      return_reflectors: also return :class:`BandReflectors` for Q1.
      merge_ts: with ``return_reflectors``, also fuse each DBR block's
        per-panel T factors into one (q·b, q·b) block-reflector T (stored as
        ``BandReflectors.Tm``) so the blocked back-transform applies rank-q·b
        GEMMs instead of per-panel rank-b updates.

    Returns:
      ``Bband`` (n, n) symmetric banded, and optionally reflectors.
    """
    n = A.shape[0]
    nb = b if nb is None else nb
    if n % b != 0:
        raise ValueError(f"n={n} must be a multiple of b={b}")
    if nb % b != 0:
        raise ValueError(f"nb={nb} must be a multiple of b={b}")

    fused_update = registry.resolve("fused_panel_update")

    dtype = A.dtype
    B = A
    max_panels = max(n // b - 1, 1)
    Vall = jnp.zeros((n, max_panels * b), dtype)
    Tall = jnp.zeros((max_panels, b, b), dtype)

    schedule = build_stage_schedule(n, b, nb)
    for e in schedule.entries:
        new_view, Vbuf, Ts = fused_update(B[e.ci :, e.ci :], b, e.w)
        B = B.at[e.ci :, e.ci :].set(new_view)
        Vall = Vall.at[e.ci :, e.panel0 * b : (e.panel0 + e.q) * b].set(Vbuf)
        Tall = Tall.at[e.panel0 : e.panel0 + e.q].set(Ts)
    p = schedule.num_panels

    if return_reflectors:
        refl = BandReflectors(
            V=Vall[:, : p * b], T=Tall[:p], b=b, blocks=schedule.blocks
        )
        if merge_ts:
            from .backtransform import merge_band_reflectors

            refl = merge_band_reflectors(refl)
        return B, refl
    return B


def apply_q_left(refl: BandReflectors, X: jax.Array, transpose: bool = False) -> jax.Array:
    """Compute Q1 @ X (or Q1^T @ X).

    Q1 = H_1 H_2 ... H_P; each H_p = I - V_p T_p V_p^T.
    Q1 @ X applies H_P first; Q1^T @ X applies H_1^T first.
    """
    P = refl.T.shape[0]
    b = refl.b
    order = range(P) if transpose else range(P - 1, -1, -1)
    for p in order:
        V = refl.V[:, p * b : (p + 1) * b]
        T = refl.T[p]
        Tp = T.T if transpose else T
        X = X - V @ (Tp @ (V.T @ X))
    return X


def form_q(refl: BandReflectors, n: int) -> jax.Array:
    """Materialize the dense orthogonal factor Q1 (n, n)."""
    return apply_q_left(refl, jnp.eye(n, dtype=refl.V.dtype))
