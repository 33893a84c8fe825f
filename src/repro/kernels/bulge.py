"""Pallas TPU kernel: wavefront bulge chasing (the paper's §4.2/§5.3).

The GPU implementation keeps two shared-memory blocks per sweep and
spin-locks between thread blocks.  The TPU translation (DESIGN.md §2) holds
the ENTIRE padded matrix in VMEM (the working set of bulge chasing is the
band — small by construction: the paper's whole point is b ≪ n) and walks
the static wavefront schedule as the Pallas grid:

* grid = (num_wavefronts, num_cells) — both sequential ("arbitrary"); the
  matrix block index is constant, so it stays resident in VMEM across all
  wavefronts and is written back to HBM once at the end.  This is the
  paper's "hide the data movement" taken to its limit: one load, one store.
* each grid cell chases a GROUP of G independent bulges of the wavefront:
  the cells of a wavefront tile its ``A = max_active_sweeps`` slots, and
  each slot applies one 3b x 3b two-sided Householder window update in
  place.  Window disjointness within a wavefront — the same invariant that
  makes the XLA executor's batched update race-free — makes the cell order
  irrelevant.  Masked slots are routed to a zero scratch corner and
  degenerate to tau = 0 no-ops, so the schedule needs no branches.
* the TPU loads and stores VMEM at (8, 128)-aligned offsets only, and a
  window starts at any row.  So each slot loads the aligned (TR, TW) tile
  that contains its window, updates the window inside it with masks (rows
  and columns outside the window are written back unchanged), and stores
  the tile.  All vectors are kept 2-D — columns (TR, 1) and rows (1, TW) —
  and a column becomes a row by a masked reduction, which is exact.
* with ``return_log=True`` each cell also writes the reflector log (v, tau,
  row0) for its slots, laid out like ``chase_wavefront``'s (W, A, b)
  sweep-major log, so the eigenvector path (``apply_q2`` and the
  back-transform regroup) consumes kernel logs unchanged.  The log leaves
  the kernel lane-dense: blocks of 8 wavefront rows, revisited by the
  cells of those 8 wavefronts and written back once.

VMEM: the padded matrix, once as input and once as output (both single-
buffered), plus the log blocks — see :func:`bulge_vmem_bytes`.  The
dispatch ceiling is that count against ``repro.kernels.limits``; above it
the ops wrapper falls back to the XLA wavefront executor (HBM-resident).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bulge_chasing import _pad_sizes, num_wavefronts, max_active_sweeps

from .limits import tile_bytes, vmem_limit_bytes

__all__ = ["bulge_wavefront_pallas", "bulge_chase_pallas", "bulge_vmem_bytes"]


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _geometry(n: int, b: int, group: int):
    """Static sizes: (off, scratch0, side, tr, tw, W_total, G, S).

    ``side`` is the padded matrix side (a multiple of 128 that holds every
    window); (tr, tw) is the aligned tile that contains any 3b x 3b window.
    """
    off, scratch0, total = _pad_sizes(n, b)
    tr = _round_up(3 * b + 7, 8)
    tw = _round_up(3 * b + 127, 128)
    side = max(_round_up(total, 128), tw)
    A = max_active_sweeps(n, b)
    G = max(1, min(int(group), A))
    S = -(-A // G)
    return off, scratch0, side, tr, tw, num_wavefronts(n, b), G, S


def bulge_vmem_bytes(n: int, b: int, *, group: int = 1, return_log: bool = False) -> int:
    """VMEM bytes held by :func:`bulge_wavefront_pallas` at (n, b, group)."""
    _, _, side, _, _, _, G, S = _geometry(n, b, group)
    nbytes = 2 * tile_bytes((side, side))  # resident input and output matrix
    if return_log:
        nbytes += tile_bytes((8, S * G * b), buffers=2)
        nbytes += 2 * tile_bytes((8, S * G), buffers=2)
    return nbytes


def _window_update(T, dr, dc, is_first, b: int):
    """Two-sided Householder update of the 3b x 3b window at (dr, dc) of the
    aligned tile ``T``; everything outside the window is left bit-identical.

    The eliminated column is window column ``b-1`` for sweep-start ops and
    ``0`` for chase ops.  Returns ``(Tn, u, tau)`` with ``u`` the (tr, 1)
    reflector column in the conventions of
    ``repro.core.bulge_chasing._window_op`` (v[0] = 1 at window row b).
    """
    tr, tw = T.shape
    dtype = T.dtype
    li = lax.broadcasted_iota(jnp.int32, (tr, 1), 0) - dr  # window row
    lj = lax.broadcasted_iota(jnp.int32, (1, tw), 1) - dc  # window column
    diag = li == lj

    def to_row(col):  # (tr, 1) -> (1, tw) along the window diagonal, exact
        return jnp.sum(jnp.where(diag, col, 0.0), axis=0, keepdims=True)

    elim = jnp.where(is_first, b - 1, 0)
    in_rows = (li >= b) & (li < 2 * b)
    col = jnp.sum(jnp.where(lj == elim, T, 0.0), axis=1, keepdims=True)
    x = jnp.where(in_rows, col, 0.0)

    # house(x) with the pivot at window row b.
    alpha = jnp.sum(jnp.where(li == b, x, 0.0))
    sigma = jnp.sum(jnp.where(li > b, x * x, 0.0))
    mu = jnp.sqrt(alpha * alpha + sigma)
    safe_denom = jnp.where(alpha + mu == 0, jnp.ones((), dtype), alpha + mu)
    v0 = jnp.where(alpha <= 0, alpha - mu, -sigma / safe_denom)
    degenerate = sigma == 0
    v0_safe = jnp.where(degenerate, jnp.ones((), dtype), v0)
    tau = jnp.where(degenerate, 0.0, 2.0 * v0_safe * v0_safe / (sigma + v0_safe * v0_safe))
    beta = jnp.where(degenerate, alpha, mu)
    u = jnp.where(li == b, 1.0, jnp.where(li > b, x / v0_safe, 0.0))
    u = jnp.where(in_rows, u, 0.0)

    # Symmetric two-sided rank-2 form, restricted to the window: u and w
    # vanish outside its rows, their row forms outside its columns.
    u_r = to_row(u)
    in_win = (li >= 0) & (li < 3 * b)
    Mv = jnp.where(in_win, jnp.sum(T * u_r, axis=1, keepdims=True), 0.0)
    vMv = jnp.sum(u * Mv)
    wvec = tau * (Mv - 0.5 * tau * vMv * u)
    Tn = T - u * to_row(wvec) - wvec * u_r

    # Exact zeros in the eliminated column/row.
    in_cols = (lj >= b) & (lj < 2 * b)
    Tn = jnp.where(in_rows & (lj == elim), jnp.where(li == b, beta, 0.0), Tn)
    Tn = jnp.where((li == elim) & in_cols, jnp.where(lj == b, beta, 0.0), Tn)
    return Tn, u, li, tau


def _bulge_kernel(
    bin_ref,
    bout_ref,
    *log_refs,
    n: int,
    b: int,
    G: int,
    off: int,
    scratch0: int,
    side: int,
    tr: int,
    tw: int,
):
    w = pl.program_id(0)
    c = pl.program_id(1)

    @pl.when((w == 0) & (c == 0))
    def _copy_in():
        bout_ref[...] = bin_ref[...]

    if log_refs:
        vs_ref, taus_ref, row0_ref = log_refs
        wrow = lax.broadcasted_iota(jnp.int32, (8, 1), 0) == w % 8
        vs_blk = vs_ref[...]
        taus_blk = taus_ref[...]
        row0_blk = row0_ref[...]
        lv = lax.broadcasted_iota(jnp.int32, (1, vs_blk.shape[1]), 1)
        lt = lax.broadcasted_iota(jnp.int32, (1, taus_blk.shape[1]), 1)

    for g in range(G):  # static unroll over the cell's bulge group
        a = c * G + g  # wavefront slot chased by this (cell, lane)
        s = w // 3 - a
        k = w - 3 * s
        kmax_s = (n - 3 - jnp.clip(s, 0, n - 3)) // b
        active = (s >= 0) & (s <= n - 3) & (k >= 0) & (k <= kmax_s)
        r0 = jnp.where(active, off + s + 1 + (k - 1) * b, scratch0)
        ra = pl.multiple_of(jnp.minimum((r0 // 8) * 8, side - tr), 8)
        ca = pl.multiple_of(jnp.minimum((r0 // 128) * 128, side - tw), 128)
        T = bout_ref[pl.ds(ra, tr), pl.ds(ca, tw)]
        Tn, u, li, tau = _window_update(T, r0 - ra, r0 - ca, k == 0, b)
        bout_ref[pl.ds(ra, tr), pl.ds(ca, tw)] = Tn
        if log_refs:
            # v = u[b:2b] lands in lanes [a*b, a*b + b) of this wavefront's row.
            e = lv - a * b
            v_row = jnp.sum(
                jnp.where((li - b == e) & (e >= 0) & (e < b), u, 0.0),
                axis=0, keepdims=True,
            )
            vs_blk = jnp.where(wrow & (e >= 0) & (e < b), v_row, vs_blk)
            slot = wrow & (lt == a)
            taus_blk = jnp.where(slot, tau, taus_blk)
            row0 = jnp.where(active, s + 1 + k * b, n).astype(jnp.int32)
            row0_blk = jnp.where(slot, row0, row0_blk)

    if log_refs:
        vs_ref[...] = vs_blk
        taus_ref[...] = taus_blk
        row0_ref[...] = row0_blk


@functools.partial(jax.jit, static_argnames=("b", "group", "return_log", "interpret"))
def bulge_wavefront_pallas(
    B: jax.Array,
    b: int,
    *,
    group: int = 1,
    return_log: bool = False,
    interpret: bool = False,
):
    """Band (dense storage, bandwidth b) -> tridiagonal, VMEM-resident.

    Matches ``repro.core.chase_wavefront`` up to float rounding; with
    ``return_log=True`` also returns the raw sweep-major log arrays
    ``(vs, taus, row0)`` shaped ``(W, S*group, b)`` / ``(W, S*group)`` —
    slot-compatible with the XLA executor's ``(W, A, b)`` log (slots past
    ``A`` are masked no-ops; the ops wrapper wraps them in a ``ChaseLog``).

    ``group`` is the number of bulges chased per grid cell (autotuned
    per-platform); the wavefront's ``A`` slots are tiled by
    ``S = ceil(A / group)`` cells.
    """
    n = B.shape[0]
    if n < 3 or b <= 1:
        if return_log:
            raise ValueError("trivial chase emits no log; handle n < 3 in the caller")
        return B
    off, scratch0, side, tr, tw, W_total, G, S = _geometry(n, b, group)
    W_pad = _round_up(W_total, 8)

    Bp = jnp.zeros((side, side), B.dtype)
    Bp = lax.dynamic_update_slice(Bp, B, (off, off))

    kernel = functools.partial(
        _bulge_kernel, n=n, b=b, G=G, off=off, scratch0=scratch0,
        side=side, tr=tr, tw=tw,
    )
    resident = pl.BlockSpec(
        (side, side), lambda w, c: (0, 0), pipeline_mode=pl.Buffered(1)
    )
    out_shape = [jax.ShapeDtypeStruct((side, side), B.dtype)]
    out_specs = [resident]
    if return_log:
        out_shape += [
            jax.ShapeDtypeStruct((W_pad, S * G * b), B.dtype),
            jax.ShapeDtypeStruct((W_pad, S * G), B.dtype),
            jax.ShapeDtypeStruct((W_pad, S * G), jnp.int32),
        ]
        out_specs += [
            pl.BlockSpec((8, S * G * b), lambda w, c: (w // 8, 0)),
            pl.BlockSpec((8, S * G), lambda w, c: (w // 8, 0)),
            pl.BlockSpec((8, S * G), lambda w, c: (w // 8, 0)),
        ]
    res = pl.pallas_call(
        kernel,
        grid=(W_total, S),
        in_specs=[resident],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # Above the v5e's 16 MiB default scoped VMEM from n ~ 1000 on.
            vmem_limit_bytes=vmem_limit_bytes(
                bulge_vmem_bytes(n, b, group=group, return_log=return_log)
            ),
        ),
        interpret=interpret,
        name="bulge_chase_wavefront",
    )(Bp)
    out = lax.dynamic_slice(res[0], (off, off), (n, n))
    if return_log:
        vs = res[1][:W_total].reshape(W_total, S * G, b)
        return out, (vs, res[2][:W_total], res[3][:W_total])
    return out


def bulge_chase_pallas(B: jax.Array, b: int, *, interpret: bool = False) -> jax.Array:
    """Values-only alias kept for the original kernel's call sites."""
    return bulge_wavefront_pallas(B, b, return_log=False, interpret=interpret)
