"""Pallas TPU kernel: wavefront bulge chasing (the paper's §4.2/§5.3).

The GPU implementation keeps two shared-memory blocks per sweep and
spin-locks between thread blocks.  The TPU translation (DESIGN.md §2) holds
the matrix in VMEM — all of it, or only the band strip the chase can touch
(the working set of bulge chasing is the band — small by construction: the
paper's whole point is b ≪ n) — and walks the static wavefront schedule as
the Pallas grid:

* grid = (num_wavefronts,) — sequential ("arbitrary"); the matrix block
  index is constant, so it stays resident in VMEM across all wavefronts and
  is written back to HBM once at the end.  This is the paper's "hide the
  data movement" taken to its limit: one load, one store.
* each step walks only the LIVE slots of its wavefront: slot ``a`` chases
  sweep ``w//3 - a``, and the slots whose sweep has started and not yet
  ended form one range ``[lo(w), hi(w)]`` with a closed form
  (:func:`_live_slots`), about half of the ``A = max_active_sweeps`` slots.
  A ``fori_loop`` chases the range in GROUPS of G bulges, each applying one
  3b x 3b two-sided Householder window update in place.  Window
  disjointness within a wavefront — the same invariant that makes the XLA
  executor's batched update race-free — makes the loop order irrelevant.
  A group's tail slots past ``hi(w)`` are routed to a zero scratch corner
  and degenerate to tau = 0 no-ops, so a group needs no branches.
* the TPU loads and stores VMEM at (8, 128)-aligned offsets only, and a
  window starts at any row.  So each slot loads the aligned (TR, TW) tile
  that contains its window, updates the window inside it with masks (rows
  and columns outside the window are written back unchanged), and stores
  the tile.  All vectors are kept 2-D — columns (TR, 1) and rows (1, TW) —
  and a column becomes a row by a masked reduction, which is exact.
* with ``return_log=True`` each step also writes the reflector log (v, tau,
  row0) of its wavefront, laid out like ``chase_wavefront``'s (W, A, b)
  sweep-major log, so the eigenvector path (``apply_q2`` and the
  back-transform regroup) consumes kernel logs unchanged.  The step writes
  its whole row: slots outside the live range read v = 0, tau = 0 and
  row0 = n.  The log leaves the kernel lane-dense: blocks of 8 wavefront
  rows, revisited by the steps of those 8 wavefronts and written back once.

Two layouts of the resident matrix, each its own ``pallas_call``:

* **dense** (``bulge_chase_wavefront``): the padded ``(side, side)`` matrix,
  once as input and once as output (both single-buffered), plus the log
  blocks — see :func:`bulge_vmem_bytes`.  It fits the budget up to
  n ~ 1900 at b = 8.
* **band strip** (``bulge_chase_strip``): only the 128-column blocks around
  the diagonal — see :func:`bulge_strip_vmem_bytes` (DESIGN.md §2).
  Invariants:

  - row ``i`` of the ``(side, L)`` strip, ``L = tw + 128``, in 128-row
    block ``p = i // 128``, holds columns ``[128(p - 1), 128(p - 1) + L)``
    of the padded matrix; columns outside the matrix read zero;
  - every aligned tile has rows ``[ra, ra + tr)`` in blocks ``P = r0 // 128``
    and ``P + 1`` (``tr <= 128``), and columns ``[128P, 128P + tw)``: rows
    of block ``P`` find them at strip lanes ``[128, 128 + tw)``, rows of
    block ``P + 1`` at ``[0, tw)`` — two static lane offsets, so only the
    row offset is dynamic;
  - ``side`` holds block ``P + 1`` of the scratch window, so ``ca`` is never
    clamped;
  - every entry a window touches lies in its tile, so the strip kernel
    computes exactly what the dense kernel computes; entries outside the
    strip are never touched and the ops wrapper takes them from the input.

The ops wrapper (``repro.kernels.ops.bulge_kernel``) tries dense, then
strip, against the budget of ``repro.kernels.limits``; above both it falls
back to the XLA wavefront executor (HBM-resident).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bulge_chasing import _pad_sizes, num_wavefronts, max_active_sweeps

from .limits import tile_bytes, vmem_limit_bytes

__all__ = [
    "bulge_wavefront_pallas",
    "bulge_vmem_bytes",
    "chase_slot_counts",
    "bulge_strip_vmem_bytes",
    "strip_fits_tile",
    "BULGE_DENSE",
    "BULGE_STRIP",
]

# The pallas_call names of the two layouts.
BULGE_DENSE = "bulge_chase_wavefront"
BULGE_STRIP = "bulge_chase_strip"
LANE_BLOCK = 128


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _geometry(n: int, b: int, group: int, strip: bool = False):
    """Static sizes: (off, scratch0, side, tr, tw, W_total, G, S).

    ``side`` is the padded matrix side (a multiple of 128 that holds every
    window); (tr, tw) is the aligned tile that contains any 3b x 3b window.
    The strip layout adds the block past the scratch window's, so a tile's
    columns are never clamped.
    """
    off, scratch0, total = _pad_sizes(n, b)
    tr = _round_up(3 * b + 7, 8)
    tw = _round_up(3 * b + 127, 128)
    if strip:
        side = (scratch0 // LANE_BLOCK + 2) * LANE_BLOCK
    else:
        side = max(_round_up(total, 128), tw)
    A = max_active_sweeps(n, b)
    G = max(1, min(int(group), A))
    S = -(-A // G)
    return off, scratch0, side, tr, tw, num_wavefronts(n, b), G, S


def _log_vmem_bytes(S: int, G: int, b: int) -> int:
    return tile_bytes((8, S * G * b), buffers=2) + 2 * tile_bytes((8, S * G), buffers=2)


def bulge_vmem_bytes(n: int, b: int, *, group: int = 1, return_log: bool = False) -> int:
    """VMEM bytes held by the dense layout of :func:`bulge_wavefront_pallas`."""
    _, _, side, _, _, _, G, S = _geometry(n, b, group)
    nbytes = 2 * tile_bytes((side, side))  # resident input and output matrix
    if return_log:
        nbytes += _log_vmem_bytes(S, G, b)
    return nbytes


def strip_fits_tile(b: int) -> bool:
    """Whether every aligned tile at bandwidth ``b`` spans at most two
    128-row blocks, which the strip layout's two lane offsets assume."""
    return _round_up(3 * b + 7, 8) <= LANE_BLOCK


def bulge_strip_vmem_bytes(
    n: int, b: int, *, group: int = 1, return_log: bool = False
) -> int:
    """VMEM bytes held by the band-strip layout of :func:`bulge_wavefront_pallas`."""
    _, _, side, _, tw, _, G, S = _geometry(n, b, group, strip=True)
    nbytes = 2 * tile_bytes((side, tw + LANE_BLOCK))  # resident input and output strip
    if return_log:
        nbytes += _log_vmem_bytes(S, G, b)
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


def _strip_tile(ref, ra, r0, tr: int, tw: int):
    """The aligned (tr, tw) tile at row ``ra``, columns ``128 * (r0 // 128)``,
    read from the band strip: ``(band, upper, T)``, with ``upper`` the tile
    rows of block ``r0 // 128`` (the rest are of the next block)."""
    band = ref[pl.ds(ra, tr), :]
    rows = ra + lax.broadcasted_iota(jnp.int32, (tr, 1), 0)
    upper = rows < (r0 // LANE_BLOCK + 1) * LANE_BLOCK
    T = jnp.where(upper, band[:, LANE_BLOCK:], band[:, :tw])
    return band, upper, T


def _strip_band(band, upper, Tn, tw: int):
    """``band`` with the updated tile ``Tn`` put back at each row's lane
    offset, built whole so that one store writes it."""
    return jnp.where(
        upper,
        jnp.concatenate([band[:, :LANE_BLOCK], Tn], axis=1),
        jnp.concatenate([Tn, band[:, tw:]], axis=1),
    )


def _live_slots(w, n: int, b: int, A: int, xp=jnp):
    """The live slots ``[lo, hi]`` of wavefront ``w`` (empty when hi < lo).

    Slot ``a`` chases sweep ``s = w//3 - a`` at step ``k = w%3 + 3a``; it
    is live while ``0 <= s <= n - 3`` and ``b k <= n - 3 - s``, which bounds
    ``a`` below by ``w//3 - (n - 3)`` and above by
    ``(n - 3 - w//3 - b (w%3)) / (3b - 1)``.  ``xp`` is ``jnp`` in the
    kernel (scalar arithmetic on the program id) and ``np`` on the host.
    """
    q, r = w // 3, w % 3
    lo = xp.maximum(q - (n - 3), 0)
    num = n - 3 - q - b * r
    hi = xp.minimum(xp.minimum(q, A - 1), xp.maximum(num, 0) // (3 * b - 1))
    return lo, xp.where(num < 0, -1, hi)


def chase_slot_counts(n: int, b: int, group: int = 1):
    """``(live, visited, rectangle)`` window updates of one chase: the live
    slots, the slots the kernel visits (each wavefront's live range rounded
    up to whole groups of ``group``), and the ``(W, S*G)`` rectangle of
    every slot of every wavefront."""
    _, _, _, _, _, W, G, S = _geometry(n, b, group)
    lo, hi = _live_slots(np.arange(W), n, b, max_active_sweeps(n, b), xp=np)
    live = np.maximum(hi - lo + 1, 0)
    return int(live.sum()), int((-(-live // G) * G).sum()), W * S * G


def _bulge_kernel(
    bin_ref,
    bout_ref,
    *log_refs,
    n: int,
    b: int,
    A: int,
    G: int,
    off: int,
    scratch0: int,
    side: int,
    tr: int,
    tw: int,
    strip: bool,
):
    w = pl.program_id(0)

    @pl.when(w == 0)
    def _copy_in():
        bout_ref[...] = bin_ref[...]

    a_lo, a_hi = _live_slots(w, n, b, A)
    q, r = w // 3, w % 3
    if log_refs:
        vs_ref, taus_ref, row0_ref = log_refs
        wrow = lax.broadcasted_iota(jnp.int32, (8, 1), 0) == w % 8
        lv = lax.broadcasted_iota(jnp.int32, (1, vs_ref.shape[1]), 1)
        lt = lax.broadcasted_iota(jnp.int32, (1, taus_ref.shape[1]), 1)

    def chase(i, logs):
        for g in range(G):  # static unroll over the iteration's bulge group
            a = a_lo + i * G + g  # wavefront slot chased by this (iteration, lane)
            s = q - a
            k = r + 3 * a
            active = a <= a_hi
            r0 = jnp.where(active, off + s + 1 + (k - 1) * b, scratch0)
            if strip:
                ra = pl.multiple_of((r0 // 8) * 8, 8)
                band, upper, T = _strip_tile(bout_ref, ra, r0, tr, tw)
                Tn, u, li, tau = _window_update(T, r0 - ra, r0 % LANE_BLOCK, k == 0, b)
                bout_ref[pl.ds(ra, tr), :] = _strip_band(band, upper, Tn, tw)
            else:
                ra = pl.multiple_of(jnp.minimum((r0 // 8) * 8, side - tr), 8)
                ca = pl.multiple_of(jnp.minimum((r0 // 128) * 128, side - tw), 128)
                T = bout_ref[pl.ds(ra, tr), pl.ds(ca, tw)]
                Tn, u, li, tau = _window_update(T, r0 - ra, r0 - ca, k == 0, b)
                bout_ref[pl.ds(ra, tr), pl.ds(ca, tw)] = Tn
            if log_refs:
                # v = u[b:2b] lands in lanes [a*b, a*b + b) of this wavefront's
                # row; a masked tail slot leaves the row as it starts.
                vs_blk, taus_blk, row0_blk = logs
                e = lv - a * b
                lanes = wrow & (e >= 0) & (e < b) & active
                v_row = jnp.sum(jnp.where(li - b == e, u, 0.0), axis=0, keepdims=True)
                slot = wrow & (lt == a) & active
                logs = (
                    jnp.where(lanes, v_row, vs_blk),
                    jnp.where(slot, tau, taus_blk),
                    jnp.where(slot, s + 1 + k * b, row0_blk),
                )
        return logs

    steps = (jnp.maximum(a_hi - a_lo + 1, 0) + G - 1) // G
    if not log_refs:
        lax.fori_loop(0, steps, chase, ())
        return

    # The step writes all of its wavefront's row: the slots the loop does not
    # visit read v = 0, tau = 0 and row0 = n, the log of an inactive slot.
    logs = (
        jnp.where(wrow, 0.0, vs_ref[...]),
        jnp.where(wrow, 0.0, taus_ref[...]),
        jnp.where(wrow, n, row0_ref[...]),
    )
    vs_ref[...], taus_ref[...], row0_ref[...] = lax.fori_loop(0, steps, chase, logs)


def _pack_strip(B: jax.Array, off: int, side: int, tw: int) -> jax.Array:
    """(side, tw + 128) strip of ``B`` placed at (off, off) in a zero
    (side, side) matrix: row block p holds columns [128(p - 1), 128(p - 1) + L)."""
    n = B.shape[0]
    L = tw + LANE_BLOCK
    # Column c of the padded matrix sits at column c + 128, so block -1 and
    # the columns past the end read zero.
    left = off + LANE_BLOCK
    Bq = jnp.pad(B, ((off, side - off - n), (left, side + tw - n - left)))
    return jnp.concatenate([
        lax.slice(Bq, (r, r), (r + LANE_BLOCK, r + L))
        for r in range(0, side, LANE_BLOCK)
    ])


def _unpack_strip(strip: jax.Array, B: jax.Array, off: int, side: int, tw: int) -> jax.Array:
    """The (n, n) matrix of ``strip`` (the inverse of :func:`_pack_strip`);
    entries the strip does not hold are taken from ``B``."""
    n = B.shape[0]
    L = tw + LANE_BLOCK
    Dq = jnp.concatenate([
        jnp.pad(strip[r:r + LANE_BLOCK], ((0, 0), (r, side + tw - r - L)))
        for r in range(0, side, LANE_BLOCK)
    ])
    D = lax.slice(Dq, (off, off + LANE_BLOCK), (off + n, off + LANE_BLOCK + n))
    i = lax.broadcasted_iota(jnp.int32, (n, n), 0) + off
    j = lax.broadcasted_iota(jnp.int32, (n, n), 1) + off
    lo = (i // LANE_BLOCK - 1) * LANE_BLOCK
    return jnp.where((j >= lo) & (j < lo + L), D, B)


@functools.partial(
    jax.jit, static_argnames=("b", "group", "return_log", "strip", "interpret")
)
def bulge_wavefront_pallas(
    B: jax.Array,
    b: int,
    *,
    group: int = 1,
    return_log: bool = False,
    strip: bool = False,
    interpret: bool = False,
):
    """Band (dense storage, bandwidth b) -> tridiagonal, VMEM-resident.

    Matches ``repro.core.chase_wavefront`` up to float rounding; with
    ``return_log=True`` also returns the raw sweep-major log arrays
    ``(vs, taus, row0)`` shaped ``(W, S*group, b)`` / ``(W, S*group)`` —
    slot-compatible with the XLA executor's ``(W, A, b)`` log (slots that
    are not live, those past ``A`` among them, are no-ops; the ops wrapper
    wraps them in a ``ChaseLog``).

    ``group`` is the number of bulges chased per loop iteration (autotuned
    per-platform); the log keeps ``S = ceil(A / group)`` groups of lanes
    per wavefront.  ``strip`` holds the band strip in VMEM
    instead of the dense matrix (``bulge_chase_strip``); both layouts give
    the same result.
    """
    n = B.shape[0]
    if n < 3 or b <= 1:
        if return_log:
            raise ValueError("trivial chase emits no log; handle n < 3 in the caller")
        return B
    if strip and not strip_fits_tile(b):
        raise ValueError(f"the band-strip layout needs 3b + 7 <= 128, got b = {b}")
    off, scratch0, side, tr, tw, W_total, G, S = _geometry(n, b, group, strip)
    W_pad = _round_up(W_total, 8)

    if strip:
        Bp = _pack_strip(B, off, side, tw)
        count = bulge_strip_vmem_bytes
    else:
        Bp = jnp.zeros((side, side), B.dtype)
        Bp = lax.dynamic_update_slice(Bp, B, (off, off))
        count = bulge_vmem_bytes

    kernel = functools.partial(
        _bulge_kernel, n=n, b=b, A=max_active_sweeps(n, b), G=G, off=off,
        scratch0=scratch0, side=side, tr=tr, tw=tw, strip=strip,
    )
    resident = pl.BlockSpec(
        Bp.shape, lambda w: (0, 0), pipeline_mode=pl.Buffered(1)
    )
    out_shape = [jax.ShapeDtypeStruct(Bp.shape, B.dtype)]
    out_specs = [resident]
    if return_log:
        out_shape += [
            jax.ShapeDtypeStruct((W_pad, S * G * b), B.dtype),
            jax.ShapeDtypeStruct((W_pad, S * G), B.dtype),
            jax.ShapeDtypeStruct((W_pad, S * G), jnp.int32),
        ]
        out_specs += [
            pl.BlockSpec((8, S * G * b), lambda w: (w // 8, 0)),
            pl.BlockSpec((8, S * G), lambda w: (w // 8, 0)),
            pl.BlockSpec((8, S * G), lambda w: (w // 8, 0)),
        ]
    res = pl.pallas_call(
        kernel,
        grid=(W_total,),
        in_specs=[resident],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # Above the v5e's 16 MiB default scoped VMEM from n ~ 1000 on.
            vmem_limit_bytes=vmem_limit_bytes(
                count(n, b, group=group, return_log=return_log)
            ),
        ),
        interpret=interpret,
        name=BULGE_STRIP if strip else BULGE_DENSE,
    )(Bp)
    if strip:
        out = _unpack_strip(res[0], B, off, side, tw)
    else:
        out = lax.dynamic_slice(res[0], (off, off), (n, n))
    if return_log:
        vs = res[1][:W_total].reshape(W_total, S * G, b)
        return out, (vs, res[2][:W_total], res[3][:W_total])
    return out
