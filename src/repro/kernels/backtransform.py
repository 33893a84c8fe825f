"""Pallas TPU kernel: blocked compact-WY eigenvector back-transform (Q2).

Applies the bulge-chase orthogonal factor Q2 (or its transpose) to the
eigenvector panel X through the sweep-major regrouped reflector log (see
``repro.core.backtransform``).  The memory story mirrors the bulge kernel:

* grid = (C, S) — C column blocks of X (Q2 acts on each column alone), and
  one step per sweep, sequential ("arbitrary"); within a column block the
  X output block index is constant, so that padded slice of the
  eigenvector panel stays resident in VMEM across all sweeps and is written
  back to HBM once.  The scan applier reads and writes X O(n) times; this
  kernel does it once each way — the back-transform's data movement
  collapses to the panel size.  The column block is the widest that fits
  the VMEM budget, so large panels stay on the kernel path.
* the reflectors stream in lane-dense: the log is laid out as rows of
  ``K*b`` values per sweep, and each grid step reads the 8-sweep block that
  holds its sweep (selected by an index map that also encodes the sweep
  direction: reversed for Q2 @ X, forward for Q2^T @ X).
* within a step, groups of ``group`` consecutive reflectors update one
  contiguous (b·group)-row slice of the resident panel in place — their row
  supports are disjoint by the sweep-major invariant, so a group is one
  branch-free batched update (masked slots carry tau == 0 and no-op).  The
  TPU loads rows at multiples of 8 only, so a group updates the aligned
  row slab that holds its slice; two 0/1 segment matrices on the MXU form
  the per-reflector projections and spread them back.  Groups that start
  at row n or below it touch only the zero padding and are skipped.

VMEM: one column block of the padded panel (n + group·b + 8, mb), as input
and as output (both single-buffered), plus the streamed log blocks — see
:func:`backtransform_vmem_bytes`.  Where no column block fits the budget in
``repro.kernels.limits`` the jit wrapper in ``repro.kernels.ops`` falls back
to the XLA scan implementation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .limits import tile_bytes, vmem_limit_bytes

__all__ = ["backtransform_wy_pallas", "backtransform_vmem_bytes", "column_block"]


def _rows(n: int, K: int, b: int, group: int) -> int:
    """Padded panel rows: any group starting below row n fits."""
    return -(-(n + min(group, K) * b + 8) // 8) * 8


def backtransform_vmem_bytes(n: int, mb: int, K: int, b: int, group: int) -> int:
    """VMEM bytes held by :func:`backtransform_wy_pallas` on an (n, mb)
    column block with K reflectors of length b per sweep."""
    group = max(1, min(int(group), K))
    panel = 2 * tile_bytes((_rows(n, K, b, group), mb))
    log = tile_bytes((8, K * b), buffers=2) + tile_bytes((8, K), buffers=2)
    return panel + log


def column_block(n: int, m: int, K: int, b: int, group: int, fits) -> int:
    """The widest column block ``mb`` of an (n, m) panel whose VMEM count
    ``fits`` accepts: m itself, or m halved while it stays a multiple of
    128 lanes.  0 when none fits."""
    mb = m
    while not fits(backtransform_vmem_bytes(n, mb, K, b, group)):
        if mb % 256:
            return 0
        mb //= 2
    return mb


def _bt_kernel(
    vs_ref, taus_ref, x_in_ref, x_out_ref, *, S, K, b, group, transpose, n
):
    w = pl.program_id(1)

    @pl.when(w == 0)
    def _copy_in():
        x_out_ref[...] = x_in_ref[...]

    # Sweep order: forward for Q2^T, reversed for Q2 (the index maps stream
    # the matching reflector block; this is the same arithmetic).
    s = w if transpose else S - 1 - w
    hi = lax.Precision.HIGHEST
    sel = lax.broadcasted_iota(jnp.int32, (8, 1), 0) == s % 8
    vrow = jnp.sum(jnp.where(sel, vs_ref[...], 0.0), axis=0, keepdims=True)
    trow = jnp.sum(jnp.where(sel, taus_ref[...], 0.0), axis=0, keepdims=True)
    n_groups = -(-K // group)
    for g in range(n_groups):
        k0 = g * group
        gk = min(group, K - k0)
        rt = -(-(gk * b + 7) // 8) * 8
        r0 = s + 1 + k0 * b

        @pl.when(r0 < n)
        def _group():
            ra = pl.multiple_of((r0 // 8) * 8, 8)
            X = x_out_ref[pl.ds(ra, rt), :]
            rho_c = lax.broadcasted_iota(jnp.int32, (rt, 1), 0) - (r0 - ra)
            rho_r = lax.broadcasted_iota(jnp.int32, (1, rt), 1) - (r0 - ra)
            # v of the group as a (rt, 1) column aligned with the slab rows.
            lane = lax.broadcasted_iota(jnp.int32, (1, gk * b), 1)
            v = jnp.sum(
                jnp.where(rho_c == lane, vrow[:, k0 * b : (k0 + gk) * b], 0.0),
                axis=1, keepdims=True,
            )
            kr = lax.broadcasted_iota(jnp.int32, (gk, 1), 0)
            kc = lax.broadcasted_iota(jnp.int32, (1, gk), 1)
            seg = ((rho_r >= kr * b) & (rho_r < kr * b + b)).astype(X.dtype)
            seg_t = (rho_c >= kc * b) & (rho_c < kc * b + b)
            seg_t = jnp.where(seg_t, trow[:, k0 : k0 + gk], 0.0)
            proj = jnp.dot(seg, v * X, precision=hi, preferred_element_type=jnp.float32)
            upd = v * jnp.dot(seg_t, proj, precision=hi, preferred_element_type=jnp.float32)
            x_out_ref[pl.ds(ra, rt), :] = X - upd.astype(X.dtype)


@functools.partial(
    jax.jit, static_argnames=("b", "group", "mb", "transpose", "interpret")
)
def backtransform_wy_pallas(
    X: jax.Array,
    vs: jax.Array,
    taus: jax.Array,
    *,
    b: int,
    group: int,
    mb: int = 0,
    transpose: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """Blocked Q2 application, VMEM-resident per column block.

    X: (n, m); vs: (S, K, b) / taus: (S, K) sweep-major (masked tails carry
    tau == 0); ``mb`` the column block (a divisor of m; 0 means m).
    Matches ``repro.core.backtransform.backtransform_wy_xla`` up to float
    rounding.
    """
    S, K, _ = vs.shape
    n, m = X.shape
    group = max(1, min(int(group), K))
    mb = mb or m
    if m % mb:
        raise ValueError(f"column block {mb} does not divide m={m}")
    rows = _rows(n, K, b, group)
    Xp = jnp.zeros((rows, m), X.dtype).at[:n, :].set(X)
    # Lane-dense log: one (K*b)-row per sweep, padded to whole 8-sweep blocks.
    S_pad = -(-S // 8) * 8
    vs2 = jnp.zeros((S_pad, K * b), vs.dtype).at[:S].set(vs.reshape(S, K * b))
    taus2 = jnp.zeros((S_pad, K), taus.dtype).at[:S].set(taus)

    def order(w):
        return (w if transpose else S - 1 - w) // 8

    kernel = functools.partial(
        _bt_kernel, S=S, K=K, b=b, group=group, transpose=transpose, n=n
    )
    panel = pl.BlockSpec(
        (rows, mb), lambda j, w: (0, j), pipeline_mode=pl.Buffered(1)
    )
    out = pl.pallas_call(
        kernel,
        grid=(m // mb, S),
        in_specs=[
            pl.BlockSpec((8, K * b), lambda j, w: (order(w), 0)),
            pl.BlockSpec((8, K), lambda j, w: (order(w), 0)),
            panel,
        ],
        out_specs=panel,
        out_shape=jax.ShapeDtypeStruct((rows, m), X.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            # Above the v5e's 16 MiB default scoped VMEM for column blocks
            # wider than ~900 at n ~ 1000.
            vmem_limit_bytes=vmem_limit_bytes(
                backtransform_vmem_bytes(n, mb, K, b, group)
            ),
        ),
        interpret=interpret,
        name="backtransform_wy",
    )(vs2, taus2, Xp)
    return out[:n, :]
