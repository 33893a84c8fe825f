"""Distributed EVD building blocks (shard_map).

The paper targets a single accelerator; its future-work section calls out
"scaling these problems on emerging clusters".  Two regimes matter for us:

1. **One huge matrix** (the paper's standalone workload): the DBR trailing
   update ``A <- A - Z Y^T - Y Z^T`` is row-parallel — each device owns a
   block of rows of A, Y/Z are broadcast (they are tall-skinny, k = nb ≪ n),
   and the update is a pair of local GEMMs with NO inter-device
   communication.  The panel QR + Z formation need `A @ V`, which row-sharded
   A provides with one psum.  ``dist_trailing_update`` / ``dist_symm_panel``
   implement both; ``dist_band_reduce_demo`` wires them into a full sharded
   band reduction for the examples/benchmarks.

2. **Many medium matrices** (the Shampoo regime): a batch of (n, n)
   preconditioner blocks sharded over the flattened mesh; each device runs
   the full two-stage solver locally.  This regime now lives behind
   ``repro.solver.solve_many(..., devices=(mesh, axes))`` — the one front
   door for every multi-matrix consumer — and ``sharded_eigh_batch`` /
   ``sharded_inverse_roots`` here are thin deprecated shims over it.
"""
from __future__ import annotations

import warnings
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.backend import registry
from repro.solver import EvdConfig, solve_many

__all__ = [
    "dist_trailing_update",
    "dist_symm_matmul",
    "dist_band_reduce",
    "sharded_eigh_batch",
    "sharded_inverse_roots",
]


def dist_trailing_update(
    mesh: Mesh, axis: str, A: jax.Array, Y: jax.Array, Z: jax.Array
) -> jax.Array:
    """A - Z Y^T - Y Z^T with A row-sharded over ``axis``; Y, Z replicated.

    Pure local GEMMs — zero collective bytes (the point of the paper's DBR:
    the big-k update is embarrassingly parallel once Y/Z are formed).
    """

    def local(a_blk, y_full, z_full):
        # a_blk: (n/d, n); y/z: (n, k)
        idx = jax.lax.axis_index(axis)
        rows = a_blk.shape[0]
        y_blk = jax.lax.dynamic_slice_in_dim(y_full, idx * rows, rows, 0)
        z_blk = jax.lax.dynamic_slice_in_dim(z_full, idx * rows, rows, 0)
        return a_blk - z_blk @ y_full.T - y_blk @ z_full.T

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis, None), P(None, None), P(None, None)),
        out_specs=P(axis, None),
        check_vma=False,
    )(A, Y, Z)


def dist_symm_matmul(mesh: Mesh, axis: str, A: jax.Array, V: jax.Array) -> jax.Array:
    """M = A @ V with A row-sharded: local GEMM, result gathered (psum-free:
    each device holds its row block of M; we all-gather rows).
    """

    def local(a_blk, v_full):
        m_blk = a_blk @ v_full  # (n/d, k)
        return jax.lax.all_gather(m_blk, axis, axis=0, tiled=True)

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis, None), P(None, None)),
        out_specs=P(None, None),
        check_vma=False,
    )(A, V)


def dist_band_reduce(
    mesh: Mesh,
    axis: str,
    A: jax.Array,
    b: int,
    nb: int,
    panel_qr_fn=None,
):
    """Distributed DBR band reduction (demonstration-scale).

    A is row-sharded over ``axis``; every panel QR runs replicated (panels
    are (m, b), tiny next to the trailing matrix), A@V products and trailing
    updates run row-parallel.  Matches ``repro.core.band_reduce`` numerically.

    The structure mirrors the single-device `_reduce_block` with two
    distributed primitives swapped in; see that function for the algebra.
    """
    from .panel_qr import panel_qr_geqrf

    panel_qr_fn = panel_qr_fn or panel_qr_geqrf
    n = A.shape[0]
    if n % b or nb % b:
        raise ValueError("n and nb must be multiples of b")

    B = A
    ci = 0
    while n - ci > b:
        m = n - ci
        w = min(nb, m - b)
        q = w // b
        view = B[ci:, ci:]
        Vbuf = jnp.zeros((m, w), A.dtype)
        Zbuf = jnp.zeros((m, w), A.dtype)
        F = jnp.zeros((m, w), A.dtype)
        for j in range(q):
            c0 = j * b
            r0 = c0 + b
            Pn = view[:, c0 : c0 + b]
            if j > 0:
                Pn = (
                    Pn
                    - Zbuf[:, :c0] @ Vbuf[c0 : c0 + b, :c0].T
                    - Vbuf[:, :c0] @ Zbuf[c0 : c0 + b, :c0].T
                )
            V_j, T_j, _t, R_j = panel_qr_fn(Pn[r0:, :])
            Vhat = jnp.zeros((m, b), A.dtype).at[r0:, :].set(V_j)
            zeros_tail = jnp.zeros((m - r0, b), A.dtype)
            R_embed = zeros_tail.at[:b, :].set(R_j[:b, :])
            fcol = jnp.concatenate([Pn[:r0, :], R_embed], axis=0)
            col_global = c0 + jnp.arange(b)[None, :]
            in_band = jnp.arange(m)[:, None] >= col_global - b
            F = F.at[:, c0 : c0 + b].set(jnp.where(in_band, fcol, 0.0))
            # Distributed A @ Vhat over the *full* matrix rows >= ci.
            M = view @ Vhat  # local fallback when not under shard_map
            if j > 0:
                M = M - Zbuf[:, :c0] @ (Vbuf[:, :c0].T @ Vhat) - Vbuf[:, :c0] @ (
                    Zbuf[:, :c0].T @ Vhat
                )
            MT = M @ T_j
            Z_j = MT - 0.5 * Vhat @ (T_j.T @ (Vhat.T @ MT))
            Vbuf = Vbuf.at[:, c0 : c0 + b].set(Vhat)
            Zbuf = Zbuf.at[:, c0 : c0 + b].set(Z_j)
        n_dev = mesh.shape[axis]
        if (m - w) % n_dev == 0 and (m - w) >= n_dev:
            trailing = dist_trailing_update(
                mesh, axis, view[w:, w:], Vbuf[w:, :], Zbuf[w:, :]
            )
        else:  # trailing block smaller than the device ring: run locally
            trailing = registry.resolve("trailing_update", "jnp")(
                view[w:, w:], Vbuf[w:, :], Zbuf[w:, :]
            )
        view = view.at[w:, w:].set(trailing)
        view = view.at[:, :w].set(F)
        view = view.at[:w, w:].set(F[w:, :].T)
        B = B.at[ci:, ci:].set(view)
        ci += w
    return B


def _legacy_config(config: Optional[EvdConfig], eigh_kw: dict) -> EvdConfig:
    # Transitional: accept the historical b=/nb=/method= kwargs and fold
    # them into a config so all per-device solves go through one plan.
    if config is not None:
        if eigh_kw:
            raise ValueError(f"pass either config= or legacy kwargs, not both: {eigh_kw}")
        return config
    return EvdConfig(**eigh_kw) if eigh_kw else EvdConfig()


def _deprecated(old: str) -> None:
    warnings.warn(
        f"repro.core.distributed.{old} is a deprecated shim; call "
        f"repro.solver.solve_many(..., devices=(mesh, axes)) instead",
        DeprecationWarning,
        stacklevel=3,
    )


def sharded_eigh_batch(
    mesh: Mesh,
    axes: Sequence[str],
    A_batch: jax.Array,
    *,
    config: Optional[EvdConfig] = None,
    **eigh_kw,
):
    """Deprecated shim over :func:`repro.solver.solve_many`.

    eigh over a batch (B, n, n) sharded across the given mesh axes: each
    device runs the full two-stage solver on its local slice of the batch,
    no collectives — the Shampoo preconditioner pattern.  ``solve_many``
    pads B up to the mesh size with identity lanes, so divisibility is no
    longer a caller concern.
    """
    _deprecated("sharded_eigh_batch")
    cfg = _legacy_config(config, eigh_kw)
    return solve_many(A_batch, cfg, devices=(mesh, tuple(axes)))


def sharded_inverse_roots(
    mesh: Mesh,
    axes: Sequence[str],
    A_batch: jax.Array,
    p: int,
    *,
    eps: float = 1e-6,
    config: Optional[EvdConfig] = None,
    **eigh_kw,
):
    """Deprecated shim: batched A^{-1/p} sharded across mesh axes — now
    ``solve_many(A, cfg, op="inverse_pth_root", devices=(mesh, axes))``."""
    _deprecated("sharded_inverse_roots")
    cfg = _legacy_config(config, eigh_kw)
    return solve_many(
        A_batch, cfg, op="inverse_pth_root", p=p, eps=eps,
        devices=(mesh, tuple(axes)),
    )
