"""Pallas TPU kernel: symmetric rank-2k update (the paper's §5.2).

    C_lower  <-  C_lower + alpha * tril(A @ B^T + B @ A^T)

The paper replaces cuBLAS syr2k with a recursive decomposition into batched
diagonal GEMMs + progressively larger off-diagonal GEMMs (Algorithm 3) so
the dominant work runs as large square GEMMs.  On TPU the same effect is
structural: a Pallas grid that enumerates ONLY the lower-triangular output
tiles (via a scalar-prefetched tile index), with each tile computed as a
k-strip MXU matmul accumulated in a VMEM-resident block.  Compared to a
plain GEMM-based syr2k this halves both FLOPs and output traffic — the
paper's Table 1 / Figure 8 gap — without the recursion's launch tree.

Grid: ``(T, K)`` with ``T`` the number of lower tiles (parallel, Megacore-
friendly) and ``K`` the k-strips (arbitrary/sequential: the output block is
revisited and accumulated in VMEM).  Tile sides default to 256 and must be
multiples of the MXU lane width (128) on real hardware.

The jit-facing wrapper (padding, symmetrization, fused C input) lives in
``repro.kernels.ops``; the jnp oracle in ``repro.kernels.ref``.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .limits import tile_bytes, vmem_limit_bytes

__all__ = ["syr2k_lower_pallas", "lower_tile_indices", "syr2k_vmem_bytes"]


def lower_tile_indices(n_tiles: int) -> tuple[np.ndarray, np.ndarray]:
    """Row/col indices of lower-triangular tiles, diagonal-major order.

    Ordered so that consecutive grid steps reuse the A row-strip already in
    VMEM where possible (row-major over the triangle).
    """
    ii, jj = [], []
    for i in range(n_tiles):
        for j in range(i + 1):
            ii.append(i)
            jj.append(j)
    return np.asarray(ii, np.int32), np.asarray(jj, np.int32)


def syr2k_vmem_bytes(bm: int, bk: int, itemsize: int = 4) -> int:
    """VMEM bytes held by :func:`syr2k_lower_pallas`: four (bm, bk) operand
    strips and the C input and output tiles, all double-buffered."""
    return tile_bytes((bm, bk), itemsize, buffers=2) * 4 + tile_bytes(
        (bm, bm), itemsize, buffers=2
    ) * 2


def _syr2k_kernel(i_ref, j_ref, a_i, b_j, b_i, a_j, c_in, c_out, *, alpha, nk):
    """One (bm, bn) lower tile, one k-strip.

    a_i/b_i: (bm, bk) row strips;  a_j/b_j: (bn, bk) row strips.
    c_out is revisited across the K grid dimension (accumulate in VMEM).
    """
    kk = pl.program_id(1)

    @pl.when(kk == 0)
    def _init():
        c_out[...] = c_in[...]

    acc = jnp.dot(
        a_i[...], b_j[...].T, preferred_element_type=jnp.float32
    ) + jnp.dot(b_i[...], a_j[...].T, preferred_element_type=jnp.float32)
    c_out[...] += (alpha * acc).astype(c_out.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bk", "alpha", "interpret"),
)
def syr2k_lower_pallas(
    A: jax.Array,
    B: jax.Array,
    C: jax.Array,
    *,
    alpha: float = 1.0,
    bm: int = 256,
    bk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Lower-triangular tiles of ``C + alpha (A B^T + B A^T)``.

    A, B: (n, k); C: (n, n).  ``n % bm == 0`` and ``k % bk == 0`` (the ops
    wrapper pads).  Tiles strictly above the diagonal are returned as zeros.
    """
    n, k = A.shape
    assert B.shape == (n, k) and C.shape == (n, n)
    assert n % bm == 0 and k % bk == 0, (n, k, bm, bk)
    nm, nk = n // bm, k // bk
    ti, tj = lower_tile_indices(nm)
    T = len(ti)

    def a_i_map(t, kk, ti, tj):
        return ti[t], kk

    def b_j_map(t, kk, ti, tj):
        return tj[t], kk

    def c_map(t, kk, ti, tj):
        return ti[t], tj[t]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), a_i_map),  # A_i
            pl.BlockSpec((bm, bk), b_j_map),  # B_j   (bn == bm)
            pl.BlockSpec((bm, bk), a_i_map),  # B_i
            pl.BlockSpec((bm, bk), b_j_map),  # A_j
            pl.BlockSpec((bm, bm), c_map),    # C_in
        ],
        out_specs=pl.BlockSpec((bm, bm), c_map),
    )

    kernel = functools.partial(_syr2k_kernel, alpha=alpha, nk=nk)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, n), C.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes(
                syr2k_vmem_bytes(bm, bk, jnp.dtype(C.dtype).itemsize)
            ),
        ),
        interpret=interpret,
        name="syr2k_lower",
    )(jnp.asarray(ti), jnp.asarray(tj), A, B, B, A, C)
    # Tiles strictly above the diagonal are never written (undefined); the
    # ops-layer symmetrization consumes only the lower triangle.
    return out
