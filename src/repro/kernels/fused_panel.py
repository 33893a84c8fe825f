"""Pallas TPU kernel: FUSED panel factorization + two-sided trailing update.

This is the paper's central move (§5.1/§5.2) taken to its structural limit:
the first stage's per-block work — q = w/b compensated panel QRs, their
compact-WY (V, T) factors, the Z = A·V·T intermediates, and the rank-2w
two-sided SYR2K trailing update — executes as ONE kernel invocation, with
the panel, V (the paper's W/Y), Z, and T factors VMEM-resident across the
entire trailing sweep.  Run as separate panel QRs and a syr2k
(``repro.core.band_reduction._reduce_block``), the block step writes V/Z/T
back to HBM after every panel and re-reads them for the trailing syr2k;
here they are produced and consumed without ever leaving VMEM — the "convert memory-bound
to compute-bound" conversion applied to the whole block step, not just the
trailing GEMM.

Structure (mirrors ``repro.kernels.syr2k`` for the trailing sweep):

* grid = (T,) over the LOWER-TRIANGULAR trailing output tiles only, via the
  same scalar-prefetched tile-index scheme as ``syr2k_lower_pallas``
  (diagonal tiles are computed once, upper tiles are reconstructed by the
  ops-layer symmetrization — half the FLOPs and output traffic).
* grid step 0 runs the whole panel phase: the q-panel ``latrd``-style
  compensated recurrence of ``repro.core.band_reduction._reduce_block`` as
  a loop over panels, each panel QR inlined via
  ``repro.kernels.panel.panel_qr_rows``.  The phase works on TRANSPOSED
  factors — a b-wide panel is a (b, m) row slab, lane-dense and at a row
  offset that is a multiple of b — in zero-initialised (w, m) scratch
  buffers, so every product is a full-width MXU GEMM against them and rows
  of the (symmetric) view are read in place of its columns.  At the end of
  the phase the factors are transposed once into the resident V and F
  output blocks and a Z scratch buffer, where every later grid step reads
  them back at zero HBM cost.
* grid steps t >= 0 each compute one (bm, bm) trailing tile
  ``C_ij - Z_i V_j^T - V_i Z_j^T`` as two MXU GEMMs with k = w.

The grid dimension is sequential ("arbitrary"): step 0 must complete the
panel phase before any tile consumes the factors, and the resident factor
blocks persist across steps exactly like the syr2k accumulator tile.

On the TPU the panel rows must sit at multiples of 8 (``b % 8 == 0``) and
the trailing tiles at multiples of 128 lanes (``w`` and the padded side a
multiple of 128): :func:`fused_tpu_aligned`.  VMEM is counted by
:func:`fused_vmem_bytes`; where either check fails the ops wrapper falls
back to ``_reduce_block``'s panel QRs + syr2k, which stream and have no
residency limit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .limits import tile_bytes, vmem_limit_bytes
from .panel import panel_qr_rows
from .syr2k import lower_tile_indices

__all__ = ["fused_panel_update_pallas", "fused_vmem_bytes", "fused_tpu_aligned"]


def _padded(m: int, w: int, bm: int):
    """(bm, mt_pad, m_pad): the trailing tile and padded sides."""
    mt = m - w
    bm = min(bm, max(8, 1 << (mt - 1).bit_length()))
    mt_pad = -(-mt // bm) * bm
    return bm, mt_pad, w + mt_pad


def fused_tpu_aligned(m: int, w: int, b: int, bm: int = 128) -> bool:
    """Whether the block's panel rows and trailing tiles are TPU-aligned."""
    _, _, m_pad = _padded(m, w, bm)
    return b % 8 == 0 and w % 128 == 0 and m_pad % 128 == 0


def fused_vmem_bytes(m: int, w: int, b: int, bm: int = 128) -> int:
    """VMEM bytes held by :func:`fused_panel_update_pallas` on an (m, m) view:
    the resident view, the V/F output blocks and the Ts block (single-
    buffered), the double-buffered output tile, four (m, w) scratch buffers
    (V^T, Z^T, F^T and Z), and the operands the panel phase loads whole —
    the view and about six (w, m) factor values, which Mosaic keeps in VMEM
    (measured by compiling for a v5e at m = 1024 and 1280)."""
    bm, _, m_pad = _padded(m, w, bm)
    view = tile_bytes((m_pad, m_pad))
    factor = tile_bytes((m_pad, w))
    return (
        2 * view
        + 2 * factor
        + tile_bytes((w // b, b, b))
        + tile_bytes((bm, bm), buffers=2)
        + 4 * factor
        + 6 * factor
    )


def _dot(a, b):
    return jnp.dot(
        a, b, precision=lax.Precision.HIGHEST, preferred_element_type=jnp.float32
    )


def _dot_nt(a, b):  # a @ b.T
    return lax.dot_general(
        a, b, (((1,), (1,)), ((), ())),
        precision=lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )


def _fused_kernel(
    ti_ref, tj_ref, bv_ref, c_ref, v_ref, f_ref, t_ref,
    vt_ref, zt_ref, ft_ref, z_ref,
    *, m: int, w: int, b: int, bm: int,
):
    t = pl.program_id(0)
    dtype = bv_ref.dtype
    q = w // b

    @pl.when(t == 0)
    def _panel_phase():
        # The compensated q-panel recurrence of _reduce_block, transposed:
        # row slabs [c0, c0 + b) of V^T, Z^T, F^T hold panel jp.  Rows of
        # V^T / Z^T past the current panel are still zero, so full-width
        # products equal the prefix products of _reduce_block.
        vt_ref[...] = jnp.zeros(vt_ref.shape, dtype)
        zt_ref[...] = jnp.zeros(zt_ref.shape, dtype)
        lanes = lax.broadcasted_iota(jnp.int32, (1, m), 1)
        rows = lax.broadcasted_iota(jnp.int32, (b, 1), 0)

        def panel(jp, carry):
            c0 = pl.multiple_of(jp * b, b)
            r0 = c0 + b  # elimination starts below this row
            VT = vt_ref[...]
            ZT = zt_ref[...]
            # --- compensated panel: P^T = (B - Z V^T - V Z^T)[c0:c0+b, :] ---
            # V[c0:c0+b, :] and Z[c0:c0+b, :] come out of the transposed
            # buffers through a 0/1 row selector (exact at HIGHEST).
            E = (lanes == c0 + rows).astype(dtype)
            Vrow = _dot_nt(E, VT)
            Zrow = _dot_nt(E, ZT)
            PT = bv_ref[pl.ds(c0, b), :] - _dot(Vrow, ZT) - _dot(Zrow, VT)
            # --- panel QR of rows [r0, m), fully in VMEM -------------------
            # LAPACK signs: the _reduce_block oracle factors with
            # panel_qr_geqrf, and parity needs matching reflector signs.
            VhT, T_j, _taus, FT = panel_qr_rows(PT, b, p0=r0, lapack_sign=True)
            # --- exact final column values (band structure) ----------------
            in_band = lanes >= c0 + rows - b
            ft_ref[pl.ds(c0, b), :] = jnp.where(in_band, FT, 0.0)
            # --- Z_j^T from M^T = Vhat^T (B - Z V^T - V Z^T) ---------------
            MT = (
                _dot(VhT, bv_ref[...])
                - _dot(_dot_nt(VhT, VT), ZT)
                - _dot(_dot_nt(VhT, ZT), VT)
            )
            MTT = lax.dot_general(  # (M T_j)^T = T_j^T M^T
                T_j, MT, (((0,), (0,)), ((), ())),
                precision=lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
            )
            ZjT = MTT - 0.5 * _dot(_dot(_dot_nt(MTT, VhT), T_j), VhT)
            vt_ref[pl.ds(c0, b), :] = VhT
            zt_ref[pl.ds(c0, b), :] = ZjT.astype(dtype)
            t_ref[jp] = T_j
            return carry

        lax.fori_loop(0, q, panel, 0)
        # Factors stay resident: V/F are constant-index output blocks, Z is
        # VMEM scratch — the trailing sweep below never touches HBM for them.
        v_ref[...] = vt_ref[...].T
        f_ref[...] = ft_ref[...].T
        z_ref[...] = zt_ref[...].T

    # --- one lower-triangular trailing tile per grid step -------------------
    ri = pl.multiple_of(w + ti_ref[t] * bm, min(bm, 128))
    rj = pl.multiple_of(w + tj_ref[t] * bm, min(bm, 128))
    C = bv_ref[pl.ds(ri, bm), pl.ds(rj, bm)]
    Zi = z_ref[pl.ds(ri, bm), :]
    Vi = v_ref[pl.ds(ri, bm), :]
    Zj = z_ref[pl.ds(rj, bm), :]
    Vj = v_ref[pl.ds(rj, bm), :]
    acc = jnp.dot(Zi, Vj.T, preferred_element_type=jnp.float32) + jnp.dot(
        Vi, Zj.T, preferred_element_type=jnp.float32
    )
    c_ref[...] = C - acc.astype(dtype)


@functools.partial(jax.jit, static_argnames=("b", "w", "bm", "interpret"))
def fused_panel_update_pallas(
    Bv: jax.Array, *, b: int, w: int, bm: int = 128, interpret: bool = False
):
    """Fused block step on a trailing view ``Bv`` (m, m).

    Factors the first ``w`` columns (q = w/b panels) to bandwidth ``b`` and
    applies the rank-2w trailing update, all in one kernel.  Returns the raw
    kernel outputs ``(C_low, V, F, Ts)``:

    * ``C_low`` (mt_pad, mt_pad): lower tiles of the updated trailing
      submatrix (upper tiles undefined, like ``syr2k_lower_pallas``);
    * ``V``     (m_pad, w): the block's Householder panels;
    * ``F``     (m_pad, w): exact final (banded) values of the factored
      columns;
    * ``Ts``    (q, b, b): per-panel compact-WY T factors.

    The jit-facing assembly (symmetrization, write-back into the view) lives
    in ``repro.kernels.ops.fused_panel_update``; padding rows are zero.
    """
    m = Bv.shape[0]
    if w % b != 0 or w >= m or m - w < b:
        raise ValueError(f"need w % b == 0 and b <= m - w, got m={m} w={w} b={b}")
    q = w // b
    bm_req = bm
    bm, mt_pad, m_pad = _padded(m, w, bm)
    dtype = Bv.dtype

    Bp = jnp.zeros((m_pad, m_pad), dtype).at[:m, :m].set(Bv)
    nmt = mt_pad // bm
    ti, tj = lower_tile_indices(nmt)
    T = len(ti)

    def const2(t, ti, tj):
        return (0, 0)

    def resident(shape, index_map):
        return pl.BlockSpec(shape, index_map, pipeline_mode=pl.Buffered(1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T,),
        in_specs=[resident((m_pad, m_pad), const2)],
        out_specs=[
            pl.BlockSpec((bm, bm), lambda t, ti, tj: (ti[t], tj[t])),
            resident((m_pad, w), const2),
            resident((m_pad, w), const2),
            resident((q, b, b), lambda t, ti, tj: (0, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((w, m_pad), dtype),  # V^T
            pltpu.VMEM((w, m_pad), dtype),  # Z^T
            pltpu.VMEM((w, m_pad), dtype),  # F^T
            pltpu.VMEM((m_pad, w), dtype),  # Z
        ],
    )
    kernel = functools.partial(_fused_kernel, m=m_pad, w=w, b=b, bm=bm)
    C_low, V, F, Ts = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((mt_pad, mt_pad), dtype),
            jax.ShapeDtypeStruct((m_pad, w), dtype),
            jax.ShapeDtypeStruct((m_pad, w), dtype),
            jax.ShapeDtypeStruct((q, b, b), dtype),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # Above the v5e's 16 MiB default scoped VMEM from m ~ 1000 on.
            vmem_limit_bytes=vmem_limit_bytes(fused_vmem_bytes(m, w, b, bm_req)),
        ),
        interpret=interpret,
        name="fused_panel_update",
    )(jnp.asarray(ti), jnp.asarray(tj), Bp)
    return C_low, V, F, Ts
