"""The first stage: StageSchedule, the fused block step and the chase op.

The parity contract this file pins (DESIGN.md §"Fused first stage"):

* on the **jnp** backend ``band_reduce`` is, bit for bit, a loop of
  ``_reduce_block`` over the StageSchedule, and ``band_to_tridiag`` is the
  XLA wavefront executor;
* on the **pallas** backend the fused kernels accumulate in a different
  order, so parity is entrywise-close + spectrum-tight, the same standard
  ``test_kernels`` applies to the standalone kernels.

Plus: StageSchedule invariants, ragged last-block and prime-n fallback,
and the kernels.limits env overrides.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.backend import registry
from repro.core import (
    band_reduce,
    band_to_tridiag,
    chase_sequential,
    chase_wavefront,
    extract_tridiag,
    merge_band_reflectors,
)
from repro.core.band_reduction import BandReflectors, _reduce_block, build_stage_schedule
from repro.core.panel_qr import panel_qr_geqrf
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.limits import limit
from repro.solver import EvdConfig, by_count, plan
from conftest import random_symmetric


def _bitwise(x, y):
    assert np.array_equal(np.asarray(x), np.asarray(y)), "bitwise parity broken"


# ------------------------------------------------------------ StageSchedule
def test_stage_schedule_invariants():
    for n, b, nb in [(32, 4, 8), (48, 8, 16), (40, 4, 16), (24, 4, 4), (64, 8, 64)]:
        s = build_stage_schedule(n, b, nb)
        ci = 0
        p = 0
        for e in s.entries:
            assert e.ci == ci and e.panel0 == p
            assert e.m == n - e.ci
            assert e.w == min(nb, e.m - b) and e.w % b == 0
            assert b <= e.m - e.w  # fused-kernel / _reduce_block precondition
            assert e.q == e.w // b
            ci += e.w
            p += e.q
        assert n - ci <= b  # loop stops at a trailing view of side <= b
        assert s.num_panels == p
        assert s.blocks == tuple((e.panel0, e.q) for e in s.entries)


def test_schedule_matches_reflector_blocks(rng):
    n, b, nb = 32, 4, 16
    A = jnp.asarray(random_symmetric(rng, n))
    for backend in ("pallas", "jnp"):
        with registry.use_backend(backend):
            _, refl = band_reduce(A, b, nb, return_reflectors=True)
        assert refl.blocks == build_stage_schedule(n, b, nb).blocks


# ------------------------------------------- bit-level parity (jnp backend)
def test_fused_unfused_bitwise_reflectors_and_log_jnp(rng):
    # On jnp, band_reduce is a loop of _reduce_block over the schedule, and
    # band_to_tridiag is the XLA wavefront executor: the same bits.
    n, b, nb = 32, 4, 8
    A = jnp.asarray(random_symmetric(rng, n))
    with registry.use_backend("jnp"):
        Bf, rf = band_reduce(A, b, nb, return_reflectors=True, merge_ts=True)
        Tf, lf = band_to_tridiag(Bf, b, return_log=True)

    sched = build_stage_schedule(n, b, nb)
    Bu = A
    Vu = jnp.zeros((n, sched.num_panels * b), A.dtype)
    Tus = []
    for e in sched.entries:
        view, Vbuf, Ts = _reduce_block(
            Bu[e.ci :, e.ci :], b, e.w, panel_qr_geqrf, kref.trailing_update_ref
        )
        Bu = Bu.at[e.ci :, e.ci :].set(view)
        Vu = Vu.at[e.ci :, e.panel0 * b : (e.panel0 + e.q) * b].set(Vbuf)
        Tus.append(Ts)
    ru = merge_band_reflectors(
        BandReflectors(V=Vu, T=jnp.concatenate(Tus), b=b, blocks=sched.blocks)
    )
    _bitwise(Bf, Bu)
    _bitwise(rf.V, ru.V)
    _bitwise(rf.T, ru.T)
    assert rf.blocks == ru.blocks and rf.b == ru.b
    for tf, tu in zip(rf.Tm, ru.Tm):
        _bitwise(tf, tu)

    Tu, lu = chase_wavefront(Bu, b, return_log=True)
    _bitwise(Tf, Tu)
    assert (lf.n, lf.b) == (lu.n, lu.b)
    _bitwise(lf.vs, lu.vs)
    _bitwise(lf.taus, lu.taus)
    _bitwise(lf.row0, lu.row0)


# --------------------------------------- registry parity (both CI backends)
def test_registry_fused_panel_update_parity(rng):
    m, b, w = 24, 4, 8
    Bv = jnp.asarray(random_symmetric(rng, m))
    ref_out = kref.fused_panel_update_ref(Bv, b, w)
    out_jnp = registry.resolve("fused_panel_update", "jnp")(Bv, b, w)
    for got, want in zip(out_jnp, ref_out):
        _bitwise(got, want)
    out_pal = registry.resolve("fused_panel_update", "pallas")(Bv, b, w)
    for got, want in zip(out_pal, ref_out):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=5e-3, rtol=1e-3
        )


def test_registry_bulge_wavefront_parity(rng):
    # The jnp op is the reference for the kernel; chase_sequential, the
    # paper's serial order, is the reference for the schedule.
    n, b = 24, 4
    A = jnp.asarray(random_symmetric(rng, n))
    with registry.use_backend("jnp"):
        Bband = band_reduce(A, b, 8)
    T_ref, l_ref = registry.resolve("bulge_wavefront", "jnp")(
        Bband, b, return_log=True
    )
    assert l_ref.vs.shape[-1] == b and (l_ref.n, l_ref.b) == (n, b)
    scale = max(float(jnp.abs(Bband).max()), 1.0)
    w_seq = np.linalg.eigvalsh(np.asarray(chase_sequential(Bband, b)))
    w_ref = np.linalg.eigvalsh(np.asarray(T_ref))
    np.testing.assert_allclose(w_ref, w_seq, atol=2e-4 * scale)

    T_pal = registry.resolve("bulge_wavefront", "pallas")(Bband, b)
    d_ref, e_ref = (np.asarray(x) for x in extract_tridiag(T_ref))
    d_pal, e_pal = (np.asarray(x) for x in extract_tridiag(T_pal))
    np.testing.assert_allclose(d_pal, d_ref, atol=5e-3 * scale)
    np.testing.assert_allclose(e_pal, e_ref, atol=5e-3 * scale)
    w_pal = np.linalg.eigvalsh(np.asarray(T_pal))
    np.testing.assert_allclose(w_pal, w_ref, atol=2e-4 * scale)


# ------------------------------------------------- full pipeline vs scipy
@pytest.mark.parametrize("backend", ["pallas", "jnp"])
def test_eigh_full_and_partial_vs_numpy(rng, backend):
    n = 24
    A0 = random_symmetric(rng, n)
    A = jnp.asarray(A0)
    w_ref, V_ref = np.linalg.eigh(A0)
    scale = np.abs(w_ref).max()

    cfg = EvdConfig(b=4, nb=8, backend=backend)
    w, V = plan(n, jnp.float32, cfg)(A)
    w, V = np.asarray(w), np.asarray(V)
    np.testing.assert_allclose(w, w_ref, atol=1e-3 * scale)
    resid = np.abs(A0 @ V - V * w[None, :]).max()
    assert resid < 1e-2 * scale
    ortho = np.abs(V.T @ V - np.eye(n)).max()
    assert ortho < 1e-3

    wp, Vp = plan(n, jnp.float32, cfg.replace(spectrum=by_count(5)))(A)
    wp, Vp = np.asarray(wp), np.asarray(Vp)
    np.testing.assert_allclose(wp, w_ref[-5:], atol=1e-3 * scale)
    resid = np.abs(A0 @ Vp - Vp * wp[None, :]).max()
    assert resid < 1e-2 * scale


def test_ragged_last_block_both_modes(rng):
    # n=40, nb=16 schedules blocks w=16,16,4 — a ragged final entry.
    n, b, nb = 40, 4, 16
    sched = build_stage_schedule(n, b, nb)
    assert sched.entries[-1].w < nb
    A0 = random_symmetric(rng, n)
    A = jnp.asarray(A0)
    w_ref = np.linalg.eigvalsh(A0)
    scale = np.abs(w_ref).max()
    for backend in ("pallas", "jnp"):
        with registry.use_backend(backend):
            Bband = band_reduce(A, b, nb)
            T = band_to_tridiag(Bband, b)
        w = np.linalg.eigvalsh(np.asarray(T))
        np.testing.assert_allclose(w, w_ref, atol=1e-3 * scale)


def test_prime_n_falls_back_to_direct(rng):
    # 29 is prime: blocking collapses to b=1 and the plan records the
    # degradation.
    pl = plan(29, jnp.float32, EvdConfig())
    assert pl.fallback_reason is not None
    A0 = random_symmetric(rng, 29)
    w, V = pl(jnp.asarray(A0))
    w_ref = np.linalg.eigvalsh(A0)
    np.testing.assert_allclose(np.asarray(w), w_ref, atol=1e-3 * np.abs(w_ref).max())


# ------------------------------------------------------------ limits knobs
def test_limits_env_override(monkeypatch, rng):
    assert limit("FUSED_PANEL_INTERPRET_MAX_M") == 96
    with pytest.raises(KeyError):
        limit("NO_SUCH_LIMIT")
    monkeypatch.setenv("REPRO_FUSED_PANEL_INTERPRET_MAX_M", "0")
    assert limit("FUSED_PANEL_INTERPRET_MAX_M") == 0
    assert not kops.fused_uses_kernel(24, 8, 4)
    # Over the ceiling the op degrades to _reduce_block — which on the jnp
    # backend is bit-identical to the reference.
    Bv = jnp.asarray(random_symmetric(rng, 24))
    with registry.use_backend("jnp"):
        out = kops.fused_panel_update(Bv, 4, 8)
        ref_out = kref.fused_panel_update_ref(Bv, 4, 8)
    for got, want in zip(out, ref_out):
        _bitwise(got, want)


# ------------------------------------------------------------- VMEM budget
def test_tile_bytes_pads_to_vreg_tiles():
    from repro.kernels.limits import tile_bytes

    assert tile_bytes((1, 8)) == 8 * 128 * 4
    assert tile_bytes((3, 10, 130), buffers=2) == 2 * 3 * 16 * 256 * 4
    assert tile_bytes((256,)) == 8 * 256 * 4


@pytest.mark.parametrize(
    "m,w,fused",
    [
        (1280, 256, True),   # the largest trailing view inside the budget
        (1536, 256, False),  # over the VMEM budget: _reduce_block
        (256, 248, False),   # w not lane-aligned on the TPU: _reduce_block
    ],
)
def test_fused_dispatch_counts_vmem_bytes(m, w, fused):
    from repro.kernels.fused_panel import fused_vmem_bytes
    from repro.kernels.limits import fits_vmem, limit, vmem_limit_bytes

    assert kops.fused_uses_kernel(m, w, 8, bm=128, interpret=False) is fused
    nbytes = fused_vmem_bytes(m, w, 8, 128)
    # The compiler is given room for every counted byte.
    assert vmem_limit_bytes(nbytes) > nbytes
    if w % 128 == 0:
        assert fits_vmem(nbytes) is fused
        assert (nbytes <= limit("VMEM_BUDGET_BYTES")) is fused


def test_q2_and_bulge_dispatch_count_vmem_bytes():
    # The Q2 back-transform tiles columns, so a 4096 panel stays on the
    # kernel; the bulge kernel keeps the whole padded matrix and does not.
    assert kops.backtransform_uses_kernel(1024, 1024, 8, group=16, interpret=False)
    assert kops.backtransform_uses_kernel(4096, 4096, 8, group=16, interpret=False)
    assert kops._bt_column_block(4096, 4096, 8, 16) == 512
    assert kops.bulge_uses_kernel(1024, 8, return_log=True, interpret=False)
    assert not kops.bulge_uses_kernel(4096, 8, interpret=False)
