"""Fused first-stage tridiagonalization: fused-vs-unfused parity and the
``tridiag`` knob's plumbing.

The parity contract this file pins (DESIGN.md §"Fused first stage"):

* on the **jnp** backend the fused generation is the SAME XLA program as
  the unfused oracle (band reduction) plus the bitwise-equivalent
  slice-write chase executor — so BandReflectors, the ChaseLog, and full
  eigh outputs (eigenvalues AND eigenvectors, full and partial spectrum)
  must match **bit for bit**;
* on the **pallas** backend the fused kernels accumulate in a different
  order, so parity is entrywise-close + spectrum-tight, the same standard
  ``test_kernels`` applies to the standalone kernels.

Plus: StageSchedule invariants, ragged last-block and prime-n fallback,
plan-cache keying/no-retrace on the knob, and the kernels.limits env
overrides.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.backend import registry
from repro.core import band_reduce, band_to_tridiag, extract_tridiag
from repro.core.band_reduction import build_stage_schedule
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.limits import limit
from repro.solver import EvdConfig, by_count, plan, trace_count
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
    for mode in ("fused", "unfused"):
        _, refl = band_reduce(A, b, nb, return_reflectors=True, mode=mode)
        assert refl.blocks == build_stage_schedule(n, b, nb).blocks


# ------------------------------------------- bit-level parity (jnp backend)
def test_fused_unfused_bitwise_reflectors_and_log_jnp(rng):
    n, b, nb = 32, 4, 8
    A = jnp.asarray(random_symmetric(rng, n))
    with registry.use_backend("jnp"):
        Bf, rf = band_reduce(A, b, nb, return_reflectors=True, merge_ts=True,
                             mode="fused")
        Bu, ru = band_reduce(A, b, nb, return_reflectors=True, merge_ts=True,
                             mode="unfused")
        _bitwise(Bf, Bu)
        _bitwise(rf.V, ru.V)
        _bitwise(rf.T, ru.T)
        assert rf.blocks == ru.blocks and rf.b == ru.b
        for tf, tu in zip(rf.Tm, ru.Tm):
            _bitwise(tf, tu)

        Tf, lf = band_to_tridiag(Bf, b, return_log=True, mode="fused")
        Tu, lu = band_to_tridiag(Bu, b, return_log=True, mode="unfused")
        _bitwise(Tf, Tu)
        assert (lf.n, lf.b) == (lu.n, lu.b)
        _bitwise(lf.vs, lu.vs)
        _bitwise(lf.taus, lu.taus)
        _bitwise(lf.row0, lu.row0)


def test_eigh_bitwise_fused_vs_unfused_jnp(rng):
    n = 24
    A = jnp.asarray(random_symmetric(rng, n))
    cf = EvdConfig(b=4, nb=8, backend="jnp", tridiag="fused")
    cu = EvdConfig(b=4, nb=8, backend="jnp", tridiag="unfused")
    wf, Vf = plan(n, jnp.float32, cf)(A)
    wu, Vu = plan(n, jnp.float32, cu)(A)
    _bitwise(wf, wu)
    _bitwise(Vf, Vu)
    # partial spectrum: the knob only touches the first stage, so the
    # top-k eigenpairs inherit the same bit-level parity.
    wfp, Vfp = plan(n, jnp.float32, cf.replace(spectrum=by_count(5)))(A)
    wup, Vup = plan(n, jnp.float32, cu.replace(spectrum=by_count(5)))(A)
    assert Vfp.shape == (n, 5)
    _bitwise(wfp, wup)
    _bitwise(Vfp, Vup)


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
    n, b = 24, 4
    A = jnp.asarray(random_symmetric(rng, n))
    Bband = band_reduce(A, b, 8, mode="unfused")
    T_ref, l_ref = kref.bulge_wavefront_ref(Bband, b, return_log=True)

    T_jnp, l_jnp = registry.resolve("bulge_wavefront", "jnp")(
        Bband, b, return_log=True
    )
    _bitwise(T_jnp, T_ref)
    _bitwise(l_jnp.vs, l_ref.vs)
    _bitwise(l_jnp.taus, l_ref.taus)
    _bitwise(l_jnp.row0, l_ref.row0)

    T_pal = registry.resolve("bulge_wavefront", "pallas")(Bband, b)
    d_ref, e_ref = (np.asarray(x) for x in extract_tridiag(T_ref))
    d_pal, e_pal = (np.asarray(x) for x in extract_tridiag(T_pal))
    scale = max(np.abs(d_ref).max(), 1.0)
    np.testing.assert_allclose(d_pal, d_ref, atol=5e-3 * scale)
    np.testing.assert_allclose(e_pal, e_ref, atol=5e-3 * scale)
    w_ref = np.linalg.eigvalsh(np.asarray(T_ref))
    w_pal = np.linalg.eigvalsh(np.asarray(T_pal))
    np.testing.assert_allclose(w_pal, w_ref, atol=2e-4 * scale)


# ------------------------------------------------- full pipeline vs scipy
@pytest.mark.parametrize("mode", ["fused", "unfused"])
def test_eigh_full_and_partial_vs_numpy(rng, mode):
    n = 24
    A0 = random_symmetric(rng, n)
    A = jnp.asarray(A0)
    w_ref, V_ref = np.linalg.eigh(A0)
    scale = np.abs(w_ref).max()

    cfg = EvdConfig(b=4, nb=8, tridiag=mode)
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
    for mode in ("fused", "unfused"):
        Bband = band_reduce(A, b, nb, mode=mode)
        T = band_to_tridiag(Bband, b, mode=mode)
        w = np.linalg.eigvalsh(np.asarray(T))
        np.testing.assert_allclose(w, w_ref, atol=1e-3 * scale)


def test_prime_n_falls_back_to_direct(rng):
    # 29 is prime: blocking collapses to b=1 and the plan records the
    # degradation; the tridiag knob must ride along without breaking it.
    pl = plan(29, jnp.float32, EvdConfig(tridiag="fused"))
    assert pl.fallback_reason is not None
    A0 = random_symmetric(rng, 29)
    w, V = pl(jnp.asarray(A0))
    w_ref = np.linalg.eigvalsh(A0)
    np.testing.assert_allclose(np.asarray(w), w_ref, atol=1e-3 * np.abs(w_ref).max())


# ------------------------------------------------------ plan-cache plumbing
def test_tridiag_knob_resolution_and_cache(monkeypatch):
    monkeypatch.delenv("REPRO_TRIDIAG", raising=False)
    cfg = EvdConfig(b=4, nb=8)
    p_def = plan(28, jnp.float32, cfg)
    assert p_def.tridiag == "fused"
    assert "tridiag=fused" in p_def.describe()
    assert plan(28, jnp.float32, cfg) is p_def  # cache hit

    monkeypatch.setenv("REPRO_TRIDIAG", "unfused")
    p_env = plan(28, jnp.float32, cfg)
    assert p_env.tridiag == "unfused"
    assert p_env is not p_def  # the env knob is part of the cache key

    monkeypatch.setenv("REPRO_TRIDIAG", "bogus")
    with pytest.raises(ValueError):
        plan(28, jnp.float32, EvdConfig(b=4, nb=8, backtransform="scan"))
    with pytest.raises(ValueError):
        EvdConfig(tridiag="bogus")


def test_no_retrace_on_tridiag_knob(rng):
    A = jnp.asarray(random_symmetric(rng, 28))
    for mode in ("fused", "unfused"):
        p = plan(28, jnp.float32, EvdConfig(b=4, nb=8, tridiag=mode))
        before = trace_count(p)
        p(A)
        traced = trace_count(p)
        p(A)
        p(A)
        assert trace_count(p) == traced  # executions after the first don't trace
        assert traced - before <= 1


# ------------------------------------------------------------ limits knobs
def test_limits_env_override(monkeypatch, rng):
    assert limit("FUSED_PANEL_INTERPRET_MAX_M") == 96
    with pytest.raises(KeyError):
        limit("NO_SUCH_LIMIT")
    monkeypatch.setenv("REPRO_FUSED_PANEL_INTERPRET_MAX_M", "0")
    assert limit("FUSED_PANEL_INTERPRET_MAX_M") == 0
    assert not kops.fused_uses_kernel(24, 8, 4)
    # Over the ceiling the op degrades to the unfused composition — which on
    # the jnp backend is bit-identical to the reference.
    Bv = jnp.asarray(random_symmetric(rng, 24))
    with registry.use_backend("jnp"):
        out = kops.fused_panel_update(Bv, 4, 8)
        ref_out = kref.fused_panel_update_ref(Bv, 4, 8)
    for got, want in zip(out, ref_out):
        _bitwise(got, want)


def test_mode_validation_errors(rng):
    A = jnp.asarray(random_symmetric(rng, 16))
    with pytest.raises(ValueError):
        band_reduce(A, 4, 8, mode="sideways")
    # Injected phases own the composition: fused mode must refuse them.
    with pytest.raises(ValueError):
        band_reduce(A, 4, 8, mode="fused", panel_method="householder")


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
        (1536, 256, False),  # over the VMEM budget: unfused composition
        (256, 248, False),   # w not lane-aligned on the TPU: unfused
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
