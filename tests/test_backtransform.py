"""Blocked compact-WY back-transform: parity against the scan oracles.

The blocked path (``repro.core.backtransform``) must match the per-reflector
appliers to float rounding in every configuration the plan API can reach:
full and partial spectra, transposed application, ragged reflector tails
(K not a multiple of the WY group G), both chase logs, both registry
backends, and vmapped execution through a ``BatchPlan``.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.backend import registry
from repro.core import (
    apply_q2,
    apply_q2_blocked,
    apply_q_left,
    apply_q_left_blocked,
    band_reduce,
    band_to_tridiag,
    chase_sequential,
    eigvecs_inverse_iteration,
    merge_band_reflectors,
    sweep_major_log,
    tridiagonalize,
)
from repro.core.backtransform import backtransform_wy_xla, sweep_group_count
from repro.solver import EvdConfig, batch_plan, by_count, plan
from repro.solver.autotune import backtransform_group
from conftest import random_symmetric


def _band_and_log(rng, n, b, nb, sequential=False):
    A = jnp.asarray(random_symmetric(rng, n))
    B, refl = band_reduce(A, b, nb, return_reflectors=True, merge_ts=True)
    if sequential:
        T, log = chase_sequential(B, b, return_log=True)
    else:
        T, log = band_to_tridiag(B, b, return_log=True)
    return A, refl, log


def _scan_vectors(A, b, nb, w):
    """The plan's first stage, inverse iteration at ``w`` and the scan
    appliers (the ordering oracle): Q1 (Q2 V_T) reflector by reflector."""
    d, e, (_, (refl1, log2)) = tridiagonalize(A, b=b, nb=nb, return_reflectors=True)
    VT = eigvecs_inverse_iteration(d, e, w)
    return apply_q_left(refl1, apply_q2(log2, VT))


# ------------------------------------------------------------------ Q1 merge
@pytest.mark.parametrize("n,b,nb", [(32, 8, 16), (64, 8, 32), (48, 4, 16)])
def test_q1_blocked_matches_scan(rng, n, b, nb):
    _, refl, _ = _band_and_log(rng, n, b, nb)
    assert refl.Tm is not None and len(refl.Tm) == len(refl.blocks)
    X = jnp.asarray(rng.normal(size=(n, 7)).astype(np.float32))
    for transpose in (False, True):
        Y_scan = apply_q_left(refl, X, transpose=transpose)
        Y_blk = apply_q_left_blocked(refl, X, transpose=transpose)
        np.testing.assert_allclose(
            np.asarray(Y_blk), np.asarray(Y_scan), atol=2e-5
        )


def test_q1_blocked_roundtrip(rng):
    n, b, nb = 48, 8, 16
    _, refl, _ = _band_and_log(rng, n, b, nb)
    X = jnp.asarray(rng.normal(size=(n, 5)).astype(np.float32))
    Y = apply_q_left_blocked(refl, X)
    X2 = apply_q_left_blocked(refl, Y, transpose=True)
    np.testing.assert_allclose(np.asarray(X2), np.asarray(X), atol=2e-5)


def test_merge_band_reflectors_idempotent_and_validates(rng):
    n, b, nb = 32, 8, 16
    _, refl, _ = _band_and_log(rng, n, b, nb)
    assert merge_band_reflectors(refl) is refl  # already merged: no-op
    import dataclasses

    bare = dataclasses.replace(refl, Tm=None, blocks=())
    with pytest.raises(ValueError, match="no block structure"):
        merge_band_reflectors(bare)


# --------------------------------------------------------------- Q2 regroup
@pytest.mark.parametrize("chase", ["wavefront", "sequential"])
@pytest.mark.parametrize("n,b", [(32, 8), (48, 4), (40, 2)])
def test_q2_blocked_matches_scan(rng, n, b, chase):
    _, _, log = _band_and_log(rng, n, b, b, sequential=chase == "sequential")
    X = jnp.asarray(rng.normal(size=(n, 6)).astype(np.float32))
    for transpose in (False, True):
        Z_scan = apply_q2(log, X, transpose=transpose)
        Z_blk = apply_q2_blocked(log, X, transpose=transpose, backend="jnp")
        np.testing.assert_allclose(
            np.asarray(Z_blk), np.asarray(Z_scan), atol=2e-5
        )


def test_q2_blocked_ragged_group_tails(rng):
    # K = (48-3)//4 + 1 = 12 reflectors per sweep: G in {5, 7} leaves a
    # ragged tail group (12 % G != 0), G=12 is one panel, G=1 degenerates
    # to per-reflector updates — all must agree with the scan applier.
    n, b = 48, 4
    _, _, log = _band_and_log(rng, n, b, b)
    X = jnp.asarray(rng.normal(size=(n, 4)).astype(np.float32))
    Z_scan = apply_q2(log, X)
    vs, taus = sweep_major_log(log)
    K = vs.shape[1]
    assert K == 12
    for G in (1, 5, 7, 12):
        assert sweep_group_count(n, b, G) == -(-K // G)
        Z = backtransform_wy_xla(X, vs, taus, b=b, group=G)
        np.testing.assert_allclose(np.asarray(Z), np.asarray(Z_scan), atol=2e-5)


def test_q2_blocked_registry_backend_parity(rng):
    # n=32 is under the interpret-mode kernel ceiling: "pallas" runs the
    # actual Pallas kernel; "jnp" is the XLA reference.  Both via registry.
    n, b = 32, 8
    _, _, log = _band_and_log(rng, n, b, b)
    X = jnp.asarray(rng.normal(size=(n, 8)).astype(np.float32))
    Z_jnp = apply_q2_blocked(log, X, backend="jnp")
    Z_pal = apply_q2_blocked(log, X, backend="pallas")
    np.testing.assert_allclose(np.asarray(Z_pal), np.asarray(Z_jnp), atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(Z_jnp), np.asarray(apply_q2(log, X)), atol=2e-5
    )


def test_pallas_kernel_explicit_interpret_grouped(rng):
    from repro.kernels.ops import backtransform_wy

    n, b = 32, 8
    _, _, log = _band_and_log(rng, n, b, b)
    X = jnp.asarray(rng.normal(size=(n, 5)).astype(np.float32))
    vs, taus = sweep_major_log(log)
    Z_ref = backtransform_wy_xla(X, vs, taus, b=b)
    for G in (1, 3, None):
        Z = backtransform_wy(X, vs, taus, b=b, group=G, interpret=True)
        np.testing.assert_allclose(np.asarray(Z), np.asarray(Z_ref), atol=2e-5)


# ------------------------------------------------------------ plan threading
def test_plan_resolves_group_and_caches_separately():
    pb = plan(64, jnp.float32, EvdConfig(b=8, nb=32))
    p4 = plan(64, jnp.float32, EvdConfig(b=4, nb=32))
    assert pb is not p4
    assert pb.bt_group == backtransform_group(64, 8) > 0
    assert p4.bt_group == backtransform_group(64, 4) > 0
    assert f"G={pb.bt_group}" in pb.describe()
    assert plan(64, jnp.float32, EvdConfig(method="direct")).bt_group == 0


@pytest.mark.parametrize("n", [24, 64])
def test_eigh_blocked_vs_scan_parity(rng, n):
    A = jnp.asarray(random_symmetric(rng, n))
    cfg = dict(b=8, nb=min(32, n // 2))
    wb, Vb = plan(n, jnp.float32, EvdConfig(**cfg))(A)
    Vs = _scan_vectors(A, cfg["b"], cfg["nb"], wb)
    np.testing.assert_allclose(np.asarray(Vb), np.asarray(Vs), atol=1e-4)
    # Eigen-residual + orthogonality on the blocked default.
    scale = max(float(jnp.abs(wb).max()), 1.0)
    resid = jnp.abs(A @ Vb - Vb * wb[None, :]).max()
    assert float(resid) < 1e-4 * scale
    orth = jnp.abs(Vb.T @ Vb - jnp.eye(n)).max()
    assert float(orth) < 1e-4


def test_partial_spectrum_blocked(rng):
    n, k = 64, 6
    A = jnp.asarray(random_symmetric(rng, n))
    pl = plan(n, jnp.float32, EvdConfig(b=8, nb=32, spectrum=by_count(k)))
    assert pl.bt_group > 0
    w, V = pl(A)
    assert V.shape == (n, k)
    scale = max(float(jnp.abs(w).max()), 1.0)
    assert float(jnp.abs(A @ V - V * w[None, :]).max()) < 1e-4 * scale
    V_scan = _scan_vectors(A, 8, 32, w)
    np.testing.assert_allclose(np.asarray(V), np.asarray(V_scan), atol=1e-4)


def test_batch_plan_vmap_blocked(rng):
    n, batch = 32, 3
    As = np.stack([random_symmetric(rng, n) for _ in range(batch)])
    As = jnp.asarray(As)
    bpl = batch_plan(n, batch, jnp.float32, EvdConfig(b=8, nb=16))
    wB, VB = bpl(As)
    assert VB.shape == (batch, n, n)
    pl = plan(n, jnp.float32, EvdConfig(b=8, nb=16))
    for i in range(batch):
        wi, Vi = pl(As[i])
        np.testing.assert_allclose(np.asarray(wB[i]), np.asarray(wi), atol=1e-5)
        # Interpret-mode Pallas kernels are traced inline, so their rounding
        # depends on the surrounding program: the vmapped batch trace can
        # round an inverse-iteration pivot the other way and flip a column's
        # sign.  Eigenvector sign is not defined anyway — align per column.
        Vb, Vi = np.asarray(VB[i]), np.asarray(Vi)
        s = np.sign(np.sum(Vb * Vi, axis=0))
        np.testing.assert_allclose(Vb * s[None, :], Vi, atol=1e-4)


def test_registry_jnp_env_pin_covers_backtransform(rng, monkeypatch):
    # The CI jnp matrix leg exercises exactly this: with the env pin the
    # blocked default must resolve backtransform_wy to the jnp reference.
    monkeypatch.setenv(registry.ENV_VAR, "jnp")
    registry.set_backend(None)
    try:
        assert registry.resolve("backtransform_wy").__name__ == "backtransform_wy_xla"
        n = 24
        A = jnp.asarray(random_symmetric(rng, n))
        w, V = plan(n, jnp.float32, EvdConfig(b=8, nb=8))(A)
        scale = max(float(jnp.abs(w).max()), 1.0)
        assert float(jnp.abs(A @ V - V * w[None, :]).max()) < 1e-4 * scale
    finally:
        registry.set_backend(None)
