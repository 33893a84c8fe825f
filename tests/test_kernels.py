"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracles."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels import syr2k, trailing_update, bulge_wavefront, panel_qr
from repro.kernels.ref import syr2k_ref, trailing_update_ref
from repro.core import band_reduce, chase_sequential, panel_qr_householder
from conftest import random_symmetric


# ------------------------------------------------------------------ syr2k
@pytest.mark.parametrize(
    "n,k,bm,bk",
    [
        (32, 8, 8, 8),
        (64, 16, 16, 8),
        (64, 64, 32, 32),
        (96, 32, 32, 16),   # 3 tiles per side (odd triangle)
        (128, 24, 32, 8),
        (48, 16, 16, 16),
    ],
)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_syr2k_sweep(rng, n, k, bm, bk, dtype):
    dtype = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    A = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32)).astype(dtype)
    B = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32)).astype(dtype)
    C0 = random_symmetric(rng, n)
    C = jnp.asarray(C0).astype(dtype)
    out = syr2k(A, B, C, alpha=-1.0, bm=bm, bk=bk)
    ref = syr2k_ref(A.astype(jnp.float32), B.astype(jnp.float32), C.astype(jnp.float32), alpha=-1.0)
    tol = 2e-5 if dtype == jnp.float32 else 5e-2
    scale = float(jnp.abs(ref).max())
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), atol=tol * scale
    )
    # exact symmetry by construction
    np.testing.assert_allclose(np.asarray(out), np.asarray(out).T, atol=0)


def test_syr2k_no_initial_c(rng):
    A = jnp.asarray(rng.normal(size=(32, 16)).astype(np.float32))
    B = jnp.asarray(rng.normal(size=(32, 16)).astype(np.float32))
    out = syr2k(A, B, bm=16, bk=16)
    np.testing.assert_allclose(out, syr2k_ref(A, B), atol=2e-5 * float(jnp.abs(out).max()))


def test_trailing_update_matches_ref(rng):
    n, k = 40, 12
    C = jnp.asarray(random_symmetric(rng, n))
    Y = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32))
    Z = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32))
    out = trailing_update(C, Y, Z, bm=8, bk=8)
    np.testing.assert_allclose(
        out, trailing_update_ref(C, Y, Z), atol=3e-5 * float(jnp.abs(C).max() + 10)
    )


# ------------------------------------------------------------------ bulge
@pytest.mark.parametrize("n,b", [(24, 2), (32, 4), (48, 4), (40, 8)])
def test_bulge_kernel_vs_sequential(rng, n, b):
    """Kernel and sequential oracle interleave ops differently, so entries
    agree only to accumulated rounding; the spectrum must match tightly
    (same structure as test_wavefront_matches_sequential)."""
    import scipy.linalg as sla

    A = jnp.asarray(random_symmetric(rng, n))
    B = band_reduce(A, b, min(2 * b, n - b))
    T1 = bulge_wavefront(B, b)
    T2 = chase_sequential(B, b)
    scale = float(jnp.abs(B).max())
    np.testing.assert_allclose(T1, T2, atol=5e-3 * scale)  # loose entrywise
    ew = lambda T: np.sort(
        sla.eigvalsh_tridiagonal(
            np.asarray(jnp.diagonal(T), np.float64),
            np.asarray(jnp.diagonal(T, -1), np.float64),
        )
    )
    np.testing.assert_allclose(ew(T1), ew(T2), atol=2e-4 * scale)


def test_bulge_kernel_large_falls_back(monkeypatch, rng):
    import repro.kernels.ops as ops

    monkeypatch.setenv("REPRO_BULGE_INTERPRET_MAX_N", "8")
    n, b = 16, 4
    B = band_reduce(jnp.asarray(random_symmetric(rng, n)), b, b)
    T = ops.bulge_wavefront(B, b)  # falls back to XLA wavefront
    T2 = chase_sequential(B, b)
    np.testing.assert_allclose(T, T2, atol=1e-4 * float(jnp.abs(B).max()))


# ------------------------------------------------------------------ panel
@pytest.mark.parametrize("m,b", [(16, 4), (32, 8), (24, 6), (64, 16)])
def test_panel_kernel_sweep(rng, m, b):
    P = jnp.asarray(rng.normal(size=(m, b)).astype(np.float32))
    V1, T1, tau1, R1 = panel_qr(P)
    V2, T2, tau2, R2 = panel_qr_householder(P)
    for a, c in zip((V1, T1, tau1, R1), (V2, T2, tau2, R2)):
        np.testing.assert_allclose(a, c, atol=5e-5 * max(float(jnp.abs(c).max()), 1.0))
