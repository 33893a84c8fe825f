"""End-to-end EVD tests: tridiagonal solvers, full eigh, inverse roots."""
import numpy as np
import pytest
import scipy.linalg as sla
import jax
import jax.numpy as jnp
from conftest import hypothesis_or_stub

given, settings, st = hypothesis_or_stub()

from repro.core import (
    eigvalsh_tridiag,
    eigvalsh_tridiag_range,
    eigvecs_inverse_iteration,
    eigh,
    eigvalsh,
    eigh_batched,
    eigvalsh_batched,
    inverse_pth_root,
    jacobi_eigh,
    sturm_count,
)
from conftest import random_symmetric, random_psd


# ---------------------------------------------------------------- tridiag
@pytest.mark.parametrize("n", [4, 16, 33, 64])
def test_bisection_matches_scipy(rng, n):
    d = rng.normal(size=n).astype(np.float32)
    e = rng.normal(size=n - 1).astype(np.float32)
    w = np.asarray(eigvalsh_tridiag(jnp.asarray(d), jnp.asarray(e)))
    w_ref = sla.eigvalsh_tridiagonal(d.astype(np.float64), e.astype(np.float64))
    scale = max(np.abs(w_ref).max(), 1.0)
    np.testing.assert_allclose(np.sort(w), np.sort(w_ref), atol=5e-5 * scale)


@pytest.mark.parametrize("start,count", [(0, 4), (7, 9), (28, 5)])
def test_bisection_range_matches_full(rng, start, count):
    n = 33
    d = rng.normal(size=n).astype(np.float32)
    e = rng.normal(size=n - 1).astype(np.float32)
    w_full = np.asarray(eigvalsh_tridiag(jnp.asarray(d), jnp.asarray(e)))
    w_part = np.asarray(
        eigvalsh_tridiag_range(jnp.asarray(d), jnp.asarray(e), start=start, count=count)
    )
    scale = max(np.abs(w_full).max(), 1.0)
    np.testing.assert_allclose(w_part, w_full[start : start + count], atol=1e-5 * scale)


def test_sturm_count_monotone(rng):
    n = 32
    d = rng.normal(size=n).astype(np.float32)
    e = rng.normal(size=n - 1).astype(np.float32)
    xs = jnp.linspace(-10, 10, 41)
    counts = np.asarray(sturm_count(jnp.asarray(d), jnp.asarray(e), xs))
    assert (np.diff(counts) >= 0).all()
    assert counts[0] == 0 and counts[-1] == n


def test_inverse_iteration_residuals(rng):
    n = 48
    d = jnp.asarray(rng.normal(size=n).astype(np.float32))
    e = jnp.asarray(rng.normal(size=n - 1).astype(np.float32))
    w = eigvalsh_tridiag(d, e)
    V = eigvecs_inverse_iteration(d, e, w)
    T = np.diag(np.asarray(d)) + np.diag(np.asarray(e), 1) + np.diag(np.asarray(e), -1)
    resid = T @ np.asarray(V) - np.asarray(V) * np.asarray(w)[None, :]
    scale = np.abs(np.asarray(w)).max()
    assert np.abs(resid).max() < 2e-3 * scale
    np.testing.assert_allclose(np.asarray(V).T @ np.asarray(V), np.eye(n), atol=1e-4)


def _clustered(rng, n, kind):
    if kind == "low_rank":  # Shampoo statistics: rank n/4 plus a ridge
        G = rng.normal(size=(n, n // 4))
        A = G @ G.T / (n // 4)
        return A + 0.1 * np.trace(A) / n * np.eye(n)
    # xLATMS style: geometric magnitudes plus an n/4 cluster of width 1e-6
    Q, _ = np.linalg.qr(rng.normal(size=(n, n)))
    k = 3 * n // 4
    lam = np.concatenate([
        1e-4 ** (np.arange(k) / (k - 1)) * rng.choice([-1.0, 1.0], size=k),
        0.5 * (1.0 + 1e-6 * rng.uniform(size=n - k)),
    ])
    return (Q * lam) @ Q.T


@pytest.mark.parametrize("kind", ["low_rank", "geometric_cluster"])
def test_inverse_iteration_clustered_spectra(rng, kind):
    # Lanes shifted onto single members of a cluster used to converge onto
    # parallel vectors, leaving residuals ~1e4 n*eps after the final QR.
    n = 128
    T = sla.hessenberg(_clustered(rng, n, kind))
    d = np.diag(T).astype(np.float32)
    e = np.diag(T, 1).astype(np.float32)
    w = eigvalsh_tridiag(jnp.asarray(d), jnp.asarray(e))
    V = np.asarray(eigvecs_inverse_iteration(jnp.asarray(d), jnp.asarray(e), w), np.float64)
    T32 = np.diag(d) + np.diag(e, 1) + np.diag(e, -1)
    w = np.asarray(w, np.float64)
    bound = n * np.finfo(np.float32).eps
    resid = np.linalg.norm(T32 @ V - V * w[None, :], axis=0).max() / np.abs(w).max()
    assert resid <= bound
    assert np.abs(V.T @ V - np.eye(n)).max() <= bound


# ---------------------------------------------------------------- full eigh
@pytest.mark.parametrize(
    "method,kw",
    [
        ("two_stage", dict(b=4, nb=16)),   # DBR (the paper)
        ("two_stage", dict(b=4, nb=4)),    # SBR
        ("direct", {}),
        ("jacobi", {}),
    ],
)
def test_eigh_methods(rng, method, kw):
    n = 32
    A = jnp.asarray(random_symmetric(rng, n))
    w, V = eigh(A, method=method, **kw)
    w, V = np.asarray(w), np.asarray(V)
    w_ref = np.sort(sla.eigvalsh(np.asarray(A, np.float64)))
    scale = np.abs(w_ref).max()
    np.testing.assert_allclose(np.sort(w), w_ref, atol=3e-4 * scale)
    resid = np.asarray(A) @ V - V * w[None, :]
    assert np.abs(resid).max() < 5e-4 * scale
    np.testing.assert_allclose(V.T @ V, np.eye(n), atol=2e-4)
    assert (np.diff(w) >= -1e-5 * scale).all()  # ascending


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000))
def test_eigh_invariants_property(seed):
    """trace(A) == sum(w); scale equivariance; spectrum of A+cI shifts."""
    rng = np.random.default_rng(seed)
    n = 16
    A = jnp.asarray(random_symmetric(rng, n))
    w = np.asarray(eigvalsh(A, b=4, nb=8))
    scale = max(np.abs(w).max(), 1.0)
    assert abs(w.sum() - float(jnp.trace(A))) < 1e-3 * scale * n ** 0.5
    w2 = np.asarray(eigvalsh(2.5 * A, b=4, nb=8))
    np.testing.assert_allclose(np.sort(w2), 2.5 * np.sort(w), atol=2e-3 * scale)
    w3 = np.asarray(eigvalsh(A + 3.0 * jnp.eye(n), b=4, nb=8))
    np.testing.assert_allclose(np.sort(w3), np.sort(w) + 3.0, atol=2e-3 * scale)


def test_eigh_batched(rng):
    A = np.stack([random_symmetric(rng, 16) for _ in range(4)])
    w, V = eigh_batched(jnp.asarray(A), b=4, nb=8)
    for i in range(4):
        w_ref = np.sort(sla.eigvalsh(A[i].astype(np.float64)))
        np.testing.assert_allclose(
            np.sort(np.asarray(w[i])), w_ref, atol=3e-4 * np.abs(w_ref).max()
        )


def test_eigh_batched_values_only(rng):
    """Regression: eigenvectors=False used to crash unpacking (w, V)."""
    A = np.stack([random_symmetric(rng, 16) for _ in range(3)])
    w = eigh_batched(jnp.asarray(A), b=4, nb=8, eigenvectors=False)
    assert w.shape == (3, 16)
    w2 = eigvalsh_batched(jnp.asarray(A), b=4, nb=8)
    np.testing.assert_array_equal(np.asarray(w), np.asarray(w2))
    for i in range(3):
        w_ref = np.sort(sla.eigvalsh(A[i].astype(np.float64)))
        np.testing.assert_allclose(
            np.sort(np.asarray(w[i])), w_ref, atol=3e-4 * np.abs(w_ref).max()
        )


def test_eigvalsh_batched_nd_batch(rng):
    """(..., n, n) leading batch dims survive the round trip."""
    A = np.stack([random_symmetric(rng, 8) for _ in range(6)]).reshape(2, 3, 8, 8)
    w = eigvalsh_batched(jnp.asarray(A), b=4, nb=4)
    assert w.shape == (2, 3, 8)


def test_eigh_vmap_jit(rng):
    """The solver must be vmap/jit composable (Shampoo requirement)."""
    A = np.stack([random_symmetric(rng, 16) for _ in range(3)])
    f = jax.jit(jax.vmap(lambda M: eigh(M, b=4, nb=8, eigenvectors=False)))
    w = np.asarray(f(jnp.asarray(A)))
    for i in range(3):
        w_ref = np.sort(sla.eigvalsh(A[i].astype(np.float64)))
        np.testing.assert_allclose(np.sort(w[i]), w_ref, atol=3e-4 * np.abs(w_ref).max())


# ------------------------------------------------------------ inverse roots
@pytest.mark.parametrize("p", [2, 4])
def test_inverse_pth_root(rng, p):
    n = 24
    S = jnp.asarray(random_psd(rng, n))
    X = np.asarray(inverse_pth_root(S, p, b=4, nb=8), np.float64)
    err = np.linalg.matrix_power(X, p) @ np.asarray(S, np.float64) - np.eye(n)
    assert np.abs(err).max() < 5e-2  # eps-ridged root: loose but meaningful
    np.testing.assert_allclose(X, X.T, atol=1e-5 * np.abs(X).max())


def test_inverse_root_clamps_singular(rng):
    """Rank-deficient PSD stats must not produce inf/nan (Shampoo safety)."""
    n = 16
    g = rng.normal(size=(n, 3)).astype(np.float32)
    S = jnp.asarray(g @ g.T)  # rank 3
    X = np.asarray(inverse_pth_root(S, 4, b=4, nb=8))
    assert np.isfinite(X).all()


def test_jacobi_eigh(rng):
    n = 20
    A = jnp.asarray(random_symmetric(rng, n))
    w, V = jacobi_eigh(A)
    w_ref = np.sort(sla.eigvalsh(np.asarray(A, np.float64)))
    np.testing.assert_allclose(np.sort(np.asarray(w)), w_ref, atol=1e-3 * np.abs(w_ref).max())
    resid = np.asarray(A) @ np.asarray(V) - np.asarray(V) * np.asarray(w)[None, :]
    assert np.abs(resid).max() < 2e-3 * np.abs(w_ref).max()
