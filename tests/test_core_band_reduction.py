"""SBR / DBR band reduction tests (paper Algorithm 1)."""
import numpy as np
import pytest
import scipy.linalg as sla
import jax.numpy as jnp

from repro.core import band_reduce, form_q, apply_q_left
from conftest import random_symmetric


def band_mask(n, b):
    return np.abs(np.subtract.outer(np.arange(n), np.arange(n))) > b


@pytest.mark.parametrize(
    "n,b,nb",
    [
        (32, 4, 4),    # SBR (b == nb)
        (32, 4, 16),   # DBR
        (48, 8, 16),
        (64, 4, 32),   # DBR, large block
        (64, 16, 16),  # SBR wide band
        (40, 4, 8),
    ],
)
def test_band_structure_and_similarity(rng, n, b, nb):
    A = jnp.asarray(random_symmetric(rng, n))
    B, refl = band_reduce(A, b, nb, return_reflectors=True)
    Bn = np.asarray(B)
    scale = np.abs(Bn).max()
    # structurally banded, symmetric
    assert np.abs(Bn * band_mask(n, b)).max() == 0.0
    np.testing.assert_allclose(Bn, Bn.T, atol=1e-5 * scale)
    # similarity: A = Q B Q^T
    Q = np.asarray(form_q(refl, n))
    np.testing.assert_allclose(Q.T @ Q, np.eye(n), atol=5e-5)
    np.testing.assert_allclose(Q @ Bn @ Q.T, np.asarray(A), atol=2e-4 * scale)
    # spectrum preserved
    np.testing.assert_allclose(
        np.sort(sla.eigvalsh(Bn)), np.sort(sla.eigvalsh(np.asarray(A))),
        atol=2e-4 * scale,
    )


def test_dbr_equals_sbr_output_spectrum(rng):
    """DBR and SBR produce different orthogonal factors but the same band
    spectrum (mathematical equivalence, paper §4.1)."""
    n, b = 48, 4
    A = jnp.asarray(random_symmetric(rng, n))
    B_sbr = np.asarray(band_reduce(A, b, b))
    B_dbr = np.asarray(band_reduce(A, b, 16))
    np.testing.assert_allclose(
        np.sort(sla.eigvalsh(B_sbr)), np.sort(sla.eigvalsh(B_dbr)), atol=2e-4 * np.abs(B_sbr).max()
    )


def test_registry_backends_agree_in_dbr(rng):
    """The default (registry-resolved, Pallas) trailing update and the forced
    jnp reference backend produce the same band reduction."""
    from repro.backend import registry

    n, b, nb = 32, 4, 16
    A = jnp.asarray(random_symmetric(rng, n))
    B1 = band_reduce(A, b, nb)  # default dispatch (pallas where available)
    with registry.use_backend("jnp"):
        B2 = band_reduce(A, b, nb)
    np.testing.assert_allclose(B1, B2, atol=5e-5 * float(jnp.abs(B1).max()))


def test_apply_q_left_transpose_roundtrip(rng):
    n, b, nb = 32, 4, 8
    A = jnp.asarray(random_symmetric(rng, n))
    _, refl = band_reduce(A, b, nb, return_reflectors=True)
    X = jnp.asarray(rng.normal(size=(n, 5)).astype(np.float32))
    Y = apply_q_left(refl, X, transpose=False)
    X2 = apply_q_left(refl, Y, transpose=True)
    np.testing.assert_allclose(X2, X, atol=5e-5)
