"""Plan-based solver API: config validation, plan caching/no-retrace,
legacy-wrapper parity, partial spectrum, degenerate-blocking fallback."""
import numpy as np
import pytest
import scipy.linalg as sla
import jax
import jax.numpy as jnp

from repro.solver import (
    EvdConfig,
    EvdPlan,
    Spectrum,
    by_count,
    by_index,
    plan,
    plan_for,
    resolve_blocking,
    trace_count,
)
from repro.core import eigh, eigh_batched, eigvalsh, inverse_pth_root
from repro.solver.plan import tridiagonalize
from conftest import random_symmetric, random_psd


CFG = EvdConfig(b=4, nb=16)


def _sym(rng, n=32):
    return jnp.asarray(random_symmetric(rng, n))


# ------------------------------------------------------------- config layer
def test_config_validation():
    with pytest.raises(ValueError):
        EvdConfig(method="qr")
    with pytest.raises(ValueError):
        EvdConfig(tol=2.0)
    with pytest.raises(ValueError):
        Spectrum.by_index(5, 5)
    with pytest.raises(ValueError):
        Spectrum.by_count(0)


def test_spectrum_index_range():
    assert Spectrum.all().index_range(10) == (0, 10)
    assert by_index(2, 7).index_range(10) == (2, 5)
    assert by_count(3).index_range(10) == (7, 3)
    assert by_count(3, largest=False).index_range(10) == (0, 3)
    with pytest.raises(ValueError):
        by_index(2, 11).index_range(10)
    with pytest.raises(ValueError):
        by_count(11).index_range(10)


def test_config_hashable_and_frozen():
    c1 = EvdConfig(b=4, nb=16, spectrum=by_count(3))
    c2 = EvdConfig(b=4, nb=16, spectrum=by_count(3))
    assert c1 == c2 and hash(c1) == hash(c2)
    with pytest.raises(Exception):
        c1.b = 8


@pytest.mark.parametrize(
    "field,value", [("tridiag", "fused"), ("chase", "wavefront"), ("backtransform", "blocked")]
)
def test_config_has_no_pipeline_options(field, value):
    # One pipeline (fused first stage, wavefront chase, blocked
    # back-transform): its oracles are test references, not options.
    with pytest.raises(TypeError):
        EvdConfig(**{field: value})


@pytest.mark.parametrize("entry", [eigh, eigh_batched, tridiagonalize], ids=lambda f: f.__name__)
def test_entry_points_reject_chase_kwarg(rng, entry):
    A = _sym(rng, 16)
    with pytest.raises(TypeError):
        entry(A[None] if entry is eigh_batched else A, chase="wavefront")


# ---------------------------------------------------------------- plan cache
def test_plan_cache_returns_same_object():
    p1 = plan(32, jnp.float32, CFG)
    p2 = plan(32, jnp.float32, EvdConfig(b=4, nb=16))
    assert p1 is p2
    assert isinstance(p1, EvdPlan)
    # different shape or config -> different plan
    assert plan(48, jnp.float32, CFG) is not p1
    assert plan(32, jnp.float32, EvdConfig(b=4, nb=8)) is not p1


def test_plan_execute_no_retrace(rng):
    pl = plan(24, jnp.float32, CFG)
    A = _sym(rng, 24)
    w1, V1 = pl(A)
    pl.eigvals(A)  # warm the eigenvectors=False variant (its own trace)
    before = trace_count(pl)
    # Fresh arrays, same shape/dtype: must hit the jit cache, zero retraces.
    for _ in range(3):
        w2, V2 = pl(A + 0.0)
        _ = pl.eigvals(_sym(rng, 24))
    assert trace_count(pl) == before
    # And the plan() call itself returns the cached object, so a consumer
    # re-building its config each step still never retraces.
    w3, V3 = plan(24, jnp.float32, EvdConfig(b=4, nb=16))(A)
    assert trace_count(pl) == before
    np.testing.assert_array_equal(np.asarray(w1), np.asarray(w3))


def test_legacy_wrapper_parity(rng):
    """eigh(A, b=, nb=) must be the plan-built result, bit for bit."""
    A = _sym(rng, 32)
    w_legacy, V_legacy = eigh(A, b=4, nb=16)
    w_plan, V_plan = plan_for(A, CFG)(A)
    np.testing.assert_array_equal(np.asarray(w_legacy), np.asarray(w_plan))
    np.testing.assert_array_equal(np.asarray(V_legacy), np.asarray(V_plan))
    np.testing.assert_array_equal(
        np.asarray(eigvalsh(A, b=4, nb=16)), np.asarray(plan_for(A, CFG).eigvals(A))
    )


def test_legacy_wrapper_rejects_mixed_config(rng):
    A = _sym(rng, 16)
    with pytest.raises(ValueError):
        eigh(A, config=CFG, b=8)


# ----------------------------------------------------------- partial spectrum
@pytest.mark.parametrize("k", [1, 5])
def test_by_count_matches_full_topk(rng, k):
    n = 32
    A = _sym(rng, n)
    w_full, V_full = plan(n, jnp.float32, CFG)(A)
    pl = plan(n, jnp.float32, EvdConfig(b=4, nb=16, spectrum=by_count(k)))
    w_k, V_k = pl(A)
    assert w_k.shape == (k,) and V_k.shape == (n, k)
    scale = float(np.abs(np.asarray(w_full)).max())
    np.testing.assert_allclose(
        np.asarray(w_k), np.asarray(w_full)[-k:], atol=5e-4 * scale
    )
    # Same eigenpairs: residual check against A itself.
    resid = np.asarray(A) @ np.asarray(V_k) - np.asarray(V_k) * np.asarray(w_k)[None, :]
    assert np.abs(resid).max() < 1e-3 * scale
    np.testing.assert_allclose(
        np.asarray(V_k).T @ np.asarray(V_k), np.eye(k), atol=2e-4
    )


def test_by_count_smallest(rng):
    n, k = 32, 4
    A = _sym(rng, n)
    w_ref = np.sort(sla.eigvalsh(np.asarray(A, np.float64)))
    pl = plan(n, jnp.float32, EvdConfig(b=4, nb=16, spectrum=by_count(k, largest=False)))
    w_k = pl.eigvals(A)
    np.testing.assert_allclose(
        np.asarray(w_k), w_ref[:k], atol=5e-4 * np.abs(w_ref).max()
    )


def test_by_index_window(rng):
    n = 32
    A = _sym(rng, n)
    w_ref = np.sort(sla.eigvalsh(np.asarray(A, np.float64)))
    pl = plan(n, jnp.float32, EvdConfig(b=4, nb=16, spectrum=by_index(10, 20)))
    w, V = pl(A)
    assert w.shape == (10,) and V.shape == (n, 10)
    np.testing.assert_allclose(
        np.asarray(w), w_ref[10:20], atol=5e-4 * np.abs(w_ref).max()
    )


def test_partial_spectrum_jacobi(rng):
    n, k = 20, 3
    A = _sym(rng, n)
    w_ref = np.sort(sla.eigvalsh(np.asarray(A, np.float64)))
    pl = plan(n, jnp.float32, EvdConfig(method="jacobi", spectrum=by_count(k)))
    w, V = pl(A)
    assert V.shape == (n, k)
    np.testing.assert_allclose(
        np.asarray(w), w_ref[-k:], atol=1e-3 * np.abs(w_ref).max()
    )


def test_inverse_root_requires_full_spectrum(rng):
    pl = plan(16, jnp.float32, EvdConfig(b=4, nb=8, spectrum=by_count(4)))
    with pytest.raises(ValueError):
        pl.inverse_pth_root(jnp.asarray(random_psd(np.random.default_rng(0), 16)), 4)


# ---------------------------------------------------- degenerate blocking
def test_fallback_reason_for_prime_n(rng):
    n = 13  # prime: no power-of-two factor, b collapses to 1
    pl = plan(n, jnp.float32, EvdConfig())
    assert pl.fallback_reason is not None
    assert "b=1" in pl.fallback_reason
    assert pl.method == "direct"
    A = _sym(rng, n)
    w, V = pl(A)
    w_ref = np.sort(sla.eigvalsh(np.asarray(A, np.float64)))
    scale = np.abs(w_ref).max()
    np.testing.assert_allclose(np.sort(np.asarray(w)), w_ref, atol=3e-4 * scale)
    resid = np.asarray(A) @ np.asarray(V) - np.asarray(V) * np.asarray(w)[None, :]
    assert np.abs(resid).max() < 1e-3 * scale


def test_no_fallback_reason_for_composite_n():
    assert plan(32, jnp.float32, CFG).fallback_reason is None
    dec = resolve_blocking(32, b=4, nb=16)
    assert (dec.b, dec.nb, dec.fallback_reason) == (4, 16, None)
    assert resolve_blocking(13).degenerate


# ----------------------------------------------------------- plan plumbing
def test_plan_backend_pin(rng):
    """config.backend pins kernel dispatch; results match across backends."""
    A = _sym(rng, 16)
    w_jnp = plan(16, jnp.float32, EvdConfig(b=4, nb=8, backend="jnp")).eigvals(A)
    w_def = plan(16, jnp.float32, EvdConfig(b=4, nb=8)).eigvals(A)
    assert plan(16, jnp.float32, EvdConfig(b=4, nb=8, backend="jnp")).backend == "jnp"
    np.testing.assert_allclose(np.asarray(w_jnp), np.asarray(w_def), atol=1e-4)
    with pytest.raises(ValueError):
        plan(16, jnp.float32, EvdConfig(backend="cuda12"))  # unknown name


def test_plan_vmap_composable(rng):
    """plan_for + execute must stay vmap/jit composable (Shampoo path)."""
    A = np.stack([random_symmetric(rng, 16) for _ in range(3)])
    cfg = EvdConfig(b=4, nb=8)
    f = jax.jit(jax.vmap(lambda M: plan_for(M, cfg).eigvals(M)))
    w = np.asarray(f(jnp.asarray(A)))
    for i in range(3):
        w_ref = np.sort(sla.eigvalsh(A[i].astype(np.float64)))
        np.testing.assert_allclose(np.sort(w[i]), w_ref, atol=3e-4 * np.abs(w_ref).max())


def test_inverse_pth_root_via_plan(rng):
    n = 16
    S = jnp.asarray(random_psd(rng, n))
    pl = plan(n, jnp.float32, EvdConfig(b=4, nb=8))
    X = np.asarray(pl.inverse_pth_root(S, 4), np.float64)
    err = np.linalg.matrix_power(X, 4) @ np.asarray(S, np.float64) - np.eye(n)
    assert np.abs(err).max() < 5e-2
    # legacy wrapper goes through the same plan
    X2 = np.asarray(inverse_pth_root(S, 4, b=4, nb=8), np.float64)
    np.testing.assert_array_equal(X, X2)


def test_plan_rejects_mismatched_operand(rng):
    pl = plan(16, jnp.float32, EvdConfig(b=4, nb=8))
    with pytest.raises(ValueError):
        pl(_sym(rng, 24))          # wrong n
    with pytest.raises(ValueError):
        pl.eigvals(jnp.asarray(random_symmetric(rng, 16), jnp.float64)
                   if jax.config.jax_enable_x64 else
                   jnp.zeros((16, 16), jnp.bfloat16))  # wrong dtype


def test_plan_tol_controls_bisection_budget():
    fine = plan(16, jnp.float32, EvdConfig(b=4, nb=8))
    coarse = plan(16, jnp.float32, EvdConfig(b=4, nb=8, tol=1e-3))
    assert coarse.bisect_iters < fine.bisect_iters
