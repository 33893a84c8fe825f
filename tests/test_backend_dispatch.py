"""Dispatch-layer tests: registry resolution, overrides, kernel parity.

Covers the acceptance contract of the backend subsystem:
* every (op, backend) pair resolves and the pallas/jnp pairs agree
  numerically;
* ``eigh(A, method="two_stage")`` executes the Pallas fused first-stage op
  via the registry by default, and its block steps above the fused
  kernel's ceiling go through the registry ``trailing_update``;
* ``REPRO_KERNEL_BACKEND=jnp`` (and the programmatic overrides) force the
  reference path.
"""
from pathlib import Path

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.backend import cache, compat, registry
from repro.core.panel_qr import panel_qr_geqrf
from repro.kernels import ops
from repro.kernels import ref as kref
from conftest import random_symmetric


# ------------------------------------------------------------- resolution
def test_default_backend_is_pallas_here(monkeypatch):
    # The container ships Pallas (interpret on CPU); the paper's kernels must
    # be the default hot path, not dead code.
    monkeypatch.delenv(registry.ENV_VAR, raising=False)
    assert registry.default_backend() == "pallas"


def test_resolve_never_degrades_to_jnp(monkeypatch):
    # No capability probe stands between the default and the kernels: with
    # no override, resolution lands on the Pallas implementation.
    monkeypatch.delenv(registry.ENV_VAR, raising=False)
    op = "trailing_update"
    assert registry.resolve(op) is registry.resolve(op, "pallas")
    assert registry.resolve(op) is not registry.resolve(op, "jnp")


def test_env_var_overrides_default(monkeypatch):
    monkeypatch.setenv(registry.ENV_VAR, "jnp")
    assert registry.default_backend() == "jnp"
    monkeypatch.setenv(registry.ENV_VAR, "bogus")
    with pytest.raises(ValueError):
        registry.default_backend()


def test_use_backend_scopes_and_restores(monkeypatch):
    monkeypatch.delenv(registry.ENV_VAR, raising=False)
    assert registry.default_backend() == "pallas"
    with registry.use_backend("jnp"):
        assert registry.default_backend() == "jnp"
    assert registry.default_backend() == "pallas"
    # the programmatic override beats the env var
    monkeypatch.setenv(registry.ENV_VAR, "jnp")
    with registry.use_backend("pallas"):
        assert registry.default_backend() == "pallas"
    assert registry.default_backend() == "jnp"


def test_resolve_rejects_unknown():
    with pytest.raises(KeyError):
        registry.resolve("not_an_op")
    # The pipeline's five ops; the kernel wrappers behind them are no ops.
    assert registry.OPS == (
        "trailing_update", "fused_panel_update", "bulge_wavefront",
        "backtransform_wy", "stage_mark",
    )
    for op in ("syr2k", "panel_qr", "bulge_chase"):
        with pytest.raises(KeyError):
            registry.resolve(op)
    with pytest.raises(ValueError):
        registry.resolve("trailing_update", "cuda")


def test_tile_defaults_per_platform():
    assert registry.tile_defaults("trailing_update", "tpu")["bm"] == 256
    assert registry.tile_defaults("trailing_update", "cpu")["bm"] == 128
    assert registry.tile_defaults("bulge_wavefront") == {}


# ----------------------------------------------------------- kernel parity
@pytest.mark.parametrize("n,k", [(32, 8), (48, 16), (40, 12)])
def test_trailing_update_parity(rng, n, k):
    C = jnp.asarray(random_symmetric(rng, n))
    Y = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32))
    Z = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32))
    out_p = registry.resolve("trailing_update", "pallas")(C, Y, Z)
    out_j = registry.resolve("trailing_update", "jnp")(C, Y, Z)
    np.testing.assert_allclose(
        out_p, out_j, atol=1e-5 * float(jnp.abs(out_j).max() + 1.0)
    )


@pytest.mark.parametrize("n,k", [(32, 16), (24, 24)])
def test_syr2k_parity(rng, n, k):
    A = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32))
    B = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32))
    out_p = ops.syr2k(A, B)
    out_j = kref.syr2k_ref(A, B)
    np.testing.assert_allclose(
        out_p, out_j, atol=1e-5 * float(jnp.abs(out_j).max() + 1.0)
    )


@pytest.mark.parametrize("n,b", [(24, 2), (32, 4)])
def test_bulge_chase_parity(rng, n, b):
    from repro.core import band_reduce

    A = jnp.asarray(random_symmetric(rng, n))
    Bband = band_reduce(A, b, min(2 * b, n - b))
    T_p = registry.resolve("bulge_wavefront", "pallas")(Bband, b)
    T_j = registry.resolve("bulge_wavefront", "jnp")(Bband, b)
    scale = float(jnp.abs(Bband).max())
    # Different op interleavings: compare the invariant (the spectrum) tight,
    # entries loose.
    np.testing.assert_allclose(T_p, T_j, atol=5e-3 * scale)
    import scipy.linalg as sla

    ew = lambda T: np.sort(
        sla.eigvalsh_tridiagonal(
            np.asarray(jnp.diagonal(T), np.float64),
            np.asarray(jnp.diagonal(T, -1), np.float64),
        )
    )
    np.testing.assert_allclose(ew(T_p), ew(T_j), atol=2e-4 * scale)


@pytest.mark.parametrize("m,b", [(24, 4), (32, 8)])
def test_panel_qr_parity(rng, m, b):
    P = jnp.asarray(rng.normal(size=(m, b)).astype(np.float32))
    V1, T1, tau1, R1 = ops.panel_qr(P)
    V2, T2, tau2, R2 = panel_qr_geqrf(P)
    # geqrf and the kernel may differ in column-sign convention; the applied
    # orthogonal factor must match up to the signs of R's diagonal.
    Q1 = np.asarray(jnp.eye(m) - V1 @ T1 @ V1.T)
    Q2 = np.asarray(jnp.eye(m) - V2 @ T2 @ V2.T)
    d = np.sign(np.diag(np.asarray(R1)) * np.diag(np.asarray(R2)))
    np.testing.assert_allclose(Q1[:, :b] * d[None, :], Q2[:, :b], atol=5e-5)
    np.testing.assert_allclose(
        np.abs(np.asarray(R1)), np.abs(np.asarray(R2)), atol=5e-5
    )


# ------------------------------------------------- eigh dispatch (the point)
def _spy_impl(monkeypatch, op, backend):
    """Wrap the registered (op, backend) impl with a call counter."""
    real = registry.resolve(op, backend)  # also forces _build_impls
    calls = {"n": 0}

    def spy(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setitem(registry._IMPLS, (op, backend), spy)
    return calls


def test_eigh_two_stage_resolves_pallas_by_default(rng, monkeypatch):
    from repro.core import eigh

    monkeypatch.delenv(registry.ENV_VAR, raising=False)
    spy = _spy_impl(monkeypatch, "fused_panel_update", "pallas")
    # Unique (shape, blocking) so the jit cache cannot satisfy this call
    # without re-tracing through the registry.
    n = 56
    A = jnp.asarray(random_symmetric(rng, n))
    w, V = eigh(A, method="two_stage", b=4, nb=24)
    assert spy["n"] > 0, "eigh did not route the fused first stage to Pallas"
    resid = np.asarray(A) @ np.asarray(V) - np.asarray(V) * np.asarray(w)[None, :]
    assert np.abs(resid).max() < 5e-4 * float(np.abs(np.asarray(w)).max())


def test_unfused_mode_routes_trailing_update(rng, monkeypatch):
    # Above FUSED_PANEL_INTERPRET_MAX_M a two-stage solve runs its block
    # steps as panel QRs + the registry trailing_update; the fused kernel
    # takes the blocks under it.
    import scipy.linalg as sla

    from repro.core import eigh

    monkeypatch.delenv(registry.ENV_VAR, raising=False)
    monkeypatch.setenv("REPRO_FUSED_PANEL_INTERPRET_MAX_M", "32")
    spy_trailing = _spy_impl(monkeypatch, "trailing_update", "pallas")
    spy_fused = _spy_impl(monkeypatch, "fused_panel_update", "pallas")
    n = 52  # block steps on trailing views m = 52, 36 (streamed) and 20 (kernel)
    A = jnp.asarray(random_symmetric(rng, n))
    w = eigh(A, method="two_stage", b=4, nb=16, eigenvectors=False)
    assert spy_fused["n"] == 3
    assert spy_trailing["n"] == 2, "the streamed block steps skipped trailing_update"
    w_ref = np.sort(sla.eigvalsh(np.asarray(A, np.float64)))
    np.testing.assert_allclose(
        np.sort(np.asarray(w)), w_ref, atol=3e-4 * np.abs(w_ref).max()
    )


def test_env_var_forces_jnp_fallback(rng, monkeypatch):
    from repro.core import eigh

    monkeypatch.setenv(registry.ENV_VAR, "jnp")
    spy_pallas = _spy_impl(monkeypatch, "fused_panel_update", "pallas")
    spy_jnp = _spy_impl(monkeypatch, "fused_panel_update", "jnp")
    n = 44
    A = jnp.asarray(random_symmetric(rng, n))
    w = eigh(A, method="two_stage", b=4, nb=20, eigenvectors=False)
    assert spy_jnp["n"] > 0
    assert spy_pallas["n"] == 0
    import scipy.linalg as sla

    w_ref = np.sort(sla.eigvalsh(np.asarray(A, np.float64)))
    np.testing.assert_allclose(
        np.sort(np.asarray(w)), w_ref, atol=3e-4 * np.abs(w_ref).max()
    )


def test_backend_override_beats_jit_cache(rng, monkeypatch):
    """Flipping the backend between two same-shape eigh calls must take
    effect: the resolved backend is part of the jit cache key."""
    from repro.core import eigh

    monkeypatch.delenv(registry.ENV_VAR, raising=False)
    n = 36
    A = jnp.asarray(random_symmetric(rng, n))
    w1 = eigh(A, b=4, nb=16, eigenvectors=False)  # traces the pallas path
    spy_jnp = _spy_impl(monkeypatch, "fused_panel_update", "jnp")
    with registry.use_backend("jnp"):
        w2 = eigh(A, b=4, nb=16, eigenvectors=False)  # same shape + statics
    assert spy_jnp["n"] > 0, "jnp override was swallowed by the jit cache"
    np.testing.assert_allclose(
        w1, w2, atol=1e-4 * float(jnp.abs(np.asarray(w1)).max() + 1.0)
    )


def test_backend_parity_full_eigh(rng):
    """Acceptance: pallas and jnp pipelines agree to <= 1e-5 fp32 relative.

    The backends differ in BOTH the trailing update and the bulge executor;
    the executors interleave ops differently, so tridiagonal ENTRIES only
    agree loosely while the invariant — the spectrum — must agree tightly.
    (Entrywise trailing-update parity is covered by
    test_registry_backends_agree_in_dbr, which pins everything else.)
    """
    import scipy.linalg as sla

    from repro.core import tridiagonalize

    n = 48
    A = jnp.asarray(random_symmetric(rng, n))
    with registry.use_backend("pallas"):
        d1, e1 = tridiagonalize(A, b=4, nb=16)
    with registry.use_backend("jnp"):
        d2, e2 = tridiagonalize(A, b=4, nb=16)
    ew = lambda d, e: np.sort(
        sla.eigvalsh_tridiagonal(np.asarray(d, np.float64), np.asarray(e, np.float64))
    )
    w1, w2 = ew(d1, e1), ew(d2, e2)
    scale = max(np.abs(w1).max(), 1.0)
    np.testing.assert_allclose(w1, w2, atol=1e-5 * scale)


# ---------------------------------------------------------------- compat
def test_compat_make_mesh_single_device():
    mesh = compat.make_mesh((1,), ("x",))
    assert mesh.axis_names == ("x",)
    assert mesh.axis_types == (jax.sharding.AxisType.Auto,)


def test_compat_shard_map_runs_single_device(rng):
    from jax.sharding import PartitionSpec as P

    mesh = compat.make_mesh((1,), ("data",))
    x = jnp.asarray(rng.normal(size=(4, 8)).astype(np.float32))
    y = jax.shard_map(
        lambda v: v * 2.0, mesh=mesh, in_specs=(P(),), out_specs=P(),
        check_vma=False,
    )(x)
    np.testing.assert_allclose(y, 2.0 * x)


# ------------------------------------------------------ compilation cache
@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compilation_cache_defaults_to_repo_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(cache.ENV_VAR, raising=False)
    path = cache.enable_compilation_cache()
    repo = Path(__file__).resolve().parents[1]
    assert path == str(repo / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_compilation_cache_env_var_wins(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv(cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cache.enable_compilation_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # left to JAX
