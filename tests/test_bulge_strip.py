"""The band-strip layout of the wavefront bulge-chase kernel.

The strip kernel (``bulge_chase_strip``) holds only the 128-column blocks
around the diagonal in VMEM.  At b = 8 and n just over 128 its windows
straddle a 128-row block boundary, so both static lane offsets are used.
Every kernel call passes an explicit ``interpret=True``; dispatch is checked
with the VMEM budget's environment override.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
import scipy.linalg as sla

from repro.core import apply_q2, chase_sequential, chase_wavefront, extract_tridiag
from repro.kernels import ops
from repro.kernels.bulge import (
    bulge_strip_vmem_bytes,
    bulge_vmem_bytes,
    bulge_wavefront_pallas,
    strip_fits_tile,
)
from repro.kernels.limits import limit

B8 = 8


def _band(rng, n, b):
    A = rng.normal(size=(n, n))
    A = A + A.T
    i, j = np.indices((n, n))
    A[np.abs(i - j) > b] = 0.0
    return jnp.asarray(A.astype(np.float32))


def _spectrum(T):
    d, e = (np.asarray(x, np.float64) for x in extract_tridiag(T))
    return np.sort(sla.eigvalsh_tridiagonal(d, e))


def _kernel_names(fn, *args):
    text = str(jax.make_jaxpr(fn)(*args))
    return {k for k in (ops.BULGE_DENSE, ops.BULGE_STRIP) if k in text}


@pytest.mark.parametrize("n", [136, 160])
def test_strip_matches_xla_and_sequential(rng, n):
    Bb = _band(rng, n, B8)
    T = bulge_wavefront_pallas(Bb, B8, group=4, strip=True, interpret=True)
    scale = float(jnp.abs(Bb).max())
    for oracle in (chase_wavefront, chase_sequential):
        T_ref = oracle(Bb, B8)
        np.testing.assert_allclose(T, T_ref, atol=5e-3 * scale)
        np.testing.assert_allclose(_spectrum(T), _spectrum(T_ref), atol=2e-4 * scale)
    # Each window sees the same tile as in the dense layout.
    T_dense = bulge_wavefront_pallas(Bb, B8, group=4, interpret=True)
    np.testing.assert_array_equal(np.asarray(T), np.asarray(T_dense))


@pytest.mark.parametrize("n", [136, 160])
def test_strip_log_reconstructs_the_band(rng, n):
    Bb = _band(rng, n, B8)
    T, log = ops.bulge_wavefront(Bb, B8, return_log=True, group=4, interpret=True)
    assert ops.bulge_kernel(n, B8, group=4, return_log=True, interpret=True) == ops.BULGE_DENSE
    T_s, (vs, taus, row0) = bulge_wavefront_pallas(
        Bb, B8, group=4, return_log=True, strip=True, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(T_s), np.asarray(T))
    for got, want in zip((vs, taus, row0), (log.vs, log.taus, log.row0)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    # The strip log is slot-compatible with the XLA executor's.
    _, log_x = chase_wavefront(Bb, B8, return_log=True)
    A = log_x.row0.shape[1]
    np.testing.assert_array_equal(np.asarray(row0)[:, :A], np.asarray(log_x.row0))
    assert np.all(np.asarray(row0)[:, A:] == n)

    d, e = extract_tridiag(T_s)
    Tt = jnp.diag(d) + jnp.diag(e, -1) + jnp.diag(e, 1)
    log_s = type(log)(vs=vs, taus=taus, row0=row0, n=n, b=B8)
    Q2 = apply_q2(log_s, jnp.eye(n, dtype=jnp.float32))
    scale = float(jnp.abs(Bb).max())
    np.testing.assert_allclose(Q2 @ Tt @ Q2.T, Bb, atol=1e-4 * n * scale / 100)
    np.testing.assert_allclose(Q2.T @ Q2, np.eye(n), atol=1e-5)


def test_strip_under_vmap_matches_each_matrix(rng):
    # A batched solve (solve_many) vmaps the kernel: a batch grid axis and
    # batched packing and unpacking.
    Bs = jnp.stack([_band(rng, 136, B8) for _ in range(2)])
    run = lambda B: bulge_wavefront_pallas(
        B, B8, group=4, return_log=True, strip=True, interpret=True
    )
    T_b, log_b = jax.vmap(run)(Bs)
    for i in range(2):
        T, log = run(Bs[i])
        np.testing.assert_array_equal(np.asarray(T_b[i]), np.asarray(T))
        for got, want in zip(log_b, log):
            np.testing.assert_array_equal(np.asarray(got[i]), np.asarray(want))


@pytest.mark.parametrize("fits", ["dense", "strip", "neither"])
def test_dispatch_follows_the_vmem_budget(monkeypatch, rng, fits):
    # From n ~ 390 on at b = 8 the strip holds fewer bytes than the matrix.
    n = 392
    dense = bulge_vmem_bytes(n, B8, group=4)
    strip = bulge_strip_vmem_bytes(n, B8, group=4)
    assert strip < dense
    budget, kernel = {
        "dense": (dense, ops.BULGE_DENSE),
        "strip": (dense - 1, ops.BULGE_STRIP),
        "neither": (strip - 1, None),
    }[fits]
    monkeypatch.setenv("REPRO_VMEM_BUDGET_BYTES", str(budget))
    assert limit("VMEM_BUDGET_BYTES") == budget
    assert ops.bulge_kernel(n, B8, group=4, interpret=True) == kernel
    Bb = _band(rng, n, B8)
    run = lambda B: ops.bulge_wavefront(B, B8, group=4, interpret=True)
    assert _kernel_names(run, Bb) == ({kernel} if kernel else set())
    T = run(Bb)
    # Entries drift apart with n under any change of operation order; the
    # spectrum does not.
    scale = float(jnp.abs(Bb).max())
    want = chase_wavefront(Bb, B8)
    np.testing.assert_allclose(_spectrum(T), _spectrum(want), atol=2e-4 * scale)
    if kernel == ops.BULGE_STRIP:
        dense = bulge_wavefront_pallas(Bb, B8, group=4, interpret=True)
        np.testing.assert_array_equal(np.asarray(T), np.asarray(dense))


def test_dispatch_at_4096_takes_the_strip(monkeypatch):
    # On the chip (no interpreter): the dense matrix is over the budget.
    for log in (False, True):
        assert ops.bulge_kernel(4096, B8, return_log=log, interpret=False) == ops.BULGE_STRIP
    monkeypatch.setenv("REPRO_VMEM_BUDGET_BYTES", str(bulge_vmem_bytes(4096, B8)))
    assert ops.bulge_kernel(4096, B8, interpret=False) == ops.BULGE_DENSE
    monkeypatch.setenv("REPRO_VMEM_BUDGET_BYTES", str(bulge_strip_vmem_bytes(4096, B8) - 1))
    assert ops.bulge_kernel(4096, B8, interpret=False) is None


@pytest.mark.parametrize("log", [False, True])
def test_dispatch_at_1024_keeps_the_dense_kernel(log):
    assert ops.bulge_kernel(1024, B8, return_log=log, interpret=False) == ops.BULGE_DENSE


def test_implied_interpretation_keeps_the_xla_executor_above_its_ceiling():
    # On the CPU with no explicit interpret flag: kernels only up to
    # BULGE_INTERPRET_MAX_N, the XLA executor above, never the strip.
    n = limit("BULGE_INTERPRET_MAX_N")
    assert ops.bulge_kernel(n, B8) == ops.BULGE_DENSE
    assert ops.bulge_kernel(n + 8, B8) is None


def test_strip_needs_tiles_within_two_row_blocks(rng):
    assert strip_fits_tile(8) and strip_fits_tile(40) and not strip_fits_tile(41)
    with pytest.raises(ValueError, match="band-strip"):
        bulge_wavefront_pallas(_band(rng, 200, 48), 48, strip=True, interpret=True)
