"""The bulge-chase kernel walks only the live slots of each wavefront.

Slot ``a`` of wavefront ``w`` chases sweep ``s = w//3 - a`` at step
``k = w - 3s``; the kernel visits the closed-form range ``[lo(w), hi(w)]``
of :func:`repro.kernels.bulge._live_slots` and no other slot.  The range is
checked against the slot predicate of the XLA wavefront executor, and the
kernel's tridiagonal and log against that executor.
"""
import numpy as np
import pytest
import jax.numpy as jnp
import scipy.linalg as sla

from repro.core import apply_q2, chase_wavefront, extract_tridiag
from repro.core.bulge_chasing import ChaseLog, max_active_sweeps, num_wavefronts
from repro.kernels.bulge import _live_slots, bulge_wavefront_pallas, chase_slot_counts
from repro.solver import EvdConfig, plan
from repro.solver.autotune import wavefront_group

B8 = 8


def _active(n, b):
    """(W, A) mask of the live slots, by the executor's predicate."""
    W, A = num_wavefronts(n, b), max_active_sweeps(n, b)
    w = np.arange(W)[:, None]
    a = np.arange(A)[None, :]
    s = w // 3 - a
    k = w - 3 * s
    kmax = (n - 3 - np.clip(s, 0, n - 3)) // b
    return (s >= 0) & (s <= n - 3) & (k >= 0) & (k <= kmax)


@pytest.mark.parametrize("b", [2, 4, 8, 16])
def test_closed_form_range_is_the_live_set(b):
    for n in [*range(3, 301), 1024]:
        act = _active(n, b)
        lo, hi = _live_slots(np.arange(act.shape[0]), n, b, act.shape[1], xp=np)
        a = np.arange(act.shape[1])[None, :]
        np.testing.assert_array_equal((a >= lo[:, None]) & (a <= hi[:, None]), act, err_msg=f"n={n}")


def test_slot_counts():
    # The two cells' chases: half of the (W, A) rectangle is live.
    assert chase_slot_counts(4096, B8, 1) == (1_049_600, 1_049_600, 2_112_160)
    assert chase_slot_counts(1024, B8, 1) == (65_792, 65_792, 134_816)
    live, visited, rect = chase_slot_counts(1024, B8, 4)
    assert live == 65_792 and live < visited < live + 3 * num_wavefronts(1024, B8)
    assert rect == num_wavefronts(1024, B8) * 44


def _band(rng, n, b):
    A = rng.normal(size=(n, n))
    A = A + A.T
    i, j = np.indices((n, n))
    A[np.abs(i - j) > b] = 0.0
    return jnp.asarray(A.astype(np.float32))


def _spectrum(T):
    d, e = (np.asarray(x, np.float64) for x in extract_tridiag(T))
    return np.sort(sla.eigvalsh_tridiagonal(d, e))


@pytest.mark.parametrize("strip", [False, True], ids=["dense", "strip"])
@pytest.mark.parametrize("group", [1, 4])
@pytest.mark.parametrize("n", [136, 160])
def test_kernel_log_holds_the_live_slots_alone(n, group, strip):
    # One matrix per n, whatever runs before.  Entries and reflectors drift
    # apart from the executor's by rounding, as far as each matrix's
    # conditioning lets them; the spectrum does not.
    Bb = _band(np.random.default_rng(n), n, B8)
    T, (vs, taus, row0) = bulge_wavefront_pallas(
        Bb, B8, group=group, return_log=True, strip=strip, interpret=True
    )
    scale = float(jnp.abs(Bb).max())
    np.testing.assert_allclose(
        _spectrum(T), _spectrum(chase_wavefront(Bb, B8)), atol=2e-4 * scale
    )

    vs, taus, row0 = (np.asarray(x) for x in (vs, taus, row0))
    act = _active(n, B8)
    A = act.shape[1]
    live = np.zeros(row0.shape, bool)
    live[:, :A] = act
    assert np.all(vs[~live] == 0) and np.all(taus[~live] == 0) and np.all(row0[~live] == n)

    # The live slots log the executor's reflectors: the same supports, and
    # with the kernel's tridiagonal they rebuild the band.
    _, log_x = chase_wavefront(Bb, B8, return_log=True)
    np.testing.assert_array_equal(row0[:, :A], np.asarray(log_x.row0))
    d, e = extract_tridiag(T)
    Tt = jnp.diag(d) + jnp.diag(e, -1) + jnp.diag(e, 1)
    log = ChaseLog(vs=jnp.asarray(vs), taus=jnp.asarray(taus), row0=jnp.asarray(row0), n=n, b=B8)
    Q2 = apply_q2(log, jnp.eye(n, dtype=jnp.float32))
    np.testing.assert_allclose(Q2 @ Tt @ Q2.T, Bb, atol=1e-4 * n * scale / 100)
    np.testing.assert_allclose(Q2.T @ Q2, np.eye(n), atol=1e-5)

    # The walk over the live range does not change the arithmetic: one bulge
    # a step gives the same bits as four.
    if group > 1:
        T1, log1 = bulge_wavefront_pallas(
            Bb, B8, group=1, return_log=True, strip=strip, interpret=True
        )
        np.testing.assert_array_equal(np.asarray(T), np.asarray(T1))
        for got, want in zip((vs, taus, row0), log1):
            np.testing.assert_array_equal(got[:, :A], np.asarray(want))


def test_plan_describes_the_chase_slots():
    pl = plan(64, jnp.float32, EvdConfig())
    assert pl.kernels(False) & {"bulge_chase_wavefront", "bulge_chase_strip"}
    lines = pl.describe().splitlines()
    live, visited, rect = chase_slot_counts(64, pl.b, wavefront_group(64, pl.b, pl.platform))
    line = f"  bulge_chase slots: {live} of {rect} ({100 * live / rect:.1f}%), {visited} visited"
    assert lines[lines.index(line) - 1].startswith("  bulge_chase: ")
    assert "bulge_chase slots" not in plan(64, jnp.float32, EvdConfig(method="jacobi")).describe()
