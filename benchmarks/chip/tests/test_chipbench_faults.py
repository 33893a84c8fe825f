"""With the timed path broken underneath, a run's ``correct`` comes out
false: once for each fault a cell can have."""
import importlib

import jax
import jax.numpy as jnp
import pytest

from test_chipbench_run import run_small

plan_mod = importlib.import_module("repro.solver.plan")


def _answer_altered(monkeypatch):
    """The largest eigenvalue moved by 1e-3 of the norm where it is produced."""
    orig = plan_mod._execute

    def execute(A, *, pl, eigenvectors):
        w = orig(A, pl=pl, eigenvectors=eigenvectors)
        return w.at[-1].add(1e-3 * jnp.max(jnp.abs(w)))

    monkeypatch.setattr(plan_mod, "_execute", execute)


def _stage_returns_its_input(monkeypatch):
    """The bulge chase hands back the band unchanged."""
    bulge = importlib.import_module("repro.core.bulge_chasing")

    def band_to_tridiag(B, b, **kw):
        return (B, None) if kw.get("return_log") else B

    monkeypatch.setattr(plan_mod._Deps, "_mod", None)
    monkeypatch.setattr(bulge, "band_to_tridiag", band_to_tridiag)


FAULTS = [
    ("dense4096_eigvals", _answer_altered),
    ("dense4096_eigvals", _stage_returns_its_input),
]


@pytest.mark.parametrize("name,fault", FAULTS, ids=[f"{n}-{f.__name__[1:]}" for n, f in FAULTS])
def test_fault_is_caught(monkeypatch, name, fault):
    jax.clear_caches()  # the solver's jitted stages retrace with the fault in
    fault(monkeypatch)
    try:
        _, res, _ = run_small(name, seed=2**31 + 29)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert res["correct"] is False
    assert res["failed"] >= 1
