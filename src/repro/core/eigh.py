"""Legacy-compatible EVD entry points over the plan-based solver API.

The paper's full pipeline (DBR band reduction -> wavefront bulge chasing ->
parallel bisection + inverse iteration -> back-transform) now lives behind
``repro.solver``: a frozen :class:`~repro.solver.EvdConfig` plus a cached
:class:`~repro.solver.EvdPlan` carry every tuning decision from the user to
kernel dispatch.  This module keeps the historical kwarg surface —

    eigh(A, b=8, nb=64)            ==  plan_for(A, EvdConfig(b=8, nb=64))(A)
    eigvalsh(A)                    ==  plan.eigvals(A)
    inverse_pth_root(A, p)         ==  plan.inverse_pth_root(A, p)

— as thin wrappers: each call builds (or re-uses, via the plan cache) the
equivalent plan and executes it, so legacy callers share jit caches with
plan-API callers.  New code should prefer ``repro.solver`` directly,
especially for partial-spectrum requests (``spectrum=by_count(k)``).
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.solver import EvdConfig, plan_for, solve_many
from repro.solver.plan import tridiagonalize  # noqa: F401  (re-export)

__all__ = [
    "tridiagonalize",
    "eigh",
    "eigvalsh",
    "eigh_batched",
    "eigvalsh_batched",
    "inverse_pth_root",
]


def _as_config(
    config: Optional[EvdConfig],
    *,
    b: Optional[int],
    nb: Optional[int],
    method: str,
    max_sweeps: int = 16,
) -> EvdConfig:
    if config is not None:
        overridden = {
            k: v
            for k, v, default in (
                ("b", b, None), ("nb", nb, None), ("method", method, "two_stage"),
                ("max_sweeps", max_sweeps, 16),
            )
            if v != default
        }
        if overridden:
            raise ValueError(
                f"pass solver options via config=EvdConfig(...), not alongside "
                f"it: {overridden}"
            )
        return config
    return EvdConfig(method=method, b=b, nb=nb, max_sweeps=max_sweeps)


def eigh(
    A: jax.Array,
    *,
    config: Optional[EvdConfig] = None,
    b: Optional[int] = None,
    nb: Optional[int] = None,
    method: str = "two_stage",
    eigenvectors: bool = True,
    max_sweeps: int = 16,
):
    """Full symmetric eigendecomposition.  Eigenvalues ascending.

    Returns ``w`` or ``(w, V)`` with ``A @ V ≈ V @ diag(w)``.  Prefer the
    plan API (``repro.solver``) for repeated same-shape solves and
    partial-spectrum selection; this wrapper shares its caches.
    """
    cfg = _as_config(config, b=b, nb=nb, method=method, max_sweeps=max_sweeps)
    return plan_for(A, cfg)(A, eigenvectors=eigenvectors)


def eigvalsh(A: jax.Array, **kw) -> jax.Array:
    return eigh(A, eigenvectors=False, **kw)


def eigh_batched(
    A: jax.Array,
    *,
    config: Optional[EvdConfig] = None,
    eigenvectors: bool = True,
    b: Optional[int] = None,
    nb: Optional[int] = None,
    method: str = "two_stage",
    max_sweeps: int = 16,
):
    """eigh over a batch of matrices (..., n, n).

    Delegates to :func:`repro.solver.solve_many`: the plan is resolved ONCE
    for the whole batch (one cached ``BatchPlan``, one compile — not one
    plan resolution per vmap lane), so a batched call shares its executable
    with every other same-(n, batch, config) consumer.  Returns ``(w, V)``
    — or just ``w`` when called with ``eigenvectors=False`` (see also
    :func:`eigvalsh_batched`).
    """
    cfg = _as_config(config, b=b, nb=nb, method=method, max_sweeps=max_sweeps)
    return solve_many(A, cfg, eigenvectors=eigenvectors)


def eigvalsh_batched(A: jax.Array, **kw) -> jax.Array:
    """Eigenvalues-only batched solve over (..., n, n)."""
    return eigh_batched(A, eigenvectors=False, **kw)


def inverse_pth_root(
    A: jax.Array,
    p: int,
    *,
    eps: float = 1e-6,
    config: Optional[EvdConfig] = None,
    method: str = "two_stage",
    b: Optional[int] = None,
    nb: Optional[int] = None,
) -> jax.Array:
    """A^{-1/p} for symmetric PSD A — the Shampoo preconditioner kernel.

    Eigenvalues are ridged by ``eps * max(w)`` before the root, matching
    distributed-Shampoo practice.
    """
    cfg = _as_config(config, b=b, nb=nb, method=method)
    return plan_for(A, cfg).inverse_pth_root(A, p, eps=eps)
