"""Kernel registry: ONE dispatch point from hot op -> implementation.

The EVD pipeline resolves five ops here:

* ``trailing_update`` — the DBR rank-2·nb syr2k trailing update
  (``C - Z Y^T - Y Z^T``), the first stage's step above the fused
  kernel's ceiling.
* ``fused_panel_update`` — one whole first-stage block step (panel QRs +
  trailing update fused, factors VMEM-resident); above the kernel's
  ceiling it runs the panel QRs and ``trailing_update`` in turn.
* ``bulge_wavefront`` — band -> tridiagonal grouped wavefront chasing,
  with an optional reflector log (eigenvectors stay on the kernel).
* ``backtransform_wy`` — the blocked compact-WY eigenvector back-transform
  (sweep-major grouped Q2 application; see ``repro.core.backtransform``).
* ``stage_mark``      — a named no-op kernel marking a stage boundary on the
  device timeline (``repro.kernels.mark``); the identity under ``jnp``.

Each op maps to one of two backends:

* ``"pallas"`` — the Pallas TPU kernels in ``repro.kernels`` (compiled on
  TPU, interpret-mode on CPU — see ``repro.backend.probe``), with
  per-platform tile-size defaults chosen here.
* ``"jnp"``    — the pure jnp/XLA reference path.  Always available; doubles
  as the numerical-parity oracle for the Pallas path.

Resolution order: programmatic override (:func:`set_backend` /
:func:`use_backend`) > ``REPRO_KERNEL_BACKEND`` env var > ``"pallas"``.
Future backends (GPU pallas, pure-XLA variants, distributed) plug in via
:func:`register`.
"""
from __future__ import annotations

import os
from contextlib import contextmanager
from typing import Callable, Dict, Optional, Tuple

__all__ = [
    "ENV_VAR",
    "BACKENDS",
    "OPS",
    "default_backend",
    "set_backend",
    "use_backend",
    "validate_backend",
    "resolve",
    "register",
    "tile_defaults",
]

ENV_VAR = "REPRO_KERNEL_BACKEND"
BACKENDS = ("pallas", "jnp")  # built-ins; register() can add more names
OPS = (
    "trailing_update",
    "fused_panel_update",
    "bulge_wavefront",
    "backtransform_wy",
    "stage_mark",
)

_override: Optional[str] = None
_extra_backends: set = set()

def tile_defaults(op: str, platform: Optional[str] = None) -> dict:
    """Default tile sizes for ``op`` on ``platform`` (default: the live one).

    The authoritative table lives with the rest of the planning-time size
    decisions in ``repro.solver.autotune``; this delegate keeps the
    historical registry entry point working.  (Deferred import: the solver
    package imports ``repro.backend`` at module scope.)
    """
    from repro.solver.autotune import tile_defaults as _solver_tiles

    return _solver_tiles(op, platform)


def _validate(backend: str) -> str:
    if backend not in BACKENDS and backend not in _extra_backends:
        known = tuple(BACKENDS) + tuple(sorted(_extra_backends))
        raise ValueError(f"unknown kernel backend {backend!r}; expected one of {known}")
    return backend


def validate_backend(backend: str) -> str:
    """Public name-check for backend strings (used by repro.solver.plan)."""
    return _validate(backend)


def default_backend() -> str:
    """The backend ops resolve to when no explicit backend is requested."""
    if _override is not None:
        return _override
    env = os.environ.get(ENV_VAR)
    if env:
        return _validate(env)
    return "pallas"


def set_backend(backend: Optional[str]) -> None:
    """Process-wide programmatic override (``None`` restores env/auto)."""
    global _override
    _override = None if backend is None else _validate(backend)


@contextmanager
def use_backend(backend: Optional[str]):
    """Scoped backend override (trace-time dispatch; use around jit entry)."""
    global _override
    prev = _override
    set_backend(backend)
    try:
        yield
    finally:
        _override = prev


# ------------------------------------------------------------ implementations
_IMPLS: Dict[Tuple[str, str], Callable] = {}
_built = False


def register(op: str, backend: str, fn: Callable) -> None:
    """Register/replace an implementation (the future-backend plug point).

    A backend name registered here becomes valid for :func:`resolve`,
    :func:`set_backend`, and the env var.
    """
    if op not in OPS:
        raise KeyError(f"unknown op {op!r}; expected one of {OPS}")
    if backend not in BACKENDS:
        _extra_backends.add(backend)
    _IMPLS[(op, backend)] = fn


def _build_impls() -> None:
    # Deferred so that importing repro.backend never drags in the kernels
    # (and to break the kernels -> compat -> registry import cycle).
    global _built
    from repro.kernels import ref as kref
    from repro.core.backtransform import backtransform_wy_xla
    from repro.core.bulge_chasing import chase_wavefront

    def default(op, backend, fn):
        # setdefault semantics: a register() call made before the first
        # resolve (the documented plug point) must not be clobbered.
        if (op, backend) not in _IMPLS:
            register(op, backend, fn)

    default("trailing_update", "jnp", kref.trailing_update_ref)
    default("fused_panel_update", "jnp", kref.fused_panel_update_ref)
    default("bulge_wavefront", "jnp", chase_wavefront)
    default("backtransform_wy", "jnp", backtransform_wy_xla)
    default("stage_mark", "jnp", lambda tile, stage: tile)

    from repro.kernels import ops as kops

    def pallas_trailing_update(C, Y, Z):
        return kops.trailing_update(C, Y, Z, **tile_defaults("trailing_update"))

    def pallas_fused_panel_update(Bv, b, w):
        return kops.fused_panel_update(
            Bv, b, w, **tile_defaults("fused_panel_update")
        )

    def pallas_bulge_wavefront(B, b, *, return_log=False):
        return kops.bulge_wavefront(B, b, return_log=return_log)

    default("trailing_update", "pallas", pallas_trailing_update)
    default("fused_panel_update", "pallas", pallas_fused_panel_update)
    default("bulge_wavefront", "pallas", pallas_bulge_wavefront)
    default("backtransform_wy", "pallas", kops.backtransform_wy)
    default("stage_mark", "pallas", kops.stage_mark)

    # Only mark built on success: a failed import above propagates, stays
    # unbuilt, and is retried (surfacing the real error) on the next resolve.
    _built = True


def resolve(op: str, backend: Optional[str] = None) -> Callable:
    """Resolve ``op`` to a callable for ``backend`` (default: the active one).

    Resolution happens at trace time — inside ``jit`` the chosen kernel is
    baked into the compiled program, so overrides must wrap the jit entry.
    """
    if op not in OPS:
        raise KeyError(f"unknown op {op!r}; expected one of {OPS}")
    be = default_backend() if backend is None else _validate(backend)
    if not _built:
        _build_impls()
    impl = _IMPLS.get((op, be))
    if impl is None:
        raise KeyError(
            f"no implementation registered for op {op!r} on backend {be!r}"
            f" (registered: {sorted(k for k in _IMPLS if k[0] == op)})"
        )
    return impl
