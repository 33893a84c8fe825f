"""Solver configuration: the plan/execute split's *what* half.

``EvdConfig`` is a frozen, hashable description of HOW an EVD should be
computed (method, blocking policy, kernel backend, tolerance, spectrum
selection).  It deliberately contains no shapes: the
same config can plan solvers for many (n, dtype) pairs.  ``Spectrum``
selects WHICH part of the spectrum to compute — vendor libraries (cuSOLVER
syevdx, LAPACK ``RANGE='I'``) and Keyes et al. 2021 treat partial-spectrum
selection as a first-class API concern, and on the two-stage pipeline a
partial request skips the unneeded inverse-iteration lanes entirely.

Both types are plain frozen dataclasses so they can serve as jit static
arguments and plan-cache keys.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

__all__ = ["Spectrum", "EvdConfig", "full_spectrum", "by_index", "by_count"]

METHODS = ("two_stage", "direct", "jacobi")


@dataclasses.dataclass(frozen=True)
class Spectrum:
    """Which eigenpairs to compute.  Construct via the classmethods.

    * ``Spectrum.all()``                 — the full spectrum (default).
    * ``Spectrum.by_index(lo, hi)``      — eigenvalues ``lo .. hi-1`` in the
      ascending order (half-open, Python-slice convention).
    * ``Spectrum.by_count(k, largest=)`` — the ``k`` largest (default) or
      smallest eigenpairs.

    Selected eigenvalues are always returned ascending; eigenvector column
    ``j`` pairs with eigenvalue ``j`` of the selection.
    """

    kind: str = "all"        # "all" | "index" | "count"
    lo: int = 0              # [lo, hi) for kind == "index"
    hi: int = 0
    k: int = 0               # for kind == "count"
    largest: bool = True

    @classmethod
    def all(cls) -> "Spectrum":
        return cls()

    @classmethod
    def by_index(cls, lo: int, hi: int) -> "Spectrum":
        if not (0 <= lo < hi):
            raise ValueError(f"by_index needs 0 <= lo < hi, got lo={lo}, hi={hi}")
        return cls(kind="index", lo=int(lo), hi=int(hi))

    @classmethod
    def by_count(cls, k: int, largest: bool = True) -> "Spectrum":
        if k < 1:
            raise ValueError(f"by_count needs k >= 1, got k={k}")
        return cls(kind="count", k=int(k), largest=bool(largest))

    @property
    def is_full(self) -> bool:
        return self.kind == "all"

    def index_range(self, n: int):
        """Resolve to ``(start, count)`` in the ascending spectrum of size n."""
        if self.kind == "all":
            return 0, n
        if self.kind == "index":
            if self.hi > n:
                raise ValueError(f"by_index({self.lo}, {self.hi}) out of range for n={n}")
            return self.lo, self.hi - self.lo
        if self.kind == "count":
            if self.k > n:
                raise ValueError(f"by_count(k={self.k}) out of range for n={n}")
            return (n - self.k, self.k) if self.largest else (0, self.k)
        raise ValueError(f"unknown spectrum kind {self.kind!r}")


# Module-level aliases for the common constructions (readable call sites:
# ``EvdConfig(spectrum=by_count(8))``).
full_spectrum = Spectrum.all
by_index = Spectrum.by_index
by_count = Spectrum.by_count


@dataclasses.dataclass(frozen=True)
class EvdConfig:
    """Frozen description of how to solve a symmetric EVD.

    * ``method``  — ``two_stage`` (the paper), ``direct`` (one-stage
      Householder baseline), ``jacobi`` (dense parallel Jacobi).
    * ``b, nb``   — bandwidth / update block.  ``None`` = resolved from the
      per-platform autotuning table at plan time (repro.solver.autotune).
    * ``backend`` — kernel-registry backend pin (``pallas`` | ``jnp`` | a
      registered name).  ``None`` = the process default at plan time.
    * ``spectrum``— which eigenpairs to compute (see :class:`Spectrum`).
    * ``tol``     — absolute bisection tolerance as a fraction of the
      Gershgorin span; ``None`` = iterate to float32 working precision.
    * ``max_sweeps`` — Jacobi sweep budget (ignored by other methods).
    """

    method: str = "two_stage"
    b: Optional[int] = None
    nb: Optional[int] = None
    backend: Optional[str] = None
    spectrum: Spectrum = Spectrum()
    tol: Optional[float] = None
    max_sweeps: int = 16

    def __post_init__(self):
        if self.method not in METHODS:
            raise ValueError(f"unknown method {self.method!r}; expected one of {METHODS}")
        if self.b is not None and self.b < 1:
            raise ValueError(f"b must be >= 1, got {self.b}")
        if self.nb is not None and self.nb < 1:
            raise ValueError(f"nb must be >= 1, got {self.nb}")
        if self.tol is not None and not (0.0 < self.tol < 1.0):
            raise ValueError(f"tol must be in (0, 1), got {self.tol}")

    def replace(self, **kw) -> "EvdConfig":
        return dataclasses.replace(self, **kw)
