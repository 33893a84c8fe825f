"""Per-platform autotuning tables: blocking for the two-stage pipeline and
tile sizes for the Pallas kernels.

This is the planning-time home for every "which sizes run fast here"
decision (Ballard–Demmel–Dumitriu: blocking belongs to a planning step, not
per-call kwargs).  Two tables live here:

* ``_BLOCKING_TABLE`` — (bandwidth b, update block nb) per platform and
  problem-size band.  The paper's tuning claim is exactly that decoupling
  nb from b lets a small bandwidth (cheap bulge chasing) coexist with a
  large update block (compute-bound trailing syr2k); bigger problems can
  afford bigger nb before the stage-1 panel work stops amortizing.
* ``_TILE_TABLE`` — Pallas kernel tile sizes.  ``repro.backend.registry``
  delegates its ``tile_defaults`` here so the solver plan and the kernel
  dispatch read one table.

``resolve_blocking`` applies the table (or explicit user values), then
clamps to feasibility: ``n % b == 0`` (halving b until it divides) and
``nb`` a multiple of ``b`` no larger than ``n``.  When b collapses to 1 —
odd/prime n with no power-of-two factor — the decision records an explicit
``fallback_reason`` instead of silently degrading, and the plan switches to
the direct one-stage reduction.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from repro.backend import probe

__all__ = [
    "BlockingDecision",
    "resolve_blocking",
    "blocking_defaults",
    "tile_defaults",
    "backtransform_group",
    "wavefront_group",
    "DEFAULT_B",
    "DEFAULT_NB",
    "DEFAULT_BT_GROUP",
    "DEFAULT_WAVEFRONT_GROUP",
]

DEFAULT_B = 8
DEFAULT_NB = 64

# platform -> ((n_upper_exclusive | None, b, nb), ...) scanned in order.
# TPU rows follow the paper's regime split: the MXU wants k = nb as large
# as the panel amortization allows, so nb grows with n; interpret-mode
# platforms (CPU oracle runs) keep nb modest so emulated grids stay cheap.
_BLOCKING_TABLE = {
    "tpu": (
        (256, 8, 64),
        (1024, 8, 128),
        (None, 8, 256),
    ),
    None: (  # any non-TPU platform
        (128, 8, 32),
        (None, 8, 64),
    ),
}

# Blocked back-transform WY group size G: each sweep's reflectors are
# applied in groups of G consecutive k's, i.e. contiguous (b·G)-row panel
# updates (repro.core.backtransform).  The TPU kernel wants wide resident
# panels (fewer in-VMEM slice round-trips); interpret/CPU platforms keep
# groups moderate so the unrolled per-sweep group loop stays cheap.
# (n_upper_exclusive | None, G) rows scanned in order, like the blocking
# table; G is clamped to the per-sweep reflector count at plan time.
DEFAULT_BT_GROUP = 8
_BT_GROUP_TABLE = {
    "tpu": (
        (1024, 8),
        (None, 16),
    ),
    None: (
        (None, 8),
    ),
}

# Fused-chase wavefront group size G: the bulge_wavefront kernel chases G
# independent bulges per iteration of its loop over a wavefront's live
# slots (repro.kernels.bulge).  On TPU each window update is VPU-bound, so
# one bulge per iteration wastes no update on a group's masked tail; under
# the interpreter every iteration costs a Python-level step, so grouping
# several bulges per iteration amortizes it.  (n_upper_exclusive | None, G) rows like the tables
# above; G is clamped to the wavefront's slot count at dispatch time.
DEFAULT_WAVEFRONT_GROUP = 1
_WAVEFRONT_GROUP_TABLE = {
    "tpu": (
        (None, 1),
    ),
    None: (  # interpret mode
        (None, 4),
    ),
}

# platform -> op -> tile kwargs (absorbed from repro.backend.registry; the
# registry's pallas wrappers call back into tile_defaults below).
_TILE_TABLE = {
    "tpu": {
        "trailing_update": dict(bm=256, bk=256),
        # Trailing tile of the fused panel+trailing kernel.  Smaller than
        # the standalone syr2k tile: the resident factor buffers (V/Z/F at
        # k = nb) share VMEM with the trailing view.
        "fused_panel_update": dict(bm=128),
    },
    None: {  # interpret mode: small tiles keep emulated grids cheap
        "trailing_update": dict(bm=128, bk=128),
        "fused_panel_update": dict(bm=64),
    },
}


def _platform_key(platform: Optional[str]) -> Optional[str]:
    plat = probe.platform() if platform is None else platform
    return plat if plat in _BLOCKING_TABLE else None


def blocking_defaults(n: int, platform: Optional[str] = None):
    """Table (b, nb) for an n x n problem on ``platform`` (default: live)."""
    rows = _BLOCKING_TABLE[_platform_key(platform)]
    for bound, b, nb in rows:
        if bound is None or n < bound:
            return b, nb
    return DEFAULT_B, DEFAULT_NB  # unreachable: tables end with a None bound


def tile_defaults(op: str, platform: Optional[str] = None) -> dict:
    """Default Pallas tile sizes for ``op`` on ``platform`` (default: live)."""
    plat = probe.platform() if platform is None else platform
    table = _TILE_TABLE.get(plat, _TILE_TABLE[None])
    return dict(table.get(op, {}))


def backtransform_group(n: int, b: int, platform: Optional[str] = None) -> int:
    """Back-transform WY group size G for an n x n problem at bandwidth b.

    Table value clamped to [1, K] with K the per-sweep reflector count —
    groups wider than a whole sweep buy nothing.
    """
    rows = _BT_GROUP_TABLE.get(_platform_key(platform), _BT_GROUP_TABLE[None])
    g = DEFAULT_BT_GROUP
    for bound, val in rows:
        if bound is None or n < bound:
            g = val
            break
    # Deferred import: repro.core pulls in repro.solver at package scope.
    from repro.core.backtransform import _sweep_shape

    _, K = _sweep_shape(n, b)
    return max(1, min(int(g), K))


def wavefront_group(n: int, b: int, platform: Optional[str] = None) -> int:
    """Bulge-chase wavefront group size G for an n x n problem at bandwidth b.

    Table value clamped to [1, A] with A the wavefront slot count — groups
    wider than a whole wavefront buy nothing.
    """
    rows = _WAVEFRONT_GROUP_TABLE.get(
        _platform_key(platform), _WAVEFRONT_GROUP_TABLE[None]
    )
    g = DEFAULT_WAVEFRONT_GROUP
    for bound, val in rows:
        if bound is None or n < bound:
            g = val
            break
    # Deferred import: repro.core pulls in repro.solver at package scope.
    from repro.core.bulge_chasing import max_active_sweeps

    return max(1, min(int(g), max_active_sweeps(n, b)))


@dataclasses.dataclass(frozen=True)
class BlockingDecision:
    """Resolved (b, nb) plus an explicit record of any degradation."""

    b: int
    nb: int
    fallback_reason: Optional[str] = None

    @property
    def degenerate(self) -> bool:
        return self.fallback_reason is not None


def resolve_blocking(
    n: int,
    b: Optional[int] = None,
    nb: Optional[int] = None,
    platform: Optional[str] = None,
) -> BlockingDecision:
    """Resolve blocking for an n x n two-stage reduction.

    Explicit ``b``/``nb`` win over the table; either may be None
    independently.  The CLAMPING rules match the historical
    ``_resolve_blocking`` exactly, so explicit-b/nb call sites see
    identical blocking; default-kwarg callers now get the per-platform
    table above instead of a flat nb=64 (that change is the point of the
    autotune layer).  A collapse to b == 1 carries a ``fallback_reason``.
    """
    tb, tnb = blocking_defaults(n, platform)
    requested_b = tb if b is None else int(b)
    nb = tnb if nb is None else int(nb)

    b = requested_b
    while b > 1 and n % b != 0:
        b //= 2
    b = max(b, 1)
    nb = max((min(nb, n) // b) * b, b)

    reason = None
    if b <= 1 and n > 2:
        reason = (
            f"blocking collapsed to b=1 (n={n} has no power-of-two factor of "
            f"requested b={requested_b}); using direct one-stage "
            f"tridiagonalization"
        )
    return BlockingDecision(b=b, nb=nb, fallback_reason=reason)
