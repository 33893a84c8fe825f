"""Plan/execute split for the symmetric EVD pipeline.

    cfg = EvdConfig(spectrum=by_count(8))        # how to solve
    pl  = plan(n, jnp.float32, cfg)              # resolve + cache
    w, V = pl(A)                                 # execute (jit-cached)

``plan`` resolves everything shape-dependent ONCE — blocking from the
per-platform autotuning table, the kernel backend, the bisection budget,
the spectrum index window — into a frozen, hashable :class:`EvdPlan`.
Plans are cached process-wide: the same (n, dtype, config) always returns
the SAME object, and execution jits with the plan as a static argument, so
repeated same-shape calls never retrace (the cuSOLVER handle/workspace
model, minus the manual workspace bookkeeping).

Partial-spectrum plans (``spectrum=by_index/by_count``) bisect only the
selected index window and run inverse iteration for only those columns —
the eigenvector phase scales with k, not n.

``repro.core.eigh`` keeps the legacy kwarg API as thin wrappers over this
module.  Imports of the pipeline stages are deferred (``_deps``) so that
``repro.solver`` and ``repro.core`` can import in either order.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
from collections import Counter
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.backend import probe, registry

from .autotune import backtransform_group, resolve_blocking, tile_defaults, wavefront_group
from .config import EvdConfig, Spectrum

__all__ = [
    "EvdPlan",
    "StagePath",
    "plan",
    "plan_for",
    "clear_plan_cache",
    "plan_cache_size",
    "trace_count",
    "tridiagonalize",
]

_DEFAULT_BISECT_ITERS = 48

# Every dot the pipeline traces — XLA stages and Pallas kernel bodies alike —
# runs at this precision.  On a TPU, fp32 dots otherwise default to one bf16
# pass, which misses the n·eps residual bounds by orders of magnitude.
MATMUL_PRECISION = "highest"


class _Deps:
    """Lazily-bound pipeline stages (breaks the solver <-> core import cycle)."""

    _mod = None

    def __getattr__(self, name):
        if _Deps._mod is None:
            from repro.core import backtransform as bt
            from repro.core import band_reduction, bulge_chasing, direct_tridiag
            from repro.core import jacobi, tridiag_eig

            class _M:
                band_reduce = staticmethod(band_reduction.band_reduce)
                apply_q_left_blocked = staticmethod(bt.apply_q_left_blocked)
                apply_q2_blocked = staticmethod(bt.apply_q2_blocked)
                band_to_tridiag = staticmethod(bulge_chasing.band_to_tridiag)
                extract_tridiag = staticmethod(bulge_chasing.extract_tridiag)
                direct_tridiagonalize = staticmethod(direct_tridiag.direct_tridiagonalize)
                apply_q_direct = staticmethod(direct_tridiag.apply_q_direct)
                jacobi_eigh = staticmethod(jacobi.jacobi_eigh)
                eigvalsh_tridiag_range = staticmethod(tridiag_eig.eigvalsh_tridiag_range)
                eigvecs_inverse_iteration = staticmethod(
                    tridiag_eig.eigvecs_inverse_iteration
                )

            _Deps._mod = _M
        return getattr(_Deps._mod, name)


_deps = _Deps()


# ------------------------------------------------------------------ pipeline
def _end_stage(stage: str, outs):
    """Mark the end of ``stage`` on the device timeline; ``outs`` unchanged.

    ``outs`` pass through an optimization barrier, a ``stage_mark`` kernel
    (``evd_mark_<stage>``) takes a tile cut from them, and ``outs`` and the
    mark pass through a second barrier: the mark runs after every operation
    that produces ``outs`` and before any operation that consumes them.
    Under the ``jnp`` registry backend the mark is the identity and no
    kernel is emitted.
    """
    from repro.kernels.mark import MARK_TILE

    outs = lax.optimization_barrier(outs)
    first = jax.tree_util.tree_leaves(outs)[0]   # never empty: n, k >= 1
    tile = jnp.broadcast_to(first.reshape(-1)[0].astype(jnp.float32), MARK_TILE)
    marked = registry.resolve("stage_mark")(tile, stage)
    outs, _ = lax.optimization_barrier((outs, marked))
    return outs


def _tridiag_pipeline(A, *, b, nb, method, return_reflectors=False):
    """Reduce symmetric A to tridiagonal (d, e).

    With ``return_reflectors`` the two-stage method also returns the
    T-merged Q1 reflectors and the chase log that the blocked
    back-transform applies.  The two-stage method runs its stages under
    ``jax.named_scope`` ``evd.first_stage`` and ``evd.bulge_chase`` and
    marks the end of each (:func:`_end_stage`); the direct method is one
    stage of its caller's.
    """
    if method == "direct":
        T, refl = _deps.direct_tridiagonalize(A, return_reflectors=True)
        d, e = _deps.extract_tridiag(T)
        if return_reflectors:
            return d, e, ("direct", refl)
        return d, e

    if not return_reflectors:
        with jax.named_scope("evd.first_stage"):
            Bband = _deps.band_reduce(A, b, nb)
        Bband = _end_stage("first_stage", Bband)
        with jax.named_scope("evd.bulge_chase"):
            T = _deps.band_to_tridiag(Bband, b)
            d, e = _deps.extract_tridiag(T)
        return _end_stage("bulge_chase", (d, e))

    with jax.named_scope("evd.first_stage"):
        Bband, refl1 = _deps.band_reduce(
            A, b, nb, return_reflectors=True, merge_ts=True
        )
    Bband, refl1 = _end_stage("first_stage", (Bband, refl1))
    with jax.named_scope("evd.bulge_chase"):
        T, log2 = _deps.band_to_tridiag(Bband, b, return_log=True)
        d, e = _deps.extract_tridiag(T)
    d, e, refl1, log2 = _end_stage("bulge_chase", (d, e, refl1, log2))
    return d, e, ("two_stage", (refl1, log2))


def _backtransform(kind_refl, X: jax.Array, carry=(), *, group: int = 0):
    """x_A = Q x_T where Q is the accumulated tridiagonalization transform.

    The two-stage transform runs the compact-WY GEMM subsystem
    (``repro.core.backtransform``): Q2 through the registry
    ``backtransform_wy`` op with WY group size ``group``, Q1 through the
    per-block T-merged appliers.  Q2 and Q1 run under ``jax.named_scope``
    ``evd.backtransform_q2`` / ``evd.backtransform_q1``, each end marked;
    ``carry`` passes through the marks with ``X``, so that nothing that
    reads it runs before the last.  Returns ``(X, carry)``.
    """
    kind, refl = kind_refl
    if kind == "direct":
        return _deps.apply_q_direct(refl, X, transpose=False), carry
    refl1, log2 = refl
    with jax.named_scope("evd.backtransform_q2"):
        X = _deps.apply_q2_blocked(log2, X, transpose=False, group=group or None)
    X, refl1, carry = _end_stage("backtransform_q2", (X, refl1, carry))
    with jax.named_scope("evd.backtransform_q1"):
        X = _deps.apply_q_left_blocked(refl1, X, transpose=False)
    return _end_stage("backtransform_q1", (X, carry))


def tridiagonalize(
    A: jax.Array,
    *,
    b: Optional[int] = None,
    nb: Optional[int] = None,
    method: str = "two_stage",
    return_reflectors: bool = False,
):
    """Symmetric A -> (d, e) tridiagonal, optionally with back-transform data.

    Legacy-compatible entry point (blocking resolved through the autotune
    table).  Returns ``(d, e)`` or ``(d, e, backtransform_data)``.
    """
    n = A.shape[0]
    if method == "direct":
        return _tridiag_pipeline(
            A, b=1, nb=1, method="direct", return_reflectors=return_reflectors
        )
    if method != "two_stage":
        raise ValueError(f"unknown tridiagonalization method: {method}")
    dec = resolve_blocking(n, b=b, nb=nb)
    eff = "direct" if dec.b <= 1 else "two_stage"
    return _tridiag_pipeline(
        A, b=dec.b, nb=dec.nb, method=eff, return_reflectors=return_reflectors
    )


# ------------------------------------------------------------------ the plan
XLA = "xla"


@dataclasses.dataclass(frozen=True)
class StagePath:
    """Where one stage of a solve runs, as ``repro.kernels.ops.*_uses_kernel``
    decide it when the plan is made: ``kernel`` names the Pallas kernel, or
    is ``"xla"`` for the XLA path.  ``widths`` are the first stage's trailing
    widths m that take it; ``eigenvectors`` None holds for values-only and
    eigenvector solves alike, True or False for one of them."""

    stage: str
    kernel: str
    widths: Tuple[int, ...] = ()
    eigenvectors: Optional[bool] = None

    def describe(self) -> str:
        out = self.kernel
        if self.widths:
            out += " m=" + ",".join(map(str, self.widths))
        if self.eigenvectors is not None:
            out += " (vectors)" if self.eigenvectors else " (values)"
        return out


def _stage_paths(n, b, nb, k, *, platform, bt_group):
    """The kernel-versus-XLA record of a two-stage plan on the Pallas backend."""
    from repro.core.backtransform import _sweep_shape
    from repro.core.band_reduction import build_stage_schedule
    from repro.kernels import ops

    bm = tile_defaults("fused_panel_update", platform)["bm"]
    first: Dict[str, list] = {}
    for e in build_stage_schedule(n, b, nb).entries:
        fused = ops.fused_uses_kernel(e.m, e.w, b, bm=bm)
        first.setdefault("fused_panel_update" if fused else "syr2k_lower", []).append(e.m)
    paths = [StagePath("first_stage", kern, tuple(ms)) for kern, ms in first.items()]

    for vectors in (False, True):
        kernel = ops.bulge_kernel(n, b, return_log=vectors)
        paths.append(StagePath("bulge_chase", kernel or XLA, eigenvectors=vectors))

    kernel = (
        0 not in _sweep_shape(n, b)
        and ops.backtransform_uses_kernel(n, k, b, group=bt_group or None)
    )
    paths.append(StagePath(
        "backtransform_q2", "backtransform_wy" if kernel else XLA, eigenvectors=True
    ))
    return tuple(paths)


@dataclasses.dataclass(frozen=True)
class EvdPlan:
    """A fully-resolved, cached, executable EVD solver for one (n, dtype).

    Hashable and frozen: the plan itself is the jit static argument, so one
    plan == one trace.  Call it: ``w, V = plan(A)``; ``w = plan.eigvals(A)``;
    ``X = plan.inverse_pth_root(A, p)``.
    """

    n: int
    dtype: str                       # canonical dtype name ("float32", ...)
    config: EvdConfig
    b: int                           # resolved bandwidth (0: not applicable)
    nb: int                          # resolved update block
    bisect_iters: int
    backend: str                     # resolved kernel backend
    platform: str
    fallback_reason: Optional[str] = None
    bt_group: int = 0                # blocked back-transform WY group size G
                                     # (0: back-transform not applicable)
    paths: Tuple[StagePath, ...] = ()  # kernel-versus-XLA record (Pallas
                                       # backend, two-stage method only)

    # ---- derived views ----------------------------------------------------
    @property
    def method(self) -> str:
        """Effective method (``direct`` when blocking degenerated)."""
        if self.config.method == "two_stage" and self.b <= 1:
            return "direct"
        return self.config.method

    @property
    def spectrum_range(self) -> Tuple[int, int]:
        """(start, count) into the ascending spectrum."""
        return self.config.spectrum.index_range(self.n)

    @property
    def k(self) -> int:
        """Number of eigenpairs this plan computes."""
        return self.spectrum_range[1]

    # ---- execution --------------------------------------------------------
    def _check_operand(self, A: jax.Array) -> None:
        if A.shape[-2:] != (self.n, self.n):
            raise ValueError(
                f"plan built for n={self.n}, got operand shape {A.shape}; "
                f"use plan_for(A, config) to plan from the array"
            )
        got = jnp.dtype(A.dtype).name
        if got != self.dtype:
            raise ValueError(f"plan built for dtype {self.dtype}, got {got}")

    def __call__(self, A: jax.Array, *, eigenvectors: bool = True):
        """Execute: returns ``(w, V)`` or ``w``; ``w`` ascending, shape (k,),
        ``V`` shape (n, k) with ``A @ V ≈ V @ diag(w)``."""
        self._check_operand(A)
        return _execute(A, pl=self, eigenvectors=eigenvectors)

    def eigvals(self, A: jax.Array) -> jax.Array:
        self._check_operand(A)
        return _execute(A, pl=self, eigenvectors=False)

    def inverse_pth_root(self, A: jax.Array, p: int, *, eps: float = 1e-6):
        """A^{-1/p} for symmetric PSD A (the Shampoo preconditioner kernel)."""
        if not self.config.spectrum.is_full:
            raise ValueError(
                "inverse_pth_root needs the full spectrum; this plan selects "
                f"{self.config.spectrum}"
            )
        self._check_operand(A)
        # Ridge in the operand dtype: a float32 eps would silently promote /
        # downcast mid-pipeline for float64 plans.
        return _inverse_pth_root(A, jnp.asarray(eps, self.dtype), pl=self, p=p)

    def describe(self) -> str:
        parts = [
            f"EvdPlan(n={self.n}, {self.dtype}, method={self.method}, "
            f"b={self.b}, nb={self.nb}, backend={self.backend}, "
            f"platform={self.platform}, k={self.k}/{self.n}"
            + (f", backtransform G={self.bt_group}" if self.bt_group else "")
            + ")"
        ]
        if self.fallback_reason:
            parts.append(f"  fallback: {self.fallback_reason}")
        stages: Dict[str, list] = {}
        for p in self.paths:
            stages.setdefault(p.stage, []).append(p.describe())
        chase_kernel = any(p.stage == "bulge_chase" and p.kernel != XLA for p in self.paths)
        for stage, ds in stages.items():
            parts.append(f"  {stage}: " + "; ".join(ds))
            if stage == "bulge_chase" and chase_kernel:
                parts.append(self._chase_slots())
        if self.method != "jacobi":
            from repro.core.tridiag_eig import INVERSE_ITERATION_STEPS

            parts.append(f"  inverse_iteration: {INVERSE_ITERATION_STEPS} steps (vectors)")
        return "\n".join(parts)

    def _chase_slots(self) -> str:
        """The chase kernel's window updates: live slots against the
        ``(W, S*G)`` rectangle, and the slots it visits."""
        from repro.kernels.bulge import chase_slot_counts

        group = wavefront_group(self.n, self.b, self.platform)
        live, visited, rect = chase_slot_counts(self.n, self.b, group)
        return (
            f"  bulge_chase slots: {live} of {rect} "
            f"({100 * live / rect:.1f}%), {visited} visited"
        )

    def kernels(self, eigenvectors: bool) -> frozenset:
        """Names of the Pallas kernels of the stages a solve dispatches to
        (values only, or with ``eigenvectors``), read from :attr:`paths`."""
        return frozenset(
            p.kernel for p in self.paths
            if p.kernel != XLA and p.eigenvectors in (None, eigenvectors)
        )


# ------------------------------------------------------------------ planning
_PLAN_CACHE: Dict[tuple, EvdPlan] = {}


def _bisect_iters(tol: Optional[float]) -> int:
    if tol is None:
        return _DEFAULT_BISECT_ITERS
    # Bisection halves the bracket each iteration; tol is relative to the
    # initial Gershgorin span.
    return max(8, min(64, int(math.ceil(math.log2(1.0 / tol))) + 1))


def plan(n: int, dtype, config: EvdConfig = EvdConfig()) -> EvdPlan:
    """Resolve ``config`` for an (n, n) ``dtype`` problem.  Cached: repeated
    calls with equal arguments return the identical :class:`EvdPlan` object.
    """
    if n < 1:
        raise ValueError(f"n must be >= 1, got {n}")
    dtype_name = jnp.dtype(dtype).name
    platform = probe.platform()
    if config.backend is None:
        backend = registry.default_backend()
    else:
        backend = registry.validate_backend(config.backend)
    key = (n, dtype_name, config, backend, platform)
    cached = _PLAN_CACHE.get(key)
    if cached is not None:
        return cached

    config.spectrum.index_range(n)  # validate the selection against n early
    if config.method == "two_stage":
        dec = resolve_blocking(n, b=config.b, nb=config.nb, platform=platform)
        b, nb, reason = dec.b, dec.nb, dec.fallback_reason
    else:
        b, nb, reason = 0, 0, None
    bt_group = 0
    if config.method == "two_stage" and b > 1:
        bt_group = backtransform_group(n, b, platform)
    paths = ()
    if config.method == "two_stage" and b > 1 and backend == "pallas":
        paths = _stage_paths(
            n, b, nb, config.spectrum.index_range(n)[1],
            platform=platform, bt_group=bt_group,
        )

    pl = EvdPlan(
        n=n,
        dtype=dtype_name,
        config=config,
        b=b,
        nb=nb,
        bisect_iters=_bisect_iters(config.tol),
        backend=backend,
        platform=platform,
        fallback_reason=reason,
        bt_group=bt_group,
        paths=paths,
    )
    _PLAN_CACHE[key] = pl
    return pl


def plan_for(A: jax.Array, config: EvdConfig = EvdConfig()) -> EvdPlan:
    """Plan from an array's trailing (n, n) shape and dtype (vmap-safe)."""
    if A.ndim < 2 or A.shape[-1] != A.shape[-2]:
        raise ValueError(f"expected a square trailing shape, got {A.shape}")
    return plan(A.shape[-1], A.dtype, config)


def clear_plan_cache() -> None:
    _PLAN_CACHE.clear()


def plan_cache_size() -> int:
    return len(_PLAN_CACHE)


# ------------------------------------------------------------------ execution
# Python-side trace counter: the jitted bodies below only run while tracing,
# so incrementing here counts traces, not executions (tests rely on this to
# prove the no-retrace property).
_TRACE_COUNTS: Counter = Counter()


def trace_count(pl=None) -> int:
    """Traces recorded for ``pl`` — an :class:`EvdPlan` or a
    :class:`~repro.solver.batch.BatchPlan` — or all plans when None."""
    if pl is None:
        return sum(_TRACE_COUNTS.values())
    return sum(v for (p, _), v in _TRACE_COUNTS.items() if p == pl)


@partial(jax.jit, static_argnames=("pl", "eigenvectors"))
def _execute(A: jax.Array, *, pl: EvdPlan, eigenvectors: bool):
    _TRACE_COUNTS[(pl, eigenvectors)] += 1
    # The backend is baked into the plan (and thus the jit cache key); the
    # scoped pin makes trace-time registry dispatch match it.
    with registry.use_backend(pl.backend), jax.default_matmul_precision(MATMUL_PRECISION):
        A = _end_stage("begin", A)
        if pl.method == "two_stage":
            with jax.named_scope("evd.first_stage"):
                A = 0.5 * (A + A.T)  # enforce symmetry
            return _solve(A, pl=pl, eigenvectors=eigenvectors, staged=True)
        # The one-stage methods: one scope and one end mark, named after
        # the method.
        with jax.named_scope(f"evd.{pl.method}"):
            A = 0.5 * (A + A.T)
            out = _solve(A, pl=pl, eigenvectors=eigenvectors, staged=False)
        return _end_stage(pl.method, out)


def _solve(A: jax.Array, *, pl: EvdPlan, eigenvectors: bool, staged: bool):
    """The solve after symmetrisation.  ``staged`` (the two-stage method)
    scopes and marks bisection and inverse iteration; each mark also carries
    the values later stages read, so none of their work runs before it."""
    start, count = pl.spectrum_range
    if pl.method == "jacobi":
        w, V = _deps.jacobi_eigh(A, max_sweeps=pl.config.max_sweeps)
        w = w[start : start + count]
        if not eigenvectors:
            return w
        return w, V[:, start : start + count]

    def scope(name):
        return jax.named_scope(f"evd.{name}") if staged else contextlib.nullcontext()

    def mark(name, outs):
        return _end_stage(name, outs) if staged else outs

    d, e, *refl = _tridiag_pipeline(
        A, b=pl.b, nb=pl.nb, method=pl.method, return_reflectors=eigenvectors
    )
    with scope("bisection"):
        w = _deps.eigvalsh_tridiag_range(
            d, e, start=start, count=count, max_iter=pl.bisect_iters
        )
    if not eigenvectors:
        return mark("bisection", w)
    kind, refl = refl[0]
    w, d, e, refl = mark("bisection", (w, d, e, refl))
    # Partial spectrum: inverse iteration runs ONE lane per selected
    # eigenvalue — the eigenvector phase (inverse iteration AND the
    # back-transform, whose panels are (rows, k)) costs O(k), not O(n).
    with scope("inverse_iteration"):
        VT = _deps.eigvecs_inverse_iteration(d, e, w)
    VT, w, refl = mark("inverse_iteration", (VT, w, refl))
    V, w = _backtransform((kind, refl), VT, w, group=pl.bt_group)
    return w, V


@partial(jax.jit, static_argnames=("pl", "p"))
def _inverse_pth_root(A: jax.Array, eps: jax.Array, *, pl: EvdPlan, p: int):
    _TRACE_COUNTS[(pl, f"inv{p}")] += 1
    w, V = _execute(A, pl=pl, eigenvectors=True)
    with jax.named_scope("evd.root"):
        wmax = jnp.maximum(jnp.max(w), 0.0)
        ridge = eps * jnp.maximum(wmax, 1e-30)
        w_safe = jnp.maximum(w, 0.0) + ridge
        root = jnp.power(w_safe, -1.0 / p)
        with jax.default_matmul_precision(MATMUL_PRECISION):
            X = (V * root[None, :]) @ V.T
    with registry.use_backend(pl.backend):
        return _end_stage("root", X)
