#!/usr/bin/env python3
"""Smoke run of the two-stage symmetric EVD on a TPU, through its public API.

    python chip_smoke.py [--seed 0]              # one chip
    python chip_smoke.py --chips 4 [--seed 0]    # the sharded refresh only

One chip runs two phases at sizes users run:

* ``shampoo_refresh`` — a stacked (8, 1024, 1024) fp32 batch of Shampoo-like
  statistics ``S = G G^T / 256 + delta I`` through ``solve_many`` as the
  inverse 4th root and as eigenpairs;
* ``dense_4096`` — ``A = Q diag(lam) Q^T`` at n = 4096 with a geometric and
  a tightly clustered part of the spectrum (LAPACK ``xLATMS`` style), through
  ``plan(4096).eigvals`` and ``plan(4096)(A)``.

``--chips 4`` runs only the batch-sharded refresh: ``solve_many(...,
devices=mesh)`` on a (32, 1024, 1024) batch over four devices, against the
same call on the first device alone.

Every program line reports its compile seconds, the median of 3 steady
calls, ``plan.describe()``, the Pallas kernels in the compiled program and
its accuracy against a float64 host reference (eps = 2^-23):

* eigenvalues: ``max |lam_hat - lam| / ||A||_2 <= n eps``;
* eigenpairs: ``max_i ||A v_i - lam_i v_i|| / ||A||_2 <= n eps`` and
  ``||V^T V - I||_max <= n eps``;
* inverse roots: relative Frobenius error ``<= 1e-3``.

The last line is ``{"ok": true, "device": {...}}``.  The script exits
non-zero and prints no such line when JAX finds no TPU, when a bound fails,
or when a kernel that ``repro.kernels.ops`` dispatches at a program's
shapes is missing from its compiled program.  Data comes from ``--seed``;
everything runs in this one process.
"""
from __future__ import annotations

import argparse
import json
import re
import statistics
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

EPS = 2.0 ** -23
ROOT_TOL = 1e-3
SHAMPOO_N = 1024


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


# --------------------------------------------------------------------- data
def shampoo_stats(rng, batch: int, n: int):
    """(batch, n, n) statistics ``G G^T / rank + delta I`` in float64 with
    ``rank = n / 4`` and ``delta = 0.1 tr(G G^T / rank) / n``: low rank plus
    a cluster."""
    rank = n // 4
    out = np.empty((batch, n, n))
    for i in range(batch):
        G = rng.standard_normal((n, rank), dtype=np.float32).astype(np.float64)
        S = G @ G.T / rank
        S = 0.5 * (S + S.T)
        S[np.diag_indices(n)] += 0.1 * np.trace(S) / n
        out[i] = S
    return out


def dense_matrix(rng, n: int):
    """``Q diag(lam) Q^T`` in float64 with Q from the QR of a Gaussian; lam
    holds 3n/4 geometric magnitudes in [1e-4, 1] with random signs and an
    n/4 cluster at 0.5 of relative width 1e-6.  Returns (A, sorted lam)."""
    Q, _ = np.linalg.qr(rng.standard_normal((n, n)))
    k = 3 * n // 4
    geo = 1e-4 ** (np.arange(k) / (k - 1)) * rng.choice([-1.0, 1.0], size=k)
    cluster = 0.5 * (1.0 + 1e-6 * rng.uniform(size=n - k))
    lam = np.concatenate([geo, cluster])
    A = (Q * lam) @ Q.T
    return 0.5 * (A + A.T), np.sort(lam)


def inverse_root(S: np.ndarray, p: int, eps: float) -> np.ndarray:
    """float64 ``S^{-1/p}`` with the library's relative ridge."""
    w, V = np.linalg.eigh(S)
    ridge = eps * max(w.max(), 1e-30)
    return (V * (np.maximum(w, 0.0) + ridge) ** (-1.0 / p)) @ V.T


# ----------------------------------------------------------------- checking
def eigpair_errors(A: np.ndarray, w: np.ndarray, V: np.ndarray, norm: float):
    """(max residual / ||A||_2, ||V^T V - I||_max) in float64."""
    V = V.astype(np.float64)
    resid = np.linalg.norm(A @ V - V * w.astype(np.float64), axis=0).max() / norm
    orth = np.abs(V.T @ V - np.eye(V.shape[1])).max()
    return float(resid), float(orth)


def kernels_in(hlo: str) -> list:
    """Names of the Pallas kernels (``tpu_custom_call``) in a compiled program."""
    names = set()
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = re.match(r"\s*(?:ROOT\s+)?%([A-Za-z_]\w*?)(?:\.\d+)*\s*=", line)
            if m:
                names.add(m.group(1))
    return sorted(names)


def expected_kernels(n: int, config, eigenvectors: bool) -> set:
    """The kernels one (n, n) solve dispatches, as its plan records them
    (``EvdPlan.paths``, decided by ``repro.kernels.ops``)."""
    import jax.numpy as jnp

    from repro.solver import plan

    return set(plan(n, jnp.float32, config).kernels(eigenvectors))


class Phase:
    """Compile, time and check programs; remember every failed bound."""

    def __init__(self):
        self.failures = []

    def run(self, name: str, fn, args, *, expect: set, describe: str):
        """Compile ``fn`` for ``args``, run it once, time 3 steady calls.
        Returns the last result (device arrays)."""
        import jax

        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        compile_s = time.perf_counter() - t0
        kernels = kernels_in(compiled.as_text())
        missing = sorted(expect - set(kernels))
        jax.block_until_ready(compiled(*args))
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            out = jax.block_until_ready(compiled(*args))
            times.append(time.perf_counter() - t0)
        emit(
            program=name, compile_s=compile_s, steady_s=statistics.median(times),
            steady_runs_s=times, kernels=kernels, expected_kernels=sorted(expect),
            plan=describe,
        )
        if missing:
            self.failures.append(f"{name}: kernels {missing} missing from the program")
        return out

    def check(self, name: str, metric: str, value: float, bound: float) -> None:
        ok = bool(value <= bound)
        emit(program=name, metric=metric, value=value, bound=bound, ok=ok)
        if not ok:
            self.failures.append(f"{name}: {metric} = {value:.3e} > {bound:.3e}")


# ------------------------------------------------------------------- phases
def shampoo_refresh(ph: Phase, rng, n: int = SHAMPOO_N) -> None:
    import jax.numpy as jnp

    from repro.solver import EvdConfig, batch_plan, solve_many

    cfg = EvdConfig()
    S64 = shampoo_stats(rng, 8, n)
    S = jnp.asarray(S64.astype(np.float32))
    S64 = np.asarray(S, np.float64)  # the operand actually solved
    describe = batch_plan(n, S.shape[0], jnp.float32, cfg).describe()
    expect = expected_kernels(n, cfg, eigenvectors=True)

    X = np.asarray(ph.run(
        "shampoo_refresh.inverse_pth_root",
        lambda S: solve_many(S, cfg, op="inverse_pth_root", p=4),
        (S,), expect=expect, describe=describe,
    ))
    w, V = map(np.asarray, ph.run(
        "shampoo_refresh.eigh", lambda S: solve_many(S, cfg), (S,),
        expect=expect, describe=describe,
    ))
    resid, orth, root_err = [], [], []
    for i in range(S64.shape[0]):
        norm = np.abs(np.linalg.eigvalsh(S64[i])).max()
        r, o = eigpair_errors(S64[i], w[i], V[i], norm)
        resid.append(r)
        orth.append(o)
        ref = inverse_root(S64[i], 4, 1e-6)
        root_err.append(float(np.linalg.norm(X[i] - ref) / np.linalg.norm(ref)))
    ph.check("shampoo_refresh.eigh", "max_residual", max(resid), n * EPS)
    ph.check("shampoo_refresh.eigh", "max_orthogonality", max(orth), n * EPS)
    ph.check("shampoo_refresh.inverse_pth_root", "max_root_rel_fro", max(root_err), ROOT_TOL)


def dense_4096(ph: Phase, rng, n: int = 4096) -> None:
    import jax.numpy as jnp

    from repro.solver import EvdConfig, plan

    A64, lam = dense_matrix(rng, n)
    A = jnp.asarray(A64.astype(np.float32))
    A64 = np.asarray(A, np.float64)
    norm = np.abs(lam).max()
    pl = plan(n, jnp.float32, EvdConfig())

    w = np.asarray(ph.run(
        "dense_4096.eigvals", pl.eigvals, (A,),
        expect=expected_kernels(n, pl.config, eigenvectors=False),
        describe=pl.describe(),
    ))
    ph.check("dense_4096.eigvals", "max_eig_err", float(np.abs(w - lam).max() / norm), n * EPS)
    w, V = map(np.asarray, ph.run(
        "dense_4096.eigh", pl, (A,),
        expect=expected_kernels(n, pl.config, eigenvectors=True),
        describe=pl.describe(),
    ))
    ph.check("dense_4096.eigh", "max_eig_err", float(np.abs(w - lam).max() / norm), n * EPS)
    resid, orth = eigpair_errors(A64, w, V, norm)
    ph.check("dense_4096.eigh", "max_residual", resid, n * EPS)
    ph.check("dense_4096.eigh", "max_orthogonality", orth, n * EPS)


def sharded_refresh(ph: Phase, rng, devices, n: int = SHAMPOO_N) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.backend.compat import make_mesh
    from repro.solver import EvdConfig, batch_plan, solve_many

    cfg = EvdConfig()
    batch = 8 * len(devices)
    S32 = shampoo_stats(rng, batch, n).astype(np.float32)
    S64 = S32.astype(np.float64)
    mesh = make_mesh((len(devices),), ("x",), devices=devices)
    expect = expected_kernels(n, cfg, eigenvectors=True)

    S_mesh = jax.device_put(S32, NamedSharding(mesh, P("x", None, None)))
    X_mesh = ph.run(
        "sharded_refresh.mesh",
        lambda S: solve_many(S, cfg, op="inverse_pth_root", p=4, devices=mesh),
        (S_mesh,), expect=expect,
        describe=batch_plan(n, batch // len(devices), jnp.float32, cfg).describe(),
    )
    emit(
        program="sharded_refresh.mesh",
        per_device_shapes={str(s.device): list(s.data.shape) for s in X_mesh.addressable_shards},
    )
    X_mesh = np.asarray(X_mesh)
    S_one = jax.device_put(S32, devices[0])
    X_one = np.asarray(ph.run(
        "sharded_refresh.one_device",
        lambda S: solve_many(S, cfg, op="inverse_pth_root", p=4),
        (S_one,), expect=expect,
        describe=batch_plan(n, batch, jnp.float32, cfg).describe(),
    ))
    err_mesh, err_one = [], []
    for i in range(batch):
        ref = inverse_root(S64[i], 4, 1e-6)
        err_mesh.append(float(np.linalg.norm(X_mesh[i] - ref) / np.linalg.norm(ref)))
        err_one.append(float(np.linalg.norm(X_one[i] - ref) / np.linalg.norm(ref)))
    ph.check("sharded_refresh.mesh", "max_root_rel_fro", max(err_mesh), ROOT_TOL)
    ph.check("sharded_refresh.one_device", "max_root_rel_fro", max(err_one), ROOT_TOL)
    diff = float(np.linalg.norm(X_mesh - X_one) / np.linalg.norm(X_one))
    ph.check("sharded_refresh", "mesh_vs_one_device_rel_fro", diff, ROOT_TOL)


# --------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4 runs only the batch-sharded refresh over four devices",
    )
    args = ap.parse_args(argv)

    try:
        from repro.backend.cache import enable_compilation_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not next to this script ({e})", file=sys.stderr)
        return 2
    cache_dir = enable_compilation_cache()

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU; JAX found platform {platform!r}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found {len(devices)} device(s)",
              file=sys.stderr)
        return 1
    emit(jax=jax.__version__, devices=len(devices), kind=devices[0].device_kind,
         compilation_cache=cache_dir, seed=args.seed)

    rng = np.random.default_rng(args.seed)
    ph = Phase()
    if args.chips == 4:
        sharded_refresh(ph, rng, devices[:4])
    else:
        shampoo_refresh(ph, rng)
        dense_4096(ph, rng)

    if ph.failures:
        for f in ph.failures:
            print(f"chip_smoke: FAILED {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind, "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
