"""``solve_many`` — the one front door for every multi-matrix EVD consumer.

The paper's core observation is that small/medium symmetric EVDs are
memory-bound at <3% compute utilization; the regime that fills an
accelerator is *many matrices at once* (Shampoo preconditioner refreshes,
EVD-serving traffic).  This module turns that regime into a solver concern
instead of a caller concern:

    from repro.solver import EvdConfig, PadPolicy, solve_many

    # heterogeneous shapes: bucketed by n, one BatchPlan execution each,
    # results scattered back in input order
    results = solve_many([A32, A48, B32], EvdConfig())      # [(w,V), ...]

    # a stacked homogeneous batch: returns stacked (w, V)
    w, V = solve_many(As, EvdConfig())                      # As: (B, n, n)

    # Shampoo's refresh: batched inverse p-th roots, optionally sharded
    X = solve_many(stats, cfg, op="inverse_pth_root", p=4,
                   devices=(mesh, ("x",)))

Input is a pytree whose leaves are arrays with trailing square (n, n)
shapes (a single stacked array, a list of matrices, a dict of stacks, ...).
Matrices are grouped into shape buckets under a :class:`PadPolicy` —
optionally padded up to declared ``bucket_sizes`` with a ridge-identity
fill — each bucket executes as ONE cached :class:`BatchPlan` (one compile
per bucket, provable via ``trace_count``), and results are scattered back
into the input structure.  With the default exact policy the result is
bit-identical to a per-matrix ``EvdPlan`` loop on the jnp reference
backend (rounding-level on the Pallas default: interpreted kernels fuse
with surrounding ops, so vmap can perturb rounding).

``devices=`` routes every bucket through the ``jax.shard_map`` path
(batch sharded over the mesh, full solver local per device) — this is the
engine under ``repro.core.distributed.sharded_eigh_batch`` /
``sharded_inverse_roots``, which are now thin deprecated shims.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .batch import PadPolicy, batch_plan
from .config import EvdConfig, Spectrum
from .plan import MATMUL_PRECISION

__all__ = ["solve_many"]

_OPS = ("eigh", "eigvals", "inverse_pth_root")


# ------------------------------------------------------------- mesh plumbing
def _normalize_devices(devices) -> Optional[Tuple[Mesh, Tuple[str, ...]]]:
    """Accept a Mesh, a (mesh, axes) pair, or a flat device sequence."""
    if devices is None:
        return None
    if isinstance(devices, Mesh):
        return devices, tuple(devices.axis_names)
    if (
        isinstance(devices, (tuple, list))
        and len(devices) == 2
        and isinstance(devices[0], Mesh)
    ):
        mesh, axes = devices
        axes = (axes,) if isinstance(axes, str) else tuple(axes)
        return mesh, axes
    devs = tuple(devices)  # a flat sequence of jax devices
    if not devs:
        raise ValueError("devices= was an empty sequence")
    mesh = Mesh(np.asarray(devs), ("solve_many",))
    return mesh, ("solve_many",)


# --------------------------------------------------------------- ragged fill
def _embed(X: jax.Array, N: int, ridge: float) -> jax.Array:
    """Embed a (m, n, n) stack into (m, N, N) as blockdiag(A, fill * I).

    The fill sits strictly above each matrix's Gershgorin upper bound, so
    the pad eigenvalues are the largest N - n of the padded spectrum and
    the real spectrum keeps its ascending positions [0, n).
    """
    n = X.shape[-1]
    if n == N:
        return X
    diag = jnp.diagonal(X, axis1=-2, axis2=-1)
    offdiag = jnp.sum(jnp.abs(X), axis=-1) - jnp.abs(diag)
    g_hi = jnp.max(diag + offdiag, axis=-1)
    g_lo = jnp.min(diag - offdiag, axis=-1)
    fill = g_hi + ridge * (1.0 + (g_hi - g_lo))
    out = fill[:, None, None] * jnp.eye(N, dtype=X.dtype)[None]
    return out.at[:, :n, :n].set(X)


def _roots_from_window(w, V, p: int, eps: float):
    """(V root(w) V^T) per matrix from the real eigenpair window — the same
    ridge/root formula as ``EvdPlan.inverse_pth_root``."""
    wmax = jnp.maximum(jnp.max(w, axis=-1), 0.0)
    # Ridge in the operand dtype (see EvdPlan.inverse_pth_root).
    ridge = jnp.asarray(eps, w.dtype) * jnp.maximum(wmax, 1e-30)
    w_safe = jnp.maximum(w, 0.0) + ridge[:, None]
    root = jnp.power(w_safe, -1.0 / p)
    with jax.default_matmul_precision(MATMUL_PRECISION):
        return jnp.einsum("bik,bk,bjk->bij", V, root, V)


def _pad_batch(stack: jax.Array, target: int) -> jax.Array:
    """Append identity lanes so the bucket batch reaches ``target``."""
    B, N = stack.shape[0], stack.shape[-1]
    if B == target:
        return stack
    lanes = jnp.tile(jnp.eye(N, dtype=stack.dtype)[None], (target - B, 1, 1))
    return jnp.concatenate([stack, lanes], axis=0)


# ------------------------------------------------------------ bucket dispatch
def _run_bucket(
    stack: jax.Array,
    cfg: EvdConfig,
    op: str,
    p: int,
    eps: float,
    pad: PadPolicy,
    meshspec: Optional[Tuple[Mesh, Tuple[str, ...]]],
):
    """Execute one shape bucket through a single cached BatchPlan."""
    B, N = stack.shape[0], stack.shape[-1]
    multiple = pad.batch_multiple
    if meshspec is not None:
        mesh, axes = meshspec
        ndev = int(np.prod([mesh.shape[a] for a in axes]))
        multiple = math.lcm(multiple, ndev)
    B_pad = -(-B // multiple) * multiple
    stack = _pad_batch(stack, B_pad)

    if meshspec is None:
        bpl = batch_plan(N, B_pad, stack.dtype, cfg)
        if op == "eigh":
            out = bpl(stack, donate=pad.donate)
        elif op == "eigvals":
            out = bpl.eigvals(stack, donate=pad.donate)
        else:
            out = bpl.inverse_pth_root(stack, p, eps=eps, donate=pad.donate)
    else:
        mesh, axes = meshspec
        bpl = batch_plan(N, B_pad // ndev, stack.dtype, cfg)
        spec_b = P(tuple(axes))
        spec_m = P(tuple(axes), None, None)
        if op == "eigh":
            local, out_specs = (lambda a: bpl(a)), (spec_b, spec_m)
        elif op == "eigvals":
            local, out_specs = bpl.eigvals, spec_b
        else:
            local, out_specs = (
                lambda a: bpl.inverse_pth_root(a, p, eps=eps)
            ), spec_m
        out = jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(spec_m,),
            out_specs=out_specs,
            check_vma=False,
        )(stack)

    # Drop the identity batch-pad lanes.
    if op == "eigh":
        w, V = out
        return w[:B], V[:B]
    return out[:B]


# ------------------------------------------------------------------ front door
def solve_many(
    mats: Any,
    config: EvdConfig = EvdConfig(),
    *,
    op: str = "eigh",
    eigenvectors: bool = True,
    p: int = 4,
    eps: float = 1e-6,
    pad: PadPolicy = PadPolicy(),
    devices=None,
):
    """Solve every symmetric matrix in ``mats`` under one ``config``.

    ``mats`` is a pytree whose leaves are arrays with trailing square
    (n, n) shapes; leading leaf dims are batch dims.  Matrices are bucketed
    by (padded) size and dtype, each bucket runs as ONE cached
    :class:`BatchPlan` execution, and results come back in the input
    structure: each leaf is replaced by ``(w, V)`` (``op="eigh"``), ``w``
    (``op="eigvals"`` or ``eigenvectors=False``), or ``X``
    (``op="inverse_pth_root"``), with the leaf's batch dims preserved.

    ``devices=`` (a Mesh, a ``(mesh, axes)`` pair, or a device sequence)
    shards every bucket's batch over the mesh via ``shard_map`` — the
    Shampoo many-medium-matrices regime; bucket batches are padded up to
    the device count with identity lanes.  ``pad`` controls bucket sizes,
    ridge-identity fill, batch padding, and input-buffer donation (see
    :class:`PadPolicy`).
    """
    if op not in _OPS:
        raise ValueError(f"unknown op {op!r}; expected one of {_OPS}")
    if op == "eigh" and not eigenvectors:
        op = "eigvals"
    if op == "inverse_pth_root" and not config.spectrum.is_full:
        raise ValueError(
            f"inverse_pth_root needs the full spectrum; config selects "
            f"{config.spectrum}"
        )
    meshspec = _normalize_devices(devices)

    leaves, treedef = jax.tree_util.tree_flatten(mats)
    if not leaves:
        return jax.tree_util.tree_unflatten(treedef, leaves)

    # ---- leaf metadata ----------------------------------------------------
    infos = []
    for i, leaf in enumerate(leaves):
        leaf = jnp.asarray(leaf)
        if leaf.ndim < 2 or leaf.shape[-1] != leaf.shape[-2]:
            raise ValueError(
                f"solve_many leaf {i} must have a trailing square shape, "
                f"got {leaf.shape}"
            )
        n = leaf.shape[-1]
        infos.append(
            dict(
                leaf=leaf,
                batch_shape=leaf.shape[:-2],
                n=n,
                N=pad.bucket_for(n),
                dtype=jnp.dtype(leaf.dtype).name,
                count=int(np.prod(leaf.shape[:-2], dtype=np.int64)) if leaf.ndim > 2 else 1,
            )
        )

    # ---- group into (bucket size, dtype) buckets --------------------------
    # Zero-size leaves ((0, n, n) stacks) get empty results directly — the
    # old vmap path accepted them and consumers rely on that.
    buckets: Dict[Tuple[int, str], List[int]] = {}
    results: List[Any] = [None] * len(leaves)
    for i, info in enumerate(infos):
        if info["count"] == 0:
            n, bshape, dt = info["n"], info["batch_shape"], info["leaf"].dtype
            _, k = config.spectrum.index_range(n)
            if op == "eigh":
                results[i] = (
                    jnp.zeros(bshape + (k,), dt),
                    jnp.zeros(bshape + (n, k), dt),
                )
            elif op == "eigvals":
                results[i] = jnp.zeros(bshape + (k,), dt)
            else:
                results[i] = jnp.zeros(bshape + (n, n), dt)
            continue
        buckets.setdefault((info["N"], info["dtype"]), []).append(i)
    for (N, _dtype), leaf_ids in buckets.items():
        padded = any(infos[i]["n"] != N for i in leaf_ids)
        # A padded bucket mixes real sizes, so the plan computes the FULL
        # padded spectrum and the per-leaf scatter slices each matrix's
        # requested window out of positions [0, n) (the fill keeps the real
        # spectrum there).  Exact buckets run the config's window directly.
        # Padded inverse roots go through eigh + real-window reconstruction:
        # the pad block is an exactly-degenerate cluster whose inverse-
        # iteration columns are unreliable, so they must be sliced away
        # BEFORE forming V root(w) V^T (a full-spectrum batched
        # inverse_pth_root on the padded matrix would fold them in).
        cfg = config.replace(spectrum=Spectrum.all()) if padded else config
        exec_op = "eigh" if (padded and op == "inverse_pth_root") else op

        segs = [infos[i]["leaf"].reshape((-1,) + infos[i]["leaf"].shape[-2:])
                for i in leaf_ids]
        if padded:
            segs = [_embed(s, N, pad.ridge) for s in segs]
        stack = segs[0] if len(segs) == 1 else jnp.concatenate(segs, axis=0)
        out = _run_bucket(stack, cfg, exec_op, p, eps, pad, meshspec)

        # ---- scatter back in input order ----------------------------------
        off = 0
        for i in leaf_ids:
            info = infos[i]
            n, m, bshape = info["n"], info["count"], info["batch_shape"]
            if op == "eigh":
                w, V = out[0][off : off + m], out[1][off : off + m]
                if padded:
                    start, count = config.spectrum.index_range(n)
                    w = w[:, start : start + count]
                    V = V[:, :n, start : start + count]
                results[i] = (
                    w.reshape(bshape + w.shape[1:]),
                    V.reshape(bshape + V.shape[1:]),
                )
            elif op == "eigvals":
                w = out[off : off + m]
                if padded:
                    start, count = config.spectrum.index_range(n)
                    w = w[:, start : start + count]
                results[i] = w.reshape(bshape + w.shape[1:])
            elif padded:  # inverse_pth_root over a padded bucket
                w, V = out[0][off : off + m], out[1][off : off + m]
                X = _roots_from_window(w[:, :n], V[:, :n, :n], p, eps)
                results[i] = X.reshape(bshape + X.shape[1:])
            else:
                X = out[off : off + m]
                results[i] = X.reshape(bshape + X.shape[1:])
            off += m

    return jax.tree_util.tree_unflatten(treedef, results)
