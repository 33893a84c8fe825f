"""Shampoo with EVD-powered preconditioners — the paper's production consumer.

Shampoo (Gupta et al., cited as [20] by the paper) preconditions each 2-D
parameter block G with L^{-1/4} G R^{-1/4} where L = EMA[G G^T],
R = EMA[G^T G].  The inverse fourth roots are symmetric-EVD problems — the
exact workload the paper accelerates — computed by ONE call to
``repro.solver.solve_many(stats, evd, op="inverse_pth_root")`` per refresh:
the batched front door owns the plan cache, the batch padding, and (when
``precond_mesh`` is set) the shard_map routing, so this file carries no
bespoke padding/sharding plumbing.  All solver tuning flows through ONE
field: ``ShampooOptions.evd`` is a frozen :class:`repro.solver.EvdConfig`
(method, blocking, kernel-backend pin).

Layout: every eligible parameter is cut into (block, block) tiles; all tiles
across the whole model are stacked into ONE (NB, bs, bs) batch so the solver
runs as a single batched/sharded call — the TPU-native "many medium
matrices" regime (DESIGN.md §3).  1-D / embedding params fall back to Adam.

Grafting: AdaGrad-norm grafting (update rescaled to the diagonal-Adam update
norm per parameter), the standard distributed-Shampoo recipe.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from .base import Optimizer, clip_by_global_norm
from repro.solver import EvdConfig, solve_many

__all__ = ["shampoo", "ShampooState", "ShampooOptions"]


@dataclasses.dataclass(frozen=True)
class ShampooOptions:
    block_size: int = 128
    update_interval: int = 10       # preconditioner refresh period
    beta2: float = 0.99             # stats EMA
    beta1: float = 0.9              # momentum
    eps: float = 1e-6               # root ridge
    graft_eps: float = 1e-8
    max_dim_for_shampoo: int = 65536
    vocab_threshold: int = 16384    # leaves with a dim this big use Adam
    evd: EvdConfig = EvdConfig(b=8, nb=64)  # the solver plan config
    precond_mesh: Any = None        # optional (mesh, axes) to shard the EVD batch


class ShampooState(NamedTuple):
    step: jax.Array
    mu: Any          # momentum tree
    nu: Any          # diagonal second moment (grafting + fallback)
    stats_l: jax.Array  # (NB, bs, bs)
    stats_r: jax.Array
    pre_l: jax.Array
    pre_r: jax.Array


def _leaf_plan(path: str, shape, opts: ShampooOptions):
    """Decide how a leaf is preconditioned.  Returns dict or None (diag)."""
    if len(shape) < 2:
        return None
    if max(shape) > opts.max_dim_for_shampoo:
        return None
    # Embedding-like leaves: any dim above the vocab threshold -> Adam.
    if max(shape) >= opts.vocab_threshold and ("embed" in path or "unembed" in path):
        return None
    if len(shape) == 2:
        batch, m, n = 1, shape[0], shape[1]
    else:
        # Leading dim = stacked layers (batch); split the rest into the most
        # square (m, n) factorization (a bad split like m=24, n=393216 makes
        # thousands of mostly-padding blocks).
        batch = shape[0]
        rest = list(shape[1:])
        best, best_ratio = 1, float("inf")
        prod_all = 1
        for d in rest:
            prod_all *= d
        acc = 1
        for j in range(1, len(rest)):
            acc *= rest[j - 1]
            ratio = max(acc, prod_all // acc) / max(min(acc, prod_all // acc), 1)
            if ratio < best_ratio:
                best_ratio, best = ratio, j
        m = 1
        for d in rest[:best]:
            m *= d
        n = prod_all // m
    bs = opts.block_size
    nbm = -(-m // bs)
    nbn = -(-n // bs)
    return dict(batch=batch, m=m, n=n, nbm=nbm, nbn=nbn, count=batch * nbm * nbn)


def _to_blocks(g: jax.Array, plan, bs: int) -> jax.Array:
    b, m, n = plan["batch"], plan["m"], plan["n"]
    nbm, nbn = plan["nbm"], plan["nbn"]
    g = g.reshape(b, m, n).astype(jnp.float32)
    g = jnp.pad(g, ((0, 0), (0, nbm * bs - m), (0, nbn * bs - n)))
    g = g.reshape(b, nbm, bs, nbn, bs).transpose(0, 1, 3, 2, 4)
    return g.reshape(b * nbm * nbn, bs, bs)


def _from_blocks(blocks: jax.Array, plan, bs: int, shape) -> jax.Array:
    b, m, n = plan["batch"], plan["m"], plan["n"]
    nbm, nbn = plan["nbm"], plan["nbn"]
    g = blocks.reshape(b, nbm, nbn, bs, bs).transpose(0, 1, 3, 2, 4)
    g = g.reshape(b, nbm * bs, nbn * bs)[:, :m, :n]
    return g.reshape(shape)


def _flatten_with_paths(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    paths = ["/".join(str(k) for k in path) for path, _ in flat]
    leaves = [v for _, v in flat]
    return paths, leaves, treedef


def shampoo(
    lr=1e-3,
    opts: ShampooOptions = ShampooOptions(),
    weight_decay: float = 0.0,
    grad_clip: Optional[float] = 1.0,
) -> Optimizer:
    schedule = lr if callable(lr) else (lambda _: jnp.asarray(lr, jnp.float32))
    bs = opts.block_size

    def make_plans(params):
        paths, leaves, _ = _flatten_with_paths(params)
        plans, offset = [], 0
        for path, leaf in zip(paths, leaves):
            plan = _leaf_plan(path, leaf.shape, opts)
            if plan is not None:
                plan["offset"] = offset
                offset += plan["count"]
            plans.append(plan)
        # Mesh-divisibility padding is solve_many's job now (PadPolicy
        # identity lanes); the stats batch is exactly the block count.
        return plans, max(offset, 1)

    def init(params):
        plans, nb = make_plans(params)
        zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
        def eye():  # distinct buffers: donation forbids aliased leaves
            return jnp.tile(jnp.eye(bs, dtype=jnp.float32), (nb, 1, 1))

        return ShampooState(
            step=jnp.zeros((), jnp.int32),
            mu=jax.tree_util.tree_map(zeros, params),
            nu=jax.tree_util.tree_map(zeros, params),
            stats_l=jnp.zeros((nb, bs, bs), jnp.float32),
            stats_r=jnp.zeros((nb, bs, bs), jnp.float32),
            pre_l=eye(),
            pre_r=eye(),
        )

    def _roots(stats):
        # ONE solve_many call owns plan caching, batch padding, and (with
        # precond_mesh) the shard_map routing; the EvdConfig carries any
        # kernel-backend pin into the plan it builds.
        return solve_many(
            stats, opts.evd, op="inverse_pth_root", p=4, eps=opts.eps,
            devices=opts.precond_mesh,
        )

    def update(grads, state, params):
        paths, gleaves, treedef = _flatten_with_paths(grads)
        _, pleaves, _ = _flatten_with_paths(params)
        plans, _ = make_plans(params)
        grads_f = [g.astype(jnp.float32) for g in gleaves]
        if grad_clip is not None:
            clipped, _ = clip_by_global_norm(grads_f, grad_clip)
            grads_f = clipped

        step = state.step + 1
        lr_t = schedule(step)

        # ---- diagonal stats (grafting + fallback) -------------------------
        nuleaves = jax.tree_util.tree_leaves(state.nu)
        nu_new = [0.99 * v + 0.01 * g * g for v, g in zip(nuleaves, grads_f)]

        # ---- gather blocks, update Kronecker stats ------------------------
        blocks = [
            _to_blocks(g, plan, bs)
            for g, plan in zip(grads_f, plans)
            if plan is not None
        ]
        if blocks:
            G = jnp.concatenate(blocks, axis=0)
            L = opts.beta2 * state.stats_l + (1 - opts.beta2) * jnp.einsum(
                "kmn,kpn->kmp", G, G
            )
            R = opts.beta2 * state.stats_r + (1 - opts.beta2) * jnp.einsum(
                "kmn,kmp->knp", G, G
            )
        else:
            G = jnp.zeros((1, bs, bs), jnp.float32)
            L, R = state.stats_l, state.stats_r

        # ---- refresh preconditioners every update_interval ----------------
        def refresh(_):
            return _roots(L), _roots(R)

        def keep(_):
            return state.pre_l, state.pre_r

        do = jnp.logical_or(step % opts.update_interval == 0, step == 1)
        pre_l, pre_r = lax.cond(do, refresh, keep, operand=None)

        # ---- precondition + graft -----------------------------------------
        P = jnp.einsum("kab,kbc,kcd->kad", pre_l, G, pre_r) if blocks else G

        updates = []
        c2 = 1.0 - 0.99 ** step.astype(jnp.float32)  # bias correction
        for g, p, v, plan, path in zip(grads_f, pleaves, nu_new, plans, paths):
            adam_dir = g / (jnp.sqrt(v / c2) + opts.graft_eps)
            if plan is None:
                u = adam_dir
            else:
                blk = lax.dynamic_slice_in_dim(P, plan["offset"], plan["count"], 0)
                pg = _from_blocks(blk, plan, bs, g.shape)
                graft = jnp.linalg.norm(adam_dir.reshape(-1)) / jnp.maximum(
                    jnp.linalg.norm(pg.reshape(-1)), 1e-16
                )
                u = pg * graft
            u = u + weight_decay * p.astype(jnp.float32)
            updates.append(u)

        # ---- momentum ------------------------------------------------------
        muleaves = jax.tree_util.tree_leaves(state.mu)
        mu_new = [opts.beta1 * m + u for m, u in zip(muleaves, updates)]
        out = [
            (-lr_t * m).astype(p.dtype) for m, p in zip(mu_new, pleaves)
        ]

        unf = lambda leaves: jax.tree_util.tree_unflatten(treedef, leaves)
        return unf(out), ShampooState(
            step=step,
            mu=unf(mu_new),
            nu=unf(nu_new),
            stats_l=L,
            stats_r=R,
            pre_l=pre_l,
            pre_r=pre_r,
        )

    return Optimizer(init=init, update=update)
