"""Beyond-paper: the EVD solver inside its production consumer (Shampoo).

Measures (a) batched inverse-4th-root throughput — the solver call Shampoo
issues every refresh — and (b) full Shampoo step time vs AdamW on a reduced
LM, isolating the preconditioner overhead the paper's speedups amortize.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.backend import registry
from repro.optim import adamw, shampoo, ShampooOptions, apply_updates
from repro.solver import EvdConfig, plan, solve_many
from benchmarks.common import bench, emit, is_smoke


def run():
    rng = np.random.default_rng(5)

    # (a) batched inverse roots — the exact solve_many call Shampoo's
    # refresh issues (one cached BatchPlan per matrix size)
    cases = [(32, 4)] if is_smoke() else [(64, 8), (128, 8)]
    for n, batch in cases:
        G = rng.normal(size=(batch, n, n)).astype(np.float32)
        S = jnp.asarray(np.einsum("bij,bkj->bik", G, G) + 0.1 * np.eye(n, dtype=np.float32))
        cfg = EvdConfig(b=8, nb=32)
        f = lambda X: solve_many(X, cfg, op="inverse_pth_root", p=4)
        t = bench(f, S)
        emit(f"inv4root_batched_{batch}x{n}", t, f"per_matrix_us={t/batch*1e6:.1f}",
             op="inverse_pth_root", n=n,
             backend=plan(n, jnp.float32, cfg).backend)

    # (b) optimizer step comparison on a reduced LM
    from repro.configs import get_smoke_config
    from repro.models import model_params
    from repro.train import make_train_step
    from repro.data import DataConfig, synthetic_batch

    cfg = get_smoke_config("llama3.2-3b")
    params = model_params(cfg, jax.random.PRNGKey(0), model_axis=1)
    dc = DataConfig(vocab=cfg.vocab, seq_len=64, global_batch=8)
    batch = synthetic_batch(dc, jnp.asarray(0, jnp.int32))
    for name, opt in [
        ("adamw", adamw(1e-3)),
        ("shampoo_evd", shampoo(1e-3, opts=ShampooOptions(
            block_size=32, update_interval=1, evd=EvdConfig(b=8, nb=32)))),
    ]:
        state = opt.init(params)
        step = jax.jit(make_train_step(cfg, opt))
        t = bench(step, params, state, batch, jnp.zeros((), jnp.int32))
        emit(f"train_step_{name}", t, f"arch={cfg.name};smoke=1",
             op="train_step", n=cfg.d_model,
             backend=registry.default_backend())
