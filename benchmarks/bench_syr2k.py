"""Paper Table 1 + Figure 8: SYR2K performance across shapes.

Table 1 sweeps (n, k) for tall-skinny inputs; Fig 8 compares the proposed
syr2k against the vendor baseline on square and tall-skinny shapes.  Both
sides resolve through ``repro.backend.registry`` (the pipeline's dispatch
point, with its per-platform tile defaults): the "pallas" backend is the
triangular-tile kernel (interpret off-TPU), the "jnp" backend the XLA
baseline (full GEMM + symmetrize).  The derived column reports the
FLOP-savings ratio (the kernel does half the multiply work by touching only
lower tiles).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.backend import probe, registry
from benchmarks.common import bench, emit, is_smoke


def run():
    rng = np.random.default_rng(0)
    shapes = [
        # Table-1 style: fixed n, sweep k (tall-skinny -> square-ish)
        (512, 32), (512, 64), (512, 128), (512, 256),
        # Fig-8 style: square-ish growth
        (128, 128), (256, 256), (384, 384),
    ]
    if is_smoke():
        shapes = [(128, 32), (128, 128)]
    for n, k in shapes:
        A = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32))
        B = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32))
        C = jnp.zeros((n, n), jnp.float32)
        flops = 2.0 * n * n * k  # useful syr2k flops (both products, symm)

        for backend in ("jnp", "pallas"):
            fn = registry.resolve("syr2k", backend)
            t = bench(jax.jit(lambda a, b, c, fn=fn: fn(a, b, c)), A, B, C)
            extra = (
                f";interpret={'off' if probe.is_tpu() else 'on'}"
                f";tile_flop_savings=0.5" if backend == "pallas" else ""
            )
            emit(
                f"syr2k_{backend}_n{n}_k{k}", t,
                f"gflops={flops/t/1e9:.2f}{extra}",
                op="syr2k", n=n, backend=backend,
            )
