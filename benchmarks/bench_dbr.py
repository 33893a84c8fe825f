"""Paper Table 2 + Figure 4: DBR/SBR elapsed time across (b, nb) and the
band-reduction / bulge-chasing balance.

Reproduces the paper's central tuning claim: decoupling nb from b lets a
SMALL bandwidth (cheap bulge chasing) coexist with a LARGE update block
(compute-bound trailing syr2k).  We sweep (b, nb) at fixed n and report both
stages' times + the trailing-update k (= nb, the paper's key quantity).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.backend import registry
from repro.core import band_reduce, chase_wavefront
from benchmarks.common import bench, emit, is_smoke


def run(n: int = 256):
    if is_smoke():
        n = 128
    rng = np.random.default_rng(1)
    A0 = rng.normal(size=(n, n)).astype(np.float32)
    A = jnp.asarray(A0 + A0.T)

    for b in (4, 8, 16):
        for nb in (b, 4 * b, 8 * b):
            if nb > n // 2:
                continue
            br = jax.jit(lambda M, b=b, nb=nb: band_reduce(M, b, nb))
            t_br = bench(br, A)
            Bband = br(A)
            bc = jax.jit(lambda M, b=b: chase_wavefront(M, b))
            t_bc = bench(bc, Bband)
            kind = "SBR" if nb == b else "DBR"
            emit(
                f"{kind.lower()}_n{n}_b{b}_nb{nb}", t_br,
                f"bulge_chase_us={t_bc*1e6:.1f};total_us={(t_br+t_bc)*1e6:.1f};"
                f"update_k={nb}",
                op="band_reduce", n=n, backend=registry.default_backend(),
            )
