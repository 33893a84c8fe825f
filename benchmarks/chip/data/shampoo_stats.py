"""Shampoo Kronecker-factor statistics: ``S = G G^T / rank + delta I`` with
G an (n, rank) float32 Gaussian, ``delta = ridge_share * tr(G G^T / rank) / n``,
summed in float64 and rounded to float32.  The spectrum is ``rank``
Marchenko-Pastur values above ``delta`` and a cluster of ``n - rank`` at
``delta``, as the statistics of a layer early in training look.
"""
from __future__ import annotations

import numpy as np


def make(config: dict, traffic: dict, seed: int) -> dict:
    """``operand``: the (batch, n, n) float32 stack the program solves;
    ``operand64``: the same stack in float64."""
    n = int(config["n"])
    stats = config["statistics"]
    rank, share = int(stats["rank"]), float(stats["ridge_share"])
    batch = int(traffic["batch"])
    rng = np.random.default_rng(seed)
    out = np.empty((batch, n, n), np.float32)
    for i in range(batch):
        G = rng.standard_normal((n, rank), dtype=np.float32).astype(np.float64)
        S = G @ G.T / rank
        S = 0.5 * (S + S.T)
        S[np.diag_indices(n)] += share * np.trace(S) / n
        out[i] = S
    return {"operand": out, "operand64": out.astype(np.float64)}
