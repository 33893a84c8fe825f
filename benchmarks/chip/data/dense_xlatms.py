"""Dense symmetric matrices with a known spectrum, in the style of LAPACK's
``xLATMS``: ``A = Q diag(lam) Q^T`` with ``Q`` from the QR factorization of a
Gaussian matrix, all in float64 on the host.

The spectrum holds ``geometric_share * n`` geometric magnitudes from 1 down to
``geometric_low`` with random signs, and the rest as a tight cluster at
``cluster_value`` of relative width ``cluster_rel_width``.  Every seed gives the
same sizes and the same spectrum shape.
"""
from __future__ import annotations

import numpy as np


def make(config: dict, traffic: dict, seed: int) -> dict:
    """``operand``: the float32 matrix the program solves; ``operand64``: the
    same matrix in float64; ``lam``: its spectrum as constructed, ascending."""
    if traffic.get("batch", 1) != 1:
        raise ValueError("dense_xlatms makes one matrix per call")
    n = int(config["n"])
    spec = config["spectrum"]
    rng = np.random.default_rng(seed)
    Q, _ = np.linalg.qr(rng.standard_normal((n, n)))
    k = int(round(spec["geometric_share"] * n))
    geo = spec["geometric_low"] ** (np.arange(k) / (k - 1)) * rng.choice([-1.0, 1.0], size=k)
    cluster = spec["cluster_value"] * (1.0 + spec["cluster_rel_width"] * rng.uniform(size=n - k))
    lam = np.concatenate([geo, cluster])
    A = (Q * lam) @ Q.T
    A32 = (0.5 * (A + A.T)).astype(np.float32)
    return {"operand": A32, "operand64": A32.astype(np.float64), "lam": np.sort(lam)}
