"""One stack of matrices per call through ``solve_many(S, EvdConfig(),
op="inverse_pth_root", p=p)``: every eigenpair of every matrix and its
inverse p-th root (the Shampoo preconditioner refresh).

Compared: ``root_err``, ``||X - S^{-1/p}||_F / ||S^{-1/p}||_F`` per matrix,
against a float64 root of the float32 operand: ``numpy.linalg.eigh`` with
the library's relative ridge ``eps * max(w)`` added to the clamped
eigenvalues.
"""
from __future__ import annotations

import numpy as np


def build(config: dict, traffic: dict, data: dict, devices: list) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.solver import EvdConfig, batch_plan

    n, batch = int(config["n"]), int(traffic["batch"])
    cfg = EvdConfig()
    return {
        "fn": _refresh(cfg, traffic),
        "args": (jax.device_put(data["operand"], devices[0]),),
        "describe": batch_plan(n, batch, jnp.float32, cfg).describe(),
        "answers_per_call": batch,
    }


def _refresh(cfg, traffic: dict):
    from repro.solver import solve_many

    p, eps = int(traffic["p"]), float(traffic["eps"])
    return lambda S: solve_many(S, cfg, op="inverse_pth_root", p=p, eps=eps)


def control_fn(config: dict, traffic: dict, devices: list):
    """The program as its control runs it, taking the same arguments: the
    same refresh with its kernels run as their XLA reference (backend
    ``jnp``), since at ``high`` Mosaic does not lower the ``syr2k_lower``
    kernel, whose dots take the ambient precision.  ``calibrate.py`` traces
    it with the program's precision switch lowered (``precision.py``)."""
    from repro.solver import EvdConfig

    return _refresh(EvdConfig(backend="jnp"), traffic)


def inverse_root(S: np.ndarray, p: int, eps: float) -> np.ndarray:
    """float64 ``S^{-1/p}`` with the relative ridge ``eps * max(w)``."""
    w, V = np.linalg.eigh(S)
    ridge = eps * max(w.max(), 1e-30)
    return (V * (np.maximum(w, 0.0) + ridge) ** (-1.0 / p)) @ V.T


def readings(out, data: dict, config: dict, traffic: dict) -> dict:
    p, eps = int(traffic["p"]), float(traffic["eps"])
    X = np.asarray(out, np.float64)
    err = np.empty(X.shape[0])
    for i, S in enumerate(data["operand64"]):
        ref = inverse_root(S, p, eps)
        err[i] = np.linalg.norm(X[i] - ref) / np.linalg.norm(ref)
    return {"root_err": err}
