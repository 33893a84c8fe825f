"""One dense matrix per call through ``plan(n, float32).eigvals``: the
eigenvalues only (tridiagonalization and bisection).

Compared: ``eig_err``, the largest gap between a computed eigenvalue and the
constructed spectrum, over ``||A||_2``.
"""
from __future__ import annotations

import numpy as np


def build(config: dict, traffic: dict, data: dict, devices: list) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.solver import EvdConfig, plan

    pl = plan(int(config["n"]), jnp.float32, EvdConfig())
    return {
        "fn": pl.eigvals,
        "args": (jax.device_put(data["operand"], devices[0]),),
        "describe": pl.describe(),
        "answers_per_call": 1,
    }


def control_fn(config: dict, traffic: dict, devices: list):
    """The program as its control runs it, taking the same arguments: the
    same ``plan`` with its kernels run as their XLA reference (backend
    ``jnp``), since at ``high`` Mosaic does not lower the ``syr2k_lower``
    kernel, whose dots take the ambient precision.  ``calibrate.py`` traces
    it with the program's precision switch lowered (``precision.py``)."""
    import jax.numpy as jnp

    from repro.solver import EvdConfig, plan

    return plan(int(config["n"]), jnp.float32, EvdConfig(backend="jnp")).eigvals


def readings(out, data: dict, config: dict, traffic: dict) -> dict:
    lam = data["lam"]
    w = np.asarray(out, np.float64)
    return {"eig_err": np.array([np.abs(w - lam).max() / np.abs(lam).max()])}
