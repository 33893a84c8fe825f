"""Capability probe: what accelerator substrate is this process running on?

The answers drive kernel dispatch (``repro.kernels.ops``):

* :func:`platform` — the active XLA backend ("cpu" | "tpu" | "gpu").
* :func:`interpret_mode` — whether Pallas kernels must run under the
  interpreter (anywhere that is not a real TPU; the validation mode).  On a
  TPU no kernel is interpreted unless its caller passes ``interpret=True``.
"""
from __future__ import annotations

import jax

__all__ = ["platform", "is_tpu", "interpret_mode"]


def platform() -> str:
    """The active XLA backend name ("cpu", "tpu", "gpu")."""
    return jax.default_backend()


def is_tpu() -> bool:
    return platform() == "tpu"


def interpret_mode() -> bool:
    """Pallas interpret mode: on for CPU/GPU (validation), off on real TPUs."""
    return not is_tpu()
