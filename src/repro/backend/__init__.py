"""repro.backend — capability probe, jax-compat shim, kernel dispatch.

This is the architectural seam between the algorithm layer (``repro.core``)
and the kernel layer (``repro.kernels``):

* ``repro.backend.compat``   — ``make_mesh`` with ``Auto`` axis types.
* ``repro.backend.probe``    — platform / interpret-mode.
* ``repro.backend.cache``    — the persistent compilation cache directory
  (set up by scripts at start-up, never on import).
* ``repro.backend.registry`` — hot-op -> kernel dispatch with per-backend
  tile defaults and the ``REPRO_KERNEL_BACKEND`` override.
"""
from . import compat, probe, registry
from .compat import make_mesh
from .probe import platform, interpret_mode
from .registry import (
    resolve,
    register,
    default_backend,
    set_backend,
    use_backend,
    tile_defaults,
)

__all__ = [
    "compat",
    "probe",
    "registry",
    "make_mesh",
    "platform",
    "interpret_mode",
    "resolve",
    "register",
    "default_backend",
    "set_backend",
    "use_backend",
    "tile_defaults",
]
