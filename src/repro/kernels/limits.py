"""VMEM accounting and interpret-mode ceilings for ALL Pallas kernels.

Every VMEM-resident kernel in this package dispatches on two numbers:

* its **VMEM bytes** — the bytes the ``pallas_call`` really holds in VMEM:
  every block in its (8, 128)-tiled layout, times its buffer count (2 for
  pipelined blocks, 1 for resident blocks declared ``pl.Buffered(1)``),
  plus its scratch.  Each kernel module exports the function that counts
  them (``*_vmem_bytes``), built from the same shapes as its BlockSpecs.
  The ops wrappers in ``repro.kernels.ops`` run the kernel only when
  :func:`fits_vmem` accepts that count, and the kernel passes
  :func:`vmem_limit_bytes` of the same count to the compiler — so dispatch
  and compiler agree by construction.
* an **interpret ceiling** — off-TPU the kernels run under the Pallas
  interpreter for validation only, and the emulated grid unrolls into the
  traced program; above the validation sizes the wrappers fall back so CPU
  oracle runs stay cheap.  An EXPLICIT ``interpret=True`` (validating the
  kernel itself) bypasses the interpret ceiling — see the ops wrappers.

The budget is a quarter of the 128 MiB of VMEM a TPU v5e core has.  At
b = 8, nb = 256 it admits the fused panel kernel on trailing views up to
m = 1280 (m = 1536 counts 36 MiB), the dense-resident bulge kernel up to
n ~ 1900 and its band-strip layout up to n ~ 10,700 (12.75 MiB at
n = 4096); the Q2 back-transform tiles its columns.  It is twice the
compiler's default scoped limit (16 MiB on a v5e), so every kernel passes
its own limit: the count plus :data:`VMEM_HEADROOM_BYTES` for Mosaic's
internal scratch (:func:`vmem_limit_bytes`).

Every entry of :data:`LIMITS` can be overridden with an environment variable
``REPRO_<NAME>`` (e.g. ``REPRO_BULGE_INTERPRET_MAX_N=128``) — read at call
time, so tests can retune dispatch without code changes.

==============================  ========  =====================================
name                            default   gates
==============================  ========  =====================================
VMEM_BUDGET_BYTES               32 MiB    every compiled kernel (counted bytes)
BULGE_INTERPRET_MAX_N                 64  bulge kernel off-TPU (3(n-3)+1 grid
                                          steps unroll under the interpreter)
BACKTRANSFORM_INTERPRET_MAX_N         48  Q2 back-transform off-TPU ((S,)-grid)
FUSED_PANEL_INTERPRET_MAX_M           96  fused panel kernel off-TPU
==============================  ========  =====================================
"""
from __future__ import annotations

import math
import os

__all__ = [
    "LIMITS",
    "ENV_PREFIX",
    "limit",
    "tile_bytes",
    "fits_vmem",
    "vmem_limit_bytes",
]

ENV_PREFIX = "REPRO_"

# Physical VMEM of one TPU v5e TensorCore.
VMEM_CAPACITY_BYTES = 128 * 1024 * 1024
# Mosaic's internal scratch and spilled temporaries, on top of the counted
# blocks.
VMEM_HEADROOM_BYTES = 8 * 1024 * 1024

LIMITS = {
    "VMEM_BUDGET_BYTES": 32 * 1024 * 1024,
    "BULGE_INTERPRET_MAX_N": 64,
    "BACKTRANSFORM_INTERPRET_MAX_N": 48,
    "FUSED_PANEL_INTERPRET_MAX_M": 96,
}


def limit(name: str) -> int:
    """The active value of ceiling ``name`` (env override wins over default).

    Reads ``REPRO_<name>`` from the environment at every call so overrides
    take effect without reimporting (tests monkeypatch the env var).
    """
    if name not in LIMITS:
        raise KeyError(
            f"unknown kernel limit {name!r}; expected one of {sorted(LIMITS)}"
        )
    env = os.environ.get(ENV_PREFIX + name)
    if env is not None and env != "":
        return int(env)
    return LIMITS[name]


def tile_bytes(shape, itemsize: int = 4, buffers: int = 1) -> int:
    """VMEM bytes of one block of ``shape``: the last two dims pad to the
    (8, 128) tile, leading dims multiply."""
    *lead, r, c = (1,) * max(0, 2 - len(shape)) + tuple(shape)
    rows = -(-r // 8) * 8
    cols = -(-c // 128) * 128
    return buffers * math.prod(lead) * rows * cols * itemsize


def fits_vmem(nbytes: int) -> bool:
    """Whether a kernel holding ``nbytes`` of VMEM blocks may dispatch."""
    return nbytes <= limit("VMEM_BUDGET_BYTES")


def vmem_limit_bytes(nbytes: int) -> int:
    """The compiler's scoped-VMEM limit for a kernel holding ``nbytes``."""
    return min(nbytes + VMEM_HEADROOM_BYTES, VMEM_CAPACITY_BYTES)
