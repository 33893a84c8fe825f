"""Work of one ``backtransform_wy`` call: Q2, the product of the bulge
chase's Householder reflectors of length b, applied to an (n, m) panel of
eigenvectors of the tridiagonal matrix.

The call's operands are the lane-dense reflector log, (S8, K*b) vectors and
(S8, K) taus with S8 the n - 2 sweeps padded to a multiple of 8, and the
panel, padded below to ``rows``.  Sweep s (0 <= s < n - 2) holds
``(n - 3 - s) // b + 1`` live reflectors; the other slots carry tau = 0.

FLOPs: each live reflector applied to the m columns, ``v^T X`` and
``X - tau v (v^T X)``, 4 b m.  Bytes: the padded panel read and written
once, and the log read once.  n is taken as m, the panel of a full
spectrum; a call whose log does not fit that n is refused.  Leading
dimensions of the operands (a ``vmap`` batch) multiply both.
"""
import math


def work(call):
    mats = [s for s in call.operands if len(s.dims) >= 2]
    if len(mats) != 3:
        raise ValueError(f"unexpected backtransform_wy operands {call.operands}")
    vs, taus, panel = mats
    s8, kb = vs.dims[-2:]
    K = taus.dims[-1]
    rows, m = panel.dims[-2:]
    b, n = kb // K, m
    if kb != K * b or (n - 3) // b + 1 != K or -(-(n - 2) // 8) * 8 != s8 or rows < n:
        raise ValueError(f"backtransform_wy operands {call.operands} are not a full spectrum's")
    live = sum((n - 3 - s) // b + 1 for s in range(n - 2))
    batch = math.prod(panel.dims[:-2])
    flops = 4 * b * m * live
    nbytes = panel.itemsize * (2 * rows * m + s8 * kb + s8 * K)
    return batch * flops, batch * nbytes
