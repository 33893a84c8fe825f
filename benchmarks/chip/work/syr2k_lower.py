"""Work of one ``syr2k_lower`` call: the symmetric rank-2k trailing update
``C - Z Y^T - Y Z^T`` on the lower triangle of an (n, n) ``C`` with (n, k)
factors, as the first stage issues it.

FLOPs: each of the n(n+1)/2 lower entries takes two length-k dot products,
2 * 2k FLOPs, so 2 k n (n+1).  Bytes: the two factors read once and the
lower triangle read and written once.  Leading dimensions of the operands
(a ``vmap`` batch) multiply both.
"""
import math


def work(call):
    mats = [s for s in call.operands if len(s.dims) >= 2]
    factor, c = mats[0], mats[-1]
    n, k = factor.dims[-2:]
    if c.dims[-2:] != (n, n):
        raise ValueError(f"unexpected syr2k_lower operands {call.operands}")
    batch = math.prod(c.dims[:-2])
    flops = 2 * k * n * (n + 1)
    nbytes = c.itemsize * (2 * n * k + n * (n + 1))
    return batch * flops, batch * nbytes
