"""Paper Figure 9: bulge chasing — serial (the 'CPU consensus') vs the
wavefront schedule (the paper's accelerator-resident claim).

The paper's result is that pipelined sweeps beat the serial CPU
implementation ~8x.  Our executors share arithmetic but differ exactly in
that schedule: ``chase_sequential`` = one op at a time (the consensus
implementation), ``chase_wavefront`` = all independent sweeps batched per
wavefront (the paper's pipeline, statically scheduled).  The speedup column
is the reproduction; absolute times are CPU proxies.
"""
from __future__ import annotations

import jax
import numpy as np
import jax.numpy as jnp

from repro.backend import probe, registry
from repro.core import band_reduce, chase_sequential, chase_wavefront
from benchmarks.common import bench, emit, is_smoke


def run():
    rng = np.random.default_rng(2)
    cases = [(128, 4)] if is_smoke() else [(128, 4), (256, 4), (256, 8), (384, 8)]
    for n, b in cases:
        A0 = rng.normal(size=(n, n)).astype(np.float32)
        A = jnp.asarray(A0 + A0.T)
        B = jax.jit(lambda M, b=b: band_reduce(M, b, 4 * b))(A)

        t_seq = bench(jax.jit(lambda M, b=b: chase_sequential(M, b)), B)
        t_wav = bench(jax.jit(lambda M, b=b: chase_wavefront(M, b)), B)
        # The paper's Fig-9 claim is about PARALLEL hardware: the wavefront
        # schedule exposes avg_par-way batch parallelism per step, which one
        # CPU core cannot realize (wall time here inverts, honestly).  The
        # structural reproduction is the schedule itself: serial executes
        # total_ops steps; the wavefront executes num_wavefronts steps of
        # avg_par concurrent Householder windows each.
        from repro.core.bulge_chasing import _kmax_table, num_wavefronts

        total_ops = int((_kmax_table(n, b) + 1).sum())
        W = num_wavefronts(n, b)
        avg_par = total_ops / max(W, 1)
        emit(f"bulge_sequential_n{n}_b{b}", t_seq, f"serial_steps={total_ops}",
             op="bulge_chase", n=n, backend="jnp")
        emit(
            f"bulge_wavefront_n{n}_b{b}", t_wav,
            f"wavefronts={W};avg_parallel_ops={avg_par:.1f};"
            f"ideal_speedup={total_ops/W:.1f};cpu1core_wall_ratio={t_seq/t_wav:.2f}",
            op="bulge_chase", n=n, backend="jnp",
        )
        from repro.kernels.ops import bulge_uses_kernel

        kernel = registry.resolve("bulge_chase", "pallas")
        ran_kernel = bulge_uses_kernel(n, b, group=1)  # bulge_chase's decision
        t_pal = bench(jax.jit(lambda M, b=b, kernel=kernel: kernel(M, b)), B)
        emit(
            f"bulge_pallas_n{n}_b{b}", t_pal,
            f"path={'kernel' if ran_kernel else 'xla_fallback'};"
            + (
                f"interpret={'off' if probe.is_tpu() else 'on'};"
                f"vmem_resident={int(probe.is_tpu())}"
                if ran_kernel else "above_interpret_ceiling=1"
            ),
            op="bulge_chase", n=n, backend="pallas",
        )
