#!/usr/bin/env python3
"""Record ``data/v5e_marks.xplane.pb``, the trace the stage-mark readers'
tests read: three calls of ``plan(512, float32).eigvals`` in the harness's
window and call spans, each marking ``begin``, ``first_stage``,
``bulge_chase`` and ``bisection``.  Bisection runs 8 steps (``tol=1e-2``)
in place of 48, which keeps the file small.  Run it on a machine with a
TPU, from the repository root:

    python3 benchmarks/chip/tests/record_marks_trace.py

It prints each mark's device duration and the device time between marks.
"""
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[2] / "src"))
sys.path.insert(0, str(HERE.parent))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import devtrace  # noqa: E402
import stages  # noqa: E402
from harness import _window  # noqa: E402
from repro.solver import EvdConfig, plan  # noqa: E402

N = 512
OUT = HERE / "data" / "v5e_marks.xplane.pb"


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_marks_trace: needs a TPU", file=sys.stderr)
        return 1
    g = np.random.default_rng(0).standard_normal((N, N))
    a = jnp.asarray((g + g.T).astype(np.float32))
    pl = plan(N, jnp.float32, EvdConfig(tol=1e-2))
    fn = jax.jit(pl.eigvals).lower(a).compile()
    jax.block_until_ready(fn(a))
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
            for _ in range(3):
                _window(fn, (a,), 0.0, jax.profiler.TraceAnnotation)
        jax.profiler.stop_trace()
        src = next(Path(tmp).rglob("*.xplane.pb"))
        OUT.parent.mkdir(exist_ok=True)
        shutil.copy(src, OUT)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    t = devtrace.load(str(OUT))
    for d, marks in stages.marks_by_solve(t):
        order = sorted(marks.items(), key=lambda kv: kv[1].start_ns)
        print({"device": d, "mark_us": {s: e.duration_ns * 1e-3 for s, e in order},
               "between_ms": {f"{a}..{b}": (eb.start_ns - ea.end_ns) * 1e-6
                              for (a, ea), (b, eb) in zip(order, order[1:])}})
    print({"bytes": OUT.stat().st_size, "busy_s": t.mean_busy_s(), "window_s": t.window_s})
    return 0


if __name__ == "__main__":
    sys.exit(main())
