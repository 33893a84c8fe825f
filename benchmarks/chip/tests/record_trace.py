#!/usr/bin/env python3
"""Record ``data/v5e_small.xplane.pb``, the small trace the reducer's tests
read: three calls of a jitted trailing update (the ``syr2k_lower`` kernel
between two XLA fusions) in the harness's window and call spans.  Run it on
a machine with a TPU, from the repository root:

    python3 benchmarks/chip/tests/record_trace.py
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

import devtrace  # noqa: E402
from harness import _window  # noqa: E402
from repro.kernels import ops  # noqa: E402


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 1
    a = jax.random.normal(jax.random.key(0), (512, 256), jnp.float32)
    c = jnp.eye(512, dtype=jnp.float32)
    fn = jax.jit(lambda a, c: ops.trailing_update(c * 2.0, a, a) + 1.0).lower(a, c).compile()
    jax.block_until_ready(fn(a, c))
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
            _window(fn, (a, c), 0.0, jax.profiler.TraceAnnotation)
            _window(fn, (a, c), 0.0, jax.profiler.TraceAnnotation)
            _window(fn, (a, c), 0.0, jax.profiler.TraceAnnotation)
        jax.profiler.stop_trace()
        src = next(Path(tmp).rglob("*.xplane.pb"))
        (HERE / "data").mkdir(exist_ok=True)
        shutil.copy(src, HERE / "data" / "v5e_small.xplane.pb")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    t = devtrace.load(str(HERE / "data" / "v5e_small.xplane.pb"))
    print({"devices": t.devices, "busy_s": t.mean_busy_s(), "window_s": t.window_s,
           "top_ops": t.top_ops(5), "idle_gaps": t.idle_gaps(5)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
