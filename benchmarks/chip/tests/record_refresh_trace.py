#!/usr/bin/env python3
"""Record the fixtures the refresh metrics' tests read:

* ``data/v5e_refresh.xplane.pb``: three calls of a vmapped refresh,
  ``solve_many(S, EvdConfig(tol=1e-2), op="inverse_pth_root", p=4)`` on a
  (4, 256, 256) stack of Shampoo statistics, in the harness's window and
  call spans; each call marks ``begin`` through ``root``.  Bisection runs 8
  steps (``tol=1e-2``) in place of 48, which keeps the file small;
* ``data/v5e_refresh_kernels.txt``: the compiled program's kernel calls
  (``tpu_custom_call`` lines, cut before their ``backend_config``), whose
  shapes the roofline reads.

Run it on a machine with a TPU, from the repository root:

    python3 benchmarks/chip/tests/record_refresh_trace.py

It prints each solve's marks, the device time between them, and the two
refresh metrics read from the new files.
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
import harness  # noqa: E402
import hlo  # noqa: E402
import stages  # noqa: E402
from harness import _window  # noqa: E402
from repro.solver import EvdConfig, solve_many  # noqa: E402

N, BATCH = 256, 4
TRACE = HERE / "data" / "v5e_refresh.xplane.pb"
KERNELS = HERE / "data" / "v5e_refresh_kernels.txt"


def kernel_lines(text: str) -> str:
    keep = [line.split(", backend_config=")[0] for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]
    return "\n".join(keep) + "\n"


def main() -> int:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("record_refresh_trace: needs a TPU", file=sys.stderr)
        return 1
    config = harness.load_cell("shampoo1024_root_b32").config
    data = harness.load_module("data", "shampoo_stats").make(
        dict(config, n=N), {"batch": BATCH}, 0
    )
    S = jax.device_put(jnp.asarray(data["operand"]), devices[0])
    cfg = EvdConfig(tol=1e-2)
    fn = jax.jit(lambda S: solve_many(S, cfg, op="inverse_pth_root", p=4)).lower(S).compile()
    KERNELS.parent.mkdir(exist_ok=True)
    KERNELS.write_text(kernel_lines(fn.as_text()))
    jax.block_until_ready(fn(S))
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
            for _ in range(3):
                _window(fn, (S,), 0.0, jax.profiler.TraceAnnotation)
        jax.profiler.stop_trace()
        shutil.copy(next(Path(tmp).rglob("*.xplane.pb")), TRACE)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    t = devtrace.load(str(TRACE))
    for d, marks in stages.marks_by_solve(t):
        order = sorted(marks.items(), key=lambda kv: kv[1].start_ns)
        print({"device": d, "mark_us": {s: e.duration_ns * 1e-3 for s, e in order},
               "between_ms": {f"{a}..{b}": (eb.start_ns - ea.end_ns) * 1e-6
                              for (a, ea), (b, eb) in zip(order, order[1:])}})
    ctx = harness.Context(1.0, t.window_s, 3, 3 * BATCH, devices[0].device_kind, trace=t,
                          custom_calls=hlo.custom_calls(KERNELS.read_text()))
    print({m: harness.load_module("metrics", m).read(ctx)
           for m in ("inverse_iteration_ms.refresh", "backtransform_wy_roofline.refresh")})
    print({"bytes": TRACE.stat().st_size, "kernel_bytes": KERNELS.stat().st_size,
           "busy_s": t.mean_busy_s(), "window_s": t.window_s,
           "kernels": hlo.kernels_in(KERNELS.read_text())})
    return 0


if __name__ == "__main__":
    sys.exit(main())
