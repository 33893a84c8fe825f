#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cells, configurations and metrics are those of ``BENCHMARK.json`` at the
repository root.  A run turns on JAX's persistent compilation cache at the
fixed ``<checkout>/.jax_cache``, refuses to run without a TPU or with fewer
chips than the cell asks for, makes the cell's data from ``--seed``,
compiles and warms the cell's program, times whole calls back to back for
``--seconds`` (under the profiler with ``--trace 1``), and then checks every
output of the window against a float64 reference on the host.

Earlier stdout lines carry the plan, the kernels in the compiled program,
the compile and call seconds.  The last stdout line is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (end-to-end metrics with
``--trace 0``, per-layer metrics with ``--trace 1``), ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``: each number compared
beside its limit.  The same numbers end standard error.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE_DIR = ROOT / ".jax_cache"


def _emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def _fail(msg: str, code: int = 1) -> int:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)
    return code


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT / "src"))
    try:
        import repro
    except ImportError as e:
        return _fail(f"the system under test is not in this checkout ({e})", 2)
    if ROOT not in Path(repro.__file__).resolve().parents:
        return _fail(f"imported repro from {repro.__file__}, outside {ROOT}", 2)
    import harness

    try:
        cell = harness.load_cell(args.workload, ROOT)
    except (KeyError, FileNotFoundError, StopIteration) as e:
        return _fail(f"cannot load workload {args.workload!r}: {e!r}", 2)

    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        return _fail(f"no TPU: JAX found platform {devices[0].platform!r}")
    if len(devices) < cell.chips:
        return _fail(f"{args.workload} needs {cell.chips} chips; JAX found {len(devices)}")
    _emit({"jax": jax.__version__, "kind": devices[0].device_kind, "devices": len(devices),
           "workload": cell.name, "seed": args.seed, "seconds": args.seconds,
           "trace": args.trace, "compilation_cache": str(CACHE_DIR)})

    result = harness.run_cell(
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        devices=devices[: cell.chips], t_process=T_PROCESS, emit=_emit,
    )
    for name, c in result["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "OVER"
        print(f"check {name} = {c['value']!r} limit {c['limit']!r} {ok}", file=sys.stderr)
    print(f"correct {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
