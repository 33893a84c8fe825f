#!/usr/bin/env python3
"""Readings from which a cell's limits are set: the program's, and its
control's.

    python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 1,2,... \
        --control-seeds 101,102,103

In one process, for every program seed: the cell's data, one call of the
cell's own compiled program at the cell's own sizes, and the numbers its
entry compares (the worst over the call's answers).  Then, for every control
seed, the same numbers from the control: the program with its own precision
switch, ``repro.solver.plan.MATMUL_PRECISION``, at ``high``, one step below
the ``highest`` the configurations state (``precision.py``), run as the
entry's ``control_fn`` gives it.  The control's path at ``highest`` is read
too, on the first control seed, to show that the path alone does not move
the numbers.

Prints one JSON line per seed and a summary with, per number, the lower
reading (the largest over the program's seeds) and the upper reading (the
smallest over the control's seeds).  The control's outputs then go through
``harness.judge`` against the cell's own limits, as a run's outputs do: the
script exits 3 when the control comes out correct, since the limits then
cannot tell it from the program.  The benchmark's runs never run this;
``PERF.md`` records the readings and the limits set between them.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from typing import Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

CONTROL_PRECISION = "high"


def _worst(r: dict) -> Dict[str, float]:
    import numpy as np

    return {k: float(np.max(v)) for k, v in r.items()}


def raw_readings(
    cell, seeds: List[int], devices: list, control: Optional[str] = None
) -> List[Dict]:
    """The entry's readings of one call per seed, one per answer: of the
    cell's program, or (``control`` a precision) of the entry's control at
    that precision; either compiled once."""
    import contextlib

    import jax

    import harness
    from precision import switched

    gen = harness.load_module("data", cell.config["generator"])
    entry = harness.load_module("entries", cell.traffic["entry"])
    fn = None
    out = []
    with switched(control) if control else contextlib.nullcontext():
        for seed in seeds:
            data = gen.make(cell.config, cell.traffic, seed)
            prog = entry.build(cell.config, cell.traffic, data, devices)
            if fn is None:
                fn = jax.jit(
                    entry.control_fn(cell.config, cell.traffic, devices) if control else prog["fn"]
                )
            host = jax.device_get(jax.block_until_ready(fn(*prog["args"])))
            out.append(entry.readings(host, data, cell.config, cell.traffic))
    return out


def readings(cell, seeds: List[int], devices: list, control: Optional[str] = None) -> List[Dict]:
    """One dict of worst readings per seed (see :func:`raw_readings`)."""
    return [_worst(r) for r in raw_readings(cell, seeds, devices, control)]


def summary(program: List[Dict], control: List[Dict]) -> Dict[str, Dict[str, float]]:
    out = {}
    for k in program[0]:
        lo = max(r[k] for r in program)
        hi = min(r[k] for r in control) if control else None
        out[k] = {"lower": lo, "upper": hi, "upper_over_lower": hi / lo if hi and lo else None}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated program seeds")
    ap.add_argument("--control-seeds", default="", help="comma-separated control seeds")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    import harness

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    cell = harness.load_cell(args.workload, ROOT)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"calibrate: needs {cell.chips} TPU chips; found {devices}", file=sys.stderr)
        return 1
    devices = devices[: cell.chips]
    seeds = [int(s) for s in args.seeds.split(",") if s]
    cseeds = [int(s) for s in args.control_seeds.split(",") if s]
    prog = readings(cell, seeds, devices)
    for s, r in zip(seeds, prog):
        print(json.dumps({"kind": "program", "seed": s, **r}), flush=True)
    if cseeds:
        (path,) = readings(cell, cseeds[:1], devices, "highest")
        print(json.dumps({"kind": "control_path", "precision": "highest", "seed": cseeds[0],
                          **path}), flush=True)
    raw = raw_readings(cell, cseeds, devices, CONTROL_PRECISION) if cseeds else []
    ctrl = [_worst(r) for r in raw]
    for s, r in zip(cseeds, ctrl):
        print(json.dumps({"kind": "control", "precision": CONTROL_PRECISION, "seed": s, **r}),
              flush=True)
    print(json.dumps({"workload": cell.name, "limits": cell.limits,
                      "summary": summary(prog, ctrl)}), flush=True)
    if not raw:
        return 0
    judged = harness.judge(raw, cell.limits, 0)
    print(json.dumps({"kind": "control_judged", **judged}), flush=True)
    return 3 if judged["correct"] else 0


if __name__ == "__main__":
    sys.exit(main())
