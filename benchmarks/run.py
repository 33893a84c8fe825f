# One function per paper table. Print ``name,us_per_call,derived`` CSV and
# write machine-readable BENCH_<suite>.json records per suite.
"""Benchmark harness.

    PYTHONPATH=src python -m benchmarks.run [--only syr2k,dbr,...]
        [--smoke] [--json-dir experiments/bench]

Paper-artifact mapping (DESIGN.md §8):
    syr2k   -> Table 1 / Figure 8
    dbr     -> Table 2 / Figure 4
    bulge   -> Figure 9
    tridiag -> Figure 10
    evd     -> Figure 11
    batched -> beyond-paper (solve_many front door: the many-matrices regime)
    shampoo -> beyond-paper (production consumer)

Each suite also writes ``<json-dir>/BENCH_<suite>.json``: a list of
``{name, op, n, dtype, backend, median_ms, derived}`` records plus a
header with the platform/backend the run resolved to — the perf
trajectory CI steps collect over time.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--only", default=None, help="comma-separated subset")
    p.add_argument(
        "--smoke", action="store_true",
        help="smallest problem sizes (CI CPU smoke; sets REPRO_BENCH_SMOKE)",
    )
    p.add_argument(
        "--json-dir", default="experiments/bench",
        help="directory for BENCH_<suite>.json records ('' disables)",
    )
    args = p.parse_args()

    if args.smoke:
        os.environ["REPRO_BENCH_SMOKE"] = "1"

    from benchmarks import (
        bench_syr2k,
        bench_dbr,
        bench_bulge,
        bench_tridiag,
        bench_evd,
        bench_batched,
        bench_shampoo,
    )
    from benchmarks import common
    from repro.backend import probe, registry
    from repro.backend.cache import enable_compilation_cache

    enable_compilation_cache()

    suites = {
        "syr2k": bench_syr2k.run,
        "dbr": bench_dbr.run,
        "bulge": bench_bulge.run,
        "tridiag": bench_tridiag.run,
        "evd": bench_evd.run,
        "batched": bench_batched.run,
        "shampoo": bench_shampoo.run,
    }
    selected = args.only.split(",") if args.only else list(suites)
    if args.json_dir:
        os.makedirs(args.json_dir, exist_ok=True)
    print("name,us_per_call,derived")
    for name in selected:
        common.reset_records()
        t0 = time.time()
        suites[name]()
        elapsed = time.time() - t0
        print(f"# suite {name} done in {elapsed:.0f}s", file=sys.stderr)
        if args.json_dir:
            payload = {
                "suite": name,
                "platform": probe.platform(),
                "default_backend": registry.default_backend(),
                "smoke": common.is_smoke(),
                "elapsed_s": round(elapsed, 1),
                "records": common.records(),
            }
            path = os.path.join(args.json_dir, f"BENCH_{name}.json")
            with open(path, "w") as f:
                json.dump(payload, f, indent=2)
            print(f"# wrote {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
