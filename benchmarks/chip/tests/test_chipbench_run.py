"""A run end to end on the CPU at a small size (past the harness's look for
a chip), its result line, and the refusals."""
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

import harness
from conftest import BENCH, ROOT

# Small sizes the CPU can hold (configuration, traffic); the limits stay
# the cells' own.
SMALL = {
    "dense4096_eigvals": ({"n": 128}, {}),
}


def small_cell(name):
    cell = harness.load_cell(name)
    config, traffic = SMALL[name]
    cell.config.update(config)
    cell.traffic.update(traffic)
    return cell


def run_small(name, *, trace=False, seed=2**31 + 11):
    cell = small_cell(name)
    lines = []
    res = harness.run_cell(
        cell, seed=seed, seconds=0.2, trace=trace, devices=jax.devices()[: cell.chips],
        t_process=0.0, emit=lines.append,
    )
    return cell, res, lines


@pytest.mark.parametrize("name", sorted(SMALL))
def test_sound_run_is_correct_and_line_has_the_contract_keys(name):
    cell, res, lines = run_small(name)
    assert res["correct"] is True and res["failed"] == 0
    assert list(res)[:3] == ["correct", "attempted", "failed"]
    assert list(res)[-1] == "checks"
    assert {"metrics", "device"} <= set(res)
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(set(v) == {"value", "unit"} for v in res["metrics"].values())
    assert set(res["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert set(res["checks"]) == set(cell.limits) | {"retraces"}
    assert res["checks"]["retraces"] == {"value": 0, "limit": 0}
    assert res["attempted"] >= cell.traffic["batch"] and res["attempted"] % cell.traffic["batch"] == 0
    assert any("kernels" in line for line in lines)
    json.dumps(res)


def test_traced_run_reports_no_device_metric_from_the_cpu():
    cell, res, _ = run_small("dense4096_eigvals", trace=True)
    assert res["correct"] is True
    assert res["metrics"] == {}  # no device plane on the CPU: readers find nothing
    assert {"busy_s", "window_s", "memory_peak_bytes"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def _run_cli(cwd, env=None):
    cmd = [sys.executable, "benchmarks/chip/run.py", "--workload", "dense4096_eigvals",
           "--seed", "3", "--seconds", "1", "--trace", "0"]
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result_line(out: str) -> bool:
    return not any(line.startswith("{") and '"correct"' in line for line in out.splitlines())


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _run_cli(ROOT, env)
    assert p.returncode != 0
    assert _no_result_line(p.stdout)
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = _run_cli(tmp_path, env)
    assert p.returncode != 0
    assert _no_result_line(p.stdout)

