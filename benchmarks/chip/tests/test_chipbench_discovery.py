"""Everything BENCHMARK.json names is found by name, and the file keeps the
shape the benchmark's contract gives it."""
import json
import re

import pytest

import harness
from conftest import BENCH, ROOT

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys():
    assert set(SPEC) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"
    }
    assert SPEC["paths"] == ["benchmarks/chip"]
    assert (ROOT / SPEC["command"][1]).resolve().parent == BENCH
    assert 1 <= SPEC["run_seconds"] <= 51


def test_names_units_and_keys():
    names = [c["name"] for c in SPEC["configs"]] + CELLS + [
        m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]
    ]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert set(m["workloads"]) <= set(CELLS)
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_loads_by_name(cell):
    c = harness.load_cell(cell)
    assert c.chips in (1, 4)
    assert harness.load_module("data", c.config["generator"]).make
    entry = harness.load_module("entries", c.traffic["entry"])
    assert entry.build and entry.readings
    assert c.limits and all(v > 0 for v in c.limits.values())
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer and all(m["moves"] in e2e for m in c.per_layer)


def test_every_metric_has_a_reader_and_every_roofline_a_work_function():
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert harness.load_module("metrics", m["name"]).read
    kernels = re.findall(r'roofline_pct\("(\w+)"\)', "".join(
        p.read_text() for p in (BENCH / "metrics").glob("*.py")
    ))
    assert kernels
    for k in kernels:
        assert harness.load_module("work", k).work


def test_configs_name_their_files_and_every_config_is_used():
    used = {w["config"] for w in SPEC["workloads"]}
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))
    for c in SPEC["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("benchmarks/chip/")
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]


def test_peaks_for_the_v5e_and_unknown_kinds_refused():
    ctx = harness.Context(1.0, 1.0, 1, 1, "TPU v5 lite")
    assert ctx.peaks()["flops_per_s"] == 197e12
    assert ctx.peaks()["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.Context(1.0, 1.0, 1, 1, "TPU v9 imaginary").peaks()


def test_unknown_names_are_errors():
    with pytest.raises(KeyError):
        harness.load_cell("no_such_cell")
    with pytest.raises(FileNotFoundError):
        harness.load_module("metrics", "no_such_metric")
