"""The Shampoo refresh cell, ``shampoo1024_root_b32``, on the CPU at a small
size: its data, its reading against numpy, its control, the work of
``backtransform_wy``, a run's ``correct`` with and without a fault, and its
metric readers on a trace of a vmapped refresh recorded on a v5e
(``record_refresh_trace.py``)."""
import importlib
from pathlib import Path

import jax
import numpy as np
import pytest

import calibrate
import devtrace
import harness
import hlo
import precision as precision_mod
import stages
from hlo import CustomCall, Shape
from test_chipbench_control import _dot_precisions

CELL = "shampoo1024_root_b32"
DATA = Path(__file__).resolve().parent / "data"
TRACE = DATA / "v5e_refresh.xplane.pb"
KERNELS = DATA / "v5e_refresh_kernels.txt"
STAGES = ["begin", "first_stage", "bulge_chase", "bisection", "inverse_iteration",
          "backtransform_q2", "backtransform_q1", "root"]


def small_cell(n=128, batch=2):
    cell = harness.load_cell(CELL)
    cell.config.update(n=n, statistics=dict(cell.config["statistics"], rank=n // 4))
    cell.traffic.update(batch=batch)
    return cell


def _make(cell, seed):
    return harness.load_module("data", cell.config["generator"]).make(
        cell.config, cell.traffic, seed
    )


# ------------------------------------------------------------------ data
def test_statistics_are_seeded_float32_and_low_rank_plus_ridge():
    cell = small_cell()
    a, b = _make(cell, 2**31 + 5), _make(cell, 2**31 + 5)
    assert np.array_equal(a["operand"], b["operand"])
    assert not np.array_equal(a["operand"], _make(cell, 2**31 + 6)["operand"])
    S = a["operand"]
    assert S.shape == (2, 128, 128) and S.dtype == np.float32
    assert np.array_equal(a["operand64"], S.astype(np.float64))
    assert np.array_equal(S, np.swapaxes(S, 1, 2))
    # n - rank eigenvalues sit at the ridge: a cluster far narrower than
    # eps ||S||, the rest well above it.
    w = np.linalg.eigvalsh(a["operand64"][0])
    cluster, bulk = w[: 128 - 32], w[128 - 32:]
    assert np.ptp(cluster) < 2 * np.finfo(np.float32).eps * w.max()
    assert bulk.min() > cluster.max() + 0.1 * cluster.max()


def test_reading_is_the_frobenius_error_against_numpys_root():
    cell = small_cell()
    entry = harness.load_module("entries", "inverse_pth_root")
    data = _make(cell, 7)
    X = np.empty_like(data["operand64"])
    for i, S in enumerate(data["operand64"]):
        w, V = np.linalg.eigh(S)
        X[i] = (V * (w + 1e-6 * w.max()) ** -0.25) @ V.T
    X[1] *= 1 + 1e-4
    r = entry.readings(X.astype(np.float32), data, cell.config, cell.traffic)["root_err"]
    assert r.shape == (2,)
    assert r[0] < 1e-6
    assert r[1] == pytest.approx(1e-4, rel=1e-2)


# ------------------------------------------------------------------ runs
def _run(cell, seed=2**31 + 11):
    return harness.run_cell(
        cell, seed=seed, seconds=0.2, trace=False, devices=jax.devices()[:1],
        t_process=0.0, emit=lambda _: None,
    )


def test_sound_run_is_correct():
    cell = small_cell()
    res = _run(cell)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] % 2 == 0 and res["attempted"] >= 2
    assert set(res["metrics"]) == {"solve_s", "setup_s"}
    assert 0 < res["checks"]["root_err"]["value"] <= cell.limits["root_err"]


def test_a_root_off_by_its_limit_is_caught(monkeypatch):
    # The batched refresh calls the plan module's root through its own name.
    batch_mod = importlib.import_module("repro.solver.batch")
    orig = batch_mod._inverse_pth_root

    def root(A, eps, *, pl, p):
        return orig(A, eps, pl=pl, p=p) * (1 + 1e-4)

    jax.clear_caches()
    monkeypatch.setattr(batch_mod, "_inverse_pth_root", root)
    try:
        res = _run(small_cell(), seed=2**31 + 29)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert res["correct"] is False and res["failed"] >= 1


# ------------------------------------------------------------------ control
@pytest.mark.parametrize("precision", ["high", "highest"])
def test_control_dots_run_at_the_switched_precision(precision):
    cell = small_cell()
    entry = harness.load_module("entries", "inverse_pth_root")
    prog = entry.build(cell.config, cell.traffic, _make(cell, 3), jax.devices()[:1])
    with precision_mod.switched(precision):
        fn = entry.control_fn(cell.config, cell.traffic, jax.devices()[:1])
        dots = _dot_precisions(jax.make_jaxpr(fn)(*prog["args"]).jaxpr)
    want = f"(Precision.{precision.upper()}, Precision.{precision.upper()})"
    assert dots and set(dots) == {want}, dots
    dots = _dot_precisions(jax.make_jaxpr(prog["fn"])(*prog["args"]).jaxpr)
    assert dots and set(dots) == {"(Precision.HIGHEST, Precision.HIGHEST)"}, dots


def test_calibrate_reads_program_and_control():
    cell = small_cell()
    devices = jax.devices()[:1]
    prog = calibrate.raw_readings(cell, [2**31 + 1], devices)
    ctrl = calibrate.raw_readings(cell, [2**31 + 3], devices, calibrate.CONTROL_PRECISION)
    summary = calibrate.summary(
        [calibrate._worst(r) for r in prog], [calibrate._worst(r) for r in ctrl]
    )
    assert set(summary) == {"root_err"}
    assert 0 < summary["root_err"]["lower"] <= cell.limits["root_err"]
    assert summary["root_err"]["upper"] > 0
    assert harness.judge(prog, cell.limits, 0)["correct"] is True
    over = {"root_err": 0.5 * min(summary["root_err"]["lower"], summary["root_err"]["upper"])}
    judged = harness.judge(ctrl, over, 0)
    assert judged["correct"] is False and judged["failed"] >= 1


# ------------------------------------------------------------------ work
def f32(*dims):
    return Shape("f32", tuple(dims))


def _bt(n, b, K, s8, rows, batch=()):
    return CustomCall("backtransform_wy.1", "backtransform_wy", (f32(*batch, rows, n),), (
        f32(*batch, s8, K * b), f32(*batch, s8, K), f32(*batch, rows, n)
    ))


def test_backtransform_wy_hand_count():
    # n = 5, b = 2: sweeps s = 0, 1, 2 hold 2, 1, 1 live reflectors (K = 2),
    # each 4 * b * m = 40 FLOPs on the five columns; the log pads 3 sweeps to 8.
    work = harness.load_module("work", "backtransform_wy").work
    assert work(_bt(5, 2, 2, 8, 16)) == (4 * 40, 4 * (2 * 16 * 5 + 8 * 4 + 8 * 2))
    with pytest.raises(ValueError):
        work(_bt(5, 2, 3, 8, 16))  # a log too long for n = m = 5


def test_backtransform_wy_at_the_compiled_v5e_refresh_shape():
    # The kernel call of vmap(backtransform_wy_pallas) at n = 1024, b = 8,
    # G = 16, batch 32, compiled for a v5e (cut after the layouts).
    line = (
        '  %backtransform_wy.1 = f32[32,1160,1024]{2,1,0:T(8,128)} custom-call(%pad.6, '
        '%custom-call, %pad.8), custom_call_target="tpu_custom_call", '
        'operand_layout_constraints={f32[32,1024,1024]{2,1,0}, f32[32,1024,128]{2,1,0}, '
        'f32[32,1160,1024]{2,1,0}}, frontend_attributes={kernel_metadata={}}'
    )
    call = hlo.custom_calls(line)["backtransform_wy.1"]
    flops, nbytes = harness.load_module("work", "backtransform_wy").work(call)
    live = sum((1021 - s) // 8 + 1 for s in range(1022))
    assert flops == 32 * 4 * 8 * 1024 * live
    assert nbytes == 32 * 4 * (2 * 1160 * 1024 + 1024 * 1024 + 1024 * 128)


# ------------------------------------------------------------------ trace
def _recorded_ctx():
    t = devtrace.load(str(TRACE))
    calls = hlo.custom_calls(KERNELS.read_text())
    return harness.Context(1.0, t.window_s, 3, 12, "TPU v5 lite", trace=t, custom_calls=calls)


def test_recorded_refresh_marks_every_stage_in_order():
    t = devtrace.load(str(TRACE))
    solves = stages.marks_by_solve(t)
    whole = [m for _, m in solves if "begin" in m]
    assert len(solves) == 3 and len(whole) >= 2
    for marks in whole:
        assert sorted(marks, key=lambda s: marks[s].start_ns) == STAGES


def test_recorded_refresh_metrics():
    ctx = _recorded_ctx()
    ms = harness.load_module("metrics", "inverse_iteration_ms.refresh").read(ctx)
    spans = stages.intervals(ctx.trace, "bisection", "inverse_iteration")
    assert len(spans) == 3
    assert ms == pytest.approx(1e-6 * sum(e - s for _, s, e in spans) / 3)
    assert 0 < ms < 1e3 * ctx.window_s / 3
    share = harness.load_module("metrics", "backtransform_wy_roofline.refresh").read(ctx)
    assert ctx.trace.kernel_events("backtransform_wy")
    assert 0 < share <= 100


def test_refresh_metrics_read_nothing_without_a_trace_or_marks():
    ctx = harness.Context(1.0, 1.0, 1, 1, "TPU v5 lite")
    for m in ("inverse_iteration_ms.refresh", "backtransform_wy_roofline.refresh"):
        assert harness.load_module("metrics", m).read(ctx) is None
    t = devtrace.DeviceTrace(window=(0, 10), device_ops={
        "/device:TPU:0": devtrace.Ops.of([devtrace.Event("fusion.1", 0, 5)])
    }, host_spans=[])
    ctx = harness.Context(1.0, 1.0, 1, 1, "TPU v5 lite", trace=t)
    assert harness.load_module("metrics", "inverse_iteration_ms.refresh").read(ctx) is None
