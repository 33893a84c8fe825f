"""The control: the program with its own precision switch
(``repro.solver.plan.MATMUL_PRECISION``) at ``high``, one step below the
``highest`` the configurations state, its kernels run as their XLA
reference.  The CPU computes every float32 dot in full whatever its
precision, so the tests here see that the control's program is what it
claims -- every dot it traces runs at the switched precision, where the
cell's own program runs each at ``highest`` -- and that ``calibrate.py``
reads both through the entry.  That the control fails the cell's limits is
read on the chip, at the cell's own size, by ``calibrate.py``, which puts
the control's outputs through the judge and exits 3 when they pass."""
from collections import Counter

import jax
import pytest

import calibrate
import harness
import precision as precision_mod
from test_chipbench_run import SMALL, small_cell


def _dot_precisions(jaxpr) -> Counter:
    """The precision of every ``dot_general`` in ``jaxpr``, sub-jaxprs too."""
    out = Counter()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            out[str(eqn.params["precision"])] += 1
        for v in eqn.params.values():
            for x in v if isinstance(v, (list, tuple)) else [v]:
                sub = getattr(x, "jaxpr", x)
                if hasattr(sub, "eqns"):
                    out += _dot_precisions(sub)
    return out


def _program_and_control(name):
    cell = small_cell(name)
    devices = jax.devices()[: cell.chips]
    entry = harness.load_module("entries", cell.traffic["entry"])
    data = harness.load_module("data", cell.config["generator"]).make(
        cell.config, cell.traffic, 2**31 + 1
    )
    return cell, entry, entry.build(cell.config, cell.traffic, data, devices), devices


@pytest.mark.parametrize("precision", ["high", "highest"])
@pytest.mark.parametrize("name", sorted(SMALL))
def test_control_dots_run_at_the_switched_precision(name, precision):
    cell, entry, prog, devices = _program_and_control(name)
    with precision_mod.switched(precision):
        fn = entry.control_fn(cell.config, cell.traffic, devices)
        dots = _dot_precisions(jax.make_jaxpr(fn)(*prog["args"]).jaxpr)
    want = f"(Precision.{precision.upper()}, Precision.{precision.upper()})"
    assert dots and set(dots) == {want}, dots


@pytest.mark.parametrize("name", sorted(SMALL))
def test_program_dots_run_at_highest(name):
    _, _, prog, _ = _program_and_control(name)
    dots = _dot_precisions(jax.make_jaxpr(prog["fn"])(*prog["args"]).jaxpr)
    assert dots and set(dots) == {"(Precision.HIGHEST, Precision.HIGHEST)"}, dots


@pytest.mark.parametrize("name", sorted(SMALL))
def test_calibrate_reads_program_and_control(name):
    cell = small_cell(name)
    devices = jax.devices()[: cell.chips]
    prog = calibrate.raw_readings(cell, [2**31 + 1, 5], devices)
    ctrl = calibrate.raw_readings(cell, [2**31 + 3], devices, calibrate.CONTROL_PRECISION)
    summary = calibrate.summary(
        [calibrate._worst(r) for r in prog], [calibrate._worst(r) for r in ctrl]
    )
    assert set(summary) == set(cell.limits)
    for k, limit in cell.limits.items():
        assert 0 < summary[k]["lower"] <= limit
        assert summary[k]["upper"] > 0
    assert harness.judge(prog, cell.limits, 0)["correct"] is True
    # Over the limits, the control's outputs fail the judge as a run's do.
    over = {k: 0.5 * min(summary[k]["lower"], summary[k]["upper"]) for k in cell.limits}
    judged = harness.judge(ctrl, over, 0)
    assert judged["correct"] is False and judged["failed"] >= 1
