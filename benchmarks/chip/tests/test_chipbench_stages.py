"""The stage-mark reader: marks cut into solves, the interval between two
marks and its idle share, on hand-made events and on a trace of
``plan(512).eigvals`` recorded on a v5e (``record_marks_trace.py``)."""
from pathlib import Path

import pytest

import devtrace
import harness
import stages
from devtrace import DeviceTrace, Event, Ops

RECORDED = Path(__file__).resolve().parent / "data" / "v5e_marks.xplane.pb"
TPU = "/device:TPU:0"
WHOLE = ["begin", "first_stage", "bulge_chase", "bisection"]


def _hand_made():
    # Solve 1 holds both marks, solve 2 only its begin mark.  Between the
    # marks of solve 1 ([12, 50]) the device is busy over [20, 40].  As on
    # the v5e, the device's stamps run early against the host's call
    # spans: solve 2 begins inside the first call's span.
    ops = Ops.of([
        Event("evd_mark_begin.1", 10, 12), Event("fusion.3", 20, 30),
        Event("syr2k_lower.2", 25, 40), Event("evd_mark_first_stage.1", 50, 52),
        Event("fusion.4", 60, 90), Event("evd_mark_begin.1", 92, 94),
        Event("fusion.3", 115, 150),
    ])
    host = [Event(devtrace.CALL_SPAN, 15, 95), Event(devtrace.CALL_SPAN, 100, 160)]
    return DeviceTrace(window=(0, 160), device_ops={TPU: ops}, host_spans=host)


def _without(trace, kernel):
    """``trace`` with every event of ``kernel`` taken out."""
    out = {}
    for d, ops in trace.device_ops.items():
        keep = ~ops.matching(kernel)
        out[d] = Ops(ops.names, ops.name_id[keep], ops.start[keep], ops.end[keep])
    return DeviceTrace(window=trace.window, device_ops=out, host_spans=trace.host_spans)


def test_marks_are_cut_into_solves_at_begin_by_hand():
    t = _hand_made()
    solves = stages.marks_by_solve(t)
    assert [(d, sorted(m)) for d, m in solves] == [
        (TPU, ["begin", "first_stage"]), (TPU, ["begin"]),
    ]
    assert solves[1][1]["begin"].start_ns == 92
    # A solve whose begin mark lies before the window is a solve without it.
    late = _without(t, "evd_mark_begin")
    assert [sorted(m) for _, m in stages.marks_by_solve(late)] == [["first_stage"]]
    assert stages.marks_by_solve(_without(late, "evd_mark_first_stage")) == []


def test_interval_and_its_idle_share_by_hand():
    t = _hand_made()
    spans = stages.intervals(t, "begin", "first_stage")
    assert spans == [(TPU, 12, 50)]
    assert stages.idle_share_pct(t, spans) == pytest.approx(100 * (1 - 20 / 38))
    assert stages.intervals(t, "first_stage", "begin") == []


@pytest.mark.parametrize("missing", ["evd_mark_begin", "evd_mark_first_stage"])
@pytest.mark.parametrize("trace", ["hand_made", "recorded"])
def test_no_interval_without_both_marks(missing, trace):
    t = _hand_made() if trace == "hand_made" else devtrace.load(str(RECORDED))
    assert stages.intervals(_without(t, missing), "begin", "first_stage") == []


def test_recorded_v5e_marks_in_stage_order():
    # Three calls.  The device's stamps run early against the host's
    # window span, so the first solve's first marks fall before the window
    # and that solve is read without its begin mark; the other two are
    # whole.
    t = devtrace.load(str(RECORDED))
    solves = stages.marks_by_solve(t)
    assert len(solves) == 3
    for i, (d, marks) in enumerate(solves):
        assert d == TPU
        order = sorted(marks, key=lambda s: marks[s].start_ns)
        assert order == (WHOLE[WHOLE.index(order[0]):] if i == 0 else WHOLE)
        assert all(0 < e.duration_ns < 10_000 for e in marks.values())
    for a, b in zip(WHOLE, WHOLE[1:]):
        assert len(stages.intervals(t, a, b)) >= 2


def test_recorded_v5e_first_stage_idle_share():
    t = devtrace.load(str(RECORDED))
    spans = stages.intervals(t, "begin", "first_stage")
    assert spans and all(0 < e - s < 1e9 * t.window_s for _, s, e in spans)
    assert 0 <= stages.idle_share_pct(t, spans) < 100
    # The share over one interval is the whole window's reader on that
    # interval alone.
    d, s, e = spans[0]
    ops = t.device_ops[d]
    keep = (ops.end > s) & (ops.start < e)
    clipped = DeviceTrace(window=(s, e), device_ops={d: Ops(
        ops.names, ops.name_id[keep], ops.start[keep].clip(s, e), ops.end[keep].clip(s, e)
    )}, host_spans=[])
    ctx = harness.Context(1.0, 1.0, 1, 1, "TPU v5 lite", trace=clipped)
    assert stages.idle_share_pct(t, spans[:1]) == pytest.approx(ctx.idle_share_pct())
