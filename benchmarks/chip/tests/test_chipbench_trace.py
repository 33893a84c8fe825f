"""The trace reducer: busy time as a union, kernel sums, idle gaps named by
host spans, on hand-made events and on a small trace recorded on a v5e."""
from pathlib import Path

import pytest

import devtrace
import harness
from devtrace import DeviceTrace, Event, Ops

RECORDED = Path(__file__).resolve().parent / "data" / "v5e_small.xplane.pb"


def _busy(pairs):
    ops = {"/device:TPU:0": Ops.of([Event("op", s, e) for s, e in pairs])}
    return DeviceTrace(window=(0, 100), device_ops=ops, host_spans=[]).busy_s("/device:TPU:0")


def test_busy_is_the_union_of_overlapping_ops():
    assert _busy([]) == 0
    idle = DeviceTrace(window=(0, 100), device_ops={"/device:TPU:0": Ops.of([])}, host_spans=[])
    assert idle.idle_gaps() == [("no host span / after window start", pytest.approx(100e-9))]
    assert _busy([(0, 2), (1, 3), (5, 6)]) == pytest.approx(4e-9)
    assert _busy([(0, 10), (2, 3), (4, 5)]) == pytest.approx(10e-9)


def _hand_made():
    ops = {
        "/device:TPU:0": Ops.of([
            Event("fusion.1", 0, 20), Event("syr2k_lower.3", 10, 40),  # overlap
            Event("syr2k_lower.7", 60, 80), Event("bulge_chase_wavefront", 90, 95),
        ]),
        "/device:TPU:1": Ops.of([Event("syr2k_lower.3", 0, 50)]),
    }
    host = [Event(devtrace.CALL_SPAN, 0, 55), Event(devtrace.CALL_SPAN, 58, 100),
            Event("wait", 42, 50)]
    return DeviceTrace(window=(0, 100), device_ops=ops, host_spans=host)


def test_busy_kernels_and_idle_by_hand():
    t = _hand_made()
    assert t.window_s == pytest.approx(100e-9)
    assert t.busy_s("/device:TPU:0") == pytest.approx(65e-9)   # [0,40] [60,80] [90,95]
    assert t.mean_busy_s() == pytest.approx((65e-9 + 50e-9) / 2)
    assert devtrace.base_name("syr2k_lower.3.1") == "syr2k_lower"
    assert sum(e.duration_ns for e in t.kernel_events("syr2k_lower")) == 30 + 20 + 50
    assert t.top_ops(1) == [("syr2k_lower.3", pytest.approx(80e-9))]
    gaps = dict(t.idle_gaps())
    # TPU:0 idles [40,60] (mid 50: the first call span, after syr2k_lower.3),
    # [80,90] and [95,100] (second call span, after syr2k_lower.7 and the
    # bulge kernel); TPU:1 idles [50,100] (mid 75: second call span).
    call = devtrace.CALL_SPAN
    assert gaps == {
        f"{call} / after syr2k_lower": pytest.approx((20 + 10 + 50) * 1e-9),
        f"{call} / after bulge_chase_wavefront": pytest.approx(5e-9),
    }


def test_context_readers_by_hand():
    from hlo import CustomCall, Shape

    t = _hand_made()
    call = CustomCall("syr2k_lower.3", "syr2k_lower", (), (
        Shape("s32", (1,)), Shape("f32", (256, 256)), Shape("f32", (256, 256)),
        Shape("f32", (256, 256)), Shape("f32", (256, 256)), Shape("f32", (256, 256)),
    ))
    ctx = harness.Context(1.0, 1.0, 2, 2, "TPU v5 lite", trace=t,
                          custom_calls={"syr2k_lower.3": call, "syr2k_lower.7": call})
    assert ctx.idle_share_pct() == pytest.approx(100 * (1 - (65 + 50) / 2 / 100))
    flops = 2 * 256 * 256 * 257
    least = max(flops / 197e12, 4 * (2 * 256 * 256 + 256 * 257) / 819e9)
    assert ctx.roofline_pct("syr2k_lower") == pytest.approx(100 * 3 * least / 100e-9)
    assert ctx.roofline_pct("fused_panel_update") is None   # nothing to read
    missing = harness.Context(1.0, 1.0, 2, 2, "TPU v5 lite", trace=t)
    assert missing.roofline_pct("syr2k_lower") is None        # no shapes: no number


def test_recorded_v5e_trace():
    t = devtrace.load(str(RECORDED))
    assert t.devices == ["/device:TPU:0"]
    assert 0 < t.mean_busy_s() < t.window_s
    names = {devtrace.base_name(n) for n in t.device_ops["/device:TPU:0"].names}
    assert "syr2k_lower" in names
    assert t.kernel_events("syr2k_lower")
    assert sum(s for _, s in t.idle_gaps(100)) == pytest.approx(
        t.window_s - t.busy_s("/device:TPU:0"), rel=1e-6
    )
    ops = t.device_ops["/device:TPU:0"]
    assert ops.start.min() >= t.window[0] and ops.end.max() <= t.window[1]


def test_trace_without_a_window_is_refused(tmp_path):
    with pytest.raises(Exception):
        devtrace.load(str(tmp_path / "missing.xplane.pb"))


SYNTHETIC = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 50000 }
    events { metadata_id: 2 offset_ps: 10000 duration_ps: 5000 }
    events { metadata_id: 2 offset_ps: 30000 duration_ps: 8000 }
    events { metadata_id: 2 offset_ps: 45000 duration_ps: 5000 }
  }
  lines { id: 2 name: "XLA TraceMe" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 40000 duration_ps: 60000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%while.1 = (s32[]{:T(128)}) while((s32[]) %t), condition=%c" } }
  event_metadata { key: 2 value { id: 2 name: "%syr2k_lower.3 = f32[8,8]{1,0:T(8,128)} custom-call(f32[8,8]{1,0} %a)" } }
  event_metadata { key: 3 value { id: 3 name: "Trace Buffers Dropped" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 7 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
}
"""


def _synthetic(tmp_path, text):
    from jax.profiler import ProfileData

    path = tmp_path / "synthetic.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return devtrace.load(str(path))


def test_load_drops_control_flow_and_stops_where_events_were_dropped(tmp_path):
    t = _synthetic(tmp_path, SYNTHETIC)
    # The drop is at 1040; the last operation recorded before it ends at 1038.
    assert t.window == (1000, 1038)
    ops = t.device_ops["/device:TPU:0"]
    assert {ops.names[i] for i in ops.name_id} == {"syr2k_lower.3"}  # a loop is not work
    assert len(ops) == 2                           # the call after the drop is out
    assert t.busy_s("/device:TPU:0") == pytest.approx(13e-9)
    assert dict(t.idle_gaps(5)) == {
        "no host span / after window start": pytest.approx(10e-9),
        "no host span / after syr2k_lower": pytest.approx(15e-9),
    }


def test_window_ends_at_the_last_device_event_without_a_drop_marker(tmp_path):
    """A profiler that stops recording without saying so: device events end
    at 1050 while the host window runs to 1100.  The untraced rest is not
    counted as idle."""
    unmarked = SYNTHETIC.replace('name: "Trace Buffers Dropped"', 'name: "Something else"')
    t = _synthetic(tmp_path, unmarked)
    assert t.window == (1000, 1050)
    assert len(t.device_ops["/device:TPU:0"]) == 3
    assert t.busy_s("/device:TPU:0") == pytest.approx(18e-9)
    ctx = harness.Context(1.0, 1.0, 1, 1, "TPU v5 lite", trace=t)
    assert ctx.idle_share_pct() == pytest.approx(100 * (1 - 18 / 50))


def test_instruction_names_and_opcodes():
    assert devtrace.instruction(
        "%while.646 = (s32[]{:T(128)}, f32[8]{0}) while((s32[]) %tuple.1), condition=%c"
    ) == ("while.646", "while")
    assert devtrace.instruction(
        "%slice.2402 = s32[1]{0:T(128)} slice(s32[2]{0:T(128)S(1)} %fusion.1446)"
    ) == ("slice.2402", "slice")
    assert devtrace.instruction("ReadSyncFlag") == ("ReadSyncFlag", "")
