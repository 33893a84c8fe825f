"""The benchmark's general machinery: find a cell by name, make its data,
compile and warm its program, time a window of whole calls, read the trace,
compare the outputs, and assemble the result line.

Everything that belongs to one configuration, traffic mix, entry, limit set,
metric or kernel lives in a file of its own that this module finds by name:

* ``BENCHMARK.json`` (repository root): cells, configurations, metrics;
* ``configs/<config>.json``: the deployment's sizes and data generator;
* ``traffic/<traffic>.json``: the call mix (entry, batch, arguments);
* ``limits/<cell>.json``: the limit of each number compared;
* ``data/<generator>.py``: ``make(config, traffic, seed)``;
* ``entries/<entry>.py``: ``build(...)`` and ``readings(...)``;
* ``metrics/<metric>.py``: ``read(ctx)`` -> a number or None;
* ``work/<kernel>.py``: ``work(call)`` -> (FLOPs, bytes) from shapes;
* ``peaks.json``: published peaks keyed by ``device_kind``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import shutil
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

import devtrace
import hlo

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

__all__ = [
    "Cell",
    "Context",
    "load_cell",
    "load_module",
    "run_cell",
]


# ------------------------------------------------------------------ discovery
def _json(path: Path) -> dict:
    return json.loads(path.read_text())


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` beside this file, imported under a unique name."""
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"chipbench.{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    limits: Dict[str, float]
    end_to_end: List[dict]
    per_layer: List[dict]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = _json(root / "BENCHMARK.json")
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload named {name!r}; have {sorted(work)}")
    w = work[name]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    per_layer = [m for m in bench["per_layer"] if name in m["workloads"]]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=_json(root / cfg_entry["file"]),
        traffic=_json(HERE / "traffic" / f"{w['traffic']}.json"),
        limits=_json(HERE / "limits" / f"{name}.json"),
        end_to_end=e2e,
        per_layer=per_layer,
    )


# ------------------------------------------------------------------ metrics
@dataclasses.dataclass
class Context:
    """What a metric reader may read.  ``trace`` is None with ``--trace 0``."""

    setup_s: float
    window_s: float
    calls: int
    answers: int
    device_kind: str
    trace: Optional[devtrace.DeviceTrace] = None
    custom_calls: Dict[str, hlo.CustomCall] = dataclasses.field(default_factory=dict)

    def peaks(self) -> dict:
        table = _json(HERE / "peaks.json")["devices"]
        if self.device_kind not in table:
            raise KeyError(f"no peaks for device kind {self.device_kind!r} in peaks.json")
        return table[self.device_kind]

    def idle_share_pct(self) -> Optional[float]:
        if self.trace is None or self.trace.window_s <= 0 or not self.trace.mean_busy_s():
            return None
        return 100.0 * (1.0 - self.trace.mean_busy_s() / self.trace.window_s)

    def roofline_pct(self, kernel: str) -> Optional[float]:
        """The least time the chip could take for the work of every traced
        invocation of ``kernel`` -- max(FLOPs / peak, bytes / bandwidth), from
        ``work/<kernel>.py`` at the invocation's shapes -- over the summed
        device time of those invocations, in percent."""
        if self.trace is None:
            return None
        evs = self.trace.kernel_events(kernel)
        if not evs:
            return None
        work = load_module("work", kernel).work
        peaks = self.peaks()
        least = 0.0
        for e in evs:
            call = self.custom_calls.get(e.name)
            if call is None:
                return None
            flops, nbytes = work(call)
            least += max(flops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
        busy = sum(e.duration_ns for e in evs) * 1e-9
        return 100.0 * least / busy


def read_metrics(specs: List[dict], ctx: Context) -> Dict[str, dict]:
    out = {}
    for m in specs:
        value = load_module("metrics", m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# ------------------------------------------------------------------ the run
def _same(a, b) -> bool:
    """Bitwise equality of two output pytrees, computed on the device."""
    import jax.numpy as jnp

    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        x.shape == y.shape and bool(jnp.array_equal(x, y)) for x, y in zip(la, lb)
    )


def _window(compiled, args, seconds: float, span: Callable):
    """Whole calls back to back until ``seconds`` have passed."""
    outs, times = [], []
    t_start = time.perf_counter()
    while True:
        t0 = time.perf_counter()
        with span(devtrace.CALL_SPAN):
            outs.append(jax.block_until_ready(compiled(*args)))
        t1 = time.perf_counter()
        times.append(t1 - t0)
        if t1 - t_start >= seconds:
            return outs, times, t1 - t_start


def run_cell(
    cell: Cell,
    *,
    seed: int,
    seconds: float,
    trace: bool,
    devices: list,
    t_process: float,
    emit: Callable[[dict], None],
) -> dict:
    """Run one cell on ``devices`` and return the result line's object."""
    from repro.solver import trace_count

    data = load_module("data", cell.config["generator"]).make(cell.config, cell.traffic, seed)
    entry = load_module("entries", cell.traffic["entry"])
    prog = entry.build(cell.config, cell.traffic, data, devices)
    emit({"describe": prog["describe"]})

    t0 = time.perf_counter()
    compiled = jax.jit(prog["fn"]).lower(*prog["args"]).compile()
    compile_s = time.perf_counter() - t0
    text = compiled.as_text()
    calls_hlo = hlo.custom_calls(text)
    emit({"compile_s": compile_s, "kernels": hlo.kernels_in(text)})
    t0 = time.perf_counter()
    jax.block_until_ready(compiled(*prog["args"]))
    emit({"warm_call_s": time.perf_counter() - t0})
    traces_before = trace_count()

    tmp = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        if trace:
            jax.profiler.start_trace(tmp)
        setup_s = time.perf_counter() - t_process
        span = jax.profiler.TraceAnnotation
        with span(devtrace.WINDOW_SPAN):
            outs, times, window_s = _window(compiled, prog["args"], seconds, span)
        if trace:
            jax.profiler.stop_trace()
        retraces = trace_count() - traces_before
        memory_peak = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices
        )
        emit({"calls": len(outs), "call_s": times, "window_s": window_s})

        ctx = Context(
            setup_s=setup_s, window_s=window_s, calls=len(outs),
            answers=len(outs) * prog["answers_per_call"],
            device_kind=devices[0].device_kind, custom_calls=calls_hlo,
        )
        device = {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": int(memory_peak),
        }
        result = {"correct": None, "attempted": ctx.answers, "failed": 0}
        if trace:
            t0 = time.perf_counter()
            path = next(Path(tmp).rglob("*.xplane.pb"))
            ctx.trace = devtrace.load(str(path), devices=[d.id for d in devices])
            result["metrics"] = read_metrics(cell.per_layer, ctx)
            device["busy_s"] = ctx.trace.mean_busy_s()
            device["window_s"] = ctx.trace.window_s
            result["breakdown"] = {
                "device_ops": [list(x) for x in ctx.trace.top_ops(10)],
                "idle_gaps": [list(x) for x in ctx.trace.idle_gaps(10)],
            }
            emit({"trace_read_s": time.perf_counter() - t0})
        else:
            result["metrics"] = read_metrics(cell.end_to_end, ctx)
        result["device"] = device
    finally:
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)

    # ---- correctness: every output of the window, after the window -------
    same = [True] + [_same(o, outs[0]) for o in outs[1:]]
    emit({"calls_bitwise_equal_to_first": sum(same) - 1, "of": len(same) - 1})
    host = [jax.device_get(outs[0])] + [
        None if s else jax.device_get(o) for s, o in zip(same[1:], outs[1:])
    ]
    del outs, compiled, prog
    per_call = []
    for h in host:
        per_call.append(per_call[0] if h is None else entry.readings(h, data, cell.config, cell.traffic))
    result.update(judge(per_call, cell.limits, retraces))
    return result


def judge(per_call: List[Dict[str, np.ndarray]], limits: Dict[str, float], retraces: int) -> dict:
    """``correct``, ``failed`` and ``checks`` from one reading per answer.

    An answer fails when any of its readings is over its limit (or not a
    number).  ``checks`` holds each number's worst reading beside its limit,
    and the retraces inside the window beside 0."""
    names = sorted(per_call[0])
    missing = [n for n in names if n not in limits]
    if missing:
        raise KeyError(f"no limit for {missing}")
    failed = 0
    worst = {n: 0.0 for n in names}
    for r in per_call:
        bad = np.zeros(len(r[names[0]]), bool)
        for n in names:
            v = np.asarray(r[n], np.float64)
            bad |= ~(v <= limits[n])
            worst[n] = float(np.max(np.where(np.isfinite(v), v, np.inf), initial=worst[n]))
        failed += int(bad.sum())
    checks = {n: {"value": worst[n], "limit": limits[n]} for n in names}
    checks["retraces"] = {"value": retraces, "limit": 0}
    return {
        "correct": failed == 0 and retraces == 0,
        "failed": failed,
        "checks": checks,
    }
