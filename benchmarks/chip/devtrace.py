"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy time, kernel
times and idle gaps.

Reads the file with ``jax.profiler.ProfileData`` and nothing else.  A device
is a plane named ``/device:<KIND>:<id>`` other than a CPU; its operations are
the events of its line named ``XLA Ops``, each named by the text of its HLO
instruction (``%syr2k_lower.1 = f32[...] custom-call(...)``), of which the
instruction name and opcode are kept.  Control flow (``while``,
``conditional``, ``call``) spans the operations of its body and is left out,
so busy time counts the operations that do the work.  Host spans are the events of the
host thread that opened the window.  Everything is clipped to the traced window, which the
harness marks with a host span named :data:`WINDOW_SPAN`, and ends at the
earliest of the span's end, the point where the profiler first dropped
device events (it keeps at most about 2 GB of them, some 6 M operations),
and the end of the last device operation recorded: past that point the
trace no longer shows what ran.

* busy time: the union of a device's operation intervals in the window;
* kernel time: the summed durations of the events whose instruction name,
  without a trailing ``.<digits>`` suffix, is the kernel's name;
* idle gaps: the stretches of the window in which no operation ran on a
  device, named by the innermost host span that covers each gap's middle and
  by the device operation that ran last before the gap.

A long solve holds millions of tiny operations, so events are kept as
arrays.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = [
    "WINDOW_SPAN",
    "CALL_SPAN",
    "Event",
    "Ops",
    "DeviceTrace",
    "base_name",
    "instruction",
    "load",
]

WINDOW_SPAN = "bench.window"
CALL_SPAN = "bench.call"
OPS_LINE = "XLA Ops"
DROPPED = "Trace Buffers Dropped"
CONTROL_FLOW = ("while", "conditional", "call")
_HLO = re.compile(r"^%([\w.\-]+) = .*?[\]})] ([a-z][a-z0-9\-]*)\(")
_DEVICE_PLANE = re.compile(r"^/device:([A-Za-z_]+):(\d+)$")
_SUFFIX = re.compile(r"(\.\d+)+$")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float

    @property
    def duration_ns(self) -> float:
        return self.end_ns - self.start_ns


def base_name(name: str) -> str:
    """``syr2k_lower.3`` -> ``syr2k_lower``."""
    return _SUFFIX.sub("", name)


def instruction(text: str) -> Tuple[str, str]:
    """``(name, opcode)`` of an HLO instruction's text; a name that is not
    such text is its own name, with no opcode."""
    m = _HLO.match(text)
    return (m.group(1), m.group(2)) if m else (text, "")


def _merge(starts: np.ndarray, ends: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Disjoint sorted intervals covering the same set as the input pairs."""
    if len(starts) == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], np.maximum.accumulate(ends[order])
    new = np.empty(len(s), bool)
    new[0] = True
    new[1:] = s[1:] > e[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, len(s) - 1)
    return s[first], e[last]


@dataclasses.dataclass
class Ops:
    """One device's operations: parallel arrays plus the table of names."""

    names: List[str]
    name_id: np.ndarray
    start: np.ndarray
    end: np.ndarray

    @classmethod
    def of(cls, events: Sequence[Event]) -> "Ops":
        table: Dict[str, int] = {}
        ids = [table.setdefault(e.name, len(table)) for e in events]
        return cls(
            names=list(table),
            name_id=np.asarray(ids, np.int64),
            start=np.asarray([e.start_ns for e in events], np.float64),
            end=np.asarray([e.end_ns for e in events], np.float64),
        )

    def __len__(self) -> int:
        return len(self.start)

    def matching(self, kernel: str) -> np.ndarray:
        """Mask of the events of ``kernel``."""
        ids = [i for i, n in enumerate(self.names) if base_name(n) == kernel]
        return np.isin(self.name_id, ids)

    def events(self, mask: np.ndarray) -> List[Event]:
        return [
            Event(self.names[self.name_id[i]], self.start[i], self.end[i])
            for i in np.flatnonzero(mask)
        ]


@dataclasses.dataclass
class DeviceTrace:
    """The device operations and host spans of one traced window."""

    window: Tuple[float, float]                 # (start_ns, end_ns)
    device_ops: Dict[str, Ops]                  # device plane -> ops in window
    host_spans: List[Event]                     # host spans in window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def devices(self) -> List[str]:
        return sorted(self.device_ops)

    def busy_s(self, device: str) -> float:
        ops = self.device_ops[device]
        s, e = _merge(ops.start, ops.end)
        return float(np.sum(e - s)) * 1e-9

    def mean_busy_s(self) -> float:
        """Busy seconds averaged over the devices that ran an operation."""
        used = [d for d in self.devices if len(self.device_ops[d])]
        if not used:
            return 0.0
        return sum(self.busy_s(d) for d in used) / len(used)

    def kernel_events(self, kernel: str) -> List[Event]:
        """Events of ``kernel`` on every device, in the window."""
        out: List[Event] = []
        for d in self.devices:
            ops = self.device_ops[d]
            out.extend(ops.events(ops.matching(kernel)))
        return out

    def top_ops(self, k: int = 10) -> List[Tuple[str, float]]:
        """The ``k`` operation names that took the most device time (summed
        over devices), in seconds."""
        acc: Dict[str, float] = defaultdict(float)
        for d in self.devices:
            ops = self.device_ops[d]
            sums = np.bincount(ops.name_id, weights=ops.end - ops.start, minlength=len(ops.names))
            for i, name in enumerate(ops.names):
                acc[name] += float(sums[i]) * 1e-9
        return sorted(acc.items(), key=lambda kv: -kv[1])[:k]

    def idle_gaps(self, k: int = 10) -> List[Tuple[str, float]]:
        """Idle seconds of the window, summed over devices and grouped by the
        innermost host span covering each gap's middle and the base name of
        the device operation that ended last before the gap; the ``k``
        largest groups."""
        acc: Dict[str, float] = defaultdict(float)
        spans = sorted(self.host_spans, key=lambda e: e.duration_ns)
        for d in self.devices:
            ops = self.device_ops[d]
            s, e = _merge(ops.start, ops.end)
            gs = np.concatenate([[self.window[0]], e])
            ge = np.concatenate([s, [self.window[1]]])
            keep = ge > gs
            gs, ge = gs[keep], ge[keep]
            if not len(gs):
                continue
            mid = 0.5 * (gs + ge)
            host = np.full(len(gs), -1)
            for i, h in enumerate(spans):
                hit = (host < 0) & (mid >= h.start_ns) & (mid < h.end_ns)
                host[hit] = i
            # The operation that ended last at or before each gap's start.
            order = np.argsort(ops.end, kind="stable")
            pos = np.searchsorted(ops.end[order], gs, side="right") - 1
            before = np.full(len(gs), -1)
            if len(ops):
                before = np.where(pos >= 0, ops.name_id[order][np.maximum(pos, 0)], -1)
            width = len(ops.names) + 1
            keys = host.astype(np.int64) * width + (before + 1)
            uniq, inv = np.unique(keys, return_inverse=True)
            for u, total in zip(uniq.tolist(), np.bincount(inv, weights=ge - gs).tolist()):
                h, b = divmod(u, width)
                hname = spans[h].name if h >= 0 else "no host span"
                bname = base_name(ops.names[b - 1]) if b > 0 else "window start"
                acc[f"{hname} / after {bname}"] += total * 1e-9
        return sorted(acc.items(), key=lambda kv: -kv[1])[:k]


def load(path: str, devices: Optional[Sequence[int]] = None) -> DeviceTrace:
    """Read an ``.xplane.pb`` file.  ``devices`` keeps only the device planes
    with those ids (default: every device plane).  The window is the first
    host span named :data:`WINDOW_SPAN`; a trace without one is an error."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host: List[Event] = []
    dev_raw: Dict[str, Tuple[list, list, list]] = {}
    dropped: List[float] = []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            if m.group(1) == "CPU" or (devices is not None and int(m.group(2)) not in devices):
                continue
            names, starts, durs = dev_raw.setdefault(plane.name, ([], [], []))
            for line in plane.lines:
                for e in line.events:
                    if line.name == OPS_LINE:
                        names.append(e.name)
                        starts.append(e.start_ns)
                        durs.append(e.duration_ns)
                    elif e.name == DROPPED:
                        dropped.append(e.start_ns)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [Event(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events]
                # Only the thread that ran the window: what the host was doing.
                if any(e.name == WINDOW_SPAN for e in evs):
                    host.extend(evs)
    windows = [e for e in host if e.name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"{path}: no host span named {WINDOW_SPAN!r}")
    w0, w1 = windows[0].start_ns, min([windows[0].end_ns] + dropped)

    parsed_planes = {}
    last_end = w0
    for plane, (names, starts, durs) in dev_raw.items():
        table: Dict[str, int] = {}
        raw = np.asarray([table.setdefault(n, len(table)) for n in names], np.int64)
        parsed = [instruction(t) for t in table]
        short: Dict[str, int] = {}
        remap = np.asarray(
            [short.setdefault(n, len(short)) for n, _ in parsed] or [0], np.int64
        )
        work = np.asarray([op not in CONTROL_FLOW for _, op in parsed] or [True])
        start = np.asarray(starts, np.float64)
        end = start + np.asarray(durs, np.float64)
        keep = (end > w0) & (start < w1) & work[raw] if len(raw) else np.zeros(0, bool)
        if keep.any():
            last_end = max(last_end, float(end[keep].max()))
        parsed_planes[plane] = (short, remap, raw, keep, start, end)
    # Every call of the window ends in a wait for the device, so the device's
    # last operation ends the window's work.  Where the profiler stopped
    # recording without a marker, this also keeps the untraced rest out.
    w1 = min(w1, last_end) if last_end > w0 else w1

    device_ops = {}
    for plane, (short, remap, raw, keep, start, end) in parsed_planes.items():
        keep = keep & (start < w1)
        device_ops[plane] = Ops(
            names=list(short), name_id=remap[raw[keep]] if len(raw) else raw,
            start=np.maximum(start[keep], w0), end=np.minimum(end[keep], w1),
        )
    spans = [
        Event(e.name, max(e.start_ns, w0), min(e.end_ns, w1))
        for e in host if e.end_ns > w0 and e.start_ns < w1 and e.name != WINDOW_SPAN
    ]
    return DeviceTrace(window=(w0, w1), device_ops=device_ops, host_spans=spans)
