"""Stage marks read from a device trace.

The solver marks the end of each stage of a solve with a no-op kernel named
``evd_mark_<stage>``; ``evd_mark_begin`` marks its start.  This module cuts
each device's marks into solves, in the order they ran, and gives the
device interval between two marks of one solve: from the end of the first
to the start of the second.

Solves are cut at their ``begin`` marks, not by the host's call spans: in a
v5e trace the device's events are stamped about a millisecond earlier than
the host's spans around them, so a call span holds the end of its own solve
and the start of the next.  A program without marks, or a solve whose
marks fell outside the traced window, gives nothing to read.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

import devtrace

__all__ = ["MARK", "marks_by_solve", "intervals", "idle_share_pct"]

MARK = "evd_mark_"


def marks_by_solve(trace: devtrace.DeviceTrace) -> List[Tuple[str, Dict[str, devtrace.Event]]]:
    """One ``(device, {stage: mark event})`` per traced solve on each
    device: the device's marks in the order they ran, cut before each
    ``begin``.  Marks before the first ``begin`` in the window make a solve
    of their own, without ``begin``; a stage marked twice in a solve keeps
    its first mark."""
    out = []
    for d in trace.devices:
        ops = trace.device_ops[d]
        kernels = {devtrace.base_name(n) for n in ops.names
                   if devtrace.base_name(n).startswith(MARK)}
        events = sorted(
            (e for k in kernels for e in ops.events(ops.matching(k))),
            key=lambda e: e.start_ns,
        )
        solves: List[Dict[str, devtrace.Event]] = []
        for e in events:
            stage = devtrace.base_name(e.name)[len(MARK):]
            if stage == "begin" or not solves:
                solves.append({})
            solves[-1].setdefault(stage, e)
        out += [(d, marks) for marks in solves]
    return out


def intervals(trace: devtrace.DeviceTrace, first: str, second: str) -> List[Tuple[str, float, float]]:
    """``(device, start_ns, end_ns)`` from the end of mark ``first`` to the
    start of mark ``second``, for every traced solve that holds both."""
    out = []
    for d, marks in marks_by_solve(trace):
        if first in marks and second in marks:
            s, e = marks[first].end_ns, marks[second].start_ns
            if e > s:
                out.append((d, s, e))
    return out


def idle_share_pct(trace: devtrace.DeviceTrace, spans: List[Tuple[str, float, float]]) -> float:
    """100 x (1 - busy / length) over ``spans`` together, busy being the
    union of each device's operation intervals clipped to its spans."""
    busy = length = 0.0
    for d, s, e in spans:
        ops = trace.device_ops[d]
        keep = (ops.end > s) & (ops.start < e)
        starts, ends = devtrace._merge(np.maximum(ops.start[keep], s), np.minimum(ops.end[keep], e))
        busy += float(np.sum(ends - starts))
        length += e - s
    return 100.0 * (1.0 - busy / length)
