"""Pallas kernels in a compiled program's HLO text, with their shapes.

``kernels_in`` is a copy of the reader in the repository's ``chip_smoke.py``;
``custom_calls`` adds each kernel call's result and operand shapes, which the
``work/<kernel>.py`` functions turn into FLOPs and bytes.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Tuple

__all__ = ["CustomCall", "Shape", "custom_calls", "kernels_in"]

_TARGET = 'custom_call_target="tpu_custom_call"'
_NAME = re.compile(r"\s*(?:ROOT\s+)?%([A-Za-z_][\w.]*)\s*=")
_SHAPE = re.compile(r"\b(pred|[su]\d+|f\d+|bf16)\[([\d,]*)\]")
_ITEMSIZE = {"pred": 1, "bf16": 2}


@dataclasses.dataclass(frozen=True)
class Shape:
    dtype: str
    dims: Tuple[int, ...]

    @property
    def itemsize(self) -> int:
        if self.dtype in _ITEMSIZE:
            return _ITEMSIZE[self.dtype]
        return int(self.dtype[1:]) // 8


@dataclasses.dataclass(frozen=True)
class CustomCall:
    """One kernel call: its HLO instruction name, kernel name, shapes."""

    name: str
    kernel: str
    results: Tuple[Shape, ...]
    operands: Tuple[Shape, ...]


def _shapes(text: str) -> Tuple[Shape, ...]:
    return tuple(
        Shape(t, tuple(int(d) for d in dims.split(",") if d))
        for t, dims in _SHAPE.findall(text)
    )


def kernels_in(hlo: str) -> List[str]:
    """Names of the Pallas kernels (``tpu_custom_call``) in a compiled program."""
    names = set()
    for line in hlo.splitlines():
        if _TARGET in line:
            m = re.match(r"\s*(?:ROOT\s+)?%([A-Za-z_]\w*?)(?:\.\d+)*\s*=", line)
            if m:
                names.add(m.group(1))
    return sorted(names)


def custom_calls(hlo: str) -> Dict[str, CustomCall]:
    """Every kernel call of the program, keyed by its instruction name."""
    out = {}
    for line in hlo.splitlines():
        if _TARGET not in line:
            continue
        m = _NAME.match(line)
        if not m or "custom-call(" not in line:
            continue
        name = m.group(1)
        head = line[m.end():].split("custom-call(", 1)[0]
        # Compiled HLO names its operands without shapes; the shapes are in
        # the operand layout constraints.
        lc = re.search(r"operand_layout_constraints=\{(.*?)\}(?:,\s*\w+=|$)", line)
        operands = lc.group(1) if lc else ""
        out[name] = CustomCall(
            name=name,
            kernel=re.sub(r"(\.\d+)+$", "", name),
            results=_shapes(head),
            operands=_shapes(operands),
        )
    return out
