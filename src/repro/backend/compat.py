"""Mesh construction with the axis types the solver's ``shard_map`` paths use.

``jax.make_mesh`` defaults to ``Explicit`` axes; every mesh in this
framework is consumed by ``jax.shard_map`` with manual in/out specs, which
wants ``Auto`` axes.  :func:`make_mesh` is ``jax.make_mesh`` with that
default flipped; pass ``axis_types=...`` to override it.
"""
from __future__ import annotations

from typing import Sequence

import jax

__all__ = ["make_mesh"]


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], **kw):
    """``jax.make_mesh`` with ``AxisType.Auto`` on every axis by default."""
    kw.setdefault("axis_types", (jax.sharding.AxisType.Auto,) * len(axis_names))
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names), **kw)
