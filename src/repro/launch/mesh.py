"""Production mesh factory (TPU v5e target).

Defined as a function — importing this module never touches jax device state,
so tests and benches keep seeing 1 CPU device; only ``dryrun.py`` forces 512
host devices (and only in its own process).  Every axis is Auto.
"""
from __future__ import annotations

from typing import Sequence

import jax


def _make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """``jax.make_mesh`` with all-Auto axis types."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; multi_pod stacks 2 pods -> 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh for CPU tests/examples (1x1, same axis names)."""
    return _make_mesh((1, 1), ("data", "model"))


def data_axes(mesh) -> tuple:
    """Mesh axes that shard the batch dimension."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def model_axis_size(mesh) -> int:
    return mesh.shape.get("model", 1)
