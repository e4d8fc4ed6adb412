"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — required because the dry-run
forces 512 host devices while tests/benches run on the single real device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _mk(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
    Multi-pod:  (pod=2, data=16, model=16) = 512 chips across 2 pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_test_mesh(shape: tuple[int, ...] = None, axes: tuple[str, ...] = None) -> Mesh:
    """Mesh over however many devices exist (tests / local runs)."""
    n = len(jax.devices())
    if shape is None:
        shape, axes = (1, n) if n > 1 else (1, 1), ("data", "model")
    return _mk(shape, axes)


def graph_axes(mesh: Mesh) -> tuple[str, ...]:
    """The SSSP engine flattens every mesh axis into one vertex partition."""
    return tuple(mesh.axis_names)
