"""Production mesh construction (assigned: 16x16 single pod; 2x16x16
multi-pod). A FUNCTION, not a module constant — importing this module
never touches jax device state.

Every mesh here uses ``AxisType.Auto`` axes: ``jax.make_mesh`` defaults
to ``Explicit`` axes, under which sharding constraints and gathers out
of sharded arrays must spell out their out-shardings. The quadrature
drivers and the sharding rules rely on the compiler's propagation."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Whatever devices exist locally (tests / smoke), data x model."""
    n = len(jax.devices())
    assert n % model == 0
    return _auto_mesh((n // model, model), ("data", "model"))


def data_axes_for(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def make_lane_mesh(num_devices: int | None = None, *, axis: str = "lanes"):
    """1-D mesh for data-parallel quadrature lanes (DESIGN.md Sec. 7).

    The K candidate systems of the batched retrospective driver shard
    over this single axis (``core.sharded``); operators are replicated.
    Defaults to every local device — on CPU tests, launch with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to get 8
    virtual devices.
    """
    n = len(jax.devices()) if num_devices is None else int(num_devices)
    return _auto_mesh((n,), (axis,))
