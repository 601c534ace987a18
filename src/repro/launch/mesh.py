"""Production meshes. Functions only — importing this module never touches
jax device state (jax locks the device count on first backend init)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """A mesh whose axes are Auto: shardings are constraints the
    compiler propagates (`with_sharding_constraint` in
    `distributed/meshctx.py`), not explicit per-op sharding types."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod (data, model); 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Whatever this host offers, as a (data, model) mesh for tests."""
    n = len(jax.devices())
    model = 1
    for m in (4, 2, 1):
        if n % m == 0:
            model = m
            break
    return _auto_mesh((n // model, model), ("data", "model"))
