"""Production mesh construction.

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (device count is locked at first jax init).

Every mesh here has ``Auto`` axes: ``jax.make_mesh`` defaults to
``Explicit`` axes, under which ``with_sharding_constraint`` becomes an
assertion and reshapes that merge a sharded dim raise.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_serving_mesh(batch: int = None, max_devices: int = None):
    """1-D ``("data",)`` mesh for the cognitive serving tick: the
    largest visible-device count that divides the tick ``batch`` (the
    per-slot math is batch-parallel, so the only constraint is an even
    slot split).  Returns ``None`` when a single device (or batch=1)
    makes sharding pointless — callers degrade to the local path."""
    n = len(jax.devices())
    if max_devices is not None:
        n = min(n, max_devices)
    if batch is not None:
        while n > 1 and batch % n:
            n -= 1
    if n <= 1:
        return None
    return _auto_mesh((n,), ("data",))


def make_mesh_for(devices: int, model_parallel: int = None):
    """Elastic mesh: derive the largest (data, model) mesh from whatever
    device count survives a failure (see distributed/elastic.py)."""
    model_parallel = model_parallel or min(16, devices)
    while devices % model_parallel:
        model_parallel //= 2
    return _auto_mesh((devices // model_parallel, model_parallel),
                      ("data", "model"))
