"""Production mesh construction.

Single pod: (data=16, model=16) — 256 chips, both axes on ICI.
Multi-pod:  (pod=2, data=16, model=16) — 512 chips; the ``pod`` axis crosses
DCN, so the sharding rules place only data parallelism (gradient all-reduce,
batch splitting) on it; ``model`` carries TP/EP/SP collectives on ICI.

A FUNCTION, not a module-level constant: importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    """Auto axes: sharding is resolved by the rules' constraints, which JAX
    refuses on the Explicit axes that ``jax.make_mesh`` defaults to."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, pod: int = 0):
    """Small mesh over this host's devices: a four-chip host's chips, or the
    CPU devices that xla_force_host_platform_device_count makes in tests."""
    if pod:
        return _mesh((pod, data, model), ("pod", "data", "model"))
    return _mesh((data, model), ("data", "model"))
