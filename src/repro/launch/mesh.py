"""Production mesh construction (DESIGN.md §5).

A function, not a module-level constant — importing this module never touches
jax device state.  The dry-run (and only the dry-run) boots 512 placeholder
host devices; smoke tests and benches see the 1 real CPU device.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 = 256 chips/pod (single pod) or 2x16x16 = 512 chips (two pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh_for(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    """Arbitrary mesh (elastic re-plans, tests) over the first prod(shape) devices."""
    return _mesh(shape, axes)


def _mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    need = math.prod(shape)
    devs = jax.devices()
    if len(devs) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, found {len(devs)} — the dry-run "
            "must set XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "any jax import (launch/dryrun.py does)")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devs[:need])
