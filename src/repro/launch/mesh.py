"""Production meshes.

All constructors are FUNCTIONS — importing this module never touches jax
device state (the dry-run must set XLA_FLAGS before the first jax call).

Topology (TPU v5e numbers used by the roofline):
  single pod   : 16 x 16 = 256 chips,  axes ("data", "model")
  multi-pod    : 2 x 16 x 16 = 512,    axes ("pod", "data", "model")
"""
from __future__ import annotations

import math
from typing import Optional

import jax
from jax.sharding import Mesh

from jax import make_mesh
from jax.sharding import AxisType

SINGLE_POD = ((16, 16), ("data", "model"))
MULTI_POD = ((2, 16, 16), ("pod", "data", "model"))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devs)} — the dry-run "
            f"sets XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            f"before any jax import")
    return make_mesh(shape, axes, devices=devs[:n],
                     axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: Optional[int] = None) -> Mesh:
    """Largest (data, model) mesh on the ACTUAL local devices — used by
    tests and the laptop-scale examples (1-8 CPU devices)."""
    devs = jax.devices()
    n = len(devs)
    if model is None:
        model = 1
        while model * 2 <= n and n % (model * 2) == 0 and model * 2 <= 4:
            model *= 2
    data = n // model
    return make_mesh((data, model), ("data", "model"),
                     devices=devs[:data * model],
                     axis_types=(AxisType.Auto, AxisType.Auto))


def make_elastic_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    """Mesh for a re-planned (post-failure) topology — see
    runtime.coordinator.plan_elastic_mesh."""
    n = math.prod(shape)
    return make_mesh(shape, axes, devices=jax.devices()[:n],
                     axis_types=(AxisType.Auto,) * len(axes))


def dp_axes(mesh: Mesh) -> tuple[str, ...]:
    """The data-parallel (batch) axes of a production mesh."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis_size(mesh: Mesh) -> int:
    return mesh.shape["model"] if "model" in mesh.axis_names else 1
