"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state.  Single pod: 256 chips as (data=16, model=16).
Multi-pod: 2 pods = 512 chips as (pod=2, data=16, model=16) — the pod axis
is the slow (DCN/ICI-bridge) dimension and carries only data parallelism.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_host_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(*, data: int | None = None, model: int = 1):
    """Small mesh over whatever local devices exist (tests/examples).

    Validates the shape against the visible device count instead of silently
    building a degenerate mesh: ``model > len(jax.devices())`` used to floor
    ``data`` to 0 and fail much later inside jax with an opaque shape error.
    """
    n = len(jax.devices())
    if model < 1:
        raise ValueError(f"make_host_mesh: model={model} must be >= 1")
    if model > n:
        raise ValueError(
            f"make_host_mesh: model={model} exceeds the {n} visible "
            f"device(s); run under XLA_FLAGS=--xla_force_host_platform_"
            f"device_count={model} (or more) or shrink the model axis")
    if data is None:
        if n % model:
            raise ValueError(
                f"make_host_mesh: model={model} does not divide the {n} "
                f"visible device(s) evenly; pass data= explicitly or pick "
                f"a model size that divides {n}")
        data = n // model
    if data < 1:
        raise ValueError(f"make_host_mesh: data={data} must be >= 1")
    if data * model > n:
        raise ValueError(
            f"make_host_mesh: mesh ({data}x{model}) needs {data * model} "
            f"devices but only {n} are visible; run under XLA_FLAGS="
            f"--xla_force_host_platform_device_count={data * model} or "
            f"shrink the mesh")
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
