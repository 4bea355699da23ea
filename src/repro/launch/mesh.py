"""Production meshes.

Single pod: 256 chips as (data=16, model=16).
Multi-pod:  2 pods x 256 chips as (pod=2, data=16, model=16); the "pod"
axis carries data parallelism + ZeRO sharding across pods (DCN-ish in real
deployments; ICI-attached in the port model with its own link budget).

Defined as functions so importing this module never touches jax device
state (the dry-run pins XLA_FLAGS before first jax init)."""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_test_mesh(*, multi_pod: bool = False) -> Mesh:
    """Reduced mesh for CI (8 forced host devices)."""
    shape = (2, 2, 2) if multi_pod else (4, 2)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def _mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) == n:
        # Auto axes: the model code places arrays with
        # with_sharding_constraint, which Explicit axes reject
        return jax.make_mesh(shape, axes,
                             axis_types=(AxisType.Auto,) * len(axes))
    if len(devices) > n:
        # e.g. 512 forced host devices, single-pod 256-chip mesh
        return Mesh(np.asarray(devices[:n]).reshape(shape), axes)
    raise RuntimeError(
        f"need {n} devices for mesh {dict(zip(axes, shape))}, have "
        f"{len(devices)}; the dry-run must set "
        f"XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
        f"any jax import")
