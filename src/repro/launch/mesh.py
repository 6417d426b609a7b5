"""Production meshes. A FUNCTION, not a module constant — importing this
module never touches jax device state.

Single pod:  (data=16, model=16) = 256 chips (one v5e pod)
Multi-pod:   (pod=2, data=16, model=16) = 512 chips
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh

from repro.distributed.sharding import MeshInfo


def _make_mesh(shape, axes) -> Mesh:
    """``jax.make_mesh`` with Auto axes (its default is Explicit): the
    model code places its shardings with constraints, not mesh types."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    import numpy as np

    n = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) == n:
        return _make_mesh(shape, axes)
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}; have {len(devs)} — either "
            "run under dryrun.py (sets --xla_force_host_platform_device_count"
            ") or build a host-sized mesh with make_fleet_mesh_info()")
    # placeholder-device container has 512; single-pod uses the first 256
    arr = np.asarray(devs[:n]).reshape(shape)
    return Mesh(arr, axes)


def make_fleet_mesh_info(n_data: int = None) -> MeshInfo:
    """Small-mesh constructor for the streaming fleet: a 1-D data-only mesh
    shaped from the devices ACTUALLY present (``jax.device_count()``), so
    examples and CI on a host CPU build a real mesh — no 256-chip production
    shape, no dryrun placeholder devices.  Under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` this yields an
    N-way data mesh on one CPU, which is how the multi-device dispatch path
    is exercised in CI.

    ``n_data`` defaults to every device; a 1-device mesh is valid and the
    ``StreamEngine`` degenerates to the single-device dispatch path for it.
    """
    avail = jax.device_count()
    n = avail if n_data is None else int(n_data)
    if n < 1:
        raise ValueError(f"n_data must be ≥ 1, got {n}")
    if n > avail:
        raise RuntimeError(
            f"n_data={n} exceeds the {avail} visible devices — set "
            "XLA_FLAGS=--xla_force_host_platform_device_count before the "
            "first jax call to split the host CPU")
    mesh = _make_mesh((n,), ("data",))
    return MeshInfo(mesh, dp_axes=("data",))


def make_mesh_info(*, multi_pod: bool = False) -> MeshInfo:
    mesh = make_production_mesh(multi_pod=multi_pod)
    dp = ("pod", "data") if multi_pod else ("data",)
    return MeshInfo(mesh, dp_axes=dp)


def make_debug_mesh_info(n_data: int = 1, n_model: int = 1) -> MeshInfo:
    mesh = _make_mesh((n_data, n_model), ("data", "model"))
    return MeshInfo(mesh, dp_axes=("data",))
