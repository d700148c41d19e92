"""Production meshes (as FUNCTIONS — importing this never touches devices).

Single pod: (16, 16) = 256 chips, axes ("data", "model") — FSDP over
``data``, tensor/expert parallel over ``model``.

Multi-pod: (2, 16, 16) = 512 chips, axes ("pod", "data", "model") — the
``pod`` axis carries ONLY the gradient all-reduce (params replicated across
pods), which is the DCN-friendly layout for 1000+ node scale: everything
chatty stays on ICI inside a pod.

The dry-run materializes these on 512 placeholder CPU devices
(``--xla_force_host_platform_device_count=512`` — set by dryrun.py before
any jax import).
"""

from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_auto_mesh(shape, axis_names, devices=None):
    """``jax.make_mesh`` with every axis ``Auto`` (GSPMD-propagated
    shardings): ``jax.make_mesh`` would otherwise pick explicit axes."""
    kwargs = {} if devices is None else {"devices": devices}
    return jax.make_mesh(shape, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         **kwargs)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = math.prod(shape)
    devs = jax.devices()
    if len(devs) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, found {len(devs)}. "
            "For the dry-run set XLA_FLAGS=--xla_force_host_platform_"
            "device_count=512 BEFORE importing jax (dryrun.py does this).")
    return make_auto_mesh(shape, axes, devices=devs[:need])


def make_host_mesh(model_axis: int | None = None):
    """Best-effort mesh over whatever devices exist (tests, examples).

    Factors the device count into (data, model); model_axis forces the
    model dimension.
    """
    n = len(jax.devices())
    m = model_axis or max(d for d in (1, 2, 4, 8) if n % d == 0)
    return make_auto_mesh((n // m, m), ("data", "model"))


def mesh_dims(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
