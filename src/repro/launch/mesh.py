"""Mesh factories.  FUNCTIONS (not module constants) so importing this
module never touches jax device state."""
from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import AxisType


def make_mesh(mesh_shape: tuple[int, ...] = ()):
    """Launcher mesh over the devices present: ``mesh_shape`` (data, model)
    when given, else (1, 1) on the first device.  Axes are ``Auto`` — the
    default of ``jax.make_mesh`` is ``Explicit``, under which the
    backbone's ``with_sharding_constraint`` calls are refused."""
    shape = tuple(mesh_shape) or (1, 1)
    need = math.prod(shape)
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, have {len(devices)}")
    return jax.make_mesh(shape, ("data", "model")[:len(shape)],
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices[:need])


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e: 256 chips/pod as (data=16, model=16); multi-pod adds a
    leading pod axis (2 pods = 512 chips).  Only ``launch/dryrun.py`` builds
    it, over 512 placeholder CPU devices; devices are sliced explicitly so
    that process can build the 256-chip mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = math.prod(shape)
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, have {len(devices)} — "
            "run under launch/dryrun.py (sets "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512)")
    return jax.sharding.Mesh(
        np.asarray(devices[:need]).reshape(shape), axes)
