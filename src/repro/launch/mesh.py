"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  The production target is a TPU v5e pod of
16 x 16 = 256 chips (axes: data, model), and 2 pods = 512 chips with a
leading "pod" axis.  On this CPU container the dry-run launcher sets
XLA_FLAGS=--xla_force_host_platform_device_count=... before any jax import
so these shapes can be built from placeholder host devices.
"""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

import jax
from jax.sharding import AxisType


def _mesh(shape, axes, devices):
    """Mesh with ``Auto`` axes: the partitioner propagates shardings from
    the placed inputs and ``with_sharding_constraint`` hints, which is what
    every rule in ``dist/partitioning.py`` assumes.  (``jax.make_mesh``
    defaults to ``Explicit`` axes since JAX 0.7, under which an unannotated
    gather such as the embedding lookup is a sharding type error.)"""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)}; "
            "set XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "importing jax (launch/dryrun.py does this)")
    return _mesh(shape, axes, devices[:n])


def make_debug_mesh(data: int = 1, model: int = 1,
                    devices: Optional[Sequence] = None):
    """Small mesh over however many (possibly forced-host) devices exist:
    the first ``data * model``, or exactly ``devices`` when given (a
    one-device mesh pins a serving replica to its own chip)."""
    n = data * model
    devices = list(jax.devices()[:n] if devices is None else devices)
    if len(devices) != n:
        raise RuntimeError(f"need {n} devices, have {len(devices)}")
    return _mesh((data, model), ("data", "model"), devices)


def make_scaled_mesh(n_chips: int, model: int = 16):
    """Meshes of varying size for Ernest f(m) fitting (m = n_chips).

    Keeps the model axis fixed (TP within a host ring) and scales the data
    axis, mirroring how capacity is added in production."""
    model = min(model, n_chips)
    data = n_chips // model
    devices = jax.devices()
    if len(devices) < data * model:
        raise RuntimeError(f"need {data * model} devices, have {len(devices)}")
    return _mesh((data, model), ("data", "model"), devices[: data * model])
