"""Mesh construction (functions, not module-level constants, so importing
never touches jax device state).

Single pod:  (16, 16)      axes ("data", "model")   — 256 chips (TPU v5e pod)
Multi-pod:   (2, 16, 16)   axes ("pod", "data", "model") — 512 chips

Every mesh in the repo is built by :func:`make_mesh`, with ``Auto`` axes:
the model code places arrays with ``with_sharding_constraint`` and bare
``PartitionSpec``s under ``with mesh:`` and lets GSPMD propagate the rest,
which ``jax.make_mesh``'s default ``Explicit`` axes refuse.

The dry-run launcher sets XLA_FLAGS=--xla_force_host_platform_device_count=512
before any jax import; everything else in the repo sees the real device
count.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None) -> Mesh:
    """A mesh of ``shape`` over ``devices`` (default: all) with Auto axes."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model_axis: int = 1, devices: Optional[Sequence] = None
                    ) -> Mesh:
    """A ("data", "model") mesh over ``devices`` (default: every device this
    process sees) — used by trainers/tests."""
    devices = list(jax.devices() if devices is None else devices)
    if len(devices) % model_axis:
        raise ValueError(f"--model_axis {model_axis} does not divide the "
                         f"{len(devices)} devices")
    return make_mesh((len(devices) // model_axis, model_axis),
                     ("data", "model"), devices=devices)
