"""Production mesh builders.

Single pod: (16, 16) = (data, model) — 256 chips (TPU v5e pod).
Multi-pod:  (2, 16, 16) = (pod, data, model) — 512 chips; the thin `pod`
axis composes with `data` for batch/gradient reduction (DCN-side), `model`
stays intra-pod (ICI-side).

Functions, not module constants: importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before any jax import).
"""
from __future__ import annotations

import numpy as np
import jax


def _make_mesh(shape, axes):
    n = int(np.prod(shape))
    # never silently truncate to however many devices happen to exist — a
    # (16, 16) mesh on a 1-device host must fail loudly with the actual
    # count (core.shard.take_devices raises with the CPU-emulation recipe)
    from ..core.shard import take_devices
    devices = take_devices(n)
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(shape=(1, 1), axes=("data", "model")):
    """Tiny mesh for CPU smoke tests (1 real device)."""
    return _make_mesh(shape, axes)


def dp_axes(mesh) -> tuple:
    """Axes used for batch/data parallelism on this mesh."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
