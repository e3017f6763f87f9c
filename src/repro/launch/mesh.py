"""Production mesh builders.

Importing this module never touches jax device state — meshes are built
lazily by functions, and the dry-run sets XLA_FLAGS before any jax import.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax


def _mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 chips per pod; 2 pods via the DCN-connected "pod" axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_local_mesh(model_axis: int = 1,
                    devices: Optional[Sequence] = None):
    """(data, model) mesh over ``devices`` (default: every visible device).

    A caller that means to use some of the host's chips passes them: a
    one-chip phase passes ``jax.devices()[:1]``.
    """
    devices = list(jax.devices() if devices is None else devices)
    if len(devices) % model_axis:
        raise ValueError(f"model_axis={model_axis} does not divide "
                         f"{len(devices)} devices")
    return _mesh((len(devices) // model_axis, model_axis),
                 ("data", "model"), devices)
