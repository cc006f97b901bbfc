"""Mesh construction: production meshes (single-pod 16x16, multi-pod
2x16x16), small test meshes, and the launchers' ``--mesh`` meshes.

Functions, not module-level constants: importing this module never
touches jax device state (required so smoke tests see 1 device).

Every mesh here has ``Auto`` axes.  ``jax.make_mesh`` defaults to
``Explicit`` axes, under which the sharding rules of
``repro.parallel.sharding`` (``with_sharding_constraint`` by logical
axis, GSPMD-propagated gathers) are refused.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None) -> Mesh:
    """Auto-axis mesh of ``shape`` over the first ``prod(shape)`` of
    ``devices`` (default: ``jax.devices()``)."""
    n = int(np.prod(shape))
    devices = list(jax.devices() if devices is None else devices)
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {tuple(shape)} needs {n} devices but only "
            f"{len(devices)} are available")
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices[:n],
                         axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False,
                         devices: Optional[Sequence] = None) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes, devices)


def make_test_mesh(shape=(2, 2), axes=("data", "model")) -> Mesh:
    """Small mesh for multi-device unit tests (subprocess-forced devices)."""
    return make_mesh(shape, axes)
