"""Mesh construction.

Functions, not module-level constants, so importing this module never
touches jax device state (the dry-run must set XLA_FLAGS first).

Every mesh in the repo is built by ``auto_mesh``: all axes are
``AxisType.Auto``, so ``with_sharding_constraint`` and ``jax.shard_map``
accept specs over any of them (``jax.make_mesh`` defaults to Explicit
axes, which refuse both).
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def auto_mesh(shape, axes, *, devices=None) -> Mesh:
    """Mesh of ``shape`` over ``axes`` with every axis Auto.

    ``devices`` defaults to ``jax.devices()``; pass described devices
    (``topologies.get_topology_desc(...).devices``) to compile for a chip
    that is not attached.
    """
    shape, axes = tuple(shape), tuple(axes)
    types = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(shape, axes, axis_types=types)
    n = int(np.prod(shape))
    return Mesh(np.asarray(devices[:n], dtype=object).reshape(shape), axes,
                axis_types=types)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_mesh_for_devices(n_devices: int | None = None, model_parallel: int = 0):
    """Best-effort mesh for whatever devices exist (tests / local runs)."""
    n = n_devices or len(jax.devices())
    if model_parallel <= 0:
        model_parallel = 1
        while (model_parallel * 2) ** 2 <= n:
            model_parallel *= 2
        model_parallel = min(model_parallel, n)
    data = max(n // model_parallel, 1)
    return auto_mesh((data, model_parallel), ("data", "model"))


def mesh_chip_count(mesh) -> int:
    return mesh.devices.size
