"""Locations of a cell: a jittered regular grid on the unit square, in
Morton (Z-curve) order, and uniform prediction locations.

The Morton order is what lets the off-diagonal tiles of Sigma be low-rank
(Salvaña et al. 2020, §5.3), so every configuration is fitted in it.
"""
from __future__ import annotations

import numpy as np


def jittered_grid(nx: int, ny: int, jitter: float,
                  rng: np.random.Generator) -> np.ndarray:
    """(nx * ny, 2) cell centres, each moved by up to ``jitter`` of a cell."""
    xs = (np.arange(nx) + 0.5) / nx
    ys = (np.arange(ny) + 0.5) / ny
    gx, gy = np.meshgrid(xs, ys, indexing="ij")
    locs = np.stack([gx.ravel(), gy.ravel()], axis=-1)
    return locs + rng.uniform(-jitter / nx, jitter / nx, size=locs.shape)


def _spread_bits(v: np.ndarray) -> np.ndarray:
    """Put a zero bit between each of the lower 16 bits of ``v``."""
    v = v.astype(np.uint64) & np.uint64(0xFFFF)
    for shift, mask in ((8, 0x00FF00FF), (4, 0x0F0F0F0F), (2, 0x33333333),
                        (1, 0x55555555)):
        v = (v | (v << np.uint64(shift))) & np.uint64(mask)
    return v


def morton_order(locs: np.ndarray) -> np.ndarray:
    """Permutation sorting 2-D locations by their 16-bit Morton code."""
    lo, hi = locs.min(axis=0), locs.max(axis=0)
    span = np.where(hi > lo, hi - lo, 1.0)
    q = np.clip(((locs - lo) / span * 65535.0).astype(np.uint64), 0, 65535)
    code = _spread_bits(q[:, 0]) | (_spread_bits(q[:, 1]) << np.uint64(1))
    return np.argsort(code, kind="stable")


def locations(grid, jitter: float, rng: np.random.Generator) -> np.ndarray:
    locs = jittered_grid(grid[0], grid[1], jitter, rng)
    return locs[morton_order(locs)]
