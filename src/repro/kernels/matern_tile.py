"""Pallas TPU kernel: covariance-tile generation (the paper's GEN phase).

Computes one nb x nb tile of the Matérn covariance directly from the two
location panels — the task HiCMA/STARS-H calls the "matrix generator", and
the first phase the paper times (GEN_TIME in Figs. 10-11).

TPU adaptation (DESIGN.md §2): pairwise distances use the difference form on
the VPU — the |a|^2+|b|^2-2ab^T MXU formulation is rejected because a d=2
contraction uses 2/128 of the systolic array while its cancellation destroys
f32 accuracy at small distances (the near-diagonal tiles that dominate the
covariance).  The Matérn correlation uses the *closed-form half-integer*
smoothness (exp/mul only — VPU-friendly).  General real nu stays on the XLA
path (core/matern.kv): its continued-fraction iteration is scalar-sequential
and branch-heavy, a poor fit for the VPU inner loop.

Grid: (rows/bn, cols/bm); each instance loads a (bn, 2) and (bm, 2) location
panel into VMEM plus two SMEM scalars (1/a, amp) and writes a (bn, bm) tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_SUPPORTED_NU = (0.5, 1.5, 2.5)


def _default_interpret() -> bool:
    """Resolve ``interpret=None``: compiled Mosaic on a TPU backend, the
    interpreter everywhere else (CPU tests, compile rehearsals)."""
    return jax.default_backend() != "tpu"


def compiles_for(dtype, interpret: bool | None = None) -> bool:
    """Whether ``matern_tile`` runs at ``dtype``: the interpreter takes any
    float; Mosaic on TPU has no 64-bit element types."""
    if interpret is None:
        interpret = _default_interpret()
    return interpret or jnp.dtype(dtype).itemsize <= 4


def _matern_halfint_body(u, nu: float):
    zero = u <= 0.0
    us = jnp.where(zero, 1.0, u)
    if nu == 0.5:
        val = jnp.exp(-us)
    elif nu == 1.5:
        val = (1.0 + us) * jnp.exp(-us)
    else:  # 2.5
        val = (1.0 + us + us * us * (1.0 / 3.0)) * jnp.exp(-us)
    return jnp.where(zero, jnp.ones_like(val), val)


def _matern_tile_kernel(scalars_ref, la_ref, lb_ref, out_ref, *, nu: float):
    inv_range = scalars_ref[0, 0]
    amp = scalars_ref[0, 1]
    la = la_ref[...]                      # (bn, 2)
    lb = lb_ref[...]                      # (bm, 2)
    # Difference-based squared distances (VPU).  The |a|^2+|b|^2-2ab^T MXU
    # trick is NOT used: with d=2 the systolic contraction is only 2/128
    # utilized, and the cancellation destroys f32 accuracy exactly where the
    # covariance matters most (near-diagonal tiles, small distances).
    dx = la[:, 0:1] - lb[:, 0:1].T                        # (bn, bm)
    dy = la[:, 1:2] - lb[:, 1:2].T
    d2 = dx * dx + dy * dy
    u = jnp.sqrt(jnp.maximum(d2, 0.0)) * inv_range
    out_ref[...] = (amp * _matern_halfint_body(u, nu)).astype(out_ref.dtype)


def _fit_block(n: int, want: int) -> int:
    """Largest divisor of n that is <= want (grid blocks must tile exactly)."""
    want = max(1, min(want, n))
    if n % want == 0:
        return want
    for b in range(want, 0, -1):
        if n % b == 0:
            return b
    return 1


@functools.partial(jax.jit, static_argnames=("nu", "block_n", "block_m",
                                             "interpret"))
def matern_tile(locs_a, locs_b, inv_range, amp, *, nu: float,
                block_n: int = 256, block_m: int = 256,
                interpret: bool | None = None):
    """Covariance tile C[r, c] = amp * M_nu(||a_r - b_c|| * inv_range).

    locs_a: (n, 2), locs_b: (m, 2).  Block sizes are rounded down to the
    nearest divisor of n / m, so callers may hand arbitrary panel shapes
    (the TLR strict-lower panels are (T-1-j)*nbl tall).  nu must be a static
    half-integer in {0.5, 1.5, 2.5}.  ``interpret=None`` auto-selects:
    compiled Mosaic on TPU, interpreter elsewhere.  Compiled, the kernel
    takes float32 or narrower locations (``compiles_for``).
    """
    if nu not in _SUPPORTED_NU:
        raise ValueError(f"kernel supports nu in {_SUPPORTED_NU}; general nu "
                         "uses the XLA path (core.matern)")
    if interpret is None:
        interpret = _default_interpret()
    n, m = locs_a.shape[0], locs_b.shape[0]
    bn, bm = _fit_block(n, block_n), _fit_block(m, block_m)
    dtype = jnp.result_type(locs_a.dtype, locs_b.dtype)
    if not compiles_for(dtype, interpret):
        raise TypeError(f"matern_tile: Mosaic has no {dtype} lowering on TPU; "
                        "pass float32 locations or generate with gen='xla'")
    scalars = jnp.stack([jnp.asarray(inv_range, dtype),
                         jnp.asarray(amp, dtype)]).reshape(1, 2)

    grid = (n // bn, m // bm)
    return pl.pallas_call(
        functools.partial(_matern_tile_kernel, nu=nu),
        out_shape=jax.ShapeDtypeStruct((n, m), dtype),
        grid=grid,
        # Index maps return int32 zeros: a bare 0 is int64 under x64, which
        # Mosaic cannot lower ("failed to legalize func.return").
        in_specs=[
            pl.BlockSpec((1, 2), lambda i, j: (i * 0, j * 0)),  # scalars
            pl.BlockSpec((bn, 2), lambda i, j: (i, j * 0)),     # row panel
            pl.BlockSpec((bm, 2), lambda i, j: (j, i * 0)),     # col panel
        ],
        out_specs=pl.BlockSpec((bn, bm), lambda i, j: (i, j)),
        interpret=interpret,
    )(scalars, locs_a.astype(dtype), locs_b.astype(dtype))
