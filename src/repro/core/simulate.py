"""Synthetic multivariate Gaussian random field generator (paper §6.4.1).

Generates exact samples Z = L eps with L the Cholesky factor of Sigma(theta),
on regular grids (Fig. 12: 158 x 158 unit-square grid) or irregular uniform
locations.  Also provides the WRF-like bivariate/trivariate "real data
application" surrogate used by benchmarks/bench_real_app.py: since the paper's
WRF dataset is not redistributable, we synthesize fields from the *fitted*
parameters the paper reports (Tables 1-2) so the inference pipeline can be
validated against published values.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .linalg import cholesky
from .covariance import MaternParams, build_sigma, morton_order


def grid_locations(nx: int, ny: int | None = None, jitter: float = 0.0,
                   seed: int = 0) -> np.ndarray:
    """Regular (optionally jittered) grid on the unit square, (nx*ny, 2)."""
    ny = nx if ny is None else ny
    xs = (np.arange(nx) + 0.5) / nx
    ys = (np.arange(ny) + 0.5) / ny
    gx, gy = np.meshgrid(xs, ys, indexing="ij")
    locs = np.stack([gx.ravel(), gy.ravel()], axis=-1)
    if jitter != 0.0:               # host-side numpy; explicit, not truthiness
        rng = np.random.default_rng(seed)
        locs = locs + rng.uniform(-jitter / nx, jitter / nx, size=locs.shape)
    return locs


def uniform_locations(n: int, seed: int = 0) -> np.ndarray:
    """n iid-uniform locations on the unit square (irregular sampling)."""
    rng = np.random.default_rng(seed)
    return rng.uniform(0.0, 1.0, size=(n, 2))


def simulate_mgrf(key, locs, params: MaternParams, representation: str = "I",
                  nugget: float = 0.0, nsamples: int = 1):
    """Exact sample(s) from the zero-mean multivariate GRF.

    Returns (nsamples, p*n) ordered per ``representation``.
    """
    locs = jnp.asarray(locs)
    n = locs.shape[0]
    p = params.p
    sigma = build_sigma(locs, params, representation=representation, nugget=nugget)
    chol = cholesky(sigma)
    eps = jax.random.normal(key, (nsamples, n * p), dtype=sigma.dtype)
    return eps @ chol.T


def split_train_pred(locs, z, n_pred: int, seed: int = 0, p: int = 1,
                     representation: str = "I"):
    """Hold out ``n_pred`` locations (all p variables missing there, §4.3)."""
    locs = np.asarray(locs)
    n = locs.shape[0]
    rng = np.random.default_rng(seed)
    perm = rng.permutation(n)
    pred_idx = np.sort(perm[:n_pred])
    obs_idx = np.sort(perm[n_pred:])
    z = np.asarray(z)

    def gather(idx):
        if representation.upper() == "I":
            rows = (idx[:, None] * p + np.arange(p)[None, :]).ravel()
        else:
            rows = (np.arange(p)[:, None] * n + idx[None, :]).ravel()
        return z[..., rows]

    return (locs[obs_idx], gather(obs_idx), locs[pred_idx], gather(pred_idx),
            obs_idx, pred_idx)


def morton_sorted_locations(locs):
    """Morton-sort locations (the paper's TLR preprocessing)."""
    perm = morton_order(locs)
    return np.asarray(locs)[perm], perm


# Parameters the paper reports for the real WRF datasets (Tables 1 and 2);
# used to synthesize "real-data-like" fields for the application benchmark.
PAPER_TABLE1_BIVARIATE = dict(sigma11=0.718, sigma22=0.710, a=0.161,
                              nu11=2.283, nu22=2.033, beta=0.192)
PAPER_TABLE2_TRIVARIATE = dict(sigma2=(0.788, 0.874, 0.301), a=0.0822,
                               nu=(1.689, 1.629, 1.234),
                               beta12=0.243, beta13=-0.124, beta23=-0.059)


def wrf_like_params(kind: str = "bivariate", dtype=jnp.float64) -> MaternParams:
    if kind == "bivariate":
        return MaternParams.bivariate(dtype=dtype, **PAPER_TABLE1_BIVARIATE)
    if kind == "trivariate":
        return MaternParams.trivariate(dtype=dtype, **PAPER_TABLE2_TRIVARIATE)
    raise ValueError(kind)
