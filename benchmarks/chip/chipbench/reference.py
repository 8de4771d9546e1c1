"""Plain dense reference of the parsimonious multivariate Matérn model.

numpy and scipy on the host, in float64 unless told otherwise.  It imports
nothing of the program under test and takes nothing the program made: it
builds Sigma from its own Matérn function, factors it with LAPACK, and
answers the same questions the timed path answers (a log-likelihood, a
cokriging mean and variance).  It also simulates the field every cell fits.

Model (Gneiting, Kleiber and Schlather 2010; Salvaña et al. 2020, §5.2):

    C_ij(h) = rho_ij sigma_i sigma_j M(h / a; nu_ij),  nu_ij = (nu_i + nu_j)/2
    M(u; nu) = 2^(1 - nu) / Gamma(nu) u^nu K_nu(u),   M(0) = 1
    rho_ij = beta_ij sqrt(G(nu_i + d/2) / G(nu_i)) sqrt(G(nu_j + d/2) / G(nu_j))
             G(nu_ij) / G(nu_ij + d/2)

Sigma is in Representation I: entry [l*p + i, r*p + j] = C_ij(s_l - s_r),
with the nugget on the diagonal.  Prediction targets the field without
the nugget, so C(0) = rho_ij sigma_i sigma_j.
"""
from __future__ import annotations

import math
import os
from concurrent.futures import ThreadPoolExecutor
from typing import NamedTuple

import numpy as np
import scipy.linalg as sla
import scipy.special as sp

THREADS = max(1, min(16, os.cpu_count() or 1))
ROWS_PER_TASK = 256


class Params(NamedTuple):
    sigma2: np.ndarray   # (p,)
    a: float
    nu: np.ndarray       # (p,)
    beta: np.ndarray     # (p, p), unit diagonal

    @property
    def p(self) -> int:
        return len(self.sigma2)


def params_from_config(truth: dict) -> Params:
    """``truth`` as a configuration file states it: sigma2, a, nu, beta."""
    p = len(truth["sigma2"])
    beta = np.eye(p)
    iu = np.triu_indices(p, 1)
    beta[iu] = truth["beta"]
    beta[iu[::-1]] = truth["beta"]
    return Params(np.asarray(truth["sigma2"], float), float(truth["a"]),
                  np.asarray(truth["nu"], float), beta)


# The objective's search space: log sigma2, log a, log nu (clipped to
# [1e-2, nu_max]), atanh of beta's upper triangle.  That transform is the
# public contract of ``core.mle.make_objective``; it is written out here so
# that the reference reads a point of the search space on its own.
def pack(prm: Params) -> np.ndarray:
    iu = np.triu_indices(prm.p, 1)
    return np.concatenate([np.log(prm.sigma2), [math.log(prm.a)],
                           np.log(prm.nu), np.arctanh(prm.beta[iu])])


def unpack(x, p: int, nu_max: float = 4.0) -> Params:
    x = np.asarray(x, float)
    sigma2 = np.exp(x[:p])
    a = math.exp(x[p])
    nu = np.clip(np.exp(x[p + 1:2 * p + 1]), 1e-2, nu_max)
    beta = np.eye(p)
    iu = np.triu_indices(p, 1)
    vals = np.tanh(x[2 * p + 1:])
    beta[iu] = vals
    beta[iu[::-1]] = vals
    return Params(sigma2, a, nu, beta)


def matern(u, nu: float) -> np.ndarray:
    u = np.asarray(u, float)
    out = np.ones_like(u)
    pos = u > 0
    up = u[pos]
    out[pos] = np.exp((1.0 - nu) * math.log(2.0) - sp.gammaln(nu)
                      + nu * np.log(up)) * sp.kv(nu, up)
    return out


def colocated(prm: Params, d: int = 2) -> np.ndarray:
    """C(0): the (p, p) colocated covariance, rho_ij sigma_i sigma_j."""
    nu, hd = prm.nu, 0.5 * d
    nij = 0.5 * (nu[:, None] + nu[None, :])
    marg = 0.5 * (sp.gammaln(nu + hd) - sp.gammaln(nu))
    rho = prm.beta * np.exp(marg[:, None] + marg[None, :]
                            + sp.gammaln(nij) - sp.gammaln(nij + hd))
    np.fill_diagonal(rho, 1.0)
    sig = np.sqrt(prm.sigma2)
    return rho * sig[:, None] * sig[None, :]


def _panel(rows, cols, prm: Params, out):
    """out[(l, i), (r, j)] = C_ij(rows[l] - cols[r]), Representation I."""
    p = prm.p
    amp = colocated(prm)
    u = np.sqrt(((rows[:, None, :] - cols[None, :, :]) ** 2).sum(-1)) / prm.a
    for i in range(p):
        for j in range(i, p):
            c = amp[i, j] * matern(u, 0.5 * (prm.nu[i] + prm.nu[j]))
            out[i::p, j::p] = c
            if i != j:
                out[j::p, i::p] = c


def cross_cov(rows, cols, prm: Params, dtype=np.float64,
              symmetric: bool = False) -> np.ndarray:
    """(len(rows) p, len(cols) p) cross-covariance, built in row blocks on
    ``THREADS`` threads (scipy's K_nu releases the interpreter lock).
    ``symmetric`` (rows is cols): only the blocks on and right of the
    diagonal are generated, and mirrored."""
    rows = np.asarray(rows, float)
    cols = np.asarray(cols, float)
    p = prm.p
    out = np.empty((rows.shape[0] * p, cols.shape[0] * p), dtype)

    def block(r0):
        r1 = min(r0 + ROWS_PER_TASK, rows.shape[0])
        c0 = r0 if symmetric else 0
        _panel(rows[r0:r1], cols[c0:], prm, out[r0 * p:r1 * p, c0 * p:])
        if symmetric:
            out[r1 * p:, r0 * p:r1 * p] = out[r0 * p:r1 * p, r1 * p:].T

    with ThreadPoolExecutor(THREADS) as ex:
        list(ex.map(block, range(0, rows.shape[0], ROWS_PER_TASK)))
    return out


def sigma(locs, prm: Params, nugget: float, dtype=np.float64) -> np.ndarray:
    s = cross_cov(locs, locs, prm, dtype, symmetric=True)
    s[np.diag_indices_from(s)] += nugget
    return s


def factor(locs, prm: Params, nugget: float, dtype=np.float64):
    """Lower Cholesky factor of Sigma, or None where it breaks down."""
    try:
        return sla.cholesky(sigma(locs, prm, nugget, dtype), lower=True,
                            overwrite_a=True, check_finite=False)
    except np.linalg.LinAlgError:
        return None


def loglik(locs, z, prm: Params, nugget: float, dtype=np.float64):
    """Gaussian log-likelihood of ``z`` (a float), None on breakdown."""
    lo = factor(locs, prm, nugget, dtype)
    if lo is None:
        return None
    diag = np.diagonal(lo)
    if not np.all(diag > 0):
        return None
    alpha = sla.solve_triangular(lo, np.asarray(z, dtype), lower=True,
                                 check_finite=False)
    m = len(z)
    val = -0.5 * (m * math.log(2 * math.pi)
                  + 2.0 * np.sum(np.log(diag.astype(float)))
                  + float(np.dot(alpha.astype(float), alpha.astype(float))))
    return val if math.isfinite(val) else None


def simulate(locs, prm: Params, nugget: float, rng: np.random.Generator):
    """An exact draw z = L eps of the field, and L."""
    lo = factor(locs, prm, nugget)
    if lo is None:
        raise ValueError("Sigma at the configured parameters is not "
                         "positive definite")
    eps = rng.standard_normal(lo.shape[0])
    return lo @ eps, lo


class Kriging:
    """Dense cokriging from a lower factor ``lo`` of Sigma at ``prm``."""

    def __init__(self, lo, locs, z, prm: Params):
        self.lo, self.locs, self.prm = lo, np.asarray(locs, float), prm
        y = sla.solve_triangular(lo, z, lower=True, check_finite=False)
        self.alpha = sla.solve_triangular(lo, y, lower=True, trans="T",
                                          check_finite=False)
        self.c00 = colocated(prm)

    def predict(self, pred):
        """Mean and variance, each (B, p), at the (B, 2) locations."""
        p = self.prm.p
        c0 = cross_cov(self.locs, pred, self.prm, self.lo.dtype)
        w = sla.solve_triangular(self.lo, c0, lower=True, check_finite=False)
        mean = (c0.T @ self.alpha).reshape(-1, p)
        var = np.diagonal(self.c00) - (w * w).sum(0).reshape(-1, p)
        return mean.astype(float), var.astype(float)
