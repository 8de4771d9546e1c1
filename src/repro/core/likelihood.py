"""Gaussian log-likelihood (Eq. 1) — exact dense path + profile likelihood.

l(theta) = -np/2 log(2 pi) - 1/2 log|Sigma| - 1/2 Z^T Sigma^{-1} Z

The dense path Cholesky-factorizes Sigma (O(p^3 n^3)); the profile path
(§5.2) removes the p marginal variances from the optimization and recovers
them in closed form afterwards:

    sigma_ii^2 = n^{-1} Z_i^T R_ii(theta_i)^{-1} Z_i.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .linalg import cholesky, solve_lower
from .covariance import (MaternParams, build_correlation_matrix, build_sigma,
                         pairwise_distances)
from .recovery import FactorStatus, init_status


class LoglikResult(NamedTuple):
    loglik: jax.Array
    logdet: jax.Array
    quad: jax.Array          # Z^T Sigma^{-1} Z
    chol: jax.Array | None   # lower Cholesky factor (None if not kept)
    status: FactorStatus | None = None  # factorization health (None if untracked)


@jax.named_scope("repro.solve")
def loglik_from_chol(chol, z, keep_chol: bool = False,
                     status: FactorStatus | None = None) -> LoglikResult:
    """Log-likelihood given the lower Cholesky factor of Sigma.

    When no factorization ``status`` is threaded in, a cheap one is derived
    from the factor's diagonal (the dense path has a single POTRF).
    """
    m = z.shape[-1]
    if status is None:
        status = init_status(chol.dtype).update_potrf(chol)
    logdet = 2.0 * jnp.sum(jnp.log(jnp.diagonal(chol)))
    alpha = solve_lower(chol, z)
    quad = jnp.sum(alpha * alpha, axis=-1)
    ll = -0.5 * (m * math.log(2.0 * math.pi) + logdet + quad)
    return LoglikResult(ll, logdet, quad, chol if keep_chol else None, status)


def exact_loglik(locs, z, params: MaternParams, representation: str = "I",
                 nugget: float = 0.0, dists=None,
                 keep_chol: bool = False) -> LoglikResult:
    """Dense-Cholesky evaluation of Eq. (1)."""
    sigma = build_sigma(locs, params, representation=representation,
                        nugget=nugget, dists=dists)
    with jax.named_scope("repro.factorize"):
        chol = cholesky(sigma)
    return loglik_from_chol(chol, z, keep_chol=keep_chol)


def profile_variances(dists, z, a, nu, p: int, nugget: float = 0.0,
                      representation: str = "I"):
    """Closed-form marginal variance estimates (profile trick, §5.2).

    z is the (p*n,) data vector in the given representation ordering.
    Returns (p,) sigma_ii^2 estimates.
    """
    n = dists.shape[0]

    def one(i):
        r = build_correlation_matrix(None, a, nu[i], nugget=nugget, dists=dists)
        chol = cholesky(r)
        if representation.upper() == "I":
            zi = z[i::p]
        else:
            zi = jax.lax.dynamic_slice_in_dim(z, i * n, n)
        alpha = solve_lower(chol, zi)
        return jnp.sum(alpha * alpha) / n

    return jnp.stack([one(i) for i in range(p)])


def profile_loglik(locs, z, a, nu, beta, p: int, representation: str = "I",
                   nugget: float = 0.0, dists=None) -> LoglikResult:
    """Profile log-likelihood: variances replaced by their marginal estimates.

    This follows the paper's §5.2: optimize only (a, nu_i, beta_ij); at each
    objective evaluation plug the closed-form sigma_ii^2 back into the full
    likelihood.
    """
    if dists is None:
        dists = pairwise_distances(locs)
    sigma2_hat = profile_variances(dists, z, a, nu, p, nugget=nugget,
                                   representation=representation)
    params = MaternParams(sigma2=sigma2_hat, a=jnp.asarray(a), nu=jnp.asarray(nu),
                          beta=jnp.asarray(beta))
    return exact_loglik(None, z, params, representation=representation,
                        nugget=nugget, dists=dists)
