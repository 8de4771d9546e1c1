"""Cokriging prediction (Eq. 3) and prediction-error metrics (§4.5).

Z_hat(s0) = c0^T Sigma(theta)^{-1} Z

All n_pred prediction locations are solved in ONE batched triangular solve
(Level-3 BLAS) instead of the per-location Level-2 loop the paper times as
COMP_TIME — this is the first beyond-paper optimization (see EXPERIMENTS.md
§Perf-assessment).

The factor-once / predict-millions API lives on ``CokrigeFactor``: one
handle carrying the Cholesky factor (dense (m, m) lower triangle, or the
pair-major TLR tiles from core/dist_tlr.py), the precomputed ``alpha =
Sigma^{-1} z`` weights, and the observation geometry.  ``cokrige`` /
``cokrige_and_score`` accept ``factor=`` and never touch Sigma again;
``serving/cokrige_service.py`` builds the TLR variant and streams batched
prediction panels against it.  The old ``chol=`` kwarg threading is a
one-release deprecation shim.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .linalg import cho_solve, cholesky
from .covariance import MaternParams, build_c0, build_sigma
from .recovery import init_status


class CokrigingResult(NamedTuple):
    predictions: jax.Array   # (npred, p)
    mspe: jax.Array          # scalar: mean over locations of ||Zhat - Z||^2
    mspe_per_var: jax.Array  # (p,)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class CokrigeFactor:
    """On-device factorized-Sigma handle: factor once, predict millions.

    ``kind="dense"``: ``diag_l`` is the (m, m) lower Cholesky factor of
    Sigma and u/v/ranks are None.  ``kind="tlr"``: ``diag_l`` is the (T,
    nb, nb) factored diagonal tiles and u/v/ranks the pair-major
    strict-lower factor tiles of core/dist_tlr.py (their block-cyclic
    layout is reconstructed from the static ``n_shards``, like PairTLR).

    ``alpha = Sigma^{-1} z`` is precomputed at fit time, so a prediction
    batch costs one streamed c0 panel contraction (the mean) plus one
    forward solve (the variance) — Sigma is never rebuilt or refactorized
    between batches.  The handle is a registered pytree: it passes through
    jit boundaries, and donated fit buffers alias straight into it.
    """

    diag_l: jax.Array          # dense (m, m) chol | TLR (T, nb, nb) tiles
    u: jax.Array | None        # TLR (length, nb, kmax) pair-major tiles
    v: jax.Array | None
    ranks: jax.Array | None    # TLR (length,) int32
    alpha: jax.Array           # (m,) Sigma^{-1} z
    locs: jax.Array            # (n, d) observation locations
    params: MaternParams
    kind: str = "dense"        # static: "dense" | "tlr"
    n_shards: int = 1          # static: TLR pair layout shard count
    representation: str = "I"  # static: dense-path Sigma layout
    d_spatial: int = 2         # static
    z: jax.Array | None = None       # (m,) observed data (degraded refits)
    status: object = None            # FactorStatus | None: factor health

    def tree_flatten(self):
        children = (self.diag_l, self.u, self.v, self.ranks, self.alpha,
                    self.locs, self.params, self.z, self.status)
        aux = (self.kind, self.n_shards, self.representation, self.d_spatial)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        kind, n_shards, representation, d_spatial = aux
        diag_l, u, v, ranks, alpha, locs, params, z, status = children
        return cls(diag_l=diag_l, u=u, v=v, ranks=ranks, alpha=alpha,
                   locs=locs, params=params, kind=kind, n_shards=n_shards,
                   representation=representation, d_spatial=d_spatial,
                   z=z, status=status)

    @property
    def m(self) -> int:
        return self.alpha.shape[0]


def dense_factor(obs_locs, z_obs, params: MaternParams,
                 representation: str = "I", nugget: float = 0.0,
                 chol=None) -> CokrigeFactor:
    """Factorize dense Sigma once and wrap it as a ``CokrigeFactor``.

    ``chol`` accepts an already-computed lower Cholesky factor (no Sigma
    rebuild); otherwise Sigma is built and factorized here — the one
    O(m^3) step the handle amortizes away.
    """
    if chol is None:
        sigma = build_sigma(obs_locs, params, representation=representation,
                            nugget=nugget)
        chol = cholesky(sigma)
    alpha = cho_solve(chol, z_obs)
    status = init_status(chol.dtype).update_potrf(chol)
    return CokrigeFactor(diag_l=chol, u=None, v=None, ranks=None, alpha=alpha,
                         locs=jnp.asarray(obs_locs), params=params,
                         kind="dense", representation=representation,
                         z=jnp.asarray(z_obs), status=status)


def _chol_shim(obs_locs, z_obs, params, representation, chol):
    """One-release deprecation shim: wrap a raw ``chol=`` lower factor in a
    CokrigeFactor without ever calling build_sigma (tested)."""
    from ..distribution.pair_qr import warn_fallback_once
    warn_fallback_once(
        "cokrige-chol-deprecated",
        "cokrige/cokrige_and_score: the chol= kwarg is deprecated and will "
        "be removed next release — pass factor=dense_factor(..., chol=chol) "
        "(or a serving fit_factor handle) instead")
    return dense_factor(obs_locs, z_obs, params,
                        representation=representation, chol=chol)


def cokrige(obs_locs, z_obs, pred_locs, params: MaternParams = None,
            representation: str = "I", nugget: float = 0.0, chol=None,
            factor: CokrigeFactor | None = None):
    """Best linear unbiased cokriging predictor at ``pred_locs``.

    Returns (npred, p) predictions for all p variables at each location.

    ``factor`` takes a pre-computed ``CokrigeFactor`` (dense_factor, or
    serving.fit_factor for the TLR path): the handle already carries
    ``alpha = Sigma^{-1} z`` and the observation geometry, so repeated
    prediction batches skip the O(m^3) rebuild entirely — obs_locs/z_obs/
    params may then be None.  ``chol=`` (a raw lower Cholesky factor) is
    deprecated; it is wrapped in a dense handle with a one-shot warning.
    """
    if factor is None and chol is not None:
        factor = _chol_shim(obs_locs, z_obs, params, representation, chol)
    if factor is not None:
        obs_locs, params = factor.locs, factor.params
        representation = factor.representation
        if factor.kind != "dense":
            from ..serving.cokrige_service import predict_with_factor
            return predict_with_factor(factor, pred_locs).mean
        alpha = factor.alpha
    else:
        sigma = build_sigma(obs_locs, params, representation=representation,
                            nugget=nugget)
        chol = cholesky(sigma)
        alpha = cho_solve(chol, z_obs)
    c0 = build_c0(pred_locs, obs_locs, params, representation=representation)
    # Contract the precomputed Sigma^{-1} Z with all c0 blocks at once.
    return jnp.einsum("lrp,r->lp", c0, alpha)


def mspe(pred, truth):
    """Mean square prediction error, total and per variable.

    pred/truth: (npred, p).
    """
    err2 = (pred - truth) ** 2
    return jnp.mean(jnp.sum(err2, axis=-1)), jnp.mean(err2, axis=0)


def msrp(pred, truth, eps: float = 1e-12):
    """Mean square relative prediction error (Yan & Genton 2018)."""
    rel = (pred - truth) / jnp.where(jnp.abs(truth) < eps, eps, truth)
    return jnp.mean(rel ** 2)


def cokrige_and_score(obs_locs, z_obs, pred_locs, z_pred_true,
                      params: MaternParams = None,
                      representation: str = "I", nugget: float = 0.0,
                      chol=None,
                      factor: CokrigeFactor | None = None) -> CokrigingResult:
    """Predict and score in one call.  ``factor`` threads a pre-computed
    ``CokrigeFactor`` through to ``cokrige`` — a caller that already
    factorized does not rebuild + refactorize the (m, m) matrix.  ``chol=``
    is the deprecated raw-factor form (shimmed, one-shot warning)."""
    if factor is None and chol is not None:
        factor = _chol_shim(obs_locs, z_obs, params, representation, chol)
        chol = None
    pred = cokrige(obs_locs, z_obs, pred_locs, params,
                   representation=representation, nugget=nugget,
                   factor=factor)
    if factor is not None:
        params, representation = factor.params, factor.representation
    p = params.p
    truth = z_pred_true.reshape(-1, p) if representation.upper() == "I" else \
        z_pred_true.reshape(p, -1).T
    total, per_var = mspe(pred, truth)
    return CokrigingResult(pred, total, per_var)
