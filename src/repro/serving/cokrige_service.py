"""Cokriging-as-a-service: factor once, predict millions (Eq. 3 at scale).

The estimation pipeline (core/dist_tlr.py) runs pair-sharded TLR at 65k+
locations, but prediction — the workload production users actually hit
millions of times (ExaGeoStat's production-facing phase; Abdulah et al.
2018) — previously rebuilt and refactorized dense Sigma per call.  This
module is the prefill/decode split of serving/engine.py applied to
cokriging:

  * ``fit_factor`` (prefill, once): generator-direct compress + distributed
    TLR Cholesky + both triangular solves for ``alpha = Sigma^{-1} z``,
    returning an on-device ``CokrigeFactor`` handle.  O(m^3 / tile) work,
    paid once per (locations, theta).
  * ``predict_batch`` (decode, millions): one streamed c0 panel batch
    against the cached factor — a tile-panel generator sweep, one
    multi-RHS forward solve, and a small GEMM.  Sigma is never rebuilt,
    the factor never leaves device memory, and neither Sigma nor the
    all-points c0 is materialized: each batch holds one (m, B*p) panel.

Batch products are first-class: predictions (the cokriging mean),
kriging variances and central prediction intervals, and conditional-
simulation draws (per-location conditional law — the p x p colocated
conditional covariance, not the O(B^2) joint over the batch).

``make_cokrige_serve_fns`` returns the two functions jit-compiled with the
factor pytree flowing through unchanged — repeated ``predict_batch`` calls
at fixed B hit one executable.  The dry-run (launch/dryrun.py) lowers both
phases at pod scale and reports per-device temps and predictions/sec; the
bench (benchmarks/bench_tlr.py) measures them.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import PartitionSpec as P

from ..core.linalg import solve_lower
from ..core.covariance import (MaternParams, build_c0_panels,
                               build_sigma_panel, cross_cov_at_zero)
from ..core.dist_tlr import (dist_compress_tiles, dist_tlr_cholesky_pairs,
                             dist_tlr_solve_lower_pairs,
                             dist_tlr_solve_upper_pairs)
from ..core.prediction import CokrigeFactor
from ..core.tlr import _constrain, choose_tile_size
from ..distribution.block_cyclic import pair_layout, pair_shards

__all__ = ["CokrigeServeConfig", "CokrigePrediction", "ServeError",
           "fit_factor", "heal_factor", "predict_batch",
           "predict_with_factor", "make_cokrige_serve_fns",
           "cokrige_fit_lowerable", "cokrige_predict_lowerable"]


class ServeError(ValueError):
    """Structured refusal: the service will not serve garbage.

    ``code`` is machine-readable (``bad_shape`` | ``bad_dtype`` |
    ``nonfinite_locs`` | ``broken_factor``); ``status`` carries the
    factor's ``FactorStatus.as_dict()`` when the refusal is about factor
    health.  ``to_dict()`` is the wire form.
    """

    def __init__(self, code: str, message: str, status: dict | None = None,
                 detail: dict | None = None):
        super().__init__(f"[{code}] {message}")
        self.code = code
        self.message = message
        self.status = status
        self.detail = detail or {}

    def to_dict(self) -> dict:
        return {"code": self.code, "message": self.message,
                "status": self.status, "detail": self.detail}


@dataclasses.dataclass(frozen=True)
class CokrigeServeConfig:
    """Static knobs of one serving deployment (hashable: jit-cache key).

    tile_size/max_rank/tol mirror GeoStatConfig; ``interval`` is the
    central prediction-interval mass (0.95 -> the 2.5%/97.5% band).
    """

    tile_size: int = 0            # 0 -> choose_tile_size heuristic
    max_rank: int = 0             # 0 -> nb // 4 heuristic
    tol: float = 1e-7
    nugget: float = 0.0
    gen: str = "xla"
    d_spatial: int = 2
    row_axes: tuple = ("data",)
    col_block: int = 1
    shard_svd: bool = True
    shard_recompress: bool = True
    super_panels: int = 1
    interval: float = 0.95
    # Request validation in ``predict_batch``: refuse malformed or
    # non-finite prediction locations and broken factors with a structured
    # ``ServeError`` instead of serving NaNs.
    validate: bool = True
    # Degraded mode: a broken factor is transparently re-fit with the
    # nugget escalated along the jitter ladder (``heal_factor``) instead of
    # refused.  Costs one prefill per failed rung, on the request path.
    degraded: bool = False
    degraded_initial_jitter: float = 1e-8
    degraded_factor: float = 10.0
    degraded_max_jitter: float = 1e-2
    degraded_max_attempts: int = 5


class CokrigePrediction(NamedTuple):
    """One decoded batch: mean, kriging variance, interval, draws."""

    mean: jax.Array            # (B, p) cokriging predictions (Eq. 3)
    variance: jax.Array        # (B, p) kriging variances, clipped >= 0
    lower: jax.Array           # (B, p) central-interval bounds
    upper: jax.Array           # (B, p)
    draws: jax.Array | None = None   # (n_draws, B, p) conditional draws


def _z_crit(interval: float):
    """Two-sided normal critical value for the central interval mass."""
    from jax.scipy.special import ndtri
    return ndtri(0.5 + 0.5 * interval)


def fit_factor(locs, z, params: MaternParams, cfg: CokrigeServeConfig,
               mesh=None, nugget=None) -> CokrigeFactor:
    """Prefill: compress + factorize Sigma once, precompute alpha.

    Generator-direct: the dense (m, m) Sigma never exists.  The tile
    buffers flow compress -> Cholesky -> solves inside one trace, so under
    jit XLA aliases them in place (the donation half of the serving
    contract; ``make_cokrige_serve_fns`` compiles exactly this).  Returns
    the on-device ``CokrigeFactor`` — everything ``predict_batch`` needs,
    nothing it would rebuild.

    The factorization's ``FactorStatus`` rides on ``factor.status`` (an
    in-graph pytree — no host sync here); ``predict_batch`` checks it
    before serving.  ``nugget`` (a traced scalar operand, NOT a jit-cache
    key) is *added* to ``cfg.nugget`` — the jitter ladder of
    ``heal_factor`` re-executes one compiled prefill at escalating values.
    """
    locs = jnp.asarray(locs)
    z = jnp.asarray(z)
    m = z.shape[0]
    p = params.p
    nb = choose_tile_size(m, cfg.tile_size, multiple_of=p)
    T = m // nb
    layout = pair_layout(T, pair_shards(mesh, cfg.row_axes))
    eff_nugget = cfg.nugget if nugget is None else cfg.nugget + nugget
    scale = jnp.max(params.sigma2) + cfg.nugget
    t = dist_compress_tiles(locs, params, tile_size=cfg.tile_size,
                            tol=cfg.tol, max_rank=cfg.max_rank,
                            nugget=eff_nugget, gen=cfg.gen,
                            d_spatial=cfg.d_spatial, scale=scale, mesh=mesh,
                            row_axes=cfg.row_axes, layout=layout,
                            col_block=cfg.col_block, shard_svd=cfg.shard_svd)
    diag_l, u, v, ranks, status = dist_tlr_cholesky_pairs(
        t.diag, t.u, t.v, t.ranks, layout=layout, tol=cfg.tol, scale=scale,
        mesh=mesh, row_axes=cfg.row_axes, super_panels=cfg.super_panels,
        shard_recompress=cfg.shard_recompress, track_status=True)
    y = dist_tlr_solve_lower_pairs(diag_l, u, v, z, layout=layout)
    alpha = dist_tlr_solve_upper_pairs(diag_l, u, v, y, layout=layout)
    status = status.add_nonfinite(
        jnp.sum(~jnp.isfinite(alpha)).astype(jnp.int32))
    return CokrigeFactor(diag_l=diag_l, u=u, v=v, ranks=ranks, alpha=alpha,
                         locs=locs, params=params, kind="tlr",
                         n_shards=layout.n_shards,
                         d_spatial=cfg.d_spatial, z=z, status=status)


def _predict_core(factor: CokrigeFactor, pred_locs, *, interval: float,
                  gen: str, mesh=None, row_axes=("data",)):
    """Mean + conditional covariance of one batch against a cached factor.

    Returns (mean (B, p), cond_cov (B, p, p)).  The c0 panel batch is
    generated tile-row-wise (build_c0_panels) and consumed twice: the mean
    is its contraction with the precomputed alpha; the conditional
    covariance is C(0) - w^T w with w = L^{-1} c0 from ONE multi-RHS
    forward solve — per-location (p, p) blocks, never the O(B^2) joint.
    """
    params = factor.params
    p = params.p
    pred_locs = jnp.asarray(pred_locs)
    B = pred_locs.shape[0]
    m = factor.m
    row = row_axes if len(row_axes) > 1 else row_axes[0]

    if factor.kind == "dense":
        with jax.named_scope("repro.gen"):
            c0 = build_sigma_panel(factor.locs, pred_locs, params,
                                   d_spatial=factor.d_spatial,
                                   gen=gen)                   # (m, B*p)
        with jax.named_scope("repro.solve"):
            w = solve_lower(factor.diag_l, c0)
    else:
        T, nb = factor.diag_l.shape[0], factor.diag_l.shape[1]
        layout = pair_layout(T, factor.n_shards)
        c0 = build_c0_panels(factor.locs, pred_locs, params, nbl=nb // p,
                             d_spatial=factor.d_spatial, gen=gen)
        c0 = _constrain(c0, mesh, P(row, None, None))
        c0 = c0.reshape(m, B * p)
        w = dist_tlr_solve_lower_pairs(factor.diag_l, factor.u, factor.v,
                                       c0, layout=layout)     # (m, B*p)

    mean = (c0.T @ factor.alpha).reshape(B, p)
    w3 = w.reshape(m, B, p)
    cond = cross_cov_at_zero(params, d_spatial=factor.d_spatial)[None] \
        - jnp.einsum("mbp,mbq->bpq", w3, w3)
    return mean, cond


def predict_with_factor(factor: CokrigeFactor, pred_locs, *,
                        interval: float = 0.95, gen: str = "xla",
                        mesh=None, row_axes=("data",),
                        key=None, n_draws: int = 1) -> CokrigePrediction:
    """Decode one batch: mean, variance, interval, optional draws.

    Pure function of the factor pytree — jit it (or use the pre-jitted
    pair from ``make_cokrige_serve_fns``).  ``key`` switches on
    conditional-simulation draws: (n_draws, B, p) samples from each
    location's conditional law N(mean, cond_cov), via the Cholesky of the
    jittered (p, p) conditional covariance.
    """
    mean, cond = _predict_core(factor, pred_locs, interval=interval,
                               gen=gen, mesh=mesh, row_axes=row_axes)
    var = jnp.clip(jnp.diagonal(cond, axis1=-2, axis2=-1), min=0.0)
    half = _z_crit(interval) * jnp.sqrt(var)
    draws = None
    if key is not None:
        p = mean.shape[-1]
        jitter = 1e-10 * jnp.trace(cond, axis1=-2, axis2=-1)[:, None, None]
        lc = jnp.linalg.cholesky(cond + jitter * jnp.eye(p, dtype=cond.dtype))
        eps = jax.random.normal(key, (n_draws,) + mean.shape, mean.dtype)
        draws = mean[None] + jnp.einsum("bpq,nbq->nbp", lc, eps)
    return CokrigePrediction(mean=mean, variance=var, lower=mean - half,
                             upper=mean + half, draws=draws)


@functools.lru_cache(maxsize=None)
def _serve_fns(cfg: CokrigeServeConfig, mesh):
    fit = jax.jit(functools.partial(fit_factor, cfg=cfg, mesh=mesh))

    @functools.partial(jax.jit, static_argnames=("n_draws",))
    def predict(factor, pred_locs, key=None, n_draws: int = 1):
        return predict_with_factor(factor, pred_locs, interval=cfg.interval,
                                   gen=cfg.gen, mesh=mesh,
                                   row_axes=cfg.row_axes, key=key,
                                   n_draws=n_draws)

    return fit, predict


def make_cokrige_serve_fns(cfg: CokrigeServeConfig, mesh=None):
    """Returns jitted ``(fit_factor(locs, z, params), predict_batch(factor,
    pred_locs, key=None, n_draws=1))`` for one deployment config.

    The pair is cached per (cfg, mesh): every request batch of the same B
    reuses one compiled executable, and the factor handle round-trips
    through ``predict_batch`` as a pytree without leaving the device.
    """
    return _serve_fns(cfg, mesh)


def _factor_ok(factor: CokrigeFactor) -> bool:
    """Host-side health check (None status = legacy untracked factor)."""
    return factor.status is None or bool(factor.status.ok)


def _validate_request(factor: CokrigeFactor, pred_locs):
    """Refuse malformed requests up front (host-side, before the jit)."""
    pl = np.asarray(pred_locs)
    if pl.ndim != 2 or pl.shape[-1] != factor.d_spatial:
        raise ServeError(
            "bad_shape",
            f"pred_locs must have shape (B, {factor.d_spatial}), "
            f"got {pl.shape}")
    if not np.issubdtype(pl.dtype, np.floating):
        raise ServeError(
            "bad_dtype",
            f"pred_locs must be a floating dtype, got {pl.dtype}")
    if not np.all(np.isfinite(pl)):
        bad = np.argwhere(~np.isfinite(pl))
        raise ServeError(
            "nonfinite_locs",
            f"{len(bad)} non-finite coordinate(s) in pred_locs "
            f"(first at row {int(bad[0][0])})",
            detail={"n_nonfinite": int(len(bad)),
                    "first_row": int(bad[0][0])})


def heal_factor(factor: CokrigeFactor, cfg: CokrigeServeConfig,
                mesh=None) -> CokrigeFactor:
    """Re-fit a broken factor with the nugget escalated along the ladder.

    Returns the first healthy re-fit (or ``factor`` unchanged if it was
    already healthy).  The re-fits reuse the cached compiled prefill —
    ``nugget`` enters as a traced operand, so every rung is a re-execution,
    not a re-compile.  Raises ``ServeError(code="broken_factor")`` when the
    ladder is exhausted or the factor carries no data to re-fit from.
    """
    if _factor_ok(factor):
        return factor
    status = factor.status.as_dict() if factor.status is not None else None
    if factor.z is None:
        raise ServeError(
            "broken_factor",
            "factor failed health check and carries no z to re-fit from",
            status=status)
    fit, _ = make_cokrige_serve_fns(cfg, mesh)
    jitter = cfg.degraded_initial_jitter
    tried = []
    cand = factor
    for _ in range(cfg.degraded_max_attempts):
        tried.append(jitter)
        cand = fit(factor.locs, factor.z, factor.params,
                   nugget=jnp.asarray(jitter, factor.alpha.dtype))
        if _factor_ok(cand):
            return cand
        jitter = min(jitter * cfg.degraded_factor, cfg.degraded_max_jitter)
    last = cand.status.as_dict() if cand.status is not None else None
    raise ServeError(
        "broken_factor",
        f"jitter ladder exhausted after {len(tried)} re-fit(s) "
        f"(jitters tried: {tried})", status=last,
        detail={"jitters_tried": tried})


# Request numbers of ``predict_batch``'s profiler spans.
_requests = itertools.count()


def predict_batch(factor: CokrigeFactor, pred_locs,
                  cfg: CokrigeServeConfig = CokrigeServeConfig(),
                  mesh=None, key=None, n_draws: int = 1) -> CokrigePrediction:
    """Convenience decode entry point (module-level, jit-cached via
    ``make_cokrige_serve_fns``).

    With ``cfg.validate`` (default) the request is checked up front —
    malformed/non-finite ``pred_locs`` or a factor whose ``FactorStatus``
    failed raise a structured ``ServeError`` instead of serving NaNs.
    ``cfg.degraded`` instead re-fits a broken factor via ``heal_factor``
    (the healed handle serves this request; callers wanting to keep it
    should call ``heal_factor`` themselves).

    Under ``jax.profiler`` each call shows as a host span
    ``repro.serve.predict_batch`` holding ``repro.serve.validate``,
    ``repro.serve.status`` (the ``FactorStatus`` read-back) and
    ``repro.serve.dispatch``, all with the call's number as ``req``."""
    req = next(_requests)
    with TraceAnnotation("repro.serve.predict_batch", req=req):
        if cfg.validate:
            with TraceAnnotation("repro.serve.validate", req=req):
                _validate_request(factor, pred_locs)
            with TraceAnnotation("repro.serve.status", req=req):
                ok = _factor_ok(factor)
            if not ok:
                if cfg.degraded:
                    factor = heal_factor(factor, cfg, mesh)
                else:
                    raise ServeError(
                        "broken_factor",
                        "factor failed its factorization health check; "
                        "re-fit with a larger nugget (heal_factor) or "
                        "enable degraded mode",
                        status=factor.status.as_dict())
        with TraceAnnotation("repro.serve.dispatch", req=req):
            _, predict = make_cokrige_serve_fns(cfg, mesh)
            return predict(factor, pred_locs, key=key, n_draws=n_draws)


# ---------------------------------------------------------------------------
# Dry-run / spmd-lint lowerables: the two serving phases as (fn, specs)
# ---------------------------------------------------------------------------


def cokrige_fit_lowerable(n: int, p: int, params, *, tile_size: int,
                          max_rank: int, tol: float, nugget: float = 0.0,
                          gen: str = "xla", mesh, dtype=jnp.float32,
                          row_axes=("data",)):
    """(fn, specs) for the prefill phase: (locs, z) -> factor arrays.

    Returns the raw (diag_l, u, v, ranks, alpha) arrays rather than the
    handle so the dry-run can chain them into the decode lowerable's
    input specs and shardings."""
    cfg = CokrigeServeConfig(tile_size=tile_size, max_rank=max_rank, tol=tol,
                             nugget=nugget, gen=gen,
                             row_axes=tuple(row_axes))

    def fn(locs, z):
        f = fit_factor(locs, z, params, cfg, mesh=mesh)
        return f.diag_l, f.u, f.v, f.ranks, f.alpha

    specs = (jax.ShapeDtypeStruct((n, 2), dtype),
             jax.ShapeDtypeStruct((n * p,), dtype))
    return fn, specs


def cokrige_predict_lowerable(n: int, p: int, params, *, tile_size: int,
                              max_rank: int, batch: int = 512,
                              gen: str = "xla", mesh, dtype=jnp.float32,
                              row_axes=("data",), interval: float = 0.95):
    """(fn, specs) for the decode phase: (factor arrays, pred_locs) ->
    (mean, variance, lower, upper) for a batch of ``batch`` points.

    The factor arrays arrive as inputs (the cached handle, NOT donated —
    reuse across batches is the whole point) with the same pair-major
    specs/shardings as dist_tlr_lowerable's block-cyclic form."""
    m = n * p
    nb = choose_tile_size(m, tile_size, multiple_of=p)
    T = m // nb
    kmax = min(max_rank, nb) if max_rank > 0 else max(8, nb // 4)
    layout = pair_layout(T, pair_shards(mesh, row_axes))

    def fn(diag_l, u, v, ranks, alpha, locs, pred_locs):
        factor = CokrigeFactor(diag_l=diag_l, u=u, v=v, ranks=ranks,
                               alpha=alpha, locs=locs, params=params,
                               kind="tlr", n_shards=layout.n_shards)
        out = predict_with_factor(factor, pred_locs, interval=interval,
                                  gen=gen, mesh=mesh, row_axes=row_axes)
        return out.mean, out.variance, out.lower, out.upper

    specs = (jax.ShapeDtypeStruct((T, nb, nb), dtype),
             jax.ShapeDtypeStruct((layout.length, nb, kmax), dtype),
             jax.ShapeDtypeStruct((layout.length, nb, kmax), dtype),
             jax.ShapeDtypeStruct((layout.length,), jnp.int32),
             jax.ShapeDtypeStruct((m,), dtype),
             jax.ShapeDtypeStruct((n, 2), dtype),
             jax.ShapeDtypeStruct((batch, 2), dtype))
    return fn, specs
