"""MLE driver: parameter transforms + objective + fit loop (exact/TLR/DST).

Mirrors the paper's estimation pipeline: a gradient-free optimizer (our
Nelder–Mead standing in for NLOPT/BOBYQA) over transformed parameters, with
the log-likelihood backend selectable between:

  * "exact" — dense Cholesky (Eq. 1),
  * "tlr"   — Tile Low-Rank Cholesky at accuracy 1e-5/1e-7/1e-9 (§5.3),
  * "dst"   — Diagonal Super Tile baseline (§4.4).

Transforms: log for sigma^2 / a / nu, atanh for beta_ij.  The profile mode
(§5.2) drops the p marginal variances from the search space and recovers them
in closed form after convergence.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .covariance import MaternParams, pairwise_distances
from .likelihood import exact_loglik, profile_variances
from .optimize import multistart_nelder_mead, nelder_mead
from .recovery import find_duplicate_locations, jitter_escalate


@dataclasses.dataclass(frozen=True)
class MLEConfig:
    p: int = 2
    representation: str = "I"
    nugget: float = 1e-8
    profile: bool = True
    backend: str = "exact"          # exact | tlr | dst
    tlr_tol: float = 1e-7           # TLR5/7/9 <-> 1e-5/1e-7/1e-9
    tlr_max_rank: int = 64
    # Generator-direct TLR (tlr_compress_tiles): never builds the dense Sigma.
    # Requires locs (fit/make_objective thread them through automatically).
    tlr_from_tiles: bool = False
    # Route the TLR backend through the distributed streaming pipeline
    # (core/dist_tlr.py): dist_compress_tiles -> fori_loop TLR Cholesky.
    # Generator-direct like tlr_from_tiles, but the whole evaluation is one
    # SPMD program; on a single device it runs the same trace unsharded.
    dist_tlr_from_tiles: bool = False
    # Block-cyclic pair placement for the distributed factorization
    # (distribution/block_cyclic.py): the strict-lower pair batch (~2.4x
    # less recompression work than the masked T^2 grid) stays load-balanced
    # and pair-native end-to-end.  Only read by the dist_tlr path.
    block_cyclic: bool = False
    super_panels: int = 1           # >1: two-level dist factorization (§Perf)
    # Shard the compression-phase truncation SVDs (and, pair-native, the GEN
    # panel itself) over the pair axis via shard_map — each device generates
    # and compresses only the block-cyclic slots it owns
    # (distribution/compress_svd.py).  Only read by the dist_tlr path; on a
    # single device (mesh=None) the replicated batch runs either way.
    shard_svd: bool = True
    # Mixed-precision storage policy for the TLR backends
    # (core/precision.py): None keeps one uniform dtype; "mixed_f32" /
    # "mixed_bf16" store off-diagonal U/V (and run their truncation SVDs)
    # at the narrow dtype while diagonal tiles, POTRF/TRSM and the logdet
    # stay wide.  Certify a policy with
    # ``python -m repro.analysis --target ... --policy <name>``.
    dtype_policy: str | None = None
    gen: str = "pallas"             # tile generator: pallas half-integer fast
                                    # path (per-pair XLA fallback) | xla
    tile_size: int = 0              # 0 -> auto (~sqrt(pn))
    dst_keep_fraction: float = 0.7  # DST 70/30
    max_iters: int = 150
    nu_max: float = 4.0
    # Morton-sort locations before tiling (§5.3: without it the off-diagonal
    # tiles are not low-rank and the truncated factor can go indefinite).
    # The exact likelihood is permutation-invariant, so this is always safe.
    morton: bool = True
    # Jitter-escalation retry (core/recovery.py): when a factorization
    # breaks (FactorStatus.ok False or non-finite loglik), re-evaluate with
    # the nugget bumped along an additive ladder initial -> *factor capped
    # at max_jitter.  Runs as a do-while lax.while_loop inside the jitted
    # objective, so retries re-execute without re-tracing and a clean
    # evaluation costs one ordinary pass.  Off by default: the while_loop
    # wrapper ~4x-es XLA compile time of the objective; without it a broken
    # factorization still degrades safely (finite penalty, never NaN).
    recovery: bool = False
    recovery_initial_jitter: float = 1e-8
    recovery_factor: float = 10.0
    recovery_max_jitter: float = 1e-2
    recovery_max_attempts: int = 6
    # Pre-flight duplicate/near-duplicate location check in ``fit`` (the
    # classic singular-Sigma cause).  Set False to skip.
    check_duplicates: bool = True


def n_free_params(p: int, profile: bool) -> int:
    base = 1 + p + p * (p - 1) // 2   # a, nu_i, beta_ij
    return base if profile else base + p


def pack_params(params: MaternParams, profile: bool) -> jnp.ndarray:
    p = params.p
    iu, ju = np.triu_indices(p, k=1)
    parts = []
    if not profile:
        parts.append(jnp.log(params.sigma2))
    parts.append(jnp.log(params.a)[None])
    parts.append(jnp.log(params.nu))
    if p > 1:
        parts.append(jnp.arctanh(params.beta[iu, ju]))
    return jnp.concatenate(parts)


def unpack_params(x, p: int, profile: bool, nu_max: float = 4.0) -> MaternParams:
    iu, ju = np.triu_indices(p, k=1)
    i = 0
    if profile:
        sigma2 = jnp.ones((p,), x.dtype)
    else:
        sigma2 = jnp.exp(x[i:i + p])
        i += p
    a = jnp.exp(x[i])
    i += 1
    # Clipped-log nu keeps K_nu evaluations stable at simplex extremes.
    nu = jnp.clip(jnp.exp(x[i:i + p]), 1e-2, nu_max)
    i += p
    beta = jnp.eye(p, dtype=x.dtype)
    if p > 1:
        vals = jnp.tanh(x[i:])
        beta = beta.at[iu, ju].set(vals).at[ju, iu].set(vals)
    return MaternParams(sigma2=sigma2, a=a, nu=nu, beta=beta)


def initial_guess(p: int, profile: bool, a0=0.1, nu0=1.0, dtype=jnp.float64):
    params = MaternParams(sigma2=jnp.ones((p,), dtype),
                          a=jnp.asarray(a0, dtype),
                          nu=jnp.full((p,), nu0, dtype),
                          beta=jnp.eye(p, dtype=dtype) * 1.0
                          + (jnp.ones((p, p), dtype)
                             - jnp.eye(p, dtype=dtype)) * 0.1)
    return pack_params(params, profile)


class FitResult(NamedTuple):
    params: MaternParams
    loglik: jax.Array
    n_iters: jax.Array
    n_evals: jax.Array
    converged: jax.Array
    clamped_evals: jax.Array | None = None    # evals clamped to the penalty
    recovery_retries: jax.Array | None = None  # total jitter-ladder retries


class ObjectiveAux(NamedTuple):
    """Per-evaluation fault counters threaded out of the objective."""
    clamped: jax.Array     # int32: 1 if this eval returned the penalty value
    retries: jax.Array     # int32: jitter-ladder retries this eval performed
    breakdowns: jax.Array  # int32: 1 if the clean first attempt broke
    max_rank: jax.Array    # int32: FactorStatus.max_rank (0 for exact)

    def merge(self, other: "ObjectiveAux") -> "ObjectiveAux":
        """Running total over evaluations (``nelder_mead``'s
        ``aux_combine``): counts add, the rank is the largest seen."""
        return ObjectiveAux(*(op(a, b) for op, a, b in
                              zip(_AUX_OPS, self, other)))


_AUX_OPS = (jnp.add, jnp.add, jnp.add, jnp.maximum)   # per ObjectiveAux field


def _backend_loglik(dists, z, params: MaternParams, cfg: MLEConfig, locs=None,
                    extra_nugget=None):
    """Full LoglikResult from the configured backend.

    ``extra_nugget`` (a traced scalar) is *added* to ``cfg.nugget`` — the
    jitter-escalation ladder uses it so retries re-execute the same trace.
    """
    nugget = cfg.nugget if extra_nugget is None else cfg.nugget + extra_nugget
    if cfg.backend == "exact":
        return exact_loglik(None, z, params, representation=cfg.representation,
                            nugget=nugget, dists=dists)
    if cfg.backend == "tlr":
        if cfg.dist_tlr_from_tiles:
            if locs is None:
                raise ValueError("dist_tlr_from_tiles requires locs "
                                 "(Morton-ordered)")
            from .dist_tlr import dist_tlr_loglik
            return dist_tlr_loglik(None, z, locs=locs, params=params,
                                   from_tiles=True, tile_size=cfg.tile_size,
                                   max_rank=cfg.tlr_max_rank,
                                   nugget=nugget, gen=cfg.gen,
                                   tol=cfg.tlr_tol,
                                   super_panels=cfg.super_panels,
                                   block_cyclic=cfg.block_cyclic,
                                   shard_svd=cfg.shard_svd,
                                   dtype_policy=cfg.dtype_policy)
        from .tlr import tlr_loglik
        return tlr_loglik(dists, z, params, tol=cfg.tlr_tol,
                          max_rank=cfg.tlr_max_rank, tile_size=cfg.tile_size,
                          nugget=nugget, locs=locs,
                          from_tiles=cfg.tlr_from_tiles, gen=cfg.gen,
                          dtype_policy=cfg.dtype_policy)
    if cfg.backend == "dst":
        from .dst import dst_loglik
        return dst_loglik(dists, z, params, keep_fraction=cfg.dst_keep_fraction,
                          tile_size=cfg.tile_size, nugget=nugget,
                          representation=cfg.representation)
    raise ValueError(f"unknown backend {cfg.backend!r}")


def apply_morton(locs, z, p: int, representation: str = "I"):
    """Morton-sort locations and permute z consistently (Rep I interleave)."""
    from .covariance import morton_order
    locs = np.asarray(locs)
    perm = morton_order(locs)
    zn = np.asarray(z)
    n = locs.shape[0]
    if representation.upper() == "I":
        zn = zn.reshape(n, p)[perm].reshape(-1)
    else:
        zn = zn.reshape(p, n)[:, perm].reshape(-1)
    return locs[perm], jnp.asarray(zn)


def make_objective(locs, z, cfg: MLEConfig, dists=None, with_aux=False):
    """Negative log-likelihood over transformed parameters (jit-compiled).

    Callers must pass Morton-consistent (locs, z) for tiled backends;
    ``fit`` handles that via apply_morton.  The generator-direct TLR
    backends (tlr_from_tiles / dist_tlr_from_tiles, non-profile) never read
    the dense (n, n) distance matrix, so it is not built for them — at
    production n it would be the largest allocation of the whole fit.

    A broken or non-finite evaluation never leaks NaN: with
    ``cfg.recovery`` the jitter-escalation ladder retries in-graph, and
    whatever survives is clamped to a large finite dtype-aware penalty
    (``sqrt(finfo.max)`` — the old hardcoded ``1e12`` was *below* real
    |loglik| values at production n in f64, silently inverting the simplex
    ordering).  With ``with_aux=True`` the objective returns
    ``(value, ObjectiveAux)`` for fault accounting (clamp/retry counters)
    and the factor's largest tile rank.
    """
    generator_direct = (cfg.backend == "tlr" and not cfg.profile and
                        (cfg.tlr_from_tiles or cfg.dist_tlr_from_tiles))
    if dists is None and not generator_direct:
        dists = pairwise_distances(locs)
    z = jnp.asarray(z)
    locs_j = None if locs is None else jnp.asarray(locs)
    dtype = z.dtype

    def eval_at(x, jitter):
        params = unpack_params(x, cfg.p, cfg.profile, cfg.nu_max)
        if cfg.profile:
            sigma2 = profile_variances(dists, z, params.a, params.nu, cfg.p,
                                       nugget=cfg.nugget + jitter,
                                       representation=cfg.representation)
            params = params._replace(sigma2=sigma2)
        res = _backend_loglik(dists, z, params, cfg, locs=locs_j,
                              extra_nugget=jitter)
        ll = res.loglik
        ok = jnp.isfinite(ll)
        max_rank = jnp.zeros((), jnp.int32)
        if res.status is not None:
            ok = ok & res.status.ok
            max_rank = res.status.max_rank
        return ll, ok, max_rank

    def neg_ll(x):
        if cfg.recovery:
            rec = jitter_escalate(lambda j: eval_at(x, j),
                                  initial=cfg.recovery_initial_jitter,
                                  factor=cfg.recovery_factor,
                                  max_jitter=cfg.recovery_max_jitter,
                                  max_attempts=cfg.recovery_max_attempts,
                                  dtype=dtype,
                                  aux_init=jnp.zeros((), jnp.int32))
            ll, ok, max_rank = rec.loglik, rec.ok, rec.aux
            retries = rec.attempts - 1
        else:
            ll, ok, max_rank = eval_at(x, jnp.zeros((), dtype))
            retries = jnp.zeros((), jnp.int32)
        good = ok & jnp.isfinite(ll)
        penalty = jnp.asarray(jnp.finfo(dtype).max ** 0.5, dtype)
        val = jnp.where(good, -ll, penalty)
        if not with_aux:
            return val
        aux = ObjectiveAux(
            clamped=(~good).astype(jnp.int32),
            retries=jnp.asarray(retries, jnp.int32),
            breakdowns=((retries > 0) | ~good).astype(jnp.int32),
            max_rank=max_rank)
        return val, aux

    return jax.jit(neg_ll), dists


def check_locations(locs, tol=None):
    """Raise ValueError naming duplicate / near-duplicate location rows.

    Host-side pre-flight guard for the classic singular-Sigma cause; no-op
    when ``locs`` is a tracer (jit callers validate outside the trace).
    """
    if locs is None or isinstance(locs, jax.core.Tracer):
        return
    pairs = find_duplicate_locations(np.asarray(locs), tol=tol)
    if pairs:
        shown = ", ".join(f"({i}, {j})" for i, j in pairs[:8])
        more = "" if len(pairs) <= 8 else f" (+{len(pairs) - 8} more)"
        raise ValueError(
            f"{len(pairs)} duplicate/near-duplicate location pair(s): "
            f"{shown}{more} — Sigma is singular at these rows regardless of "
            "parameters.  De-duplicate the locations, or pass "
            "MLEConfig(check_duplicates=False) to rely on jitter recovery.")


def fit(locs, z, cfg: MLEConfig, x0=None, dists=None, n_starts: int = 1,
        seed: int = 0, checkpoint_dir=None,
        checkpoint_every: int = 0) -> FitResult:
    """Run the full estimation (the paper's 'MLE operation').

    ``n_starts > 1`` runs a multistart (perturbed initial guesses, keep the
    best); ``checkpoint_dir`` makes the multistart crash-tolerant — the
    per-start simplex state is checkpointed every ``checkpoint_every``
    iterations (0 = once per completed start) and a re-run resumes instead
    of restarting.
    """
    if cfg.check_duplicates:
        check_locations(locs)
    if cfg.morton and dists is None and locs is not None:
        locs, z = apply_morton(locs, z, cfg.p, cfg.representation)
    neg_ll, dists = make_objective(locs, z, cfg, dists=dists, with_aux=True)
    if x0 is None:
        x0 = initial_guess(cfg.p, cfg.profile, dtype=jnp.asarray(z).dtype)
    if n_starts > 1:
        rng = np.random.default_rng(seed)
        x0s = [jnp.asarray(x0)] + [
            jnp.asarray(x0) + jnp.asarray(
                rng.normal(scale=0.25, size=np.asarray(x0).shape),
                jnp.asarray(x0).dtype)
            for _ in range(n_starts - 1)]
        res = multistart_nelder_mead(neg_ll, x0s, max_iters=cfg.max_iters,
                                     has_aux=True,
                                     aux_combine=ObjectiveAux.merge,
                                     checkpoint_dir=checkpoint_dir,
                                     checkpoint_every=checkpoint_every)
    elif checkpoint_dir is not None:
        res = multistart_nelder_mead(neg_ll, [x0], max_iters=cfg.max_iters,
                                     has_aux=True,
                                     aux_combine=ObjectiveAux.merge,
                                     checkpoint_dir=checkpoint_dir,
                                     checkpoint_every=checkpoint_every)
    else:
        res = nelder_mead(neg_ll, x0, max_iters=cfg.max_iters, has_aux=True,
                          aux_combine=ObjectiveAux.merge)
    params = unpack_params(res.x, cfg.p, cfg.profile, cfg.nu_max)
    if cfg.profile:
        sigma2 = profile_variances(dists, jnp.asarray(z), params.a, params.nu,
                                   cfg.p, nugget=cfg.nugget,
                                   representation=cfg.representation)
        params = params._replace(sigma2=sigma2)
    clamped = retries = None
    if res.aux is not None:
        clamped = res.aux.clamped
        retries = res.aux.retries
    return FitResult(params, -res.value, res.n_iters, res.n_evals,
                     res.converged, clamped, retries)
