"""Numerical fault tolerance: breakdown status + jitter-escalation retry.

The Gaussian log-likelihood pipeline lives or dies on the Cholesky
factorization.  Near-duplicate locations, tight Matern ranges, or a zero
nugget make Sigma near-singular; a non-PSD diagonal tile then turns the
whole loglik into NaN, which silently poisons the Nelder-Mead simplex.
This module holds the pieces that stop that contagion:

``FactorStatus``
    A tiny pytree threaded *in-graph* through ``tlr_panel_body`` /
    ``pair_panel_loop`` alongside the factor (no host sync on the hot
    path).  It records the smallest POTRF diagonal pivot seen, a count of
    POTRF steps whose pivot was non-positive or non-finite, a count of
    non-finite singular values observed by the GEMM-phase recompress (on
    the pair path: over each step's live-pair window, not every slot), and
    the largest off-diagonal tile rank the factor held at any panel step
    (a counter, not part of ``ok``).  ``status.ok`` is a traced scalar;
    ``tlr_loglik`` / ``dist_tlr_loglik`` use it to emit a well-defined
    finite sentinel instead of NaN.

``jitter_escalate``
    A do-while ``lax.while_loop`` retry ladder: evaluate an objective at
    jitter 0, and on breakdown re-evaluate with an additive nugget bump
    escalating ``initial * factor**k`` up to ``max_jitter``.  The
    evaluation closure is traced exactly once, so retries never re-trace
    and a clean first attempt costs one ordinary evaluation.

``find_duplicate_locations``
    Host-side pre-flight check for the classic singular-Sigma cause.

Deliberately free of imports from the rest of ``repro`` so every layer
(core, distribution, serving) can depend on it without cycles.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import numpy as np

import jax
import jax.numpy as jnp


def _big(dtype) -> jax.Array:
    return jnp.asarray(jnp.finfo(dtype).max, dtype)


def sentinel_loglik(dtype) -> jax.Array:
    """Large-but-finite 'the factorization broke' log-likelihood.

    ``-sqrt(finfo.max)`` (~ -1.3e154 in f64) is orders of magnitude below
    any real loglik yet survives negation, subtraction, and ordering
    without overflowing — unlike NaN or ``-inf``, both of which poison
    simplex ordering and convergence tests downstream.
    """
    return -jnp.sqrt(_big(dtype))


class FactorStatus(NamedTuple):
    """In-graph health of a (distributed) TLR Cholesky factorization.

    All fields are traced scalars; the pytree rides the panel-loop scan
    carry.  NaN pivots are sanitized to ``-finfo.max`` on entry so every
    field stays finite even when the factor itself is garbage — ``ok``
    never depends on NaN comparison semantics.
    """

    min_pivot: jax.Array        # smallest POTRF diagonal seen (NaN -> -max)
    nonfinite_count: jax.Array  # int32: non-finite recompress singular values
    breakdown_count: jax.Array  # int32: POTRF steps with a bad pivot
    # int32: largest off-diagonal rank: the compression's, folded with each
    # recompression's kept ranks.  Equal to kmax when truncation hit the cap.
    max_rank: jax.Array

    @property
    def ok(self) -> jax.Array:
        return ((self.min_pivot > 0)
                & (self.breakdown_count == 0)
                & (self.nonfinite_count == 0))

    def update_potrf(self, lkk: jax.Array) -> "FactorStatus":
        """Fold one POTRF result ``lkk = cholesky(dkk)``, shape (..., nb, nb)."""
        piv = jnp.diagonal(lkk, axis1=-2, axis2=-1)
        piv = jnp.where(jnp.isfinite(piv), piv, -_big(piv.dtype))
        worst = jnp.min(piv).astype(self.min_pivot.dtype)
        bad = (~(worst > 0)).astype(jnp.int32)
        return self._replace(min_pivot=jnp.minimum(self.min_pivot, worst),
                             breakdown_count=self.breakdown_count + bad)

    def add_nonfinite(self, count: jax.Array) -> "FactorStatus":
        """Fold a recompress non-finite singular-value count."""
        return self._replace(
            nonfinite_count=self.nonfinite_count
            + jnp.asarray(count, jnp.int32))

    def fold_ranks(self, ranks: jax.Array) -> "FactorStatus":
        """Fold the tile ranks the factor holds now (any shape, int)."""
        top = jnp.max(ranks, initial=0).astype(jnp.int32)
        return self._replace(max_rank=jnp.maximum(self.max_rank, top))

    def merge(self, other: "FactorStatus") -> "FactorStatus":
        """Combine two independent status accumulations (super-tile slices)."""
        return FactorStatus(
            jnp.minimum(self.min_pivot, other.min_pivot),
            self.nonfinite_count + other.nonfinite_count,
            self.breakdown_count + other.breakdown_count,
            jnp.maximum(self.max_rank, other.max_rank))

    def as_dict(self) -> dict:
        """Host-side summary (concrete values only — not for traced use)."""
        return {"ok": bool(self.ok),
                "min_pivot": float(self.min_pivot),
                "nonfinite_count": int(self.nonfinite_count),
                "breakdown_count": int(self.breakdown_count),
                "max_rank": int(self.max_rank)}


def init_status(dtype=jnp.float64, ranks=None) -> FactorStatus:
    """Identity element for ``FactorStatus.merge``; with ``ranks`` (the
    compressed tiles' ranks) the status starts from their largest."""
    zero = jnp.zeros((), jnp.int32)
    status = FactorStatus(_big(dtype), zero, zero, zero)
    return status if ranks is None else status.fold_ranks(ranks)


class RecoveryResult(NamedTuple):
    """Outcome of a ``jitter_escalate`` ladder."""

    loglik: jax.Array   # last evaluation (sentinel if every rung broke)
    ok: jax.Array       # bool: did the accepted attempt factorize cleanly
    attempts: jax.Array  # int32 evaluations performed (1 == clean first try)
    jitter: jax.Array   # additive jitter used by the accepted attempt
    aux: object = None  # the accepted attempt's aux pytree (with aux_init)


def jitter_escalate(eval_fn: Callable[[jax.Array], tuple],
                    *,
                    initial: float = 1e-8,
                    factor: float = 10.0,
                    max_jitter: float = 1e-2,
                    max_attempts: int = 6,
                    dtype=jnp.float64,
                    aux_init=None) -> RecoveryResult:
    """Evaluate ``eval_fn(jitter) -> (value, ok)`` with an escalating ladder.

    With ``aux_init`` (a pytree shaped like ``eval_fn``'s extra output)
    ``eval_fn`` returns ``(value, ok, aux)`` instead, and the accepted
    attempt's ``aux`` is returned on ``RecoveryResult.aux``.

    The first attempt runs at jitter 0 (the clean path); each retry bumps
    the additive jitter ``0 -> initial -> initial*factor -> ...`` capped at
    ``max_jitter``, stopping as soon as ``ok`` or after ``max_attempts``
    evaluations.  Implemented as a do-while ``lax.while_loop`` so the
    evaluation closure is traced exactly once — retries cost re-execution,
    never re-tracing.  Not reverse-differentiable (while_loop); intended
    for the derivative-free Nelder-Mead objective.
    """
    dtype = jnp.dtype(dtype)
    zero = jnp.zeros((), dtype)

    def body(state):
        attempt, jitter = state[:2]
        out = eval_fn(jitter)
        val, ok = out[:2]
        aux = out[2] if aux_init is not None else None
        val = jnp.asarray(val, dtype)
        val = jnp.where(jnp.isfinite(val), val, sentinel_loglik(dtype))
        nxt = jnp.where(
            jitter == 0, jnp.asarray(initial, dtype),
            jnp.minimum(jitter * factor, jnp.asarray(max_jitter, dtype)))
        return (attempt + 1, nxt, jitter, val, jnp.asarray(ok, bool), aux)

    def cond(state):
        attempt, _, _, _, ok, _ = state
        return (~ok) & (attempt < max_attempts)

    init = (jnp.zeros((), jnp.int32), zero, zero,
            sentinel_loglik(dtype), jnp.zeros((), bool), aux_init)
    attempts, _, used, val, ok, aux = jax.lax.while_loop(cond, body, init)
    return RecoveryResult(val, ok, attempts, used, aux)


def find_duplicate_locations(locs, tol: float | None = None) -> list:
    """Find duplicate / near-duplicate location rows (host-side, numpy).

    Returns a sorted list of ``(i, j)`` index pairs whose rows coincide to
    within ``tol`` (default: 1e-9 x the bounding-box diagonal).  Detection
    is lexsort-adjacency: exact duplicates are always caught; near
    duplicates are caught when adjacent in lexicographic order, which is
    the overwhelmingly common case for the sensor-collision failure mode
    this guards against.
    """
    locs = np.asarray(locs)
    if locs.ndim != 2 or locs.shape[0] < 2:
        return []
    if tol is None:
        span = locs.max(axis=0) - locs.min(axis=0)
        # spmdlint: ignore[A3] host-side pre-flight on concrete numpy locs
        tol = 1e-9 * (float(np.linalg.norm(span)) + 1.0)
    order = np.lexsort(locs.T[::-1])
    diffs = np.max(np.abs(np.diff(locs[order], axis=0)), axis=1)
    hits = np.nonzero(diffs <= tol)[0]
    pairs = {tuple(sorted((int(order[i]), int(order[i + 1])))) for i in hits}
    return sorted(pairs)
