"""Tile Low-Rank (TLR) covariance computations (§5.3 of the paper).

The matrix is split into T x T tiles of size nb.  Diagonal tiles stay dense;
each strict-lower off-diagonal tile A[i,j] is stored as U V^T with rank k(i,j)
determined by the accuracy threshold (TLR5/TLR7/TLR9 <-> 1e-5/1e-7/1e-9).

Two compression entry points:

  * tlr_compress_tiles — the production pipeline: tiles are generated straight
    from the Matérn *generator* over Morton-ordered locations (the GEN phase
    of Figs. 10-11, via kernels.matern_tile for half-integer nu or the XLA
    K_nu path for general nu) and SVD-truncated panel by panel.  The dense
    (pn x pn) Sigma is never materialized — panels stream through the
    compression loop one at a time, so the peak transient is one strict-lower
    column panel, O(m*nb), which is what lets TLR run at sizes where dense
    Sigma no longer fits (HiCMA/STARS-H's generator-direct design).
  * tlr_compress — the validation path: compress an already-dense matrix.

TPU adaptation (DESIGN.md §2): variable per-tile ranks become a *fixed* kmax
with zero-padded columns and an integer rank array — static shapes feed the
MXU; reported memory uses actual ranks, compute uses the padded rank.

Operations implemented directly on the compressed representation:

  * tlr_compress_tiles / tlr_compress / tlr_to_dense
  * tlr_cholesky                     (right-looking scan form: one traced
                                      panel body under lax.fori_loop, shared
                                      with the distributed factorization in
                                      core/dist_tlr.py)
  * tlr_solve_lower                  (forward substitution with UV tiles)
  * tlr_loglik                       (Eq. 1 through the TLR factor;
                                      from_tiles=True is generator-direct)
  * memory_footprint                 (Fig. 6 model)
  * rank_distribution                (Fig. 5 report)

Complexity: the dominant kernel is the TLR-MM chain U_ik (V_ik^T V_jk) U_jk^T
(36 nb k^2 flops, paper §5.3); total O(n^2 k) at nb = O(sqrt(n)) versus the
exact path's O(n^3).
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding

from ..distribution.block_cyclic import window_schedule
from ..distribution.compress_svd import svd_truncate_batch
from ..distribution.pair_qr import window_recompress
from .linalg import cholesky, qr, right_svd, solve_lower
from .covariance import MaternParams, build_sigma, build_sigma_panel
from .likelihood import LoglikResult
from .precision import resolve_policy
from .recovery import FactorStatus, init_status, sentinel_loglik


class TLRMatrix(NamedTuple):
    """Symmetric positive-definite matrix in TLR form (lower storage).

    Fixed-kmax convention (DESIGN.md §2): ``u``/``v`` always carry kmax
    columns; columns at index >= ranks[i, j] are zero-padded.  All compute
    (Cholesky, solves, matvec) runs on the padded layout and is *independent*
    of ``ranks`` — a tile whose rank reads 0 still participates with its
    (all-zero) padded factors, so rank-0 entries outside the strict lower
    triangle are structural, not "empty tiles".  ``ranks`` is reporting
    metadata: memory_footprint / rank_distribution use it for actual-rank
    accounting (Figs. 5-6).
    """

    diag: jax.Array    # (T, nb, nb) dense diagonal tiles
    u: jax.Array       # (T, T, nb, kmax); [i, j] valid for i > j
    v: jax.Array       # (T, T, nb, kmax)
    ranks: jax.Array   # (T, T) int32 actual ranks (0 outside strict lower)

    @property
    def n_tiles(self) -> int:
        return self.diag.shape[0]

    @property
    def tile_size(self) -> int:
        return self.diag.shape[1]

    @property
    def max_rank(self) -> int:
        return self.u.shape[-1]

    @property
    def shape(self):
        m = self.n_tiles * self.tile_size
        return (m, m)


def choose_tile_size(m: int, target: int = 0, multiple_of: int = 1) -> int:
    """nb = O(sqrt(m)) per the paper's complexity trade-off, rounded to a
    divisor of m.

    ``multiple_of`` additionally constrains nb to a multiple (the tiles path
    passes p so every Representation-I tile covers whole locations).  Runs in
    O(sqrt(m)): divisors are enumerated as (i, m//i) pairs, and an exact
    target hit returns immediately without any scan.
    """
    if multiple_of > 1 and m % multiple_of:
        raise ValueError(f"m={m} not divisible by multiple_of={multiple_of}")
    if target <= 0:
        target = max(32, int(math.sqrt(m)) // 32 * 32 or 32)
    if 0 < target <= m and m % target == 0 and target % multiple_of == 0:
        return target
    divisors = []
    i = 1
    while i * i <= m:
        if m % i == 0:
            divisors.append(i)
            divisors.append(m // i)
        i += 1
    best, best_gap = None, None
    for nb in sorted(divisors):   # ascending: ties resolve to the smaller nb
        if nb % multiple_of:
            continue
        gap = abs(nb - target)
        if best is None or gap < best_gap:
            best, best_gap = nb, gap
    if best is None:
        # Returning None here used to crash far downstream with an opaque
        # "unsupported operand type(s) for //: 'int' and 'NoneType'".
        raise ValueError(
            f"choose_tile_size: no divisor of m={m} is a multiple of "
            f"multiple_of={multiple_of} (target={target}); pass a tile size "
            "that divides m, or fix m/multiple_of")
    return best


def truncate_core(core, tol, kmax: int, scale, left=None, right=None):
    """Rank <= kmax truncation of ``left @ core @ right^T`` at ``tol *
    scale``, in the fixed-kmax layout.

    ``core`` is (..., r, r); ``left``/``right`` are orthonormal (..., nb, r)
    bases (None: the identity, so ``core`` is the tile itself).  Returns
    (U, V, rank, s): U = left @ core @ V_k carries the singular values, V =
    right @ V_k, both zero beyond ``rank`` and padded to kmax columns; ``s``
    is the core's spectrum (breakdown accounting reads it).  ``scale`` may
    be traced; the threshold is taken in s's dtype, so a wide traced scale
    does not promote a narrow spectrum (convert churn under mixed policies).
    """
    s, v = _right_svd(core)
    k = min(s.shape[-1], kmax)
    keep = (s[..., :k] > jnp.asarray(tol * scale, dtype=s.dtype))[..., None, :]
    vk = v[..., :k]
    uu = core @ vk
    if left is not None:
        uu = left @ uu
    if right is not None:
        vk = right @ vk
    uu = jnp.where(keep, uu, 0.0)
    vv = jnp.where(keep, vk, 0.0)
    if k < kmax:
        pad = [(0, 0)] * (uu.ndim - 1) + [(0, kmax - k)]
        uu, vv = jnp.pad(uu, pad), jnp.pad(vv, pad)
    return uu, vv, jnp.sum(keep, axis=(-2, -1)).astype(jnp.int32), s


@jax.named_scope("repro.compress")
def tlr_compress(sigma, tile_size: int = 0, tol: float = 1e-7,
                 max_rank: int = 0, scale=None,
                 multiple_of: int = 1, dtype_policy=None) -> TLRMatrix:
    """Compress a dense SPD matrix to TLR (validation path).

    The production path compresses tiles straight from the generator without
    materializing sigma (see tlr_compress_tiles / kernels.matern_tile).
    ``scale`` may be a traced scalar (jit-safe); accuracy is absolute w.r.t.
    the matrix's diagonal scale, matching HiCMA's fixed-accuracy mode.
    ``multiple_of`` constrains the auto tile size the same way the tiles
    path does (pass p so both paths land on the same tile grid).
    ``dtype_policy`` stores the off-diagonal U/V factors (and runs their
    truncation SVD) in the policy's narrow dtype; diagonal tiles keep the
    generated (wide) dtype — see core.precision.
    """
    sigma = jnp.asarray(sigma)
    m = sigma.shape[0]
    nb = choose_tile_size(m, tile_size, multiple_of=multiple_of)
    T = m // nb
    if max_rank <= 0:
        max_rank = max(8, nb // 4)
    kmax = min(max_rank, nb)
    if scale is None:
        scale = jnp.max(jnp.abs(jnp.diagonal(sigma)))

    tiles = sigma.reshape(T, nb, T, nb).transpose(0, 2, 1, 3)  # (T,T,nb,nb)
    diag = jnp.stack([tiles[t, t] for t in range(T)])

    policy = resolve_policy(dtype_policy)
    uv_dtype = sigma.dtype if policy is None else policy.narrow_dtype
    u = jnp.zeros((T, T, nb, kmax), uv_dtype)
    v = jnp.zeros((T, T, nb, kmax), uv_dtype)
    ranks = jnp.zeros((T, T), jnp.int32)
    il, jl = np.tril_indices(T, k=-1)
    if len(il):
        low = tiles[il, jl].astype(uv_dtype)                 # (L, nb, nb)
        U, V, R = svd_truncate_batch(low, tol, kmax, scale)
        u = u.at[il, jl].set(U)
        v = v.at[il, jl].set(V)
        ranks = ranks.at[il, jl].set(R)
    return TLRMatrix(diag=diag, u=u, v=v, ranks=ranks)


def apply_nugget(diag_tiles, nugget, dtype):
    """Nugget on (..., nb, nb) diagonal tiles — `is not None`, not
    truthiness: a traced nugget (the MLE estimating it under jit) raises
    TracerBoolConversionError in a bool context.  Placement matches
    ``build_sigma``: diagonal tiles only.  Shared by the single-device
    (generate_tiles) and distributed (dist_compress_tiles) paths."""
    if nugget is None:
        return diag_tiles
    nb = diag_tiles.shape[-1]
    return diag_tiles + jnp.asarray(nugget, dtype) * jnp.eye(nb, dtype=dtype)


def generate_tiles(locs, params: MaternParams, tile_size: int = 0,
                   nugget: float = 0.0, gen: str = "pallas",
                   d_spatial: int = 2):
    """GEN phase (the paper's GEN_TIME, Figs. 10-11): produce diagonal tiles
    and strict-lower column panels straight from the Matérn generator.

    Returns ``(diag, lower, nb, T)`` where ``diag`` is (T, nb, nb) with the
    nugget already applied and ``lower`` is a *generator* yielding the
    (T-1-j, nb, nb) stack of strict-lower tiles for each column j in turn —
    streaming, so consumers that process one panel then drop it (the
    compression loop) keep at most one panel live.  Locations must be
    Morton-ordered by the caller; Representation-I interleaving happens
    inside each panel, so the tile values equal the corresponding slices of
    ``build_sigma``.  The dense (pn x pn) Sigma is never formed — the
    largest transient is the first column panel, (m - nb) x nb.
    """
    locs = jnp.asarray(locs)
    n = locs.shape[0]
    p = params.p
    m = n * p
    nb = choose_tile_size(m, tile_size, multiple_of=p)
    nbl = nb // p                       # locations per tile
    T = m // nb
    panels = [locs[t * nbl:(t + 1) * nbl] for t in range(T)]

    with jax.named_scope("repro.gen"):
        diag = jnp.stack([build_sigma_panel(panels[t], panels[t], params,
                                            d_spatial=d_spatial, gen=gen)
                          for t in range(T)])
    diag = apply_nugget(diag, nugget, diag.dtype)

    def lower_panels():
        for j in range(T - 1):
            rows = locs[(j + 1) * nbl:]
            with jax.named_scope("repro.gen"):
                blk = build_sigma_panel(rows, panels[j], params,
                                        d_spatial=d_spatial, gen=gen,
                                        block=nb)
            yield blk.reshape(T - 1 - j, nb, nb)

    return diag, lower_panels(), nb, T


@jax.named_scope("repro.compress")
def tlr_compress_tiles(locs, params: MaternParams, tile_size: int = 0,
                       tol: float = 1e-7, max_rank: int = 0,
                       nugget: float = 0.0, gen: str = "pallas",
                       d_spatial: int = 2, scale=None,
                       dtype_policy=None) -> TLRMatrix:
    """Generator-direct TLR compression (the production path, §5.3).

    Equivalent to ``tlr_compress(build_sigma(locs, params, "I", nugget))`` to
    SVD/fp tolerance, but tile-by-tile from the generator: diagonal tiles and
    batched strict-lower panels come from ``kernels.matern_tile`` (``gen=
    "pallas"``, concrete half-integer nu) or the XLA K_nu path (``gen="xla"``
    or general/traced nu), so the dense Sigma is never materialized.  The
    nugget lands on diagonal tiles only — exactly where ``build_sigma`` puts
    it.  ``scale`` (threshold reference) defaults to max(sigma2) + nugget,
    which equals the dense path's max |diag(Sigma)|.

    ``dtype_policy`` (a core.precision policy or name) is the mixed-
    precision entry point: off-diagonal panels are down-cast to the
    policy's narrow dtype *before* their truncation SVD and U/V are stored
    narrow, while diagonal tiles keep the generated (wide) dtype — the
    downstream factorization adapts to the storage dtypes, widening only
    at the documented TRSM/SYRK boundaries.
    """
    diag, lower, nb, T = generate_tiles(locs, params, tile_size=tile_size,
                                        nugget=nugget, gen=gen,
                                        d_spatial=d_spatial)
    if max_rank <= 0:
        max_rank = max(8, nb // 4)
    kmax = min(max_rank, nb)
    if scale is None:
        scale = jnp.max(params.sigma2) + nugget

    policy = resolve_policy(dtype_policy)
    uv_dtype = diag.dtype if policy is None else policy.narrow_dtype
    u = jnp.zeros((T, T, nb, kmax), uv_dtype)
    v = jnp.zeros((T, T, nb, kmax), uv_dtype)
    ranks = jnp.zeros((T, T), jnp.int32)
    for j, tiles in enumerate(lower):
        U, V, R = svd_truncate_batch(tiles.astype(uv_dtype), tol, kmax, scale)
        u = u.at[j + 1:, j].set(U)
        v = v.at[j + 1:, j].set(V)
        ranks = ranks.at[j + 1:, j].set(R)
    return TLRMatrix(diag=diag, u=u, v=v, ranks=ranks)


def tlr_to_dense(t: TLRMatrix, symmetric: bool = True) -> jax.Array:
    T, nb = t.n_tiles, t.tile_size
    m = T * nb
    out = jnp.zeros((m, m), t.diag.dtype)
    for i in range(T):
        out = out.at[i * nb:(i + 1) * nb, i * nb:(i + 1) * nb].set(t.diag[i])
        for j in range(i):
            block = (t.u[i, j] @ t.v[i, j].T).astype(out.dtype)
            out = out.at[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb].set(block)
            if symmetric:
                out = out.at[j * nb:(j + 1) * nb, i * nb:(i + 1) * nb].set(block.T)
    return out


# ---------------------------------------------------------------------------
# Recompression (the "GEMM + SVD" task of HiCMA)
# ---------------------------------------------------------------------------


def _constrain(x, mesh, spec):
    """with_sharding_constraint, or the identity when no mesh is given (so
    the single-device and distributed paths share traced bodies verbatim)."""
    if mesh is None or spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


@jax.custom_jvp
def _safe_qr(a):
    """Reduced QR with rank-deficiency-safe derivatives.

    The recompress concats carry zero-padded rank columns, so R is exactly
    singular and the textbook QR JVP (a triangular solve against R) returns
    NaN.  The primal is ``core.linalg.qr``; the JVP bumps (near-)zero R
    diagonal entries to 1 before the solve — those directions correspond to
    the padded columns, whose downstream contributions the tol*scale rank
    mask zeroes anyway, so the guard only replaces NaN with a finite
    subgradient choice."""
    q, r = qr(a)
    return q, r              # plain tuple: custom_jvp needs one pytree shape


@_safe_qr.defjvp
def _safe_qr_jvp(primals, tangents):
    (a,), (da,) = primals, tangents
    q, r = _safe_qr(a)
    kk = r.shape[-2]                  # rows of reduced R = min(m, n)
    r1 = r[..., :, :kk]               # leading square block (== r when m >= n)
    diag = jnp.diagonal(r1, axis1=-2, axis2=-1)
    lim = 1e-40 + 1e-12 * jnp.max(jnp.abs(diag), axis=-1, keepdims=True)
    bump = jnp.where(jnp.abs(diag) > lim, 0.0, 1.0)
    r_safe = r1 + jnp.eye(kk, dtype=r.dtype) * bump[..., None, :]
    da_rinv = lax.linalg.triangular_solve(r_safe, da[..., :, :kk])
    qt_da_rinv = jnp.swapaxes(q, -1, -2) @ da_rinv
    low = jnp.tril(qt_da_rinv, -1)
    do = low - jnp.swapaxes(low, -1, -2)                    # skew-symmetric
    dq = q @ (do - qt_da_rinv) + da_rinv
    if r.shape[-1] == kk:
        dr = (qt_da_rinv - do) @ r
    else:
        # Wide R (2*kmax > nb): only the leading square block is invertible;
        # dR = Q^T dA - Omega R with Omega = Q^T dQ skew-symmetric.
        dr = jnp.swapaxes(q, -1, -2) @ da - do @ r
    return (q, r), (dq, dr)


@jax.custom_jvp
def _right_svd(a):
    """Singular values (descending) and right singular vectors of ``a``
    (..., m, n) with degenerate-gap-safe derivatives.

    The primal is ``core.linalg.right_svd`` (Jacobi on TPU).

    Recompress cores carry zero-padded rank columns, so they have exactly
    repeated zero singular values and the textbook derivative divides by
    s_j^2 - s_i^2.  The JVP (the eigen-perturbation of a^T a) zeroes those
    terms inside (near-)degenerate blocks; they are the components the
    tol*scale rank mask drops downstream, so the guard only replaces NaN
    with a finite subgradient choice."""
    return right_svd(a)


@_right_svd.defjvp
def _right_svd_jvp(primals, tangents):
    (a,), (da,) = primals, tangents
    s, v = _right_svd(a)
    w = jnp.swapaxes(a @ v, -1, -2) @ (da @ v)
    dg = w + jnp.swapaxes(w, -1, -2)                    # V^T d(A^T A) V
    s2 = s * s
    lim = 1e-40 + 1e-12 * jnp.max(s2, axis=-1, keepdims=True)
    pos = s2 > lim
    ds = jnp.where(pos, jnp.diagonal(dg, axis1=-2, axis2=-1), 0.0) / (
        2.0 * jnp.where(pos, s, 1.0))
    gap = s2[..., None, :] - s2[..., :, None]           # gap[i,j] = s_j^2-s_i^2
    safe = jnp.abs(gap) > lim[..., None]
    f = jnp.where(safe, 1.0, 0.0) / jnp.where(safe, gap, 1.0)
    return (s, v), (ds, v @ (f * dg))


def _recompress_parts(u1, v1, u2, v2, tol, scale):
    """(B..., nb, k) pairs -> recompressed sum with rank <= kmax, batched.

    QR(U')·QR(V') then the truncated SVD of the small core
    (``truncate_core``).  Returns (U, V, ranks, cs) where ranks counts the
    singular values kept (int32, shape B...) and cs is the raw
    singular-value spectrum (for breakdown accounting — a NaN input tile
    surfaces here as non-finite singular values).
    """
    kmax = u1.shape[-1]
    ucat = jnp.concatenate([u1, u2], axis=-1)       # (..., nb, 2k)
    vcat = jnp.concatenate([v1, v2], axis=-1)
    # one batched QR for both sides: a single QR in the compiled program
    (qu, qv), (ru, rv) = _safe_qr(jnp.stack([ucat, vcat]))
    core = ru @ jnp.swapaxes(rv, -1, -2)
    return truncate_core(core, tol, kmax, scale, left=qu, right=qv)


def _batched_recompress(u1, v1, u2, v2, tol, scale):
    """Compatibility 3-tuple form of ``_recompress_parts`` (no counting)."""
    return _recompress_parts(u1, v1, u2, v2, tol, scale)[:3]


def _batched_recompress_stat(u1, v1, u2, v2, tol, scale):
    """As ``_batched_recompress`` plus an int32 scalar count of non-finite
    singular values — the in-graph breakdown signal the panel bodies fold
    into ``FactorStatus.nonfinite_count``."""
    un, vn, rn, cs = _recompress_parts(u1, v1, u2, v2, tol, scale)
    bad = jnp.sum(~jnp.isfinite(cs)).astype(jnp.int32)
    return un, vn, rn, bad


def recompress(u1, v1, u2, v2, tol: float, scale: float):
    """(u1 v1^T + u2 v2^T) -> (U, V, rank) with rank <= kmax (= u1 cols).

    Unbatched reference entry point; the factorizations use the same math
    through _batched_recompress inside the shared panel body.
    """
    return _batched_recompress(u1, v1, u2, v2, tol, scale)


# ---------------------------------------------------------------------------
# TLR Cholesky (right-looking; the paper's Fig. 1 dataflow on UV tiles).
# One traced panel body serves both the single-device scan form below and
# the distributed SPMD factorization in core/dist_tlr.py.
# ---------------------------------------------------------------------------


class TLRCholesky(NamedTuple):
    diag: jax.Array    # (T, nb, nb) lower Cholesky factors of diagonal tiles
    u: jax.Array       # (T, T, nb, kmax) factor tiles  L[i,j] = u v^T
    v: jax.Array
    ranks: jax.Array
    status: FactorStatus | None = None  # breakdown accounting (if tracked)


def panel_trsm(lkk, vk):
    """L_kk^{-1} V_ik for a whole (T, nb, kmax) panel column as one
    multi-RHS solve against the wide diagonal factor; the result is cast
    back to the panel's storage dtype."""
    T, nb, k = vk.shape
    rhs = jnp.moveaxis(vk.astype(lkk.dtype), 0, 1).reshape(nb, T * k)
    x = solve_lower(lkk, rhs).reshape(nb, T, k)
    return jnp.moveaxis(x, 1, 0).astype(vk.dtype)


def tlr_panel_body(k, diag, u, v, ranks, status=None, *, tol, scale,
                   pairs=None, mesh=None, dspec=None, uvspec=None):
    """One right-looking panel step k on rank-padded (kmax) trailing blocks.

    The four paper-Fig.-1 task classes, with ``k`` a *traced* loop index so
    the whole factorization is one trace regardless of T:

        POTRF — factor diagonal tile (k, k)
        TRSM  — triangular-solve column k's V tiles (masked to rows i > k)
        SYRK  — batched TLR-MM onto the trailing diagonal tiles
        GEMM  — batched TLR-MM + QR/SVD recompression of trailing tiles
                i > j > k (one _batched_recompress call)

    Static shapes force masked overcompute; ``pairs`` selects how the GEMM
    batch is laid out:

      * pairs=(il, jl) — gather the static strict-lower index set, batch of
        T(T-1)/2 (the single-device form; ~2.4x less QR/SVD work than the
        full grid, measured 387 ms vs 625 ms on the T=6/nb=78 CPU case).
      * pairs=None — masked full-(T, T)-grid batch that never reshuffles the
        2-D tile layout (the SPMD form: each device recompresses its own
        P(row, "model") shard; a gather over pair indices would re-shard
        every step).

    When a ``FactorStatus`` is threaded in (riding the scan carry), the
    POTRF pivot minimum and the recompress non-finite counts fold into it
    and a 5-tuple comes back; ``status=None`` keeps the historical 4-tuple.
    """
    T, nb = diag.shape[0], diag.shape[1]
    kmax = u.shape[-1]
    rows = jnp.arange(T)
    # ---- POTRF on tile (k, k): replicated small factorization.
    dkk = lax.dynamic_index_in_dim(diag, k, 0, keepdims=False)
    # spmdlint: ignore[R1] one (nb, nb) panel-head POTRF replicated on purpose: every shard needs L_kk immediately and nb^2 is tiny next to the pair batch
    lkk = cholesky(dkk)
    if status is not None:
        status = status.update_potrf(lkk)
    row_is_k = (rows == k)[:, None, None]
    # ---- TRSM on panel column k (V only; U untouched — §5.3).
    vk = lax.dynamic_index_in_dim(v, k, 1, keepdims=False)       # (T, nb, kmax)
    # TRSM widening boundary: the solve runs against the wide diagonal
    # factor and the result is stored back at the (possibly narrow) U/V
    # storage dtype.  Uniform-dtype policies make both casts no-ops.
    vk_solved = panel_trsm(lkk, vk)
    below = (rows > k)[:, None, None]
    vk = jnp.where(below, vk_solved, vk)
    v = lax.dynamic_update_index_in_dim(v, vk, k, 1)
    uk = lax.dynamic_index_in_dim(u, k, 1, keepdims=False)       # (T, nb, kmax)

    # ---- SYRK onto trailing diagonal tiles i > k: D_i -= U (V^T V) U^T.
    w = jnp.einsum("tnk,tnl->tkl", vk, vk)
    upd = jnp.einsum("tnk,tkl,tml->tnm", uk, w, uk)
    diag = diag - jnp.where(below, upd, 0.0)
    diag = jnp.where(row_is_k, lkk[None], diag)

    # ---- GEMM + recompress: Delta A[i,j] = -U_ik (V_ik^T V_jk) U_jk^T.
    if pairs is not None:
        il, jl = pairs
        wij = jnp.einsum("lnk,lnq->lkq", vk[il], vk[jl])          # V_ik^T V_jk
        du = jnp.einsum("lnk,lkq->lnq", uk[il], wij)              # U_ik W
        dv = -uk[jl]
        act = (jl > k)[:, None, None]
        du = jnp.where(act, du, 0.0)
        dv = jnp.where(act, dv, 0.0)
        u0, v0 = u[il, jl], v[il, jl]
        with jax.named_scope("repro.recompress"):
            if status is not None:
                un, vn, rn, bad = _batched_recompress_stat(u0, v0, du, dv,
                                                           tol, scale)
                status = status.add_nonfinite(bad)
            else:
                un, vn, rn = _batched_recompress(u0, v0, du, dv, tol, scale)
        u = u.at[il, jl].set(jnp.where(act, un, u0))
        v = v.at[il, jl].set(jnp.where(act, vn, v0))
        ranks = ranks.at[il, jl].set(
            jnp.where(act[:, 0, 0], rn, ranks[il, jl]))
    else:
        wij = jnp.einsum("ink,jnl->ijkl", vk, vk)                 # (T,T,k,k)
        du = jnp.einsum("ijkl,ink->ijnl", wij, uk)                # U_ik W
        dv = jnp.broadcast_to(-uk[None], (T, T, nb, kmax))        # -U_jk
        act = ((rows[:, None] > rows[None, :]) &
               (rows[None, :] > k))[..., None, None]
        du = jnp.where(act, du, 0.0)
        dv = jnp.where(act, dv, 0.0)
        du = _constrain(du, mesh, uvspec)
        with jax.named_scope("repro.recompress"):
            if status is not None:
                un, vn, rn, bad = _batched_recompress_stat(u, v, du, dv,
                                                           tol, scale)
                status = status.add_nonfinite(bad)
            else:
                un, vn, rn = _batched_recompress(u, v, du, dv, tol, scale)
        u = jnp.where(act, un, u)
        v = jnp.where(act, vn, v)
        ranks = jnp.where(act[..., 0, 0], rn, ranks)
    u = _constrain(u, mesh, uvspec)
    v = _constrain(v, mesh, uvspec)
    diag = _constrain(diag, mesh, dspec)
    if status is not None:
        return diag, u, v, ranks, status.fold_ranks(ranks)
    return diag, u, v, ranks


def indexed_scan(body, k_hi: int, carry, k_lo: int = 0):
    """fori_loop(k_lo, k_hi) with an s32 induction variable that reverse-mode
    AD can handle: one lax.scan over a static int32 arange.

    Two constraints meet here.  The SPMD partitioner rejects mixed s64/s32
    index arithmetic in dynamic updates, so under jax_enable_x64 the loop
    index must be s32 — but fori_loop only keeps it s32 when given jnp.int32
    bounds, which reverse-mode AD then refuses ("dynamic start/stop").
    Scanning over jnp.arange(k_hi, dtype=int32) gives a static trip count
    (reverse-differentiable — the MLE gradding through a traced nugget) and
    an s32 index, and lowers to the same while loop.  ``body`` has the
    fori_loop signature (k, carry) -> carry."""
    def step(c, k):
        return body(k, c), None

    carry, _ = lax.scan(step, carry,
                        jnp.arange(k_lo, k_hi, dtype=jnp.int32))
    return carry


def panel_loop(diag, u, v, ranks, k_hi: int, *, tol, scale, pairs=None,
               mesh=None, dspec=None, uvspec=None, status=None):
    """Run the shared panel body for k in [0, k_hi) under one indexed_scan
    (static trip count — one traced body, reverse-differentiable).  Passing
    a ``FactorStatus`` rides it on the scan carry and returns a 5-tuple."""
    def body(k, carry):
        return tlr_panel_body(k, *carry, tol=tol, scale=scale, pairs=pairs,
                              mesh=mesh, dspec=dspec, uvspec=uvspec)

    carry = (diag, u, v, ranks) if status is None else \
        (diag, u, v, ranks, status)
    return indexed_scan(body, k_hi, carry)


def tlr_panel_body_bc(k, diag, up, vp, ranks, status=None, *, layout, width,
                      tol, scale, mesh=None, dspec=None, pspec=None,
                      shard_axes=None):
    """One right-looking panel step k on *pair-major* strict-lower storage
    (distribution.block_cyclic.PairLayout): the static strict-lower pair
    batch of the single-device form, made shardable.

    ``up``/``vp`` are (length, nb, kmax) with the leading axis laid out
    block-cyclically over the devices (pspec), so the GEMM + recompress —
    the dominant work — is a purely local batch per shard.  The only
    per-step communication is the panel-column gather/scatter through
    ``layout.pos[:, k]`` (the broadcast of column k that the right-looking
    algorithm needs anyway).  The (T, T) grid is never materialized.

    The GEMM + recompress runs on a static window, the last ``width`` local
    slots of every shard, which must hold every pair this step updates
    (i > j > k; ``block_cyclic.window_schedule`` picks it); inside it the
    pads and the pairs of retired columns are masked.  ``width=0`` skips
    the GEMM and the recompress: POTRF, TRSM and SYRK still run.

    ``shard_axes`` names the mesh axes the pair axis is laid out over:
    the recompress QR/SVD then runs under shard_map so each device
    factorizes only its own window (distribution/pair_qr.py) — without it
    GSPMD replicates the whole QR batch on every device.  None keeps the
    replicated batch (the mesh=None / fallback path).
    """
    T, nb = diag.shape[0], diag.shape[1]
    rows = jnp.arange(T)
    pos = jnp.asarray(layout.pos)
    # ---- POTRF on tile (k, k): replicated small factorization.
    dkk = lax.dynamic_index_in_dim(diag, k, 0, keepdims=False)
    # spmdlint: ignore[R1] one (nb, nb) panel-head POTRF replicated on purpose: every shard needs L_kk immediately and nb^2 is tiny next to the pair batch
    lkk = cholesky(dkk)
    if status is not None:
        status = status.update_potrf(lkk)
    row_is_k = (rows == k)[:, None, None]
    below = (rows > k)[:, None, None]
    # ---- gather panel column k from the pair slots (i <= k reads an out-
    # of-bounds slot -> zero-filled, masked below anyway).
    pcol = lax.dynamic_index_in_dim(pos, k, 1, keepdims=False)       # (T,)
    vk = vp.at[pcol].get(mode="fill", fill_value=0.0)        # (T, nb, kmax)
    uk = up.at[pcol].get(mode="fill", fill_value=0.0)
    # ---- TRSM on panel column k (V only; U untouched — §5.3).
    # TRSM widening boundary: solve wide against L_kk, store back narrow.
    vk_solved = panel_trsm(lkk, vk)
    vk = jnp.where(below, vk_solved, vk)
    vp = vp.at[pcol].set(vk, mode="drop")  # OOB slots (i <= k) are dropped
    # ---- SYRK onto trailing diagonal tiles i > k: D_i -= U (V^T V) U^T.
    w = jnp.einsum("tnk,tnl->tkl", vk, vk)
    upd = jnp.einsum("tnk,tkl,tml->tnm", uk, w, uk)
    diag = diag - jnp.where(below, upd, 0.0)
    diag = jnp.where(row_is_k, lkk[None], diag)
    # ---- GEMM + recompress over the window's pair slots (local per shard).
    if width:
        S, pps = layout.n_shards, layout.pairs_per_shard
        slots = (np.arange(S)[:, None] * pps
                 + np.arange(pps - width, pps)[None, :]).ravel()
        il, jl = layout.il[slots], layout.jl[slots]
        wij = jnp.einsum("lnk,lnq->lkq", vk[il], vk[jl])      # V_ik^T V_jk
        du = jnp.einsum("lnk,lkq->lnq", uk[il], wij)          # U_ik W
        dv = -uk[jl]
        act = jnp.asarray(il > jl) & (jnp.asarray(jl) > k)  # pads fail il > jl
        du = jnp.where(act[:, None, None], du, 0.0)
        dv = jnp.where(act[:, None, None], dv, 0.0)
        du = _constrain(du, mesh, pspec)
        with jax.named_scope("repro.recompress"):
            up, vp, ranks, bad = window_recompress(
                up, vp, ranks, du, dv, act, tol, scale, n_shards=S,
                mesh=mesh, axes=shard_axes)
        if status is not None:
            status = status.add_nonfinite(bad)
    up = _constrain(up, mesh, pspec)
    vp = _constrain(vp, mesh, pspec)
    diag = _constrain(diag, mesh, dspec)
    if status is not None:
        return diag, up, vp, ranks, status.fold_ranks(ranks)
    return diag, up, vp, ranks


def pair_panel_loop(diag, up, vp, ranks, k_hi: int, *, layout, tol, scale,
                    mesh=None, dspec=None, pspec=None, shard_axes=None,
                    status=None):
    """The block-cyclic pair body for k in [0, k_hi): one indexed_scan per
    segment of ``block_cyclic.window_schedule(layout, k_hi)``, each with
    its static window width, so a step recompresses only the window that
    holds its live pairs."""
    carry = (diag, up, vp, ranks) if status is None else \
        (diag, up, vp, ranks, status)
    for seg in window_schedule(layout, k_hi).segments:
        def body(k, c, width=seg.width):
            return tlr_panel_body_bc(k, *c, layout=layout, width=width,
                                     tol=tol, scale=scale, mesh=mesh,
                                     dspec=dspec, pspec=pspec,
                                     shard_axes=shard_axes)

        carry = indexed_scan(body, seg.k1, carry, k_lo=seg.k0)
    return carry


@jax.named_scope("repro.factorize")
def tlr_cholesky(t: TLRMatrix, tol: float = 1e-9, scale: float = 1.0,
                 track_status: bool = False) -> TLRCholesky:
    """Factor A = L L^T keeping off-diagonal tiles compressed.

    Scan form: a single traced panel step under lax.fori_loop (trace size
    O(1) in T, versus the former Python-unrolled O(T) trace with shrinking
    slices), shared verbatim with the distributed factorization in
    core/dist_tlr.py.  Trailing blocks are rank-padded to kmax so every step
    has static shapes; the GEMM batch covers the fixed strict-lower index
    set with inactive (j <= k) pairs masked to zero updates.  The last
    column needs only its POTRF, which runs outside the loop.
    """
    T = t.n_tiles
    diag, u, v, ranks = t.diag, t.u, t.v, t.ranks
    status = init_status(diag.dtype, ranks) if track_status else None
    il, jl = np.tril_indices(T, k=-1)
    if len(il):
        pairs = (jnp.asarray(il), jnp.asarray(jl))
        out = panel_loop(diag, u, v, ranks, T - 1, tol=tol,
                         scale=scale, pairs=pairs, status=status)
        if track_status:
            diag, u, v, ranks, status = out
        else:
            diag, u, v, ranks = out
    lkk = cholesky(diag[T - 1])  # last column: POTRF only
    if track_status:
        status = status.update_potrf(lkk)
    diag = diag.at[T - 1].set(lkk)
    return TLRCholesky(diag=diag, u=u, v=v, ranks=ranks, status=status)


def solve_lower_grid(diag_l, u, v, z) -> jax.Array:
    """Forward substitution L alpha = z on grid-form TLR factors as one
    lax.fori_loop: a single traced step (trace size O(1) in T, versus the
    former Python-unrolled O(T) slices), shared with the distributed solve
    (core.dist_tlr.dist_tlr_solve_lower).  Step k's trailing update is a
    masked batch over all T rows — the same static-shape overcompute trade
    the panel bodies make."""
    T, nb = diag_l.shape[0], diag_l.shape[1]
    z = jnp.asarray(z).reshape(T, nb)
    rows = jnp.arange(T)

    def body(k, carry):
        z, out = carry
        lkk = lax.dynamic_index_in_dim(diag_l, k, 0, keepdims=False)
        zk = lax.dynamic_index_in_dim(z, k, 0, keepdims=False)
        ak = solve_lower(lkk, zk)
        out = lax.dynamic_update_index_in_dim(out, ak, k, 0)
        # z_i -= U_ik (V_ik^T a_k) for i > k  (masked batched).
        uk = lax.dynamic_index_in_dim(u, k, 1, keepdims=False)
        vk = lax.dynamic_index_in_dim(v, k, 1, keepdims=False)
        wk = jnp.einsum("tnk,n->tk", vk, ak)
        delta = jnp.einsum("tnk,tk->tn", uk, wk)
        below = (rows > k)[:, None]
        z = z - jnp.where(below, delta, 0.0)
        return z, out

    _, out = indexed_scan(body, T, (z, jnp.zeros_like(z)))
    return out.reshape(-1)


@jax.named_scope("repro.solve")
def tlr_solve_lower(chol: TLRCholesky, z) -> jax.Array:
    """Solve L alpha = z with L in TLR form (forward substitution)."""
    return solve_lower_grid(chol.diag, chol.u, chol.v, z)


def tlr_logdet(chol: TLRCholesky) -> jax.Array:
    diags = jnp.diagonal(chol.diag, axis1=-2, axis2=-1)
    return 2.0 * jnp.sum(jnp.log(diags))


def tlr_matvec(t: TLRMatrix, x) -> jax.Array:
    """y = A x with A symmetric in TLR form.

    One lax.fori_loop over tile columns k (trace size O(1) in T, versus the
    former doubly-unrolled O(T^2) trace): step k applies column k's tiles
    both below the diagonal (y_i += U_ik V_ik^T x_k, i > k) and, transposed,
    above it (y_k += sum_{i>k} V_ik U_ik^T x_i) as masked batches.
    """
    T, nb = t.n_tiles, t.tile_size
    x = jnp.asarray(x).reshape(T, nb)
    y0 = jnp.einsum("tnm,tm->tn", t.diag, x)
    rows = jnp.arange(T)

    def body(k, y):
        uk = lax.dynamic_index_in_dim(t.u, k, 1, keepdims=False)  # (T,nb,kmax)
        vk = lax.dynamic_index_in_dim(t.v, k, 1, keepdims=False)
        xk = lax.dynamic_index_in_dim(x, k, 0, keepdims=False)    # (nb,)
        below = (rows > k)[:, None]
        # strict-lower tiles of column k: y_i += U_ik (V_ik^T x_k).
        w = jnp.einsum("tnk,n->tk", vk, xk)
        y = y + jnp.where(below, jnp.einsum("tnk,tk->tn", uk, w), 0.0)
        # their transposes (row k): y_k += sum_{i>k} V_ik (U_ik^T x_i).
        wu = jnp.where(below, jnp.einsum("tnk,tn->tk", uk, x), 0.0)
        return y.at[k].add(jnp.einsum("tnk,tk->n", vk, wu))

    y = indexed_scan(body, T, y0)
    return y.reshape(-1)


# ---------------------------------------------------------------------------
# Log-likelihood through the TLR factorization (Eq. 1)
# ---------------------------------------------------------------------------


def tlr_loglik_from_matrix(t: TLRMatrix, z, tol: float = 1e-9,
                           scale: float = 1.0,
                           track_status: bool = True) -> LoglikResult:
    chol = tlr_cholesky(t, tol=tol, scale=scale, track_status=track_status)
    with jax.named_scope("repro.solve"):
        alpha = tlr_solve_lower(chol, z)
        quad = jnp.sum(alpha * alpha)
        logdet = tlr_logdet(chol)
        m = t.shape[0]
        ll = -0.5 * (m * math.log(2.0 * math.pi) + logdet + quad)
        status = chol.status
        if status is not None:
            # Breakdown -> a well-defined finite sentinel, never NaN
            # contagion.
            status = status.add_nonfinite(
                (~jnp.isfinite(ll)).astype(jnp.int32))
            ok = status.ok
            ll = jnp.where(ok, ll, sentinel_loglik(ll.dtype))
            logdet = jnp.where(ok, logdet, jnp.zeros_like(logdet))
            quad = jnp.where(ok, quad, jnp.zeros_like(quad))
    return LoglikResult(ll, logdet, quad, None, status)


def tlr_loglik(dists, z, params: MaternParams, tol: float = 1e-7,
               max_rank: int = 64, tile_size: int = 0,
               nugget: float = 0.0, *, locs=None, from_tiles: bool = False,
               gen: str = "pallas", track_status: bool = True,
               dtype_policy=None) -> LoglikResult:
    """End-to-end TLR likelihood: GEN -> compress -> TLR Cholesky -> solve.

    Locations must be Morton-ordered by the caller for good rank decay.
    With ``from_tiles=True`` (generator-direct production path) tiles come
    straight from ``tlr_compress_tiles(locs, ...)`` — ``dists`` may be None
    and the dense Sigma is never materialized.  ``gen`` selects the tile
    generator ("pallas" half-integer fast path with per-pair XLA fallback, or
    "xla").  The default path keeps the historical behavior: build the dense
    Sigma from ``dists`` and compress it (validation / small n).
    """
    if from_tiles:
        if locs is None:
            raise ValueError("from_tiles=True requires locs (Morton-ordered)")
        scale = jnp.max(params.sigma2) + nugget
        t = tlr_compress_tiles(locs, params, tile_size=tile_size, tol=tol,
                               max_rank=max_rank, nugget=nugget, gen=gen,
                               scale=scale, dtype_policy=dtype_policy)
    else:
        # spmdlint: ignore[A4] from_tiles=False is the dense validation path (small n, tests only)
        sigma = build_sigma(None, params, representation="I", nugget=nugget,
                            dists=dists)
        scale = jnp.max(jnp.abs(jnp.diagonal(sigma)))
        # multiple_of=p keeps the auto tile grid identical to the tiles path.
        t = tlr_compress(sigma, tile_size=tile_size, tol=tol,
                         max_rank=max_rank, scale=scale,
                         multiple_of=params.p, dtype_policy=dtype_policy)
    return tlr_loglik_from_matrix(t, z, tol=tol, scale=scale,
                                  track_status=track_status)


# ---------------------------------------------------------------------------
# Reports: memory footprint (Fig. 6) and rank distribution (Fig. 5)
# ---------------------------------------------------------------------------


def memory_footprint(t: TLRMatrix, itemsize: int | None = None) -> dict:
    """Bytes for the TLR representation (actual ranks) vs dense."""
    T, nb = t.n_tiles, t.tile_size
    if itemsize is None:
        itemsize = t.diag.dtype.itemsize
    ranks = np.asarray(t.ranks)
    il, jl = np.tril_indices(T, k=-1)
    lowrank_entries = int(2 * nb * ranks[il, jl].sum())
    diag_entries = T * nb * nb
    m = T * nb
    tlr_bytes = (lowrank_entries + diag_entries) * itemsize
    dense_bytes = m * m * itemsize
    return dict(tlr_bytes=tlr_bytes, dense_bytes=dense_bytes,
                ratio=dense_bytes / max(tlr_bytes, 1),
                diag_bytes=diag_entries * itemsize,
                lowrank_bytes=lowrank_entries * itemsize)


def rank_distribution(t: TLRMatrix) -> np.ndarray:
    """(T, T) array: off-diagonal actual ranks, diagonal = nb (dense)."""
    ranks = np.asarray(t.ranks).copy()
    ranks = ranks + ranks.T
    np.fill_diagonal(ranks, t.tile_size)
    return ranks


def tlr_mm_flops(nb: int, k: int) -> int:
    """The paper's §5.3 model: one TLR-MM costs 36 nb k^2 flops."""
    return 36 * nb * k * k
