"""Dense factorizations for the TLR pipeline in loop form.

The TPU compiler expands XLA's ``cholesky``, ``triangular_solve``, ``qr``
and ``svd`` into code unrolled over the matrix, and emulates f64 in pairs
of f32 words, so compile time grows with the dimension and explodes in
f64.  For a v5e chip, compiled from the host: f64 Cholesky 42 s at
n = 2048 and 235 s at n = 8192, an f64 solve of a 2048 tile 15 s, the
f64 SVD of a 256 x 256 core ~70 s, and the SVD of a 2048 x 2048 tile 337 s
even in f32.  XLA's f64 QR does not compile inside ``shard_map`` for TPU
at all, nor its f64 Cholesky inside a loop over a sharded operand ("A
tuple parameter that is being flattened shouldn't have frontend
attributes"): the pair-sharded factorization needs both.

The forms here keep one small loop body in the program whatever the size:

* ``cholesky`` / ``solve_lower`` / ``cho_solve``: on a TPU backend,
  ``BLOCK``-wide block columns under ``lax.fori_loop``.  Each block step
  reads the whole factor through a masked GEMM (static shapes under a
  traced block index), so the Cholesky does ~6x the n^3/3 flops of the
  unblocked algorithm and the solves ~2x; that part is MXU work.  Matrices
  no larger than one block, or not a multiple of it, are one block.
  Elsewhere the whole matrix goes to XLA's (LAPACK on CPU).
* Within a block, and for ``qr`` / ``right_svd``: on a TPU backend,
  column and row loops (unblocked Cholesky, substitution, Householder QR,
  one-sided Jacobi SVD) in plain array ops, so no XLA decomposition is in
  the program at all; elsewhere XLA's (LAPACK on CPU), which compiles at
  once.  They agree to round-off, except that Jacobi resolves singular
  values at round-off level that LAPACK leaves as noise of ~eps * s[0].
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

BLOCK = 128
_JACOBI_MAX_SWEEPS = 15
# Columns count as orthogonal at |cos| <= this.  sqrt(m) * eps (LAPACK's
# dgesvj) is out of reach of TPU's emulated f64, whose inner products
# carry ~1e-10 relative error: sweeps then ran to the cap.  Jacobi
# converges quadratically, so the sweep that first rotates nothing at 1e-9
# leaves singular values as accurate as at sqrt(m) * eps (round-off of
# s[0] on the CPU).
_JACOBI_TOL = 1e-9

__all__ = ["BLOCK", "cholesky", "solve_lower", "cho_solve", "qr",
           "right_svd"]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _blocked(n: int, block: int) -> bool:
    return _on_tpu() and n > block and n % block == 0


def _potrf_columns(a):
    """Unblocked right-looking Cholesky, one column per fori step."""
    n = a.shape[-1]
    idx = jnp.arange(n)

    def step(j, w):
        cj = lax.dynamic_index_in_dim(w, j, -1, keepdims=False)
        d = jnp.sqrt(lax.dynamic_index_in_dim(cj, j, -1, keepdims=False))
        below = jnp.where(idx > j, cj / d[..., None], 0.0)
        w = w - below[..., :, None] * below[..., None, :]
        col = jnp.where(idx == j, d[..., None], below)
        return lax.dynamic_update_index_in_dim(w, col[..., None], j, -1)

    return jnp.tril(lax.fori_loop(0, n, step, a))


def _trsm_rows(lo, b, transpose):
    """Substitution for lower ``L`` (..., n, n), one row of x per fori step
    (backwards for ``L^T``); ``b`` is (..., n, r)."""
    n = lo.shape[-1]

    def step(i, x):
        k = n - 1 - i if transpose else i
        # the unsolved rows of x are still zero, so the full row/column
        # of L contributes only its solved part
        lk = lax.dynamic_index_in_dim(lo, k, -1 if transpose else -2, False)
        bk = lax.dynamic_index_in_dim(b, k, -2, keepdims=False)
        dk = lax.dynamic_index_in_dim(lk, k, -1, keepdims=False)
        xk = (bk - jnp.einsum("...n,...nr->...r", lk, x)) / dk[..., None]
        return lax.dynamic_update_index_in_dim(x, xk[..., None, :], k, -2)

    return lax.fori_loop(0, n, step, jnp.zeros_like(b))


def _potrf(a):
    """Cholesky of one block: XLA's on CPU; on TPU a column loop (XLA's f64
    Cholesky and triangular solve do not compile inside a loop over a
    sharded operand: the same "tuple parameter ... frontend attributes"
    error as QR in shard_map)."""
    return _potrf_columns(a) if _on_tpu() else jnp.linalg.cholesky(a)


def _trsm(lo, b, transpose=False):
    """``L^{-1} b`` (``L^{-T} b``) for one block; row loop on TPU."""
    if _on_tpu():
        return _trsm_rows(lo, b, transpose)
    return lax.linalg.triangular_solve(lo, b, left_side=True, lower=True,
                                       transpose_a=transpose)


def cholesky(a, *, block: int = BLOCK):
    """Lower Cholesky factor of SPD ``a`` (..., n, n), as
    ``jnp.linalg.cholesky``: NaN entries where ``a`` is not positive
    definite, so the factor's diagonal carries the breakdown signal.

    Left-looking: block column j is ``A[:, j] - L L[j]^T`` over the finished
    columns (the unfinished ones are still zero), so no n x n temporary is
    formed beyond the factor itself."""
    n = a.shape[-1]
    if not _blocked(n, block):
        return _potrf(a)
    if a.ndim > 2:
        return jax.vmap(lambda x: cholesky(x, block=block))(a)
    rows = jnp.arange(n)[:, None]

    def step(j, lo):
        o = j * block
        col = (lax.dynamic_slice(a, (0, o), (n, block))
               - lo @ lax.dynamic_slice(lo, (o, 0), (block, n)).T)
        # spmdlint: ignore[R1] one (block, block) POTRF per step, replicated on purpose: every row of the panel needs it
        lkk = jnp.tril(_potrf(lax.dynamic_slice(col, (o, 0), (block, block))))
        sol = _trsm(lkk, col.T).T                       # col L_kk^{-T}
        lcol = jnp.where(rows >= o + block, sol, 0.0)
        lcol = lax.dynamic_update_slice(lcol, lkk, (o, 0))
        return lax.dynamic_update_slice(lo, lcol, (0, o))

    return lax.fori_loop(0, n // block, step, jnp.zeros_like(a))


def solve_lower(lo, b, *, transpose: bool = False, block: int = BLOCK):
    """Solve ``L x = b`` (or ``L^T x = b`` with ``transpose``) for lower
    triangular ``L`` (n, n); ``b`` is (n,) or (n, r)."""
    n = lo.shape[-1]
    vec = b.ndim == 1
    b = b[:, None] if vec else b
    if not _blocked(n, block):
        x = _trsm(lo, b, transpose)
        return x[:, 0] if vec else x
    nblk = n // block

    def step(i, x):
        j = nblk - 1 - i if transpose else i
        o = j * block
        lkk = lax.dynamic_slice(lo, (o, o), (block, block))
        if transpose:     # rows below block j are solved; L^T row j = col j
            done = lax.dynamic_slice(lo, (0, o), (n, block)).T @ x
        else:             # rows above block j are solved
            done = lax.dynamic_slice(lo, (o, 0), (block, n)) @ x
        rhs = lax.dynamic_slice(b, (o, 0), (block, b.shape[1])) - done
        return lax.dynamic_update_slice(x, _trsm(lkk, rhs, transpose), (o, 0))

    x = lax.fori_loop(0, nblk, step, jnp.zeros_like(b))
    return x[:, 0] if vec else x


def cho_solve(lo, b, *, block: int = BLOCK):
    """``Sigma^{-1} b`` from the lower Cholesky factor of Sigma."""
    return solve_lower(lo, solve_lower(lo, b, block=block), transpose=True,
                       block=block)


def _householder_qr(a):
    """Thin QR of ``a`` (..., m, n), m >= n, by Householder reflections:
    one reflector per column under ``lax.fori_loop``, then Q accumulated
    backwards from the first n columns of the identity.  A zero column
    gets the identity reflector, so zero-padded rank columns stay zero."""
    m, n = a.shape[-2:]
    rows = jnp.arange(m)

    def reflect(j, carry):
        r, vs, taus = carry
        x = jnp.where(rows >= j, lax.dynamic_index_in_dim(r, j, -1, False),
                      0.0)
        alpha = lax.dynamic_index_in_dim(x, j, -1, False)
        beta = -jnp.where(alpha >= 0, 1.0, -1.0) * jnp.sqrt(
            jnp.sum(x * x, axis=-1))
        v = x - jnp.where(rows == j, beta[..., None], 0.0)
        vv = jnp.sum(v * v, axis=-1)
        tau = jnp.where(vv > 0, 2.0 / jnp.where(vv > 0, vv, 1.0), 0.0)
        w = tau[..., None] * jnp.einsum("...m,...mn->...n", v, r)
        r = r - v[..., :, None] * w[..., None, :]
        vs = lax.dynamic_update_index_in_dim(vs, v[..., None], j, -1)
        taus = lax.dynamic_update_index_in_dim(taus, tau[..., None], j, -1)
        return r, vs, taus

    r, vs, taus = lax.fori_loop(
        0, n, reflect, (a, jnp.zeros_like(a), jnp.zeros(a.shape[:-2] + (n,),
                                                        a.dtype)))

    def accumulate(i, q):
        j = n - 1 - i
        v = lax.dynamic_index_in_dim(vs, j, -1, False)
        tau = lax.dynamic_index_in_dim(taus, j, -1, False)
        w = tau[..., None] * jnp.einsum("...m,...mn->...n", v, q)
        return q - v[..., :, None] * w[..., None, :]

    eye = jnp.broadcast_to(jnp.eye(m, n, dtype=a.dtype), a.shape)
    q = lax.fori_loop(0, n, accumulate, eye)
    return q, jnp.triu(r[..., :n, :])


def qr(a):
    """Thin QR of ``a`` (..., m, n), m >= n: (Q, R)."""
    if _on_tpu():
        return _householder_qr(a)
    return jnp.linalg.qr(a)


def _round_robin_perm(n: int) -> np.ndarray:
    """Column permutation applied after each round of the circle-method
    tournament on pairs (i, n/2 + i): n - 1 rounds meet every pair of
    columns once and return them to their order."""
    h = n // 2
    if h == 1:
        return np.arange(2)
    return np.array([0, h, *range(1, h - 1), *range(h + 1, n), h - 1])


def _jacobi_svd(a):
    """One-sided (Hestenes) Jacobi on the columns of ``a`` (..., m, n):
    (s, V) with ``a @ V = U diag(s)``, s descending, V orthogonal.

    Each round rotates the n/2 disjoint column pairs of the round-robin
    tournament at once; sweeps repeat until one rotates nothing
    (``_JACOBI_TOL``, or LAPACK dgesvj's sqrt(m) eps where that is larger).
    The compiled program is one round's body whatever n is, where XLA's TPU
    SVD (a QDWH polar step and an eigensolver) takes ~70 s to compile at
    256 x 256 in f64.
    """
    n = a.shape[-1]
    npad = n + n % 2                 # an odd n gets a zero column: never
    if npad != n:                    # rotated, it sorts last and is dropped
        a = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, 1)])
    h = npad // 2
    perm = jnp.asarray(_round_robin_perm(npad))
    tiny = max(math.sqrt(a.shape[-2]) * float(jnp.finfo(a.dtype).eps),
               _JACOBI_TOL)

    def rnd(carry, _):
        g, v, rotated = carry
        gp, gq = g[..., :h], g[..., h:]
        alpha = jnp.sum(gp * gp, axis=-2)
        beta = jnp.sum(gq * gq, axis=-2)
        gamma = jnp.sum(gp * gq, axis=-2)
        # TPU f64 is emulated in pairs of f32 words: f32's exponent range,
        # and an overflow gives NaN, not inf.  So the norms are not
        # multiplied, and zeta is not formed where it would pass 1e15:
        # there t = 1 / (2 zeta) = gamma / (beta - alpha) to round-off.
        rot = jnp.abs(gamma) > tiny * jnp.sqrt(alpha) * jnp.sqrt(beta)
        diff = beta - alpha
        far = jnp.abs(diff) > 1e15 * jnp.abs(gamma)
        zeta = diff / (2.0 * jnp.where(rot & ~far, gamma, 1.0))
        t = jnp.where(zeta >= 0, 1.0, -1.0) / (
            jnp.abs(zeta) + jnp.sqrt(1.0 + zeta * zeta))
        t = jnp.where(far, gamma / jnp.where(far, diff, 1.0), t)
        c = 1.0 / jnp.sqrt(1.0 + t * t)
        sn = (jnp.where(rot, c * t, 0.0))[..., None, :]
        c = jnp.where(rot, c, 1.0)[..., None, :]
        vp, vq = v[..., :h], v[..., h:]
        g = jnp.concatenate([c * gp - sn * gq, sn * gp + c * gq], -1)
        v = jnp.concatenate([c * vp - sn * vq, sn * vp + c * vq], -1)
        return (g[..., perm], v[..., perm], rotated | jnp.any(rot)), None

    def sweep(state):
        g, v, _, i = state
        (g, v, rotated), _ = lax.scan(rnd, (g, v, jnp.zeros((), bool)),
                                      None, length=npad - 1)
        return g, v, rotated, i + 1

    v0 = jnp.broadcast_to(jnp.eye(npad, dtype=a.dtype),
                          a.shape[:-2] + (npad, npad))
    # spmdlint: ignore[R5] sweeps until a sweep rotates nothing: the count is data-dependent by design; derivatives come from the custom JVP
    g, v, _, _ = lax.while_loop(
        lambda st: st[2] & (st[3] < _JACOBI_MAX_SWEEPS), sweep,
        (a, v0, jnp.ones((), bool), 0))
    s = jnp.sqrt(jnp.sum(g * g, axis=-2))
    order = jnp.argsort(-s, axis=-1)
    s = jnp.take_along_axis(s, order, axis=-1)[..., :n]
    v = jnp.take_along_axis(v, order[..., None, :], axis=-1)[..., :n, :n]
    return s, v


def right_svd(a):
    """(s, V) of ``a`` (..., m, n): singular values descending and the
    right singular vectors, ``a @ V = U diag(s)``."""
    if _on_tpu():
        return _jacobi_svd(a)
    _, s, vt = jnp.linalg.svd(a, full_matrices=False)
    return s, jnp.swapaxes(vt, -1, -2)
