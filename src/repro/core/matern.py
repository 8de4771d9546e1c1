"""Matérn and parsimonious multivariate Matérn cross-covariance functions.

This module implements the statistical core of Salvaña et al. (2020):

* ``kv``          — modified Bessel function of the second kind K_nu(x) for real
                    order nu > 0, pure JAX (Temme series for x <= 2, Steed's CF2
                    continued fraction for x > 2, upward recurrence in the order).
* ``matern_correlation`` — the normalized Matérn correlation
                    M_nu(u) = u^nu K_nu(u) / (2^{nu-1} Gamma(nu)),  M_nu(0) = 1,
                    with fast closed forms for nu in {1/2, 3/2, 5/2}.
* ``parsimonious_rho``   — the colocated cross-correlation rho_ij implied by the
                    latent beta_ij (Gneiting–Kleiber–Schlather 2010, Eq. (2) of
                    the paper).
* ``cross_covariance``   — the p x p matrix-valued C(h; theta) of Eq. (2).

Numerical notes
---------------
The order nu is a *traced scalar* (one order per variable pair); the argument x
is an arbitrary-shape array.  This matches how Sigma(theta) is assembled: only
p(p+1)/2 distinct orders are ever needed per likelihood evaluation, so we pay
the order-reduction control flow once per pair, not per matrix entry.

Accuracy: validated against ``scipy.special.kv`` to <1e-10 relative (f64) over
nu in (0, 6], x in [1e-8, 60]; see tests/test_matern.py.

The paper runs in f64; on TPU the deploy dtype is f32 with nugget
regularization (see DESIGN.md §2).  All functions preserve the input dtype.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

# ---------------------------------------------------------------------------
# K_nu — modified Bessel function of the second kind, real order.
# ---------------------------------------------------------------------------


# Chebyshev coefficients of Temme's Gamma_1 and Gamma_2 on |mu| <= 1/2
# (Numerical Recipes, ``beschb``).
_GAM1_CHEB = (-1.142022680371168e0, 6.5165112670737e-3, 3.087090173086e-4,
              -3.4706269649e-6, 6.9437664e-9, 3.67795e-11, -1.356e-13)
_GAM2_CHEB = (1.843740587300905e0, -7.68528408447867e-2, 1.2719271366546e-3,
              -4.9717367042e-6, -3.31261198e-8, 2.423096e-10, -1.702e-13,
              -1.49e-15)


def _chebev(coeffs, y):
    """Clenshaw sum of a Chebyshev series on [-1, 1] (NR ``chebev``)."""
    d = dd = jnp.zeros_like(y)
    for c in coeffs[:0:-1]:
        d, dd = 2.0 * y * d - dd + c, d
    return y * d - dd + 0.5 * coeffs[0]


def _chepolish(mu, dtype):
    """gam1, gam2, gampl, gammi used by the Temme series.

    gampl = 1/Gamma(1+mu),   gammi = 1/Gamma(1-mu)
    gam1  = (gammi - gampl) / (2 mu)      (-> -EulerGamma as mu -> 0)
    gam2  = (gammi + gampl) / 2

    Chebyshev series in 8 mu^2 - 1 rather than two log-gamma evaluations:
    no cancellation in gam1 as mu -> 0, and a handful of multiply-adds
    where each f64 log-gamma adds ~12 s to a v5e compile.
    """
    mu = jnp.asarray(mu, dtype)
    y = 8.0 * mu * mu - 1.0
    gam1 = _chebev(_GAM1_CHEB, y)
    gam2 = _chebev(_GAM2_CHEB, y)
    return gam1, gam2, gam2 - mu * gam1, gam2 + mu * gam1


def _kv_temme_series(mu, x, max_iter=200):
    """K_mu(x) and K_{mu+1}(x) for x <= 2, |mu| <= 1/2 (Temme's method).

    Early-exit while_loop: the series converges in <= ~25 terms at x <= 2
    (terms fall like (x^2/4)^i / i!^2), so the loop cost tracks the data,
    not the worst case.
    """
    dtype = x.dtype
    eps = jnp.finfo(dtype).eps
    x = jnp.maximum(x, jnp.asarray(1e-30, dtype))

    x2 = 0.5 * x
    pimu = jnp.asarray(math.pi, dtype) * mu
    fact = jnp.where(jnp.abs(pimu) < 1e-12, 1.0, pimu / jnp.sin(pimu))
    d = -jnp.log(x2)
    e = mu * d
    fact2 = jnp.where(jnp.abs(e) < 1e-12, 1.0,
                      jnp.sinh(e) / jnp.where(jnp.abs(e) < 1e-12, 1.0, e))
    gam1, gam2, gampl, gammi = _chepolish(mu, dtype)
    ee = jnp.exp(e)
    ff0 = fact * (gam1 * (0.5 * (ee + 1.0 / ee)) + gam2 * fact2 * d)
    p0 = 0.5 * ee / gampl
    q0 = 0.5 / (ee * gammi)
    c0 = jnp.ones_like(x)
    d2 = x2 * x2
    # an array mu (one order per leading slice) broadcasts against x; the
    # loop carry takes the joint shape
    shape = jnp.broadcast_shapes(jnp.shape(mu), x.shape)
    ff0, p0, q0, c0 = (jnp.broadcast_to(t, shape) for t in (ff0, p0, q0, c0))

    def cond(carry):
        i = carry[0]
        done = carry[-1]
        return (i <= max_iter) & ~jnp.all(done)

    def body(carry):
        i, ff, p, q, c, ksum, ksum1, done = carry
        fi = i.astype(dtype)
        ff = (fi * ff + p + q) / (fi * fi - mu * mu)
        c = c * d2 / fi
        p = p / (fi - mu)
        q = q / (fi + mu)
        delk = c * ff
        delk1 = c * (p - fi * ff)
        ksum = jnp.where(done, ksum, ksum + delk)
        ksum1 = jnp.where(done, ksum1, ksum1 + delk1)
        done = done | (jnp.abs(delk) < jnp.abs(ksum) * eps)
        return i + 1, ff, p, q, c, ksum, ksum1, done

    init = (jnp.asarray(1, jnp.int32), ff0, p0, q0, c0, ff0, p0,
            jnp.zeros(shape, bool))
    # spmdlint: ignore[R5] early-exit series convergence is the point (i32 carry, elementwise); differentiable paths use kv_half_integer closed forms
    out = lax.while_loop(cond, body, init)
    ksum, ksum1 = out[5], out[6]
    rkmu = ksum
    rk1 = ksum1 * 2.0 / x
    return rkmu, rk1


def _kv_steed_cf2(mu, x, max_iter=400):
    """K_mu(x) and K_{mu+1}(x) for x > 2, |mu| <= 1/2 (Steed's CF2).

    Early-exit while_loop; convergence slows toward x -> 2+ (max_iter bounds
    the worst case, typical counts are < 60).

    NR's recurrence carries c_i (growing like 2^i) and q_i (shrinking like
    2^-i) and adds only their product.  Here the carry is the products
    ``cq1 = c_{i-1} q_{i-1}`` and ``cq2 = c_{i-1} q_i``, which stay bounded:
    TPU f64 is emulated in pairs of f32 words, whose exponent range c_i and
    q_i leave after ~128 terms (inf * 0 = NaN near x = 2).
    """
    dtype = x.dtype
    eps = jnp.finfo(dtype).eps
    a1 = 0.25 - mu * mu
    b0 = 2.0 * (1.0 + x)
    d0 = 1.0 / b0
    h0 = d0
    delh0 = d0
    cq1_0 = jnp.zeros_like(x)
    cq2_0 = a1 * jnp.ones_like(x)
    q0 = a1 * jnp.ones_like(x)
    s0 = 1.0 + q0 * delh0

    def cond(carry):
        i = carry[0]
        done = carry[-1]
        return (i <= max_iter + 1) & ~jnp.all(done)

    def body(carry):
        i, a, b, d, h, delh, q, cq1, cq2, s, done = carry
        fi = i.astype(dtype)
        a = a - 2.0 * (fi - 1.0)
        cq = -(cq1 - b * cq2) / fi                  # c_i q_{i+1}
        cq1, cq2 = -a * cq2 / fi, cq
        q = q + cq
        b = b + 2.0
        d = 1.0 / (b + a * d)
        delh = (b * d - 1.0) * delh
        hn = h + delh
        dels = q * delh
        sn = s + dels
        h = jnp.where(done, h, hn)
        s = jnp.where(done, s, sn)
        done = done | (jnp.abs(dels / sn) < eps)
        return i + 1, a, b, d, h, delh, q, cq1, cq2, s, done

    shape = jnp.broadcast_shapes(jnp.shape(mu), x.shape)
    init = (jnp.asarray(2, jnp.int32),) + tuple(
        jnp.broadcast_to(t, shape) for t in (
            -a1 * jnp.ones_like(x), b0, d0, h0, delh0, q0, cq1_0, cq2_0,
            s0)) + (jnp.zeros(shape, bool),)
    # spmdlint: ignore[R5] early-exit CF2 convergence is the point (i32 carry, elementwise); differentiable paths use kv_half_integer closed forms
    out = lax.while_loop(cond, body, init)
    h, s = out[4], out[9]
    h = a1 * h
    rkmu = jnp.sqrt(jnp.asarray(math.pi, dtype) / (2.0 * x)) * jnp.exp(-x) / s
    rk1 = rkmu * (mu + x + 0.5 - h) / x
    return rkmu, rk1


@partial(jax.jit, static_argnames=())
def kv(nu, x):
    """Modified Bessel function of the second kind K_nu(x).

    nu: > 0, a scalar or an array that broadcasts against x (may be
    traced).  x: array-like > 0.  Mirrors Numerical-Recipes ``bessik``:
    reduce nu = nl + mu with |mu| <= 1/2, evaluate K_mu, K_{mu+1} (Temme
    for x<=2, CF2 for x>2), then recur upward.  Several orders in one call
    share one copy of the loops in the compiled program.
    """
    x = jnp.asarray(x)
    dtype = x.dtype if jnp.issubdtype(x.dtype, jnp.floating) else jnp.result_type(float)
    x = x.astype(dtype)
    nu = jnp.asarray(nu, dtype)
    nl = jnp.floor(nu + 0.5).astype(jnp.int32)  # number of upward recurrences
    mu = nu - nl.astype(dtype)

    xs = jnp.maximum(x, jnp.asarray(1e-30, dtype))
    k_small = _kv_temme_series(mu, jnp.minimum(xs, 2.0))
    k_large = _kv_steed_cf2(mu, jnp.maximum(xs, 2.0))
    use_small = xs <= 2.0
    rkmu = jnp.where(use_small, k_small[0], k_large[0])
    rk1 = jnp.where(use_small, k_small[1], k_large[1])

    def recur(i, carry):
        rkmu, rk1 = carry
        fi = i.astype(dtype)
        rktemp = (mu + fi) * (2.0 / xs) * rk1 + rkmu
        act = i <= nl                   # orders needing fewer steps hold
        return jnp.where(act, rk1, rkmu), jnp.where(act, rktemp, rk1)

    # spmdlint: ignore[R5,A2] nl = floor(nu + 0.5) recurrences — nu may be traced, so the trip count is data-dependent by design
    rkmu, rk1 = lax.fori_loop(1, jnp.max(nl) + 1, recur, (rkmu, rk1))
    return rkmu


def kv_half_integer(nu_half: float, x):
    """Closed-form K_{n+1/2}(x) for small half-integers (hot path; no loops).

    Used by the Pallas tile-generation kernel and by the fast correlation
    paths below.  nu_half must be a *static* python value in {0.5, 1.5, 2.5}.
    """
    x = jnp.asarray(x)
    pref = jnp.sqrt(jnp.asarray(math.pi, x.dtype) / (2.0 * x)) * jnp.exp(-x)
    if nu_half == 0.5:
        return pref
    if nu_half == 1.5:
        return pref * (1.0 + 1.0 / x)
    if nu_half == 2.5:
        return pref * (1.0 + 3.0 / x + 3.0 / (x * x))
    raise ValueError(f"no closed form wired for nu={nu_half}")


# ---------------------------------------------------------------------------
# Matérn correlation
# ---------------------------------------------------------------------------


def matern_correlation_halfint(u, nu_half: float):
    """M_nu(u) with static half-integer nu (paper's Eq. (2) normalization)."""
    u = jnp.asarray(u)
    zero = u <= 0.0
    us = jnp.where(zero, 1.0, u)
    if nu_half == 0.5:
        val = jnp.exp(-us)
    elif nu_half == 1.5:
        val = (1.0 + us) * jnp.exp(-us)
    elif nu_half == 2.5:
        val = (1.0 + us + us * us / 3.0) * jnp.exp(-us)
    else:
        raise ValueError(f"no closed form wired for nu={nu_half}")
    return jnp.where(zero, jnp.ones_like(val), val)


def matern_correlation(u, nu):
    """M_nu(u) = u^nu K_nu(u) / (2^{nu-1} Gamma(nu)); M_nu(0)=1. Traced nu."""
    u = jnp.asarray(u)
    dtype = u.dtype if jnp.issubdtype(u.dtype, jnp.floating) else jnp.result_type(float)
    u = u.astype(dtype)
    nu = jnp.asarray(nu, dtype)
    zero = u <= 0.0
    us = jnp.where(zero, 1.0, u)
    lognorm = ((nu - 1.0) * jnp.log(jnp.asarray(2.0, dtype))
               + jax.scipy.special.gammaln(nu))
    val = jnp.exp(nu * jnp.log(us) - lognorm) * kv(nu, us)
    return jnp.where(zero, jnp.ones_like(val), val)


def matern_covariance(h, sigma2, a, nu):
    """Marginal Matérn covariance sigma2 * M_nu(h / a)."""
    return sigma2 * matern_correlation(jnp.asarray(h) / a, nu)


def effective_range(a, nu, target=0.05, rmax=10.0, iters=60):
    """Distance at which the correlation drops to ``target`` (paper's ER).

    Bisection on M_nu(r/a) = target.  Used to annotate Fig. 13-style reports:
    ER = {0.1, 0.3, 0.7} <-> a = {0.03, 0.09, 0.2} at nu = 0.5.
    """
    a = jnp.asarray(a, jnp.result_type(float))

    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        val = matern_correlation(mid / a, nu)
        lo = jnp.where(val > target, mid, lo)
        hi = jnp.where(val > target, hi, mid)
        return lo, hi

    lo, hi = lax.fori_loop(0, iters, body, (jnp.zeros_like(a), jnp.full_like(a, rmax)))
    return 0.5 * (lo + hi)


# ---------------------------------------------------------------------------
# Parsimonious multivariate Matérn (Eq. (2))
# ---------------------------------------------------------------------------


def parsimonious_nu_matrix(nus):
    """nu_ij = (nu_ii + nu_jj) / 2 for the parsimonious model."""
    nus = jnp.asarray(nus)
    return 0.5 * (nus[:, None] + nus[None, :])


def parsimonious_rho(nus, beta, d: int = 2):
    """Colocated cross-correlation matrix rho_ij from the latent beta_ij.

    rho_ij = beta_ij * sqrt(G(nu_i + d/2)/G(nu_i)) * sqrt(G(nu_j + d/2)/G(nu_j))
             * G((nu_i + nu_j)/2) / G((nu_i + nu_j)/2 + d/2)

    (Gneiting–Kleiber–Schlather 2010; the canonical form of the factor the
    paper prints with a stray exponent.)  rho_ii = 1.
    """
    nus = jnp.asarray(nus)
    beta = jnp.asarray(beta)
    dtype = jnp.result_type(nus.dtype, beta.dtype, float)
    nus = nus.astype(dtype)
    beta = beta.astype(dtype)
    half_d = jnp.asarray(0.5 * d, dtype)
    nu_ij = parsimonious_nu_matrix(nus)
    p = nus.shape[0]
    # one log-gamma over all four argument sets: each f64 log-gamma op adds
    # ~12 s to a v5e compile
    g = jax.scipy.special.gammaln(jnp.concatenate(
        [nus + half_d, nus, nu_ij.ravel(), nu_ij.ravel() + half_d]))
    gmarg = 0.5 * (g[:p] - g[p:2 * p])      # log sqrt(G(nu+d/2)/G(nu))
    g_ij = (g[2 * p:2 * p + p * p] - g[2 * p + p * p:]).reshape(p, p)
    logfac = gmarg[:, None] + gmarg[None, :] + g_ij
    rho = beta * jnp.exp(logfac)
    return jnp.where(jnp.eye(p, dtype=bool), jnp.ones_like(rho), rho)


def cross_covariance(h, sigma2s, a, nus, beta, d: int = 2):
    """The p x p matrix C(h; theta) of Eq. (2) at (scalar or array) lag ||h||.

    Returns an array of shape h.shape + (p, p).
    """
    h = jnp.asarray(h)
    sigma2s = jnp.asarray(sigma2s)
    nus = jnp.asarray(nus)
    p = sigma2s.shape[0]
    rho = parsimonious_rho(nus, beta, d=d)
    sig = jnp.sqrt(sigma2s)
    amp = rho * (sig[:, None] * sig[None, :])  # rho_ij * sigma_i * sigma_j
    nu_ij = parsimonious_nu_matrix(nus)
    u = h[..., None, None] / a

    def corr_for_pair(nu_pair, u_pair):
        return matern_correlation(u_pair, nu_pair)

    # vmap over the p*p (duplicated-symmetric) set of orders.
    flat_nu = nu_ij.reshape(-1)
    u_b = jnp.broadcast_to(u, h.shape + (p, p)).reshape(h.shape + (p * p,))
    corr = jax.vmap(corr_for_pair, in_axes=(0, -1), out_axes=-1)(flat_nu, u_b)
    corr = corr.reshape(h.shape + (p, p))
    return amp * corr
