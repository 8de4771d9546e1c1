"""K_nu and Matérn correlation vs scipy oracles."""
import numpy as np
import pytest
import scipy.special as sps

import jax.numpy as jnp

from repro.core import matern


XS = np.concatenate([
    np.geomspace(1e-6, 1.9, 25),
    np.array([1.999, 2.0, 2.001]),
    np.geomspace(2.1, 60.0, 25),
])


@pytest.mark.parametrize("nu", [0.1, 0.3, 0.5, 0.73, 1.0, 1.5, 2.0, 2.283, 2.5,
                                3.0, 3.7, 4.5, 5.5])
def test_kv_matches_scipy(nu):
    got = np.asarray(matern.kv(nu, jnp.asarray(XS, jnp.float64)))
    want = sps.kv(nu, XS)
    np.testing.assert_allclose(got, want, rtol=5e-9)


@pytest.mark.parametrize("x", [0.05, 0.2, 0.5])
def test_kv_cf2_within_f32_exponent_range(x):
    """CF2 run long (hundreds of terms at x < 2) in f32, whose exponent
    range TPU's emulated f64 shares: the carry must not overflow into
    inf * 0 = NaN."""
    got = matern._kv_steed_cf2(jnp.float32(0.3),
                               jnp.asarray([x], jnp.float32), max_iter=2000)
    np.testing.assert_allclose(np.asarray(got[0]), sps.kv(0.3, [x]),
                               rtol=1e-5)


def test_kv_half_integer_closed_forms():
    for nu in (0.5, 1.5, 2.5):
        got = np.asarray(matern.kv_half_integer(nu, jnp.asarray(XS)))
        want = sps.kv(nu, XS)
        np.testing.assert_allclose(got, want, rtol=1e-12)


@pytest.mark.parametrize("nu", [0.5, 1.0, 1.5, 2.033, 2.5])
def test_matern_correlation_normalization(nu):
    # M_nu(0) = 1 and monotone decreasing in u.
    us = jnp.asarray(np.linspace(0.0, 5.0, 200), jnp.float64)
    vals = np.asarray(matern.matern_correlation(us, nu))
    assert vals[0] == pytest.approx(1.0, abs=1e-9)
    assert np.all(np.diff(vals) <= 1e-12)
    assert np.all(vals >= -1e-12)


@pytest.mark.parametrize("nu", [0.5, 1.5, 2.5])
def test_matern_halfint_matches_general(nu):
    us = jnp.asarray(np.geomspace(1e-4, 8.0, 60), jnp.float64)
    fast = np.asarray(matern.matern_correlation_halfint(us, nu))
    slow = np.asarray(matern.matern_correlation(us, nu))
    np.testing.assert_allclose(fast, slow, rtol=1e-8)


def test_matern_correlation_vs_scipy_formula():
    # u^nu K_nu(u) / (2^{nu-1} Gamma(nu)) straight from scipy.
    for nu in (0.7, 1.0, 2.283):
        us = np.geomspace(1e-3, 10.0, 50)
        want = us**nu * sps.kv(nu, us) / (2 ** (nu - 1) * sps.gamma(nu))
        got = np.asarray(matern.matern_correlation(jnp.asarray(us), nu))
        np.testing.assert_allclose(got, want, rtol=1e-8)


def test_parsimonious_rho_properties():
    nus = jnp.asarray([0.5, 1.0])
    beta = jnp.asarray([[1.0, 0.5], [0.5, 1.0]])
    rho = np.asarray(matern.parsimonious_rho(nus, beta, d=2))
    assert rho[0, 0] == pytest.approx(1.0)
    assert rho[1, 1] == pytest.approx(1.0)
    assert rho[0, 1] == pytest.approx(rho[1, 0])
    # |rho_ij| <= |beta_ij| (the Gamma factor is < 1 for d >= 1).
    assert abs(rho[0, 1]) < 0.5
    # beta = 0 -> independent.
    rho0 = np.asarray(matern.parsimonious_rho(nus, jnp.eye(2), d=2))
    assert rho0[0, 1] == pytest.approx(0.0, abs=1e-12)


def test_parsimonious_rho_closed_form():
    # Equal smoothness: rho = beta * Gamma(nu + d/2)/... collapses so that
    # rho_12 = beta_12 exactly when nu_11 == nu_22 (GKS 2010).
    nus = jnp.asarray([1.3, 1.3])
    beta = jnp.asarray([[1.0, 0.4], [0.4, 1.0]])
    rho = np.asarray(matern.parsimonious_rho(nus, beta, d=2))
    assert rho[0, 1] == pytest.approx(0.4, rel=1e-10)


def test_effective_range_monotone():
    # Paper: ER = {0.1, 0.3, 0.7} for a = {0.03, 0.09, 0.2} at nu = 0.5.
    ers = [float(matern.effective_range(a, 0.5)) for a in (0.03, 0.09, 0.2)]
    assert ers[0] < ers[1] < ers[2]
    np.testing.assert_allclose(ers, [0.0899, 0.2696, 0.599], rtol=0.02)


def test_cross_covariance_shape_and_symmetry():
    h = jnp.asarray(np.linspace(0, 1, 7))
    c = matern.cross_covariance(h, jnp.asarray([1.0, 2.0]), 0.2,
                                jnp.asarray([0.5, 1.0]),
                                jnp.asarray([[1.0, 0.5], [0.5, 1.0]]))
    assert c.shape == (7, 2, 2)
    np.testing.assert_allclose(np.asarray(c), np.swapaxes(np.asarray(c), -1, -2))
    np.testing.assert_allclose(np.asarray(c[0]).diagonal(), [1.0, 2.0], rtol=1e-9)
