"""Sigma(theta) assembly: representations, SPD, Morton ordering, c0."""
import numpy as np
import pytest
import scipy.special as sps

from repro.core import covariance as cov
from repro.core.simulate import grid_locations, uniform_locations


def _params():
    return cov.MaternParams.bivariate(sigma11=1.0, sigma22=1.5, a=0.2,
                                      nu11=0.5, nu22=1.0, beta=0.5)


def _sigma_oracle(locs, params, representation):
    """numpy/scipy reference implementation straight from Eq. (2)."""
    locs = np.asarray(locs)
    n = locs.shape[0]
    p = params.p
    sig2 = np.asarray(params.sigma2)
    a = float(params.a)
    nus = np.asarray(params.nu)
    beta = np.asarray(params.beta)
    d = np.linalg.norm(locs[:, None] - locs[None, :], axis=-1)

    def rho(i, j):
        if i == j:
            return 1.0
        ni, nj = nus[i], nus[j]
        fac = (np.sqrt(sps.gamma(ni + 1) / sps.gamma(ni))
               * np.sqrt(sps.gamma(nj + 1) / sps.gamma(nj))
               * sps.gamma((ni + nj) / 2) / sps.gamma((ni + nj) / 2 + 1))
        return beta[i, j] * fac

    def matern(u, nu):
        out = np.ones_like(u)
        m = u > 0
        out[m] = u[m]**nu * sps.kv(nu, u[m]) / (2**(nu - 1) * sps.gamma(nu))
        return out

    sigma = np.zeros((n * p, n * p))
    for i in range(p):
        for j in range(p):
            nuij = 0.5 * (nus[i] + nus[j])
            block = (rho(i, j) * np.sqrt(sig2[i] * sig2[j])
                     * matern(d / a, nuij))
            if representation == "I":
                sigma[i::p, j::p] = block
            else:
                sigma[i * n:(i + 1) * n, j * n:(j + 1) * n] = block
    return sigma


@pytest.mark.parametrize("rep", ["I", "II"])
def test_sigma_matches_oracle(rep):
    locs = uniform_locations(23, seed=1)
    params = _params()
    got = np.asarray(cov.build_sigma(locs, params, representation=rep))
    want = _sigma_oracle(locs, params, rep)
    np.testing.assert_allclose(got, want, rtol=1e-7, atol=1e-10)


def test_sigma_column_panels_match_oracle(monkeypatch):
    """The TPU assembly: column panels of _PANEL_LOCS locations under a
    fori_loop, nugget added on the diagonal."""
    from repro.core import linalg

    monkeypatch.setattr(linalg, "_on_tpu", lambda: True)
    monkeypatch.setattr(cov, "_PANEL_LOCS", 8)
    locs = uniform_locations(24, seed=1)
    params = _params()
    got = np.asarray(cov.build_sigma(locs, params, nugget=1e-3))
    want = _sigma_oracle(locs, params, "I") + 1e-3 * np.eye(48)
    np.testing.assert_allclose(got, want, rtol=1e-7, atol=1e-10)


def test_representations_are_permutations():
    locs = uniform_locations(17, seed=2)
    params = _params()
    s1 = np.asarray(cov.build_sigma(locs, params, representation="I"))
    s2 = np.asarray(cov.build_sigma(locs, params, representation="II"))
    n, p = 17, 2
    # perm maps rep-II index (i*n + l) -> rep-I index (l*p + i)
    perm = np.array([loc * p + i for i in range(p) for loc in range(n)])
    np.testing.assert_allclose(s1[np.ix_(perm, perm)], s2, rtol=1e-12)
    # same determinant => identical likelihoods (paper §5.2 equivalence)
    np.testing.assert_allclose(np.linalg.slogdet(s1)[1],
                               np.linalg.slogdet(s2)[1], rtol=1e-9)


def test_sigma_is_spd():
    locs = grid_locations(7, jitter=0.3, seed=3)
    params = _params()
    s = np.asarray(cov.build_sigma(locs, params, nugget=1e-10))
    np.testing.assert_allclose(s, s.T, rtol=1e-12)
    w = np.linalg.eigvalsh(s)
    assert w.min() > 0


def test_c0_consistent_with_sigma():
    """c0 built from pred locations == the corresponding Sigma columns."""
    locs = uniform_locations(12, seed=4)
    params = _params()
    full = np.asarray(cov.build_sigma(locs, params, representation="I"))
    c0 = np.asarray(cov.build_c0(locs[:3], locs, params, representation="I"))
    p = 2
    for loc in range(3):
        np.testing.assert_allclose(c0[loc], full[:, loc * p:(loc + 1) * p],
                                   rtol=1e-9, atol=1e-12)


def test_cross_cov_at_zero():
    params = _params()
    c00 = np.asarray(cov.cross_cov_at_zero(params))
    np.testing.assert_allclose(np.diag(c00), [1.0, 1.5], rtol=1e-12)
    assert c00[0, 1] == pytest.approx(c00[1, 0])


def test_morton_order_locality():
    """Morton-sorted neighbors in index space are close in physical space."""
    locs = grid_locations(16)
    perm = cov.morton_order(locs)
    sorted_locs = np.asarray(locs)[perm]
    gaps = np.linalg.norm(np.diff(sorted_locs, axis=0), axis=1)
    # Z-curve: median consecutive gap equals one grid step.
    assert np.median(gaps) <= 1.5 / 16
    assert sorted(perm.tolist()) == list(range(256))


def test_morton_improves_offdiag_rank():
    """The paper's motivation for Morton ordering: faster tile-rank decay."""
    rng = np.random.default_rng(0)
    locs = rng.uniform(size=(256, 2))
    params = cov.MaternParams.univariate(1.0, 0.2, 1.0)

    def offdiag_rank(order):
        s = np.asarray(cov.build_sigma(np.asarray(locs)[order], params))
        tile = s[:128, 128:]
        sv = np.linalg.svd(tile, compute_uv=False)
        return int((sv > 1e-7 * sv[0]).sum())

    natural = offdiag_rank(np.arange(256))
    morton = offdiag_rank(cov.morton_order(locs))
    assert morton <= natural
