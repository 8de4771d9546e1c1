"""Nelder–Mead optimizer + small-n parameter recovery (Experiment-2 style)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import MaternParams, MLEConfig, fit, simulate_mgrf, uniform_locations
from repro.core.mle import pack_params, unpack_params
from repro.core.optimize import nelder_mead


def test_nelder_mead_rosenbrock():
    def rosen(x):
        return (1 - x[0]) ** 2 + 100.0 * (x[1] - x[0] ** 2) ** 2

    res = nelder_mead(rosen, jnp.asarray([-1.2, 1.0]), max_iters=400)
    np.testing.assert_allclose(np.asarray(res.x), [1.0, 1.0], atol=1e-3)
    assert float(res.value) < 1e-6


def test_nelder_mead_quadratic_nd():
    target = jnp.asarray([0.3, -1.0, 2.0, 0.0, 5.0])

    def quad(x):
        return jnp.sum((x - target) ** 2)

    res = nelder_mead(quad, jnp.zeros(5), max_iters=500)
    np.testing.assert_allclose(np.asarray(res.x), np.asarray(target), atol=1e-3)


def test_nelder_mead_aux_total_and_aux_combine():
    """The running total of the objective's aux is a tree-sum, or what
    ``aux_combine`` makes of it (``ObjectiveAux.merge``: counts add, the
    rank is the largest seen)."""
    from repro.core.mle import ObjectiveAux

    def quad(x):
        v = jnp.sum(x ** 2)
        rank = (10 * jnp.abs(x[0])).astype(jnp.int32)
        one = jnp.ones((), jnp.int32)
        return v, ObjectiveAux(one, one, jnp.zeros((), jnp.int32), rank)

    x0 = jnp.asarray([3.0, 1.0])
    summed = nelder_mead(quad, x0, max_iters=5, has_aux=True)
    merged = nelder_mead(quad, x0, max_iters=5, has_aux=True,
                         aux_combine=ObjectiveAux.merge)
    n = int(merged.n_evals)
    assert int(summed.aux.clamped) == int(merged.aux.clamped) == n
    assert int(merged.aux.breakdowns) == 0
    assert int(merged.aux.max_rank) == 37          # 10 * 3.75, the largest
    assert int(summed.aux.max_rank) > int(merged.aux.max_rank)


def test_pack_unpack_roundtrip():
    params = MaternParams.bivariate(sigma11=1.3, sigma22=0.7, a=0.12,
                                    nu11=0.6, nu22=1.4, beta=-0.35)
    for profile in (False, True):
        x = pack_params(params, profile)
        back = unpack_params(x, 2, profile)
        np.testing.assert_allclose(float(back.a), 0.12, rtol=1e-9)
        np.testing.assert_allclose(np.asarray(back.nu), [0.6, 1.4], rtol=1e-9)
        np.testing.assert_allclose(float(back.beta[0, 1]), -0.35, rtol=1e-9)
        if not profile:
            np.testing.assert_allclose(np.asarray(back.sigma2), [1.3, 0.7],
                                       rtol=1e-9)


@pytest.mark.slow
def test_bivariate_mle_recovers_parameters():
    """Exact-MLE parameter recovery at n=250 (reduced-n Experiment 2)."""
    true = MaternParams.bivariate(sigma11=1.0, sigma22=1.0, a=0.09,
                                  nu11=0.5, nu22=1.0, beta=0.5)
    locs = uniform_locations(250, seed=7)
    z = simulate_mgrf(jax.random.PRNGKey(7), locs, true, nugget=1e-10)[0]
    cfg = MLEConfig(p=2, profile=True, max_iters=120)
    res = fit(locs, z, cfg)
    est = res.params
    # Generous tolerances: n=250 sampling noise; medians over replicates are
    # tighter (see benchmarks/bench_estimation.py).
    assert 0.02 < float(est.a) < 0.4
    assert 0.25 < float(est.nu[0]) < 1.0
    assert 0.5 < float(est.nu[1]) < 2.2
    assert 0.0 < float(est.beta[0, 1]) < 0.95
    assert 0.3 < float(est.sigma2[0]) < 3.0
    ll_true = -float(fit(locs, z, cfg, x0=pack_params(true, True)).loglik)
    assert float(res.loglik) >= -abs(ll_true) * 2  # fit found a decent optimum
