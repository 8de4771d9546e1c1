"""Loop-form dense factorizations (core/linalg.py) against numpy, and the
TLR pipeline with the forms a TPU runs (Householder QR, Jacobi SVD)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MaternParams, exact_loglik, linalg
from repro.core.covariance import morton_order
from repro.core.simulate import grid_locations, simulate_mgrf


def _spd(n, seed=0):
    a = np.random.default_rng(seed).normal(size=(n, n))
    return a @ a.T + n * np.eye(n)


@pytest.mark.parametrize("tpu", [False, True])
@pytest.mark.parametrize("n,block", [(384, 64), (96, 128), (100, 32)])
def test_cholesky_and_solves_match_numpy(n, block, tpu, monkeypatch):
    """XLA's whole-matrix forms off TPU; on TPU the blocked (n a multiple
    of the block) and direct (otherwise) loop forms."""
    monkeypatch.setattr(linalg, "_on_tpu", lambda: tpu)
    a = _spd(n)
    lo = np.asarray(linalg.cholesky(jnp.asarray(a), block=block))
    np.testing.assert_allclose(lo, np.linalg.cholesky(a), atol=1e-12)
    assert np.all(np.triu(lo, 1) == 0)
    b = np.random.default_rng(1).normal(size=(n, 3))
    x = np.asarray(linalg.solve_lower(jnp.asarray(lo), jnp.asarray(b),
                                      block=block))
    np.testing.assert_allclose(lo @ x, b, atol=1e-10)
    x = np.asarray(linalg.solve_lower(jnp.asarray(lo), jnp.asarray(b[:, 0]),
                                      transpose=True, block=block))
    np.testing.assert_allclose(lo.T @ x, b[:, 0], atol=1e-10)
    x = np.asarray(linalg.cho_solve(jnp.asarray(lo), jnp.asarray(b),
                                    block=block))
    np.testing.assert_allclose(a @ x, b, atol=1e-9)


@pytest.mark.parametrize("tpu", [False, True])
def test_blocked_cholesky_flags_indefinite_and_differentiates(tpu,
                                                              monkeypatch):
    monkeypatch.setattr(linalg, "_on_tpu", lambda: tpu)
    a = _spd(256)
    bad = a.copy()
    bad[200, 200] = -1e6
    assert np.isnan(np.asarray(linalg.cholesky(jnp.asarray(bad), block=64))).any()
    g = jax.grad(lambda s: jnp.sum(jnp.log(jnp.diagonal(
        linalg.cholesky(jnp.asarray(a) * s, block=64)))))(1.0)
    assert float(g) == pytest.approx(128.0, rel=1e-12)    # d/ds log|sA|/2


def test_householder_qr_matches_definition():
    a = np.random.default_rng(0).normal(size=(3, 40, 12))
    a[1, :, 5] = 0.0                                  # a zero-padded column
    q, r = (np.asarray(t) for t in linalg._householder_qr(jnp.asarray(a)))
    np.testing.assert_allclose(q @ r, a, atol=1e-13)
    np.testing.assert_allclose(np.swapaxes(q, -1, -2) @ q,
                               np.broadcast_to(np.eye(12), (3, 12, 12)),
                               atol=1e-13)
    assert np.all(np.tril(r, -1) == 0)


@pytest.mark.parametrize("shape", [(3, 32, 32), (2, 40, 33), (16, 16)])
def test_jacobi_svd_matches_numpy(shape):
    """Graded spectra down to 1e-12 and an odd column count."""
    a = np.random.default_rng(0).normal(size=shape) * np.logspace(
        0, -12, shape[-1])
    s, v = (np.asarray(t) for t in linalg._jacobi_svd(jnp.asarray(a)))
    ref = np.linalg.svd(a, compute_uv=False)
    np.testing.assert_allclose(s, ref, atol=1e-14 * ref.max())
    n = shape[-1]
    np.testing.assert_allclose(np.swapaxes(v, -1, -2) @ v,
                               np.broadcast_to(np.eye(n), v.shape), atol=1e-13)
    u_s = a @ v                                       # columns U * s
    np.testing.assert_allclose(np.linalg.norm(u_s, axis=-2), s, atol=1e-13)


def test_round_robin_meets_every_pair():
    for n in (2, 4, 6, 16):
        perm, pos, seen, h = linalg._round_robin_perm(n), np.arange(n), set(), n // 2
        for _ in range(n - 1):
            seen.update(frozenset((pos[i], pos[h + i])) for i in range(h))
            pos = pos[perm]
        assert len(seen) == n * (n - 1) // 2
        assert np.array_equal(pos, np.arange(n))


def test_tlr_pipeline_with_tpu_forms(monkeypatch):
    """dist_tlr_loglik through Householder QR + Jacobi SVD (the chip's
    forms) agrees with the LAPACK forms and with the dense loglik."""
    from repro.core.dist_tlr import dist_tlr_loglik

    locs = grid_locations(12, jitter=0.2, seed=0)            # m = 288
    locs = jnp.asarray(np.asarray(locs)[morton_order(locs)])
    params = MaternParams.bivariate(a=0.09, nu11=0.5, nu22=1.3, beta=0.5)
    z = simulate_mgrf(jax.random.PRNGKey(0), locs, params, nugget=1e-4)[0]
    kw = dict(from_tiles=True, block_cyclic=True, tile_size=72, max_rank=32,
              nugget=1e-4, tol=1e-9)
    cpu = dist_tlr_loglik(None, z, locs=locs, params=params, **kw)
    monkeypatch.setattr(linalg, "_on_tpu", lambda: True)
    tpu = dist_tlr_loglik(None, z, locs=locs, params=params, **kw)
    exact = float(exact_loglik(locs, z, params, nugget=1e-4).loglik)
    assert bool(tpu.status.ok)
    assert float(tpu.loglik) == pytest.approx(float(cpu.loglik), abs=1e-8)
    assert abs(float(tpu.loglik) - exact) < 1e-3
