"""Generator-direct TLR compression (tlr_compress_tiles) vs the dense path.

The production pipeline must reproduce tlr_compress(build_sigma(...)) to fp
tolerance for both generators (Pallas half-integer fast path and XLA general
nu) while never materializing the dense (pn x pn) Sigma.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import MaternParams, pairwise_distances
from repro.core import tlr as T
from repro.core.covariance import build_sigma, build_sigma_panel, morton_order
from repro.core.mle import MLEConfig, make_objective, pack_params
from repro.core.simulate import grid_locations, simulate_mgrf


def _locs(n_side=8, seed=0):
    locs = grid_locations(n_side, jitter=0.2, seed=seed)
    return np.asarray(locs)[morton_order(locs)]


def test_build_sigma_panel_matches_dense_slices():
    locs = _locs(8)
    params = MaternParams.bivariate(a=0.09, nu11=0.5, nu22=1.0, beta=0.5)
    sigma = np.asarray(build_sigma(locs, params))
    p = params.p
    for r0, r1, c0, c1 in ((0, 16, 16, 48), (8, 64, 0, 8), (0, 64, 0, 64)):
        pan = np.asarray(build_sigma_panel(locs[r0:r1], locs[c0:c1], params))
        np.testing.assert_allclose(pan, sigma[r0 * p:r1 * p, c0 * p:c1 * p],
                                   rtol=1e-12, atol=1e-14)


@pytest.mark.parametrize("row_block", [0, 16])
def test_build_sigma_column_matches_dense_slice(row_block):
    """A tile column in one piece and in row chunks (the one-device
    compression loop), at a traced column index."""
    from repro.core.covariance import build_sigma_column

    locs = _locs(8)
    params = MaternParams.bivariate(a=0.09, nu11=0.5, nu22=1.3, beta=0.5)
    sigma = np.asarray(build_sigma(locs, params))
    col = jax.jit(lambda j: build_sigma_column(locs, j, 16, params,
                                               row_block=row_block))(2)
    np.testing.assert_allclose(np.asarray(col), sigma[:, 64:96],
                               rtol=1e-12, atol=1e-14)


# nu pairs whose pairwise orders (nu_i + nu_j)/2 are all half-integers are
# Pallas-eligible; (0.5, 1.0) forces the general-nu XLA fallback for nu_12.
@pytest.mark.parametrize("gen", ["pallas", "xla"])
@pytest.mark.parametrize("nu", [(0.5, 0.5), (1.5, 1.5), (0.5, 2.5),
                                (0.5, 1.0)])
def test_compress_tiles_matches_dense_compress(gen, nu):
    locs = _locs(8)
    params = MaternParams.bivariate(a=0.09, nu11=nu[0], nu22=nu[1], beta=0.5)
    dists = pairwise_distances(locs)
    sigma = build_sigma(None, params, dists=dists, nugget=1e-8)
    t_dense = T.tlr_compress(sigma, tile_size=32, tol=1e-7, max_rank=32)
    t_tiles = T.tlr_compress_tiles(locs, params, tile_size=32, tol=1e-7,
                                   max_rank=32, nugget=1e-8, gen=gen)
    assert np.array_equal(np.asarray(t_tiles.ranks), np.asarray(t_dense.ranks))
    np.testing.assert_allclose(np.asarray(T.tlr_to_dense(t_tiles)),
                               np.asarray(T.tlr_to_dense(t_dense)),
                               rtol=1e-10, atol=1e-10)


def test_compress_tiles_nugget_roundtrip():
    """The nugget lands on diagonal tiles only — reconstruction matches the
    dense Sigma with the nugget on its full diagonal."""
    locs = _locs(8)
    params = MaternParams.bivariate(a=0.09, nu11=0.5, nu22=1.5, beta=0.4)
    nugget = 1e-3
    sigma = build_sigma(locs, params, nugget=nugget)
    t = T.tlr_compress_tiles(locs, params, tile_size=32, tol=1e-9,
                             max_rank=32, nugget=nugget)
    err = np.abs(np.asarray(T.tlr_to_dense(t)) - np.asarray(sigma)).max()
    assert err < 1e-9 * 50, err


def test_compress_tiles_never_builds_dense(monkeypatch):
    """Generator-direct means generator-direct: the dense assembly routine is
    never called, and no stored buffer reaches the dense m*m size."""
    import repro.core.covariance as C

    def boom(*a, **k):
        raise AssertionError("dense build_sigma was called")

    monkeypatch.setattr(T, "build_sigma", boom)
    monkeypatch.setattr(C, "build_sigma", boom)
    locs = _locs(8)
    params = MaternParams.bivariate(a=0.09, nu11=0.5, nu22=1.5, beta=0.4)
    t = T.tlr_compress_tiles(locs, params, tile_size=32, tol=1e-7,
                             max_rank=8, nugget=1e-8)
    m = t.shape[0]
    # shape accounting: every component of the returned representation is
    # strictly smaller than the dense matrix it replaces.
    for arr in (t.diag, t.u, t.v):
        assert arr.size < m * m, (arr.shape, m)


def test_tlr_loglik_from_tiles_matches_dense_path():
    """Acceptance: 2-variable n=256 problem at tol=1e-7, <=1e-6 relative."""
    locs = _locs(16)                       # 256 locations, m = 512
    params = MaternParams.bivariate(a=0.09, nu11=0.5, nu22=1.0, beta=0.5)
    dists = pairwise_distances(locs)
    z = simulate_mgrf(jax.random.PRNGKey(3), locs, params, nugget=1e-8)[0]
    ll_dense = float(T.tlr_loglik(dists, z, params, tol=1e-7, max_rank=64,
                                  tile_size=64, nugget=1e-8).loglik)
    ll_tiles = float(T.tlr_loglik(None, z, params, tol=1e-7, max_rank=64,
                                  tile_size=64, nugget=1e-8, locs=locs,
                                  from_tiles=True).loglik)
    assert abs(ll_tiles - ll_dense) <= 1e-6 * abs(ll_dense)


def test_tlr_loglik_from_tiles_requires_locs():
    params = MaternParams.bivariate()
    with pytest.raises(ValueError, match="locs"):
        T.tlr_loglik(None, jnp.zeros(8), params, from_tiles=True)


def test_mle_objective_from_tiles_matches_dense_backend():
    """MLEConfig gen/tlr_from_tiles knobs: identical objective under jit
    (traced nu falls back to XLA inside the pallas generator)."""
    locs = _locs(8)
    params = MaternParams.bivariate(a=0.09, nu11=0.6, nu22=1.2, beta=0.4)
    z = simulate_mgrf(jax.random.PRNGKey(0), locs, params, nugget=1e-8)[0]
    cfg = MLEConfig(p=2, profile=False, backend="tlr", tile_size=32,
                    nugget=1e-8, morton=False)
    x = pack_params(params, profile=False)
    obj_dense, _ = make_objective(locs, z, cfg)
    obj_tiles, _ = make_objective(
        locs, z, dataclasses.replace(cfg, tlr_from_tiles=True, gen="pallas"))
    assert float(obj_tiles(x)) == pytest.approx(float(obj_dense(x)), rel=1e-9)


def test_mle_objective_dist_tlr_matches_dense_backend():
    """MLEConfig.dist_tlr_from_tiles routes the TLR backend through the
    distributed streaming pipeline; on one device the objective matches the
    dense-compress TLR backend under jit."""
    locs = _locs(8)
    params = MaternParams.bivariate(a=0.09, nu11=0.6, nu22=1.2, beta=0.4)
    z = simulate_mgrf(jax.random.PRNGKey(0), locs, params, nugget=1e-8)[0]
    cfg = MLEConfig(p=2, profile=False, backend="tlr", tile_size=32,
                    nugget=1e-8, morton=False)
    x = pack_params(params, profile=False)
    obj_dense, _ = make_objective(locs, z, cfg)
    obj_dist, _ = make_objective(
        locs, z, dataclasses.replace(cfg, dist_tlr_from_tiles=True))
    assert float(obj_dist(x)) == pytest.approx(float(obj_dense(x)), rel=1e-9)


def test_mle_objective_block_cyclic_matches_masked():
    """MLEConfig.block_cyclic flips the distributed TLR backend onto the
    pair-batch factorization; the jitted objective is unchanged."""
    locs = _locs(8)
    params = MaternParams.bivariate(a=0.09, nu11=0.6, nu22=1.2, beta=0.4)
    z = simulate_mgrf(jax.random.PRNGKey(0), locs, params, nugget=1e-8)[0]
    cfg = MLEConfig(p=2, profile=False, backend="tlr", tile_size=32,
                    nugget=1e-8, morton=False, dist_tlr_from_tiles=True)
    x = pack_params(params, profile=False)
    obj_masked, _ = make_objective(locs, z, cfg)
    obj_bc, _ = make_objective(
        locs, z, dataclasses.replace(cfg, block_cyclic=True))
    assert float(obj_bc(x)) == pytest.approx(float(obj_masked(x)), rel=1e-9)



def test_objective_aux_reports_max_rank():
    """ObjectiveAux.max_rank is the TLR factor's largest tile rank: at
    least the compression's, at most kmax; the exact backend reports 0."""
    from repro.core.dist_tlr import dist_compress_tiles
    from repro.distribution.block_cyclic import pair_layout

    locs = _locs(8)
    params = MaternParams.bivariate(a=0.09, nu11=0.6, nu22=1.2, beta=0.4)
    z = simulate_mgrf(jax.random.PRNGKey(0), locs, params, nugget=1e-8)[0]
    cfg = MLEConfig(p=2, profile=False, backend="tlr", tile_size=32,
                    tlr_max_rank=12, nugget=1e-8, morton=False,
                    dist_tlr_from_tiles=True, block_cyclic=True)
    x = pack_params(params, profile=False)
    _, aux = make_objective(locs, z, cfg, with_aux=True)[0](x)
    t = dist_compress_tiles(locs, params, tile_size=32, max_rank=12,
                            nugget=1e-8, layout=pair_layout(4, 1))
    assert int(aux.clamped) == 0
    assert int(jnp.max(t.ranks)) <= int(aux.max_rank) <= 12
    exact = dataclasses.replace(cfg, backend="exact")
    _, aux = make_objective(locs, z, exact, with_aux=True)[0](x)
    assert int(aux.max_rank) == 0

def test_mle_objective_generator_direct_skips_dense_distances(monkeypatch):
    """Non-profile generator-direct backends never build the (n, n) distance
    matrix — at production n it would be the fit's largest allocation."""
    import repro.core.mle as M

    def boom(*a, **k):
        raise AssertionError("dense pairwise_distances was called")

    monkeypatch.setattr(M, "pairwise_distances", boom)
    locs = _locs(8)
    params = MaternParams.bivariate(a=0.09, nu11=0.5, nu22=1.5, beta=0.4)
    z = simulate_mgrf(jax.random.PRNGKey(0), locs, params, nugget=1e-8)[0]
    x = pack_params(params, profile=False)
    for knob in ("tlr_from_tiles", "dist_tlr_from_tiles"):
        cfg = MLEConfig(p=2, profile=False, backend="tlr", tile_size=32,
                        nugget=1e-8, morton=False, **{knob: True})
        obj, dists = make_objective(locs, z, cfg)
        assert dists is None
        assert np.isfinite(float(obj(x)))


def test_choose_tile_size_multiple_of():
    for m, p in ((512, 2), (192, 3), (1000, 2)):
        nb = T.choose_tile_size(m, multiple_of=p)
        assert m % nb == 0 and nb % p == 0
    # exact target hits return the target itself
    assert T.choose_tile_size(512, 64) == 64
    assert T.choose_tile_size(512, 64, multiple_of=2) == 64
    with pytest.raises(ValueError):
        T.choose_tile_size(1001, multiple_of=2)


def test_choose_tile_size_no_divisor_raises_clearly():
    """When no divisor survives the multiple_of filter the failure names m,
    target, and multiple_of — it used to return None and crash far
    downstream with an opaque TypeError."""
    with pytest.raises(ValueError, match=r"m=0.*multiple_of=1.*target=16"):
        T.choose_tile_size(0, 16)


def test_traced_nugget_loglik_and_grad_under_jit():
    """A traced nugget — the MLE estimating it under jit — must evaluate and
    differentiate through both generator-direct likelihoods (the `if
    nugget:` truthiness checks used to raise TracerBoolConversionError, and
    the QR/SVD derivatives used to NaN on the zero-padded rank columns).
    The gradient is checked against central finite differences."""
    from repro.core.dist_tlr import dist_tlr_loglik

    locs = _locs(6)
    params = MaternParams.bivariate(a=0.09, nu11=0.5, nu22=1.5, beta=0.5)
    z = simulate_mgrf(jax.random.PRNGKey(0), locs, params, nugget=1e-4)[0]
    lj = jnp.asarray(locs)
    kw = dict(tol=1e-7, max_rank=8, tile_size=24)   # 2*kmax <= nb: tall QR

    f = jax.jit(lambda ng: T.tlr_loglik(None, z, params, nugget=ng, locs=lj,
                                        from_tiles=True, **kw).loglik)
    g = jax.jit(jax.grad(lambda ng: T.tlr_loglik(
        None, z, params, nugget=ng, locs=lj, from_tiles=True, **kw).loglik))
    ng0, eps = 1e-3, 1e-6
    fd = (float(f(jnp.asarray(ng0 + eps))) -
          float(f(jnp.asarray(ng0 - eps)))) / (2 * eps)
    gv = float(g(jnp.asarray(ng0)))
    assert np.isfinite(gv)
    assert gv == pytest.approx(fd, rel=1e-4, abs=1e-6)

    for bc in (False, True):
        gd = jax.jit(jax.grad(lambda ng: dist_tlr_loglik(
            None, z, locs=lj, params=params, from_tiles=True, nugget=ng,
            block_cyclic=bc, **kw).loglik))
        gdv = float(gd(jnp.asarray(ng0)))
        assert np.isfinite(gdv)
        assert gdv == pytest.approx(fd, rel=1e-4, abs=1e-6), bc


def test_recompress_grad_matches_finite_differences():
    """The guarded QR/SVD derivatives (_safe_qr / _core_svd) agree with
    finite differences both at full rank and — the production case — with
    zero-padded rank columns, where the textbook rules NaN."""
    rng = np.random.default_rng(0)
    arrs = [jnp.asarray(rng.normal(size=(3, 16, 4))) for _ in range(4)]

    def loss(s, pads):
        u1, v1, u2, v2 = (a.at[:, :, 2:].set(0.0) if pads else a
                          for a in arrs)
        un, vn, _ = T._batched_recompress(u1 * s, v1, u2, v2, 1e-7, 1.0)
        return jnp.sum(un ** 2) + jnp.sum(vn ** 2)

    for pads in (False, True):
        g = float(jax.grad(loss)(jnp.asarray(1.0), pads))
        e = 1e-6
        fd = (float(loss(jnp.asarray(1.0 + e), pads)) -
              float(loss(jnp.asarray(1.0 - e), pads))) / (2 * e)
        assert np.isfinite(g), pads
        assert g == pytest.approx(fd, rel=1e-5), pads
