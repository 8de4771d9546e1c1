"""Distributed geostat paths (single-device numerics) + multi-device
subprocess tests for sharding/compression/elastic restore."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import MaternParams, exact_loglik, pairwise_distances
from repro.core import tlr as T
from repro.core.covariance import build_sigma, morton_order
from repro.core.dist_cholesky import (_dist_loglik_body, blocked_cholesky,
                                      blocked_cholesky_panels,
                                      dist_cokrige_lowerable,
                                      dist_exact_loglik, forward_substitution,
                                      panels_backward_solve)
from repro.core.dist_tlr import (PairTLR, dist_compress_tiles,
                                 dist_tlr_cholesky, dist_tlr_loglik,
                                 dist_tlr_lowerable)
from repro.core.simulate import grid_locations, simulate_mgrf
from repro.distribution.block_cyclic import (grid_to_pairs, pair_layout,
                                             pairs_to_grid)


def _setup(n_side=12, a=0.09):
    locs = grid_locations(n_side, jitter=0.2, seed=0)
    locs = np.asarray(locs)[morton_order(locs)]
    params = MaternParams.bivariate(a=a, nu11=0.5, nu22=1.0, beta=0.5)
    dists = pairwise_distances(locs)
    sigma = build_sigma(None, params, dists=dists, nugget=1e-8)
    return locs, params, dists, sigma


def test_blocked_cholesky_matches_lapack():
    _, _, _, sigma = _setup()
    for panel in (32, 96, 288):
        got = np.asarray(blocked_cholesky(sigma, panel))
        want = np.asarray(jnp.linalg.cholesky(sigma))
        np.testing.assert_allclose(got, want, atol=1e-8)


def test_forward_substitution():
    _, _, _, sigma = _setup()
    lfac = jnp.linalg.cholesky(sigma)
    rng = np.random.default_rng(0)
    z = jnp.asarray(rng.normal(size=sigma.shape[0]))
    got = np.asarray(forward_substitution(lfac, z, panel=32))
    want = np.asarray(jax.scipy.linalg.solve_triangular(lfac, z,
                                                        lower=True))
    np.testing.assert_allclose(got, want, atol=1e-9)


def test_panel_form_loglik_matches_dense_assembly():
    """The distributed loglik body stays in panel form (no (m, m) factor
    round-trip) and equals the dense-assembly formulation exactly: same
    POTRF/TRSM/SYRK dataflow, only the storage differs."""
    import math as _math

    locs, params, dists, sigma = _setup()
    z = simulate_mgrf(jax.random.PRNGKey(4), locs, params, nugget=1e-8)[0]
    panel = 36
    got = _dist_loglik_body(dists, z, params, 1e-8, panel, "I", None)
    chol = blocked_cholesky(sigma, panel)
    alpha = forward_substitution(chol, z, panel)
    quad = float(jnp.sum(alpha * alpha))
    logdet = float(2.0 * jnp.sum(jnp.log(jnp.diagonal(chol))))
    want = -0.5 * (z.shape[-1] * _math.log(2.0 * _math.pi) + logdet + quad)
    assert float(got.logdet) == pytest.approx(logdet, rel=1e-12)
    assert float(got.quad) == pytest.approx(quad, rel=1e-10)
    assert float(got.loglik) == pytest.approx(want, rel=1e-12)


def test_panels_backward_solve_matches_dense():
    """panels_backward_solve solves L^T x = y against the LAPACK factor."""
    _, _, _, sigma = _setup()
    panel = 48
    panels = blocked_cholesky_panels(sigma, panel)
    lfac = jnp.linalg.cholesky(sigma)
    rng = np.random.default_rng(2)
    y = jnp.asarray(rng.normal(size=sigma.shape[0]))
    got = np.asarray(panels_backward_solve(panels, y, panel))
    want = np.asarray(jax.scipy.linalg.solve_triangular(lfac.T, y,
                                                        lower=False))
    np.testing.assert_allclose(got, want, atol=1e-8)
    # multi-RHS path
    ym = jnp.asarray(rng.normal(size=(sigma.shape[0], 3)))
    got = np.asarray(panels_backward_solve(panels, ym, panel))
    want = np.asarray(jax.scipy.linalg.solve_triangular(lfac.T, ym,
                                                        lower=False))
    np.testing.assert_allclose(got, want, atol=1e-8)


def test_dist_cokrige_lowerable_panel_form_matches_dense():
    """The dry-run cokriging cell (now panel form end-to-end) reproduces the
    dense c0^T Sigma^{-1} z predictor."""
    from repro.core.covariance import build_c0

    locs, params, dists, sigma = _setup(n_side=8)
    n = locs.shape[0]
    n_pred = 6
    rng = np.random.default_rng(9)
    pred_locs = jnp.asarray(rng.uniform(size=(n_pred, 2)))
    z = simulate_mgrf(jax.random.PRNGKey(6), locs, params, nugget=1e-8)[0]
    fn, specs = dist_cokrige_lowerable(n, n_pred, params.p, params, panel=32,
                                       mesh=None, nugget=1e-8,
                                       dtype=jnp.float64)
    assert specs[0].shape == (n, 2) and specs[1].shape == (n_pred, 2)
    got = np.asarray(fn(jnp.asarray(locs), pred_locs, z))
    alpha = jnp.linalg.solve(sigma, z)
    c0 = build_c0(pred_locs, jnp.asarray(locs), params)     # (npred, pn, p)
    want = np.asarray(jnp.einsum("lrp,r->lp", c0, alpha))
    np.testing.assert_allclose(got, want, rtol=1e-7, atol=1e-9)


def test_dist_exact_loglik_matches_dense():
    locs, params, dists, _ = _setup()
    z = simulate_mgrf(jax.random.PRNGKey(1), locs, params, nugget=1e-8)[0]
    want = float(exact_loglik(None, z, params, dists=dists,
                              nugget=1e-8).loglik)
    got = float(dist_exact_loglik(dists, z, params, nugget=1e-8,
                                  panel=36).loglik)
    assert got == pytest.approx(want, rel=1e-9)


def test_dist_tlr_cholesky_matches_single_host():
    """fori_loop masked-grid TLR == static-pair-batch scan TLR (the two
    batchings of the shared panel body give the same math AND ranks)."""
    _, _, _, sigma = _setup()
    t = T.tlr_compress(sigma, tile_size=48, tol=1e-9, max_rank=48)
    ref = T.tlr_cholesky(t, tol=1e-11, scale=1.0)
    diag_l, u, v, ranks = dist_tlr_cholesky(t.diag, t.u, t.v, t.ranks,
                                            tol=1e-11, scale=1.0)
    np.testing.assert_allclose(np.asarray(diag_l), np.asarray(ref.diag),
                               atol=1e-7)
    assert np.array_equal(np.asarray(ranks), np.asarray(ref.ranks))
    # Compare reconstructed off-diagonal factor tiles (UV is gauge-dependent,
    # the product is not).
    Tn = t.n_tiles
    for i in range(Tn):
        for j in range(i):
            got = np.asarray(u[i, j] @ v[i, j].T)
            want = np.asarray(ref.u[i, j] @ ref.v[i, j].T)
            np.testing.assert_allclose(got, want, atol=1e-7)


def test_dist_tlr_loglik_matches_exact():
    locs, params, dists, sigma = _setup()
    z = simulate_mgrf(jax.random.PRNGKey(2), locs, params, nugget=1e-8)[0]
    t = T.tlr_compress(sigma, tile_size=48, tol=1e-10, max_rank=48)
    got = float(dist_tlr_loglik(t, z, tol=1e-12, scale=1.0).loglik)
    want = float(exact_loglik(None, z, params, dists=dists,
                              nugget=1e-8).loglik)
    assert got == pytest.approx(want, rel=1e-6)


def _tiles_m512():
    """m = 512, T = 8 compressed tiles + the dense Cholesky reference."""
    locs = grid_locations(16, jitter=0.2, seed=0)          # 256 locs, m = 512
    locs = np.asarray(locs)[morton_order(locs)]
    params = MaternParams.bivariate(a=0.09, nu11=0.5, nu22=1.0, beta=0.5)
    dists = pairwise_distances(locs)
    sigma = build_sigma(None, params, dists=dists, nugget=1e-8)
    t = T.tlr_compress(sigma, tile_size=64, tol=1e-10, max_rank=48)
    return t, sigma


def test_block_cyclic_cholesky_matches_masked_and_dense():
    """m = 512: the block-cyclic pair-batch factorization == the masked
    full-grid one (values AND ranks), and both reconstruct the dense
    Cholesky factor to TLR accuracy."""
    t, sigma = _tiles_m512()
    ref = dist_tlr_cholesky(t.diag, t.u, t.v, t.ranks, tol=1e-12, scale=1.0)
    got = dist_tlr_cholesky(t.diag, t.u, t.v, t.ranks, tol=1e-12, scale=1.0,
                            block_cyclic=True)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref[0]),
                               atol=1e-8)
    assert np.array_equal(np.asarray(got[3]), np.asarray(ref[3]))
    Tn, nb = t.n_tiles, t.tile_size
    dense_l = np.asarray(jnp.linalg.cholesky(sigma))
    for i in range(Tn):
        for j in range(i):
            blk = np.asarray(got[1][i, j] @ got[2][i, j].T)
            np.testing.assert_allclose(
                blk, np.asarray(ref[1][i, j] @ ref[2][i, j].T), atol=1e-8)
            np.testing.assert_allclose(
                blk, dense_l[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb],
                atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(got[0][i]),
            dense_l[i * nb:(i + 1) * nb, i * nb:(i + 1) * nb], atol=1e-5)


def test_block_cyclic_cholesky_super_panels():
    """Two-level block-cyclic factorization == single-level, ranks
    included (the shrinking-pair-layout slot remap is exact)."""
    t, _ = _tiles_m512()
    one = dist_tlr_cholesky(t.diag, t.u, t.v, t.ranks, tol=1e-12, scale=1.0,
                            block_cyclic=True)
    two = dist_tlr_cholesky(t.diag, t.u, t.v, t.ranks, tol=1e-12, scale=1.0,
                            block_cyclic=True, super_panels=2)
    np.testing.assert_allclose(np.asarray(two[0]), np.asarray(one[0]),
                               atol=1e-8)
    assert np.array_equal(np.asarray(two[3]), np.asarray(one[3]))
    for i in range(t.n_tiles):
        for j in range(i):
            np.testing.assert_allclose(
                np.asarray(two[1][i, j] @ two[2][i, j].T),
                np.asarray(one[1][i, j] @ one[2][i, j].T), atol=1e-8)


# ---------------------------------------------------------------------------
# Streaming generator-direct pipeline (dist_compress_tiles -> dist_tlr_loglik)
# ---------------------------------------------------------------------------


def test_dist_compress_tiles_matches_single_host():
    """The sharded column-panel compression reproduces tlr_compress_tiles
    (same tiles, same real ranks) on one device."""
    locs = grid_locations(8, jitter=0.2, seed=0)
    locs = np.asarray(locs)[morton_order(locs)]
    params = MaternParams.bivariate(a=0.09, nu11=0.5, nu22=1.5, beta=0.5)
    want = T.tlr_compress_tiles(locs, params, tile_size=32, tol=1e-7,
                                max_rank=32, nugget=1e-8)
    got = dist_compress_tiles(locs, params, tile_size=32, tol=1e-7,
                              max_rank=32, nugget=1e-8)
    assert np.array_equal(np.asarray(got.ranks), np.asarray(want.ranks))
    np.testing.assert_allclose(np.asarray(T.tlr_to_dense(got)),
                               np.asarray(T.tlr_to_dense(want)),
                               rtol=1e-10, atol=1e-10)


def test_dist_tlr_loglik_from_tiles_matches_exact():
    """Acceptance: m = 512 generator-direct distributed likelihood within
    1e-3 of the dense exact one (it lands far tighter in practice)."""
    locs = grid_locations(16, jitter=0.2, seed=0)          # 256 locs, m = 512
    locs = np.asarray(locs)[morton_order(locs)]
    params = MaternParams.bivariate(a=0.09, nu11=0.5, nu22=1.0, beta=0.5)
    z = simulate_mgrf(jax.random.PRNGKey(5), locs, params, nugget=1e-8)[0]
    want = float(exact_loglik(locs, z, params, nugget=1e-8).loglik)
    got = float(dist_tlr_loglik(None, z, locs=locs, params=params,
                                from_tiles=True, tile_size=64, max_rank=64,
                                nugget=1e-8, tol=1e-7).loglik)
    assert abs(got - want) <= 1e-3 * abs(want)


def test_dist_tlr_loglik_from_tiles_super_panels():
    """The two-level (super-panel) factorization gives the same generator-
    direct likelihood as the single-level fori_loop."""
    locs = grid_locations(16, jitter=0.2, seed=0)
    locs = np.asarray(locs)[morton_order(locs)]
    params = MaternParams.bivariate(a=0.09, nu11=0.5, nu22=1.0, beta=0.5)
    z = simulate_mgrf(jax.random.PRNGKey(5), locs, params, nugget=1e-8)[0]
    one = float(dist_tlr_loglik(None, z, locs=locs, params=params,
                                from_tiles=True, tile_size=64, max_rank=64,
                                nugget=1e-8, tol=1e-7).loglik)
    two = float(dist_tlr_loglik(None, z, locs=locs, params=params,
                                from_tiles=True, tile_size=64, max_rank=64,
                                nugget=1e-8, tol=1e-7,
                                super_panels=2).loglik)
    assert two == pytest.approx(one, rel=1e-9)


def test_dist_tlr_loglik_block_cyclic_matches_masked():
    """m = 512 acceptance for the pair-native path: the block-cyclic
    generator-direct likelihood equals the masked-grid one bit-for-bit-ish
    and stays within 1e-3 of the dense exact likelihood; col_block groups
    change nothing."""
    locs = grid_locations(16, jitter=0.2, seed=0)
    locs = np.asarray(locs)[morton_order(locs)]
    params = MaternParams.bivariate(a=0.09, nu11=0.5, nu22=1.0, beta=0.5)
    z = simulate_mgrf(jax.random.PRNGKey(5), locs, params, nugget=1e-8)[0]
    want = float(exact_loglik(locs, z, params, nugget=1e-8).loglik)
    kw = dict(locs=locs, params=params, from_tiles=True, tile_size=64,
              max_rank=64, nugget=1e-8, tol=1e-7)
    masked = float(dist_tlr_loglik(None, z, **kw).loglik)
    bc = float(dist_tlr_loglik(None, z, block_cyclic=True, **kw).loglik)
    bc_grouped = float(dist_tlr_loglik(None, z, block_cyclic=True,
                                       super_panels=2, col_block=2,
                                       **kw).loglik)
    assert abs(bc - want) <= 1e-3 * abs(want)
    assert bc == pytest.approx(masked, rel=1e-9)
    assert bc_grouped == pytest.approx(masked, rel=1e-9)


def test_dist_compress_tiles_pair_native_matches_grid():
    """Pair-major compression scatters the same tiles/ranks the grid form
    produces, for several shard counts and column groupings."""
    locs = grid_locations(8, jitter=0.2, seed=0)
    locs = np.asarray(locs)[morton_order(locs)]
    params = MaternParams.bivariate(a=0.09, nu11=0.5, nu22=1.5, beta=0.5)
    want = dist_compress_tiles(locs, params, tile_size=32, tol=1e-7,
                               max_rank=32, nugget=1e-8)
    for shards, cb in ((1, 1), (4, 1), (4, 2)):
        lay = pair_layout(want.n_tiles, shards)
        got = dist_compress_tiles(locs, params, tile_size=32, tol=1e-7,
                                  max_rank=32, nugget=1e-8, layout=lay,
                                  col_block=cb)
        assert isinstance(got, PairTLR)
        assert got.u.shape == (lay.length, 32, 32)
        assert np.array_equal(np.asarray(pairs_to_grid(got.ranks, lay)),
                              np.asarray(want.ranks))
        np.testing.assert_allclose(np.asarray(got.diag),
                                   np.asarray(want.diag), atol=1e-11)
        np.testing.assert_allclose(
            np.asarray(T.tlr_to_dense(got.to_grid(lay))),
            np.asarray(T.tlr_to_dense(want)), rtol=1e-9, atol=1e-9)


def test_block_cyclic_pipeline_never_densifies(monkeypatch):
    """The pair-native streaming path must not call the dense assembly
    routine, must never materialize the (T, T) tile grid, and no output
    may reach the dense m*m size."""
    import repro.core.covariance as C
    import repro.core.dist_cholesky as DC

    def boom(*a, **k):
        raise AssertionError("dense build_sigma was called")

    monkeypatch.setattr(C, "build_sigma", boom)
    monkeypatch.setattr(T, "build_sigma", boom)
    monkeypatch.setattr(DC, "build_sigma", boom)
    locs = grid_locations(16, jitter=0.2, seed=0)
    locs = np.asarray(locs)[morton_order(locs)]
    params = MaternParams.bivariate(a=0.09, nu11=0.5, nu22=1.5, beta=0.4)
    lay = pair_layout(8, 4)
    t = dist_compress_tiles(locs, params, tile_size=64, tol=1e-7, max_rank=32,
                            nugget=1e-8, layout=lay)
    m = t.shape[0]
    assert m == 512
    grid_elems = t.n_tiles * t.n_tiles * t.tile_size * t.max_rank
    for arr in (t.diag, t.u, t.v):
        assert arr.size < m * m, (arr.shape, m)
        assert arr.size < grid_elems, (arr.shape, grid_elems)
    # pair-major strict-lower storage is ~half the grid
    assert t.u.shape == (lay.length, 64, 32)
    # the factorization + solve stay pair-native (monkeypatched boom still
    # armed) and reproduce the masked-grid loglik; the PairTLR carries the
    # shard count it was scattered for, so no layout needs to be re-passed
    assert t.n_shards == lay.n_shards
    z = jnp.asarray(np.random.default_rng(3).normal(size=m))
    got = float(dist_tlr_loglik(t, z, tol=1e-9, scale=1.0).loglik)
    grid = dist_compress_tiles(locs, params, tile_size=64, tol=1e-7,
                               max_rank=32, nugget=1e-8)
    want = float(dist_tlr_loglik(grid, z, tol=1e-9, scale=1.0).loglik)
    assert got == pytest.approx(want, rel=1e-9)
    # an explicit layout with a different slot order is rejected loudly
    with pytest.raises(ValueError, match="n_shards"):
        dist_tlr_loglik(t, z, tol=1e-9, scale=1.0, layout=pair_layout(8, 1))


def test_dist_pipeline_never_densifies(monkeypatch):
    """The streaming path must not call the dense assembly routine, and no
    component of its output may reach the dense m*m size (mirrors
    tests/test_tlr_tiles.py for the single-device path)."""
    import repro.core.covariance as C
    import repro.core.dist_cholesky as DC

    def boom(*a, **k):
        raise AssertionError("dense build_sigma was called")

    monkeypatch.setattr(C, "build_sigma", boom)
    monkeypatch.setattr(T, "build_sigma", boom)
    monkeypatch.setattr(DC, "build_sigma", boom)
    locs = grid_locations(16, jitter=0.2, seed=0)
    locs = np.asarray(locs)[morton_order(locs)]
    params = MaternParams.bivariate(a=0.09, nu11=0.5, nu22=1.5, beta=0.4)
    t = dist_compress_tiles(locs, params, tile_size=64, tol=1e-7, max_rank=32,
                            nugget=1e-8)
    m = t.shape[0]
    assert m == 512
    for arr in (t.diag, t.u, t.v):
        assert arr.size < m * m, (arr.shape, m)


def test_dist_tlr_lowerable_threads_real_ranks():
    """The dry-run lowerable takes ranks as a real input (no fabricated
    zeros) and reproduces dist_tlr_loglik on concrete tiles."""
    _, _, _, sigma = _setup()
    rng = np.random.default_rng(7)
    z = jnp.asarray(rng.normal(size=sigma.shape[0]))
    t = T.tlr_compress(sigma, tile_size=48, tol=1e-10, max_rank=48)
    fn, specs = dist_tlr_lowerable(t.n_tiles, t.tile_size, t.max_rank,
                                   tol=1e-12, mesh=None)
    assert len(specs) == 5
    assert specs[3].shape == (t.n_tiles, t.n_tiles)
    assert specs[3].dtype == jnp.int32
    got = float(fn(t.diag, t.u, t.v, t.ranks, z).loglik)
    want = float(dist_tlr_loglik(t, z, tol=1e-12, scale=1.0).loglik)
    assert got == pytest.approx(want, rel=1e-12)


def test_dist_tlr_lowerable_block_cyclic_pair_specs():
    """block_cyclic=True lowerables take pair-major inputs; return_factor
    jitted with donated tile args aliases them into the factor outputs
    (alias_size_in_bytes > 0 — the donate/alias temp-footprint fix)."""
    _, _, _, sigma = _setup()
    rng = np.random.default_rng(7)
    z = jnp.asarray(rng.normal(size=sigma.shape[0]))
    t = T.tlr_compress(sigma, tile_size=48, tol=1e-10, max_rank=48)
    lay = pair_layout(t.n_tiles, 1)
    fn, specs = dist_tlr_lowerable(t.n_tiles, t.tile_size, t.max_rank,
                                   tol=1e-12, mesh=None, block_cyclic=True)
    assert specs[1].shape == (lay.length, t.tile_size, t.max_rank)
    assert specs[3].shape == (lay.length,)
    up, vp, rp = (grid_to_pairs(x, lay) for x in (t.u, t.v, t.ranks))
    got = float(fn(t.diag, up, vp, rp, z).loglik)
    want = float(dist_tlr_loglik(t, z, tol=1e-12, scale=1.0).loglik)
    assert got == pytest.approx(want, rel=1e-12)

    fn_f, specs_f = dist_tlr_lowerable(t.n_tiles, t.tile_size, t.max_rank,
                                       tol=1e-12, mesh=None,
                                       block_cyclic=True, return_factor=True)
    comp = jax.jit(fn_f, donate_argnums=(0, 1, 2, 3)).lower(
        *specs_f).compile()
    ms = comp.memory_analysis()
    assert int(ms.alias_size_in_bytes) > 0


# ---------------------------------------------------------------------------
# Multi-device behaviour via subprocesses (fake CPU devices).
# ---------------------------------------------------------------------------

_SUBPROC_PREAMBLE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={ndev}"
import sys
sys.path.insert(0, {src!r})
import jax
import jax.numpy as jnp
import numpy as np
from repro.launch.mesh import auto_mesh
"""


def _run_subprocess(body: str, ndev: int = 8):
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    code = _SUBPROC_PREAMBLE.format(ndev=ndev, src=os.path.abspath(src)) + \
        textwrap.dedent(body)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


def test_compressed_psum_multidevice():
    """int8 error-feedback psum over a 'pod' axis of 8 fake devices."""
    out = _run_subprocess("""
    from jax.sharding import PartitionSpec as P
    from repro.distribution.compression import compressed_psum
    mesh = auto_mesh((8,), ("pod",))
    rng = np.random.default_rng(0)
    g = {"a": jnp.asarray(rng.normal(size=(64, 32)), jnp.float32),
         "b": jnp.asarray(rng.normal(size=(257,)), jnp.float32)}
    got, errs = compressed_psum(g, mesh, "pod")
    # all pods contribute the same g -> mean == g up to int8 quantization
    for k in g:
        err = np.abs(np.asarray(got[k]) - np.asarray(g[k])).max()
        scale = np.abs(np.asarray(g[k])).max() / 127.0
        assert err <= scale * 1.01, (k, err, scale)
        assert np.abs(np.asarray(errs[k])).max() <= scale * 1.01
    print("OK")
    """)
    assert "OK" in out


def test_train_step_shards_multidevice():
    """A reduced train step lowers + runs on a (2, 4) = (data, model) mesh."""
    out = _run_subprocess("""
    import jax.numpy as jnp
    from repro.configs import get_arch
    from repro.models import init_model
    from repro.training.train_step import TrainConfig, make_train_step
    from repro.training.optimizer import adamw_init
    from repro.distribution.sharding import shard_params
    from repro.dataio.tokens import SyntheticTokens

    cfg = get_arch("qwen3-4b").reduced()
    mesh = auto_mesh((2, 4), ("data", "model"))
    tcfg = TrainConfig(remat=False)
    step = make_train_step(cfg, mesh, tcfg)
    params = shard_params(init_model(jax.random.PRNGKey(0), cfg), cfg, mesh)
    opt = adamw_init(params)
    data = SyntheticTokens(cfg.vocab_size, 32, 8)
    batch = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
    params, opt, errs, metrics = step(params, opt, None, batch)
    assert np.isfinite(float(metrics["loss"]))
    print("LOSS", float(metrics["loss"]))
    """)
    assert "LOSS" in out


def test_elastic_checkpoint_restore_across_topologies(tmp_path):
    """Save on 1 device, restore resharded onto 8 (elastic scaling)."""
    body1 = f"""
    from repro.configs import get_arch
    from repro.models import init_model
    from repro.checkpointing.checkpoint import save_checkpoint
    cfg = get_arch("yi-6b").reduced()
    params = init_model(jax.random.PRNGKey(5), cfg)
    save_checkpoint({str(tmp_path)!r}, 3, dict(params=params))
    print("SAVED")
    """
    out1 = _run_subprocess(body1, ndev=1)
    assert "SAVED" in out1

    body2 = f"""
    from repro.configs import get_arch
    from repro.models import init_model
    from repro.checkpointing.checkpoint import restore_checkpoint
    from repro.distribution.sharding import param_specs, shardings_of
    cfg = get_arch("yi-6b").reduced()
    mesh = auto_mesh((2, 4), ("data", "model"))
    target = dict(params=init_model(jax.random.PRNGKey(0), cfg))
    sh = dict(params=shardings_of(param_specs(cfg), mesh))
    restored, manifest = restore_checkpoint({str(tmp_path)!r}, target,
                                            shardings=sh)
    assert manifest["step"] == 3
    leaf = jax.tree.leaves(restored)[0]
    assert len(leaf.sharding.device_set) in (1, 2, 4, 8)
    print("RESTORED", manifest["step"])
    """
    out2 = _run_subprocess(body2, ndev=8)
    assert "RESTORED 3" in out2


def test_dist_tlr_pipeline_multidevice():
    """The full generator-direct pipeline (locs -> compress -> factorize ->
    loglik) compiles and runs SPMD on a (2, 4) = (data, model) mesh in BOTH
    batching forms — masked full-grid and block-cyclic pair-batch — and
    matches the dense exact likelihood; the two factorizations agree on
    values and ranks on the 8-device mesh (m = 512)."""
    out = _run_subprocess("""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import MaternParams, exact_loglik
    from repro.core.covariance import morton_order
    from repro.core.dist_tlr import (dist_compress_tiles, dist_tlr_cholesky,
                                     dist_tlr_pipeline_lowerable)
    from repro.core.simulate import grid_locations, simulate_mgrf

    mesh = auto_mesh((2, 4), ("data", "model"))
    locs = grid_locations(16, jitter=0.2, seed=0)      # 256 locs, m = 512
    locs = np.asarray(locs)[morton_order(locs)]
    params = MaternParams.bivariate(a=0.09, nu11=0.5, nu22=1.0, beta=0.5,
                                    dtype=jnp.float32)
    z = simulate_mgrf(jax.random.PRNGKey(5), locs, params, nugget=1e-6)[0]
    want = float(exact_loglik(locs.astype(np.float32), z, params,
                              nugget=1e-6).loglik)
    sh = (NamedSharding(mesh, P("data", None)),
          NamedSharding(mesh, P("data")))
    lls = {}
    for bc in (False, True):
        fn, specs = dist_tlr_pipeline_lowerable(
            256, 2, params, tile_size=64, max_rank=32, tol=1e-7, nugget=1e-6,
            gen="xla", mesh=mesh, row_axes=("data",), block_cyclic=bc)
        jitted = jax.jit(fn, in_shardings=sh)
        got = float(jitted(jnp.asarray(locs, jnp.float32), z).loglik)
        assert abs(got - want) <= 1e-3 * abs(want), (bc, got, want)
        lls[bc] = got
    assert abs(lls[True] - lls[False]) <= 1e-5 * abs(want), lls

    # factorization forms agree (values + ranks) on the 8-device mesh
    t = dist_compress_tiles(locs.astype(np.float32), params, tile_size=64,
                            tol=1e-9, max_rank=48, nugget=1e-6, mesh=mesh)
    ref = dist_tlr_cholesky(t.diag, t.u, t.v, t.ranks, tol=1e-11, scale=1.0,
                            mesh=mesh)
    got = dist_tlr_cholesky(t.diag, t.u, t.v, t.ranks, tol=1e-11, scale=1.0,
                            mesh=mesh, block_cyclic=True)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref[0]),
                               atol=1e-5)
    assert np.array_equal(np.asarray(got[3]), np.asarray(ref[3]))
    for i in range(t.diag.shape[0]):
        for j in range(i):
            np.testing.assert_allclose(
                np.asarray(got[1][i, j] @ got[2][i, j].T),
                np.asarray(ref[1][i, j] @ ref[2][i, j].T), atol=1e-5)
    print("PIPELINE", lls[True])
    """)
    assert "PIPELINE" in out


def test_super_panel_tlr_matches_single_level():
    """Two-level (super-panel) TLR Cholesky == single-level fori version,
    including the threaded per-tile ranks."""
    _, _, _, sigma = _setup()
    t = T.tlr_compress(sigma, tile_size=48, tol=1e-10, max_rank=48)
    d1, u1, v1, r1 = dist_tlr_cholesky(t.diag, t.u, t.v, t.ranks,
                                       tol=1e-12, scale=1.0)
    d2, u2, v2, r2 = dist_tlr_cholesky(t.diag, t.u, t.v, t.ranks,
                                       tol=1e-12, scale=1.0, super_panels=3)
    np.testing.assert_allclose(np.asarray(d2), np.asarray(d1), atol=1e-8)
    assert np.array_equal(np.asarray(r2), np.asarray(r1))
    Tn = t.n_tiles
    for i in range(Tn):
        for j in range(i):
            got = np.asarray(u2[i, j] @ v2[i, j].T)
            want = np.asarray(u1[i, j] @ v1[i, j].T)
            np.testing.assert_allclose(got, want, atol=1e-8)


def test_dist_cholesky_lowerable_donates_in_place():
    """The donated dense-Cholesky lowerable must (a) match LAPACK, (b) alias
    its donated Sigma buffer on every device — the in-place .at[] POTRF/
    TRSM/SYRK chain exists precisely because the panel-assembly form's
    fresh output buffer defeats donation under SPMD — and (c) pass the
    R2 donation lint with zero errors."""
    out = _run_subprocess("""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.dist_cholesky import dist_cholesky_lowerable
    from repro.analysis import lint_lowerable

    m, panel = 256, 64
    mesh = auto_mesh((8, 1), ("data", "model"))
    fn, specs = dist_cholesky_lowerable(m, panel=panel, mesh=mesh,
                                        dtype=jnp.float32)
    sh = (NamedSharding(mesh, P("data", "model")),)
    comp = jax.jit(fn, in_shardings=sh,
                   donate_argnums=(0,)).lower(*specs).compile()
    ms = comp.memory_analysis()
    per_device = m * m * 4 // len(jax.devices())
    assert ms.alias_size_in_bytes >= per_device, (
        ms.alias_size_in_bytes, per_device)

    rng = np.random.default_rng(0)
    a = rng.normal(size=(m, m))
    sigma = (a @ a.T + m * np.eye(m)).astype(np.float32)
    want = np.linalg.cholesky(sigma)
    got = np.asarray(comp(jnp.asarray(sigma)))
    np.testing.assert_allclose(got, want, atol=5e-4)

    rep = lint_lowerable(fn, specs, mesh=mesh, in_shardings=sh,
                         donate_argnums=(0,))
    assert rep.summary["errors"] == 0, rep.summary
    assert rep.summary["undonated_dead_bytes"] == 0, rep.summary
    print("ALIAS", int(ms.alias_size_in_bytes))
    """)
    assert "ALIAS" in out
