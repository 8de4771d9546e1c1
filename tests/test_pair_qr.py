"""Pair-axis-sharded recompression (distribution/pair_qr.py): the shard_map
form must be a pure re-placement of core.tlr._batched_recompress, and the
block-cyclic factorization with it active must match the masked and dense
references."""
import functools
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import MaternParams, pairwise_distances
from repro.core import tlr as T
from repro.core.covariance import build_sigma, morton_order
from repro.core.dist_tlr import dist_tlr_cholesky, dist_tlr_cholesky_pairs
from repro.core.simulate import grid_locations
from repro.core.tlr import _batched_recompress
from repro.distribution.block_cyclic import (grid_to_pairs, pair_layout,
                                             pairs_to_grid)
from repro.distribution.pair_qr import pair_shard_count, sharded_recompress
from repro.launch.mesh import auto_mesh


def _pair_batch(length, nb=16, kmax=4, n_pad=3, seed=0):
    """Random (length, nb, kmax) U/V/dU/dV with zeroed trailing pad slots —
    the shape the block-cyclic panel body feeds the recompress."""
    rng = np.random.default_rng(seed)
    arrs = [jnp.asarray(rng.normal(size=(length, nb, kmax)))
            for _ in range(4)]
    return tuple(a.at[length - n_pad:].set(0.0) for a in arrs)


def _assert_matches(got, want, atol=1e-10):
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=atol)


def test_fallback_without_mesh_is_batched_recompress():
    up, vp, du, dv = _pair_batch(12)
    want = _batched_recompress(up, vp, du, dv, 1e-7, 1.0)
    got = sharded_recompress(up, vp, du, dv, 1e-7, 1.0)
    _assert_matches(got, want, atol=0.0)
    assert pair_shard_count(None, ("data",)) == 1


def test_shard_map_single_device_mesh_matches():
    """A 1-device mesh genuinely routes through shard_map (not the
    fallback) and reproduces the replicated batch, pad slots included."""
    up, vp, du, dv = _pair_batch(12)
    mesh = auto_mesh((1,), ("data",))
    want = _batched_recompress(up, vp, du, dv, 1e-7, 1.0)
    got = sharded_recompress(up, vp, du, dv, 1e-7, 1.0, mesh=mesh,
                             axes=("data",))
    _assert_matches(got, want)
    # traced scale (the jit path the pipelines take) works too
    got_j = jax.jit(lambda s: sharded_recompress(
        up, vp, du, dv, 1e-7, s, mesh=mesh, axes=("data",)))(jnp.asarray(1.0))
    _assert_matches(got_j, want)


class _FakeMesh:
    """Stands in for a 2-shard mesh on a 1-device host: only ``shape`` is
    read before the pad/fallback decision; if the guard ever stopped
    firing, shard_map would receive this stub and fail loudly."""

    shape = {"data": 2}


def test_indivisible_length_pads_not_replicates():
    """An indivisible batch (13 % 2) must be padded to a shard multiple —
    the pre-pad behavior silently fell back to the fully replicated QR/SVD
    batch (the per-device memory cliff this PR closes).  With pad=False the
    replicated fallback is still available but warns once."""
    import warnings

    from repro.distribution import pair_qr

    up, vp, du, dv = _pair_batch(13)
    assert pair_shard_count(_FakeMesh(), ("data",)) == 2
    want = _batched_recompress(up, vp, du, dv, 1e-7, 1.0)
    # pad=True (default) routes through shard_map: the _FakeMesh stub is not
    # a real mesh, so reaching shard_map at all proves no silent fallback.
    with pytest.raises(Exception):
        sharded_recompress(up, vp, du, dv, 1e-7, 1.0, mesh=_FakeMesh(),
                           axes=("data",))
    # pad=False: replicated batch, bit-exact, with exactly one warning.
    pair_qr._warned_fallbacks.discard("recompress-indivisible")
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        got = sharded_recompress(up, vp, du, dv, 1e-7, 1.0, mesh=_FakeMesh(),
                                 axes=("data",), pad=False)
        again = sharded_recompress(up, vp, du, dv, 1e-7, 1.0,
                                   mesh=_FakeMesh(), axes=("data",),
                                   pad=False)
    _assert_matches(got, want, atol=0.0)
    _assert_matches(again, want, atol=0.0)
    hits = [x for x in w if issubclass(x.category, RuntimeWarning)
            and "replicated" in str(x.message)]
    assert len(hits) == 1, [str(x.message) for x in w]


def test_pad_leading_helper():
    """pad_leading zero-pads every leading axis to the multiple and reports
    the original length; already-divisible batches pass through unchanged."""
    from repro.distribution.pair_qr import pad_leading

    a = jnp.ones((5, 3)); b = jnp.ones((5,))
    (pa, pb), n = pad_leading((a, b), 4)
    assert n == 5 and pa.shape == (8, 3) and pb.shape == (8,)
    assert float(pa[5:].sum()) == 0.0 and float(pb[5:].sum()) == 0.0
    (qa,), n2 = pad_leading((a,), 5)
    assert n2 == 5 and qa is a


def _tiles_m512():
    locs = grid_locations(16, jitter=0.2, seed=0)          # 256 locs, m = 512
    locs = np.asarray(locs)[morton_order(locs)]
    params = MaternParams.bivariate(a=0.09, nu11=0.5, nu22=1.0, beta=0.5)
    dists = pairwise_distances(locs)
    sigma = build_sigma(None, params, dists=dists, nugget=1e-8)
    t = T.tlr_compress(sigma, tile_size=64, tol=1e-10, max_rank=48)
    return t, sigma


def test_sharded_factorization_matches_masked_and_dense_m512():
    """m = 512 with the shard_map path active (1-device mesh): the sharded
    block-cyclic factorization == masked full-grid == dense Cholesky,
    values AND ranks (the ISSUE-4 single-device acceptance)."""
    t, sigma = _tiles_m512()
    mesh = auto_mesh((1,), ("data",))
    ref = dist_tlr_cholesky(t.diag, t.u, t.v, t.ranks, tol=1e-12, scale=1.0)
    got = dist_tlr_cholesky(t.diag, t.u, t.v, t.ranks, tol=1e-12, scale=1.0,
                            mesh=mesh, block_cyclic=True)
    repl = dist_tlr_cholesky(t.diag, t.u, t.v, t.ranks, tol=1e-12, scale=1.0,
                             mesh=mesh, block_cyclic=True,
                             shard_recompress=False)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref[0]),
                               atol=1e-8)
    assert np.array_equal(np.asarray(got[3]), np.asarray(ref[3]))
    assert np.array_equal(np.asarray(got[3]), np.asarray(repl[3]))
    Tn, nb = t.n_tiles, t.tile_size
    dense_l = np.asarray(jnp.linalg.cholesky(sigma))
    for i in range(Tn):
        for j in range(i):
            blk = np.asarray(got[1][i, j] @ got[2][i, j].T)
            np.testing.assert_allclose(
                blk, np.asarray(ref[1][i, j] @ ref[2][i, j].T), atol=1e-8)
            np.testing.assert_allclose(
                blk, np.asarray(repl[1][i, j] @ repl[2][i, j].T), atol=1e-8)
            np.testing.assert_allclose(
                blk, dense_l[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb],
                atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(got[0][i]),
            dense_l[i * nb:(i + 1) * nb, i * nb:(i + 1) * nb], atol=1e-5)


def test_sharded_factorization_super_panels_matches():
    """The two-level (shrinking pair layout) variant threads shard_axes
    through every super-step."""
    t, _ = _tiles_m512()
    mesh = auto_mesh((1,), ("data",))
    one = dist_tlr_cholesky(t.diag, t.u, t.v, t.ranks, tol=1e-12, scale=1.0,
                            mesh=mesh, block_cyclic=True)
    two = dist_tlr_cholesky(t.diag, t.u, t.v, t.ranks, tol=1e-12, scale=1.0,
                            mesh=mesh, block_cyclic=True, super_panels=2)
    np.testing.assert_allclose(np.asarray(two[0]), np.asarray(one[0]),
                               atol=1e-8)
    assert np.array_equal(np.asarray(two[3]), np.asarray(one[3]))
    for i in range(t.n_tiles):
        for j in range(i):
            np.testing.assert_allclose(
                np.asarray(two[1][i, j] @ two[2][i, j].T),
                np.asarray(one[1][i, j] @ one[2][i, j].T), atol=1e-8)


@functools.lru_cache(maxsize=None)
def _tiles(n_tiles, nb=16, kmax=8, seed=0):
    """n_tiles x n_tiles tiles of a bivariate exponential Sigma
    (B kron exp(-d / a), B = [[1, 0.5], [0.5, 1]]) on ``n_tiles * nb / 2``
    Morton-ordered uniform locations, compressed by numpy SVDs."""
    locs = np.random.default_rng(seed).uniform(size=(n_tiles * nb // 2, 2))
    locs = locs[morton_order(locs)]
    d = np.linalg.norm(locs[:, None] - locs[None], axis=-1)
    sigma = np.kron([[1.0, 0.5], [0.5, 1.0]], np.exp(-d / 0.2))
    sigma += 1e-8 * np.eye(len(sigma))
    tiles = sigma.reshape(n_tiles, nb, n_tiles, nb).transpose(0, 2, 1, 3)
    u = np.zeros((n_tiles, n_tiles, nb, kmax))
    v = np.zeros_like(u)
    ranks = np.zeros((n_tiles, n_tiles), np.int32)
    for i, j in zip(*np.tril_indices(n_tiles, k=-1)):
        left, s, right = np.linalg.svd(tiles[i, j])
        r = min(kmax, int(np.sum(s > 1e-10)))
        u[i, j, :, :r] = left[:, :r] * s[:r]
        v[i, j, :, :r] = right[:r].T
        ranks[i, j] = r
    diag = np.stack([tiles[t, t] for t in range(n_tiles)])
    return T.TLRMatrix(*(jnp.asarray(x) for x in (diag, u, v, ranks)))


def _factor_loglik(factor, z):
    """Gaussian loglik of z through a grid-layout TLR factor."""
    diag_l, u, v = factor[:3]
    y = T.solve_lower_grid(diag_l, u, v, z)
    logdet = 2.0 * jnp.sum(jnp.log(jnp.diagonal(diag_l, axis1=1, axis2=2)))
    return float(-0.5 * (y @ y + logdet + z.shape[0] * np.log(2 * np.pi)))


@pytest.mark.parametrize("n_tiles,super_panels,shards,on_mesh",
                         [(3, 1, 1, False), (4, 1, 1, False),
                          (5, 1, 2, True), (8, 1, 1, False),
                          (8, 2, 2, False)])
def test_windowed_pair_factorization_matches_masked_grid(
        n_tiles, super_panels, shards, on_mesh):
    """The pair panel loop recompresses only each step's live-pair window
    (block_cyclic.window_schedule); the masked full-grid loop recompresses
    every tile at every step.  Same math on the live pairs: diag, U, V,
    ranks and loglik agree to round-off, through the super-panel loop's
    smaller layouts, and for a layout dealt over more shards than the mesh
    spans (the window is then taken globally)."""
    t = _tiles(n_tiles)
    kw = dict(tol=1e-12, scale=1.0)
    ref = dist_tlr_cholesky(t.diag, t.u, t.v, t.ranks, **kw)
    lay = pair_layout(n_tiles, shards)
    out = dist_tlr_cholesky_pairs(
        t.diag, *(grid_to_pairs(x, lay) for x in (t.u, t.v, t.ranks)),
        layout=lay, mesh=auto_mesh((1,), ("data",)) if on_mesh else None,
        super_panels=super_panels, **kw)
    got = (out[0],) + tuple(pairs_to_grid(x, lay) for x in out[1:])
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref[0]),
                               rtol=0, atol=1e-12)
    assert np.array_equal(np.asarray(got[3]), np.asarray(ref[3]))
    for g, r in zip(got[1:3], ref[1:3]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=0, atol=1e-10)
    z = jnp.asarray(np.random.default_rng(1).normal(size=t.shape[0]))
    assert _factor_loglik(got, z) == pytest.approx(_factor_loglik(ref, z),
                                                   rel=1e-12)


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


def test_sharded_recompress_shard_counts_subprocess():
    """sharded_recompress == _batched_recompress over shard counts
    {1, 2, 4}, pad slots included (the ISSUE-4 unit-test matrix)."""
    out = _run_subprocess("""
    from repro.core.tlr import _batched_recompress
    from repro.distribution.pair_qr import sharded_recompress
    rng = np.random.default_rng(0)
    for S in (1, 2, 4):
        length = 4 * S * 3
        up, vp, du, dv = (
            jnp.asarray(rng.normal(size=(length, 16, 4)), jnp.float32)
            for _ in range(4))
        up = up.at[-3:].set(0.0); vp = vp.at[-3:].set(0.0)
        du = du.at[-3:].set(0.0); dv = dv.at[-3:].set(0.0)
        mesh = auto_mesh((S,), ("data",))
        want = _batched_recompress(up, vp, du, dv, 1e-6, 1.0)
        got = sharded_recompress(up, vp, du, dv, 1e-6, 1.0, mesh=mesh,
                                 axes=("data",))
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       atol=2e-5)
        # indivisible length is padded to a shard multiple (sharding
        # survives — the pre-pad silent replicated fallback is gone) and
        # the stripped result matches the replicated batch
        ext = [jnp.concatenate([a, a[:1]]) for a in (up, vp, du, dv)]
        if ext[0].shape[0] % S:
            want = _batched_recompress(*ext, 1e-6, 1.0)
            got = sharded_recompress(*ext, 1e-6, 1.0, mesh=mesh,
                                     axes=("data",))
            assert got[0].shape[0] == ext[0].shape[0]
            for g, w in zip(got, want):
                np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                           atol=2e-5)
    print("SHARDS_OK")
    """)
    assert "SHARDS_OK" in out


@pytest.mark.slow
def test_sharded_factorization_multidevice():
    """8-device (2, 4) mesh at m = 512: sharded recompress == replicated
    recompress == masked grid — values and ranks — through the full
    block-cyclic factorization (the ISSUE-4 multi-device acceptance)."""
    out = _run_subprocess("""
    from repro.core import MaternParams
    from repro.core.covariance import morton_order
    from repro.core.dist_tlr import dist_compress_tiles, dist_tlr_cholesky
    from repro.core.simulate import grid_locations

    mesh = auto_mesh((2, 4), ("data", "model"))
    locs = grid_locations(16, jitter=0.2, seed=0)      # 256 locs, m = 512
    locs = np.asarray(locs)[morton_order(locs)]
    params = MaternParams.bivariate(a=0.09, nu11=0.5, nu22=1.0, beta=0.5,
                                    dtype=jnp.float32)
    t = dist_compress_tiles(locs.astype(np.float32), params, tile_size=64,
                            tol=1e-9, max_rank=48, nugget=1e-6, mesh=mesh)
    masked = dist_tlr_cholesky(t.diag, t.u, t.v, t.ranks, tol=1e-11,
                               scale=1.0, mesh=mesh)
    repl = dist_tlr_cholesky(t.diag, t.u, t.v, t.ranks, tol=1e-11, scale=1.0,
                             mesh=mesh, block_cyclic=True,
                             shard_recompress=False)
    got = dist_tlr_cholesky(t.diag, t.u, t.v, t.ranks, tol=1e-11, scale=1.0,
                            mesh=mesh, block_cyclic=True)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(masked[0]),
                               atol=1e-5)
    assert np.array_equal(np.asarray(got[3]), np.asarray(masked[3]))
    assert np.array_equal(np.asarray(got[3]), np.asarray(repl[3]))
    for i in range(t.diag.shape[0]):
        for j in range(i):
            blk = np.asarray(got[1][i, j] @ got[2][i, j].T)
            np.testing.assert_allclose(
                blk, np.asarray(repl[1][i, j] @ repl[2][i, j].T), atol=1e-5)
            np.testing.assert_allclose(
                blk, np.asarray(masked[1][i, j] @ masked[2][i, j].T),
                atol=1e-5)
    print("MULTIDEV_OK")
    """)
    assert "MULTIDEV_OK" in out
