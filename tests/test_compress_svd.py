"""Pair-axis-sharded compression (distribution/compress_svd.py + the
owned-slot gen+compress path in core/dist_tlr.py): the shard_map forms must
be pure re-placements of the replicated truncation batch, matching the dense
compression in values AND ranks."""
import os
import subprocess
import sys
import textwrap
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import MaternParams, pairwise_distances
from repro.core import tlr as T
from repro.core.covariance import build_sigma, morton_order
from repro.core.dist_tlr import dist_compress_tiles
from repro.core.simulate import grid_locations
from repro.distribution.block_cyclic import (column_owner_tables, pair_layout,
                                             pair_shards)
from repro.distribution.compress_svd import (sharded_truncate_svd,
                                             svd_truncate_batch)
from repro.launch.mesh import auto_mesh


def _tile_batch(b=11, nb=16, seed=0):
    rng = np.random.default_rng(seed)
    a = rng.normal(size=(b, nb, nb))
    return jnp.asarray(a @ np.swapaxes(a, -1, -2))   # SPD-ish, real spectra


def test_sharded_truncate_svd_fallback_and_mesh():
    """mesh=None is exactly the replicated batch; a 1-device mesh genuinely
    routes through shard_map (padding the indivisible length) and matches —
    ranks bit-exact, factors to fp tolerance."""
    tiles = _tile_batch()
    want = svd_truncate_batch(tiles, 1e-6, 8, 1.0)
    got = sharded_truncate_svd(tiles, 1e-6, 8, 1.0)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=0.0)
    mesh = auto_mesh((1,), ("data",))
    got_m = sharded_truncate_svd(tiles, 1e-6, 8, 1.0, mesh=mesh,
                                 axes=("data",))
    assert got_m[0].shape == want[0].shape        # pads stripped
    assert np.array_equal(np.asarray(got_m[2]), np.asarray(want[2]))
    for g, w in zip(got_m, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-10)
    # traced scale (the jit path the pipelines take)
    got_j = jax.jit(lambda s: sharded_truncate_svd(
        tiles, 1e-6, 8, s, mesh=mesh, axes=("data",)))(jnp.asarray(1.0))
    for g, w in zip(got_j, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-10)


def test_column_owner_tables_cover_and_balance():
    """Every strict-lower pair appears exactly once at its owning shard's
    local slot, and each column's tiles split floor/ceil((T-1-j)/S) across
    shards (the balance the owned-slot GEN path relies on)."""
    for Tn, S in ((7, 3), (8, 4), (5, 1), (4, 8)):
        lay = pair_layout(Tn, S)
        rows, slots = column_owner_tables(lay)
        L = rows.shape[-1]
        assert rows.shape == (S, Tn, L) and slots.shape == (S, Tn, L)
        seen = set()
        for d in range(S):
            for j in range(Tn):
                live = rows[d, j] < Tn
                # sentinel consistency: unused entries are OOB in both maps
                assert np.all(slots[d, j][~live] == lay.pairs_per_shard)
                for i, sl in zip(rows[d, j][live], slots[d, j][live]):
                    glob = d * lay.pairs_per_shard + sl
                    assert lay.il[glob] == i and lay.jl[glob] == j
                    seen.add((int(i), int(j)))
                n_col = Tn - 1 - j
                assert np.sum(live) in (n_col // S, -(-n_col // S))
        assert len(seen) == lay.n_pairs


def _setup_m128():
    locs = grid_locations(8, jitter=0.2, seed=0)          # 64 locs, m = 128
    locs = np.asarray(locs)[morton_order(locs)]
    params = MaternParams.bivariate(a=0.09, nu11=0.5, nu22=1.0, beta=0.5)
    return locs, params


def test_owned_slot_compress_matches_replicated_and_dense():
    """shard_svd=True on a 1-device mesh (the owned-slot gen+compress path,
    genuinely under shard_map) == the replicated batch == the dense
    tlr_compress — values AND ranks (the ISSUE-5 single-device
    acceptance)."""
    locs, params = _setup_m128()
    mesh = auto_mesh((1, 1), ("data", "model"))
    lay = pair_layout(4, pair_shards(mesh))
    kw = dict(tile_size=32, tol=1e-9, max_rank=16, nugget=1e-6)
    sh = dist_compress_tiles(locs, params, mesh=mesh, layout=lay, **kw)
    repl = dist_compress_tiles(locs, params, mesh=mesh, layout=lay,
                               shard_svd=False, **kw)
    assert np.array_equal(np.asarray(sh.ranks), np.asarray(repl.ranks))
    np.testing.assert_allclose(np.asarray(sh.diag), np.asarray(repl.diag),
                               atol=1e-12)
    gs, gr = sh.to_grid(lay), repl.to_grid(lay)
    sigma = build_sigma(None, params, dists=pairwise_distances(locs),
                        nugget=1e-6)
    dense = T.tlr_compress(sigma, tile_size=32, tol=1e-9, max_rank=16)
    assert np.array_equal(np.asarray(gs.ranks), np.asarray(dense.ranks))
    for i in range(4):
        for j in range(i):
            blk = np.asarray(gs.u[i, j] @ gs.v[i, j].T)
            np.testing.assert_allclose(
                blk, np.asarray(gr.u[i, j] @ gr.v[i, j].T), atol=1e-10)
            np.testing.assert_allclose(
                blk, np.asarray(dense.u[i, j] @ dense.v[i, j].T), atol=1e-8)


def test_col_block_owned_slot_compress_matches():
    """col_block > 1 (super-panel column groups) through the owned-slot
    path scatters the same tiles as col_block=1."""
    locs, params = _setup_m128()
    mesh = auto_mesh((1,), ("data",))
    lay = pair_layout(4, pair_shards(mesh, ("data",)))
    kw = dict(tile_size=32, tol=1e-7, max_rank=16, nugget=1e-8, mesh=mesh,
              row_axes=("data",), layout=lay)
    one = dist_compress_tiles(locs, params, col_block=1, **kw)
    two = dist_compress_tiles(locs, params, col_block=2, **kw)
    assert np.array_equal(np.asarray(one.ranks), np.asarray(two.ranks))
    np.testing.assert_allclose(np.asarray(one.u), np.asarray(two.u),
                               atol=1e-10)
    np.testing.assert_allclose(np.asarray(one.diag), np.asarray(two.diag),
                               atol=1e-12)


def test_layout_mesh_shard_mismatch_warns_and_falls_back():
    """A layout built for a different shard count than the mesh pair axes
    span cannot use the owned-slot path — it must warn once and produce the
    replicated result (still correct, never silent)."""
    from repro.distribution import pair_qr

    locs, params = _setup_m128()
    mesh = auto_mesh((1, 1), ("data", "model"))
    lay3 = pair_layout(4, 3)                 # mesh spans 1 shard, not 3
    kw = dict(tile_size=32, tol=1e-7, max_rank=16, nugget=1e-8)
    want = dist_compress_tiles(locs, params, mesh=None, layout=lay3, **kw)
    pair_qr._warned_fallbacks.discard("compress-layout-shards")
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        got = dist_compress_tiles(locs, params, mesh=mesh, layout=lay3, **kw)
        dist_compress_tiles(locs, params, mesh=mesh, layout=lay3, **kw)
    hits = [x for x in w if issubclass(x.category, RuntimeWarning)
            and "replicated" in str(x.message)]
    assert len(hits) == 1, [str(x.message) for x in w]
    assert np.array_equal(np.asarray(got.ranks), np.asarray(want.ranks))
    np.testing.assert_allclose(np.asarray(got.u), np.asarray(want.u),
                               atol=1e-10)


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


def test_owned_slot_compress_shard_counts_subprocess():
    """Owned-slot sharded compress == replicated compress over shard counts
    {1, 2, 4} — values and ranks — on real device meshes (the ISSUE-5
    shard-count matrix)."""
    out = _run_subprocess("""
    from repro.core import MaternParams
    from repro.core.covariance import morton_order
    from repro.core.dist_tlr import dist_compress_tiles
    from repro.core.simulate import grid_locations
    from repro.distribution.block_cyclic import pair_layout

    locs = grid_locations(8, jitter=0.2, seed=0)
    locs = np.asarray(locs)[morton_order(locs)].astype(np.float32)
    params = MaternParams.bivariate(a=0.09, nu11=0.5, nu22=1.0, beta=0.5,
                                    dtype=jnp.float32)
    kw = dict(tile_size=32, tol=1e-7, max_rank=16, nugget=1e-6)
    for S in (1, 2, 4):
        mesh = auto_mesh((S,), ("data",))
        lay = pair_layout(4, S)
        sh = dist_compress_tiles(locs, params, mesh=mesh, layout=lay, **kw)
        repl = dist_compress_tiles(locs, params, mesh=None, layout=lay, **kw)
        assert np.array_equal(np.asarray(sh.ranks), np.asarray(repl.ranks)), S
        np.testing.assert_allclose(np.asarray(sh.u), np.asarray(repl.u),
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(sh.v), np.asarray(repl.v),
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(sh.diag),
                                   np.asarray(repl.diag), atol=1e-6)
    print("SHARDS_OK")
    """)
    assert "SHARDS_OK" in out


@pytest.mark.slow
def test_compress_sharded_pipeline_multidevice():
    """8-device (2, 4) mesh at m = 512: the full pipeline with the
    compress-phase sharding on == off == the dense exact likelihood (the
    ISSUE-5 multi-device acceptance)."""
    out = _run_subprocess("""
    from repro.core import MaternParams, exact_loglik
    from repro.core.covariance import morton_order
    from repro.core.dist_tlr import dist_tlr_loglik
    from repro.core.simulate import grid_locations, simulate_mgrf

    mesh = auto_mesh((2, 4), ("data", "model"))
    locs = grid_locations(16, jitter=0.2, seed=0)      # 256 locs, m = 512
    locs = np.asarray(locs)[morton_order(locs)].astype(np.float32)
    params = MaternParams.bivariate(a=0.09, nu11=0.5, nu22=1.0, beta=0.5,
                                    dtype=jnp.float32)
    z = simulate_mgrf(jax.random.PRNGKey(5), locs, params, nugget=1e-6)[0]
    want = float(exact_loglik(locs, z, params, nugget=1e-6).loglik)
    lj = jnp.asarray(locs)
    kw = dict(locs=lj, params=params, from_tiles=True, tile_size=64,
              max_rank=32, nugget=1e-6, tol=1e-7, block_cyclic=True,
              mesh=mesh)
    ll_sh = float(jax.jit(lambda zz: dist_tlr_loglik(
        None, zz, **kw).loglik)(z))
    ll_re = float(jax.jit(lambda zz: dist_tlr_loglik(
        None, zz, shard_svd=False, **kw).loglik)(z))
    assert abs(ll_sh - want) <= 1e-3 * abs(want), (ll_sh, want)
    assert abs(ll_sh - ll_re) <= 1e-5 * abs(want), (ll_sh, ll_re)
    print("PIPELINE_OK", ll_sh)
    """)
    assert "PIPELINE_OK" in out
