"""Distributed TLR pipeline: generate -> compress -> factorize as fori_loop
SPMD programs over a sharded tile set (the paper's HiCMA workload).

Two placements for the strict-lower UV tiles (DESIGN.md §2,4):

  * masked grid (the paper-faithful SPMD baseline)

        D     (T, nb, nb)        diagonal tiles,        sharded P("data")
        U, V  (T, T, nb, kmax)   strict-lower UV tiles, sharded P("data","model")

    i.e. tile (i, j) lives on device grid cell (i mod Pr-block, j mod
    Pc-block) — the 2-D distribution of CHAMELEON with block placement.
    Static shapes mean every panel step's GEMM batch touches all T^2 tiles:
    ~6x flop overcompute versus the exact triangle.

  * block-cyclic pair placement (distribution/block_cyclic.py, the
    production form — ``block_cyclic=True``)

        D      (T, nb, nb)           diagonal tiles,   sharded P("data")
        U, V   (length, nb, kmax)    strict-lower pairs, block-cyclic over
                                     P(("data", "model")) — length ~ T^2/2

    the ExaGeoStat/PaRSEC schedule (Abdulah et al. 2018; arXiv:1804.09137):
    each panel step recompresses only a static window that holds its live
    pairs (block_cyclic.window_schedule: at most 2 live + 2 S pairs on S
    shards), the cyclic deal keeps every device's share of the live
    trailing submatrix balanced as panels retire, and the (T, T) grid is
    never materialized (~2x less tile storage).  Per-step communication is
    the panel-column broadcast through ``layout.pos[:, k]``, which the
    right-looking algorithm needs under any placement.

The *compression* stage (dist_compress_tiles) streams ``col_block`` tile
columns of Representation-I panels at a time straight from the Matérn
generator (covariance.build_sigma_column -> kernels.matern_tile / XLA K_nu):
each fori_loop step builds the column-group panel, SVD-truncates its tiles,
and scatters the finished columns into either placement — the dense
(pn x pn) Sigma is never materialized on any device.  ``shard_svd`` (the
default) partitions the compression itself the way PR 4 partitioned the
GEMM-phase QR/SVD: in pair mode each device *generates and compresses only
the strict-lower tiles whose block-cyclic slots it owns*, slot-major
(_compress_tiles_pair_sharded over distribution.block_cyclic
.owned_pair_tables — exactly pairs_per_shard tiles per device, no masked
sentinel candidates), and the truncation-SVD workspace scales O(tiles/S) — under
plain GSPMD the batched jnp.linalg.svd has no partitioning rule and the
whole (cb*T, nb, nb) batch replicated on every device (~3.2 GB/device at
mle_65k, the post-PR-4 dominant temp).  In grid mode the truncation SVDs
run under shard_map via distribution.compress_svd.sharded_truncate_svd;
mesh=None / shard_svd=False keep the exact replicated batch (the PR-4
fallback contract).

The *factorization* stage shares its traced panel bodies with the
single-device scan form (core.tlr.tlr_panel_body / tlr_panel_body_bc).
Each fori_loop step k performs the full panel of paper-Fig.-1 tasks
(POTRF / TRSM / SYRK / GEMM+recompress) as batched kernels; see the panel
bodies for the masked-grid vs pair-batch cost trade-off.  launch/roofline.py
``tlr_pair_update_stats`` gives the closed-form overcompute model; the
quick bench (benchmarks/bench_tlr.py) measures both forms and
benchmarks/check_bench.py gates the ratio.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..distribution.block_cyclic import (PairLayout, grid_to_pairs,
                                         owned_pair_tables, pair_axis,
                                         pair_layout, pair_shards,
                                         pairs_to_grid, slice_positions)
from ..distribution.compress_svd import (sharded_truncate_svd,
                                         svd_truncate_batch)
from ..distribution.pair_qr import warn_fallback_once
from .linalg import cholesky, solve_lower
from .covariance import build_sigma_column, build_sigma_panel
from .likelihood import LoglikResult
from .precision import resolve_policy
from .recovery import FactorStatus, init_status, sentinel_loglik
from .tlr import (TLRMatrix, _constrain, apply_nugget, choose_tile_size,
                  indexed_scan, pair_panel_loop, panel_loop,
                  solve_lower_grid)

__all__ = [
    "PairTLR", "dist_compress_tiles", "dist_tlr_cholesky",
    "dist_tlr_cholesky_pairs", "dist_tlr_solve_lower",
    "dist_tlr_solve_lower_pairs", "dist_tlr_solve_upper_pairs",
    "dist_tlr_loglik", "dist_tlr_lowerable",
    "dist_tlr_in_shardings", "dist_tlr_gen_lowerable",
    "dist_tlr_compress_lowerable", "dist_tlr_pipeline_lowerable",
]


def _row(row_axes):
    return row_axes if len(row_axes) > 1 else row_axes[0] if row_axes else None


# ---------------------------------------------------------------------------
# Pair-major TLR container (block-cyclic placement)
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class PairTLR:
    """TLR matrix with strict-lower tiles in block-cyclic pair-major
    storage (see distribution/block_cyclic.py).  The slot order is
    deterministic from (n_tiles, n_shards) via ``pair_layout``, so the
    *shard count the tiles were scattered for* travels as static pytree
    aux data — two layouts of the same T can share a length while ordering
    slots differently, and reconstructing with the wrong one would be
    silently wrong, not shape-checked.
    """

    diag: jax.Array    # (T, nb, nb) dense diagonal tiles
    u: jax.Array       # (length, nb, kmax) pair-major strict-lower tiles
    v: jax.Array       # (length, nb, kmax)
    ranks: jax.Array   # (length,) int32 actual ranks (0 at pad slots)
    n_shards: int = 1  # static: the pair_layout(n_tiles, n_shards) placement

    def tree_flatten(self):
        return (self.diag, self.u, self.v, self.ranks), self.n_shards

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, n_shards=aux)

    @property
    def n_tiles(self) -> int:
        return self.diag.shape[0]

    @property
    def tile_size(self) -> int:
        return self.diag.shape[1]

    @property
    def max_rank(self) -> int:
        return self.u.shape[-1]

    @property
    def shape(self):
        m = self.n_tiles * self.tile_size
        return (m, m)

    def to_grid(self, layout: PairLayout) -> TLRMatrix:
        """Materialize the (T, T) grid form (tests / interop only)."""
        return TLRMatrix(diag=self.diag, u=pairs_to_grid(self.u, layout),
                         v=pairs_to_grid(self.v, layout),
                         ranks=pairs_to_grid(self.ranks, layout))


def _pair_specs(mesh, row_axes):
    """(diag, pair-tile, pair-rank) PartitionSpecs for the pair placement."""
    row = _row(row_axes)
    pax = pair_axis(mesh, row_axes)
    return P(row, None, None), P(pax, None, None), P(pax)


# ---------------------------------------------------------------------------
# Streaming generator-direct compression (GEN + compress, sharded)
# ---------------------------------------------------------------------------


@jax.named_scope("repro.compress")
def dist_compress_tiles(locs, params, *, tile_size: int = 0, tol: float = 1e-7,
                        max_rank: int = 0, nugget: float = 0.0,
                        gen: str = "pallas", d_spatial: int = 2, scale=None,
                        mesh=None, row_axes=("data",), layout=None,
                        col_block: int = 1, shard_svd: bool = True,
                        dtype_policy=None):
    """Build the fixed-kmax D/U/V layout straight from Morton-ordered
    locations, ``col_block`` column panels at a time (the distributed
    production path).

    Equivalent to ``tlr_compress_tiles`` to SVD/fp tolerance, but as a
    single fori_loop whose step g generates the Representation-I column
    group sigma[:, g*cb*nb:(g+1)*cb*nb] from the generator (never the dense
    Sigma), SVD-truncates its cb*T tiles, and scatters the finished columns.
    Rows i <= j are masked to zero (strict-lower storage); the diagonal tile
    gets the nugget, exactly where ``build_sigma`` puts it (``nugget`` may
    be a traced scalar — the MLE estimating it under jit).

    ``layout=None`` returns the masked-grid TLRMatrix; a PairLayout scatters
    straight into block-cyclic pair-major storage (PairTLR) so the
    block-cyclic factorization path never sees the (T, T) grid.
    ``col_block > 1`` compresses super-panel column groups — fewer, larger
    fori trips (ROADMAP temp-footprint item).  ``mesh=None`` runs the
    identical program on one device (the CPU test path); per-tile ``ranks``
    are real (threaded from the truncation), not placeholders.

    ``shard_svd`` (the default) partitions the compression over the devices
    the pair axis spans: in pair mode each device generates *and* SVDs only
    the strict-lower tiles whose block-cyclic slots it owns
    (_compress_tiles_pair_sharded), so both the GEN panel and the
    truncation-SVD workspace scale O(tiles/S) per device; in grid mode the
    (cb*T, nb, nb) truncation batch runs under shard_map
    (distribution.compress_svd.sharded_truncate_svd).  ``False`` (or
    ``mesh=None``) keeps the PR-4 fully replicated batch for comparison.
    """
    locs = jnp.asarray(locs)
    n = locs.shape[0]
    p = params.p
    m = n * p
    nb = choose_tile_size(m, tile_size, multiple_of=p)
    nbl = nb // p                       # locations per tile
    T = m // nb
    cb = max(int(col_block), 1)
    if T % cb:
        raise ValueError(f"col_block={cb} must divide n_tiles={T}")
    if max_rank <= 0:
        max_rank = max(8, nb // 4)
    kmax = min(max_rank, nb)
    if scale is None:
        scale = jnp.max(params.sigma2) + nugget
    row = _row(row_axes)
    dtype = jnp.result_type(locs.dtype, params.sigma2.dtype, jnp.float32)
    # Mixed precision (core.precision): diagonal tiles keep the wide
    # generated dtype; off-diagonal U/V storage (and its truncation SVD)
    # runs at the policy's narrow dtype.  No policy: one uniform dtype.
    policy = resolve_policy(dtype_policy)
    uv_dtype = dtype if policy is None else jnp.dtype(policy.narrow_dtype)
    rows_idx = jnp.arange(T)
    svd_axes = pair_axis(mesh, row_axes)
    svd_mesh = mesh if (shard_svd and mesh is not None and svd_axes) else None

    pair_mode = layout is not None
    if pair_mode:
        assert layout.n_tiles == T, (layout.n_tiles, T)
        if svd_mesh is not None:
            if layout.n_shards == pair_shards(mesh, row_axes):
                return _compress_tiles_pair_sharded(
                    locs, params, layout=layout, nb=nb, nbl=nbl, T=T, cb=cb,
                    tol=tol, kmax=kmax, nugget=nugget, gen=gen,
                    d_spatial=d_spatial, scale=scale, mesh=mesh,
                    row_axes=row_axes, dtype=dtype, uv_dtype=uv_dtype)
            warn_fallback_once(
                "compress-layout-shards",
                f"dist_compress_tiles: layout was built for n_shards="
                f"{layout.n_shards} but the mesh pair axes span "
                f"{pair_shards(mesh, row_axes)} devices — falling back to "
                "the replicated compression batch (a per-device memory "
                "cliff); build the layout with pair_shards(mesh, row_axes)")
            svd_mesh = None
        dspec, pspec, rspec = _pair_specs(mesh, row_axes)
        u = jnp.zeros((layout.length, nb, kmax), uv_dtype)
        v = jnp.zeros((layout.length, nb, kmax), uv_dtype)
        ranks = jnp.zeros((layout.length,), jnp.int32)
        pos = jnp.asarray(layout.pos)
    else:
        dspec = P(row, None, None)
        uvspec = P(row, "model", None, None)
        u = jnp.zeros((T, T, nb, kmax), uv_dtype)
        v = jnp.zeros((T, T, nb, kmax), uv_dtype)
        ranks = jnp.zeros((T, T), jnp.int32)
    diag = jnp.zeros((T, nb, nb), dtype)

    def body(g, carry):
        diag, u, v, ranks = carry
        with jax.named_scope("repro.gen"):
            panel = build_sigma_column(locs, g, cb * nbl, params,
                                       d_spatial=d_spatial, gen=gen,
                                       block=nb,
                                       row_block=nbl if mesh is None else 0)
        panel = _constrain(panel, mesh, P(row, "model"))
        tiles = panel.reshape(T, nb, cb, nb).transpose(2, 0, 1, 3)
        # SVD input down-cast to U/V storage dtype; diagonal tiles below
        # read the un-cast (wide) panel.
        U, V, R = sharded_truncate_svd(
            tiles.reshape(cb * T, nb, nb).astype(u.dtype), tol,
            kmax, scale, mesh=svd_mesh, axes=svd_axes)
        U = U.reshape(cb, T, nb, kmax)
        V = V.reshape(cb, T, nb, kmax)
        R = R.reshape(cb, T)
        for c in range(cb):             # static unroll over the group
            j = g * cb + c
            dj = lax.dynamic_index_in_dim(tiles[c], j, 0, keepdims=False)
            dj = apply_nugget(dj, nugget, dtype)
            diag = lax.dynamic_update_index_in_dim(diag, dj, j, 0)
            below = rows_idx > j
            Uc = jnp.where(below[:, None, None], U[c], 0.0)
            Vc = jnp.where(below[:, None, None], V[c], 0.0)
            Rc = jnp.where(below, R[c], 0)
            if pair_mode:
                pcol = lax.dynamic_index_in_dim(pos, j, 1, keepdims=False)
                u = u.at[pcol].set(Uc, mode="drop")  # OOB (i <= j) dropped
                v = v.at[pcol].set(Vc, mode="drop")
                ranks = ranks.at[pcol].set(Rc, mode="drop")
            else:
                u = lax.dynamic_update_index_in_dim(u, Uc, j, 1)
                v = lax.dynamic_update_index_in_dim(v, Vc, j, 1)
                ranks = lax.dynamic_update_index_in_dim(ranks, Rc, j, 1)
        diag = _constrain(diag, mesh, dspec)
        if pair_mode:
            u = _constrain(u, mesh, pspec)
            v = _constrain(v, mesh, pspec)
            ranks = _constrain(ranks, mesh, rspec)
        else:
            u = _constrain(u, mesh, uvspec)
            v = _constrain(v, mesh, uvspec)
        return diag, u, v, ranks

    diag, u, v, ranks = indexed_scan(body, T // cb, (diag, u, v, ranks))
    if pair_mode:
        return PairTLR(diag=diag, u=u, v=v, ranks=ranks,
                       n_shards=layout.n_shards)
    return TLRMatrix(diag=diag, u=u, v=v, ranks=ranks)


def _compress_tiles_pair_sharded(locs, params, *, layout: PairLayout, nb, nbl,
                                 T, cb, tol, kmax, nugget, gen, d_spatial,
                                 scale, mesh, row_axes, dtype, uv_dtype=None):
    """Owned-slot generator-direct compression: every device generates and
    SVD-truncates only the strict-lower tiles whose block-cyclic pair slots
    it owns, straight into its local shard — *slot-major*.

    One shard_map over the pair axes runs the whole strict-lower sweep.
    Each device walks its own local slots in groups of ``sb = col_block *
    ceil((T-1)/S)`` (the per-step tile count of the former per-column
    sweep, so the transient panel is the same size): it reads the (row,
    col) tile coordinates of each owned slot from ``owned_pair_tables`` (a
    sharded (S, pairs_per_shard) operand), gathers both location blocks,
    generates the sb (nb, nb) tiles with a vmapped ``build_sigma_panel``
    (identical per-tile values to the full build_sigma_column panel —
    entries are elementwise in the pairwise distances), SVD-truncates
    them, and writes them at their own local slots.  Sentinel entries
    (layout pads) gather zero locations and scatter to the out-of-bounds
    local slot, so they drop.

    Per device and full sweep this generates exactly ``pairs_per_shard ~
    T(T-1)/(2S)`` tiles — the owned set.  The former per-column sweep
    (``column_owner_tables``) generated ``T * ceil((T-1)/S)`` candidate
    tiles: ~2x the owned set even on one shard, and almost all masked
    sentinels once S >> T-1 (at S = 256, T = 64 every device generated 64
    tiles per sweep to keep ~8 — the ROADMAP carried item this layout
    retires).  The only communication is the replicated locs broadcast
    the generator needs anyway.

    Diagonal tiles (not in the pair set) are generated outside the
    shard_map, one (nb, nb) block per column, with the nugget applied
    jit-safely (core.tlr.apply_nugget)."""
    dspec, pspec, rspec = _pair_specs(mesh, row_axes)
    axes = pair_axis(mesh, row_axes)
    S, pps = layout.n_shards, layout.pairs_per_shard
    sb = cb * max(-(-(T - 1) // S), 1)      # tiles per step (= old cb * L)
    sb = min(sb, pps)
    G = -(-pps // sb)                        # steps to cover the owned slots
    own_rows, own_cols = owned_pair_tables(layout)
    if G * sb > pps:                         # pad tables to G*sb sentinels
        pad = np.full((S, G * sb - pps), T, np.int32)
        own_rows = np.concatenate([own_rows, pad], axis=1)
        own_cols = np.concatenate([own_cols, pad], axis=1)
    # spmdlint: ignore[R1] O(S*pps) int32 pair tables: static per layout, and sharded over the pair axes like the tiles they address
    own_rows = jnp.asarray(own_rows)        # (S, G*sb)
    own_cols = jnp.asarray(own_cols)
    ospec = P(axes, None)
    scale = jnp.asarray(scale)
    blk_off = jnp.arange(nbl)

    gen_tile = jax.vmap(lambda r, c: build_sigma_panel(
        r, c, params, d_spatial=d_spatial, gen=gen, block=nb))

    def local(u_l, v_l, r_l, rows_l, cols_l, locs_f, sc):
        rows_l = rows_l.reshape(-1)          # this shard's (1, G*sb) slice
        cols_l = cols_l.reshape(-1)

        def step(g, carry):
            u_l, v_l, r_l = carry
            ri = lax.dynamic_slice_in_dim(rows_l, g * sb, sb)
            ci = lax.dynamic_slice_in_dim(cols_l, g * sb, sb)
            ridx = (ri[:, None] * nbl + blk_off[None, :]).reshape(-1)
            cidx = (ci[:, None] * nbl + blk_off[None, :]).reshape(-1)
            row_locs = locs_f.at[ridx].get(mode="fill", fill_value=0.0)
            col_locs = locs_f.at[cidx].get(mode="fill", fill_value=0.0)
            with jax.named_scope("repro.gen"):
                tiles = gen_tile(row_locs.reshape(sb, nbl, -1),
                                 col_locs.reshape(sb, nbl, -1))
            tiles = tiles.astype(u_l.dtype)  # (sb, nb, nb), owned pairs only
            Ug, Vg, Rg = svd_truncate_batch(tiles, tol, kmax, sc)
            tgt = g * sb + jnp.arange(sb, dtype=ri.dtype)
            tgt = jnp.where(ri < T, tgt, pps)        # pads drop (OOB slot)
            u_l = u_l.at[tgt].set(Ug, mode="drop")
            v_l = v_l.at[tgt].set(Vg, mode="drop")
            r_l = r_l.at[tgt].set(Rg, mode="drop")
            return u_l, v_l, r_l

        return indexed_scan(step, G, (u_l, v_l, r_l))

    sweep = jax.shard_map(local, mesh=mesh,
                          in_specs=(pspec, pspec, rspec, ospec, ospec,
                                    P(None, None), P()),
                          out_specs=(pspec, pspec, rspec),
                          check_vma=False)

    if uv_dtype is None:
        uv_dtype = dtype
    u = jnp.zeros((layout.length, nb, kmax), uv_dtype)
    v = jnp.zeros((layout.length, nb, kmax), uv_dtype)
    ranks = jnp.zeros((layout.length,), jnp.int32)
    diag = jnp.zeros((T, nb, nb), dtype)

    u, v, ranks = sweep(u, v, ranks, own_rows, own_cols, locs, scale)
    u = _constrain(u, mesh, pspec)
    v = _constrain(v, mesh, pspec)
    ranks = _constrain(ranks, mesh, rspec)

    def body(g, diag):
        for c in range(cb):
            j = g * cb + c
            pj = lax.dynamic_slice_in_dim(locs, j * nbl, nbl, axis=0)
            with jax.named_scope("repro.gen"):
                dj = build_sigma_panel(pj, pj, params, d_spatial=d_spatial,
                                       gen=gen, block=nb).astype(dtype)
            dj = apply_nugget(dj, nugget, dtype)
            diag = lax.dynamic_update_index_in_dim(diag, dj, j, 0)
        return _constrain(diag, mesh, dspec)

    diag = indexed_scan(body, T // cb, diag)
    return PairTLR(diag=diag, u=u, v=v, ranks=ranks,
                   n_shards=layout.n_shards)


# ---------------------------------------------------------------------------
# Distributed TLR Cholesky: masked full-grid baseline and the block-cyclic
# pair-batch production form (shared panel bodies with core/tlr.py)
# ---------------------------------------------------------------------------


@jax.named_scope("repro.factorize")
def dist_tlr_cholesky(diag, u, v, ranks=None, *, tol: float = 1e-7,
                      scale: float = 1.0, mesh=None, row_axes=("data",),
                      super_panels: int = 1, block_cyclic: bool = False,
                      shard_recompress: bool = True,
                      track_status: bool = False):
    """Factor the TLR matrix in place.  Returns (diag_L, u, v, ranks) in the
    masked-grid layout (the grid API — the block-cyclic streaming pipeline
    stays pair-native through ``dist_tlr_cholesky_pairs``).

    ``block_cyclic = False`` (paper-faithful SPMD baseline): one fori_loop
    over the shared panel body (core.tlr.tlr_panel_body, pairs=None) with
    masked full-grid updates — ~6x flop overcompute versus the triangle,
    but one trace regardless of T.

    ``block_cyclic = True``: the static strict-lower pair batch on
    block-cyclic pair-major storage (core.tlr.tlr_panel_body_bc) — each
    step recompresses only a window holding its live pairs, load-balanced
    on every device; the grid inputs are converted once at entry and back
    at exit.

    ``super_panels = S > 1``: python-unrolled outer loop over S shrinking
    sub-matrices, fori_loop inside — the batch only spans the live trailing
    slice, cutting the masked overcompute to ~2.4x at S = 8 for ~S-times
    the trace size (the §Perf geostat-tlr hillclimb).  Composes with both
    placements.

    ``ranks`` threads the real per-tile ranks through the factorization
    (recompression updates them); None starts from the fixed-kmax
    convention's zero metadata (see TLRMatrix).

    ``shard_recompress`` (pair placements only) runs the recompress QR/SVD
    under shard_map over the pair axis — each device factorizes only its
    own ~length/S slots (distribution/pair_qr.py) instead of the whole
    replicated batch; False keeps the PR-3 replicated form for comparison.
    mesh=None ignores it (the batch is local either way).

    ``track_status=True`` additionally threads a ``FactorStatus`` through
    the panel loop (in-graph breakdown accounting — core.recovery) and
    returns a 5-tuple ``(diag_L, u, v, ranks, status)``."""
    if ranks is None:
        ranks = jnp.zeros(u.shape[:2], jnp.int32)
    T = diag.shape[0]
    if block_cyclic:
        layout = pair_layout(T, pair_shards(mesh, row_axes))
        out = dist_tlr_cholesky_pairs(
            diag, grid_to_pairs(u, layout), grid_to_pairs(v, layout),
            grid_to_pairs(ranks, layout), layout=layout, tol=tol, scale=scale,
            mesh=mesh, row_axes=row_axes, super_panels=super_panels,
            shard_recompress=shard_recompress, track_status=track_status)
        diag, up, vp, rp = out[:4]
        grid = (diag, pairs_to_grid(up, layout), pairs_to_grid(vp, layout),
                pairs_to_grid(rp, layout))
        return grid + (out[4],) if track_status else grid
    if super_panels > 1:
        return _tlr_cholesky_super(diag, u, v, ranks, tol=tol, scale=scale,
                                   mesh=mesh, row_axes=row_axes,
                                   super_panels=super_panels,
                                   track_status=track_status)
    row = _row(row_axes)
    dspec = P(row, None, None)
    uvspec = P(row, "model", None, None)
    status = init_status(diag.dtype, ranks) if track_status else None
    if T > 1:
        out = panel_loop(diag, u, v, ranks, T - 1, tol=tol,
                         scale=scale, mesh=mesh, dspec=dspec,
                         uvspec=uvspec, status=status)
        if track_status:
            diag, u, v, ranks, status = out
        else:
            diag, u, v, ranks = out
    lkk = cholesky(diag[T - 1])
    if track_status:
        status = status.update_potrf(lkk)
    diag = diag.at[T - 1].set(lkk)
    diag = _constrain(diag, mesh, dspec)
    if track_status:
        return diag, u, v, ranks, status
    return diag, u, v, ranks


@jax.named_scope("repro.factorize")
def dist_tlr_cholesky_pairs(diag, up, vp, ranks, *, layout: PairLayout,
                            tol: float = 1e-7, scale: float = 1.0, mesh=None,
                            row_axes=("data",), super_panels: int = 1,
                            shard_recompress: bool = True,
                            track_status: bool = False):
    """Pair-native block-cyclic TLR Cholesky: (diag, U, V, ranks) in
    pair-major storage in, same storage out.  The (T, T) grid is never
    materialized — this is the factorization the streaming production
    pipeline runs.  ``shard_recompress`` shards the recompress QR/SVD over
    the pair axis via shard_map (see dist_tlr_cholesky).
    ``track_status=True`` returns a 5-tuple with a ``FactorStatus``."""
    T = diag.shape[0]
    if super_panels > 1:
        return _tlr_cholesky_super_pairs(diag, up, vp, ranks, layout=layout,
                                         tol=tol, scale=scale, mesh=mesh,
                                         row_axes=row_axes,
                                         super_panels=super_panels,
                                         shard_recompress=shard_recompress,
                                         track_status=track_status)
    dspec, pspec, _ = _pair_specs(mesh, row_axes)
    axes = pair_axis(mesh, row_axes) if shard_recompress else None
    status = init_status(diag.dtype, ranks) if track_status else None
    if T > 1:
        out = pair_panel_loop(diag, up, vp, ranks, T - 1,
                              layout=layout, tol=tol,
                              scale=scale, mesh=mesh,
                              dspec=dspec, pspec=pspec,
                              shard_axes=axes, status=status)
        if track_status:
            diag, up, vp, ranks, status = out
        else:
            diag, up, vp, ranks = out
    lkk = cholesky(diag[T - 1])
    if track_status:
        status = status.update_potrf(lkk)
    diag = diag.at[T - 1].set(lkk)
    diag = _constrain(diag, mesh, dspec)
    if track_status:
        return diag, up, vp, ranks, status
    return diag, up, vp, ranks


def _tlr_cholesky_super(diag, u, v, ranks, *, tol, scale, mesh, row_axes,
                        super_panels: int, track_status: bool = False):
    """Two-level masked-grid variant: unrolled outer loop over shrinking
    trailing slices, fori_loop inside each.  Factored panels are written
    into full-size output buffers; the live state shrinks every
    super-step.  With ``track_status`` the per-slice ``FactorStatus``
    accumulations merge into one (min pivot / summed counts)."""
    T = diag.shape[0]
    assert T % super_panels == 0, (T, super_panels)
    chunk = T // super_panels
    row = _row(row_axes)
    dspec = P(row, None, None)
    uvspec = P(row, "model", None, None)
    status = init_status(diag.dtype, ranks) if track_status else None

    out_diag = jnp.zeros_like(diag)
    out_u = jnp.zeros_like(u)
    out_v = jnp.zeros_like(v)
    out_ranks = jnp.zeros_like(ranks)
    dh, uh, vh, rh = diag, u, v, ranks
    for s in range(super_panels):
        o = s * chunk
        # factor the first `chunk` panels of the live (T-o)-tile slice
        if s == super_panels - 1:
            out = dist_tlr_cholesky(dh, uh, vh, rh, tol=tol,
                                    scale=scale, mesh=mesh,
                                    row_axes=row_axes,
                                    track_status=track_status)
            if track_status:
                dh, uh, vh, rh, slice_status = out
                status = status.merge(slice_status)
            else:
                dh, uh, vh, rh = out
        else:
            out = panel_loop(dh, uh, vh, rh, chunk, tol=tol,
                             scale=scale, mesh=mesh, dspec=dspec,
                             uvspec=uvspec, status=status)
            if track_status:
                dh, uh, vh, rh, status = out
            else:
                dh, uh, vh, rh = out
        # write factored rows/columns back into the global buffers
        out_diag = out_diag.at[o:o + chunk].set(dh[:chunk])
        out_u = out_u.at[o:, o:o + chunk].set(uh[:, :chunk])
        out_v = out_v.at[o:, o:o + chunk].set(vh[:, :chunk])
        out_ranks = out_ranks.at[o:, o:o + chunk].set(rh[:, :chunk])
        if s < super_panels - 1:
            dh = dh[chunk:]
            uh = uh[chunk:, chunk:]
            vh = vh[chunk:, chunk:]
            rh = rh[chunk:, chunk:]
    if track_status:
        return out_diag, out_u, out_v, out_ranks, status
    return out_diag, out_u, out_v, out_ranks


def _tlr_cholesky_super_pairs(diag, up, vp, ranks, *, layout: PairLayout,
                              tol, scale, mesh, row_axes, super_panels: int,
                              shard_recompress: bool = True,
                              track_status: bool = False):
    """Two-level block-cyclic variant: the live slice's pair set shrinks
    every super-step (a fresh, smaller PairLayout per slice), so the
    recompress batch spans only the live trailing pairs.  Slot remapping
    between layouts is static numpy (slice_positions), lowering to
    constant-index gathers."""
    T = layout.n_tiles
    assert T % super_panels == 0, (T, super_panels)
    assert diag.shape[0] == T, (diag.shape, T)
    chunk = T // super_panels
    shards = layout.n_shards
    dspec, pspec, rspec = _pair_specs(mesh, row_axes)
    axes = pair_axis(mesh, row_axes) if shard_recompress else None
    status = init_status(diag.dtype, ranks) if track_status else None

    out_diag = jnp.zeros_like(diag)
    out_u = jnp.zeros_like(up)
    out_v = jnp.zeros_like(vp)
    out_ranks = jnp.zeros_like(ranks)
    dh, uh, vh, rh = diag, up, vp, ranks
    cur = layout
    for s in range(super_panels):
        o = s * chunk
        ts = T - o
        k_hi = chunk - 1 if s == super_panels - 1 else chunk
        if ts > 1 and k_hi > 0:
            out = pair_panel_loop(dh, uh, vh, rh, k_hi,
                                  layout=cur, tol=tol, scale=scale,
                                  mesh=mesh, dspec=dspec,
                                  pspec=pspec, shard_axes=axes,
                                  status=status)
            if track_status:
                dh, uh, vh, rh, status = out
            else:
                dh, uh, vh, rh = out
        if s == super_panels - 1:
            lkk = cholesky(dh[ts - 1])
            if track_status:
                status = status.update_potrf(lkk)
            dh = dh.at[ts - 1].set(lkk)
        out_diag = out_diag.at[o:o + chunk].set(dh[:chunk])
        # copy the factored pair columns (slice j < chunk) to global slots
        done = cur.valid & (cur.jl < (chunk if s < super_panels - 1 else ts))
        src = np.nonzero(done)[0]
        if len(src):
            dst = layout.pos[cur.il[src] + o, cur.jl[src] + o]
            out_u = out_u.at[dst].set(uh[src])
            out_v = out_v.at[dst].set(vh[src])
            out_ranks = out_ranks.at[dst].set(rh[src])
        if s < super_panels - 1:
            nxt = pair_layout(ts - chunk, shards)
            smap = jnp.asarray(slice_positions(cur, nxt, chunk))
            dh = dh[chunk:]
            uh = uh.at[smap].get(mode="fill", fill_value=0.0)
            vh = vh.at[smap].get(mode="fill", fill_value=0.0)
            rh = rh.at[smap].get(mode="fill", fill_value=0)
            cur = nxt
    out_diag = _constrain(out_diag, mesh, dspec)
    out_u = _constrain(out_u, mesh, pspec)
    out_v = _constrain(out_v, mesh, pspec)
    out_ranks = _constrain(out_ranks, mesh, rspec)
    if track_status:
        return out_diag, out_u, out_v, out_ranks, status
    return out_diag, out_u, out_v, out_ranks


@jax.named_scope("repro.solve")
def dist_tlr_solve_lower(diag_l, u, v, z):
    """Forward substitution with the TLR factor (fori_loop, masked grid) —
    the shared scan body in core.tlr (the single-device tlr_solve_lower is
    the same trace)."""
    return solve_lower_grid(diag_l, u, v, z)


@jax.named_scope("repro.solve")
def dist_tlr_solve_lower_pairs(diag_l, up, vp, z, *, layout: PairLayout):
    """Forward substitution on pair-major storage: step k gathers only the
    live column-k tiles through ``layout.pos[:, k]`` (zero-filled above the
    diagonal) instead of slicing a (T, T) grid — the factor never leaves
    the block-cyclic placement.

    ``z`` may be (m,) or (m, r): the r right-hand sides (a serving c0
    panel batch) share the one sweep over the factor, so the per-RHS cost
    is a GEMM column, not a re-walk of the tiles."""
    T, nb = diag_l.shape[0], diag_l.shape[1]
    single = z.ndim == 1
    r = 1 if single else z.shape[1]
    z = z.reshape(T, nb, r)
    rows = jnp.arange(T)
    pos = jnp.asarray(layout.pos)

    def body(k, carry):
        z, out = carry
        lkk = lax.dynamic_index_in_dim(diag_l, k, 0, keepdims=False)
        zk = lax.dynamic_index_in_dim(z, k, 0, keepdims=False)
        ak = solve_lower(lkk, zk)
        out = lax.dynamic_update_index_in_dim(out, ak, k, 0)
        pcol = lax.dynamic_index_in_dim(pos, k, 1, keepdims=False)
        uk = up.at[pcol].get(mode="fill", fill_value=0.0)
        vk = vp.at[pcol].get(mode="fill", fill_value=0.0)
        wk = jnp.einsum("tnk,nr->tkr", vk, ak)
        delta = jnp.einsum("tnk,tkr->tnr", uk, wk)
        below = (rows > k)[:, None, None]
        z = z - jnp.where(below, delta, 0.0)
        return z, out

    _, out = indexed_scan(body, T, (z, jnp.zeros_like(z)))
    return out.reshape(-1) if single else out.reshape(T * nb, r)


@jax.named_scope("repro.solve")
def dist_tlr_solve_upper_pairs(diag_l, up, vp, y, *, layout: PairLayout):
    """Backward substitution L^T x = y on pair-major storage (the second
    triangular solve of cokriging / alpha = Sigma^{-1} z).

    Row k of L^T x reads ``L_kk^T x_k + sum_{i>k} V_ik U_ik^T x_i`` — the
    transposed column-k tiles, gathered through the same ``layout.pos[:,
    k]`` slot map as the forward sweep.  Sweeping k = T-1 .. 0, the
    not-yet-solved rows of ``out`` are still zero and the sentinel gathers
    fill zero tiles, so no explicit row mask is needed.  Same (m,) or
    (m, r) right-hand-side convention as the forward solve."""
    T, nb = diag_l.shape[0], diag_l.shape[1]
    single = y.ndim == 1
    r = 1 if single else y.shape[1]
    y = y.reshape(T, nb, r)
    pos = jnp.asarray(layout.pos)

    def body(i, out):
        k = T - 1 - i
        pcol = lax.dynamic_index_in_dim(pos, k, 1, keepdims=False)
        uk = up.at[pcol].get(mode="fill", fill_value=0.0)
        vk = vp.at[pcol].get(mode="fill", fill_value=0.0)
        wu = jnp.einsum("tnk,tnr->tkr", uk, out)
        s = jnp.einsum("tnk,tkr->nr", vk, wu)
        lkk = lax.dynamic_index_in_dim(diag_l, k, 0, keepdims=False)
        yk = lax.dynamic_index_in_dim(y, k, 0, keepdims=False)
        xk = solve_lower(lkk, yk - s, transpose=True)
        return lax.dynamic_update_index_in_dim(out, xk, k, 0)

    out = indexed_scan(body, T, jnp.zeros_like(y))
    return out.reshape(-1) if single else out.reshape(T * nb, r)


@jax.named_scope("repro.solve")
def _loglik_of(diag_l, alpha, m: int,
               status: FactorStatus | None = None) -> LoglikResult:
    """Eq. 1 from the factored diagonal tiles and the forward solve.

    With a threaded ``FactorStatus``, a broken factorization yields a
    well-defined finite sentinel loglik (core.recovery.sentinel_loglik)
    instead of propagating NaN into the optimizer."""
    quad = jnp.sum(alpha * alpha)
    logdet = 2.0 * jnp.sum(jnp.log(jnp.diagonal(diag_l, axis1=-2, axis2=-1)))
    ll = -0.5 * (m * math.log(2.0 * math.pi) + logdet + quad)
    if status is not None:
        status = status.add_nonfinite((~jnp.isfinite(ll)).astype(jnp.int32))
        ok = status.ok
        ll = jnp.where(ok, ll, sentinel_loglik(ll.dtype))
        logdet = jnp.where(ok, logdet, jnp.zeros_like(logdet))
        quad = jnp.where(ok, quad, jnp.zeros_like(quad))
    return LoglikResult(ll, logdet, quad, None, status)


def dist_tlr_loglik(t=None, z=None, *, locs=None, params=None,
                    from_tiles: bool = False, tile_size: int = 0,
                    max_rank: int = 64, nugget: float = 0.0,
                    gen: str = "pallas", d_spatial: int = 2,
                    tol: float = 1e-7, scale=None, mesh=None,
                    row_axes=("data",), super_panels: int = 1,
                    block_cyclic: bool = False, layout: PairLayout = None,
                    col_block: int = 1, shard_recompress: bool = True,
                    shard_svd: bool = True,
                    track_status: bool = True,
                    dtype_policy=None) -> LoglikResult:
    """Distributed TLR likelihood (Eq. 1 through the sharded TLR factor).

    Two entry modes:

      * ``dist_tlr_loglik(t, z)`` — factorize pre-compressed tiles
        (TLRMatrix, or PairTLR already in block-cyclic storage).
      * ``dist_tlr_loglik(None, z, locs=..., params=..., from_tiles=True)``
        — the full streaming pipeline: generate + compress column groups
        via dist_compress_tiles (never materializing dense Sigma), then
        factorize and solve.  ``scale`` defaults to max(sigma2) + nugget,
        matching the single-device generator-direct path.

    ``block_cyclic=True`` keeps the whole evaluation pair-native: the
    compression scatters straight into block-cyclic pair-major storage and
    the factorization + forward solve never materialize the (T, T) grid.
    A pre-built PairTLR carries the shard count it was scattered for, so
    its layout is reconstructed correctly by default; an explicit
    ``layout`` must match it (ValueError otherwise — two layouts of the
    same T can share a length while ordering slots differently).
    ``shard_recompress`` (block-cyclic only) runs the recompress QR/SVD
    under shard_map over the pair axis (distribution/pair_qr.py);
    ``shard_svd`` does the same for the compression-phase truncation SVDs
    (and, pair-native, the GEN panel itself — see dist_compress_tiles).
    ``track_status`` (default on) threads a ``FactorStatus`` through the
    factorization — in-graph, no host sync — and the returned
    ``LoglikResult.status.ok`` is a traced scalar; on breakdown the loglik
    is the finite sentinel, never NaN.  ``track_status=False`` restores
    the bare 4-field result (the A/B overhead baseline in bench_tlr).
    ``dtype_policy`` (name or :class:`~repro.core.precision.PrecisionPolicy`)
    stores off-diagonal U/V at the policy's narrow dtype during the
    from-tiles compression; the factorization widens at the TRSM/SYRK
    boundaries (see core.tlr) and the logdet stays wide.
    """
    if isinstance(t, PairTLR):
        block_cyclic = True
    if from_tiles:
        if locs is None or params is None:
            raise ValueError("from_tiles=True requires locs and params")
        if scale is None:
            scale = jnp.max(params.sigma2) + nugget
        if not block_cyclic:
            layout = None
        else:
            m = jnp.asarray(locs).shape[0] * params.p
            nb = choose_tile_size(m, tile_size, multiple_of=params.p)
            if layout is None:
                layout = pair_layout(m // nb, pair_shards(mesh, row_axes))
            elif layout.n_tiles != m // nb:
                raise ValueError(f"layout covers n_tiles={layout.n_tiles} "
                                 f"but the tile grid has {m // nb}")
        t = dist_compress_tiles(locs, params, tile_size=tile_size, tol=tol,
                                max_rank=max_rank, nugget=nugget, gen=gen,
                                d_spatial=d_spatial, scale=scale, mesh=mesh,
                                row_axes=row_axes, layout=layout,
                                col_block=col_block, shard_svd=shard_svd,
                                dtype_policy=dtype_policy)
    elif t is None:
        raise ValueError("pass a TLRMatrix/PairTLR, or locs/params with "
                         "from_tiles=True")
    if scale is None:
        scale = 1.0
    if block_cyclic:
        if isinstance(t, PairTLR):
            if layout is None:
                layout = pair_layout(t.n_tiles, t.n_shards)
            elif layout.n_shards != t.n_shards:
                raise ValueError(
                    f"PairTLR was scattered for n_shards={t.n_shards} but "
                    f"layout has n_shards={layout.n_shards}; slot orders "
                    "differ")
        else:
            if layout is None:
                layout = pair_layout(t.n_tiles, pair_shards(mesh, row_axes))
            t = PairTLR(diag=t.diag, u=grid_to_pairs(t.u, layout),
                        v=grid_to_pairs(t.v, layout),
                        ranks=grid_to_pairs(t.ranks, layout),
                        n_shards=layout.n_shards)
    status = None
    if block_cyclic:
        out = dist_tlr_cholesky_pairs(
            t.diag, t.u, t.v, t.ranks, layout=layout, tol=tol, scale=scale,
            mesh=mesh, row_axes=row_axes, super_panels=super_panels,
            shard_recompress=shard_recompress, track_status=track_status)
        diag_l, u, v = out[0], out[1], out[2]
        if track_status:
            status = out[4]
        alpha = dist_tlr_solve_lower_pairs(diag_l, u, v, z, layout=layout)
    else:
        out = dist_tlr_cholesky(t.diag, t.u, t.v, t.ranks,
                                tol=tol, scale=scale, mesh=mesh,
                                row_axes=row_axes,
                                super_panels=super_panels,
                                track_status=track_status)
        diag_l, u, v = out[0], out[1], out[2]
        if track_status:
            status = out[4]
        alpha = dist_tlr_solve_lower(diag_l, u, v, z)
    return _loglik_of(diag_l, alpha, t.shape[0], status=status)


# ---------------------------------------------------------------------------
# Dry-run lowerables (launch/dryrun.py): the three pipeline phases, separately
# compilable so the roofline can report GEN / compress / factorize costs.
# ---------------------------------------------------------------------------


def dist_tlr_lowerable(n_tiles: int, tile_size: int, kmax: int, *, tol: float,
                       mesh, dtype=jnp.float32, row_axes=("data",),
                       super_panels: int = 1, block_cyclic: bool = False,
                       return_factor: bool = False,
                       shard_recompress: bool = True,
                       dtype_policy=None):
    """(fn, input specs) for the factorize + solve stage from pre-compressed
    tiles.  Real per-tile ranks are threaded as an input — consumers must not
    fabricate them (rank-0 strict-lower tiles would misread as empty; see the
    fixed-kmax convention on TLRMatrix).  ``block_cyclic=True`` takes the
    tiles in pair-major storage ((length, nb, kmax) U/V, (length,) ranks) so
    dry-run cost tables can compare both forms in one invocation.

    ``return_factor=True`` additionally returns the factored (diag_L, U, V,
    ranks) — the in-place production semantics.  Jit that variant with
    ``donate_argnums=(0, 1, 2, 3)``: the tile inputs then alias the factor
    outputs instead of being double-buffered (the donate/alias half of the
    §Perf temp-footprint item; the dry-run and bench record the resulting
    alias/temp bytes).

    ``shard_recompress`` (block_cyclic only) shards the recompress QR/SVD
    over the pair axis via shard_map — the production setting; False
    compiles the PR-3 replicated-batch form so the dry-run can report the
    per-device recompress temp drop.

    ``dtype_policy`` splits the input spec dtypes the way the mixed
    pipeline stores them: diag/z at the policy's wide dtype, U/V at its
    narrow dtype (``dtype`` is ignored when a policy is given)."""
    row = _row(row_axes)
    T, nb = n_tiles, tile_size
    policy = resolve_policy(dtype_policy)
    if policy is None:
        wide_dtype = uv_dtype = dtype
    else:
        wide_dtype = jnp.dtype(policy.wide_dtype)
        uv_dtype = jnp.dtype(policy.narrow_dtype)

    if block_cyclic:
        layout = pair_layout(T, pair_shards(mesh, row_axes))
        dspec, pspec, _ = _pair_specs(mesh, row_axes)

        def fn(diag, u, v, ranks, z):
            diag = _constrain(diag, mesh, dspec)
            u = _constrain(u, mesh, pspec)
            v = _constrain(v, mesh, pspec)
            diag_l, u, v, ranks = dist_tlr_cholesky_pairs(
                diag, u, v, ranks, layout=layout, tol=tol, scale=1.0,
                mesh=mesh, row_axes=row_axes, super_panels=super_panels,
                shard_recompress=shard_recompress)
            alpha = dist_tlr_solve_lower_pairs(diag_l, u, v, z, layout=layout)
            res = _loglik_of(diag_l, alpha, T * nb)
            if return_factor:
                return res, (diag_l, u, v, ranks)
            return res

        specs = (jax.ShapeDtypeStruct((T, nb, nb), wide_dtype),
                 jax.ShapeDtypeStruct((layout.length, nb, kmax), uv_dtype),
                 jax.ShapeDtypeStruct((layout.length, nb, kmax), uv_dtype),
                 jax.ShapeDtypeStruct((layout.length,), jnp.int32),
                 jax.ShapeDtypeStruct((T * nb,), wide_dtype))
        return fn, specs

    def fn(diag, u, v, ranks, z):
        diag = _constrain(diag, mesh, P(row, None, None))
        u = _constrain(u, mesh, P(row, "model", None, None))
        v = _constrain(v, mesh, P(row, "model", None, None))
        diag_l, u, v, ranks = dist_tlr_cholesky(
            diag, u, v, ranks, tol=tol, scale=1.0, mesh=mesh,
            row_axes=row_axes, super_panels=super_panels)
        alpha = dist_tlr_solve_lower(diag_l, u, v, z)
        res = _loglik_of(diag_l, alpha, T * nb)
        if return_factor:
            return res, (diag_l, u, v, ranks)
        return res

    specs = (jax.ShapeDtypeStruct((T, nb, nb), wide_dtype),
             jax.ShapeDtypeStruct((T, T, nb, kmax), uv_dtype),
             jax.ShapeDtypeStruct((T, T, nb, kmax), uv_dtype),
             jax.ShapeDtypeStruct((T, T), jnp.int32),
             jax.ShapeDtypeStruct((T * nb,), wide_dtype))
    return fn, specs


def dist_tlr_in_shardings(*, mesh, row_axes=("data",),
                          block_cyclic: bool = False):
    """NamedShardings matching dist_tlr_lowerable's input specs."""
    from jax.sharding import NamedSharding
    row = _row(row_axes)
    if block_cyclic:
        dspec, pspec, rspec = _pair_specs(mesh, row_axes)
        specs = (dspec, pspec, pspec, rspec, P(row))
    else:
        specs = (P(row, None, None), P(row, "model", None, None),
                 P(row, "model", None, None), P(row, "model"), P(row))
    return tuple(NamedSharding(mesh, s) for s in specs)


def dist_tlr_gen_lowerable(n: int, p: int, params, *, tile_size: int,
                           gen: str = "xla", mesh,
                           dtype=jnp.float32, row_axes=("data",),
                           d_spatial: int = 2):
    """GEN phase alone: stream every column panel through the same fori_loop
    as dist_compress_tiles but reduce each to a checksum (keeps the
    generation live for cost analysis without the SVD).  The O(nb) diagonal
    nugget-add is accounted to the compress phase, so no nugget here."""
    row = _row(row_axes)
    m = n * p
    nb = choose_tile_size(m, tile_size, multiple_of=p)
    nbl = nb // p
    T = m // nb

    def fn(locs):
        def body(j, acc):
            panel = build_sigma_column(locs, j, nbl, params,
                                       d_spatial=d_spatial, gen=gen, block=nb)
            panel = _constrain(panel, mesh, P(row, "model"))
            return acc + jnp.sum(panel * panel)

        return indexed_scan(body, T, jnp.zeros((), dtype))

    return fn, (jax.ShapeDtypeStruct((n, 2), dtype),)


def dist_tlr_compress_lowerable(n: int, p: int, params, *, tile_size: int,
                                max_rank: int, tol: float, nugget: float = 0.0,
                                gen: str = "xla", mesh, dtype=jnp.float32,
                                row_axes=("data",), block_cyclic: bool = False,
                                col_block: int = 1, shard_svd: bool = True,
                                dtype_policy=None):
    """GEN + compress: locations -> sharded fixed-kmax D/U/V/ranks (grid or
    block-cyclic pair-major).  ``shard_svd=False`` compiles the PR-4
    replicated truncation batch so the dry-run can report the per-device
    compress temp drop the sharding buys.  ``dtype_policy``: generate wide,
    store U/V narrow (locations enter at the policy's wide dtype)."""
    layout = None
    if block_cyclic:
        m = n * p
        nb = choose_tile_size(m, tile_size, multiple_of=p)
        layout = pair_layout(m // nb, pair_shards(mesh, row_axes))
    policy = resolve_policy(dtype_policy)
    if policy is not None:
        dtype = jnp.dtype(policy.wide_dtype)

    def fn(locs):
        t = dist_compress_tiles(locs, params, tile_size=tile_size, tol=tol,
                                max_rank=max_rank, nugget=nugget, gen=gen,
                                mesh=mesh, row_axes=row_axes, layout=layout,
                                col_block=col_block, shard_svd=shard_svd,
                                dtype_policy=dtype_policy)
        return t.diag, t.u, t.v, t.ranks

    return fn, (jax.ShapeDtypeStruct((n, 2), dtype),)


def dist_tlr_pipeline_lowerable(n: int, p: int, params, *, tile_size: int,
                                max_rank: int, tol: float, nugget: float = 0.0,
                                gen: str = "xla", mesh, dtype=jnp.float32,
                                row_axes=("data",), super_panels: int = 1,
                                block_cyclic: bool = False,
                                col_block: int = 1,
                                shard_recompress: bool = True,
                                shard_svd: bool = True,
                                dtype_policy=None):
    """End-to-end generator-direct pipeline: (locs, z) -> GEN -> compress ->
    factorize -> loglik, with real Matérn tiles (no random-spec stand-ins).
    ``dtype_policy``: locations/observations enter at the policy's wide
    dtype; U/V storage and the truncation SVDs run narrow."""
    policy = resolve_policy(dtype_policy)
    if policy is not None:
        dtype = jnp.dtype(policy.wide_dtype)

    def fn(locs, z):
        return dist_tlr_loglik(None, z, locs=locs, params=params,
                               from_tiles=True, tile_size=tile_size,
                               max_rank=max_rank, nugget=nugget, gen=gen,
                               tol=tol, mesh=mesh, row_axes=row_axes,
                               super_panels=super_panels,
                               block_cyclic=block_cyclic,
                               col_block=col_block,
                               shard_recompress=shard_recompress,
                               shard_svd=shard_svd,
                               dtype_policy=dtype_policy)

    specs = (jax.ShapeDtypeStruct((n, 2), dtype),
             jax.ShapeDtypeStruct((n * p,), dtype))
    return fn, specs
