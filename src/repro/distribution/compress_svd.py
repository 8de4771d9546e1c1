"""Pair-axis-partitioned compression-phase truncation SVD (the ROADMAP
"shard the per-column-group truncation SVDs" item — the compress-phase
counterpart of distribution/pair_qr.py).

The generator-direct compression (core.dist_tlr.dist_compress_tiles) SVDs
every strict-lower tile of a column group in one (cb*T, nb, nb) batch.  The
per-tile truncations are independent — HiCMA/ExaGeoStat schedule them as
independent tasks (Abdulah et al. 2018, arXiv:1804.09137) — but under plain
GSPMD the batched ``jnp.linalg.svd`` carries no partitioning rule, so after
PR 4 sharded the factorize-phase QR/SVD this batch became the dominant
per-device temp (~3.2 GB/device at mle_65k on the 256-device pod).

``sharded_truncate_svd`` runs the identical SVD + fixed-kmax truncation
under ``shard_map`` over the leading tile axis, so each device holds only
its ~batch/S tiles of SVD workspace.  Indivisible batch lengths are
zero-padded to a multiple of the shard count and stripped after
(``pair_qr.pad_leading`` — zero tiles SVD to zeros); with ``mesh=None`` or
an empty axis tuple the call is exactly the replicated batch (the PR-4
fallback contract: one code path, two placements).

The deeper form — each device *generating* only the tiles whose block-cyclic
slots it owns, so the GEN panel itself never replicates — lives in
``core.dist_tlr._compress_tiles_pair_sharded`` on top of
``distribution.block_cyclic.column_owner_tables``; this module is the
placement-agnostic batch primitive both forms share.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .pair_qr import pad_leading, pair_shard_count

__all__ = ["svd_truncate_batch", "sharded_truncate_svd"]


def svd_truncate_batch(tiles, tol, kmax: int, scale):
    """(B, nb, nb) tiles -> (U, V, ranks): rank <= kmax truncation at
    ``tol * scale`` in the fixed-kmax layout (core.tlr.truncate_core), the
    math every compression entry point runs.  ``scale`` may be traced.

    Tiles wider than 2*kmax are compressed by randomized SVD (Halko,
    Martinsson & Tropp 2011, Algs. 4.1 and 5.1; the RSDD compression of
    STARS-H, the generator library under the paper's HiCMA stack): an
    orthonormal basis Q of ``A @ Omega`` for a fixed Gaussian Omega with
    2*kmax columns, then the exact SVD of the (2*kmax)-wide core of
    ``Q Q^T A``.  The oversampling (2x the rank cap) makes the truncation
    match the exact SVD's to round-off on Matérn tiles, and it keeps an
    nb x nb SVD out of the program: for v5e XLA's takes 337 s to compile
    at nb = 2048, in f32.  Narrower tiles take the exact SVD.
    """
    from ..core.tlr import _safe_qr, truncate_core

    nb = tiles.shape[-1]
    if 2 * kmax >= nb:
        return truncate_core(tiles, tol, kmax, scale)[:3]
    omega = jax.random.normal(jax.random.key(0), (nb, 2 * kmax), tiles.dtype)
    q, _ = _safe_qr(tiles @ omega)                        # (B, nb, 2k)
    qb, rb = _safe_qr(jnp.swapaxes(tiles, -1, -2) @ q)    # A^T Q = Qb Rb
    # Q Q^T A = Q Rb^T Qb^T
    return truncate_core(jnp.swapaxes(rb, -1, -2), tol, kmax, scale,
                         left=q, right=qb)[:3]


def sharded_truncate_svd(tiles, tol, kmax: int, scale, *, mesh=None,
                         axes=None):
    """Truncation SVD of a (B, nb, nb) tile batch, sharded over the tile
    axis.

    Identical math to ``svd_truncate_batch`` but executed under
    ``shard_map`` over ``axes`` (the mesh axis names the batch axis is laid
    out over), so each device SVDs only its own ~B/S tiles — no collective
    is needed, the map is embarrassingly parallel.  ``mesh=None`` / empty
    ``axes`` is exactly the replicated batch; an indivisible B is
    zero-padded to a multiple of the shard count and stripped after.
    Returns (U, V, ranks) with U/V zero-padded to kmax columns and ranks
    int32 of shape (B,).
    """
    axes = tuple(axes) if axes else ()
    shards = pair_shard_count(mesh, axes)
    if mesh is None or not axes:
        return svd_truncate_batch(tiles, tol, kmax, scale)
    (tiles,), length = pad_leading((tiles,), shards)
    spec = P(axes, None, None)
    scale = jnp.asarray(scale)

    def local(tl, sc):
        return svd_truncate_batch(tl, tol, kmax, sc)

    fn = jax.shard_map(local, mesh=mesh, in_specs=(spec, P()),
                       out_specs=(spec, spec, P(axes)), check_vma=False)
    U, V, R = fn(tiles, scale)
    return U[:length], V[:length], R[:length]
