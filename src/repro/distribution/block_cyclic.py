"""Block-cyclic placement of the strict-lower TLR tile-pair set.

The masked full-grid factorization (core/dist_tlr.py, the paper-faithful
SPMD baseline) batches every panel step's GEMM + recompress over all T^2
tiles of the (T, T) grid so the 2-D tile sharding never moves: ~6x flop
overcompute versus the live triangle.  The single-device scan form instead
batches the *static strict-lower pair list* — T(T-1)/2 tasks, ~2.4x cheaper
— but a naive gather of that list from a P(row, "model") grid would reshard
every step.

This module makes the pair-batch form shardable the way ExaGeoStat/PaRSEC
schedule it (Abdulah et al. 2018; arXiv:1804.09137): keep the strict-lower
tiles in a *pair-major* layout, a (length,) leading axis laid out
block-cyclically over the devices, and never materialize the (T, T) grid.

Layout contract (``pair_layout``):

  * pairs are enumerated column-major — (1,0), (2,0), ..., (T-1,0), (2,1),
    ... — so the pairs a panel step k retires (column j = k) form a prefix
    of the enumeration, and the pairs it updates (j > k) the suffix;
  * enumeration index q is placed at slot ``(q % S) * pairs_per_shard +
    (q // S)`` for S shards.  Standard contiguous sharding of the leading
    axis then gives shard d the cyclically-dealt pairs {d, d+S, d+2S, ...},
    so at *every* panel step each shard holds within one pair of
    live_pairs/S — the live trailing-submatrix work stays load-balanced as
    columns die, which contiguous (block) placement cannot do;
  * the list is zero-padded to a multiple of S with (0, 0) entries, which
    fail the strict-lower predicate ``il > jl`` and are masked everywhere.

Under the cyclic deal that suffix is also a suffix of every shard's local
slots (the pads sit at the local ends), so a panel step need recompress
only the last ``b`` local slots of each shard: ``window_schedule`` picks
those static widths.

``pos`` inverts the map: ``pos[i, j]`` is the slot of strict-lower pair
(i, j), and ``length`` (one past the end — genuinely out-of-bounds, since
jax wraps *negative* indices instead of dropping them) elsewhere, so a
traced panel index k can gather/scatter its column's tiles with
``x.at[pos[:, k]].get(mode="fill")`` / ``.set(mode="drop")`` — the only
per-step communication is the panel-column broadcast the algorithm needs
anyway.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np

import jax.numpy as jnp

__all__ = ["PairLayout", "pair_layout", "pair_shards", "pair_axis",
           "grid_to_pairs", "pairs_to_grid", "slice_positions",
           "column_owner_tables", "owned_pair_tables", "WindowSegment",
           "WindowSchedule", "window_schedule"]


class PairLayout(NamedTuple):
    """Static (numpy) description of one block-cyclic pair placement."""

    n_tiles: int
    n_shards: int
    pairs_per_shard: int
    il: np.ndarray      # (length,) int32 row tile index; pads are (0, 0)
    jl: np.ndarray      # (length,) int32 col tile index
    pos: np.ndarray     # (T, T) int32 slot of pair (i, j); `length`
                        # (out-of-bounds) elsewhere

    @property
    def length(self) -> int:
        return int(self.il.shape[0])

    @property
    def n_pairs(self) -> int:
        return self.n_tiles * (self.n_tiles - 1) // 2

    @property
    def valid(self) -> np.ndarray:
        return self.il > self.jl


@functools.lru_cache(maxsize=None)
def pair_layout(n_tiles: int, n_shards: int = 1) -> PairLayout:
    """Block-cyclic layout of the strict-lower pairs of a (T, T) tile grid."""
    if n_tiles < 1 or n_shards < 1:
        raise ValueError(f"need n_tiles, n_shards >= 1, got "
                         f"{(n_tiles, n_shards)}")
    jj, ii = np.meshgrid(np.arange(n_tiles), np.arange(n_tiles),
                         indexing="ij")          # column-major enumeration
    keep = ii > jj
    ei, ej = ii[keep], jj[keep]                  # sorted by j, then i
    n_pairs = len(ei)
    pairs_per_shard = max(-(-n_pairs // n_shards), 1)
    length = pairs_per_shard * n_shards
    il = np.zeros(length, np.int32)
    jl = np.zeros(length, np.int32)
    q = np.arange(n_pairs)
    slot = (q % n_shards) * pairs_per_shard + q // n_shards
    il[slot] = ei
    jl[slot] = ej
    pos = np.full((n_tiles, n_tiles), length, np.int32)
    pos[ei, ej] = slot
    return PairLayout(n_tiles=n_tiles, n_shards=n_shards,
                      pairs_per_shard=pairs_per_shard, il=il, jl=jl, pos=pos)


def pair_shards(mesh, row_axes=("data",)) -> int:
    """Number of shards the pair axis spans: every row axis AND "model" —
    the pair list is 1-D, so the whole mesh can split it."""
    if mesh is None:
        return 1
    axes = tuple(row_axes) + ("model",)
    total = 1
    for a in axes:
        if a in mesh.axis_names:
            total *= mesh.shape[a]
    return total


def pair_axis(mesh, row_axes=("data",)):
    """The PartitionSpec entry for the pair axis (None off-mesh)."""
    if mesh is None:
        return None
    return tuple(a for a in tuple(row_axes) + ("model",)
                 if a in mesh.axis_names)


def grid_to_pairs(x, layout: PairLayout):
    """(T, T, ...) strict-lower grid -> (length, ...) pair-major array.

    Pads read grid[0, 0], which is structurally zero in strict-lower
    storage, so pad slots carry zeros.
    """
    return x[jnp.asarray(layout.il), jnp.asarray(layout.jl)]


def pairs_to_grid(xp, layout: PairLayout):
    """(length, ...) pair-major array -> dense (T, T, ...) grid (zeros
    outside the strict lower triangle)."""
    T = layout.n_tiles
    keep = np.nonzero(layout.valid)[0]
    out = jnp.zeros((T, T) + xp.shape[1:], xp.dtype)
    return out.at[layout.il[keep], layout.jl[keep]].set(xp[keep])


@functools.lru_cache(maxsize=None)
def _column_owner_tables(n_tiles: int, n_shards: int):
    layout = pair_layout(n_tiles, n_shards)
    T, S, pps = layout.n_tiles, layout.n_shards, layout.pairs_per_shard
    per_col = max(-(-(T - 1) // S), 1)
    rows = np.full((S, T, per_col), T, np.int32)
    slots = np.full((S, T, per_col), pps, np.int32)
    counts = np.zeros((S, T), np.int32)
    for s in np.nonzero(layout.valid)[0]:
        i, j = int(layout.il[s]), int(layout.jl[s])
        d, local = s // pps, s % pps
        rows[d, j, counts[d, j]] = i
        slots[d, j, counts[d, j]] = local
        counts[d, j] += 1
    return rows, slots


def column_owner_tables(layout: PairLayout):
    """Per-shard, per-column slot ownership of the block-cyclic deal.

    Returns ``(rows, slots)``, int32 arrays of shape (S, T, L) with
    L = ceil((T-1)/S): ``rows[d, j]`` lists the strict-lower row tiles i of
    tile column j whose pair slot shard d owns, and ``slots[d, j]`` the
    matching *shard-local* slot index.  Because column j's pairs are
    consecutive in the column-major enumeration and the deal is cyclic,
    every shard owns floor/ceil((T-1-j)/S) of them — the per-column GEN +
    SVD work stays balanced at every column, which is what lets the
    compression generate only owned tiles per device
    (core.dist_tlr._compress_tiles_pair_sharded).

    Unused entries carry sentinels — row ``T`` (out of bounds for a
    mode="fill" location gather) and local slot ``pairs_per_shard`` (out of
    bounds for a mode="drop" scatter into the (pairs_per_shard, ...) local
    shard) — mirroring the ``pos`` sentinel convention above.  All static
    numpy, derived from (n_tiles, n_shards) alone.
    """
    return _column_owner_tables(layout.n_tiles, layout.n_shards)


@functools.lru_cache(maxsize=None)
def _owned_pair_tables(n_tiles: int, n_shards: int):
    layout = pair_layout(n_tiles, n_shards)
    T, S, pps = layout.n_tiles, layout.n_shards, layout.pairs_per_shard
    valid = layout.valid
    rows = np.where(valid, layout.il, T).astype(np.int32).reshape(S, pps)
    cols = np.where(valid, layout.jl, T).astype(np.int32).reshape(S, pps)
    return rows, cols


def owned_pair_tables(layout: PairLayout):
    """Per-shard (row, col) tile indices of the owned pairs, slot-major.

    Returns ``(rows, cols)``, int32 arrays of shape (S, pairs_per_shard):
    ``rows[d, q]`` / ``cols[d, q]`` are the (i, j) tile coordinates of the
    pair living at shard d's *local* slot q — exactly the order the pair
    arrays store them (global slot = d * pairs_per_shard + q), so a
    generator sweeping local slots writes each result at its own index
    with no scatter indirection.  Pad slots carry the row = col = ``T``
    sentinel (out of bounds for a mode="fill" location gather), mirroring
    ``pos``'s convention.

    This is the slot-major complement of ``column_owner_tables``: that
    table answers "which of column j's pairs does shard d own" (the
    per-column sweep, which generates ceil((T-1)/S) candidate tiles per
    column — T * ceil((T-1)/S) per full sweep, mostly sentinels once
    S >> T-1); this one answers "which pair lives at local slot q" (the
    slot-major sweep, which generates exactly pairs_per_shard ~
    T(T-1)/(2S) tiles per device, the owned set and nothing else).  All
    static numpy, derived from (n_tiles, n_shards) alone.
    """
    return _owned_pair_tables(layout.n_tiles, layout.n_shards)


def slice_positions(outer: PairLayout, inner: PairLayout, offset: int
                    ) -> np.ndarray:
    """Slot map for trailing-submatrix slicing (the super-panel loop).

    Returns src (length_inner,) int32: inner slot q holds the pair that
    lives at outer slot src[q] (pair (i + offset, j + offset));
    ``outer.length`` (out-of-bounds, for mode="fill" gathers) at inner
    pads.  All static numpy, so gathers lower as constant-index ops.
    """
    src = np.full(inner.length, outer.length, np.int32)
    keep = inner.valid
    src[keep] = outer.pos[inner.il[keep] + offset, inner.jl[keep] + offset]
    return src


class WindowSegment(NamedTuple):
    """Panel steps ``[k0, k1)`` that recompress the last ``width`` local
    slots of every shard (0: no recompression at all)."""

    k0: int
    k1: int
    width: int


class WindowSchedule(NamedTuple):
    """Static live-pair windows of one pair panel loop.

    ``computed`` counts the pair-recompressions the windows run (every
    shard's ``width`` slots at every step); ``live`` those the steps need
    (pairs i > j > k).  A loop that recompresses every slot at every step
    computes ``k_hi * layout.length``.
    """

    segments: tuple
    computed: int
    live: int


@functools.lru_cache(maxsize=None)
def _window_schedule(n_tiles: int, n_shards: int, k_hi: int):
    layout = pair_layout(n_tiles, n_shards)
    S, pps = layout.n_shards, layout.pairs_per_shard
    segments, computed, live_total = [], 0, 0
    for k in range(k_hi):
        live = (layout.valid & (layout.jl > k)).reshape(S, pps)
        n_live = int(live.sum())
        # the live slots of a shard are a suffix of its local slots: the
        # window must reach back to the earliest first live slot
        need = max((pps - int(np.argmax(row)) for row in live if row.any()),
                   default=0)
        width = segments[-1].width if segments else 0
        if segments and not (width > 0 and (
                2 * need <= width or S * width > 2 * n_live + 2 * S)):
            segments[-1] = segments[-1]._replace(k1=k + 1)
        else:
            segments.append(WindowSegment(k, k + 1, need))
        computed += S * segments[-1].width
        live_total += n_live
    return WindowSchedule(tuple(segments), computed, live_total)


def window_schedule(layout: PairLayout, k_hi: int) -> WindowSchedule:
    """Segments of panel steps ``[0, k_hi)`` with one static window width
    each, covering every live pair (i > j > k) of every step.

    A step's live pairs are a suffix of each shard's local slots, so the
    width it needs is the longest such suffix over the shards.  Widths only
    fall as k grows; a segment keeps its first step's width and a new one
    starts when the need falls to half of it, or when the segment would
    compute more than ``2 * live + 2 * S`` pairs, so there are O(log T)
    segments, each one compiled scan body.  A step with no live pair gets
    width 0.  All static, from (n_tiles, n_shards, k_hi) alone: at
    T = 4, S = 1 the widths are (3, 1, 0) and 4 of 4 live
    pair-recompressions are computed, where the unwindowed loop computes
    18.
    """
    return _window_schedule(layout.n_tiles, layout.n_shards, int(k_hi))
