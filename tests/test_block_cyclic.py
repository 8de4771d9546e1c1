"""Block-cyclic pair placement (distribution/block_cyclic.py): layout
invariants, grid round-trips, live-pair load balance, and the live-pair
windows of the pair panel loop."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.distribution.block_cyclic import (grid_to_pairs, pair_axis,
                                             pair_layout, pair_shards,
                                             pairs_to_grid, slice_positions,
                                             window_schedule)


@pytest.mark.parametrize("T,S", [(2, 1), (6, 1), (6, 4), (8, 8), (9, 5),
                                 (16, 256)])
def test_layout_invariants(T, S):
    lay = pair_layout(T, S)
    assert lay.length == lay.pairs_per_shard * S
    assert lay.length >= lay.n_pairs
    assert lay.length - lay.n_pairs < S or lay.n_pairs == 0
    # every strict-lower pair appears exactly once, pos inverts the map
    il, jl = np.tril_indices(T, k=-1)
    got = sorted(zip(lay.il[lay.valid].tolist(), lay.jl[lay.valid].tolist()))
    assert got == sorted(zip(il.tolist(), jl.tolist()))
    for i, j in zip(il, jl):
        s = lay.pos[i, j]
        assert (lay.il[s], lay.jl[s]) == (i, j)
    # invalid slots use the out-of-bounds sentinel (jax wraps negatives)
    iu, ju = np.triu_indices(T)
    assert (lay.pos[iu, ju] == lay.length).all()


def test_layout_live_pair_balance():
    """At every panel step k the live pairs (j > k) on each shard differ by
    at most one — the point of the cyclic deal (contiguous placement would
    idle the shards owning retired columns)."""
    T, S = 16, 8
    lay = pair_layout(T, S)
    shard_of = np.arange(lay.length) // lay.pairs_per_shard
    for k in range(T - 1):
        live = lay.valid & (lay.jl > k)
        counts = np.bincount(shard_of[live], minlength=S)
        assert counts.max() - counts.min() <= 1, (k, counts)


def test_grid_pairs_round_trip():
    T, S = 7, 4
    lay = pair_layout(T, S)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(T, T, 3, 2)))
    x = jnp.where((np.arange(T)[:, None] > np.arange(T)[None, :])
                  [:, :, None, None], x, 0.0)   # strict-lower support
    xp = grid_to_pairs(x, lay)
    assert xp.shape == (lay.length, 3, 2)
    np.testing.assert_array_equal(np.asarray(pairs_to_grid(xp, lay)),
                                  np.asarray(x))


def test_slice_positions_trailing_submatrix():
    T, S, off = 9, 4, 3
    outer = pair_layout(T, S)
    inner = pair_layout(T - off, S)
    src = slice_positions(outer, inner, off)
    assert src.shape == (inner.length,)
    for q in range(inner.length):
        if inner.valid[q]:
            assert (outer.il[src[q]], outer.jl[src[q]]) == \
                (inner.il[q] + off, inner.jl[q] + off)
        else:
            assert src[q] == outer.length          # OOB fill sentinel


def test_pair_shards_and_axis_off_mesh():
    assert pair_shards(None) == 1
    assert pair_axis(None) is None


@pytest.mark.parametrize("S", [1, 2, 4, 8])
def test_window_schedule_covers_every_live_pair(S):
    """For T <= 16 and every loop length a super-step can ask for: the
    segments tile [0, k_hi), every live pair (i > j > k) of every step lies
    in the last ``width`` local slots of its shard, a step computes at most
    2 live + 2 S pair-recompressions (none when nothing is live), and the
    counters add up."""
    for T in range(1, 17):
        lay = pair_layout(T, S)
        pps = lay.pairs_per_shard
        local = np.arange(lay.length) % pps
        for k_hi in sorted({max(T - 1, 0), T // 2}):
            sched = window_schedule(lay, k_hi)
            steps = [k for seg in sched.segments for k in range(seg.k0, seg.k1)]
            assert steps == list(range(k_hi)), (T, S, k_hi, sched)
            assert len(sched.segments) <= 2 * T.bit_length() + 1
            computed = live_total = 0
            for seg in sched.segments:
                for k in range(seg.k0, seg.k1):
                    live = lay.valid & (lay.jl > k)
                    n_live = int(live.sum())
                    assert (local[live] >= pps - seg.width).all(), (T, S, k)
                    assert S * seg.width <= 2 * n_live + 2 * S, (T, S, k)
                    if n_live == 0:
                        assert seg.width == 0, (T, S, k)
                    computed += S * seg.width
                    live_total += n_live
            assert (sched.computed, sched.live) == (computed, live_total)


def test_window_schedule_four_tiles_one_shard():
    """The one-chip TLR7 cell's layout (T = 4, one shard): widths 3, 1, 0,
    so the loop computes the 4 live pair-recompressions and no more (the
    unwindowed loop computed 3 steps x 6 slots = 18)."""
    lay = pair_layout(4, 1)
    sched = window_schedule(lay, 3)
    assert [(s.k0, s.k1, s.width) for s in sched.segments] == \
        [(0, 1, 3), (1, 2, 1), (2, 3, 0)]
    assert (sched.computed, sched.live) == (4, 4)
