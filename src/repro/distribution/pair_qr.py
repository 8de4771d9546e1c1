"""Pair-axis-partitioned recompression QR/SVD (the ROADMAP "partitionable
batched QR" item).

The GEMM-phase recompression of the TLR Cholesky — concat the (nb, k) update
pair, QR both factors, SVD the small core, truncate — is a purely per-pair
batch: there is no cross-pair dataflow.  ExaGeoStat/HiCMA schedule it as
independent per-tile tasks (Abdulah et al. 2018, arXiv:1804.09137); our SPMD
form batches it over the block-cyclic pair axis, but under plain GSPMD the
compiler keeps the (length, nb, 2k) QR/SVD batch *replicated* on every device
(batched jnp.linalg.qr/svd carry no partitioning rule), which made the
recompress workspace the dominant per-device factorize temp (~13.5 GB/device
at mle_65k on the 256-device pod — ROADMAP PR-3 note).

``sharded_recompress`` runs the identical per-pair math under ``shard_map``
over the pair axis: every device QRs only its own ~length/S block-cyclic
slots (which ``pair_layout`` keeps within one pair of balanced at every panel
step), so the recompress workspace scales O(pairs/S) per device instead of
O(pairs).  No collective is needed — the map is embarrassingly parallel, the
out specs simply re-assert the input placement.

Fallback contract: with ``mesh=None`` (the single-device tests/benches) or an
empty axis tuple, the call is exactly ``core.tlr._batched_recompress`` — one
code path, two placements.  A batch length the mesh axes don't divide is
zero-padded to the next multiple of the shard count and stripped after
(``pad_leading`` — zero slots QR/SVD to zeros, so padding is free), so the
sharding survives indivisible lengths instead of silently replicating; a
caller that disables padding (``pad=False``) gets the replicated batch plus
a one-time ``RuntimeWarning`` (``warn_fallback_once``) so the perf cliff is
never silent.  ``distribution/compress_svd.py`` reuses the same helpers for
the compression-phase truncation SVDs.

``window_recompress`` is the form the block-cyclic panel loop runs: it
recompresses only a static window, the last ``b`` local slots of every
shard (``distribution.block_cyclic.window_schedule``), slicing and writing
back inside the ``shard_map`` so the window never gathers another device's
slots.
"""
from __future__ import annotations

import math
import warnings

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["pair_shard_count", "pad_leading", "warn_fallback_once",
           "sharded_recompress", "window_recompress"]

_warned_fallbacks: set[str] = set()


def pair_shard_count(mesh, axes) -> int:
    """Devices the pair axis spans: the product of the given mesh axes."""
    if mesh is None or not axes:
        return 1
    return math.prod(mesh.shape[a] for a in axes)


def pad_leading(arrays, multiple: int):
    """Zero-pad every array's leading axis to the next multiple.

    Returns ``(padded, length)`` with ``length`` the original leading size —
    slice ``[:length]`` after the sharded call to strip the pads.  Zero pad
    slots are free through the QR/SVD math (they factorize to zeros), which
    is what lets the sharded forms accept any batch length.
    """
    n = arrays[0].shape[0]
    pad = (-n) % multiple
    if pad == 0:
        return tuple(arrays), n
    return tuple(jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                 for a in arrays), n


def warn_fallback_once(key: str, message: str):
    """Emit one RuntimeWarning per distinct fallback site per process.

    The mesh=None / empty-axes replicated paths are *contracts* (the
    single-device tests run them on purpose); this is for the cases where a
    caller asked for sharding and silently would not get it — those were the
    PR-4 silent perf cliffs."""
    if key not in _warned_fallbacks:
        _warned_fallbacks.add(key)
        warnings.warn(message, RuntimeWarning, stacklevel=3)


def sharded_recompress(up, vp, du, dv, tol, scale, *, mesh=None, axes=None,
                       pad: bool = True, with_count: bool = False):
    """(length, nb, k) pair batches -> recompressed sum, QR/SVD sharded over
    the pair axis.

    Identical math to ``core.tlr._batched_recompress`` (concat -> QR(U'),
    QR(V') -> SVD of the small core -> threshold at tol*scale), but executed
    under ``shard_map`` so each device factorizes only its own block-cyclic
    pair slots.  ``axes`` is the tuple of mesh axis names the pair axis is
    laid out over (``distribution.block_cyclic.pair_axis``); ``scale`` may be
    a traced scalar (it travels as a replicated shard_map operand).  An
    indivisible batch length is zero-padded to a multiple of the shard count
    and stripped after (``pad=False`` instead falls back to the replicated
    batch with a one-time warning).  Returns (U, V, ranks) with ranks int32
    of shape (length,); with ``with_count=True`` a fourth int32 scalar — the
    number of non-finite core singular values, reduced over all shards (each
    device counts its own slots, the per-shard counts come out along the
    pair axis and sum here) — for ``FactorStatus`` breakdown accounting.
    """
    from ..core.tlr import _batched_recompress, _batched_recompress_stat

    axes = tuple(axes) if axes else ()
    shards = pair_shard_count(mesh, axes)
    if mesh is None or not axes:
        if with_count:
            return _batched_recompress_stat(up, vp, du, dv, tol, scale)
        return _batched_recompress(up, vp, du, dv, tol, scale)
    length = up.shape[0]
    if length % shards:
        if not pad:
            warn_fallback_once(
                "recompress-indivisible",
                f"sharded_recompress: pair batch length {length} is not "
                f"divisible by {shards} shards and pad=False — falling back "
                "to the fully replicated QR/SVD batch (a per-device memory "
                "cliff); pad the batch or fix the layout")
            if with_count:
                return _batched_recompress_stat(up, vp, du, dv, tol, scale)
            return _batched_recompress(up, vp, du, dv, tol, scale)
        (up, vp, du, dv), _ = pad_leading((up, vp, du, dv), shards)

    spec = P(axes, None, None)
    scale = jnp.asarray(scale)

    if with_count:
        def local(u1, v1, u2, v2, sc):
            u_l, v_l, r_l, bad = _batched_recompress_stat(u1, v1, u2, v2,
                                                          tol, sc)
            return u_l, v_l, r_l, bad[None]   # (1,) per shard -> (S,) global

        fn = jax.shard_map(local, mesh=mesh,
                           in_specs=(spec, spec, spec, spec, P()),
                           out_specs=(spec, spec, P(axes), P(axes)),
                           check_vma=False)
        un, vn, rn, bad = fn(up, vp, du, dv, scale)
        return un[:length], vn[:length], rn[:length], jnp.sum(bad)

    def local(u1, v1, u2, v2, sc):
        return _batched_recompress(u1, v1, u2, v2, tol, sc)

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(spec, spec, spec, spec, P()),
                       out_specs=(spec, spec, P(axes)), check_vma=False)
    un, vn, rn = fn(up, vp, du, dv, scale)
    return un[:length], vn[:length], rn[:length]


def _window_update(up, vp, ranks, du, dv, act, blocks: int, recompress):
    """Recompress the last ``b`` slots of each of ``blocks`` equal blocks of
    the pair axis (``du``/``dv``/``act`` hold the ``blocks * b`` window
    slots, block-major) and write them back where ``act``; every other slot
    passes through.  Returns (up, vp, ranks, bad)."""
    pps = up.shape[0] // blocks
    b = du.shape[0] // blocks

    def take(x):
        x = x.reshape((blocks, pps) + x.shape[1:])[:, pps - b:]
        return x.reshape((blocks * b,) + x.shape[2:])

    def put(x, w):
        blk = x.reshape((blocks, pps) + x.shape[1:])
        blk = blk.at[:, pps - b:].set(w.reshape((blocks, b) + x.shape[1:]))
        return blk.reshape(x.shape)

    u0, v0, r0 = take(up), take(vp), take(ranks)
    un, vn, rn, bad = recompress(u0, v0, du, dv)
    keep = act[:, None, None]
    return (put(up, jnp.where(keep, un, u0)), put(vp, jnp.where(keep, vn, v0)),
            put(ranks, jnp.where(act, rn, r0)), bad)


def window_recompress(up, vp, ranks, du, dv, act, tol, scale, *,
                      n_shards: int, mesh=None, axes=None):
    """Recompress a static window of the block-cyclic pair axis in place.

    ``up``/``vp``/``ranks`` are the whole (length, ...) pair arrays, in
    ``n_shards`` local blocks of ``length / n_shards`` slots (the layout's
    deal).  ``du``/``dv``/``act`` hold only the window, ``n_shards * b``
    slots: block d's last ``b`` local slots, block-major.  Each window slot
    gets ``_batched_recompress`` of its old tile plus its update where
    ``act`` is set and keeps its old tile otherwise; slots outside the
    window are not touched.  Returns (up, vp, ranks, bad), ``bad`` the int32
    count of non-finite core singular values over the window's slots.

    When the mesh axes span exactly ``n_shards`` devices, each device
    slices, recompresses and writes back its own window inside one
    ``shard_map``: no collective but the sum of ``bad``.  ``mesh=None``
    slices the blocks on the one device; a mesh of another shard count
    takes the window globally and runs ``sharded_recompress`` on it.
    """
    from ..core.tlr import _batched_recompress_stat

    axes = tuple(axes) if axes else ()
    if mesh is None or not axes:
        return _window_update(
            up, vp, ranks, du, dv, act, n_shards,
            lambda *a: _batched_recompress_stat(*a, tol, scale))
    if pair_shard_count(mesh, axes) != n_shards:
        return _window_update(
            up, vp, ranks, du, dv, act, n_shards,
            lambda *a: sharded_recompress(*a, tol, scale, mesh=mesh,
                                          axes=axes, with_count=True))

    def local(u, v, r, du_l, dv_l, act_l, sc):
        *out, bad = _window_update(
            u, v, r, du_l, dv_l, act_l, 1,
            lambda *a: _batched_recompress_stat(*a, tol, sc))
        return (*out, bad[None])          # (1,) per shard -> (S,) global

    spec, vec = P(axes, None, None), P(axes)
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(spec, spec, vec, spec, spec, vec, P()),
                       out_specs=(spec, spec, vec, vec), check_vma=False)
    up, vp, ranks, bad = fn(up, vp, ranks, du, dv, act, jnp.asarray(scale))
    return up, vp, ranks, jnp.sum(bad)
