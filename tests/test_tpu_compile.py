"""Compile the main path's kernels for a described v5e chip (no chip needed).

The TPU compiler refuses what interpret mode and the CPU backend accept:
Mosaic lowering of the Pallas GEN kernel, f64 decompositions, and
per-device memory of a sharded step.  Each test compiles one program at the
production widths of ``geostat-tlr`` (nb = 2048, kmax = 256) and takes
seconds.  The topology is described inside a fixture, never at import.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.configs.geostat import GEOSTAT_TLR

NB, KMAX = GEOSTAT_TLR.tile_size, GEOSTAT_TLR.max_rank


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it cannot describe the chip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # The persistent cache cannot read back programs compiled for a chip
    # that is not attached; keep these compiles out of it.
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture
def tpu_forms(monkeypatch):
    """core.linalg picks its TPU forms from the default backend, which is
    the CPU here: steer it to the forms a chip runs."""
    from repro.core import linalg
    monkeypatch.setattr(linalg, "_on_tpu", lambda: True)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_matern_tile_compiles_to_mosaic(one_chip):
    """x64 is on for the whole suite: the kernel's index maps must stay
    int32 for Mosaic to lower it."""
    from repro.kernels.matern_tile import matern_tile

    locs = _spec((NB, 2), jnp.float32, one_chip)
    scal = _spec((), jnp.float32, one_chip)
    compiled = jax.jit(lambda a, b, r, s: matern_tile(
        a, b, r, s, nu=1.5, interpret=False)).lower(
        locs, locs, scal, scal).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_f64_potrf_trsm_compile(one_chip, tpu_forms):
    """The panel head of every factorization step: blocked POTRF of a
    diagonal tile and the multi-RHS TRSM of its panel column."""
    from repro.core.linalg import cholesky
    from repro.core.tlr import panel_trsm

    def step(dkk, vk):
        lkk = cholesky(dkk)
        return lkk, panel_trsm(lkk, vk)

    compiled = jax.jit(step).lower(
        _spec((NB, NB), jnp.float64, one_chip),
        _spec((4, NB, KMAX), jnp.float64, one_chip)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2**30


def test_f64_pair_recompress_compiles(one_chip, tpu_forms):
    """One GEMM + recompress step over a pair batch: Householder QR and
    the Jacobi core SVD (XLA's would not compile in a test's time)."""
    from repro.core.tlr import _batched_recompress

    spec = _spec((2, NB, KMAX), jnp.float64, one_chip)
    compiled = jax.jit(lambda a, b, c, d: _batched_recompress(
        a, b, c, d, 1e-7, 1.0)).lower(spec, spec, spec, spec).compile()
    assert "while" in compiled.as_text()


def test_sharded_recompress_spreads_pairs(topo, one_chip, tpu_forms):
    """shard_map over a 2x2 mesh of the described devices: each device
    holds a quarter of the pair batch, so its bytes stay below the
    replicated program's."""
    from repro.distribution.pair_qr import sharded_recompress
    from repro.launch.mesh import auto_mesh

    mesh = auto_mesh((2, 2), ("data", "model"), devices=topo.devices)
    axes = ("data", "model")
    shape = (16, 256, 32)     # small pairs: the placement is the point

    def run(mesh_arg, sharding):
        spec = _spec(shape, jnp.float64, sharding)
        fn = jax.jit(lambda a, b, c, d: sharded_recompress(
            a, b, c, d, 1e-7, 1.0, mesh=mesh_arg,
            axes=axes if mesh_arg is not None else None))
        ma = fn.lower(spec, spec, spec, spec).compile().memory_analysis()
        return ma.argument_size_in_bytes + ma.temp_size_in_bytes

    sharded = run(mesh, NamedSharding(mesh, P(axes, None, None)))
    replicated = run(None, NamedSharding(mesh, P()))
    assert sharded < replicated / 2, (sharded, replicated)


def test_window_recompress_stays_on_its_shard(topo, tpu_forms):
    """The live-pair window of a 2x2 mesh's block-cyclic pair axis is
    sliced, recompressed and written back on each device: the compiled
    program gathers no other device's slots."""
    from repro.distribution.pair_qr import window_recompress
    from repro.launch.mesh import auto_mesh

    mesh = auto_mesh((2, 2), ("data", "model"), devices=topo.devices)
    axes = ("data", "model")
    pairs = NamedSharding(mesh, P(axes, None, None))
    slots = NamedSharding(mesh, P(axes))
    full = _spec((16, 256, 32), jnp.float64, pairs)     # 4 slots a device
    win = _spec((8, 256, 32), jnp.float64, pairs)       # the last 2 of each
    fn = jax.jit(lambda u, v, r, du, dv, act: window_recompress(
        u, v, r, du, dv, act, 1e-7, 1.0, n_shards=4, mesh=mesh, axes=axes))
    text = fn.lower(full, full, _spec((16,), jnp.int32, slots), win, win,
                    _spec((8,), jnp.bool_, slots)).compile().as_text()
    for op in ("all-gather", "all-to-all", "collective-permute"):
        assert op not in text, op
