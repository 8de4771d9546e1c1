"""Per-kernel allclose: Pallas (interpret mode) vs pure-jnp oracles.

Each kernel is swept over shapes and dtypes per the deliverable spec.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.chol_tiles import potrf, syrk, trsm
from repro.kernels.flash_attention import flash_attention
from repro.kernels.matern_tile import matern_tile
from repro.kernels.tlr_mm import tlr_mm


def _tol(dtype):
    # f32 bound covers contraction-order differences in matmul chains.
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-3, atol=1e-3) if dtype == jnp.float32 else \
        dict(rtol=1e-10, atol=1e-12)


# ---------------------------------------------------------------------------
# matern_tile
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("nu", [0.5, 1.5, 2.5])
@pytest.mark.parametrize("shape", [(64, 64), (128, 64), (256, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_matern_tile_kernel(nu, shape, dtype):
    n, m = shape
    rng = np.random.default_rng(0)
    la = jnp.asarray(rng.uniform(size=(n, 2)), dtype)
    lb = jnp.asarray(rng.uniform(size=(m, 2)), dtype)
    got = matern_tile(la, lb, 1.0 / 0.1, 1.3, nu=nu, block_n=64, block_m=64,
                      interpret=True)
    want = ref.matern_tile_ref(la, lb, 1.0 / 0.1, 1.3, nu)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **_tol(dtype))


def test_matern_tile_auto_block_fit():
    """Non-divisible panel shapes (TLR strict-lower panels) round the block
    down to the nearest divisor instead of raising."""
    rng = np.random.default_rng(8)
    la = jnp.asarray(rng.uniform(size=(96, 2)))   # 96 % 64 != 0 -> block 48
    lb = jnp.asarray(rng.uniform(size=(40, 2)))
    got = matern_tile(la, lb, 1.0 / 0.1, 1.0, nu=1.5, block_n=64, block_m=64,
                      interpret=True)
    want = ref.matern_tile_ref(la, lb, 1.0 / 0.1, 1.0, 1.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-10,
                               atol=1e-12)


def test_matern_tile_vs_sigma_build():
    """Kernel tiles assemble to the same matrix as core.build_sigma (p=1)."""
    from repro.core.covariance import MaternParams, build_sigma
    from repro.core.simulate import uniform_locations
    locs = jnp.asarray(uniform_locations(128, seed=1))
    params = MaternParams.univariate(sigma2=2.0, a=0.15, nu=1.5)
    want = np.asarray(build_sigma(locs, params))
    got = np.asarray(matern_tile(locs, locs, 1.0 / 0.15, 2.0, nu=1.5,
                                 block_n=64, block_m=64, interpret=True))
    np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-12)


def test_matern_tile_refuses_f64_when_compiled():
    """Mosaic has no 64-bit lowering: compiled mode refuses f64 locations
    up front instead of failing inside the TPU compiler, and the panel
    builder then takes the XLA generator."""
    from repro.core.covariance import MaternParams, build_sigma_panel
    from repro.kernels.matern_tile import compiles_for
    locs = jnp.asarray(np.random.default_rng(0).uniform(size=(64, 2)))
    with pytest.raises(TypeError, match="float64"):
        matern_tile(locs, locs, 1.0, 1.0, nu=0.5, interpret=False)
    assert not compiles_for(jnp.float64, interpret=False)
    assert compiles_for(jnp.float32, interpret=False)
    assert compiles_for(jnp.float64, interpret=True)
    params = MaternParams.univariate(a=0.15, nu=0.5)
    got = build_sigma_panel(locs, locs, params, gen="pallas")
    want = build_sigma_panel(locs, locs, params, gen="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-12, atol=1e-14)


# ---------------------------------------------------------------------------
# tlr_mm
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,nb,k", [(1, 64, 8), (4, 128, 16), (9, 64, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_tlr_mm_kernel(b, nb, k, dtype):
    rng = np.random.default_rng(1)
    ua, va, ub, vb = (jnp.asarray(rng.normal(size=(b, nb, k)), dtype)
                      for _ in range(4))
    acc = jnp.asarray(rng.normal(size=(b, nb, nb)), dtype)
    got = tlr_mm(ua, va, ub, vb, acc, interpret=True)
    want = ref.tlr_mm_ref(ua, va, ub, vb, acc)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **_tol(dtype))


def test_tlr_mm_padded_rank_columns_are_inert():
    """Zero-padded rank columns must not perturb the product."""
    rng = np.random.default_rng(2)
    b, nb, k = 2, 64, 16
    ua, va, ub, vb = (rng.normal(size=(b, nb, k)) for _ in range(4))
    for arr in (ua, va, ub, vb):
        arr[:, :, k // 2:] = 0.0
    acc = rng.normal(size=(b, nb, nb))
    got = tlr_mm(*(jnp.asarray(x) for x in (ua, va, ub, vb, acc)),
                 interpret=True)
    want = ref.tlr_mm_ref(*(jnp.asarray(x[:, :, :k // 2]) for x in
                            (ua, va, ub, vb)), jnp.asarray(acc))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-10)


# ---------------------------------------------------------------------------
# chol tiles
# ---------------------------------------------------------------------------


def _spd_batch(b, nb, dtype, seed=0):
    rng = np.random.default_rng(seed)
    a = rng.normal(size=(b, nb, nb))
    a = a @ np.swapaxes(a, -1, -2) + nb * np.eye(nb)
    return jnp.asarray(a, dtype)


@pytest.mark.parametrize("b,nb", [(1, 32), (4, 64), (2, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_potrf_kernel(b, nb, dtype):
    a = _spd_batch(b, nb, dtype)
    got = potrf(a, interpret=True)
    want = ref.potrf_ref(a)
    tol = dict(rtol=5e-4, atol=5e-4) if dtype == jnp.float32 else \
        dict(rtol=1e-9, atol=1e-11)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **tol)


@pytest.mark.parametrize("b,nb,m", [(1, 32, 32), (3, 64, 16), (2, 64, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_trsm_kernel(b, nb, m, dtype):
    lo = ref.potrf_ref(_spd_batch(b, nb, dtype))
    rng = np.random.default_rng(3)
    bb = jnp.asarray(rng.normal(size=(b, nb, m)), dtype)
    got = trsm(lo, bb, interpret=True)
    want = ref.trsm_ref(lo, bb)
    tol = dict(rtol=1e-3, atol=1e-3) if dtype == jnp.float32 else \
        dict(rtol=1e-9, atol=1e-11)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **tol)


@pytest.mark.parametrize("b,nb,k", [(2, 64, 64), (4, 32, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_syrk_kernel(b, nb, k, dtype):
    rng = np.random.default_rng(4)
    c = jnp.asarray(rng.normal(size=(b, nb, nb)), dtype)
    a = jnp.asarray(rng.normal(size=(b, nb, k)), dtype)
    got = syrk(c, a, interpret=True)
    want = ref.syrk_ref(c, a)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **_tol(dtype))


def test_tile_cholesky_composition():
    """POTRF + TRSM + SYRK compose into a correct 2x2-block factorization."""
    nb = 64
    a = np.asarray(_spd_batch(1, 2 * nb, jnp.float64))[0]
    a11, a21, a22 = a[:nb, :nb], a[nb:, :nb], a[nb:, nb:]
    l11 = potrf(jnp.asarray(a11)[None], interpret=True)[0]
    # L21 = A21 L11^{-T}  ==  (L11^{-1} A21^T)^T
    l21 = trsm(l11[None], jnp.asarray(a21.T)[None], interpret=True)[0].T
    s22 = syrk(jnp.asarray(a22)[None], l21[None], interpret=True)[0]
    l22 = potrf(s22[None], interpret=True)[0]
    lo = np.block([[np.asarray(l11), np.zeros((nb, nb))],
                   [np.asarray(l21), np.asarray(l22)]])
    np.testing.assert_allclose(lo @ lo.T, a, rtol=1e-9, atol=1e-9)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bh,bkv,sq,skv,d", [
    (2, 2, 128, 128, 64),     # MHA square
    (4, 2, 128, 128, 64),     # GQA group=2
    (8, 2, 64, 256, 32),      # GQA group=4, decode-ish (skv > sq)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_causal(bh, bkv, sq, skv, d, dtype):
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(bh, sq, d)), dtype)
    k = jnp.asarray(rng.normal(size=(bkv, skv, d)), dtype)
    v = jnp.asarray(rng.normal(size=(bkv, skv, d)), dtype)
    got = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                          interpret=True)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("window", [32, 64])
def test_flash_attention_sliding_window(window):
    rng = np.random.default_rng(6)
    q = jnp.asarray(rng.normal(size=(2, 256, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 256, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 256, 32)), jnp.float32)
    got = flash_attention(q, k, v, causal=True, window=window, block_q=64,
                          block_k=64, interpret=True)
    want = ref.attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


def test_flash_attention_decode_single_query():
    """sq=1 decode step against a long cache (right-aligned causality)."""
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(4, 1, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 512, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 512, 64)), jnp.float32)
    got = flash_attention(q, k, v, causal=True, block_q=1, block_k=128,
                          interpret=True)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)
