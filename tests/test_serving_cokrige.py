"""Cokriging-as-a-service (serving/cokrige_service.py + the CokrigeFactor
API surgery in core/prediction.py): factor once, predict millions.

The decode path must match dense cokriging to 1e-3 relative at m = 512
(the ISSUE-7 acceptance), must never rebuild or refactorize Sigma between
batches, and must ship calibrated prediction intervals.  The ``chol=``
kwarg is a one-release deprecation shim over ``CokrigeFactor``.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import MaternParams, cokrige
from repro.core.covariance import build_sigma, morton_order
from repro.core.dist_tlr import (dist_compress_tiles, dist_tlr_cholesky_pairs,
                                 dist_tlr_solve_lower_pairs,
                                 dist_tlr_solve_upper_pairs)
from repro.core.prediction import CokrigeFactor, dense_factor
from repro.core.simulate import grid_locations, simulate_mgrf
from repro.distribution.block_cyclic import pair_layout
from repro.serving.cokrige_service import (CokrigeServeConfig, fit_factor,
                                           make_cokrige_serve_fns,
                                           predict_with_factor)


def _bench_setup(n_side, nu22=1.0):
    """The bench geometry: morton-ordered jittered grid, f64 params."""
    locs = grid_locations(n_side, jitter=0.2, seed=0)
    locs = np.asarray(locs)[morton_order(locs)]
    params = MaternParams.bivariate(a=0.09, nu11=0.5, nu22=nu22, beta=0.5)
    return locs, params


def _pred_points(n, seed=3):
    rng = np.random.default_rng(seed)
    return rng.uniform(0.05, 0.95, size=(n, 2))


def test_predict_batch_matches_dense_m512():
    """TLR serving decode == dense cokriging to 1e-3 relative at m = 512,
    with finite variances and ordered interval bounds (the acceptance)."""
    locs, params = _bench_setup(16)                    # 256 locs, m = 512
    z = simulate_mgrf(jax.random.PRNGKey(0), locs, params, nugget=1e-8)[0]
    pred_locs = _pred_points(48)
    cfg = CokrigeServeConfig(tile_size=64, max_rank=24, tol=1e-7,
                             nugget=1e-8)
    factor = fit_factor(locs, z, params, cfg)
    assert factor.kind == "tlr"
    out = predict_with_factor(factor, pred_locs)
    want = np.asarray(cokrige(locs, z, pred_locs, params, nugget=1e-8))
    rel = np.max(np.abs(np.asarray(out.mean) - want)) / np.max(np.abs(want))
    assert rel <= 1e-3, rel
    var = np.asarray(out.variance)
    assert np.all(np.isfinite(var)) and np.all(var >= 0.0)
    assert np.all(np.asarray(out.lower) <= np.asarray(out.mean))
    assert np.all(np.asarray(out.mean) <= np.asarray(out.upper))
    # the factor= route through the core API hits the same decode path
    via_api = np.asarray(cokrige(None, None, pred_locs, factor=factor))
    np.testing.assert_allclose(via_api, np.asarray(out.mean), atol=1e-10)


def test_jitted_serve_fns_and_draws():
    """The make_cokrige_serve_fns pair round-trips the factor pytree through
    jit; conditional-simulation draws are finite and centered on the mean."""
    locs, params = _bench_setup(8)                     # 64 locs, m = 128
    z = simulate_mgrf(jax.random.PRNGKey(1), locs, params, nugget=1e-8)[0]
    pred_locs = _pred_points(16)
    cfg = CokrigeServeConfig(tile_size=32, max_rank=16, tol=1e-9,
                             nugget=1e-8)
    fit, predict = make_cokrige_serve_fns(cfg)
    factor = fit(locs, z, params)
    eager = predict_with_factor(fit_factor(locs, z, params, cfg), pred_locs)
    out = predict(factor, pred_locs)
    np.testing.assert_allclose(np.asarray(out.mean), np.asarray(eager.mean),
                               atol=1e-8)
    drawn = predict(factor, pred_locs, key=jax.random.PRNGKey(2),
                    n_draws=400)
    assert drawn.draws.shape == (400, 16, params.p)
    assert np.all(np.isfinite(np.asarray(drawn.draws)))
    # empirical draw mean -> cokriging mean, sd -> kriging sd
    emp = np.mean(np.asarray(drawn.draws), axis=0)
    sd = np.sqrt(np.asarray(drawn.variance))
    assert np.max(np.abs(emp - np.asarray(drawn.mean))) < 4.0 * np.max(sd) \
        / np.sqrt(400)
    emp_sd = np.std(np.asarray(drawn.draws), axis=0)
    np.testing.assert_allclose(emp_sd, sd, rtol=0.35, atol=1e-6)


def test_factor_reuse_never_rebuilds_sigma(monkeypatch):
    """Repeated decode batches against one factor never re-enter compress,
    the pair Cholesky, or build_sigma — Sigma is factored exactly once."""
    import repro.core.prediction as PR
    import repro.serving.cokrige_service as SVC

    locs, params = _bench_setup(8)
    z = simulate_mgrf(jax.random.PRNGKey(3), locs, params, nugget=1e-8)[0]
    cfg = CokrigeServeConfig(tile_size=32, max_rank=16, tol=1e-9,
                             nugget=1e-8)
    factor = fit_factor(locs, z, params, cfg)

    def boom(*a, **k):
        raise AssertionError("Sigma was rebuilt/refactorized during decode")

    monkeypatch.setattr(SVC, "dist_compress_tiles", boom)
    monkeypatch.setattr(SVC, "dist_tlr_cholesky_pairs", boom)
    monkeypatch.setattr(PR, "build_sigma", boom)
    import repro.core.covariance as COV
    monkeypatch.setattr(COV, "build_sigma", boom)
    a = predict_with_factor(factor, _pred_points(8, seed=1))
    b = predict_with_factor(factor, _pred_points(8, seed=2))
    assert np.all(np.isfinite(np.asarray(a.mean)))
    assert np.all(np.isfinite(np.asarray(b.mean)))
    # same batch again: bitwise-identical (pure function of the factor)
    a2 = predict_with_factor(factor, _pred_points(8, seed=1))
    np.testing.assert_array_equal(np.asarray(a.mean), np.asarray(a2.mean))


def test_prediction_interval_coverage():
    """Central 95% intervals cover the held-out truth at ~nominal rate over
    repeated simulations of the joint field (obs + pred locations)."""
    n_obs, n_pred, K = 64, 24, 25
    obs, params = _bench_setup(8)
    pred_locs = _pred_points(n_pred, seed=11)
    all_locs = np.concatenate([obs, pred_locs], axis=0)
    p = params.p
    cfg = CokrigeServeConfig(tile_size=32, max_rank=16, tol=1e-9,
                             nugget=1e-8)
    fit, predict = make_cokrige_serve_fns(cfg)
    hits = total = 0
    for k in range(K):
        z_all = simulate_mgrf(jax.random.PRNGKey(100 + k), all_locs, params,
                              nugget=1e-8)[0].reshape(n_obs + n_pred, p)
        factor = fit(jnp.asarray(obs), z_all[:n_obs].reshape(-1), params)
        out = predict(factor, jnp.asarray(pred_locs))
        truth = np.asarray(z_all[n_obs:])
        inside = (np.asarray(out.lower) <= truth) & \
                 (truth <= np.asarray(out.upper))
        hits += int(np.sum(inside))
        total += inside.size
    coverage = hits / total
    assert 0.85 <= coverage <= 0.995, coverage


def test_pair_solves_match_dense_factor_multirhs():
    """The multi-RHS pair-major triangular solves invert the reconstructed
    dense TLR factor: L @ lower(b) == b and L^T @ upper(y) == y."""
    locs, params = _bench_setup(8)
    m, nb = 128, 32
    T = m // nb
    layout = pair_layout(T, 1)
    scale = float(np.max(np.asarray(params.sigma2))) + 1e-8
    t = dist_compress_tiles(locs, params, tile_size=nb, tol=1e-10,
                            max_rank=nb, nugget=1e-8, scale=scale,
                            layout=layout)
    diag_l, u, v, ranks = dist_tlr_cholesky_pairs(
        t.diag, t.u, t.v, t.ranks, layout=layout, tol=1e-10, scale=scale)
    L = np.zeros((m, m))
    dl = np.asarray(diag_l)
    for i in range(T):
        L[i * nb:(i + 1) * nb, i * nb:(i + 1) * nb] = np.tril(dl[i])
    il, jl = np.asarray(layout.il), np.asarray(layout.jl)
    un, vn = np.asarray(u), np.asarray(v)
    for q in np.nonzero(il > jl)[0]:
        i, j = int(il[q]), int(jl[q])
        L[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb] = un[q] @ vn[q].T
    rng = np.random.default_rng(0)
    b = jnp.asarray(rng.normal(size=(m, 3)))
    w = dist_tlr_solve_lower_pairs(diag_l, u, v, b, layout=layout)
    np.testing.assert_allclose(L @ np.asarray(w), np.asarray(b), atol=1e-8)
    x = dist_tlr_solve_upper_pairs(diag_l, u, v, b, layout=layout)
    np.testing.assert_allclose(L.T @ np.asarray(x), np.asarray(b), atol=1e-8)
    # single-RHS form agrees with its own column
    w1 = dist_tlr_solve_lower_pairs(diag_l, u, v, b[:, 0], layout=layout)
    assert w1.shape == (m,)
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w)[:, 0],
                               atol=1e-10)


def test_chol_kwarg_deprecation_shim(monkeypatch):
    """chol= still works for one release: warns once (keyed), matches the
    factor= route exactly, and never rebuilds Sigma."""
    import repro.core.prediction as PR
    from repro.distribution.pair_qr import _warned_fallbacks

    locs, params = _bench_setup(6)
    z = simulate_mgrf(jax.random.PRNGKey(7), locs, params, nugget=1e-8)[0]
    pred_locs = _pred_points(5)
    chol = jnp.linalg.cholesky(build_sigma(locs, params, nugget=1e-8))
    want = np.asarray(cokrige(
        locs, z, pred_locs,
        factor=dense_factor(locs, z, params, chol=chol)))

    monkeypatch.setattr(PR, "build_sigma",
                        lambda *a, **k: (_ for _ in ()).throw(
                            AssertionError("Sigma rebuilt in the shim")))
    _warned_fallbacks.discard("cokrige-chol-deprecated")
    with pytest.warns(RuntimeWarning, match="chol= kwarg is deprecated"):
        got = cokrige(locs, z, pred_locs, params, chol=chol)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-12)
    # one-shot: a second use does not warn again
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        cokrige(locs, z, pred_locs, params, chol=chol)


def test_dense_factor_roundtrip():
    """dense_factor + the dense decode branch reproduce classic cokrige and
    expose the same CokrigePrediction products."""
    locs, params = _bench_setup(6)
    z = simulate_mgrf(jax.random.PRNGKey(9), locs, params, nugget=1e-8)[0]
    pred_locs = _pred_points(7)
    f = dense_factor(locs, z, params, nugget=1e-8)
    out = predict_with_factor(f, pred_locs)
    want = np.asarray(cokrige(locs, z, pred_locs, params, nugget=1e-8))
    np.testing.assert_allclose(np.asarray(out.mean), want, atol=1e-8)
    assert np.all(np.asarray(out.variance) >= 0.0)
    # the factor survives a jit round trip as a pytree
    leaves = jax.tree_util.tree_leaves(f)
    assert all(hasattr(x, "shape") for x in leaves)
    re = jax.jit(lambda ff: ff)(dataclasses.replace(f))
    np.testing.assert_array_equal(np.asarray(re.alpha), np.asarray(f.alpha))


# ---------------------------------------------------------------------------
# Multi-device behaviour via a subprocess (fake CPU devices).
# ---------------------------------------------------------------------------

_SUBPROC_PREAMBLE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={ndev}"
import sys
sys.path.insert(0, {src!r})
import jax
jax.config.update("jax_enable_x64", True)
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


@pytest.mark.slow
def test_serving_8device_subprocess():
    """8-device (2, 4) mesh at m = 512: pair-sharded fit + sharded decode
    match dense cokriging to 1e-3 relative (the multi-device acceptance)."""
    out = _run_subprocess("""
    from repro.core import MaternParams, cokrige
    from repro.core.covariance import morton_order
    from repro.core.simulate import grid_locations, simulate_mgrf
    from repro.serving.cokrige_service import (CokrigeServeConfig,
                                               make_cokrige_serve_fns)

    mesh = auto_mesh((2, 4), ("data", "model"))
    locs = grid_locations(16, jitter=0.2, seed=0)      # 256 locs, m = 512
    locs = np.asarray(locs)[morton_order(locs)]
    params = MaternParams.bivariate(a=0.09, nu11=0.5, nu22=1.0, beta=0.5)
    z = simulate_mgrf(jax.random.PRNGKey(0), locs, params, nugget=1e-8)[0]
    rng = np.random.default_rng(3)
    pred_locs = jnp.asarray(rng.uniform(0.05, 0.95, size=(32, 2)))
    cfg = CokrigeServeConfig(tile_size=64, max_rank=24, tol=1e-7,
                             nugget=1e-8)
    fit, predict = make_cokrige_serve_fns(cfg, mesh)
    factor = fit(jnp.asarray(locs), z, params)
    out = predict(factor, pred_locs)
    out2 = predict(factor, pred_locs)        # reuse: same executable/factor
    np.testing.assert_array_equal(np.asarray(out.mean), np.asarray(out2.mean))
    want = np.asarray(cokrige(locs, z, pred_locs, params, nugget=1e-8))
    rel = np.max(np.abs(np.asarray(out.mean) - want)) / np.max(np.abs(want))
    assert rel <= 1e-3, rel
    assert np.all(np.asarray(out.variance) >= 0.0)
    print("SERVE_8DEV_OK", rel)
    """)
    assert "SERVE_8DEV_OK" in out


def test_predict_batch_profiler_spans(tmp_path):
    """Under jax.profiler one predict_batch call shows as the span
    repro.serve.predict_batch holding validate, status and dispatch, all
    carrying the call's ``req``; the next call carries the next number."""
    from jax.profiler import ProfileData

    from repro.serving.cokrige_service import predict_batch

    locs, params = _bench_setup(8)                     # 64 locs, m = 128
    z = simulate_mgrf(jax.random.PRNGKey(1), locs, params, nugget=1e-8)[0]
    cfg = CokrigeServeConfig(tile_size=32, max_rank=16, tol=1e-9,
                             nugget=1e-8)
    factor = make_cokrige_serve_fns(cfg)[0](locs, z, params)
    pred_locs = _pred_points(16)
    jax.block_until_ready(predict_batch(factor, pred_locs, cfg))  # compile
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            jax.block_until_ready(predict_batch(factor, pred_locs, cfg))
    finally:
        jax.profiler.stop_trace()
    pb = max(tmp_path.rglob("*.xplane.pb"), key=os.path.getmtime)
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
              dict(e.stats).get("req"))
             for plane in ProfileData.from_file(str(pb)).planes
             for line in plane.lines for e in line.events
             if e.name.startswith("repro.serve.")]
    parents = [s for s in spans if s[0] == "repro.serve.predict_batch"]
    assert len(parents) == 2
    assert parents[1][3] == parents[0][3] + 1
    for name, lo, hi, req in parents:
        kids = sorted((s for s in spans if s[0] != name and s[3] == req),
                      key=lambda s: s[1])
        assert [k[0] for k in kids] == ["repro.serve.validate",
                                        "repro.serve.status",
                                        "repro.serve.dispatch"]
        assert all(lo <= k[1] <= k[2] <= hi for k in kids)
