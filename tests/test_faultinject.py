"""Fault-injection harness exercising the breakdown-detection and recovery
machinery end-to-end: corrupted tiles are *detected* (FactorStatus), never
leak NaN (finite sentinel), *heal* on the jitter ladder, and are *refused*
(or degraded-mode re-fit) by the serving layer.

The slow 8-device subprocess test is the ISSUE acceptance run at m = 512.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import MaternParams
from repro.core.covariance import morton_order
from repro.core.dist_tlr import dist_tlr_loglik
from repro.core.likelihood import exact_loglik
from repro.core.recovery import jitter_escalate, sentinel_loglik
from repro.core.simulate import grid_locations, simulate_mgrf
from repro.core.tlr import tlr_loglik
from repro.serving.cokrige_service import (CokrigeServeConfig, ServeError,
                                           fit_factor, heal_factor,
                                           predict_batch)
from repro.testing import corrupt_diag_tile, nan_compress_panel, zero_shard

_PARAMS = MaternParams.bivariate(a=0.09, nu11=0.5, nu22=1.0, beta=0.5)
_NUGGET = 1e-8
_TLR_KW = dict(tol=1e-7, max_rank=16, tile_size=32, gen="xla")


def _setup(n_side=8, seed=0):
    """Morton-ordered jittered grid + one exact simulation (m = 2 n)."""
    locs = grid_locations(n_side, jitter=0.2, seed=seed)
    locs = np.asarray(locs)[morton_order(locs)]
    z = simulate_mgrf(jax.random.PRNGKey(seed), locs, _PARAMS,
                      nugget=_NUGGET)[0]
    return jnp.asarray(locs), z


def _clean_ll(locs, z):
    return tlr_loglik(None, z, _PARAMS, nugget=_NUGGET, locs=locs,
                      from_tiles=True, **_TLR_KW)


def _dup_setup(n_side=8, n_dups=2, seed=0):
    """Geometry whose Sigma is *exactly singular* at nugget 0: the last
    ``n_dups`` locations are copies of the first ones (sensor collision)."""
    locs = np.asarray(grid_locations(n_side, jitter=0.2, seed=seed))
    locs[-n_dups:] = locs[:n_dups]
    locs = locs[morton_order(locs)]
    z = simulate_mgrf(jax.random.PRNGKey(seed), locs, _PARAMS,
                      nugget=_NUGGET)[0]
    return jnp.asarray(locs), z


def test_corrupt_diag_detected_single_path():
    locs, z = _setup()
    clean = _clean_ll(locs, z)
    assert bool(clean.status.ok)

    with corrupt_diag_tile(tile=0, magnitude=10.0):
        broken = _clean_ll(locs, z)

    st = broken.status
    assert not bool(st.ok)
    assert int(st.breakdown_count) >= 1
    assert float(st.min_pivot) <= 0.0 or int(st.nonfinite_count) > 0
    # Sentinel, not NaN — and well separated from any real loglik.
    assert np.isfinite(float(broken.loglik))
    assert float(broken.loglik) == float(sentinel_loglik(z.dtype))

    # Context exit restores the clean path (patch is scoped).
    after = _clean_ll(locs, z)
    assert bool(after.status.ok)
    assert float(after.loglik) == pytest.approx(float(clean.loglik))


def test_nan_panel_detected_single_path():
    locs, z = _setup()
    with nan_compress_panel(panel=1):  # row 1 holds the first valid tile
        broken = _clean_ll(locs, z)
    st = broken.status
    assert not bool(st.ok)
    assert int(st.nonfinite_count) + int(st.breakdown_count) >= 1
    assert np.isfinite(float(broken.loglik))


def test_zero_shard_detected_dist_path():
    locs, z = _setup()
    kw = dict(locs=locs, params=_PARAMS, from_tiles=True, nugget=_NUGGET,
              block_cyclic=True, **_TLR_KW)
    clean = dist_tlr_loglik(z=z, **kw)
    assert bool(clean.status.ok)

    with zero_shard(shard=0, n_shards=4):
        broken = dist_tlr_loglik(z=z, **kw)
    st = broken.status
    assert not bool(st.ok)
    assert float(st.min_pivot) <= 0.0  # zeroed diag tile: pivot exactly 0
    assert np.isfinite(float(broken.loglik))


@pytest.mark.parametrize("row", [1, 2, 3])
def test_nan_column0_pair_detected_pair_path(row):
    """A NaN in a column-0 pair (row, 0) on the pair path: the panel loop
    never recompresses that slot (pair (i, j) enters the live window only
    at steps k < j), but step 0 carries the NaN into the trailing diagonal
    tiles and the window's updates, so the factor is still flagged."""
    from repro.distribution.block_cyclic import pair_layout, window_schedule

    locs, z = _setup()
    lay = pair_layout(4, 1)                 # m = 128, 32-row tiles
    slot = int(lay.pos[row, 0])
    for seg in window_schedule(lay, 3).segments:
        assert slot < lay.pairs_per_shard - seg.width
    kw = dict(locs=locs, params=_PARAMS, from_tiles=True, nugget=_NUGGET,
              block_cyclic=True, **_TLR_KW)
    with nan_compress_panel(panel=slot):
        broken = dist_tlr_loglik(z=z, **kw)
    st = broken.status
    assert not bool(st.ok)
    assert int(st.nonfinite_count) + int(st.breakdown_count) >= 1
    assert np.isfinite(float(broken.loglik))


def test_jitter_ladder_heals_singular_sigma():
    """The real-world recoverable fault: duplicate locations at nugget 0
    make Sigma exactly singular.  The ladder's first rung heals, and the
    recovered loglik matches a clean dense fp64 evaluation of the *same*
    matrix at the recovered jitter to 1e-3 relative."""
    locs, z = _dup_setup()

    # The zero-jitter attempt must genuinely break.
    broken = tlr_loglik(None, z, _PARAMS, nugget=0.0, locs=locs,
                        from_tiles=True, **_TLR_KW)
    assert not bool(broken.status.ok)
    assert np.isfinite(float(broken.loglik))

    @jax.jit
    def ladder(zz):
        def eval_at(j):
            r = tlr_loglik(None, zz, _PARAMS, nugget=j, locs=locs,
                           from_tiles=True, **_TLR_KW)
            return r.loglik, r.status.ok & jnp.isfinite(r.loglik)

        return jitter_escalate(eval_at, initial=1e-6, factor=10.0,
                               max_jitter=1e-2, max_attempts=4)

    rec = ladder(z)
    assert bool(rec.ok)
    assert int(rec.attempts) == 2  # singular attempt broke, first rung healed
    assert float(rec.jitter) == pytest.approx(1e-6)
    clean = exact_loglik(locs, z, _PARAMS, nugget=float(rec.jitter))
    rel = abs(float(rec.loglik) - float(clean.loglik)) \
        / abs(float(clean.loglik))
    assert rel < 1e-3, rel


def test_serving_refuses_broken_factor():
    locs, z = _setup()
    cfg = CokrigeServeConfig(tile_size=32, max_rank=16, tol=1e-7,
                             nugget=_NUGGET, gen="xla")
    with corrupt_diag_tile(tile=0, magnitude=10.0):
        factor = fit_factor(locs, z, _PARAMS, cfg)
    assert factor.status is not None
    assert not bool(factor.status.ok)

    pred_locs = jnp.asarray(
        np.random.default_rng(1).uniform(0.1, 0.9, size=(8, 2)))

    # Request validation fires before the health check.
    with pytest.raises(ServeError) as ei:
        predict_batch(factor, np.zeros((4, 3)), cfg)
    assert ei.value.code == "bad_shape"
    with pytest.raises(ServeError) as ei:
        predict_batch(factor, np.zeros((4, 2), dtype=np.int64), cfg)
    assert ei.value.code == "bad_dtype"
    bad = np.asarray(pred_locs).copy()
    bad[2, 0] = np.nan
    with pytest.raises(ServeError) as ei:
        predict_batch(factor, bad, cfg)
    assert ei.value.code == "nonfinite_locs"
    assert ei.value.detail["n_nonfinite"] == 1

    # A well-formed request against the broken factor: structured refusal.
    with pytest.raises(ServeError) as ei:
        predict_batch(factor, pred_locs, cfg)
    err = ei.value
    assert err.code == "broken_factor"
    wire = err.to_dict()
    assert wire["status"]["ok"] is False
    assert "broken_factor" in str(err)


def test_serving_degraded_mode_heals_and_serves():
    """A deployment misconfigured with nugget 0 on colliding sensors: the
    prefill factor is broken, degraded mode re-fits it on the ladder and
    serves finite predictions."""
    locs, z = _dup_setup()
    cfg = CokrigeServeConfig(tile_size=32, max_rank=16, tol=1e-7,
                             nugget=0.0, gen="xla", degraded=True,
                             degraded_initial_jitter=1e-6)
    pred_locs = jnp.asarray(
        np.random.default_rng(2).uniform(0.1, 0.9, size=(8, 2)))

    factor = fit_factor(locs, z, _PARAMS, cfg)
    assert not bool(factor.status.ok)
    healed = heal_factor(factor, cfg)
    assert bool(healed.status.ok)
    out = predict_batch(factor, pred_locs, cfg)  # degraded end-to-end

    assert np.all(np.isfinite(np.asarray(out.mean)))
    assert np.all(np.asarray(out.variance) >= 0.0)
    # The healed handle matches what degraded serving used.
    ref = predict_batch(healed, pred_locs, cfg)
    np.testing.assert_allclose(np.asarray(out.mean), np.asarray(ref.mean),
                               rtol=1e-10)


def test_heal_factor_without_data_raises():
    locs, z = _setup()
    cfg = CokrigeServeConfig(tile_size=32, max_rank=16, tol=1e-7,
                             nugget=_NUGGET, gen="xla")
    with corrupt_diag_tile(tile=0, magnitude=10.0):
        factor = fit_factor(locs, z, _PARAMS, cfg)
    stripped = dataclasses.replace(factor, z=None)
    with pytest.raises(ServeError) as ei:
        heal_factor(stripped, cfg)
    assert ei.value.code == "broken_factor"
    assert "no z" in ei.value.message


# ---------------------------------------------------------------------------
# 8-device acceptance (ISSUE): m = 512, corrupted shard under a real mesh
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


def _run_subprocess(body: str, ndev: int = 8, timeout: int = 900):
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    code = _SUBPROC_PREAMBLE.format(ndev=ndev, src=os.path.abspath(src)) + \
        textwrap.dedent(body)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


@pytest.mark.slow
def test_fault_8device_subprocess():
    """8-device (2, 4) mesh at m = 512: an injected non-PSD tile is detected
    (status.ok False, no NaN anywhere), the jitter ladder recovers the
    loglik to within 1e-3 relative of the clean fp64 value, and serving
    refuses the broken factor with a structured ServeError."""
    out = _run_subprocess("""
    from repro.core import MaternParams
    from repro.core.covariance import morton_order
    from repro.core.dist_tlr import dist_tlr_loglik
    from repro.core.likelihood import exact_loglik
    from repro.core.recovery import jitter_escalate
    from repro.core.simulate import grid_locations, simulate_mgrf
    from repro.serving.cokrige_service import (CokrigeServeConfig, ServeError,
                                               fit_factor, predict_batch)
    from repro.testing import corrupt_diag_tile

    mesh = auto_mesh((2, 4), ("data", "model"))
    locs = np.asarray(grid_locations(16, jitter=0.2, seed=0))  # m = 512
    locs[-4:] = locs[:4]      # 4 colliding sensors: Sigma singular at nugget 0
    locs = jnp.asarray(locs[morton_order(locs)])
    params = MaternParams.bivariate(a=0.09, nu11=0.5, nu22=1.0, beta=0.5)
    z = simulate_mgrf(jax.random.PRNGKey(0), locs, params, nugget=1e-8)[0]
    kw = dict(locs=locs, params=params, from_tiles=True, tile_size=64,
              max_rank=24, tol=1e-7, gen="xla", block_cyclic=True, mesh=mesh)

    # Breakdown detected in-graph: finite sentinel, flags set, no NaN.
    broken = dist_tlr_loglik(z=z, nugget=0.0, **kw)
    st = broken.status.as_dict()
    assert st["ok"] is False, st
    for v in (broken.loglik, broken.logdet, broken.quad):
        assert np.isfinite(float(v)), st

    # Jitter escalation recovers on the first rung; the recovered loglik
    # matches a clean dense fp64 evaluation at that same nugget to 1e-3.
    @jax.jit
    def ladder(zz):
        def eval_at(j):
            r = dist_tlr_loglik(z=zz, nugget=j, **kw)
            return r.loglik, r.status.ok & jnp.isfinite(r.loglik)
        return jitter_escalate(eval_at, initial=1e-6, factor=10.0,
                               max_jitter=1e-2, max_attempts=4)

    rec = ladder(z)
    assert bool(rec.ok), int(rec.attempts)
    assert int(rec.attempts) == 2, int(rec.attempts)
    clean = exact_loglik(locs, z, params, nugget=float(rec.jitter))
    rel = abs(float(rec.loglik) - float(clean.loglik)) \\
        / abs(float(clean.loglik))
    assert rel < 1e-3, rel

    # Serving refuses a factor broken by an injected non-PSD tile.
    cfg = CokrigeServeConfig(tile_size=64, max_rank=24, tol=1e-7,
                             nugget=1e-8, gen="xla")
    with corrupt_diag_tile(tile=0, magnitude=10.0):
        factor = fit_factor(locs, z, params, cfg, mesh=mesh)
    assert factor.status is not None and not bool(factor.status.ok)
    pred_locs = jnp.asarray(
        np.random.default_rng(3).uniform(0.05, 0.95, size=(16, 2)))
    try:
        predict_batch(factor, pred_locs, cfg, mesh=mesh)
        raise SystemExit("expected ServeError for broken factor")
    except ServeError as e:
        assert e.code == "broken_factor", e.code
        assert e.to_dict()["status"]["ok"] is False

    print("FAULT_8DEV_OK", rel)
    """)
    assert "FAULT_8DEV_OK" in out
