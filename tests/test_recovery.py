"""Numerical fault tolerance: FactorStatus algebra, the jitter-escalation
ladder, NaN-aware Nelder-Mead, checkpointed multistart, and duplicate-location
pre-flight checks (core/recovery.py, core/optimize.py, checkpointing)."""
import shutil

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpointing.checkpoint import CheckpointManager, _gc_old
from repro.core import MaternParams, MLEConfig
from repro.core.covariance import build_sigma, morton_order
from repro.core.likelihood import loglik_from_chol
from repro.core.mle import check_locations, fit
from repro.core.optimize import multistart_nelder_mead, nelder_mead, nm_init_state
from repro.core.recovery import (find_duplicate_locations, init_status,
                                 jitter_escalate, sentinel_loglik)
from repro.core.simulate import grid_locations
from repro.core.tlr import tlr_loglik

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False


_PARAMS = MaternParams.bivariate(a=0.09, nu11=0.5, nu22=1.0, beta=0.5)


# ---------------------------------------------------------------------------
# FactorStatus
# ---------------------------------------------------------------------------


def test_factor_status_algebra():
    s = init_status()
    assert bool(s.ok)

    s_good = s.update_potrf(2.0 * jnp.eye(4))
    assert bool(s_good.ok)
    assert float(s_good.min_pivot) == pytest.approx(2.0)

    bad = jnp.diag(jnp.asarray([1.0, -3.0, 2.0, 1.0]))
    s_bad = s_good.update_potrf(bad)
    assert not bool(s_bad.ok)
    assert int(s_bad.breakdown_count) == 1
    assert float(s_bad.min_pivot) == pytest.approx(-3.0)

    # NaN pivots are sanitized: every field stays finite.
    s_nan = s.update_potrf(jnp.full((4, 4), jnp.nan))
    assert not bool(s_nan.ok)
    assert np.isfinite(float(s_nan.min_pivot))

    merged = s_bad.merge(s_nan)
    assert int(merged.breakdown_count) == 2
    d = merged.as_dict()
    assert d["ok"] is False and np.isfinite(d["min_pivot"])


def test_factor_status_max_rank_is_a_counter_not_health():
    s = init_status()
    assert int(s.max_rank) == 0
    s = s.fold_ranks(jnp.asarray([3, 17, 5], jnp.int32))
    s = s.fold_ranks(jnp.asarray([[4, 9]], jnp.int32))      # never shrinks
    assert int(s.max_rank) == 17 and bool(s.ok)
    assert int(s.fold_ranks(jnp.zeros((0,), jnp.int32)).max_rank) == 17
    merged = s.merge(init_status().fold_ranks(jnp.asarray([21])))
    assert merged.as_dict()["max_rank"] == 21


def test_sentinel_loglik_is_finite_and_orderable():
    s = sentinel_loglik(jnp.float64)
    assert np.isfinite(float(s))
    # Survives the arithmetic the NM simplex does to objective values.
    assert np.isfinite(float(-s)) and float(s) < -1e100
    s32 = sentinel_loglik(jnp.float32)
    assert np.isfinite(float(s32)) and s32.dtype == jnp.float32


# ---------------------------------------------------------------------------
# jitter_escalate
# ---------------------------------------------------------------------------


def test_jitter_escalate_clean_first_try():
    rec = jitter_escalate(lambda j: (jnp.asarray(-5.0), jnp.asarray(True)))
    assert bool(rec.ok)
    assert int(rec.attempts) == 1
    assert float(rec.jitter) == 0.0
    assert float(rec.loglik) == pytest.approx(-5.0)


def test_jitter_escalate_climbs_ladder():
    def eval_at(j):
        ok = j >= 1e-6
        return jnp.where(ok, 1.23, jnp.nan), ok

    rec = jax.jit(lambda: jitter_escalate(
        eval_at, initial=1e-8, factor=10.0, max_jitter=1e-2,
        max_attempts=6))()
    # Rungs: 0, 1e-8, 1e-7, 1e-6 -> four evaluations.
    assert bool(rec.ok)
    assert int(rec.attempts) == 4
    assert float(rec.jitter) == pytest.approx(1e-6)
    assert float(rec.loglik) == pytest.approx(1.23)


def test_jitter_escalate_exhausted_stays_finite():
    rec = jitter_escalate(
        lambda j: (jnp.asarray(jnp.nan), jnp.asarray(False)), max_attempts=3)
    assert not bool(rec.ok)
    assert int(rec.attempts) == 3
    assert np.isfinite(float(rec.loglik))  # sentinel, never NaN


def test_jitter_escalate_caps_at_max_jitter():
    rec = jitter_escalate(
        lambda j: (jnp.asarray(0.0), jnp.asarray(False)),
        initial=1e-3, factor=100.0, max_jitter=1e-2, max_attempts=5)
    assert float(rec.jitter) == pytest.approx(1e-2)


def test_jitter_escalate_carries_the_accepted_attempts_aux():
    """With ``aux_init`` the ladder returns the accepted attempt's extra
    output (any pytree), not a rung's that broke."""
    def eval_at(j):
        ok = j >= 1e-7
        aux = {"rank": jnp.where(ok, 7, 99).astype(jnp.int32),
               "j": j.astype(jnp.float32)}
        return jnp.where(ok, 1.0, jnp.nan), ok, aux

    init = {"rank": jnp.zeros((), jnp.int32), "j": jnp.zeros((), jnp.float32)}
    rec = jax.jit(lambda: jitter_escalate(eval_at, initial=1e-8,
                                          aux_init=init))()
    assert int(rec.attempts) == 3
    assert int(rec.aux["rank"]) == 7
    assert float(rec.aux["j"]) == pytest.approx(1e-7)
    assert jitter_escalate(
        lambda j: (jnp.asarray(0.0), jnp.asarray(True))).aux is None


def test_first_rung_recovery_matches_clean_reference():
    """Satellite regression: a zero-nugget duplicate-row breakdown heals on
    the ladder's first rung, and the recovered loglik matches a clean
    evaluation at that same nugget to 1e-3 (identical matrices)."""
    base = np.asarray(grid_locations(5, jitter=0.2, seed=1))
    locs = np.concatenate([base, base[:3]], axis=0)  # 3 exact duplicates
    n = locs.shape[0]
    sigma0 = build_sigma(locs, _PARAMS, nugget=0.0)
    m = sigma0.shape[0]
    rng = np.random.default_rng(0)
    z = jnp.asarray(rng.normal(size=m))
    eye = jnp.eye(m, dtype=sigma0.dtype)

    def eval_at(j):
        r = loglik_from_chol(jnp.linalg.cholesky(sigma0 + j * eye), z)
        return r.loglik, r.status.ok & jnp.isfinite(r.loglik)

    # The clean attempt must actually break (singular Sigma).
    _, ok0 = eval_at(jnp.zeros(()))
    assert not bool(ok0)

    rec = jax.jit(lambda: jitter_escalate(
        eval_at, initial=1e-8, factor=10.0, max_jitter=1e-2,
        max_attempts=6))()
    assert bool(rec.ok)
    assert int(rec.attempts) == 2          # first rung was enough
    assert float(rec.jitter) == pytest.approx(1e-8)
    clean = loglik_from_chol(jnp.linalg.cholesky(sigma0 + 1e-8 * eye), z)
    assert abs(float(rec.loglik) - float(clean.loglik)) < 1e-3
    assert n == 28  # geometry sanity: 25 grid + 3 duplicates


# ---------------------------------------------------------------------------
# Duplicate-location pre-flight
# ---------------------------------------------------------------------------


def test_find_duplicate_locations():
    rng = np.random.default_rng(0)
    locs = rng.uniform(size=(40, 2))
    assert find_duplicate_locations(locs) == []

    locs2 = np.concatenate(
        [locs, locs[5:6], locs[7:8] + 1e-13], axis=0)
    pairs = find_duplicate_locations(locs2)
    assert (5, 40) in pairs
    assert (7, 41) in pairs


def test_check_locations_raises_with_indices():
    locs = np.asarray([[0.1, 0.2], [0.3, 0.4], [0.1, 0.2]])
    with pytest.raises(ValueError, match=r"\(0, 2\)"):
        check_locations(locs)
    check_locations(locs[:2])  # distinct rows: no raise


def test_fit_rejects_duplicates_before_compiling():
    locs = np.asarray([[0.1, 0.2], [0.3, 0.4], [0.1, 0.2], [0.5, 0.5]])
    z = np.zeros(8)
    with pytest.raises(ValueError, match="check_duplicates"):
        fit(locs, z, MLEConfig(p=2, backend="exact"))


# ---------------------------------------------------------------------------
# NaN-aware Nelder-Mead
# ---------------------------------------------------------------------------


def test_nelder_mead_recovers_from_nan_region():
    """Initial simplex pokes into a NaN plateau; the recenter-shrink step
    pulls it back and the minimum is still found."""
    def fn(x):
        v = jnp.sum((x - 1.0) ** 2)
        return jnp.where(jnp.max(jnp.abs(x)) > 1.5, jnp.nan, v)

    res = nelder_mead(fn, jnp.asarray([1.4, 1.4]), max_iters=300)
    assert np.isfinite(float(res.value))
    assert float(res.value) < 1e-4
    np.testing.assert_allclose(np.asarray(res.x), [1.0, 1.0], atol=1e-2)


def test_nelder_mead_has_aux_accumulates():
    def fn(x):
        v = jnp.sum(x ** 2)
        bad = jnp.max(jnp.abs(x)) > 0.6
        return jnp.where(bad, jnp.nan, v), bad.astype(jnp.int32)

    res = nelder_mead(fn, jnp.asarray([0.5, -0.3]), max_iters=100,
                      has_aux=True)
    assert np.isfinite(float(res.value))
    assert res.aux is not None
    assert int(res.aux) >= 1  # the initial simplex crossed 0.6


def test_nelder_mead_resume_matches_oneshot():
    fn = lambda x: jnp.sum((x - 3.0) ** 2) + x[0] * x[1] * 0.1
    x0 = jnp.asarray([0.0, 0.0])
    full = nelder_mead(fn, x0, max_iters=100)
    part = nelder_mead(fn, x0, max_iters=7)
    resumed = nelder_mead(fn, x0, max_iters=100, init_state=part.state)
    assert float(resumed.value) == pytest.approx(float(full.value), abs=1e-12)
    assert int(resumed.n_iters) == int(full.n_iters)
    np.testing.assert_allclose(np.asarray(resumed.x), np.asarray(full.x),
                               atol=1e-12)


# ---------------------------------------------------------------------------
# Checkpointing
# ---------------------------------------------------------------------------


def test_checkpoint_manager_roundtrip_and_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "cm"), keep=2)
    tree = {"a": jnp.arange(4.0), "b": jnp.ones((2, 3))}
    for s in range(4):
        mgr.save(s, tree, extra={"s": s})
    assert mgr.latest_step() == 3
    assert mgr.all_steps() == [2, 3]  # keep=2 garbage-collected 0, 1
    restored, manifest = mgr.restore(tree)
    np.testing.assert_array_equal(np.asarray(restored["a"]), np.arange(4.0))
    assert manifest["extra"]["s"] == 3


def test_checkpoint_gc_tolerates_racing_deletion(tmp_path):
    d = str(tmp_path / "gc")
    mgr = CheckpointManager(d, keep=1)
    for s in range(3):
        mgr.save(s, {"x": jnp.zeros(2)})
    _gc_old(str(tmp_path / "missing"), keep=1)  # directory never existed
    shutil.rmtree(d)
    _gc_old(d, keep=1)                          # vanished mid-flight
    assert CheckpointManager(d).all_steps() == []


def test_multistart_checkpoint_resume(tmp_path):
    fn = lambda x: jnp.sum((x - 2.0) ** 2)
    x0s = [jnp.asarray([0.0, 0.0]), jnp.asarray([5.0, 5.0])]
    ref = multistart_nelder_mead(fn, x0s, max_iters=60)

    d = str(tmp_path / "ck")
    r1 = multistart_nelder_mead(fn, x0s, max_iters=60, checkpoint_dir=d,
                                checkpoint_every=10)
    assert float(r1.value) == pytest.approx(float(ref.value), abs=1e-10)

    # Re-running against the finished checkpoint replays recorded results.
    r2 = multistart_nelder_mead(fn, x0s, max_iters=60, checkpoint_dir=d,
                                checkpoint_every=10)
    assert float(r2.value) == pytest.approx(float(ref.value), abs=1e-10)
    np.testing.assert_allclose(np.asarray(r2.x), np.asarray(r1.x))


def test_multistart_resumes_mid_start_state(tmp_path):
    """Crash simulation: a checkpoint written mid-way through start 0 is
    picked up and continued to the same optimum as an uninterrupted run."""
    fn = lambda x: jnp.sum((x - 2.0) ** 2)
    x0s = [jnp.asarray([0.0, 0.0]), jnp.asarray([5.0, 5.0])]
    ref = multistart_nelder_mead(fn, x0s, max_iters=60)

    partial = nelder_mead(fn, x0s[0], max_iters=8)
    d = str(tmp_path / "crash")
    mgr = CheckpointManager(d)
    mgr.save(0, {"state": partial.state},
             extra={"start_index": 0,
                    "iters_done": int(partial.state.n_iters),
                    "done_values": []})
    res = multistart_nelder_mead(fn, x0s, max_iters=60, checkpoint_dir=d,
                                 checkpoint_every=30)
    assert float(res.value) == pytest.approx(float(ref.value), abs=1e-10)


# ---------------------------------------------------------------------------
# Property: recovery never emits NaN on near-singular inputs
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    _N = 24

    @jax.jit
    def _dense_ladder(sigma, z):
        def eval_at(j):
            chol = jnp.linalg.cholesky(
                sigma + j * jnp.eye(_N, dtype=sigma.dtype))
            r = loglik_from_chol(chol, z)
            return r.loglik, r.status.ok & jnp.isfinite(r.loglik)

        return jitter_escalate(eval_at, initial=1e-10, factor=10.0,
                               max_jitter=1.0, max_attempts=12)

    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 10_000), rank=st.integers(1, _N),
           noise=st.sampled_from([0.0, 1e-14, 1e-10]))
    def test_recovery_finite_on_near_singular_dense(seed, rank, noise):
        rng = np.random.default_rng(seed)
        b = rng.normal(size=(_N, rank))
        sigma = jnp.asarray(b @ b.T + noise * np.eye(_N))
        z = jnp.asarray(rng.normal(size=_N))
        rec = _dense_ladder(sigma, z)
        assert np.isfinite(float(rec.loglik))
        assert bool(rec.ok)

    _TLR_BASE = np.asarray(grid_locations(4, jitter=0.3, seed=3))  # 16 locs

    @jax.jit
    def _tlr_ladder(locs, z):
        def eval_at(j):
            r = tlr_loglik(None, z, _PARAMS, tol=1e-9, max_rank=8,
                           tile_size=8, nugget=j, locs=locs,
                           from_tiles=True, gen="xla")
            return r.loglik, r.status.ok & jnp.isfinite(r.loglik)

        return jitter_escalate(eval_at, initial=1e-8, factor=10.0,
                               max_jitter=1.0, max_attempts=10)

    @settings(max_examples=10, deadline=None)
    @given(dups=st.integers(0, 5), seed=st.integers(0, 1000))
    def test_recovery_finite_on_tlr_duplicates(dups, seed):
        """tlr_loglik + jitter ladder stays finite (and usually heals) when
        up to 5 of 16 locations collide at nugget 0."""
        locs = _TLR_BASE.copy()
        if dups:
            locs[-dups:] = locs[:dups]
        locs = locs[morton_order(locs)]
        rng = np.random.default_rng(seed)
        z = jnp.asarray(rng.normal(size=2 * locs.shape[0]))
        rec = _tlr_ladder(jnp.asarray(locs), z)
        assert np.isfinite(float(rec.loglik))
        assert bool(rec.ok) or int(rec.attempts) == 10
