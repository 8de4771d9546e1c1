"""Paper Figs. 5-8 and 10-11: ranks, memory, GEN phase, one MLE iteration.

Reduced-n CPU reproduction of the TLR claims; the full-scale systems numbers
come from the dry-run roofline (EXPERIMENTS.md §Roofline).  ``main`` returns
the BENCH_tlr.json artifact dict (written by benchmarks/run.py) so future PRs
have a perf trajectory: GEN / compress / factorize timings, peak tile memory,
and the loglik delta of the generator-direct path vs the exact likelihood.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import MaternParams, exact_loglik, pairwise_distances
from repro.core import tlr as T
from repro.core.covariance import build_sigma, morton_order
from repro.core.simulate import grid_locations, simulate_mgrf

from .common import emit, time_fn


def _mesh1():
    """1-device ("data", "model") mesh: activates the shard_map recompress
    path (and the compress-phase sharding constraints) on a single CPU."""
    from repro.launch.mesh import auto_mesh

    return auto_mesh((1, 1), ("data", "model"))


def _setup(n_side, a=0.09, nu22=1.0):
    locs = grid_locations(n_side, jitter=0.2, seed=0)
    locs = np.asarray(locs)[morton_order(locs)]
    params = MaternParams.bivariate(a=a, nu11=0.5, nu22=nu22, beta=0.5)
    dists = pairwise_distances(locs)
    return locs, params, dists


def bench_rank_distribution(quick=False):
    """Fig. 5: off-diagonal tile ranks at TLR5/7/9 grow toward the diagonal."""
    locs, params, dists = _setup(16 if quick else 24)
    sigma = build_sigma(None, params, dists=dists, nugget=1e-8)
    nb = 64 if quick else 96
    for name, tol in (("TLR5", 1e-5), ("TLR7", 1e-7), ("TLR9", 1e-9)):
        us, t = time_fn(functools.partial(T.tlr_compress, sigma, nb, tol,
                                          min(nb, 64)), iters=1)
        ranks = T.rank_distribution(t)
        tn = t.n_tiles
        near = np.mean([ranks[i, i - 1] for i in range(1, tn)])
        far = np.mean([ranks[i, j] for i in range(tn) for j in range(i)
                       if i - j >= tn // 2]) if tn >= 4 else 0.0
        emit(f"fig5_rank_dist_{name}", us,
             f"near_diag_rank={near:.1f};far_rank={far:.1f};dense={nb}")


def bench_memory_footprint(quick=False):
    """Fig. 6: TLR memory vs dense (paper: 6.68X/4.93X/3.86X at n~10^5)."""
    for n_side in ((16, 24) if quick else (16, 24, 28)):
        locs, params, dists = _setup(n_side)
        sigma = build_sigma(None, params, dists=dists, nugget=1e-8)
        m = sigma.shape[0]
        for name, tol in (("TLR5", 1e-5), ("TLR7", 1e-7), ("TLR9", 1e-9)):
            t = T.tlr_compress(sigma, 0, tol, 64)
            mem = T.memory_footprint(t)
            emit(f"fig6_memory_{name}_m{m}", 0.0,
                 f"ratio={mem['ratio']:.2f};tlr_mb={mem['tlr_bytes']/1e6:.1f};"
                 f"dense_mb={mem['dense_bytes']/1e6:.1f}")


def bench_mle_iteration(quick=False):
    """Figs. 7-8: one MLE iteration, exact vs TLR (wall time, CPU f64)."""
    key = jax.random.PRNGKey(0)
    for n_side in ((16,) if quick else (16, 24, 28)):
        locs, params, dists = _setup(n_side)
        z = simulate_mgrf(key, locs, params, nugget=1e-8)[0]
        m = 2 * n_side * n_side

        exact_fn = jax.jit(lambda d, zz: exact_loglik(
            None, zz, params, dists=d, nugget=1e-8).loglik)
        us_exact, _ = time_fn(exact_fn, dists, z, iters=2)
        emit(f"fig7_exact_m{m}", us_exact, "backend=dense")

        for name, tol in (("TLR5", 1e-5), ("TLR7", 1e-7), ("TLR9", 1e-9)):
            tlr_fn = jax.jit(functools.partial(
                T.tlr_loglik, tol=tol, max_rank=48,
                tile_size=max(64, m // 16), nugget=1e-8))
            us_tlr, _ = time_fn(tlr_fn, dists, z, params, iters=2)
            emit(f"fig7_{name}_m{m}", us_tlr,
                 f"speedup_vs_exact={us_exact / us_tlr:.2f}")


def _drain_gen(locs, params, nb, gen):
    """Execute the full GEN phase (diag + every streamed lower panel)."""
    diag, lower, _, _ = T.generate_tiles(locs, params, nb, 1e-8, gen)
    last = diag
    for blk in lower:
        last = blk
    return diag, last


def bench_gen_phase(quick=False):
    """Figs. 10-11 GEN_TIME: generator-direct tile generation, Pallas
    half-integer kernel vs the XLA K_nu path, dense build_sigma as baseline.
    nu22=2.5 keeps every pairwise order half-integer (Pallas-eligible)."""
    n_side = 12 if quick else 16
    locs, params, dists = _setup(n_side, nu22=2.5)
    nb = T.choose_tile_size(2 * n_side * n_side, 64, multiple_of=2)
    us_dense, _ = time_fn(functools.partial(build_sigma, None, params,
                                            dists=dists, nugget=1e-8), iters=2)
    emit("fig10_gen_dense", us_dense, "path=build_sigma")
    for gen in ("pallas", "xla"):
        us, _ = time_fn(functools.partial(_drain_gen, locs, params, nb, gen),
                        iters=2)
        emit(f"fig10_gen_{gen}", us, f"tile_size={nb};vs_dense={us_dense/us:.2f}")


def bench_factorize_forms(quick=False):
    """Masked full-grid vs block-cyclic pair-batch distributed TLR Cholesky,
    both jitted, same compressed tiles (m >= 288; the ISSUE-3 acceptance
    comparison).  Returns the artifact fields check_bench gates on: the
    pair-batch form must not regress past the masked baseline (it measures
    ~1.5-1.6x faster on CPU at T = 8).  A third run times the pair-batch
    form with the recompress QR/SVD under shard_map over the pair axis
    (distribution/pair_qr.py, here on a 1-device mesh — the production
    sharded form; ``recompress_sharded_time_us``)."""
    from repro.core.dist_tlr import dist_tlr_cholesky

    n_side = 16 if quick else 20           # m = 512 / 800
    locs, params, _ = _setup(n_side, nu22=2.5)
    m = 2 * n_side * n_side
    nb = T.choose_tile_size(m, m // 8, multiple_of=2)   # T = 8 tiles
    t = T.tlr_compress_tiles(locs, params, tile_size=nb, tol=1e-7,
                             max_rank=48, nugget=1e-8)
    mesh1 = _mesh1()
    times = {}
    for name, kw in (("masked", dict()),
                     ("bc", dict(block_cyclic=True)),
                     ("bc_sharded", dict(block_cyclic=True, mesh=mesh1))):
        fn = jax.jit(functools.partial(dist_tlr_cholesky, tol=1e-7,
                                       scale=1.0, **kw))
        jax.block_until_ready(fn(t.diag, t.u, t.v, t.ranks))  # compile
        us, _ = time_fn(fn, t.diag, t.u, t.v, t.ranks, iters=3)
        times[name] = us
    speedup = times["masked"] / times["bc"]
    emit("factorize_masked_vs_bc", times["bc"],
         f"masked_us={times['masked']:.0f};speedup={speedup:.2f};m={m}")
    emit("factorize_bc_sharded", times["bc_sharded"],
         f"bc_us={times['bc']:.0f};"
         f"shard_map_overhead={times['bc_sharded'] / times['bc']:.2f};m={m}")
    return dict(factorize_m=m, factorize_tile_size=nb,
                cholesky_masked_time_us=times["masked"],
                cholesky_bc_time_us=times["bc"],
                cholesky_bc_speedup=speedup,
                recompress_sharded_time_us=times["bc_sharded"])


def _phase_temp_bytes(n, p, params, *, tile_size, max_rank, tol, nugget):
    """Compile the pipeline phases on one device and read
    memory_analysis().temp_size_in_bytes — the temp-footprint trajectory
    (the dry-run reports the same stat on the 256-device pod mesh).  The
    factorize stages donate their tile inputs, the production setting.
    ``*_bc_sharded`` compiles the pair-axis-sharded recompress form
    (shard_map on a 1-device mesh) so its compiled temps are gated too."""
    from repro.core.dist_tlr import (dist_tlr_compress_lowerable,
                                     dist_tlr_lowerable,
                                     dist_tlr_pipeline_lowerable)

    m = n * p
    nb = T.choose_tile_size(m, tile_size, multiple_of=p)
    t_tiles = m // nb
    kmax = min(max_rank, nb)
    mesh1 = _mesh1()
    out = {}
    comp_fn, comp_specs = dist_tlr_compress_lowerable(
        n, p, params, tile_size=nb, max_rank=kmax, tol=tol, nugget=nugget,
        gen="xla", mesh=None, dtype=jnp.float64)
    out["gen_compress"] = (comp_fn, comp_specs, ())
    # compress-phase sharding alone: owned-slot gen + truncation SVD under
    # shard_map over the pair axis (ISSUE-5)
    comp_sh_fn, comp_sh_specs = dist_tlr_compress_lowerable(
        n, p, params, tile_size=nb, max_rank=kmax, tol=tol, nugget=nugget,
        gen="xla", mesh=mesh1, dtype=jnp.float64, block_cyclic=True,
        shard_svd=True)
    out["compress_sharded"] = (comp_sh_fn, comp_sh_specs, ())
    for name, bc, mesh in (("factorize_masked", False, None),
                           ("factorize_bc", True, None),
                           ("factorize_bc_sharded", True, mesh1)):
        fn, specs = dist_tlr_lowerable(t_tiles, nb, kmax, tol=tol, mesh=mesh,
                                       dtype=jnp.float64, block_cyclic=bc,
                                       return_factor=True)
        out[name] = (fn, specs, (0, 1, 2, 3))
    # pipeline_bc_sharded keeps its PR-4 meaning (recompress sharding only:
    # shard_svd=False); pipeline_compress_sharded turns both shardings on —
    # the production form the dry-run compiles on the pod meshes.
    # pipeline_mixed_f32 is the compress-sharded production form under the
    # mixed storage policy (core/precision.py): check_bench gates its temps
    # strictly below the fp64 pipeline entry it narrows.
    for name, bc, mesh, ssvd, pol in (
            ("pipeline_masked", False, None, False, None),
            ("pipeline_bc", True, None, False, None),
            ("pipeline_bc_sharded", True, mesh1, False, None),
            ("pipeline_compress_sharded", True, mesh1, True, None),
            ("pipeline_mixed_f32", True, mesh1, True, "mixed_f32")):
        fn, specs = dist_tlr_pipeline_lowerable(
            n, p, params, tile_size=nb, max_rank=kmax, tol=tol, nugget=nugget,
            gen="xla", mesh=mesh, dtype=jnp.float64, block_cyclic=bc,
            shard_svd=ssvd, dtype_policy=pol)
        out[name] = (fn, specs, ())
    from repro.analysis import LintConfig, lint_lowerable, tlr_dense_frac
    temps = {}
    gate = dict(replicated_temp_bytes=0, undonated_dead_bytes=0)
    # Quick-bench geometry has fat tiles (kmax/nb ~ 2/3), so R3's bar must
    # scale past the legitimate (kmax/nb) m^2 tile storage.
    lcfg = LintConfig(dense_frac=tlr_dense_frac(tile_size, max_rank))
    for name, (fn, specs, donate) in out.items():
        comp = jax.jit(fn, donate_argnums=donate).lower(*specs).compile()
        ms = comp.memory_analysis()
        temps[name] = int(getattr(ms, "temp_size_in_bytes", 0))
        # SPMD-lint gate metrics: replicated decomposition bytes (R1) and
        # donatable-but-undonated dead input bytes (R2) must stay at zero
        # on every benchmarked phase (check_bench gates both keys).
        rep = lint_lowerable(fn, specs, mesh=None, donate_argnums=donate,
                             matrix_dim=m, compiled=comp, config=lcfg)
        gate["replicated_temp_bytes"] += rep.summary["replicated_temp_bytes"]
        gate["undonated_dead_bytes"] += rep.summary["undonated_dead_bytes"]
    return temps, gate


def collect_artifact(quick=False):
    """BENCH_tlr.json: separate GEN / compress / factorize timings, peak tile
    memory, the generator-direct loglik deltas vs the exact likelihood for
    both the single-device path and the distributed streaming pipeline
    (dist_compress_tiles -> fori_loop Cholesky, run unsharded here), the
    masked vs block-cyclic factorization comparison, per-phase compiled
    temp bytes (peak_temp_bytes), and the serving prefill/decode split
    (fit_factor / predict_batch timings + predictions/sec + the relative
    accuracy of the served mean vs dense cokriging)."""
    from repro.core.dist_tlr import dist_compress_tiles, dist_tlr_loglik

    n_side = 12 if quick else 16
    locs, params, dists = _setup(n_side, nu22=2.5)
    z = simulate_mgrf(jax.random.PRNGKey(0), locs, params, nugget=1e-8)[0]
    m = 2 * n_side * n_side
    tol, kmax = 1e-7, 48
    nb = T.choose_tile_size(m, 64, multiple_of=2)   # the actual tile size

    gen_us, _ = time_fn(functools.partial(_drain_gen, locs, params, nb,
                                          "pallas"), iters=2)
    compress_us, t = time_fn(functools.partial(
        T.tlr_compress_tiles, locs, params, tile_size=nb, tol=tol,
        max_rank=kmax, nugget=1e-8), iters=2)
    assert t.tile_size == nb
    chol_us, _ = time_fn(functools.partial(T.tlr_cholesky, t, tol=1e-9),
                         iters=2)
    mem = T.memory_footprint(t)
    # peak transient: the first (widest) strict-lower column panel, (m-nb) x nb
    peak_panel_bytes = (m - nb) * nb * t.diag.dtype.itemsize
    ll_exact = float(exact_loglik(None, z, params, dists=dists,
                                  nugget=1e-8).loglik)
    ll_tlr = float(T.tlr_loglik(None, z, params, tol=tol, max_rank=kmax,
                                tile_size=nb, nugget=1e-8, locs=locs,
                                from_tiles=True).loglik)

    # Distributed streaming pipeline, same problem (mesh=None: one device).
    locs_j = jnp.asarray(locs)
    dist_compress = jax.jit(lambda pts: dist_compress_tiles(
        pts, params, tile_size=nb, tol=tol, max_rank=kmax, nugget=1e-8))
    dist_compress_us, _ = time_fn(dist_compress, locs_j, iters=2)
    dist_ll = jax.jit(lambda pts, zz: dist_tlr_loglik(
        None, zz, locs=pts, params=params, from_tiles=True, tile_size=nb,
        max_rank=kmax, nugget=1e-8, tol=tol).loglik)
    dist_ll_us, ll_dist = time_fn(dist_ll, locs_j, z, iters=2)
    ll_dist = float(ll_dist)
    # Pair-native block-cyclic pipeline: same problem, never builds the grid.
    dist_ll_bc = jax.jit(lambda pts, zz: dist_tlr_loglik(
        None, zz, locs=pts, params=params, from_tiles=True, tile_size=nb,
        max_rank=kmax, nugget=1e-8, tol=tol, block_cyclic=True).loglik)
    dist_ll_bc_us, ll_dist_bc = time_fn(dist_ll_bc, locs_j, z, iters=2)
    ll_dist_bc = float(ll_dist_bc)
    # Fault-tolerance overheads (ISSUE 8), both measured on the pair-native
    # block-cyclic pipeline above.  (a) status threading: the identical
    # program with track_status=False, compared on compiled FLOP counts —
    # wall-clock on the quick-size workload carries +-5-8% timer noise, far
    # above the 1% gate, while the XLA cost model is deterministic and
    # catches exactly the regression the gate exists for (someone making
    # the FactorStatus carry do real work on the hot path).  The us figure
    # is derived as frac x the measured pipeline time.
    # (b) retry machinery: the jitter_escalate while_loop wrapped around the
    # same evaluation, clean data — no retries fire, so the measured excess
    # is pure ladder plumbing (cond/carry); its gate (50%) sits far above
    # the timer noise, so wall-clock is fine there.
    from repro.core.recovery import jitter_escalate
    from repro.launch.roofline import cost_analysis_dict
    dist_ll_bc_ns = jax.jit(lambda pts, zz: dist_tlr_loglik(
        None, zz, locs=pts, params=params, from_tiles=True, tile_size=nb,
        max_rank=kmax, nugget=1e-8, tol=tol, block_cyclic=True,
        track_status=False).loglik)
    flops_ws = float(cost_analysis_dict(
        dist_ll_bc.lower(locs_j, z).compile()).get("flops", 0.0))
    flops_ns = float(cost_analysis_dict(
        dist_ll_bc_ns.lower(locs_j, z).compile()).get("flops", 0.0))
    if flops_ns > 0:
        status_overhead_frac = max(flops_ws - flops_ns, 0.0) / flops_ns
    else:  # cost model unavailable on this backend: report 0, don't gate noise
        status_overhead_frac = 0.0
    status_overhead_us = status_overhead_frac * dist_ll_bc_us
    ws_us, _ = time_fn(dist_ll_bc, locs_j, z, iters=9)

    @jax.jit
    def _recovery_ll(pts, zz):
        def eval_at(j):
            r = dist_tlr_loglik(None, zz, locs=pts, params=params,
                                from_tiles=True, tile_size=nb, max_rank=kmax,
                                nugget=1e-8 + j, tol=tol, block_cyclic=True)
            return r.loglik, r.status.ok & jnp.isfinite(r.loglik)
        return jitter_escalate(eval_at).loglik

    rec_us, _ = time_fn(_recovery_ll, locs_j, z, iters=9)
    retry_overhead_frac = max(rec_us - ws_us, 0.0) / ws_us
    emit("fault_status_overhead", status_overhead_us,
         f"frac={status_overhead_frac:.4f};flops_no_status={flops_ns:.3e}")
    emit("fault_retry_overhead", max(rec_us - ws_us, 0.0),
         f"frac={retry_overhead_frac:.4f};recovery_us={rec_us:.0f}")

    # Sharded-recompress form: the same pair-native pipeline with the
    # recompress QR/SVD under shard_map over the pair axis (1-device mesh
    # here; the dry-run compiles the same program on the pod meshes).
    # shard_svd=False keeps this measurement recompress-sharding-only.
    mesh1 = _mesh1()
    dist_ll_sh = jax.jit(lambda pts, zz: dist_tlr_loglik(
        None, zz, locs=pts, params=params, from_tiles=True, tile_size=nb,
        max_rank=kmax, nugget=1e-8, tol=tol, block_cyclic=True,
        mesh=mesh1, shard_svd=False).loglik)
    dist_ll_sh_us, ll_dist_sh = time_fn(dist_ll_sh, locs_j, z, iters=2)
    ll_dist_sh = float(ll_dist_sh)
    # Compress-sharded form (ISSUE-5): owned-slot GEN + truncation SVD under
    # shard_map, plus the sharded recompress — the full production setting.
    from repro.distribution.block_cyclic import pair_layout, pair_shards
    layout1 = pair_layout(m // nb, pair_shards(mesh1))
    comp_sh = jax.jit(lambda pts: dist_compress_tiles(
        pts, params, tile_size=nb, tol=tol, max_rank=kmax, nugget=1e-8,
        mesh=mesh1, layout=layout1))
    comp_sh_us, _ = time_fn(comp_sh, locs_j, iters=2)
    dist_ll_csh = jax.jit(lambda pts, zz: dist_tlr_loglik(
        None, zz, locs=pts, params=params, from_tiles=True, tile_size=nb,
        max_rank=kmax, nugget=1e-8, tol=tol, block_cyclic=True,
        mesh=mesh1).loglik)
    dist_ll_csh_us, ll_dist_csh = time_fn(dist_ll_csh, locs_j, z, iters=2)
    ll_dist_csh = float(ll_dist_csh)

    # Mixed-precision pipeline (ROADMAP item 1): the same compress-sharded
    # program under dtype_policy="mixed_f32" — U/V storage and the
    # truncation SVDs at f32, diagonal/POTRF/logdet at f64.  Its delta is
    # measured against the fp64 pipeline it narrows (not the exact
    # likelihood), isolating the narrowing error from the TLR truncation
    # error; check_bench gates it at the standard 1e-3 loglik bound.
    dist_ll_mixed = jax.jit(lambda pts, zz: dist_tlr_loglik(
        None, zz, locs=pts, params=params, from_tiles=True, tile_size=nb,
        max_rank=kmax, nugget=1e-8, tol=tol, block_cyclic=True,
        mesh=mesh1, dtype_policy="mixed_f32").loglik)
    dist_ll_mixed_us, ll_dist_mixed = time_fn(dist_ll_mixed, locs_j, z,
                                              iters=2)
    ll_dist_mixed = float(ll_dist_mixed)
    emit("pipeline_mixed_f32", dist_ll_mixed_us,
         f"delta_vs_f64={abs(ll_dist_mixed - ll_dist_csh):.2e};"
         f"f64_us={dist_ll_csh_us:.0f}")

    # Parameter recovery under the mixed policy: two short fits from the
    # same start (f64 storage vs mixed_f32) must land on the same
    # parameters — the end-to-end accuracy statement a loglik point delta
    # cannot make.  Transformed (log/atanh) packed-vector relative error;
    # check_bench gates it at --max-recovery-err.
    from repro.core.mle import MLEConfig, fit, pack_params
    mle_fits = {}
    for pol in (None, "mixed_f32"):
        mcfg = MLEConfig(backend="tlr", tlr_tol=tol, tlr_max_rank=kmax,
                         tlr_from_tiles=True, tile_size=nb, nugget=1e-8,
                         gen="xla", max_iters=10 if quick else 25,
                         check_duplicates=False, dtype_policy=pol)
        mle_fits[pol] = fit(locs, z, mcfg)
    ref = np.asarray(pack_params(mle_fits[None].params, profile=False))
    got = np.asarray(pack_params(mle_fits["mixed_f32"].params, profile=False))
    recovery_err = float(np.linalg.norm(got - ref) / np.linalg.norm(ref))
    emit("mle_recovery_mixed_f32", 0.0,
         f"rel_param_err={recovery_err:.2e};"
         f"loglik_f64={float(mle_fits[None].loglik):.6f};"
         f"loglik_mixed={float(mle_fits['mixed_f32'].loglik):.6f}")

    # Serving (factor-once / predict-millions): time the prefill (compress +
    # pair Cholesky + alpha) and the decode (one B-point batch against the
    # cached factor).  The warmup + timed iters all reuse ONE factor handle —
    # Sigma is never rebuilt between batches (the serving contract; the
    # no-rebuild assertion itself lives in tests/test_serving_cokrige.py).
    # loglik_delta_predict is the RELATIVE max error of the served mean vs
    # the dense cokrige baseline, so check_bench's loglik_delta* gate (1e-3,
    # the ISSUE acceptance bound at m=512) applies to it unchanged.
    from repro.core.prediction import cokrige
    from repro.serving.cokrige_service import (CokrigeServeConfig,
                                               make_cokrige_serve_fns)
    B = 64 if quick else 128
    pred_locs = jnp.asarray(grid_locations(n_side, jitter=0.4, seed=7)[:B])
    scfg = CokrigeServeConfig(tile_size=nb, max_rank=kmax, tol=tol,
                              nugget=1e-8)
    fit_fn, pred_fn = make_cokrige_serve_fns(scfg)
    fit_us, factor = time_fn(fit_fn, locs_j, z, params, iters=2)
    pred_us, served = time_fn(pred_fn, factor, pred_locs, iters=3)
    dense_mean = np.asarray(cokrige(locs, z, pred_locs, params, nugget=1e-8))
    delta_pred = float(np.max(np.abs(np.asarray(served.mean) - dense_mean))
                       / np.max(np.abs(dense_mean)))
    emit("serving_fit_factor", fit_us, f"m={m};tile_size={nb}")
    emit("serving_predict_batch", pred_us,
         f"B={B};predictions_per_sec={B * 1e6 / pred_us:.0f};"
         f"rel_err_vs_dense={delta_pred:.2e}")

    phase_temps, lint_gate = _phase_temp_bytes(n_side * n_side, 2, params,
                                               tile_size=nb, max_rank=kmax,
                                               tol=tol, nugget=1e-8)
    return dict(
        **bench_factorize_forms(quick),
        peak_temp_bytes=phase_temps,
        **lint_gate,
        m=m, tile_size=nb, tol=tol, max_rank=kmax, quick=bool(quick),
        gen_time_us=gen_us,
        compress_time_us=compress_us,       # includes GEN (end-to-end)
        svd_time_us=max(compress_us - gen_us, 0.0),
        cholesky_time_us=chol_us,
        dist_compress_time_us=dist_compress_us,
        dist_loglik_time_us=dist_ll_us,     # full pipeline (GEN -> loglik)
        tlr_bytes=mem["tlr_bytes"], dense_bytes=mem["dense_bytes"],
        peak_tile_bytes=mem["tlr_bytes"] + peak_panel_bytes,
        loglik_exact=ll_exact, loglik_tlr=ll_tlr,
        loglik_delta_vs_exact=abs(ll_tlr - ll_exact),
        loglik_dist=ll_dist,
        loglik_delta_dist_vs_exact=abs(ll_dist - ll_exact),
        dist_loglik_bc_time_us=dist_ll_bc_us,
        loglik_dist_bc=ll_dist_bc,
        loglik_delta_dist_bc_vs_exact=abs(ll_dist_bc - ll_exact),
        dist_loglik_bc_sharded_time_us=dist_ll_sh_us,
        loglik_dist_bc_sharded=ll_dist_sh,
        loglik_delta_bc_sharded_vs_exact=abs(ll_dist_sh - ll_exact),
        # sharded vs replicated recompress must agree (check_bench gates it)
        loglik_delta_sharded_vs_bc=abs(ll_dist_sh - ll_dist_bc),
        # compress-phase sharding (ISSUE-5): owned-slot gen + sharded SVD
        compress_sharded_time_us=comp_sh_us,
        dist_loglik_compress_sharded_time_us=dist_ll_csh_us,
        loglik_dist_compress_sharded=ll_dist_csh,
        loglik_delta_compress_sharded=abs(ll_dist_csh - ll_exact),
        loglik_delta_compress_sharded_vs_bc=abs(ll_dist_csh - ll_dist_bc),
        # mixed-precision pipeline (ROADMAP item 1): narrowing error vs the
        # fp64 pipeline, and parameter recovery across a short fit
        dist_loglik_mixed_f32_time_us=dist_ll_mixed_us,
        loglik_dist_mixed_f32=ll_dist_mixed,
        loglik_delta_mixed_f32=abs(ll_dist_mixed - ll_dist_csh),
        mle_param_recovery_err_mixed_f32=recovery_err,
        # cokriging-as-a-service (PR 7): prefill/decode split
        fit_factor_time_us=fit_us,
        predict_batch_p50_us=pred_us,
        predictions_per_sec=B * 1e6 / pred_us,
        loglik_delta_predict=delta_pred,
        # fault tolerance (PR 8): status threading must be ~free on the hot
        # path (compiled-FLOP frac gated < 1% — deterministic, unlike the
        # noisy quick-size wall clock); the clean-path cost of the retry
        # ladder's while_loop wrapper is gated loosely (no retries fire).
        status_check_overhead_us=status_overhead_us,
        status_check_overhead_frac=status_overhead_frac,
        recovery_retry_overhead_frac=retry_overhead_frac,
    )


def main(quick=False):
    bench_rank_distribution(quick)
    bench_memory_footprint(quick)
    bench_gen_phase(quick)
    bench_mle_iteration(quick)
    return collect_artifact(quick)


if __name__ == "__main__":
    main()
