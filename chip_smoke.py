#!/usr/bin/env python3
"""Drive the geostat main path once on TPU and check what comes out.

    python3 chip_smoke.py              # one chip: phases 1-4
    python3 chip_smoke.py --chips 4    # four chips: the pair-sharded
                                       # dist_tlr_loglik against mesh=None

Phases, in one process (each prints its numbers on lines of its own):

1. device: platform, kind and count as JAX reports them.  Anything but a
   TPU fails; there is no CPU fallback.
2. accuracy: a bivariate parsimonious Matérn field (nu22 off the
   half-integers, ExaGeoStat's weak-correlation range a = 0.03) simulated
   from ``--seed`` on a Morton-ordered jittered grid;
   ``dist_tlr_loglik(from_tiles=True, block_cyclic=True)`` at
   geostat-tlr's geometry (nb=2048, kmax, tol=1e-7) against the dense
   ``exact_loglik``, both on the chip.  Fails if |delta| > 1e-3, or if the
   dense factor's residual ||L L^T - Sigma|| on its last column panel
   (an XLA GEMM against a freshly generated panel, independent of the
   loop-form Cholesky) exceeds 1e-9 relative.
3. fit: ``core.mle.fit`` with the TLR backend for a Nelder-Mead
   iteration on that data, at mle_65k's geometry cut to the n the run's
   time limit allows (the ``reduced`` line lists each cut).  Set-up
   (trace, lower, compile or cache load) inside the fit is clocked apart
   from its evaluations.  Fails on the sentinel, or if any evaluation's
   FactorStatus was not ok (``clamped_evals``).
4. serve: ``make_cokrige_serve_fns`` -> ``fit_factor`` once at the true
   parameters (its FactorStatus must be ok), then a few ``predict_batch``
   requests; the served mean against dense ``cokrige`` within 1e-3
   relative, finite means and ordered intervals.

Four programs are compiled: the dense reference, the MLE objective (phases
2 and 3 share it), ``fit_factor`` and ``predict_batch``; f64 compiles
dominate a cold run.  Set-up times are trace + lower + compile.
The compile cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, else in
``<repo>/.jax_cache``; the objective is compiled a second time from that
cache, so every run prints a cold and a warm set-up time.  The last line of
standard output is ``{"ok": true, "device": {...}}``, printed only when
every phase passed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent

# Jittered grid, 64 x 32: n = 2048, m = 4096 (mle_65k: 256 x 256), two
# tiles of geostat-tlr's nb = 2048 per side.  Time sets n: one f64 TLR
# evaluation took 63.5 s on v5e at n = 4096 (T = 4 panel steps of
# latency-bound loop-form decompositions), and the cold run, with its four
# f64 compiles, must end within 1200 s.
GRID = (64, 32)
SHARDED_GRID = (64, 64)   # --chips 4: n = 4096, six tile pairs to deal out
FIT_ITERS = 1         # Nelder-Mead iterations of phase 3
NUGGET = 1e-6
TRUE = dict(sigma11=1.0, sigma22=1.0, a=0.03, nu11=0.5, nu22=1.3, beta=0.5)
PRED_B = 64           # prediction locations per request
N_REQUESTS = 3
LL_GATE = 1e-3        # |TLR - exact| loglik, the check_bench gate
MEAN_GATE = 1e-3      # relative served-mean error against dense cokrige
RESID_GATE = 1e-9     # relative ||L L^T - Sigma|| on the last column panel
RESID_LOCS = 128      # locations in that panel


def log(*parts):
    print(*parts, flush=True)


class PhaseError(RuntimeError):
    pass


def check(cond: bool, what: str):
    if not cond:
        raise PhaseError(what)


def timed(fn, *args):
    """Run ``fn(*args)``, wait for the device, return (result, seconds)."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def compile_aot(fn, *args):
    """Trace, lower and compile ``fn`` for ``args``: (compiled, seconds)."""
    import jax

    t0 = time.perf_counter()
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    compiled = jitted.lower(*args).compile()
    return compiled, time.perf_counter() - t0


class SetupClock:
    """Wall time JAX spends tracing, lowering and compiling (or loading from
    the cache) while the block runs: the union of its compile-event spans,
    so nested traces count once."""

    EVENTS = ("/jax/core/compile/",)

    def __enter__(self):
        import jax.monitoring

        self.spans = []
        jax.monitoring.register_event_time_span_listener(self._span)
        return self

    def _span(self, event, start, end, **_):
        if event.startswith(self.EVENTS):
            self.spans.append((start, end))

    def __exit__(self, *exc):
        import jax.monitoring

        jax.monitoring.unregister_event_time_span_listener(self._span)

    @property
    def seconds(self) -> float:
        total, reach = 0.0, float("-inf")
        for start, end in sorted(self.spans):
            if end > reach:
                total += end - max(start, reach)
                reach = end
        return total


def generator(compiled) -> str:
    """Which tile generator the compiled program runs."""
    return "pallas" if "tpu_custom_call" in compiled.as_text() else "xla"


def peak_gib(device) -> str:
    stats = device.memory_stats() or {}
    return f"{stats.get('peak_bytes_in_use', 0) / 2**30:.3f}"


def field(grid, seed: int):
    """Morton-ordered jittered grid locations (numpy)."""
    import numpy as np
    from repro.core.covariance import morton_order
    from repro.core.simulate import grid_locations

    locs = grid_locations(*grid, jitter=0.2, seed=seed)
    return np.asarray(locs)[morton_order(locs)]


def tlr_kwargs():
    from repro.configs.geostat import GEOSTAT_TLR as g

    return dict(from_tiles=True, block_cyclic=True, tile_size=g.tile_size,
                max_rank=g.max_rank, tol=g.tol, nugget=NUGGET)


def dense_reference(locs, pred_locs, key, x):
    """Data simulated at packed (non-profile) parameters ``x``, its exact
    loglik, the dense cokriging mean at ``x``, and the factor's relative
    residual on its last ``RESID_LOCS`` locations' columns.  The simulation,
    the loglik and the residual build the same Sigma(x) from the same
    operand, and XLA compiles it once."""
    import jax.numpy as jnp
    from repro.core import (build_sigma, cokrige, dense_factor, exact_loglik,
                            simulate_mgrf)
    from repro.core.mle import unpack_params

    params = unpack_params(x, 2, False)
    z = simulate_mgrf(key, locs, params, nugget=NUGGET)[0]
    res = exact_loglik(locs, z, params, nugget=NUGGET, keep_chol=True)
    factor = dense_factor(locs, z, params, chol=res.chol)
    lo, k = res.chol, 2 * RESID_LOCS
    panel = build_sigma(locs, params, nugget=NUGGET)[:, -k:]
    resid = (jnp.linalg.norm(lo @ lo[-k:].T - panel)
             / jnp.linalg.norm(panel))
    return z, res.loglik, cokrige(None, None, pred_locs, factor=factor), resid


def mle_config(max_iters: int):
    from repro.configs.geostat import GEOSTAT_TLR as g
    from repro.core.mle import MLEConfig

    return MLEConfig(p=2, profile=False, backend="tlr",
                     dist_tlr_from_tiles=True, block_cyclic=True,
                     tile_size=g.tile_size, tlr_max_rank=g.max_rank,
                     tlr_tol=g.tol, nugget=NUGGET, max_iters=max_iters)


def phase_accuracy(args, dev):
    """Dense reference and the TLR likelihood (through the MLE objective
    the fit uses) at the true parameters."""
    import jax
    import jax.numpy as jnp
    from repro.configs.geostat import GEOSTAT_TLR as g
    from repro.core import MaternParams
    from repro.core.mle import make_objective, pack_params

    locs = jnp.asarray(field(GRID, args.seed))
    n = locs.shape[0]
    key = jax.random.key(args.seed)
    pred = jax.random.uniform(jax.random.fold_in(key, 1),
                              (N_REQUESTS * PRED_B, 2), locs.dtype)
    x = pack_params(MaternParams.bivariate(**TRUE), profile=False)
    ref, setup = compile_aot(dense_reference, locs, pred, key, x)
    (z, exact, dense_mean, resid), run = timed(ref, locs, pred, key, x)
    log(f"accuracy dense n={n} m={2 * n} a={TRUE['a']} setup_s={setup:.3f} "
        f"run_s={run:.3f} exact_loglik={float(exact):.6f} "
        f"chol_resid={float(resid):.3e} gate={RESID_GATE:g} "
        f"peak_gib={peak_gib(dev)}")
    check(float(resid) <= RESID_GATE,
          f"accuracy: dense factor residual {float(resid):.3e}")

    neg_ll, _ = make_objective(locs, z, mle_config(FIT_ITERS), with_aux=True)
    obj, cold = compile_aot(neg_ll, x)
    (val, aux), run = timed(obj, x)
    jax.clear_caches()                       # in-memory only; disk stays
    _, warm = compile_aot(neg_ll, x)
    ll = -float(val)
    delta = abs(ll - float(exact))
    log(f"accuracy tlr n={n} nb={g.tile_size} kmax={g.max_rank} tol={g.tol:g}"
        f" generator={generator(obj)} setup_cold_s={cold:.3f}"
        f" setup_warm_s={warm:.3f} eval_s={run:.3f} tlr_loglik={ll:.6f}"
        f" delta={delta:.3e} gate={LL_GATE:g} clamped={int(aux.clamped)} "
        f"peak_gib={peak_gib(dev)}")
    check(int(aux.clamped) == 0, "accuracy: the TLR factorization broke down")
    check(delta <= LL_GATE, f"accuracy: |delta loglik| {delta:.3e} > {LL_GATE}")
    return dict(locs=locs, z=z, pred=pred, x=x, dense_mean=dense_mean)


def phase_fit(args, dev, acc):
    """``FIT_ITERS`` Nelder-Mead iterations of ``core.mle.fit`` from a start
    off the truth."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import MaternParams
    from repro.core.mle import fit, pack_params
    from repro.core.recovery import sentinel_loglik

    locs, z = acc["locs"], acc["z"]
    n = locs.shape[0]
    log(f"reduced n 65536->{n} (one f64 TLR evaluation took 63.5 s at "
        "n=4096 on v5e; the cold run must end within 1200 s) "
        f"max_iters 150->{FIT_ITERS} (the same limit)")
    x0 = pack_params(MaternParams.bivariate(a=0.04, nu11=0.6, nu22=1.1,
                                            beta=0.3), profile=False)
    with SetupClock() as clock:
        t0 = time.perf_counter()
        res = fit(np.asarray(locs), np.asarray(z), mle_config(FIT_ITERS),
                  x0=x0)
        ll = float(res.loglik)
        fit_s = time.perf_counter() - t0
    evals = int(res.n_evals)
    eval_s = (fit_s - clock.seconds) / evals
    log(f"fit n={n} fit_s={fit_s:.3f} setup_s={clock.seconds:.3f} "
        f"evals={evals} s_per_eval={eval_s:.3f} iters={int(res.n_iters)} "
        f"loglik={ll:.6f} clamped_evals={int(res.clamped_evals)} "
        f"peak_gib={peak_gib(dev)}")
    # the sentinel is taken on the CPU: TPU f64 has f32's exponent range,
    # where -sqrt(finfo(f64).max) is not finite
    with jax.default_device(jax.devices("cpu")[0]):
        sentinel = float(sentinel_loglik(jnp.float64))
    check(np.isfinite(ll) and ll > sentinel,
          f"fit: loglik {ll} is the sentinel or not finite")
    # an evaluation is clamped when its FactorStatus is not ok
    check(int(res.clamped_evals) == 0,
          f"fit: {int(res.clamped_evals)} evaluations broke down")


def phase_serve(args, dev, acc):
    """``fit_factor`` once at the true parameters, then ``predict_batch``
    requests against its factor, checked against dense cokriging."""
    import jax
    import numpy as np
    from repro.configs.geostat import GEOSTAT_TLR as g
    from repro.core.mle import unpack_params
    from repro.serving.cokrige_service import (CokrigeServeConfig,
                                               make_cokrige_serve_fns,
                                               predict_batch)

    locs, z, pred = acc["locs"], acc["z"], acc["pred"]
    cfg = CokrigeServeConfig(tile_size=g.tile_size, max_rank=g.max_rank,
                             tol=g.tol, nugget=NUGGET)
    fit_factor, predict = make_cokrige_serve_fns(cfg)
    truth = unpack_params(acc["x"], 2, False)
    t0 = time.perf_counter()
    fit_c = fit_factor.lower(locs, z, truth).compile()
    setup = time.perf_counter() - t0
    factor, prefill = timed(fit_c, locs, z, truth)
    check(bool(factor.status.ok),
          f"serve: factor status {factor.status.as_dict()}")
    ranks = np.asarray(factor.ranks)
    t0 = time.perf_counter()
    pred_c = predict.lower(factor, pred[:PRED_B]).compile()
    # the jitted entry point traces again and loads pred_c from the cache
    jax.block_until_ready(predict_batch(factor, pred[:PRED_B], cfg))
    setup += time.perf_counter() - t0
    log(f"serve fit_factor n={locs.shape[0]} generator={generator(fit_c)}/"
        f"{generator(pred_c)} setup_s={setup:.3f} prefill_s={prefill:.3f} "
        f"max_rank={int(ranks.max())} tiles_at_kmax="
        f"{int(np.sum(ranks >= g.max_rank))}/{ranks.size} "
        f"status={factor.status.as_dict()}")
    means, lat = [], []
    for r in range(N_REQUESTS):
        out, s = timed(predict_batch, factor,
                       pred[r * PRED_B:(r + 1) * PRED_B], cfg)
        lat.append(s)
        means.append(np.asarray(out.mean))
        check(bool(np.all(np.isfinite(out.mean))), "serve: non-finite mean")
        check(bool(np.all(out.lower <= out.mean) and
                   np.all(out.mean <= out.upper)),
              "serve: prediction interval out of order")
    mean = np.concatenate(means)
    dense = np.asarray(acc["dense_mean"])
    rel = float(np.linalg.norm(mean - dense) / np.linalg.norm(dense))
    log(f"serve predict requests={N_REQUESTS} batch={PRED_B} "
        "latency_s=" + ",".join(f"{s:.4f}" for s in lat) +
        f" rel_err_vs_dense={rel:.3e} gate={MEAN_GATE:g} "
        f"peak_gib={peak_gib(dev)}")
    check(rel <= MEAN_GATE, f"serve: relative mean error {rel:.3e}")
    jax.block_until_ready(factor)


def phase_sharded(args, devices):
    """--chips 4: the pair-sharded pipeline against the mesh=None call.

    z is a seeded standard-normal vector: the comparison needs the same
    data on both sides, not a field from the model, and it spares the run
    a dense m x m program."""
    import jax
    import jax.numpy as jnp
    from repro.core import MaternParams
    from repro.core.dist_tlr import dist_tlr_loglik
    from repro.launch.mesh import make_mesh_for_devices

    mesh = make_mesh_for_devices(len(devices))
    params = MaternParams.bivariate(**TRUE)
    locs = jnp.asarray(field(SHARDED_GRID, args.seed))
    z = jax.random.normal(jax.random.key(args.seed), (2 * locs.shape[0],),
                          locs.dtype)
    kw = tlr_kwargs()

    def loglik(mesh):
        return lambda lo, zz: dist_tlr_loglik(None, zz, locs=lo, params=params,
                                              mesh=mesh, **kw).loglik

    # sharded first, so each device's peak is the sharded program's alone
    sharded, m_setup = compile_aot(loglik(mesh), locs, z)
    ll4, m_run = timed(sharded, locs, z)
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    per_dev = sharded.memory_analysis()
    single, s_setup = compile_aot(loglik(None), locs, z)
    ll1, s_run = timed(single, locs, z)
    one = single.memory_analysis()
    delta = abs(float(ll4) - float(ll1))
    log(f"sharded mesh={dict(mesh.shape)} n={locs.shape[0]} "
        f"sharded_setup_s={m_setup:.3f} sharded_eval_s={m_run:.3f} "
        f"single_setup_s={s_setup:.3f} single_eval_s={s_run:.3f} "
        f"loglik_sharded={float(ll4):.6f} loglik_single={float(ll1):.6f} "
        f"delta={delta:.3e} gate={LL_GATE:g}")
    dev_bytes = per_dev.temp_size_in_bytes + per_dev.argument_size_in_bytes
    one_bytes = one.temp_size_in_bytes + one.argument_size_in_bytes
    log(f"sharded program_bytes_per_device={dev_bytes} "
        f"single_program_bytes={one_bytes}")
    for d, peak in zip(devices, peaks):
        log(f"sharded device={d.id} peak_bytes_in_use={peak}")
    check(delta <= LL_GATE, f"sharded: |delta loglik| {delta:.3e}")
    check(dev_bytes < one_bytes,
          "sharded: a device holds as much as the single-device program")
    check(min(peaks) > 0.5 * max(peaks),
          f"sharded: device peaks {peaks} are not spread over the mesh")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the pair-sharded comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(REPO / ".jax_cache"))
    jax.config.update("jax_enable_x64", True)
    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    log("device", json.dumps(device))
    if dev.platform != "tpu":
        log(f"FAIL device: platform {dev.platform!r} is not a TPU")
        return 1
    if len(devices) < args.chips:
        log(f"FAIL device: {len(devices)} device(s), --chips {args.chips}")
        return 1
    sys.path.insert(0, str(REPO / "src"))

    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            phase_sharded(args, devices)
        else:
            acc = phase_accuracy(args, dev)
            phase_fit(args, dev, acc)
            phase_serve(args, dev, acc)
    except PhaseError as e:
        log(f"FAIL {e}")
        return 1
    log(f"total_s={time.perf_counter() - t0:.3f}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
