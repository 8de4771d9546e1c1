"""Traffic kind ``serve_closed``: a closed loop of cokriging requests.

Set-up simulates the configuration's field from the seed and runs
``fit_factor`` once at the true parameters (set-up the traffic needs), then
one warm-up request, which compiles ``predict_batch`` or loads it from the
compile cache.  In the window, ``clients`` = 1 client sends
``predict_batch`` requests back to back through the module-level entry
point (request validation, the FactorStatus read-back, dispatch), each of
``batch`` locations drawn uniformly over the unit square from the seed,
and reads the mean, variance and interval back to the host.  Each request
is timed from the client's side.  It sends none once ``seconds`` have
passed.

Mix parameters (``traffic/<mix>.json``): ``batch`` and ``check_requests``
(how many requests, drawn from the seed, are compared with the
reference).
"""
from __future__ import annotations

import dataclasses
import math
import time

import numpy as np

from . import field, reference
from .streams import sample, stream


@dataclasses.dataclass
class State:
    locs: np.ndarray
    z: np.ndarray
    lower_factor: np.ndarray   # the reference's Cholesky factor at the truth
    prm: reference.Params
    cfg: object                # CokrigeServeConfig
    factor: object             # the program's CokrigeFactor
    rng: np.random.Generator   # request locations
    batch: int
    check_requests: int


@dataclasses.dataclass
class Request:
    locs: np.ndarray
    answer: tuple | None       # (mean, variance, lower, upper), each (B, p)
    seconds: float
    error: str | None
    dispatch: float = 0.0      # seconds until predict_batch returned


@dataclasses.dataclass
class Window:
    requests: list
    seconds: float


def serve_config(cfg: dict):
    from repro.serving.cokrige_service import CokrigeServeConfig

    return CokrigeServeConfig(tile_size=cfg["tile_size"],
                              max_rank=cfg["max_rank"], tol=cfg["tol"],
                              nugget=cfg["nugget"])


def program_params(prm: reference.Params):
    import jax.numpy as jnp
    from repro.core import MaternParams

    return MaternParams(jnp.asarray(prm.sigma2), jnp.asarray(prm.a),
                        jnp.asarray(prm.nu), jnp.asarray(prm.beta))


def setup(cell, seed: int) -> State:
    import jax
    import jax.numpy as jnp
    from repro.serving.cokrige_service import (make_cokrige_serve_fns,
                                               predict_batch)

    cfg, mix = cell.config, cell.traffic
    prm = reference.params_from_config(cfg["truth"])
    locs = field.locations(cfg["grid"], cfg["jitter"],
                           stream(cfg["network_seed"], 0))
    z, lower = reference.simulate(locs, prm, cfg["nugget"], stream(seed, 1))
    scfg = serve_config(cfg)
    fit_factor, _ = make_cokrige_serve_fns(scfg)
    factor = jax.block_until_ready(
        fit_factor(jnp.asarray(locs), jnp.asarray(z), program_params(prm)))
    warm = stream(seed, 5).uniform(size=(mix["batch"], 2))
    try:
        jax.block_until_ready(predict_batch(factor, warm, scfg))
    except ValueError:        # ServeError: a broken factor fails every request
        pass
    return State(locs, z, lower, prm, scfg, factor, stream(seed, 4),
                 mix["batch"], mix["check_requests"])


def window(state: State, seconds: float, probe) -> Window:
    import jax
    from repro.serving.cokrige_service import ServeError, predict_batch

    win = Window([], 0.0)
    t0 = time.perf_counter()
    while True:
        locs = state.rng.uniform(size=(state.batch, 2))
        with probe.span("request"):
            t = time.perf_counter()
            td = t
            try:
                out = predict_batch(state.factor, locs, state.cfg)
                td = time.perf_counter()
                answer = jax.device_get((out.mean, out.variance, out.lower,
                                         out.upper))
                error = None
            except ServeError as e:
                answer, error = None, e.code
            t1 = time.perf_counter()
        win.requests.append(Request(locs, answer, t1 - t, error, td - t))
        probe.tick()
        if t1 - t0 >= seconds:
            win.seconds = t1 - t0
            return win


def release(state: State):
    state.factor = None


def _bad(r: Request) -> bool:
    if r.answer is None:
        return True
    mean, var, lo, hi = (np.asarray(a) for a in r.answer)
    return not (np.all(np.isfinite(r.answer)) and np.all(var >= 0)
                and np.all(lo <= mean) and np.all(mean <= hi))


def attempted(win: Window) -> int:
    return len(win.requests)


def failed(win: Window) -> int:
    return sum(_bad(r) for r in win.requests)


def end_to_end(win: Window) -> dict:
    lat = np.array([r.seconds for r in win.requests])
    served = sum(r.locs.shape[0] for r in win.requests if not _bad(r))
    return {"predict_p95_ms": float(np.percentile(lat, 95)) * 1e3,
            "predict_locs_per_s": served / win.seconds}


def note(win: Window) -> str:
    """Where a request's time went on the host clock: until
    ``predict_batch`` returned (validation, the FactorStatus read-back,
    dispatch), and the read-back of the answer, which waits for the
    device.  Mean, min and max over the window's requests, ms."""
    def stats(xs):
        xs = np.asarray(xs) * 1e3
        return f"{float(xs.mean())!r} [{float(xs.min())!r}, {float(xs.max())!r}]"
    return (f"request dispatch ms {stats([r.dispatch for r in win.requests])}"
            f"; read-back ms "
            f"{stats([r.seconds - r.dispatch for r in win.requests])}")


def _gap(got, want) -> float:
    g = float(np.max(np.abs(np.asarray(got) - want)))
    return g if math.isfinite(g) else math.inf


def check(state: State, win: Window, seed: int) -> dict:
    """The numbers compared with their limits.

    ``mean_gap`` / ``var_gap``: the largest |program - reference| of the
    cokriging mean and of the kriging variance over ``check_requests``
    requests drawn from the seed (the field has unit marginal variance);
    ``failed_requests``: requests refused (ServeError) or answered with a
    non-finite value, a negative variance or an interval out of order.
    """
    krig = reference.Kriging(state.lower_factor, state.locs, state.z,
                             state.prm)
    mean_gap = var_gap = 0.0
    for r in sample(win.requests, state.check_requests, seed, 3):
        if r.answer is None:
            mean_gap = var_gap = math.inf
            continue
        mean, var = krig.predict(r.locs)
        mean_gap = max(mean_gap, _gap(r.answer[0], mean))
        var_gap = max(var_gap, _gap(r.answer[1], var))
    return {"mean_gap": mean_gap, "var_gap": var_gap,
            "failed_requests": failed(win)}
