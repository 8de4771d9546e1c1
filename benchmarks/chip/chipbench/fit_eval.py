"""Traffic kind ``fit_eval``: likelihood evaluations as an MLE fit makes them.

Set-up simulates the configuration's field from the seed, builds the
objective that ``core.mle.fit`` hands to its optimizer
(``core.mle.make_objective``) and compiles it, or loads it from the
compile cache.  The field enters the compiled program as arguments:
``make_objective`` closes over it, and ``jax.jit`` would bake it into the
program as constants, so every seed's field would compile anew.

The window calls that compiled objective back to back, one host round
trip per evaluation, cycling through the ``1 + d`` points of Nelder-Mead's
initial simplex around a start drawn from the seed near the true
parameters.  It starts no evaluation once ``seconds`` have passed, so
it runs from the first evaluation's start to the last one's end.

Mix parameters (``traffic/<mix>.json``):

* ``start_scale``: standard deviation of the start's offset from the truth
  in the search space (log and atanh scales);
* ``simplex_radius``: the initial simplex step, as a share of each
  coordinate (1.0 for a coordinate at zero), as ``core.optimize`` builds it;
* ``check_points``: how many distinct evaluated points, drawn from the
  seed, are compared with the reference.
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
    points: list
    objective: object      # compiled, (x, locs, z) -> (value, aux)
    args: tuple            # (locs, z) on the device
    nugget: float
    p: int
    check_points: int


@dataclasses.dataclass
class Window:
    point: list            # index into State.points, per evaluation
    loglik: list           # the loglik each evaluation returned
    clamped: list          # ObjectiveAux.clamped, per evaluation
    durations: list        # seconds of each evaluation, host clock
    seconds: float         # first evaluation's start to last one's end


def simplex(x0: np.ndarray, radius: float) -> list:
    steps = radius * np.where(np.abs(x0) > 1e-8, np.abs(x0), 1.0)
    return [x0] + [x0 + steps[i] * np.eye(len(x0))[i] for i in range(len(x0))]


def mle_config(cfg: dict):
    from repro.core.mle import MLEConfig

    return MLEConfig(p=cfg["p"], profile=False, nugget=cfg["nugget"],
                     backend=cfg["backend"], tile_size=cfg.get("tile_size", 0),
                     tlr_max_rank=cfg.get("max_rank", 64),
                     tlr_tol=cfg.get("tol", 1e-7),
                     dtype_policy=cfg.get("dtype_policy"),
                     **cfg.get("layout", {}))


def objective(cfg: dict):
    """The objective of ``core.mle.make_objective``, with the data as
    arguments: (x, locs, z) -> (-loglik, ObjectiveAux)."""
    import jax
    from repro.core.mle import make_objective

    mcfg = mle_config(cfg)

    def neg_ll(x, locs, z):
        return make_objective(locs, z, mcfg, with_aux=True)[0](x)

    return jax.jit(neg_ll)


def setup(cell, seed: int) -> State:
    import jax.numpy as jnp

    cfg, mix = cell.config, cell.traffic
    prm = reference.params_from_config(cfg["truth"])
    locs = field.locations(cfg["grid"], cfg["jitter"],
                           stream(cfg["network_seed"], 0))
    z, _ = reference.simulate(locs, prm, cfg["nugget"], stream(seed, 1))
    x0 = reference.pack(prm) + stream(seed, 2).normal(
        scale=mix["start_scale"], size=len(reference.pack(prm)))
    args = (jnp.asarray(locs), jnp.asarray(z))
    compiled = objective(cfg).lower(jnp.asarray(x0), *args).compile()
    return State(locs, z, simplex(x0, mix["simplex_radius"]), compiled,
                 args, cfg["nugget"], cfg["p"], mix["check_points"])


def window(state: State, seconds: float, probe) -> Window:
    import jax.numpy as jnp

    win = Window([], [], [], [], 0.0)
    t0 = time.perf_counter()
    while True:
        k = len(win.point) % len(state.points)
        t = time.perf_counter()
        with probe.span("eval"):
            val, aux = state.objective(jnp.asarray(state.points[k]),
                                       *state.args)
            probe.wait(val)
            win.loglik.append(-float(val))
            win.clamped.append(int(aux.clamped))
        win.durations.append(time.perf_counter() - t)
        t = time.perf_counter() - t0
        win.point.append(k)
        probe.tick()
        if t >= seconds:
            win.seconds = t
            return win


def release(state: State):
    state.objective = state.args = None


def attempted(win: Window) -> int:
    return len(win.point)


def failed(win: Window) -> int:
    return sum(1 for v, c in zip(win.loglik, win.clamped)
               if c or not math.isfinite(v))


def end_to_end(win: Window) -> dict:
    return {"eval_s": win.seconds / len(win.point)}


def check(state: State, win: Window, seed: int) -> dict:
    """The numbers compared with their limits.

    ``loglik_gap``: the largest |program - reference| over the evaluations
    at ``check_points`` distinct points drawn from the seed;
    ``failed_evals``: evaluations clamped to the penalty (a factorization
    whose FactorStatus was not ok, or a non-finite loglik).
    """
    gap = 0.0
    for k in sample(sorted(set(win.point)), state.check_points, seed, 3):
        ref = reference.loglik(state.locs, state.z,
                               reference.unpack(state.points[k], state.p),
                               state.nugget)
        for pt, val in zip(win.point, win.loglik):
            if pt == k:
                gap = max(gap, abs(val - ref) if ref is not None and
                          math.isfinite(val) else math.inf)
    return {"loglik_gap": gap, "failed_evals": failed(win)}
