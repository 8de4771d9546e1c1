"""The control of the correctness comparison: what a run would read if a
lower precision took the program's place.

``reference_f32`` is the plain reference computed in float32, one step
below the float64 that every configuration states, put in the program's
place: the window's outputs are replaced by its answers to the same
requests, and ``check`` then compares them with the float64 reference as
it compares the program's.  It factors Sigma and solves in float32 and
sums the log-determinant and the quadratic form in float64, as the
program's precision policies require of any narrower factorization.

``Recorder`` and ``Float32`` drive both sides through ``runner.execute``:
a run of the program that keeps its set-up and window, then a run whose
window is the float32 reference's answers to that window's requests.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from . import fit_eval, reference


def reference_f32(state, win):
    """``win`` with each output replaced by the float32 reference's."""
    if isinstance(win, fit_eval.Window):
        f32 = {k: reference.loglik(
            state.locs, state.z, reference.unpack(state.points[k], state.p),
            state.nugget, np.float32) for k in set(win.point)}
        return dataclasses.replace(win, loglik=[
            np.nan if f32[k] is None else f32[k] for k in win.point])
    lo = reference.factor(state.locs, state.prm,
                          state.cfg.nugget, np.float32)
    if lo is None:
        return dataclasses.replace(win, requests=[
            dataclasses.replace(r, answer=None) for r in win.requests])
    krig = reference.Kriging(lo, state.locs, state.z.astype(np.float32),
                             state.prm)
    requests = []
    for r in win.requests:
        mean, var = krig.predict(r.locs)
        half = 1.96 * np.sqrt(np.clip(var, 0, None))
        requests.append(dataclasses.replace(
            r, answer=(mean, var, mean - half, mean + half)))
    return dataclasses.replace(win, requests=requests)


class Recorder:
    """A traffic kind that keeps the state and the window of its run."""

    def __init__(self, base):
        self.base = base

    def __getattr__(self, name):
        return getattr(self.base, name)

    def setup(self, cell, seed):
        self.state = self.base.setup(cell, seed)
        return self.state

    def window(self, state, seconds, probe):
        self.win = self.base.window(state, seconds, probe)
        return self.win


class Float32:
    """A traffic kind whose window is the float32 reference's answers to
    the requests of a recorded run, on that run's state."""

    def __init__(self, recorded: Recorder):
        self.recorded = recorded

    def __getattr__(self, name):
        return getattr(self.recorded.base, name)

    def setup(self, cell, seed):
        return self.recorded.state

    def window(self, state, seconds, probe):
        return reference_f32(state, self.recorded.win)

    def release(self, state):
        pass
