"""Derivative-free optimization (the NLOPT role in the paper's stack).

The paper calls NLOPT (BOBYQA) because dK_nu/dnu has no stable closed form.
We implement a Nelder–Mead simplex whose control flow runs on the host and
calls the (jitted) objective only at the points each iteration needs (~2
objective evaluations per iteration on average) — each objective evaluation
is one Sigma build + Cholesky, exactly the unit the paper benchmarks as "one
iteration of the MLE optimization".  The objective is one compiled program
that every evaluation reuses, and the optimizer never holds more than one
evaluation's memory.

Fault tolerance (robustness PR):

* Every objective value is sanitized on entry — a non-finite evaluation is
  stored as ``+inf`` so it can never poison the reflect/expand/contract
  ordering (``NaN < x`` is False for every x, which silently freezes the
  textbook simplex update).
* When any vertex holds a non-finite value the iteration performs a
  re-centering shrink toward the best (finite) vertex instead of a normal
  step, pulling the simplex back into the feasible region.
* ``has_aux`` threads an auxiliary pytree (clamp/retry counters from
  ``mle.make_objective``) out of every evaluation; the running total
  (a tree-sum, or ``aux_combine``) is returned on ``NMResult.aux``.
* ``init_state`` / ``NMResult.state`` make the loop resumable: run a
  bounded segment, checkpoint the ``NMState``, resume later —
  ``multistart_nelder_mead`` uses this for crash-tolerant multistart MLE.
"""
from __future__ import annotations

import math
from typing import Callable, NamedTuple

import numpy as np

import jax
import jax.numpy as jnp


class NMState(NamedTuple):
    simplex: jax.Array   # (m+1, m) sorted by value
    values: jax.Array    # (m+1,)
    n_evals: jax.Array
    n_iters: jax.Array
    aux: object = None   # running tree-sum of per-eval aux (scalar 0 if none)


class NMResult(NamedTuple):
    x: jax.Array
    value: jax.Array
    n_evals: jax.Array
    n_iters: jax.Array
    converged: jax.Array
    aux: object = None        # summed aux pytree (only when has_aux=True)
    state: NMState | None = None  # final loop state (resume/checkpoint handle)


def _order(simplex, values):
    idx = jnp.argsort(values)
    return simplex[idx], values[idx]


def _wrap_eval(fn: Callable, has_aux: bool):
    """Sanitizing evaluation: returns (value, aux) with NaN/inf -> +inf.

    A jitted ``fn`` is called as it is, so the optimizer runs the caller's
    compiled objective; anything else is jitted here."""
    fn = fn if hasattr(fn, "lower") else jax.jit(fn)

    def ev(x):
        out = fn(x)
        if has_aux:
            val, aux = out
        else:
            val, aux = out, jnp.zeros((), jnp.int32)
        val = jnp.asarray(val)
        if not math.isfinite(float(val)):
            val = jnp.asarray(jnp.inf, val.dtype)
        return val, aux
    return ev


def _tree_add(a, b):
    return jax.tree.map(jnp.add, a, b)


def _eval_rows(ev, points, combine=_tree_add):
    """Evaluate the rows of ``points`` one after another: one compiled
    objective, and one evaluation's memory at a time."""
    outs = [ev(x) for x in points]
    aux = outs[0][1]
    for _, a in outs[1:]:
        aux = combine(aux, a)
    return jnp.stack([v for v, _ in outs]), aux


def nm_init_state(fn: Callable, x0, *, initial_radius: float = 0.25,
                  has_aux: bool = False,
                  aux_combine: Callable | None = None) -> NMState:
    """Build (and evaluate) the initial simplex around ``x0``.

    Public so checkpoint-resume callers can construct a template state with
    the right pytree structure for ``restore_checkpoint``.
    """
    ev = _wrap_eval(fn, has_aux)
    x0 = jnp.asarray(x0)
    m = x0.shape[0]
    steps = initial_radius * jnp.where(jnp.abs(x0) > 1e-8, jnp.abs(x0), 1.0)
    simplex = jnp.concatenate([x0[None], x0[None] + jnp.diag(steps)], axis=0)
    values, aux = _eval_rows(ev, simplex, aux_combine or _tree_add)
    simplex, values = _order(simplex, values)
    return NMState(simplex, values, jnp.asarray(m + 1), jnp.asarray(0), aux)


def nelder_mead(fn: Callable, x0, *, max_iters: int = 200,
                initial_radius: float = 0.25, xtol: float = 1e-6,
                ftol: float = 1e-8, has_aux: bool = False,
                aux_combine: Callable | None = None,
                init_state: NMState | None = None) -> NMResult:
    """Minimize ``fn`` (scalar, jax-traceable) from x0 (shape (m,)).

    The simplex logic runs on the host and calls ``fn`` once per point it
    needs, so a jitted ``fn`` is compiled once and holds one evaluation's
    memory: an MLE objective is a whole TLR factorization.
    With ``has_aux=True`` the objective returns ``(value, aux_pytree)`` and
    the tree-sum of every evaluation's aux is returned on ``result.aux``;
    ``aux_combine(total, aux)`` replaces the sum where an aux needs another
    running total (``mle.ObjectiveAux.merge``).
    ``init_state`` resumes a previous run's ``result.state`` (the loop
    iteration/eval counters continue, so ``max_iters`` is a *total* cap).
    """
    ev = _wrap_eval(fn, has_aux)
    combine = aux_combine or _tree_add
    x0 = jnp.asarray(x0)
    m = x0.shape[0]

    if init_state is None:
        init_state = nm_init_state(fn, x0, initial_radius=initial_radius,
                                   has_aux=has_aux, aux_combine=combine)
    simplex, values, aux = init_state.simplex, init_state.values, init_state.aux
    n_evals, n_iters = int(init_state.n_evals), int(init_state.n_iters)

    alpha, gamma, rho_c, shrink_c = 1.0, 2.0, 0.5, 0.5

    while n_iters < max_iters:
        vals = np.asarray(values)
        spread_f = vals[-1] - vals[0]
        spread_x = float(jnp.max(jnp.abs(simplex - simplex[0:1])))
        if not (spread_f > ftol or spread_x > xtol):
            break
        n_iters += 1
        if not np.all(np.isfinite(vals)):
            # A vertex went non-finite (sanitized to +inf): pull the whole
            # simplex toward the best vertex instead of reflecting through
            # a poisoned centroid, and re-evaluate everything.
            s = simplex[0:1] + shrink_c * (simplex - simplex[0:1])
            v, a = _eval_rows(ev, s, combine)
            simplex, values = _order(s, v)
            n_evals += m + 1
            aux = combine(aux, a)
            continue

        centroid = jnp.mean(simplex[:-1], axis=0)
        worst = simplex[-1]
        f_best, f_second, f_worst = vals[0], vals[-2], vals[-1]

        xr = centroid + alpha * (centroid - worst)
        fr, a = ev(xr)
        n_evals += 1
        aux = combine(aux, a)
        new_pt, new_f, accepted = xr, fr, True
        if float(fr) < f_best:
            xe = centroid + gamma * (xr - centroid)
            fe, a = ev(xe)
            n_evals += 1
            aux = combine(aux, a)
            if float(fe) < float(fr):
                new_pt, new_f = xe, fe
        elif float(fr) >= f_second:
            if float(fr) < f_worst:             # outside contraction
                xc = centroid + rho_c * (xr - centroid)
                fc, a = ev(xc)
                accepted = float(fc) <= float(fr)
            else:                               # inside contraction
                xc = centroid - rho_c * (centroid - worst)
                fc, a = ev(xc)
                accepted = float(fc) < f_worst
            n_evals += 1
            aux = combine(aux, a)
            new_pt, new_f = xc, fc

        if accepted:
            simplex = simplex.at[-1].set(new_pt)
            values = values.at[-1].set(new_f)
        else:
            # Shrink toward the best vertex, which keeps its value.
            simplex = simplex[0:1] + shrink_c * (simplex - simplex[0:1])
            v, a = _eval_rows(ev, simplex[1:], combine)
            values = jnp.concatenate([values[:1], v])
            n_evals += m
            aux = combine(aux, a)
        simplex, values = _order(simplex, values)

    final = NMState(simplex, values, jnp.asarray(n_evals),
                    jnp.asarray(n_iters), aux)
    return NMResult(simplex[0], values[0], final.n_evals, final.n_iters,
                    jnp.asarray(n_iters < max_iters),
                    aux if has_aux else None, final)


def multistart_nelder_mead(fn: Callable, x0s, *, checkpoint_dir=None,
                           checkpoint_every: int = 0, has_aux: bool = False,
                           max_iters: int = 200, **kwargs) -> NMResult:
    """Run Nelder–Mead from several starts, keep the best.

    With ``checkpoint_dir`` set, progress is checkpointed so a crashed
    multistart resumes where it left off: completed starts are replayed
    from the manifest, and the in-progress start's simplex state is
    restored and continued.  ``checkpoint_every`` bounds how many
    iterations run between saves (0 = one save per completed start).
    """
    x0s = [jnp.asarray(x0) for x0 in x0s]
    if checkpoint_dir is None:
        results = [nelder_mead(fn, x0, max_iters=max_iters, has_aux=has_aux,
                               **kwargs) for x0 in x0s]
        values = jnp.stack([r.value for r in results])
        best = int(jnp.argmin(values))
        return results[best]

    from ..checkpointing.checkpoint import CheckpointManager

    mgr = CheckpointManager(checkpoint_dir)
    segment = checkpoint_every if checkpoint_every > 0 else max_iters
    initial_radius = kwargs.get("initial_radius", 0.25)

    start_idx, iters_done, done_results = 0, 0, []
    state = None
    latest = mgr.latest_step()
    if latest is not None:
        template = nm_init_state(fn, x0s[0], initial_radius=initial_radius,
                                 has_aux=has_aux)
        tree, manifest = mgr.restore(
            {"state": template}, step=latest)
        extra = manifest["extra"]
        start_idx = int(extra["start_index"])
        iters_done = int(extra["iters_done"])
        done_results = [tuple(r) for r in extra["done_values"]]
        state = tree["state"] if iters_done > 0 else None

    results = [NMResult(jnp.asarray(x), jnp.asarray(v),
                        jnp.asarray(ne), jnp.asarray(ni),
                        jnp.asarray(bool(c)))
               for x, v, ne, ni, c in done_results]
    step = latest if latest is not None else -1

    for i in range(start_idx, len(x0s)):
        while True:
            cap = min(max_iters, iters_done + segment)
            res = nelder_mead(fn, x0s[i], max_iters=cap, has_aux=has_aux,
                              init_state=state, **kwargs)
            state = res.state
            iters_done = int(state.n_iters)
            finished = bool(res.converged) or iters_done >= max_iters
            if finished:
                results.append(res)
                done_results.append((np.asarray(res.x).tolist(),
                                     float(res.value), int(res.n_evals),
                                     int(res.n_iters), bool(res.converged)))
            step += 1
            mgr.save(step, {"state": state},
                     extra={"start_index": i + 1 if finished else i,
                            "iters_done": 0 if finished else iters_done,
                            "done_values": done_results})
            if finished:
                state, iters_done = None, 0
                break

    values = jnp.stack([r.value for r in results])
    best = int(jnp.argmin(values))
    return results[best]
