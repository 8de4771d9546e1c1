"""Program phases of the traced device operations.

The program names its phases with ``jax.named_scope``: ``repro.gen``,
``repro.compress``, ``repro.factorize`` (with ``repro.recompress`` inside
it) and ``repro.solve``.  A scope is compile-time metadata: it reaches the
compiled program as each HLO instruction's ``op_name``, and a device trace
event carries only the instruction's text.  So the programs the cell's
window runs are compiled once more after the window (a load from the
compile cache that set-up filled) and their text maps each instruction
name to its ``op_name``.  An instruction without one takes the ``op_name``
of the instruction that calls its computation (a loop's body takes the
loop's), and one whose ``op_name`` does not start at the module's root
(``jit(f)/...``; a reducer's region) is read below its caller's.  Each
program is lowered first: one that names no phase (an older build) is
not compiled.  The compile cache keys a program without
its metadata, so it can hand back an unscoped build's executable; the
program is then compiled anew (``_scoped_text``).

Which programs: a traffic kind names them with a module-level
``programs(cell)`` that returns ``[(jitted, args, kwargs)]``, one entry per
program and shape its window runs (a serving kind with several batch
shapes gives one per shape); ``args`` may be ``jax.ShapeDtypeStruct``.
The kinds ``fit_eval`` and ``serve_closed`` predate that hook, and their
programs are built here (``_FALLBACK``).

An operation's phase is the innermost of the four phase scopes on its
path, so the phases split the leaf time with the unscoped rest, and
``recompress`` is a part of ``factorize``.  Where no program carries these
scopes (an older build), the readers return None; any other failure
fails the traced run.  An event whose instruction name is in the map but
whose result type differs belongs to another program (in the serving
window, small eager programs outside ``predict_batch``'s): it counts as
unscoped, and
where such events hold more than ``MISMATCH_SHARE`` of the leaf time the
map is in doubt and the readers return None.

The map is built once per process: the metrics of one run share it.
"""
from __future__ import annotations

import re
import sys
import time

PHASES = ("gen", "compress", "factorize", "solve")
NESTED = ("recompress",)
MISMATCH_SHARE = 1e-3      # of the leaf time, at most, in mismatched events

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([^\s(]+) ")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([^\s=]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\b(?:calls|body|condition|to_apply|branch_computations|"
                    r"called_computations)=(\{[^}]*\}|%?[\w.\-]+)")
_NAME = re.compile(r"%?([\w.\-]+)")
_SCOPE = re.compile(r"\brepro\.(\w+)")
_ROOTED = re.compile(r"^[\w.\-]+\(")     # "jit(f)/...": from the module's root


def instruction_paths(hlo_text: str) -> dict:
    """{instruction name: (op_name path, result type)} of an HLO module's
    text (``Compiled.as_text()``)."""
    own, caller, comp = {}, {}, None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None and line.rstrip().endswith("{"):
                comp = c.group(1)
            continue
        name, rest = m.groups()
        body, _, meta = rest.partition(", metadata={")
        path = _OP_NAME.search(meta)
        own[name] = (comp, path.group(1) if path else None,
                     rest.split(" ", 1)[0])
        for called in _CALLS.findall(body):
            for callee in _NAME.findall(called):
                caller.setdefault(callee, name)

    paths: dict = {}

    def path_of(name, depth=0):
        if name in paths:
            return paths[name]
        comp, path, _ = own[name]
        if path is None or not _ROOTED.match(path):
            up = caller.get(comp)
            base = path_of(up, depth + 1) if up in own and depth < 64 else ""
            path = base if path is None else "/".join(filter(None, (base,
                                                                   path)))
        paths[name] = path
        return path

    return {name: (path_of(name), own[name][2]) for name in own}


def phase(path: str) -> str | None:
    """The innermost phase scope on ``path``, or None."""
    found = [s for s in _SCOPE.findall(path) if s in PHASES]
    return found[-1] if found else None


def scopes(path: str) -> set:
    return set(_SCOPE.findall(path))


def event_instruction(event_name: str) -> tuple:
    """(instruction name, result type or None) of a device event: a TPU
    event's name is the instruction's text, ``%name = type op(...)``."""
    head, eq, rest = event_name.partition(" = ")
    if not eq:
        return event_name.lstrip("%"), None
    return head.lstrip("%"), rest.split(" ", 1)[0]


class ScopeMap:
    """Path of each traced operation name, from the compiled programs."""

    def __init__(self, hlo_texts):
        self.paths: dict = {}
        for text in hlo_texts:
            self.paths.update(instruction_paths(text))
        self.scoped = any(scopes(p) for p, _ in self.paths.values())
        self._memo: dict = {}
        self.mismatched: set = set()    # event names of another program

    def path(self, event_name: str) -> str:
        """The operation's scope path; "" where no program holds it."""
        if event_name not in self._memo:
            name, typ = event_instruction(event_name)
            path, want = self.paths.get(name, ("", None))
            if path and typ is not None and want is not None and typ != want:
                self.mismatched.add(event_name)
                path = ""
            self._memo[event_name] = path
        return self._memo[event_name]


def leaf_by_scope(trace, lo: float, hi: float, smap: ScopeMap) -> dict:
    """Leaf time (ns) in [lo, hi], averaged over the devices, by phase,
    nested scope, ``None`` (unscoped), ``"mismatched"`` (the part of the
    unscoped time in events of another program) and ``"total"``."""
    out = dict.fromkeys(PHASES + NESTED + (None, "mismatched", "total"), 0.0)
    for d in trace.devices:
        for name, ns in d.leaf_time(lo, hi).items():
            ns /= len(trace.devices)
            path = smap.path(name)
            out[phase(path)] += ns
            for s in NESTED:
                if s in scopes(path):
                    out[s] += ns
            if name in smap.mismatched:
                out["mismatched"] += ns
            out["total"] += ns
    return out


def _in_doubt(split: dict) -> bool:
    return split["mismatched"] > MISMATCH_SHARE * split["total"]


_MAPS: dict = {}


def scope_map(r) -> ScopeMap | None:
    """The cell's ScopeMap, or None where no program of the cell carries
    a phase scope."""
    key = r.cell.name
    if key not in _MAPS:
        t = time.perf_counter()
        texts = program_texts(r.cell)
        t1 = time.perf_counter()
        smap = ScopeMap(texts)
        print(f"scopes: program text in {t1 - t!r} s "
              f"({sum(map(len, texts))} chars), map in "
              f"{time.perf_counter() - t1!r} s", file=sys.stderr)
        _MAPS[key] = smap if smap.scoped else None
        if smap.scoped:
            _note(r, smap)
    return _MAPS[key]


def _note(r, smap: ScopeMap):
    """The traced window's split by phase, its largest leaves with their
    phase, and the events of another program, on standard error."""
    w = r.trace.window()
    if w is None:
        return
    split = leaf_by_scope(r.trace, w.start, w.end, smap)
    total = split.pop("total") or 1.0
    shares = {str(k): 100.0 * v / total for k, v in split.items()}
    top = [[n[:120], phase(smap.path(n)) or "", s] for n, s in
           _top_leaves(r.trace, w.start, w.end)]
    print(f"scopes: leaf share % {shares}; top leaves {top}; "
          f"mismatched names {len(smap.mismatched)} "
          f"{sorted(n[:80] for n in smap.mismatched)}",
          file=sys.stderr, flush=True)


def _top_leaves(trace, lo, hi, k: int = 10) -> list:
    """[(full name, seconds)] of the leaves that took most."""
    tot: dict = {}
    for d in trace.devices:
        for n, ns in d.leaf_time(lo, hi).items():
            tot[n] = tot.get(n, 0.0) + ns / len(trace.devices)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [(n, s / 1e9) for n, s in best]


def program_texts(cell) -> list:
    """HLO text of each program the cell's window runs, compiled as set-up
    compiled it (so the compile cache serves it), leaving out programs
    that name no phase."""
    texts = (_scoped_text(*p) for p in programs(cell))
    return [t for t in texts if t is not None]


def programs(cell) -> list:
    """[(jitted, args, kwargs)]: the programs of the cell's window, from
    its traffic kind's ``programs(cell)`` or, for the kinds that predate
    it, from ``_FALLBACK``."""
    from .bench import Bench, BenchError

    own = getattr(Bench.load().kind(cell.traffic), "programs", None)
    if own is not None:
        return list(own(cell))
    kind = cell.traffic["kind"]
    if kind not in _FALLBACK:
        raise BenchError(f"traffic kind {kind!r} names no programs: give "
                         f"chipbench/{kind}.py a programs(cell)")
    return _FALLBACK[kind](cell)


def _scoped_text(jitted, args, kwargs=None) -> str | None:
    """Compiled text of ``jitted(*args, **kwargs)`` with its scopes, or None
    where the program has none.

    The compile cache keys a program without its metadata, so it may serve
    the executable of an earlier build of the same program that had no
    scopes.  Then the program is compiled anew, with the in-process caches
    cleared, under a key that keeps the metadata."""
    import jax

    kwargs = kwargs or {}
    lowered = jitted.lower(*args, **kwargs)
    if not _SCOPE.search(lowered.as_text(debug_info=True)):
        return None
    text = lowered.compile().as_text()
    if _SCOPE.search(text):
        return text
    print("scopes: the cached executable has no scopes; compiling anew",
          file=sys.stderr, flush=True)
    flag = "jax_compilation_cache_include_metadata_in_key"
    old = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        jax.clear_caches()
        return jitted.lower(*args, **kwargs).compile().as_text()
    finally:
        jax.config.update(flag, old)


def _fit_programs(cell) -> list:
    import jax
    import jax.numpy as jnp

    from . import fit_eval, reference

    cfg = cell.config
    n = cfg["grid"][0] * cfg["grid"][1]
    d = len(reference.pack(reference.params_from_config(cfg["truth"])))
    f64 = jnp.float64
    args = (jax.ShapeDtypeStruct((d,), f64), jax.ShapeDtypeStruct((n, 2), f64),
            jax.ShapeDtypeStruct((n * cfg["p"],), f64))
    return [(fit_eval.objective(cfg), args, {})]


def _serve_programs(cell) -> list:
    """``predict_batch``'s jitted program at the mix's one batch size."""
    import jax
    import jax.numpy as jnp
    from repro.serving.cokrige_service import make_cokrige_serve_fns

    from . import reference, serve_closed

    cfg = cell.config
    n = cfg["grid"][0] * cfg["grid"][1]
    f64 = jnp.float64
    fit, predict = make_cokrige_serve_fns(serve_closed.serve_config(cfg))
    params = serve_closed.program_params(
        reference.params_from_config(cfg["truth"]))
    factor = jax.eval_shape(fit, jax.ShapeDtypeStruct((n, 2), f64),
                            jax.ShapeDtypeStruct((n * cfg["p"],), f64),
                            params)
    locs = jax.ShapeDtypeStruct((cell.traffic["batch"], 2), f64)
    return [(predict, (factor, locs), {"key": None, "n_draws": 1})]


# Programs of the traffic kinds that have no ``programs(cell)`` of their own.
_FALLBACK = {"fit_eval": _fit_programs, "serve_closed": _serve_programs}


def fit_share(r, scope: str) -> float | None:
    """Percent of the traced window's leaf time under ``scope``."""
    w = r.trace.window()
    smap = scope_map(r) if w is not None else None
    if smap is None:
        return None
    split = leaf_by_scope(r.trace, w.start, w.end, smap)
    if not split["total"] or _in_doubt(split):
        return None
    return 100.0 * split[scope] / split["total"]


def request_ms(r, scope: str) -> float | None:
    """Device leaf time under ``scope`` per ``request`` span (ms), averaged
    over the requests the device trace covers."""
    spans = r.trace.covered("request")
    smap = scope_map(r) if spans and r.trace.devices else None
    if smap is None:
        return None
    splits = [leaf_by_scope(r.trace, s.start, s.end, smap) for s in spans]
    total = {k: sum(sp[k] for sp in splits) for k in splits[0]}
    if _in_doubt(total):
        return None
    return total[scope] / len(spans) / 1e6
