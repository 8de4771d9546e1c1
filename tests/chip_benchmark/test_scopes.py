"""The program's phase scopes as the chip benchmark reads them
(``benchmarks/chip/chipbench/scopes.py``), on the CPU.

A device trace event names only its HLO instruction; the scope path comes
from the compiled program's text.  Checked here: the mapping on a program
compiled on the CPU, the phase shares and per-request times on a recorded
trace with scoped leaves, loop containers and unscoped operations, and that
the program's own host spans leave every existing metric as it was.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "benchmarks" / "chip"))

from chipbench import fit_eval, runner, scopes, serve_closed  # noqa: E402
from chipbench.bench import Bench  # noqa: E402

from test_chip_benchmark import PEAKS, _trace  # noqa: E402

HLO = """HloModule jit_neg_ll, is_scheduled=true

%fc.1 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  ROOT %m = f32[8]{0} multiply(%p0, %p0), metadata={op_name="jit(neg_ll)/repro.factorize/while/body/repro.recompress/mul"}
}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %gte = f32[8]{0} get-tuple-element(%p), index=1
  %fusion.1 = f32[8]{0} fusion(%gte), kind=kLoop, calls=%fc.1, metadata={op_name="jit(neg_ll)/repro.factorize/while/body/repro.recompress/mul"}
  %copy.3 = f32[8]{0} copy(%fusion.1)
  ROOT %tuple = (s32[], f32[8]{0}) tuple(%gte, %copy.3)
}

%region.5 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.6 = f32[] add(%a, %b), metadata={op_name="reduce_sum"}
}

ENTRY %main.9 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %gen.0 = f32[8]{0} exponential(%x), metadata={op_name="jit(neg_ll)/repro.compress/repro.gen/exp"}
  %while.2 = (s32[], f32[8]{0}) while(%t), condition=%cond, body=%body, metadata={op_name="jit(neg_ll)/repro.factorize/while"}
  %solve.4 = f32[8]{0} dot(%gte2, %gte2), metadata={op_name="jit(neg_ll)/repro.solve/dot"}
  %red.8 = f32[] reduce(%solve.4, %c), dimensions={0}, to_apply=%region.5, metadata={op_name="jit(neg_ll)/repro.solve/reduce_sum"}
  ROOT %neg.5 = f32[8]{0} negate(%solve.4), metadata={op_name="jit(neg_ll)/neg"}
}
"""


def _op(name, typ="f32[8]{0}", op="fusion"):
    return f"%{name} = {typ} {op}(f32[8]{{0}} %x)"


# ms: a loop (while.2) holding its body's leaves, then GEN, the solve, an
# unscoped op, an op of no compiled program, and a short op of another
# program that shares an instruction name with this one (20 us).
FOREIGN = _op("solve.4", "pred[]", "compare")
OPS = [(_op("while.2", "(s32[], f32[8]{0})", "while"), 0, 15),
       (_op("fusion.1"), 1, 6), (_op("copy.3", op="copy"), 6, 8),
       (_op("fusion.1"), 8, 14), (_op("gen.0", op="exponential"), 20, 25),
       (_op("solve.4", op="dot"), 30, 36), (_op("neg.5", op="negate"), 40, 42),
       (_op("mystery.7", op="add"), 44, 45), (FOREIGN, 46, 46.02)]
LEAF_MS = 27.02
FIT_SPANS = [("window", 0, 60), ("eval", 0, 48)]
SERVE_SPANS = [("window", 0, 60), ("request", 0, 26), ("request", 28, 48)]
PROGRAM_SPANS = [("repro.serve.predict_batch", 0, 26),
                 ("repro.serve.validate", 0, 0.5),
                 ("repro.serve.status", 0.5, 0.8),
                 ("repro.serve.dispatch", 0.8, 25),
                 ("repro.serve.predict_batch", 28, 48),
                 ("repro.serve.dispatch", 15, 20)]
NEW = ("fit_share.gen", "fit_share.compress", "fit_share.factorize",
       "fit_share.recompress", "fit_share.solve", "predict_gen_device_ms",
       "predict_solve_device_ms")


@pytest.fixture
def program(monkeypatch):
    """The cells' program is ``HLO``; no compile."""
    monkeypatch.setattr(scopes, "program_texts", lambda cell: [HLO])
    monkeypatch.setattr(scopes, "_MAPS", {})


def _reading(trace, cell):
    bench = Bench.load()
    c = bench.cell(cell)
    window = (serve_closed.Window([], 1.0) if "serve" in cell else
              fit_eval.Window([], [], [], [0.048], 0.048))
    return bench, runner.Reading(trace, c, (1.0, 1.0), PEAKS, window)


def _read(trace, cell, names):
    bench, r = _reading(trace, cell)
    return {m: bench.metric(m).read(r) for m in names}


def test_instruction_paths_fall_back_to_the_caller():
    paths = scopes.instruction_paths(HLO)
    assert paths["fusion.1"][0].endswith("repro.recompress/mul")
    assert paths["copy.3"][0] == "jit(neg_ll)/repro.factorize/while"
    assert paths["gen.0"] == ("jit(neg_ll)/repro.compress/repro.gen/exp",
                              "f32[8]{0}")
    assert scopes.phase(paths["gen.0"][0]) == "gen"      # the innermost
    assert scopes.phase(paths["fusion.1"][0]) == "factorize"
    assert scopes.phase(paths["neg.5"][0]) is None
    # a reducer's region names its ops from the region, not the module root
    assert paths["add.6"][0] == "jit(neg_ll)/repro.solve/reduce_sum/reduce_sum"
    assert scopes.event_instruction(_op("solve.4", "pred[]")) == (
        "solve.4", "pred[]")


def test_fit_shares_from_recorded_trace(program):
    t = _trace({"XLA Ops": OPS}, FIT_SPANS)
    got = _read(t, "tlr7.fit-eval", NEW[:5])
    # leaves 11 + 2 (loop body) + 5 + 6 + 2 + 1 + 0.02 = 27.02 ms; the
    # loop's own event holds its body and is no leaf
    assert got == pytest.approx({
        "fit_share.gen": 100 * 5 / LEAF_MS, "fit_share.compress": 0.0,
        "fit_share.factorize": 100 * 13 / LEAF_MS,
        "fit_share.recompress": 100 * 11 / LEAF_MS,
        "fit_share.solve": 100 * 6 / LEAF_MS})
    unscoped = 100 * 3.02 / LEAF_MS  # neg, mystery, the other program's op
    assert sum(got[f"fit_share.{p}"] for p in scopes.PHASES) + unscoped \
        == pytest.approx(100.0)


def test_request_device_ms_from_recorded_trace(program):
    t = _trace({"XLA Ops": OPS}, SERVE_SPANS)
    got = _read(t, "tlr7.serve-predict", NEW[5:] + ("predict_device_ms",))
    assert got["predict_gen_device_ms"] == pytest.approx(5 / 2)
    assert got["predict_solve_device_ms"] == pytest.approx(6 / 2)
    assert got["predict_device_ms"] == pytest.approx((20 + 9.02) / 2)


def test_program_spans_leave_existing_metrics_alone(program):
    """Host spans ``repro.*`` in the trace change no accepted metric, no
    breakdown and no new one."""
    bench = Bench.load()
    for cell, spans in (("tlr7.fit-eval", FIT_SPANS),
                        ("exact.fit-eval", FIT_SPANS),
                        ("tlr7.serve-predict", SERVE_SPANS)):
        names = [m["name"] for m in bench.cell(cell).per_layer]
        plain = _trace({"XLA Ops": OPS}, spans)
        spanned = _trace({"XLA Ops": OPS}, spans + PROGRAM_SPANS)
        assert _read(spanned, cell, names) == _read(plain, cell, names)
        w = plain.window()
        assert spanned.top_ops(w.start, w.end) == plain.top_ops(w.start,
                                                                w.end)
        assert spanned.idle_gaps(w.start, w.end) == plain.idle_gaps(
            w.start, w.end)


def test_program_without_scopes_reads_nothing(monkeypatch):
    """The parent's program has no scopes: the new metrics return None."""
    bare = HLO.replace("repro.", "")
    monkeypatch.setattr(scopes, "program_texts", lambda cell: [bare])
    monkeypatch.setattr(scopes, "_MAPS", {})
    t = _trace({"XLA Ops": OPS}, SERVE_SPANS)
    assert set(_read(t, "tlr7.fit-eval", NEW[:5]).values()) == {None}
    assert set(_read(t, "tlr7.serve-predict", NEW[5:]).values()) == {None}


def test_a_failed_compile_fails_the_run(monkeypatch):
    """Only a program without scopes reads nothing: any other failure of
    the reader (a compile, a mix it cannot read) fails the traced run."""
    def fail(cell):
        raise RuntimeError("no compile here")
    monkeypatch.setattr(scopes, "program_texts", fail)
    monkeypatch.setattr(scopes, "_MAPS", {})
    t = _trace({"XLA Ops": OPS}, FIT_SPANS)
    with pytest.raises(RuntimeError, match="no compile here"):
        _read(t, "exact.fit-eval", ("fit_share.gen",))


@pytest.mark.parametrize("cell,names", [("tlr7.fit-eval", NEW[:5]),
                                        ("tlr7.serve-predict", NEW[5:])])
def test_events_of_another_program_put_the_map_in_doubt(program, cell,
                                                        names):
    """An event whose name is in the map with another result type belongs
    to another program.  A short one counts as unscoped (above); where such
    events hold more than ``MISMATCH_SHARE`` of the leaf time the map is in
    doubt and the readers return None."""
    long = [op if op[0] != FOREIGN else (FOREIGN, 46, 47) for op in OPS]
    spans = SERVE_SPANS if "serve" in cell else FIT_SPANS
    assert set(_read(_trace({"XLA Ops": long}, spans), cell,
                     names).values()) == {None}
    assert None not in _read(_trace({"XLA Ops": OPS}, spans), cell,
                             names).values()


def _jitted_program(scope):
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope(scope):
            return jnp.sin(x) * 2.0
    return jax.jit(f)


def test_a_traffic_kind_names_its_programs(monkeypatch):
    """A kind's ``programs(cell)`` gives the programs to map, one per shape
    its window runs; the map holds the instructions of each."""
    import jax
    import jax.numpy as jnp

    f = _jitted_program("repro.solve")
    shapes = [jax.ShapeDtypeStruct((b, 2), jnp.float64) for b in (8, 24)]
    monkeypatch.setattr(serve_closed, "programs", raising=False,
                        value=lambda cell: [(f, (s,), {}) for s in shapes])
    cell = Bench.load().cell("tlr7.serve-predict")
    assert scopes.programs(cell) == [(f, (s,), {}) for s in shapes]
    texts = scopes.program_texts(cell)
    assert len(texts) == 2 and all("f64[24,2]" in t or "f64[8,2]" in t
                                   for t in texts)
    assert {scopes.phase(p) for p, _ in
            scopes.ScopeMap(texts).paths.values()} >= {"solve"}


def test_a_traffic_kind_without_programs_fails(monkeypatch):
    """A kind that names no programs and is not one of the kinds built
    here cannot feed a phase metric: the reader says so."""
    from chipbench.bench import BenchError

    cell = Bench.load().cell("tlr7.serve-predict")
    cell.traffic = dict(cell.traffic, kind="streams")   # a module, no kind
    with pytest.raises(BenchError, match="names no programs"):
        scopes.programs(cell)


def test_scope_paths_of_a_program_compiled_here():
    """Scopes reach the compiled program's text: loop control and the
    loop body's operations get their scope, the innermost phase wins."""
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("repro.gen"):
            y = jnp.sin(x) * 2.0

        def body(c, _):
            with jax.named_scope("repro.solve"):
                return c + jnp.cos(c), None

        with jax.named_scope("repro.factorize"):
            y, _ = jax.lax.scan(body, y, None, length=3)
        return -y.sum()

    text = jax.jit(f).lower(jnp.ones((8, 8))).compile().as_text()
    paths = scopes.instruction_paths(text)
    by_op = {}
    for name, (path, _) in paths.items():
        by_op.setdefault(name.split(".")[0], set()).add(scopes.phase(path))
    assert by_op["while"] == {"factorize"}
    assert "gen" in {scopes.phase(p) for p, _ in paths.values()}
    assert "solve" in {scopes.phase(p) for p, _ in paths.values()}
    smap = scopes.ScopeMap([text])
    assert smap.scoped
    loop = next(n for n in paths if n.startswith("while"))
    assert scopes.phase(smap.path(f"%{loop} = {paths[loop][1]} while()")) \
        == "factorize"
    assert not scopes.ScopeMap([jax.jit(jnp.sin).lower(
        jnp.ones(3)).compile().as_text()]).scoped


def _tlr_program(backend):
    """The smallest ``from_tiles`` TLR likelihood (64 locations, p = 2,
    four 32-tiles a side), single-device or pair-major."""
    import jax
    import numpy as np

    from repro.core import MaternParams
    from repro.core.covariance import morton_order
    from repro.core.dist_tlr import dist_tlr_loglik
    from repro.core.simulate import grid_locations
    from repro.core.tlr import tlr_loglik

    locs = np.asarray(grid_locations(8, jitter=0.2, seed=0))
    locs = locs[morton_order(locs)]
    params = MaternParams.bivariate(a=0.09, nu11=0.6, nu22=1.2, beta=0.4)
    kw = dict(tile_size=32, max_rank=12, nugget=1e-8, gen="xla")
    if backend == "tlr":
        def f(locs, z):
            return tlr_loglik(None, z, params, locs=locs, from_tiles=True,
                              **kw).loglik
    else:
        def f(locs, z):
            return dist_tlr_loglik(z=z, locs=locs, params=params,
                                   from_tiles=True, block_cyclic=True,
                                   **kw).loglik
    return jax.jit(f).lower(locs, np.zeros(128)).compile().as_text()


@pytest.mark.parametrize("backend", ["tlr", "dist_tlr"])
def test_every_tlr_backend_scopes_its_phases(backend):
    """Each TLR backend marks GEN, compress, factorize and solve, and its
    recompress lies inside factorize; with every instruction as a 1-ns
    leaf the four phases and the unscoped rest make the whole."""
    import types

    text = _tlr_program(backend)
    smap = scopes.ScopeMap([text])
    leaves = {f"%{n} = {typ} op()": 1.0 for n, (_, typ) in smap.paths.items()}
    trace = types.SimpleNamespace(devices=[types.SimpleNamespace(
        leaf_time=lambda lo, hi: leaves)])
    split = scopes.leaf_by_scope(trace, 0, 1, smap)
    assert all(split[p] > 0 for p in scopes.PHASES + scopes.NESTED), split
    assert sum(split[p] for p in scopes.PHASES) + split[None] \
        == split["total"] == len(leaves)
    assert split["mismatched"] == 0
    for path, _ in smap.paths.values():
        if "recompress" in scopes.scopes(path):
            assert scopes.phase(path) == "factorize", path


def test_cached_executable_without_scopes_is_compiled_anew(tmp_path):
    """The compile cache keys a program without its metadata, so it serves
    an unscoped build's executable for the scoped program; the reader then
    compiles the program anew and still finds its scopes.  A clean process:
    the cache is set up before the first compile."""
    code = textwrap.dedent(f"""
        import sys
        import jax, jax.numpy as jnp
        jax.config.update("jax_compilation_cache_dir", {str(tmp_path)!r})
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        sys.path.insert(0, {str(ROOT / "benchmarks" / "chip")!r})
        from chipbench import scopes

        def build(scoped):
            def f(x):
                if scoped:
                    with jax.named_scope("repro.gen"):
                        return jnp.sin(x) * 2.0
                return jnp.sin(x) * 2.0
            return jax.jit(f)

        x = jnp.ones((16, 16))
        build(False).lower(x).compile()      # an older build fills the cache
        jax.clear_caches()
        new = build(True)
        assert "repro." not in new.lower(x).compile().as_text()
        assert "repro.gen" in scopes._scoped_text(new, (x,))
        assert scopes._scoped_text(build(False), (x,)) is None
        print("ok")
    """)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0 and "ok" in p.stdout, p.stderr[-3000:]
