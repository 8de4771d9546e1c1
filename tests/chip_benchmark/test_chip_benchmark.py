"""The chip benchmark's yardstick, on the CPU (``benchmarks/chip``).

Times, idle shares and roofline shares come only from a chip; what is
checked here is the arithmetic and the control flow: the reduction of a
trace with known busy intervals, the work models' counts at small shapes,
finding every piece by name, refusing what is not a TPU, each traffic kind
driven end to end at a tiny size with the device check left out, and the
comparison with the reference failing on each fault a cell can have and on
the float32 control.
"""
import dataclasses
import json
import math
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
CHIP = ROOT / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))

from chipbench import control, fit_eval, runner, serve_closed  # noqa: E402
from chipbench import trace as tracing  # noqa: E402
from chipbench.bench import Bench, BenchError  # noqa: E402

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
TINY = {"tlr7.fit-eval": dict(grid=[8, 8], tile_size=32, max_rank=32),
        "exact.fit-eval": dict(grid=[8, 4]),
        "tlr7.serve-predict": dict(grid=[8, 8], tile_size=32, max_rank=32)}
SEED = 2**31 + 12345        # seeds run past 32 signed bits


def tiny(name):
    cell = Bench.load().cell(name)
    traffic = dict(cell.traffic)
    if "batch" in traffic:
        traffic["batch"] = 8
    return dataclasses.replace(cell, config=dict(cell.config, **TINY[name]),
                               traffic=traffic,
                               traced={"start_s": 0.1, "seconds": 0.2})


def run_cell(cell, seconds=0.5, trace=False, kind=None):
    import jax

    return runner.execute(Bench.load(), cell, jax.devices(), PEAKS,
                          seed=SEED, seconds=seconds, trace=trace,
                          t0=time.perf_counter(), kind=kind)


# -- trace reduction --------------------------------------------------------

def _plane(pid, name, lines):
    """One XPlane in text proto: ``lines`` {line: [(event, start_ms,
    end_ms)]}."""
    names = sorted({e[0] for evs in lines.values() for e in evs})
    body = ""
    for lid, (line, evs) in enumerate(lines.items()):
        body += f'lines {{ id: {lid + 1} name: "{line}" timestamp_ns: 0 '
        body += "".join(
            f"events {{ metadata_id: {names.index(n) + 1} "
            f"offset_ps: {int(a * 1e9)} duration_ps: {int((b - a) * 1e9)} }}"
            for n, a, b in evs) + " } "
    meta = "".join(f'event_metadata {{ key: {i + 1} value {{ id: {i + 1} '
                   f'name: "{n}" }} }}' for i, n in enumerate(names))
    return f'planes {{ id: {pid} name: "{name}" {body} {meta} }}'


def _trace(device_lines, spans):
    from jax.profiler import ProfileData

    return tracing.reduce(ProfileData.from_text_proto(
        _plane(1, "/device:TPU:0", device_lines)
        + _plane(2, "/host:CPU", {"python3": spans})))


OPS = [("while.2", 0, 15), ("fusion.1", 1, 6), ("fusion.1", 8, 14),
       ("fusion.1", 30, 36), ("copy.3", 52, 58)]   # a loop with its body
SPANS = [("window", 0, 60), ("eval", 0, 20), ("eval", 28, 48),
         ("eval", 50, 60)]


@pytest.fixture
def synthetic():
    return _trace({"XLA Ops": OPS}, SPANS)


def test_trace_busy_is_the_union_of_device_ops(synthetic):
    w = synthetic.window()
    assert (w.start, w.end) == (0, 60e6)
    assert synthetic.busy_ns(w.start, w.end) == 27e6      # 15 + 6 + 6
    assert synthetic.busy_ns(8e6, 31e6) == 8e6            # clipped: 7 + 1
    assert synthetic.op_count(0, 20e6) == 3


def _read(trace, names, work=(197e12 * 1e-3, 1.0)):  # 1 ms at the peak
    bench = Bench.load()
    window = fit_eval.Window([], [], [], [0.010, 0.020], 0.0)
    r = runner.Reading(trace, bench.cell("tlr7.fit-eval"), work, PEAKS,
                       window)
    return {m: bench.metric(m).read(r) for m in names}


def test_trace_metrics_from_known_intervals(synthetic):
    got = _read(synthetic, ("device_idle_share.fit", "device_ops_per_ms",
                            "eval_roofline_share", "predict_device_ms",
                            "predict_host_ms"))
    assert got["device_idle_share.fit"] == pytest.approx(100 * (1 - 27 / 60))
    assert got["device_ops_per_ms"] == pytest.approx(5 / 27)
    # busy time of the whole evaluations: 15, 6 and 6 ms
    assert got["eval_roofline_share"] == pytest.approx(100 * 1 / 9)
    assert got["predict_device_ms"] is None       # no request spans
    assert got["predict_host_ms"] is None


def test_roofline_needs_a_whole_evaluation():
    """A slice inside one evaluation holds no whole one to divide by."""
    t = _trace({"XLA Ops": OPS}, [("window", 10, 40), ("eval", 0, 60)])
    got = _read(t, ("eval_roofline_share", "device_idle_share.fit"))
    assert got["eval_roofline_share"] is None
    assert got["device_idle_share.fit"] == pytest.approx(100 * (1 - 11 / 30))


def test_trace_metrics_stop_where_the_device_buffer_filled():
    t = _trace({"XLA Ops": OPS, "XLA TraceMe": [(tracing.DROPPED, 40, 60)]},
               SPANS + [("request", 29, 39), ("request", 39, 45)])
    w = t.window()
    assert (w.start, w.end) == (0, 40e6)
    assert [s.start for s in t.covered("eval")] == [0]
    got = _read(t, ("device_idle_share.fit", "eval_roofline_share",
                    "predict_device_ms", "predict_host_ms"))
    assert got["device_idle_share.fit"] == pytest.approx(100 * (1 - 21 / 40))
    assert got["eval_roofline_share"] == pytest.approx(100 * 1 / 15)
    assert got["predict_device_ms"] == pytest.approx(6)    # [30, 36]
    assert got["predict_host_ms"] == pytest.approx(4)


def test_trace_breakdown(synthetic):
    top = synthetic.top_ops(0, 60e6)    # leaves: the loop holds its body
    assert top == [["fusion.1", pytest.approx(0.017)],
                   ["copy.3", pytest.approx(0.006)]]
    gaps = synthetic.idle_gaps(0, 60e6)       # [15, 30], [36, 52], [58, 60]
    assert [g[0] for g in gaps] == ["eval", "window", "eval"]
    assert [g[1] for g in gaps] == pytest.approx([0.016, 0.015, 0.002])


def test_reader_finds_nothing_without_a_device_plane():
    from jax.profiler import ProfileData

    t = tracing.reduce(ProfileData.from_text_proto(
        'planes { id: 2 name: "/host:CPU" }'))
    r = runner.Reading(t, Bench.load().cell("tlr7.fit-eval"), (1.0, 1.0),
                       PEAKS, fit_eval.Window([], [], [], [1.0], 1.0))
    for m in ("device_idle_share.fit", "eval_roofline_share",
              "device_ops_per_ms"):
        assert Bench.load().metric(m).read(r) is None


# -- work models ------------------------------------------------------------

def test_exact_work_counts():
    ops, nbytes = Bench.load().work("exact").work(dict(p=2, grid=[2, 1]))
    assert ops == pytest.approx(64 / 3 + 16 + 8)      # m = 4
    assert nbytes == 4 * 8 * 10


def _tlr_tasks(t, nb, k):
    """The TLR Cholesky task by task (right-looking, tile (i, j), i >= j)."""
    qr = lambda n, c: 2 * n * c * c - 2 * c ** 3 / 3  # noqa: E731
    ops = 0.0
    for i in range(t):
        for j in range(i):
            ops += 4 * nb * nb * k                    # compress tile (i, j)
    for j in range(t):
        ops += nb ** 3 / 3                            # POTRF
        for i in range(j + 1, t):
            ops += nb * nb * k                        # TRSM
            ops += 4 * nb * k * k + 2 * nb * nb * k   # update of (i, i)
            for m_ in range(j + 1, i):                # update of (i, m_)
                ops += 4 * nb * k * k + 2 * qr(nb, 2 * k) \
                    + 22 * (2 * k) ** 3 + 4 * nb * 2 * k * k
    ops += t * nb * nb + t * (t - 1) // 2 * 4 * nb * k + 2 * t * nb
    return ops


@pytest.mark.parametrize("grid,nb,k", [([4, 4], 8, 2), ([8, 8], 32, 4),
                                       ([64, 64], 2048, 256)])
def test_tlr_work_counts(grid, nb, k):
    ops, nbytes = Bench.load().work("tlr").work(
        dict(p=2, grid=grid, tile_size=nb, max_rank=k))
    t = 2 * grid[0] * grid[1] // nb
    assert ops == pytest.approx(_tlr_tasks(t, nb, k))
    assert nbytes == 32 * (t * nb * (nb + 1) // 2 + t * (t - 1) * nb * k)


# -- finding pieces by name -------------------------------------------------

def test_every_cell_finds_its_pieces():
    bench = Bench.load()
    for w in bench.spec["workloads"]:
        cell = bench.cell(w["name"])
        kind = bench.kind(cell.traffic)
        for fn in ("setup", "window", "release", "check", "end_to_end",
                   "attempted", "failed"):
            assert callable(getattr(kind, fn))
        assert cell.end_to_end and cell.per_layer
        assert set(cell.limits) == {
            "fit_eval": {"loglik_gap", "failed_evals"},
            "serve_closed": {"mean_gap", "var_gap", "failed_requests"},
        }[cell.traffic["kind"]]
        assert "setup_s" in [m["name"] for m in cell.end_to_end]
        assert set(cell.traced) == {"start_s", "seconds"}
        for m in cell.per_layer:
            assert callable(bench.metric(m["name"]).read)
        ops, nbytes = bench.work(cell.config["backend"]).work(cell.config)
        assert ops > 0 and nbytes > 0
    assert bench.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_a_new_cell_needs_only_new_files(tmp_path):
    """A mix, a metric and limits added as files are found by name."""
    here = tmp_path / "chip"
    shutil.copytree(CHIP, here, ignore=shutil.ignore_patterns("__pycache__"))
    (here / "traffic" / "serve-big.json").write_text(json.dumps(
        {"kind": "serve_closed", "batch": 512, "check_requests": 4}))
    (here / "limits" / "tlr7.serve-big.json").write_text(json.dumps(
        {"mean_gap": 1.0, "var_gap": 1.0, "failed_requests": 0}))
    (here / "traced" / "tlr7.serve-big.json").write_text(json.dumps(
        {"start_s": 1, "seconds": 2}))
    (here / "metrics" / "requests_seen.py").write_text(
        "def read(r):\n    return len(r.trace.named('request')) or None\n")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "tlr7.serve-big", "chips": 1,
                              "config": "biv-matern-tlr7-n4096",
                              "traffic": "serve-big", "why": "test"})
    spec["per_layer"].append({"name": "requests_seen", "unit": "requests",
                              "workloads": ["tlr7.serve-big"]})
    bench = Bench(spec, ROOT, here)
    cell = bench.cell("tlr7.serve-big")
    assert cell.traffic["batch"] == 512
    assert cell.traced == {"start_s": 1, "seconds": 2}
    assert [m["name"] for m in cell.per_layer] == ["requests_seen"]
    assert bench.metric("requests_seen").read is not None
    with pytest.raises(BenchError):
        bench.cell("tlr7.serve-huge")
    with pytest.raises(BenchError):
        bench.metric("no_such_metric")


def test_unknown_device_kind_is_refused(monkeypatch):
    import jax
    import run

    fake = type("D", (), {"platform": "tpu", "device_kind": "TPU v99"})()
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    with pytest.raises(BenchError, match="TPU v99"):
        run.devices_for(Bench.load(), 1)
    fake.device_kind = "TPU v5 lite"
    devices, peaks = run.devices_for(Bench.load(), 1)
    assert devices == [fake] and peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(BenchError, match="needs 4"):
        run.devices_for(Bench.load(), 4)


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "exact.fit-eval", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cli_refuses_the_cpu():
    p = _run_cli(ROOT)
    assert p.returncode == 2, p.stderr
    assert "not a TPU" in p.stderr and '"correct"' not in p.stdout


def test_cli_fails_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for path in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(tmp_path)
    assert p.returncode != 0 and '"correct"' not in p.stdout


# -- each traffic kind end to end, tiny, device check left out --------------

@pytest.mark.parametrize("name", list(TINY))
def test_cell_runs_end_to_end(name):
    cell = tiny(name)
    res = run_cell(cell)
    assert res["correct"], res["check"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "check"
    json.loads(json.dumps(res))
    traced = run_cell(cell, trace=True)      # no device plane on the CPU
    assert traced["correct"] and traced["metrics"] == {}


def test_fit_window_starts_no_evaluation_after_its_seconds():
    cell = tiny("exact.fit-eval")
    state = fit_eval.setup(cell, SEED)
    win = fit_eval.window(state, 0.3, runner.Probe())
    assert win.seconds >= 0.3
    assert win.point == [k % 7 for k in range(len(win.point))]
    assert fit_eval.end_to_end(win)["eval_s"] == pytest.approx(
        win.seconds / len(win.point))


def test_trace_holds_a_prefix_of_the_window(tmp_path):
    """The profiler stops at the first tick past the slice's end; the
    ``window`` span closes with it, and the window runs on."""
    probe = runner.Probe(str(tmp_path), seconds=0.05)
    probe.start()
    with probe.span("request"):
        pass
    probe.tick()
    assert probe.active
    time.sleep(0.06)
    with probe.span("request"):
        pass
    probe.tick()
    assert not probe.active
    with probe.span("request"):
        pass
    t = tracing.read_dir(str(tmp_path))
    assert [s.name for s in t.spans] == ["window", "request", "request"]
    assert t.spans[0].end >= t.spans[2].end


def test_trace_stops_inside_a_long_evaluation(tmp_path):
    """``wait`` ends the traced prefix while the device still computes."""
    import jax.numpy as jnp

    probe = runner.Probe(str(tmp_path), seconds=0.0)
    probe.start()
    probe.wait(jnp.ones(3))
    assert not probe.active
    untraced = runner.Probe()
    untraced.wait(jnp.ones(3))                # returns at once
    assert not untraced.active


class _Slow:
    """A device value that is ready ``seconds`` after it was made."""

    def __init__(self, seconds):
        self.ready_at = time.perf_counter() + seconds

    def is_ready(self):
        return time.perf_counter() >= self.ready_at


def test_trace_slice_inside_a_long_evaluation(tmp_path):
    """A slice that starts later: ``wait`` starts and stops the profiler
    while one evaluation runs, and no span of that evaluation is kept."""
    probe = runner.Probe(str(tmp_path), start_s=0.05, seconds=0.05)
    probe.start()
    assert not probe.active
    with probe.span("eval"):
        value = _Slow(0.3)
        probe.wait(value)
        assert probe.done and not probe.active
        assert not value.is_ready()
    t = tracing.read_dir(str(tmp_path))
    assert [s.name for s in t.spans] == ["window"]
    assert t.spans[0].ns == pytest.approx(0.05e9, abs=0.03e9)


def test_trace_slice_starts_at_a_tick(tmp_path):
    """Short evaluations: the slice starts at the first tick past
    ``start_s``, so it holds whole evaluations."""
    probe = runner.Probe(str(tmp_path), start_s=0.03, seconds=0.05)
    probe.start()
    for _ in range(8):
        with probe.span("eval"):
            time.sleep(0.02)
        probe.tick()
    assert probe.done
    t = tracing.read_dir(str(tmp_path))
    w = t.named("window")[0]
    evals = t.named("eval")
    assert evals and all(w.start <= s.start and s.end <= w.end
                         for s in evals)


def test_same_seed_same_inputs():
    cell = tiny("exact.fit-eval")
    a, b = fit_eval.setup(cell, SEED), fit_eval.setup(cell, SEED)
    c = fit_eval.setup(cell, SEED + 1)
    assert np.array_equal(a.z, b.z) and np.array_equal(a.points, b.points)
    assert not np.array_equal(a.z, c.z)


# -- the comparison fails on each fault and on the control ------------------

@dataclasses.dataclass
class _Kind:
    """A traffic kind with one of its functions replaced."""
    base: object
    setup: object = None

    def __getattr__(self, name):
        return getattr(self.base, name)


def _answer_altered(st, jf):
    def f(x, locs, z):
        val, aux = jf(x, locs, z)
        return val * (1 + 1e-3), aux
    return f


def _half_the_data(st, jf):
    def f(x, locs, z):
        h = locs.shape[0] // 2
        val, aux = jf(x, locs[:h], z[:h * st.p])
        return 2 * val, aux
    return f


@pytest.mark.parametrize("name", ["tlr7.fit-eval", "exact.fit-eval"])
@pytest.mark.parametrize("fault", [_answer_altered, _half_the_data])
def test_fit_fault_is_not_correct(name, fault):
    def setup(cell, seed):
        st = fit_eval.setup(cell, seed)
        st.objective = fault(st, fit_eval.objective(cell.config))
        return st

    res = run_cell(tiny(name), kind=_Kind(fit_eval, setup))
    assert not res["correct"], res["check"]
    assert res["check"]["loglik_gap"]["value"] > \
        res["check"]["loglik_gap"]["limit"]


def _mean_altered(out):
    return out._replace(mean=out.mean.at[0, 0].add(1e-3))


def _variance_altered(out):
    return out._replace(variance=out.variance.at[-1, -1].add(1e-3))


def _half_the_batch(out):
    h = out.mean.shape[0] // 2
    return out._replace(**{f: getattr(out, f).at[h:2 * h].set(
        getattr(out, f)[:h]) for f in ("mean", "variance", "lower",
                                       "upper")})


@pytest.mark.parametrize("fault", [_mean_altered, _variance_altered,
                                   _half_the_batch])
def test_serve_fault_is_not_correct(fault, monkeypatch):
    from repro.serving import cokrige_service

    real = cokrige_service.predict_batch
    monkeypatch.setattr(cokrige_service, "predict_batch",
                        lambda *a, **k: fault(real(*a, **k)))
    res = run_cell(tiny("tlr7.serve-predict"))
    assert not res["correct"], res["check"]


@pytest.mark.parametrize("name", list(TINY))
def test_float32_control_reads_above_the_program(name):
    """The control (``control.py`` on the chip, at the cell's size) here at
    a tiny size, both sides through ``runner.execute``: the float32
    reference in the program's place reads a gap well above the program's
    float64 one, and with each limit between the two readings the
    control's run is not correct where the program's is."""
    cell = tiny(name)
    recorded = control.Recorder(Bench.load().kind(cell.traffic))
    program = run_cell(cell, seconds=0.3, kind=recorded)
    f32 = run_cell(cell, seconds=0.3, kind=control.Float32(recorded))
    p = {k: c["value"] for k, c in program["check"].items()}
    c = {k: v["value"] for k, v in f32["check"].items()}
    for key in p:
        if key.endswith("_gap"):
            assert c[key] > 3 * p[key], (key, c, p)
        else:
            assert c[key] == p[key] == 0
    between = dict(cell.limits, **{k: math.sqrt(p[k] * c[k])
                                   for k in p if k.endswith("_gap")})
    cell = dataclasses.replace(cell, limits=between)
    assert run_cell(cell, seconds=0.3, kind=recorded)["correct"]
    assert not run_cell(cell, seconds=0.3,
                        kind=control.Float32(recorded))["correct"]


def test_numbers_json_has_no_infinity():
    assert runner.number(math.inf) is None
    assert not runner.within(None, 1.0)
    assert serve_closed._gap(np.array([np.nan]), 0.0) == math.inf
