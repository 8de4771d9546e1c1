#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload tlr7.fit-eval --seed 7 \\
        --seconds 10 --trace 0

Run it from the root of a checkout, on a machine whose JAX sees at least
the chips the cell asks for.  The cell's configuration, traffic mix, limits
and metrics are found by the names in ``BENCHMARK.json``
(``chipbench/bench.py``).  With ``--trace 0`` the result carries the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics from a
profiler trace of the window.  The last line of standard output is the
result as JSON; the numbers compared with the reference, each beside its
limit, are the last lines of standard error.

There is no CPU fallback: anything but a TPU, fewer chips than the cell
asks for, or a device kind missing from ``peaks.json`` exits with code 2
and prints no result.  The persistent compilation cache is where
``JAX_COMPILATION_CACHE_DIR`` says, else ``.jax_cache`` in the checkout.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent.parent
sys.path.insert(0, str(HERE))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def devices_for(bench, chips: int):
    """The first ``chips`` TPU devices and their peaks, or BenchError."""
    import jax
    from chipbench.bench import BenchError

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"platform {devices[0].platform!r} is not a TPU")
    if len(devices) < chips:
        raise BenchError(f"{len(devices)} device(s); the cell needs {chips}")
    return devices[:chips], bench.peaks(devices[0].device_kind)


def configure_jax():
    import jax

    jax.config.update("jax_enable_x64", True)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def main(argv=None) -> int:
    args = parse(argv)
    from chipbench.bench import Bench, BenchError

    try:
        bench = Bench.load()
        cell = bench.cell(args.workload)
        configure_jax()
        devices, peaks = devices_for(bench, cell.chips)
    except BenchError as e:
        print(f"chip benchmark: {e}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from chipbench import runner

    result = runner.execute(bench, cell, devices, peaks, seed=args.seed,
                            seconds=args.seconds, trace=bool(args.trace),
                            t0=T0)
    runner.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
