#!/usr/bin/env python3
"""Read the correctness comparison for the program and for its control,
seed by seed, in one process on the chip.

    python3 benchmarks/chip/control.py --workload tlr7.fit-eval \\
        --seeds 11,12,13 --seconds 40 [--trace-seeds 11]

For each seed, two runs through ``runner.execute``: the cell as
``run.py`` runs it (with ``--trace 1`` for the seeds in
``--trace-seeds``), and then its control (``chipbench/control.py``), the
float32 reference in the program's place, answering the same requests.
Each prints one JSON line: the seed, the side, and the run's result with
its ``correct`` and the numbers compared beside their limits.  The
program's numbers are the lower readings of the limits, the control's the
upper ones (PERF.md).  The benchmark's own runs never run this.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run  # noqa: E402  (sits beside this file; sets up sys.path)


def seeds(text: str) -> list:
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace-seeds", default="")
    args = ap.parse_args(argv)
    from chipbench.bench import Bench, BenchError

    try:
        bench = Bench.load()
        cell = bench.cell(args.workload)
        run.configure_jax()
        devices, peaks = run.devices_for(bench, cell.chips)
    except BenchError as e:
        print(f"chip benchmark: {e}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(run.ROOT / "src"))
    from chipbench import control, runner

    traced = set(seeds(args.trace_seeds))
    for seed in seeds(args.seeds):
        recorded = control.Recorder(bench.kind(cell.traffic))
        sides = [("program", recorded, seed in traced),
                 ("reference_f32", control.Float32(recorded), False)]
        for side, kind, trace in sides:
            t = time.perf_counter()
            result = runner.execute(bench, cell, devices, peaks, seed=seed,
                                    seconds=args.seconds, trace=trace,
                                    t0=t, kind=kind)
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "side": side, "trace": int(trace),
                              "seconds": time.perf_counter() - t,
                              "result": result}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
