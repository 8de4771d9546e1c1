"""Find a cell's pieces by the names in ``BENCHMARK.json``.

Each piece sits in a file of its own under the benchmark's directory, so a
later change adds a cell, a mix or a metric by adding files:

* configuration ``<name>``: the file ``BENCHMARK.json`` gives it;
* traffic mix ``<mix>``: ``traffic/<mix>.json``, whose ``kind`` names the
  general generator that reads it (``chipbench/<kind>.py``);
* per-layer metric ``<metric>``: ``metrics/<metric>.py``, a ``read(r)``
  that returns a number, or None where the trace has nothing to read;
* work model of a backend: ``work/<backend>.py``, ``work(config)``;
* limits of the correctness comparison of cell ``<cell>``:
  ``limits/<cell>.json``;
* the slice of the window that a traced run of cell ``<cell>`` traces:
  ``traced/<cell>.json``, ``start_s`` and ``seconds`` into the window;
* peaks of each device kind: ``peaks.json``.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent.parent      # benchmarks/chip
ROOT = HERE.parent.parent                          # the checkout


class BenchError(RuntimeError):
    """The benchmark cannot run this cell here: no such piece, no chip."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    traced: dict              # start_s, seconds: the traced slice
    end_to_end: list          # metric entries of BENCHMARK.json
    per_layer: list


def _load_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise BenchError(f"no file {path}") from None


def load_module(path: Path, name: str):
    if not path.is_file():
        raise BenchError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bench:
    def __init__(self, spec: dict, root: Path = ROOT, here: Path = HERE):
        self.spec, self.root, self.here = spec, root, here

    @classmethod
    def load(cls, root: Path = ROOT, here: Path = HERE) -> "Bench":
        return cls(_load_json(root / "BENCHMARK.json"), root, here)

    def _for(self, metrics: list, cell: str) -> list:
        return [m for m in metrics if cell in m.get("workloads", [cell])]

    def cell(self, name: str) -> Cell:
        cells = {w["name"]: w for w in self.spec["workloads"]}
        if name not in cells:
            raise BenchError(f"no workload {name!r} in BENCHMARK.json")
        w = cells[name]
        configs = {c["name"]: c for c in self.spec["configs"]}
        config = _load_json(self.root / configs[w["config"]]["file"])
        return Cell(name=name, chips=int(w["chips"]), config=config,
                    traffic=self.traffic(w["traffic"]),
                    limits=_load_json(self.here / "limits" / f"{name}.json"),
                    traced=_load_json(self.here / "traced" / f"{name}.json"),
                    end_to_end=self._for(self.spec["end_to_end"], name),
                    per_layer=self._for(self.spec["per_layer"], name))

    def traffic(self, mix: str) -> dict:
        return _load_json(self.here / "traffic" / f"{mix}.json")

    def kind(self, traffic: dict):
        """The general generator that reads a traffic mix of this kind."""
        kind = traffic["kind"]
        if not (self.here / "chipbench" / f"{kind}.py").is_file():
            raise BenchError(f"no generator chipbench/{kind}.py")
        return importlib.import_module(f"chipbench.{kind}")

    def metric(self, name: str):
        return load_module(self.here / "metrics" / f"{name}.py",
                           f"chipbench_metric_{name.replace('.', '_')}")

    def work(self, backend: str):
        return load_module(self.here / "work" / f"{backend}.py",
                           f"chipbench_work_{backend}")

    def peaks(self, device_kind: str) -> dict:
        table = _load_json(self.here / "peaks.json")["devices"]
        if device_kind not in table:
            raise BenchError(f"device kind {device_kind!r} is not in "
                             "peaks.json")
        return table[device_kind]
