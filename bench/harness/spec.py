"""Find everything that belongs to one cell by the names in BENCHMARK.json.

A cell names a configuration and a traffic mix.  The configuration's file
(``configs[].file``) names its model; the model's program binding is
``bench/models/<model>.py`` and its plain reference
``bench/reference/<model>.py``.  The traffic mix is
``bench/traffic/<traffic>.json``, the cell's limits on the numbers that
decide ``correct`` are ``bench/limits/<cell>.json``, and each per-layer
metric is read by ``bench/metrics/<metric>.py``.  A new configuration,
traffic mix, cell or metric is a new file and a new entry, never an edit.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys
from types import ModuleType

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_module(path: pathlib.Path) -> ModuleType:
    """Import a file of the benchmark by path (names may hold '-' and '.')."""
    name = "bench_" + "_".join(path.relative_to(BENCH).with_suffix("").parts)
    name = name.replace("-", "_").replace(".", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict  # the configuration's file, as run
    traffic: dict  # bench/traffic/<traffic>.json
    limits: dict  # bench/limits/<cell>.json: number -> limit
    end_to_end: tuple[dict, ...]  # the end-to-end metrics this cell reports
    per_layer: tuple[dict, ...]  # the per-layer metrics this cell reports

    @property
    def model(self) -> ModuleType:
        return load_module(BENCH / "models" / f"{self.config['model']}.py")

    @property
    def reference(self) -> ModuleType:
        return load_module(BENCH / "reference" / f"{self.config['model']}.py")


def _reports(metric: dict, cell: str, reported: set[str] | None = None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return reported is None or metric["moves"] in reported


def find_cell(name: str, bench: dict | None = None) -> Cell:
    bench = bench if bench is not None else load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    limits = json.loads((BENCH / "limits" / f"{name}.json").read_text())
    e2e = tuple(m for m in bench["end_to_end"] if _reports(m, name))
    reported = {m["name"] for m in e2e}
    layer = tuple(m for m in bench["per_layer"] if _reports(m, name, reported))
    return Cell(name, int(w["chips"]), config, traffic, limits, e2e, layer)


def metric_reader(name: str) -> ModuleType:
    return load_module(BENCH / "metrics" / f"{name}.py")
