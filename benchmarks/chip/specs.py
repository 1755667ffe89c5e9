"""Find a cell's parts by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix, one metric or
one kernel family sits in a file of its own, found by name:

* ``configs/<config>.json``   -- the deployment: scheme, geometry, guarantee;
* ``workloads/<traffic>.json`` -- the traffic mix and the drive failures;
* ``metrics/<metric>.py``      -- a reader: ``read(ctx)`` returns a number
  or ``None`` where it finds nothing to read;
* ``kernels/<family>.py``      -- the bytes a kernel program moves, from its
  argument shapes, and the trace names of its programs.

Adding a cell, a configuration or a metric means adding files here and an
entry in ``BENCHMARK.json``; no code changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from types import ModuleType

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    metrics: list            # [(name, module, entry)] for this cell and mode
    kernels: dict            # family -> module


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_json(kind: str, name: str) -> dict:
    return json.loads((HERE / kind / f"{name}.json").read_text())


def _load_module(path: pathlib.Path, label: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(label, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_metric(name: str) -> ModuleType:
    return _load_module(HERE / "metrics" / f"{name}.py",
                        "chipbench_metric_" + name.replace(".", "_"))


def load_kernels() -> dict:
    return {
        p.stem: _load_module(p, "chipbench_kernels_" + p.stem)
        for p in sorted((HERE / "kernels").glob("*.py"))
    }


def metrics_for(bench: dict, cell_name: str, trace: bool) -> list:
    """The metrics a cell reports: its end-to-end metrics untraced, its
    per-layer metrics traced; a metric without ``workloads`` is every
    cell's."""
    entries = bench["per_layer"] if trace else bench["end_to_end"]
    return [
        (e["name"], load_metric(e["name"]), e)
        for e in entries
        if cell_name in e.get("workloads", [cell_name])
    ]


def load_cell(name: str, trace: bool, bench: dict | None = None) -> Cell:
    bench = bench if bench is not None else load_benchmark()
    for w in bench["workloads"]:
        if w["name"] == name:
            return Cell(
                name=name,
                chips=int(w["chips"]),
                config=load_json("configs", w["config"]),
                traffic=load_json("workloads", w["traffic"]),
                metrics=metrics_for(bench, name, trace),
                kernels=load_kernels(),
            )
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")
