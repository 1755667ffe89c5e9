"""Find a cell's parts by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix, one metric or
one kernel family sits in a file of its own, found by name:

* ``configs/<config>.json``   -- the deployment.  Every key that names a
  ``ZapRaidConfig`` field goes to the array as it stands (``logical_blocks``
  excepted: the traffic's volume sets it), the drive keys
  (:data:`ZNS_KEYS`) go to ``ZnsConfig``, the descriptive keys
  (:data:`DESCRIPTIVE_KEYS`) nowhere; any other key is refused, so that a
  misspelt key cannot fall back to the program's default unseen;
* ``workloads/<traffic>.json`` -- the traffic mix, the drive failures, and
  the runner that drives it (``runner``; ``block`` where it names none);
* ``runners/<runner>.py``      -- one kind of run: its warm-up, its measured
  window and its checks (see ``harness.py`` for what a runner provides);
* ``metrics/<metric>.py``      -- a reader: ``read(ctx)`` returns a number
  or ``None`` where it finds nothing to read;
* ``kernels/<family>.py``      -- the bytes a kernel program moves, from its
  argument shapes, and the trace names of its programs.

Adding a cell, a configuration, a kind of run or a metric means adding
files here and an entry in ``BENCHMARK.json``; no code changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from types import ModuleType

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
DESCRIPTIVE_KEYS = ("name", "source", "published", "reduced", "assumed",
                    "guarantee", "zones_per_drive")
ZNS_KEYS = ("zone_cap_blocks", "block_bytes", "max_open_zones")
DEFAULT_RUNNER = "block"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    metrics: list            # [(name, module, entry)] for this cell and mode
    kernels: dict            # family -> module
    runner: ModuleType       # runners/<traffic's runner>.py


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


def config_parts(config: dict) -> tuple[dict, dict]:
    """``(ZapRaidConfig keys, ZnsConfig keys)`` of a configuration file;
    ``ValueError`` on a key that is neither of them nor descriptive."""
    from repro.core.array import ZapRaidConfig
    array_keys = {f.name for f in dataclasses.fields(ZapRaidConfig)}
    array_keys.discard("logical_blocks")
    unknown = sorted(set(config) - array_keys - set(ZNS_KEYS)
                     - set(DESCRIPTIVE_KEYS))
    if unknown:
        raise ValueError(f"configuration {config.get('name')!r}: keys {unknown} "
                         "are neither ZapRaidConfig fields, drive keys "
                         f"{ZNS_KEYS} nor descriptive {DESCRIPTIVE_KEYS}")
    return ({k: v for k, v in config.items() if k in array_keys},
            {k: v for k, v in config.items() if k in ZNS_KEYS})


def load_runner(name: str) -> ModuleType:
    return _load_module(HERE / "runners" / f"{name}.py",
                        "chipbench_runner_" + name.replace(".", "_"))


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
            config = load_json("configs", w["config"])
            config_parts(config)   # an unknown key fails here, before a build
            traffic = load_json("workloads", w["traffic"])
            return Cell(
                name=name,
                chips=int(w["chips"]),
                config=config,
                traffic=traffic,
                metrics=metrics_for(bench, name, trace),
                kernels=load_kernels(),
                runner=load_runner(traffic.get("runner", DEFAULT_RUNNER)),
            )
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")
