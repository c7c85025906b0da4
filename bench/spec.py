"""Finding a cell's pieces by name: ``BENCHMARK.json`` names a workload,
its configuration and its traffic mix, and each lives in a file of its
own under ``bench/``.  Nothing here knows any particular cell, family,
query kind, system or metric: each is a file found by its name, so a
later cell is new files and new entries, never an edit.

- ``BENCHMARK.json`` ``configs[].file``: the configuration (a deployment:
  graph family, its parameters, the population of graphs served).
- ``bench/families/<family>.py``: the configuration's graph generator,
  ``generate(seed=, **params) -> (n, canonical edges)``.
- ``bench/traffic/<traffic>.json``: the traffic mix, data read by the one
  generator in ``bench/loadgen.py``.
- ``bench/workloads/<workload>.py``: a query kind the mix names: how the
  program is asked, how its answer is read, the plain reference's answer
  and the number of mismatches (see ``bench/workloads/ktruss.py``).
- ``bench/systems/<system>.py``: the system under test that the mix
  drives (``"system"``, default ``session``), a class ``System``.
- ``bench/end_to_end/<metric>.py`` and ``bench/layer_metrics/<metric>.py``:
  one reader per metric, a ``read(run)`` function over a finished run
  (``bench/record.py``).
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

__all__ = ["Cell", "load_cell", "load_module", "load_reader", "BenchmarkError"]


class BenchmarkError(RuntimeError):
    """The benchmark's own files do not describe the cell asked for."""


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    root: str
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]  # the metrics this cell reports with --trace 0
    per_layer: list[dict]  # ... and with --trace 1


def _read_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise BenchmarkError(f"missing benchmark file: {path}") from e


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: str, name: str) -> Cell:
    """The workload ``name`` of ``<root>/BENCHMARK.json``."""
    spec = _read_json(os.path.join(root, "BENCHMARK.json"))
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise BenchmarkError(f"no workload {name!r}; have {sorted(work)}")
    w = work[name]
    configs = {c["name"]: c for c in spec["configs"]}
    if w["config"] not in configs:
        raise BenchmarkError(f"workload {name!r} names unknown config {w['config']!r}")
    config = _read_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _read_json(os.path.join(root, "bench", "traffic", w["traffic"] + ".json"))
    e2e = [m for m in spec["end_to_end"] if _applies(m, name)]
    layer = [m for m in spec["per_layer"] if _applies(m, name)]
    return Cell(
        root=root,
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        end_to_end=e2e,
        per_layer=layer,
    )


_MODULES: dict = {}


def load_module(root: str, folder: str, name: str):
    """The module ``bench/<folder>/<name>.py`` of the checkout at ``root``
    (loaded once per process)."""
    path = os.path.join(root, "bench", folder, name + ".py")
    if path not in _MODULES:
        if not os.path.exists(path):
            raise BenchmarkError(f"no {folder} file for {name!r}: {path}")
        module_name = "bench_" + folder + "_" + name.replace(".", "_").replace("-", "_")
        loader = importlib.util.spec_from_file_location(module_name, path)
        module = importlib.util.module_from_spec(loader)
        loader.loader.exec_module(module)
        _MODULES[path] = module
    return _MODULES[path]


def load_reader(root: str, metric: str, folder: str = "layer_metrics"):
    """The ``read(run)`` function of ``bench/<folder>/<metric>.py``."""
    return load_module(root, folder, metric).read
