"""Rehearsals of the benchmark on the CPU, at tiny sizes.

Run from the root of the repository (the repo's own ``pytest`` collects
only ``tests/``)::

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests

``tiny_root`` is a copy of the benchmark with tiny cells added the way a
later change would add one: new configuration files, a new traffic mix
and new entries in ``BENCHMARK.json``, reusing the real traffic mixes
and metric readers.  ``tiny-serve-4chip`` is the serving mix on a slot
mesh of four devices (``Session(mesh=slot_mesh(4), max_batch=32)``).
"""

import json
import os
import shutil
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir, os.pardir))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
SRC = os.path.join(ROOT, "src")
if SRC not in sys.path:
    sys.path.insert(1, SRC)

# Tiny stand-ins, one per real cell: (name, config, traffic, chips, stands in for).
TINY_CELLS = [
    ("tiny-dec", "tiny-kron", "closed-decompose", 1, "kron8-decompose"),
    ("tiny-k3", "tiny-kron", "closed-ktruss3", 1, "kron8-ktruss3"),
    ("tiny-serve", "tiny-kron", "open-ktruss3-b8", 1, "kron7-serve"),
    ("tiny-serve-4chip", "tiny-kron", "tiny-open-ktruss3-b32", 4, "kron7-serve"),
]
TINY_CONFIGS = {
    "tiny-kron": {
        "name": "tiny-kron", "family": "kronecker",
        "params": {"scale": 5, "edge_factor": 8},
        "population": {"size": 3, "first_seed": 0},
    },
}


def add_cells(root: str) -> None:
    """Add the tiny cells to the benchmark at ``root`` from new files."""
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    for name, config in TINY_CONFIGS.items():
        file = f"bench/configs/{name}.json"
        with open(os.path.join(root, file), "w") as f:
            json.dump(config, f)
        spec["configs"].append(
            {"name": name, "source": "test", "file": file, "reduced": [], "why": "rehearsal"}
        )
    with open(os.path.join(root, "bench", "traffic", "open-ktruss3-b8.json")) as f:
        mix = json.load(f)
    mix["session"] = {"max_batch": 32}
    with open(os.path.join(root, "bench", "traffic", "tiny-open-ktruss3-b32.json"), "w") as f:
        json.dump(mix, f)
    for name, config, traffic, chips, real in TINY_CELLS:
        spec["workloads"].append(
            {"name": name, "config": config, "traffic": traffic, "chips": chips, "why": "rehearsal"}
        )
        for metric in spec["end_to_end"] + spec["per_layer"]:
            if real in metric.get("workloads", ()):
                metric["workloads"].append(name)
    with open(path, "w") as f:
        json.dump(spec, f, indent=1)


def copy_benchmark(dest: str) -> str:
    """``BENCHMARK.json`` and ``bench/`` (without tests) copied to ``dest``."""
    os.makedirs(dest, exist_ok=True)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dest)
    shutil.copytree(
        os.path.join(ROOT, "bench"), os.path.join(dest, "bench"),
        ignore=shutil.ignore_patterns("tests", "__pycache__"),
    )
    return dest


@pytest.fixture
def tiny_root(tmp_path, monkeypatch):
    root = copy_benchmark(str(tmp_path / "checkout"))
    add_cells(root)
    # The CPU has no row in the peak table; rehearsals give it one.
    from bench import work

    real = work.peaks
    monkeypatch.setattr(
        work, "peaks", lambda kind: {"hbm_bytes_per_s": 1e11} if kind == "cpu" else real(kind)
    )
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    return root


def run(root, workload, *, seed=2**31 + 7, seconds=1.0, trace=False, **kw):
    import time

    from bench.harness import run_cell

    return run_cell(
        root, workload, seed=seed, seconds=seconds, trace=trace,
        t_process=time.perf_counter(), require_tpu=False, **kw,
    )
