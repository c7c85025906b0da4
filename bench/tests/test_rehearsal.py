"""Every cell's traffic driven through the harness at a tiny size."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest
from conftest import ROOT, SRC, copy_benchmark, run

from bench import loadgen
from bench.spec import load_cell

E2E = {
    "tiny-dec": {"edges_per_s", "setup_s"},
    "tiny-k3": {"edges_per_s", "setup_s"},
    "tiny-serve": {"query_p95_ms", "setup_s"},
}
LAYER = {
    "tiny-dec": {
        "pack_ms_per_batch.static", "peel_trips_per_query.static", "device_ms_per_trip.static",
        "peel_roofline", "device_idle_pct.static",
    },
    "tiny-serve": {
        "queue_wait_p95_ms.serve", "slot_occupancy_pct.serve", "pack_ms_per_batch.serve",
        "device_idle_pct.serve",
    },
}
LAYER["tiny-k3"] = LAYER["tiny-dec"]


def _clean(line):
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["run"]["compiles_in_window"] == 0
    assert list(line)[-1] == "checks"
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] >= 1


@pytest.mark.parametrize("cell", sorted(E2E))
def test_cell_end_to_end(tiny_root, cell):
    line = run(tiny_root, cell)
    _clean(line)
    assert set(line["metrics"]) == E2E[cell]
    assert all(m["value"] > 0 for m in line["metrics"].values())


@pytest.mark.parametrize("cell", sorted(LAYER))
def test_cell_traced(tiny_root, cell):
    line = run(tiny_root, cell, trace=True)
    _clean(line)
    assert set(line["metrics"]) == LAYER[cell]
    dev = line["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert 0 < len(line["breakdown"]["device_ops"]) <= 10
    assert 0 < len(line["breakdown"]["idle_gaps"]) <= 10


def test_four_chip_cell_on_four_virtual_devices(tiny_root):
    code = (
        "import sys, json; sys.path[:0] = [%r, %r, %r];"
        "from conftest import run; from bench import work;"
        "work.peaks = lambda kind: {'hbm_bytes_per_s': 1e11};"
        "print(json.dumps(run(%r, 'tiny-serve-4chip', seconds=1.0)))"
        % (os.path.dirname(__file__), ROOT, SRC, tiny_root)
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    _clean(line)
    assert line["device"]["count"] == 4
    assert set(line["metrics"]) == {"query_p95_ms", "setup_s"}


RING_FAMILY = '''
import numpy as np
from bench.graphs import canonical_edges

def generate(*, n, reach, seed):
    """Circulant graph: vertex v joined to v + 1 .. v + reach (mod n),
    plus one chord drawn from the seed."""
    v = np.arange(n)
    edges = [np.stack([v, (v + d) % n], 1) for d in range(1, reach + 1)]
    rng = np.random.default_rng(seed)
    edges.append(rng.integers(0, n, size=(1, 2)))
    return n, canonical_edges(n, np.concatenate(edges))
'''

KMAX_WORKLOAD = '''
from bench import reference as R

CHECK = "kmax_wrong"

def to_program(graph, args):
    from repro.api import TrussQuery
    return TrussQuery.kmax(graph)

def from_program(result):
    return int(result)

def expected(n, edges, args, window=None):
    return R.kmax_of(R.trussness(n, edges, window=window))

def wrong(answer, expected):
    return int(answer != expected)

def answer_bytes(n, m, args):
    return 4
'''


def _write(root, rel, text):
    with open(os.path.join(root, rel), "w") as f:
        f.write(text)


def test_a_cell_added_from_new_files_alone(tiny_root):
    """A new graph family, a new query kind, a new traffic mix (on/off
    bursts, weighted popularity), a new per-layer metric and a new cell:
    new files and new entries only."""
    _write(tiny_root, "bench/families/ring.py", RING_FAMILY)
    _write(tiny_root, "bench/workloads/kmax.py", KMAX_WORKLOAD)
    _write(tiny_root, "bench/configs/tiny-ring.json", json.dumps({
        "name": "tiny-ring", "family": "ring", "params": {"n": 40, "reach": 3},
        "population": {"size": 4, "first_seed": 0},
    }))
    _write(tiny_root, "bench/traffic/burst-kmax-b4.json", json.dumps({
        "loop": "open", "rate_qps": 8.0, "phases": [{"seconds": 0.25, "rate": 2.0},
                                                    {"seconds": 0.25, "rate": 0.0}],
        "mix": [{"workload": "kmax", "weight": 1}, {"workload": "ktruss", "k": 4, "weight": 1}],
        "graphs": {"pick": "weights", "weights": [4, 2, 1, 1]}, "session": {"max_batch": 4},
        "warmup": {"workload": "kmax"},
    }))
    _write(tiny_root, "bench/layer_metrics/answered_share.burst.py",
           "def read(run):\n    return 100.0 * len(run.answered_in_window()) / len(run.queries)\n")
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "tiny-ring", "source": "test",
                            "file": "bench/configs/tiny-ring.json", "reduced": [], "why": "new"})
    spec["workloads"].append({"name": "tiny-burst", "config": "tiny-ring",
                              "traffic": "burst-kmax-b4", "chips": 1, "why": "new"})
    [p95] = [m for m in spec["end_to_end"] if m["name"] == "query_p95_ms"]
    p95["workloads"].append("tiny-burst")
    spec["per_layer"].append({"name": "answered_share.burst", "unit": "%", "better": "higher",
                              "source": "host_clock", "layer": "session",
                              "moves": "query_p95_ms", "workloads": ["tiny-burst"]})
    with open(path, "w") as f:
        json.dump(spec, f)
    line = run(tiny_root, "tiny-burst", seconds=2.0, trace=True)
    _clean(line)
    assert set(line["checks"]) == {"edges_wrong", "kmax_wrong", "failed", "unanswered"}
    assert line["metrics"]["answered_share.burst"]["value"] == 100.0
    line = run(tiny_root, "tiny-burst", seconds=2.0)
    _clean(line)
    assert line["attempted"] == 16 and set(line["metrics"]) == {"query_p95_ms", "setup_s"}


def test_bursts_arrive_in_their_phases():
    due = loadgen.arrivals(10.0, 4.0, [{"seconds": 1.0, "rate": 2.0}, {"seconds": 1.0, "rate": 0.0}],
                           np.random.default_rng(0))
    assert len(due) == 40 and (np.diff(due) >= 0).all()
    assert ((due % 2.0) < 1.0).all()  # nothing due in an off phase
    flat = loadgen.arrivals(10.0, 4.0, None, np.random.default_rng(0))
    assert len(flat) == 40 and 0 <= flat.min() and flat.max() < 4.0


def test_same_seed_same_queries(tiny_root):
    big = 2**31 + 12345
    cell = load_cell(tiny_root, "tiny-serve")
    pop = loadgen.population(tiny_root, cell.config)
    a, a2, b = (loadgen.Traffic(cell.traffic, pop, seed, 2.0).schedule()
                for seed in (big, big, big + 1))
    # The same work for every seed: due times, workloads and graphs ...
    assert [(q.workload, q.args, q.graph.gid, q.due) for q in a] == \
        [(q.workload, q.args, q.graph.gid, q.due) for q in b]
    # ... under vertex labels of the seed's own.
    assert all(np.array_equal(x.graph.edges, y.graph.edges) for x, y in zip(a, a2))
    assert not all(np.array_equal(x.graph.edges, y.graph.edges) for x, y in zip(a, b))
    closed = load_cell(tiny_root, "tiny-dec")
    t1, t2, t3 = (loadgen.Traffic(closed.traffic, loadgen.population(tiny_root, closed.config),
                                  seed, 1.0)
                  for seed in (big, big, big + 1))
    assert all(np.array_equal(t1.query(i).graph.edges, t2.query(i).graph.edges) for i in range(5))
    assert not all(np.array_equal(t1.query(i).graph.edges, t3.query(i).graph.edges)
                   for i in range(5))


def _command(cwd, env=None):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "kron8-decompose", "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=dict(os.environ, JAX_PLATFORMS="cpu", **(env or {})),
        capture_output=True, text=True, timeout=300,
    )


def test_the_command_refuses_a_cpu():
    out = _command(ROOT)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "no TPU" in out.stderr


def test_the_command_refuses_without_the_program(tmp_path):
    out = _command(copy_benchmark(str(tmp_path / "bare")))
    assert out.returncode != 0
    assert out.stdout == ""
