"""Serving through a session on a four-device slot mesh.

``Session(mesh=slot_mesh(4), max_batch=32)`` with the strict retry policy
(no retry, no fallback, no bisect) serves seeded Kronecker scale-5 graphs
in batches of 1, 9 and 32 members, for ``ktruss(3)`` and ``decompose``;
every answer must equal the numpy oracle exactly.  The traced session must
show one ``shard`` span per batch (the arguments placed across the four
devices) and a ``batch_chips_used`` of 1, 2 and 4 for those fills (8 slots
a device), and every dispatch runs the support pass per chip
(``peel_sharded_support_dispatches``).  A session without a mesh uses one
chip, shards nothing and runs no per-chip support.

The mesh needs four devices before JAX starts, so the serving runs once,
in a fresh process with ``--xla_force_host_platform_device_count=4``, and
the tests read what it printed.
"""

import json
import os
import subprocess
import sys

import pytest

FILLS = (1, 9, 32)
WORKLOADS = ("ktruss", "decompose")

_SCRIPT = r"""
import json

import numpy as np

from repro.api import Session, TrussQuery
from repro.core.reference import ktruss_numpy, trussness_numpy
from repro.distributed import slot_mesh
from repro.graphs import rmat
from repro.resilience.retry import RetryPolicy

FILLS, WORKLOADS = (1, 9, 32), ("ktruss", "decompose")


def strict_session(**kw):
    policy = RetryPolicy(max_attempts=1, fallback=False, bisect=False)
    return Session(trace=True, retry=policy, max_batch=32, **kw)


def query(workload, g):
    return TrussQuery.ktruss(g, 3) if workload == "ktruss" else TrussQuery.decompose(g)


def wrong(workload, g, result):
    if workload == "ktruss":
        alive, support = ktruss_numpy(g, 3)
        return int((result.alive != alive).sum() + (result.support != support).sum())
    return int((result.trussness != trussness_numpy(g)).sum())


def serve(session, workload, graphs):
    # One batch: every member in one (bucket, backend) group, one poll.
    futs = [session.submit(query(workload, g)) for g in graphs]
    h = session.obs.metrics.histogram("batch_chips_used")
    before = (h.count, h.sum) if h is not None else (0, 0.0)
    served = session.poll()
    h = session.obs.metrics.histogram("batch_chips_used")
    return {
        "served": served,
        "wrong": sum(wrong(workload, g, f.result()) for g, f in zip(graphs, futs)),
        "chips_used": [h.count - before[0], h.sum - before[1]],
    }


mesh_session = strict_session(mesh=slot_mesh(4))
group, graphs = None, []
for seed in range(400):
    g = rmat(5, 8, seed=seed)
    key = mesh_session.planner.assign(TrussQuery.ktruss(g, 3)).group
    group = group or key
    if key == group:
        graphs.append(g)
    if len(graphs) == 32:
        break
out = {"graphs": len(graphs), "mesh": {}, "meshless": None}
for workload in WORKLOADS:
    for fill in FILLS:
        out["mesh"][f"{workload}-{fill}"] = serve(mesh_session, workload, graphs[:fill])
events = mesh_session.obs.tracer.events()
out["shard"] = [ev["args"] for ev in events if ev["name"] == "shard"]
out["dispatch_batches"] = [ev["args"]["batch"] for ev in events if ev["name"] == "dispatch"]
out["shard_inside_dispatch"] = all(
    any(d["ts"] <= s["ts"] and s["ts"] + s["dur"] <= d["ts"] + d["dur"]
        for d in events if d["name"] == "dispatch")
    for s in events if s["name"] == "shard"
)
plain = strict_session()
out["meshless"] = serve(plain, "ktruss", graphs[:9])
out["meshless"]["shard"] = sum(ev["name"] == "shard" for ev in plain.obs.tracer.events())
out["per_chip_support"] = {
    side: [s.obs.metrics.value(name)
           for name in ("peel_sharded_support_dispatches", "peel_dispatches")]
    for side, s in (("mesh", mesh_session), ("meshless", plain))
}
print("MESH_SERVING " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def served():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=4 " + env.get("XLA_FLAGS", "")
    ).strip()
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir, "src"))
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT], env=env, capture_output=True, text=True, timeout=900
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    [line] = [ln for ln in proc.stdout.splitlines() if ln.startswith("MESH_SERVING ")]
    out = json.loads(line.split(" ", 1)[1])
    assert out["graphs"] == 32  # enough same-group graphs for a full batch
    return out


@pytest.mark.parametrize("fill", FILLS)
@pytest.mark.parametrize("workload", WORKLOADS)
def test_mesh_session_answers_exactly(served, workload, fill):
    case = served["mesh"][f"{workload}-{fill}"]
    assert case["served"] == fill  # one batch, every member answered
    assert case["wrong"] == 0


def test_each_mesh_batch_has_one_shard_span(served):
    batches = served["dispatch_batches"]
    assert len(batches) == len(WORKLOADS) * len(FILLS)
    assert sorted(s["batch"] for s in served["shard"]) == sorted(batches)
    assert all(s["chips"] == 4 for s in served["shard"])
    assert served["shard_inside_dispatch"]


def test_batch_chips_used_counts_the_occupied_chips(served):
    # 8 slots a chip: 1 member fills one chip, 9 two, 32 all four.
    for workload in WORKLOADS:
        used = [served["mesh"][f"{workload}-{fill}"]["chips_used"] for fill in FILLS]
        assert used == [[1, 1.0], [1, 2.0], [1, 4.0]]


def test_per_chip_support_ticks_once_per_mesh_dispatch(served):
    # The mesh session's fine/xla eager batches run the support per chip,
    # each dispatch once; a session without a mesh never does.
    mesh_sharded, mesh_dispatches = served["per_chip_support"]["mesh"]
    assert mesh_dispatches == len(WORKLOADS) * len(FILLS)
    assert mesh_sharded == mesh_dispatches
    assert served["per_chip_support"]["meshless"] == [0, 1]


def test_meshless_session_uses_one_chip_and_shards_nothing(served):
    case = served["meshless"]
    assert case["served"] == 9 and case["wrong"] == 0
    assert case["chips_used"] == [1, 1.0]
    assert case["shard"] == 0
