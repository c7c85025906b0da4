"""The check that decides ``correct`` fails when the timed path is broken.

Each test plants one fault underneath a tiny cell's run (the harness's
look for a chip skipped, the rest of the run as on the chip) and sees
``correct`` come out false; the control (the reference with its support
window cut, ``bench/control.py``) must fail too.  A run with no fault
planted passes (``test_rehearsal.py``).
"""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest
from conftest import ROOT, SRC, run

from bench.control import ControlSystem


def _blank(state):
    """What a member whose peel never ran would read back."""
    from repro.api import KTrussResult, TrussDecomposition

    nnz, q = state.query.graph.nnz, state.query
    if q.workload == "ktruss":
        return KTrussResult(k=q.k, alive=np.zeros(nnz, bool), support=np.zeros(nnz, np.int32),
                            iterations=0, edges_remaining=0)
    return TrussDecomposition(trussness=np.full(nnz, 2, np.int32), kmax=0, levels=0)


def plant_unchanged_state(monkeypatch):
    """The peel returns its state as it went in."""
    import jax.numpy as jnp

    from repro.exec import peel as P

    def peel(self, p, *, slot_ids, k0, single_level=None, alive0=None, frozen=None,
             frozen_truss=None):
        k0 = jnp.asarray(np.asarray(k0, np.int32))
        single = jnp.asarray(np.zeros(k0.shape, bool) if single_level is None
                             else np.asarray(single_level, bool))
        alive0 = p.colidx != 0 if alive0 is None else alive0
        zeros = jnp.zeros(alive0.shape, jnp.int32)
        st = P.init_peel_state(p, jnp.asarray(slot_ids), k0, single, alive0,
                               jnp.zeros(alive0.shape, bool), zeros)
        return st._replace(done=jnp.ones_like(st.done))

    monkeypatch.setattr(P.PeelExecutor, "peel", peel)


def _wrap_execute(monkeypatch, after):
    from repro.api.planner import Planner

    orig = Planner.execute

    def execute(self, batch, cache):
        return after(self, batch, cache, orig)

    monkeypatch.setattr(Planner, "execute", execute)


def plant_half_batch(monkeypatch):
    """Only the first half of each batch's members is peeled."""
    def after(self, batch, cache, orig):
        keep = (len(batch.queries) + 1) // 2
        res = orig(self, dataclasses.replace(batch, queries=batch.queries[:keep]), cache)
        return res + [_blank(st) for st in batch.queries[keep:]]

    _wrap_execute(monkeypatch, after)


def plant_no_exchange(monkeypatch, devices):
    """Slots beyond the first device's share come back as they went in,
    as if the devices never agreed on when the peel was done."""
    def after(self, batch, cache, orig):
        res = orig(self, batch, cache)
        share = batch.slots // devices
        return [r if i < share else _blank(st)
                for i, (r, st) in enumerate(zip(res, batch.queries))]

    _wrap_execute(monkeypatch, after)


def plant_altered_answer(monkeypatch):
    """One answer of the window altered where it is produced: one edge's
    trussness or one edge's k-truss membership."""
    seen = {"batches": 0}

    def after(self, batch, cache, orig):
        res = orig(self, batch, cache)
        seen["batches"] += 1
        if seen["batches"] == 2:  # the first batch of the window
            r, q = res[0], batch.queries[0].query
            if q.workload == "ktruss":
                r.alive[0] = not r.alive[0]
            else:
                r.trussness[0] += 1
        return res

    _wrap_execute(monkeypatch, after)


FAULTS = {
    "unchanged-state": plant_unchanged_state,
    "half-batch": plant_half_batch,
    "altered-answer": plant_altered_answer,
}
# Which faults each cell can have: a batch of one has no half to leave out.
CELL_FAULTS = [
    ("tiny-dec", "unchanged-state"), ("tiny-dec", "altered-answer"),
    ("tiny-k3", "unchanged-state"), ("tiny-k3", "altered-answer"),
    ("tiny-serve", "unchanged-state"), ("tiny-serve-burst", "half-batch"),
    ("tiny-serve", "altered-answer"),
]


def _add_burst_cell(root, name="tiny-serve-burst", traffic="open-ktruss3-b8", chips=1):
    """A tiny serving cell at a rate that fills its batches on the CPU."""
    with open(os.path.join(root, "bench", "traffic", traffic + ".json")) as f:
        mix = json.load(f)
    mix["rate_qps"] = 400.0
    with open(os.path.join(root, "bench", "traffic", traffic + "-burst.json"), "w") as f:
        json.dump(mix, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["workloads"].append({"name": name, "config": "tiny-kron",
                              "traffic": traffic + "-burst", "chips": chips, "why": "burst"})
    with open(path, "w") as f:
        json.dump(spec, f)


@pytest.mark.parametrize("cell,fault", CELL_FAULTS)
def test_a_planted_fault_fails_the_check(tiny_root, monkeypatch, cell, fault):
    if cell == "tiny-serve-burst":
        _add_burst_cell(tiny_root)
    FAULTS[fault](monkeypatch)
    line = run(tiny_root, cell, seconds=0.5)
    assert line["correct"] is False, line["checks"]


def test_the_four_chip_cell_without_its_exchange_fails(tiny_root):
    _add_burst_cell(tiny_root, "tiny-serve-4chip-burst", "tiny-open-ktruss3-b32", 4)
    code = (
        "import sys, json; sys.path[:0] = [%r, %r, %r];"
        "import pytest; from conftest import run; from bench import work;"
        "from test_faults import plant_no_exchange;"
        "work.peaks = lambda kind: {'hbm_bytes_per_s': 1e11};"
        "mp = pytest.MonkeyPatch(); plant_no_exchange(mp, 4);"
        "print(json.dumps(run(%r, 'tiny-serve-4chip-burst', seconds=0.5)))"
        % (os.path.dirname(__file__), ROOT, SRC, tiny_root)
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is False, line["checks"]


class _TinyControl(ControlSystem):
    WINDOW = 4  # the tiny graphs' degrees are about a quarter of the real ones'


@pytest.mark.parametrize("cell", ["tiny-dec", "tiny-k3", "tiny-serve"])
def test_the_control_fails_the_check(tiny_root, cell):
    line = run(tiny_root, cell, seconds=0.5, make_system=_TinyControl)
    assert line["correct"] is False, line["checks"]
    assert line["checks"]["edges_wrong"]["value"] > 0
