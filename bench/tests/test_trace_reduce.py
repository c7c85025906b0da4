"""The reduction from a profiler trace to busy time, peel time, top
operations and labelled idle gaps."""

import json
import os

import pytest

from bench.trace_reduce import reduce, union_ns

DATA = os.path.join(os.path.dirname(__file__), "data")


def _events():
    # One device; the window is [1000, 11000) ns.  A while [1000, 5000)
    # holds fusion.1 [1000, 3000) and fusion.2 [3000, 4000); fusion.1
    # runs again at [6000, 7000); a copy runs past the window's end
    # ([10000, 12000): 1000 inside).  Busy: 4000 + 1000 + 1000.
    return {
        "devices": {
            "/device:TPU:0": {
                "ops": [
                    ["while.3", 1000.0, 4000.0],
                    ["fusion.1", 1000.0, 2000.0],
                    ["fusion.2", 3000.0, 1000.0],
                    ["fusion.1", 6000.0, 1000.0],
                    ["copy", 10000.0, 2000.0],
                ],
                "modules": [["jit_peel(7)", 1000.0, 4000.0], ["jit_other", 6000.0, 1000.0],
                            ["jit_peel(7)", 10000.0, 2000.0]],
            }
        },
        "host": [
            ["python3", "bench.window", 1000.0, 10000.0],
            ["bench-server", "bench.poll", 900.0, 6500.0],  # covers the gap 5000-6000
            ["bench-server", "bench.server-idle", 7400.0, 2700.0],  # most of 7000-10000
            ["python3", "bench.wait-due", 7000.0, 3000.0],
        ],
    }


def test_union_merges_and_clips():
    ops = [["a", 0.0, 10.0], ["b", 5.0, 10.0], ["c", 30.0, 5.0], ["d", 40.0, 100.0]]
    assert union_ns(ops, 2.0, 50.0) == [(2.0, 15.0), (30.0, 35.0), (40.0, 50.0)]


def test_reduce_on_a_small_trace():
    r = reduce(_events())
    assert r.window_s == pytest.approx(10000e-9)
    assert r.busy_s == pytest.approx(6000e-9)
    assert r.peel_s == pytest.approx(5000e-9)  # 4000 + the 1000 inside the window
    assert r.devices == 1
    # Leaf operations only: the while's time is its body's.
    assert dict(r.device_ops) == pytest.approx(
        {"fusion.1": 3000e-9, "fusion.2": 1000e-9, "copy": 1000e-9})
    gaps = dict(r.idle_gaps)
    assert gaps["bench.poll"] == pytest.approx(1000e-9)  # 5000-6000
    # 7000-10000: the server polls until 7400 and idles after; the main
    # thread's annotation covers it all, but the server's label wins.
    assert gaps["bench.server-idle"] == pytest.approx(3000e-9)
    assert sum(gaps.values()) == pytest.approx(r.window_s - r.busy_s)


def test_a_trace_without_the_window_is_refused():
    ev = _events()
    ev["host"] = [h for h in ev["host"] if h[1] != "bench.window"]
    with pytest.raises(ValueError, match="bench.window"):
        reduce(ev)


@pytest.mark.parametrize("name", sorted(f for f in os.listdir(DATA) if f.endswith(".json")))
def test_recorded_chip_trace(name):
    """A trace recorded on a TPU v5e: the reduction's invariants hold and
    its numbers are those recorded beside it."""
    with open(os.path.join(DATA, name)) as f:
        rec = json.load(f)
    r = reduce(rec["events"])
    assert 0 < r.busy_s <= r.window_s
    assert 0 < r.peel_s
    assert sum(s for _, s in r.idle_gaps) == pytest.approx(r.window_s - r.busy_s, rel=1e-9)
    for key, value in rec["expect"].items():
        assert getattr(r, key) == pytest.approx(value, rel=1e-9), key
