"""``program_trace.py`` on the CPU: the program's scopes and spans read
from a tiny cell's profiler trace."""

import pytest

import program_trace as PT

HLO = """
  %fusion.7 = s32[64]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(peel)/while/body/support/while/body/gather"}
  ROOT %copy.2 = s32[64]{0} copy(%q), metadata={op_name="jit(peel)/while/body/prune/add"}
  %while.1 = (s32[]) while(%t), metadata={op_name="jit(peel)/while"}
"""


def test_scopes_from_hlo_text():
    assert PT.instruction_scopes([HLO]) == {"fusion.7": "support", "copy.2": "prune"}
    clash = HLO.replace('body/prune/add"}', 'body/support/add"}')
    assert PT.instruction_scopes([HLO, clash]) == {"fusion.7": "support"}


def _events():
    # Window [0, 100); the device is busy [10, 20), [60, 70) and [80, 90)
    # in two peel executions, and [92, 95) in another program.  The
    # server polls over [0, 90): pack [20, 35), dispatch [35, 40), then
    # unpack [70, 80); [0, 10) and [40, 60) no program span covers.
    return {
        "host": [
            ["main", "bench.window", 0.0, 100.0],
            ["server", "bench.poll", 0.0, 90.0],
            ["server", "bench.server-idle", 90.0, 10.0],
        ],
        "devices": {"/device:TPU:0": {
            "ops": [["fusion.7", 10.0, 10.0], ["copy.2", 60.0, 10.0],
                    ["fusion.7", 80.0, 10.0], ["fusion.1", 92.0, 3.0]],
            "modules": [["jit_peel(1)", 10.0, 60.0], ["jit_peel(1)", 80.0, 10.0],
                        ["jit_other(2)", 92.0, 3.0]],
        }},
    }


PROGRAM = [
    ["server", "repro.pack", 20.0, 15.0],
    ["server", "repro.dispatch", 35.0, 5.0],
    ["server", "repro.unpack", 70.0, 10.0],
    ["client", "repro.plan", 40.0, 20.0],  # another thread: not the server's
]


def test_idle_gaps_take_the_program_span_over_most_of_them():
    gaps = {k: v * 1e9 for k, v in PT.label_gaps(_events(), PROGRAM, 0.0, 100.0).items()}
    # [20, 60) is mostly pack; [70, 80) unpack; [0, 10) no span covers.
    assert gaps == pytest.approx({"repro.pack": 40.0, "repro.unpack": 10.0, "bench.poll": 10.0,
                                  "bench.server-idle": 7.0})


def test_scope_and_execution_times():
    events = _events()
    secs = PT.scope_seconds(events, PT.instruction_scopes([HLO]), 0.0, 100.0)
    assert {k: v * 1e9 for k, v in secs.items()} == pytest.approx(
        {"support": 20.0, "prune": 10.0, "unscoped": 0.0})
    # Batch 0 ran [10, 70) in 2 trips and batch 1 [80, 90) in 1; a window
    # that cuts batch 0 counts batch 1 alone.
    assert PT.exact_ms_per_trip(events, {0: 2, 1: 1}, 0.0, 100.0) == pytest.approx(70e-6 / 3)
    assert PT.exact_ms_per_trip(events, {0: 2, 1: 1}, 50.0, 100.0) == pytest.approx(10e-6)
    assert PT.exact_ms_per_trip(events, {0: 2}, 0.0, 100.0) is None


def test_a_tiny_serving_run(tiny_root):
    line = PT.run(tiny_root, "tiny-serve", seed=2**31 + 7, seconds=1.0, require_tpu=False)
    assert line["correct"]
    assert set(line["metrics"]) == {"query_p95_ms"}
    prog = line["program"]
    assert prog["scoped_instructions"] > 0 and prog["trips"] > 0 and prog["batches"] > 0
    assert prog["scopes_s"]["support"] > 0
    assert any(k.startswith("repro.") for k in prog["idle_gaps"])
    assert prog["device_ms_per_trip"]["reader"] > 0
    assert prog["device_ms_per_trip"]["window_clipped"] > 0
    assert prog["device_ms_per_trip"]["exact"] is None  # the CPU has no execution events
    assert prog["spans_per_batch"] > 5
