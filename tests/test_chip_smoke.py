"""``chip_smoke.py`` rehearsed on the CPU at tiny sizes.

The phase functions take their graphs as arguments, so the same code the
chip runs at the repo's largest sizes runs here on a few small graphs with
the oracle checks on (Pallas stays in interpret mode off the TPU).  The
four-chip phase runs on four virtual host devices in a child process,
because the device count is fixed when JAX starts.
"""

import os
import subprocess
import sys

import pytest

from repro.graphs import erdos, rmat, road

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def _assert_clean(line):
    assert line["check_passed"], line
    assert line["device_dispatches"] >= 1, line
    assert line["retries"] == 0 and line["backend_fallbacks"] == 0, line
    assert line["queries_failed"] == 0, line


@pytest.mark.parametrize(
    "graph", [rmat(6, 4, seed=1), road(8, 0.1, seed=2)], ids=["rmat", "road"]
)
def test_large_static_phase(graph):
    line = chip_smoke.phase_large_static(graph.name, graph, cut_from="rmat-15")
    _assert_clean(line)
    assert len(line["planner_backends"]) == 1
    assert line["cut_from"] == "rmat-15"


def test_many_small_phase_second_flush_compiles_nothing():
    graphs = chip_smoke.small_population(8, lambda s: erdos(64, 8.0, seed=s))
    line = chip_smoke.phase_many_small(graphs, max_batch=4)
    _assert_clean(line)
    assert line["second_flush_compiles"] == 0
    assert line["device_dispatches"] == 4  # 2 flushes x 2 batches of 4


def test_stream_phase_checks_every_update():
    line = chip_smoke.phase_stream(
        "rmat-7", rmat(7, 4, seed=3), updates=3, inserts=4, deletes=4
    )
    _assert_clean(line)
    assert len(line["update_s"]) == 3


def test_a_wrong_answer_fails_the_phase(monkeypatch):
    monkeypatch.setattr(chip_smoke, "_expected_kmax", lambda t: -1)
    g = road(8, 0.1, seed=2)
    with pytest.raises(chip_smoke.SmokeFailure, match="check failed"):
        chip_smoke.phase_large_static(g.name, g)


def test_main_refuses_a_platform_without_tpu(capsys):
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert "no TPU found" in err
    assert '"ok"' not in out


_SHARDED = """
import jax, chip_smoke
assert len(jax.devices()) == 4, jax.devices()
from repro.graphs import erdos
graphs = chip_smoke.small_population(8, lambda s: erdos(64, 8.0, seed=s))
line = chip_smoke.phase_sharded(graphs, devices=4, max_batch=4)
assert line["dispatches_per_batch"] == 1, line
print("SHARDED_SMOKE_OK", line["planner_backends"])
"""


def test_sharded_phase_on_four_host_devices():
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=4 " + env.get("XLA_FLAGS", "")
    ).strip()
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), ROOT, env.get("PYTHONPATH", "")]
    )
    proc = subprocess.run(
        [sys.executable, "-c", _SHARDED],
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "SHARDED_SMOKE_OK" in proc.stdout
