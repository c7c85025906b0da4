"""``kron8-mesh4-serve`` rehearsed on four virtual CPU devices: its traffic
mix (``open-ktruss3-b32``) on Kronecker scale-5 graphs, through the
harness, traced.  The run must be correct on four devices and read every
per-layer metric the real cell lists, the mesh's two among them."""

import json
import os
import subprocess
import sys

from conftest import ROOT, SRC

CELL = "kron8-mesh4-serve"


def _add_tiny_copy(root: str) -> set:
    """A cell ``tiny-mesh4``: the real cell's mix and chips on the tiny
    Kronecker configuration, in every metric list that names the real
    cell.  Returns the per-layer metrics it reports."""
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    [real] = [w for w in spec["workloads"] if w["name"] == CELL]
    spec["workloads"].append(dict(real, name="tiny-mesh4", config="tiny-kron"))
    layer = set()
    for metric in spec["end_to_end"] + spec["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("tiny-mesh4")
            if metric in spec["per_layer"]:
                layer.add(metric["name"])
    with open(path, "w") as f:
        json.dump(spec, f, indent=1)
    return layer


def test_mesh_cell_traced_on_four_virtual_devices(tiny_root):
    layer = _add_tiny_copy(tiny_root)
    assert {"shard_ms_per_batch.mesh", "chips_used_per_batch.mesh"} <= layer
    code = (
        "import sys, json; sys.path[:0] = [%r, %r, %r];"
        "from conftest import run; from bench import work;"
        "work.peaks = lambda kind: {'hbm_bytes_per_s': 1e11};"
        "print(json.dumps(run(%r, 'tiny-mesh4', seconds=1.0, trace=True)))"
        % (os.path.dirname(__file__), ROOT, SRC, tiny_root)
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["run"]["compiles_in_window"] == 0
    assert line["device"]["count"] == 4
    assert set(line["metrics"]) == layer
    assert line["metrics"]["shard_ms_per_batch.mesh"]["value"] > 0
    assert 1 <= line["metrics"]["chips_used_per_batch.mesh"]["value"] <= 4
