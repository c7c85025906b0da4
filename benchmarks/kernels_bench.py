"""Kernel benchmarks: structural models + interpret-mode timing.

This container has no TPU, so the Pallas kernels are profiled
*structurally* (the §Perf methodology for kernels): per tile configuration
we report VMEM working set, arithmetic intensity, and the analytic MXU/VPU
cycle model — plus interpret-mode correctness timing (NOT TPU wall-clock;
flagged).  The table shows why the fine-grained edge-tile kernel is the
right TPU decomposition: its tiles are dense and uniform (lane efficiency
1.0 by construction), while the coarse row decomposition's efficiency is
the graph's lane-efficiency statistic.  `BENCH_kernels.json` carries
both tables (CI uploads it like the peel/stream/api/obs artifacts).
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.configs.ktruss import BENCH_GRAPHS
from repro.core import KTrussEngine
from repro.graphs import imbalance_stats

__all__ = [
    "kernel_structure_rows",
    "run_kernel_bench",
]

_VPU_LANES = 8 * 128  # v5e VPU: 8 sublanes × 128 lanes
_CLOCK = 0.94e9  # ~v5e clock


def kernel_structure_rows(tiles=((256, 128), (256, 256), (128, 512), (512, 256))):
    rows = []
    for t, w in tiles:
        vmem_bytes = 4 * t * w * 4  # four int32 operand tiles
        # compare schedule: W²/128 slabs of (T, W, 128) compares
        cmp_ops = t * w * w
        cmp_cycles = cmp_ops / _VPU_LANES
        # bsearch schedule: log2(W)+1 rounds of gather+compare over (T, W)
        bs_rounds = int(np.ceil(np.log2(w + 1)))
        bs_cycles = bs_rounds * t * w * 3 / _VPU_LANES  # gather≈3 ops/lane
        rows.append(
            {
                "tile": f"{t}x{w}",
                "vmem_kb": vmem_bytes // 1024,
                "vmem_ok": vmem_bytes < 16 * 2**20,
                "compare_cycles": int(cmp_cycles),
                "bsearch_cycles": int(bs_cycles),
                "bsearch_speedup": round(cmp_cycles / bs_cycles, 1),
                "edges_per_s_model_compare": int(t / (cmp_cycles / _CLOCK)),
                "edges_per_s_model_bsearch": int(t / (bs_cycles / _CLOCK)),
            }
        )
    return rows


def run_kernel_bench():
    """Interpret-mode end-to-end timing for the pallas-backed engine."""
    rows = []
    for spec in BENCH_GRAPHS[:2]:
        g = spec.build()
        for schedule in ("compare", "bsearch"):
            import functools

            from repro.kernels import ops as kops

            eng = KTrussEngine(g, granularity="fine", backend="pallas")
            eng._support = functools.partial(
                kops.support_fine,
                eng.problem,
                window=eng.window,
                chunk=eng.chunk,
                schedule=schedule,
            )
            import jax

            fn = jax.jit(eng._support)
            alive = eng.initial_alive()
            fn(alive).block_until_ready()
            t0 = time.perf_counter()
            fn(alive).block_until_ready()
            dt = time.perf_counter() - t0
            rows.append(
                {
                    "graph": g.name,
                    "schedule": schedule,
                    "interpret_ms": round(dt * 1e3, 1),
                    "note": "interpret-mode (CPU emulation, not TPU wall-clock)",
                }
            )
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None, help="write BENCH_kernels.json here")
    args = ap.parse_args()

    print("# structural model (v5e)")
    structural = kernel_structure_rows()
    cols = list(structural[0].keys())
    print(",".join(cols))
    for r in structural:
        print(",".join(str(r[c]) for c in cols))

    print("# interpret-mode end-to-end")
    interpret = run_kernel_bench()
    cols = list(interpret[0].keys())
    print(",".join(cols))
    for r in interpret:
        print(",".join(str(r[c]) for c in cols))

    if args.out:
        with open(args.out, "w") as f:
            json.dump(
                {
                    "structural": structural,
                    "interpret": interpret,
                },
                f,
                indent=2,
            )
        print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
