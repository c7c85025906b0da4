"""Benchmark orchestrator — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows per section, plus the section
tables.  Sections:

  table1    — paper Table I analog (coarse/fine runtimes + ME/s)
  fig23     — paper Fig 2/3 analog (fine-over-coarse speedups + geomean)
  imbalance — load-imbalance statistics (the paper's §III-A mechanism)
  kernels   — Pallas kernel structural models + interpret-mode checks
  service   — TrussService throughput + compile-cache hit rate (batch sweep)
  peel      — on-device peel: decompose graphs/s, sharded vs unsharded
  stream    — incremental truss maintenance: updates/s + frontier ratio
  api       — repro.api planner overhead + backend auto-choice per bucket
  obs       — tracing overhead on/off + observed per-bucket imbalance
  serve     — multi-replica fleet: queries/s, p50/p99, affinity hit rate
"""

from __future__ import annotations

import sys
import time


def _section(title: str):
    print(f"\n##### {title} " + "#" * max(1, 60 - len(title)), flush=True)


def main() -> None:
    only = sys.argv[1] if len(sys.argv) > 1 else None
    t_start = time.time()

    if only in (None, "imbalance"):
        _section("imbalance")
        from repro.configs.ktruss import BENCH_GRAPHS
        from repro.graphs import imbalance_stats

        cols = None
        for spec in BENCH_GRAPHS:
            st = imbalance_stats(spec.build()).row()
            if cols is None:
                cols = list(st.keys())
                print(",".join(cols))
            print(",".join(f"{st[c]:.3g}" if isinstance(st[c], float) else str(st[c]) for c in cols))

    if only in (None, "table1"):
        _section("table1 (paper Table I analog, K=3)")
        from . import ktruss_table

        rows = ktruss_table.run_table()
        cols = sorted({c for r in rows for c in r})
        print(",".join(cols))
        for r in rows:
            print(",".join(str(r.get(c, "")) for c in cols))
        for r in rows:
            if r.get("support_ms_fe"):
                print(
                    f"bench,ktruss_fine_support_{r['graph']},"
                    f"{r['support_ms_fe']*1e3:.0f},ME/s={r.get('me_s_fe')}"
                )

    if only in (None, "fig23"):
        _section("fig23 (speedup fine/coarse + geomean)")
        from . import ktruss_speedup

        rows, geo = ktruss_speedup.run_speedup()
        cols = list(rows[0].keys())
        print(",".join(cols))
        for r in rows:
            print(",".join(str(r[c]) for c in cols))
        print(f"geomean_speedup,{geo:.2f}")
        print("paper_reference,CPU 1.48x / GPU 16.93x (K=3)")

    if only in (None, "kernels"):
        _section("kernels (structural + interpret)")
        from . import kernels_bench

        for r in kernels_bench.kernel_structure_rows():
            print(r)
        for r in kernels_bench.run_kernel_bench():
            print(r)

    if only in (None, "service"):
        _section("service (batched serving: graphs/s + cache hit rate)")
        from . import service_bench

        service_bench.report(service_bench.run_service_bench())

    if only in (None, "peel"):
        _section("peel (one-dispatch decompose: graphs/s)")
        from . import peel_bench

        peel_bench.report(peel_bench.run_peel_bench())

    if only in (None, "stream"):
        _section("stream (incremental updates: updates/s + frontier frac)")
        from . import stream_bench

        stream_bench.report(
            stream_bench.run_stream_bench(widths=(1, 16), updates_per_width=2)
        )

    if only in (None, "api"):
        _section("api (planner overhead + backend auto-choice)")
        from . import api_bench

        api_bench.report(api_bench.run_api_bench())

    if only in (None, "obs"):
        _section("obs (tracing overhead + observed imbalance)")
        from . import obs_bench

        obs_bench.report(obs_bench.run_obs_bench(repeats=2))

    if only in (None, "serve"):
        _section("serve (fleet: qps + p50/p99 + affinity hit rate)")
        from . import serve_bench

        serve_bench.report(serve_bench.run_serve_bench(queries_per_fleet=24))

    print(f"\n# total bench wall time: {time.time()-t_start:.1f}s")


if __name__ == "__main__":
    main()
