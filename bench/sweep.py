#!/usr/bin/env python3
"""The knee of an open-loop cell: the highest rate it sustains.

    python3 bench/sweep.py --workload kron7-serve --rates 10,14,18,22 --seconds 20 \
        --runs 2 --seed 5

Runs the cell ``--runs`` times per rate (seeds ``--seed``, ``--seed + 1``,
...), in one process, with the traffic mix's ``rate_qps`` replaced, and
prints one JSON line per run: the rate offered, the rate answered within
the window, the time-mean backlog (queries sent and not yet answered)
over each half of the window, and the p95 latency.  A run sustains its
rate when the backlog does not grow: its mean over the second half
exceeds its mean over the first half by less than one batch (the mix's
``max_batch``).  A rate is sustained when every run of it is.  The last line names the knee: the highest rate
that is sustained with every lower rate of the sweep.  The cell's traffic
file then fixes its rate from this knee; the benchmark's own runs never
search for one.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rates", required=True, help="comma-separated queries/s")
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--runs", type=int, default=2)
    parser.add_argument("--seed", type=int, default=1)
    args = parser.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(1, os.path.join(ROOT, "src"))
    from bench.harness import NoChip, run_cell
    from bench.spec import load_cell

    batch = int(load_cell(ROOT, args.workload).traffic.get("session", {}).get("max_batch", 1))
    knee, broken = None, False
    for rate in sorted(float(r) for r in args.rates.split(",")):
        sustained = True
        for i in range(args.runs):
            try:
                line = run_cell(
                    ROOT, args.workload, seed=args.seed + i, seconds=args.seconds, trace=False,
                    t_process=time.perf_counter(), traffic_override={"rate_qps": rate},
                )
            except NoChip as e:
                print(f"sweep: {e}", file=sys.stderr)
                return 2
            run = line["run"]
            first, second = run["backlog_mean_halves"]
            ok = line["correct"] and second - first < batch
            sustained = sustained and ok
            print(json.dumps({
                "workload": args.workload, "rate_qps": rate, "seed": args.seed + i,
                "seconds": args.seconds, "attempted": line["attempted"],
                "correct": line["correct"],
                "answered_qps": run["answered_in_window"] / args.seconds,
                "backlog_mean_halves": run["backlog_mean_halves"],
                "backlog_at_close": run["backlog_at_close"], "sustained": ok,
                "metrics": line["metrics"],
            }), flush=True)
        broken = broken or not sustained
        if not broken:
            knee = rate
    print(json.dumps({"workload": args.workload, "knee_qps": knee, "batch": batch,
                      "rule": "mean backlog of the second half under the first half's plus one batch"}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
