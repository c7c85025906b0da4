#!/usr/bin/env python3
"""Record a small chip trace for ``test_trace_reduce.py``.

    python3 bench/tests/record_trace.py --workload kron8-ktruss3 --seconds 0.3 \\
        --out trace.json [--keep-ms 20]

Runs one traced run of the cell on the chip and writes what
``bench.trace_reduce.extract`` read from the profiler, cut to the first
``--keep-ms`` milliseconds of the window (so that it stays small), with
the reduction's numbers on that cut beside it under ``expect``.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir, os.pardir))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, trace_reduce  # noqa: E402


def cut(events: dict, keep_ns: float) -> dict:
    """The events of the window's first ``keep_ns``, the window shortened to match."""
    (lo, d), = [(s, d) for _t, n, s, d in events["host"] if n == trace_reduce.WINDOW]
    hi = lo + min(d, keep_ns)

    def inside(rows, at):
        return [r for r in rows if r[at] < hi and r[at] + r[at + 1] > lo]

    host = [r for r in inside(events["host"], 2) if r[1] != trace_reduce.WINDOW]
    host.append(["python3", trace_reduce.WINDOW, lo, hi - lo])
    devices = {k: {"ops": inside(v["ops"], 1), "modules": inside(v["modules"], 1)}
               for k, v in events["devices"].items()}
    return {"devices": devices, "host": host}


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seconds", type=float, default=0.3)
    parser.add_argument("--keep-ms", type=float, default=20.0)
    parser.add_argument("--out", required=True)
    args = parser.parse_args()
    seen = {}
    extract = trace_reduce.extract

    def keep(path):
        seen["events"] = extract(path)
        return seen["events"]

    trace_reduce.extract = keep
    harness.run_cell(ROOT, args.workload, seed=1, seconds=args.seconds, trace=True,
                     t_process=time.perf_counter())
    small = cut(seen["events"], args.keep_ms * 1e6)
    r = trace_reduce.reduce(small)
    with open(args.out, "w") as f:
        json.dump({"workload": args.workload, "device_kind": "TPU v5 lite",
                   "events": small,
                   "expect": {"window_s": r.window_s, "busy_s": r.busy_s, "peel_s": r.peel_s}}, f)
    print(json.dumps({"ops": sum(len(v["ops"]) for v in small["devices"].values()),
                      "expect": {"window_s": r.window_s, "busy_s": r.busy_s, "peel_s": r.peel_s}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
