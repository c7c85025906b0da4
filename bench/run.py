#!/usr/bin/env python3
"""The benchmark's command: one run of one cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell, its configuration, its
traffic mix and its metrics are found by name from ``BENCHMARK.json``
and the files under ``bench/``.  Set-up (JAX start-up, data from the
seed, executors from the compile cache, one warm query per shape bucket)
is timed from process start; then the window runs for ``--seconds``;
then every answer is checked against the plain reference.  The last line
of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` the per-layer
metrics and ``breakdown``, and last ``checks``: each number compared,
beside its limit).  The checks are also the last lines of standard error.

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for, or when the checkout's ``src/`` is missing.
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
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    sys.path.insert(0, ROOT)
    from bench.harness import NoChip, run_cell
    from bench.spec import BenchmarkError

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"bench: the program is missing: no {src}/repro", file=sys.stderr)
        return 2
    sys.path.insert(1, src)
    try:
        line = run_cell(
            ROOT,
            args.workload,
            seed=args.seed,
            seconds=args.seconds,
            trace=bool(args.trace),
            t_process=T_PROCESS,
        )
    except (NoChip, BenchmarkError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, check in line["checks"].items():
        print(f"check {name}: {check['value']} (limit {check['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
