#!/usr/bin/env python3
"""The control of the check that decides ``correct``: it has to fail.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds <s>

The configurations state one guarantee, exactness: every edge's
trussness and every k-truss edge set equal the reference's.
The control breaks it in the way a faster support pass would be tempted
to: the plain reference (``bench/reference.py``) put in the program's
place (each query kind's ``expected`` in ``bench/workloads/``), closing
triangles only through each vertex's first 16 neighbours, as an
intersection cut to a fixed window would.  (Stopping each level
after one pruning pass would not do: at k = 3 one pass already reaches
the fixed point.)  It runs through the whole harness (set-up, the cell's own
traffic and window, the comparison) for each seed, in one process, and
prints one JSON line per seed with the numbers compared.  The
benchmark's own runs never run it.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Future:
    def __init__(self):
        self._done = False
        self._value = None

    def done(self) -> bool:
        return self._done

    def result(self):
        return self._value


class ControlSystem:
    """The reference in the program's place, its window cut to 16."""

    WINDOW = 16

    def __init__(self, cell, *, trace: bool):
        self.root = cell.root
        self.max_batch = int(cell.traffic.get("session", {}).get("max_batch", 1))
        self._pending: list = []

    def prepare(self, q):
        return q

    def submit(self, q):
        fut = _Future()
        self._pending.append((q, fut))
        return fut

    def poll(self) -> int:
        from bench.spec import load_module

        batch, self._pending = self._pending[: self.max_batch], self._pending[self.max_batch:]
        for q, fut in batch:
            kind = load_module(self.root, "workloads", q.workload)
            fut._value = kind.expected(q.graph.n, q.graph.edges, q.args, window=self.WINDOW)
            fut._done = True
        return len(batch)

    @staticmethod
    def answer(q, result):
        return result

    @staticmethod
    def iterations(future):
        return None

    def bucket_representatives(self, pop):
        return pop[:1]

    def histogram(self, name):
        return (0, 0.0)

    def spans(self):
        return []

    def close(self):
        self._pending = []


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True, help="comma-separated")
    parser.add_argument("--seconds", type=float, required=True)
    args = parser.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(1, os.path.join(ROOT, "src"))
    from bench.harness import NoChip, run_cell

    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            line = run_cell(
                ROOT, args.workload, seed=seed, seconds=args.seconds, trace=False,
                t_process=time.perf_counter(), make_system=ControlSystem,
            )
        except NoChip as e:
            print(f"control: {e}", file=sys.stderr)
            return 2
        print(json.dumps({
            "workload": args.workload, "seed": seed, "control": f"support window cut to {ControlSystem.WINDOW}",
            "correct": line["correct"], "attempted": line["attempted"], "checks": line["checks"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
