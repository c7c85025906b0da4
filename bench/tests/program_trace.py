#!/usr/bin/env python3
"""What a traced serving run shows of the program's own spans and scopes,
beyond what ``bench/trace_reduce.py`` reads yet.

    python3 bench/tests/program_trace.py --workload kron7-serve --seed 1 [--seconds 12]

Temporary: once ``bench/trace_reduce.py`` reads the ``support`` scope and
labels idle gaps by the program's ``repro.*`` annotations, this file and
its test go (PERF.md, Open questions).

Runs the cell once through ``bench.harness.run_cell`` with the program's
tracer on and a JAX profiler trace taken around the whole run, then reads
that trace with ``bench.trace_reduce`` (``extract`` and ``reduce``) and
adds, under ``"program"``:

- ``scopes_s`` and ``ms_per_trip``: device time of the peel program's
  leaf operations under the ``support`` and ``prune`` scopes of
  ``repro.exec.peel.build_peel`` (and under neither), over the window and
  per peel trip.  An operation's scope is the ``op_name`` of its HLO
  instruction in the session's compiled peel
  (``CompileCache.executors()``, ``PeelExecutor.compiled_text()``), joined
  by the instruction's name;
- ``idle_gaps``: the device's idle time by label, as the reduction labels
  it, except that an idle gap inside ``bench.poll`` takes the name of the
  server thread's ``repro.*`` annotation (``repro.obs.trace``) that covers
  most of it; a gap no program span covers keeps ``bench.poll``;
- ``device_ms_per_trip``: what the ``device_ms_per_trip.serve`` reader
  reads from this run, beside the exact number (each ``jit_peel``
  execution joined to its batch, the n-th execution being batch ``n``,
  over the batches whose execution lies wholly inside the window) and
  the plain window-clipped reading (all ``jit_peel`` time in the window
  over the trips read back in it);
- ``spans_per_batch``.

The line's ``metrics`` are the cell's end-to-end metrics other than
``setup_s``, read with tracing on, to set beside an untraced run's (what
tracing costs).  Chip
numbers come only from a run on the chip; on the CPU (``--cpu``) it
rehearses.
"""

import argparse
import glob
import json
import os
import re
import shutil
import statistics
import sys
import tempfile
import time

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir, os.pardir))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, trace_reduce  # noqa: E402
from bench.record import RunRecord  # noqa: E402
from bench.spec import load_module, load_reader  # noqa: E402

PREFIX = "repro."
SCOPES = ("support", "prune")
_INSTRUCTION = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*op_name="([^"]*)"')


def program_annotations(path: str) -> list:
    """The program's ``repro.*`` host annotations of one ``.xplane.pb`` as
    ``[thread, name, start_ns, duration_ns]`` rows, the shape of
    ``trace_reduce.extract``'s ``host`` rows (which keep ``bench.*`` only)."""
    from jax.profiler import ProfileData

    return [
        [line.name, ev.name, float(ev.start_ns), float(ev.duration_ns)]
        for plane in ProfileData.from_file(path).planes if plane.name == "/host:CPU"
        for line in plane.lines
        for ev in line.events if ev.name.startswith(PREFIX)
    ]


def instruction_scopes(hlo_texts) -> dict:
    """``{instruction name: "support" | "prune"}`` over the peel programs'
    HLO text; a name two programs scope differently is left out."""
    scopes: dict = {}
    clash = set()
    for text in hlo_texts:
        for line in text.splitlines():
            m = _INSTRUCTION.match(line)
            if not m:
                continue
            parts = m.group(2).split("/")
            scope = next((s for s in SCOPES if s in parts), None)
            if scope is None:
                continue
            if scopes.setdefault(m.group(1), scope) != scope:
                clash.add(m.group(1))
    for name in clash:
        del scopes[name]
    return scopes


def _peel_ops(dev: dict) -> list:
    """A device's operations that ran inside a ``jit_peel`` execution."""
    peel = sorted((s, s + d) for name, s, d in dev["modules"]
                  if name.startswith(trace_reduce.PEEL_MODULE_PREFIX))
    ops, i = [], 0
    for op in sorted(dev["ops"], key=lambda op: op[1]):
        while i < len(peel) and peel[i][1] <= op[1]:
            i += 1
        if i < len(peel) and peel[i][0] <= op[1]:
            ops.append(op)
    return ops


def scope_seconds(events: dict, scopes: dict, lo: float, hi: float) -> dict:
    """Device seconds of the peel program's leaf operations inside
    ``[lo, hi)`` by scope (summed over devices)."""
    out = dict.fromkeys((*SCOPES, "unscoped"), 0.0)
    for dev in events["devices"].values():
        for name, s, d in trace_reduce._leaves(_peel_ops(dev)):
            inside = min(s + d, hi) - max(s, lo)
            if inside > 0:
                out[scopes.get(name, "unscoped")] += inside / 1e9
    return out


def label_gaps(events: dict, program: list, lo: float, hi: float) -> dict:
    """Device idle seconds in ``[lo, hi)`` by label (see the module
    docstring), averaged over devices."""
    base = trace_reduce._Labels(events["host"])
    server = {t for t, n, _s, _d in events["host"] if n == "bench.poll"}
    inner = trace_reduce._Labels([row for row in program if row[0] in server])
    gaps: dict = {}
    for dev in events["devices"].values():
        merged = trace_reduce.union_ns(dev["ops"], lo, hi)
        edges = [lo] + [x for se in merged for x in se] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            what = base(a, b)
            if what == "bench.poll":
                what = inner(a, b)
                what = "bench.poll" if what == "none" else what
            gaps[what] = gaps.get(what, 0.0) + (b - a)
    n = max(1, len(events["devices"]))
    return {k: v / n / 1e9 for k, v in sorted(gaps.items(), key=lambda kv: -kv[1])}


def exact_ms_per_trip(events: dict, trips: dict, lo: float, hi: float) -> float | None:
    """``jit_peel`` device time over trips, for the batches whose execution
    lies wholly inside ``[lo, hi)``; the n-th execution on a device is
    batch ``n`` (one dispatch a batch, the trace taken from before the
    first).  ``None`` off a TPU, where an execution is not one event."""
    ns = count = 0
    for plane, dev in events["devices"].items():
        if not plane.startswith("/device:"):
            return None
        runs = sorted((s, d) for name, s, d in dev["modules"]
                      if name.startswith(trace_reduce.PEEL_MODULE_PREFIX))
        if len(runs) != len(trips):
            return None
        for batch, (s, d) in enumerate(runs):
            if lo <= s and s + d <= hi:
                ns += d
                count += trips[batch]
    return 1e3 * ns / 1e9 / count if count else None


def run(root: str, workload: str, *, seed: int, seconds: float, require_tpu: bool = True) -> dict:
    """One traced run of the cell; its result line with ``"program"`` added."""
    import jax

    kept: dict = {}

    def make_system(cell, *, trace):
        system = load_module(root, "systems", cell.traffic.get("system", "session")).System(
            cell, trace=True)
        kept["cell"], kept["session"] = cell, system.session
        return system

    # The persistent compile cache keys a program without its debug
    # information, so an executable compiled before the scopes existed
    # would be found and read back without them: key this run's with it.
    key_metadata = jax.config.jax_compilation_cache_include_metadata_in_key
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.devices()  # the backend is up before the profiler starts
    logdir = tempfile.mkdtemp(prefix="program-trace-")
    trace_reduce.Capture(logdir).start()
    try:
        line = harness.run_cell(root, workload, seed=seed, seconds=seconds, trace=False,
                                t_process=time.perf_counter(), require_tpu=require_tpu,
                                make_system=make_system)
    finally:
        jax.profiler.stop_trace()
        jax.config.update("jax_compilation_cache_include_metadata_in_key", key_metadata)
    try:
        [path] = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
        events = trace_reduce.extract(path)
        program = program_annotations(path)
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    reduction = trace_reduce.reduce(events)
    # Set-up here starts after JAX's start-up and under the profiler:
    # not the benchmark's ``setup_s``.
    line["metrics"].pop("setup_s", None)
    [(lo, d)] = [(s, d) for _t, n, s, d in events["host"] if n == trace_reduce.WINDOW]
    hi = lo + d

    # The program's Chrome spans on the profiler's clock: each span is
    # also an annotation, so the n-th ``unpack`` of each is the same one.
    session = kept["session"]
    spans = [ev for ev in session.obs.tracer.events() if ev.get("ph") == "X"]
    unpacks = sorted((ev for ev in spans if ev["name"] == "unpack"), key=lambda ev: ev["ts"])
    marks = sorted(s for _t, n, s, _d in program if n == PREFIX + "unpack")
    assert len(marks) == len(unpacks), (len(marks), len(unpacks))
    offset_us = statistics.median(m / 1e3 - ev["ts"] for m, ev in zip(marks, unpacks))
    w0, close = (lo / 1e3 - offset_us) / 1e6, (hi / 1e3 - offset_us) / 1e6
    in_window = [ev for ev in spans if w0 <= ev["ts"] / 1e6 <= close]
    record = RunRecord(cell=kept["cell"], seconds=seconds, setup_s=0.0, window_start=w0,
                       window_close=close, queries=[], device_kind=line["device"]["kind"],
                       chips=kept["cell"].chips, spans=in_window, profile=reduction)

    trips = {ev["args"]["batch"]: ev["args"]["trips"] for ev in unpacks}
    window_unpacks = [ev for ev in in_window if ev["name"] == "unpack"]
    window_trips = sum(ev["args"]["trips"] for ev in window_unpacks)
    scopes = instruction_scopes(
        text for text in (exe.compiled_text() for exe in session.cache.executors()) if text)
    secs = scope_seconds(events, scopes, lo, hi)
    gaps = label_gaps(events, program, lo, hi)
    poll_idle = sum(v for k, v in gaps.items() if k == "bench.poll" or k.startswith(PREFIX))
    batches = len(window_unpacks)
    line["device"].update(busy_s=reduction.busy_s, window_s=reduction.window_s)
    line["breakdown"] = reduction.breakdown()
    line["program"] = {
        "scopes_s": secs,
        "trips": window_trips,
        "ms_per_trip": {k: 1e3 * v / window_trips for k, v in secs.items()} if window_trips else {},
        "scoped_instructions": len(scopes),
        "idle_gaps": gaps,
        "poll_idle_labelled_pct": (
            100.0 * sum(v for k, v in gaps.items() if k.startswith(PREFIX)) / poll_idle
            if poll_idle else None),
        "device_ms_per_trip": {
            "reader": load_reader(root, "device_ms_per_trip.serve")(record),
            "exact": exact_ms_per_trip(events, trips, lo, hi),
            "window_clipped": 1e3 * reduction.peel_s / window_trips if window_trips else None,
        },
        "spans_per_batch": len(in_window) / batches if batches else None,
        "batches": batches,
    }
    return line


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--seconds", type=float, default=12.0)
    parser.add_argument("--cpu", action="store_true", help="rehearse without a TPU")
    args = parser.parse_args()
    line = run(ROOT, args.workload, seed=args.seed, seconds=args.seconds,
               require_tpu=not args.cpu)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
