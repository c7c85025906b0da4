"""From a JAX profiler trace to the device numbers of a traced run.

Two steps, kept apart so that the second can be checked on a small
recorded trace (``bench/tests/data``):

1. :func:`extract` reads the ``.xplane.pb`` that ``jax.profiler`` wrote
   and keeps what the benchmark uses, as plain lists: per device, the
   operations (``XLA Ops`` line) and the program executions
   (``XLA Modules`` line) with start and duration in ns; and the
   harness's own ``bench.*`` annotations on host threads.  Off a TPU
   (the CPU rehearsal) the device is the host's XLA client thread.
2. :func:`reduce` turns those into a :class:`Reduction` over the traced
   window, which is the harness's ``bench.window`` annotation:

   - ``busy_s``: the union of the intervals in which an operation ran,
     per device, averaged over the devices; ``window_s`` its length;
   - ``peel_s``: device time of the peel program's executions (module
     names starting ``jit_peel``), summed over the devices;
   - ``device_ops``: the operations that took most device time (leaf
     operations: a loop's time is its body's);
   - ``idle_gaps``: device idle time by what the harness was doing at
     the time (the server thread's annotation in progress).
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import shutil
import time

__all__ = ["Capture", "Reduction", "extract", "reduce", "union_ns"]

PEEL_MODULE_PREFIX = "jit_peel"
WINDOW = "bench.window"
# What the server thread does: a gap takes the server's label when it has one.
_SERVER_LABELS = ("bench.poll", "bench.server-idle")


def _stats(ev) -> dict:
    try:
        return {k: v for k, v in ev.stats}
    except (TypeError, ValueError):
        return {}


def extract(path: str) -> dict:
    """The parts of one ``.xplane.pb`` that :func:`reduce` reads."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: dict = {}
    host: list = []
    cpu_ops: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if key is None:
                    continue
                for ev in line.events:
                    # TPU op names carry their whole HLO instruction; keep
                    # the instruction's name.
                    name = ev.name.split(" = ", 1)[0].lstrip("%")
                    dev[key].append([name, float(ev.start_ns), float(ev.duration_ns)])
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host.append([line.name, ev.name, float(ev.start_ns), float(ev.duration_ns)])
                    elif line.name.startswith("tf_XLACpu") or line.name.startswith("tf_XLAPjRtCpu"):
                        st = _stats(ev)
                        if "hlo_module" in st:
                            cpu_ops.append(
                                [ev.name, float(ev.start_ns), float(ev.duration_ns), st["hlo_module"]]
                            )
    if not devices and cpu_ops:
        devices["/host:CPU"] = {
            "ops": [[n, s, d] for n, s, d, _ in cpu_ops],
            "modules": [[m, s, d] for _, s, d, m in cpu_ops],
        }
    return {"devices": devices, "host": host}


def union_ns(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged ``[start, end)`` intervals, clipped to ``[lo, hi)``."""
    spans = sorted((max(s, lo), min(s + d, hi)) for _, s, d, *_ in intervals)
    out: list[list[float]] = []
    for s, e in spans:
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class _Labels:
    """What the harness was doing over an interval: the server thread's
    annotation that covers most of it, else any other annotation's."""

    def __init__(self, host: list):
        server = [h for h in host if h[1] in _SERVER_LABELS]
        other = [h for h in host if h[1] not in _SERVER_LABELS and h[1] != WINDOW]
        self._lists = []
        for events in (server, other):
            events = sorted(events, key=lambda h: h[2])
            ends, top = [], float("-inf")
            for h in events:  # running maximum of ends, for the bisect below
                top = max(top, h[2] + h[3])
                ends.append(top)
            self._lists.append((events, ends))

    def __call__(self, lo: float, hi: float) -> str:
        for events, ends in self._lists:
            cover: dict = {}
            for h in events[bisect.bisect_right(ends, lo):]:
                if h[2] >= hi:
                    break
                overlap = min(h[2] + h[3], hi) - max(h[2], lo)
                if overlap > 0:
                    cover[h[1]] = cover.get(h[1], 0.0) + overlap
            if cover:
                return max(cover, key=cover.get)
        return "none"


def _leaves(ops: list) -> list:
    """The operations that hold no other: a ``while`` spans its body's
    operations on the same line, and would count their time again."""
    ordered = sorted(ops, key=lambda op: (op[1], -op[2]))
    return [
        op for op, nxt in zip(ordered, ordered[1:] + [None])
        if nxt is None or nxt[1] + nxt[2] > op[1] + op[2]
    ]


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float
    peel_s: float
    devices: int
    device_ops: list  # [[name, seconds]], most first
    idle_gaps: list  # [[label, seconds]], most first
    stop_s: float = 0.0  # host seconds the profiler took to stop and write
    read_s: float = 0.0  # ... and to read and reduce what it wrote

    def breakdown(self) -> dict:
        return {"device_ops": self.device_ops, "idle_gaps": self.idle_gaps}


def reduce(events: dict, *, top: int = 10) -> Reduction:
    """The traced window's device numbers (see the module docstring)."""
    windows = [(s, s + d) for _t, name, s, d in events["host"] if name == WINDOW]
    if not windows:
        raise ValueError("the trace holds no bench.window annotation")
    lo, hi = windows[0]
    devs = events["devices"]
    busy = peel = 0.0
    ops: dict = {}
    gaps: dict = {}
    label = _Labels(events["host"])
    for dev in devs.values():
        merged = union_ns(dev["ops"], lo, hi)
        busy += sum(e - s for s, e in merged)
        for name, s, d in dev["modules"]:
            if name.startswith(PEEL_MODULE_PREFIX):
                peel += max(0.0, min(s + d, hi) - max(s, lo))
        for name, s, d in _leaves(dev["ops"]):
            inside = min(s + d, hi) - max(s, lo)
            if inside > 0:
                ops[name] = ops.get(name, 0.0) + inside
        edges = [lo] + [x for se in merged for x in se] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                what = label(a, b)
                gaps[what] = gaps.get(what, 0.0) + (b - a)
    n = max(1, len(devs))

    def ranked(table: dict) -> list:
        rows = sorted(table.items(), key=lambda kv: -kv[1])[:top]
        return [[name, ns / n / 1e9] for name, ns in rows]

    return Reduction(
        window_s=(hi - lo) / 1e9,
        busy_s=busy / n / 1e9,
        peel_s=peel / 1e9,
        devices=len(devs),
        device_ops=ranked(ops),
        idle_gaps=ranked(gaps),
    )


class Capture:
    """One profiler trace of the window, reduced and then deleted."""

    def __init__(self, logdir: str):
        self.logdir = logdir

    def start(self) -> None:
        import jax

        # Device operations and the harness's annotations only: the
        # Python tracer would record every Python call of the window.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        options.enable_hlo_proto = False
        jax.profiler.start_trace(self.logdir, profiler_options=options)

    def stop(self) -> Reduction:
        import jax

        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        t1 = time.perf_counter()
        try:
            paths = glob.glob(os.path.join(self.logdir, "**", "*.xplane.pb"), recursive=True)
            if not paths:
                raise RuntimeError(f"the profiler wrote no trace under {self.logdir}")
            events = extract(paths[0])
            reduction = reduce(events)
            reduction.stop_s, reduction.read_s = t1 - t0, time.perf_counter() - t1
            return reduction
        finally:
            shutil.rmtree(self.logdir, ignore_errors=True)
