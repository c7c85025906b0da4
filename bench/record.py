"""What one run recorded, and the helpers the metric readers share.

A reader (``bench/end_to_end/<name>.py`` or
``bench/layer_metrics/<name>.py``) is a function ``read(run)`` of a
:class:`RunRecord`; it returns a number, or ``None`` when the run holds
nothing for it to read (the metric is then left out of the line).
Times are ``time.perf_counter`` seconds, the clock of the program's own
spans.
"""

from __future__ import annotations

import dataclasses
import math
import threading

__all__ = [
    "QueryRecord",
    "RunRecord",
    "p95",
    "span_self_seconds",
    "latency_p95_ms",
    "pack_ms_per_batch",
    "device_idle_pct",
]


@dataclasses.dataclass
class QueryRecord:
    query: object  # bench.loadgen.Query
    payload: object = None  # what was submitted to the system
    target: float = 0.0  # when it was due (open loop) or sent (closed)
    sent: float | None = None
    poll_start: float | None = None  # start of the poll() that resolved it
    done_at: float | None = None
    failed: bool = False
    error: str | None = None
    answer: object = None  # as the query kind's from_program reads it
    iterations: int | None = None  # the program's QueryStats.iterations
    future: object = None
    event: threading.Event = dataclasses.field(default_factory=threading.Event)

    @property
    def latency(self) -> float:
        """Due (or send) time to result; a failed or missing answer is
        slower than any other."""
        if self.failed or self.done_at is None:
            return math.inf
        return self.done_at - self.target


@dataclasses.dataclass
class RunRecord:
    cell: object  # bench.spec.Cell
    seconds: float
    setup_s: float
    window_start: float
    window_close: float  # open loop: start + seconds; closed: last completion
    queries: list[QueryRecord]
    device_kind: str
    chips: int
    spans: list[dict] = dataclasses.field(default_factory=list)
    histograms: dict = dataclasses.field(default_factory=dict)  # name -> (count, sum)
    profile: object = None  # bench.trace_reduce.Reduction (traced runs)

    def answered_in_window(self) -> list[QueryRecord]:
        return [
            r for r in self.queries
            if not r.failed and r.done_at is not None and r.done_at <= self.window_close
        ]


def p95(values) -> float:
    """Nearest-rank 95th percentile (``inf`` counts as largest)."""
    xs = sorted(values)
    if not xs:
        return math.nan
    return xs[max(0, math.ceil(0.95 * len(xs)) - 1)]


def span_self_seconds(spans: list[dict], name: str) -> tuple[int, float]:
    """``(count, total self seconds)`` of the program's ``name`` spans:
    each span's duration less the part its child spans on the same
    thread cover."""
    by_tid: dict = {}
    for ev in spans:
        if ev.get("ph") == "X":
            by_tid.setdefault(ev.get("tid"), []).append(ev)
    count, total = 0, 0.0
    for evs in by_tid.values():
        evs.sort(key=lambda e: (e["ts"], -e["dur"]))
        for i, ev in enumerate(evs):
            if ev["name"] != name:
                continue
            end = ev["ts"] + ev["dur"]
            child, covered = ev["ts"], 0.0
            for other in evs[i + 1:]:
                if other["ts"] >= end:
                    break
                if other["ts"] >= child:  # a direct child: nested, not overlapping
                    covered += min(other["ts"] + other["dur"], end) - other["ts"]
                    child = other["ts"] + other["dur"]
            count += 1
            total += (ev["dur"] - covered) / 1e6
    return count, total


def latency_p95_ms(run: RunRecord) -> float | None:
    """p95 over every query of the window, from its due time to its answer."""
    return 1e3 * p95(r.latency for r in run.queries) if run.queries else None


def pack_ms_per_batch(run: RunRecord) -> float | None:
    """Mean self time of the program's ``pack`` span per batch."""
    count, total = span_self_seconds(run.spans, "pack")
    return 1e3 * total / count if count else None


def device_idle_pct(run: RunRecord) -> float | None:
    """Share of the traced window with no operation on the device, mean
    over the cell's chips."""
    p = run.profile
    if p is None or p.window_s <= 0:
        return None
    return 100.0 * (1.0 - p.busy_s / p.window_s)
