"""One run of one cell: set-up, the measured window, the check, the line.

``run_cell`` is the whole run that ``bench/run.py`` makes; tests call it
with ``require_tpu=False`` to rehearse a cell on the CPU, and
``bench/control.py`` calls it with another system in the program's place.
The system under test is the one the traffic mix names
(``bench/systems/<name>.py``, default ``session``).

The window: a closed loop sends a client's next query when its last one
is answered, starting new queries while less than ``seconds`` have
passed, and closes at the last answer.  An open loop sends each query at
its due time, closes at ``seconds``, and then waits (up to a minute) for
the answers still owed.  A server thread calls the system's ``poll()``
the whole time.  Nothing else runs in the process.

Once the window has closed, the device's memory peak is read, the
system is dropped, and every answer the window produced is compared
with the plain reference (``bench/reference.py``).
"""

from __future__ import annotations

import gc
import math
import os
import sys
import tempfile
import threading
import time
import traceback

from . import loadgen
from .record import QueryRecord, RunRecord
from .spec import load_cell, load_module, load_reader

__all__ = ["run_cell", "NoChip", "compare"]

LATE_ANSWER_GRACE_S = 60.0


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


class _Load:
    """Sends the window's queries and the server thread that answers them."""

    def __init__(self, system, log):
        self.system = system
        self.log = log
        self._lock = threading.Lock()
        self._outstanding: dict[int, QueryRecord] = {}
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._serve, name="bench-server", daemon=True)

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._wake.set()
        self._thread.join(timeout=LATE_ANSWER_GRACE_S + 60)
        if self._thread.is_alive():
            raise RuntimeError("the server thread did not stop")

    def send(self, rec: QueryRecord) -> None:
        rec.sent = time.perf_counter()
        with self._lock:
            rec.future = self.system.submit(rec.payload)
            self._outstanding[id(rec)] = rec
        self._wake.set()

    def _serve(self) -> None:
        while not self._stop.is_set():
            self._wake.clear()
            t0 = time.perf_counter()
            with _annotate("bench.poll"):
                try:
                    n = self.system.poll()
                except Exception:  # the batch's futures carry the failure
                    self.log(traceback.format_exc())
                    n = 1
            t1 = time.perf_counter()
            if n:
                self._collect(t0, t1)
            else:
                with _annotate("bench.server-idle"):
                    self._wake.wait(0.01)

    def _collect(self, t0: float, t1: float) -> None:
        with self._lock:
            done = [r for r in self._outstanding.values() if r.future.done()]
            for r in done:
                del self._outstanding[id(r)]
        for r in done:
            r.poll_start, r.done_at = t0, t1
            try:
                result = r.future.result()
            except Exception as e:  # a failed query: counted, never hidden
                r.failed, r.error = True, f"{type(e).__name__}: {e}"
            else:
                r.answer = self.system.answer(r.query, result)
                r.iterations = self.system.iterations(r.future)
            r.event.set()

    def wait_all(self, records, deadline: float) -> None:
        for r in records:
            r.event.wait(max(0.0, deadline - time.perf_counter()))


def _closed_loop(load, system, traffic, seconds, log) -> tuple[list, float, float]:
    """Every client sends, waits, sends again while the window is open."""
    records: list[QueryRecord] = []
    lock = threading.Lock()
    w0 = time.perf_counter()

    def client(c: int) -> None:
        i = c
        nxt = QueryRecord(traffic.query(i))
        nxt.payload = system.prepare(nxt.query)
        while time.perf_counter() - w0 < seconds:
            rec = nxt
            rec.target = time.perf_counter()
            load.send(rec)
            with lock:
                records.append(rec)
            i += traffic.clients
            nxt = QueryRecord(traffic.query(i))
            nxt.payload = system.prepare(nxt.query)
            with _annotate("bench.client-wait"):
                rec.event.wait(seconds + LATE_ANSWER_GRACE_S)
            if not rec.event.is_set():
                log(f"query {rec.query.index} unanswered after {seconds + LATE_ANSWER_GRACE_S}s")
                return

    threads = [threading.Thread(target=client, args=(c,)) for c in range(traffic.clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    ends = [r.done_at for r in records if r.done_at is not None]
    return records, w0, max(ends) if ends else time.perf_counter()


def _open_loop(load, schedule, seconds, log) -> tuple[list, float, float]:
    """Every query sent at its due time, whatever the system does."""
    w0 = time.perf_counter()
    for rec in schedule:
        rec.target = w0 + rec.query.due
        delay = rec.target - time.perf_counter()
        if delay > 0:
            with _annotate("bench.wait-due"):
                time.sleep(delay)
        load.send(rec)
    close = w0 + seconds
    delay = close - time.perf_counter()
    if delay > 0:
        time.sleep(delay)
    late = [r.sent - r.target for r in schedule]
    if late:
        log(
            f"generator lateness: mean {sum(late) / len(late) * 1e3:.3f} ms, "
            f"max {max(late) * 1e3:.3f} ms over {len(late)} queries"
        )
    return schedule, w0, close


def compare(root: str, cell, records) -> dict:
    """Every answer against the plain reference: ``{name: (value, limit)}``.

    Each query kind of the cell's mix (``bench/workloads/<kind>.py``)
    names the number it adds to (``edges_wrong``: edges whose trussness
    or k-truss membership differs; ``kmax_wrong``: kmax answers that
    differ); ``failed`` counts queries that raised, ``unanswered`` those
    that never came back.  The guarantee is exactness, so every limit is 0.
    """
    kinds = {w["workload"] for w in cell.traffic["mix"]} | {r.query.workload for r in records}
    kinds = {name: load_module(root, "workloads", name) for name in sorted(kinds)}
    counts = {kind.CHECK: 0 for kind in kinds.values()}
    failed = unanswered = 0
    expected: dict = {}
    for r in records:
        q = r.query
        if r.failed:
            failed += 1
            continue
        if r.done_at is None:
            unanswered += 1
            continue
        kind = kinds[q.workload]
        key = (id(q.graph), q.workload, tuple(sorted(q.args.items())))
        if key not in expected:
            expected[key] = kind.expected(q.graph.n, q.graph.edges, q.args)
        counts[kind.CHECK] += kind.wrong(r.answer, expected[key])
    counts.update(failed=failed, unanswered=unanswered)
    return {name: (value, 0) for name, value in counts.items()}


def _devices(cell, require_tpu: bool):
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and platform != "tpu":
        raise NoChip(f"no TPU found (JAX platform: {platform})")
    if len(devices) < cell.chips:
        raise NoChip(f"the cell asks for {cell.chips} chips; JAX sees {len(devices)}")
    return devices[: cell.chips]


def _enable_compile_cache(root: str) -> str:
    """JAX's persistent compile cache: ``$JAX_COMPILATION_CACHE_DIR``, else
    the fixed ``<checkout>/.jax_cache``, so that only a checkout's first
    run of a cell compiles."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


class _CompileCounter:
    """Counts XLA compile requests (compiles and cache loads) in the process."""

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.count += 1


def _memory_peak(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run_cell(
    root: str,
    workload: str,
    *,
    seed: int,
    seconds: float,
    trace: bool,
    t_process: float,
    require_tpu: bool = True,
    make_system=None,
    traffic_override: dict | None = None,
    log=None,
) -> dict:
    """One run of the cell; returns the result line as a dict.

    ``t_process`` is the ``time.perf_counter()`` reading taken when the
    process started: set-up is timed from there.  ``make_system(cell,
    trace=)`` builds the system under test (default: the ``System`` of
    the file ``bench/systems/`` holds under the traffic mix's name).
    """
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    cell = load_cell(root, workload)
    if traffic_override:
        cell.traffic = {**cell.traffic, **traffic_override}
    devices = _devices(cell, require_tpu)
    if trace and "trace_seconds" in cell.traffic:
        # A profiler trace of a busy window is slow to stop and read: the
        # traced run of such a cell is shorter (its per-layer metrics are
        # read over it; its end-to-end metrics are not reported).
        seconds = min(seconds, float(cell.traffic["trace_seconds"]))
    _enable_compile_cache(root)
    compiles = _CompileCounter()
    if make_system is None:
        make_system = load_module(root, "systems", cell.traffic.get("system", "session")).System
    system = make_system(cell, trace=trace)

    pop = loadgen.population(root, cell.config)
    traffic = loadgen.Traffic(cell.traffic, pop, seed, seconds)
    load = _Load(system, log)
    load.start()
    # Set-up: warm every shape bucket the population hits, once.
    for g in system.bucket_representatives(pop):
        rec = QueryRecord(traffic.warmup(g))
        rec.payload = system.prepare(rec.query)
        load.send(rec)
        rec.event.wait()
        if rec.failed:
            raise RuntimeError(f"the warm-up query failed: {rec.error}")
    schedule = []
    if traffic.loop == "open":
        for q in traffic.schedule():
            rec = QueryRecord(q)
            rec.payload = system.prepare(q)
            schedule.append(rec)
    hist_names = ("batch_occupancy",)
    hist0 = {name: system.histogram(name) for name in hist_names}
    compiles0 = compiles.count

    profiler = None
    if trace:
        from .trace_reduce import Capture

        profiler = Capture(tempfile.mkdtemp(prefix="bench-trace-"))
        profiler.start()
    setup_s = time.perf_counter() - t_process
    with _annotate("bench.window"):
        if traffic.loop == "closed":
            records, w0, close = _closed_loop(load, system, traffic, seconds, log)
        elif traffic.loop == "open":
            records, w0, close = _open_loop(load, schedule, seconds, log)
        else:
            raise ValueError(f"unknown loop {traffic.loop!r}")
    reduction = profiler.stop() if profiler is not None else None
    compiles_in_window = compiles.count - compiles0
    load.wait_all(records, close + LATE_ANSWER_GRACE_S)
    load.stop()
    memory_peak = _memory_peak(devices)
    hist = {
        name: tuple(b - a for a, b in zip(hist0[name], system.histogram(name)))
        for name in hist_names
    }
    spans = [
        ev for ev in (system.spans() if trace else [])
        if ev.get("ph") == "X" and w0 <= ev["ts"] / 1e6 <= close
    ]
    system.close()
    for r in records:
        r.future = r.payload = None
    del system, load
    gc.collect()

    run = RunRecord(
        cell=cell,
        seconds=seconds,
        setup_s=setup_s,
        window_start=w0,
        window_close=close,
        queries=records,
        device_kind=devices[0].device_kind,
        chips=cell.chips,
        spans=spans,
        histograms=hist,
        profile=reduction,
    )
    if compiles_in_window:
        log(f"warning: {compiles_in_window} XLA compile requests inside the window")
    checks = compare(root, cell, records)
    metrics = {}
    wanted = cell.per_layer if trace else cell.end_to_end
    for m in wanted:
        value = _read_metric(root, m["name"], run, per_layer=trace)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": memory_peak,
    }
    line = {
        "correct": all(v <= limit for v, limit in checks.values()),
        "attempted": len(records),
        "failed": checks["failed"][0] + checks["unanswered"][0],
        "metrics": metrics,
        "device": device,
    }
    if reduction is not None:
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
        line["breakdown"] = reduction.breakdown()
    # Diagnostics beside the metrics: what a reader of the line needs to
    # trust them.
    line["run"] = {
        "compiles_in_window": compiles_in_window,
        "answered_in_window": len(run.answered_in_window()),
        "backlog_mean_halves": [
            _mean_backlog(records, w0, (w0 + close) / 2),
            _mean_backlog(records, (w0 + close) / 2, close),
        ],
        "backlog_at_close": _backlog(records, close),
        "generator_late_ms_max": 1e3 * max((r.sent - r.target for r in records), default=0.0),
        "query_ms": _quantiles_ms(records, w0),
        "window_s": close - w0,
    }
    if reduction is not None:
        line["run"]["trace_stop_s"] = reduction.stop_s
        line["run"]["trace_read_s"] = reduction.read_s
    line["checks"] = {k: {"value": v, "limit": limit} for k, (v, limit) in checks.items()}
    return line


def _backlog(records, t: float) -> int:
    """Queries sent by ``t`` and not answered by then."""
    return sum(
        1 for r in records
        if r.sent is not None and r.sent <= t and (r.done_at is None or r.done_at > t)
    )


def _mean_backlog(records, t0: float, t1: float, samples: int = 200) -> float:
    """Time-mean backlog over ``[t0, t1)``."""
    step = (t1 - t0) / samples
    return sum(_backlog(records, t0 + (i + 0.5) * step) for i in range(samples)) / samples


def _quantiles_ms(records, w0: float) -> dict:
    """Send-to-answer times of the answered queries: median, max (with
    when, after the window opened, the slowest was sent), and how many
    took over 1.5 times the median (a stall, or the whole run slow)."""
    done = sorted((r.done_at - r.sent, r.sent - w0) for r in records if r.done_at is not None)
    if not done:
        return {}
    median = done[len(done) // 2][0]
    return {"p50": 1e3 * median, "max": 1e3 * done[-1][0], "max_sent_at_s": done[-1][1],
            "over_1.5x_p50": sum(1 for t, _ in done if t > 1.5 * median)}


def _read_metric(root: str, name: str, run: RunRecord, *, per_layer: bool):
    if name == "setup_s":
        return run.setup_s
    folder = "layer_metrics" if per_layer else "end_to_end"
    value = load_reader(root, name, folder)(run)
    if value is None or (isinstance(value, float) and math.isnan(value)):
        return None
    return float(value)
