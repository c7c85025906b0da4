"""repro.obs: tracing, metrics, peel telemetry, and the stats() contracts.

Locks the observable surface other tooling depends on:

* key sets of ``Session.stats()`` / ``CacheStats.snapshot()`` /
  ``obs.metrics_snapshot()`` (extend, don't rename);
* a traced ``solve()`` writes Chrome trace-event JSON that
  ``json.loads`` with well-formed ``ph``/``ts``/``dur`` fields;
* deadline handling runs on the obs clock (fake-able, no sleeping);
* per-session metric isolation (the ``ENUM_COUNTS`` global is only a
  deprecated aggregate view);
* the removed ``repro.service.cache`` / ``repro.service.batcher`` shims
  stay gone (ImportError, not a silent resurrection).
"""

import importlib
import json
import sys
import warnings

import pytest

from repro import obs
from repro.api import Session, TrussQuery, solve
from repro.api.cache import CacheStats
from repro.api.errors import TrussTimeoutError
from repro.graphs import erdos, rmat

SESSION_STATS_KEYS = {
    "requests_served",
    "batches_run",
    "device_dispatches",
    "deadline_misses",
    "pending",
    "device_time_s",
    "retries",
    "backend_fallbacks",
    "queries_quarantined",
    "batch_bisects",
    "queries_failed",
    "queries_shed",
    "faults_injected",
    "cache_compiles",
    "cache_hits",
    "cache_hit_rate",
    "planner_queries_planned",
    "planner_plan_time_s",
    "planner_plan_us_per_query",
    "planner_backends",
}

CACHE_SNAPSHOT_KEYS = {"compiles", "hits", "hit_rate"}

STREAM_STATS_KEYS = {
    "updates_applied",
    "update_dispatches",
    "edges_repeeled",
    "edges",
    "kmax",
    "cached_triangles",
    "checkpoints_written",
}

SPAN_NAMES = {"solve", "plan", "pack", "compile", "dispatch", "device-wait", "unpack"}


@pytest.fixture(scope="module")
def graphs():
    return [erdos(60, 6.0, seed=3), rmat(6, 6, seed=4)]


@pytest.fixture(scope="module")
def traced(tmp_path_factory, graphs):
    """One traced mixed-workload solve, shared across assertions."""
    path = tmp_path_factory.mktemp("obs") / "trace.json"
    s = Session(trace=str(path), max_batch=4)
    res = s.solve(
        [
            TrussQuery.decompose(graphs[0]),
            TrussQuery.decompose(graphs[1]),
            TrussQuery.kmax(graphs[0]),
            TrussQuery.ktruss(graphs[1], k=3),
        ]
    )
    return s, res, path


# --------------------------------------------------------------------- #
# stats() key-set snapshots
# --------------------------------------------------------------------- #
def test_session_stats_keys_locked(traced):
    s, res, _ = traced
    assert len(res) == 4
    assert set(s.stats().keys()) == SESSION_STATS_KEYS


def test_session_stats_values_are_metric_views(traced):
    s, _, _ = traced
    st = s.stats()
    assert st["requests_served"] == 4
    assert st["device_dispatches"] == st["batches_run"] >= 1
    assert st["deadline_misses"] == 0
    assert st["device_time_s"] > 0
    # the same numbers via the registry directly
    assert s.obs.metrics.value("requests_served") == 4
    assert s.obs.metrics.value("dispatches") == st["device_dispatches"]


def test_cache_stats_snapshot_keys():
    cs = CacheStats()
    assert set(cs.snapshot().keys()) == CACHE_SNAPSHOT_KEYS
    cs.record_compile()
    cs.record_hit()
    assert cs.compiles == 1 and cs.hits == 1
    assert cs.snapshot()["hit_rate"] == 0.5


def test_stream_stats_keys(graphs):
    s = Session(max_batch=2)
    stream = s.open_stream(graphs[0])
    assert set(stream.stats().keys()) == STREAM_STATS_KEYS


def test_metrics_snapshot_structure(traced):
    s, _, _ = traced
    snap = s.metrics_snapshot()
    assert set(snap.keys()) == {"counters", "gauges", "histograms"}
    assert snap["counters"]["requests_served"] == 4
    assert "queue_depth" in snap["gauges"]
    occ = snap["histograms"]["batch_occupancy"]
    # histogram rows carry the full summary, cumulative buckets included
    for field in ("count", "sum", "min", "max", "mean", "buckets"):
        assert field in occ
    assert occ["count"] >= 1


# --------------------------------------------------------------------- #
# Chrome trace JSON
# --------------------------------------------------------------------- #
def test_traced_solve_exports_chrome_trace(traced):
    _, _, path = traced
    data = json.loads(path.read_text())
    events = data["traceEvents"]
    assert events, "traced solve produced no events"
    names = set()
    for ev in events:
        assert ev["ph"] in {"X", "i"}
        assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
        if ev["ph"] == "X":
            assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0
        assert "pid" in ev and "tid" in ev
        names.add(ev["name"])
    # every stage of the query path shows up as a span
    assert SPAN_NAMES <= names
    # spans carry their workload attributes
    plan = next(e for e in events if e["name"] == "plan")
    assert "workload" in plan["args"] and "backend" in plan["args"]
    compile_ev = next(e for e in events if e["name"] == "compile")
    assert "hit" in compile_ev["args"]


def test_trace_env_var(tmp_path, graphs, monkeypatch):
    path = tmp_path / "env_trace.json"
    monkeypatch.setenv(obs.TRACE_ENV_VAR, str(path))
    solve(TrussQuery.decompose(graphs[0]))
    data = json.loads(path.read_text())
    assert any(e["name"] == "solve" for e in data["traceEvents"])


def test_trace_disabled_is_noop(graphs):
    s = Session(trace=False, max_batch=2)
    s.solve([TrussQuery.decompose(graphs[0])])
    assert s.obs.tracer is obs.NULL_TRACER
    assert not s.obs.tracing
    assert s.export_trace() is None


# --------------------------------------------------------------------- #
# Peel telemetry: the paper's imbalance statistic, observed at runtime
# --------------------------------------------------------------------- #
def test_peel_telemetry_recorded_per_bucket_backend(traced):
    s, _, _ = traced
    hists = s.metrics_snapshot()["histograms"]
    imb = {k: v for k, v in hists.items() if k.startswith("peel_batch_imbalance")}
    assert imb, "no peel_batch_imbalance histograms recorded"
    for key, row in imb.items():
        assert "bucket=" in key and "backend=" in key
        assert row["min"] >= 1.0  # max/mean per-slot iters is >= 1 by definition
    rows = obs.imbalance_summary(s.obs.metrics)
    assert rows and all("bucket" in r and "backend" in r for r in rows)
    # per-slot and per-level histograms ride along
    assert any(k.startswith("peel_slot_iters") for k in hists)
    assert any(k.startswith("peel_level_edges") for k in hists)


# --------------------------------------------------------------------- #
# Prometheus text exposition
# --------------------------------------------------------------------- #
def test_prometheus_text_format(traced):
    s, _, _ = traced
    text = s.prometheus_text()
    lines = text.splitlines()
    assert "# TYPE requests_served counter" in lines
    assert "requests_served 4" in lines
    assert "# TYPE queue_depth gauge" in lines
    assert "# TYPE batch_occupancy histogram" in lines
    assert any(
        line.startswith("batch_occupancy_bucket{") and 'le="+Inf"' in line
        for line in lines
    )
    assert any(line.startswith("batch_occupancy_sum ") for line in lines)
    assert any(line.startswith("batch_occupancy_count ") for line in lines)


# --------------------------------------------------------------------- #
# Deadlines run on the obs clock (fake-able: no sleeping in this test)
# --------------------------------------------------------------------- #
def test_deadline_miss_on_fake_clock(graphs):
    clock = obs.FakeClock()
    with obs.use_clock(clock):
        s = Session(max_batch=2)
        fut = s.submit(TrussQuery.decompose(graphs[0], deadline_s=5.0))
        assert fut.request.time_remaining() == pytest.approx(5.0)
        clock.advance(10.0)  # deadline blown without any wall time passing
        assert fut.request.time_remaining() == 0.0
        with pytest.raises(TrussTimeoutError) as ei:
            fut.result()  # default timeout = remaining deadline budget
        assert s.deadline_misses == 1
        assert s.stats()["deadline_misses"] == 1
        # shed_on_timeout (the default): the query was marked dead and its
        # queue slot reclaimed; a later result() re-raises, never re-runs.
        assert ei.value.shed is True
        assert len(s.queue) == 0
        assert s.stats()["queries_shed"] == 1
        with pytest.raises(TrussTimeoutError):
            fut.result(timeout=None)


def test_remaining_is_the_one_deadline_rule():
    clock = obs.FakeClock()
    with obs.use_clock(clock):
        t0 = obs.now()
        assert obs.remaining(t0, None) is None
        assert obs.remaining(t0, 2.0) == pytest.approx(2.0)
        clock.advance(1.5)
        assert obs.remaining(t0, 2.0) == pytest.approx(0.5)
        clock.advance(1.0)
        assert obs.remaining(t0, 2.0) == 0.0  # clamped, never negative


# --------------------------------------------------------------------- #
# Per-session metric isolation (the ENUM_COUNTS satellite)
# --------------------------------------------------------------------- #
def test_stream_enumerations_per_session(graphs):
    from repro.stream.frontier import ENUM_COUNTS

    base_full = ENUM_COUNTS["full"]
    s = Session(max_batch=2)
    st_a = s.open_stream(graphs[0])
    st_b = s.open_stream(graphs[1])
    from repro.stream.delta import EdgeBatch

    st_a.update(EdgeBatch.of(inserts=[(1, 2)]), strict=False)
    # each stream's full enumeration landed in its own registry...
    assert st_a.metrics.value("stream_enumerations", kind="full") == 1
    assert st_b.metrics.value("stream_enumerations", kind="full") == 0
    # ...while the deprecated global alias still sees the aggregate
    assert ENUM_COUNTS["full"] >= base_full + 1
    assert set(iter(ENUM_COUNTS)) == {"full", "incident"}
    assert len(ENUM_COUNTS) == 2


def test_stream_counters_are_metric_views(graphs):
    from repro.stream.delta import EdgeBatch

    s = Session(max_batch=2)
    stream = s.open_stream(graphs[0])
    stream.update(EdgeBatch.of(inserts=[(2, 3)]), strict=False)
    assert stream.updates_applied == 1
    assert stream.metrics.value("stream_updates") == 1
    assert stream.update_dispatches == stream.metrics.value(
        "stream_update_dispatches"
    )
    # frontier fraction histogram observed on the stream's registry
    hists = stream.metrics.snapshot()["histograms"]
    assert any(k.startswith("stream_frontier_frac") for k in hists)


# --------------------------------------------------------------------- #
# Deprecation shims (removed in PR 9)
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("mod", ["repro.service.cache", "repro.service.batcher"])
def test_service_shims_are_gone(mod):
    sys.modules.pop(mod, None)
    with pytest.raises(ImportError):
        importlib.import_module(mod)


def test_service_package_import_is_warning_free():
    sys.modules.pop("repro.service", None)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        import repro.service  # noqa: F401

        # the documented surface resolves without any shim
        assert callable(repro.service.bucket_for)
        assert repro.service.TrussService is not None
    assert "MicroBatcher" not in repro.service.__all__
    # the batcher's replacement lives in repro.api now
    from repro.api import QueryQueue

    assert QueryQueue is not None


# --------------------------------------------------------------------- #
# Registry mechanics the wiring relies on
# --------------------------------------------------------------------- #
def test_metric_key_escaping_roundtrips_hostile_labels():
    """Label values containing the key format's own separators — commas,
    equals signs, backslashes — must survive the fmt/parse roundtrip
    byte-exact, not shift into neighbouring labels."""
    from repro.obs.metrics import _fmt_key, _label_key, _parse_key

    hostile = {
        "graph": "road,usa=west\\v2",
        "note": "a=b,c=d",
        "plain": "fine",
        "trail": "ends with backslash\\",
    }
    key = _fmt_key("requests_served", _label_key(hostile))
    name, labels = _parse_key(key)
    assert name == "requests_served"
    assert labels == hostile

    # legacy unescaped keys (pre-escaping snapshots) still parse
    name, labels = _parse_key("cache_bucket_hits{bucket=b64x2}")
    assert name == "cache_bucket_hits" and labels == {"bucket": "b64x2"}


def test_registry_escaped_labels_are_distinct_series():
    """Two label sets that would collide without escaping stay separate."""
    r = obs.MetricsRegistry()
    r.inc("requests_served", 1, graph="a,b", note="c")
    r.inc("requests_served", 5, graph="a", note="b,c")
    assert r.value("requests_served", graph="a,b", note="c") == 1
    assert r.value("requests_served", graph="a", note="b,c") == 5
    counters = r.snapshot()["counters"]
    assert sum(v for k, v in counters.items() if k.startswith("requests_served{")) == 6


def test_registry_parent_chaining():
    parent = obs.MetricsRegistry()
    child = obs.MetricsRegistry(parent=parent)
    child.inc("x", 2, where="here")  # trusslint: disable=R5
    assert child.value("x", where="here") == 2  # trusslint: disable=R5
    assert parent.value("x", where="here") == 2  # propagated up; trusslint: disable=R5
    parent.inc("x", 1, where="here")  # trusslint: disable=R5
    assert child.value("x", where="here") == 2  # isolation downward; trusslint: disable=R5


def test_session_metrics_chain_to_global(graphs):
    before = obs.get_registry().value("requests_served")
    solve(TrussQuery.decompose(graphs[0]))
    assert obs.get_registry().value("requests_served") == before + 1


def test_fake_clock_drives_trace_timestamps():
    clock = obs.FakeClock()
    with obs.use_clock(clock):
        tr = obs.Tracer()
        with obs.use_tracer(tr):
            with obs.current_tracer().span("work"):
                clock.advance(0.25)
        (ev,) = tr.events()
        assert ev["dur"] == pytest.approx(0.25e6)  # microseconds
