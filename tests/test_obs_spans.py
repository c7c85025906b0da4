"""Program spans on the profiler's clock, batch identity, and the named
support pass inside the compiled peel.

* a traced session's spans are ``jax.profiler`` annotations
  (``repro.<name>``) for their exact extent; an untraced one builds none;
* every span of one batch carries that batch's id, and each member's
  ``queue`` span (submission to batch formation) names it;
* the ``unpack`` span carries the dispatch's while-loop trips, read from
  the per-slot iteration counts it already reads back;
* the compiled peel's instructions sit under the ``support`` and
  ``prune`` scopes.
"""

import glob
import os
import re

import jax
import pytest

from repro import obs
from repro.api import Session, TrussQuery
from repro.exec import peel as P
from repro.graphs import rmat
from repro.obs.trace import _NULL_SPAN, QUEUE_TRACK

BATCH_SPANS = ("pack", "compile", "dispatch", "device-wait", "unpack")


@pytest.fixture(scope="module")
def graph():
    return rmat(5, 4, seed=1)


def _profiled(tmp_path, work):
    """Host annotations ``[(name, start_ns, end_ns)]`` of a JAX profiler
    trace taken around ``work()`` inside an ``outer`` annotation."""
    from jax.profiler import ProfileData

    logdir = str(tmp_path / "profile")
    jax.profiler.start_trace(logdir)
    try:
        with jax.profiler.TraceAnnotation("outer"):
            work()
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == "outer" or ev.name.startswith("repro."):
                        events.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return events


def _serve(session, graph):
    session.submit(TrussQuery.ktruss(graph, k=3))
    session.submit(TrussQuery.decompose(graph))
    assert session.poll() == 2


def test_traced_session_annotates_on_the_profiler_clock(tmp_path, graph):
    s = Session(trace=True, max_batch=2, chunk=64)
    _serve(s, graph)  # compile outside the profile
    events = _profiled(tmp_path, lambda: _serve(s, graph))
    [(_, lo, hi)] = [e for e in events if e[0] == "outer"]
    names = {name for name, _, _ in events}
    assert {"repro.plan", "repro.pack", "repro.dispatch", "repro.device-wait",
            "repro.unpack"} <= names
    for name, start, end in events:
        assert lo <= start <= end <= hi, name
    # The Chrome JSON keeps the plain names.
    assert {"pack", "dispatch", "unpack"} <= {ev["name"] for ev in s.obs.tracer.events()}


def test_untraced_session_annotates_nothing(tmp_path, graph, monkeypatch):
    s = Session(trace=False, max_batch=2, chunk=64)
    _serve(s, graph)
    outer = jax.profiler.TraceAnnotation

    def refuse(name, **kw):
        if name != "outer":
            raise AssertionError(f"an untraced session built the annotation {name!r}")
        return outer(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    events = _profiled(tmp_path, lambda: _serve(s, graph))
    assert [name for name, _, _ in events] == ["outer"]
    assert s.obs.tracer is obs.NULL_TRACER and s.obs.tracer.events() == []


def test_disabled_tracer_hands_out_the_shared_null_span():
    tr = obs.NULL_TRACER
    assert tr.span("pack", batch=1) is _NULL_SPAN
    assert tr.tagged(batch=1) is _NULL_SPAN
    tr.complete("queue", 0.0, 1.0, batch=1)
    assert tr.events() == []


def test_tagged_spans_and_spans_recorded_after_the_fact():
    clock = obs.FakeClock(start=5.0)
    tr = obs.Tracer()
    with obs.use_clock(clock):
        with tr.tagged(batch=3):
            with tr.span("pack", members=2):
                clock.advance(0.5)
            with tr.tagged(member=1), tr.span("unpack"):
                pass
        with tr.span("plan"):
            pass
        tr.complete("queue", 1.0, 4.0, tid=QUEUE_TRACK, batch=9)
    pack, unpack, plan, queue = tr.events()
    assert pack["args"] == {"batch": 3, "members": 2} and pack["dur"] == pytest.approx(0.5e6)
    assert unpack["args"] == {"batch": 3, "member": 1}
    assert "args" not in plan
    assert queue["tid"] == QUEUE_TRACK and queue["args"] == {"batch": 9}
    assert (queue["ts"], queue["dur"]) == pytest.approx((1e6, 3e6))


def test_every_span_of_a_batch_carries_its_id(graph):
    s = Session(trace=True, max_batch=2, chunk=64)
    futs = [s.submit(TrussQuery.ktruss(graph, k=3)) for _ in range(3)]
    s.flush()  # batches 0 and 1
    s.solve([TrussQuery.decompose(graph)] * 2)  # batch 2
    events = s.obs.tracer.events()
    by_batch: dict = {}
    for ev in events:
        if ev["name"] in BATCH_SPANS:
            by_batch.setdefault(ev["args"]["batch"], []).append(ev["name"])
    assert sorted(by_batch) == [0, 1, 2]
    for names in by_batch.values():
        assert sorted(names) == sorted(BATCH_SPANS)
    members = {ev["args"]["batch"]: ev["args"]["members"] for ev in events if ev["name"] == "pack"}
    assert members == {0: 2, 1: 1, 2: 2}
    queues = [ev for ev in events if ev["name"] == "queue"]
    assert len(queues) == len(futs) + 2
    assert all(q["tid"] == QUEUE_TRACK for q in queues)
    for batch, count in members.items():
        assert sum(q["args"]["batch"] == batch for q in queues) == count
    # The flush formed batches 0 and 1 from the three submissions, in order.
    waits = sorted(q["dur"] for q in queues if q["args"]["batch"] in (0, 1))
    assert waits == pytest.approx(sorted(1e6 * f.request.stats.queue_time_s for f in futs))


def test_unpack_carries_the_dispatch_trips(monkeypatch, graph):
    seen = []
    real = P.PeelExecutor.peel

    def peel(self, p, **kw):
        st = real(self, p, **kw)
        seen.append(int(st.total_iters))
        return st

    monkeypatch.setattr(P.PeelExecutor, "peel", peel)
    s = Session(trace=True, max_batch=4, chunk=64)
    for q in (TrussQuery.decompose(graph), TrussQuery.ktruss(graph, k=3),
              TrussQuery.kmax(graph)):
        s.submit(q)
    s.flush()
    assert len(seen) == 1 and seen[0] > 2
    [unpack] = [ev for ev in s.obs.tracer.events() if ev["name"] == "unpack"]
    assert unpack["args"]["trips"] == seen[0]


def test_compiled_peel_names_the_support_pass_and_the_prune():
    exe = P.PeelExecutor(window=16, chunk=64)
    assert exe.compiled_text() is None
    exe.compile(n=64, nnz_pad=256, slots=2)
    scopes = re.findall(r'op_name="jit\(peel\)/while/body/(\w+)/', exe.compiled_text())
    assert {"support", "prune"} <= set(scopes)


def test_session_cache_lists_its_compiled_peels(graph):
    s = Session(max_batch=2, chunk=64)
    assert s.cache.executors() == []
    s.solve([TrussQuery.ktruss(graph, k=3)])
    [exe] = s.cache.executors()
    assert "/while/body/support/" in exe.compiled_text()
