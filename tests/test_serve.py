"""repro.serve: wire codec, health reports, router policy, thread-safe
Session, in-process replica ops, and the multi-process fleet proof.

The expensive piece is ``test_fleet_integration`` — it spawns three real
replica processes (separate interpreters, real sockets, real SIGKILL)
and asserts the tier's whole contract in one pass: mixed queries over
three shape buckets come back bit-identical to a local ``solve()``, a
replica killed mid-stream hands its streaming session off warm to a
survivor with identical trussness, and the router's affinity accounting
adds up.  Everything else runs in-process.
"""

import json
import socket
import threading

import numpy as np
import pytest

from repro.api import Session, TrussQuery, solve
from repro.api.cache import bucket_for, bucket_str
from repro.errors import (
    DeviceError,
    InvalidGraphError,
    QueryFailedError,
    TrussTimeoutError,
)
from repro.graphs import erdos, rmat
from repro.serve import (
    Fleet,
    FleetClient,
    HealthReport,
    Replica,
    ReplicaConfig,
    ReplicaHandle,
    Router,
    health_report,
)
from repro.serve.replica import _WARMUP_KINDS, _warm_graph
from repro.serve.wire import (
    MAX_FRAME_BYTES,
    WireError,
    decode_array,
    decode_graph,
    decode_query,
    decode_result,
    encode_array,
    encode_error,
    encode_graph,
    encode_query,
    encode_result,
    raise_remote_error,
    recv_msg,
    send_msg,
)
from repro.stream import EdgeBatch


def _fresh_edge(g):
    """One (u, v) not in ``g`` (0-based), deterministic."""
    existing = set(map(tuple, (g.edge_list() - 1)))
    for u in range(g.n):
        for v in range(u + 1, g.n):
            if (u, v) not in existing:
                return (u, v)
    raise AssertionError("graph is complete")


# ------------------------------------------------------------------ #
# Wire protocol
# ------------------------------------------------------------------ #
def test_wire_framing_roundtrip():
    a, b = socket.socketpair()
    try:
        send_msg(a, {"op": "ping", "payload": [1, 2, 3]})
        send_msg(a, {"op": "second"})
        assert recv_msg(b) == {"op": "ping", "payload": [1, 2, 3]}
        assert recv_msg(b) == {"op": "second"}
        a.close()
        assert recv_msg(b) is None  # clean EOF at a frame boundary
    finally:
        b.close()


def test_wire_rejects_oversized_frames():
    a, b = socket.socketpair()
    try:
        # A hostile/corrupt length prefix must not allocate 4 GiB.
        a.sendall((MAX_FRAME_BYTES + 1).to_bytes(4, "big"))
        with pytest.raises(WireError):
            recv_msg(b)
    finally:
        a.close()
        b.close()


def test_array_and_graph_roundtrip_bit_exact():
    rng = np.random.default_rng(3)
    for arr in (
        rng.integers(-(2**31), 2**31, size=(17,), dtype=np.int32),
        rng.integers(0, 2, size=(4, 9)).astype(bool),
        np.zeros((0, 2), np.int64),
    ):
        out = decode_array(json.loads(json.dumps(encode_array(arr))))
        assert out.dtype == arr.dtype and np.array_equal(out, arr)

    g = rmat(6, 5, seed=1)
    g2 = decode_graph(json.loads(json.dumps(encode_graph(g))))
    assert g2.n == g.n
    assert np.array_equal(g2.rowptr, g.rowptr)
    assert np.array_equal(g2.colidx, g.colidx)


def test_query_roundtrip_preserves_fields():
    g = erdos(40, 5.0, seed=0)
    q = TrussQuery.ktruss(g, k=4, deadline_s=2.5)
    q2 = decode_query(json.loads(json.dumps(encode_query(q))))
    assert (q2.workload, q2.k, q2.deadline_s) == ("ktruss", 4, 2.5)
    assert np.array_equal(q2.graph.colidx, g.colidx)

    frontier = np.zeros(g.nnz, bool)
    frontier[:3] = True
    frozen = np.arange(g.nnz, dtype=np.int32)
    qs = TrussQuery.stream_update(g, frontier=frontier, frozen_truss=frozen)
    qs2 = decode_query(json.loads(json.dumps(encode_query(qs))))
    assert np.array_equal(qs2.frontier, frontier)
    assert np.array_equal(qs2.frozen_truss, frozen)


def test_result_roundtrip_all_kinds():
    g = erdos(48, 6.0, seed=0)
    dec, km, kt = solve(
        [TrussQuery.decompose(g), TrussQuery.kmax(g), TrussQuery.ktruss(g, k=3)]
    )
    dec2 = decode_result(json.loads(json.dumps(encode_result(dec))))
    assert np.array_equal(dec2.trussness, dec.trussness)
    assert (dec2.kmax, dec2.levels) == (dec.kmax, dec.levels)
    assert decode_result(json.loads(json.dumps(encode_result(km)))) == km
    kt2 = decode_result(json.loads(json.dumps(encode_result(kt))))
    assert np.array_equal(kt2.alive, kt.alive)
    assert np.array_equal(kt2.support, kt.support)
    assert kt2.edges_remaining == kt.edges_remaining
    arr = dec.trussness
    assert np.array_equal(
        decode_result(json.loads(json.dumps(encode_result(arr)))), arr
    )


def test_remote_errors_reraise_typed_with_context():
    # The shed signal must survive the hop: a replica's admission shed
    # arrives as TrussTimeoutError(shed=True), not a lookalike message.
    frame = json.loads(
        json.dumps(encode_error(TrussTimeoutError("full", shed=True, queue_depth=7)))
    )
    with pytest.raises(TrussTimeoutError) as ei:
        raise_remote_error(frame)
    assert ei.value.shed is True
    assert ei.value.queue_depth == 7
    assert "[remote]" in str(ei.value)

    with pytest.raises(InvalidGraphError):
        raise_remote_error(encode_error(InvalidGraphError("bad", kind="self_loop")))

    # Unknown names never import anything — they degrade to RuntimeError.
    with pytest.raises(RuntimeError, match="NoSuchError"):
        raise_remote_error({"error": {"type": "NoSuchError", "message": "x"}})
    with pytest.raises(RuntimeError):
        raise_remote_error({"error": {"type": "os.system", "message": "x"}})


# ------------------------------------------------------------------ #
# HealthReport (the shed/quarantine roundtrip the router depends on)
# ------------------------------------------------------------------ #
def test_health_report_roundtrip_preserves_shed_and_quarantine():
    report = HealthReport(
        name="replica-1",
        queue_depth=3,
        live_queries=5,
        requests_served=41,
        queries_shed=7,
        queries_failed=2,
        queries_quarantined=4,
        retries=9,
        warmup_queries=2,
        draining=False,
        streams=("stream-0", "stream-3"),
        compiled_buckets=("n64-nnz256-w16",),
        cache_bucket_hits={"n64-nnz256-w16": 12},
        imbalance=({"bucket": "n64-nnz256-w16", "max_over_mean": 1.5},),
    )
    # Through JSON, like the health op sends it.
    back = HealthReport.from_dict(json.loads(json.dumps(report.to_dict())))
    assert back == report
    assert back.queries_shed == 7 and back.queries_quarantined == 4


def test_health_report_reads_session_counters():
    s = Session(max_batch=2)
    g = erdos(48, 6.0, seed=0)
    s.submit(TrussQuery.decompose(g)).result(timeout=None)
    s.submit(TrussQuery.kmax(g)).result(timeout=None)
    rep = health_report(s, name="r0", streams=("s1",))
    assert rep.requests_served == s.requests_served == 2
    assert rep.queries_shed == s.queries_shed
    assert rep.queries_quarantined == s.queries_quarantined
    label = bucket_str(bucket_for(g, chunk=s.chunk))
    assert label in rep.compiled_buckets
    # Second query hit the compiled bucket at least once.
    assert rep.cache_bucket_hits.get(label, 0) >= 1
    assert rep.streams == ("s1",)
    back = HealthReport.from_dict(json.loads(json.dumps(rep.to_dict())))
    assert back == rep


def test_warmup_specs_are_allowlisted():
    g = _warm_graph({"kind": "erdos", "n": 32, "avg_degree": 4.0, "seed": 1})
    assert g.n == 32
    with pytest.raises(ValueError, match="unknown warmup generator"):
        _warm_graph({"kind": "os.system"})
    with pytest.raises(ValueError):
        _warm_graph({})
    assert "erdos" in _WARMUP_KINDS


# ------------------------------------------------------------------ #
# Router policy (fake handles — no sockets)
# ------------------------------------------------------------------ #
class _StubHandle(ReplicaHandle):
    """Handle whose RPCs are canned: submit counts, health is scripted."""

    def __init__(self, name, report=None):
        super().__init__(name, "127.0.0.1", 0)
        self.report = report
        self.submitted = 0

    def submit(self, qmsg):
        self.submitted += 1
        return self.submitted

    def health(self):
        if self.report is None:
            raise ConnectionError(f"{self.name} is down")
        return self.report

    def close(self):
        pass


def _report(name, **over):
    base = dict(
        name=name,
        queue_depth=0,
        live_queries=0,
        requests_served=0,
        queries_shed=0,
        queries_failed=0,
        queries_quarantined=0,
        retries=0,
        warmup_queries=0,
        draining=False,
        streams=(),
        compiled_buckets=(),
        cache_bucket_hits={},
        imbalance=(),
    )
    base.update(over)
    return HealthReport(**base)


def test_router_affinity_sticks_and_counts():
    r = Router([_StubHandle("r0"), _StubHandle("r1")], spill_depth=100)
    h, affine = r.pick("bucketA")
    assert affine is False  # cold assignment
    home = h.name
    for _ in range(5):
        h2, affine = r.pick("bucketA")
        assert h2.name == home and affine is True
    st = r.stats()
    assert st["cold_assignments"] == 1 and st["affinity_hits"] == 5
    assert st["routed"] == 6
    assert st["affinity"]["bucketA"] == home
    assert st["affinity_hit_rate"] == round(5 / 6, 4)


def test_router_spills_past_depth_and_sheds_at_saturation():
    r = Router(
        [_StubHandle("r0"), _StubHandle("r1")], spill_depth=2, shed_depth=2
    )
    home = r.pick("b")[0].name  # cold: depth home=1
    r.pick("b")  # hit: home=2
    spill, affine = r.pick("b")  # home at spill_depth -> least-loaded
    assert spill.name != home and affine is False
    r.pick("b")  # other still strictly less loaded -> spills again
    assert r.stats()["spillovers"] == 2
    with pytest.raises(TrussTimeoutError) as ei:
        r.pick("b")  # every replica at shed_depth
    assert ei.value.shed is True
    assert r.stats()["queries_shed"] == 1
    r.release(home)  # one slot frees -> admission resumes
    assert r.pick("b")[0] is not None


def test_router_learns_warm_home_from_health():
    warm = _report("r1", compiled_buckets=("bucketX",))
    r = Router([_StubHandle("r0"), _StubHandle("r1", report=warm)])
    r._replicas["r0"].report = _report("r0")
    r.poll_health()
    h, _ = r.pick("bucketX")
    assert h.name == "r1"  # adopted the replica that already compiled it


def test_router_quarantine_redistributes_and_recovers():
    h0 = _StubHandle("r0", report=_report("r0"))
    h1 = _StubHandle("r1", report=_report("r1", streams=("s7",)))
    r = Router([h0, h1], max_health_fails=1)
    assert r.pick("b")[0].name == "r0"  # cold -> least loaded = r0
    r.release("r0")
    h1.report = None  # r1 stops answering health
    r.poll_health()
    assert r.is_quarantined("r1")
    assert not r.is_quarantined("r0")
    # But r1 held no routed buckets; now kill r0 which owns "b".
    streams = r.quarantine("r0")
    assert streams == ()  # r0 reported no streams
    with pytest.raises(QueryFailedError):
        r.pick("b")  # nobody healthy
    r.reinstate("r1", _StubHandle("r1", report=_report("r1")))
    h, _ = r.pick("b")
    assert h.name == "r1"
    assert r.stats()["replicas_quarantined"] == 2


def test_router_quarantine_reports_orphaned_streams():
    h0 = _StubHandle("r0", report=_report("r0", streams=("sA", "sB")))
    h1 = _StubHandle("r1", report=_report("r1"))
    r = Router([h0, h1])
    r.poll_health()
    assert r.quarantine("r0") == ("sA", "sB")
    assert r.quarantine("r0") == ()  # idempotent


def test_network_fault_site_raises_typed_device_error():
    """The ``network`` site fires inside ``ReplicaHandle.rpc`` before any
    socket is opened, so chaos storms can break connections on demand."""
    from repro.errors import DeviceError
    from repro.resilience import FaultPlan, FaultSpec, use_plan

    handle = ReplicaHandle("r0", "127.0.0.1", 1)  # never actually connects
    with use_plan(FaultPlan([FaultSpec("network", times=1)])):
        with pytest.raises(DeviceError) as ei:
            handle.ping()
        assert ei.value.site == "network" and ei.value.injected
        assert handle._sock is None  # fault fired pre-connect


class _NetFlakyHandle(_StubHandle):
    """Stub whose submit passes through the real ``network`` fault site."""

    def submit(self, qmsg):
        from repro.resilience import inject

        inject("network", replica=self.name, op="submit")
        return super().submit(qmsg)


def test_router_reroutes_around_injected_network_fault():
    from repro.resilience import FaultPlan, FaultSpec, use_plan

    g = erdos(30, 3.0, seed=5)
    q = TrussQuery.decompose(g)
    r = Router(
        [_NetFlakyHandle("r0"), _NetFlakyHandle("r1")], max_health_fails=1
    )
    plan = FaultPlan(
        [FaultSpec("network", times=1, where=(("replica", "r0"),))]
    )
    with use_plan(plan):
        routed = r.submit(q, {"op": "submit"})
    # The injected connection failure quarantined r0 and the query
    # re-routed to the survivor — the affinity map follows.
    assert routed.replica.name == "r1"
    assert r.is_quarantined("r0")
    assert plan.fired("network") == 1


def test_replica_kill_is_a_pure_action_site():
    """``replica_kill`` must *return* its fired spec, never raise: the
    fleet monitor polls it each tick and performs the kill itself."""
    from repro.resilience import FaultPlan, FaultSpec, inject, use_plan

    plan = FaultPlan(
        [FaultSpec("replica_kill", times=1, where=(("replica", "r1"),))]
    )
    with use_plan(plan):
        assert inject("replica_kill", replica="r0") is None  # no match
        spec = inject("replica_kill", replica="r1")
        assert spec is not None and spec.site == "replica_kill"
        assert inject("replica_kill", replica="r1") is None  # times=1 spent
    assert plan.fired("replica_kill") == 1


def test_router_ingests_replica_counters():
    h0 = _StubHandle(
        "r0", report=_report("r0", queries_shed=4, requests_served=11)
    )
    r = Router([h0])
    r.poll_health()
    snap = r.metrics.snapshot()["gauges"]
    assert snap["replica_queries_shed{replica=r0}"] == 4
    assert snap["replica_requests_served{replica=r0}"] == 11


def test_route_many_is_edf_ordered():
    g = erdos(24, 4.0, seed=0)
    qs = [
        TrussQuery.kmax(g),  # no deadline -> last, submission order
        TrussQuery.kmax(g, deadline_s=5.0),
        TrussQuery.kmax(g, deadline_s=1.0),
        TrussQuery.kmax(g),
    ]
    r = Router([_StubHandle("r0")])
    assert r.route_many(qs) == [2, 1, 0, 3]


# ------------------------------------------------------------------ #
# Thread-safe Session (the substrate replicas stand on)
# ------------------------------------------------------------------ #
def test_session_threaded_hammer_matches_serial():
    g_small = erdos(48, 6.0, seed=0)
    g_big = erdos(150, 5.0, seed=1)
    queries = [
        TrussQuery.decompose(g_small if i % 2 else g_big) for i in range(12)
    ] + [TrussQuery.kmax(g_small), TrussQuery.ktruss(g_big, k=3)]
    expect = solve(list(queries), max_batch=4)

    s = Session(max_batch=4)
    results: dict[int, object] = {}
    errors: list[BaseException] = []

    def worker(idxs):
        try:
            futs = [(i, s.submit(queries[i])) for i in idxs]
            for i, f in futs:
                results[i] = f.result(timeout=None)
        except BaseException as e:  # pragma: no cover - failure detail
            errors.append(e)

    threads = [
        threading.Thread(target=worker, args=(range(t, len(queries), 4),))
        for t in range(4)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errors, errors
    assert len(results) == len(queries)
    for i, exp in enumerate(expect):
        got = results[i]
        if isinstance(exp, int):
            assert got == exp
        elif hasattr(exp, "trussness"):
            assert np.array_equal(got.trussness, exp.trussness)
        else:
            assert np.array_equal(got.alive, exp.alive)
    assert s.requests_served == len(queries)
    assert s.drain() == 0  # nothing left in flight


def test_session_drain_flushes_queued_work():
    s = Session(max_batch=4)
    g = erdos(48, 6.0, seed=0)
    futs = [s.submit(TrussQuery.kmax(g)) for _ in range(3)]
    assert s.drain() >= 1
    assert all(f.done() for f in futs)
    assert len(s.queue) == 0


# ------------------------------------------------------------------ #
# Replica ops in-process (one process, real handler paths)
# ------------------------------------------------------------------ #
@pytest.fixture()
def replica(tmp_path):
    cfg = ReplicaConfig(
        name="r-test",
        port_file=str(tmp_path / "port"),
        max_batch=2,
        max_live=2,
        checkpoint_root=str(tmp_path / "ckpt"),
        checkpoint_every=1,
    )
    return Replica(cfg)


def test_replica_admission_sheds_past_max_live(replica):
    g = erdos(48, 6.0, seed=0)
    q = encode_query(TrussQuery.kmax(g))
    qid1 = replica._handle({"op": "submit", "query": q})["qid"]
    replica._handle({"op": "submit", "query": q})
    with pytest.raises(TrussTimeoutError) as ei:
        replica._handle({"op": "submit", "query": q})  # 3rd > max_live=2
    assert ei.value.shed is True
    out = replica._handle({"op": "result", "qid": qid1, "timeout": None})
    assert isinstance(decode_result(out["result"]), int)
    rep = replica.health()
    assert rep.queries_shed >= 1
    assert rep.live_queries == 1  # one still uncollected
    with pytest.raises(KeyError):
        replica._handle({"op": "result", "qid": qid1})  # already collected


def test_replica_drain_refuses_new_work(replica):
    g = erdos(48, 6.0, seed=0)
    q = encode_query(TrussQuery.kmax(g))
    replica._handle({"op": "submit", "query": q})
    replica._handle({"op": "drain"})
    assert replica.health().draining is True
    with pytest.raises(TrussTimeoutError):
        replica._handle({"op": "submit", "query": q})


def test_replica_stream_seq_is_exactly_once(replica, tmp_path):
    g = erdos(48, 6.0, seed=0)
    opened = replica._handle(
        {"op": "open_stream", "stream_id": "s0", "graph": encode_graph(g)}
    )
    assert opened["seq"] == 0
    ins = _fresh_edge(g)
    dele = tuple(g.edge_list()[0] - 1)
    msg = {
        "op": "stream_update",
        "stream_id": "s0",
        "seq": 1,
        "inserts": encode_array(np.asarray([ins], np.int64)),
        "deletes": encode_array(np.asarray([dele], np.int64)),
    }
    first = replica._handle(msg)
    assert first["seq"] == 1 and "replayed" not in first
    # The exact frame again (a client retry after a lost ack): replayed,
    # not re-applied — committed state comes back unchanged.
    again = replica._handle(msg)
    assert again["replayed"] is True and again["seq"] == 1
    assert again["trussness"] == first["trussness"]
    with pytest.raises(ValueError, match="expects seq 2"):
        replica._handle({**msg, "seq": 5})
    with pytest.raises(KeyError):
        replica._handle({**msg, "stream_id": "nope"})


def test_replica_restore_stream_resumes_from_checkpoint(replica):
    g = erdos(48, 6.0, seed=0)
    replica._handle(
        {"op": "open_stream", "stream_id": "s1", "graph": encode_graph(g)}
    )
    msg = {
        "op": "stream_update",
        "stream_id": "s1",
        "seq": 1,
        "inserts": encode_array(np.asarray([_fresh_edge(g)], np.int64)),
        "deletes": encode_array(np.zeros((0, 2), np.int64)),
    }
    committed = replica._handle(msg)
    # A "new" replica process (fresh Replica over the same checkpoint
    # root) restores the stream warm, at the committed seq.
    twin = Replica(replica.config)
    restored = twin._handle({"op": "restore_stream", "stream_id": "s1"})
    assert restored["seq"] == 1
    assert restored["trussness"] == committed["trussness"]
    # And the retried update is recognized as already applied.
    replay = twin._handle(msg)
    assert replay["replayed"] is True


# ------------------------------------------------------------------ #
# One TPU chip per replica process
# ------------------------------------------------------------------ #
def test_fleet_refuses_more_tpu_replicas_than_chips(monkeypatch, tmp_path):
    import repro.serve.fleet as fleet_mod

    monkeypatch.setattr(fleet_mod, "tpu_chips", lambda: 1)
    with pytest.raises(DeviceError, match="3 TPU chips.*has 1"):
        Fleet(3, workdir=str(tmp_path / "fleet"))


def test_fleet_pins_each_tpu_replica_to_its_own_chip(monkeypatch, tmp_path):
    import repro.serve.fleet as fleet_mod

    monkeypatch.setattr(fleet_mod, "tpu_chips", lambda: 4)
    fleet = Fleet(3, workdir=str(tmp_path / "fleet"))
    envs = [fleet.replica_env(i) for i in range(3)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2"]
    assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 3
    assert all(e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1" for e in envs)
    monkeypatch.setattr(fleet_mod, "tpu_chips", lambda: 0)
    assert Fleet(3, workdir=str(tmp_path / "cpu")).replica_env(1) == {}


def test_tpu_chips_is_zero_when_jax_platforms_excludes_tpu(monkeypatch):
    from repro.serve.fleet import tpu_chips

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert tpu_chips() == 0


# ------------------------------------------------------------------ #
# The multi-process fleet (the tier-1 proof)
# ------------------------------------------------------------------ #
def test_fleet_integration(tmp_path):
    g1 = erdos(48, 6.0, seed=0)
    g2 = erdos(150, 5.0, seed=1)
    g3 = rmat(7, 5, seed=2)
    buckets = {bucket_str(bucket_for(g, chunk=256)) for g in (g1, g2, g3)}
    assert len(buckets) == 3  # the mix really spans three shape buckets

    warm = [
        {"kind": "erdos", "n": 48, "avg_degree": 6.0, "seed": 0},
        {"kind": "erdos", "n": 150, "avg_degree": 5.0, "seed": 1},
        {"kind": "rmat", "scale": 7, "edge_factor": 5, "seed": 2},
    ]
    queries = [
        TrussQuery.decompose(g1),
        TrussQuery.kmax(g2),
        TrussQuery.ktruss(g3, k=3),
        TrussQuery.decompose(g2),
        TrussQuery.kmax(g1),
        TrussQuery.decompose(g3),
    ]
    expect = solve(list(queries), max_batch=2)

    ins = _fresh_edge(g1)
    dele = tuple(g1.edge_list()[0] - 1)
    local = Session(max_batch=2)
    lstream = local.open_stream(g1)
    lstream.update(EdgeBatch.of([ins]))
    lstream.update(EdgeBatch.of([], [dele]))

    with Fleet(3, workdir=str(tmp_path / "fleet"), max_batch=2, warmup=warm) as fleet:
        client = FleetClient(fleet)

        # Mixed queries over 3 buckets: bit-identical to local solve().
        got = client.solve(list(queries))
        for exp, res in zip(expect, got):
            if isinstance(exp, int):
                assert res == exp
            elif hasattr(exp, "trussness"):
                assert np.array_equal(res.trussness, exp.trussness)
                assert res.kmax == exp.kmax
            else:
                assert np.array_equal(res.alive, exp.alive)
                assert np.array_equal(res.support, exp.support)

        # Warmup seeded affinity: repeat traffic stays home.
        st = client.stats()
        assert st["routed"] >= len(queries)
        assert st["routed"] == (
            st["affinity_hits"] + st["spillovers"] + st["cold_assignments"]
        )
        assert st["affinity_hits"] > 0

        # Kill a replica mid-stream: the stream resumes on a survivor
        # with trussness identical to the never-crashed local session.
        stream = client.open_stream(g1)
        owner = stream.owner
        assert owner is not None
        stream.update(EdgeBatch.of([ins]))
        fleet.kill(owner)
        reply = stream.update(EdgeBatch.of([], [dele]))
        assert stream.owner != owner
        assert stream.seq == 2 and reply["seq"] == 2
        assert np.array_equal(stream.trussness, lstream.trussness)
        assert stream.kmax == lstream.kmax
        assert fleet.stats()["replicas"][owner]["quarantined"] is True

        # The fleet accounted the warm handoff for this stream.
        assert (
            int(
                fleet.router.metrics.value(
                    "fleet_stream_handoffs", stream=stream.stream_id
                )
            )
            >= 1
        )
