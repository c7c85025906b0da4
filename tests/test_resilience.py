"""Fault-domain isolation for the batched peel path (repro.resilience).

What's covered:

* the typed failure taxonomy (``repro.errors``) and its context fields;
* CSR invariant validation at Graph construction — every ``kind`` of
  violation raises :class:`InvalidGraphError` naming the first bad row,
  driven by the deterministic ``poison_csr_arrays`` corpus;
* the fault-injection harness: spec gating (times/skip/p/where), seeded
  determinism, the ``REPRO_FAULTS`` mini-language, context-plan scoping;
* retry/backoff (on the fake clock — no sleeping), registry fallback
  chains, quarantine of poisoned batch members with bit-identical
  survivors, and batch bisection when a fault has no attribution;
* streaming checkpoint/restore: atomic write, checksum/version/shape
  verification, restore-equivalence (a restored session continues
  bit-identically), and auto-checkpoint retention.
"""

import os

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.api import (
    CheckpointError,
    CompileError,
    DeviceError,
    InvalidGraphError,
    QueryFailedError,
    Session,
    TrussError,
    TrussQuery,
    available_backends,
    fallback_backends,
)
from repro.api.cache import CompileCache
from repro.api.registry import BackendKey
from repro.core import trussness_numpy
from repro.graphs import CSRGraph, erdos, validate_csr
from repro.obs.clock import FakeClock, use_clock
from repro.resilience import (
    CHECKPOINT_VERSION,
    FaultPlan,
    FaultSpec,
    RetryPolicy,
    latest_checkpoint,
    load_checkpoint,
    parse_faults,
    restore_session,
    save_checkpoint,
    use_plan,
)
from repro.resilience.faults import poison_csr_arrays
from repro.stream.delta import EdgeBatch
from repro.stream.session import StreamingTrussSession

FAST_RETRY = RetryPolicy(backoff_base_s=0.0)


def tiny(seed=0):
    return erdos(50, 4.0, seed=seed)


# --------------------------------------------------------------------- #
# (a) Taxonomy
# --------------------------------------------------------------------- #
def test_taxonomy_hierarchy_and_context():
    e = DeviceError("boom", oom=True, bucket="b", backend="k", slot=2, site="x")
    assert isinstance(e, TrussError) and isinstance(e, RuntimeError)
    assert e.oom and e.slot == 2
    ctx = e.context()
    assert ctx["slot"] == 2 and ctx["site"] == "x"
    assert isinstance(InvalidGraphError("bad"), ValueError)
    assert isinstance(CompileError("bad"), RuntimeError)
    assert isinstance(QueryFailedError("bad", attempts=3), RuntimeError)
    assert isinstance(CheckpointError("bad", path="/p"), RuntimeError)
    # legacy except-clauses keep working through the taxonomy
    with pytest.raises(ValueError):
        raise InvalidGraphError("still a ValueError")


# --------------------------------------------------------------------- #
# (b) CSR invariant validation at construction
# --------------------------------------------------------------------- #
def test_validate_csr_names_first_violating_row():
    # row 2 (1-based) holds a self-loop
    with pytest.raises(InvalidGraphError) as ei:
        CSRGraph(3, np.array([0, 1, 2, 2]), np.array([2, 2], np.int32))
    assert ei.value.kind == "self_loop"
    assert ei.value.row == 2
    # duplicate column within row 1
    with pytest.raises(InvalidGraphError) as ei:
        CSRGraph(3, np.array([0, 2, 2, 2]), np.array([2, 2], np.int32))
    assert ei.value.kind == "duplicate"
    assert ei.value.row == 1
    # column out of range
    with pytest.raises(InvalidGraphError) as ei:
        CSRGraph(2, np.array([0, 1, 1]), np.array([7], np.int32))
    assert ei.value.kind == "col_range"
    # rowptr not monotone
    with pytest.raises(InvalidGraphError) as ei:
        CSRGraph(3, np.array([0, 2, 1, 2]), np.array([2, 3], np.int32))
    assert ei.value.kind == "rowptr_unsorted"
    # validate=False is the test/tool escape hatch
    g = CSRGraph(2, np.array([0, 1, 1]), np.array([7], np.int32), validate=False)
    assert g.nnz == 1


def test_poison_corpus_always_caught():
    """Every deterministic corruption of a real graph is caught with the
    kind the corruptor promised."""
    g = erdos(40, 5.0, seed=1)
    for seed in range(24):
        n, rowptr, colidx, kind = poison_csr_arrays(
            g.n, g.rowptr, g.colidx, seed=seed
        )
        with pytest.raises(InvalidGraphError) as ei:
            validate_csr(n, rowptr, colidx, name=f"poison{seed}")
        assert ei.value.kind == kind, f"seed {seed}: {ei.value.kind} != {kind}"
        assert ei.value.row is not None and 1 <= ei.value.row <= n


def test_valid_graphs_pass_validation(small_graphs):
    for g in small_graphs:
        validate_csr(g.n, g.rowptr, g.colidx)  # no raise
        g.undirected_csr()  # symmetrized construction re-validates


# --------------------------------------------------------------------- #
# (c) Fault plan mechanics
# --------------------------------------------------------------------- #
def test_fault_spec_gating_times_skip_where():
    plan = FaultPlan(
        [
            FaultSpec("dispatch", times=2, skip=1),
            FaultSpec("poison", times=None, where=(("query", 7),)),
        ]
    )
    # skip=1: first hit passes, next two fire, then exhausted
    assert plan.should_fire("dispatch", {}) is None
    assert plan.should_fire("dispatch", {}) is not None
    assert plan.should_fire("dispatch", {}) is not None
    assert plan.should_fire("dispatch", {}) is None
    # where: equality and tuple-membership
    assert plan.should_fire("poison", {"query": 3}) is None
    assert plan.should_fire("poison", {"query": 7}) is not None
    assert plan.should_fire("poison", {"queries": (1, 7, 9), "query": 7}) is not None
    plan.reset()
    assert plan.fired() == 0
    assert plan.should_fire("dispatch", {}) is None  # skip applies again


def test_fault_probability_is_seed_deterministic():
    def draw(seed):
        plan = FaultPlan([FaultSpec("dispatch", times=None, p=0.5)], seed=seed)
        return [plan.should_fire("dispatch", {}) is not None for _ in range(32)]

    a, b, c = draw(1), draw(1), draw(2)
    assert a == b  # same seed -> same firing pattern
    assert a != c  # different seed -> different pattern (w.h.p.)
    assert any(a) and not all(a)  # p=0.5 actually gates


def test_parse_faults_mini_language():
    plan = parse_faults(
        "dispatch:times=1;device_oom:skip=2:times=*:p=0.25;"
        "poison:where.query=7:msg=bad member;clock_skew:skew=9.5;seed=11"
    )
    assert plan.seed == 11
    d, o, p, c = plan.specs
    assert (d.site, d.times) == ("dispatch", 1)
    assert (o.skip, o.times, o.p) == (2, None, 0.25)
    assert p.where == (("query", 7),) and p.message == "bad member"
    assert c.skew_s == 9.5
    with pytest.raises(ValueError):
        parse_faults("warp_core_breach")
    with pytest.raises(ValueError):
        parse_faults("dispatch:frequency=11")


def test_faults_env_var(monkeypatch):
    monkeypatch.delenv("REPRO_FAULTS", raising=False)
    assert FaultPlan.from_env() is None
    monkeypatch.setenv("REPRO_FAULTS", "dispatch:times=1;seed=5")
    plan = FaultPlan.from_env()
    assert plan.seed == 5 and plan.specs[0].site == "dispatch"
    # Session picks the env plan up by default
    s = Session(backend="fine/xla/aligned", max_batch=2, chunk=64, retry=FAST_RETRY)
    assert s.faults is not None and s.faults.specs[0].site == "dispatch"


# --------------------------------------------------------------------- #
# (d) Retry policy + fallback chain
# --------------------------------------------------------------------- #
def test_retry_policy_backoff_schedule():
    p = RetryPolicy(backoff_base_s=0.01, backoff_mult=2.0, backoff_max_s=0.05)
    assert [p.delay(i) for i in (1, 2, 3, 4, 5)] == [
        0.01,
        0.02,
        0.04,
        0.05,
        0.05,  # capped
    ]
    with pytest.raises(ValueError):
        RetryPolicy(max_attempts=0)


def test_fallback_chain_shapes():
    assert fallback_backends("fine/xla/aligned") == (
        BackendKey("coarse", "xla", "aligned"),
    )
    assert fallback_backends("fine/pallas/contig") == (
        BackendKey("fine", "xla", "contig"),
        BackendKey("coarse", "xla", "contig"),
    )
    # layout is preserved down the whole chain (mesh safety)
    assert all(k.layout == "aligned" for k in fallback_backends("fine/pallas/aligned"))
    assert BackendKey("fine", "fused", "aligned") not in available_backends()
    # the last resort has nowhere to fall
    assert fallback_backends("coarse/xla/contig") == ()


def test_compile_cache_wraps_builder_failures():
    cache = CompileCache(lambda key: (_ for _ in ()).throw(RuntimeError("no exe")))
    with pytest.raises(CompileError) as ei:
        cache.get(("bucket",), 1, "variant")
    assert "no exe" in str(ei.value)
    assert cache.stats.compiles == 0  # failed builds are not compiles


def test_refused_compile_is_a_compile_error_at_executor_build(monkeypatch):
    """Executors compile ahead of time in the cache's builder, so a
    program the device's compiler refuses fails as a typed CompileError
    before any dispatch — not as a DeviceError at the first dispatch."""
    from repro.exec import PeelExecutor

    def refuse(self, **shapes):
        raise NotImplementedError("Only 2D gather is supported")

    monkeypatch.setattr(PeelExecutor, "compile", refuse)
    s = Session(
        backend="fine/xla/aligned",
        max_batch=2,
        chunk=64,
        retry=RetryPolicy(max_attempts=1, fallback=False, bisect=False),
    )
    fut = s.submit(TrussQuery.decompose(tiny()))
    s.flush()
    with pytest.raises(QueryFailedError) as ei:
        fut.result()
    assert isinstance(ei.value.cause, CompileError)
    assert "2D gather" in str(ei.value.cause)
    assert s.device_dispatches == 0 and s.retries == 0
    assert s.backend_fallbacks == 0 and s.queries_failed == 1


# --------------------------------------------------------------------- #
# (e) Batch fault isolation end to end
# --------------------------------------------------------------------- #
def _oracle(g):
    return trussness_numpy(g)


def test_transient_dispatch_fault_is_retried_under_fake_time():
    g = tiny()
    clk = FakeClock()
    with use_clock(clk):
        s = Session(
            backend="fine/xla/aligned",
            max_batch=2,
            chunk=64,
            faults=FaultPlan([FaultSpec("dispatch", times=1)]),
            retry=RetryPolicy(backoff_base_s=0.5),
        )
        dec = s.solve([TrussQuery.decompose(g)])[0]
    assert np.array_equal(dec.trussness, _oracle(g))
    assert s.retries == 1 and s.queries_failed == 0
    assert s.stats()["faults_injected"] == 1
    # backoff waited on the fake clock, not the wall
    assert clk.now() >= 0.5


def test_oom_fault_exhausts_retries_then_falls_back():
    g = tiny()
    s = Session(
        backend="fine/xla/aligned",
        max_batch=2,
        chunk=64,
        faults=FaultPlan([FaultSpec("device_oom", times=None)]),  # never heals
        retry=RetryPolicy(max_attempts=2, backoff_base_s=0.0),
    )
    fut = s.submit(TrussQuery.decompose(g))
    s.flush()
    with pytest.raises(QueryFailedError) as ei:
        fut.result()
    err = ei.value
    assert isinstance(err.cause, DeviceError) and err.cause.oom
    assert err.attempts >= 2  # retried on the primary before falling back
    assert tuple(str(b) for b in err.backends_tried) == (
        "fine/xla/aligned",
        "coarse/xla/aligned",
    )
    assert s.backend_fallbacks == 1 and s.queries_failed == 1


def test_compile_fault_falls_back_bit_identically():
    g = tiny()
    s = Session(
        backend="fine/xla/aligned",
        max_batch=2,
        chunk=64,
        faults=FaultPlan([FaultSpec("compile", times=1)]),
        retry=FAST_RETRY,
    )
    dec = s.solve([TrussQuery.decompose(g)])[0]
    assert np.array_equal(dec.trussness, _oracle(g))  # coarse parity
    assert s.backend_fallbacks == 1 and s.retries == 0


def test_poisoned_pallas_compile_lands_on_xla_bit_identically():
    """A forced Pallas kernel whose compile is poisoned takes the chain's
    first step (pallas -> xla, same formulation and layout) and lands the
    batch on fine/xla with oracle-identical results."""
    g = tiny()
    s = Session(
        backend="fine/pallas/aligned",
        max_batch=2,
        chunk=64,
        faults=FaultPlan(
            [
                FaultSpec(
                    "compile", times=1, where=(("backend", "fine/pallas/aligned"),)
                ),
            ]
        ),
        retry=FAST_RETRY,
    )
    fut = s.submit(TrussQuery.decompose(g))
    s.flush()
    dec = fut.result()
    assert fut.stats.backend == BackendKey("fine", "xla", "aligned")
    assert np.array_equal(dec.trussness, _oracle(g))
    assert s.backend_fallbacks == 1 and s.retries == 0
    assert s.obs.metrics.value(
        "backend_fallbacks", **{"from": "fine/pallas/aligned", "to": "fine/xla/aligned"}
    ) == 1


def test_poison_member_quarantined_survivors_bit_identical():
    gs = [tiny(seed=i) for i in range(3)]
    s = Session(
        backend="fine/xla/aligned", max_batch=4, chunk=64, retry=FAST_RETRY
    )
    futs = [s.submit(TrussQuery.decompose(g)) for g in gs]
    target = futs[1].request.id
    s.faults = FaultPlan(
        [FaultSpec("poison", times=None, where=(("query", target),))]
    )
    s.flush()
    with pytest.raises(QueryFailedError) as ei:
        futs[1].result()
    assert ei.value.query_id == target
    assert isinstance(ei.value.cause, InvalidGraphError)
    assert ei.value.cause.injected
    for i in (0, 2):  # batch-mates resolved bit-identically
        assert np.array_equal(futs[i].result().trussness, _oracle(gs[i]))
    assert s.queries_quarantined == 1
    assert s.queries_failed == 1


def test_unattributed_fault_bisects_to_isolate():
    gs = [tiny(seed=i) for i in range(4)]
    s = Session(
        backend="fine/xla/aligned",
        max_batch=4,
        chunk=64,
        faults=FaultPlan([FaultSpec("dispatch", times=None)]),  # hits everyone
        retry=RetryPolicy(max_attempts=1, backoff_base_s=0.0),
    )
    futs = [s.submit(TrussQuery.decompose(g)) for g in gs]
    s.flush()
    for f in futs:
        with pytest.raises(QueryFailedError):
            f.result()
    assert s.batch_bisects >= 1  # the batch was split to isolate
    assert s.queries_failed == 4


def test_clock_skew_fault_advances_fake_clock_only():
    g = tiny()
    clk = FakeClock()
    with use_clock(clk):
        s = Session(
            backend="fine/xla/aligned",
            max_batch=2,
            chunk=64,
            faults=FaultPlan([FaultSpec("clock_skew", times=1, skew_s=123.0)]),
            retry=FAST_RETRY,
        )
        dec = s.solve([TrussQuery.decompose(g)])[0]
        assert clk.now() >= 123.0  # time jumped mid-dispatch
    assert np.array_equal(dec.trussness, _oracle(g))  # results unaffected
    assert s.stats()["faults_injected"] == 1


def test_peel_iteration_cap_is_a_typed_device_error():
    g = erdos(60, 6.0, seed=2)
    s = Session(
        backend="fine/xla/aligned",
        max_batch=1,
        chunk=64,
        max_iters=1,  # provably too few trips to finish
        retry=RetryPolicy(max_attempts=1, backoff_base_s=0.0, fallback=False),
    )
    fut = s.submit(TrussQuery.decompose(g))
    s.flush()
    with pytest.raises(QueryFailedError) as ei:
        fut.result()
    assert isinstance(ei.value.cause, DeviceError)
    assert "iteration cap" in str(ei.value.cause)


def test_use_plan_scoping():
    plan = FaultPlan([FaultSpec("dispatch", times=None)])
    with use_plan(plan):
        with use_plan(None):  # inner fault-free scope masks the outer plan
            from repro.resilience.faults import current_plan

            assert current_plan() is None
        from repro.resilience.faults import current_plan

        assert current_plan() is plan


# --------------------------------------------------------------------- #
# (f) Streaming checkpoint / restore
# --------------------------------------------------------------------- #
def _stream_graph(seed=0):
    return erdos(40, 5.0, seed=seed)


def _batches(rng, g, count):
    """Deterministic mixed insert/delete batches against evolving state."""
    out = []
    for _ in range(count):
        ins = [
            (int(rng.integers(g.n)), int(rng.integers(g.n))) for _ in range(3)
        ]
        out.append(EdgeBatch.of(inserts=[(u, v) for u, v in ins if u != v]))
    return out


def test_checkpoint_roundtrip(tmp_path):
    g = _stream_graph()
    t = trussness_numpy(g)
    path = str(tmp_path / "state.npz")
    save_checkpoint(path, graph=g, trussness=t, tri_keys=None, updates_applied=3)
    ck = load_checkpoint(path)
    assert ck.graph.n == g.n and ck.graph.nnz == g.nnz
    assert np.array_equal(ck.graph.colidx, g.colidx)
    assert np.array_equal(ck.trussness, t)
    assert ck.tri_keys is None
    assert ck.meta["version"] == CHECKPOINT_VERSION
    assert ck.meta["updates_applied"] == 3
    assert ck.kmax == int(t.max(initial=0))


def test_checkpoint_refuses_inconsistent_state(tmp_path):
    g = _stream_graph()
    with pytest.raises(CheckpointError):
        save_checkpoint(
            str(tmp_path / "bad.npz"),
            graph=g,
            trussness=np.zeros(g.nnz + 1, np.int32),
        )


def test_checkpoint_detects_corruption(tmp_path):
    g = _stream_graph()
    path = str(tmp_path / "state.npz")
    save_checkpoint(path, graph=g, trussness=trussness_numpy(g))
    blob = bytearray(open(path, "rb").read())
    blob[len(blob) // 2] ^= 0xFF  # flip one byte mid-file
    open(path, "wb").write(bytes(blob))
    with pytest.raises(CheckpointError) as ei:
        load_checkpoint(path)
    assert ei.value.path == path


def test_checkpoint_missing_file_and_version(tmp_path):
    with pytest.raises(CheckpointError):
        load_checkpoint(str(tmp_path / "nope.npz"))
    assert latest_checkpoint(str(tmp_path / "empty-dir")) is None


def test_restored_session_continues_bit_identically(tmp_path):
    """The acceptance property: crash after checkpoint, restore, apply the
    same updates — state equals the session that never crashed."""
    rng = np.random.default_rng(7)
    g = _stream_graph()
    live = StreamingTrussSession.for_graph(g, backend="fine/xla/aligned", chunk=64)
    warm = _batches(rng, g, 2)
    tail = _batches(rng, g, 2)
    for b in warm:
        live.update(b, strict=False)
    path = live.checkpoint(str(tmp_path / "mid.npz"))

    # "crash": rebuild from disk only
    restored = restore_session(path, backend="fine/xla/aligned", chunk=64)
    assert np.array_equal(restored.trussness, live.trussness)
    assert restored._tri_cache is not None  # no re-enumeration needed
    assert restored._tri_cache.num_triangles == live._tri_cache.num_triangles

    for b in tail:
        ra = live.update(b, strict=False)
        rb = restored.update(b, strict=False)
        assert np.array_equal(ra.trussness, rb.trussness)
        assert ra.kmax == rb.kmax
    # full-state agreement with the from-scratch oracle
    assert np.array_equal(restored.trussness, trussness_numpy(restored.graph))


def test_auto_checkpoint_retention(tmp_path):
    rng = np.random.default_rng(3)
    g = _stream_graph(seed=1)
    ckdir = str(tmp_path / "ck")
    st = StreamingTrussSession.for_graph(
        g,
        backend="fine/xla/aligned",
        chunk=64,
        checkpoint_dir=ckdir,
        checkpoint_every=1,
    )
    for b in _batches(rng, g, 3):
        st.update(b, strict=False)
    files = sorted(os.listdir(ckdir))
    assert len(files) == 2  # keep-last-two retention
    assert st.checkpoints_written == 3
    assert st.stats()["checkpoints_written"] == 3
    # the latest checkpoint restores to the current committed state
    restored = StreamingTrussSession.restore(
        latest_checkpoint(ckdir), backend="fine/xla/aligned", chunk=64
    )
    assert np.array_equal(restored.trussness, st.trussness)


@settings(max_examples=4, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10**6))
def test_checkpoint_restore_property(seed):
    """Property form: for random update streams and a random split point,
    restore-then-continue equals never-crashed."""
    import tempfile

    rng = np.random.default_rng(seed)
    g = _stream_graph(seed=seed % 5)
    batches = _batches(rng, g, 3)
    cut = int(rng.integers(1, len(batches) + 1))

    live = StreamingTrussSession.for_graph(g, backend="fine/xla/aligned", chunk=64)
    for b in batches[:cut]:
        live.update(b, strict=False)
    with tempfile.TemporaryDirectory() as tmp:
        path = live.checkpoint(os.path.join(tmp, "cut.npz"))
        restored = restore_session(path, backend="fine/xla/aligned", chunk=64)
    for b in batches[cut:]:
        ra = live.update(b, strict=False)
        rb = restored.update(b, strict=False)
        assert np.array_equal(ra.trussness, rb.trussness)
    assert np.array_equal(restored.trussness, live.trussness)
