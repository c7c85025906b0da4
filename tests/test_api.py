"""repro.api: the declarative front door + planner/backend registry.

Pins the PR-level contracts:

* every registered (formulation × kernel × layout) backend returns
  bit-identical trussness on R-MAT and paper-style skewed graphs;
* the public surface (``repro.api.__all__``) is snapshot-locked so
  accidental breakage fails CI;
* a mixed ktruss/kmax/decompose/stream query set resolves in ONE device
  dispatch through ``Session.solve()``;
* ``TrussFuture.result(timeout=...)`` raises a named
  ``TrussTimeoutError`` carrying the bucket and queue depth;
* the auto rule picks formulations from the paper's imbalance stats.
"""

import numpy as np
import pytest

import repro.api as api
from repro.api import (
    BackendKey,
    Session,
    TrussQuery,
    TrussTimeoutError,
    available_backends,
    bucket_for,
    choose_backend,
    solve,
)
from repro.core import KTrussResult, TrussDecomposition, trussness_numpy
from repro.graphs import barabasi, erdos, imbalance_stats, rmat, road


def _same_bucket(factory, count, *, chunk=64, tries=64):
    groups = {}
    for s in range(tries):
        g = factory(s)
        groups.setdefault(bucket_for(g, chunk=chunk), []).append(g)
        if len(groups[bucket_for(g, chunk=chunk)]) == count:
            return groups[bucket_for(g, chunk=chunk)]
    raise AssertionError(f"no bucket reached {count} graphs in {tries} tries")


# ------------------------------------------------------------------ #
# (a) Registry parity: every backend, bit-identical trussness
# ------------------------------------------------------------------ #
@pytest.mark.parametrize(
    "backend", available_backends(), ids=[str(k) for k in available_backends()]
)
def test_backend_parity_bit_identical(backend):
    """R-MAT (the paper's heavy-tail regime) + Barabási (power-law) must
    decompose identically on every registered backend — the formulation /
    kernel / layout axes are performance choices, never semantic ones."""
    for g in [rmat(6, 4, seed=2), barabasi(70, 3, seed=0)]:
        dec = solve(
            TrussQuery.decompose(g), backend=backend, chunk=64, max_batch=2
        )
        assert isinstance(dec, TrussDecomposition)
        oracle = trussness_numpy(g)
        assert np.array_equal(dec.trussness, oracle), (str(backend), g.name)
        assert dec.kmax == int(oracle.max(initial=0))


@pytest.mark.parametrize(
    "backend", available_backends(), ids=[str(k) for k in available_backends()]
)
def test_warm_path_compiles_nothing(backend):
    """The runtime half of the R2 recompile lint: an identical query mix
    re-solved on a live session must hit the compile cache exactly — zero
    XLA compilations on the warm pass.  A failure here means some
    attribute the executor builder closes over leaked out of the
    compile-cache variant key (see ``Planner.cache_variant``)."""
    from repro.analysis.sentinel import assert_no_compiles

    s = Session(backend=backend, chunk=64, max_batch=2)
    gs = [rmat(6, 4, seed=3), erdos(40, 3.0, seed=1)]

    def mix():
        return s.solve([TrussQuery.decompose(g) for g in gs])

    cold = mix()
    with assert_no_compiles(f"warm solve on {backend}"):
        warm = mix()
    for c, w in zip(cold, warm):
        assert np.array_equal(c.trussness, w.trussness)


# ------------------------------------------------------------------ #
# (b) API surface snapshot
# ------------------------------------------------------------------ #
def test_api_surface_snapshot():
    """The public surface is part of the contract: additions are deliberate
    (update this snapshot), removals/renames fail CI."""
    assert sorted(api.__all__) == sorted(
        [
            "TrussQuery",
            "WORKLOADS",
            "PLACEMENTS",
            "solve",
            "Session",
            "TrussFuture",
            "TrussError",
            "InvalidGraphError",
            "CompileError",
            "DeviceError",
            "QueryFailedError",
            "TrussTimeoutError",
            "CheckpointError",
            "Planner",
            "Plan",
            "PlannedBatch",
            "QueryState",
            "QueryQueue",
            "RequestStats",
            "BackendKey",
            "BackendSpec",
            "FORMULATIONS",
            "KERNELS",
            "LAYOUTS",
            "register_backend",
            "get_backend",
            "available_backends",
            "choose_backend",
            "default_kernel",
            "fallback_backends",
            "Bucket",
            "bucket_for",
            "build_peel",
            "CompileCache",
            "enable_persistent_cache",
            "KTrussResult",
            "TrussDecomposition",
        ]
    )
    for name in api.__all__:
        assert hasattr(api, name), name


def test_default_backends_registered():
    keys = available_backends()
    # coarse×pallas is invalid (the hand kernel is fine-only); every other
    # point of the grid is registered for both layouts.
    assert len(keys) == 6
    assert BackendKey("fine", "xla", "aligned") in keys
    assert BackendKey("coarse", "xla", "contig") in keys
    assert BackendKey("fine", "pallas", "aligned") in keys
    assert BackendKey("coarse", "pallas", "aligned") not in keys
    assert BackendKey("fine", "fused", "aligned") not in keys


# ------------------------------------------------------------------ #
# (c) Mixed workloads through solve(): ONE dispatch per batch
# ------------------------------------------------------------------ #
def test_mixed_query_set_one_dispatch_via_solve():
    graphs = _same_bucket(lambda s: erdos(80, 6.0, seed=s), 4)
    g_stream = graphs[3]
    ref_stream = trussness_numpy(g_stream)
    s = Session(backend="fine/xla/aligned", max_batch=4, chunk=64)
    results = s.solve(
        [
            TrussQuery.ktruss(graphs[0], k=4),
            TrussQuery.kmax(graphs[1]),
            TrussQuery.decompose(graphs[2]),
            # An all-free frontier re-peel is exactly a decompose.
            TrussQuery.stream_update(
                g_stream,
                frontier=np.ones(g_stream.nnz, bool),
                frozen_truss=np.zeros(g_stream.nnz, np.int32),
            ),
        ]
    )
    st = s.stats()
    assert st["device_dispatches"] == 1, st  # the whole mixed set: once
    assert st["batches_run"] == 1 and st["pending"] == 0

    r_kt, r_km, r_dc, r_st = results
    assert isinstance(r_kt, KTrussResult) and r_kt.k == 4
    assert r_km == int(trussness_numpy(graphs[1]).max(initial=0))
    assert np.array_equal(r_dc.trussness, trussness_numpy(graphs[2]))
    assert np.array_equal(r_st, ref_stream)


def test_different_backends_split_batches():
    """Queries forcing different backends cannot share an executable, so
    they form separate dispatches even inside one bucket."""
    graphs = _same_bucket(lambda s: erdos(80, 6.0, seed=s), 2)
    s = Session(max_batch=4, chunk=64)
    s.solve(
        [
            TrussQuery.kmax(graphs[0], backend="fine/xla/aligned"),
            TrussQuery.kmax(graphs[1], backend="coarse/xla/aligned"),
        ]
    )
    assert s.stats()["device_dispatches"] == 2


# ------------------------------------------------------------------ #
# (d) result(timeout=...) raises the named error with context
# ------------------------------------------------------------------ #
def test_future_timeout_sheds_query_and_reclaims_slot():
    """Timeout marks the query dead (default shed_on_timeout=True): its
    queue slot is reclaimed — no leak — and batch-mates still resolve."""
    graphs = _same_bucket(lambda s: erdos(60, 5.0, seed=s), 2)
    s = Session(backend="fine/xla/aligned", max_batch=1, chunk=64)
    f1 = s.submit(TrussQuery.kmax(graphs[0]))
    f2 = s.submit(TrussQuery.kmax(graphs[1]))
    with pytest.raises(TrussTimeoutError) as ei:
        f2.result(timeout=0)
    err = ei.value
    assert err.bucket == bucket_for(graphs[1], chunk=64)
    assert err.queue_depth == 2  # both queries were still queued
    assert err.request_id is not None
    assert err.shed is True
    assert "queue_depth" in str(err) and isinstance(err, TimeoutError)
    # The dead query's slot was reclaimed; it re-raises, never re-runs.
    assert len(s.queue) == 1
    with pytest.raises(TrussTimeoutError):
        f2.result(timeout=None)
    assert s.stats()["queries_shed"] == 1
    # The batch-mate is unaffected.
    assert f1.result(timeout=None) == int(
        trussness_numpy(graphs[0]).max(initial=0)
    )
    assert s.stats()["pending"] == 0


def test_future_timeout_without_shedding_keeps_query_resolvable():
    """shed_on_timeout=False is the legacy escape hatch: a timed-out query
    stays queued and a later result() still resolves it."""
    g = erdos(60, 5.0, seed=0)
    s = Session(
        backend="fine/xla/aligned", max_batch=1, chunk=64, shed_on_timeout=False
    )
    fut = s.submit(TrussQuery.kmax(g))
    with pytest.raises(TrussTimeoutError) as ei:
        fut.result(timeout=0)
    assert ei.value.shed is False
    assert fut.result(timeout=None) == int(trussness_numpy(g).max(initial=0))
    assert s.stats()["queries_shed"] == 0


def test_deadline_is_default_result_budget():
    g = erdos(60, 5.0, seed=0)
    s = Session(
        backend="fine/xla/aligned", max_batch=1, chunk=64, shed_on_timeout=False
    )
    fut = s.submit(TrussQuery.kmax(g, deadline_s=0.0))
    with pytest.raises(TrussTimeoutError):
        fut.result()  # expired deadline is the default timeout
    assert fut.result(timeout=None) >= 0  # explicit timeout overrides


# ------------------------------------------------------------------ #
# (e) Auto rule: formulation keyed on the paper's imbalance statistics
# ------------------------------------------------------------------ #
def test_auto_rule_tracks_imbalance():
    skew = choose_backend(imbalance_stats(rmat(8, 5, seed=1)), kernel="xla")
    assert skew.formulation == "fine"  # heavy tail -> nonzero tasks
    grid = choose_backend(imbalance_stats(road(8, 0.1, seed=0)), kernel="xla")
    assert grid.formulation == "coarse"  # balanced -> row tasks
    # Pallas implements the fine formulation only.
    forced = choose_backend(imbalance_stats(road(8, 0.1, seed=0)), kernel="pallas")
    assert forced.formulation == "fine"


def test_auto_rule_end_to_end_identical_results():
    """Whatever the auto rule picks, results equal the oracle."""
    for g in [road(8, 0.1, seed=0), rmat(6, 4, seed=3)]:
        dec = solve(TrussQuery.decompose(g), chunk=64, max_batch=1)
        assert np.array_equal(dec.trussness, trussness_numpy(g)), g.name


# ------------------------------------------------------------------ #
# (f) Query validation
# ------------------------------------------------------------------ #
def test_query_validation():
    g = erdos(30, 4.0, seed=0)
    with pytest.raises(ValueError):
        TrussQuery(graph=g, workload="nope")
    with pytest.raises(ValueError):
        TrussQuery.ktruss(g, k=2)
    with pytest.raises(ValueError):
        TrussQuery(graph=g, workload="stream_update")  # missing frontier
    with pytest.raises(ValueError):
        TrussQuery.stream_update(
            g,
            frontier=np.ones(3, bool),  # wrong length
            frozen_truss=np.zeros(3, np.int32),
        )
    with pytest.raises(ValueError):
        TrussQuery.ktruss(g, k=3, frontier=np.ones(g.nnz, bool))
    with pytest.raises(ValueError):
        TrussQuery.ktruss(g, k=3, placement="everywhere")


def test_solve_single_query_roundtrip():
    g = erdos(50, 5.0, seed=1)
    km = solve(TrussQuery.kmax(g), backend="fine/xla/aligned", chunk=64, max_batch=1)
    assert km == int(trussness_numpy(g).max(initial=0))
