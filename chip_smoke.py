#!/usr/bin/env python3
"""Chip smoke test: the ``repro.api`` main path once on a TPU, checked.

Run from the root of a checkout, on a machine with a TPU::

    python3 chip_smoke.py             # one chip: large-static, many-small, stream
    python3 chip_smoke.py --chips 4   # four chips: the many-small batch on a
                                      # 4-device slot mesh vs the same batch
                                      # unsharded, and nothing else

Every phase goes through the normal entry points (``Session.solve``,
``Session.submit``/``flush``, ``Session.open_stream``), checks every
output against the numpy oracle ``repro.core.reference.trussness_numpy``
(the four-chip phase: against the same batch unsharded) and prints one
JSON line.  Sessions run with a ``RetryPolicy`` that neither retries nor
falls back, so a kernel that fails on the chip fails the smoke instead of
being hidden behind an XLA fallback.  The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

The script exits non-zero and prints no result line when JAX finds no
TPU, when ``src/`` of the checkout is missing, or when any phase fails.
It is one process and starts no child that touches JAX.  The persistent
compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to
``<checkout>/.jax_cache`` (``repro.api.cache.persistent_cache_dir``); each
run prints how many compiles that cache served.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import numpy as np

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

SMALL_GRAPHS = 32


# The repo's largest configurations (src/repro/configs/ktruss.py) cut in
# scale, with family, edge factor and seed kept, until each phase fits the
# smoke's time limit on one v5e chip: one support pass of the fine/xla peel
# took 6.0 s at rmat-12 and 106 s at rmat-14 there.  Each phase's line
# names the configuration it was cut from.
def large_static_graphs():
    """``(name, cut from, graph)`` for the large-static phase."""
    from repro.graphs import rmat, road

    return [
        ("rmat-9", "rmat-15", rmat(9, 8, seed=21)),
        ("road-512", None, road(512, 0.05, seed=23)),
    ]


def stream_graph():
    """``(name, cut from, graph)`` for the stream phase."""
    from repro.graphs import rmat

    return ("rmat-9", "rmat-12", rmat(9, 6, seed=15))


def small_graph(seed: int):
    """The many-small population's generator: 512 vertices, mean degree
    16 (kmax 4, so every workload of the mix has work)."""
    from repro.graphs import erdos

    return erdos(512, 16.0, seed=seed)


class SmokeFailure(RuntimeError):
    """A phase produced a wrong answer, a fault, or a fallback."""


def _strict_session(**kwargs):
    """A traced Session that surfaces every fault: one attempt, no
    fallback down the registry chain, no batch bisection."""
    from repro.api import Session
    from repro.resilience.retry import RetryPolicy

    policy = RetryPolicy(max_attempts=1, fallback=False, bisect=False)
    return Session(trace=True, retry=policy, **kwargs)


def _counters(*sessions) -> dict:
    """The phase line's fault and dispatch counters, summed over sessions."""
    keys = ("device_dispatches", "retries", "backend_fallbacks", "queries_failed")
    stats = [s.stats() for s in sessions]
    out = {k: sum(st[k] for st in stats) for k in keys}
    out["planner_backends"] = sorted(
        {row["backend"] for row in stats[0]["planner_backends"]}
    )
    return out


def _span_seconds(*sessions) -> dict:
    """Total seconds per span name (``plan``, ``pack``, ``compile``,
    ``dispatch``, ``device-wait``, ``unpack``, ``stream.*``) over the
    sessions' traces; executors compile ahead of time inside ``compile``."""
    out: dict = {}
    for s in sessions:
        for ev in s.obs.tracer.events():
            if ev.get("ph") == "X":
                out[ev["name"]] = out.get(ev["name"], 0.0) + ev["dur"] / 1e6
    return out


def _timing(*sessions) -> dict:
    spans = _span_seconds(*sessions)
    return {"compile_s": spans.get("compile", 0.0), "spans_s": spans}


def _expected_kmax(trussness: np.ndarray) -> int:
    top = int(trussness.max(initial=0))
    return top if top >= 3 else 0


def _check_line(line: dict, ok: bool, what: str) -> dict:
    line["check_passed"] = bool(ok)
    faults = {k: line[k] for k in ("retries", "backend_fallbacks", "queries_failed")}
    if not ok:
        raise SmokeFailure(f"{what}: {line['check']} check failed: {json.dumps(line)}")
    if any(faults.values()):
        raise SmokeFailure(f"{what}: faults {faults}: {json.dumps(line)}")
    return line


def phase_large_static(name: str, g, *, max_batch: int = 2,
                       cut_from: str | None = None) -> dict:
    """``solve([decompose(g), kmax(g)])`` twice on one session — cold,
    then warm — checked against the oracle."""
    from repro.api import TrussQuery
    from repro.core.reference import trussness_numpy

    s = _strict_session(max_batch=max_batch)
    queries = [TrussQuery.decompose(g), TrussQuery.kmax(g)]
    t0 = time.perf_counter()
    dec, kmax = s.solve(queries)
    cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    dec_w, kmax_w = s.solve(queries)
    warm_s = time.perf_counter() - t0
    ref = trussness_numpy(g)
    ok = (
        np.array_equal(dec.trussness, ref)
        and np.array_equal(dec_w.trussness, ref)
        and kmax == kmax_w == _expected_kmax(ref)
    )
    line = {
        "phase": "large-static",
        "graph": name,
        "cut_from": cut_from,
        "n": g.n,
        "nnz": g.nnz,
        "max_batch": max_batch,
        **_counters(s),
        **_timing(s),
        "cold_s": cold_s,
        "warm_s": warm_s,
        "kmax": kmax,
        "check": "oracle:trussness_numpy",
    }
    return _check_line(line, ok, f"large-static {name}")


def small_population(count: int, make, *, tries: int = 256):
    """``count`` graphs ``make(seed)`` sharing one shape bucket and one
    auto-rule backend, so that they batch together."""
    from repro.api import choose_backend
    from repro.api.cache import bucket_for
    from repro.graphs.stats import imbalance_stats

    groups: dict = {}
    for seed in range(tries):
        g = make(seed)
        key = (bucket_for(g), choose_backend(imbalance_stats(g)))
        groups.setdefault(key, []).append(g)
        if len(groups[key]) == count:
            return groups[key]
    raise SmokeFailure(f"no bucket collected {count} graphs in {tries} seeds")


def _many_small_queries(graphs):
    """The ktruss(k=4) / kmax / decompose mix, one query per graph."""
    from repro.api import TrussQuery

    makers = (
        lambda g: TrussQuery.ktruss(g, 4),
        TrussQuery.kmax,
        TrussQuery.decompose,
    )
    return [makers[i % 3](g) for i, g in enumerate(graphs)]


def _many_small_ok(queries, results, oracle) -> bool:
    for q, r, ref in zip(queries, results, oracle):
        if q.workload == "ktruss":
            if not np.array_equal(r.alive, ref >= 4):
                return False
        elif q.workload == "kmax":
            if r != _expected_kmax(ref):
                return False
        elif not np.array_equal(r.trussness, ref):
            return False
    return True


def _serve_twice(session, graphs):
    """Submit the mix and flush, twice; the second flush must compile
    nothing.  Returns (results of both flushes, warm seconds, compiles)."""
    from repro.analysis.sentinel import count_compiles

    queries = _many_small_queries(graphs)
    futures = [session.submit(q) for q in queries]
    session.flush()
    first = [f.result() for f in futures]
    futures = [session.submit(q) for q in queries]
    with count_compiles() as log:
        t0 = time.perf_counter()
        session.flush()
        warm_s = time.perf_counter() - t0
    second = [f.result() for f in futures]
    return queries, first, second, warm_s, log.compiles


def phase_many_small(graphs, *, max_batch: int = 8) -> dict:
    """One ``Session(max_batch=8)`` serving the ktruss/kmax/decompose mix
    over every graph, flushed twice; checked against the oracle."""
    from repro.api.cache import bucket_for, bucket_str
    from repro.core.reference import trussness_numpy

    s = _strict_session(max_batch=max_batch)
    queries, first, second, warm_s, warm_compiles = _serve_twice(s, graphs)
    oracle = [trussness_numpy(g) for g in graphs]
    ok = (
        _many_small_ok(queries, first, oracle)
        and _many_small_ok(queries, second, oracle)
        and warm_compiles == 0
    )
    line = {
        "phase": "many-small",
        "graphs": len(graphs),
        "bucket": bucket_str(bucket_for(graphs[0])),
        "max_batch": max_batch,
        **_counters(s),
        **_timing(s),
        "warm_s": warm_s,
        "second_flush_compiles": warm_compiles,
        "check": "oracle:trussness_numpy",
    }
    return _check_line(line, ok, "many-small")


def random_update(rng, g, *, inserts: int, deletes: int):
    """An ``EdgeBatch`` of ``inserts`` new edges and ``deletes`` existing
    ones (0-based endpoints), drawn from ``rng``."""
    from repro.stream import EdgeBatch

    edges = g.edge_list() - 1
    existing = set(map(tuple, edges.tolist()))
    ins = []
    while len(ins) < inserts:
        a, b = (int(x) for x in rng.integers(0, g.n, 2))
        key = (min(a, b), max(a, b))
        if a != b and key not in existing:
            existing.add(key)
            ins.append(key)
    dels = edges[rng.permutation(g.nnz)[:deletes]]
    return EdgeBatch.of(ins, [tuple(e) for e in dels.tolist()])


def phase_stream(name: str, g, *, updates: int = 5, inserts: int = 64,
                 deletes: int = 64, seed: int = 0,
                 cut_from: str | None = None) -> dict:
    """``open_stream(g)`` then ``updates`` insert/delete batches; the
    maintained trussness is checked against the oracle after each one."""
    from repro.core.reference import trussness_numpy

    rng = np.random.default_rng(seed)
    s = _strict_session(max_batch=1)
    t0 = time.perf_counter()
    stream = s.open_stream(g)
    open_s = time.perf_counter() - t0
    ok = np.array_equal(stream.trussness, trussness_numpy(g))
    update_s = []
    for _ in range(updates):
        batch = random_update(rng, stream.graph, inserts=inserts, deletes=deletes)
        t0 = time.perf_counter()
        res = stream.update(batch)
        update_s.append(time.perf_counter() - t0)
        ok = ok and np.array_equal(res.trussness, trussness_numpy(stream.graph))
    line = {
        "phase": "stream",
        "graph": name,
        "cut_from": cut_from,
        "nnz": g.nnz,
        "updates": updates,
        **_counters(s),
        **_timing(s),
        "open_s": open_s,
        "warm_s": update_s[-1] if update_s else 0.0,
        "update_s": update_s,
        "frontier_edges": stream.edges_repeeled,
        "check": "oracle:trussness_numpy after each update",
    }
    return _check_line(line, ok, f"stream {name}")


def phase_sharded(graphs, *, devices: int, max_batch: int = 8) -> dict:
    """The many-small mix on ``Session(mesh=slot_mesh(devices))`` against
    the same mix unsharded, in this process: results must be
    bit-identical, with one dispatch per sharded batch."""
    from repro.api.cache import bucket_for, bucket_str
    from repro.distributed import slot_mesh

    sharded = _strict_session(max_batch=max_batch, mesh=slot_mesh(devices))
    plain = _strict_session(max_batch=max_batch)
    queries, first, second, warm_s, warm_compiles = _serve_twice(sharded, graphs)
    _, ref, _, _, _ = _serve_twice(plain, graphs)

    def same(a, b):
        if isinstance(a, (int, np.integer)):
            return a == b
        field = "alive" if hasattr(a, "alive") else "trussness"
        return np.array_equal(getattr(a, field), getattr(b, field))

    batches = -(-len(graphs) // max_batch)
    counters = _counters(sharded)
    ok = (
        all(same(a, b) for a, b in zip(first, ref))
        and all(same(a, b) for a, b in zip(second, ref))
        and counters["device_dispatches"] == 2 * batches
        and warm_compiles == 0
    )
    line = {
        "phase": "sharded-many-small",
        "devices": devices,
        "graphs": len(graphs),
        "bucket": bucket_str(bucket_for(graphs[0])),
        "max_batch": max_batch,
        **counters,
        "dispatches_per_batch": counters["device_dispatches"] / (2 * batches),
        "unsharded": _counters(plain),
        **_timing(sharded),
        "warm_s": warm_s,
        "second_flush_compiles": warm_compiles,
        "check": "bit-identity:unsharded",
    }
    return _check_line(line, ok, "sharded many-small")


def run(phases, *, device_kind: str) -> None:
    """Run each phase (a no-argument callable) and print its line."""
    for phase in phases:
        print(json.dumps({"device_kind": device_kind, **phase()}), flush=True)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument(
        "--chips",
        type=int,
        choices=(1, 4),
        default=1,
        help="4: run only the sharded many-small phase on a 4-chip slot mesh",
    )
    args = parser.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform: {platform})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devices)}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, _SRC)
    try:
        from repro.api.cache import enable_persistent_cache
    except ImportError as e:
        print(f"chip_smoke: the checkout's src/ is missing: {e}", file=sys.stderr)
        return 2

    cache_dir = enable_persistent_cache()
    cache_hits = []
    jax.monitoring.register_event_listener(
        lambda event, **kw: cache_hits.append(event)
        if event == "/jax/compilation_cache/cache_hits"
        else None
    )
    kind = devices[0].device_kind
    small = small_population(SMALL_GRAPHS, small_graph)
    if args.chips == 4:
        phases = [functools.partial(phase_sharded, small, devices=4)]
    else:
        phases = [
            functools.partial(phase_large_static, name, g, cut_from=cut)
            for name, cut, g in large_static_graphs()
        ]
        phases.append(functools.partial(phase_many_small, small))
        name, cut, g = stream_graph()
        phases.append(functools.partial(phase_stream, name, g, cut_from=cut))
    t0 = time.perf_counter()
    try:
        run(phases, device_kind=kind)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({
        "phase": "summary",
        "device_kind": kind,
        "seconds": time.perf_counter() - t0,
        "compile_cache_dir": cache_dir,
        "compile_cache_hits": len(cache_hits),
    }), flush=True)
    print(json.dumps({
        "ok": True,
        "device": {"platform": platform, "kind": kind, "count": len(devices)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
