"""Planner: lower declarative ``TrussQuery`` sets onto the device peel.

This is the ONE pack/cache/dispatch path every entry point shares — the
glue that used to be triplicated across ``service/service.py`` (batched
serving), ``core/truss.py`` (single-graph engine) and
``stream/session.py`` (streaming re-peels).  Lowering one batch:

1. **assign** — each query is canonicalized to a shape :class:`Bucket`
   and a registry :class:`BackendKey` (forced per query or per planner,
   else the imbalance-statistic auto rule of ``repro.api.registry``);
2. **pack**  — same-``(bucket, backend)`` queries are packed
   block-diagonally (``repro.graphs.pack``) in the backend's layout;
3. **dispatch** — the bucket's cached :class:`repro.exec.PeelExecutor`
   peels every member to completion in ONE device call (per-slot
   thresholds advance inside the compiled loop; ktruss members retire at
   their first fixed point, kmax/decompose peel to exhaustion, stream
   members re-peel only their frontier against frozen lanes);
4. **unpack** — each member's edge range is read back into its workload's
   result type.

The planner is deliberately stateless about queues and futures — that is
:class:`repro.api.Session`'s job — so ``solve()`` and the legacy
adapters can drive the same lowering from different control flows.
"""

from __future__ import annotations

import dataclasses
import itertools
import threading
from typing import Any, Optional

import numpy as np

from ..core.truss import KTrussResult, TrussDecomposition
from ..errors import DeviceError, TrussError
from ..graphs.pack import pack_problems
from ..graphs.stats import imbalance_stats
from ..obs import current_tracer, record_peel_batch
from ..obs import clock as obs_clock
from ..resilience.faults import inject
from .cache import Bucket, CompileCache, bucket_for
from .query import TrussQuery
from .registry import BackendKey, choose_backend, default_kernel, get_backend

__all__ = ["RequestStats", "QueryState", "PlannedBatch", "Plan", "Planner"]

_ids = itertools.count()


@dataclasses.dataclass
class RequestStats:
    """Per-query observability (exposed on the future)."""

    queue_time_s: float = 0.0  # submit -> batch formation
    pack_time_s: float = 0.0  # host-side block-diagonal packing (shared)
    # Host wall time from the peel's launch to its readback (shared): the
    # device's run plus whatever the host did meanwhile; not device time.
    device_time_s: float = 0.0
    plan_time_s: float = 0.0  # bucket + backend assignment for THIS query
    compile_hit: bool = False  # did the batch reuse a cached executable
    bucket: Optional[Bucket] = None
    backend: Optional[BackendKey] = None
    batch_size: int = 0  # real members in the packed batch
    rounds: int = 0  # fixed-point levels THIS member peeled
    iterations: int = 0  # prune iterations while THIS member was live


@dataclasses.dataclass
class QueryState:
    """A submitted query with its planner assignment (queue entry)."""

    query: TrussQuery
    bucket: Bucket
    backend: BackendKey
    submitted_at: float = dataclasses.field(default_factory=obs_clock.now)
    id: int = dataclasses.field(default_factory=lambda: next(_ids))
    stats: RequestStats = dataclasses.field(default_factory=RequestStats)

    @property
    def group(self) -> tuple[Bucket, BackendKey]:
        """Batchable-together key: same bucket AND same backend."""
        return (self.bucket, self.backend)

    def time_remaining(self) -> float | None:
        """Seconds left of this query's deadline budget (``None`` = no
        deadline).  The ONE place deadline arithmetic happens — on the
        observability clock, so tests can fake time instead of sleeping."""
        return obs_clock.remaining(self.submitted_at, self.query.deadline_s)

    # Legacy aliases (the old service Request shape) ------------------- #
    @property
    def graph(self):
        return self.query.graph

    @property
    def workload(self) -> str:
        return self.query.workload

    @property
    def k(self) -> int:
        return self.query.k


@dataclasses.dataclass
class PlannedBatch:
    """One packed dispatch: same-(bucket, backend) queries on ``slots`` slots.

    ``id`` is the owning session's batch number (``None`` outside a
    session); every span of the batch's lowering carries it as ``batch``.
    """

    bucket: Bucket
    backend: BackendKey
    queries: list[QueryState]
    slots: int
    id: int | None = None


@dataclasses.dataclass
class Plan:
    """A lowered query set (``Planner.plan``): batches in dispatch order."""

    batches: list[PlannedBatch]
    plan_time_s: float = 0.0

    @property
    def num_queries(self) -> int:
        return sum(len(b.queries) for b in self.batches)

    @property
    def num_dispatches(self) -> int:
        return len(self.batches)


class Planner:
    """Lowers queries onto ``(bucket, backend)`` batches and executes them."""

    def __init__(
        self,
        *,
        max_batch: int = 8,
        chunk: int = 256,
        kernel: str | None = None,
        layout: str | None = None,
        backend: BackendKey | str | None = None,
        mode: str | None = None,
        max_iters: int | None = None,
        mesh=None,
    ):
        if chunk & (chunk - 1):
            raise ValueError(f"chunk={chunk} must be a power of two")
        if max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        self.max_batch = int(max_batch)
        self.chunk = int(chunk)
        self.kernel = kernel or default_kernel()
        self.mode = mode
        # None = the peel's provable iteration bound; an explicit cap that
        # fires raises instead of returning truncated results.
        self.max_iters = None if max_iters is None else int(max_iters)
        self.mesh = mesh
        if mesh is not None:
            if layout is not None and layout != "aligned":
                raise ValueError(
                    "mesh sharding needs layout='aligned' (slot blocks are "
                    "the shard boundaries)"
                )
            layout = "aligned"
            self._mesh_key = (
                tuple(mesh.axis_names),
                tuple(dict(mesh.shape).values()),
            )
        else:
            self._mesh_key = None
        self.layout = layout or "aligned"
        # Forced backend for every query (None = per-query auto rule).
        self.backend = get_backend(backend).key if backend is not None else None
        if (
            mesh is not None
            and self.backend is not None
            and self.backend.layout != "aligned"
        ):
            raise ValueError(
                f"backend {self.backend} has layout={self.backend.layout!r}, "
                "but mesh sharding needs layout='aligned'"
            )
        # Observability + shared caches.  Concurrent submitters (the
        # serving tier's connection threads) all assign through one
        # planner, so everything mutable below is lock-guarded.
        self._stats_lock = threading.Lock()
        self._slot_ids: dict[tuple[int, int], Any] = {}  # guarded-by: _stats_lock
        self.queries_planned = 0  # guarded-by: _stats_lock
        self.plan_time_s = 0.0  # guarded-by: _stats_lock
        self.backend_choices: dict[tuple[Bucket, BackendKey], int] = {}  # guarded-by: _stats_lock

    # ------------------------------------------------------------------ #
    # Assignment: query -> (bucket, backend)
    # ------------------------------------------------------------------ #
    def assign(self, query: TrussQuery) -> QueryState:
        """Canonicalize one query: shape bucket + registry backend."""
        t0 = obs_clock.now()
        with current_tracer().span("plan", workload=query.workload) as span:
            bucket = bucket_for(query.graph, chunk=self.chunk)
            if query.placement == "sharded" and self.mesh is None:
                raise ValueError("placement='sharded' needs a session mesh")
            if query.placement == "replicated" and self.mesh is not None:
                raise ValueError(
                    "placement='replicated' conflicts with the session mesh "
                    "(placement is per-session; open a mesh-less session)"
                )
            key = query.backend if query.backend is not None else self.backend
            if key is None:
                key = choose_backend(
                    imbalance_stats(query.graph),
                    kernel=self.kernel,
                    layout=self.layout,
                )
            else:
                key = get_backend(key).key
            if self.mesh is not None and key.layout != "aligned":
                # The aligned layout is what makes slot boundaries shard
                # boundaries; a contig backend on a mesh would split member
                # graphs across devices.
                raise ValueError(
                    f"backend {key} has layout={key.layout!r}, but mesh "
                    "sharding needs layout='aligned'"
                )
            span.attrs["backend"] = str(key)
        dt = obs_clock.now() - t0
        with self._stats_lock:
            self.queries_planned += 1
            self.plan_time_s += dt
            self.backend_choices[(bucket, key)] = (
                self.backend_choices.get((bucket, key), 0) + 1
            )
        state = QueryState(query=query, bucket=bucket, backend=key)
        state.stats.plan_time_s = dt
        state.stats.bucket = bucket
        state.stats.backend = key
        return state

    def plan(self, states: list[QueryState]) -> Plan:
        """Group assigned queries into dispatchable batches (FIFO within a
        ``(bucket, backend)`` group, at most ``max_batch`` members each)."""
        t0 = obs_clock.now()
        batches: list[PlannedBatch] = []
        by_group: dict[tuple, list[QueryState]] = {}
        order: list[tuple] = []
        for st in states:
            if st.group not in by_group:
                by_group[st.group] = []
                order.append(st.group)
            by_group[st.group].append(st)
        for group in order:
            members = by_group[group]
            for at in range(0, len(members), self.max_batch):
                chunk_members = members[at : at + self.max_batch]
                batches.append(
                    PlannedBatch(
                        bucket=group[0],
                        backend=group[1],
                        queries=chunk_members,
                        slots=self.max_batch,
                    )
                )
        dt = obs_clock.now() - t0
        with self._stats_lock:
            self.plan_time_s += dt  # batching is planning work too
        return Plan(batches=batches, plan_time_s=dt)

    # ------------------------------------------------------------------ #
    # Lowering: batch -> one device dispatch -> per-query results
    # ------------------------------------------------------------------ #
    def cache_variant(self, backend: BackendKey):
        """What beyond (bucket, slots) specializes the executable.

        Every planner attribute ``build_executor`` closes over MUST be
        folded in here (``mesh`` rides as its hashable ``_mesh_key``) —
        a closed-over scalar missing from this tuple is a recompile
        hazard: two configs would share one cache row and the second
        would silently reuse the first's executable.  The R2 lint
        (``repro.analysis.rules_recompile``) enforces the invariant."""
        return (
            backend,
            self.mode,
            self._mesh_key,
            self.chunk,
            self.max_iters,
        )

    def build_executor(self, key: tuple[Bucket, int, Any]):
        """Compile-cache builder: one peel executor per cache key,
        compiled ahead of time for the key's packed shapes — a kernel the
        device's compiler refuses raises here, and the cache types it as
        a ``CompileError``.

        ``chunk``/``max_iters`` are read from the key, not ``self`` —
        every non-static input that specializes the executable must
        arrive through the variant tuple (see :meth:`cache_variant`).
        ``self.mesh`` is the one closed-over object (unhashable), keyed
        by its ``_mesh_key`` fold."""
        bucket, slots, (backend, mode, _mesh_key, chunk, max_iters) = key
        exe = get_backend(backend).make_executor(
            window=bucket.window,
            chunk=chunk,
            max_iters=max_iters,
            mesh=self.mesh,
            mode=mode,
        )
        exe.compile(
            n=slots * bucket.n_pad, nnz_pad=slots * bucket.nnz_pad, slots=slots
        )
        return exe

    def chips_used(self, batch: PlannedBatch) -> int:
        """Devices of the mesh whose slot block holds at least one of the
        batch's members (1 without a mesh): members take slots 0..m-1, and
        each device holds an equal run of consecutive slots."""
        if self.mesh is None:
            return 1
        slots_per_chip = batch.slots // self.mesh.size
        return -(-len(batch.queries) // slots_per_chip)

    def _slot_ids_for(self, batch: PlannedBatch, edge_ranges) -> np.ndarray:
        nnzp_total = batch.slots * batch.bucket.nnz_pad
        if batch.backend.layout == "aligned":
            # Lane blocks are slot blocks: one cached id vector per shape.
            cache_key = (batch.slots, batch.bucket.nnz_pad)
            with self._stats_lock:
                ids = self._slot_ids.get(cache_key)
            if ids is None:
                import jax.numpy as jnp

                ids = jnp.asarray(
                    np.repeat(
                        np.arange(batch.slots, dtype=np.int32),
                        batch.bucket.nnz_pad,
                    )
                )
                with self._stats_lock:
                    # Two threads may build the same vector concurrently;
                    # first writer wins so every batch shares one device
                    # array per shape.
                    ids = self._slot_ids.setdefault(cache_key, ids)
            return ids
        # Contig layout: members are prefix-packed, so slot ownership
        # depends on this batch's member sizes.  Pad-tail lanes are dead
        # (never alive, never frozen) — parking them on slot 0 is inert.
        ids = np.zeros(nnzp_total, np.int32)
        for i, (a, b) in enumerate(edge_ranges):
            ids[a:b] = i
        return ids

    def execute(self, batch: PlannedBatch, cache: CompileCache) -> list[Any]:
        """Run one planned batch — ONE device dispatch — and unpack results.

        Returns one result per query, in batch order: ``KTrussResult``
        (ktruss), ``int`` (kmax), ``TrussDecomposition`` (decompose), or
        the member's full ``(nnz,)`` trussness (stream_update).  Every
        span of the lowering carries the batch's id as ``batch``.
        """
        with current_tracer().tagged(batch=batch.id):
            return self._execute(batch, cache)

    def _execute(self, batch: PlannedBatch, cache: CompileCache) -> list[Any]:
        bucket, backend, queries = batch.bucket, batch.backend, batch.queries
        tracer = current_tracer()
        qids = tuple(st.id for st in queries)
        # Fault sites (repro.resilience.faults): no-ops without an active
        # FaultPlan; under one, these are where the chaos suite makes the
        # dispatch fail in every taxonomy-distinct way.
        for i, st in enumerate(queries):
            inject(
                "poison",
                slot=i,
                query=st.id,
                queries=qids,
                bucket=bucket,
                backend=str(backend),
            )
        t0 = obs_clock.now()
        with tracer.span(
            "pack",
            members=len(queries),
            slots=batch.slots,
            layout=backend.layout,
            chips_used=self.chips_used(batch),
        ):
            packed = pack_problems(
                [st.query.graph for st in queries],
                slot_n=bucket.n_pad,
                slot_nnz=bucket.nnz_pad,
                slots=batch.slots,
                chunk=self.chunk,
                layout=backend.layout,
            )
        pack_dt = obs_clock.now() - t0
        with tracer.span("compile", backend=str(backend)) as span:
            inject("compile", bucket=bucket, backend=str(backend), queries=qids)
            exe, hit = cache.get(
                bucket, batch.slots, self.cache_variant(backend)
            )
            span.attrs["hit"] = hit
        for st in queries:
            st.stats.pack_time_s = pack_dt
            st.stats.compile_hit = hit

        slot_ids = self._slot_ids_for(batch, packed.edge_ranges)
        k0 = np.full(batch.slots, 3, np.int32)
        single_level = np.zeros(batch.slots, bool)
        for i, st in enumerate(queries):
            k0[i] = st.query.k
            single_level[i] = st.query.workload == "ktruss"

        # Streaming members peel only their affected frontier; the rest of
        # their lanes are frozen at the session's maintained trussness.
        # Ordinary members stay on the executor's defaults (fully alive,
        # nothing frozen) — zeros here reproduce those defaults exactly.
        alive0 = frozen = frozen_truss = None
        if any(st.query.workload == "stream_update" for st in queries):
            import jax.numpy as jnp

            # The default alive mask ("every real lane") is a pure
            # function of the pack's host-side edge ranges: pad lanes sit
            # outside every member's range (colidx == 0 there, see
            # graphs.pack).  Building it from edge_ranges avoids a
            # device->host colidx readback on the request path, which
            # would serialize packing with the previous dispatch.
            nnzp = int(packed.problem.colidx.shape[0])
            alive_np = np.zeros(nnzp, bool)
            for a, b in packed.edge_ranges:
                alive_np[a:b] = True
            frozen_np = np.zeros(nnzp, bool)
            ft_np = np.zeros(nnzp, np.int32)
            for st, (a, b) in zip(queries, packed.edge_ranges):
                if st.query.workload != "stream_update":
                    continue
                alive_np[a:b] = st.query.frontier
                frozen_np[a:b] = ~st.query.frontier
                ft_np[a:b] = st.query.frozen_truss
            alive0 = jnp.asarray(alive_np)
            frozen = jnp.asarray(frozen_np)
            frozen_truss = jnp.asarray(ft_np)

        # peel() synchronizes internally (its iteration-cap check reads back
        # the done flags), so dt covers the whole dispatch.
        inject("clock_skew", bucket=bucket, backend=str(backend), queries=qids)
        inject("device_oom", bucket=bucket, backend=str(backend), queries=qids)
        inject("dispatch", bucket=bucket, backend=str(backend), queries=qids)
        t0 = obs_clock.now()
        try:
            st_dev = exe.peel(
                packed.problem,
                slot_ids=slot_ids,
                k0=k0,
                single_level=single_level,
                alive0=alive0,
                frozen=frozen,
                frozen_truss=frozen_truss,
            )
        except TrussError:
            raise  # already typed (iteration cap, injected faults)
        except Exception as e:
            # Raw XLA/Pallas failures become typed device faults so the
            # resilience layer can retry/fall back on them.
            raise DeviceError(
                f"peel dispatch failed on backend {backend}: {e}",
                bucket=bucket,
                backend=backend,
                cause=e,
            ) from e
        dt = obs_clock.now() - t0

        with tracer.span("unpack", members=len(queries)) as span:
            alive = np.asarray(st_dev.alive)
            support = np.asarray(st_dev.support)
            trussness = np.asarray(st_dev.trussness)
            kmax = np.asarray(st_dev.kmax)
            levels = np.asarray(st_dev.levels)
            iters = np.asarray(st_dev.iters)
            edges_alive = np.asarray(st_dev.edges_alive)
            # The slot that retired last was live on every trip, so the
            # largest per-slot count is the loop's trip count.
            span.attrs["trips"] = int(iters.max(initial=0))

            results: list[Any] = []
            for i, (st, (a, b)) in enumerate(zip(queries, packed.edge_ranges)):
                st.stats.device_time_s = dt  # the batch's single dispatch
                st.stats.rounds = int(levels[i])
                st.stats.iterations = int(iters[i])
                workload = st.query.workload
                if workload == "ktruss":
                    member_alive = alive[a:b].copy()
                    results.append(
                        KTrussResult(
                            k=st.query.k,
                            alive=member_alive,
                            support=support[a:b].copy(),
                            iterations=int(iters[i]),
                            edges_remaining=int(member_alive.sum()),
                        )
                    )
                elif workload == "kmax":
                    results.append(int(kmax[i]))
                elif workload == "stream_update":
                    # Full member trussness: frontier lanes re-peeled, frozen
                    # lanes passed through by the peel (see exec.build_peel).
                    results.append(trussness[a:b].copy())
                else:
                    t = trussness[a:b].copy()
                    results.append(
                        TrussDecomposition(
                            trussness=t,
                            kmax=int(t.max(initial=0)) if t.size else 0,
                            levels=int(levels[i]),
                        )
                    )

        # The paper's load-imbalance statistic, observed at runtime: the
        # per-slot iteration spread of THIS dispatch, recorded per
        # (bucket, backend) so the auto rule can be calibrated from data.
        record_peel_batch(
            bucket=bucket,
            backend=backend,
            levels=levels,
            iters=iters,
            edges_alive=edges_alive,
            batch_size=len(queries),
            device_time_s=dt,
        )
        return results

    # ------------------------------------------------------------------ #
    def stats(self) -> dict:
        """Planning observability: overhead per query + chosen backends."""
        from .cache import bucket_str

        with self._stats_lock:
            queries_planned = self.queries_planned
            plan_time_s = self.plan_time_s
            choices = dict(self.backend_choices)
        per_query_us = (
            1e6 * plan_time_s / queries_planned if queries_planned else 0.0
        )
        return {
            "queries_planned": queries_planned,
            "plan_time_s": round(plan_time_s, 6),
            "plan_us_per_query": round(per_query_us, 2),
            # One row per (bucket, backend) choice — the same bucket can
            # legitimately map to several backends under the auto rule.
            "backends": [
                {
                    "bucket": bucket_str(b),
                    "backend": str(k),
                    "queries": n,
                }
                for (b, k), n in sorted(choices.items(), key=lambda kv: -kv[1])
            ],
        }
