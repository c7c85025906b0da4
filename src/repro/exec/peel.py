"""Device-resident level peeling: the whole truss decomposition in one dispatch.

``KTrussEngine`` and ``TrussService`` used to peel truss levels from the
host: one compiled fixed point per level, a ``np.asarray(alive)`` readback
and threshold re-upload between levels, and two copies of the peel logic
(engine loop, service loop).  PKT frames decomposition as a *single*
peeling computation; this module is that framing on device.

:func:`build_peel` compiles one ``lax.while_loop`` whose body runs a
support computation, prunes against each packed slot's current threshold,
and — for every slot whose alive mask just reached a fixed point — records
the surviving edges' trussness at ``cur_k``, bumps the slot's kmax/level
counters, and advances its threshold to ``cur_k + 1`` (or retires the slot
when its level emptied, or immediately for single-level ``ktruss(k)``
members).  The loop exits only when every slot is done, so a batched
``decompose`` costs **one** dispatch instead of one per level per round.

Slots are the block-diagonal members of ``repro.graphs.pack``; because the
packing is a disjoint union, each slot's fixed point is independent and a
per-slot convergence test (``segment_sum`` of changed lanes) is exact.

:class:`PeelExecutor` wraps the compiled peel with optional mesh placement
(slot blocks sharded across devices, the fine eager support pass run per
device — see ``repro.distributed.ktruss``) and a dispatch counter that
tests use to assert the one-dispatch contract.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.eager_coarse import support_coarse_eager
from ..core.eager_fine import FineProblem, support_fine_eager, support_fine_owner
from ..errors import DeviceError
from ..obs import current_registry, current_tracer

__all__ = [
    "PeelState",
    "make_problem_support",
    "init_peel_state",
    "peel_arg_shapes",
    "build_peel",
    "PeelExecutor",
]


class PeelState(NamedTuple):
    """Carry/result of the on-device peel.

    Per-edge arrays span the packed problem's ``nnz_pad`` lanes; per-slot
    arrays have one entry per packed slot.
    """

    alive: jax.Array  # (nnzp,) bool — final alive mask (fixed point of cur_k)
    support: jax.Array  # (nnzp,) int32 — post-prune supports of that mask
    trussness: jax.Array  # (nnzp,) int32 — last k whose truss held the edge
    cur_k: jax.Array  # (S,) int32 — threshold each slot ended on
    kmax: jax.Array  # (S,) int32 — largest k with non-empty truss (0 if none)
    levels: jax.Array  # (S,) int32 — fixed-point levels peeled
    iters: jax.Array  # (S,) int32 — prune iterations while the slot was live
    done: jax.Array  # (S,) bool
    total_iters: jax.Array  # () int32 — while-loop trips (the cap's subject)
    edges_alive: jax.Array  # (S,) int32 — alive edges at the last converged level


def make_problem_support(
    *,
    granularity: str = "fine",
    mode: str = "eager",
    backend: str = "xla",
    window: int,
    chunk: int = 256,
    row_chunk: int = 32,
) -> Callable[[FineProblem, jax.Array], jax.Array]:
    """Problem-polymorphic ``(problem, alive) -> support`` for one config.

    Unlike ``repro.core.truss.make_support_fn`` this does not close over a
    graph, so one compiled peel serves every same-bucket problem —
    including block-diagonal batches of them.
    """
    if backend == "pallas":
        from ..kernels import ops as kernel_ops  # lazy: keeps exec dep-light

        if granularity != "fine":
            raise ValueError("pallas backend implements the fine granularity")
        return functools.partial(
            kernel_ops.support_fine,
            window=window,
            chunk=chunk,
            tile=min(256, chunk),
        )
    if backend != "xla":
        raise ValueError(f"unknown backend {backend!r}")
    if granularity == "coarse":
        if mode != "eager":
            raise ValueError("coarse granularity implements the eager mode")
        return functools.partial(
            support_coarse_eager, window=window, row_chunk=row_chunk
        )
    if granularity != "fine":
        raise ValueError(f"unknown granularity {granularity!r}")
    if mode == "eager":
        return functools.partial(support_fine_eager, window=window, chunk=chunk)
    if mode == "owner":
        return functools.partial(support_fine_owner, window=window, chunk=chunk)
    raise ValueError(f"unknown mode {mode!r}")


def init_peel_state(
    p: FineProblem,
    slot_ids: jax.Array,
    k0: jax.Array,
    single_level: jax.Array,
    alive0: jax.Array,
    frozen: jax.Array,
    frozen_truss: jax.Array,
) -> PeelState:
    """The peel's starting carry (traced inside :func:`build_peel`'s jit).
    Frozen lanes carry their known trussness straight through to the
    output; free lanes start at the vacuous floor."""
    num_slots = int(k0.shape[0])
    seg = functools.partial(jax.ops.segment_sum, num_segments=num_slots)
    edges0 = seg(alive0.astype(jnp.int32), slot_ids)
    return PeelState(
        alive=alive0,
        support=jnp.zeros_like(alive0, jnp.int32),
        trussness=jnp.where(
            frozen,
            frozen_truss,
            jnp.maximum(jnp.int32(2), k0 - 1)[slot_ids]
            * alive0.astype(jnp.int32),
        ),
        cur_k=k0,
        kmax=jnp.zeros(num_slots, jnp.int32),
        levels=jnp.zeros(num_slots, jnp.int32),
        iters=jnp.zeros(num_slots, jnp.int32),
        done=edges0 == 0,
        total_iters=jnp.int32(0),
        edges_alive=edges0,
    )


def peel_arg_shapes(*, n: int, nnz_pad: int, slots: int) -> tuple:
    """Shapes of one peel call's arguments ``(p, slot_ids, k0,
    single_level, alive0, frozen, frozen_truss)`` for a packed problem of
    ``n`` vertices, ``nnz_pad`` directed lanes (twice that undirected) and
    ``slots`` slots — what ``repro.graphs.pack.pack_problems`` produces for
    ``slots`` members of one bucket."""

    def shape(length, dtype):
        return jax.ShapeDtypeStruct((length,), dtype)

    i32, b8 = jnp.int32, jnp.bool_
    p = FineProblem(
        rowptr=shape(n + 1, i32),
        colidx=shape(nnz_pad, i32),
        edge_row=shape(nnz_pad, i32),
        deg=shape(n + 1, i32),
        urowptr=shape(n + 1, i32),
        ucolidx=shape(2 * nnz_pad, i32),
        u2d=shape(2 * nnz_pad, i32),
        uedge_row=shape(2 * nnz_pad, i32),
        udeg=shape(n + 1, i32),
    )
    lanes_i32, lanes_b8 = shape(nnz_pad, i32), shape(nnz_pad, b8)
    return (p, lanes_i32, shape(slots, i32), shape(slots, b8), lanes_b8, lanes_b8, lanes_i32)


def build_peel(
    support: Callable[[FineProblem, jax.Array], jax.Array],
    *,
    max_iters: int | None = None,
) -> Callable:
    """Compile the full level peel into one jitted callable.

    The returned function has signature

        peel(p, slot_ids, k0, single_level, alive0, frozen, frozen_truss) -> PeelState

    where ``slot_ids`` maps every edge lane to its packed slot, ``k0`` is
    each slot's starting k, and ``single_level`` marks slots that stop at
    their first fixed point (the ``ktruss(k)`` workload) instead of peeling
    on.  ``frozen`` marks lanes whose trussness is already known
    (``frozen_truss``): they are never pruned or re-ranked, but count as
    alive for support exactly while the slot's threshold is within their
    truss (``frozen_truss >= cur_k``) — the masked sub-problem form the
    streaming layer (``repro.stream``) peels, where only a frontier of
    affected edges is free and the rest of the graph is frozen at its
    maintained trussness.  ``alive0`` and ``frozen`` must be disjoint.
    ``max_iters`` caps total loop trips across all levels; ``None``
    (the default) uses ``nnz_pad + n + 4``, a provable upper bound (every
    trip each active slot either prunes ≥ 1 edge — at most nnz per slot —
    or converges a level — at most kmax + 2 ≤ n + 3 per slot), so an
    uncapped peel can never be truncated.  An explicit cap that fires
    raises in :meth:`PeelExecutor.peel` rather than returning a truncated
    state as final.

    Semantics per while-loop trip: compute supports, prune each lane
    against its slot's ``cur_k - 2``, and per-slot test convergence (no
    lane of the slot changed).  A converged slot's surviving edges get
    ``trussness = cur_k``; if edges survive the slot advances to
    ``cur_k + 1`` (warm-started from the current mask), otherwise — or when
    ``single_level`` — it retires.  Retired slots keep their threshold, so
    re-running them is idempotent and their alive/support lanes stay
    frozen at the converged values.
    """

    def peel(
        p: FineProblem,
        slot_ids: jax.Array,
        k0: jax.Array,
        single_level: jax.Array,
        alive0: jax.Array,
        frozen: jax.Array,
        frozen_truss: jax.Array,
    ) -> PeelState:
        num_slots = int(k0.shape[0])
        limit = (
            int(alive0.shape[0]) + p.n + 4 if max_iters is None else int(max_iters)
        )
        seg = functools.partial(jax.ops.segment_sum, num_segments=num_slots)
        state = init_peel_state(
            p, slot_ids, k0, single_level, alive0, frozen, frozen_truss
        )

        def cond(st: PeelState):
            return jnp.any(~st.done) & (st.total_iters < limit)

        def body(st: PeelState) -> PeelState:
            # The two named scopes split a trip's device time in a profiler
            # trace: the support pass, and the prune with its bookkeeping.
            with jax.named_scope("support"):
                # Frozen lanes participate in supports exactly while the
                # slot's threshold is inside their truss: at level k the
                # from-scratch k-truss contains a frozen edge iff its
                # trussness >= k, so the restricted peel over the free
                # lanes sees the same subgraph.
                eff_alive = st.alive | (frozen & (frozen_truss >= st.cur_k[slot_ids]))
                s = support(p, eff_alive)
            with jax.named_scope("prune"):
                thresh = (st.cur_k - 2)[slot_ids]
                new_alive = st.alive & (s >= thresh)
                changed = seg((new_alive ^ st.alive).astype(jnp.int32), slot_ids)
                converged = (changed == 0) & ~st.done
                conv_lane = converged[slot_ids]
                trussness = jnp.where(
                    conv_lane & new_alive, st.cur_k[slot_ids], st.trussness
                )
                left = seg(new_alive.astype(jnp.int32), slot_ids)
                nonempty = left > 0
                retired = converged & (~nonempty | single_level)
                cur_k = jnp.where(converged & ~retired, st.cur_k + 1, st.cur_k)
                # Prune-ahead: slots that just advanced re-prune against their
                # new threshold using the support already in hand (the free mask
                # is unchanged, so s IS the next level's first support; with
                # frozen lanes s only over-counts — support is monotone in the
                # alive set — so every ahead-pruned edge would be pruned by the
                # next level's first true support anyway) — saving one full
                # support evaluation per level, the peel's dominant cost.
                # Retired/done slots see their old threshold: idempotent.
                new_alive = new_alive & (s >= (cur_k - 2)[slot_ids])
                return PeelState(
                    alive=new_alive,
                    support=s * new_alive.astype(s.dtype),
                    trussness=trussness,
                    cur_k=cur_k,
                    kmax=jnp.where(converged & nonempty, st.cur_k, st.kmax),
                    levels=st.levels + converged.astype(jnp.int32),
                    iters=st.iters + (~st.done).astype(jnp.int32),
                    done=st.done | retired,
                    total_iters=st.total_iters + 1,
                    # Live slots track their current level's alive-edge count;
                    # a retired slot freezes at its final level — free per-slot
                    # telemetry for the runtime imbalance histograms
                    # (repro.obs.peel_stats).
                    edges_alive=jnp.where(st.done, st.edges_alive, left),
                )

        return jax.lax.while_loop(cond, body, state)

    return jax.jit(peel)


class PeelExecutor:
    """Unified executor for every multi-level K-truss workload.

    One instance owns one compiled peel (one support configuration) and
    serves ``ktruss`` / ``kmax`` / ``decompose`` for any problem matching
    its shapes — a single graph (one slot) or a packed batch.  With
    ``mesh=`` the packed slot blocks are sharded across devices before
    dispatch (slot boundaries are natural shard boundaries because the
    block-diagonal packing makes slots independent).  On a mesh the
    fine/xla eager support pass runs per device
    (``repro.distributed.ktruss.per_chip_support``): each device scans its
    own lanes, with ``rowptr`` rebased to its lane block — exact because
    the pass reads ``rowptr`` only as row starts and every row of an
    aligned slot lies in the slot's lanes.  ``support_per_chip`` says
    whether it engaged; every other support, and the prune with its
    per-slot bookkeeping, stays GSPMD-partitioned.  Without a mesh the
    program is the plain :func:`build_peel` of the support.

    ``dispatches`` counts calls into the compiled peel; the serving layer
    and tests use it to assert the one-dispatch-per-batch contract.
    """

    def __init__(
        self,
        *,
        granularity: str = "fine",
        mode: str = "eager",
        backend: str = "xla",
        window: int | None = None,
        chunk: int = 256,
        row_chunk: int = 32,
        max_iters: int | None = None,
        mesh=None,
        support: Callable[[FineProblem, jax.Array], jax.Array] | None = None,
    ):
        per_chip = False
        if support is None:
            if window is None:
                raise ValueError("window is required unless support= is given")
            support = make_problem_support(
                granularity=granularity,
                mode=mode,
                backend=backend,
                window=window,
                chunk=chunk,
                row_chunk=row_chunk,
            )
            # The one support known to read lane positions only through
            # rowptr's row starts, which is what per-device rebasing needs.
            per_chip = mesh is not None and (granularity, mode, backend) == (
                "fine", "eager", "xla"
            )
        if per_chip:
            from ..distributed.ktruss import per_chip_support

            support = per_chip_support(support, mesh)
        self.support = support
        self.support_per_chip = per_chip
        self.mesh = mesh
        self._peel = build_peel(support, max_iters=max_iters)
        self.dispatches = 0

    def compile(self, *, n: int, nnz_pad: int, slots: int) -> None:
        """Compile now, for packed problems of ``n`` vertices, ``nnz_pad``
        edge lanes and ``slots`` slots, so that a program the device's
        compiler refuses fails here — when the bucket's executor is built —
        and not at its first dispatch.  Later :meth:`peel` calls with those
        shapes run the compiled program; other shapes are refused.
        """
        args = peel_arg_shapes(n=n, nnz_pad=nnz_pad, slots=slots)
        if self.mesh is not None:
            from ..distributed.ktruss import peel_arg_shardings

            args = jax.tree.map(
                lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
                args,
                peel_arg_shardings(self.mesh),
            )
        self._peel = self._peel.lower(*args).compile()

    def compiled_text(self) -> str | None:
        """The compiled peel's HLO text, after XLA's passes: the
        instruction names a profiler trace shows, each with its scope in
        ``op_name`` (``.../while/body/support/...``).  ``None`` until
        :meth:`compile` has run."""
        as_text = getattr(self._peel, "as_text", None)
        return as_text() if as_text is not None else None

    def peel(
        self,
        p: FineProblem,
        *,
        slot_ids,
        k0: Sequence[int] | np.ndarray,
        single_level: Sequence[bool] | np.ndarray | None = None,
        alive0: jax.Array | None = None,
        frozen: jax.Array | None = None,
        frozen_truss: jax.Array | None = None,
    ) -> PeelState:
        """Run the whole peel for one packed problem in one dispatch.

        ``frozen``/``frozen_truss`` mark lanes whose trussness is already
        known (see :func:`build_peel`); callers must keep ``alive0`` and
        ``frozen`` disjoint.  Defaults (all-free) reproduce the plain
        from-scratch peel bit-for-bit.
        """
        tracer = current_tracer()
        num_slots = len(k0)
        # "dispatch" is the host's side of the launch: the arguments built
        # and placed, then the (async) launch of the compiled peel — on a
        # first call per executor it includes the XLA compile; the
        # blocking readback below is the true device wait.
        with tracer.span("dispatch", slots=num_slots):
            k0 = jnp.asarray(np.asarray(k0, dtype=np.int32))
            if single_level is None:
                single_level = np.zeros(num_slots, dtype=bool)
            single_level = jnp.asarray(np.asarray(single_level, dtype=bool))
            slot_ids = jnp.asarray(np.asarray(slot_ids, dtype=np.int32))
            if alive0 is None:
                alive0 = p.colidx != 0
            if frozen is None:
                frozen = jnp.zeros(alive0.shape, bool)
            if frozen_truss is None:
                frozen_truss = jnp.zeros(alive0.shape, jnp.int32)
            if self.mesh is not None:
                from ..distributed.ktruss import shard_peel_args

                # The batch was built on the default device; "shard" is the
                # host's cost of re-placing it across the mesh.
                with tracer.span("shard", chips=self.mesh.size):
                    (p, slot_ids, k0, single_level, alive0, frozen, frozen_truss) = (
                        shard_peel_args(
                            self.mesh, p, slot_ids, k0, single_level, alive0,
                            frozen, frozen_truss,
                        )
                    )
            self.dispatches += 1
            current_registry().inc("peel_dispatches")
            if self.support_per_chip:
                current_registry().inc("peel_sharded_support_dispatches")
            st = self._peel(
                p, slot_ids, k0, single_level, alive0, frozen, frozen_truss
            )
        # Belt: the iteration cap is provably unreachable (see build_peel),
        # so an un-done slot means a peel bug — fail loudly rather than
        # letting callers read back a truncated state as final.
        with tracer.span("device-wait"):
            all_done = bool(np.asarray(st.done).all())
        if not all_done:
            # Typed (DeviceError is still a RuntimeError) so the
            # resilience layer treats a capped peel like any other
            # device-side dispatch failure: retry, then fall back.
            raise DeviceError(
                f"peel hit the iteration cap after {int(st.total_iters)} "
                f"trips with slots unfinished: done={np.asarray(st.done)}",
                site="peel",
            )
        return st
