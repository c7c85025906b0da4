"""K-truss sharding: packed slot blocks across a device mesh.

The serving layer packs B same-bucket graphs block-diagonally, so the
packed arrays have a leading slot-block structure: edge lanes
``[i * slot_nnz, (i+1) * slot_nnz)`` belong to slot i (``layout="aligned"``
packing), and slots never interact.  Slot boundaries are therefore natural
shard boundaries — sharding every edge-dim array over a 1-D ``"slots"``
mesh axis gives each device a subset of whole member graphs, with no
cross-device triangle closing.  Vertex-dim arrays (``rowptr``, ``deg``,
``urowptr``, ``udeg``) stay replicated: they are O(n) index metadata, tiny
next to the O(nnz·window) intersection state.

The fine eager support pass runs per device (:func:`per_chip_support`,
under ``shard_map``): each device scans only its own lanes, with
``rowptr`` rebased to its lane block.  That is exact because the kernel
reads ``rowptr`` only as row starts, and the aligned packing keeps every
row of a slot inside the slot's lane block, so every vertex a device's
edges name has its row on that device; vertex ids stay global (indices
into the replicated metadata and keys of the compare).  Under GSPMD alone
the pass's ``lax.scan`` took its trip count from the global lane count,
and every device ran every chunk of the mesh.  The prune and its per-slot
bookkeeping stay GSPMD-partitioned, meeting across devices only in
reductions.

Sharded results are bit-identical to unsharded: all peel state is
integer/bool, so neither partitioning can introduce rounding
differences.  On a four-chip TPU v5e host (2x2) ``chip_smoke.py --chips 4``
compares a sharded batch with the same batch on one chip, and the
benchmark cell ``kron8-mesh4-serve`` serves Kronecker scale-8 3-truss
queries through ``Session(mesh=slot_mesh(4), max_batch=32)`` at a fixed
rate, every answer checked against the plain reference.  On the CPU,
``tests/test_exec_peel.py`` and ``tests/test_mesh_serving.py`` run the
path on virtual devices (``XLA_FLAGS=--xla_force_host_platform_device_count``)
and ``tests/test_tpu_compile.py`` compiles it for a described ``v5e:2x2``.
"""

from __future__ import annotations

from typing import Callable

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.eager_fine import FineProblem

__all__ = [
    "SLOT_AXIS",
    "slot_mesh",
    "peel_problem_specs",
    "per_chip_support",
    "peel_arg_shardings",
    "shard_peel_args",
]

SLOT_AXIS = "slots"


def slot_mesh(num_devices: int | None = None) -> Mesh:
    """1-D mesh over the ``"slots"`` axis (the first ``num_devices`` local
    devices; all of them by default).

    The axis is ``Auto``: ``jax.make_mesh`` defaults to explicit axes, under
    which arguments placed on this mesh outside a mesh context lose their
    ``"slots"`` partitioning when the peel is traced."""
    devs = jax.devices()
    d = int(num_devices) if num_devices is not None else len(devs)
    if d > len(devs):
        raise ValueError(f"requested {d} devices, have {len(devs)}")
    return jax.make_mesh(
        (d,),
        (SLOT_AXIS,),
        axis_types=(jax.sharding.AxisType.Auto,),
        devices=devs[:d],
    )


def peel_problem_specs() -> list[P]:
    """PartitionSpec per :class:`FineProblem` field (field order).

    Edge-dim arrays shard over ``"slots"``; vertex-dim arrays replicate.
    Returned as a plain list (PartitionSpec is a tuple subclass, so a
    FineProblem of specs would be flattened *into* the specs by pytree
    maps).
    """
    edge = P(SLOT_AXIS)
    rep = P()
    return [
        rep,  # rowptr   (n+1,)
        edge,  # colidx   (nnzp,)
        edge,  # edge_row (nnzp,)
        rep,  # deg      (n+1,)
        rep,  # urowptr  (n+1,)
        edge,  # ucolidx  (unnzp,)
        edge,  # u2d      (unnzp,)
        edge,  # uedge_row(unnzp,)
        rep,  # udeg     (n+1,)
    ]


def per_chip_support(
    support: Callable[[FineProblem, jax.Array], jax.Array], mesh: Mesh
) -> Callable[[FineProblem, jax.Array], jax.Array]:
    """``support`` run on each device of ``mesh`` over its own lane block.

    For a support that reads ``rowptr`` only as row starts and touches no
    other lane-position field (the fine eager pass): each device sees its
    ``nnz_pad / d`` edge lanes and the replicated vertex metadata, with
    ``rowptr`` rebased by the block's first lane, so a lane position is
    local.  Exact for slot-aligned packings whose slots shard whole (see
    the module docstring).
    """
    edge = P(SLOT_AXIS)

    def local_support(p: FineProblem, alive: jax.Array) -> jax.Array:
        base = jax.lax.axis_index(SLOT_AXIS) * p.nnz_pad
        return support(p._replace(rowptr=p.rowptr - base), alive)

    return jax.shard_map(
        local_support,
        mesh=mesh,
        in_specs=(FineProblem(*peel_problem_specs()), edge),
        out_specs=edge,
        # The support's scan starts from a replicated zero accumulator and
        # carries per-device sums; the output is per-device by its spec.
        check_vma=False,
    )


def peel_arg_shardings(mesh: Mesh) -> tuple:
    """Sharding per peel argument ``(p, slot_ids, k0, single_level, alive0,
    frozen, frozen_truss)``: slot blocks sharded, vertex metadata
    replicated.  Shared by :func:`shard_peel_args` (placing arrays) and the
    executor's ahead-of-time compile (placing shapes)."""
    slots = NamedSharding(mesh, P(SLOT_AXIS))
    p = FineProblem(*(NamedSharding(mesh, s) for s in peel_problem_specs()))
    return (p, slots, slots, slots, slots, slots, slots)


def shard_peel_args(
    mesh: Mesh,
    p: FineProblem,
    slot_ids: jax.Array,
    k0: jax.Array,
    single_level: jax.Array,
    alive0: jax.Array,
    frozen: jax.Array,
    frozen_truss: jax.Array,
):
    """Place peel inputs on ``mesh``: slot blocks sharded, metadata replicated.

    Requires the slot count (and hence every edge-dim length, which is a
    slot multiple) to divide the mesh size, so each device owns whole
    slots.
    """
    d = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    num_slots = int(k0.shape[0])
    nnzp = int(p.colidx.shape[0])
    if num_slots % d or nnzp % d:
        raise ValueError(
            f"mesh size {d} must evenly divide slots={num_slots} "
            f"(and nnz_pad={nnzp}) so each device owns whole slots"
        )
    args = (p, slot_ids, k0, single_level, alive0, frozen, frozen_truss)
    return jax.device_put(args, peel_arg_shardings(mesh))
