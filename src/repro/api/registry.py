"""Backend registry: formulation × kernel × layout, chosen per shape bucket.

The paper's subject is a *formulation* choice — coarse row tasks
(Algorithm 2) vs. fine nonzero tasks (Algorithm 3) of Eager K-truss — and
its result is that the right choice is input-dependent: fine wins under
load imbalance (heavy-tailed degree distributions), while the row
formulation is competitive on balanced graphs.  This module makes that
choice a first-class, swappable backend axis instead of a constructor
flag smeared across entry points:

* ``formulation`` — ``coarse`` (row tasks) | ``fine`` (nonzero tasks);
* ``kernel``      — ``xla`` (XLA scatter/gather ops; the default on
                    every platform) | ``pallas`` (the hand-written per-step
                    TPU support kernel, interpret-mode off the TPU).  The
                    Pallas kernel does not lower for a TPU at the windows
                    the auto rule produces, so it is reached only when
                    forced;
* ``layout``      — ``contig`` (prefix-sum packed lanes) | ``aligned``
                    (slot-aligned lanes, shardable across a mesh).

Every registered backend is *semantically identical* — bit-identical
``trussness`` on any graph (parity-tested in ``tests/test_api.py``) — so
the :func:`choose_backend` auto rule is purely a performance policy keyed
on the paper's imbalance statistics (``repro.graphs.stats``), and a
benchmark sweep over backends is a one-flag axis.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Union

from ..graphs.stats import ImbalanceStats

__all__ = [
    "FORMULATIONS",
    "KERNELS",
    "LAYOUTS",
    "BackendKey",
    "BackendSpec",
    "register_backend",
    "get_backend",
    "available_backends",
    "choose_backend",
    "default_kernel",
    "fallback_backends",
]

FORMULATIONS = ("coarse", "fine")
KERNELS = ("xla", "pallas")
LAYOUTS = ("contig", "aligned")


class BackendKey(NamedTuple):
    """One point of the backend grid; the registry and compile-cache key."""

    formulation: str  # coarse | fine
    kernel: str  # xla | pallas
    layout: str  # contig | aligned

    def __str__(self) -> str:  # "fine/xla/aligned" — the CLI/bench spelling
        return "/".join(self)


@dataclasses.dataclass(frozen=True)
class BackendSpec:
    """A registered backend: its key plus how to build its executor.

    ``mode`` is the update dataflow the support kernel uses (``eager``
    scatter vs ``owner`` collision-free); it is an implementation detail
    of the spec, not a registry axis — the Pallas kernels are owner-form
    by construction (TPU grid cells cannot atomically collide).
    """

    key: BackendKey
    mode: str = "eager"
    description: str = ""

    def make_executor(
        self,
        *,
        window: int,
        chunk: int = 256,
        row_chunk: int = 32,
        max_iters: int | None = None,
        mesh=None,
        mode: str | None = None,
    ):
        """Build this backend's :class:`repro.exec.PeelExecutor` for one
        shape bucket.  ``mode`` overrides the spec's dataflow (the legacy
        ``TrussService(mode=...)`` knob)."""
        from ..exec.peel import PeelExecutor  # lazy: registry stays import-light

        return PeelExecutor(
            granularity=self.key.formulation,
            mode=mode or self.mode,
            backend=self.key.kernel,
            window=window,
            chunk=chunk,
            row_chunk=row_chunk,
            max_iters=max_iters,
            mesh=mesh,
        )


_REGISTRY: dict[BackendKey, BackendSpec] = {}


def register_backend(spec: BackendSpec, *, overwrite: bool = False) -> BackendSpec:
    """Add ``spec`` to the registry (axes validated; duplicates rejected)."""
    key = spec.key
    if key.formulation not in FORMULATIONS:
        raise ValueError(f"unknown formulation {key.formulation!r} ({FORMULATIONS})")
    if key.kernel not in KERNELS:
        raise ValueError(f"unknown kernel {key.kernel!r} ({KERNELS})")
    if key.layout not in LAYOUTS:
        raise ValueError(f"unknown layout {key.layout!r} ({LAYOUTS})")
    if key in _REGISTRY and not overwrite:
        raise ValueError(f"backend {key} already registered")
    _REGISTRY[key] = spec
    return spec


def get_backend(key: Union[BackendKey, str, tuple]) -> BackendSpec:
    """Resolve a key, 3-tuple, or ``"formulation/kernel/layout"`` string."""
    if isinstance(key, str):
        parts = tuple(key.split("/"))
        if len(parts) != 3:
            raise ValueError(
                f"backend string must be 'formulation/kernel/layout', got {key!r}"
            )
        key = BackendKey(*parts)
    elif not isinstance(key, BackendKey):
        key = BackendKey(*key)
    spec = _REGISTRY.get(key)
    if spec is None:
        raise KeyError(
            f"no backend registered for {key}; available: "
            f"{[str(k) for k in available_backends()]}"
        )
    return spec


def available_backends() -> tuple[BackendKey, ...]:
    """Every registered key, in a stable order (the parity-test axis)."""
    return tuple(sorted(_REGISTRY))


def default_kernel() -> str:
    """XLA on every platform.

    The Pallas kernel stays off the auto path until it lowers for a TPU
    at the windows the auto rule gives it: Mosaic refuses the ``bsearch``
    schedule's ``take_along_axis``, and the ``compare`` schedule's
    (T, W, 128) intermediate overflows scoped VMEM above tile=128,
    W=128.  Forcing it on a TPU raises a typed ``CompileError`` when the
    bucket's executor is built (``Planner.build_executor``).
    """
    return "xla"


def choose_backend(
    stats: ImbalanceStats,
    *,
    kernel: str | None = None,
    layout: str = "aligned",
) -> BackendKey:
    """The auto rule: pick a formulation from the paper's imbalance stats.

    The coarse (row-task) formulation pads every row task to the longest
    one, so its cost inflates by ``1 / coarse_lane_efficiency``; the fine
    (nonzero-task) formulation splits rows into per-edge tasks and is
    insensitive to the degree tail (paper §III-A).  Coarse therefore only
    wins on near-balanced graphs where its fewer, fatter tasks amortize
    task overhead:

      coarse  iff  coarse_lane_efficiency >= 0.4 and coarse_imbalance <= 2.5

    (the road-network regime, where the paper measures fine/coarse ≈ 1×),
    otherwise fine.  The Pallas kernel implements the fine formulation
    only, so ``kernel="pallas"`` forces ``fine``.  Every backend returns
    identical results, so a wrong guess costs time, never correctness.
    """
    kernel = kernel or default_kernel()
    balanced = stats.coarse_lane_efficiency >= 0.4 and stats.coarse_imbalance <= 2.5
    formulation = "coarse" if (balanced and kernel != "pallas") else "fine"
    key = BackendKey(formulation, kernel, layout)
    if key not in _REGISTRY:
        raise KeyError(f"auto-chosen backend {key} is not registered")
    return key


def fallback_backends(key: Union[BackendKey, str, tuple]) -> tuple[BackendKey, ...]:
    """The degradation chain below ``key``, most-capable first.

    Every registered backend is bit-identical (the parity contract), so
    falling down this chain on a compile/kernel fault trades performance
    for availability, never correctness.  The chain steps down one axis
    at a time and **preserves the layout** (a mesh session requires
    ``aligned``; re-packing stays shape-compatible):

    1. ``pallas -> xla`` — same formulation, same layout: a hand-written
       kernel that fails to build still has the XLA-ops twin;
    2. ``fine -> coarse`` on ``xla`` — the row-task formulation as the
       last resort (slower under imbalance, but always compilable).

    Only registered keys are returned, and never ``key`` itself.
    """
    key = get_backend(key).key
    chain: list[BackendKey] = []
    if key.kernel == "pallas":
        chain.append(BackendKey(key.formulation, "xla", key.layout))
    if key.formulation == "fine":
        chain.append(BackendKey("coarse", "xla", key.layout))
    return tuple(k for k in chain if k != key and k in _REGISTRY)


def _register_defaults() -> None:
    for layout in LAYOUTS:
        register_backend(
            BackendSpec(
                key=BackendKey("coarse", "xla", layout),
                mode="eager",
                description="row tasks (Alg. 2) on XLA ops",
            )
        )
        register_backend(
            BackendSpec(
                key=BackendKey("fine", "xla", layout),
                mode="eager",
                description="nonzero tasks (Alg. 3) on XLA scatter-adds",
            )
        )
        register_backend(
            BackendSpec(
                key=BackendKey("fine", "pallas", layout),
                mode="owner",
                description="nonzero tasks, collision-free Pallas TPU kernel",
            )
        )

_register_defaults()
