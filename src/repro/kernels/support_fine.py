"""Fine-grained edge-tile support kernel (Pallas TPU).

TPU-native adaptation of Algorithm 3 (DESIGN.md §2/§4): the grid iterates
over **uniform tiles of T edge tasks** — the paper's flat nonzero range —
and each tile intersects two pre-gathered sorted neighbor windows of width
``W`` per edge.  Ownership partitioning (each edge's support produced by its
own tile) replaces GPU atomics; the eager triple-update is recovered
algebraically by intersecting *undirected* neighborhoods (property-tested
against the faithful scatter implementation).

Hot loop layout:
  * Tile shapes are (T, W) int32 blocks in VMEM; T=128..512, W a multiple of
    the 128-lane VPU width.  VMEM per tile: 4 inputs × T×W×4B (e.g.
    256×512 → 2.0 MiB — comfortably inside the ~16 MiB v5e VMEM).
  * Two selectable inner schedules:
      - ``compare``: chunked O(W²) broadcast equality over 128-lane slabs of
        the navigation window.  Pure VPU compare/OR-reduce; no gathers; the
        conservative, guaranteed-lowerable schedule.
      - ``bsearch``: branchless binary search, ``ceil(log2(W+1))`` rounds of
        take-along-axis — O(W log W), the schedule the XLA path uses.
  * Output block is (T, 1) int32 counts.

The window gather that feeds this kernel stays in XLA (it is a bandwidth-
bound gather that XLA already emits optimally; the kernel owns the
compute-bound intersection).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

__all__ = ["support_fine_pallas"]

_LANES = 128


def _kernel_compare(a_nav_ref, a_ok_ref, b_nav_ref, b_ok_ref, out_ref):
    """Chunked O(W²) broadcast-equality intersection count."""
    a_nav = a_nav_ref[...]  # (T, W)
    a_ok = a_ok_ref[...] != 0
    w = a_nav.shape[1]
    found = jnp.zeros(a_nav.shape, jnp.bool_)
    # Slab over the navigation window in 128-lane chunks: VPU-native
    # compare + OR-reduce; trip count is static (W is a block constant).
    for c0 in range(0, w, _LANES):
        b_nav = b_nav_ref[:, c0 : c0 + _LANES]  # (T, 128)
        b_ok = b_ok_ref[:, c0 : c0 + _LANES] != 0
        eq = (a_nav[:, :, None] == b_nav[:, None, :]) & b_ok[:, None, :]
        found |= jnp.any(eq, axis=2)
    counts = jnp.sum((found & a_ok).astype(jnp.int32), axis=1, keepdims=True)
    out_ref[...] = counts


def _kernel_bsearch(a_nav_ref, a_ok_ref, b_nav_ref, b_ok_ref, out_ref):
    """Branchless binary-search intersection count (O(W log W))."""
    a_nav = a_nav_ref[...]
    a_ok = a_ok_ref[...] != 0
    b_nav = b_nav_ref[...]
    b_ok = b_ok_ref[...] != 0
    w = b_nav.shape[1]
    lo = jnp.zeros(a_nav.shape, jnp.int32)
    hi = jnp.full(a_nav.shape, w, jnp.int32)
    big = jnp.iinfo(b_nav.dtype).max
    for _ in range(max(1, int(np.ceil(np.log2(w + 1))))):
        mid = (lo + hi) >> 1
        bm = jnp.take_along_axis(b_nav, jnp.clip(mid, 0, w - 1), axis=1, mode="clip")
        # Out-of-range probes (lo == hi == w) must never move lo further.
        bm = jnp.where(mid >= w, big, bm)
        go_right = bm < a_nav
        lo = jnp.where(go_right, mid + 1, lo)
        hi = jnp.where(go_right, hi, mid)
    safe = jnp.minimum(lo, w - 1)
    hit = jnp.take_along_axis(b_nav, safe, axis=1, mode="clip") == a_nav
    hit &= jnp.take_along_axis(b_ok, safe, axis=1, mode="clip") & a_ok & (lo < w)
    out_ref[...] = jnp.sum(hit.astype(jnp.int32), axis=1, keepdims=True)


@functools.partial(
    jax.jit, static_argnames=("tile", "schedule", "interpret")
)
def support_fine_pallas(
    a_nav: jax.Array,
    a_ok: jax.Array,
    b_nav: jax.Array,
    b_ok: jax.Array,
    *,
    tile: int = 256,
    schedule: str = "compare",
    interpret: bool | None = None,
) -> jax.Array:
    """Intersection counts for E edges from pre-gathered (E, W) windows.

    Args / semantics match :func:`repro.kernels.ref.support_tiles_ref`.
    E must be a multiple of ``tile``; W a multiple of 128 (the wrapper in
    ``ops.py`` pads both).  ``interpret=None`` runs the Pallas interpreter
    off a TPU only.

    Precondition (CSR rows satisfy it by construction): valid lanes of
    ``b_nav`` are **strictly** ascending — the ``bsearch`` schedule locates
    the unique first occurrence, so duplicate values with mixed ``b_ok``
    would under-count.  The ``compare`` schedule has no such requirement.
    """
    e, w = a_nav.shape
    if e % tile:
        raise ValueError(f"E={e} not a multiple of tile={tile}")
    if w % _LANES:
        raise ValueError(f"W={w} not a multiple of {_LANES}")
    kernel = _kernel_compare if schedule == "compare" else _kernel_bsearch
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    in_spec = pl.BlockSpec((tile, w), lambda g: (g, 0))
    out = pl.pallas_call(
        kernel,
        grid=(e // tile,),
        in_specs=[in_spec, in_spec, in_spec, in_spec],
        out_specs=pl.BlockSpec((tile, 1), lambda g: (g, 0)),
        out_shape=jax.ShapeDtypeStruct((e, 1), jnp.int32),
        interpret=interpret,
    )(
        a_nav.astype(jnp.int32),
        a_ok.astype(jnp.int32),
        b_nav.astype(jnp.int32),
        b_ok.astype(jnp.int32),
    )
    return out[:, 0]
