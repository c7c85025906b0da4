"""jit'd wrappers around the Pallas kernels (padding, window prep, chunking).

``support_fine`` matches the ``alive -> support`` contract of
``repro.core.truss.make_support_fn`` so ``KTrussEngine(backend="pallas")``
drops it in transparently: XLA performs the bandwidth-bound window gathers,
the Pallas kernel performs the compute-bound intersections, and a
``lax.scan`` pipelines edge chunks so peak memory stays at
``chunk × window`` regardless of graph size.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core.eager_fine import FineProblem
from .support_dense import support_dense_pallas
from .support_fine import support_fine_pallas

__all__ = ["support_fine", "support_fine_stacked", "support_dense", "on_tpu"]

_LANES = 128


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def support_fine(
    p: FineProblem,
    alive: jax.Array,
    *,
    window: int,
    chunk: int = 1024,
    tile: int = 256,
    schedule: str = "compare",
    interpret: bool | None = None,
) -> jax.Array:
    """Owner-mode fine-grained support via the Pallas edge-tile kernel.

    Semantically identical to
    :func:`repro.core.eager_fine.support_fine_owner` (property-tested).
    """
    nnzp = p.nnz_pad
    if nnzp % chunk or chunk % tile:
        raise ValueError(f"need tile | chunk | nnz_pad, got {tile}/{chunk}/{nnzp}")
    w = _round_up(max(int(window), _LANES), _LANES)

    unnzp = int(p.ucolidx.shape[0])
    large = jnp.int32(p.n + 2)
    offs = jnp.arange(w, dtype=jnp.int32)[None, :]

    alive_pad = jnp.concatenate([alive, jnp.zeros((1,), alive.dtype)])
    ualive = alive_pad[jnp.minimum(p.u2d, nnzp)] & (p.ucolidx != 0)

    def row_window(v: jax.Array):
        start = p.urowptr[jnp.maximum(v, 1) - 1] * (v > 0)
        idx = start[:, None] + offs
        n_in = offs < p.udeg[v][:, None]
        idx_c = jnp.clip(idx, 0, unnzp - 1)
        nav = jnp.where(n_in, p.ucolidx[idx_c], large)
        return nav, n_in & ualive[idx_c]

    def body(_, chunk_start: jax.Array):
        t = chunk_start + jnp.arange(chunk, dtype=jnp.int32)
        a, b = p.edge_row[t], p.colidx[t]
        valid_t = (b != 0) & alive[t]
        a_nav, a_alive = row_window(a)
        b_nav, b_alive = row_window(b)
        a_ok = a_alive & valid_t[:, None] & (a_nav < large)
        counts = support_fine_pallas(
            a_nav,
            a_ok,
            b_nav,
            b_alive,
            tile=tile,
            schedule=schedule,
            interpret=interpret,
        )
        return _, counts * valid_t.astype(jnp.int32)

    starts = jnp.arange(0, nnzp, chunk, dtype=jnp.int32)
    _, s_chunks = jax.lax.scan(body, None, starts)
    return s_chunks.reshape(-1)


def support_fine_stacked(
    p: FineProblem,
    alive: jax.Array,
    *,
    window: int,
    chunk: int = 1024,
    tile: int = 256,
    schedule: str = "compare",
    interpret: bool | None = None,
) -> jax.Array:
    """Batched Pallas ``alive -> support`` over a leading batch axis.

    Mirrors :func:`repro.core.eager_fine.support_fine_stacked` for the
    kernel backend: ``p``'s fields carry a leading ``(B, ...)`` dimension
    (same shape bucket for all members) and the batch runs through one
    ``lax.map``-sequenced program — one dispatch per micro-batch.
    """
    fn = functools.partial(
        support_fine,
        window=window,
        chunk=chunk,
        tile=tile,
        schedule=schedule,
        interpret=interpret,
    )
    return jax.lax.map(lambda pa: fn(pa[0], pa[1]), (p, alive))


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def support_dense(
    u_sym: jax.Array, *, block: int = 128, interpret: bool | None = None
) -> jax.Array:
    """S = (U @ U) ∘ U with automatic padding to the block size."""
    v = u_sym.shape[0]
    vp = _round_up(v, block)
    u = jnp.zeros((vp, vp), jnp.float32).at[:v, :v].set(u_sym.astype(jnp.float32))
    s = support_dense_pallas(u, block=block, interpret=interpret)
    return s[:v, :v]
