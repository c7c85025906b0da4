"""Fine-grained parallel Eager K-truss support computation (Algorithm 3).

One task per **nonzero** (edge) of the upper-triangular adjacency.  Task
``t`` — the j-th nonzero of row ``i`` with column ``κ = colidx[t]`` —
intersects the row-``i`` suffix ``a_i12[j+1:]`` with row ``A(κ,:)`` and
performs the paper's three eager updates:

  u1:  S[t]            += |suffix ∩ N⁺(κ)|      (edge (i,κ) itself)
  u2:  S[pos of m in i] += 1  per match m        (edges (i,m))
  u3:  S[pos of m in κ] += 1  per match m        (edges (κ,m))

Two execution modes (DESIGN.md §4):

* ``eager`` — the faithful dataflow: scatter-adds replace GPU atomics
  (associativity ⇒ determinism under XLA's sorted combiners).
* ``owner`` — collision-free reformulation: each edge's support is computed
  wholly by its own task as |N(a) ∩ N(b)| over the *undirected* alive
  neighborhoods.  Algebraically identical (property-tested); this is the
  form the Pallas TPU kernel implements, since TPU grid cells cannot
  atomically collide.

All shapes are static: windows of width ``window`` (≥ max degree), tasks
processed in chunks of ``chunk`` via ``lax.scan`` to bound memory.

The eager mode intersects a task's two windows by one broadcast compare,
O(W²) per task with no gather.  On a TPU v5e one gathered element costs
as much as thousands of compares, so this beats a binary search of
element gathers, O(W log W), at every window from 128 to 4,096 (PERF.md,
section 6).  The owner mode still searches.

For the same reason the eager mode reads its windows whole.  Both are
rows of the adjacency (row i past the task's own position, and row κ), so
a pass first lays the alive keys out as an ``(n+1, W)`` table, one row
per vertex, and each chunk then reads its windows by two row gathers and
adds its updates by two row scatter-adds into a support table of that
shape.  Read lane by lane the same chunk costs 6·chunk·W element gathers
and scatters, which took 98% of a pass on the chip; read by a dynamic
slice of the lane array, XLA expands each window into a loop of slices.
The explicit-task path (degree buckets) still reads lane by lane.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..graphs.csr import CSRGraph
from .taskmap import sorted_window_member

__all__ = [
    "FineProblem",
    "prepare_fine",
    "support_fine_eager",
    "support_fine_owner",
    "support_fine_stacked",
]

# Row-κ entries compared per step of the eager intersection: one step's
# compare is at most (chunk, window, _B_BLOCK).
_B_BLOCK = 256


class FineProblem(NamedTuple):
    """Static-shape device arrays for the fine-grained algorithm.

    Directed (upper-triangular) arrays drive the eager mode; the undirected
    mirror (u*) drives the owner mode.  ``u2d`` maps each undirected nonzero
    to its directed edge id so a single ``alive`` vector (over directed
    edges) masks both views.

    Contract: ``rowptr``/``urowptr`` are read only as row *starts*
    (``rowptr[v-1]`` begins row v; extents come from ``deg``/``udeg``), so
    layouts may leave unowned pad lanes between rows — the slot-aligned
    packing (``repro.graphs.pack``, ``layout="aligned"``) relies on this.
    """

    rowptr: jax.Array  # (n+1,) int32
    colidx: jax.Array  # (nnzp,) int32, 0 = pad
    edge_row: jax.Array  # (nnzp,) int32
    deg: jax.Array  # (n+1,) int32
    urowptr: jax.Array  # (n+1,) int32
    ucolidx: jax.Array  # (unnzp,) int32
    u2d: jax.Array  # (unnzp,) int32 -> directed edge id (nnzp for pad)
    uedge_row: jax.Array  # (unnzp,) int32  (row id of undirected entry)
    udeg: jax.Array  # (n+1,) int32

    @property
    def n(self) -> int:
        return int(self.rowptr.shape[0] - 1)

    @property
    def nnz_pad(self) -> int:
        return int(self.colidx.shape[0])


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def prepare_fine(
    g: CSRGraph,
    chunk: int = 1024,
    *,
    nnz_pad: int | None = None,
    unnz_pad: int | None = None,
) -> FineProblem:
    """Host-side packing of a CSR graph into :class:`FineProblem` arrays.

    ``nnz_pad``/``unnz_pad`` override the default round-up-to-chunk padding
    with explicit targets so callers (the serving compile cache) can
    canonicalize many graphs onto one static shape.
    """
    nnzp = max(_round_up(g.nnz, chunk), chunk) if nnz_pad is None else int(nnz_pad)
    if nnzp < g.nnz or nnzp % chunk:
        raise ValueError(f"nnz_pad={nnzp} must be a chunk multiple >= nnz={g.nnz}")
    d = g.device_csr(nnzp)
    u = g.undirected_csr()
    unnzp = max(_round_up(u.nnz, chunk), chunk) if unnz_pad is None else int(unnz_pad)
    if unnzp < u.nnz:
        raise ValueError(f"unnz_pad={unnzp} < undirected nnz={u.nnz}")

    # Map undirected nonzeros to directed edge ids: entry (a,b) of the
    # symmetric CSR corresponds to directed edge (min(a,b), max(a,b)).  The
    # directed nonzeros are globally sorted under the composite key
    # row * (n + 2) + col (rows ascending, colidx ascending within a row),
    # so one vectorized searchsorted over those keys resolves every
    # undirected entry at once — no per-edge Python loop.
    urows = u.row_of_edge()
    lo = np.minimum(urows, u.colidx).astype(np.int64)
    hi = np.maximum(urows, u.colidx).astype(np.int64)
    stride = np.int64(g.n + 2)
    dkeys = g.row_of_edge().astype(np.int64) * stride + g.colidx
    u2d = np.searchsorted(dkeys, lo * stride + hi)
    pad_u = unnzp - u.nnz

    return FineProblem(
        rowptr=jnp.asarray(d.rowptr),
        colidx=jnp.asarray(d.colidx),
        edge_row=jnp.asarray(d.edge_row),
        deg=jnp.asarray(d.deg),
        urowptr=jnp.asarray(u.rowptr.astype(np.int32)),
        ucolidx=jnp.asarray(np.pad(u.colidx.astype(np.int32), (0, pad_u))),
        u2d=jnp.asarray(
            np.pad(u2d.astype(np.int32), (0, pad_u), constant_values=nnzp)
        ),
        uedge_row=jnp.asarray(np.pad(u.row_of_edge().astype(np.int32), (0, pad_u))),
        udeg=jnp.asarray(u.degrees().astype(np.int32)),
    )


# ---------------------------------------------------------------------- #
# Mode "eager": faithful Algorithm 3 dataflow (scatter-adds for atomics)
# ---------------------------------------------------------------------- #
def support_fine_eager(
    p: FineProblem,
    alive: jax.Array,
    *,
    window: int,
    chunk: int = 1024,
    tasks: jax.Array | None = None,
    s_init: jax.Array | None = None,
) -> jax.Array:
    """Support per directed edge via the eager triple-update (Alg. 3).

    Without ``tasks`` every lane is a task, and both windows are read as
    rows of a per-vertex table (:func:`_support_rows`).  With ``tasks`` a
    bucket's window may be narrower than a row, so the windows are read
    lane by lane (:func:`_support_tasks`).

    Args:
      p: problem arrays (``prepare_fine``).
      alive: (nnzp,) bool — surviving edges (pad lanes False).
      window: static window width ≥ max degree of the graph.
      chunk: tasks per scan step.
      tasks: optional (multiple-of-chunk,) explicit task ids to process
        (``nnz_pad`` = skip) — the degree-bucketing hook: each bucket runs
        with a window sized to its own degree class instead of the global
        max (EXPERIMENTS §Perf-ktruss).
      s_init: optional accumulator to add into (bucket chaining).

    Returns:
      (nnzp,) int32 support (0 on dead/pad lanes).
    """
    n_tasks = p.nnz_pad if tasks is None else int(tasks.shape[0])
    if n_tasks % chunk:
        raise ValueError(f"tasks={n_tasks} not a multiple of chunk={chunk}")
    if tasks is None:
        s = _support_rows(p, alive, window=int(window), chunk=chunk)
        return s if s_init is None else s_init + s
    return _support_tasks(p, alive, tasks, window=int(window), chunk=chunk, s_init=s_init)


def _intersect(q: jax.Array, b_key: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Broadcast compare of (chunk, W) queries with (chunk, W) row-κ keys.

    Queries are 0 or 1-based vertex ids, keys a vertex id or -1, so only
    live entries of both windows match.  Each window holds distinct ids,
    so a query matches at most one key and the reverse.  Row κ is compared
    in blocks of at most ``_B_BLOCK`` entries to bound one step's
    (chunk, W, block) compare; XLA fuses each into its two reductions.
    Returns (a_hit, b_hit): which queries and which keys matched.
    """
    a_hit = jnp.zeros(q.shape, bool)
    b_hits = []
    for k0 in range(0, b_key.shape[1], _B_BLOCK):
        eq = q[:, :, None] == b_key[:, None, k0 : k0 + _B_BLOCK]
        a_hit |= jnp.any(eq, axis=2)
        b_hits.append(jnp.any(eq, axis=1))
    return a_hit, jnp.concatenate(b_hits, axis=1)


def _support_rows(p: FineProblem, alive: jax.Array, *, window: int, chunk: int) -> jax.Array:
    """The eager pass over every lane, its windows read as table rows.

    Lane ``e`` of row ``i`` is cell ``(i, pos(e))`` of an ``(n+1, W)``
    table, ``pos`` its offset from the row's start.  Task ``t`` of edge
    ``(i, κ)`` then reads its suffix window as row ``i`` past ``pos(t)``
    and its row-κ window as row ``κ``: one row gather each per chunk.  The
    u2 and u3 updates are one row scatter-add each into a support table of
    the same shape, with u1 folded into u2 at the task's own cell.  A pass
    costs one element scatter (the key table) and one element gather (the
    support read back) of ``nnz_pad`` lanes, against 6·chunk·W element
    reads and writes a chunk lane by lane.  Pad lanes map to no cell; the
    cells no lane fills hold the key -1 and never match.
    """
    nnzp, rows, w = p.nnz_pad, p.n + 1, window
    offs = jnp.arange(w, dtype=jnp.int32)[None, :]
    lane = jnp.arange(nnzp, dtype=jnp.int32)
    real = p.colidx != 0
    i = p.edge_row
    # Row starts from rowptr, the only way it is read (FineProblem's
    # contract): per_chip_support rebases it to a chip's lane block.
    pos = lane - p.rowptr[jnp.maximum(i, 1) - 1] * (i > 0)
    cell_row = jnp.where(real, i, rows)  # pad lanes: out of range, dropped
    key = jnp.where(alive, p.colidx, -1)
    tab = jnp.full((rows, w), -1, jnp.int32).at[cell_row, pos].set(key, mode="drop")
    valid = real & alive

    def body(s_tab: jax.Array, xs):
        i_c, kappa, pos_t, valid_t = xs
        a = tab[i_c]
        q = jnp.where((offs > pos_t[:, None]) & valid_t[:, None] & (a > 0), a, 0)
        a_hit, b_hit = _intersect(q, tab[kappa])
        a_hit = a_hit.astype(jnp.int32)
        # u2 on the matched suffix cells, u1 on the task's own cell.
        own = jnp.where(offs == pos_t[:, None], jnp.sum(a_hit, axis=1, keepdims=True), 0)
        s_tab = s_tab.at[i_c].add(a_hit + own)
        # u3 on the matched row-κ cells.
        s_tab = s_tab.at[kappa].add(b_hit.astype(jnp.int32))
        return s_tab, None

    xs = tuple(x.reshape(-1, chunk) for x in (i, p.colidx, pos, valid))
    s_tab, _ = jax.lax.scan(body, jnp.zeros((rows, w), jnp.int32), xs)
    return s_tab.at[cell_row, pos].get(mode="fill", fill_value=0)


def _support_tasks(
    p: FineProblem,
    alive: jax.Array,
    tasks: jax.Array,
    *,
    window: int,
    chunk: int,
    s_init: jax.Array | None,
) -> jax.Array:
    """The eager pass over explicit tasks, its windows read lane by lane."""
    nnzp, w = p.nnz_pad, window
    offs = jnp.arange(w, dtype=jnp.int32)[None, :]

    def body(s_acc: jax.Array, chunk_start: jax.Array):
        raw = tasks[chunk_start + jnp.arange(chunk, dtype=jnp.int32)]
        t = jnp.minimum(raw, nnzp - 1).astype(jnp.int32)
        kappa = p.colidx[t]
        i = p.edge_row[t]
        valid_t = (kappa != 0) & alive[t] & (raw < nnzp)

        # --- row-i suffix window (queries) -------------------------------
        a_idx = t[:, None] + 1 + offs  # global colidx positions
        # Row end as start + degree (not rowptr[i]): rowptr is read only as
        # row *starts* so slot-aligned packings may interleave pad lanes
        # between slots without violating any prefix-sum invariant.
        i_start = p.rowptr[jnp.maximum(i, 1) - 1] * (i > 0)
        row_end = (i_start + p.deg[i])[:, None]
        a_in = a_idx < row_end
        a_idx_c = jnp.clip(a_idx, 0, nnzp - 1)
        a_vals = jnp.where(a_in, p.colidx[a_idx_c], 0)
        a_alive = a_in & alive[a_idx_c]
        q = jnp.where(a_alive & valid_t[:, None], a_vals, 0)

        # --- row-κ window, keyed for the compare -------------------------
        # Dead and out-of-row lanes read -1, which no query equals.
        b_start = p.rowptr[jnp.maximum(kappa, 1) - 1] * (kappa > 0)
        b_idx_c = jnp.clip(b_start[:, None] + offs, 0, nnzp - 1)
        b_in = offs < p.deg[kappa][:, None]
        b_key = jnp.where(b_in & alive[b_idx_c], p.colidx[b_idx_c], -1)

        a_hit, b_hit = _intersect(q, b_key)
        # u1: the task's own edge accumulates the intersection size.
        s_acc = s_acc.at[t].add(jnp.sum(a_hit, axis=1, dtype=jnp.int32))
        # u2: matched suffix entries (edges (i, m)) — scatter to row i slots.
        u2_tgt = jnp.where(a_hit, a_idx_c, nnzp)
        s_acc = s_acc.at[u2_tgt.reshape(-1)].add(1, mode="drop")
        # u3: matched row-κ entries (edges (κ, m)) — scatter to row κ slots.
        u3_tgt = jnp.where(b_hit, b_idx_c, nnzp)
        s_acc = s_acc.at[u3_tgt.reshape(-1)].add(1, mode="drop")
        return s_acc, None

    starts = jnp.arange(0, int(tasks.shape[0]), chunk, dtype=jnp.int32)
    s0 = jnp.zeros(nnzp, jnp.int32) if s_init is None else s_init
    s_final, _ = jax.lax.scan(body, s0, starts)
    return s_final


def bucket_tasks(g: CSRGraph, chunk: int = 256) -> list[tuple[int, np.ndarray]]:
    """Partition edge tasks into power-of-two window buckets.

    Task t of edge (i,κ) needs window ≥ max(deg(i)−pos−1, deg(κ)); using
    the global max degree pads every task to the heaviest one — the same
    waste the paper removes at the row level, now removed at the window
    level (the "ultra-fine-grained" direction the paper defers).  Returns
    [(window, task_ids padded to chunk multiples with nnz_pad sentinels)].
    """
    deg = g.degrees()
    rows = g.row_of_edge()
    pos = g.pos_in_row()
    need = np.maximum(deg[rows] - pos - 1, deg[g.colidx]).astype(np.int64)
    need = np.maximum(need, 1)
    bucket_of = np.maximum(8, 2 ** np.ceil(np.log2(need)).astype(np.int64))
    out = []
    for wb in sorted(set(bucket_of.tolist())):
        ids = np.nonzero(bucket_of == wb)[0].astype(np.int32)
        padded = -(-len(ids) // chunk) * chunk
        ids = np.pad(ids, (0, padded - len(ids)), constant_values=np.iinfo(np.int32).max)
        out.append((int(wb), ids))
    return out


def support_fine_bucketed(
    p: FineProblem,
    alive: jax.Array,
    buckets: list[tuple[int, jax.Array]],
    *,
    chunk: int = 256,
) -> jax.Array:
    """Fine eager support with per-bucket windows (chained accumulation)."""
    s = jnp.zeros(p.nnz_pad, jnp.int32)
    for wb, ids in buckets:
        s = support_fine_eager(
            p, alive, window=wb, chunk=min(chunk, ids.shape[0]), tasks=ids, s_init=s
        )
    return s


# ---------------------------------------------------------------------- #
# Mode "owner": collision-free symmetric reformulation (TPU-kernel form)
# ---------------------------------------------------------------------- #
def support_fine_owner(
    p: FineProblem, alive: jax.Array, *, window: int, chunk: int = 1024
) -> jax.Array:
    """Support per directed edge as |N(a) ∩ N(b)| over undirected alive rows.

    ``window`` must be ≥ max *undirected* degree.  No scatters: each output
    lane is written by exactly one task (ownership partitioning).
    """
    nnzp = p.nnz_pad
    if nnzp % chunk:
        raise ValueError(f"nnz_pad={nnzp} not a multiple of chunk={chunk}")
    w = int(window)
    unnzp = int(p.ucolidx.shape[0])
    large = jnp.int32(p.n + 2)
    offs = jnp.arange(w, dtype=jnp.int32)[None, :]

    # alive mask lifted to the undirected view (pad u2d lanes -> False).
    alive_pad = jnp.concatenate([alive, jnp.zeros((1,), alive.dtype)])
    ualive = alive_pad[jnp.minimum(p.u2d, nnzp)] & (p.ucolidx != 0)

    def row_window(v: jax.Array):
        """(C, w) undirected window of vertex v: (nav values, alive mask)."""
        start = p.urowptr[jnp.maximum(v, 1) - 1] * (v > 0)
        idx = start[:, None] + offs
        n_in = offs < p.udeg[v][:, None]
        idx_c = jnp.clip(idx, 0, unnzp - 1)
        nav = jnp.where(n_in, p.ucolidx[idx_c], large)
        return nav, n_in & ualive[idx_c]

    def body(_, chunk_start: jax.Array):
        t = chunk_start + jnp.arange(chunk, dtype=jnp.int32)
        a = p.edge_row[t]
        b = p.colidx[t]
        valid_t = (b != 0) & alive[t]

        a_nav, a_alive = row_window(a)
        b_nav, b_alive = row_window(b)
        q = jnp.where(a_alive & valid_t[:, None], a_nav, 0)
        # a_nav uses `large` for invalid lanes; queries must be 0 there.
        q = jnp.where(q >= large, 0, q)
        member, pos = sorted_window_member(q, b_nav)
        member &= jnp.take_along_axis(b_alive, jnp.minimum(pos, w - 1), axis=1, mode="clip")
        return _, jnp.sum(member.astype(jnp.int32), axis=1) * valid_t.astype(
            jnp.int32
        )

    starts = jnp.arange(0, nnzp, chunk, dtype=jnp.int32)
    _, s_chunks = jax.lax.scan(body, None, starts)
    return s_chunks.reshape(-1)


# ---------------------------------------------------------------------- #
# Batched entry point: many same-shape graphs in one device dispatch
# ---------------------------------------------------------------------- #
def support_fine_stacked(
    p: FineProblem,
    alive: jax.Array,
    *,
    window: int,
    chunk: int = 1024,
    mode: str = "eager",
) -> jax.Array:
    """``alive -> support`` over a leading batch axis.

    ``p`` is a :class:`FineProblem` whose every field carries a leading
    ``(B, ...)`` batch dimension (see ``repro.graphs.pack.stack_problems``)
    and ``alive`` is ``(B, nnzp)``.  All B graphs must share one shape
    bucket; the batch is sequenced through one compiled program via
    ``lax.map`` so a micro-batch costs one dispatch, not B.

    Returns (B, nnzp) int32 supports.
    """
    if mode == "eager":
        fn = functools.partial(support_fine_eager, window=window, chunk=chunk)
    elif mode == "owner":
        fn = functools.partial(support_fine_owner, window=window, chunk=chunk)
    else:
        raise ValueError(f"unknown mode {mode!r}")
    return jax.lax.map(lambda pa: fn(pa[0], pa[1]), (p, alive))
