"""The plain reference that decides ``correct``, independent of the program.

A k-truss is the largest subgraph in which every edge closes at least
``k - 2`` triangles; an edge's trussness is the largest ``k`` whose
k-truss holds it (2 for an edge in no triangle).  This module computes
both from the benchmark's own canonical edge list (``bench/graphs.py``)
with a dense 0/1 adjacency in numpy: support is ``(A @ A) * A``
(Algorithm 1 of the paper), and a k-truss is that pruning run to its
fixed point.  It imports nothing of the program.

Products are taken in float64, which holds every triangle count of a
graph below 2**53 vertices exactly.  Dense is right for the benchmark's
graphs (n <= 512: one support pass is a 512 x 512 product).
"""

from __future__ import annotations

import numpy as np

__all__ = ["adjacency", "ktruss_alive", "trussness", "kmax_of"]


def adjacency(n: int, edges: np.ndarray) -> np.ndarray:
    """Dense symmetric 0/1 float64 adjacency of a canonical edge list."""
    a = np.zeros((n, n), np.float64)
    a[edges[:, 0], edges[:, 1]] = 1.0
    a[edges[:, 1], edges[:, 0]] = 1.0
    return a


def _support(a: np.ndarray, edges: np.ndarray, window: int | None) -> np.ndarray:
    """Triangles each edge closes within the alive adjacency ``a``; with
    ``window``, only through each vertex's first ``window`` alive
    neighbours (by id)."""
    if window is not None:
        a = a * (np.cumsum(a, axis=1) <= window)
        return (a @ a.T)[edges[:, 0], edges[:, 1]]
    return (a @ a)[edges[:, 0], edges[:, 1]]


def _prune_to_fixed_point(a, edges, alive, k, window=None):
    """Drop alive edges with support below ``k - 2`` until none drops."""
    while True:
        s = _support(a, edges, window)
        drop = alive & (s < k - 2)
        if not drop.any():
            break
        alive = alive & ~drop
        a[edges[drop, 0], edges[drop, 1]] = 0.0
        a[edges[drop, 1], edges[drop, 0]] = 0.0
    return alive


def ktruss_alive(n: int, edges: np.ndarray, k: int, *, window=None) -> np.ndarray:
    """``(m,)`` bool: the edges of the k-truss (``window``: see below)."""
    a = adjacency(n, edges)
    return _prune_to_fixed_point(a, edges, np.ones(len(edges), bool), k, window)


def trussness(n: int, edges: np.ndarray, *, window=None) -> np.ndarray:
    """``(m,)`` int64 trussness per edge, peeling level by level from k = 3.

    ``window`` closes triangles only through each vertex's first
    ``window`` neighbours, as an intersection cut to a fixed window
    would.  That breaks exactness on purpose: it is the benchmark's
    control (``bench/control.py``), never the reference.
    """
    a = adjacency(n, edges)
    out = np.full(len(edges), 2, np.int64)
    alive = np.ones(len(edges), bool)
    k = 3
    while alive.any():
        alive = _prune_to_fixed_point(a, edges, alive, k, window)
        out[alive] = k
        k += 1
    return out


def kmax_of(truss: np.ndarray) -> int:
    """Largest k with a non-empty k-truss; 0 when even the 3-truss is empty."""
    top = int(truss.max(initial=0))
    return top if top >= 3 else 0
