"""The benchmark's edge lists: the canonical form every graph family
(``bench/families/<family>.py``) returns, and the helpers that work on it.

A canonical edge list is 0-based ``(u, v)`` pairs with ``u < v``,
deduplicated, self loops dropped, sorted by ``(u, v)``.  That order is
the order of the answers: the program's upper-triangular CSR lists its
edges the same way, so trussness and alive masks line up edge by edge.
"""

from __future__ import annotations

import numpy as np

__all__ = ["canonical_edges", "relabel", "max_degree", "to_csr"]


def canonical_edges(n: int, edges: np.ndarray) -> np.ndarray:
    """``(m, 2)`` int64: undirected, ``u < v``, unique, sorted, no loops."""
    edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    u = np.minimum(edges[:, 0], edges[:, 1])
    v = np.maximum(edges[:, 0], edges[:, 1])
    keep = u != v
    key = np.unique(u[keep] * n + v[keep])
    return np.stack([key // n, key % n], axis=1)


def relabel(n: int, edges: np.ndarray, perm: np.ndarray) -> np.ndarray:
    """The same graph with vertex ``v`` renamed ``perm[v]``: an isomorphic
    copy, so the same work, under other labels."""
    return canonical_edges(n, perm[edges])


def max_degree(n: int, edges: np.ndarray) -> int:
    """Largest undirected degree."""
    return int(np.bincount(edges.ravel(), minlength=n).max(initial=0))


def to_csr(n: int, edges: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """``(rowptr, colidx)`` of the upper-triangular CSR in the program's
    1-based convention: row ``v`` spans ``colidx[rowptr[v-1]:rowptr[v]]``."""
    counts = np.bincount(edges[:, 0] + 1, minlength=n + 1)[: n + 1]
    rowptr = np.cumsum(counts).astype(np.int64)
    colidx = (edges[:, 1] + 1).astype(np.int32)
    return rowptr, colidx
