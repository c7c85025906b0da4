"""Graph500 Kronecker (R-MAT) graphs, pinned here so that no change to the
program's own generators can move the data a cell runs on: a copy of
``rmat`` in ``src/repro/graphs/generators.py`` as of the benchmark's
first version (the Graph500 initiator, A/B/C = 0.57/0.19/0.19).
"""

from __future__ import annotations

import numpy as np

from bench.graphs import canonical_edges


def generate(
    *,
    scale: int,
    edge_factor: int = 16,
    a: float = 0.57,
    b: float = 0.19,
    c: float = 0.19,
    seed: int = 0,
) -> tuple[int, np.ndarray]:
    """Graph500 Kronecker (R-MAT) graph with ``2**scale`` vertices and
    ``edge_factor * 2**scale`` generated edges, randomly relabelled.
    Returns ``(n, canonical edges)``."""
    rng = np.random.default_rng(seed)
    n = 1 << scale
    m = n * edge_factor
    src = np.zeros(m, dtype=np.int64)
    dst = np.zeros(m, dtype=np.int64)
    ab = a + b
    for bit in range(scale):
        r = rng.random(m)
        right = r >= ab
        r2 = rng.random(m)
        col_right = np.where(
            right,
            r2 >= (c / (1.0 - ab)) if ab < 1.0 else False,
            r2 >= (a / ab),
        )
        src |= right.astype(np.int64) << bit
        dst |= col_right.astype(np.int64) << bit
    perm = rng.permutation(n)
    return n, canonical_edges(n, np.stack([perm[src], perm[dst]], 1))
