"""The pinned generators and the plain reference."""

import hashlib

import numpy as np
import pytest
from conftest import ROOT

from bench import graphs as G
from bench import reference as R
from bench.spec import load_module

kronecker = load_module(ROOT, "families", "kronecker").generate


def _digest(edges):
    return hashlib.sha256(np.ascontiguousarray(edges, np.int64).tobytes()).hexdigest()[:16]


# Recorded when the generators were pinned: a change to bench/graphs.py
# that moves the data shows here.
PINNED = {
    ("kronecker", 8, 0): (2124, "b81d84a5d052523e"),
}


@pytest.mark.parametrize("family,scale,seed", sorted(PINNED))
def test_generators_are_pinned(family, scale, seed):
    n, edges = load_module(ROOT, "families", family).generate(scale=scale, seed=seed)
    count, digest = PINNED[(family, scale, seed)]
    assert len(edges) == count
    assert _digest(edges) == digest
    assert (edges[:, 0] < edges[:, 1]).all()
    assert len(np.unique(edges[:, 0] * n + edges[:, 1])) == len(edges)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_reference_agrees_with_an_independent_oracle(seed):
    """The set-intersection oracle of the program's test suite, on the
    program's own CSR of the same edges."""
    from repro.core.reference import ktruss_numpy, trussness_numpy
    from repro.graphs.csr import CSRGraph

    for n, edges in (kronecker(scale=6, edge_factor=8, seed=seed),
                     kronecker(scale=7, edge_factor=4, seed=seed)):
        g = CSRGraph(n, *G.to_csr(n, edges))
        assert np.array_equal(R.trussness(n, edges), trussness_numpy(g))
        assert np.array_equal(R.ktruss_alive(n, edges, 4), ktruss_numpy(g, 4)[0])


def test_the_window_cut_breaks_exactness():
    n, edges = kronecker(scale=7, seed=4)
    assert (R.trussness(n, edges, window=16) != R.trussness(n, edges)).any()
    assert (R.ktruss_alive(n, edges, 3, window=16) != R.ktruss_alive(n, edges, 3)).any()


def test_relabel_keeps_the_graph():
    n, edges = kronecker(scale=6, edge_factor=8, seed=1)
    perm = np.random.default_rng(0).permutation(n)
    other = G.relabel(n, edges, perm)
    assert len(other) == len(edges)
    assert sorted(R.trussness(n, other)) == sorted(R.trussness(n, edges))
