"""Core K-truss correctness: all decompositions/modes vs independent oracles."""

import networkx as nx
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import (
    KTrussEngine,
    kmax_numpy,
    ktruss_dense,
    ktruss_numpy,
    prepare_fine,
    support_coarse_eager,
    support_fine_eager,
    support_fine_owner,
    support_numpy,
    trussness_numpy,
)
from repro.exec import PeelExecutor
from repro.graphs import CSRGraph, from_edges, pack_problems, rmat

import jax.numpy as jnp


ALL_VARIANTS = [("fine", "eager"), ("fine", "owner"), ("coarse", "eager")]


def _w(g, owner=False):
    deg = g.undirected_csr().max_degree() if owner else g.max_degree()
    return max(8, ((deg + 7) // 8) * 8)


# ------------------------------------------------------------------ #
# Support computation == oracle
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("variant", ALL_VARIANTS, ids=["fine-eager", "fine-owner", "coarse"])
def test_support_matches_oracle(small_graphs, variant):
    gran, mode = variant
    for g in small_graphs:
        p = prepare_fine(g, chunk=256)
        alive = jnp.asarray(p.colidx != 0)
        if gran == "coarse":
            s = support_coarse_eager(p, alive, window=_w(g), row_chunk=16)
        elif mode == "eager":
            s = support_fine_eager(p, alive, window=_w(g), chunk=256)
        else:
            s = support_fine_owner(p, alive, window=_w(g, owner=True), chunk=256)
        assert np.array_equal(np.asarray(s)[: g.nnz], support_numpy(g)), g.name


def test_support_on_pruned_graph(small_graphs):
    """Alive-masked supports must agree across variants mid-convergence."""
    g = small_graphs[1]
    p = prepare_fine(g, chunk=256)
    rng = np.random.default_rng(0)
    alive_np = rng.random(p.nnz_pad) < 0.7
    alive_np &= np.asarray(p.colidx) != 0
    alive = jnp.asarray(alive_np)
    ref = support_numpy(g, alive_np[: g.nnz])
    s1 = np.asarray(support_fine_eager(p, alive, window=_w(g), chunk=256))[: g.nnz]
    s2 = np.asarray(support_fine_owner(p, alive, window=_w(g, True), chunk=256))[: g.nnz]
    s3 = np.asarray(support_coarse_eager(p, alive, window=_w(g), row_chunk=8))[: g.nnz]
    live = alive_np[: g.nnz]
    assert np.array_equal(s1 * live, ref * live)
    assert np.array_equal(s2 * live, ref * live)
    assert np.array_equal(s3 * live, ref * live)


# ------------------------------------------------------------------ #
# Fixed point + kmax vs oracles (incl. networkx)
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("variant", ALL_VARIANTS, ids=["fine-eager", "fine-owner", "coarse"])
def test_ktruss_fixed_point(small_graphs, variant):
    gran, mode = variant
    for g in small_graphs:
        eng = KTrussEngine(g, granularity=gran, mode=mode, chunk=256)
        for k in (3, 4):
            res = eng.ktruss(k)
            alive_ref, s_ref = ktruss_numpy(g, k)
            assert np.array_equal(res.alive, alive_ref)
            assert np.array_equal(res.support, s_ref)


def test_ktruss_matches_networkx():
    g = from_edges(
        60, np.random.default_rng(3).integers(0, 60, size=(400, 2))
    )
    eng = KTrussEngine(g, granularity="fine", mode="eager", chunk=256)
    edges = g.edge_list() - 1  # back to 0-based
    nxg = nx.Graph(list(map(tuple, edges)))
    for k in (3, 4, 5):
        res = eng.ktruss(k)
        ours = {tuple(e) for e, a in zip(map(tuple, edges), res.alive) if a}
        theirs = set()
        for u, v in nx.k_truss(nxg, k).edges():
            theirs.add((min(u, v), max(u, v)))
        assert ours == theirs, f"k={k}"


def test_kmax_warm_start(small_graphs):
    for g in small_graphs[:2]:
        eng = KTrussEngine(g, granularity="fine", mode="owner", chunk=256)
        assert eng.kmax() == kmax_numpy(g)


def test_dense_reference_agrees(small_graphs):
    g = small_graphs[0]
    u = g.dense_upper()
    u = jnp.asarray(u + u.T)
    adj, s = ktruss_dense(u, 3)
    alive_ref, s_ref = ktruss_numpy(g, 3)
    rows, cols = g.row_of_edge(), g.colidx
    assert np.array_equal(np.asarray(adj)[rows, cols] > 0, alive_ref)
    assert np.array_equal(np.asarray(s)[rows, cols], s_ref)


# ------------------------------------------------------------------ #
# Properties (hypothesis)
# ------------------------------------------------------------------ #
@given(
    n=st.integers(4, 24),
    m=st.integers(0, 80),
    seed=st.integers(0, 10_000),
)
@settings(max_examples=25, deadline=None)
def test_property_modes_agree(n, m, seed):
    rng = np.random.default_rng(seed)
    g = from_edges(n, rng.integers(0, n, size=(m, 2)))
    if g.nnz == 0:
        return
    p = prepare_fine(g, chunk=64)
    alive = jnp.asarray(p.colidx != 0)
    s_e = np.asarray(support_fine_eager(p, alive, window=_w(g), chunk=64))
    s_o = np.asarray(support_fine_owner(p, alive, window=_w(g, True), chunk=64))
    assert np.array_equal(s_e, s_o)  # ownership == eager (DESIGN §4)


@given(n=st.integers(5, 20), m=st.integers(5, 60), seed=st.integers(0, 9999))
@settings(max_examples=15, deadline=None)
def test_property_truss_is_maximal_and_stable(n, m, seed):
    rng = np.random.default_rng(seed)
    g = from_edges(n, rng.integers(0, n, size=(m, 2)))
    if g.nnz == 0:
        return
    eng = KTrussEngine(g, granularity="fine", mode="eager", chunk=64)
    res = eng.ktruss(3)
    # Every surviving edge has support ≥ 1 within the surviving subgraph.
    s = support_numpy(g, res.alive)
    assert np.all(s[res.alive] >= 1)
    # Fixed point: running again changes nothing.
    pad = eng.problem.nnz_pad - g.nnz
    res2 = eng.ktruss(3, alive0=jnp.asarray(np.pad(res.alive, (0, pad))))
    assert np.array_equal(res.alive, res2.alive)


def test_bucketed_fine_matches_oracle(small_graphs):
    """Degree-bucketed windows (beyond-paper §Perf-ktruss) are exact."""
    for g in small_graphs:
        eng = KTrussEngine(g, bucketed=True, chunk=256)
        for k in (3, 4):
            res = eng.ktruss(k)
            alive_ref, s_ref = ktruss_numpy(g, k)
            assert np.array_equal(res.alive, alive_ref), (g.name, k)
            assert np.array_equal(res.support, s_ref), (g.name, k)


# ------------------------------------------------------------------ #
# Window intersection at windows of one and of several blocks
# ------------------------------------------------------------------ #
def _hub_graph(w, seed):
    """A hub whose directed row is exactly ``w`` wide, below a vertex
    linked to it, so the task (that vertex, hub) compares a suffix with a
    full ``w``-wide row-κ window; chords among the hub's neighbours close
    triangles at several truss levels."""
    rng = np.random.default_rng(seed)
    nbrs = np.arange(2, w + 2)  # 0-based: vertex 1 is the hub
    hub = np.stack([np.ones(w, np.int64), nbrs], axis=1)
    low = np.stack(
        [np.zeros(w // 2 + 1, np.int64), np.r_[1, rng.choice(nbrs, w // 2, replace=False)]],
        axis=1,
    )
    chords = rng.choice(nbrs, size=(w, 2))
    return from_edges(w + 2, np.concatenate([hub, low, chords]), name=f"hub{w}-{seed}")


def _support_at(variant, p, alive, window):
    gran, mode = variant
    if gran == "coarse":
        return support_coarse_eager(p, alive, window=window, row_chunk=8)
    if mode == "owner":
        return support_fine_owner(p, alive, window=window, chunk=64)
    return support_fine_eager(p, alive, window=window, chunk=64)


@pytest.mark.parametrize("variant", ALL_VARIANTS, ids=["fine-eager", "fine-owner", "coarse"])
@pytest.mark.parametrize("w", [8, 32, 64, 256, 512])
def test_support_matches_oracle_at_window(w, variant):
    graphs = [_hub_graph(w, seed) for seed in range(3)]
    assert all(g.max_degree() == w for g in graphs)  # the hub sets W
    # Each support's own window rule: the owner support reads the
    # undirected degree, the others the directed one.
    owner = variant[1] == "owner"
    window = max(_w(g, owner=owner) for g in graphs)

    # Dead lanes: a partly peeled alive mask.
    g = graphs[0]
    p = prepare_fine(g, chunk=64)
    rng = np.random.default_rng(w)
    alive_np = (rng.random(p.nnz_pad) < 0.7) & (np.asarray(p.colidx) != 0)
    s = np.asarray(_support_at(variant, p, jnp.asarray(alive_np), window))
    live = alive_np[: g.nnz]
    assert np.array_equal(s[: g.nnz], support_numpy(g, live) * live)
    assert not s[g.nnz :].any()

    # A slot-aligned pack: windows of each slot's last rows run into the
    # pad lanes before the next slot; the fourth slot is empty.
    slot_nnz = max(64, 1 << max(g.nnz for g in graphs).bit_length())
    pp = pack_problems(
        graphs, slot_n=w + 2, slot_nnz=slot_nnz, slots=4, chunk=64, layout="aligned"
    )
    alive = jnp.asarray(pp.problem.colidx != 0)
    s = np.asarray(_support_at(variant, pp.problem, alive, window))
    for g, (a, b) in zip(graphs, pp.edge_ranges):
        assert np.array_equal(s[a:b], support_numpy(g)), g.name
    if variant[0] == "coarse" and w > 256:
        # A coarse pass costs O(n·W²), far outside its bounded-degree
        # regime at W 512: its supports are checked above, its peel to W 256.
        return
    exe = PeelExecutor(
        granularity=variant[0], mode=variant[1], backend="xla",
        window=window, chunk=64, row_chunk=8,
    )
    st = exe.peel(
        pp.problem,
        slot_ids=np.repeat(np.arange(4, dtype=np.int32), slot_nnz),
        k0=[3] * 4,
    )
    truss = np.asarray(st.trussness)
    for g, (a, b) in zip(graphs, pp.edge_ranges):
        assert np.array_equal(truss[a:b], trussness_numpy(g)), g.name


# ------------------------------------------------------------------ #
# Eager windows read as table rows: families, masks, exact windows
# ------------------------------------------------------------------ #
# Graph500's Kronecker initiator at its edge factor 16 (the benchmark's
# graphs, cut to scale 6), and an R-MAT of a flatter initiator.
_FAMILIES = {
    "kron": lambda seed: rmat(6, 16, seed=seed),
    "rmat": lambda seed: rmat(7, 4, a=0.45, b=0.22, c=0.22, seed=seed),
}


def _mask(name, real, seed):
    if name == "full":
        return real
    if name == "empty":
        return np.zeros_like(real)
    return real & (np.random.default_rng(seed).random(real.shape[0]) < 0.6)


@pytest.mark.parametrize("mask", ["full", "partial", "empty"])
@pytest.mark.parametrize("family", sorted(_FAMILIES))
def test_fine_eager_support_matches_oracle_under_masks(family, mask):
    """The eager pass, its windows read as rows of the per-vertex table,
    at a window exactly the widest directed row (its last lane fills the
    table's last column): alone, and in a slot-aligned batch whose slots
    end in pad lanes, the last slot empty."""
    graphs = [_FAMILIES[family](seed) for seed in range(2)]
    for g in graphs:
        p = prepare_fine(g, chunk=64)
        alive = _mask(mask, np.asarray(p.colidx) != 0, g.nnz)
        s = np.asarray(
            support_fine_eager(p, jnp.asarray(alive), window=g.max_degree(), chunk=64)
        )
        live = alive[: g.nnz]
        assert np.array_equal(s[: g.nnz], support_numpy(g, live) * live), g.name
        assert not s[g.nnz :].any()

    slot_nnz = 64 * -(-max(g.nnz for g in graphs) // 64) + 64
    pp = pack_problems(
        graphs, slot_n=max(g.n for g in graphs), slot_nnz=slot_nnz, slots=3,
        chunk=64, layout="aligned",
    )
    alive = _mask(mask, np.asarray(pp.problem.colidx) != 0, 7)
    window = max(g.max_degree() for g in graphs)
    s = np.asarray(support_fine_eager(pp.problem, jnp.asarray(alive), window=window, chunk=64))
    owned = np.zeros(s.shape[0], bool)
    for g, (a, b) in zip(graphs, pp.edge_ranges):
        assert np.array_equal(s[a:b], support_numpy(g, alive[a:b]) * alive[a:b]), g.name
        owned[a:b] = True
    assert not s[~owned].any()
