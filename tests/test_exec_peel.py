"""Exec layer: the on-device peel behind every multi-level workload.

Covers the PR-level contracts: one device dispatch per decompose/kmax (no
per-level host round-trips, asserted via the executor dispatch counter),
batched trussness bit-identical to the per-graph engine across generator
families, slot-aligned packing, the Pallas backend through the serving
path, every support giving the eager XLA support's whole peel state,
targeted ``result()`` resolution, the sharded executor on 8
simulated host devices matching unsharded results exactly, and the support
pass run per device on a 4-device slot mesh matching one device bit for bit.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import KTrussEngine, support_fine_eager, support_numpy, trussness_numpy
from repro.exec import PeelExecutor
from repro.graphs import barabasi, clustered, erdos, pack_problems, rmat, road
from repro.service import TrussService, bucket_for


def _families():
    return [
        erdos(90, 6.0, seed=0),
        barabasi(110, 3, seed=1),
        clustered(3, 14, 0.6, seed=2),
        road(9, 0.1, seed=3),
        rmat(6, 4, seed=4),
    ]


def _same_bucket(factory, count, *, chunk=64, tries=64):
    """First ``count`` generated graphs sharing one shape bucket (different
    seeds can shift the power-of-two window/nnz bucket)."""
    groups = {}
    for s in range(tries):
        g = factory(s)
        groups.setdefault(bucket_for(g, chunk=chunk), []).append(g)
        if len(groups[bucket_for(g, chunk=chunk)]) == count:
            return groups[bucket_for(g, chunk=chunk)]
    raise AssertionError(f"no bucket reached {count} graphs in {tries} tries")


# ------------------------------------------------------------------ #
# One dispatch per multi-level workload + bit-identical results
# ------------------------------------------------------------------ #
def test_engine_decompose_is_one_dispatch():
    for g in _families():
        eng = KTrussEngine(g, chunk=64)
        dec = eng.decompose()
        assert eng.peel_executor.dispatches == 1, g.name
        km = eng.kmax()
        assert eng.peel_executor.dispatches == 2, g.name
        # decompose kmax floors at 2 (every edge is in the 2-truss);
        # kmax() reports 0 when even the 3-truss is empty.
        assert km == (dec.kmax if dec.kmax >= 3 else 0)
        # levels == peeled thresholds: one per k in [3, kmax] + final empty.
        assert dec.levels == (max(dec.kmax - 2, 0) + 1 if g.nnz else 0)


def test_batched_decompose_one_dispatch_matches_engine():
    graphs = _same_bucket(lambda s: erdos(80, 6.0, seed=s), 4)
    svc = TrussService(max_batch=4, chunk=64)
    futs = [svc.submit_decompose(g) for g in graphs]
    svc.flush()
    st = svc.stats()
    assert st["device_dispatches"] == 1, st  # whole batch, every level: once
    assert st["batches_run"] == 1
    for g, fut in zip(graphs, futs):
        dec = fut.result()
        edec = KTrussEngine(g, chunk=64).decompose()
        assert np.array_equal(dec.trussness, edec.trussness), g.name
        assert dec.kmax == edec.kmax and dec.levels == edec.levels


def test_mixed_workload_batch_resolves_in_one_dispatch():
    graphs = _same_bucket(lambda s: erdos(80, 6.0, seed=s), 4)
    svc = TrussService(max_batch=4, chunk=64)
    f_kt = svc.submit_ktruss(graphs[0], 4)
    f_km = svc.submit_kmax(graphs[1])
    f_dc = svc.submit_decompose(graphs[2])
    f_k3 = svc.submit_ktruss(graphs[3], 3)
    svc.flush()
    assert svc.stats()["device_dispatches"] == 1
    eng0 = KTrussEngine(graphs[0], chunk=64)
    ref = eng0.ktruss(4)
    res = f_kt.result()
    assert np.array_equal(res.alive, ref.alive)
    assert np.array_equal(res.support, ref.support)
    assert f_km.result() == KTrussEngine(graphs[1], chunk=64).kmax()
    edec = KTrussEngine(graphs[2], chunk=64).decompose()
    assert np.array_equal(f_dc.result().trussness, edec.trussness)
    ref3 = KTrussEngine(graphs[3], chunk=64).ktruss(3)
    assert np.array_equal(f_k3.result().alive, ref3.alive)
    # per-member stats: the single-level ktruss member peeled one level,
    # the decompose member peeled through its kmax.
    assert f_kt.stats.rounds == 1
    assert f_dc.stats.rounds == edec.levels


def test_peel_levels_consistent_with_executor():
    g = clustered(3, 12, 0.8, seed=0)
    eng = KTrussEngine(g, chunk=64)
    km, levels = eng.peel_levels()
    assert km == eng.kmax()
    dec = eng.decompose()
    # level k's alive mask is exactly the trussness >= k edge set.
    for res in levels:
        assert np.array_equal(res.alive, dec.trussness >= res.k), res.k


def test_executor_direct_single_level_matches_ktruss():
    g = erdos(70, 7.0, seed=1)
    eng = KTrussEngine(g, chunk=64)
    exe = PeelExecutor(
        mode="eager", backend="xla", window=eng.window, chunk=64
    )
    st = exe.peel(
        eng.problem,
        slot_ids=np.zeros(eng.problem.nnz_pad, np.int32),
        k0=[4],
        single_level=[True],
    )
    ref = eng.ktruss(4)
    assert np.array_equal(np.asarray(st.alive)[: g.nnz], ref.alive)
    assert np.array_equal(np.asarray(st.support)[: g.nnz], ref.support)
    assert int(st.iters[0]) == ref.iterations


# ------------------------------------------------------------------ #
# Slot-aligned packing
# ------------------------------------------------------------------ #
def test_aligned_pack_supports_match_members():
    gs = [erdos(50, 6.0, seed=0), clustered(2, 14, 0.7, seed=1), road(6, 0.2, seed=2)]
    w = max(8, -(-max(int(g.undirected_csr().max_degree()) for g in gs) // 8) * 8)
    pp = pack_problems(gs, slot_n=64, slot_nnz=256, slots=4, chunk=64, layout="aligned")
    assert pp.layout == "aligned"
    assert pp.problem.nnz_pad == 4 * 256
    # Member i's real lanes start exactly at its slot block.
    for i, (g, (a, b)) in enumerate(zip(gs, pp.edge_ranges)):
        assert a == i * 256 and b == a + g.nnz
    alive = jnp.asarray(pp.problem.colidx != 0)
    s = np.asarray(support_fine_eager(pp.problem, alive, window=w, chunk=64))
    for g, (a, b) in zip(gs, pp.edge_ranges):
        assert np.array_equal(s[a:b], support_numpy(g)), g.name
    # The empty 4th slot contributes nothing.
    assert not np.any(s[3 * 256 :])


def test_aligned_pack_validates_capacity():
    g = erdos(50, 6.0, seed=0)
    with pytest.raises(ValueError):
        pack_problems([g], slot_n=16, slot_nnz=256, chunk=64, layout="aligned")
    with pytest.raises(ValueError):
        pack_problems([g], slot_n=64, slot_nnz=64, chunk=64, layout="aligned")


# ------------------------------------------------------------------ #
# Targeted result(): resolving one future leaves other buckets queued
# ------------------------------------------------------------------ #
def test_result_does_not_drain_other_buckets():
    g1, g2 = erdos(80, 5.0, seed=0), road(8, 0.1, seed=1)
    assert bucket_for(g1, chunk=64) != bucket_for(g2, chunk=64)
    svc = TrussService(max_batch=2, chunk=64)
    f_other = svc.submit_ktruss(g1, 3)  # older, different bucket
    f_mine = svc.submit_ktruss(g2, 3)
    res = f_mine.result()
    assert f_mine.done() and res.k == 3
    assert not f_other.done()
    assert svc.stats()["pending"] == 1  # g1 still queued, untouched
    f_other.result()
    assert svc.stats()["pending"] == 0


# ------------------------------------------------------------------ #
# Pallas backend through the serving path (interpret mode on CPU)
# ------------------------------------------------------------------ #
def test_pallas_service_matches_xla_service():
    graphs = [erdos(40, 5.0, seed=0), clustered(2, 10, 0.7, seed=1)]
    results = {}
    for backend in ("xla", "pallas"):
        svc = TrussService(backend=backend, max_batch=2, chunk=64)
        f_dec = svc.submit_decompose(graphs[0])
        f_kt = svc.submit_ktruss(graphs[1], 3)
        svc.flush()
        st = svc.stats()
        assert st["device_dispatches"] == st["batches_run"]  # 1 per batch
        results[backend] = (f_dec.result(), f_kt.result())
    dec_x, kt_x = results["xla"]
    dec_p, kt_p = results["pallas"]
    assert np.array_equal(dec_p.trussness, dec_x.trussness)
    assert dec_p.kmax == dec_x.kmax and dec_p.levels == dec_x.levels
    assert np.array_equal(kt_p.alive, kt_x.alive)
    assert np.array_equal(kt_p.support, kt_x.support)


# ------------------------------------------------------------------ #
# Every support gives the eager XLA support's whole PeelState
# ------------------------------------------------------------------ #
_STATE_FIELDS = (
    "alive", "support", "trussness", "cur_k", "kmax",
    "levels", "iters", "done", "edges_alive",
)


def _packed_batch(graphs, chunk=64):
    buckets = [bucket_for(g, chunk=chunk) for g in graphs]
    n_pad = max(b.n_pad for b in buckets)
    nnz_pad = max(b.nnz_pad for b in buckets)
    window = max(b.window for b in buckets)
    slots = len(graphs)
    packed = pack_problems(
        graphs,
        slot_n=n_pad,
        slot_nnz=nnz_pad,
        slots=slots,
        chunk=chunk,
        layout="aligned",
    )
    slot_ids = np.repeat(np.arange(slots, dtype=np.int32), nnz_pad)
    return packed, slot_ids, window


def _assert_states_equal(st_a, st_b):
    for field in _STATE_FIELDS:
        a = np.asarray(getattr(st_a, field))
        b = np.asarray(getattr(st_b, field))
        assert np.array_equal(a, b), f"{field}: {a} != {b}"


@pytest.mark.parametrize("frozen_half", [False, True], ids=["plain", "frozen"])
@pytest.mark.parametrize(
    "support",
    [("fine", "owner", "xla"), ("coarse", "eager", "xla"), ("fine", "owner", "pallas")],
    ids=["fine-owner-xla", "coarse-eager-xla", "fine-owner-pallas"],
)
def test_supports_give_identical_peel_state(support, frozen_half):
    """Each support under the one peel program ends on exactly the state
    fine/eager/xla ends on — per-slot iteration and level trajectories
    included — from scratch and with half the real lanes frozen at the
    oracle's trussness (the streaming form)."""
    graphs = [rmat(5, 6, seed=1), barabasi(30, 3, seed=0)]
    packed, slot_ids, window = _packed_batch(graphs)
    p = packed.problem
    kwargs = dict(slot_ids=slot_ids, k0=np.full(packed.slots, 3, np.int32))
    if frozen_half:
        real = np.asarray(p.colidx) != 0
        frozen = real & (np.arange(real.shape[0]) % 2 == 0)
        frozen_truss = np.zeros(real.shape[0], np.int32)
        for g, (a, b) in zip(graphs, packed.edge_ranges):
            frozen_truss[a:b] = np.where(frozen[a:b], trussness_numpy(g), 0)
        kwargs.update(alive0=real & ~frozen, frozen=frozen, frozen_truss=frozen_truss)

    ref = PeelExecutor(
        granularity="fine", mode="eager", backend="xla", window=window, chunk=64
    ).peel(p, **kwargs)
    granularity, mode, backend = support
    st = PeelExecutor(
        granularity=granularity, mode=mode, backend=backend, window=window, chunk=64
    ).peel(p, **kwargs)
    _assert_states_equal(ref, st)
    truss = np.asarray(st.trussness)
    for g, (a, b) in zip(graphs, packed.edge_ranges):
        assert np.array_equal(truss[a:b], trussness_numpy(g)), g.name


# ------------------------------------------------------------------ #
# Sharded executor on 8 simulated host devices == unsharded
# ------------------------------------------------------------------ #
_SHARDED_SCRIPT = """
import numpy as np, jax
assert len(jax.devices()) == 8, jax.devices()
from repro.graphs import erdos
from repro.distributed import slot_mesh
from repro.service import TrussService, bucket_for

groups = {}
for s in range(64):
    g = erdos(40, 5.0, seed=s)
    groups.setdefault(bucket_for(g, chunk=64), []).append(g)
    if len(groups[bucket_for(g, chunk=64)]) == 8:
        graphs = groups[bucket_for(g, chunk=64)]
        break
svc_sharded = TrussService(max_batch=8, chunk=64, mesh=slot_mesh(8))
svc_plain = TrussService(max_batch=8, chunk=64)
fs = [svc_sharded.submit_decompose(g) for g in graphs]
fp = [svc_plain.submit_decompose(g) for g in graphs]
svc_sharded.flush(); svc_plain.flush()
assert svc_sharded.stats()["device_dispatches"] == 1
for g, a, b in zip(graphs, fs, fp):
    da, db = a.result(), b.result()
    assert np.array_equal(da.trussness, db.trussness), g.name
    assert da.kmax == db.kmax and da.levels == db.levels
print("SHARDED_OK")
"""


def test_sharded_peel_matches_unsharded_subprocess():
    """8 simulated host devices (fresh process: XLA_FLAGS must precede jax
    init); sharded batched decompose must equal unsharded bit-for-bit."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=8 " + env.get("XLA_FLAGS", "")
    ).strip()
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", _SHARDED_SCRIPT],
        env=env,
        capture_output=True,
        text=True,
        timeout=900,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "SHARDED_OK" in proc.stdout


# ------------------------------------------------------------------ #
# Per-chip support on a 4-device slot mesh == one chip, bit for bit
# ------------------------------------------------------------------ #
_PER_CHIP_SCRIPT = r"""
import json

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.reference import trussness_numpy
from repro.distributed import slot_mesh
from repro.distributed.ktruss import shard_peel_args
from repro.exec import PeelExecutor
from repro.graphs import from_edges, pack_problems, rmat
from repro.obs import MetricsRegistry, use_registry

assert len(jax.devices()) == 4, jax.devices()
CHUNK, SLOTS, SLOT_N = 16, 8, 32


def hub_graph(nnz, seed):
    # Hub 15 (0-based) links up to 16..31, which link nowhere further, and
    # down to 0..14, which also link to some of 16..31: the hub's row is the
    # last non-empty row, so it ends on the member's last lane.
    ups = [(15, a) for a in range(16, 32)]
    downs = [(x, 15) for x in range(15)]
    links = [(x, a) for x in range(15) for a in range(16, 32)]
    pick = np.random.default_rng(seed).permutation(len(links))[: nnz - 31]
    g = from_edges(32, np.array(ups + downs + [links[i] for i in pick]))
    assert g.nnz == nnz and g.row_of_edge()[-1] == 16
    return g


rmats = [rmat(5, 8, seed=s) for s in range(4)]
slot_nnz = -(-max(g.nnz for g in rmats) // CHUNK) * CHUNK
hubs = [hub_graph(slot_nnz, seed=s) for s in range(4)]
# Slots 1, 3, 5, 7 (each chip's last) hold a hub whose row ends on the
# chip's last lane.
full = [g for pair in zip(rmats, hubs) for g in pair]
window = 1 << int(max(g.undirected_csr().degrees().max() for g in full) - 1).bit_length()
mesh = slot_mesh(4)


def batch(graphs, frozen_every):
    pp = pack_problems(graphs, slot_n=SLOT_N, slot_nnz=slot_nnz, slots=SLOTS,
                       chunk=CHUNK, layout="aligned")
    real = np.zeros(SLOTS * slot_nnz, bool)
    truss = np.zeros(SLOTS * slot_nnz, np.int32)
    for g, (lo, hi) in zip(graphs, pp.edge_ranges):
        real[lo:hi] = True
        truss[lo:hi] = trussness_numpy(g)
    frozen = np.zeros_like(real)
    if frozen_every:
        frozen = real & (np.arange(real.size) % frozen_every == 0)
    return pp.problem, real & ~frozen, frozen, np.where(frozen, truss, 0)


CASES = {
    "ktruss3-frozen": ("eager", full, True, 5),
    "decompose-frozen": ("eager", full, False, 5),
    "ktruss3-empty-chips": ("eager", full[:2], True, 0),
    "decompose-empty-chips": ("eager", full[:2], False, 0),
    "owner-decompose-frozen": ("owner", full, False, 5),
}
executors = {
    mode: {
        "plain": PeelExecutor(mode=mode, window=window, chunk=CHUNK),
        "mesh": PeelExecutor(mode=mode, window=window, chunk=CHUNK, mesh=mesh),
    }
    for mode in ("eager", "owner")
}
out = {}
for name, (mode, graphs, single, frozen_every) in CASES.items():
    p, alive0, frozen, frozen_truss = batch(graphs, frozen_every)
    kw = dict(
        slot_ids=np.repeat(np.arange(SLOTS), slot_nnz), k0=np.full(SLOTS, 3),
        single_level=np.full(SLOTS, single), alive0=jnp.asarray(alive0),
        frozen=jnp.asarray(frozen), frozen_truss=jnp.asarray(frozen_truss),
    )
    states, counts = {}, {}
    for side, ex in executors[mode].items():
        reg = MetricsRegistry()
        with use_registry(reg):
            states[side] = ex.peel(p, **kw)
        counts[side] = [reg.value("peel_sharded_support_dispatches"),
                        reg.value("peel_dispatches")]
    # The support alone, on one chip and per chip, over the same lanes.
    eff = jnp.asarray(alive0 | frozen)
    plain_s = jax.jit(executors[mode]["plain"].support)(p, eff)
    sp, *_, s_eff, _, _ = shard_peel_args(mesh, p, kw["slot_ids"], kw["k0"], kw["single_level"],
                                          eff, kw["frozen"], kw["frozen_truss"])
    mesh_s = jax.jit(executors[mode]["mesh"].support)(sp, s_eff)
    a, b = states["mesh"], states["plain"]
    out[name] = {
        "fields_differ": [f for f in a._fields
                          if not np.array_equal(np.asarray(getattr(a, f)), np.asarray(getattr(b, f)))],
        "support_same": bool(np.array_equal(np.asarray(plain_s), np.asarray(mesh_s))),
        "support_nonzero": int((np.asarray(plain_s) > 0).sum()),
        "per_chip": executors[mode]["mesh"].support_per_chip,
        "counts": counts,
        "kmax": np.asarray(b.kmax).tolist(),
    }
print("PER_CHIP " + json.dumps(out))
"""

PER_CHIP_CASES = (
    "ktruss3-frozen",
    "decompose-frozen",
    "ktruss3-empty-chips",
    "decompose-empty-chips",
    "owner-decompose-frozen",
)


@pytest.fixture(scope="module")
def per_chip_runs():
    """The cases of ``_PER_CHIP_SCRIPT``, peeled once in a fresh process on
    4 simulated host devices (``XLA_FLAGS`` must precede JAX's start)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=4 " + env.get("XLA_FLAGS", "")
    ).strip()
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", _PER_CHIP_SCRIPT],
        env=env,
        capture_output=True,
        text=True,
        timeout=900,
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    [line] = [ln for ln in proc.stdout.splitlines() if ln.startswith("PER_CHIP ")]
    return json.loads(line.split(" ", 1)[1])


@pytest.mark.parametrize("case", PER_CHIP_CASES)
def test_per_chip_support_matches_one_chip(per_chip_runs, case):
    """The support run per chip over its own lanes (fine/xla eager), and the
    GSPMD support it falls back to (owner), give one chip's peel bit for
    bit: frozen lanes, hub rows ending on a chip's last lane, whole chips of
    empty slots."""
    run = per_chip_runs[case]
    assert run["fields_differ"] == []
    assert run["support_same"] and run["support_nonzero"] > 0
    assert run["per_chip"] == (not case.startswith("owner"))
    # One dispatch each side; the per-chip counter ticks only where it ran.
    sharded = 1 if run["per_chip"] else 0
    assert run["counts"] == {"mesh": [sharded, 1], "plain": [0, 1]}
    if "empty-chips" in case:
        assert run["kmax"][2:] == [0] * 6
