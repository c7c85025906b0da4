"""Ahead-of-time compiles for a described TPU v5e, with no chip attached.

The TPU compiler is installed wherever ``libtpu`` is, and compiles for a
topology that is described rather than attached.  These tests compile the
peel programs the auto rule sends to the chip — the XLA peel in each
formulation and dataflow, one chip at a bucket of ``chip_smoke.py``, and
the slot-sharded peel over the four chips of a ``v5e:2x2`` — so a program
the chip's compiler refuses fails here, at no chip time.  Nothing runs:
results and times need the chip.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker imports
this file.  The persistent compile cache is off around these compiles — an
entry compiled for a described chip cannot be read back without one.
"""

import os
import re

import numpy as np
import pytest

import jax
from jax.sharding import Mesh, SingleDeviceSharding

from repro.distributed.ktruss import SLOT_AXIS, peel_arg_shardings
from repro.exec.peel import PeelExecutor, build_peel, make_problem_support, peel_arg_shapes

# Buckets (n_pad, nnz_pad, window) and slot counts of chip_smoke.py's
# phases: large-static rmat-9 (the auto rule picks fine/xla) and road-512
# (coarse/xla) on 2 slots; many-small on 8.
RMAT_BUCKET, ROAD_BUCKET, STATIC_SLOTS = (512, 4096, 256), (262144, 1048576, 8), 2
SMALL_BUCKET, SMALL_SLOTS = (512, 4096, 32), 8
# The benchmark's buckets: Kronecker scale 8 on 1 slot, scale 7 on 8; and
# scale 8 on 32 slots over the four chips of kron8-mesh4-serve.
KRON8_BUCKET, KRON7_BUCKET = (256, 4096, 256), (128, 1024, 128)
MESH_SLOTS = 32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe is a skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was_enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _placed(args, shardings):
    return jax.tree.map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        args,
        shardings,
    )


def _peel_program(granularity, mode, window):
    support = make_problem_support(
        granularity=granularity, mode=mode, backend="xla", window=window, chunk=256
    )
    return build_peel(support)


def _support_program(one_chip, bucket, slots):
    """The compiled text of the fine eager peel for ``slots`` slots of
    ``bucket`` on one described chip."""
    n_pad, nnz_pad, window = bucket
    args = peel_arg_shapes(n=slots * n_pad, nnz_pad=slots * nnz_pad, slots=slots)
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), args
    )
    return _peel_program("fine", "eager", window).lower(*args).compile().as_text()


@pytest.mark.parametrize(
    "granularity,mode,bucket",
    [
        ("fine", "eager", RMAT_BUCKET),
        ("fine", "owner", RMAT_BUCKET),
        ("coarse", "eager", ROAD_BUCKET),
    ],
    ids=["fine-eager", "fine-owner", "coarse"],
)
def test_xla_peel_compiles_for_one_v5e_chip(one_chip, granularity, mode, bucket):
    n_pad, nnz_pad, window = bucket
    args = peel_arg_shapes(
        n=STATIC_SLOTS * n_pad, nnz_pad=STATIC_SLOTS * nnz_pad, slots=STATIC_SLOTS
    )
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), args
    )
    compiled = _peel_program(granularity, mode, window).lower(*args).compile()
    assert "while" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2**30


@pytest.mark.parametrize(
    "bucket,slots", [(KRON8_BUCKET, 1), (KRON7_BUCKET, 8)], ids=["kron8", "kron7-b8"]
)
def test_fine_eager_support_intersects_without_search_gathers(one_chip, bucket, slots):
    # The window intersection is a broadcast compare: the only gathers left
    # in the support pass read the windows and the chunk's lanes, none is
    # the binary search's take_along_axis.
    text = _support_program(one_chip, bucket, slots)
    gathers = [
        line for line in text.splitlines() if " gather(" in line and "/support/" in line
    ]
    assert gathers  # scope names reach the compiled program
    assert not [line for line in gathers if "take_along_axis" in line]


def _support_scan_bounds(text):
    """Trip counts of the support pass's chunk loops in a compiled program:
    the constant each ``while/cond`` compare inside the ``support`` scope
    tests the chunk counter against."""
    consts = dict(re.findall(r"%(\S+) = s32\[\]\S* constant\((\d+)\)", text))
    cond = r'compare\(%\S+, %(\S+)\), direction=LT, metadata=\{op_name="[^"]*/support/[^"]*while/cond/'
    return [int(consts[name]) for name in re.findall(cond, text)]


def _without_source_locations(text):
    """A compiled program's text less what names the Python source that
    built it: the same program built at two call sites reads the same."""
    lines = (re.sub(r", metadata=\{[^}]*\}", "", line) for line in text.splitlines())
    return [
        line
        for line in lines
        if not re.match(r"\d+ |FileNames|FunctionNames|FileLocations|StackFrames", line)
    ]


def _sharded_peel(topo, bucket, slots):
    # The program the executor builds for a mesh session: the support pass
    # run per chip, the prune partitioned by GSPMD.
    mesh = Mesh(
        np.array(topo.devices),
        (SLOT_AXIS,),
        axis_types=(jax.sharding.AxisType.Auto,),
    )
    n_pad, nnz_pad, window = bucket
    executor = PeelExecutor(window=window, chunk=256, mesh=mesh)
    assert executor.support_per_chip
    executor.compile(n=slots * n_pad, nnz_pad=slots * nnz_pad, slots=slots)
    compiled = executor._peel
    # Slot blocks stay sharded: every edge-lane input keeps its "slots"
    # partitioning, and the devices meet only in reductions.
    (p, slot_ids, *_rest), _kwargs = compiled.input_shardings
    assert p.colidx.spec == jax.sharding.PartitionSpec(SLOT_AXIS)
    assert slot_ids.spec == jax.sharding.PartitionSpec(SLOT_AXIS)
    assert "all-gather" not in compiled.as_text()
    return compiled


@pytest.fixture(scope="module")
def mesh_cell_peel(topo):
    return _sharded_peel(topo, KRON8_BUCKET, MESH_SLOTS)


def test_sharded_peel_compiles_for_v5e_2x2(topo):
    _sharded_peel(topo, SMALL_BUCKET, SMALL_SLOTS)


def test_mesh_cell_peel_compiles_for_v5e_2x2(mesh_cell_peel):
    assert mesh_cell_peel.memory_analysis().temp_size_in_bytes < 16 * 2**30


def test_mesh_cell_support_scans_each_chips_own_chunks(mesh_cell_peel):
    # 32 slots of 4,096 lanes over four chips: each chip's chunk loop runs
    # over its own 8 slots' 128 chunks of 256 lanes, not the mesh's 512.
    _n_pad, nnz_pad, _window = KRON8_BUCKET
    chunks_per_chip = MESH_SLOTS // 4 * nnz_pad // 256
    assert _support_scan_bounds(mesh_cell_peel.as_text()) == [chunks_per_chip]


def test_one_chip_peel_is_the_plain_build(one_chip):
    # Without a mesh the executor compiles the plain build_peel of its
    # support: the one-chip cells run the program they ran before.
    n_pad, nnz_pad, window = KRON8_BUCKET
    args = peel_arg_shapes(n=n_pad, nnz_pad=nnz_pad, slots=1)
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), args
    )
    executor = PeelExecutor(window=window, chunk=256)
    assert not executor.support_per_chip
    text = executor._peel.lower(*args).compile().as_text()
    plain = _peel_program("fine", "eager", window).lower(*args).compile().as_text()
    assert _without_source_locations(text) == _without_source_locations(plain)
    assert _support_scan_bounds(text) == [nnz_pad // 256]


def _moves(text):
    """Each gather and scatter of a compiled program as (kind, elements
    moved, window): a gather's output size and its ``slice_sizes``, a
    scatter's update size and its ``update_window_dims``."""
    shapes = {
        name: [int(d) for d in dims.split(",") if d]
        for name, dims in re.findall(r"%(\S+) = \w+\[([\d,]*)\]", text)
    }
    moves = []
    for dims, sizes in re.findall(r"= \w+\[([\d,]*)\]\S* gather\(.*slice_sizes=\{([\d,]*)\}", text):
        out = [int(d) for d in dims.split(",") if d]
        moves.append(("gather", out, [int(s) for s in sizes.split(",")]))
    for updates, window in re.findall(
        r" scatter\(%[^,]+, %[^,]+, %([^,)]+)\), update_window_dims=\{([\d,]*)\}", text
    ):
        moves.append(("scatter", shapes[updates], [int(d) for d in window.split(",") if d]))
    return moves


@pytest.mark.parametrize("program", ["kron8", "kron7-b8", "mesh"])
def test_fine_eager_support_reads_windows_as_table_rows(one_chip, mesh_cell_peel, program):
    # Each chunk reads its two windows as two row gathers of (chunk, W)
    # from the per-vertex table and updates the support table by two row
    # scatter-adds.  No op of the program moves chunk×W single elements:
    # the element gathers and scatters left (the key table, the support
    # read back, the prune's per-slot sums) each move at most the chip's
    # nnz_pad lanes.  The program holds two loops, the peel's and the
    # support's chunk scan, so no dynamic-slice loop reads a window.
    if program == "mesh":
        bucket, slots = KRON8_BUCKET, MESH_SLOTS // 4  # a chip's own slots
        text = mesh_cell_peel.as_text()
    else:
        bucket, slots = {"kron8": (KRON8_BUCKET, 1), "kron7-b8": (KRON7_BUCKET, 8)}[program]
        text = _support_program(one_chip, bucket, slots)
    _n_pad, nnz_pad, window = bucket
    lanes = slots * nnz_pad
    chunk = 256
    assert lanes < chunk * window
    moves = _moves(text)
    rows = [m for m in moves if m[0] == "gather" and m[1] == [chunk, window]]
    assert [m[2] for m in rows] == [[1, window]] * 2
    row_scatters = [m for m in moves if m[0] == "scatter" and m[2] == [1]]
    assert [m[1] for m in row_scatters] == [[chunk, window]] * 2
    for kind, shape, window_dims in moves:
        element = set(window_dims) == {1} if kind == "gather" else not window_dims
        if element:
            assert int(np.prod(shape)) <= lanes, (kind, shape)
    assert len(re.findall(r" while\(", text)) == 2
