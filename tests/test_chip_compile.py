"""Compile the served path's kernels for a DESCRIBED v5e, without the chip.

The TPU's compiler is installed on CPU-only hosts and compiles for a
topology that is described, not attached
(``jax.experimental.topologies``), so these cases raise here what the
chip's compiler would raise there: an i64 operand Mosaic refuses, a
kernel that wants more scoped VMEM than it may have, a slice that does
not fit the tiling.  Interpret mode shows none of that.  Nothing runs —
a case that passes says the program BUILDS for the chip, never that it
is right or fast (``chip_smoke.py`` on the chip says that).

Every case runs under the x64 setting ``standalone.main`` leaves a
server process in, lowers shapes (never arrays: no device is attached
to hold one) and asserts a Pallas kernel is in the compiled program.

One file on purpose: only one process at a time may load the TPU
library, pytest-xdist hands a file to one worker, and the topology is
described inside a fixture — never at import — so every worker collects
the same tests and only the one that runs them loads the library.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from filodb_tpu.codecs.xorgrid import pack_vals
from filodb_tpu.memstore import devicestore
from filodb_tpu.memstore.devicestore import BLOCK_BUCKETS
from filodb_tpu.ops import grid
from filodb_tpu.ops.grid import MAX_GRID_ROWS, GridQuery, lane_tile, max_k_for

GSTEP = 15_000          # the 15 s scrape cadence of ROADMAP R1


@pytest.fixture(scope="module")
def topo():
    """The described chip, with JAX in a server process's settings and
    the persistent compilation cache off (a compile for a described
    device is written to the cache but cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    from filodb_tpu import standalone
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    x64 = jax.config.jax_enable_x64
    cache = jax.config.jax_enable_compilation_cache
    standalone.enable_server_x64()
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_x64", x64)
    jax.config.update("jax_enable_compilation_cache", cache)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_tpu(monkeypatch):
    """``jax.default_backend()`` still says cpu here, so the fused
    programs would take their portable branch: steer them in the test."""
    monkeypatch.setattr(grid, "on_tpu_backend", lambda: True)


def _abstract(tree, sharding):
    """Arrays (or shapes) -> ShapeDtypeStructs placed on the described
    device; ints and None pass through as the static/absent operands
    they are."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
        if hasattr(a, "shape") else a, tree)


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _compile(fn, sharding, *args, **kwargs):
    """Lower + compile a ``devicewatch.jit`` program for the described
    chip and require a Mosaic kernel in it."""
    jitted = getattr(fn, "_jitted", None) or jax.jit(fn)
    compiled = jitted.lower(*_abstract(args, sharding),
                            **_abstract(kwargs, sharding)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _query(mode: str, nsteps: int, k: int, op=None, dense=None) -> GridQuery:
    op = op or {"ts": "rate", "phase": "rate", "free": "sum"}[mode]
    if dense is None:
        dense = mode == "phase"
    q = GridQuery(nsteps, k, GSTEP, op=op, dense=dense, farg=0.75)
    phase = object() if mode == "phase" else None
    assert grid._mode_for(q, phase) == mode
    return q


def _series_args(mode: str, rows: int, ncols: int):
    """(ts, vals, steps0, phase) operand shapes of rate_grid per mode."""
    return (_sds((rows, ncols), jnp.int32) if mode == "ts" else None,
            _sds((rows, ncols), jnp.float32), _sds((), jnp.int32),
            _sds((ncols,), jnp.int32) if mode == "phase" else None)


def _rate_grid(one_chip, mode, q, rows, ncols, lanes):
    ts, vals, s0, phase = _series_args(mode, rows, ncols)
    return _compile(grid.rate_grid, one_chip, ts, vals, s0, q=q,
                    lanes=lanes, phase=phase)


@pytest.mark.parametrize("mode", ["ts", "phase", "free"])
def test_rate_grid_small(one_chip, mode):
    """A small panel: [64, 4096], T=56, K=5."""
    _rate_grid(one_chip, mode, _query(mode, 56, 5), 64, 4096, 1024)


# (mode, op, dense, K): per input-plane mode, the tallest grid
# supports_grid admits on a TPU (MAX_GRID_ROWS rows at the op's
# max_k_for) — for the mainstream op AND for the op with the largest
# VMEM footprint in that mode (deriv / mad: the two the 16 MiB default
# budget refuses; they set _MOSAIC_PARAMS).  Phase kernels are K-free,
# so their extremes are the most steps (K=2) and the widest window.
_TALLEST = [
    ("ts", "rate", False, None),
    ("ts", "deriv", False, None),
    ("free", "sum", False, None),
    ("free", "mad", True, None),
    ("phase", "rate", True, 2),
    ("phase", "rate", True, MAX_GRID_ROWS),
]


@pytest.mark.parametrize("mode,op,dense,k", _TALLEST)
def test_rate_grid_tallest_admitted(one_chip, as_tpu, mode, op, dense, k):
    kmax = max_k_for(op, dense)
    k = k or kmax
    nsteps = MAX_GRID_ROWS - k + 1
    # the planner's own bound: this shape is admitted, one more step or
    # one more bucket per window is not
    assert grid.supports_grid(k * GSTEP, GSTEP, GSTEP, nsteps, max_k=kmax)
    assert not grid.supports_grid(k * GSTEP, GSTEP, GSTEP, nsteps + 1,
                                  max_k=kmax)
    assert not grid.supports_grid((kmax + 1) * GSTEP, GSTEP, GSTEP, 1,
                                  max_k=kmax)
    ncols = 1024
    _rate_grid(one_chip, mode, _query(mode, nsteps, k, op, dense),
               MAX_GRID_ROWS, ncols, lane_tile(ncols, MAX_GRID_ROWS))


# the WIDEST tile at its tallest: lane_tile keeps 1024 lanes up to 256
# rows.  ts/rate K=20 is a 1 h rate(m[5m]) panel over 15 s scrapes —
# the shape the default VMEM budget refused (20.4 MiB of 16).
@pytest.mark.parametrize("mode,op,dense,k", [
    ("ts", "rate", False, 20),
    ("free", "mad", True, None),
    ("phase", "rate", True, 20),
])
def test_rate_grid_widest_tile(one_chip, mode, op, dense, k):
    rows, ncols = 256, 2048
    assert lane_tile(ncols, rows) == 1024 and lane_tile(ncols, rows + 1) < 1024
    k = k or max_k_for(op, dense)
    _rate_grid(one_chip, mode, _query(mode, rows - k + 1, k, op, dense),
               rows, ncols, 1024)


def _packed_block(ncols: int, stride: int = 1):
    """One compressed-resident block as the device store builds it
    (BLOCK_BUCKETS rows of 16-bit-class counters in one identity
    plane), reduced to shapes."""
    rng = np.random.default_rng(0)
    start = (2 ** 23 + 128 * rng.integers(0, 2 ** 15, ncols))
    inc = 128 * rng.integers(1, 8, (BLOCK_BUCKETS, ncols))
    vals = (start[None, :] + np.cumsum(inc, axis=0)).astype(np.float32)
    phase = np.repeat(rng.integers(1, GSTEP, ncols // stride),
                      stride).astype(np.int32)
    pk = pack_vals(vals, phase=phase, min_width=16, stride=stride)
    assert pk.planes["p16"].shape == (BLOCK_BUCKETS, ncols)
    return dict(pk.planes)


# row0 is a traced int32: the span's first row in the block, any of them
# (3 is not a sublane multiple), rotated to the top inside the kernel —
# one build serves every offset
ROW0 = _sds((), jnp.int32)


# (60, 20): a "last 15 minutes" panel at a 15 s step, 79 rows of a block
@pytest.mark.parametrize("nsteps,k", [(56, 5), (60, 20), (100, 20),
                                      (120, 5)])
@pytest.mark.parametrize("use_phase", [True, False],
                         ids=["phase", "free"])
def test_rate_grid_packed(one_chip, use_phase, nsteps, k):
    q = _query("phase" if use_phase else "free", nsteps, k)
    _compile(grid.rate_grid_packed, one_chip, _packed_block(4096),
             _sds((), jnp.int32), q=q, row0=ROW0, use_phase=use_phase)


def test_hist_grid_grouped_packed(one_chip):
    """64 buckets — the reference's histogram width (doc/compression.md)."""
    hb, groups = 64, 4
    _compile(grid.hist_grid_grouped_packed, one_chip,
             _packed_block(groups * 1024, stride=hb), _sds((), jnp.int32),
             q=_query("phase", 56, 5), hb=hb, group_lanes=1024, row0=ROW0)


def test_event_topk_grid_packed(one_chip):
    ncols, groups = 4096, 64
    _compile(grid.event_topk_grid_packed, one_chip, _packed_block(ncols),
             _sds((), jnp.int32), q=_query("free", 56, 5), k=10,
             garr=_sds((ncols,), jnp.int32), num_groups=groups,
             filt_packed=_packed_block(ncols), filt_op="gt",
             filt_thresh=_sds((), jnp.float32),
             filt_q=_query("free", 56, 5, op="last"), row0=ROW0)


def test_m4_grid(one_chip):
    """?downsample=<pixels>: a 1 h panel at the scrape step onto 100
    pixel columns."""
    _compile(grid.m4_grid, one_chip, _sds((240, 4096), jnp.float32),
             pixels=100)


# ---------------------------------------------------------------------
# the fused serving programs: what the server dispatches per query
# ---------------------------------------------------------------------

def _resident_parts(mode: str, nblocks: int, ncols: int):
    """(ts_parts, val_parts) the way _plan_locked hands them over:
    compressed blocks (XLA decode in the program); a ts plane only in
    ts mode, then as the uniform-phase descriptor it is elided to."""
    vals = tuple(_packed_block(ncols) for _ in range(nblocks))
    if mode != "ts":
        return (), vals
    ts = tuple({"base": (bi * BLOCK_BUCKETS - 1) * GSTEP, "g": GSTEP,
                "phase": _sds((ncols,), jnp.int32)}
               for bi in range(nblocks))
    return ts, vals


@pytest.mark.parametrize("prog,mode", [
    ("series", "ts"), ("series", "phase"), ("series", "free"),
    ("grouped", "ts"), ("grouped", "phase"), ("grouped", "free")])
def test_fused_program_multi_block(one_chip, as_tpu, prog, mode):
    """A 1 h panel over 15 s scrapes spans two 128-bucket blocks:
    decode + concat + slice + grid kernel (+ grouped reduce) as ONE
    program.  In ts mode the plane is rebuilt from base/g/phase with
    Python ints — under x64 that is where an int64 ts plane would come
    from if the kernels' operand contract slipped.  (Dense there: the
    K-unrolled non-dense kernel at this tile is
    test_rate_grid_widest_tile's.)"""
    ncols, nrows, k = 2048, 240, 20
    q = _query(mode, nrows - k + 1, k, dense=mode != "free")
    ts_parts, val_parts = _resident_parts(mode, 2, ncols)
    phase = _sds((ncols,), jnp.int32) if mode == "phase" else None
    kw = dict(q=q, lanes=lane_tile(ncols, nrows), nrows=nrows)
    row0, s0 = _sds((), jnp.int64), _sds((), jnp.int64)   # Python ints
    fn = devicestore._fused_progs()[prog]
    if prog == "series":
        _compile(fn, one_chip, ts_parts, val_parts, row0, s0, phase, **kw)
    else:
        _compile(fn, one_chip, ts_parts, val_parts, row0, s0,
                 _sds((ncols,), jnp.int32), phase, num_groups=8, op="sum",
                 **kw)


def test_fused_program_wide_mixed_classes_compiles_fast(one_chip, as_tpu):
    """10 240 lanes in two classes (16-bit + raw), one block raw and one
    compressed — what the first chip run served.  Its XLA decode used
    lax.associative_scan, and scan + inv gather cost the TPU compiler
    time quadratic in the lane count: 123 s here, 95-110 s a first
    query on the chip, hours at 102 400 lanes.  The shifted-XOR form
    compiles in ~2 s; the bound is loose for a loaded test host."""
    import time
    ncols, nrows, k = 10_240, 240, 20
    rng = np.random.default_rng(1)
    start = 2 ** 21 + rng.integers(0, 2 ** 21, ncols)
    vals = (start[None, :] + np.cumsum(
        rng.integers(0, 50, (BLOCK_BUCKETS, ncols)), axis=0)
    ).astype(np.float32)
    vals[:, ::16] = rng.random((BLOCK_BUCKETS, ncols // 16)) * 100
    planes = dict(pack_vals(vals, phase=np.ones(ncols, np.int32)).planes)
    assert planes["p16"].shape[1] and planes["raw"].shape[1]
    t0 = time.perf_counter()
    _compile(devicestore._fused_progs()["grouped"], one_chip, (),
             (_sds((BLOCK_BUCKETS, ncols), jnp.float32), planes),
             _sds((), jnp.int64), _sds((), jnp.int64),
             _sds((ncols,), jnp.int32), _sds((ncols,), jnp.int32),
             q=_query("phase", nrows - k + 1, k),
             lanes=lane_tile(ncols, nrows), nrows=nrows, num_groups=16,
             op="sum")
    assert time.perf_counter() - t0 < 60


@pytest.mark.parametrize("use_phase", [True, False],
                         ids=["phase", "free"])
@pytest.mark.parametrize("prog", ["series_packed", "grouped_packed"])
def test_fused_program_packed(one_chip, as_tpu, prog, use_phase):
    ncols = 4096
    q = _query("phase" if use_phase else "free", 100, 20)
    kw = dict(q=q, row0=ROW0, use_phase=use_phase)
    fn = devicestore._fused_progs()[prog]
    if prog == "series_packed":
        _compile(fn, one_chip, _packed_block(ncols), _sds((), jnp.int64),
                 **kw)
    else:
        _compile(fn, one_chip, _packed_block(ncols), _sds((), jnp.int64),
                 _sds((ncols,), jnp.int32), num_groups=8, op="sum", **kw)


@pytest.mark.parametrize("prog", ["series_batch", "grouped_batch"])
def test_fused_program_batched(one_chip, as_tpu, prog):
    """The fleet tier: pallas_call under vmap over the member axis."""
    ncols, nrows, k, members = 2048, 100, 20, 4
    q = _query("phase", nrows - k + 1, k)
    _ts, val_parts = _resident_parts("phase", 1, ncols)
    kw = dict(q=q, lanes=lane_tile(ncols, nrows), nrows=nrows)
    row0s, s0s = _sds((members,), jnp.int64), _sds((members,), jnp.int64)
    phase = _sds((ncols,), jnp.int32)
    fn = devicestore._fused_progs()[prog]
    if prog == "series_batch":
        _compile(fn, one_chip, (), val_parts, row0s, s0s, phase, **kw)
    else:
        _compile(fn, one_chip, (), val_parts, row0s, s0s,
                 _sds((ncols,), jnp.int32), phase, num_groups=8, op="sum",
                 **kw)


# ---------------------------------------------------------------------
# the live edge (PR 37): a span that ends in the OPEN block, and the
# program that appends to it
# ---------------------------------------------------------------------

def _live_parts(mode: str, ncols: int, segs: int):
    """(ts_parts, val_parts) of a span whose last block is the open one,
    the way _plan_locked hands them over.  Over three blocks: the
    cache's first block dense (its empty bucket 0 keeps it unpacked), a
    packed one, then the open block's dense planes; over two (what the
    edge changes to as the open block fills): a packed one and the open
    block.  A ts plane of its own for the open block alone (the frozen
    ones are rebuilt from a phase row), and none at all for an op that
    reads no timestamps."""
    dense = _sds((BLOCK_BUCKETS, ncols), jnp.float32)
    vals = ((dense, _packed_block(ncols), dense) if segs == 3
            else (_packed_block(ncols), dense))
    if mode != "ts":
        return (), vals
    ts = tuple({"base": (bi * BLOCK_BUCKETS - 1) * GSTEP, "g": GSTEP,
                "phase": _sds((ncols,), jnp.int32)}
               for bi in range(3 - segs, 2))
    return ts + (_sds((BLOCK_BUCKETS, ncols), jnp.int32),), vals


@pytest.mark.parametrize("segs", [3, 2])
@pytest.mark.parametrize("mode", ["ts", "free"])
@pytest.mark.parametrize("prog", ["series", "grouped", "series_batch",
                                  "grouped_batch"])
def test_fused_program_live_edge(one_chip, as_tpu, prog, mode, segs):
    """A 1 h panel whose end has moved into the open block: three
    segments (two once the open block has filled past the span's reach),
    a dense plane beside packed ones, no phase row (the phase proof
    leaves open blocks out, so ``rate`` streams the ts planes), solo and
    stacked: the shapes ``DeviceGridCache._rehearse`` compiles ahead."""
    ncols, nrows, k = 2048, 240, 20
    q = _query(mode, 23, k, dense=True)._replace(stride=10)
    assert (q.nsteps - 1) * q.stride + k == nrows
    ts_parts, val_parts = _live_parts(mode, ncols, segs)
    kw = dict(q=q, lanes=lane_tile(ncols, nrows), nrows=nrows)
    at = (_sds((), jnp.int64),) * 2 if "batch" not in prog \
        else (_sds((4,), jnp.int64),) * 2
    fn = devicestore._fused_progs()[prog]
    if prog.startswith("series"):
        _compile(fn, one_chip, ts_parts, val_parts, *at, None, **kw)
    else:
        _compile(fn, one_chip, ts_parts, val_parts, *at,
                 _sds((ncols,), jnp.int32), None, num_groups=8, op="sum",
                 **kw)


def test_tail_append_program(one_chip):
    """``devicestore.tail_append`` at the deployment's width: 102 400
    lanes, ``APPEND_CELLS`` cells a launch, the planes not donated (the
    result is a copy on the device).  Pure XLA: no kernel to look for."""
    ncols, cells = 102_400, devicestore.APPEND_CELLS
    jitted = _tail_append_jitted()
    compiled = jitted.lower(*_abstract((
        _sds((BLOCK_BUCKETS, ncols), jnp.int32),
        _sds((BLOCK_BUCKETS, ncols), jnp.float32),
        _sds((3, cells), jnp.int32), _sds((cells,), jnp.float32)),
        one_chip)).compile()
    text = compiled.as_text()
    assert "scatter" in text
    # two planes in, two out, nothing aliased onto its input
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >= 2 * BLOCK_BUCKETS * ncols * 4
    assert mem.alias_size_in_bytes == 0


def _tail_append_jitted():
    """The jitted append program, without launching it: ``_tail_append``
    builds it on its first call, so call it on tiny CPU planes once."""
    if devicestore._TAIL_APPEND_FN is None:
        devicestore._tail_append(
            jnp.zeros((BLOCK_BUCKETS, 8), jnp.int32),
            jnp.zeros((BLOCK_BUCKETS, 8), jnp.float32),
            jnp.full((3, devicestore.APPEND_CELLS), BLOCK_BUCKETS,
                     jnp.int32),
            jnp.zeros(devicestore.APPEND_CELLS, jnp.float32))
    return devicestore._TAIL_APPEND_FN._jitted


def test_fused_mesh_program_four_chips(topo, as_tpu):
    """The mesh fabric's fused program (scan -> window -> group reduce
    -> cross-shard psum -> present) on a Mesh of the four described
    chips, one shard slice per chip: what a four-chip host serves
    ``sum by (g)(rate(m[5m]))`` with."""
    from filodb_tpu.parallel import mesh as pmesh
    from filodb_tpu.parallel import meshgrid
    mesh = pmesh.make_mesh(list(topo.devices))
    ndev = mesh.devices.size
    assert ndev == 4
    ksub, nrows, lmax, k, groups = 1, 240, 2048, 20, 8
    q = _query("phase", nrows - k + 1, k)
    fn = meshgrid._grid_mesh_present_program(
        pmesh._mesh_key(mesh), q, "phase", ksub, nrows, lmax, groups,
        "sum", "sum")
    axes = meshgrid._AXES

    def on_mesh(shape, dtype, *rest):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, P(axes, *rest)))
    # the ts plane is a placeholder in phase mode: one row per slice
    compiled = fn._jitted.lower(
        on_mesh((ndev * ksub, 1, lmax), jnp.int32, None, None),
        on_mesh((ndev * ksub, nrows, lmax), jnp.float32, None, None),
        on_mesh((ndev * ksub, lmax), jnp.int32, None),
        on_mesh((ndev * ksub,), jnp.int32),
        on_mesh((ndev * ksub, lmax), jnp.int32, None)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text


# the width dev-4shard's four caches agree on (26 368 lanes a chip) and the
# rows a 23-step panel at a 150 s step covers: what dev4.mesh-wide launches
_DEV4_LANES, _DEV4_ROWS = 26_368, 240


def _dev4_query(op: str, dense: bool) -> GridQuery:
    return GridQuery(23, 20, GSTEP, op=op, dense=dense, stride=10)


def _on_mesh(mesh, shape, dtype, *rest):
    from filodb_tpu.parallel import meshgrid
    return jax.ShapeDtypeStruct(
        shape, dtype, sharding=NamedSharding(mesh, P(meshgrid._AXES, *rest)))


@pytest.mark.parametrize("mode,op", [("phase", "rate"), ("free", "sum")])
def test_fused_mesh_program_at_the_deployments_width(topo, as_tpu, mode, op):
    """``meshgrid.fused`` as ``dev4.mesh-wide`` launches it: a shard a
    chip at the caches' common width, the workspace-wide ``sum(rate)``
    (phase mode) and ``sum(sum_over_time)`` (no ts plane and no phase:
    ``free``)."""
    from filodb_tpu.parallel import mesh as pmesh
    from filodb_tpu.parallel import meshgrid
    mesh = pmesh.make_mesh(list(topo.devices))
    q = _dev4_query(op, dense=True)
    fn = meshgrid._grid_mesh_present_program(
        pmesh._mesh_key(mesh), q, mode, 1, _DEV4_ROWS, _DEV4_LANES, 1,
        "sum", "sum")
    compiled = fn._jitted.lower(
        _on_mesh(mesh, (4, 1, _DEV4_LANES), jnp.int32, None, None),
        _on_mesh(mesh, (4, _DEV4_ROWS, _DEV4_LANES), jnp.float32, None, None),
        _on_mesh(mesh, (4, _DEV4_LANES), jnp.int32, None),
        _on_mesh(mesh, (4,), jnp.int32),
        _on_mesh(mesh, (4, _DEV4_LANES), jnp.int32, None)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text


def test_mesh_members_program_four_chips(topo, as_tpu):
    """``meshgrid.members``, the exact quantile's launch: every chip
    steps all of its lanes, keeps the 128 its ``sel`` row names and
    all-gathers them, so what comes back is [4, T, 128] and never
    [4, 26 368, T]."""
    from filodb_tpu.parallel import mesh as pmesh
    from filodb_tpu.parallel import meshgrid
    mesh = pmesh.make_mesh(list(topo.devices))
    q = _dev4_query("last", dense=False)
    fn = meshgrid._grid_mesh_members_program(
        pmesh._mesh_key(mesh), q, "free", 1, _DEV4_ROWS, _DEV4_LANES, 128)
    compiled = fn._jitted.lower(
        _on_mesh(mesh, (4, 1, _DEV4_LANES), jnp.int32, None, None),
        _on_mesh(mesh, (4, _DEV4_ROWS, _DEV4_LANES), jnp.float32, None, None),
        _on_mesh(mesh, (4, _DEV4_LANES), jnp.int32, None),
        _on_mesh(mesh, (4,), jnp.int32),
        _on_mesh(mesh, (4, 128), jnp.int32, None)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-gather" in text
    out, = compiled.output_shardings if isinstance(
        compiled.output_shardings, (list, tuple)) \
        else (compiled.output_shardings,)
    assert out.is_fully_replicated
