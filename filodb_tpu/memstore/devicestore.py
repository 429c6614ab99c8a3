"""Device-resident chunk store: the HBM arena the serving path reads from.

The reference serves queries from off-heap block memory with
reclaim-on-demand eviction (reference: memory/src/main/scala/filodb.memory/
BlockManager.scala:142 PageAlignedBlockManager, Block.scala:90; eviction
callbacks into TimeSeriesShard.scala:279-301).  The TPU equivalent keeps
frozen chunk data **on device** as time-bucketed grids so queries read HBM
directly instead of re-uploading numpy per query:

- Per (shard, schema, column) a :class:`DeviceGridCache` assigns each
  partition a fixed lane and materializes time **blocks** — device arrays
  ``[BLOCK_BUCKETS, lanes]`` covering ``BLOCK_BUCKETS`` consecutive
  buckets of width ``gstep``.  Blocks stay COMPRESSED in HBM when it
  pays (round 5): uniform-phase blocks elide the ts plane entirely
  (reconstructed on device from one phase row), and value planes pack
  into fixed-width XOR-residual classes decoded inside the serving
  program — the reference's serve-compressed-vectors-in-place trick
  (BlockManager.scala:142, doc/compression.md) restated with static
  shapes for XLA.
- Blocks are built once from the partitions' frozen chunks (host decode ->
  one ``device_put``) and then serve every later query from HBM; a repeat
  query performs **zero** host->device chunk transfer.
- Blocks are evicted oldest-first when the arena exceeds its byte budget
  (``StoreConfig.device_cache_bytes``) — reclaim-on-demand in time order,
  like the reference's time-ordered block lists.
- Chunk freezes invalidate overlapping blocks (the shard wires
  ``partition.on_freeze`` to :meth:`note_freeze`); the mutable write-buffer
  rows are served from an OPEN block: dense planes that live on the device
  and are appended to as containers arrive (a container's common series
  through :meth:`note_append_rows`, once; the rest through
  ``partition.on_append`` -> :meth:`note_append`; then
  ``devicestore.tail_append``), never rebuilt for an ingest epoch.

The grid layout contract matches :mod:`filodb_tpu.ops.grid`: row ``c``
holds the (single) sample with ``ts in (epoch0+(c-1)*gstep, epoch0+c*gstep]``.
Partitions whose samples violate the one-per-bucket invariant disable the
grid for this cache generation; queries then fall back to the general
:mod:`filodb_tpu.ops.windows` path, so the fast path is never wrong, only
absent.
"""

from __future__ import annotations

import collections
import itertools
import threading
from typing import NamedTuple, Optional, Sequence

import numpy as np

from filodb_tpu.memstore.gridshapes import pad_lanes
from filodb_tpu.ops.grid import (DENSE_ONLY_OPS, PHASE_OPS, TS_FREE_OPS,
                                 GridQuery, lane_tile, max_k_for,
                                 on_tpu_backend, phase_eligible,
                                 supports_grid)
from filodb_tpu.query.logical import RangeFunctionId as F
from filodb_tpu.utils import devicewatch
from filodb_tpu.utils.costmemo import CostMemo
from filodb_tpu.utils.devicewatch import FLIGHT, LEDGER
from filodb_tpu.utils.observability import TRACER

BLOCK_BUCKETS = 128
_I32_SPAN = 2**31 - 2
_NO_ROW_TS = -2**62     # an open block's lane that holds no row yet

# range functions the aligned grid can serve, mapped to the fused
# kernel op (ops/grid.py GridQuery.op); None = the bare instant
# selector's staleness lookback (last sample in the window)
_GRID_OPS = {
    F.RATE: "rate", F.INCREASE: "increase",
    F.SUM_OVER_TIME: "sum", F.COUNT_OVER_TIME: "count",
    F.AVG_OVER_TIME: "avg", F.MIN_OVER_TIME: "min",
    F.MAX_OVER_TIME: "max", F.LAST_OVER_TIME: "last",
    F.STDDEV_OVER_TIME: "stddev", F.STDVAR_OVER_TIME: "stdvar",
    F.CHANGES: "changes", F.RESETS: "resets",
    F.IRATE: "irate", F.IDELTA: "idelta",
    F.DERIV: "deriv", F.PREDICT_LINEAR: "predict_linear",
    F.Z_SCORE: "zscore",
    F.QUANTILE_OVER_TIME: "quantile", F.MAD_OVER_TIME: "mad",
    F.DELTA: "delta", F.TIMESTAMP: "timestamp",
    F.HOLT_WINTERS: "holt_winters",
    None: "last",
}

# timestamp() outputs epoch-relative seconds from the kernel (int32 grid
# timestamps); the serving path re-bases to absolute and excludes the op
# from the fused grouped reduce (summing absolute timestamps would need
# a count-scaled re-base)
_REBASE_OPS = {"timestamp"}

# grid ops taking scalar function arguments: op -> arity
# (GridQuery.farg / farg2)
_ARG_OPS = {"predict_linear": 1, "quantile": 1, "holt_winters": 2}

# the subset defined on first-class histogram columns (per-bucket
# semantics; matches the host path in query/rangefns.py _HIST_FNS)
_HIST_GRID_FNS = {F.RATE, F.INCREASE, F.SUM_OVER_TIME, None}


_ONEHOT_MAX_G = 2048  # one-hot matmul reduce beyond this costs too much VMEM

# ---------------------------------------------------------------------------
# compressed HBM residents (round 5, VERDICT r4 #4; fused in ISSUE 3)
#
# Grid blocks may keep their VALUE plane in XOR-class form and (for
# uniform-phase data) drop the ts plane entirely; both decode ON DEVICE
# inside the serving program (reference: queries read compressed
# BinaryVectors straight from block memory, BlockManager.scala:142,
# doc/compression.md:96-99).  The layout lives in codecs/xorgrid.py —
# the encode side guarantees the lane-block alignment and meta tiles
# the FUSED Pallas kernels (ops/grid.py rate_grid_packed) rely on, so
# eligible queries decode inside the grid kernel itself and HBM serves
# ~2.5 B/sample instead of 4; the pure-XLA decode below remains the
# path for multi-block spans, f64 (CPU) residents, and ts-streaming
# ops.  Incompressible planes stay raw; a block only compresses when
# it saves >= 25%.
# ---------------------------------------------------------------------------

# tests flip this to exercise the fused packed kernels on CPU CI
# (devicestore then passes interpret=True through to pallas); never set
# in production — on a TPU backend the kernels compile natively
_PACKED_INTERPRET = False
# tripped if the fused packed program ever fails to compile/run on this
# backend: serving falls back to the XLA decode path permanently (the
# fused kernel is an optimization, never a correctness dependency)
_PACKED_BROKEN = False


def _seg_vals_device(seg):
    """Traced: materialize one value-plane segment — raw array pass-
    through or on-device XOR-class decode."""
    if not isinstance(seg, dict):
        return seg
    import jax.numpy as jnp
    from jax import lax

    raw = seg["raw"]
    word = jnp.uint32 if raw.dtype.itemsize == 4 else jnp.uint64
    parts = []
    for w in (8, 16, 32):
        p = seg.get(f"p{w}")
        if p is None:
            continue
        parts.append(p.astype(word) << seg[f"z{w}"].astype(word)[None, :])
    parts.append(lax.bitcast_convert_type(raw, word))
    u = jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]
    # prefix-XOR down the bucket axis as log2(B) shifted XORs, the fused
    # kernels' formulation (ops/grid.py _decode_packed).  The same bits
    # as lax.associative_scan — but a scan followed by the inv gather
    # below costs the TPU compiler time quadratic in the lane count
    # (123 s at 10 240 lanes; this form 0.7 s)
    sh = 1
    while sh < u.shape[0]:
        u = u ^ jnp.pad(u[:-sh], ((sh, 0), (0, 0)))
        sh *= 2
    u = u ^ lax.bitcast_convert_type(seg["first"], word)[None, :]
    vals = lax.bitcast_convert_type(u, raw.dtype)
    return vals[:, seg["inv"]]


def _seg_ts_device(seg):
    """Traced: materialize one ts-plane segment — raw int32 array or the
    uniform-phase reconstruction ``(c-1)*g + phase`` (the block proved
    every lane uniform-phase at build time, so this is bit-exact for
    every cell the kernels read through the finite-value mask)."""
    if not isinstance(seg, dict):
        return seg
    import jax.numpy as jnp

    rows = jnp.arange(BLOCK_BUCKETS, dtype=jnp.int32)[:, None]
    return seg["base"] + rows * seg["g"] + seg["phase"][None, :]


def hist_slot_garr(garr: np.ndarray, lane_idx: np.ndarray,
                   gid_arr: np.ndarray, hb: int) -> None:
    """Fill ``garr`` in place with the histogram group-slot layout:
    series slot s, bucket j -> group slot gid*hb + j, so a plain
    segment reduce sums each bucket lane independently (the bucket-wise
    hist sum).  ONE definition — the single-device fused path and the
    mesh staging must never drift on this layout."""
    cols, slots = hist_slot_pairs(lane_idx, gid_arr, hb)
    garr[cols] = slots


def hist_slot_pairs(lane_idx: np.ndarray, gid_arr: np.ndarray,
                    hb: int) -> tuple:
    """The same layout as (column, group slot) pairs, series-major: what
    the mesh path keeps in place of a dense map (MeshShardPlan)."""
    buckets = np.arange(hb, dtype=np.int32)      # int32 slots stay int32
    return ((lane_idx[:, None] * hb + buckets).ravel(),
            (gid_arr[:, None] * hb + buckets).ravel())


def hist_planes_split(both, num_groups: int, hb: int):
    """[2, G*hb, T] sum+count planes -> ``(hist_sum [G, T, hb],
    count [G, T])`` (count from the +Inf total bucket).  np/jnp
    agnostic — ONE definition shared by the host present path below and
    the fused mesh histq program (parallel/meshgrid.py), so the
    on-device cluster-wide quantile and the scatter-gather oracle read
    bucket state through the same reshape."""
    G, T = num_groups, both.shape[-1]
    hist_sum = both[0].reshape(G, hb, T).transpose(0, 2, 1)
    count = both[1].reshape(G, hb, T)[:, -1, :]
    return hist_sum, count


def hist_state_from_planes(both: np.ndarray, num_groups: int, hb: int,
                           tops) -> dict:
    """[2, G*hb, T] sum+count planes -> the MomentAggregator hist state
    ({"hist_sum": [G, T, hb], "count": [G, T] from the total bucket},
    plus bucket_tops).  Shared by the single-device and mesh paths."""
    hist_sum, count = hist_planes_split(both, num_groups, hb)
    return {"hist_sum": hist_sum, "count": count, "bucket_tops": tops}


def _grouped_reduce_impl(stepped, garr, num_groups, op):
    """Device-side segment reduce of the grid kernel's [T, lanes] output:
    only [G, T] partials ever cross the host link.  ``garr`` maps lane ->
    group (num_groups = drop bucket for unrequested/padding lanes).

    For sum/count at modest G the reduce is a one-hot matmul so it runs
    on the MXU — TPU scatter-adds (segment_sum) serialize and dominate
    the served latency otherwise."""
    import jax
    import jax.numpy as jnp

    from filodb_tpu.ops import aggregate as segops

    v = stepped.T                                # [lanes, T]
    G = num_groups
    if op in ("sum", "avg", "count", "moments"):
        fin = jnp.isfinite(v)
        vz = jnp.where(fin, v, 0.0)
        fz = fin.astype(v.dtype)
        planes = [vz, fz]
        if op == "moments":                      # stddev/stdvar partials
            planes.append(vz * vz)
        if G + 1 <= _ONEHOT_MAX_G:
            onehot = (garr[:, None] ==
                      jnp.arange(G, dtype=garr.dtype)[None, :]
                      ).astype(v.dtype)          # [lanes, G]
            # HIGHEST precision: the TPU default truncates f32 matmul
            # inputs to bf16, which would make fused sums diverge from
            # the host segment-sum path by up to ~0.4%
            hp = jax.lax.Precision.HIGHEST
            outs = [jnp.matmul(onehot.T, p, precision=hp)  # MXU: [G, T]
                    for p in planes]
        else:
            outs = [jax.ops.segment_sum(p, garr, G + 1)[:G]
                    for p in planes]
        return jnp.stack(outs)                   # one readback downstream
    if op == "min":
        return segops.seg_min(v, garr, G + 1)[:G]
    if op == "max":
        return segops.seg_max(v, garr, G + 1)[:G]
    raise ValueError(f"unsupported grouped op {op}")


_FUSED_PROGS: dict = {}


def _fused_progs():
    """The one-dispatch query programs, jitted lazily.  The whole
    serving pipeline — block decode + concat, row slice, grid kernel,
    segment reduce — is ONE program per query: eager slices + two jit
    calls would cost 4-6 dispatches and as many host round-trips."""
    if _FUSED_PROGS:
        return _FUSED_PROGS
    import functools

    import jax
    import jax.numpy as jnp
    from jax import lax

    from filodb_tpu.ops.grid import (rate_grid_auto, rate_grid_batch_impl,
                                     rate_grid_packed)

    # jax.named_scope on the programs' stages (decode, window, reduce):
    # names on the HLO's op_name metadata only, nothing computed changes
    def _concat(parts, decode):
        if not parts:
            return None    # phase mode: no ts plane in the program
        with jax.named_scope("decode"):
            segs = [decode(s) for s in parts]
            return segs[0] if len(segs) == 1 \
                else jnp.concatenate(segs, axis=0)

    def _window(fn, *a, **kw):
        with jax.named_scope("window"):
            return fn(*a, **kw)

    def _reduce(stepped, garr, num_groups, op):
        with jax.named_scope("reduce"):
            return _grouped_reduce_impl(stepped, garr, num_groups, op)

    def _sliced(parts, row0, nrows, decode):
        all_ = _concat(parts, decode)
        if all_ is None:
            return None
        return lax.dynamic_slice_in_dim(all_, row0, nrows, axis=0)

    @functools.partial(devicewatch.jit, program="devicestore.series",
                       static_argnames=("q", "lanes", "nrows"))
    def series_prog(ts_parts, val_parts, row0, steps0, phase=None, *,
                    q, lanes, nrows):
        ts_sl = _sliced(ts_parts, row0, nrows, _seg_ts_device)
        val_sl = _sliced(val_parts, row0, nrows, _seg_vals_device)
        return _window(rate_grid_auto, ts_sl, val_sl, steps0, q, lanes,
                       phase=phase)

    @functools.partial(devicewatch.jit, program="devicestore.grouped",
                       static_argnames=("q", "lanes", "nrows",
                                        "num_groups", "op"))
    def grouped_prog(ts_parts, val_parts, row0, steps0, garr, phase=None,
                     *, q, lanes, nrows, num_groups, op):
        ts_sl = _sliced(ts_parts, row0, nrows, _seg_ts_device)
        val_sl = _sliced(val_parts, row0, nrows, _seg_vals_device)
        stepped = _window(rate_grid_auto, ts_sl, val_sl, steps0, q, lanes,
                          phase=phase)
        return _reduce(stepped, garr, num_groups, op)

    # fused compressed-resident programs (ISSUE 3 tentpole): the XOR-
    # class decode runs INSIDE the grid kernel, so HBM serves the
    # packed ~2.5 B/sample planes — no decoded plane is ever written.
    # row0, the span's first row in the block, is a traced int32 like
    # steps0 (the kernel rotates it to the top): one executable serves
    # every end of a panel.  Outputs are in PACKED lane order.
    @functools.partial(devicewatch.jit,
                       program="devicestore.series_packed",
                       static_argnames=("q", "use_phase", "interpret"))
    def series_prog_packed(packed, steps0, *, q, row0, use_phase,
                           interpret=False):
        return _window(rate_grid_packed, packed, steps0, q, row0=row0,
                       interpret=interpret, use_phase=use_phase)

    @functools.partial(devicewatch.jit,
                       program="devicestore.grouped_packed",
                       static_argnames=("q", "use_phase", "num_groups",
                                        "op", "interpret"))
    def grouped_prog_packed(packed, steps0, garr, *, q, row0, use_phase,
                            num_groups, op, interpret=False):
        stepped = _window(rate_grid_packed, packed, steps0, q, row0=row0,
                          interpret=interpret, use_phase=use_phase)
        return _reduce(stepped, garr, num_groups, op)

    # fleet-batched programs (ISSUE 20): B shape-compatible queries
    # against the SAME resident planes — decode + concat happen ONCE,
    # then the per-member row slice and grid kernel run vmapped over
    # the leading member axis, so a whole co-arrival group costs one
    # launch and one stacked readback instead of B of each.
    @functools.partial(devicewatch.jit,
                       program="devicestore.series_batch",
                       static_argnames=("q", "lanes", "nrows"))
    def series_batch_prog(ts_parts, val_parts, row0s, steps0s,
                          phase=None, *, q, lanes, nrows):
        ts_all = _concat(ts_parts, _seg_ts_device)
        val_all = _concat(val_parts, _seg_vals_device)
        ts_b = None if ts_all is None else jax.vmap(
            lambda r: lax.dynamic_slice_in_dim(ts_all, r, nrows,
                                               axis=0))(row0s)
        val_b = jax.vmap(
            lambda r: lax.dynamic_slice_in_dim(val_all, r, nrows,
                                               axis=0))(row0s)
        return _window(rate_grid_batch_impl, ts_b, val_b, steps0s, q,
                       lanes, phase=phase)

    @functools.partial(devicewatch.jit,
                       program="devicestore.grouped_batch",
                       static_argnames=("q", "lanes", "nrows",
                                        "num_groups", "op"))
    def grouped_batch_prog(ts_parts, val_parts, row0s, steps0s, garr,
                           phase=None, *, q, lanes, nrows, num_groups,
                           op):
        ts_all = _concat(ts_parts, _seg_ts_device)
        val_all = _concat(val_parts, _seg_vals_device)

        def one(r, s):
            ts_sl = None if ts_all is None else \
                lax.dynamic_slice_in_dim(ts_all, r, nrows, axis=0)
            val_sl = lax.dynamic_slice_in_dim(val_all, r, nrows, axis=0)
            stepped = _window(rate_grid_auto, ts_sl, val_sl, s, q, lanes,
                              phase=phase)
            return _reduce(stepped, garr, num_groups, op)
        return jax.vmap(one)(row0s, steps0s)

    def staged(prog):
        """``prog`` as the ``grid.dispatch`` stage: the call until it
        returns is the host work inside the jit call (operand handling,
        enqueue).  On a launch the kernel timer samples (1 in 64) the
        wrapper waits for the device inside it too."""
        name = getattr(prog, "_program", "")

        @functools.wraps(prog)       # keeps _program and _jitted
        def launch(*a, **kw):
            with TRACER.stage("grid.dispatch", program=name):
                return prog(*a, **kw)
        return launch

    def staged_packed(prog):
        """A packed program as the ``grid.dispatch`` stage with the
        ``grid.packed`` stage inside it over the same call (tags
        ``program``, ``row0``): its count is the requests the packed
        kernels served, its wall the compile where one falls on a
        request.  ``row0`` goes in as an int32, one signature for every
        offset."""
        name = getattr(prog, "_program", "")

        @functools.wraps(prog)
        def launch(*a, row0, **kw):
            with TRACER.stage("grid.dispatch", leaf=False, program=name), \
                    TRACER.stage("grid.packed", program=name,
                                 row0=int(row0)):
                return prog(*a, row0=np.int32(row0), **kw)
        return launch

    # published whole: a request that arrives while the first one is
    # still building sees no program or all six, never a part of them
    # (entry by entry, it met a non-empty dict without "grouped_batch")
    _FUSED_PROGS.update({
        "series": staged(series_prog),
        "grouped": staged(grouped_prog),
        "series_packed": staged_packed(series_prog_packed),
        "grouped_packed": staged_packed(grouped_prog_packed),
        "series_batch": staged(series_batch_prog),
        "grouped_batch": staged(grouped_batch_prog)})
    return _FUSED_PROGS


def _other_stack_sizes(row0s, steps0s, sizes):
    """What a co-arrival group's launch is followed by where it compiled
    (its program holds more executables than before: this size is the
    first of its plan shape to stack): the ``(row0s, steps0s)`` of one
    launch at each OTHER stack size, over copies of the group's first
    member.  A shape that stacks at all soon stacks at every size, and a
    size first met later would compile then, with a whole group waiting
    on it.  What is loaded then depends on which shapes have stacked,
    not on which sizes happened to meet first."""
    for k in sizes:
        if k != len(row0s):
            yield np.full(k, row0s[0]), np.full(k, steps0s[0])


def _fetch(out, dtype=None) -> np.ndarray:
    """Wait for a launch's result and copy it to the host, as the
    ``grid.device_wait`` and ``grid.readback`` stages.  ``np.asarray``
    blocks on the device anyway: the explicit wait adds no sync, it
    only tells the device's time from the copy's."""
    import jax
    with TRACER.stage("grid.device_wait"):
        jax.block_until_ready(out)
    with TRACER.stage("grid.readback") as sp:
        host = np.asarray(out, dtype=dtype)
        sp.tag(bytes=int(host.nbytes))
    return host


def _run_packed(dispatch):
    """Run a fused packed-kernel dispatch; on the FIRST failure (a
    backend whose Mosaic build rejects the decode ops) trip the
    process-wide breaker and return None so the caller falls back to
    the XLA decode path — the fused kernel is an optimization, never a
    correctness dependency."""
    global _PACKED_BROKEN
    if _PACKED_BROKEN:
        # memoized plans keep their .packed field after the breaker
        # trips; never re-attempt the failing (uncached) Pallas build
        return None
    try:
        return dispatch()
    except Exception as e:
        import logging
        _PACKED_BROKEN = True
        FLIGHT.record("breaker.trip", breaker="packed_kernel",
                      error=repr(e)[:200])
        logging.getLogger(__name__).exception(
            "fused packed grid kernel failed; falling back to the XLA "
            "decode path for this process")
        return None


_HBM_METRIC = None


def _hbm_metric():
    global _HBM_METRIC
    if _HBM_METRIC is None:
        from filodb_tpu.utils.observability import query_metrics
        _HBM_METRIC = query_metrics()["hbm_read_bytes"]
    return _HBM_METRIC


def _note_hbm(plan: "_GridPlan") -> None:
    """Account the serving program's HBM reads by resident format:
    the filodb_query_hbm_read_bytes_total counter (format label) and
    the active query's QueryStats.hbm_read_bytes buckets — so the
    format actually serving traffic is observable (ISSUE 3; the
    compressed-hist bucket-plane format is ISSUE 14)."""
    if not (plan.hbm_dense or plan.hbm_comp or plan.hbm_comp_hist):
        return
    m = _hbm_metric()
    if plan.hbm_dense:
        m.inc(plan.hbm_dense, format="dense")
    if plan.hbm_comp:
        m.inc(plan.hbm_comp, format="compressed")
    if plan.hbm_comp_hist:
        m.inc(plan.hbm_comp_hist, format="compressed-hist")
    from filodb_tpu.query.exec import active_exec_ctx
    ctx = active_exec_ctx()
    if ctx is not None:
        ctx.note_counts(hbm_dense=plan.hbm_dense,
                        hbm_compressed=plan.hbm_comp,
                        hbm_hist=plan.hbm_comp_hist)


def _note_kernel_bytes(prog_fn, plan: "_GridPlan") -> None:
    """Kernel flight deck (ISSUE 15): attribute the plan's HBM reads to
    the fused program that actually dispatched — the numerator of the
    per-program live achieved-bytes/s join on /admin/kernels.  The
    program name comes off the wrapped callable itself
    (``devicewatch.jit`` stamps ``_program``), so a rename at the jit
    declaration can never decouple the bytes/launches join."""
    program = getattr(prog_fn, "_program", None)
    if program:
        devicewatch.KERNEL_TIMER.note_bytes(
            program, plan.hbm_dense + plan.hbm_comp + plan.hbm_comp_hist)


class _GridPlan(NamedTuple):
    """Everything needed to dispatch one fused serving program."""

    ts_parts: tuple       # device arrays, one per covered block; () when
                          # the program needs no ts plane (phase mode)
    val_parts: tuple
    row0: int             # first slice row in the concatenated blocks
    steps0_rel: int       # first window end, epoch-relative ms
    q: "GridQuery"
    lane_mult: int
    nrows: int
    ncols: int
    lane_idx: np.ndarray  # requested pid -> lane slot, in request order
    phase: object = None  # [ncols] int32 device array (uniform-phase mode)
    segs: tuple = ()      # the covered _Block objects (mesh staging)
    # fused compressed-resident dispatch (ISSUE 3): when set, the scan
    # runs the packed kernels on this single block's class planes —
    # decode happens inside the kernel, output in packed lane order
    packed: object = None          # the block's XOR-class plane dict
    packed_row0: int = 0           # the span's first row in the block
    packed_use_phase: bool = False
    packed_inv: object = None      # np [ncols] orig lane -> packed pos
    # logical HBM bytes the serving program reads, by resident format
    # (QueryStats.hbm_read_bytes; approximate: whole covered planes).
    # Histogram caches account their packed planes under the dedicated
    # "compressed-hist" format (ISSUE 14) so the bucket-plane substrate
    # is observable separately from scalar compressed residents.
    hbm_dense: int = 0
    hbm_comp: int = 0
    hbm_comp_hist: int = 0
    # the MeshShardPlans made of this plan, by grouping (mesh_plan): they
    # live and die with the plan, so whatever retires the plan (version,
    # ingest and removal epochs in its memo key; freeze, quarantine,
    # repin, a lane-width change clearing the memo) retires them too
    mesh: Optional[dict] = None


class MeshShardPlan(NamedTuple):
    """One shard's device-resident contribution to a mesh grid query:
    the staged planes, and the lanes the query asked of them as (column,
    group slot) pairs.  Nothing here is as wide as the lanes RESIDENT:
    the fabric scatters the pairs into its ``[Kp, lmax]`` rows itself
    (parallel/meshgrid.py ``_prepare``)."""

    ts: object            # [nrows, ncols] int32, on this shard's device
    vals: object          # [nrows, ncols] f32/f64, same device
    phase: object         # [ncols] int32 device array or None
    cols: np.ndarray      # [n] the columns asked, ascending
    slots: np.ndarray     # [n] int32 group slot of each (hist: slot =
    #                       gid*hb + bucket)
    rows_fp: tuple        # content fingerprint of (cols, slots), made
    #                       once with the plan: the fabric's rows memo
    #                       keys on it and hashes no row a request
    q: "GridQuery"
    steps0_rel: int
    ncols: int
    device: object
    hb: int = 0           # bucket lanes per series (0 = scalar column)
    bucket_tops: object = None     # [hb] np array (hist only)
    part_ids: object = None        # the lookup result the columns came
    order: object = None           # from, and cols[i]'s place in it
    #                                (None: in request order): lets the
    #                                k-slot mesh path resolve a selected
    #                                lane back to its series (scalar
    #                                columns only)

    def pid_of_lane(self, lane: int) -> int:
        """The partition id a lane was asked for, -1 where the query
        asked for none there (or the column is a histogram's)."""
        if self.hb or self.part_ids is None:
            return -1
        i = int(np.searchsorted(self.cols, lane))
        if i >= len(self.cols) or self.cols[i] != lane:
            return -1
        return int(self.part_ids[i if self.order is None
                                 else self.order[i]])


def _mesh_lanes(plan: "_GridPlan", group_ids, hb: int) -> tuple:
    """(cols ascending, their group slots, the columns' places in the
    request or None where it was in lane order) of one mesh plan: every
    lane-wide array a fabric request needs from a shard, made once a
    (plan, grouping) and outside the grid lock (it reads the plan's
    ``lane_idx`` and the caller's group ids, nothing the lock guards).
    ``group_ids`` is one int where every series falls into one group."""
    lane_idx = plan.lane_idx
    n = len(lane_idx)
    if isinstance(group_ids, (int, np.integer)):
        gids = np.full(n, group_ids, dtype=np.int32)
    else:
        gids = np.asarray(group_ids, dtype=np.int32)
    if hb:
        lane_idx, gids = hist_slot_pairs(lane_idx, gids, hb)
    if n < 2 or bool((lane_idx[1:] > lane_idx[:-1]).all()):
        return lane_idx, gids, None
    order = np.argsort(lane_idx, kind="stable")
    return lane_idx[order], gids[order], order


_MESH_STAGE_FN = None


def _mesh_stage(ts_parts, val_parts: tuple, row0: int, nrows: int):
    """Device-side block concat + row slice for the mesh path: inputs
    are committed to the shard's device, so the outputs stay there (a
    pure HBM->HBM copy, no host transfer).  Jitted per shape.

    ``ts_parts=None`` (uniform-phase plans, ISSUE 3) stages only the
    value plane — the mesh program's phase mode reconstructs timestamp
    geometry from the per-lane phase row, so no [nrows, ncols] ts plane
    is ever materialized or assembled for those queries (half the
    staged resident bytes)."""
    global _MESH_STAGE_FN
    if _MESH_STAGE_FN is None:
        import functools

        import jax.numpy as jnp
        from jax import lax

        @functools.partial(devicewatch.jit,
                           program="devicestore.mesh_stage",
                           static_argnames=("nrows",))
        def stage(ts_parts, val_parts, row0, *, nrows):
            val_segs = [_seg_vals_device(s) for s in val_parts]
            val_all = val_segs[0] if len(val_segs) == 1 \
                else jnp.concatenate(val_segs, axis=0)
            val_sl = lax.dynamic_slice_in_dim(val_all, row0, nrows, axis=0)
            if ts_parts is None:
                return None, val_sl
            ts_segs = [_seg_ts_device(s) for s in ts_parts]
            ts_all = ts_segs[0] if len(ts_segs) == 1 \
                else jnp.concatenate(ts_segs, axis=0)
            return (lax.dynamic_slice_in_dim(ts_all, row0, nrows, axis=0),
                    val_sl)
        _MESH_STAGE_FN = stage
    return _MESH_STAGE_FN(ts_parts, val_parts, row0, nrows=nrows)


# cells one launch of the append program writes: ONE shape whatever a
# container held, so that no append compiles (a container a second of a
# 102 400-series shard scraped every 15 s is 6 827 cells; more go in turns)
APPEND_CELLS = 8192
_TAIL_APPEND_FN = None


def _tail_append(ts_plane, val_plane, idx, vals):
    """An open block's planes with ``APPEND_CELLS`` cells written:
    ``idx`` ``[3, APPEND_CELLS]`` int32 holds each cell's row, column
    and epoch-relative timestamp, ``vals`` its value; a row index of
    ``BLOCK_BUCKETS`` pads (dropped).  The planes are NOT donated: a plan
    that was handed them dispatches outside the grid lock and may still
    be reading them, so the write lands in a copy made on the device
    (HBM to HBM) and only the cells cross the host link.  A helper: it
    answers no request (like ``devicestore.mesh_stage``)."""
    global _TAIL_APPEND_FN
    if _TAIL_APPEND_FN is None:
        import functools

        @functools.partial(devicewatch.jit,
                           program="devicestore.tail_append")
        def append(ts_plane, val_plane, idx, vals):
            rows, cols = idx[0], idx[1]
            return (ts_plane.at[rows, cols].set(idx[2], mode="drop"),
                    val_plane.at[rows, cols].set(vals, mode="drop"))
        _TAIL_APPEND_FN = append
    return _TAIL_APPEND_FN(ts_plane, val_plane, idx, vals)


def _rehearse_call(call: tuple, stack) -> None:
    """Launch one remembered program call over an open block's planes so
    that it is compiled (``DeviceGridCache._rehearse``): the solo program
    where ``stack`` is None, else its stacked form at that stack size.
    The arguments mirror the serving calls' to the letter (a ``None``
    given by position and one left to its default are two programs), the
    ``grid.dispatch`` stage is left out (a compile is no dispatch), and
    the result is dropped."""
    (kind, ts_parts, val_parts, row0, steps0, kw, num_groups, op,
     width) = call
    try:
        import jax
        extra, kw = (), dict(kw)
        if kind == "grouped":
            extra = (np.full(width, num_groups, dtype=np.int32),)
            kw.update(num_groups=num_groups, op=op)
        if stack is None:
            prog = _fused_progs()[kind].__wrapped__
        else:
            prog = _fused_progs()[kind + "_batch"].__wrapped__
            row0, steps0 = np.full(stack, row0), np.full(stack, steps0)
        jax.block_until_ready(prog(ts_parts, val_parts, row0, steps0,
                                   *extra, None, **kw))
    except Exception:  # noqa: BLE001 — the request compiles instead
        import logging
        logging.getLogger(__name__).exception(
            "rehearsing %s for an open block failed", kind)


_REHEARSERS = None


def _rehearsers():
    """The threads that compile an open block's programs before a request
    needs them.  A ``concurrent.futures`` pool: the interpreter joins its
    threads at exit, so none is inside the compiler when it goes."""
    global _REHEARSERS
    if _REHEARSERS is None:
        from concurrent.futures import ThreadPoolExecutor
        _REHEARSERS = ThreadPoolExecutor(4, thread_name_prefix="grid-rehearse")
    return _REHEARSERS


def _ids_fingerprint(part_ids) -> int:
    """Content hash guarding the id()-keyed prep cache against address
    reuse and keying the big-K deny set.  Position-dependent mix over
    EVERY id (vectorized: ~1ms/1M ids, small next to the query it
    gates) — a sampled fingerprint could let one lookup result's
    denial suppress the dense fast path for an unrelated id list of
    the same length (ADVICE r2)."""
    n = len(part_ids)
    ids = np.asarray(part_ids, dtype=np.uint64)
    with np.errstate(over="ignore"):
        mixed = (ids + np.arange(1, n + 1, dtype=np.uint64)) \
            * np.uint64(0x9E3779B97F4A7C15)
    return n * 1_000_003 + int(np.bitwise_xor.reduce(mixed))


class _Block:
    """One resident time block: device arrays [BLOCK_BUCKETS, lanes].

    ``fmin/fmax/fcnt`` (host numpy, per lane) record the filled-bucket
    range so queries can prove the dense-lane contract (ops/grid.py
    GridQuery.dense) without touching device data: a lane is
    *contiguous* iff fcnt == fmax - fmin + 1, dense over local rows
    [a, b] iff contiguous and fmin <= a <= b <= fmax, and empty over
    [a, b] iff fcnt == 0 or fmax < a or fmin > b.

    ``pmin/pmax`` (host numpy, per lane) record the within-bucket scrape
    offset range (``ts - bucket_start``, in (0, gstep]) of the lane's
    filled cells: a lane with ``pmin == pmax`` in every covered block is
    UNIFORM-PHASE and rate/increase/delta queries reconstruct its
    timestamps from one phase scalar — the ts plane is never streamed
    (ops/grid.py PHASE_OPS).

    An OPEN block (``hi_ts`` set: it holds write-buffer rows) keeps dense
    planes that ``DeviceGridCache._append_cells`` replaces as rows arrive,
    the fill and phase ranges moving with them; ``hi_ts`` (host int64, per
    lane) is the newest timestamp staged a lane, so a row that a build
    read from a write buffer and the ingest hook also queued lands once;
    ``gen`` counts the appends (what is staged FROM the planes keys on
    it); ``later`` the rehearsals that wait for a row (``_rehearse``)."""

    __slots__ = ("ts", "vals", "lanes", "nbytes", "last_used",
                 "fmin", "fmax", "fcnt", "pmin", "pmax", "staged_hi",
                 "ts_desc", "width", "pack_inv", "hi_ts", "gen", "later")

    def __init__(self, ts, vals, lanes: int, seq: int, fill_stats,
                 phase_stats, staged_hi: int, ts_desc=None,
                 nbytes: Optional[int] = None, width: int = 0,
                 pack_inv=None):
        # ts: device int32 plane, or None when every lane proved
        # uniform-phase at build time — ``ts_desc`` then reconstructs it
        # on device.  vals: device plane, or the XOR-class dict.
        self.ts = ts
        self.vals = vals
        self.lanes = lanes
        self.width = width          # columns (lanes * hist stride)
        self.nbytes = nbytes if nbytes is not None else \
            int(ts.size * ts.dtype.itemsize + vals.size * vals.dtype.itemsize)
        self.last_used = seq
        self.fmin, self.fmax, self.fcnt = fill_stats
        self.pmin, self.pmax = phase_stats
        self.ts_desc = ts_desc
        # host copy of the pack's original-lane -> packed-position map
        # (codecs/xorgrid.py); None for decoded-plane blocks.  Lets the
        # fused packed kernels run in packed lane order while callers
        # compose their lane indirections host-side.
        self.pack_inv = pack_inv
        self.hi_ts = None
        self.gen = 0
        self.later = ()         # rehearsals due at a later row (_rehearse)
        # lanes < staged_hi were populated at build time; a lane at or
        # beyond it belongs to a partition that joined later and is NOT
        # represented in this block (it must rebuild, never serve NaN)
        self.staged_hi = staged_hi

    @property
    def ts_seg(self):
        """The ts-plane segment descriptor the serving program consumes."""
        return self.ts if self.ts is not None else self.ts_desc

    def dense_or_empty(self, a: int, b: int, req: np.ndarray):
        """(dense, empty) bool masks over the columns ``req``: provably
        dense over local rows [a, b] / provably empty there.  The fill
        stats are gathered at ``req`` FIRST: a plan costs what the
        request asks for, not the block's width."""
        fmin, fmax, fcnt = self.fmin[req], self.fmax[req], self.fcnt[req]
        contiguous = fcnt == fmax - fmin + 1
        dense = contiguous & (fmin <= a) & (fmax >= b)
        empty = (fcnt == 0) | (fmax < a) | (fmin > b)
        return dense, empty


class DeviceGridCache:
    """Per-(shard, schema, value-column) device grid with eviction."""

    def __init__(self, shard, schema_hash: int, column_id: int,
                 budget_bytes: int, gstep_ms: Optional[int] = None,
                 hist: bool = False):
        self._shard = shard
        self.schema_hash = schema_hash
        self.column_id = column_id
        self.budget = budget_bytes
        # HBM-ledger owner tag for every resident byte this cache
        # commits (devicewatch: filodb_device_hbm_bytes{owner,format})
        self.owner = (f"grid:{getattr(shard, 'dataset', '?')}/"
                      f"{getattr(shard, 'shard_num', '?')}:c{column_id}")
        self.gstep = gstep_ms          # None until detected
        # histogram columns: each partition slot spans ``hb`` device
        # columns (one per cumulative bucket); the SAME scalar kernel
        # then computes per-bucket rates (the reference's per-bucket
        # HistRateFunction semantics, rangefn/RangeFunction.scala:376)
        self.hist = hist
        self.hb: Optional[int] = None          # bucket lanes per slot
        self.bucket_tops: Optional[np.ndarray] = None
        self.epoch0: Optional[int] = None
        self.lane_of: dict[int, int] = {}
        self._next_lane = 0
        self.blocks: dict[int, _Block] = {}
        # bi -> the OPEN block: the one that holds write-buffer rows,
        # resident once and appended to (two while the live edge straddles
        # a block boundary).  What retired one is kept until it is built
        # again (``grid.tail_build``'s ``why``)
        self._open: dict[int, _Block] = {}
        self._open_retired: dict[int, str] = {}
        # rows the ingest hook queued (note_append, a series' rows under
        # one lane; note_append_rows, a container's with a lane a row; any
        # thread, no lock) for the ingest thread's flush_appends: (lanes,
        # ts, vals, the rows whose earliest lowers the frontier or None,
        # the shard's newest timestamp before the batch)
        self._pend: collections.deque = collections.deque()
        # newest timestamp this cache has staged or been told of: a block
        # that begins at or after it holds nothing yet (_apply_pending)
        self._seen_hi = -1
        # what the served plans' program calls are made of, by shape
        # (insertion-ordered, the oldest goes): compiled over an open
        # block when one is created (_rehearse), by futures a request
        # that gets there first waits for (_await_rehearsals)
        self._recipes: dict = {}
        self._rehearsals: list = []
        self.version = 0               # bumped on invalidating freezes
        # quarantine epoch the resident blocks were staged under: a
        # chunk quarantined AFTER staging must stop being served, so a
        # changed epoch drops every block for a re-stage through the
        # (exclusion-applying) partition read path
        self._quarantine_epoch = -1
        self.disabled_until_version = -1
        self._disable_count = 0        # exponential re-try backoff
        self._disk_floor: Optional[tuple[int, int]] = None  # (ver, floor_ms)
        # id(part_ids) -> prep, priced by the ids walked (_prep_for)
        self._preps = CostMemo(16)
        # large-K shapes that failed the dense proof: deny until data
        # changes, so a refreshing dashboard doesn't re-pay speculative
        # block staging every cycle
        self._bigk_deny: dict[tuple, tuple] = {}
        # (bi_lo, bi_hi, version) -> (host phases, device phases): the
        # uniform-phase vector for the frozen block range (see
        # _phase_device); stale keys never match, single-entry by design
        self._phase_memo: dict[tuple, tuple] = {}
        # mesh staging memo: (row0, nrows) -> (parts identity, staged
        # ts, staged vals) — see mesh_plan
        self._mesh_stage_memo: dict[tuple, tuple] = {}
        # full-plan memo: a repeat dashboard query skips lane
        # resolution, block assembly and the proofs.  A miss costs
        # O(lanes requested), never O(lanes resident), so the memo stays
        # small: its key holds ``steps0``, and a dashboard whose ``end``
        # advances never hits.  Keys carry every invalidation axis
        # (cache version, ingest epoch, removal epoch, id-list
        # fingerprint); cleared on freeze/repin/reclaim.  When full ONE
        # entry leaves, the cheapest to prove again first (its price is
        # the lanes it asked for): 800 namespaces of 64 lanes turn over
        # beside a workspace-wide plan of 25 000 and never push it out
        self._plan_memo = CostMemo(8)
        # the last frozen-frontier walk: (state key, earliest buffered
        # row's timestamp or None) — see _frozen_high; ``_freezes``
        # counts what can RAISE it (a freeze empties buffers)
        self._frontier: tuple = (None, None)
        self._freezes = 0
        self._seq = 0
        self._lock = threading.Lock()
        # stats
        self.builds = 0
        self.hits = 0
        self.dense_hits = 0
        self.evictions = 0
        self.frontier_walks = 0
        self.appends = 0               # launches of the append program
        self.opened = 0                # open blocks created empty

    # ------------------------------------------------------------ bookkeeping

    @property
    def bytes_resident(self) -> int:
        n = sum(b.nbytes for b in self.blocks.values())
        n += sum(blk.nbytes for blk in self._open.values())
        return n

    def _drop_open(self, why: str) -> None:  # holds-lock: _lock
        """Let every open block go (a width that grew, a re-pin, a
        quarantine, the cache disabled): the next plan that needs one builds it over
        every lane again.  The plans made of them go too, so that no
        plane of a block that was let go stays referenced."""
        for bi in self._open:
            self._open_retired[bi] = why
        if self._open:
            self._open.clear()
            self._plan_memo.clear()

    def note_repin(self) -> None:
        """The shard was pinned to a different mesh device: resident
        blocks (and the device-side memos holding arrays) live on the
        old device — drop them so they rebuild in place on the new one
        (shard.pin_grid_device)."""
        with self._lock:
            n = len(self.blocks) + len(self._open)
            if n:
                LEDGER.note_eviction(self.owner, "epoch_purge", n=n,
                                     nbytes=self.bytes_resident)
            self.blocks.clear()
            self._drop_open("recovery")
            self._phase_memo.clear()
            self._mesh_stage_memo.clear()
            self._plan_memo.clear()
            self.version += 1

    def note_freeze(self, cs) -> None:
        """A chunk froze: blocks overlapping it are stale (a lagging series
        back-filled an old bucket) and buffers emptied (the frozen frontier
        may have risen: the next plan walks for it).  The open blocks
        STAY: their cells are the same samples whether a chunk or a write
        buffer holds them now, and the rows that follow are appended as
        before; a flush group's freeze costs no every-lane build.  An
        open block gives way to a frozen, packed one once the frontier
        has passed its range: no buffer holds a row of it any more
        (``_block_for``).  (The shard bumps its ``ingest_epoch``
        separately.)"""
        with self._lock:
            self._freezes += 1
            if self.hist:
                self._drop_open("recovery")   # bucket planes take no append
            self._plan_memo.clear()       # plans keyed by the old epoch
            if self.gstep is None or self.epoch0 is None:
                return
            lo_block = (cs.info.start_time - self.epoch0) // (
                self.gstep * BLOCK_BUCKETS)
            stale = [bi for bi in self.blocks if bi >= lo_block]
            nbytes = sum(self.blocks[bi].nbytes for bi in stale)
            for bi in stale:
                del self.blocks[bi]
            if stale:
                LEDGER.note_eviction(self.owner, "epoch_purge",
                                     n=len(stale), nbytes=nbytes)
                self.version += 1

    _STD_STEPS = (1_000, 2_000, 5_000, 10_000, 15_000, 30_000, 60_000,
                  120_000, 300_000, 600_000, 900_000, 1_800_000, 3_600_000)

    def _detect_gstep(self, part) -> Optional[int]:
        """Median inter-sample delta snapped to the nearest standard scrape
        interval (jitter skews the raw median; the block build verifies the
        one-sample-per-bucket invariant regardless)."""
        ts, _ = part.read_range(0, 2**62, self.column_id)
        if len(ts) < 3:
            return None
        deltas = np.diff(ts)
        deltas = deltas[deltas > 0]
        if len(deltas) == 0:
            return None
        med = float(np.median(deltas))
        best = min(self._STD_STEPS, key=lambda c: abs(c - med))
        if abs(best - med) <= 0.5 * best:
            return best
        return int(med)

    def _disable(self) -> None:  # holds-lock: _lock
        """Turn the fast path off; retries back off exponentially so a
        shard whose frozen history permanently violates the layout
        invariant doesn't re-stage a full block on every query."""
        self._disable_count += 1
        backoff = 2 ** min(self._disable_count, 16)
        self.disabled_until_version = self._shard.ingest_epoch + backoff
        n = len(self.blocks) + len(self._open)
        if n:
            LEDGER.note_eviction(self.owner, "epoch_purge", n=n,
                                 nbytes=self.bytes_resident)
        self.blocks.clear()
        self._drop_open("recovery")
        self._plan_memo.clear()            # plans pin the dropped blocks
        # re-probe the bucket scheme on the next attempt: a widened
        # histogram (16 -> 20 buckets) must not disable the fast path
        # forever once the narrow chunks age out
        self.hb = None
        self.bucket_tops = None

    # ---------------------------------------------------------------- serving

    def scan_rate(self, part_ids: Sequence[int], func: F, steps0: int,
                  nsteps: int, step_ms: int, window_ms: int,
                  fargs: tuple = ()):
        """Serve any _GRID_OPS window function (rate/increase, the
        *_over_time family, the bare instant selector's last-sample scan)
        on the query step grid from device-resident blocks.  Returns
        values ``[S_req, T]`` (``[S_req, T, hb]`` per-bucket for
        histogram columns) as numpy, or None when the fast path cannot
        serve this query (caller falls back).  Histogram results come
        paired with the bucket tops snapshotted under the same lock (a
        concurrent _disable may null ``self.bucket_tops``)."""
        if func not in _GRID_OPS:
            return None
        if self.hist and func not in _HIST_GRID_FNS:
            return None
        if len(fargs) != _ARG_OPS.get(_GRID_OPS[func], 0):
            return None        # unexpected / missing function argument
        waited = TRACER.stage("grid.lock_wait", leaf=False).begin()
        with self._lock:
            waited.end()
            plan = self._plan_staged(  # filolint: disable=blocking-under-lock — staging under the grid lock is the design: one query stages the block, contenders reuse it instead of duplicating the HBM upload; the breaker bounds pathological re-staging
                part_ids, func, steps0, nsteps, step_ms, window_ms, fargs)
            if plan is None:
                return None
            _note_hbm(plan)
            self._note_recipe(plan, "series", 0, "")
            tops = np.asarray(self.bucket_tops) if self.hist else None
        # dispatch + readback run OUTSIDE the grid lock (the
        # scan_rate_grouped structure): the plan tuple holds live refs
        # to its device arrays, so a concurrent eviction cannot free
        # them mid-dispatch — and concurrent shape-compatible queries
        # can now rendezvous in the fleet batching tier
        self._await_rehearsals(plan)
        vals = self._dispatch_series(plan)
        return vals, tops

    def scan_rate_grouped(self, part_ids: Sequence[int], func: F,
                          steps0: int, nsteps: int, step_ms: int,
                          window_ms: int, group_ids: Sequence[int],
                          num_groups: int, op: str = "sum",
                          fargs: tuple = ()):
        """Fused serve of ``agg by (g)(<grid window fn>(...))``: any
        _GRID_OPS window function under a distributive aggregate; the
        grid kernel's
        [T, lanes] output is segment-reduced ON DEVICE, so only the tiny
        [G, T] partials cross the host link (not the full per-series
        matrix, read back and re-uploaded).  Returns the mergeable
        partial state dict ({"sum","count"} / {"min"} / {"max"}) or None
        to fall back."""
        if func not in _GRID_OPS:
            return None
        if self.hist and (func not in _HIST_GRID_FNS or op != "sum"):
            return None
        if _GRID_OPS[func] in _REBASE_OPS:
            return None        # re-based ops skip the fused reduce
        if len(fargs) != _ARG_OPS.get(_GRID_OPS[func], 0):
            return None        # unexpected / missing function argument
        waited = TRACER.stage("grid.lock_wait", leaf=False).begin()
        with self._lock:
            waited.end()
            plan = self._plan_staged(  # filolint: disable=blocking-under-lock — staging under the grid lock is the design: one query stages the block, contenders reuse it instead of duplicating the HBM upload; the breaker bounds pathological re-staging
                part_ids, func, steps0, nsteps, step_ms, window_ms, fargs)
            if plan is None:
                return None
            stride = self.hb if self.hist else 1
            tops = np.asarray(self.bucket_tops) if self.hist else None
            _note_hbm(plan)
            self._note_recipe(plan, "grouped", num_groups * stride, op)
        # the full-width group map is built OUTSIDE the grid lock: the
        # plan tuple and the caller's arguments are all it reads
        # (``stride`` and ``tops`` were snapshotted under it)
        self._await_rehearsals(plan)
        garr = np.full(plan.ncols, num_groups * stride, dtype=np.int32)
        gid_arr = np.asarray(group_ids, dtype=np.int32)
        if stride == 1:
            garr[plan.lane_idx] = gid_arr
        else:
            hist_slot_garr(garr, plan.lane_idx, gid_arr, stride)

        def grouped_solo():
            # today's per-query fused reduce: also the batching tier's
            # bit-identical fallback (it IS the same dispatch)
            o = _fused_progs()["grouped"](
                plan.ts_parts, plan.val_parts, plan.row0, plan.steps0_rel,
                garr, plan.phase, q=plan.q, lanes=plan.lane_mult,
                nrows=plan.nrows, num_groups=num_groups * stride, op=op)
            _note_kernel_bytes(_fused_progs()["grouped"], plan)
            return _fetch(o, np.float64)  # host-sync-ok: ONE blocked readback of the reduced partials

        both = None
        if plan.packed is not None and not _PACKED_BROKEN:
            # packed lane order: scatter the group map through inv;
            # pack pad lanes keep the drop bucket
            n_pk = int(plan.packed["first"].shape[0])
            garr_pk = np.full(n_pk, num_groups * stride, dtype=np.int32)
            garr_pk[plan.packed_inv] = garr
            out = _run_packed(
                lambda: _fused_progs()["grouped_packed"](
                    plan.packed, plan.steps0_rel, garr_pk, q=plan.q,
                    row0=plan.packed_row0,
                    use_phase=plan.packed_use_phase,
                    num_groups=num_groups * stride, op=op,
                    interpret=_PACKED_INTERPRET))
            if out is not None:
                _note_kernel_bytes(_fused_progs()["grouped_packed"], plan)
                both = _fetch(out, np.float64)  # host-sync-ok: the one designed readback of the fused reduce
        if both is None and not self.hist:
            both = self._batched_grouped(plan, garr,
                                         num_groups * stride, op,
                                         grouped_solo)
        if both is None:
            both = grouped_solo()
        with TRACER.stage("grid.select"):
            if self.hist:
                # both: [2, G*hb, T] hist planes
                return hist_state_from_planes(both, num_groups, stride,
                                              tops)
            if op in ("sum", "avg", "count", "moments"):
                if op == "count":
                    return {"count": both[1]}
                if op == "moments":
                    return {"sum": both[0], "count": both[1],
                            "sumsq": both[2]}
                return {"sum": both[0], "count": both[1]}
            return {op: both}

    def _batched_grouped(self, plan, garr, num_groups, op, grouped_solo):
        """Offer a fused grouped reduce to the fleet batching tier.
        Members must share the group map exactly (``garr`` bytes are
        part of the key): the stacked program reduces every member
        with the one shared map.  Returns the member's float64
        partial-planes slice, or None for the solo fallback."""
        batcher = getattr(self._shard, "query_batcher", None)
        if batcher is None or not batcher.enabled:
            return None
        from filodb_tpu.query.exec import active_exec_ctx
        ctx = active_exec_ctx()
        qctx = ctx.query_context if ctx is not None else None
        key = ("grouped", tuple(id(p) for p in plan.ts_parts),
               tuple(id(p) for p in plan.val_parts), id(plan.phase),
               plan.q, plan.lane_mult, plan.nrows, num_groups, op,
               garr.tobytes())
        prog = _fused_progs()["grouped_batch"]

        kw = dict(q=plan.q, lanes=plan.lane_mult, nrows=plan.nrows,
                  num_groups=num_groups, op=op)

        def batch_launch(row0s, steps0s):
            loaded = prog._jitted._cache_size()
            out = _fused_progs()["grouped_batch"](
                plan.ts_parts, plan.val_parts, row0s, steps0s,
                garr, plan.phase, **kw)
            if prog._jitted._cache_size() > loaded:
                for r0s, s0s in _other_stack_sizes(row0s, steps0s,
                                                   batcher.stack_sizes()):
                    prog(plan.ts_parts, plan.val_parts, r0s, s0s, garr,
                         plan.phase, **kw)
            _note_kernel_bytes(prog, plan)
            return _fetch(out, np.float64)  # host-sync-ok: ONE stacked readback of the group's reduced partials

        return batcher.dispatch(key, plan.row0, plan.steps0_rel, qctx,
                                batch_launch, grouped_solo)

    def mesh_plan(self, part_ids: Sequence[int], func: F, steps0: int,
                  nsteps: int, step_ms: int, window_ms: int,
                  group_ids, fargs: tuple = ()):
        """Plan + device-RESIDENT staging for the SPMD mesh serving path
        (parallel/meshgrid.py): the composition of the device grid with
        the shard-axis mesh (VERDICT r2 #1).  Returns a MeshShardPlan
        whose staged arrays live on this shard's pinned device — the
        mesh program reads them in place, zero per-query host upload —
        or None to fall back to the host-batch mesh path.
        ``group_ids``: a group id a series of ``part_ids``, or ONE int
        where they all fall into one group (an aggregate with no ``by``).

        Staging (block concat + row slice) runs once per (range,
        version) and is memoized by block identity, and the
        MeshShardPlan is kept with its grid plan, one a grouping: a
        repeat dashboard query performs no device work here, makes no
        array as wide as its lanes, and is handed the SAME object (the
        fabric's memos key on it).  Only where one is BUILT does the
        ``mesh.plan_build`` stage open, outside the grid lock."""
        if func not in _GRID_OPS:
            return None
        if self.hist and func not in _HIST_GRID_FNS:
            return None
        op = _GRID_OPS[func]
        if op in _REBASE_OPS or len(fargs) != _ARG_OPS.get(op, 0):
            return None
        if isinstance(group_ids, (int, np.integer)):
            grouping = int(group_ids)
        else:
            group_ids = np.asarray(group_ids, dtype=np.int32)
            grouping = (len(group_ids), hash(group_ids.tobytes()))
        waited = TRACER.stage("grid.lock_wait", leaf=False).begin()
        with self._lock:
            waited.end()
            plan = self._plan_staged(  # filolint: disable=blocking-under-lock — staging under the grid lock is the design: one query stages the block, contenders reuse it instead of duplicating the HBM upload; the breaker bounds pathological re-staging
                part_ids, func, steps0, nsteps, step_ms, window_ms, fargs)
            if plan is None or not plan.segs:
                return None
            _note_hbm(plan)
            # no ts plane is staged where the SPMD program streams none:
            # the phase kernels reconstruct the geometry from the phase
            # row, the ts-free ops (sum, last, ...) never read it — so
            # one dashboard's rate, sum_over_time and instant panels
            # over one range share ONE staged value plane
            no_ts = plan.phase is not None or op in TS_FREE_OPS
            key = (plan.row0, plan.nrows, no_ts)
            parts_id = tuple((id(b), b.gen) for b in plan.segs)
            memo = self._mesh_stage_memo.get(key)
            if memo is not None and memo[0] == parts_id:
                _, ts_st, val_st, segs_ref = memo
            else:
                with TRACER.stage("mesh.stage", rows=plan.nrows,
                                  lanes=plan.ncols):
                    ts_st, val_st = _mesh_stage(
                        None if no_ts
                        else tuple(b.ts_seg for b in plan.segs),
                        tuple(b.vals for b in plan.segs),
                        plan.row0, nrows=plan.nrows)
                # the staged planes are HBM residents held by the memo:
                # they belong on the ledger like any committed block
                LEDGER.track(ts_st, owner=self.owner, fmt="mesh-staged")
                LEDGER.track(val_st, owner=self.owner, fmt="mesh-staged")
                if len(self._mesh_stage_memo) > 4:
                    self._mesh_stage_memo.clear()
                    # ... and with them the shard plans made of them: a
                    # kept plan must not pin a plane this memo let go
                    for kept_plan in self._plan_memo.values():
                        kept_plan.mesh.clear()
                # hold the block refs: id() stays unambiguous while the
                # memo entry lives
                self._mesh_stage_memo[key] = (parts_id, ts_st, val_st,
                                              plan.segs)
            def kept():
                # ... of the planes staged NOW: one made of planes the
                # stage memo has since let go is made again
                got = plan.mesh.get(grouping)
                return got if got is not None and got.ts is ts_st \
                    and got.vals is val_st else None
            if kept() is not None:
                return kept()
            why = "rows" if plan.mesh else "plan"
            hb = self.hb if self.hist else 0
            tops = np.asarray(self.bucket_tops) if self.hist else None
            device = self._shard.grid_device
        # what is made a (plan, grouping) is made OUTSIDE the grid lock:
        # the lock guards the blocks and the plan, not a query's own
        # rows (``hb``, ``tops`` and the device were read under it)
        with TRACER.stage("mesh.plan_build", why=why,
                          lanes_requested=len(plan.lane_idx)):
            cols, slots, order = _mesh_lanes(plan, group_ids, hb)
            built = MeshShardPlan(
                ts_st, val_st, plan.phase, cols, slots,
                (len(cols), hash(cols.tobytes()), hash(slots.tobytes())),
                plan.q, plan.steps0_rel, plan.ncols, device, hb=hb,
                bucket_tops=tops, part_ids=part_ids, order=order)
        with self._lock:
            if kept() is not None:
                return kept()          # another worker built it meanwhile
            if len(plan.mesh) >= 4:
                # a dashboard groups one selection a few ways; past that
                # the oldest grouping is made again when next asked
                del plan.mesh[next(iter(plan.mesh))]
            plan.mesh[grouping] = built
        return built

    def _plan_staged(self, part_ids, func, steps0, nsteps, step_ms,  # holds-lock: _lock
                     window_ms, fargs):
        """``_plan_locked`` as the ``grid.plan`` stage, under the grid
        lock: on a plan-memo miss lane resolution (``_prep_for``), block
        assembly and the dense and phase proofs, all over the lanes
        REQUESTED; what costs the lanes resident runs as a stage of its
        own inside it, the frontier walk once per shard state
        (``grid.frontier``; tag ``frontier``: ``walk`` where this plan
        made it, else ``memo``) and, on a cold range, the builds
        (``grid.build``; ``grid.tail_build`` for an open block that no
        append could make).  The wait for the lock is the caller's
        ``grid.lock_wait`` stage: what a worker loses to the other
        workers' plans."""
        with TRACER.stage("grid.plan", cpu=True,
                          lanes_requested=len(part_ids)) as sp:
            walks = self.frontier_walks
            plan = self._plan_locked(part_ids, func, steps0, nsteps,
                                     step_ms, window_ms, fargs)
            sp.tag(frontier="memo" if walks == self.frontier_walks
                   else "walk")
            if plan is not None:
                sp.tag(lanes=plan.ncols)
            return plan

    def _series_solo(self, plan):
        """Today's per-query series launch + readback: the unchanged
        chain every batching fallback demotes to (bit-identical by
        construction — it IS the same dispatch)."""
        stepped = _fused_progs()["series"](
            plan.ts_parts, plan.val_parts, plan.row0, plan.steps0_rel,
            plan.phase, q=plan.q, lanes=plan.lane_mult,
            nrows=plan.nrows)
        _note_kernel_bytes(_fused_progs()["series"], plan)
        return _fetch(stepped)  # host-sync-ok: the designed stepped readback — only [T, lanes] crosses the host link

    def _batched_series(self, plan):
        """Offer this dispatch to the fleet batching tier (ISSUE 20).
        Returns the member's ``[T, lanes]`` readback slice, or None
        when the batcher declined (absent, disabled, breaker open,
        deadline too short, group demoted) — the caller then runs the
        unchanged solo chain."""
        batcher = getattr(self._shard, "query_batcher", None)
        if batcher is None or not batcher.enabled or self.hist:
            return None
        from filodb_tpu.query.exec import active_exec_ctx
        ctx = active_exec_ctx()
        qctx = ctx.query_context if ctx is not None else None
        # batch-compatibility at the device boundary: the SAME resident
        # planes (segment identity), the same static kernel signature,
        # and the same grid shape — members differ only in the traced
        # (row0, steps0) stack axis.  lane_idx may differ per member:
        # the series program computes every lane, request slicing is
        # host-side on the member's own slice.
        key = ("series", tuple(id(p) for p in plan.ts_parts),
               tuple(id(p) for p in plan.val_parts), id(plan.phase),
               plan.q, plan.lane_mult, plan.nrows)
        prog = _fused_progs()["series_batch"]

        kw = dict(q=plan.q, lanes=plan.lane_mult, nrows=plan.nrows)

        def batch_launch(row0s, steps0s):
            loaded = prog._jitted._cache_size()
            out = _fused_progs()["series_batch"](
                plan.ts_parts, plan.val_parts, row0s, steps0s,
                plan.phase, **kw)
            if prog._jitted._cache_size() > loaded:
                for r0s, s0s in _other_stack_sizes(row0s, steps0s,
                                                   batcher.stack_sizes()):
                    prog(plan.ts_parts, plan.val_parts, r0s, s0s,
                         plan.phase, **kw)
            _note_kernel_bytes(prog, plan)
            return _fetch(out)  # host-sync-ok: ONE stacked [B, T, lanes] readback serves the whole co-arrival group

        return batcher.dispatch(key, plan.row0, plan.steps0_rel, qctx,
                                batch_launch, lambda: self._series_solo(plan))

    def _dispatch_series(self, plan):
        lanes_req = plan.lane_idx
        used_packed = False
        out_np = None
        if plan.packed is not None:
            stepped = _run_packed(
                lambda: _fused_progs()["series_packed"](
                    plan.packed, plan.steps0_rel, q=plan.q,
                    row0=plan.packed_row0,
                    use_phase=plan.packed_use_phase,
                    interpret=_PACKED_INTERPRET))
            if stepped is not None:
                used_packed = True
                if not self.hist:
                    # packed lane order: compose request map with inv
                    lanes_req = plan.packed_inv[plan.lane_idx]
                _note_kernel_bytes(_fused_progs()["series_packed"], plan)
                out_np = _fetch(stepped)  # host-sync-ok: the designed stepped readback — only [T, lanes] crosses the host link
        if out_np is None:
            out_np = self._batched_series(plan)
        if out_np is None:
            out_np = self._series_solo(plan)
        with TRACER.stage("grid.select"):
            return self._select_lanes(plan, out_np, lanes_req, used_packed)

    def _select_lanes(self, plan, out_np, lanes_req, used_packed):
        """Host lane selection: the requested series' columns of the
        stepped ``[T, lanes]`` plane, and the re-base of the ops that
        need one."""
        if self.hist:
            # COLUMN-granular indirection: a hist series' device columns
            # are lane*hb + bucket, so the pack's inv must compose with
            # the expanded column map, never the lane map alone
            cols = plan.lane_idx[:, None] * self.hb \
                + np.arange(self.hb)[None, :]
            if used_packed:
                cols = plan.packed_inv[cols]
            return out_np[:, cols].transpose(1, 0, 2)     # [S_req, T, hb]
        out = out_np[:, lanes_req].T                      # [S_req, T]
        if plan.q.op in _REBASE_OPS:
            # absolute window-end seconds, re-based in f64 on only the
            # requested lanes (the kernel emits window-relative seconds
            # so f32 stays exact)
            q = plan.q
            abs_s = (self.epoch0 + plan.steps0_rel
                     + np.arange(q.nsteps, dtype=np.int64)
                     * q.gstep_ms * q.stride) / 1000.0
            out = out.astype(np.float64) + np.where(
                np.isfinite(out), abs_s[None, :], 0.0)
        return out

    def _prep_for(self, part_ids, fp=None):
        """Memoized resolution of one lookup result: validate every pid
        (present + matching schema), assign lanes, and build the lane
        index.  Keyed on the lookup cache's array identity and the
        shard's partition removal epoch — repeated dashboard queries
        skip the 20k-dict walk entirely (it otherwise dominates
        host-side serving time at high cardinality).  ``fp`` lets the
        caller reuse an already-computed content fingerprint (the
        full-array hash is O(n))."""
        shard = self._shard
        n = len(part_ids)
        if n == 0:
            return None
        key = id(part_ids)
        if fp is None:
            fp = _ids_fingerprint(part_ids)
        prep = self._preps.get(key)
        if (prep is not None and prep["epoch"] == shard.removal_epoch
                and prep["fp"] == fp and prep["obj"] is part_ids):
            return prep
        # snapshot the epoch BEFORE the walk: an eviction racing the
        # validation must leave the prep stamped stale, not fresh
        epoch = shard.removal_epoch
        ids = [int(p) for p in part_ids]
        for pid in ids:
            part = shard.grid_partition(pid)
            if part is None:
                return None                    # evicted/paged: fall back
            if part.schema.schema_hash != self.schema_hash:
                return None                    # mixed-schema id list
            if pid not in self.lane_of:
                self.lane_of[pid] = self._next_lane
                self._next_lane += 1
        lane_idx = np.fromiter((self.lane_of[pid] for pid in ids),
                               dtype=np.int64, count=n)
        # "obj" holds a STRONG reference to the keyed array: id() stays
        # unambiguous for the entry's lifetime (no address reuse)
        prep = {"epoch": epoch, "fp": fp, "obj": part_ids, "ids": ids,
                "lane_idx": lane_idx}
        self._preps.put(key, prep, n)
        return prep

    def _fingerprint_of(self, part_ids) -> int:
        """``_ids_fingerprint`` of a lookup result, hashed once for the
        object: a result the shard's lookup cache hands out is read-only
        and its prep keeps it alive, so the prep's ``fp`` IS this
        array's and a repeated request reaches the plan memo without a
        call over every id.  Anything else (a list, a writable array: an
        ODP lookup, a test's own ids) is hashed as before."""
        if isinstance(part_ids, np.ndarray) and not part_ids.flags.writeable:
            prep = self._preps.get(id(part_ids))
            if prep is not None and prep["obj"] is part_ids:
                return prep["fp"]
        return _ids_fingerprint(part_ids)

    def _plan_locked(self, part_ids, func, steps0, nsteps, step_ms,
                     window_ms, fargs=()):
        """Shared grid preamble: eligibility checks, block assembly, and
        the dense-contract proof.  Returns a :class:`_GridPlan` (device
        block refs + kernel config — NO device dispatch happens here; the
        caller runs ONE fused program) or None to fall back."""
        shard = self._shard
        if self.disabled_until_version >= shard.ingest_epoch:
            return None
        if len(part_ids) == 0:
            return None
        from filodb_tpu.integrity import QUARANTINE
        qepoch = QUARANTINE.epoch()
        if qepoch != self._quarantine_epoch:
            # blocks staged before a quarantine still CONTAIN the
            # quarantined chunk's rows — serving them would defeat the
            # exclusion the partition read path applies.  Quarantine is
            # rare; a full re-stage is the correct price.
            if self._quarantine_epoch >= 0 and (self.blocks or self._open):
                LEDGER.note_eviction(self.owner, "integrity_quarantine",
                                     n=len(self.blocks) + len(self._open),
                                     nbytes=self.bytes_resident)
                self.blocks.clear()
                self._drop_open("recovery")
                self._plan_memo.clear()
                self._phase_memo.clear()
                self._mesh_stage_memo.clear()
                self.version += 1
            self._quarantine_epoch = qepoch
        # ALL eligibility checks run before _prep_for assigns lanes —
        # an ineligible query must not widen the lane count (that would
        # clear every resident block on the next eligible query)
        first = shard.grid_partition(int(part_ids[0]))
        if first is None or first.schema.schema_hash != self.schema_hash:
            return None
        if self.gstep is None:
            g = shard.config.grid_step_ms or self._detect_gstep(first)
            if not g or g <= 0:
                self._disable()                # don't re-detect every query
                return None
            self.gstep = g
        g = self.gstep
        # optimistic K cap: K-free ops may take large windows IF the
        # dense proof below succeeds (checked again once dense is known)
        if not supports_grid(window_ms, step_ms, g, nsteps,
                             max_k=max_k_for(_GRID_OPS[func], dense=True)):
            return None
        ids_fp = self._fingerprint_of(part_ids)
        deny_key = (func, window_ms, step_ms, ids_fp)
        if self._bigk_deny.get(deny_key) == \
                (self.version, shard.ingest_epoch):
            return None     # dense proof failed for this shape; data unchanged
        shape = (func, steps0, nsteps, step_ms, window_ms, fargs, ids_fp)
        # the epochs are read once, before the plan: one that moves while
        # the plan is made leaves it under a key nothing asks for again
        epochs = (shard.ingest_epoch, shard.removal_epoch)
        cached = self._plan_memo.get((*shape, self.version, *epochs))
        if cached is not None:
            self._seq += 1
            for blk in cached.segs:
                blk.last_used = self._seq
            self.hits += 1
            return cached
        if self.hist and self.hb is None:
            # probe a narrow leading slice for the bucket scheme — a
            # full-history read_range would decode (and memoize) every
            # chunk of the partition while holding the cache lock
            e0 = first.earliest_timestamp
            _pts, pvals = first.read_range(e0, e0 + 64 * g, self.column_id)
            buckets = pvals[0] if isinstance(pvals, tuple) else None
            if buckets is None or buckets.num_buckets == 0:
                self._disable()
                return None
            self.hb = int(buckets.num_buckets)
            self.bucket_tops = np.asarray(buckets.bucket_tops(), np.float64)
        if self.epoch0 is None:
            parts0 = (shard.grid_partition(int(pid)) for pid in part_ids)
            earliest = [p.earliest_timestamp for p in parts0 if p is not None]
            first_ts = min((t for t in earliest if t >= 0), default=-1)
            if first_ts < 0:
                return None
            self.epoch0 = (first_ts // g) * g
        if (steps0 - self.epoch0) % g != 0:
            return None                        # windows don't land on edges
        K = window_ms // g
        stride_r = step_ms // g                # query step in buckets
        # first window ends at steps0 and covers buckets [c0, c0+K-1];
        # window t starts stride_r buckets after window t-1
        c0 = (steps0 - self.epoch0) // g - K + 1
        c_last = c0 + (nsteps - 1) * stride_r + K - 1     # inclusive
        if c0 < 0:
            return None
        if (c_last + 1) * g > _I32_SPAN:
            return None                        # int32-relative overflow
        if hasattr(shard, "paged"):
            # ODP shard: residents may hold only their post-recovery tail,
            # with older chunks on disk; the grid would serve NaN there.
            # This runs BEFORE _prep_for so a rejected query cannot
            # widen the lane count (see the invariant above).
            parts = [shard.grid_partition(int(pid)) for pid in part_ids]
            if any(p is None for p in parts):
                return None
            lo_ms = self.epoch0 + (c0 - 1) * g
            if lo_ms < self._disk_floor_ms(parts):
                return None
        prep = self._prep_for(part_ids, fp=ids_fp)
        if prep is None:
            return None
        lanes = pad_lanes(self._next_lane)
        shapes = self._shard.grid_shapes
        if shapes is not None:
            # ... or the widest sibling shard's, where that is close: one
            # width a dataset, so that its shards share their programs
            lanes = shapes.lanes_for(lanes)
        if any(b.lanes != lanes for b in self.blocks.values()) \
                or any(b.lanes != lanes for b in self._open.values()):
            self.blocks.clear()                # widths must match to concat
            self._drop_open("width")
            self._plan_memo.clear()            # plans pin old-width blocks
        frozen_hi = self._frozen_high()
        bi_lo = c0 // BLOCK_BUCKETS
        bi_hi = c_last // BLOCK_BUCKETS
        # a block built BEFORE some requested partition got its lane has
        # that lane unstaged (all-NaN): it would pass the dense proof as
        # "empty" and silently serve NaN for a series that has data —
        # any such block must rebuild with the current lane roster
        need_hi = int(prep["lane_idx"].max()) + 1
        segments = []
        self._seq += 1
        for bi in range(bi_lo, bi_hi + 1):
            blk = self._block_for(bi, lanes, frozen_hi, need_hi)
            if blk is None:
                return None                    # invariant violated
            blk.last_used = self._seq
            segments.append(blk)
        self._evict(keep=set(range(bi_lo, bi_hi + 1)))

        row0 = c0 - bi_lo * BLOCK_BUCKETS
        nrows = c_last - c0 + 1
        ncols = segments[0].width
        # prove the dense-lane contract from per-block fill ranges: a
        # lane must be dense in EVERY covered block segment, or empty in
        # every one (a series that starts/stops mid-range is neither).
        # Only the REQUESTED lanes matter — per-lane outputs are
        # independent, and unrequested lanes are sliced away / mapped to
        # the drop bucket downstream.
        req = prep["lane_idx"]
        if self.hist:
            req = (req[:, None] * self.hb
                   + np.arange(self.hb)[None, :]).ravel()
        op = _GRID_OPS[func]
        # phase proof piggybacks on the dense walk: every requested lane
        # must be uniform-phase within each covered block AND carry the
        # SAME phase across blocks.  Open blocks are excluded: theirs
        # are the rows still arriving, a lane's first row there (or a
        # jittered scrape) would change the proof under a memoized device
        # phase vector keyed by block range and cache version, neither of
        # which an append moves — so a span that touches an open block
        # streams the ts planes (the open block's own, kept current by
        # every append).  Those program shapes are compiled when the
        # block opens (_rehearse), not by the first request.  Final
        # eligibility is grid.phase_eligible on the built query (adds
        # dense + K>=2); this is the cheap pre-filter for the proof walk.
        want_phase = op in PHASE_OPS and K >= 2 and \
            bi_hi * BLOCK_BUCKETS + BLOCK_BUCKETS - 1 <= frozen_hi
        ph_req = np.full(len(req), -1, np.int64)
        ph_ok = want_phase
        all_dense = np.ones(len(req), bool)
        all_empty = np.ones(len(req), bool)
        for off, blk in zip(range(bi_lo, bi_hi + 1), segments):
            a = max(c0 - off * BLOCK_BUCKETS, 0)
            b = min(c_last - off * BLOCK_BUCKETS, BLOCK_BUCKETS - 1)
            d, e = blk.dense_or_empty(a, b, req)
            all_dense &= d
            all_empty &= e
            if ph_ok:
                nonempty = ~e
                pmin = blk.pmin[req]
                uniform = pmin == blk.pmax[req]
                bph = pmin.astype(np.int64)
                conflict = nonempty & (ph_req >= 0) & (ph_req != bph)
                if (nonempty & ~uniform).any() or conflict.any():
                    ph_ok = False
                else:
                    ph_req = np.where(nonempty & (ph_req < 0), bph, ph_req)
        dense = bool((all_dense | all_empty).all())
        if (op in DENSE_ONLY_OPS and not dense) \
                or K > max_k_for(op, dense):
            # adjacency ops need every row present; large windows need
            # the proven-dense K-free path.  Either way, memoize the
            # denial so a refreshing dashboard doesn't re-stage blocks
            # every cycle; the data changing (version/epoch) retries.
            # The key includes the request fingerprint: a gappy series
            # set must not disable the fast path for a dense one that
            # happens to share the query shape.
            # LRU-on-write: re-denied hot shapes move to the back so the
            # overflow eviction below drops a stale one-off, not them
            self._bigk_deny.pop(deny_key, None)
            self._bigk_deny[deny_key] = (self.version, shard.ingest_epoch)
            if len(self._bigk_deny) > 64:
                self._bigk_deny.pop(next(iter(self._bigk_deny)))
            return None
        if dense:
            self.dense_hits += 1
        q = GridQuery(nsteps=nsteps, kbuckets=K, gstep_ms=g,
                      is_rate=(func == F.RATE), op=op,
                      dense=dense, stride=stride_r,
                      farg=float(fargs[0]) if fargs else 0.0,
                      farg2=float(fargs[1]) if len(fargs) > 1 else 0.0)
        phase_dev = None
        if ph_ok and phase_eligible(q):
            phase_dev = self._phase_device(ph_req, req, ncols,
                                           (bi_lo, bi_hi, self.version))
        lane_mult = lane_tile(ncols, nrows)
        self.hits += 1
        # phase mode and ts-free ops need no ts plane in the program
        ts_parts = () if (phase_dev is not None or op in TS_FREE_OPS) \
            else tuple(b.ts_seg for b in segments)
        # fused compressed-resident dispatch (ISSUE 3; histograms since
        # ISSUE 14): one compressed block covering the whole row span
        # serves through the packed kernels — the XOR-class decode runs
        # inside the grid kernel, so HBM reads the ~2.5 B/sample planes.
        # Phase mode reads the block's own meta phase row (identical to
        # phase_dev on every requested lane; unrequested lanes are
        # sliced/dropped).  Histogram caches qualify like scalar ones:
        # each bucket column is an independent packed lane and callers
        # compose their ``lane*hb + bucket`` indirections through the
        # pack's ``inv``.  Multi-block spans, ts-streaming ops, and f64
        # (no meta) residents keep the XLA decode path.  One segment is
        # the proof the kernels' traced row offset relies on:
        # row0 + nrows <= BLOCK_BUCKETS, so the rotate wraps no row in.
        seg0 = segments[0]
        packed = packed_inv = None
        packed_phase = False
        if (len(segments) == 1 and isinstance(seg0.vals, dict)
                and seg0.pack_inv is not None
                and not _PACKED_BROKEN
                and (on_tpu_backend() or _PACKED_INTERPRET)
                and any(k.startswith("m") for k in seg0.vals)):
            if op in TS_FREE_OPS:
                packed, packed_inv = seg0.vals, seg0.pack_inv
            elif phase_dev is not None and op in PHASE_OPS:
                packed, packed_inv = seg0.vals, seg0.pack_inv
                packed_phase = True
        hbm_dense = hbm_comp = hbm_hist = 0
        for blk in segments:
            if isinstance(blk.vals, dict):
                nb_c = sum(int(a.nbytes) for a in blk.vals.values())
                if self.hist:
                    hbm_hist += nb_c
                else:
                    hbm_comp += nb_c
            else:
                hbm_dense += int(blk.vals.nbytes)
        for t in ts_parts:
            if isinstance(t, dict):
                nb_c = int(t["phase"].nbytes)
                if self.hist:
                    hbm_hist += nb_c
                else:
                    hbm_comp += nb_c
            else:
                hbm_dense += int(t.nbytes)
        plan = _GridPlan(ts_parts,
                         tuple(b.vals for b in segments), row0,
                         steps0 - self.epoch0, q, lane_mult, nrows, ncols,
                         prep["lane_idx"], phase_dev, tuple(segments),
                         packed=packed, packed_row0=row0,
                         packed_use_phase=packed_phase,
                         packed_inv=packed_inv,
                         hbm_dense=hbm_dense, hbm_comp=hbm_comp,
                         hbm_comp_hist=hbm_hist, mesh={})
        # ``version`` as it stands NOW: a block this plan built bumped it
        # (under the lock this plan holds), and the next request asks
        # under the new one
        self._plan_memo.put((*shape, self.version, *epochs), plan, len(req))
        return plan

    def _phase_device(self, ph_req, req, ncols: int, key) -> object:  # holds-lock: _lock
        """Device [ncols] phase vector for the uniform-phase kernels,
        memoized per (block range, cache version) — no ~4 B/lane
        upload per query.
        Unrequested lanes get phase 1; their outputs are sliced away or
        segment-dropped downstream, so any value is safe."""
        phases = np.where(ph_req > 0, ph_req, 1).astype(np.int32)
        memo = self._phase_memo.get(key)
        if memo is not None and memo[0].shape[0] == ncols:
            host, dev = memo
            if np.array_equal(host[req], phases):
                return dev
            # different id-lists over the same blocks accumulate into
            # one merged vector so alternating dashboards don't ping-
            # pong uploads
            ph_cols = host.copy()
            ph_cols[req] = phases
        else:
            ph_cols = np.ones(ncols, np.int32)
            ph_cols[req] = phases
        dev = LEDGER.device_put(ph_cols, self._shard.grid_device,
                                owner=self.owner, fmt="scratch")
        self._phase_memo.clear()
        self._phase_memo[key] = (ph_cols, dev)
        return dev

    # ---------------------------------------------------------------- blocks

    def _disk_floor_ms(self, parts) -> int:
        """Highest timestamp below which some requested partition's data
        lives only in the column store (recovery tail / re-ingested after
        eviction).  Cached per shard ingest epoch."""
        epoch = self._shard.ingest_epoch
        if self._disk_floor is not None and self._disk_floor[0] == epoch:
            return self._disk_floor[1]
        floor = -(2**62)
        index = self._shard.index
        for part in parts:
            earliest = part.earliest_timestamp
            if earliest < 0:
                continue
            try:
                idx_start = index.start_time(part.part_id)
            except KeyError:
                continue
            if idx_start < earliest:
                floor = max(floor, earliest)
        self._disk_floor = (epoch, floor)
        return floor

    def _frozen_high(self) -> int:  # holds-lock: _lock
        """Highest bucket (exclusive) fully covered by frozen chunks: the
        earliest write-buffer row across THIS cache's lanes bounds it —
        an unrelated metric's laggy buffer must not demote this cache's
        recent blocks to open blocks.

        The bound is MAINTAINED, not walked: an append to a buffer that
        already holds a row cannot move a lane's earliest buffered row, so
        ingest moves the bound only where a buffer went from empty to
        non-empty (``note_append`` and ``note_append_rows`` say so with
        the rows; folded in by ``_apply_pending``).  The walk over every lane
        (``grid.frontier``) runs only where the bound may have RISEN or
        the lanes walked have changed: after a chunk freeze
        (``_freezes``), a removal (``removal_epoch``: eviction, purge,
        page-cache eviction) and a lane assigned (``_prep_for``) or
        pruned (``_build_block``); a page-in moves none and need not (a
        paged partition holds chunks only).  The key is read BEFORE the
        walk, so a freeze mid-walk leaves the memo stale, not fresh.  A
        buffer detached for a pipelined flush (``freeze_raw``) moves
        nothing until its chunk freezes: the bound then reads too LOW,
        which only sends a block down the exact open-block path.  The
        ingest thread folds a batch's rows in itself (``flush_appends``:
        the bound, and the open blocks' cells) before the epoch bump
        that makes them readable, also where the batch raised midway: a
        plan appends nothing, and sees whole batches only."""
        shard = self._shard
        key = (self._freezes, shard.removal_epoch, self._next_lane,
               len(self.lane_of))
        memo_key, lo = self._frontier
        if memo_key != key:
            with TRACER.stage("grid.frontier", lanes=len(self.lane_of)):
                lo = self._earliest_buffered()
            self._frontier = (key, lo)
            self.frontier_walks += 1
        if lo is None:
            return 2**62
        # bucket containing lo is NOT fully frozen
        return (lo - self.epoch0 + self.gstep - 1) // self.gstep - 1

    def _earliest_buffered(self) -> Optional[int]:
        """Earliest write-buffer row's timestamp over every lane's
        partition, or None when no lane has a row buffered: O(lanes
        resident), so only ``_frozen_high`` calls it, on a memo miss."""
        lo = None
        grid_partition = self._shard.grid_partition
        for pid in self.lane_of:
            part = grid_partition(pid)
            if part is not None and part._buf_n:
                t = int(part._buf_ts[0])
                if lo is None or t < lo:
                    lo = t
        return lo

    def _block_for(self, bi: int, lanes: int,  # holds-lock: _lock
                   frozen_hi: int,
                   need_hi: int):
        b_lo = bi * BLOCK_BUCKETS          # first bucket index of the block
        b_hi = b_lo + BLOCK_BUCKETS - 1
        blk = self.blocks.get(bi)
        if blk is not None and blk.lanes == lanes \
                and blk.staged_hi >= need_hi and b_hi <= frozen_hi:
            # a cached FROZEN block is only valid while its whole bucket
            # range stays below the frozen frontier: once write-buffer
            # rows land inside it (live ingest after the block was
            # staged), the staged copy is missing them and the dense
            # proof would read the hole as "no samples" — serving a
            # silently-partial window.  Such ranges take the open-block
            # path below; note_freeze drops the stale copy when the
            # buffer flushes.
            return blk
        if b_hi > frozen_hi:
            # open block: includes mutable write-buffer rows.  Resident
            # once: the rows that arrive are appended to it (by the ingest
            # thread, before the epoch bump), it is never rebuilt for an
            # ingest epoch or a freeze, and it is never packed (the pack
            # would be pure added latency on the live-ingest path).  The
            # build over every lane is left for what needs it: no open
            # block yet and rows in the range that the ingest hook did not
            # bring (the first query over an unflushed shard, a restart),
            # one let go by a re-pin or a lane width that grew
            blk = self._open.get(bi)
            if blk is not None and blk.lanes == lanes:
                if blk.staged_hi < need_hi \
                        and not self._stage_new_lanes(bi, blk):
                    return None
                return blk
            blk = self._build(bi, lanes, compress=False,
                              why=self._open_retired.pop(bi, "first"))
            if blk is not None:
                self._open[bi] = blk
                self._rehearse(bi)
            return blk
        blk = self._build(bi, lanes)
        if blk is not None:
            self.blocks[bi] = blk
            self._open_retired.pop(bi, None)   # frozen for good
            if self._open.pop(bi, None) is not None:
                # the open block it replaces (every buffer with a row in
                # its range has frozen: the last group's flush, or a node
                # gone quiet): no plan keeps its planes
                self._plan_memo.clear()
            self.version += 1
        return blk

    def _val_dtype(self):
        """f32 on TPU (matching the Pallas kernels); f64 on CPU backends so
        the portable reference path keeps full double precision."""
        import jax

        from filodb_tpu.ops.grid import on_tpu_backend
        if on_tpu_backend():
            return np.float32
        return np.float64 if jax.config.jax_enable_x64 else np.float32

    def _build(self, bi: int, lanes: int, compress: bool = True,
               why: str = "first"):
        """Host staging + one upload for block ``bi``: a frozen block as
        the ``grid.build`` stage (most of a cold node's set-up), an open
        one (``compress=False``) as ``grid.tail_build``, whose ``why``
        says what made an every-lane build of it necessary (``first``,
        ``width``, ``recovery``)."""
        if not compress:
            with TRACER.stage("grid.tail_build", cpu=True, lanes=lanes,
                              why=why):
                return self._build_block(bi, lanes, False, None)
        with TRACER.stage("grid.build", lanes=lanes, compressed=compress):
            shapes = self._shard.grid_shapes
            if shapes is None:
                return self._build_block(bi, lanes, compress, None)
            # the dataset's other shards build this block now too (the
            # query fanned out): their packs wait for each other and
            # come out with the same class widths
            with shapes.building((self.schema_hash, self.column_id, bi,
                                  lanes)) as agree:
                return self._build_block(bi, lanes, compress, agree)

    def _build_block(self, bi: int, lanes: int, compress: bool, agree):
        g = self.gstep
        stride = self.hb if self.hist else 1
        # block bi holds buckets [bi*BB, bi*BB+BB-1]; bucket c covers
        # (epoch0+(c-1)*g, epoch0+c*g]
        b_lo_ms = self.epoch0 + (bi * BLOCK_BUCKETS - 1) * g  # left edge excl
        b_hi_ms = b_lo_ms + BLOCK_BUCKETS * g                 # right edge incl
        ts_stage = np.zeros((BLOCK_BUCKETS, lanes * stride), np.int32)
        val_stage = np.full((BLOCK_BUCKETS, lanes * stride), np.nan,
                            self._val_dtype())
        dropped_lane = False
        for pid, lane in list(self.lane_of.items()):
            part = self._shard.grid_partition(pid)
            if part is None:
                # A laned partition with no resolvable data (ODP
                # page-evicted, or evicted/purged from memory) must not
                # stay laned: the block cache is keyed only by (bucket,
                # lanes, staged_hi) and page-in does not invalidate
                # blocks, so a cached NaN lane would silently serve
                # "empty" for history that exists on disk (round-4
                # ADVICE, medium).  PRUNE the lane — a re-materialized
                # partition then gets a FRESH lane >= every cached
                # block's staged_hi, forcing a rebuild — AND fail THIS
                # build: an in-flight query whose pre-eviction prep
                # still maps the pid to this lane must fall back to the
                # host path, not read a cached NaN lane.  The next
                # build succeeds (the lane is gone), so a permanent
                # eviction cannot wedge future builds.
                del self.lane_of[pid]
                dropped_lane = True
                continue
            ts, vals = part.read_range(b_lo_ms + 1, b_hi_ms, self.column_id)
            if len(ts) == 0:
                continue
            if self.hist:
                hbk, rows = vals
                if rows.size == 0:
                    continue
                if rows.shape[1] > self.hb:
                    self._disable()             # bucket scheme widened
                    return None
                arr = rows.astype(self._val_dtype())
                if arr.shape[1] < self.hb:
                    # narrower cumulative hist: top bucket IS the total,
                    # edge-pad (same convention as scan_batch)
                    arr = np.pad(arr, ((0, 0), (0, self.hb - arr.shape[1])),
                                 mode="edge")
            elif not isinstance(vals, np.ndarray):
                self._disable()                 # string column
                return None
            else:
                arr = vals
            buckets = (ts - self.epoch0 + g - 1) // g - bi * BLOCK_BUCKETS
            if len(np.unique(buckets)) != len(buckets):
                self._disable()                 # >1 sample per bucket
                return None
            col0 = lane * stride
            ts_stage[buckets, col0:col0 + stride] = \
                (ts - self.epoch0).astype(np.int32)[:, None]
            val_stage[buckets, col0:col0 + stride] = \
                arr if self.hist else arr[:, None]
        if dropped_lane:
            return None
        self.builds += 1
        fin = np.isfinite(val_stage)
        fcnt = fin.sum(axis=0).astype(np.int32)
        fmin = fin.argmax(axis=0).astype(np.int32)
        fmax = (BLOCK_BUCKETS - 1 - fin[::-1].argmax(axis=0)).astype(np.int32)
        fmax[fcnt == 0] = -1
        # per-lane within-bucket offset range over the filled cells:
        # cell (local row r, lane) holds ts_rel in ((c-1)*g, c*g] for
        # global bucket c = bi*BB + r, so phase = ts_rel - (c-1)*g
        cstart = ((np.arange(BLOCK_BUCKETS, dtype=np.int64)
                   + bi * BLOCK_BUCKETS - 1) * g)[:, None]
        ph = ts_stage.astype(np.int64) - cstart
        pmin = np.where(fin, ph, 2**31).min(axis=0).astype(np.int32)
        pmax = np.where(fin, ph, -1).max(axis=0).astype(np.int32)
        dev = self._shard.grid_device      # mesh-pinned; None = default
        # compressed residents (VERDICT r4 #4): drop the ts plane when
        # every lane is uniform-phase (reconstructed on device), and
        # keep the value plane in XOR-class form when it pays.  BOTH
        # forms honor the device-cache-compress kill switch — the flag
        # documents itself as covering ts-plane elision too
        # (storeconfig.py), and an operator reverting a reconstruction
        # bug must actually get decoded planes back
        do_compress = compress and self._shard.config.device_cache_compress
        uniform = do_compress \
            and bool(((pmin == pmax) | (fcnt == 0)).all())
        nbytes = 0
        ts_desc = None
        phase = None
        if uniform:
            ts_dev = None
            phase = np.where(fcnt > 0, pmin, 1).astype(np.int32)
            ts_desc = {"base": int((bi * BLOCK_BUCKETS - 1) * g),
                       "g": int(g),
                       "phase": LEDGER.device_put(phase, dev,
                                                  owner=self.owner,
                                                  fmt="compressed")}
            nbytes += phase.nbytes
        else:
            ts_dev = LEDGER.device_put(ts_stage, dev, owner=self.owner,
                                       fmt="dense")
            nbytes += ts_stage.nbytes
        from filodb_tpu.codecs import xorgrid
        # histogram caches pack at SERIES granularity (stride=hb): a
        # series' bucket columns classify together and stay contiguous
        # in bucket order — the layout contract of the fused hist
        # kernels (ops/grid.py hist_grid_grouped_packed)
        packed = xorgrid.pack_vals(val_stage, phase=phase,
                                   stride=stride, agree=agree) \
            if do_compress else None
        pack_inv = None
        if packed is not None:
            vals_dev = {k: LEDGER.device_put(v, dev, owner=self.owner,
                                             fmt="compressed")
                        for k, v in packed.planes.items()}
            pack_inv = packed.inv
            nbytes += packed.nbytes
        else:
            vals_dev = LEDGER.device_put(val_stage, dev, owner=self.owner,
                                         fmt="dense")
            nbytes += val_stage.nbytes
        blk = _Block(ts_dev, vals_dev,
                     lanes, self._seq, (fmin, fmax, fcnt), (pmin, pmax),
                     staged_hi=self._next_lane, ts_desc=ts_desc,
                     nbytes=nbytes, width=val_stage.shape[1],
                     pack_inv=pack_inv)
        newest = np.where(fin, ts_stage, -1).max(axis=0).astype(np.int64)
        self._seen_hi = max(self._seen_hi, self.epoch0 + int(newest.max()))
        if not compress and not self.hist:
            # an open block that takes appends: the newest row staged a
            # lane, and fill and phase ranges that ``ufunc.at`` can move
            # (an empty lane reads "nothing yet" on both sides)
            blk.hi_ts = np.where(fcnt > 0, self.epoch0 + newest, _NO_ROW_TS)
            fmin[fcnt == 0] = BLOCK_BUCKETS
            pmin[fcnt == 0] = np.iinfo(np.int32).max
        return blk

    # ------------------------------------------------------------ open blocks

    def note_append(self, pid: int, ts, vals, was_empty: bool) -> None:
        """The ingest hook (``partition.on_append`` through the shard):
        rows ``ts`` / ``vals`` (arrays, or one row's scalars) were
        appended to partition ``pid``'s write buffer, which held no row
        before them where ``was_empty``.  Any thread, no lock, O(1) a
        call: the rows wait in ``_pend`` for ``flush_appends`` at the end
        of the batch.  A partition with no lane here is not this cache's
        yet: it is staged when a query first selects it."""
        lane = self.lane_of.get(pid)
        if lane is not None:
            self._pend.append((lane, ts, vals, ts if was_empty else None,
                               self._shard.latest_ingest_ts))

    def note_append_rows(self, pids: list, ts: np.ndarray,
                         vals: np.ndarray, opened: list) -> None:
        """The bulk form of :meth:`note_append`, once a container: row
        ``i`` went to partition ``pids[i]``; the rows at ``opened`` are
        the first of a buffer that held none.  The rows of partitions with
        a lane here wait in ``_pend`` as ONE item."""
        lanes = list(map(self.lane_of.get, pids, itertools.repeat(-1)))
        firsts = [int(ts[i]) for i in opened if lanes[i] >= 0]
        missing = -1 in lanes
        lanes = np.array(lanes, np.int64)
        if missing:
            keep = lanes >= 0
            lanes, ts, vals = lanes[keep], ts[keep], vals[keep]
        if len(lanes):
            self._pend.append((lanes, ts, vals,
                               min(firsts) if firsts else None,
                               self._shard.latest_ingest_ts))

    def flush_appends(self) -> None:
        """What the hook queued, into the open blocks: the ingest thread
        calls it once a batch, BEFORE the epoch bump that makes the
        batch's rows readable (one thread owns the append), so a request
        waits for no more than one batch's append."""
        if self._pend:
            with self._lock:
                self._apply_pending()  # filolint: disable=blocking-under-lock — the append's dispatch under the grid lock is the design: a plan never sees a plane half replaced

    def _apply_pending(self) -> None:  # holds-lock: _lock
        """Fold the queued rows in: the frozen frontier (a buffer that
        went from empty to non-empty may lower it), and the open blocks,
        with host work proportional to the rows queued, never to the
        lanes resident.  Rows of a block that is not open are dropped
        here (the every-lane build reads the write buffers themselves)
        unless the block is provably NEW: it begins at or after the
        newest timestamp the shard had ingested, and this cache had seen,
        before its first row arrived, so it holds nothing but what the
        hook brings and is opened empty, on this thread, with no walk."""
        pend = self._pend
        if not pend:
            return
        items = []
        while True:
            try:
                items.append(pend.popleft())
            except IndexError:
                break
        if self.gstep is None or self.epoch0 is None:
            return
        # (an item's earliest row of a buffer that held none, if any)
        firsts = [int(np.min(it[3])) for it in items if it[3] is not None]
        memo_key, lo = self._frontier
        if firsts and memo_key is not None:
            first = min(firsts)
            self._frontier = (memo_key,
                              first if lo is None else min(lo, first))
        if self.hist:
            # bucket planes take no append: the next plan rebuilds
            self._drop_open("recovery")
            return
        sizes = [np.size(it[1]) for it in items]
        if len(items) == 1 and isinstance(items[0][0], np.ndarray):
            lanes, ts, vals = items[0][:3]      # a container's bulk rows
        else:
            # (one series' rows under its lane, or a container's bulk
            # rows with a lane each)
            lanes = np.concatenate([
                it[0] if isinstance(it[0], np.ndarray)
                else np.full(n, it[0], np.int64)
                for it, n in zip(items, sizes)])
            ts = np.concatenate([np.atleast_1d(it[1]) for it in items])
            vals = np.concatenate([np.atleast_1d(it[2]) for it in items])
        ts = ts.astype(np.int64, copy=False)
        g = self.gstep
        lo, hi = int(ts.min()), int(ts.max())
        seen_before = self._seen_hi
        self._seen_hi = max(seen_before, hi)
        bi_lo, bi_hi = (((t - self.epoch0 + g - 1) // g) // BLOCK_BUCKETS
                        for t in (lo, hi))
        if bi_lo == bi_hi:
            blocks = [(bi_lo, None)]            # every row in one block
        else:
            bis = ((ts - self.epoch0 + g - 1) // g) // BLOCK_BUCKETS
            blocks = [(bi, bis == bi) for bi in np.unique(bis).tolist()]
        for bi, sel in blocks:
            blk = self._open.get(bi)
            if blk is None:
                first = 0 if sel is None else int(np.argmax(sel))
                shard_hi = items[int(np.searchsorted(
                    np.cumsum(sizes), first, side="right"))][4]
                known = max(seen_before, shard_hi,
                            int(ts[:first].max()) if first else -1)
                if bi < 0 or known > self.epoch0 \
                        + (bi * BLOCK_BUCKETS - 1) * g:
                    continue
                blk = self._open_empty(bi)
                if blk is None:
                    continue
            cells = (lanes, ts, vals) if sel is None \
                else (lanes[sel], ts[sel], vals[sel])
            if not self._append_cells(bi, blk, *cells):
                return

    def _open_empty(self, bi: int):  # holds-lock: _lock
        """An open block with nothing in it, made on the device (no
        upload), at the width the resident blocks have."""
        import jax
        import jax.numpy as jnp
        widths = {b.lanes for b in self.blocks.values()} \
            | {b.lanes for b in self._open.values()}
        if len(widths) != 1:
            return None          # no width to agree with: the build's
        lanes = widths.pop()
        dev = self._shard.grid_device
        with jax.default_device(dev):
            ts_dev = jnp.zeros((BLOCK_BUCKETS, lanes), jnp.int32)
            vals_dev = jnp.full((BLOCK_BUCKETS, lanes), jnp.nan,
                                self._val_dtype())
        for arr in (ts_dev, vals_dev):
            LEDGER.track(arr, owner=self.owner, fmt="dense")
        fill = (np.full(lanes, BLOCK_BUCKETS, np.int32),
                np.full(lanes, -1, np.int32), np.zeros(lanes, np.int32))
        phase = (np.full(lanes, np.iinfo(np.int32).max, np.int32),
                 np.full(lanes, -1, np.int32))
        blk = _Block(ts_dev, vals_dev, lanes, self._seq, fill, phase,
                     staged_hi=self._next_lane, width=lanes)
        blk.hi_ts = np.full(lanes, _NO_ROW_TS, np.int64)
        self._open[bi] = blk
        self._open_retired.pop(bi, None)
        self.opened += 1
        self._rehearse(bi)
        return blk

    def _append_cells(self, bi: int, blk: "_Block", lanes: np.ndarray,  # holds-lock: _lock
                      ts: np.ndarray, vals: np.ndarray) -> bool:
        """Write rows (lane, absolute timestamp, value) into open block
        ``bi``, as the ``grid.tail_append`` stage: the fill and phase
        ranges on the host, the cells on the device by
        ``devicestore.tail_append``, ``APPEND_CELLS`` a launch.  A row a
        build already staged (``hi_ts``), or of a lane the block was not
        built with, is skipped; a second row in a lane's bucket breaks the
        layout and disables the cache (False), as in a build."""
        # (few NumPy calls over the rows: each lets the interpreter go,
        # and the ingest thread waits to have it back)
        hi = min(blk.staged_hi, blk.width)
        if len(lanes) and int(lanes.max()) >= hi:
            keep = lanes < hi
            lanes, ts, vals = lanes[keep], ts[keep], vals[keep]
        if len(lanes):
            fresh = ts > blk.hi_ts[lanes]
            if not fresh.all():
                lanes, ts, vals = lanes[fresh], ts[fresh], vals[fresh]
        n = len(lanes)
        if n == 0:
            return True
        with TRACER.stage("grid.tail_append", cpu=True, cells=n) as sp:
            g = self.gstep
            rel = ts - self.epoch0
            # the bucket a row lands in, and its phase in the bucket:
            # ``rel - (bucket - 1) * g``
            bucket, phase = np.divmod(rel + (g - 1), g)
            phase += 1
            rows = bucket - bi * BLOCK_BUCKETS
            # >1 sample per bucket: a row at or before a lane's newest, or
            # two of one lane's rows in a cell (a lane once: none can be)
            if (rows <= blk.fmax[lanes]).any() or (
                    np.bincount(lanes).max() > 1 and len(np.unique(
                        lanes * BLOCK_BUCKETS + rows)) != n):
                self._disable()
                return False
            np.add.at(blk.fcnt, lanes, 1)
            np.minimum.at(blk.fmin, lanes, rows)
            np.maximum.at(blk.fmax, lanes, rows)
            np.minimum.at(blk.pmin, lanes, phase)
            np.maximum.at(blk.pmax, lanes, phase)
            np.maximum.at(blk.hi_ts, lanes, ts)
            dev = self._shard.grid_device
            ts_dev, vals_dev, nbytes = blk.ts, blk.vals, 0
            for a in range(0, n, APPEND_CELLS):
                b = min(a + APPEND_CELLS, n)
                idx = np.full((3, APPEND_CELLS), BLOCK_BUCKETS, np.int32)
                idx[0, :b - a], idx[1, :b - a] = rows[a:b], lanes[a:b]
                idx[2, :b - a] = rel[a:b]
                v = np.zeros(APPEND_CELLS, blk.vals.dtype)
                v[:b - a] = vals[a:b]
                nbytes += idx.nbytes + v.nbytes
                ts_dev, vals_dev = _tail_append(
                    ts_dev, vals_dev,
                    LEDGER.device_put(idx, dev, owner=self.owner,
                                      fmt="scratch"),
                    LEDGER.device_put(v, dev, owner=self.owner,
                                      fmt="scratch"))
                self.appends += 1
            row_lo, row_hi = int(rows.min()), int(rows.max())
            sp.tag(rows=row_hi - row_lo + 1, bytes=nbytes)
        if blk.later:
            self._rehearse_due(bi, blk, row_hi)
        for arr in (ts_dev, vals_dev):
            LEDGER.track(arr, owner=self.owner, fmt="dense")
        blk.ts, blk.vals = ts_dev, vals_dev
        blk.gen += 1
        # a plan made of the planes just replaced pins them: let it go
        # (its key holds an ingest epoch that is about to pass anyway)
        self._plan_memo.discard(lambda plan: blk in plan.segs)
        return True

    def _stage_new_lanes(self, bi: int, blk: "_Block") -> bool:  # holds-lock: _lock
        """Lanes assigned since open block ``blk`` was built (a series a
        query first selects after its rows began to arrive): their rows
        of the block's range, read from their partitions and appended.
        O(lanes new): ``lane_of`` keeps the order they were assigned in."""
        g = self.gstep
        b_lo_ms = self.epoch0 + (bi * BLOCK_BUCKETS - 1) * g
        lanes, ts_l, val_l = [], [], []
        for pid, lane in reversed(self.lane_of.items()):
            if lane < blk.staged_hi:
                break
            part = self._shard.grid_partition(pid)
            if part is None:
                return False           # evicted mid-plan: fall back
            ts, vals = part.read_range(b_lo_ms + 1,
                                       b_lo_ms + BLOCK_BUCKETS * g,
                                       self.column_id)
            if not isinstance(vals, np.ndarray):
                self._disable()
                return False
            lanes.append(np.full(len(ts), lane, np.int64))
            ts_l.append(ts)
            val_l.append(vals)
        blk.staged_hi = self._next_lane
        if not lanes:
            return True
        return self._append_cells(bi, blk, np.concatenate(lanes),
                                  np.concatenate(ts_l).astype(np.int64),
                                  np.concatenate(val_l))

    def _note_recipe(self, plan: "_GridPlan", kind: str, num_groups: int,  # holds-lock: _lock
                     op: str) -> None:
        """Remember what a served plan's program call is made of, by
        SHAPE alone (a handful a dashboard, whatever blocks its spans
        began in): ``_rehearse`` compiles the same call over an open
        block when one is created."""
        key = (kind, plan.q, plan.lane_mult, plan.nrows, num_groups, op)
        if key not in self._recipes:
            self._recipes[key] = None
            if len(self._recipes) > 64:     # the oldest shape goes
                del self._recipes[next(iter(self._recipes))]

    # bucket rows ahead of the row where a span's segment count changes
    # at which the programs of the new count are compiled (16 min at 15 s)
    REHEARSE_LEAD_ROWS = 64

    def _rehearse(self, bi: int) -> None:  # holds-lock: _lock
        """Open block ``bi`` was just created: the served spans will soon
        end in it, with a dense plane beside packed ones and (the phase
        proof leaves open blocks out) a ts plane — program shapes no
        request has met.  They do not depend on how many rows the block
        holds, so they are compiled ahead, on helper threads, by
        launching each remembered call once over the real planes, solo
        and, stacked, at every stack size: NOW with the span's last row
        on the block's first (the imminent shape, the most segments a
        span of that length covers), and with its last row on the
        block's last (the fewest: what the edge changes to once the span
        no longer reaches back into the oldest block) when the block has
        filled to ``REHEARSE_LEAD_ROWS`` short of that change
        (``_rehearse_due``, from the append that writes the row) — so a
        cold node compiles one set while it serves, not two.  A request
        that reaches an open block before its programs are done waits for
        them (``_await_rehearsals``) and compiles nothing itself; once
        the programs exist (the next block's planes have the same shapes)
        a rehearsal is a launch.  The result is dropped; a failure leaves
        the compile to the request."""
        if self.hist or not self._recipes:
            return
        blk = self._open[bi]
        jobs = {}
        for recipe in self._recipes:
            nrows = recipe[3]
            # the row of a block from which a span of ``nrows`` rows no
            # longer reaches the block before its first
            change = (nrows - 1) % BLOCK_BUCKETS
            for last_row, due in (
                    (0, 0), (BLOCK_BUCKETS - 1,
                             max(0, change - self.REHEARSE_LEAD_ROWS))):
                lo = (bi * BLOCK_BUCKETS + last_row - nrows + 1) \
                    // BLOCK_BUCKETS
                jobs.setdefault((recipe, lo), (due, recipe, last_row))
        # (what waits holds no plane: the call is made of the planes the
        # block has when it falls due)
        blk.later = sorted(jobs.values(), key=lambda job: job[0])
        self._rehearse_due(bi, blk, 0)

    def _rehearse_due(self, bi: int, blk: "_Block", row: int) -> None:  # holds-lock: _lock
        """Hand the helpers every call of open block ``bi`` that falls
        due at bucket row ``row`` or before it; the rest wait for the
        append that writes their row."""
        due = [job for job in blk.later if job[0] <= row]
        if not due:
            return
        blk.later = [job for job in blk.later if job[0] > row]
        calls = []
        for _at, (kind, q, lane_mult, nrows, num_groups, op), last_row in due:
            end = bi * BLOCK_BUCKETS + last_row
            c0 = end - nrows + 1
            lo = c0 // BLOCK_BUCKETS
            segs = [self._open.get(b) or self.blocks.get(b)
                    for b in range(lo, bi)] + [blk]
            if c0 < 0 or any(b is None or b.lanes != blk.lanes
                             for b in segs):
                continue            # no such span on this node (yet)
            ts_parts = () if q.op in TS_FREE_OPS \
                else tuple(b.ts_seg for b in segs)
            calls.append((kind, ts_parts, tuple(b.vals for b in segs),
                          c0 - lo * BLOCK_BUCKETS,
                          (end - (q.nsteps - 1) * q.stride) * q.gstep_ms,
                          dict(q=q, lanes=lane_mult, nrows=nrows),
                          num_groups, op, blk.width))
        # the ts-streaming shapes first: they take longest to compile
        calls.sort(key=lambda call: not call[1])
        batcher = getattr(self._shard, "query_batcher", None)
        sizes = batcher.stack_sizes() \
            if batcher is not None and batcher.enabled else []
        pool = _rehearsers()
        self._rehearsals = [f for f in self._rehearsals if not f.done()] \
            + [pool.submit(_rehearse_call, call, stack)
               for stack in [None] + sizes for call in calls]

    def _await_rehearsals(self, plan: "_GridPlan") -> None:
        """A plan that reads an open block whose programs are still being
        compiled waits for the helpers here, outside every lock, and
        compiles nothing itself (stage ``grid.rehearse_wait``: only where
        a request arrived within seconds of a block's creation on a node
        that had never served an open block)."""
        pending = self._rehearsals
        if not pending or not any(b.hi_ts is not None for b in plan.segs):
            return
        pending = [f for f in pending if not f.done()]
        if pending:
            from concurrent.futures import wait
            with TRACER.stage("grid.rehearse_wait", leaf=False,
                              programs=len(pending)):
                wait(pending)

    def _reclaim(self, target_bytes: int,  # holds-lock: _lock
                 keep: set) -> int:
        """Oldest-first reclaim down to ``target_bytes`` (the reference's
        reclaim-on-demand over time-ordered block lists).  Caller holds
        the lock.  Returns bytes freed."""
        freed = 0
        evicted = 0
        while self.bytes_resident > target_bytes and len(self.blocks) > 1:
            victims = [bi for bi in sorted(self.blocks) if bi not in keep]
            if not victims:
                break
            freed += self.blocks[victims[0]].nbytes
            del self.blocks[victims[0]]
            self.evictions += 1
            evicted += 1
        if evicted:
            LEDGER.note_eviction(self.owner, "budget_overflow", n=evicted,
                                 nbytes=freed)
        if freed:
            # memoized plans hold strong block refs: drop them so the
            # reclaim actually releases HBM
            self._plan_memo.clear()
        return freed

    def _evict(self, keep: set) -> None:
        self._reclaim(self.budget, keep)

    def ensure_headroom(self, frac: float) -> int:
        """Proactive reclaim down to ``(1-frac)`` of the budget, run OFF
        the query path (the shard calls it from flush tasks) so queries
        rarely pay inline eviction — the reference's background headroom
        task (BlockManager.scala ensureHeadroomPercentAvailable :142)."""
        with self._lock:
            return self._reclaim(int(self.budget * (1.0 - frac)), set())
