"""Aligned-grid leaf kernels: the memory-bound serving fast path.

The device chunk store lays frozen chunks out as a **time-major bucket
grid**: ``ts/vals [B, S]`` where column *s* is a series (lanes) and row
*c* is a time bucket (sublanes), with the layout invariant that the
sample in row ``c`` has ``ts in (t0 + (c-1)*gstep, t0 + c*gstep]`` and
missing buckets hold NaN.  PromQL range queries evaluate on a regular
step grid, so when ``window % gstep == 0`` and the query steps land on
bucket edges, every window covers exactly ``K = window//gstep`` full
buckets — **static sublane slices**, no searchsorted, no gathers.

This replaces the reference's per-window row iteration
(reference: query/exec/rangefn/RangeFunction.scala:102-161 addChunks +
binarySearch; AggrOverRangeVectors.scala:161-277 fastReduce) with one
fused pass: counter correction (prefix scan) -> per-window first/last
finite sample extraction (K select passes) -> Prometheus extrapolated
rate (RateFunctions.scala:37-80), all in VMEM.  The grouped sum/count
reduction follows as an XLA one-hot reduce inside the serving program
(memstore/devicestore.py ``_grouped_reduce_impl``).

Two implementations with identical semantics:

- :func:`rate_grid` (decoded planes) / :func:`rate_grid_packed`
  (XOR-class packed planes, decode fused in) — Pallas TPU kernels;
  :func:`rate_grid_auto` / :func:`rate_grid_batch_impl` are what the
  serving programs trace.
- :func:`rate_grid_ref` — pure-XLA reference (runs everywhere; used on
  CPU and as the numerical oracle in tests).

Layout contract (enforced by the caller / device store):
- ``ts`` int32 milliseconds relative to an epoch the caller also
  subtracts from the query steps (absolute ms overflow int32).
- query step == ``gstep`` (the dashboard case; others fall back to
  :mod:`filodb_tpu.ops.windows`), ``window == K * gstep``.
- the caller slices the stored grid so that window ``t`` (ending at
  ``steps0 + t*gstep``) covers input rows ``[t, t+K-1]`` — i.e. row 0
  is the first bucket of the first window.  Mosaic requires dynamic
  sublane offsets to be 8-aligned, so the per-query row offset is
  applied host-side (an XLA ``dynamic_slice``), keeping ONE compiled
  kernel per (T, K) signature; ``steps0`` stays a traced SMEM scalar.
  The packed kernels decode a whole block in VMEM and take their row
  offset as a traced SMEM scalar too: a sublane rotate by it brings the
  query's rows to the top (:func:`_decode_rows`).
- counter correction runs from input row 0, i.e. from the start of the
  scanned range — same scope as the general path, which corrects from
  the first scanned row (filodb_tpu/ops/windows.py counter_correct).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from filodb_tpu.utils import devicewatch

_IBIG = 2**30


def on_tpu_backend() -> bool:
    """One shared predicate for every formulation switch: the one-hot /
    Pallas paths exist for the TPU backend; anything else takes the
    portable gathers."""
    return jax.default_backend() == "tpu"


def _x32(fn):
    """Trace a Pallas entry point with 32-bit defaults, whatever the
    process-wide ``jax_enable_x64`` says.  The server turns x64 on
    (standalone.main: epoch-ms step grids are int64 on the general
    path), and under x64 every Python int in a kernel body, roll shift
    or BlockSpec index map becomes an i64 that Mosaic refuses
    ('tpu.dynamic_rotate' operand must be 32-bit; 'func.return' (i64)).
    The kernels' operands are int32/f32 by the layout contract, so
    nothing is narrowed — only the weak-typed scalars are."""
    @functools.wraps(fn)
    def traced(*args, **kwargs):
        with jax.enable_x64(False):
            return fn(*args, **kwargs)
    return traced


def _win_slicer(q: "GridQuery", ns: int):
    """Window-indexed slice: row d of window t is input row t*stride+d,
    so slicing at offset d with row-stride q.stride yields the [T, ns]
    tile of every window's d-th row — static slices, no gathers.

    Only the portable reference path takes stride here: Mosaic cannot
    lower a strided sublane slice (vector.extract_strided_slice requires
    stride 1), so the Pallas wrappers run the stride-1 fine grid and
    subsample OUTSIDE the kernel (see _fine_query)."""
    T = q.nsteps
    if q.stride == 1:
        return lambda x, d: jax.lax.slice(x, (d, 0), (d + T, ns))
    return lambda x, d: jax.lax.slice(
        x, (d, 0), (d + (T - 1) * q.stride + 1, ns), (q.stride, 1))


def _fine_query(q: "GridQuery") -> "GridQuery":
    """The stride-1 query computing every bucket-edge window of q's
    range: q's window t is fine window t*stride."""
    return q._replace(nsteps=(q.nsteps - 1) * q.stride + 1, stride=1)


def _rows_needed(q: "GridQuery") -> int:
    return (q.nsteps - 1) * q.stride + q.kbuckets


class GridQuery(NamedTuple):
    """Static kernel configuration for one (shape, query-grid) signature.

    ``op`` selects the fused window function:
      "rate" / "increase"  — counter correction + Prometheus extrapolation
      "sum" / "count" / "avg" / "min" / "max"
                           — the *_over_time family (no correction)
      "last"               — last_over_time / the instant-selector
                             staleness lookback
    ``is_rate`` is kept for backward compatibility with callers that
    predate ``op``; it is honored only when op is "rate"/"increase".

    ``dense`` asserts the **dense-lane contract**: over the used rows
    ``[0, (nsteps-1)*stride + kbuckets)`` every lane is either finite in
    ALL rows or finite in NONE (rows beyond the used range are
    unconstrained).  Regular scrapes with no missed samples — the
    dominant production shape and the QueryInMemoryBenchmark shape —
    satisfy it.  The kernel then skips the NaN-hole forward-fill and
    collapses the K-pass window loops to two static slices (first/last
    sample of each window are rows ``t`` and ``t+K-1``), roughly
    halving VPU work.  The caller must PROVE the contract (the device
    store tracks per-block, per-lane fill ranges); setting it on
    non-conforming data yields wrong results, not an error.
    """

    nsteps: int       # T output steps
    kbuckets: int     # K = window // gstep buckets per window
    gstep_ms: int     # bucket width (== query step when stride == 1)
    is_rate: bool = True   # rate() vs increase() (when op is rate-like)
    op: str = "rate"
    dense: bool = False
    # scalar function arguments (predict_linear's horizon seconds;
    # holt_winters' smoothing factors); static, so each distinct value
    # compiles its own kernel — dashboards use a handful of fixed values
    farg: float = 0.0
    farg2: float = 0.0
    # query step = stride * gstep: window t covers input rows
    # [t*stride, t*stride + K - 1].  Dashboards commonly query with a
    # coarser step than the scrape cadence (step 5m over 1m data);
    # strided static slices keep those on the fast path without
    # computing the skipped windows.
    stride: int = 1


def _correct_and_mask(ts, vals, roll):
    """Counter correction (prefix formulation of the reference's
    CorrectionMeta threading) + finite mask, on a [B, L] tile.

    A reset must be detected against the previous *finite* sample — a
    missed scrape leaves a NaN bucket, and comparing against NaN would
    silently skip the correction (the dense general path has no holes).
    The previous finite value is a log-step forward-fill scan."""
    nb = ts.shape[0]
    fin = jnp.isfinite(vals)
    row = jax.lax.broadcasted_iota(jnp.int32, vals.shape, 0)
    # forward fill: ffill[r] = last finite value at row <= r.  The mask
    # scans as int32 — Mosaic's dynamic_rotate has no i1 lowering
    # ("Rotate with non-32-bit data"), so never roll a bool tile.
    fv, fm = vals, fin.astype(jnp.int32)
    sh = 1
    while sh < nb:
        shifted_v, shifted_m = roll(fv, sh), roll(fm, sh)
        in_range = row >= sh
        fv = jnp.where(fm > 0, fv, jnp.where(in_range, shifted_v, fv))
        fm = fm | jnp.where(in_range, shifted_m, 0)
        sh *= 2
    prev = roll(fv, 1)                         # last finite at row <= r-1
    return fin, _apply_reset_correction(vals, prev, row, roll)


def _apply_reset_correction(vals, prev, row, roll):
    """Given each row's previous sample, add the running sum of counter
    drops (prefix formulation of the reference's CorrectionMeta
    threading)."""
    nb = vals.shape[0]
    prev = jnp.where(row == 0, vals, prev)
    drop = jnp.where(vals < prev, prev, 0.0)   # NaN compares are False
    acc = drop
    sh = 1
    while sh < nb:
        acc = jnp.where(row >= sh, acc + roll(acc, sh), acc)
        sh *= 2
    return vals + acc


def _correct_dense(vals, roll):
    """Counter correction under the dense-lane contract: the previous
    sample IS the previous row (no holes), so the forward-fill scan
    disappears — one roll feeds the shared reset-correction scan."""
    row = jax.lax.broadcasted_iota(jnp.int32, vals.shape, 0)
    return _apply_reset_correction(vals, roll(vals, 1), row, roll)


# above this row count the [B, B] triangular matmul's O(B^2) work and
# VMEM footprint overtake the O(B log B) roll-scan it replaces
_MXU_CORR_MAX_ROWS = 256


def _correct_dense_mxu(vals):
    """Dense counter correction with the prefix sum on the MXU: the
    cumulative drop is a lower-triangular ones-matmul over the per-row
    drops, replacing the log2(B) VPU roll-scan (measured +13% on the
    headline kernel; the [B, B] triangle is generated in-register).
    Row 0 has no previous sample — its (bogus, rolled-from-last-row)
    drop is excluded by zeroing the triangle's first column instead of
    masking the drop tile, saving an iota+where pass."""
    nb = vals.shape[0]
    prev = pltpu.roll(vals, 1, axis=0)
    drop = jnp.where(vals < prev, prev, 0.0)   # never NaN: prev or 0.0
    r1 = jax.lax.broadcasted_iota(jnp.int32, (nb, nb), 0)
    r2 = jax.lax.broadcasted_iota(jnp.int32, (nb, nb), 1)
    tri = ((r2 <= r1) & (r2 > 0)).astype(jnp.float32)
    acc = jax.lax.dot(tri, drop, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)
    return vals + acc


def _correct_dense_auto(vals, roll):
    """MXU prefix for short blocks; the roll-scan for tall ones (the
    K-free dense path admits up to MAX_GRID_ROWS=1024 rows, where the
    [B, B] matmul would do ~100x the arithmetic)."""
    if vals.shape[0] <= _MXU_CORR_MAX_ROWS:
        return _correct_dense_mxu(vals)
    return _correct_dense(vals, roll)


def _corr_v1_delta_banded(vals, q: GridQuery, roll):
    """Corrected window-start values and window deltas via ONE banded
    lower-triangular matmul — the MXU correction-prefix trick extended
    so ``vcorr`` is never materialized:

        v1[t]    = vals[t]       + sum_{0 < c <= t}       drop[c]
        delta[t] = vals[t+K-1] - vals[t] + sum_{t < c <= t+K-1} drop[c]

    Both prefix/band sums are rows of a [2T, B] 0/1 matrix applied to
    the [B, L] drop plane in one ``dot``, replacing the [B, B]
    triangular matmul + two sublane slices + subtract.  With 2T < B
    (the K-heavy dashboard shape: long windows, few steps) this is
    strictly less MXU work AND two fewer [B, L] VMEM passes; the
    caller keeps the [B, B] formulation otherwise."""
    nb = vals.shape[0]
    T, K = q.nsteps, q.kbuckets
    prev = roll(vals, 1)
    drop = jnp.where(vals < prev, prev, 0.0)   # row 0 excluded by c > 0
    r = jax.lax.broadcasted_iota(jnp.int32, (2 * T, nb), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (2 * T, nb), 1)
    t = jnp.where(r < T, r, r - T)
    lo = jnp.where(r < T, 0, t)                # c > lo
    hi = jnp.where(r < T, t, t + K - 1)        # c <= hi
    m = ((c > lo) & (c <= hi)).astype(jnp.float32)
    acc = jax.lax.dot(m, drop, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)
    sl = _win_slicer(q, vals.shape[1])
    v1 = sl(vals, 0) + acc[:T]
    delta = (sl(vals, K - 1) - sl(vals, 0)) + acc[T:]
    return v1, delta


# ops with a dense+uniform-phase kernel: the ts plane is never streamed;
# per-lane scrape phase (one row) reconstructs the extrapolation geometry
PHASE_OPS = frozenset(("rate", "increase", "delta"))


def _phase_block(phase_row, vals, q: GridQuery, roll, mxu: bool):
    """rate/increase/delta under dense + UNIFORM-PHASE: every live lane
    is scraped at a constant offset ``phase in (0, gstep]`` within its
    bucket, so ``t1 - window_start == phase`` and ``window_end - t2 ==
    gstep - phase`` are per-lane constants and ``sampled == (K-1)*gstep``
    exactly.  The reference extrapolation (RateFunctions.scala:37-80)
    then collapses:

    - ``avg_dur == gstep`` and both boundary gaps are < 1.1*gstep, so
      the threshold selects are always-true and vanish;
    - the counter zero-point clamp's divide cancels against the final
      ``delta *`` multiply: ``delta * (sampled*v1/delta * scale) ==
      sampled*v1*scale`` — the kernel is divide-free;
    - ``delta > 0`` is implied by ``v1 >= 0 & sampled*v1 < phase*delta``
      (phase > 0), dropping a compare.

    Liveness is row-0-derived (dense), so masks and the grouped count
    are [1, ns] rows, not [T, ns] tiles."""
    out, live_row = _phase_block_raw(phase_row, vals, q, roll, mxu)
    return jnp.where(live_row, out, jnp.nan)


def _phase_block_raw(phase_row, vals, q: GridQuery, roll, mxu: bool):
    """Unmasked phase-mode compute: returns ``(out [T, ns], live_row
    [1, ns])`` so grouped callers can mask-to-zero without a second
    [T, ns] pass.  ``out`` is finite wherever ``live_row`` holds (dense:
    K >= 2 samples, strictly increasing ts => sampled > 0)."""
    ns = vals.shape[1]
    dt = vals.dtype
    sl = _win_slicer(q, ns)
    K, g = q.kbuckets, q.gstep_ms
    live_row = jnp.isfinite(vals[0:1, :])
    if q.op == "delta":
        v1 = sl(vals, 0)
        delta = sl(vals, K - 1) - v1
    elif mxu and q.stride == 1 and vals.shape[0] <= _MXU_CORR_MAX_ROWS \
            and 2 * q.nsteps < vals.shape[0]:
        # K-heavy shape: the banded formulation does less MXU work than
        # the [B, B] prefix and skips materializing vcorr entirely
        v1, delta = _corr_v1_delta_banded(vals, q, roll)
    else:
        vcorr = _correct_dense_auto(vals, roll) if mxu \
            else _correct_dense(vals, roll)
        v1 = sl(vcorr, 0)
        delta = sl(vcorr, K - 1) - v1
    sampled = jnp.asarray((K - 1) * g * 1e-3, dt)
    if q.op == "delta":
        # no zero-clamp for gauges: extrap == sampled + gstep == K*gstep
        return delta * jnp.asarray(K / (K - 1), dt), live_row
    phase_s = phase_row.astype(dt) * jnp.asarray(1e-3, dt)       # [1, ns]
    g_s = jnp.asarray(g * 1e-3, dt)
    is_rate = q.op == "rate" and q.is_rate
    scale = jnp.asarray(1e3 / (K * g), dt) / sampled if is_rate \
        else jnp.asarray(1.0, dt) / sampled
    end_sc = (sampled + g_s - phase_s) * scale                   # [1, ns]
    sv1 = sampled * v1
    pd = phase_s * delta
    clamp = (sv1 < pd) & (v1 >= 0)
    start_num = jnp.where(clamp, sv1, pd)      # == delta * start_dur
    return delta * end_sc + start_num * scale, live_row


def phase_eligible(q: GridQuery) -> bool:
    """Can this query use the uniform-phase kernels (given a proven
    phase vector)?  K >= 2: the collapsed extrapolation divides by
    (K-1); the ts path's nf>=2 guard yields NaN for K=1, so routing
    K=1 there keeps semantics.  The device store must use THIS
    predicate when deciding to drop the ts plane from a plan — the
    kernel wrappers fall back to ts mode under the same condition.
    stride > 1 runs the stride-1 fine query inside the wrappers, so
    eligibility doesn't depend on it."""
    return q.dense and q.op in PHASE_OPS and q.kbuckets >= 2


def _phase_mode(q: GridQuery, phase) -> bool:
    return phase is not None and phase_eligible(q)


def _window_stats_dense(ts, vals, vcorr, q: GridQuery):
    """Window stats under the dense-lane contract: window ``t`` covers
    rows ``[t, t+K-1]`` and a live lane has a sample in every row, so
    first/last are static slices and the finite count is ``K`` exactly
    (0 for empty lanes)."""
    ns = vals.shape[1]
    dt = vcorr.dtype
    sl = _win_slicer(q, ns)
    live = jnp.isfinite(sl(vals, 0))
    nf = jnp.asarray(q.kbuckets, dt) * live.astype(dt)
    return nf, sl(ts, 0), sl(ts, q.kbuckets - 1), sl(vcorr, 0), \
        sl(vcorr, q.kbuckets - 1)


def _window_stats(ts, fin, vcorr, q: GridQuery):
    """First/last finite sample (ts and corrected value) + finite count
    per window, via K forward/backward select passes over static
    sublane slices: window t covers rows [t*stride, t*stride+K-1]."""
    ns = vcorr.shape[1]
    T = q.nsteps
    dt = vcorr.dtype
    sl = _win_slicer(q, ns)
    shape = (T, ns)
    nf = jnp.zeros(shape, dt)
    t2 = jnp.full(shape, _IBIG, ts.dtype)
    v2 = jnp.full(shape, jnp.nan, dt)
    for d in range(q.kbuckets):            # forward: last finite wins
        fd = sl(fin, d)
        nf = nf + fd.astype(dt)
        t2 = jnp.where(fd, sl(ts, d), t2)
        v2 = jnp.where(fd, sl(vcorr, d), v2)
    t1 = jnp.full(shape, _IBIG, ts.dtype)
    v1 = jnp.full(shape, jnp.nan, dt)
    for d in range(q.kbuckets - 1, -1, -1):  # reverse: first finite wins
        fd = sl(fin, d)
        t1 = jnp.where(fd, sl(ts, d), t1)
        v1 = jnp.where(fd, sl(vcorr, d), v1)
    return nf, t1, t2, v1, v2


def _extrapolate(nf, t1, t2, v1, v2, steps0, q: GridQuery):
    """Prometheus extrapolatedRate on [T, L] tiles (reference:
    RateFunctions.scala:37-80; same math as windows._extrapolated)."""
    ns = nf.shape[1]
    dt = v1.dtype
    window = q.kbuckets * q.gstep_ms
    tcol = jax.lax.broadcasted_iota(jnp.int32, (q.nsteps, ns), 0)
    hi = (steps0 + tcol * jnp.int32(q.gstep_ms * q.stride)).astype(dt)
    lo = hi - jnp.asarray(window, dt)
    t1f = t1.astype(dt)
    t2f = t2.astype(dt)
    dur_start = (t1f - lo) / 1000.0
    dur_end = (hi - t2f) / 1000.0
    sampled = (t2f - t1f) / 1000.0
    avg_dur = sampled / jnp.maximum(nf - 1.0, 1.0)
    delta = v2 - v1
    if q.op != "delta":    # counter zero-point clamp (rate/increase only)
        dur_zero = sampled * v1 / jnp.where(delta == 0, 1.0, delta)
        clamp = (delta > 0) & (v1 >= 0) & (dur_zero < dur_start)
        dur_start = jnp.where(clamp, dur_zero, dur_start)
    thresh = avg_dur * 1.1
    extrap = (sampled + jnp.where(dur_start < thresh, dur_start, avg_dur / 2.0)
              + jnp.where(dur_end < thresh, dur_end, avg_dur / 2.0))
    scaled = delta * extrap / jnp.where(sampled == 0, 1.0, sampled)
    # rate divides by window seconds; increase does not.  op is
    # authoritative ("increase" must never divide); is_rate only
    # disambiguates legacy callers that left op at its "rate" default.
    if q.op == "rate" and q.is_rate:
        scaled = scaled / (jnp.asarray(window, dt) / 1000.0)
    return jnp.where((nf >= 2) & (sampled > 0), scaled, jnp.nan)


def _instant_pair_block(ts, vals, q: GridQuery):
    """irate/idelta under the dense contract: the window's last two
    samples ARE its last two rows (reference: IRateFunction /
    windows._instant_pair).  K-free — two static slices.  The counter
    correction between ADJACENT samples collapses to the pair itself:
    vcorr2 - vcorr1 = v2 - v1 + (v1 if v2 < v1 else 0) = v2 on a reset,
    so no prefix scan is needed."""
    if not q.dense:
        raise ValueError(f"grid op {q.op} requires the dense contract")
    ns = vals.shape[1]
    dt = vals.dtype
    K = q.kbuckets
    sl = _win_slicer(q, ns)
    if K < 2:
        return jnp.full(((q.nsteps), ns), jnp.nan, dt)
    v2, v1 = sl(vals, K - 1), sl(vals, K - 2)
    t2, t1 = sl(ts, K - 1), sl(ts, K - 2)
    live = jnp.isfinite(v2)
    delta = v2 - v1
    if q.op == "irate":
        delta = jnp.where(v2 < v1, v2, delta)   # adjacent-pair reset
    dt_s = (t2 - t1).astype(dt) / 1000.0
    # the reference's shared instant-pair semantics drop a zero
    # sampledInterval for idelta and irate alike (ADVICE r2)
    if q.op == "idelta":
        return jnp.where(live & (dt_s > 0), delta, jnp.nan)
    return jnp.where(live & (dt_s > 0), delta / dt_s, jnp.nan)


def _agg_block_dense(ts, vals, q: GridQuery):
    """The *_over_time family under the dense-lane contract: live lanes
    have a sample in every row, so the per-slice finite masks vanish —
    NaN in empty lanes propagates through the accumulation and the
    single ``live`` mask finishes the job."""
    ns = vals.shape[1]
    dt = vals.dtype
    sl = _win_slicer(q, ns)
    if q.op == "last":
        return sl(vals, q.kbuckets - 1)
    live = jnp.isfinite(sl(vals, 0))
    if q.op == "count":
        return jnp.where(live, jnp.asarray(q.kbuckets, dt), jnp.nan)
    if q.op in ("changes", "resets"):
        # consecutive-row pairs fully inside the window (the reference's
        # pair semantics: windows.changes_over_time / resets_over_time)
        c = jnp.zeros(live.shape, dt)
        prev = sl(vals, 0)
        for d in range(1, q.kbuckets):
            cur = sl(vals, d)
            c = c + ((cur != prev) if q.op == "changes"
                     else (cur < prev)).astype(dt)
            prev = cur
        return jnp.where(live, c, jnp.nan)
    if q.op in ("sum", "avg"):
        s = sl(vals, 0)
        for d in range(1, q.kbuckets):
            s = s + sl(vals, d)
        if q.op == "avg":
            s = s / jnp.asarray(q.kbuckets, dt)
        return jnp.where(live, s, jnp.nan)
    m = sl(vals, 0)
    for d in range(1, q.kbuckets):
        m = (jnp.minimum if q.op == "min" else jnp.maximum)(m, sl(vals, d))
    return jnp.where(live, m, jnp.nan)


def _agg_block(ts, vals, q: GridQuery):
    """The *_over_time family on the aligned grid: no correction, no
    forward fill — K static sublane slices accumulate directly
    (reference: AggrOverTimeFunctions.scala sum/count/avg/min/max/last)."""
    if q.dense and q.op not in ("stddev", "stdvar"):
        return _agg_block_dense(ts, vals, q)
    if q.op in DENSE_ONLY_OPS:
        raise ValueError(f"grid op {q.op} requires the dense contract")
    ns = vals.shape[1]
    T = q.nsteps
    dt = vals.dtype
    fin = jnp.isfinite(vals)
    sl = _win_slicer(q, ns)
    shape = (T, ns)
    if q.op == "last":
        v2 = jnp.full(shape, jnp.nan, dt)
        for d in range(q.kbuckets):          # forward: last finite wins
            fd = sl(fin, d)
            v2 = jnp.where(fd, sl(vals, d), v2)
        return v2
    if q.op in ("stddev", "stdvar"):
        n, _mean, var = _masked_moments(vals, fin, sl, q.kbuckets, dt)
        var = jnp.where(n > 0, var, jnp.nan)
        return jnp.sqrt(var) if q.op == "stddev" else var
    s = jnp.zeros(shape, dt)
    c = jnp.zeros(shape, dt)
    mn = jnp.full(shape, jnp.inf, dt)
    mx = jnp.full(shape, -jnp.inf, dt)
    for d in range(q.kbuckets):
        fd = sl(fin, d)
        vd = sl(vals, d)
        c = c + fd.astype(dt)
        if q.op in ("sum", "avg"):
            s = s + jnp.where(fd, vd, 0.0)
        elif q.op == "min":
            mn = jnp.minimum(mn, jnp.where(fd, vd, jnp.inf))
        elif q.op == "max":
            mx = jnp.maximum(mx, jnp.where(fd, vd, -jnp.inf))
    if q.op == "count":
        return jnp.where(c > 0, c, jnp.nan)
    if q.op == "avg":
        return jnp.where(c > 0, s / jnp.maximum(c, 1.0), jnp.nan)
    if q.op == "min":
        return jnp.where(jnp.isfinite(mn), mn, jnp.nan)
    if q.op == "max":
        return jnp.where(jnp.isfinite(mx), mx, jnp.nan)
    return jnp.where(c > 0, s, jnp.nan)   # sum


def _linreg_block(ts, vals, steps0, q: GridQuery):
    """Least-squares slope/forecast over each window (reference:
    windows._linreg / Prometheus linearRegression with interceptTime =
    the range end).  x is seconds relative to the window end, recentered
    by +W/2 during accumulation so the f32 var/cov differences don't
    cancel catastrophically (the slope is shift-invariant)."""
    ns = vals.shape[1]
    dt = vals.dtype
    K = q.kbuckets
    sl = _win_slicer(q, ns)
    fin = jnp.isfinite(vals)
    tcol = jax.lax.broadcasted_iota(jnp.int32, (q.nsteps, ns), 0)
    hi = (steps0 + tcol * jnp.int32(q.gstep_ms * q.stride)).astype(dt)
    w_s = q.kbuckets * q.gstep_ms / 1000.0
    shift = jnp.asarray(w_s / 2.0, dt)
    n = jnp.zeros(hi.shape, dt)
    sx = jnp.zeros(hi.shape, dt)
    sy = jnp.zeros(hi.shape, dt)
    sxx = jnp.zeros(hi.shape, dt)
    sxy = jnp.zeros(hi.shape, dt)
    for d in range(K):
        fd = sl(fin, d)
        x = (sl(ts, d).astype(dt) - hi) / 1000.0 + shift
        y = sl(vals, d)
        fdt = fd.astype(dt)
        x = jnp.where(fd, x, 0.0)
        y = jnp.where(fd, y, 0.0)
        n = n + fdt
        sx = sx + x
        sy = sy + y
        sxx = sxx + x * x
        sxy = sxy + x * y
    nsafe = jnp.maximum(n, 1.0)
    cov = sxy - sx * sy / nsafe
    var = sxx - sx * sx / nsafe
    slope = cov / jnp.where(var == 0, 1.0, var)
    ok = (n >= 2) & (var > 0)
    if q.op == "deriv":
        return jnp.where(ok, slope, jnp.nan)
    # intercept at x=0 of the ORIGINAL axis (window end): undo the shift
    intercept = sy / nsafe - slope * (sx / nsafe - shift)
    out = intercept + slope * jnp.asarray(q.farg, dt)
    return jnp.where(ok, out, jnp.nan)


def _masked_moments(vals, fin, sl, K, dt):
    """Per-window (n, mean, var), centered on the per-lane grand mean
    exactly like windows.stdvar_stddev (the centering defeats the
    E[x^2]-E[x]^2 cancellation; variance itself is center-invariant).
    In f32 the device and host paths agree to ~1e-4 relative
    (summation-order rounding) — exact in the f64 reference."""
    nall = jnp.maximum(fin.sum(axis=0, keepdims=True), 1).astype(dt)
    center = jnp.where(fin, vals, 0.0).sum(axis=0, keepdims=True) / nall
    x = vals - center
    s1 = None
    s2 = None
    n = None
    for d in range(K):
        fd = sl(fin, d)
        xd = jnp.where(fd, sl(x, d), 0.0)
        fdt = fd.astype(dt)
        s1 = xd if s1 is None else s1 + xd
        s2 = xd * xd if s2 is None else s2 + xd * xd
        n = fdt if n is None else n + fdt
    nsafe = jnp.maximum(n, 1.0)
    mean_x = s1 / nsafe
    var = jnp.maximum(s2 / nsafe - mean_x * mean_x, 0.0)
    return n, center + mean_x, var   # mean: [1,ns]+[T,ns] broadcasts


def _zscore_block(ts, vals, q: GridQuery):
    """(last - mean) / stddev over the window (reference ZScoreChunked /
    windows.z_score, incl. the sd == 0 / n < 2 -> NaN rules)."""
    ns = vals.shape[1]
    dt = vals.dtype
    K = q.kbuckets
    sl = _win_slicer(q, ns)
    fin = jnp.isfinite(vals)
    n, mean, var = _masked_moments(vals, fin, sl, K, dt)
    sd = jnp.sqrt(var)
    lastv = None
    for d in range(K):
        fd = sl(fin, d)
        vd = sl(vals, d)
        lastv = jnp.where(fd, vd, jnp.nan if lastv is None else lastv)
    out = (lastv - mean) / jnp.where(sd == 0, 1.0, sd)
    return jnp.where((n >= 2) & (sd > 0), out, jnp.nan)


def _batcher_pairs(K: int) -> list:
    """Batcher odd-even mergesort compare-exchange pairs for K inputs —
    a data-independent sorting network generated at trace time."""
    pairs = []
    p = 1
    while p < K:
        k = p
        while k >= 1:
            for j in range(k % p, K - k, 2 * k):
                for i in range(0, min(k, K - j - k)):
                    if (i + j) // (2 * p) == (i + j + k) // (2 * p):
                        pairs.append((i + j, i + j + k))
            k //= 2
        p *= 2
    return pairs


def _sort_tiles(tiles: list) -> list:
    out = list(tiles)
    for a, b in _batcher_pairs(len(out)):
        lo = jnp.minimum(out[a], out[b])
        hi = jnp.maximum(out[a], out[b])
        out[a], out[b] = lo, hi
    return out


def _interp_rank(sorted_tiles: list, phi: float):
    """Linear-interpolated quantile over K sorted tiles: rank indices
    are STATIC for a static (phi, K) — two tile reads, no gathers.
    Matches jnp.nanquantile's linear method at n == K."""
    import math
    K = len(sorted_tiles)
    if math.isnan(phi):
        return jnp.full_like(sorted_tiles[0], jnp.nan)
    # Prometheus returns +Inf/-Inf for out-of-range phi (±Inf included)
    # rather than clamping (reference QuantileOverTimeFunction); mask to
    # live lanes happens in the caller
    if phi > 1.0:
        return jnp.full_like(sorted_tiles[0], jnp.inf)
    if phi < 0.0:
        return jnp.full_like(sorted_tiles[0], -jnp.inf)
    r = phi * (K - 1)
    lo_i, hi_i = int(math.floor(r)), int(math.ceil(r))
    frac = r - lo_i
    if lo_i == hi_i:
        return sorted_tiles[lo_i]
    return sorted_tiles[lo_i] * (1.0 - frac) + sorted_tiles[hi_i] * frac


def _sort_ops_block(ts, vals, q: GridQuery):
    """quantile_over_time / mad_over_time under the dense contract via a
    compile-time sorting network over the K window tiles (reference:
    QuantileOverTimeChunkedFunction / MedianAbsoluteDeviationOverTime)."""
    if not q.dense:
        raise ValueError(f"grid op {q.op} requires the dense contract")
    ns = vals.shape[1]
    K = q.kbuckets
    sl = _win_slicer(q, ns)
    tiles = [sl(vals, d) for d in range(K)]
    live = jnp.isfinite(tiles[0])
    s = _sort_tiles(tiles)
    if q.op == "quantile":
        out = _interp_rank(s, q.farg)
    else:                                     # mad
        med = _interp_rank(s, 0.5)
        dev = [jnp.abs(t - med) for t in tiles]
        out = _interp_rank(_sort_tiles(dev), 0.5)
    return jnp.where(live, out, jnp.nan)




def _holt_winters_block(ts, vals, q: GridQuery):
    """Double exponential smoothing under the dense contract: level
    seeds from the window's first row, trend from the first pair, then
    a K-step unrolled recurrence over the window tiles (reference
    HoltWintersFunction; identical math to windows.holt_winters with
    every sample present)."""
    if not q.dense:
        raise ValueError(f"grid op {q.op} requires the dense contract")
    ns = vals.shape[1]
    dt = vals.dtype
    K = q.kbuckets
    sl = _win_slicer(q, ns)
    if K < 2:
        return jnp.full((q.nsteps, ns), jnp.nan, dt)
    sf = jnp.asarray(q.farg, dt)
    tf = jnp.asarray(q.farg2, dt)
    s = sl(vals, 0)
    live = jnp.isfinite(s)
    b = jnp.zeros_like(s)
    for i in range(1, K):
        y = sl(vals, i)
        b_eff = (y - s) if i == 1 else b
        xn = sf * y + (1.0 - sf) * (s + b_eff)
        b = tf * (xn - s) + (1.0 - tf) * b_eff
        s = xn
    return jnp.where(live, s, jnp.nan)


def _timestamp_block(ts, vals, steps0, q: GridQuery):
    """timestamp() emitting seconds RELATIVE to each window's end: the
    magnitudes stay within the window span, exact in f32 (epoch-relative
    ms near the int32 limit would lose ~0.13 s to f32 rounding).  The
    serving path re-bases to absolute seconds in f64 on the host."""
    ns = vals.shape[1]
    dt = vals.dtype
    sl = _win_slicer(q, ns)
    fin = jnp.isfinite(vals)
    tcol = jax.lax.broadcasted_iota(jnp.int32, (q.nsteps, ns), 0)
    hi = steps0 + tcol * jnp.int32(q.gstep_ms * q.stride)
    if q.dense:
        live = jnp.isfinite(sl(vals, 0))
        rel = sl(ts, q.kbuckets - 1) - hi
        return jnp.where(live, rel.astype(dt) / 1000.0, jnp.nan)
    sel = jnp.full((q.nsteps, ns), _IBIG, ts.dtype)
    for d in range(q.kbuckets):              # forward: last finite wins
        fd = sl(fin, d)
        sel = jnp.where(fd, sl(ts, d), sel)
    return jnp.where(sel != _IBIG, (sel - hi).astype(dt) / 1000.0, jnp.nan)


def _rate_block(ts, vals, steps0, q: GridQuery):
    if q.op in ("irate", "idelta"):
        return _instant_pair_block(ts, vals, q)
    if q.op in ("quantile", "mad"):
        return _sort_ops_block(ts, vals, q)
    if q.op == "holt_winters":
        return _holt_winters_block(ts, vals, q)
    if q.op == "timestamp":
        return _timestamp_block(ts, vals, steps0, q)
    if q.op in ("deriv", "predict_linear"):
        return _linreg_block(ts, vals, steps0, q)
    if q.op == "zscore":
        return _zscore_block(ts, vals, q)
    if q.op == "delta":
        # gauge delta: extrapolated like rate but with NO counter
        # correction and NO zero-point clamp (reference delta_fn)
        if q.dense:
            stats = _window_stats_dense(ts, vals, vals, q)
        else:
            stats = _window_stats(ts, jnp.isfinite(vals), vals, q)
        return _extrapolate(*stats, steps0, q)
    if q.op not in ("rate", "increase"):
        return _agg_block(ts, vals, q)
    roll = lambda x, s: pltpu.roll(x, s, axis=0)
    if q.dense:
        # _rate_block only runs inside Pallas TPU kernels (the portable
        # dispatch lives in rate_grid_ref), so the MXU prefix is safe
        vcorr = _correct_dense_auto(vals, roll)
        stats = _window_stats_dense(ts, vals, vcorr, q)
    else:
        fin, vcorr = _correct_and_mask(ts, vals, roll)
        stats = _window_stats(ts, fin, vcorr, q)
    return _extrapolate(*stats, steps0, q)


# ops whose kernels never read the ts plane (window membership is the
# bucket index; the math uses values only): for these the Pallas wrappers
# do not stream ts at all — half the HBM traffic of a two-plane op
TS_FREE_OPS = frozenset(("quantile", "mad", "holt_winters", "zscore",
                         "last", "sum", "count", "avg", "min", "max",
                         "changes", "resets", "stddev", "stdvar"))


def _series_kernel(s0_ref, ts_ref, vals_ref, out_ref, *, q: GridQuery):
    out_ref[:] = _rate_block(ts_ref[:], vals_ref[:], s0_ref[0, 0], q)


def _series_kernel_free(s0_ref, vals_ref, out_ref, *, q: GridQuery):
    out_ref[:] = _rate_block(None, vals_ref[:], s0_ref[0, 0], q)


def _series_kernel_phase(s0_ref, ph_ref, vals_ref, out_ref, *,
                         q: GridQuery):
    roll = lambda x, s: pltpu.roll(x, s, axis=0)
    out, live_row = _phase_block_raw(ph_ref[0:1, :], vals_ref[:], q, roll,
                                     mxu=True)
    out_ref[:] = jnp.where(live_row, out, jnp.nan)


def _smem():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _s0_tile(steps0):
    """A traced scalar (``steps0``; the packed kernels' ``row0``) as the
    kernels' SMEM operand.  [1, 1], not [1]: under
    the fleet tier's vmap the operand grows a leading member axis, and
    Mosaic wants a block's last two dims equal to the array's (or 8/128
    multiples) — [B, 1, 1] blocked (None, 1, 1) is, [B, 1] blocked
    (None, 1) is not."""
    return jnp.asarray(steps0, jnp.int32).reshape(1, 1)


# Mosaic's default scoped-VMEM budget on a v5e is 16 MiB of the core's
# 128 MiB, and the K-unrolled kernels keep many [T, lanes] temporaries
# live: at the tallest admitted grid (MAX_GRID_ROWS x a 128-lane tile)
# mad wants 21.7 MiB and deriv at K=64 18.3 MiB.  Half the core's VMEM
# covers every op at every shape lane_tile/supports_grid admit
# (tests/test_chip_compile.py compiles them for a described v5e).
_MOSAIC_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=64 << 20)


def _mode_for(q: GridQuery, phase) -> str:
    """Input-plane mode: 'free' ops stream only values; 'phase' streams
    values + one phase row; 'ts' streams both planes."""
    if q.op in TS_FREE_OPS:
        return "free"
    if _phase_mode(q, phase):
        return "phase"
    return "ts"


def _phase8(phase):
    """Phase as an [8, S] tile: Mosaic DMAs sublane-multiples; 8 rows of
    int32 per 1024-lane block is 32 KB — noise next to the vals plane."""
    ph = jnp.asarray(phase, jnp.int32)
    if ph.ndim == 1:
        ph = ph[None, :]
    return jnp.broadcast_to(ph[0:1, :], (8, ph.shape[-1]))


@functools.partial(devicewatch.jit, program="grid.rate_grid",
                   static_argnames=("q", "lanes", "interpret"))
@_x32
def rate_grid(ts, vals, steps0, q: GridQuery, lanes: int = 1024,
              interpret: bool = False, phase=None):
    """Per-series windowed function over an aligned grid: [B, S] -> [T, S].

    ``steps0`` is a traced scalar (int32): differing query starts reuse
    one compiled kernel.  Row 0 must be the first bucket of the first
    window (see module docstring).

    ``phase`` ([S] int32, per-lane within-bucket scrape offset in
    (0, gstep]) activates the uniform-phase kernels for PHASE_OPS under
    the dense contract: the ts plane is not streamed at all.  For
    TS_FREE_OPS the ts plane is never streamed; ``ts`` may be None in
    both cases.
    """
    nb, ns = vals.shape
    if ns % lanes != 0 or ns == 0:
        raise ValueError(f"series count {ns} must be a non-zero multiple of "
                         f"lanes={lanes} (pad with NaN columns)")
    if nb < _rows_needed(q):
        raise ValueError(f"grid has {nb} rows; need (nsteps-1)*stride+K = "
                         f"{_rows_needed(q)}")
    if q.stride > 1:
        # Mosaic cannot lower strided sublane slices: run the stride-1
        # fine grid and subsample the output at the XLA level (the
        # extra windows cost VPU time but stay on the fast path)
        fine = rate_grid(ts, vals, steps0, _fine_query(q), lanes, interpret,
                         phase)
        return fine[::q.stride]
    mode = _mode_for(q, phase)
    vspec = pl.BlockSpec((nb, lanes), lambda i: (0, i),
                         memory_space=pltpu.VMEM)
    if mode == "free":
        kern, extra, especs = _series_kernel_free, (), ()
    elif mode == "phase":
        kern = _series_kernel_phase
        extra = (_phase8(phase),)
        especs = (pl.BlockSpec((8, lanes), lambda i: (0, i),
                               memory_space=pltpu.VMEM),)
    else:
        kern, extra, especs = _series_kernel, (ts,), (vspec,)
    return pl.pallas_call(
        functools.partial(kern, q=q),
        interpret=interpret, compiler_params=_MOSAIC_PARAMS,
        out_shape=jax.ShapeDtypeStruct((q.nsteps, ns), jnp.float32),
        grid=(ns // lanes,),
        in_specs=[_smem(), *especs, vspec],
        out_specs=pl.BlockSpec((q.nsteps, lanes), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
    )(_s0_tile(steps0), *extra, vals)


# ---------------------------------------------------------------------------
# Compressed-resident kernels: on-device XOR-class decode fused into the
# grid compute, so one compiled program reads the ~2.5 B/sample packed
# planes from HBM instead of the 4 B/sample decoded plane (reference:
# serving compressed BinaryVectors in place, BlockManager.scala:142).
# Input layout contract: codecs/xorgrid.py (class sub-planes p8/p16/raw
# + [8, n] meta tiles: row 0 shift, row 1 first-value bits, row 2 phase).
# Everything runs in PACKED lane order — callers compose their existing
# host-side lane indirections with the pack's ``inv`` map; the device
# never gathers.
# ---------------------------------------------------------------------------


def _decode_packed(p_ref, m_ref):
    """In-VMEM XOR-class decode of one packed [B, L] tile to f32:
    widen -> per-lane shift -> log2(B) prefix-XOR roll scan -> XOR the
    first-row bits -> bitcast.  Raw (f32) tiles take the same path with
    shift 0, so every class decodes through one code shape."""
    p = p_ref[:]
    if p.dtype == jnp.float32:
        u = jax.lax.bitcast_convert_type(p, jnp.uint32)
    else:
        u = p.astype(jnp.uint32)
    z = m_ref[0:1, :].astype(jnp.uint32)
    u = u << z
    nb = u.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, u.shape, 0)
    sh = 1
    while sh < nb:
        u = jnp.where(row >= sh, u ^ pltpu.roll(u, sh, axis=0), u)
        sh *= 2
    first = jax.lax.bitcast_convert_type(m_ref[1:2, :], jnp.uint32)
    return jax.lax.bitcast_convert_type(u ^ first, jnp.float32)


def _decode_rows(p_ref, m_ref, r0_ref, q: GridQuery):
    """Decode the full packed block (the prefix-XOR scan must start at
    block row 0), bring the query's first row (``r0_ref``, a traced SMEM
    scalar) to the top with a sublane rotate by a traced shift, and take
    the span as a static slice at offset 0: one compiled kernel serves
    every offset, 8-aligned or not.  The caller proves that the span
    fits the block (``devicestore._plan_staged``: one block covers it);
    an offset past that would wrap rows round."""
    vals = _decode_packed(p_ref, m_ref)
    nb = vals.shape[0]
    need = _rows_needed(q)
    if need < nb:
        vals = pltpu.roll(vals, (nb - r0_ref[0, 0]) % nb, axis=0)
    return jax.lax.slice(vals, (0, 0), (need, vals.shape[1]))


def _series_kernel_packed(s0_ref, r0_ref, m_ref, p_ref, out_ref, *,
                          q: GridQuery, use_phase: bool):
    vals = _decode_rows(p_ref, m_ref, r0_ref, q)
    if use_phase:
        roll = lambda x, s: pltpu.roll(x, s, axis=0)
        out, live_row = _phase_block_raw(m_ref[2:3, :], vals, q, roll,
                                         mxu=True)
        out_ref[:] = jnp.where(live_row, out, jnp.nan)
    else:
        out_ref[:] = _rate_block(None, vals, s0_ref[0, 0], q)


def _packed_planes(packed: dict):
    """(packed plane, meta tile) pairs in packed (class) order, empty
    planes skipped."""
    out = []
    for key, mkey in (("p8", "m8"), ("p16", "m16"), ("p32", "m32"),
                      ("raw", "mraw")):
        p = packed.get(key)
        if p is None or p.shape[1] == 0:
            continue
        m = packed.get(mkey)
        if m is None:
            raise ValueError(f"packed plane {key} has no meta tile "
                             f"{mkey} (f64 packs carry no meta; the "
                             f"fused kernels are f32-only)")
        out.append((p, m))
    return out


def packed_width(packed: dict) -> int:
    """Total packed lane count (sum of class-plane widths, pads
    included) — the lane dimension of the fused kernels' output."""
    return sum(p.shape[1] for p, _m in _packed_planes(packed))


def _packed_check(packed: dict, q: GridQuery, use_phase: bool):
    if use_phase:
        if not phase_eligible(q):
            raise ValueError(f"op {q.op} not phase-eligible (dense="
                             f"{q.dense}, K={q.kbuckets})")
    elif q.op not in TS_FREE_OPS:
        raise ValueError(f"packed kernels serve TS_FREE or phase-mode "
                         f"ops only; {q.op} needs a ts plane")
    for p, _m in _packed_planes(packed):
        if p.shape[0] < _rows_needed(q):
            raise ValueError(
                f"packed block has {p.shape[0]} rows; query needs "
                f"{_rows_needed(q)}")


def _plane_lane_tile(n: int) -> int:
    """Lane-tile width for one class plane: packed planes halve (p16)
    or quarter (p8) the bytes per lane, so coarser 1024-lane tiles keep
    DMA sizes up; odd tails fall back to one whole-plane block (Mosaic
    masks sub-128 lane dims)."""
    if n % 1024 == 0:
        return 1024
    if n % 128 == 0:
        return 128
    return n


@functools.partial(devicewatch.jit, program="grid.rate_grid_packed",
                   static_argnames=("q", "interpret", "use_phase"))
@_x32
def rate_grid_packed(packed: dict, steps0, q: GridQuery, row0=0,
                     interpret: bool = False, use_phase: bool = False):
    """Per-series windowed function over XOR-class packed residents:
    packed planes -> [T, packed_width] stepped values in PACKED lane
    order (map back through the pack's ``inv``).

    One pallas_call per class plane (uniform dtype per call); decode
    runs in VMEM, so HBM sees only the packed bytes.  ``row0`` is the
    first query row within the block, a traced scalar like ``steps0``:
    one compiled kernel per (T, K) signature serves every offset
    (:func:`_decode_rows`).  ``row0 + (T - 1) * stride + K`` must not
    pass the block's rows: the caller's proof, not checked here.
    ``use_phase`` activates the uniform-phase kernels reading meta
    row 2; otherwise only TS_FREE ops are legal.
    """
    _packed_check(packed, q, use_phase)
    if q.stride > 1:
        fine = rate_grid_packed(packed, steps0, _fine_query(q), row0,
                                interpret, use_phase)
        return fine[::q.stride]
    s0, r0 = _s0_tile(steps0), _s0_tile(row0)
    outs = []
    for p, m in _packed_planes(packed):
        nb, n = p.shape
        lt = _plane_lane_tile(n)
        outs.append(pl.pallas_call(
            functools.partial(_series_kernel_packed, q=q,
                              use_phase=use_phase),
            interpret=interpret, compiler_params=_MOSAIC_PARAMS,
            out_shape=jax.ShapeDtypeStruct((q.nsteps, n), jnp.float32),
            grid=(n // lt,),
            in_specs=[_smem(), _smem(),
                      pl.BlockSpec((8, lt), lambda i: (0, i),
                                   memory_space=pltpu.VMEM),
                      pl.BlockSpec((nb, lt), lambda i: (0, i),
                                   memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec((q.nsteps, lt), lambda i: (0, i),
                                   memory_space=pltpu.VMEM),
        )(s0, r0, m, p))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)


# ---------------------------------------------------------------------------
# Compressed-resident HISTOGRAM kernels (ISSUE 14): decode bucket planes
# in VMEM and reduce the bucket dimension with BANDED MXU matmuls.
#
# Input layout contract (codecs/xorgrid.py ``pack_vals(stride=hb)`` over
# the device store's hist group-slot plane, devicestore.hist_slot_garr):
# column ``s*hb + j`` holds series s's cumulative bucket j, a series'
# ``hb`` columns classify together and stay contiguous in bucket order.
# The fused grouped kernel additionally requires the group-aligned
# single-class identity pack (min_width, no pads: a zero pad lane
# decodes to a constant finite 0.0 series, and with no group map to drop
# it would count as a live series), with ``group_lanes % hb == 0``.
#
# The per-bucket window compute is the SAME code path as the scalar
# kernels (each bucket column is an independent counter lane, incl. the
# banded ``_corr_v1_delta_banded`` correction on K-heavy shapes); what
# is hist-specific is the in-kernel bucket reduce: summing series within
# a group PER BUCKET is a banded 0/1 matmul ``M[j, c] = (c mod hb == j)``
# applied to the [T, group_lanes] stepped tile — the
# ``_corr_v1_delta_banded`` trick (arXiv:2112.09017's reductions-as-
# banded-matmuls) restated on the bucket axis, so the reduce runs on the
# MXU instead of a serialized scatter-add.
# ---------------------------------------------------------------------------


def _hb8(hb: int) -> int:
    """Bucket count padded to the sublane multiple: output blocks are
    [hb8, T] per group, so dynamic sublane offsets never appear."""
    return -(-hb // 8) * 8


def _hist_grouped_kernel_packed(s0_ref, r0_ref, m_ref, p_ref, sum_ref,
                                cnt_ref, *, q: GridQuery, use_phase: bool,
                                hb: int):
    """One group per kernel instance: decode the group's packed
    [nb, group_lanes] tile, run the windowed op per bucket column, and
    band-reduce series into [hb8, T] per-bucket (sum, count) planes."""
    vals = _decode_rows(p_ref, m_ref, r0_ref, q)
    if use_phase:
        roll = lambda x, s: pltpu.roll(x, s, axis=0)
        out, live_row = _phase_block_raw(m_ref[2:3, :], vals, q, roll,
                                         mxu=True)
        vz = jnp.where(live_row, out, 0.0)
        ok = jnp.broadcast_to(live_row, out.shape).astype(jnp.float32)
    else:
        r = _rate_block(None, vals, s0_ref[0, 0], q)
        fin = jnp.isfinite(r)
        vz = jnp.where(fin, r, 0.0)
        ok = fin.astype(jnp.float32)
    gl = vz.shape[1]
    hb8 = sum_ref.shape[0]
    j = jax.lax.broadcasted_iota(jnp.int32, (hb8, gl), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (hb8, gl), 1)
    band = (c % hb == j).astype(jnp.float32)      # [hb8, gl] banded 0/1
    hp = jax.lax.Precision.HIGHEST
    dims = (((1,), (1,)), ((), ()))
    sum_ref[:, :] = jax.lax.dot_general(band, vz, dims, precision=hp,
                                        preferred_element_type=jnp.float32)
    cnt_ref[:, :] = jax.lax.dot_general(band, ok, dims, precision=hp,
                                        preferred_element_type=jnp.float32)


@functools.partial(devicewatch.jit,
                   program="grid.hist_grid_grouped_packed",
                   static_argnames=("q", "hb", "group_lanes",
                                    "interpret", "use_phase"))
@_x32
def hist_grid_grouped_packed(packed: dict, steps0, q: GridQuery, hb: int,
                             group_lanes: int = 1024, row0=0,
                             interpret: bool = False,
                             use_phase: bool = True):
    """Fully fused ``sum by (g)(rate(latency_bucket[w]))`` over packed
    HISTOGRAM residents: packed bucket planes -> (sum, count)
    ``[G*hb, T]`` — decode, per-bucket window compute, and the banded-
    MXU bucket reduce in ONE kernel per class plane.  Output slot
    ``g*hb + j`` is group g's cumulative bucket j (the
    ``hist_slot_garr`` layout ``hist_state_from_planes`` consumes).

    Requires the hist group-aligned pack contract: a single-class
    identity-order pack (``pack_vals(stride=hb, min_width=...)``, no
    alignment pads), ``group_lanes % hb == 0``, and every group's
    ``group_lanes`` columns contiguous.  Mixed-class hist packs must
    use :func:`rate_grid_packed` + a segment reduce instead."""
    if group_lanes % hb != 0:
        raise ValueError(f"group_lanes {group_lanes} not a multiple of "
                         f"the bucket count {hb}")
    _packed_check(packed, q, use_phase)
    inv = packed.get("inv")
    if inv is not None and packed_width(packed) != inv.shape[0]:
        raise ValueError(
            "pack carries alignment-pad lanes; the fused hist grouped "
            "kernel has no group map to drop them — use the identity "
            "min_width hist pack")
    if q.stride > 1:
        s, c = hist_grid_grouped_packed(packed, steps0, _fine_query(q), hb,
                                        group_lanes, row0, interpret,
                                        use_phase)
        return s[:, ::q.stride], c[:, ::q.stride]
    s0, r0 = _s0_tile(steps0), _s0_tile(row0)
    hb8 = _hb8(hb)
    sums, cnts = [], []
    for p, m in _packed_planes(packed):
        nb, n = p.shape
        ng = n // group_lanes
        if n % group_lanes != 0 or ng == 0:
            raise ValueError(
                f"packed plane width {n} must be a whole number of "
                f"{group_lanes}-column groups — use the hist "
                f"group-aligned pack layout")
        s, c = pl.pallas_call(
            functools.partial(_hist_grouped_kernel_packed, q=q,
                              use_phase=use_phase, hb=hb),
            interpret=interpret, compiler_params=_MOSAIC_PARAMS,
            out_shape=(jax.ShapeDtypeStruct((ng * hb8, q.nsteps),
                                            jnp.float32),
                       jax.ShapeDtypeStruct((ng * hb8, q.nsteps),
                                            jnp.float32)),
            grid=(ng,),
            in_specs=[_smem(), _smem(),
                      pl.BlockSpec((8, group_lanes), lambda i: (0, i),
                                   memory_space=pltpu.VMEM),
                      pl.BlockSpec((nb, group_lanes), lambda i: (0, i),
                                   memory_space=pltpu.VMEM)],
            out_specs=(pl.BlockSpec((hb8, q.nsteps), lambda i: (i, 0),
                                    memory_space=pltpu.VMEM),
                       pl.BlockSpec((hb8, q.nsteps), lambda i: (i, 0),
                                    memory_space=pltpu.VMEM)),
        )(s0, r0, m, p)
        sums.append(s)
        cnts.append(c)
    s = sums[0] if len(sums) == 1 else jnp.concatenate(sums, axis=0)
    c = cnts[0] if len(cnts) == 1 else jnp.concatenate(cnts, axis=0)
    if hb8 != hb:
        G = s.shape[0] // hb8
        s = s.reshape(G, hb8, -1)[:, :hb, :].reshape(G * hb, -1)
        c = c.reshape(G, hb8, -1)[:, :hb, :].reshape(G * hb, -1)
    return s, c


# ---------------------------------------------------------------------------
# Generic columnar event scan -> filter -> topK (ISSUE 14): the GDELT
# shape.  Each event stream is a lane of a (packed) numeric column
# plane; the fused program decodes the value column in VMEM, runs the
# windowed aggregate, masks lanes through an optional predicate on a
# SECOND column (scanned the same fused way), reduces lanes into groups
# with a one-hot MXU matmul (the banded-reduce family: group lanes are
# contiguous, so the 0/1 matrix is banded), and ranks groups with
# top_k — one compiled program, only [T, k] values + indices leave the
# device.
# ---------------------------------------------------------------------------

_FILTER_OPS = {
    "gt": lambda v, t: v > t, "ge": lambda v, t: v >= t,
    "lt": lambda v, t: v < t, "le": lambda v, t: v <= t,
    "eq": lambda v, t: v == t, "ne": lambda v, t: v != t,
}

# one-hot group reduce beyond this many groups costs too much memory
# (the [lanes, G] operand) — same cap and segment_sum fallback as the
# devicestore's _grouped_reduce_impl
_TOPK_ONEHOT_MAX_G = 2048


@functools.partial(devicewatch.jit,
                   program="grid.event_topk_grid_packed",
                   static_argnames=("q", "k", "num_groups", "filt_op",
                                    "filt_q", "interpret", "largest",
                                    "group_width"))
def event_topk_grid_packed(packed: dict, steps0, q: GridQuery, k: int,
                           garr, num_groups: int,
                           filt_packed: Optional[dict] = None,
                           filt_op: str = "gt", filt_thresh=0.0,
                           filt_q: Optional[GridQuery] = None,
                           filt_pos=None, row0=0,
                           interpret: bool = False, largest: bool = True,
                           group_width: int = 0):
    """``topk(k, agg by (g)(fn(value_col[w])))`` with an optional scan
    filter on a second column, over packed columnar residents.

    - ``packed``: the value column's XOR-class planes (packed order).
    - ``garr``: [packed_width] int32 lane -> group slot in PACKED order
      (``num_groups`` = drop bucket for pad/unrequested lanes).
    - ``group_width``: when every group is ``group_width`` CONTIGUOUS
      packed lanes (the banded layout: group g = lanes [g*W, (g+1)*W)),
      pass it and ``garr=None`` — the reduce becomes a reshape-sum with
      no [lanes, G] one-hot operand at all (the memory-free banded
      form; a 256k-lane table would otherwise stream a multi-GiB
      one-hot).  A general ``garr`` uses the one-hot MXU
      matmul up to ``_TOPK_ONEHOT_MAX_G`` groups and segment_sum past
      it (the devicestore ``_grouped_reduce_impl`` policy).
    - ``filt_packed``/``filt_op``/``filt_thresh``: keep only lanes whose
      filter-column window value satisfies ``filt_op(v, thresh)``
      (ops: gt/ge/lt/le/eq/ne); ``filt_q`` defaults to ``q`` with the
      same window; ``filt_pos`` ([packed_width] int32) maps the VALUE
      pack's lane order into the FILTER pack's when the two columns
      packed with different layouts (identity packs need none).
    - returns ``(vals [T, k], idx [T, k])``: per step the top-k group
      sums (``largest=False`` ranks smallest) and their group slots;
      exhausted ranks come back NaN / -1.
    """
    if filt_op not in _FILTER_OPS:
        raise ValueError(f"unknown filter op {filt_op!r} "
                         f"(have {sorted(_FILTER_OPS)})")
    if group_width and garr is not None:
        raise ValueError("pass garr OR group_width, not both")
    stepped = rate_grid_packed(packed, steps0, q, row0=row0,
                               interpret=interpret)          # [T, n]
    if filt_packed is not None:
        fq = filt_q if filt_q is not None else q
        fstep = rate_grid_packed(filt_packed, steps0, fq, row0=row0,
                                 interpret=interpret)
        if filt_pos is not None:
            fstep = fstep[:, filt_pos]
        keep = _FILTER_OPS[filt_op](fstep,
                                    jnp.asarray(filt_thresh, fstep.dtype))
        stepped = jnp.where(keep, stepped, jnp.nan)
    fin = jnp.isfinite(stepped)
    vz = jnp.where(fin, stepped, 0.0)
    T, n = stepped.shape
    if group_width:
        if n != num_groups * group_width:
            raise ValueError(
                f"packed width {n} != num_groups {num_groups} x "
                f"group_width {group_width}")
        sums = vz.reshape(T, num_groups, group_width).sum(2).T
        cnts = fin.reshape(T, num_groups, group_width) \
            .sum(2).T.astype(stepped.dtype)
    elif num_groups + 1 <= _TOPK_ONEHOT_MAX_G:
        garr = jnp.asarray(garr, jnp.int32)
        onehot = (garr[:, None] ==
                  jnp.arange(num_groups, dtype=jnp.int32)[None, :]
                  ).astype(stepped.dtype)                    # [n, G]
        hp = jax.lax.Precision.HIGHEST
        sums = jnp.matmul(onehot.T, vz.T, precision=hp)      # [G, T]
        cnts = jnp.matmul(onehot.T, fin.astype(stepped.dtype).T,
                          precision=hp)
    else:
        garr = jnp.asarray(garr, jnp.int32)
        sums = jax.ops.segment_sum(vz.T, garr,
                                   num_groups + 1)[:num_groups]
        cnts = jax.ops.segment_sum(fin.astype(stepped.dtype).T, garr,
                                   num_groups + 1)[:num_groups]
    sentinel = -jnp.inf if largest else jnp.inf
    ranked = jnp.where(cnts > 0, sums, sentinel).T           # [T, G]
    if not largest:
        ranked = -ranked
    vals, idx = jax.lax.top_k(ranked, k)
    live = jnp.isfinite(vals)
    if not largest:
        vals = -vals
    return (jnp.where(live, vals, jnp.nan),
            jnp.where(live, idx, -1))


# ---------------------------------------------------------------------------
# Pure-XLA reference implementation (CPU fallback + test oracle)
# ---------------------------------------------------------------------------

def rate_grid_ref(ts, vals, steps0: int, q: GridQuery, phase=None):
    """Same semantics as :func:`rate_grid`, in portable jnp.  ``phase``
    activates the collapsed uniform-phase formulation (used as the CPU
    serving path and as the oracle for the phase kernels); ``ts`` may
    then be None."""
    def roll(x, s):
        return jnp.concatenate([x[-s:], x[:-s]], axis=0)
    if _phase_mode(q, phase):
        ph = jnp.asarray(phase, jnp.int32)
        if ph.ndim == 1:
            ph = ph[None, :]
        return _phase_block(ph[0:1, :], vals, q, roll, mxu=False)
    if q.op in ("irate", "idelta"):
        return _instant_pair_block(ts, vals, q)
    if q.op in ("quantile", "mad"):
        return _sort_ops_block(ts, vals, q)
    if q.op == "holt_winters":
        return _holt_winters_block(ts, vals, q)
    if q.op == "timestamp":
        return _timestamp_block(ts, vals, jnp.int32(steps0), q)
    if q.op in ("deriv", "predict_linear"):
        return _linreg_block(ts, vals, jnp.int32(steps0), q)
    if q.op == "zscore":
        return _zscore_block(ts, vals, q)
    if q.op == "delta":
        if q.dense:
            stats = _window_stats_dense(ts, vals, vals, q)
        else:
            stats = _window_stats(ts, jnp.isfinite(vals), vals, q)
        return _extrapolate(*stats, jnp.int32(steps0), q)
    if q.op not in ("rate", "increase"):
        return _agg_block(ts, vals, q)
    if q.dense:
        vcorr = _correct_dense(vals, roll)
        stats = _window_stats_dense(ts, vals, vcorr, q)
    else:
        fin, vcorr = _correct_and_mask(ts, vals, roll)
        stats = _window_stats(ts, fin, vcorr, q)
    return _extrapolate(*stats, jnp.int32(steps0), q)


def rate_grid_auto(ts, vals, steps0, q: GridQuery, lanes: int = 1024,
                   phase=None):
    """Pallas on TPU backends, portable reference elsewhere.  ``steps0``
    may be a traced scalar (this runs under the serving path's fused
    jit program)."""
    if on_tpu_backend() and vals.shape[1] % lanes == 0:
        return rate_grid(ts, vals, steps0, q, lanes, phase=phase)
    return rate_grid_ref(ts, vals, steps0, q, phase=phase)


def rate_grid_batch_impl(ts_b, vals_b, steps0s, q: GridQuery,
                         lanes: int = 1024, phase=None):
    """Fleet-batched grid kernel (ISSUE 20): vmap of
    :func:`rate_grid_auto` over a leading MEMBER axis — B shape-
    compatible queries against B pre-sliced views of the same resident
    planes, one device program instead of B (the DrJAX vmap-over-
    clients idiom).  ``ts_b``/``vals_b`` are ``[B, rows, cols]``
    (``ts_b`` None in phase mode), ``steps0s`` is the ``[B]`` vector
    of per-member first window ends; ``phase`` is shared and
    broadcast.  Plain function: the serving path fuses it into its own
    jitted program (memstore/devicestore.py ``series_batch``/
    ``grouped_batch``) so slicing + kernel + readback stay ONE
    dispatch."""
    if ts_b is None:
        return jax.vmap(lambda v, s: rate_grid_auto(
            None, v, s, q, lanes, phase=phase))(vals_b, steps0s)
    return jax.vmap(lambda t, v, s: rate_grid_auto(
        t, v, s, q, lanes, phase=phase))(ts_b, vals_b, steps0s)


MAX_K_BUCKETS = 64   # K-unrolled kernel passes; caps the compile cost
MAX_GRID_ROWS = 1024  # input rows per query: VMEM tile height bound (TPU)
# any backend: bounds blocks staged/assembled per query (a coarse step
# over a fine cadence can otherwise span millions of buckets)
MAX_GRID_SPAN_ROWS = 16_384

# ops whose DENSE kernel is K-free (rate/increase: window stats are two
# static slices; last: one slice; count: a constant; irate/idelta: the
# window's last two rows) — for these a proven-dense query may use any
# K up to the row bound, which keeps high-frequency data (5m window
# over 1s scrapes -> K=300) on the fast path.  sum/avg/min/max/stddev/
# changes/... accumulate K slices even when dense, so they keep the
# unroll cap.
K_FREE_DENSE_OPS = frozenset(("rate", "increase", "last", "count",
                              "irate", "idelta", "delta", "timestamp"))

# ops grid-served ONLY under the proven dense contract (the general
# scan path serves otherwise): consecutive-sample adjacency ops
# (changes/resets/irate/idelta), sort-based ops where NaN poisons a
# min/max sorting network (quantile/mad), and recurrence ops whose
# reference semantics SKIP NaN samples — the unrolled kernel is only
# equivalent when every window slot is filled (holt_winters)
DENSE_ONLY_OPS = frozenset(("changes", "resets", "irate", "idelta",
                            "quantile", "mad", "holt_winters"))

# sort-based ops run a Batcher network of O(K log^2 K) compare-exchanges
# over [T, L] tiles; cap K so compile time and VPU work stay sane
SORT_OPS_MAX_K = 32


def lane_tile(ncols: int, nrows: int) -> int:
    """Lane-tile width of the decoded-plane kernels for an
    ``[nrows, ncols]`` query slice (``ncols`` a multiple of 128): tall
    slices read more input rows per tile, so they narrow the tile to
    keep the VMEM footprint bounded.  ONE rule for the device store and
    the mesh fabric — it is the shape tests/test_chip_compile.py holds
    the chip's compiler to."""
    return 1024 if (ncols % 1024 == 0 and nrows <= 256) else 128


def max_k_for(op: str, dense: bool) -> int:
    if op in ("quantile", "mad"):
        return SORT_OPS_MAX_K
    return MAX_GRID_ROWS if dense and op in K_FREE_DENSE_OPS \
        else MAX_K_BUCKETS


def supports_grid(window_ms: int, step_ms: int, gstep_ms: int,
                  nsteps: int = 1, max_k: int = MAX_K_BUCKETS) -> bool:
    """Host-side check: can the aligned fast path serve this query?
    The query step may be any multiple of the bucket width (stride
    serving — dashboards commonly step coarser than the scrape
    cadence).  ``max_k`` caps K = window/gstep — pass
    ``max_k_for(op, dense)`` to allow large windows for the K-free
    dense ops; the general kernels unroll K static slice passes, so an
    uncapped K there would pay a huge one-off compile on the most
    interactive query shape.  Total input rows are capped by the VMEM
    tile height.  Beyond the caps the general path serves."""
    if not (window_ms > 0 and gstep_ms > 0 and step_ms > 0
            and step_ms % gstep_ms == 0 and window_ms % gstep_ms == 0
            and window_ms // gstep_ms <= max_k):
        return False
    stride = step_ms // gstep_ms
    rows = (nsteps - 1) * stride + window_ms // gstep_ms
    if rows > MAX_GRID_SPAN_ROWS:
        return False    # block-assembly bound, any backend
    if not on_tpu_backend():
        return True     # portable reference path: no VMEM tile bound
    return rows <= MAX_GRID_ROWS


# ---------------------------------------------------------------------------
# M4 visualization downsampling (ISSUE 16): per-pixel-bin min/max/
# first/last selection (the M4 aggregation of Jugel et al., adopted by
# tsdownsample/MinMaxLTTB, arXiv:2307.05389).  A T-step series split
# into P pixel bins keeps <= 4 points per bin — everything a width-P
# panel can render — so a year-long query returns ~4P points instead
# of millions.  Pure SELECTION, no arithmetic: the kernel output is
# bit-equal to a NumPy oracle by construction.
# ---------------------------------------------------------------------------

#: m4 plane order along output axis 1: values then LOCAL row indices
M4_PLANES = ("vmin", "vmax", "vfirst", "vlast",
             "imin", "imax", "ifirst", "ilast")


def _m4_planes(v, idx, big):
    """Shared selection math over one bin axis (rows): 8 [S]-planes.
    Ties on min/max resolve to the FIRST occurrence; empty bins yield
    NaN values and -1 indices.  Works on [W, S] blocks (kernel) and
    batched [P, W, S] (reference) alike via ``axis=-2``."""
    fin = jnp.isfinite(v)
    vmin = jnp.min(jnp.where(fin, v, jnp.inf), axis=-2)
    vmax = jnp.max(jnp.where(fin, v, -jnp.inf), axis=-2)
    ifirst = jnp.min(jnp.where(fin, idx, big), axis=-2)
    ilast = jnp.max(jnp.where(fin, idx, -1), axis=-2)
    imin = jnp.min(jnp.where(fin & (v == jnp.expand_dims(vmin, -2)),
                             idx, big), axis=-2)
    imax = jnp.min(jnp.where(fin & (v == jnp.expand_dims(vmax, -2)),
                             idx, big), axis=-2)
    vfirst = jnp.sum(jnp.where(idx == jnp.expand_dims(ifirst, -2), v, 0.0),
                     axis=-2)
    vlast = jnp.sum(jnp.where(idx == jnp.expand_dims(ilast, -2), v, 0.0),
                    axis=-2)
    empty = ifirst == big
    nanv = jnp.float32(jnp.nan)
    neg1 = jnp.float32(-1)
    return (jnp.where(empty, nanv, vmin), jnp.where(empty, nanv, vmax),
            jnp.where(empty, nanv, vfirst), jnp.where(empty, nanv, vlast),
            jnp.where(empty, neg1, imin.astype(jnp.float32)),
            jnp.where(empty, neg1, imax.astype(jnp.float32)),
            jnp.where(empty, neg1, ifirst.astype(jnp.float32)),
            jnp.where(empty, neg1, ilast.astype(jnp.float32)))


def _m4_kernel(v_ref, out_ref):
    """One (pixel bin, lane block): [wpad, L] -> [1, 8, L].  Rows past
    the bin's true width are NaN padding and never selected."""
    v = v_ref[...]
    idx = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
    planes = _m4_planes(v, idx, jnp.int32(_IBIG))
    for k in range(8):
        out_ref[0, k, :] = planes[k]


def _m4_bin_shape(nsteps: int, pixels: int) -> tuple[int, int]:
    """(bin width W, sublane-padded width) for T steps over P bins."""
    w = -(-nsteps // pixels)
    return w, -(-w // 8) * 8


@functools.partial(devicewatch.jit, program="grid.m4_grid",
                   static_argnames=("pixels", "lanes", "interpret"))
@_x32
def m4_grid(vals, pixels: int, lanes: int = 128,
            interpret: bool = False):
    """M4 pixel-bin selection: time-major ``vals [T, S]`` -> planes
    ``[P, 8, S]`` in :data:`M4_PLANES` order.  Index planes are LOCAL
    to the bin (global row = ``p * W + local``, ``W = ceil(T/P)``);
    NaN steps are absent samples, bins with no finite sample come back
    NaN / -1.  Banded layout: time on sublanes (one bin's rows per
    block), series on lanes — S must be a multiple of ``lanes`` (pad
    with NaN columns)."""
    nsteps, ns = vals.shape
    if ns % lanes != 0 or ns == 0:
        raise ValueError(f"series count {ns} must be a non-zero multiple "
                         f"of lanes={lanes} (pad with NaN columns)")
    if pixels < 1:
        raise ValueError(f"pixels must be >= 1, got {pixels}")
    w, wpad = _m4_bin_shape(nsteps, pixels)
    v = jnp.asarray(vals, jnp.float32)
    # host-side (XLA) re-banding: pad T to P*W, split bins, pad each
    # bin's rows to a sublane multiple, flatten back to 2-D so the
    # kernel sees one aligned [wpad, lanes] tile per (bin, lane block)
    v = jnp.pad(v, ((0, pixels * w - nsteps), (0, 0)),
                constant_values=jnp.nan)
    v = v.reshape(pixels, w, ns)
    v = jnp.pad(v, ((0, 0), (0, wpad - w), (0, 0)),
                constant_values=jnp.nan)
    v = v.reshape(pixels * wpad, ns)
    return pl.pallas_call(
        _m4_kernel,
        interpret=interpret, compiler_params=_MOSAIC_PARAMS,
        out_shape=jax.ShapeDtypeStruct((pixels, 8, ns), jnp.float32),
        grid=(ns // lanes, pixels),
        in_specs=[pl.BlockSpec((wpad, lanes), lambda i, p: (p, i),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((1, 8, lanes), lambda i, p: (p, 0, i),
                               memory_space=pltpu.VMEM),
    )(v)


def m4_grid_ref(vals, pixels: int):
    """Same semantics as :func:`m4_grid` in portable jnp (CPU serving
    path + test oracle's device-side twin).  Selection only — the
    outputs are bit-identical to the kernel's."""
    nsteps, ns = vals.shape
    if ns == 0 or nsteps == 0:
        raise ValueError(f"empty input {vals.shape}")
    if pixels < 1:
        raise ValueError(f"pixels must be >= 1, got {pixels}")
    w, _wpad = _m4_bin_shape(nsteps, pixels)
    v = jnp.asarray(vals, jnp.float32)
    v = jnp.pad(v, ((0, pixels * w - nsteps), (0, 0)),
                constant_values=jnp.nan)
    v = v.reshape(pixels, w, ns)
    idx = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    return jnp.stack(_m4_planes(v, idx, jnp.int32(_IBIG)), axis=1)


def m4_grid_auto(vals, pixels: int, lanes: int = 128):
    """Pallas on TPU backends (when the series axis tiles), portable
    reference elsewhere."""
    if on_tpu_backend() and vals.shape[1] % lanes == 0 and vals.shape[1]:
        return m4_grid(vals, pixels, lanes)
    return m4_grid_ref(vals, pixels)
