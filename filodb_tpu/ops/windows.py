"""Windowed range functions as batched JAX kernels.

Semantics match the reference's PeriodicSamplesMapper windows — for each
output step ``t`` the window is ``(t - window, t]``, start exclusive / end
inclusive (reference: query/exec/PeriodicSamplesMapper.scala:323-344) — and
Prometheus' extrapolation rules for rate/increase/delta (reference:
query/exec/rangefn/RateFunctions.scala:10-80 extrapolatedRate, kept
"consistent with Prometheus" per its own comment).

Formulation: instead of the reference's per-window row iteration
(ChunkedRangeFunction.addChunks doing binarySearch + a row loop per window),
every kernel here computes ALL windows of ALL series at once:

- ``window_bounds``: vmapped searchsorted -> [S, T] first/last row indices.
- prefix-path kernels: running sums over the row axis; each window is two
  gathers and a subtract (O(1) per window, O(R) total — asymptotically
  better than the reference's O(windows * rows_per_window)).
- gather-path kernels (min/max/quantile/...): bounded per-window row tiles
  [S, T, W] reduced along W on the VPU.

All kernels are shape-polymorphic in S (series), R (rows), T (steps) and are
jit-compiled per (R, T, W) bucket.  NaN is "no sample" for gauges; padded
rows carry ts=+inf / value=NaN and drop out of every path naturally.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax


class StepRange(NamedTuple):
    """Regular output grid: steps at start, start+step, ..., end (inclusive),
    like the reference's RangeParams."""

    start: int  # ms
    end: int    # ms
    step: int   # ms

    @property
    def num_steps(self) -> int:
        return (self.end - self.start) // self.step + 1

    def timestamps(self, dtype=None):
        """Host-side epoch-ms step grid as numpy int64.  Always numpy:
        epoch milliseconds overflow int32, and with jax_enable_x64 off a
        jnp array would silently truncate (device consumers rebase to
        small offsets before upload)."""
        import numpy as _np
        out = (_np.arange(self.num_steps, dtype=_np.int64) * self.step
               + _np.int64(self.start))
        return out if dtype is None else out.astype(dtype)


def window_bounds(ts: jnp.ndarray, steps: jnp.ndarray, window) -> tuple[jnp.ndarray, jnp.ndarray]:
    """[S,R] sorted timestamps x [T] step ends -> (first, last) [S,T].

    ``first`` = index of first row with ts > step-window; ``last`` = index
    one past the last row with ts <= step.  Replaces the reference's
    per-window binarySearch/ceilingIndex (memory/format/vectors/
    LongBinaryVector.scala:152,162).
    """
    lo = steps - window
    R, T = ts.shape[1], steps.shape[0]
    from filodb_tpu.ops.grid import on_tpu_backend
    on_tpu = on_tpu_backend()
    if R * T <= 262_144 and on_tpu:
        # broadcast-compare-reduce: searchsorted(side='right') == count of
        # ts <= needle.  Pure VPU compare+reduce that XLA fuses without
        # materializing [S,R,T] — measured 12x faster than the bitonic-sort
        # lowering at [1M, 60] x 55 on v5e.  (XLA:CPU does materialize the
        # broadcast, so CPU always takes the searchsorted route below.)
        idx = jnp.int32
        first = (ts[:, :, None] <= lo[None, None, :]).sum(axis=1, dtype=idx)
        last = (ts[:, :, None] <= steps[None, None, :]).sum(axis=1, dtype=idx)
        return first, last
    # bitonic-sort lowering on TPU — no While loop in the HLO (the 'scan'
    # method emits lax.scan, which the TPU executes poorly); CPU takes
    # the default lowering.
    method = "sort" if on_tpu else "scan"
    first = jax.vmap(lambda row: jnp.searchsorted(row, lo, side="right", method=method))(ts)
    last = jax.vmap(lambda row: jnp.searchsorted(row, steps, side="right", method=method))(ts)
    return first, last


def counter_correct(vals: jnp.ndarray) -> jnp.ndarray:
    """Prometheus counter-reset correction along the row axis.

    Wherever a value drops below its predecessor, all later values are
    shifted up by the predecessor — the running-prefix formulation of the
    reference's sequential CorrectionMeta threading
    (query/exec/rangefn/RangeFunction.scala:125-161).  ``vals`` is [S, R];
    correction runs along the row axis.
    """
    prev = jnp.concatenate([vals[:, :1], vals[:, :-1]], axis=1)
    drop = jnp.where((vals < prev), prev, 0.0)  # NaN comparisons are False
    return vals + jnp.cumsum(drop, axis=1)


def _prefix(x: jnp.ndarray) -> jnp.ndarray:
    """[S,R] -> [S,R+1] running sum with NaN treated as 0."""
    s = jnp.cumsum(jnp.where(jnp.isnan(x), 0.0, x), axis=1)
    return jnp.pad(s, ((0, 0), (1, 0)))


def _row_select(arr: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """arr [S,R], idx [S,T] in-range -> out[s,t] = arr[s, idx[s,t]].

    Formulated as a one-hot compare + masked reduce over R instead of
    ``take_along_axis``: TPU per-element gathers measured ~1.35s per [1M,55]
    pull vs ~90ms for the fused compare-reduce.  Falls back to gather for
    large R*T where the broadcast would dominate — and ALWAYS on non-TPU
    backends, where XLA:CPU materializes the [S,R,T] broadcast (measured
    ~100x slower than its native gathers).
    """
    R, T = arr.shape[1], idx.shape[1]
    from filodb_tpu.ops.grid import on_tpu_backend
    if R * T <= 262_144 and on_tpu_backend():
        rows = jnp.arange(R, dtype=idx.dtype)
        oh = rows[None, :, None] == idx[:, None, :]          # [S,R,T]
        return jnp.where(oh, arr[:, :, None], 0).sum(axis=1)
    return jnp.take_along_axis(arr, idx, axis=1)


def _at(P: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    return _row_select(P, idx)


def _range_sum(P: jnp.ndarray, first: jnp.ndarray, last: jnp.ndarray) -> jnp.ndarray:
    return _at(P, last) - _at(P, first)


def _gather_rows(arr: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Per-series gather: arr [S,R], idx [S,T] (clipped) -> [S,T]."""
    return _row_select(arr, jnp.clip(idx, 0, arr.shape[1] - 1))


# --------------------------------------------------------------------------
# Prefix-path kernels
# --------------------------------------------------------------------------

def sum_count_avg(ts, vals, steps, window):
    """Returns (sum, count, avg) over each window in one pass."""
    first, last = window_bounds(ts, steps, window)
    s = _range_sum(_prefix(vals), first, last)
    n = _range_sum(_prefix(jnp.isfinite(vals).astype(vals.dtype)), first, last)
    empty = n == 0
    s = jnp.where(empty, jnp.nan, s)
    avg = jnp.where(empty, jnp.nan, s / jnp.where(empty, 1.0, n))
    return s, jnp.where(empty, jnp.nan, n), avg


def sum_over_time(ts, vals, steps, window):
    return sum_count_avg(ts, vals, steps, window)[0]


def count_over_time(ts, vals, steps, window):
    return sum_count_avg(ts, vals, steps, window)[1]


def avg_over_time(ts, vals, steps, window):
    return sum_count_avg(ts, vals, steps, window)[2]


def stdvar_stddev(ts, vals, steps, window):
    """Population variance/stddev via sum & sum-of-squares prefixes — the
    same moments the reference accumulates (AggrOverTimeFunctions.scala
    VarOverTimeChunkedFunctionD keeps sum & squaredSum), but centered on a
    per-series grand mean first so the E[x^2]-E[x]^2 cancellation cannot blow
    up (single-sample windows come out exactly 0, unlike the reference)."""
    first, last = window_bounds(ts, steps, window)
    fin = jnp.isfinite(vals)
    nrows = jnp.maximum(fin.sum(axis=1, keepdims=True), 1).astype(vals.dtype)
    center = jnp.where(fin, vals, 0.0).sum(axis=1, keepdims=True) / nrows
    x = vals - center
    s1 = _range_sum(_prefix(x), first, last)
    s2 = _range_sum(_prefix(x * x), first, last)
    n = _range_sum(_prefix(fin.astype(vals.dtype)), first, last)
    empty = n == 0
    nsafe = jnp.where(empty, 1.0, n)
    mean = s1 / nsafe
    var = jnp.maximum(s2 / nsafe - mean * mean, 0.0)
    var = jnp.where(empty, jnp.nan, var)
    return var, jnp.sqrt(var)


def stdvar_over_time(ts, vals, steps, window):
    return stdvar_stddev(ts, vals, steps, window)[0]


def stddev_over_time(ts, vals, steps, window):
    return stdvar_stddev(ts, vals, steps, window)[1]


def changes_over_time(ts, vals, steps, window):
    """Number of value changes between consecutive samples inside the window."""
    prev = jnp.concatenate([vals[:, :1], vals[:, :-1]], axis=1)
    chg = (vals != prev) & jnp.isfinite(vals) & jnp.isfinite(prev)
    first, last = window_bounds(ts, steps, window)
    C = _prefix(chg.astype(vals.dtype))
    # pair i covers rows (i-1, i); only pairs fully inside the window count
    raw = _at(C, last) - _at(C, jnp.minimum(first + 1, last))
    n = _range_sum(_prefix(jnp.isfinite(vals).astype(vals.dtype)), first, last)
    return jnp.where(n == 0, jnp.nan, raw)


def resets_over_time(ts, vals, steps, window):
    prev = jnp.concatenate([vals[:, :1], vals[:, :-1]], axis=1)
    rst = (vals < prev)
    first, last = window_bounds(ts, steps, window)
    C = _prefix(rst.astype(vals.dtype))
    raw = _at(C, last) - _at(C, jnp.minimum(first + 1, last))
    n = _range_sum(_prefix(jnp.isfinite(vals).astype(vals.dtype)), first, last)
    return jnp.where(n == 0, jnp.nan, raw)


def last_sample(ts, vals, steps, window):
    """Last *non-NaN* sample in the window and its timestamp: the raw-series
    instant selector (reference: LastSampleChunkedFunctionD,
    rangefn/RangeFunction.scala:408-542).  Returns (value, ts_ms) [S,T];
    ts_ms is -1 where no sample exists."""
    S, R = vals.shape
    rows = jnp.arange(R, dtype=jnp.int32)[None, :]
    lastfin = lax.cummax(jnp.where(jnp.isfinite(vals), rows, -1), axis=1)
    first, last = window_bounds(ts, steps, window)
    j = _gather_rows(lastfin, jnp.maximum(last - 1, 0))
    valid = (last > 0) & (j >= first) & (j >= 0)
    value = jnp.where(valid, _gather_rows(vals, j), jnp.nan)
    tstamp = jnp.where(valid, _gather_rows(ts, j), -1)
    return value, tstamp


def timestamp_fn(ts, vals, steps, window):
    """PromQL timestamp(): seconds of the last sample (reference
    rangefn/RangeFunction.scala:544 TimestampChunkedFunction).

    Precision note: this general path casts absolute epoch seconds to
    the value dtype — f32 on accelerators, which quantizes to ~128 s
    near current epochs.  The device-grid serving path is exact (the
    kernel emits window-relative seconds and the host re-bases in f64);
    only this fallback carries the rounding."""
    _, t = last_sample(ts, vals, steps, window)
    return jnp.where(t < 0, jnp.nan, t.astype(vals.dtype) / 1000.0)


# --------------------------------------------------------------------------
# Rate family
# --------------------------------------------------------------------------

def _extrapolated(delta, n, t1, t2, steps, window, v1, is_counter, is_rate, dtype):
    """Prometheus extrapolatedRate (reference RateFunctions.scala:37-80)."""
    wstart = (steps - window)[None, :].astype(dtype)  # exclusive start
    f = lambda x: x.astype(dtype)
    dur_start = (f(t1) - wstart) / 1000.0
    dur_end = (f(steps)[None, :] - f(t2)) / 1000.0
    sampled = (f(t2) - f(t1)) / 1000.0
    avg_dur = sampled / jnp.maximum(f(n) - 1.0, 1.0)
    if is_counter:
        dur_zero = sampled * v1 / jnp.where(delta == 0, 1.0, delta)
        clamp = (delta > 0) & (v1 >= 0) & (dur_zero < dur_start)
        dur_start = jnp.where(clamp, dur_zero, dur_start)
    thresh = avg_dur * 1.1
    extrap = (sampled
              + jnp.where(dur_start < thresh, dur_start, avg_dur / 2.0)
              + jnp.where(dur_end < thresh, dur_end, avg_dur / 2.0))
    scaled = delta * extrap / jnp.where(sampled == 0, 1.0, sampled)
    if is_rate:
        scaled = scaled / (jnp.asarray(window, dtype) / 1000.0)
    return jnp.where((n >= 2) & (sampled > 0), scaled, jnp.nan)


def _finite_bounds(ts, vals, steps, window):
    """Window bounds restricted to *finite* samples: (j1, j2, n_finite)
    [S,T] row indices of the first/last finite sample in each window and the
    finite count.  NaN rows are "no sample" (gauge gaps, padding) and must
    not act as rate/delta boundary samples."""
    first, last = window_bounds(ts, steps, window)
    fin = jnp.isfinite(vals)
    R = vals.shape[1]
    rows = jnp.arange(R, dtype=first.dtype)[None, :]
    lastfin = lax.cummax(jnp.where(fin, rows, -1), axis=1)
    nextfin = lax.cummin(jnp.where(fin, rows, R), axis=1, reverse=True)
    j2 = _gather_rows(lastfin, jnp.maximum(last - 1, 0))
    j1 = _gather_rows(nextfin, jnp.minimum(first, R - 1))
    n = _range_sum(_prefix(fin.astype(vals.dtype)), first, last)
    valid = (last > first) & (j2 >= j1) & (j1 < last) & (j2 >= 0) & (j1 < R)
    return jnp.where(valid, j1, 0), jnp.where(valid, j2, 0), jnp.where(valid, n, 0)


def _rate_family(ts, vals, steps, window, is_counter: bool, is_rate: bool):
    v = counter_correct(vals) if is_counter else vals
    j1, j2, n = _finite_bounds(ts, vals, steps, window)
    t1 = _gather_rows(ts, j1)
    t2 = _gather_rows(ts, j2)
    v1 = _gather_rows(v, j1)
    v2 = _gather_rows(v, j2)
    # for the counter zero-point clamp the reference uses window head value
    # post-correction (sliding) — corrected v1 is what we pass
    return _extrapolated(v2 - v1, n, t1, t2, steps, window, v1,
                         is_counter, is_rate, vals.dtype)


def rate(ts, vals, steps, window):
    return _rate_family(ts, vals, steps, window, is_counter=True, is_rate=True)


def increase(ts, vals, steps, window):
    return _rate_family(ts, vals, steps, window, is_counter=True, is_rate=False)


def delta_fn(ts, vals, steps, window):
    return _rate_family(ts, vals, steps, window, is_counter=False, is_rate=False)


def _instant_pair(ts, vals, steps, window, correct: bool):
    """Last two *finite* samples in the window (for irate/idelta)."""
    v = counter_correct(vals) if correct else vals
    fin = jnp.isfinite(vals)
    R = vals.shape[1]
    first, last = window_bounds(ts, steps, window)
    rows = jnp.arange(R, dtype=first.dtype)[None, :]
    lastfin = lax.cummax(jnp.where(fin, rows, -1), axis=1)
    j2 = _gather_rows(lastfin, jnp.maximum(last - 1, 0))
    j1 = _gather_rows(lastfin, jnp.maximum(j2 - 1, 0))
    valid = (last > first) & (j2 >= first) & (j2 > 0) & (j1 >= first) & (j1 >= 0) \
        & (j1 < j2)
    j1c, j2c = jnp.maximum(j1, 0), jnp.maximum(j2, 0)
    t1, t2 = _gather_rows(ts, j1c), _gather_rows(ts, j2c)
    v1, v2 = _gather_rows(v, j1c), _gather_rows(v, j2c)
    dt = (t2 - t1).astype(vals.dtype) / 1000.0
    return v1, v2, dt, valid


def irate(ts, vals, steps, window):
    """Instant rate from the last two samples (reference IRateFunction)."""
    v1, v2, dt, valid = _instant_pair(ts, vals, steps, window, correct=True)
    return jnp.where(valid & (dt > 0), (v2 - v1) / dt, jnp.nan)


def idelta(ts, vals, steps, window):
    # zero sampledInterval drops the pair, same as irate (the
    # reference's shared instant-pair guard; ADVICE r2)
    v1, v2, dt, valid = _instant_pair(ts, vals, steps, window, correct=False)
    return jnp.where(valid & (dt > 0), v2 - v1, jnp.nan)


# --------------------------------------------------------------------------
# Gather-path kernels
# --------------------------------------------------------------------------

def max_window_rows(ts, steps, window) -> int:
    """Host-side guard for the gather path: the exact max rows in any window.
    The engine calls this (cheap: one bounds pass) to pick a sufficient
    ``wmax`` bucket — gather_windows silently truncates windows wider than
    ``wmax``, so a too-small static bound must be caught here, not there."""
    first, last = window_bounds(jnp.asarray(ts), jnp.asarray(steps), window)
    return int(jnp.max(last - first))


def gather_windows(ts, vals, steps, window, wmax: int):
    """Materialize bounded per-window tiles: values [S,T,W] (NaN-masked) and
    x-offsets [S,T,W] in seconds relative to the step end (for regression
    kernels).  W = ``wmax`` must bound the max rows per window — see
    :func:`max_window_rows`; windows with more rows are silently truncated."""
    first, last = window_bounds(ts, steps, window)
    idx = first[:, :, None] + jnp.arange(wmax, dtype=first.dtype)[None, None, :]
    in_win = idx < last[:, :, None]
    cidx = jnp.clip(idx, 0, vals.shape[1] - 1)
    vw = jnp.take_along_axis(vals[:, None, :], cidx, axis=2)
    vw = jnp.where(in_win, vw, jnp.nan)
    tw = jnp.take_along_axis(ts[:, None, :], cidx, axis=2)
    xw = (tw - steps[None, :, None]).astype(vals.dtype) / 1000.0
    xw = jnp.where(in_win, xw, jnp.nan)
    return vw, xw


def min_over_time(ts, vals, steps, window, wmax: int):
    vw, _ = gather_windows(ts, vals, steps, window, wmax)
    return _nan_reduce(vw, jnp.min, jnp.inf)


def max_over_time(ts, vals, steps, window, wmax: int):
    vw, _ = gather_windows(ts, vals, steps, window, wmax)
    return _nan_reduce(vw, jnp.max, -jnp.inf)


def _nan_reduce(vw, op, identity):
    fin = jnp.isfinite(vw)
    out = op(jnp.where(fin, vw, identity), axis=-1)
    return jnp.where(fin.any(axis=-1), out, jnp.nan)


def quantile_over_time(ts, vals, steps, window, wmax: int, q: float):
    vw, _ = gather_windows(ts, vals, steps, window, wmax)
    if q > 1.0 or q < 0.0:
        # Prometheus returns ±Inf for out-of-range phi on windows that
        # have samples (reference QuantileOverTimeFunction), where
        # jnp.nanquantile would silently clamp; gather_windows pads
        # only with NaN, so presence = any non-NaN (±Inf samples count)
        live = (~jnp.isnan(vw)).any(axis=-1)
        return jnp.where(live, jnp.inf if q > 1.0 else -jnp.inf, jnp.nan)
    out = jnp.nanquantile(vw, q, axis=-1)
    return out


def mad_over_time(ts, vals, steps, window, wmax: int):
    """Median absolute deviation (reference MedianAbsoluteDeviationOverTime)."""
    vw, _ = gather_windows(ts, vals, steps, window, wmax)
    med = jnp.nanquantile(vw, 0.5, axis=-1)
    return jnp.nanquantile(jnp.abs(vw - med[..., None]), 0.5, axis=-1)


def _linreg(vw, xw):
    """Least-squares (slope, intercept-at-x=0) over the window tile; x is
    seconds relative to the step end (matches Prometheus linearRegression
    with interceptTime = range end)."""
    fin = jnp.isfinite(vw)
    n = fin.sum(axis=-1).astype(vw.dtype)
    x = jnp.where(fin, xw, 0.0)
    y = jnp.where(fin, vw, 0.0)
    sx, sy = x.sum(-1), y.sum(-1)
    sxx, sxy = (x * x).sum(-1), (x * y).sum(-1)
    nsafe = jnp.maximum(n, 1.0)
    cov = sxy - sx * sy / nsafe
    var = sxx - sx * sx / nsafe
    slope = cov / jnp.where(var == 0, 1.0, var)
    intercept = sy / nsafe - slope * (sx / nsafe)
    ok = (n >= 2) & (var > 0)
    return jnp.where(ok, slope, jnp.nan), jnp.where(ok, intercept, jnp.nan)


def deriv(ts, vals, steps, window, wmax: int):
    vw, xw = gather_windows(ts, vals, steps, window, wmax)
    return _linreg(vw, xw)[0]


def predict_linear(ts, vals, steps, window, wmax: int, duration_s: float):
    vw, xw = gather_windows(ts, vals, steps, window, wmax)
    slope, intercept = _linreg(vw, xw)
    return intercept + slope * duration_s


def z_score(ts, vals, steps, window):
    """(last - mean) / stddev over the window (reference ZScoreChunked).

    sd == 0 implies every sample equals the mean, so the exact numerator is
    0 and the result is NaN (0/0); prefix-sum rounding noise would otherwise
    turn it into spurious +/-inf."""
    lastv, _ = last_sample(ts, vals, steps, window)
    _, sd = stdvar_stddev(ts, vals, steps, window)
    _, n, mean = sum_count_avg(ts, vals, steps, window)
    # n < 2 implies sd is exactly 0 mathematically; prefix-sum rounding
    # can leave sd ~ 1e-9 and emit finite garbage without this guard
    return jnp.where((sd == 0) | ~(n >= 2), jnp.nan, (lastv - mean) / sd)


def holt_winters(ts, vals, steps, window, wmax: int, sf: float, tf: float):
    """Double exponential smoothing, Prometheus semantics: level seeded from
    the first sample, trend from the first pair, smoothed forward over the
    window (reference HoltWintersFunction, rangefn/AggrOverTimeFunctions)."""
    vw, _ = gather_windows(ts, vals, steps, window, wmax)  # [S,T,W]

    def step(carry, y):
        s, b, cnt = carry
        valid = jnp.isfinite(y)
        b_eff = jnp.where(cnt == 1, y - s, b)  # trend seeds from the first pair
        x = sf * y + (1 - sf) * (s + b_eff)
        s_new = jnp.where(cnt == 0, y, x)
        b_new = jnp.where(cnt == 0, 0.0, tf * (x - s) + (1 - tf) * b_eff)
        s_out = jnp.where(valid, s_new, s)
        b_out = jnp.where(valid, b_new, b)
        cnt_out = cnt + valid.astype(cnt.dtype)
        return (s_out, b_out, cnt_out), None

    S, T, W = vw.shape
    init = (jnp.zeros((S, T), vw.dtype), jnp.zeros((S, T), vw.dtype),
            jnp.zeros((S, T), jnp.int32))
    (s, b, cnt), _ = lax.scan(step, init, jnp.moveaxis(vw, -1, 0))
    return jnp.where(cnt >= 2, s, jnp.nan)
