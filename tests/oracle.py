"""Brute-force numpy oracle for windowed range functions.

Independent, per-series, per-window loop implementation of the Prometheus /
reference semantics (window = (t-w, t]; extrapolatedRate per
RateFunctions.scala) used to validate the vectorized device kernels —
mirrors the reference's test strategy of comparing chunked vs sliding vs
brute force (AggrOverTimeFunctionsSpec)."""

from __future__ import annotations

import numpy as np


def window_indices(ts: np.ndarray, t: int, window: int) -> np.ndarray:
    return np.nonzero((ts > t - window) & (ts <= t))[0]


def counter_correct(vals: np.ndarray) -> np.ndarray:
    out = vals.astype(np.float64).copy()
    corr = 0.0
    prev_raw = None
    for i in range(len(vals)):
        if prev_raw is not None and vals[i] < prev_raw:
            corr += prev_raw
        out[i] = vals[i] + corr
        prev_raw = vals[i]
    return out


def extrapolated_rate(wstart, wend, ts_w, vals_w, is_counter, is_rate):
    n = len(ts_w)
    if n < 2:
        return np.nan
    t1, t2 = ts_w[0], ts_w[-1]
    v1, v2 = vals_w[0], vals_w[-1]
    dur_start = (t1 - wstart) / 1000.0
    dur_end = (wend - t2) / 1000.0
    sampled = (t2 - t1) / 1000.0
    if sampled <= 0:
        return np.nan
    avg_dur = sampled / (n - 1)
    delta = v2 - v1
    if is_counter and delta > 0 and v1 >= 0:
        dur_zero = sampled * (v1 / delta)
        if dur_zero < dur_start:
            dur_start = dur_zero
    thresh = avg_dur * 1.1
    extrap = sampled
    extrap += dur_start if dur_start < thresh else avg_dur / 2
    extrap += dur_end if dur_end < thresh else avg_dur / 2
    scaled = delta * (extrap / sampled)
    if is_rate:
        return scaled / (wend - wstart) * 1000.0
    return scaled


def range_fn(name: str, ts: np.ndarray, vals: np.ndarray, start: int, end: int,
             step: int, window: int, **params) -> np.ndarray:
    """Evaluate one range function for one series over the step grid."""
    steps = np.arange(start, end + 1, step)
    out = np.full(len(steps), np.nan)
    corrected = counter_correct(vals) if name in ("rate", "increase", "irate") else vals
    for j, t in enumerate(steps):
        w = window_indices(ts, t, window)
        vw = vals[w]
        cw = corrected[w]
        fin = np.isfinite(vw)
        if name in ("rate", "increase", "delta"):
            # NaN rows are "no sample": boundaries come from finite samples
            wf = w[fin]
            if len(wf) >= 2:
                out[j] = extrapolated_rate(t - window, t, ts[wf], corrected[wf],
                                           is_counter=name != "delta",
                                           is_rate=name == "rate")
        elif name in ("irate", "idelta"):
            wf = w[fin]
            if len(wf) >= 2:
                dt = (ts[wf][-1] - ts[wf][-2]) / 1000.0
                dv = corrected[wf][-1] - corrected[wf][-2]
                out[j] = dv / dt if name == "irate" and dt > 0 else (
                    dv if name == "idelta" else np.nan)
        elif name == "sum_over_time":
            if fin.any():
                out[j] = np.sum(vw[fin])
        elif name == "count_over_time":
            if fin.any():
                out[j] = fin.sum()
        elif name == "avg_over_time":
            if fin.any():
                out[j] = np.mean(vw[fin])
        elif name == "min_over_time":
            if fin.any():
                out[j] = np.min(vw[fin])
        elif name == "max_over_time":
            if fin.any():
                out[j] = np.max(vw[fin])
        elif name == "stdvar_over_time":
            if fin.any():
                out[j] = np.var(vw[fin])
        elif name == "stddev_over_time":
            if fin.any():
                out[j] = np.std(vw[fin])
        elif name == "changes":
            if fin.any():
                c = 0
                for i in range(1, len(w)):
                    a, b = vals[w[i - 1]], vals[w[i]]
                    if np.isfinite(a) and np.isfinite(b) and a != b:
                        c += 1
                out[j] = c
        elif name == "resets":
            if fin.any():
                c = 0
                for i in range(1, len(w)):
                    if vals[w[i]] < vals[w[i - 1]]:
                        c += 1
                out[j] = c
        elif name == "last":
            fi = np.nonzero(fin)[0]
            if len(fi):
                out[j] = vw[fi[-1]]
        elif name == "timestamp":
            fi = np.nonzero(fin)[0]
            if len(fi):
                out[j] = ts[w][fi[-1]] / 1000.0
        elif name == "quantile_over_time":
            if fin.any():
                out[j] = np.quantile(vw[fin], params["q"])
        elif name == "deriv":
            if fin.sum() >= 2:
                x = (ts[w][fin] - t) / 1000.0
                y = vw[fin]
                if np.var(x) > 0:
                    slope = np.cov(x, y, bias=True)[0, 1] / np.var(x)
                    out[j] = slope
        elif name == "predict_linear":
            if fin.sum() >= 2:
                x = (ts[w][fin] - t) / 1000.0
                y = vw[fin]
                if np.var(x) > 0:
                    slope = np.cov(x, y, bias=True)[0, 1] / np.var(x)
                    intercept = y.mean() - slope * x.mean()
                    out[j] = intercept + slope * params["duration_s"]
        elif name == "z_score":
            fi = np.nonzero(fin)[0]
            if len(fi):
                sd = np.std(vw[fin])
                # sd == 0 (constant window) divides 0/0 -> NaN, which IS
                # the reference semantics; silence the RuntimeWarning the
                # scalar divide would otherwise emit on every suite run
                with np.errstate(invalid="ignore", divide="ignore"):
                    out[j] = (vw[fi[-1]] - np.mean(vw[fin])) / sd
        elif name == "holt_winters":
            y = vw[fin]
            if len(y) >= 2:
                sf, tf = params["sf"], params["tf"]
                s, b = y[0], y[1] - y[0]
                for i in range(1, len(y)):
                    x = sf * y[i] + (1 - sf) * (s + b)
                    b = tf * (x - s) + (1 - tf) * b
                    s = x
                out[j] = s
        elif name == "mad_over_time":
            if fin.any():
                med = np.quantile(vw[fin], 0.5)
                out[j] = np.quantile(np.abs(vw[fin] - med), 0.5)
        else:
            raise ValueError(f"unknown oracle function {name}")
    return out


def grouped_reduce(stepped: np.ndarray, garr: np.ndarray, num_groups: int,
                   op: str) -> np.ndarray:
    """``<op> by (group)`` over a grid kernel's ``[T, lanes]`` output, one
    lane at a time: what ``devicestore._grouped_reduce_impl`` must give.
    A lane mapped to ``num_groups`` is dropped.  sum/avg/count ->
    ``[2, G, T]`` (sum, count of finite cells), moments -> ``[3, G, T]``
    (+ sum of squares), min/max -> ``[G, T]``, NaN where a group has no
    finite cell."""
    T = stepped.shape[0]
    planes = np.zeros((3, num_groups, T))
    lo = np.full((num_groups, T), np.inf)
    hi = np.full((num_groups, T), -np.inf)
    for lane, g in enumerate(garr):
        if g == num_groups:
            continue
        col = stepped[:, lane].astype(np.float64)
        fin = np.isfinite(col)
        planes[0, g] += np.where(fin, col, 0.0)
        planes[1, g] += fin
        planes[2, g] += np.where(fin, col * col, 0.0)
        lo[g] = np.minimum(lo[g], np.where(fin, col, np.inf))
        hi[g] = np.maximum(hi[g], np.where(fin, col, -np.inf))
    if op == "min":
        return np.where(planes[1] > 0, lo, np.nan)
    if op == "max":
        return np.where(planes[1] > 0, hi, np.nan)
    return planes[:3 if op == "moments" else 2]


# ---------------------------------------------------------------------------
# The Prometheus JSON of a result, a cell at a time: ``http/model.py`` as it
# was before PR 32, moved here unchanged as the reference that
# ``to_prom_matrix`` / ``to_prom_vector`` are held to, byte for byte
# (tests/test_http_model.py).  Only the warnings' counters are left out.
# ---------------------------------------------------------------------------

def prom_fmt(v: float) -> str:
    """Prometheus value formatting: shortest repr, NaN as \"NaN\"."""
    import math
    if math.isnan(v):
        return "NaN"
    if math.isinf(v):
        return "+Inf" if v > 0 else "-Inf"
    if v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return repr(v)


def prom_public_tags(tags: dict, metric_column: str = "_metric_") -> dict:
    if metric_column in tags:
        out = {k: v for k, v in tags.items() if k != metric_column}
        out["__name__"] = tags[metric_column]
        return out
    return dict(tags)


def _prom_matrix_entry(tags: dict, ts_ms: np.ndarray, vals: np.ndarray,
                       metric_column: str = "_metric_"):
    fin = ~np.isnan(vals)
    if not fin.any():
        return None
    return {"metric": prom_public_tags(tags, metric_column),
            "values": [[ts_ms[i] / 1000.0, prom_fmt(float(vals[i]))]
                       for i in np.flatnonzero(fin)]}


def _prom_warnings(resp: dict, result) -> dict:
    warnings = []
    n = result.stats.corrupt_chunks_excluded
    if n:
        warnings.append(
            f"partial data: {n} corrupt chunk(s) quarantined and "
            f"excluded from results (see /admin/integrity)")
    down = result.stats.shards_down
    if down:
        warnings.append(
            f"partial data: {down} shard(s) unreachable; their series "
            f"are missing from results (allow_partial_results)")
    if warnings:
        resp["warnings"] = warnings
    return resp


def prom_matrix(result, metric_column: str = "_metric_") -> dict:
    """Range-query response (resultType=matrix)."""
    from filodb_tpu.query.model import PeriodicBatch, RawBatch, ScalarResult
    out = []
    for b in result.batches:
        if isinstance(b, PeriodicBatch):
            for tags, ts, vals in b.to_series():
                e = _prom_matrix_entry(tags, ts, vals, metric_column)
                if e is not None:
                    out.append(e)
        elif isinstance(b, ScalarResult):
            ts = np.asarray(b.steps.timestamps())
            e = _prom_matrix_entry({}, ts, np.asarray(b.values))
            if e is not None:
                out.append(e)
        elif isinstance(b, RawBatch) and b.batch is not None:
            for i, tags in enumerate(b.keys):
                n = int(b.batch.row_counts[i])
                e = _prom_matrix_entry(tags,
                                       np.asarray(b.batch.timestamps[i][:n]),
                                       np.asarray(b.batch.values[i][:n]))
                if e is not None:
                    out.append(e)
    return _prom_warnings(
        {"status": "success",
         "data": {"resultType": "matrix", "result": out}}, result)


def prom_vector(result, time_ms: int, metric_column: str = "_metric_") -> dict:
    """Instant-query response (resultType=vector): last value at/before
    the evaluation timestamp."""
    from filodb_tpu.query.model import PeriodicBatch, ScalarResult
    out = []
    for b in result.batches:
        if isinstance(b, PeriodicBatch):
            for tags, ts, vals in b.to_series():
                fin = np.flatnonzero(~np.isnan(vals) & (ts <= time_ms))
                if len(fin):
                    i = fin[-1]
                    out.append({"metric": prom_public_tags(tags,
                                                           metric_column),
                                "value": [time_ms / 1000.0,
                                          prom_fmt(float(vals[i]))]})
        elif isinstance(b, ScalarResult):
            vals = np.asarray(b.values)
            if len(vals):
                return _prom_warnings(
                    {"status": "success",
                     "data": {"resultType": "scalar",
                              "value": [time_ms / 1000.0,
                                        prom_fmt(float(vals[-1]))]}}, result)
    return _prom_warnings(
        {"status": "success",
         "data": {"resultType": "vector", "result": out}}, result)
