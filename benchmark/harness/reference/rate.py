"""``rate(m[w])``: Prometheus' extrapolated per-second rate of a counter,
for every series and step at once, float64."""

from __future__ import annotations

import numpy as np

from . import windows


def series(ts, vals, steps, window_ms, scrape_ms):
    """[S, T]; NaN where a window holds fewer than two samples."""
    lo, hi = windows.bounds(ts, steps, window_ms, scrape_ms)
    n = hi - lo
    # a counter that falls has restarted: carry what it had reached
    drop = np.where(np.diff(vals, axis=1) < 0, vals[:, :-1], 0.0)
    corrected = vals.copy()
    corrected[:, 1:] += np.cumsum(drop, axis=1)
    t1 = windows.take(ts, lo).astype(np.float64)
    t2 = windows.take(ts, hi - 1).astype(np.float64)
    v1 = windows.take(corrected, lo)
    v2 = windows.take(corrected, hi - 1)
    # within the window only the resets inside it count
    v1_in = windows.take(vals, lo)
    delta = v2 - v1
    wend = steps[None, :].astype(np.float64)
    wstart = wend - window_ms
    with np.errstate(divide="ignore", invalid="ignore"):
        sampled = (t2 - t1) / 1000.0
        dur_start = (t1 - wstart) / 1000.0
        dur_end = (wend - t2) / 1000.0
        avg = sampled / (n - 1)
        # a counter cannot have been below zero before the window
        dur_zero = sampled * (v1_in / delta)
        dur_start = np.where((delta > 0) & (v1_in >= 0)
                             & (dur_zero < dur_start), dur_zero, dur_start)
        thresh = avg * 1.1
        extrap = sampled \
            + np.where(dur_start < thresh, dur_start, avg / 2) \
            + np.where(dur_end < thresh, dur_end, avg / 2)
        out = delta * (extrap / sampled) / (window_ms / 1000.0)
    return np.where((n >= 2) & (sampled > 0), out, np.nan)
