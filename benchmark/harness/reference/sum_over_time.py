"""``sum_over_time(m[w])`` for every series and step at once, float64."""

from __future__ import annotations

import numpy as np

from . import windows


def series(ts, vals, steps, window_ms, scrape_ms):
    """[S, T]; NaN where a window is empty."""
    lo, hi = windows.bounds(ts, steps, window_ms, scrape_ms)
    csum = np.concatenate([np.zeros((len(vals), 1)),
                           np.cumsum(vals, axis=1)], axis=1)
    out = np.take_along_axis(csum, hi, axis=1) \
        - np.take_along_axis(csum, lo, axis=1)
    return np.where(hi > lo, out, np.nan)
