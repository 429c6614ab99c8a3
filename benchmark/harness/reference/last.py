"""An instant selector ``m{...}`` on a step grid: the newest sample inside the
look-back window (``window_ms``: the 5 min staleness default), float64."""

from __future__ import annotations

import numpy as np

from . import windows


def series(ts, vals, steps, window_ms, scrape_ms):
    """[S, T]; NaN where the look-back holds no sample."""
    lo, hi = windows.bounds(ts, steps, window_ms, scrape_ms)
    return np.where(hi > lo, windows.take(vals, hi - 1), np.nan)
