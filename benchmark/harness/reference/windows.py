"""Which rows of a series lie in a PromQL range window: plain NumPy, float64,
nothing of the program.

A window at step ``t`` is ``(t - window, t]``.  A series scraped every
``scrape_ms`` with its own phase has its rows at ``ts[:, 0] + r * scrape_ms``,
so the count of rows at or before an instant is a floor division — for every
series and step at once, no loop and no search.
"""

from __future__ import annotations

import numpy as np


def rows_upto(ts: np.ndarray, at: np.ndarray, scrape_ms: int) -> np.ndarray:
    """[S, T] count of each series' rows with timestamp <= ``at[t]``."""
    rows = ts.shape[1]
    if rows > 1 and not (np.diff(ts, axis=1) == scrape_ms).all():
        raise ValueError("the reference wants a fixed scrape interval")
    k = (at[None, :] - ts[:, :1]) // scrape_ms + 1
    return np.clip(k, 0, rows)


def bounds(ts: np.ndarray, steps: np.ndarray, window_ms: int,
           scrape_ms: int) -> tuple:
    """(lo, hi): rows ``lo <= r < hi`` of a series lie in the step's window."""
    return (rows_upto(ts, steps - window_ms, scrape_ms),
            rows_upto(ts, steps, scrape_ms))


def take(a: np.ndarray, idx: np.ndarray) -> np.ndarray:
    """a[s, idx[s, t]] with idx clipped into range (mask afterwards)."""
    return np.take_along_axis(a, np.clip(idx, 0, a.shape[1] - 1), axis=1)
