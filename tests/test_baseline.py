"""C++ CPU baseline (native/src/baseline.cpp) vs a per-series NumPy loop.

The baseline is a stand-in for the JVM's per-row iterator path
(reference: jmh/QueryInMemoryBenchmark.scala:45-249); ``benchmark/run.py``
imports it, so it must build and keep its semantics — counter correction,
extrapolation, group sum — including on gappy/reset data.
"""

import numpy as np
import pytest

from filodb_tpu.native import baseline

pytestmark = pytest.mark.skipif(
    not baseline.available(),
    reason=f"baseline lib unavailable: {baseline.build_error()}")

WINDOW_MS = 300_000


def _oracle_rate_sum(ts, vals, ids, n_groups, steps):
    """Per-series, per-window iterator implementation — the reference's
    ChunkedRateFunction shape (binary search + per-window pass)."""
    out = np.zeros((n_groups, len(steps)))
    cnt = np.zeros((n_groups, len(steps)))
    for s in range(ts.shape[0]):
        fin = np.isfinite(vals[s])
        t_row, v_row = ts[s][fin], vals[s][fin]
        if len(t_row) < 2:
            continue
        corr = np.concatenate([[0.0], np.cumsum(np.maximum(
            v_row[:-1] - v_row[1:], 0.0))])
        v_adj = v_row + corr
        for j, st in enumerate(steps):
            lo = np.searchsorted(t_row, st - WINDOW_MS, side="right")
            hi = np.searchsorted(t_row, st, side="right")
            if hi - lo < 2:
                continue
            t1, t2 = t_row[lo], t_row[hi - 1]
            if t2 == t1:
                continue
            delta = v_adj[hi - 1] - v_adj[lo]
            avg_dur = (t2 - t1) / (hi - lo - 1)
            ext_start = min(st - WINDOW_MS + avg_dur / 2, float(t1)) \
                if t1 - (st - WINDOW_MS) <= avg_dur * 1.1 else t1 - avg_dur / 2
            ext_end = max(st - avg_dur / 2, float(t2)) \
                if st - t2 <= avg_dur * 1.1 else t2 + avg_dur / 2
            rate = (delta * ((ext_end - ext_start) / (t2 - t1))
                    / (WINDOW_MS / 1000.0))
            out[ids[s], j] += rate
            cnt[ids[s], j] += 1
    return np.where(cnt > 0, out, np.nan)


def _gen(seed, S=37, R=64, n_groups=5, gap_frac=0.2, resets=True):
    rng = np.random.default_rng(seed)
    base = 600_000
    step = 10_000
    ts = (base + np.arange(R, dtype=np.int64) * step
          + rng.integers(0, step // 2, (S, R)))
    ts = np.sort(ts, axis=1)
    incr = rng.uniform(0, 10, (S, R))
    vals = np.cumsum(incr, axis=1)
    if resets:
        # counter resets: zero the running value at random positions
        for s in range(S):
            for pos in rng.integers(1, R, size=2):
                vals[s, pos:] -= vals[s, pos]
    mask = rng.random((S, R)) < gap_frac
    vals = np.where(mask, np.nan, vals)
    ids = rng.integers(0, n_groups, S).astype(np.int32)
    steps = np.arange(base + WINDOW_MS, base + R * step, 60_000,
                      dtype=np.int64)
    return ts, vals, ids, steps


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_rate_sum_matches_oracle(seed):
    ts, vals, ids, steps = _gen(seed)
    got = baseline.rate_sum(ts, vals, ids, 5, steps, WINDOW_MS)
    want = _oracle_rate_sum(ts, vals, ids, 5, steps)
    np.testing.assert_allclose(got, want, rtol=1e-12, equal_nan=True)


def test_rate_sum_multithreaded_matches_single():
    ts, vals, ids, steps = _gen(7, S=101)
    one = baseline.rate_sum(ts, vals, ids, 5, steps, WINDOW_MS, nthreads=1)
    four = baseline.rate_sum(ts, vals, ids, 5, steps, WINDOW_MS, nthreads=4)
    np.testing.assert_allclose(one, four, rtol=1e-12, equal_nan=True)


def test_rate_sum_rejects_bad_group_ids():
    ts, vals, ids, steps = _gen(3, S=8)
    ids[3] = 99
    with pytest.raises(ValueError):
        baseline.rate_sum(ts, vals, ids, 5, steps, WINDOW_MS)


def test_sum_over_time_matches_numpy():
    ts, vals, ids, steps = _gen(4, S=23)
    got = baseline.sum_over_time_sum(ts, vals, ids, 5, steps, WINDOW_MS)
    G = 5
    want = np.zeros((G, len(steps)))
    cnt = np.zeros((G, len(steps)))
    for s in range(ts.shape[0]):
        fin = np.isfinite(vals[s])
        t_row, v_row = ts[s][fin], vals[s][fin]
        for j, st in enumerate(steps):
            sel = (t_row > st - WINDOW_MS) & (t_row <= st)
            if sel.any():
                want[ids[s], j] += v_row[sel].sum()
                cnt[ids[s], j] += 1
    want = np.where(cnt > 0, want, np.nan)
    np.testing.assert_allclose(got, want, rtol=1e-12, equal_nan=True)


def test_all_nan_series_contributes_nothing():
    ts, vals, ids, steps = _gen(5, S=4)
    vals[:] = np.nan
    got = baseline.rate_sum(ts, vals, ids, 5, steps, WINDOW_MS)
    assert np.isnan(got).all()
