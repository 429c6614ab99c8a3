"""Aligned-grid leaf kernels vs the general windows implementation.

The grid layout invariant ([B, S] time-major: row c holds the sample
with ts in (t0+(c-1)*gstep, t0+c*gstep]) makes rate windows static
slices; these
tests prove the fast path is semantically identical to
filodb_tpu.ops.windows.rate/increase (which the oracle-backed
tests/test_windows.py already validates against the reference's
RateFunctions semantics).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from filodb_tpu.memstore.devicestore import (_ONEHOT_MAX_G,
                                             _grouped_reduce_impl)
from filodb_tpu.ops import windows
from filodb_tpu.ops.grid import (GridQuery, rate_grid, rate_grid_ref,
                                 supports_grid)
from tests import oracle


def _scattered_groups(n_lanes, num_groups, seed=5):
    """lane -> group the way a served plan has it: groups interleaved
    over the lanes, one lane in ten unrequested (the drop bucket)."""
    rng = np.random.default_rng(seed)
    garr = rng.integers(0, num_groups, n_lanes).astype(np.int32)
    garr[rng.random(n_lanes) < 0.1] = num_groups
    return garr


def _assert_served_sum_by(stepped, ref, garr, num_groups, rtol):
    """What serves ``sum by (g)``: the grid kernel's [T, lanes] output
    through the device store's grouped reduce, against the portable
    reference + a per-lane NumPy reduce."""
    got = np.asarray(_grouped_reduce_impl(stepped, jnp.asarray(garr),
                                          num_groups, "sum"))
    want = oracle.grouped_reduce(np.asarray(ref), garr, num_groups, "sum")
    np.testing.assert_allclose(got[0], want[0], rtol=rtol, atol=1e-5)
    np.testing.assert_array_equal(got[1], want[1])


def _clip(ts, vals):
    """Apply the kernel layout contract: row 0 = first bucket of the
    first window (drop the pre-window bucket the generator emits)."""
    return ts[1:], vals[1:]

STEP = 60_000
T0 = 600_000
B = 40          # bucket columns
K = 5           # window = 5 buckets


def _aligned_data(n_series=64, seed=0, gap_frac=0.15, reset_frac=0.05):
    """[B, S] grid honoring the layout invariant, with NaN gaps and
    counter resets."""
    rng = np.random.default_rng(seed)
    base = (np.arange(B, dtype=np.int64) * STEP + T0 - STEP + 1)[:, None]
    jitter = rng.integers(0, STEP - 1, size=(B, n_series))
    ts = (base + jitter).astype(np.int64)
    incr = rng.random((B, n_series)) * 10.0
    vals = np.cumsum(incr, axis=0)
    resets = rng.random((B, n_series)) < reset_frac
    # a reset drops the counter back near zero from that row on
    for s in range(n_series):
        for c in np.where(resets[:, s])[0]:
            vals[c:, s] -= vals[c, s] * 0.9
    vals = vals.astype(np.float64)
    gaps = rng.random((B, n_series)) < gap_frac
    vals[gaps] = np.nan
    return jnp.asarray(ts), jnp.asarray(vals)


def _steps(n=None):
    first = T0 + K * STEP
    last = T0 + (B - 1) * STEP
    s = np.arange(first, last + 1, STEP, dtype=np.int64)
    return jnp.asarray(s if n is None else s[:n])


class TestGridRef:
    """Portable reference implementation vs windows.rate (exact)."""

    @pytest.mark.parametrize("is_rate", [True, False])
    def test_matches_windows(self, is_rate):
        ts, vals = _aligned_data()
        steps = _steps()
        q = GridQuery(nsteps=len(steps), kbuckets=K, gstep_ms=STEP,
                      is_rate=is_rate)
        cts, cvals = _clip(ts, vals)
        got = np.asarray(rate_grid_ref(cts.astype(jnp.int64),
                                       cvals.astype(jnp.float32),
                                       int(steps[0]), q))
        # the general path sees DENSE samples (read_range drops gaps);
        # compact each series and pad trailing rows like scan_batch does
        tsn, vn = np.asarray(cts), np.asarray(cvals)
        S = tsn.shape[1]
        dense_ts = np.full((S, tsn.shape[0]), 2**60, np.int64)
        dense_v = np.full((S, tsn.shape[0]), np.nan)
        for s in range(S):
            keep = np.isfinite(vn[:, s])
            k = keep.sum()
            dense_ts[s, :k] = tsn[keep, s]
            dense_v[s, :k] = vn[keep, s]
        fn = windows.rate if is_rate else windows.increase
        want = np.asarray(fn(jnp.asarray(dense_ts),
                             jnp.asarray(dense_v, dtype=jnp.float32), steps,
                             jnp.asarray(K * STEP, jnp.int64))).T
        assert (np.isfinite(got) == np.isfinite(want)).all()
        both = np.isfinite(got) & np.isfinite(want)
        np.testing.assert_allclose(got[both], want[both], rtol=2e-5)

    def test_all_nan_series(self):
        ts, vals = _aligned_data(n_series=8)
        vals = vals.at[:, 3].set(jnp.nan)
        steps = _steps()
        q = GridQuery(len(steps), K, STEP, True)
        cts, cvals = _clip(ts, vals)
        got = np.asarray(rate_grid_ref(cts, cvals.astype(jnp.float32),
                                       int(steps[0]), q))
        assert np.isnan(got[:, 3]).all()

    def test_single_sample_windows_are_nan(self):
        """n < 2 in a window -> no rate (reference: extrapolatedRate
        requires two samples)."""
        ts, vals = _aligned_data(n_series=4, gap_frac=0.0)
        # first window covers cols 1..K; keep only col K finite in series 0
        vals = vals.at[1:K, 0].set(jnp.nan)
        steps = _steps()
        q = GridQuery(len(steps), K, STEP, True)
        cts, cvals = _clip(ts, vals)
        got = np.asarray(rate_grid_ref(cts, cvals.astype(jnp.float32),
                                       int(steps[0]), q))
        assert np.isnan(got[0, 0])

    def test_reset_after_gap_matches_dense_path(self):
        """A counter reset right after a missed scrape: the grid holds a
        NaN hole where the dense general path holds adjacent samples; the
        correction must still fire (regression: prev-compare against NaN
        silently skipped it)."""
        n = 16
        base = (np.arange(B, dtype=np.int64) * STEP + T0 - STEP + 1)[:, None]
        ts = (base + 10_000 + np.zeros((B, n), np.int64))
        vals = np.cumsum(np.full((B, n), 7.0), axis=0)
        vals[10:, :] -= vals[10, 0] - 1.0          # reset at row 10
        vals[9, :] = np.nan                        # missed scrape before it
        tsj = jnp.asarray(ts)
        vj = jnp.asarray(vals)
        steps = _steps()
        q = GridQuery(len(steps), K, STEP, True)
        cts, cvals = _clip(tsj, vj)
        got = np.asarray(rate_grid_ref(cts, cvals.astype(jnp.float32),
                                       int(steps[0]), q))
        # dense oracle: drop the NaN row entirely (what read_range yields)
        keep = ~np.isnan(vals[:, 0])
        dts = jnp.asarray(ts[keep][1:].T)
        dvals = jnp.asarray(vals[keep][1:].T)
        want = np.asarray(windows.rate(dts, dvals, steps,
                                       jnp.asarray(K * STEP, jnp.int64))).T
        both = np.isfinite(got) & np.isfinite(want)
        assert both.any()
        np.testing.assert_allclose(got[both], want[both], rtol=2e-5)

    def test_supports_grid(self):
        assert supports_grid(300_000, 60_000, 60_000)
        assert not supports_grid(300_000, 30_000, 60_000)   # step != gstep
        assert not supports_grid(290_000, 60_000, 60_000)   # non-multiple

    def test_auto_falls_back_off_tpu(self):
        ts, vals = _clip(*_aligned_data(n_series=16))
        steps = _steps()
        q = GridQuery(len(steps), K, STEP, True)
        from filodb_tpu.ops.grid import rate_grid_auto
        got = np.asarray(rate_grid_auto(ts, vals.astype(jnp.float32),
                                        int(steps[0]), q))
        want = np.asarray(rate_grid_ref(ts, vals.astype(jnp.float32),
                                        int(steps[0]), q))
        np.testing.assert_array_equal(np.isfinite(got), np.isfinite(want))

    def test_shape_validation(self):
        ts, vals = _clip(*_aligned_data(n_series=16))
        ts = ts.astype(jnp.int32)
        vals = vals.astype(jnp.float32)
        steps = _steps()
        q = GridQuery(len(steps), K, STEP, True)
        with pytest.raises(ValueError, match="multiple of lanes"):
            rate_grid(ts, vals, int(steps[0]), q, lanes=1024)
        with pytest.raises(ValueError, match="rows"):
            rate_grid(ts[:3], vals[:3], int(steps[0]), q, lanes=16,
                      interpret=True)


class TestGridPallasInterpret:
    """Pallas kernels in interpreter mode (no TPU needed) vs the
    portable reference."""

    def _data128(self):
        ts, vals = _clip(*_aligned_data(n_series=128))
        return ts.astype(jnp.int32), vals.astype(jnp.float32)

    def test_series_kernel(self):
        ts, vals = self._data128()
        steps = _steps()
        q = GridQuery(len(steps), K, STEP, True)
        want = np.asarray(rate_grid_ref(ts, vals, int(steps[0]), q))
        got = np.asarray(rate_grid(ts, vals, int(steps[0]), q,
                                   lanes=128, interpret=True))
        assert (np.isfinite(got) == np.isfinite(want)).all()
        both = np.isfinite(got)
        # the in-kernel log-step scan associates the correction cumsum
        # differently from jnp.cumsum: f32 round-off only
        np.testing.assert_allclose(got[both], want[both], rtol=5e-5,
                                   atol=1e-6)

    def test_grouped_kernel(self):
        ts, vals = self._data128()
        steps = _steps()
        q = GridQuery(len(steps), K, STEP, True)
        stepped = rate_grid(ts, vals, int(steps[0]), q, lanes=128,
                            interpret=True)
        ref = rate_grid_ref(ts, vals, int(steps[0]), q)
        _assert_served_sum_by(stepped, ref, _scattered_groups(128, 8), 8,
                              rtol=5e-5)


class TestGroupedReduce:
    """devicestore._grouped_reduce_impl, the XLA reduce every grouped
    serving program ends in, against the per-lane NumPy reduce."""

    # G + 1 <= _ONEHOT_MAX_G takes the one-hot matmul, past it the
    # segment_sum fall-through; min/max take segment_min/max at any G
    @pytest.mark.parametrize("num_groups", [8, _ONEHOT_MAX_G],
                             ids=["onehot", "segment"])
    @pytest.mark.parametrize("op", ["sum", "avg", "count", "moments",
                                    "min", "max"])
    def test_matches_numpy(self, op, num_groups):
        rng = np.random.default_rng(17)
        T, lanes = 6, 512
        stepped = (rng.random((T, lanes)) * 200 - 50).astype(np.float32)
        stepped[rng.random((T, lanes)) < 0.2] = np.nan
        stepped[:, 40:48] = np.nan              # lanes with no answer
        garr = _scattered_groups(lanes, num_groups)
        garr[garr == 5] = num_groups
        garr[40:44] = 5                         # group 5: NaN lanes only
        garr[100:140] = 3
        got = np.asarray(_grouped_reduce_impl(
            jnp.asarray(stepped), jnp.asarray(garr), num_groups, op))
        want = oracle.grouped_reduce(stepped, garr, num_groups, op)
        assert got.shape == want.shape
        if op in ("min", "max"):
            assert np.isnan(want[5]).all() and np.isfinite(want[3]).all()
        np.testing.assert_allclose(got, want, rtol=2e-5, equal_nan=True)
        with pytest.raises(ValueError, match="unsupported grouped op"):
            _grouped_reduce_impl(jnp.asarray(stepped), jnp.asarray(garr),
                                 num_groups, "median")


class TestGridAggOps:
    """The *_over_time family + instant-selector 'last' on the aligned
    grid vs the general windows kernels (exact semantics match)."""

    @pytest.mark.parametrize("op,wfn", [
        ("sum", "sum_over_time"), ("count", "count_over_time"),
        ("avg", "avg_over_time"), ("last", "last_sample")])
    def test_matches_windows(self, op, wfn):
        ts, vals = _aligned_data()
        steps = _steps()
        q = GridQuery(nsteps=len(steps), kbuckets=K, gstep_ms=STEP, op=op)
        cts, cvals = _clip(ts, vals)
        got = np.asarray(rate_grid_ref(cts.astype(jnp.int64),
                                       cvals.astype(jnp.float64),
                                       int(steps[0]), q))
        tsn, vn = np.asarray(cts), np.asarray(cvals)
        S = tsn.shape[1]
        dense_ts = np.full((S, tsn.shape[0]), 2**60, np.int64)
        dense_v = np.full((S, tsn.shape[0]), np.nan)
        for s in range(S):
            keep = np.isfinite(vn[:, s])
            k = keep.sum()
            dense_ts[s, :k] = tsn[keep, s]
            dense_v[s, :k] = vn[keep, s]
        fn = getattr(windows, wfn)
        want = np.asarray(fn(jnp.asarray(dense_ts),
                             jnp.asarray(dense_v), steps,
                             jnp.asarray(K * STEP, jnp.int64)))
        if want.ndim == 3:          # last_sample returns (value, ts) pair
            want = want[0]
        want = want.T
        assert (np.isfinite(got) == np.isfinite(want)).all(), op
        both = np.isfinite(got) & np.isfinite(want)
        np.testing.assert_allclose(got[both], want[both], rtol=1e-12)

    @pytest.mark.parametrize("op,wfn", [
        ("min", "min_over_time"), ("max", "max_over_time")])
    def test_minmax_matches_windows(self, op, wfn):
        ts, vals = _aligned_data()
        steps = _steps()
        q = GridQuery(nsteps=len(steps), kbuckets=K, gstep_ms=STEP, op=op)
        cts, cvals = _clip(ts, vals)
        got = np.asarray(rate_grid_ref(cts.astype(jnp.int64),
                                       cvals.astype(jnp.float64),
                                       int(steps[0]), q))
        tsn, vn = np.asarray(cts), np.asarray(cvals)
        S = tsn.shape[1]
        dense_ts = np.full((S, tsn.shape[0]), 2**60, np.int64)
        dense_v = np.full((S, tsn.shape[0]), np.nan)
        for s in range(S):
            keep = np.isfinite(vn[:, s])
            k = keep.sum()
            dense_ts[s, :k] = tsn[keep, s]
            dense_v[s, :k] = vn[keep, s]
        from filodb_tpu.query import rangefns as rf
        wmax = rf.bucket_wmax(dense_ts, np.asarray(steps), K * STEP)
        fn = getattr(windows, wfn)
        want = np.asarray(fn(jnp.asarray(dense_ts), jnp.asarray(dense_v),
                             steps, jnp.asarray(K * STEP, jnp.int64),
                             wmax)).T
        assert (np.isfinite(got) == np.isfinite(want)).all(), op
        both = np.isfinite(got) & np.isfinite(want)
        np.testing.assert_allclose(got[both], want[both], rtol=1e-12)

    @pytest.mark.parametrize("op", ["sum", "count", "avg", "min", "max",
                                    "last"])
    def test_pallas_interpret_matches_ref(self, op):
        from filodb_tpu.ops.grid import rate_grid
        ts, vals = _aligned_data(n_series=128)
        steps = _steps()
        q = GridQuery(nsteps=len(steps), kbuckets=K, gstep_ms=STEP, op=op)
        cts, cvals = _clip(ts, vals)
        ref = np.asarray(rate_grid_ref(cts.astype(jnp.int32),
                                       cvals.astype(jnp.float32),
                                       int(steps[0]), q))
        pal = np.asarray(rate_grid(cts.astype(jnp.int32),
                                   cvals.astype(jnp.float32),
                                   jnp.int32(int(steps[0])), q, lanes=128,
                                   interpret=True))
        assert (np.isfinite(ref) == np.isfinite(pal)).all()
        both = np.isfinite(ref)
        np.testing.assert_allclose(pal[both], ref[both], rtol=1e-6)


def _dense_data(n_series=128, n_empty=16, seed=3, reset_frac=0.05):
    """Data satisfying the dense-lane contract: every lane fully finite
    over all rows, except the last ``n_empty`` lanes which are all-NaN
    (the device store's padding / unrequested lanes)."""
    ts, vals = _aligned_data(n_series=n_series, seed=seed, gap_frac=0.0,
                             reset_frac=reset_frac)
    vals = vals.at[:, n_series - n_empty:].set(jnp.nan)
    return _clip(ts, vals)


class TestGridMomentOps:
    """stddev/stdvar on the grid vs the general windows kernels (both
    use grand-mean-centered moments, so results match tightly)."""

    @pytest.mark.parametrize("op,wfn", [
        ("stdvar", "stdvar_over_time"), ("stddev", "stddev_over_time")])
    @pytest.mark.parametrize("gap_frac", [0.0, 0.15])
    def test_matches_windows(self, op, wfn, gap_frac):
        ts, vals = _aligned_data(gap_frac=gap_frac)
        steps = _steps()
        q = GridQuery(nsteps=len(steps), kbuckets=K, gstep_ms=STEP, op=op)
        cts, cvals = _clip(ts, vals)
        got = np.asarray(rate_grid_ref(cts.astype(jnp.int64),
                                       cvals.astype(jnp.float64),
                                       int(steps[0]), q))
        dense_ts, dense_v = _compact(cts, cvals)
        fn = getattr(windows, wfn)
        want = np.asarray(fn(jnp.asarray(dense_ts), jnp.asarray(dense_v),
                             steps, jnp.asarray(K * STEP, jnp.int64))).T
        assert (np.isfinite(got) == np.isfinite(want)).all(), op
        both = np.isfinite(got) & np.isfinite(want)
        # summation order differs (K-slice loop vs prefix scans); near-
        # zero variances amplify the rounding through sqrt -> atol
        np.testing.assert_allclose(got[both], want[both], rtol=1e-7,
                                   atol=1e-5)


class TestGridRegressionOps:
    """deriv / predict_linear / z_score on the grid vs the general
    windows kernels (least-squares + moment semantics)."""

    @pytest.mark.parametrize("gap_frac", [0.0, 0.15])
    def test_deriv_matches_windows(self, gap_frac):
        from filodb_tpu.query import rangefns as rf
        ts, vals = _aligned_data(gap_frac=gap_frac)
        steps = _steps()
        q = GridQuery(nsteps=len(steps), kbuckets=K, gstep_ms=STEP,
                      op="deriv")
        cts, cvals = _clip(ts, vals)
        got = np.asarray(rate_grid_ref(cts.astype(jnp.int64),
                                       cvals.astype(jnp.float64),
                                       int(steps[0]), q))
        dense_ts, dense_v = _compact(cts, cvals)
        wmax = rf.bucket_wmax(dense_ts, np.asarray(steps), K * STEP)
        want = np.asarray(windows.deriv(jnp.asarray(dense_ts),
                                        jnp.asarray(dense_v), steps,
                                        jnp.asarray(K * STEP, jnp.int64),
                                        wmax)).T
        assert (np.isfinite(got) == np.isfinite(want)).all()
        both = np.isfinite(got) & np.isfinite(want)
        np.testing.assert_allclose(got[both], want[both], rtol=1e-6,
                                   atol=1e-9)

    def test_predict_linear_matches_windows(self):
        from filodb_tpu.query import rangefns as rf
        ts, vals = _aligned_data(gap_frac=0.1)
        steps = _steps()
        horizon = 600.0
        q = GridQuery(nsteps=len(steps), kbuckets=K, gstep_ms=STEP,
                      op="predict_linear", farg=horizon)
        cts, cvals = _clip(ts, vals)
        got = np.asarray(rate_grid_ref(cts.astype(jnp.int64),
                                       cvals.astype(jnp.float64),
                                       int(steps[0]), q))
        dense_ts, dense_v = _compact(cts, cvals)
        wmax = rf.bucket_wmax(dense_ts, np.asarray(steps), K * STEP)
        want = np.asarray(windows.predict_linear(
            jnp.asarray(dense_ts), jnp.asarray(dense_v), steps,
            jnp.asarray(K * STEP, jnp.int64), wmax, horizon)).T
        assert (np.isfinite(got) == np.isfinite(want)).all()
        both = np.isfinite(got) & np.isfinite(want)
        np.testing.assert_allclose(got[both], want[both], rtol=1e-6,
                                   atol=1e-7)

    @pytest.mark.parametrize("gap_frac", [0.0, 0.15])
    def test_z_score_matches_windows(self, gap_frac):
        ts, vals = _aligned_data(gap_frac=gap_frac)
        steps = _steps()
        q = GridQuery(nsteps=len(steps), kbuckets=K, gstep_ms=STEP,
                      op="zscore")
        cts, cvals = _clip(ts, vals)
        got = np.asarray(rate_grid_ref(cts.astype(jnp.int64),
                                       cvals.astype(jnp.float64),
                                       int(steps[0]), q))
        dense_ts, dense_v = _compact(cts, cvals)
        want = np.asarray(windows.z_score(jnp.asarray(dense_ts),
                                          jnp.asarray(dense_v), steps,
                                          jnp.asarray(K * STEP,
                                                      jnp.int64))).T
        # both paths now apply the n >= 2 guard (a single sample's sd is
        # exactly 0 mathematically; rounding noise must not leak a
        # finite garbage z) — masks must agree exactly
        assert (np.isfinite(got) == np.isfinite(want)).all()
        both = np.isfinite(got) & np.isfinite(want)
        np.testing.assert_allclose(got[both], want[both], rtol=1e-6,
                                   atol=1e-7)

    @pytest.mark.parametrize("op", ["deriv", "predict_linear", "zscore"])
    def test_pallas_interpret(self, op):
        cts, cvals = _dense_data()
        steps = _steps()
        q = GridQuery(nsteps=len(steps), kbuckets=K, gstep_ms=STEP, op=op,
                      farg=300.0)
        ref = np.asarray(rate_grid_ref(cts.astype(jnp.int32),
                                       cvals.astype(jnp.float32),
                                       int(steps[0]), q))
        pal = np.asarray(rate_grid(cts.astype(jnp.int32),
                                   cvals.astype(jnp.float32),
                                   jnp.int32(int(steps[0])), q, lanes=128,
                                   interpret=True))
        assert (np.isfinite(ref) == np.isfinite(pal)).all(), op
        both = np.isfinite(ref)
        np.testing.assert_allclose(pal[both], ref[both], rtol=1e-3,
                                   atol=1e-3)


def _compact(cts, cvals):
    """Per-series NaN compaction: the layout the general kernels see."""
    tsn, vn = np.asarray(cts), np.asarray(cvals)
    S = tsn.shape[1]
    dense_ts = np.full((S, tsn.shape[0]), 2**60, np.int64)
    dense_v = np.full((S, tsn.shape[0]), np.nan)
    for s in range(S):
        keep = np.isfinite(vn[:, s])
        k = keep.sum()
        dense_ts[s, :k] = tsn[keep, s]
        dense_v[s, :k] = vn[keep, s]
    return dense_ts, dense_v


class TestGridDenseOnlyOps:
    """changes/resets/irate/idelta: consecutive-sample adjacency ops —
    grid-served only under the dense contract; exact vs windows."""

    @pytest.mark.parametrize("op,wfn", [
        ("changes", "changes_over_time"), ("resets", "resets_over_time"),
        ("irate", "irate"), ("idelta", "idelta")])
    def test_dense_matches_windows(self, op, wfn):
        cts, cvals = _dense_data(reset_frac=0.1)
        steps = _steps()
        q = GridQuery(nsteps=len(steps), kbuckets=K, gstep_ms=STEP, op=op,
                      dense=True)
        got = np.asarray(rate_grid_ref(cts.astype(jnp.int64),
                                       cvals.astype(jnp.float64),
                                       int(steps[0]), q))
        # live lanes are fully dense: compaction is the identity there
        tsn, vn = np.asarray(cts), np.asarray(cvals)
        fn = getattr(windows, wfn)
        want = np.asarray(fn(jnp.asarray(tsn.T), jnp.asarray(vn.T), steps,
                             jnp.asarray(K * STEP, jnp.int64))).T
        live = np.isfinite(vn).any(axis=0)
        got_l, want_l = got[:, live], want[:, live]
        assert (np.isfinite(got_l) == np.isfinite(want_l)).all(), op
        both = np.isfinite(got_l)
        np.testing.assert_allclose(got_l[both], want_l[both], rtol=1e-9)
        # empty lanes come back NaN
        assert np.isnan(got[:, ~live]).all()

    @pytest.mark.parametrize("gap_frac,dense", [(0.0, True), (0.15, False)])
    def test_delta_matches_windows(self, gap_frac, dense):
        ts, vals = _aligned_data(gap_frac=gap_frac)
        steps = _steps()
        q = GridQuery(nsteps=len(steps), kbuckets=K, gstep_ms=STEP,
                      op="delta", dense=dense)
        cts, cvals = _clip(ts, vals)
        got = np.asarray(rate_grid_ref(cts.astype(jnp.int64),
                                       cvals.astype(jnp.float64),
                                       int(steps[0]), q))
        dense_ts, dense_v = _compact(cts, cvals)
        want = np.asarray(windows.delta_fn(
            jnp.asarray(dense_ts), jnp.asarray(dense_v), steps,
            jnp.asarray(K * STEP, jnp.int64))).T
        assert (np.isfinite(got) == np.isfinite(want)).all()
        both = np.isfinite(got) & np.isfinite(want)
        np.testing.assert_allclose(got[both], want[both], rtol=1e-9)

    @pytest.mark.parametrize("gap_frac,dense", [(0.0, True), (0.15, False)])
    def test_timestamp_matches_windows(self, gap_frac, dense):
        ts, vals = _aligned_data(gap_frac=gap_frac)
        steps = _steps()
        q = GridQuery(nsteps=len(steps), kbuckets=K, gstep_ms=STEP,
                      op="timestamp", dense=dense)
        cts, cvals = _clip(ts, vals)
        got = np.asarray(rate_grid_ref(cts.astype(jnp.int64),
                                       cvals.astype(jnp.float64),
                                       int(steps[0]), q))
        dense_ts, dense_v = _compact(cts, cvals)
        want = np.asarray(windows.timestamp_fn(
            jnp.asarray(dense_ts), jnp.asarray(dense_v), steps,
            jnp.asarray(K * STEP, jnp.int64))).T
        assert (np.isfinite(got) == np.isfinite(want)).all()
        both = np.isfinite(got) & np.isfinite(want)
        # the kernel emits WINDOW-relative seconds (f32-exact); the
        # serving layer re-bases in f64 — re-base here the same way
        abs_got = got + (np.asarray(steps, dtype=np.float64)
                         / 1000.0)[:, None]
        np.testing.assert_allclose(abs_got[both], want[both], rtol=1e-12)

    @pytest.mark.parametrize("phi", [0.0, 0.25, 0.5, 0.9, 1.0])
    def test_quantile_matches_windows(self, phi):
        from filodb_tpu.query import rangefns as rf
        cts, cvals = _dense_data()
        steps = _steps()
        q = GridQuery(nsteps=len(steps), kbuckets=K, gstep_ms=STEP,
                      op="quantile", dense=True, farg=phi)
        got = np.asarray(rate_grid_ref(cts.astype(jnp.int64),
                                       cvals.astype(jnp.float64),
                                       int(steps[0]), q))
        dense_ts, dense_v = _compact(cts, cvals)
        wmax = rf.bucket_wmax(dense_ts, np.asarray(steps), K * STEP)
        want = np.asarray(windows.quantile_over_time(
            jnp.asarray(dense_ts), jnp.asarray(dense_v), steps,
            jnp.asarray(K * STEP, jnp.int64), wmax, phi)).T
        live = np.isfinite(np.asarray(cvals)).any(axis=0)
        assert (np.isfinite(got) == np.isfinite(want))[:, live].all()
        both = np.isfinite(got) & np.isfinite(want)
        np.testing.assert_allclose(got[both], want[both], rtol=1e-9)

    def test_mad_matches_windows(self):
        from filodb_tpu.query import rangefns as rf
        cts, cvals = _dense_data()
        steps = _steps()
        q = GridQuery(nsteps=len(steps), kbuckets=K, gstep_ms=STEP,
                      op="mad", dense=True)
        got = np.asarray(rate_grid_ref(cts.astype(jnp.int64),
                                       cvals.astype(jnp.float64),
                                       int(steps[0]), q))
        dense_ts, dense_v = _compact(cts, cvals)
        wmax = rf.bucket_wmax(dense_ts, np.asarray(steps), K * STEP)
        want = np.asarray(windows.mad_over_time(
            jnp.asarray(dense_ts), jnp.asarray(dense_v), steps,
            jnp.asarray(K * STEP, jnp.int64), wmax)).T
        live = np.isfinite(np.asarray(cvals)).any(axis=0)
        assert (np.isfinite(got) == np.isfinite(want))[:, live].all()
        both = np.isfinite(got) & np.isfinite(want)
        np.testing.assert_allclose(got[both], want[both], rtol=1e-9)

    def test_holt_winters_matches_windows(self):
        from filodb_tpu.query import rangefns as rf
        cts, cvals = _dense_data()
        steps = _steps()
        q = GridQuery(nsteps=len(steps), kbuckets=K, gstep_ms=STEP,
                      op="holt_winters", dense=True, farg=0.3, farg2=0.1)
        got = np.asarray(rate_grid_ref(cts.astype(jnp.int64),
                                       cvals.astype(jnp.float64),
                                       int(steps[0]), q))
        dense_ts, dense_v = _compact(cts, cvals)
        wmax = rf.bucket_wmax(dense_ts, np.asarray(steps), K * STEP)
        want = np.asarray(windows.holt_winters(
            jnp.asarray(dense_ts), jnp.asarray(dense_v), steps,
            jnp.asarray(K * STEP, jnp.int64), wmax, 0.3, 0.1)).T
        live = np.isfinite(np.asarray(cvals)).any(axis=0)
        assert (np.isfinite(got) == np.isfinite(want))[:, live].all()
        both = np.isfinite(got) & np.isfinite(want)
        np.testing.assert_allclose(got[both], want[both], rtol=1e-9)

    @pytest.mark.parametrize("op", ["quantile", "mad", "holt_winters"])
    def test_sort_ops_pallas_interpret(self, op):
        cts, cvals = _dense_data()
        steps = _steps()
        q = GridQuery(nsteps=len(steps), kbuckets=K, gstep_ms=STEP, op=op,
                      dense=True, farg=0.9, farg2=0.1)
        ref = np.asarray(rate_grid_ref(cts.astype(jnp.int32),
                                       cvals.astype(jnp.float32),
                                       int(steps[0]), q))
        pal = np.asarray(rate_grid(cts.astype(jnp.int32),
                                   cvals.astype(jnp.float32),
                                   jnp.int32(int(steps[0])), q, lanes=128,
                                   interpret=True))
        assert (np.isfinite(ref) == np.isfinite(pal)).all(), op
        both = np.isfinite(ref)
        np.testing.assert_allclose(pal[both], ref[both], rtol=1e-5)

    @pytest.mark.parametrize("op", ["changes", "resets", "irate", "idelta",
                                    "quantile", "mad", "holt_winters"])
    def test_general_mode_rejected(self, op):
        cts, cvals = _dense_data()
        steps = _steps()
        q = GridQuery(nsteps=len(steps), kbuckets=K, gstep_ms=STEP, op=op,
                      dense=False)
        with pytest.raises(ValueError, match="dense"):
            rate_grid_ref(cts, cvals.astype(jnp.float64), int(steps[0]), q)

    @pytest.mark.parametrize("op", ["changes", "resets", "irate", "idelta",
                                    "stddev", "stdvar"])
    def test_pallas_interpret(self, op):
        cts, cvals = _dense_data(reset_frac=0.1)
        steps = _steps()
        q = GridQuery(nsteps=len(steps), kbuckets=K, gstep_ms=STEP, op=op,
                      dense=(op not in ("stddev", "stdvar")))
        ref = np.asarray(rate_grid_ref(cts.astype(jnp.int32),
                                       cvals.astype(jnp.float32),
                                       int(steps[0]), q))
        pal = np.asarray(rate_grid(cts.astype(jnp.int32),
                                   cvals.astype(jnp.float32),
                                   jnp.int32(int(steps[0])), q, lanes=128,
                                   interpret=True))
        assert (np.isfinite(ref) == np.isfinite(pal)).all(), op
        both = np.isfinite(ref)
        np.testing.assert_allclose(pal[both], ref[both], rtol=3e-4,
                                   atol=1e-5)


class TestGridDense:
    """The dense fast path (GridQuery.dense) vs the general kernel on
    contract-conforming data: results must be identical — the dense
    kernel is an algebraic simplification, not an approximation."""

    ALL_OPS = ["rate", "increase", "sum", "count", "avg", "min", "max",
               "last"]

    @pytest.mark.parametrize("op", ALL_OPS)
    def test_ref_dense_equals_general(self, op):
        cts, cvals = _dense_data()
        steps = _steps()
        qd = GridQuery(nsteps=len(steps), kbuckets=K, gstep_ms=STEP,
                       op=op, is_rate=(op == "rate"), dense=True)
        qg = qd._replace(dense=False)
        dense = np.asarray(rate_grid_ref(cts.astype(jnp.int64),
                                         cvals.astype(jnp.float64),
                                         int(steps[0]), qd))
        general = np.asarray(rate_grid_ref(cts.astype(jnp.int64),
                                           cvals.astype(jnp.float64),
                                           int(steps[0]), qg))
        assert (np.isfinite(dense) == np.isfinite(general)).all(), op
        both = np.isfinite(dense)
        np.testing.assert_allclose(dense[both], general[both], rtol=1e-12)

    @pytest.mark.parametrize("op", ALL_OPS)
    def test_pallas_interpret_dense(self, op):
        cts, cvals = _dense_data()
        steps = _steps()
        q = GridQuery(nsteps=len(steps), kbuckets=K, gstep_ms=STEP,
                      op=op, is_rate=(op == "rate"), dense=True)
        ref = np.asarray(rate_grid_ref(cts.astype(jnp.int32),
                                       cvals.astype(jnp.float32),
                                       int(steps[0]), q))
        pal = np.asarray(rate_grid(cts.astype(jnp.int32),
                                   cvals.astype(jnp.float32),
                                   jnp.int32(int(steps[0])), q, lanes=128,
                                   interpret=True))
        assert (np.isfinite(ref) == np.isfinite(pal)).all(), op
        both = np.isfinite(ref)
        np.testing.assert_allclose(pal[both], ref[both], rtol=5e-5,
                                   atol=1e-6)

    def test_grouped_dense(self):
        cts, cvals = _dense_data()
        steps = _steps()
        q = GridQuery(nsteps=len(steps), kbuckets=K, gstep_ms=STEP,
                      dense=True)
        stepped = rate_grid(cts.astype(jnp.int32),
                            cvals.astype(jnp.float32), int(steps[0]), q,
                            lanes=128, interpret=True)
        ref = rate_grid_ref(cts.astype(jnp.int32),
                            cvals.astype(jnp.float32), int(steps[0]),
                            q._replace(dense=False))
        _assert_served_sum_by(stepped, ref,
                              _scattered_groups(stepped.shape[1], 8), 8,
                              rtol=5e-5)

    def test_strided_matches_unstrided_subsample(self):
        """stride=r output == every r-th step of the stride-1 output —
        the coarser dashboard step is a pure subsample of the windows."""
        cts, cvals = _dense_data()
        for r in (2, 3):
            full_steps = _steps()
            sub_steps = np.asarray(full_steps)[::r]
            q1 = GridQuery(nsteps=len(full_steps), kbuckets=K, gstep_ms=STEP)
            qr = GridQuery(nsteps=len(sub_steps), kbuckets=K, gstep_ms=STEP,
                           stride=r)
            full = np.asarray(rate_grid_ref(cts, cvals.astype(jnp.float64),
                                            int(full_steps[0]), q1))
            strided = np.asarray(rate_grid_ref(cts, cvals.astype(jnp.float64),
                                               int(sub_steps[0]), qr))
            want = full[::r]
            assert strided.shape == want.shape
            both = np.isfinite(want)
            assert (np.isfinite(strided) == both).all(), r
            np.testing.assert_allclose(strided[both], want[both], rtol=1e-12)

    @pytest.mark.parametrize("op", ["rate", "sum", "min", "last"])
    @pytest.mark.parametrize("dense", [False, True])
    def test_strided_pallas_interpret(self, op, dense):
        cts, cvals = _dense_data() if dense \
            else _clip(*_aligned_data(n_series=128))
        r = 2
        sub_steps = np.asarray(_steps())[::r]
        q = GridQuery(nsteps=len(sub_steps), kbuckets=K, gstep_ms=STEP,
                      op=op, is_rate=(op == "rate"), dense=dense, stride=r)
        ref = np.asarray(rate_grid_ref(cts.astype(jnp.int32),
                                       cvals.astype(jnp.float32),
                                       int(sub_steps[0]), q))
        pal = np.asarray(rate_grid(cts.astype(jnp.int32),
                                   cvals.astype(jnp.float32),
                                   jnp.int32(int(sub_steps[0])), q,
                                   lanes=128, interpret=True))
        assert (np.isfinite(ref) == np.isfinite(pal)).all(), (op, dense)
        both = np.isfinite(ref)
        np.testing.assert_allclose(pal[both], ref[both], rtol=5e-5,
                                   atol=1e-6)

    def test_supports_grid_stride_and_row_caps(self, monkeypatch):
        assert supports_grid(300_000, 120_000, 60_000)    # step = 2 buckets
        assert not supports_grid(300_000, 90_000, 60_000)  # non-multiple
        # the row cap is a VMEM tile bound: TPU backends only
        import filodb_tpu.ops.grid as gridmod
        monkeypatch.setattr(gridmod.jax, "default_backend", lambda: "tpu")
        assert supports_grid(300_000, 60_000, 60_000, nsteps=1000)
        assert not supports_grid(300_000, 600_000, 60_000, nsteps=1000)
        monkeypatch.setattr(gridmod.jax, "default_backend", lambda: "cpu")
        assert supports_grid(300_000, 600_000, 60_000, nsteps=1000)
        # the span cap holds on ANY backend: a 1h step over 1s cadence
        # would stage >1M buckets of blocks per query
        assert not supports_grid(60_000, 3_600_000, 1_000, nsteps=336)

    def test_counter_reset_still_corrected(self):
        """Dense data with a reset mid-range: the dense correction must
        fire exactly like the general one."""
        n = 16
        base = (np.arange(B, dtype=np.int64) * STEP + T0 - STEP + 1)[:, None]
        ts = base + 10_000 + np.zeros((B, n), np.int64)
        vals = np.cumsum(np.full((B, n), 7.0), axis=0)
        vals[20:, :] -= vals[20, 0] - 1.0          # reset at row 20
        cts, cvals = _clip(jnp.asarray(ts), jnp.asarray(vals))
        steps = _steps()
        qd = GridQuery(len(steps), K, STEP, True, dense=True)
        dense = np.asarray(rate_grid_ref(cts, cvals, int(steps[0]), qd))
        general = np.asarray(rate_grid_ref(cts, cvals, int(steps[0]),
                                           qd._replace(dense=False)))
        both = np.isfinite(dense) & np.isfinite(general)
        assert both.any()
        np.testing.assert_allclose(dense[both], general[both], rtol=1e-12)
        assert (np.isfinite(dense) == np.isfinite(general)).all()


class TestAdviceParityFixes:
    """Round-2 ADVICE findings: out-of-range quantile phi and idelta
    zero-interval semantics must agree across grid and windows paths."""

    @pytest.mark.parametrize("phi", [1.5, -0.5])
    def test_quantile_out_of_range_phi(self, phi):
        from filodb_tpu.query import rangefns as rf
        cts, cvals = _dense_data()
        steps = _steps()
        q = GridQuery(nsteps=len(steps), kbuckets=K, gstep_ms=STEP,
                      op="quantile", dense=True, farg=phi)
        got = np.asarray(rate_grid_ref(cts.astype(jnp.int64),
                                       cvals.astype(jnp.float64),
                                       int(steps[0]), q))
        expect = np.inf if phi > 1.0 else -np.inf
        live = np.isfinite(np.asarray(cvals)).any(axis=0)
        assert (got[:, live] == expect).all()
        assert np.isnan(got[:, ~live]).all()
        # windows fallback: same ±Inf on live windows, NaN on empty
        tsn, vn = np.asarray(cts), np.asarray(cvals)
        S = tsn.shape[1]
        dense_ts = np.full((S, tsn.shape[0]), 2**60, np.int64)
        dense_v = np.full((S, tsn.shape[0]), np.nan)
        for s in range(S):
            fin = np.isfinite(vn[:, s])
            dense_ts[s, :fin.sum()] = tsn[fin, s]
            dense_v[s, :fin.sum()] = vn[fin, s]
        wmax = rf.bucket_wmax(dense_ts, np.asarray(steps), K * STEP)
        want = np.asarray(windows.quantile_over_time(
            jnp.asarray(dense_ts), jnp.asarray(dense_v), steps,
            jnp.asarray(K * STEP, jnp.int64), wmax, phi)).T
        assert (want[:, live] == expect).all()
        assert np.isnan(want[:, ~live]).all()

    def test_idelta_zero_interval_dropped(self):
        """Two adjacent rows with IDENTICAL timestamps (possible on the
        public rate_grid_ref API): idelta must drop the pair like irate
        does, matching the reference's shared instant-pair guard."""
        n = 8
        base = (np.arange(B, dtype=np.int64) * STEP + T0 - STEP + 1)[:, None]
        ts = base + 10_000 + np.zeros((B, n), np.int64)
        ts[-1, :] = ts[-2, :]                      # dt == 0 at the pair
        vals = np.cumsum(np.full((B, n), 3.0), axis=0)
        cts, cvals = _clip(jnp.asarray(ts), jnp.asarray(vals))
        steps = _steps()
        q = GridQuery(len(steps), K, STEP, op="idelta", dense=True)
        out = np.asarray(rate_grid_ref(cts, cvals.astype(jnp.float64),
                                       int(steps[0]), q))
        # the final window's instant pair has dt==0 -> NaN there
        assert np.isnan(out[-1, :]).all()
        assert np.isfinite(out[:-1, :]).all()


def _phase_data(n_series=128, n_empty=16, seed=11, reset_frac=0.08):
    """Dense data with UNIFORM per-lane phase: every live lane scraped at
    a constant offset within its bucket (the reference producer's shape —
    TestTimeseriesProducer.scala:128 emits exact-cadence timestamps)."""
    rng = np.random.default_rng(seed)
    phase = rng.integers(1, STEP, n_series).astype(np.int64)
    base = (np.arange(B, dtype=np.int64) * STEP + T0 - STEP)[:, None]
    ts = base + phase[None, :]
    incr = rng.random((B, n_series)) * 10.0
    vals = np.cumsum(incr, axis=0)
    resets = rng.random((B, n_series)) < reset_frac
    for s in range(n_series):
        for c in np.where(resets[:, s])[0]:
            vals[c:, s] -= vals[c, s] * 0.9
    vals[:, n_series - n_empty:] = np.nan
    cts, cvals = _clip(jnp.asarray(ts), jnp.asarray(vals))
    return cts, cvals, jnp.asarray(phase, jnp.int32)


class TestPhaseMode:
    """Uniform-phase kernels: the ts plane is replaced by one per-lane
    phase row; results must match the ts-streaming dense path exactly."""

    @pytest.mark.parametrize("op", ["rate", "increase", "delta"])
    def test_ref_phase_matches_ref_ts(self, op):
        from filodb_tpu.ops.grid import rate_grid_ref
        cts, cvals, phase = _phase_data()
        steps = _steps()
        q = GridQuery(len(steps), K, STEP, op == "rate", op=op, dense=True)
        want = np.asarray(rate_grid_ref(cts, cvals.astype(jnp.float64),
                                        int(steps[0]), q))
        got = np.asarray(rate_grid_ref(None, cvals.astype(jnp.float64),
                                       int(steps[0]), q, phase=phase))
        assert (np.isfinite(got) == np.isfinite(want)).all()
        both = np.isfinite(got) & np.isfinite(want)
        np.testing.assert_allclose(got[both], want[both], rtol=1e-12)

    @pytest.mark.parametrize("op", ["rate", "increase", "delta"])
    def test_pallas_interpret_phase(self, op):
        from filodb_tpu.ops.grid import rate_grid, rate_grid_ref
        cts, cvals, phase = _phase_data()
        steps = _steps()
        q = GridQuery(len(steps), K, STEP, op == "rate", op=op, dense=True)
        want = np.asarray(rate_grid_ref(None, cvals, int(steps[0]), q,
                                        phase=phase))
        got = np.asarray(rate_grid(None, cvals.astype(jnp.float32),
                                   int(steps[0]), q, lanes=128,
                                   interpret=True, phase=phase))
        both = np.isfinite(got) & np.isfinite(want)
        assert (np.isfinite(got) == np.isfinite(want)).all()
        np.testing.assert_allclose(got[both], want[both], rtol=2e-5)

    def test_pallas_interpret_phase_grouped(self):
        cts, cvals, phase = _phase_data(n_series=128, n_empty=24)
        steps = _steps()
        q = GridQuery(len(steps), K, STEP, True, dense=True)
        stepped = rate_grid(None, cvals.astype(jnp.float32), int(steps[0]),
                            q, lanes=128, interpret=True, phase=phase)
        ref = rate_grid_ref(None, cvals, int(steps[0]), q, phase=phase)
        _assert_served_sum_by(stepped, ref, _scattered_groups(128, 8), 8,
                              rtol=2e-5)

    def test_phase_mode_requires_dense(self):
        from filodb_tpu.ops.grid import _phase_mode
        q = GridQuery(10, K, STEP, True, dense=False)
        assert not _phase_mode(q, jnp.zeros(8, jnp.int32))
        assert _phase_mode(q._replace(dense=True), jnp.zeros(8, jnp.int32))
        assert not _phase_mode(q._replace(dense=True), None)
        assert not _phase_mode(q._replace(dense=True, op="sum"),
                               jnp.zeros(8, jnp.int32))

    def test_phase_strided_matches_subsample(self):
        from filodb_tpu.ops.grid import rate_grid_ref
        cts, cvals, phase = _phase_data()
        steps = _steps()
        ns_c = (len(steps) + 1) // 2
        qs = GridQuery(ns_c, K, STEP, True, dense=True, stride=2)
        q1 = GridQuery(len(steps), K, STEP, True, dense=True)
        got = np.asarray(rate_grid_ref(None, cvals, int(steps[0]), qs,
                                       phase=phase))
        fine = np.asarray(rate_grid_ref(None, cvals, int(steps[0]), q1,
                                        phase=phase))
        np.testing.assert_allclose(got, fine[::2], rtol=1e-12)


class TestTsFreeOps:
    """TS_FREE_OPS stream no ts plane: ts=None must work and match."""

    @pytest.mark.parametrize("op", ["sum", "min", "max", "count", "avg",
                                    "last", "stddev"])
    @pytest.mark.parametrize("dense", [True, False])
    def test_ts_none_matches(self, op, dense):
        from filodb_tpu.ops.grid import rate_grid, rate_grid_ref
        if dense:
            cts, cvals = _dense_data()
        else:
            ts, vals = _aligned_data()
            cts, cvals = _clip(ts, vals)
        steps = _steps()
        q = GridQuery(len(steps), K, STEP, op=op, dense=dense)
        want = np.asarray(rate_grid_ref(cts, cvals, int(steps[0]), q))
        got_ref = np.asarray(rate_grid_ref(None, cvals, int(steps[0]), q))
        np.testing.assert_array_equal(got_ref, want)
        got_pl = np.asarray(rate_grid(None, cvals.astype(jnp.float32),
                                      int(steps[0]), q, lanes=64,
                                      interpret=True))
        both = np.isfinite(got_pl) & np.isfinite(want)
        assert (np.isfinite(got_pl) == np.isfinite(want)).all()
        # stddev in f32 is ~1e-4 relative and near-zero variances see
        # absolute cancellation noise (see grid._masked_moments)
        np.testing.assert_allclose(got_pl[both], want[both],
                                   rtol=1e-3 if op == "stddev" else 1e-4,
                                   atol=1e-2 if op == "stddev" else 0)
