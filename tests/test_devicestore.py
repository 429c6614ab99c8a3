"""Device-resident chunk store: correctness + caching + fallback.

Proves the serving seam the reference places at block memory (queries
read from BlockManager-resident chunks, never re-copying them —
reference: memory/BlockManager.scala:142): the grid path must be
bit-consistent with the general scan path, must not rebuild blocks on a
repeat query (zero host->device transfer), must invalidate on new data,
and must fall back — never be wrong — on irregular layouts.
"""

import time

import numpy as np
import pytest

from filodb_tpu.core.filters import ColumnFilter, Equals
from filodb_tpu.core.record import RecordBuilder, decode_container
from filodb_tpu.core.schemas import DEFAULT_SCHEMAS
from filodb_tpu.core.storeconfig import StoreConfig
from filodb_tpu.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.query.logical import RangeFunctionId as F

STEP = 60_000
# step-aligned in absolute ms: dashboards align query starts to the step
# grid, and the bucket-grid phase is anchored at absolute step multiples
T0 = 1_700_000_040_000
assert T0 % STEP == 0
WINDOW = 300_000
K = WINDOW // STEP


def _mk_shard(n_series=6, n_rows=50, jitter_max=30_000, seed=0,
              flush=True, **cfg_kw):
    ms = TimeSeriesMemStore()
    cfg = StoreConfig(**cfg_kw)
    shard = ms.setup("prom", DEFAULT_SCHEMAS, 0, cfg)
    rng = np.random.default_rng(seed)
    b = RecordBuilder(DEFAULT_SCHEMAS["prom-counter"])
    truth = {}
    for i in range(n_series):
        tags = {"__name__": "req_total", "instance": f"i{i}", "_ws_": "w",
                "_ns_": "n"}
        base = T0 + np.arange(n_rows, dtype=np.int64) * STEP - STEP + 1
        ts = base + rng.integers(0, max(jitter_max, 1), size=n_rows)
        vals = np.cumsum(rng.random(n_rows) * 5)
        truth[f"i{i}"] = (ts, vals)
        for t, v in zip(ts, vals):
            b.add(int(t), [float(v)], tags)
    for off, c in enumerate(b.containers()):
        shard.ingest(decode_container(c, DEFAULT_SCHEMAS), off)
    if flush:
        shard.flush_all()
    return ms, shard, truth


def _lookup(shard):
    return shard.lookup_partitions(
        [ColumnFilter("_metric_", Equals("req_total"))], 0, 2**62)


def _steps(n_rows):
    steps0 = T0 + (K - 1) * STEP
    nsteps = n_rows - K
    return steps0, nsteps


class TestDeviceGrid:
    def test_late_lane_partitions_rebuild_blocks(self):
        """A partition that gets its lane AFTER blocks were built (a
        second metric of the same schema, or a just-paged-in series)
        must trigger a block rebuild — its unstaged lanes would
        otherwise pass the dense proof as 'empty' and silently serve
        all-NaN for real data."""
        from filodb_tpu.core.filters import ColumnFilter, Equals
        from filodb_tpu.ops.windows import StepRange
        from filodb_tpu.query import rangefns

        ms = TimeSeriesMemStore()
        shard = ms.setup("prom", DEFAULT_SCHEMAS, 0, StoreConfig())
        rng = np.random.default_rng(3)
        b = RecordBuilder(DEFAULT_SCHEMAS["prom-counter"])
        for metric in ("m_a", "m_b"):
            for i in range(3):
                tags = {"__name__": metric, "instance": f"i{i}",
                        "_ws_": "w", "_ns_": "n"}
                base = T0 + np.arange(50, dtype=np.int64) * STEP - STEP + 1
                vals = np.cumsum(rng.random(50) * 5)
                for t, v in zip(base, vals):
                    b.add(int(t), [float(v)], tags)
        for off, c in enumerate(b.containers()):
            shard.ingest(decode_container(c, DEFAULT_SCHEMAS), off)
        shard.flush_all()
        steps0, nsteps = _steps(50)
        # metric A builds the blocks with only ITS lanes staged
        res_a = shard.lookup_partitions(
            [ColumnFilter("_metric_", Equals("m_a"))], 0, 2**62)
        got_a = shard.scan_grid(res_a.part_ids, F.RATE, steps0, nsteps,
                                STEP, WINDOW)
        assert got_a is not None
        # metric B gets lanes AFTER the build: must serve real values
        res_b = shard.lookup_partitions(
            [ColumnFilter("_metric_", Equals("m_b"))], 0, 2**62)
        got_b = shard.scan_grid(res_b.part_ids, F.RATE, steps0, nsteps,
                                STEP, WINDOW)
        assert got_b is not None
        _tags, vals_b, _ = got_b
        assert np.isfinite(vals_b).any(), \
            "late-lane metric served all-NaN from stale blocks"
        t2, batch = shard.scan_batch(res_b.part_ids, steps0 - WINDOW,
                                     steps0 + (nsteps - 1) * STEP)
        sr = StepRange(steps0, steps0 + (nsteps - 1) * STEP, STEP)
        oracle = np.asarray(rangefns.apply_range_function(
            batch, sr, WINDOW, F.RATE))
        np.testing.assert_allclose(vals_b, oracle[:len(vals_b)],
                                   rtol=1e-6, equal_nan=True)

    def test_matches_scan_batch_path(self):
        from filodb_tpu.ops.windows import StepRange
        from filodb_tpu.query import rangefns

        ms, shard, truth = _mk_shard()
        res = _lookup(shard)
        steps0, nsteps = _steps(50)
        got = shard.scan_grid(res.part_ids, F.RATE, steps0, nsteps, STEP,
                              WINDOW)
        assert got is not None, "grid path should serve this query"
        tags, vals, _tops = got
        # general path oracle
        t2, batch = shard.scan_batch(res.part_ids, steps0 - WINDOW,
                                     steps0 + (nsteps - 1) * STEP)
        sr = StepRange(steps0, steps0 + (nsteps - 1) * STEP, STEP)
        want = np.asarray(rangefns.apply_range_function(
            batch, sr, WINDOW, F.RATE))[:len(t2)]   # drop series padding
        assert [t["instance"] for t in tags] == \
            [t["instance"] for t in t2]
        assert (np.isfinite(vals) == np.isfinite(want)).all()
        both = np.isfinite(vals)
        np.testing.assert_allclose(vals[both], want[both], rtol=1e-4)

    def test_repeat_query_zero_uploads(self):
        ms, shard, _ = _mk_shard()
        res = _lookup(shard)
        steps0, nsteps = _steps(50)
        a = shard.scan_grid(res.part_ids, F.RATE, steps0, nsteps, STEP, WINDOW)
        cache = next(iter(shard.device_caches.values()))
        builds_after_first = cache.builds
        assert builds_after_first > 0
        b = shard.scan_grid(res.part_ids, F.RATE, steps0, nsteps, STEP, WINDOW)
        assert cache.builds == builds_after_first  # served from HBM
        np.testing.assert_array_equal(np.isfinite(a[1]), np.isfinite(b[1]))
        np.testing.assert_allclose(a[1][np.isfinite(a[1])],
                                   b[1][np.isfinite(b[1])])

    def test_new_ingest_refreshes_tail(self):
        ms, shard, truth = _mk_shard(n_rows=30, flush=False)
        res = _lookup(shard)
        steps0, nsteps = _steps(30)
        first = shard.scan_grid(res.part_ids, F.RATE, steps0, nsteps, STEP,
                                WINDOW)
        assert first is not None
        # append one more sample to series i0 inside the last window
        b = RecordBuilder(DEFAULT_SCHEMAS["prom-counter"])
        last_ts = int(truth["i0"][0][-1])
        b.add(last_ts + STEP, [truth["i0"][1][-1] + 100.0],
              {"__name__": "req_total", "instance": "i0", "_ws_": "w",
               "_ns_": "n"})
        for off, c in enumerate(b.containers()):
            shard.ingest(decode_container(c, DEFAULT_SCHEMAS), 1000 + off)
        steps0b = steps0 + STEP
        second = shard.scan_grid(res.part_ids, F.RATE, steps0b, nsteps, STEP,
                                 WINDOW)
        assert second is not None
        # the appended jump must be visible in the final windows
        assert not np.array_equal(first[1][:, -1], second[1][:, -1])

    def test_unaligned_step_falls_back(self):
        ms, shard, _ = _mk_shard()
        res = _lookup(shard)
        steps0, nsteps = _steps(50)
        assert shard.scan_grid(res.part_ids, F.RATE, steps0, nsteps,
                               STEP // 2, WINDOW) is None
        assert shard.scan_grid(res.part_ids, F.RATE, steps0 + 7, nsteps,
                               STEP, WINDOW) is None
        # argument-arity mismatch must decline, never mis-serve
        assert shard.scan_grid(res.part_ids, F.HOLT_WINTERS, steps0,
                               nsteps, STEP, WINDOW, fargs=(0.3,)) is None

    def test_flush_headroom_trims_below_budget(self):
        """The flush task proactively reclaims device blocks down to
        (1-headroom) of budget, so queries rarely pay inline eviction
        (reference: BlockManager ensureHeadroomPercentAvailable)."""
        ms, shard, _ = _mk_shard(n_rows=300, device_cache_bytes=300_000,
                                 device_headroom_frac=0.5)
        res = _lookup(shard)
        steps0, nsteps = _steps(300)
        got = shard.scan_grid(res.part_ids, F.RATE, steps0, nsteps, STEP,
                              WINDOW)
        assert got is not None
        cache = next(iter(shard.device_caches.values()))
        resident_before = cache.bytes_resident
        assert resident_before > 0
        freed = cache.ensure_headroom(shard.config.device_headroom_frac)
        assert freed > 0
        assert cache.bytes_resident <= 300_000 * 0.5 + 1
        # the flush path drives it automatically
        shard.flush_all()
        assert cache.bytes_resident <= 300_000 * 0.5 + 1

    def test_dense_contract_detected(self):
        """Regular scrapes with no holes: the store proves the
        dense-lane contract from per-block fill ranges and dispatches
        the dense kernel (GridQuery.dense)."""
        ms, shard, _ = _mk_shard()
        res = _lookup(shard)
        steps0, nsteps = _steps(50)
        got = shard.scan_grid(res.part_ids, F.RATE, steps0, nsteps, STEP,
                              WINDOW)
        assert got is not None
        cache = next(iter(shard.device_caches.values()))
        assert cache.dense_hits == cache.hits > 0

    def test_gappy_series_uses_general_kernel(self):
        """A series with a missed scrape mid-range breaks the contract:
        the grid still serves (one-per-bucket holds) but via the general
        kernel, and the result still matches the dense shard's shape."""
        ms, shard, _ = _mk_shard(n_series=4, n_rows=50)
        b = RecordBuilder(DEFAULT_SCHEMAS["prom-counter"])
        tags = {"__name__": "req_total", "instance": "gappy", "_ws_": "w",
                "_ns_": "n"}
        for c in range(0, 50, 2):              # every other bucket
            b.add(T0 + (c - 1) * STEP + 10, [float(c)], tags)
        for off, c in enumerate(b.containers()):
            shard.ingest(decode_container(c, DEFAULT_SCHEMAS), 700 + off)
        shard.flush_all()
        res = _lookup(shard)
        steps0, nsteps = _steps(50)
        got = shard.scan_grid(res.part_ids, F.RATE, steps0, nsteps, STEP,
                              WINDOW)
        assert got is not None
        cache = next(iter(shard.device_caches.values()))
        assert cache.hits > 0 and cache.dense_hits == 0
        # the gappy lane still produces finite rates (2+ samples/window)
        tags_out, vals, _tops = got
        gi = next(i for i, t in enumerate(tags_out)
                  if t.get("instance") == "gappy")
        assert np.isfinite(vals[gi]).any()

    def test_coarser_step_served_with_stride(self):
        """A dashboard step of 2x the scrape cadence stays on the grid
        (stride serving) and matches the general scan path."""
        from filodb_tpu.ops.windows import StepRange
        from filodb_tpu.query import rangefns

        ms, shard, _ = _mk_shard()
        res = _lookup(shard)
        steps0, nsteps_full = _steps(50)
        step2 = 2 * STEP
        nsteps = (nsteps_full + 1) // 2
        got = shard.scan_grid(res.part_ids, F.RATE, steps0, nsteps, step2,
                              WINDOW)
        assert got is not None, "strided grid should serve step=2*gstep"
        tags, vals, _tops = got
        assert vals.shape[1] == nsteps
        cache = next(iter(shard.device_caches.values()))
        assert cache.hits > 0
        # oracle: general scan path on the same coarse step grid
        end = steps0 + (nsteps - 1) * step2
        t2, batch = shard.scan_batch(res.part_ids, steps0 - WINDOW, end)
        sr = StepRange(steps0, end, step2)
        want = np.asarray(rangefns.apply_range_function(
            batch, sr, WINDOW, F.RATE))[:len(tags)]
        got_v = np.asarray(vals)
        assert (np.isfinite(got_v) == np.isfinite(want)).all()
        fin = np.isfinite(want)
        assert fin.any()
        np.testing.assert_allclose(got_v[fin], want[fin], rtol=1e-4)

    def test_large_window_served_when_dense(self):
        """K-free dense ops (rate) take windows beyond MAX_K_BUCKETS —
        a 2-hour lookback over 1m scrapes (K=120) stays on the fast
        path when the dense contract is proven."""
        from filodb_tpu.ops.windows import StepRange
        from filodb_tpu.query import rangefns

        ms, shard, _ = _mk_shard(n_rows=200)
        res = _lookup(shard)
        big_w = 120 * STEP                     # K = 120 > 64
        steps0 = T0 + 120 * STEP
        nsteps = 40
        got = shard.scan_grid(res.part_ids, F.RATE, steps0, nsteps, STEP,
                              big_w)
        assert got is not None, "dense large-K rate should serve"
        cache = next(iter(shard.device_caches.values()))
        assert cache.dense_hits > 0
        tags, vals, _tops = got
        end = steps0 + (nsteps - 1) * STEP
        t2, batch = shard.scan_batch(res.part_ids, steps0 - big_w, end)
        want = np.asarray(rangefns.apply_range_function(
            batch, StepRange(steps0, end, STEP), big_w,
            F.RATE))[:len(tags)]
        got_v = np.asarray(vals)
        assert (np.isfinite(got_v) == np.isfinite(want)).all()
        fin = np.isfinite(want)
        np.testing.assert_allclose(got_v[fin], want[fin], rtol=1e-4)
        # sum_over_time accumulates K slices even when dense: capped
        assert shard.scan_grid(res.part_ids, F.SUM_OVER_TIME, steps0,
                               nsteps, STEP, big_w) is None

    def test_predict_linear_served_with_arg(self):
        """predict_linear carries its horizon through GridQuery.farg."""
        from filodb_tpu.ops.windows import StepRange
        from filodb_tpu.query import rangefns

        ms, shard, _ = _mk_shard()
        res = _lookup(shard)
        steps0, nsteps = _steps(50)
        got = shard.scan_grid(res.part_ids, F.PREDICT_LINEAR, steps0,
                              nsteps, STEP, WINDOW, fargs=(600.0,))
        assert got is not None
        tags, vals, _tops = got
        end = steps0 + (nsteps - 1) * STEP
        t2, batch = shard.scan_batch(res.part_ids, steps0 - WINDOW, end)
        want = np.asarray(rangefns.apply_range_function(
            batch, StepRange(steps0, end, STEP), WINDOW, F.PREDICT_LINEAR,
            (600.0,)))[:len(tags)]
        got_v = np.asarray(vals)
        fin = np.isfinite(want)
        assert fin.any()
        assert (np.isfinite(got_v) == fin).all()
        np.testing.assert_allclose(got_v[fin], want[fin], rtol=1e-4)
        # missing the required arg: fall back, never mis-serve
        assert shard.scan_grid(res.part_ids, F.PREDICT_LINEAR, steps0,
                               nsteps, STEP, WINDOW) is None

    @pytest.mark.parametrize("func,wfn", [
        (F.STDDEV_OVER_TIME, "stddev_over_time"),
        (F.IRATE, "irate"), (F.CHANGES, "changes_over_time"),
        (F.DERIV, "deriv"), (F.Z_SCORE, "z_score"),
        (F.DELTA, "delta_fn"), (F.TIMESTAMP, "timestamp_fn")])
    def test_extended_ops_served_from_grid(self, func, wfn):
        from filodb_tpu.ops.windows import StepRange
        from filodb_tpu.query import rangefns

        ms, shard, _ = _mk_shard()
        res = _lookup(shard)
        steps0, nsteps = _steps(50)
        got = shard.scan_grid(res.part_ids, func, steps0, nsteps, STEP,
                              WINDOW)
        assert got is not None, f"{func} should serve from the grid"
        tags, vals, _tops = got
        end = steps0 + (nsteps - 1) * STEP
        t2, batch = shard.scan_batch(res.part_ids, steps0 - WINDOW, end)
        want = np.asarray(rangefns.apply_range_function(
            batch, StepRange(steps0, end, STEP), WINDOW, func))[:len(tags)]
        got_v = np.asarray(vals)
        assert (np.isfinite(got_v) == np.isfinite(want)).all(), func
        fin = np.isfinite(want)
        assert fin.any()
        np.testing.assert_allclose(got_v[fin], want[fin], rtol=1e-4,
                                   atol=1e-6)

    def test_quantile_and_mad_served_from_grid(self):
        """Sort-network ops serve dense data from the grid; the quantile
        rides GridQuery.farg."""
        from filodb_tpu.ops.windows import StepRange
        from filodb_tpu.query import rangefns

        ms, shard, _ = _mk_shard()
        res = _lookup(shard)
        steps0, nsteps = _steps(50)
        for func, fargs in ((F.QUANTILE_OVER_TIME, (0.9,)),
                            (F.MAD_OVER_TIME, ()),
                            (F.HOLT_WINTERS, (0.3, 0.1))):
            got = shard.scan_grid(res.part_ids, func, steps0, nsteps, STEP,
                                  WINDOW, fargs=fargs)
            assert got is not None, func
            tags, vals, _tops = got
            end = steps0 + (nsteps - 1) * STEP
            t2, batch = shard.scan_batch(res.part_ids, steps0 - WINDOW, end)
            want = np.asarray(rangefns.apply_range_function(
                batch, StepRange(steps0, end, STEP), WINDOW, func,
                fargs))[:len(tags)]
            got_v = np.asarray(vals)
            fin = np.isfinite(want)
            assert fin.any()
            assert (np.isfinite(got_v) == fin).all(), func
            np.testing.assert_allclose(got_v[fin], want[fin], rtol=1e-4)

    def test_adjacency_ops_gappy_fall_back(self):
        ms, shard, _ = _mk_shard(n_series=4, n_rows=50)
        b = RecordBuilder(DEFAULT_SCHEMAS["prom-counter"])
        tags = {"__name__": "req_total", "instance": "gappy", "_ws_": "w",
                "_ns_": "n"}
        for c in range(0, 50, 2):
            b.add(T0 + (c - 1) * STEP + 10, [float(c)], tags)
        for off, c in enumerate(b.containers()):
            shard.ingest(decode_container(c, DEFAULT_SCHEMAS), 800 + off)
        shard.flush_all()
        res = _lookup(shard)
        steps0, nsteps = _steps(50)
        # adjacency ops decline on gappy data; stddev (masked) still serves
        assert shard.scan_grid(res.part_ids, F.CHANGES, steps0, nsteps,
                               STEP, WINDOW) is None
        assert shard.scan_grid(res.part_ids, F.IRATE, steps0, nsteps,
                               STEP, WINDOW) is None
        assert shard.scan_grid(res.part_ids, F.STDDEV_OVER_TIME, steps0,
                               nsteps, STEP, WINDOW) is not None

    def test_large_window_gappy_falls_back(self):
        ms, shard, _ = _mk_shard(n_series=4, n_rows=200)
        b = RecordBuilder(DEFAULT_SCHEMAS["prom-counter"])
        tags = {"__name__": "req_total", "instance": "gappy", "_ws_": "w",
                "_ns_": "n"}
        for c in range(0, 200, 2):
            b.add(T0 + (c - 1) * STEP + 10, [float(c)], tags)
        for off, c in enumerate(b.containers()):
            shard.ingest(decode_container(c, DEFAULT_SCHEMAS), 900 + off)
        shard.flush_all()
        res = _lookup(shard)
        assert shard.scan_grid(res.part_ids, F.RATE, T0 + 120 * STEP, 40,
                               STEP, 120 * STEP) is None
        # the failed dense proof is memoized: the repeat attempt is
        # denied up-front (no speculative block staging), and new data
        # (epoch bump) re-enables the attempt
        cache = next(iter(shard.device_caches.values()))
        builds0 = cache.builds
        assert shard.scan_grid(res.part_ids, F.RATE, T0 + 120 * STEP, 40,
                               STEP, 120 * STEP) is None
        assert cache.builds == builds0
        assert any(k[:3] == (F.RATE, 120 * STEP, STEP)
                   for k in cache._bigk_deny)

    def test_irregular_series_disables_grid(self):
        # two samples in one bucket violate the layout invariant
        ms, shard, _ = _mk_shard(n_series=2, n_rows=20)
        b = RecordBuilder(DEFAULT_SCHEMAS["prom-counter"])
        tags = {"__name__": "req_total", "instance": "burst", "_ws_": "w",
                "_ns_": "n"}
        b.add(T0 + 100 * STEP + 1, [1.0], tags)
        b.add(T0 + 100 * STEP + 2, [2.0], tags)   # same bucket
        for off, c in enumerate(b.containers()):
            shard.ingest(decode_container(c, DEFAULT_SCHEMAS), 500 + off)
        shard.flush_all()
        res = _lookup(shard)
        steps0 = T0 + 100 * STEP
        assert shard.scan_grid(res.part_ids, F.RATE, steps0, 4, STEP,
                               WINDOW) is None

    def test_eviction_under_budget(self):
        """Reclaim-on-demand: blocks pinned by the in-flight query survive,
        and a later narrow query evicts the oldest blocks past the budget."""
        # compression off: this test exercises the eviction mechanics,
        # and compressed blocks would fit the tiny budget outright
        ms, shard, _ = _mk_shard(n_rows=300, device_cache_bytes=300_000,
                                 device_cache_compress=False)
        res = _lookup(shard)
        steps0, nsteps = _steps(300)
        got = shard.scan_grid(res.part_ids, F.RATE, steps0, nsteps, STEP,
                              WINDOW)
        assert got is not None
        cache = next(iter(shard.device_caches.values()))
        full_blocks = len(cache.blocks)
        assert full_blocks >= 2
        # narrow recent query: older blocks become evictable
        recent0 = steps0 + (nsteps - 5) * STEP
        got = shard.scan_grid(res.part_ids, F.RATE, recent0, 4, STEP, WINDOW)
        assert got is not None
        assert cache.evictions > 0
        assert len(cache.blocks) < full_blocks


class TestEndToEndGridServing:
    def test_exec_plan_uses_grid(self):
        """The leaf + mapper pipeline serves from the device grid and the
        result matches the fallback path end to end."""
        from filodb_tpu.query.exec import (ExecContext,
                                           MultiSchemaPartitionsExec)
        from filodb_tpu.query.model import QueryContext
        from filodb_tpu.query.transformers import PeriodicSamplesMapper

        ms, shard, _ = _mk_shard()
        steps0, nsteps = _steps(50)
        end = steps0 + (nsteps - 1) * STEP

        def run():
            leaf = MultiSchemaPartitionsExec(
                "prom", 0, [ColumnFilter("_metric_", Equals("req_total"))],
                steps0 - WINDOW, end)
            leaf.add_transformer(PeriodicSamplesMapper(
                start_ms=steps0, step_ms=STEP, end_ms=end,
                window_ms=WINDOW, function=F.RATE))
            return leaf.execute(ExecContext(ms, QueryContext()))

        r1 = run()
        cache = next(iter(shard.device_caches.values()))
        assert cache.hits >= 1, "grid path was not used"
        builds = cache.builds
        r2 = run()
        assert cache.builds == builds          # repeat: zero uploads
        v1 = r1.batches[0].values
        v2 = r2.batches[0].values
        np.testing.assert_allclose(v1[np.isfinite(v1)], v2[np.isfinite(v2)])


class TestGridAggregatedServing:
    """Fused agg-on-device serving (scan_rate_grouped): only [G, T]
    partials cross the host link; results must match the per-series
    grid path + host aggregation exactly."""

    @pytest.mark.parametrize("op,agg_name", [
        ("sum", "SUM"), ("count", "COUNT"), ("avg", "AVG"),
        ("min", "MIN"), ("max", "MAX")])
    def test_exec_fused_agg_matches_host_agg(self, op, agg_name):
        from filodb_tpu.query.aggregators import AggPartialBatch
        from filodb_tpu.query.exec import (ExecContext,
                                           MultiSchemaPartitionsExec,
                                           ReduceAggregateExec)
        from filodb_tpu.query.logical import AggregationOperator
        from filodb_tpu.query.model import QueryContext
        from filodb_tpu.query.transformers import (AggregateMapReduce,
                                                   AggregatePresenter,
                                                   PeriodicSamplesMapper)

        ms, shard, _ = _mk_shard(n_series=10)
        steps0, nsteps = _steps(50)
        end = steps0 + (nsteps - 1) * STEP
        operator = AggregationOperator[agg_name]

        def run(grouped: bool):
            leaf = MultiSchemaPartitionsExec(
                "prom", 0, [ColumnFilter("_metric_", Equals("req_total"))],
                steps0 - WINDOW, end)
            leaf.add_transformer(PeriodicSamplesMapper(
                start_ms=steps0, step_ms=STEP, end_ms=end,
                window_ms=WINDOW, function=F.RATE))
            if grouped:
                leaf.add_transformer(AggregateMapReduce(
                    operator, by=("instance",)))
            root = ReduceAggregateExec([leaf], operator) if grouped \
                else None
            if grouped:
                root.add_transformer(AggregatePresenter(operator))
                return root.execute(ExecContext(ms, QueryContext()))
            return leaf.execute(ExecContext(ms, QueryContext()))

        result = run(True)
        cache = next(iter(shard.device_caches.values()))
        assert cache.hits >= 1
        got = {}
        for b in result.batches:
            for tags, ts, vals in b.to_series():
                got[tags["instance"]] = np.asarray(vals)
        # oracle: per-series grid path, aggregated on host per instance
        raw = run(False)
        want = {}
        pb = raw.batches[0]
        for tags, ts, vals in pb.to_series():
            want[tags["instance"]] = np.asarray(vals)
        assert set(got) == set(want)
        for k in want:
            # by (instance): each group has ONE member, so every op
            # reduces to the member itself (count -> 1 where finite)
            w = want[k]
            if agg_name == "COUNT":
                w = np.where(np.isfinite(w), 1.0, np.nan)
            np.testing.assert_allclose(got[k], w, rtol=1e-5,
                                       equal_nan=True)

    def test_fused_global_sum_matches(self):
        from filodb_tpu.query.exec import (ExecContext,
                                           MultiSchemaPartitionsExec,
                                           ReduceAggregateExec)
        from filodb_tpu.query.logical import AggregationOperator
        from filodb_tpu.query.model import QueryContext
        from filodb_tpu.query.transformers import (AggregateMapReduce,
                                                   AggregatePresenter,
                                                   PeriodicSamplesMapper)

        ms, shard, _ = _mk_shard(n_series=8)
        steps0, nsteps = _steps(50)
        end = steps0 + (nsteps - 1) * STEP

        def mk(with_grid: bool):
            leaf = MultiSchemaPartitionsExec(
                "prom", 0, [ColumnFilter("_metric_", Equals("req_total"))],
                steps0 - WINDOW, end)
            leaf.add_transformer(PeriodicSamplesMapper(
                start_ms=steps0, step_ms=STEP, end_ms=end,
                window_ms=WINDOW, function=F.RATE))
            leaf.add_transformer(AggregateMapReduce(
                AggregationOperator.SUM))
            root = ReduceAggregateExec([leaf], AggregationOperator.SUM)
            root.add_transformer(AggregatePresenter(AggregationOperator.SUM))
            return root

        fused = mk(True).execute(ExecContext(ms, QueryContext()))
        cache = next(iter(shard.device_caches.values()))
        assert cache.hits >= 1
        # disable the grid -> host fallback oracle
        cache.disabled_until_version = shard.ingest_epoch + 10**9
        plain = mk(False).execute(ExecContext(ms, QueryContext()))
        vf = np.asarray(fused.batches[0].values[0])
        vp = np.asarray(plain.batches[0].values[0])
        fin = np.isfinite(vp)
        assert (np.isfinite(vf) == fin).all()
        np.testing.assert_allclose(vf[fin], vp[fin], rtol=1e-4)


class TestHistGridServing:
    """First-class histogram columns on the device grid: each partition
    slot spans hb bucket lanes; the scalar kernel computes per-bucket
    rates (reference: per-bucket HistRateFunction + HistSumRowAggregator
    fused on device)."""

    HSTEP = 10_000
    HWINDOW = 50_000
    HK = 5

    def _mk_hist_shard(self, n_series=3, n_rows=60):
        from tests.data import START_TS, histogram_containers
        ms = TimeSeriesMemStore()
        shard = ms.setup("prom", DEFAULT_SCHEMAS, 0, StoreConfig())
        for off, c in enumerate(histogram_containers(
                n_series=n_series, n_samples=n_rows)):
            shard.ingest(decode_container(c, DEFAULT_SCHEMAS), off)
        shard.flush_all()
        return ms, shard, START_TS

    def test_hist_rate_matches_host_kernel(self):
        from filodb_tpu.ops.windows import StepRange
        from filodb_tpu.query import rangefns

        ms, shard, t0 = self._mk_hist_shard()
        res = shard.lookup_partitions(
            [ColumnFilter("_metric_", Equals("req_latency"))], 0, 2**62)
        steps0 = t0 + (self.HK - 1) * self.HSTEP
        nsteps = 40
        got = shard.scan_grid(res.part_ids, F.RATE, steps0, nsteps,
                              self.HSTEP, self.HWINDOW)
        assert got is not None, "hist grid should serve this query"
        tags, vals, tops = got
        assert vals.ndim == 3 and vals.shape[2] == len(tops)
        cache = next(iter(shard.device_caches.values()))
        assert cache.hist and cache.hits > 0 and cache.dense_hits > 0
        # oracle: scan_batch + the host per-bucket kernel
        end = steps0 + (nsteps - 1) * self.HSTEP
        t2, batch = shard.scan_batch(res.part_ids, steps0 - self.HWINDOW,
                                     end)
        sr = StepRange(steps0, end, self.HSTEP)
        want = np.asarray(rangefns.apply_range_function(
            batch, sr, self.HWINDOW, F.RATE))[:len(tags)]
        got_v = np.asarray(vals)
        assert (np.isfinite(got_v) == np.isfinite(want)).all()
        fin = np.isfinite(want)
        np.testing.assert_allclose(got_v[fin], want[fin], rtol=1e-4)

    def test_fused_hist_sum_quantile_matches_host(self):
        """sum(rate(latency[w])) + histogram_quantile fully on the grid
        (BASELINE config 2) vs the disabled-grid host oracle."""
        from filodb_tpu.query.exec import (ExecContext,
                                           MultiSchemaPartitionsExec,
                                           ReduceAggregateExec)
        from filodb_tpu.query.logical import (AggregationOperator,
                                              InstantFunctionId)
        from filodb_tpu.query.model import QueryContext
        from filodb_tpu.query.transformers import (
            AggregateMapReduce, AggregatePresenter,
            InstantVectorFunctionMapper, PeriodicSamplesMapper)

        ms, shard, t0 = self._mk_hist_shard()
        steps0 = t0 + (self.HK - 1) * self.HSTEP
        nsteps = 40
        end = steps0 + (nsteps - 1) * self.HSTEP

        def mk():
            leaf = MultiSchemaPartitionsExec(
                "prom", 0, [ColumnFilter("_metric_", Equals("req_latency"))],
                steps0 - self.HWINDOW, end)
            leaf.add_transformer(PeriodicSamplesMapper(
                start_ms=steps0, step_ms=self.HSTEP, end_ms=end,
                window_ms=self.HWINDOW, function=F.RATE))
            leaf.add_transformer(AggregateMapReduce(AggregationOperator.SUM))
            root = ReduceAggregateExec([leaf], AggregationOperator.SUM)
            root.add_transformer(AggregatePresenter(AggregationOperator.SUM))
            root.add_transformer(InstantVectorFunctionMapper(
                InstantFunctionId.HISTOGRAM_QUANTILE, (0.9,)))
            return root

        fused = mk().execute(ExecContext(ms, QueryContext()))
        cache = next(iter(shard.device_caches.values()))
        assert cache.hist and cache.hits >= 1
        cache.disabled_until_version = shard.ingest_epoch + 10**9
        plain = mk().execute(ExecContext(ms, QueryContext()))
        vf = np.asarray(fused.batches[0].np_values()[0])
        vp = np.asarray(plain.batches[0].np_values()[0])
        fin = np.isfinite(vp)
        assert fin.any()
        assert (np.isfinite(vf) == fin).all()
        np.testing.assert_allclose(vf[fin], vp[fin], rtol=1e-4)

    def test_hist_unsupported_fn_falls_back(self):
        ms, shard, t0 = self._mk_hist_shard()
        res = shard.lookup_partitions(
            [ColumnFilter("_metric_", Equals("req_latency"))], 0, 2**62)
        steps0 = t0 + (self.HK - 1) * self.HSTEP
        # min_over_time has no histogram semantics: grid must decline
        assert shard.scan_grid(res.part_ids, F.MIN_OVER_TIME, steps0, 10,
                               self.HSTEP, self.HWINDOW) is None


class TestGridOverTimeServing:
    """The widened grid fast path (_over_time family + bare instant
    selectors) vs the general fallback, through the exec plan."""

    @pytest.mark.parametrize("func", [F.SUM_OVER_TIME, F.COUNT_OVER_TIME,
                                      F.AVG_OVER_TIME, F.MIN_OVER_TIME,
                                      F.MAX_OVER_TIME, F.LAST_OVER_TIME])
    def test_over_time_matches_fallback(self, func):
        from filodb_tpu.query.exec import (ExecContext,
                                           MultiSchemaPartitionsExec)
        from filodb_tpu.query.model import QueryContext
        from filodb_tpu.query.transformers import PeriodicSamplesMapper

        ms, shard, _ = _mk_shard()
        steps0, nsteps = _steps(50)
        end = steps0 + (nsteps - 1) * STEP

        def run():
            leaf = MultiSchemaPartitionsExec(
                "prom", 0, [ColumnFilter("_metric_", Equals("req_total"))],
                steps0 - WINDOW, end)
            leaf.add_transformer(PeriodicSamplesMapper(
                start_ms=steps0, step_ms=STEP, end_ms=end,
                window_ms=WINDOW, function=func))
            return leaf.execute(ExecContext(ms, QueryContext()))

        served = run()
        cache = next(iter(shard.device_caches.values()))
        assert cache.hits >= 1, f"{func} not served from the grid"
        cache.disabled_until_version = shard.ingest_epoch + 10**9
        fallback = run()
        for bs, bf in zip(served.batches, fallback.batches):
            vs, vf = np.asarray(bs.values), np.asarray(bf.values)
            vs = vs[:len(bs.keys)]
            vf = vf[:len(bf.keys)]
            assert (np.isfinite(vs) == np.isfinite(vf)).all(), func
            both = np.isfinite(vs)
            np.testing.assert_allclose(vs[both], vf[both], rtol=1e-4,
                                       err_msg=str(func))

    def test_instant_selector_served_from_grid(self):
        """A bare selector (no window/function) uses the staleness
        lookback; the grid serves it as a last-sample scan."""
        from filodb_tpu.query.exec import (ExecContext,
                                           MultiSchemaPartitionsExec)
        from filodb_tpu.query.model import QueryContext
        from filodb_tpu.query.transformers import PeriodicSamplesMapper

        ms, shard, _ = _mk_shard()
        steps0, nsteps = _steps(50)
        end = steps0 + (nsteps - 1) * STEP

        def run():
            leaf = MultiSchemaPartitionsExec(
                "prom", 0, [ColumnFilter("_metric_", Equals("req_total"))],
                steps0 - 300_000, end)
            leaf.add_transformer(PeriodicSamplesMapper(
                start_ms=steps0, step_ms=STEP, end_ms=end))
            return leaf.execute(ExecContext(ms, QueryContext()))

        served = run()
        cache = next(iter(shard.device_caches.values()))
        assert cache.hits >= 1, "instant selector not grid-served"
        cache.disabled_until_version = shard.ingest_epoch + 10**9
        fallback = run()
        vs = np.asarray(served.batches[0].values)[:6]
        vf = np.asarray(fallback.batches[0].values)[:6]
        assert (np.isfinite(vs) == np.isfinite(vf)).all()
        both = np.isfinite(vs)
        np.testing.assert_allclose(vs[both], vf[both], rtol=1e-4)

    def test_fused_agg_over_time(self):
        """sum(sum_over_time(...)) fuses the aggregate on device too."""
        from filodb_tpu.query.exec import (ExecContext,
                                           MultiSchemaPartitionsExec,
                                           ReduceAggregateExec)
        from filodb_tpu.query.logical import AggregationOperator
        from filodb_tpu.query.model import QueryContext
        from filodb_tpu.query.transformers import (AggregateMapReduce,
                                                   AggregatePresenter,
                                                   PeriodicSamplesMapper)

        ms, shard, _ = _mk_shard(n_series=8)
        steps0, nsteps = _steps(50)
        end = steps0 + (nsteps - 1) * STEP

        def mk():
            leaf = MultiSchemaPartitionsExec(
                "prom", 0, [ColumnFilter("_metric_", Equals("req_total"))],
                steps0 - WINDOW, end)
            leaf.add_transformer(PeriodicSamplesMapper(
                start_ms=steps0, step_ms=STEP, end_ms=end,
                window_ms=WINDOW, function=F.SUM_OVER_TIME))
            leaf.add_transformer(AggregateMapReduce(AggregationOperator.SUM))
            root = ReduceAggregateExec([leaf], AggregationOperator.SUM)
            root.add_transformer(AggregatePresenter(AggregationOperator.SUM))
            return root

        fused = mk().execute(ExecContext(ms, QueryContext()))
        cache = next(iter(shard.device_caches.values()))
        assert cache.hits >= 1
        cache.disabled_until_version = shard.ingest_epoch + 10**9
        plain = mk().execute(ExecContext(ms, QueryContext()))
        vf = np.asarray(fused.batches[0].values[0])
        vp = np.asarray(plain.batches[0].values[0])
        fin = np.isfinite(vp)
        assert (np.isfinite(vf) == fin).all()
        np.testing.assert_allclose(vf[fin], vp[fin], rtol=1e-4)


class TestDownsampledGridServing:
    """Downsampled datasets are aligned by construction (period-end
    timestamps at exact resolution multiples), so the grid fast path
    serves long-range queries routed to them (reference intent:
    DownsampledTimeSeriesShard serving from block memory)."""

    def test_ds_dataset_served_from_grid(self):
        from filodb_tpu.downsample.sharddown import MemoryDownsamplePublisher
        from filodb_tpu.downsample.dsstore import (DownsampledTimeSeriesStore,
                                                   ds_dataset_name)
        from filodb_tpu.query.exec import (ExecContext,
                                           MultiSchemaPartitionsExec)
        from filodb_tpu.query.model import QueryContext
        from filodb_tpu.query.transformers import PeriodicSamplesMapper

        RES = 60_000
        pub = MemoryDownsamplePublisher()
        _, shard2, _ = _mk_shard(n_series=5, n_rows=120,
                                 jitter_max=5_000, flush=False)
        shard2.enable_downsampling(pub, (RES,))
        shard2.flush_all()   # emits downsample records to the publisher

        ds = DownsampledTimeSeriesStore("prom", resolutions_ms=(RES,))
        ds.setup(DEFAULT_SCHEMAS, 0)
        assert ds.ingest_from_publisher(pub) > 0
        ds_shard = ds.shard(RES, 0)
        ds_shard.flush_all()   # freeze so the grid builds from chunks

        # query at the resolution step: avg_over_time over the ds series
        lookup = ds_shard.lookup_partitions(
            [ColumnFilter("_metric_", Equals("req_total"))], 0, 2**62)
        assert len(lookup.part_ids) == 5
        t_lo = min(p.earliest_timestamp
                   for p in ds_shard.partitions.values())
        steps0 = ((t_lo // RES) + 6) * RES
        end = steps0 + 30 * RES

        def run():
            leaf = MultiSchemaPartitionsExec(
                ds_dataset_name("prom", RES), 0,
                [ColumnFilter("_metric_", Equals("req_total"))],
                steps0 - 5 * RES, end)
            leaf.add_transformer(PeriodicSamplesMapper(
                start_ms=steps0, step_ms=RES, end_ms=end,
                window_ms=5 * RES, function=F.AVG_OVER_TIME))
            return leaf.execute(ExecContext(ds.memstore, QueryContext()))

        served = run()
        cache = next(iter(ds_shard.device_caches.values()))
        assert cache.hits >= 1, "ds dataset not served from the grid"
        cache.disabled_until_version = ds_shard.ingest_epoch + 10**9
        fallback = run()
        vs = np.asarray(served.batches[0].values)[:5]
        vf = np.asarray(fallback.batches[0].values)[:5]
        assert (np.isfinite(vs) == np.isfinite(vf)).all()
        both = np.isfinite(vs)
        np.testing.assert_allclose(vs[both], vf[both], rtol=1e-4)


class TestUniformPhaseServing:
    """Uniform-phase serving: per-lane constant scrape offsets let the
    grid drop the ts plane (ops/grid.py PHASE_OPS).  The proof must
    activate on fixed-cadence data, produce results identical to the
    general path, and stay OFF for per-sample-jittered data."""

    def _mk_uniform(self, n_series=6, n_rows=50, seed=3):
        ms = TimeSeriesMemStore()
        shard = ms.setup("prom", DEFAULT_SCHEMAS, 0, StoreConfig())
        rng = np.random.default_rng(seed)
        b = RecordBuilder(DEFAULT_SCHEMAS["prom-counter"])
        truth = {}
        phases = rng.integers(1, STEP, n_series)
        for i in range(n_series):
            tags = {"__name__": "req_total", "instance": f"i{i}",
                    "_ws_": "w", "_ns_": "n"}
            base = T0 + np.arange(n_rows, dtype=np.int64) * STEP - STEP
            ts = base + phases[i]          # constant per-series phase
            vals = np.cumsum(rng.random(n_rows) * 5)
            if i == 1:
                vals[n_rows // 2:] -= vals[n_rows // 2] * 0.9  # reset
            truth[f"i{i}"] = (ts, vals)
            for t, v in zip(ts, vals):
                b.add(int(t), [float(v)], tags)
        for off, c in enumerate(b.containers()):
            shard.ingest(decode_container(c, DEFAULT_SCHEMAS), off)
        shard.flush_all()
        return ms, shard, truth

    def _oracle_rate(self, shard, part_ids, steps0, nsteps):
        from filodb_tpu.ops.windows import StepRange
        from filodb_tpu.query import rangefns
        t2, batch = shard.scan_batch(part_ids, steps0 - WINDOW,
                                     steps0 + (nsteps - 1) * STEP)
        sr = StepRange(steps0, steps0 + (nsteps - 1) * STEP, STEP)
        want = np.asarray(rangefns.apply_range_function(
            batch, sr, WINDOW, F.RATE))
        return t2, want[:len(t2)]

    def test_phase_serving_matches_general(self):
        ms, shard, truth = self._mk_uniform()
        res = _lookup(shard)
        steps0, nsteps = _steps(50)
        got = shard.scan_grid(res.part_ids, F.RATE, steps0, nsteps, STEP,
                              WINDOW)
        assert got is not None
        tags, vals, _ = got
        cache = next(iter(shard.device_caches.values()))
        assert cache._phase_memo, "uniform-phase proof should activate"
        t2, want = self._oracle_rate(shard, res.part_ids, steps0, nsteps)
        by_inst = {t["instance"]: i for i, t in enumerate(t2)}
        for i, tg in enumerate(tags):
            w = want[by_inst[tg["instance"]]]
            both = np.isfinite(vals[i]) & np.isfinite(w)
            assert (np.isfinite(vals[i]) == np.isfinite(w)).all()
            np.testing.assert_allclose(vals[i][both], w[both], rtol=2e-5)

    def test_phase_proof_rejects_jitter(self):
        ms, shard, truth = _mk_shard(jitter_max=30_000)
        res = _lookup(shard)
        steps0, nsteps = _steps(50)
        got = shard.scan_grid(res.part_ids, F.RATE, steps0, nsteps, STEP,
                              WINDOW)
        assert got is not None          # ts path still serves
        cache = next(iter(shard.device_caches.values()))
        assert not cache._phase_memo, "jittered data must not prove phase"

    def test_phase_memo_reused_on_repeat(self):
        import jax
        ms, shard, truth = self._mk_uniform()
        res = _lookup(shard)
        steps0, nsteps = _steps(50)
        shard.scan_grid(res.part_ids, F.RATE, steps0, nsteps, STEP, WINDOW)
        cache = next(iter(shard.device_caches.values()))
        assert cache._phase_memo
        (key, (host, dev)) = next(iter(cache._phase_memo.items()))
        shard.scan_grid(res.part_ids, F.RATE, steps0, nsteps, STEP, WINDOW)
        (key2, (host2, dev2)) = next(iter(cache._phase_memo.items()))
        assert key2 == key and dev2 is dev, "repeat must not re-upload"

    def test_grouped_phase_serving_matches(self):
        ms, shard, truth = self._mk_uniform(n_series=8)
        res = _lookup(shard)
        steps0, nsteps = _steps(50)
        gids = [0, 1] * 4
        state = shard.scan_grid_grouped(res.part_ids, F.RATE, steps0,
                                        nsteps, STEP, WINDOW, gids, 2,
                                        "sum")
        assert state is not None
        t2, want = self._oracle_rate(shard, res.part_ids, steps0, nsteps)
        by_inst = {t["instance"]: i for i, t in enumerate(t2)}
        order = [by_inst[f"i{i}"] for i in range(8)]
        for g in range(2):
            rows = want[[order[i] for i in range(8) if gids[i] == g]]
            exp = np.nansum(np.where(np.isfinite(rows), rows, 0.0), axis=0)
            np.testing.assert_allclose(state["sum"][g], exp, rtol=2e-5)


class TestCompressedResidents:
    """Round-5 VERDICT #4: grid blocks stay compressed in HBM (XOR-class
    value planes + elided uniform-phase ts planes) and decode on device
    inside the serving program — results must be BIT-IDENTICAL to the
    decoded-plane path, and realistic (integer-valued) gauges must fit
    >=4x more resident window per HBM byte."""

    def _gauge_shard(self, compress: bool, n_series=8, n_rows=96):
        ms = TimeSeriesMemStore()
        shard = ms.setup("prom", DEFAULT_SCHEMAS, 0,
                         StoreConfig(device_cache_compress=compress))
        rng = np.random.default_rng(5)
        b = RecordBuilder(DEFAULT_SCHEMAS["gauge"])
        for i in range(n_series):
            tags = {"__name__": "g_res", "instance": f"i{i}",
                    "_ws_": "w", "_ns_": "n"}
            ts = T0 + np.arange(n_rows, dtype=np.int64) * STEP
            # integer-valued gauge (bytes/requests/connections — the
            # common shape): a bounded random walk around 1e6
            vals = (1_000_000 + np.cumsum(
                rng.integers(-500, 500, size=n_rows))).astype(np.float64)
            b.add_series(ts, [vals], tags)
        for off, c in enumerate(b.containers()):
            shard.ingest(decode_container(c, DEFAULT_SCHEMAS), off)
        shard.flush_all()
        return ms, shard

    def _serve_all(self, shard, n_rows):
        res = shard.lookup_partitions(
            [ColumnFilter("_metric_", Equals("g_res"))], 0, 2**62)
        steps0 = T0 + (K + 1) * STEP
        nsteps = n_rows - K - 2
        out = {}
        for fn in (F.RATE, F.SUM_OVER_TIME, F.MAX_OVER_TIME, None):
            got = shard.scan_grid(res.part_ids, fn, steps0, nsteps,
                                  STEP, WINDOW)
            assert got is not None, fn
            tags_l, vals, _ = got
            order = np.argsort([t["instance"] for t in tags_l])
            out[fn] = np.asarray(vals)[order]
        return out

    def test_bit_identical_to_decoded_path(self):
        _ms1, compressed = self._gauge_shard(True)
        _ms2, plain = self._gauge_shard(False)
        got_c = self._serve_all(compressed, 96)
        got_p = self._serve_all(plain, 96)
        cache = next(iter(compressed.device_caches.values()))
        # the compressed store must actually hold packed blocks with an
        # elided ts plane (uniform cadence, integral values)
        assert any(isinstance(b.vals, dict) for b in cache.blocks.values())
        assert any(b.ts is None for b in cache.blocks.values())
        for fn in got_p:
            np.testing.assert_array_equal(got_c[fn], got_p[fn],
                                          err_msg=str(fn))

    def test_resident_window_at_least_4x(self):
        _ms, shard = self._gauge_shard(True, n_series=64, n_rows=128)
        self._serve_all(shard, 128)
        cache = next(iter(shard.device_caches.values()))
        raw = comp = 0
        from filodb_tpu.memstore.devicestore import BLOCK_BUCKETS
        for b in cache.blocks.values():
            rows = BLOCK_BUCKETS
            itemsize = 8 if not isinstance(b.vals, dict) \
                else b.vals["raw"].dtype.itemsize
            raw += rows * b.width * (4 + itemsize)
            comp += b.nbytes
        assert comp > 0 and raw / comp >= 4.0, (raw, comp, raw / comp)

    def test_repeat_query_no_rebuild_compressed(self):
        _ms, shard = self._gauge_shard(True)
        self._serve_all(shard, 96)
        cache = next(iter(shard.device_caches.values()))
        builds = cache.builds
        self._serve_all(shard, 96)
        assert cache.builds == builds, "repeat query rebuilt blocks"


class TestFusedPackedServing:
    """ISSUE 3 tentpole: eligible queries over a compressed resident run
    the FUSED packed kernels (XOR-class decode inside the grid kernel,
    interpret mode on CPU CI) and must match the decoded-plane path —
    bit-identical for free ops, to f32 rounding for the MXU rate chain.
    Also covers the hbm_read_bytes accounting satellite."""

    @pytest.fixture()
    def f32_interpret(self, monkeypatch):
        from filodb_tpu.memstore import devicestore
        monkeypatch.setattr(devicestore, "_PACKED_INTERPRET", True)
        monkeypatch.setattr(devicestore, "_PACKED_BROKEN", False)
        monkeypatch.setattr(devicestore.DeviceGridCache, "_val_dtype",
                            lambda self: np.float32)
        return devicestore

    def _counter_shard(self, compress: bool, n_rows=96, n_series=8):
        ms = TimeSeriesMemStore()
        shard = ms.setup("prom", DEFAULT_SCHEMAS, 0,
                         StoreConfig(device_cache_compress=compress))
        rng = np.random.default_rng(7)
        b = RecordBuilder(DEFAULT_SCHEMAS["prom-counter"])
        for i in range(n_series):
            tags = {"__name__": "c_total", "instance": f"i{i}",
                    "_ws_": "w", "_ns_": "n"}
            ph = int(rng.integers(1, STEP))
            ts = T0 + np.arange(n_rows, dtype=np.int64) * STEP - STEP + ph
            vals = (2 ** 23 + 128 * np.cumsum(
                rng.integers(1, 8, n_rows))).astype(np.float64)
            b.add_series(ts, [vals], tags)
        for off, c in enumerate(b.containers()):
            shard.ingest(decode_container(c, DEFAULT_SCHEMAS), off)
        shard.flush_all()
        return ms, shard

    def _scan(self, shard, fn, n_rows=96):
        res = shard.lookup_partitions(
            [ColumnFilter("_metric_", Equals("c_total"))], 0, 2**62)
        steps0 = T0 + (K + 1) * STEP
        nsteps = n_rows - K - 2
        got = shard.scan_grid(res.part_ids, fn, steps0, nsteps, STEP,
                              WINDOW)
        assert got is not None, fn
        tags_l, vals, _ = got
        order = np.argsort([t["instance"] for t in tags_l])
        return np.asarray(vals)[order]

    def test_fused_packed_dispatch_and_equivalence(self, f32_interpret):
        devicestore = f32_interpret
        _ms1, comp = self._counter_shard(True)
        _ms2, plain = self._counter_shard(False)
        for fn, exact in ((F.SUM_OVER_TIME, True), (F.MAX_OVER_TIME, True),
                          (None, True), (F.RATE, False)):
            got_c = self._scan(comp, fn)
            got_p = self._scan(plain, fn)
            if exact:
                np.testing.assert_array_equal(got_c, got_p,
                                              err_msg=str(fn))
            else:
                # MXU correction formulation vs the CPU roll-scan ref
                fin = np.isfinite(got_p)
                assert (np.isfinite(got_c) == fin).all()
                np.testing.assert_allclose(got_c[fin], got_p[fin],
                                           rtol=1e-6)
        cache = next(iter(comp.device_caches.values()))
        plan = next(iter(cache._plan_memo.values()))
        assert plan.packed is not None, \
            "compressed single-block query did not take the fused path"
        assert not devicestore._PACKED_BROKEN
        assert plan.hbm_comp > 0 and plan.hbm_dense == 0

    def test_fused_grouped_matches_decoded(self, f32_interpret):
        _ms1, comp = self._counter_shard(True)
        _ms2, plain = self._counter_shard(False)
        gids = [0, 1] * 4
        outs = []
        for shard in (comp, plain):
            res = shard.lookup_partitions(
                [ColumnFilter("_metric_", Equals("c_total"))], 0, 2**62)
            steps0 = T0 + (K + 1) * STEP
            st = shard.scan_grid_grouped(res.part_ids, F.RATE, steps0,
                                         96 - K - 2, STEP, WINDOW, gids,
                                         2, "sum")
            assert st is not None
            outs.append(st)
        np.testing.assert_allclose(outs[0]["sum"], outs[1]["sum"],
                                   rtol=1e-6)
        np.testing.assert_array_equal(outs[0]["count"], outs[1]["count"])

    def test_hbm_read_bytes_reach_query_stats(self, f32_interpret):
        from filodb_tpu.query import exec as qexec
        from filodb_tpu.query.model import QueryStats
        _ms, shard = self._counter_shard(True)
        ctx = qexec.ExecContext(memstore=None)
        qexec._ACTIVE.ctx = ctx
        try:
            self._scan(shard, F.SUM_OVER_TIME)
        finally:
            qexec._ACTIVE.ctx = None
        stats = QueryStats()
        ctx.fold_into(stats)
        assert stats.hbm_read_bytes.get("compressed", 0) > 0
        assert "dense" not in stats.hbm_read_bytes
        # and the counter family is registered under filodb_query_*
        from filodb_tpu.utils.observability import query_metrics
        m = query_metrics()["hbm_read_bytes"]
        assert m is not None

    def test_broken_breaker_falls_back(self, f32_interpret, monkeypatch):
        """A failing fused dispatch must trip the breaker and serve
        through the XLA decode path, not error the query."""
        devicestore = f32_interpret
        _ms, shard = self._counter_shard(True)

        calls = []

        def boom(*a, **k):
            calls.append(1)
            raise RuntimeError("mosaic rejected the kernel")
        devicestore._fused_progs()       # ensure progs exist, then break
        monkeypatch.setitem(devicestore._FUSED_PROGS, "series_packed",
                            boom)
        out = self._scan(shard, F.SUM_OVER_TIME)
        assert np.isfinite(out).any()
        assert devicestore._PACKED_BROKEN
        assert len(calls) == 1
        # memoized plans keep .packed set; the tripped breaker must
        # short-circuit instead of re-attempting the failing build
        out2 = self._scan(shard, F.SUM_OVER_TIME)
        assert np.isfinite(out2).any()
        assert len(calls) == 1, "breaker re-dispatched the broken kernel"


class TestFusedPackedHistServing:
    """ISSUE 14 tentpole: the ``not self.hist`` gate is lifted —
    histogram bucket planes serve from packed compressed residents
    through the SAME fused kernels (bucket columns are packed lanes;
    the ``lane*hb + bucket`` indirection composes through the pack's
    ``inv``), bit-equal to the XLA decode path, with the dedicated
    ``compressed-hist`` HBM format accounted."""

    HB = 8
    HSTEP = 10_000
    HK = 5

    @pytest.fixture()
    def f32_interpret(self, monkeypatch):
        from filodb_tpu.memstore import devicestore
        monkeypatch.setattr(devicestore, "_PACKED_INTERPRET", True)
        monkeypatch.setattr(devicestore, "_PACKED_BROKEN", False)
        monkeypatch.setattr(devicestore.DeviceGridCache, "_val_dtype",
                            lambda self: np.float32)
        return devicestore

    def _hist_shard(self, compress: bool, n_series=4, n_rows=96, seed=3):
        from filodb_tpu.codecs import histcodec
        from filodb_tpu.core.histogram import GeometricBuckets
        ms = TimeSeriesMemStore()
        shard = ms.setup("prom", DEFAULT_SCHEMAS, 0,
                         StoreConfig(device_cache_compress=compress))
        rng = np.random.default_rng(seed)
        buckets = GeometricBuckets(2.0, 2.0, self.HB)
        b = RecordBuilder(DEFAULT_SCHEMAS["prom-histogram"])
        for s in range(n_series):
            ph = int(rng.integers(1, self.HSTEP))
            cum = np.zeros(self.HB, np.int64)
            for t in range(n_rows):
                # integer counts with a pinned f32 exponent: the pack's
                # 16-bit-class guarantee holds per bucket column
                cum += 128 * rng.integers(1, 8, self.HB)
                vals = 2 ** 23 + np.cumsum(cum)
                blob = histcodec.encode_hist_value(buckets, vals)
                b.add(T0 + t * self.HSTEP - self.HSTEP + ph,
                      (float(vals[-1]), float(vals[-1]), blob),
                      {"__name__": "lat", "instance": f"i{s}",
                       "_ws_": "w", "_ns_": "n"})
        for off, c in enumerate(b.containers()):
            shard.ingest(decode_container(c, DEFAULT_SCHEMAS), off)
        shard.flush_all()
        return ms, shard

    def _scan(self, shard, fn, n_rows=96):
        res = shard.lookup_partitions(
            [ColumnFilter("_metric_", Equals("lat"))], 0, 2**62)
        steps0 = T0 + (self.HK + 1) * self.HSTEP
        nsteps = n_rows - self.HK - 2
        got = shard.scan_grid(res.part_ids, fn, steps0, nsteps,
                              self.HSTEP, self.HK * self.HSTEP)
        assert got is not None, fn
        tags_l, vals, _tops = got
        order = np.argsort([t["instance"] for t in tags_l])
        return np.asarray(vals)[order]

    def test_hist_packed_dispatch_and_equivalence(self, f32_interpret):
        devicestore = f32_interpret
        _ms1, comp = self._hist_shard(True)
        _ms2, plain = self._hist_shard(False)
        for fn, exact in ((F.SUM_OVER_TIME, True), (None, True),
                          (F.RATE, False)):
            got_c = self._scan(comp, fn)
            got_p = self._scan(plain, fn)
            assert got_c.ndim == 3 and got_c.shape[2] == self.HB
            fin = np.isfinite(got_p)
            assert (np.isfinite(got_c) == fin).all(), fn
            if exact:
                np.testing.assert_array_equal(got_c, got_p,
                                              err_msg=str(fn))
            else:
                np.testing.assert_allclose(got_c[fin], got_p[fin],
                                           rtol=1e-6)
        cache = next(iter(comp.device_caches.values()))
        assert cache.hist
        plan = next(iter(cache._plan_memo.values()))
        assert plan.packed is not None, \
            "compressed hist block did not take the fused packed path"
        assert not devicestore._PACKED_BROKEN
        assert plan.hbm_comp_hist > 0 and plan.hbm_dense == 0 \
            and plan.hbm_comp == 0

    def test_hist_grouped_fused_matches_decoded(self, f32_interpret):
        _ms1, comp = self._hist_shard(True)
        _ms2, plain = self._hist_shard(False)
        gids = [0, 1, 0, 1]
        outs = []
        for shard in (comp, plain):
            res = shard.lookup_partitions(
                [ColumnFilter("_metric_", Equals("lat"))], 0, 2**62)
            steps0 = T0 + (self.HK + 1) * self.HSTEP
            st = shard.scan_grid_grouped(
                res.part_ids, F.RATE, steps0, 96 - self.HK - 2,
                self.HSTEP, self.HK * self.HSTEP, gids, 2, "sum")
            assert st is not None
            outs.append(st)
        np.testing.assert_allclose(outs[0]["hist_sum"],
                                   outs[1]["hist_sum"], rtol=1e-6)
        np.testing.assert_array_equal(outs[0]["count"], outs[1]["count"])
        np.testing.assert_array_equal(outs[0]["bucket_tops"],
                                      outs[1]["bucket_tops"])

    def test_compressed_hist_format_reaches_query_stats(self,
                                                        f32_interpret):
        from filodb_tpu.query import exec as qexec
        from filodb_tpu.query.model import QueryStats
        _ms, shard = self._hist_shard(True)
        ctx = qexec.ExecContext(memstore=None)
        qexec._ACTIVE.ctx = ctx
        try:
            self._scan(shard, F.SUM_OVER_TIME)
        finally:
            qexec._ACTIVE.ctx = None
        stats = QueryStats()
        ctx.fold_into(stats)
        assert stats.hbm_read_bytes.get("compressed-hist", 0) > 0
        assert "dense" not in stats.hbm_read_bytes
        # the packed planes must read FEWER bytes per sample than the
        # dense plane would (the acceptance criterion's lower-hbm proof)
        cache = next(iter(shard.device_caches.values()))
        from filodb_tpu.memstore.devicestore import BLOCK_BUCKETS
        dense_bytes = sum(BLOCK_BUCKETS * b.width * 4
                          for b in cache.blocks.values())
        assert 0 < stats.hbm_read_bytes["compressed-hist"] < dense_bytes


# ---------------------------------------------------------------------------
# The frozen frontier is walked once per shard state, not once per plan
# (PR 28): every event that can move it has to force the next plan to walk
# ---------------------------------------------------------------------------

FR_ROWS = 40


def _fr_ingest(shard, metric, insts, row_lo, row_hi, offset, flush=False):
    """Rows [row_lo, row_hi) of whole-number counters, one scrape a STEP
    at one phase, for ``metric``'s ``insts``."""
    b = RecordBuilder(DEFAULT_SCHEMAS["prom-counter"])
    rows = np.arange(row_lo, row_hi, dtype=np.int64)
    for i in insts:
        tags = {"__name__": metric, "instance": f"i{i}", "_ws_": "w",
                "_ns_": f"n{i // 8}"}
        b.add_series(T0 + rows * STEP - STEP + 1000,
                     [(1000.0 * (i + 1) + 7.0 * rows * (i + 1))], tags)
    for k, c in enumerate(b.containers()):
        shard.ingest(decode_container(c, DEFAULT_SCHEMAS), offset + k)
    if flush:
        shard.flush_all()


def _fr_lookup(shard, metric, **labels):
    flt = [ColumnFilter("_metric_", Equals(metric))] + \
        [ColumnFilter(k, Equals(v)) for k, v in labels.items()]
    return shard.lookup_partitions(flt, 0, 2**62)


def _fr_cache(shard):
    return next(iter(shard.device_caches.values()))


def _fr_fresh_walk(cache):
    """The frontier as the test's own walk over every lane reads it."""
    lo = None
    for pid in list(cache.lane_of):
        part = cache._shard.grid_partition(pid)
        if part is not None and part._buf_n:
            t = int(part._buf_ts[0])
            lo = t if lo is None else min(lo, t)
    if lo is None:
        return 2**62
    return (lo - cache.epoch0 + cache.gstep - 1) // cache.gstep - 1


def _fr_used(cache):
    """The frontier the next plan uses."""
    with cache._lock:
        return cache._frozen_high()


def _fr_agrees(shard, part_ids, n_rows):
    """``scan_grid`` over rows [0, n_rows) against the general scan
    path; returns the plan's segments, None where the grid declined."""
    from filodb_tpu.ops.windows import StepRange
    from filodb_tpu.query import rangefns
    steps0, nsteps = _steps(n_rows)
    got = shard.scan_grid(part_ids, F.RATE, steps0, nsteps, STEP, WINDOW)
    if got is None:
        return None
    tags, vals, _tops = got
    end = steps0 + (nsteps - 1) * STEP
    t2, batch = shard.scan_batch(part_ids, steps0 - WINDOW, end)
    want = np.asarray(rangefns.apply_range_function(
        batch, StepRange(steps0, end, STEP), WINDOW, F.RATE))[:len(t2)]
    assert [t["instance"] for t in tags] == [t["instance"] for t in t2]
    np.testing.assert_allclose(vals, want, rtol=1e-6, equal_nan=True)
    assert np.isfinite(vals[:, -1]).all()       # the newest rows count
    cache = _fr_cache(shard)
    return next(reversed(cache._plan_memo.values())).segs


def _fr_frozen(cache, segs) -> bool:
    """Every segment of the plan is a frozen block, none a tail."""
    frozen = {id(blk) for blk in cache.blocks.values()}
    return all(id(blk) in frozen for blk in segs)


def _fr_shard():
    ms = TimeSeriesMemStore()
    shard = ms.setup("prom", DEFAULT_SCHEMAS, 0, StoreConfig())
    _fr_ingest(shard, "m_a", range(6), 0, FR_ROWS, 0, flush=True)
    res = _fr_lookup(shard, "m_a")
    segs = _fr_agrees(shard, res.part_ids, FR_ROWS)
    cache = _fr_cache(shard)
    assert segs is not None and _fr_frozen(cache, segs)
    assert _fr_used(cache) == 2**62 and cache.frontier_walks == 1
    return shard, cache, res


def _fr_ingest_after_plan(_tmp_path):
    """Rows land in a staged lane's write buffer after a plan: the
    frozen block of that range lacks them and must not be served."""
    shard, cache, res = _fr_shard()
    frozen = dict(cache.blocks)
    _fr_ingest(shard, "m_a", [2], FR_ROWS, FR_ROWS + 3, 100)
    yield shard, cache
    segs = _fr_agrees(shard, res.part_ids, FR_ROWS + 3)
    assert segs is not None
    assert _fr_used(cache) < FR_ROWS + K
    assert not any(blk is frozen.get(bi) for blk in segs for bi in frozen)


def _fr_flush_afterwards(_tmp_path):
    """... and once they are flushed the frontier returns, and the
    frozen path with it."""
    shard, cache, res = _fr_shard()
    _fr_ingest(shard, "m_a", [2], FR_ROWS, FR_ROWS + 3, 100)
    assert _fr_agrees(shard, res.part_ids, FR_ROWS + 3) is not None
    assert _fr_used(cache) < 2**62
    shard.flush_all()
    yield shard, cache
    assert _fr_used(cache) == 2**62
    segs = _fr_agrees(shard, res.part_ids, FR_ROWS + 3)
    assert segs is not None and _fr_frozen(cache, segs)


def _fr_late_lanes_buffered(_tmp_path):
    """A second metric of the schema gets its lanes after the walk, its
    rows still in the write buffer."""
    shard, cache, res = _fr_shard()
    _fr_ingest(shard, "m_b", range(4), 0, FR_ROWS, 200)
    # m_a's lanes alone are walked: another metric's buffer is not theirs
    assert _fr_agrees(shard, res.part_ids, FR_ROWS) is not None
    assert _fr_used(cache) == 2**62
    res_b = _fr_lookup(shard, "m_b")
    with cache._lock:       # lanes for m_b, as the next plan assigns them
        assert cache._prep_for(res_b.part_ids) is not None
    yield shard, cache
    assert _fr_used(cache) == 0         # m_b's first row is bucket 1's
    segs = _fr_agrees(shard, res_b.part_ids, FR_ROWS)
    assert segs is not None and not _fr_frozen(cache, segs)


def _fr_partition_removed(_tmp_path):
    """The one partition with buffered rows is purged: what held the
    frontier down is gone."""
    shard, cache, _res = _fr_shard()
    _fr_ingest(shard, "m_a", [9], 0, 5, 300)        # old rows, unflushed
    res = _fr_lookup(shard, "m_a")
    assert len(res.part_ids) == 7
    assert shard.scan_grid(res.part_ids, F.RATE, *_steps(FR_ROWS), STEP,
                           WINDOW) is not None
    assert _fr_used(cache) == 0
    cutoff = T0 + 20 * STEP
    assert shard.purge_expired(0, cutoff) == 1
    yield shard, cache
    assert _fr_used(cache) == 2**62
    res = _fr_lookup(shard, "m_a")
    assert len(res.part_ids) == 6
    # the build that meets the purged lane prunes it and declines once
    segs = _fr_agrees(shard, res.part_ids, FR_ROWS) or \
        _fr_agrees(shard, res.part_ids, FR_ROWS)
    assert segs is not None and _fr_frozen(cache, segs)


def _fr_ingest_during_walk(_tmp_path):
    """A row lands in a lane the walk has already passed: the walk
    misses it, the ingest hook has queued it, and it is folded into the
    bound once the ingest thread gets the grid lock (or by the next
    plan): the memo is never left fresh over a missed row."""
    import threading
    shard, cache, res = _fr_shard()
    shard.bump_removal_epoch()          # any event: the next plan walks
    pids = list(cache.lane_of)
    inner = shard.grid_partition
    ingest = threading.Thread(
        target=_fr_ingest, args=(shard, "m_a", [0], FR_ROWS, FR_ROWS + 1,
                                 400))

    def racing(pid):
        if pid == pids[-1] and shard.grid_partition is racing:
            shard.grid_partition = inner        # once
            ingest.start()      # another thread's: it waits for the lock
            while not cache._pend and ingest.is_alive():
                time.sleep(0.001)
        return inner(pid)

    shard.grid_partition = racing
    try:
        assert _fr_used(cache) == 2**62     # the walk missed the row
    finally:
        shard.grid_partition = inner
    ingest.join(30)
    assert not ingest.is_alive()
    yield shard, cache
    assert _fr_used(cache) < 2**62
    assert _fr_agrees(shard, res.part_ids, FR_ROWS + 1) is not None


def _fr_batch_raises_midway(_tmp_path):
    """An ingest batch that raises after rows landed still moves the
    epoch: the retry adds nothing (its rows are duplicates) and would
    move none."""
    shard, cache, res = _fr_shard()
    b = RecordBuilder(DEFAULT_SCHEMAS["prom-counter"])
    rows = np.arange(FR_ROWS, FR_ROWS + 2, dtype=np.int64)
    b.add_series(T0 + rows * STEP - STEP + 1000, [7000.0 + rows],
                 {"__name__": "m_a", "instance": "i3", "_ws_": "w",
                  "_ns_": "n0"})
    recs = list(decode_container(b.containers()[0], DEFAULT_SCHEMAS))

    def batch():
        yield from recs
        raise OSError("the stream broke")

    with pytest.raises(OSError):
        shard.ingest(batch(), 500)
    assert shard.ingest(recs, 501) == 0
    yield shard, cache
    assert _fr_used(cache) < 2**62
    assert _fr_agrees(shard, res.part_ids, FR_ROWS + 2) is not None


def _fr_odp_page_in_and_evict(tmp_path):
    """An ``OnDemandPagingShard``: eviction takes the partitions with
    their buffers away, a page-in brings them back as chunks alone, and
    page-cache eviction takes them away again."""
    from filodb_tpu.memstore.odp import OnDemandPagingShard
    from filodb_tpu.store.persistence import (DiskColumnStore,
                                              DiskMetaStore)
    store = TimeSeriesMemStore(DiskColumnStore(str(tmp_path / "c.db")),
                               DiskMetaStore(str(tmp_path / "m.db")))
    shard = store.setup("prom", DEFAULT_SCHEMAS, 0,
                        StoreConfig(groups_per_shard=2))
    assert isinstance(shard, OnDemandPagingShard)
    _fr_ingest(shard, "m_a", range(6), 0, FR_ROWS, 0, flush=True)
    _fr_ingest(shard, "m_a", range(6), FR_ROWS, FR_ROWS + 2, 100)
    res = _fr_lookup(shard, "m_a")
    assert _fr_agrees(shard, res.part_ids, FR_ROWS + 2) is not None
    cache = _fr_cache(shard)
    assert _fr_used(cache) < 2**62      # the buffers hold it down
    assert shard.evict_partitions(6) == 6           # flushes, then drops
    shard.scan_batch(res.part_ids, 0, 2**62)        # pages all six in
    assert shard.stats.partitions_paged == 6
    yield shard, cache
    assert _fr_used(cache) == 2**62
    segs = _fr_agrees(shard, res.part_ids, FR_ROWS + 2)
    assert segs is not None and _fr_frozen(cache, segs)
    walks = cache.frontier_walks
    shard.paged.max_bytes = 1
    shard.paged.put(999_999, object(), 10)          # LRU pressure
    assert _fr_used(cache) == _fr_fresh_walk(cache)
    assert cache.frontier_walks == walks + 1
    # declined (a lane's partition is paged out) or re-paged and right
    _fr_agrees(shard, res.part_ids, FR_ROWS + 2)


class TestFrozenFrontierMemo:
    @pytest.mark.parametrize("event", [
        _fr_ingest_after_plan, _fr_flush_afterwards,
        _fr_late_lanes_buffered, _fr_partition_removed,
        _fr_odp_page_in_and_evict, _fr_ingest_during_walk,
        _fr_batch_raises_midway], ids=lambda f: f.__name__[4:])
    def test_event_invalidates_the_frontier(self, event, tmp_path):
        """After each event (a) the frontier the next plan uses is a
        fresh walk's and (b) ``scan_grid`` agrees with the general scan
        path on the rows involved (the case's own assertions, after its
        ``yield``)."""
        case = event(tmp_path)
        shard, cache = next(case)
        fresh = _fr_fresh_walk(cache)
        assert _fr_used(cache) == fresh
        walks = cache.frontier_walks
        assert _fr_used(cache) == fresh             # ... and is memoized
        assert cache.frontier_walks == walks
        assert next(case, None) is None

    @pytest.mark.parametrize("namespaces", [10, 30])
    def test_a_plan_costs_the_lanes_requested(self, namespaces):
        """Counted, not timed: with N lanes staged, a plan on a memo
        miss asks ``grid_partition`` about the ids requested and no
        others, and the walk over all N runs once for the state."""
        from filodb_tpu.utils.observability import TRACER
        ms = TimeSeriesMemStore()
        shard = ms.setup("prom", DEFAULT_SCHEMAS, 0, StoreConfig())
        n = 8 * namespaces
        _fr_ingest(shard, "m_a", range(n), 0, FR_ROWS, 0, flush=True)
        res = _fr_lookup(shard, "m_a")
        assert _fr_agrees(shard, res.part_ids, FR_ROWS) is not None
        cache = _fr_cache(shard)
        assert len(cache.lane_of) == n

        def walks():
            return TRACER.stages.snapshot().get(
                "grid.frontier", {"count": 0})["count"]

        calls = []
        inner = shard.grid_partition
        shard.grid_partition = lambda pid: calls.append(pid) or inner(pid)
        walks0, per_plan = walks(), []
        steps0, nsteps = _steps(FR_ROWS)
        args = (F.RATE, steps0, nsteps, STEP, WINDOW, ())
        try:
            for ns in range(10):
                ids = _fr_lookup(shard, "m_a", _ns_=f"n{ns}").part_ids
                assert len(ids) == 8
                before = len(calls)
                with cache._lock:
                    plan = cache._plan_staged(ids, *args)
                assert plan is not None and plan.ncols >= n
                per_plan.append(len(calls) - before)
            with cache._lock:
                again = cache._plan_staged(ids, *args)
        finally:
            shard.grid_partition = inner
        assert again is plan                # the memoized plan object
        # the first id's schema check and one visit of each id: the same
        # whatever is resident
        assert per_plan == [1 + 8] * 10
        assert walks() - walks0 <= 1
        assert cache.frontier_walks <= 2


def test_fused_progs_are_published_whole(monkeypatch):
    """A request that arrives while a process's first one is still
    building the programs must see none of them or all.  Filled entry by
    entry, the dict answered it as soon as it held one (``KeyError:
    'grouped_batch'``, a 400 on a server's first panels, met by the
    multi-server chaos tests on a loaded host)."""
    from filodb_tpu.memstore import devicestore as dvs
    names = sorted(dvs._fused_progs())
    seen_partly = []

    class Watched(dict):
        def __setitem__(self, key, value):
            seen_partly.append(sorted(self))    # what a reader finds now
            super().__setitem__(key, value)

    progs = Watched()
    monkeypatch.setattr(dvs, "_FUSED_PROGS", progs)
    assert dvs._fused_progs() is progs and sorted(progs) == names
    assert not seen_partly
