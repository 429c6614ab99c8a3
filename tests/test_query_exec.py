"""Query engine tests: exec plans + transformers + aggregators over a real
in-process memstore (reference test pattern: direct ExecPlan construction
with InProcessPlanDispatcher, MultiSchemaPartitionsExecSpec,
AggrOverRangeVectorsSpec, BinaryJoinExecSpec — SURVEY.md §4)."""

import threading

import numpy as np
import pytest

from filodb_tpu.core.filters import ColumnFilter, Equals
from filodb_tpu.core.schemas import DEFAULT_SCHEMAS
from filodb_tpu.core.storeconfig import StoreConfig
from filodb_tpu.memstore import TimeSeriesMemStore
from filodb_tpu.query.exec import (BinaryJoinExec, DistConcatExec, ExecContext,
                                   LabelValuesDistConcatExec, LabelValuesExec,
                                   MultiSchemaPartitionsExec, PartKeysExec,
                                   ReduceAggregateExec, ScalarBinaryOperationExec,
                                   ScalarFixedDoubleExec, SetOperatorExec,
                                   TimeScalarGeneratorExec)
from filodb_tpu.query.logical import (AggregationOperator, BinaryOperator,
                                      Cardinality, InstantFunctionId,
                                      MiscellaneousFunctionId, RangeFunctionId,
                                      ScalarFunctionId, SortFunctionId)
from filodb_tpu.query.model import PeriodicBatch, QueryContext, QueryError
from filodb_tpu.query.transformers import (AbsentFunctionMapper,
                                           AggregateMapReduce,
                                           AggregatePresenter,
                                           HistogramQuantileMapper,
                                           InstantVectorFunctionMapper,
                                           MiscellaneousFunctionMapper,
                                           PeriodicSamplesMapper,
                                           ScalarOperationMapper,
                                           SortFunctionMapper,
                                           StitchRvsMapper)
from tests import oracle
from tests.data import START_TS, counter_containers, gauge_containers, histogram_containers

MAX = np.iinfo(np.int64).max
STEP = 10_000


def eq(k, v):
    return ColumnFilter(k, Equals(v))


@pytest.fixture(scope="module")
def ms():
    store = TimeSeriesMemStore()
    cfg = StoreConfig(groups_per_shard=4, max_chunks_size=64,
                      batch_row_pad=32, batch_series_pad=4)
    for shard in (0, 1):
        store.setup("ds", DEFAULT_SCHEMAS, shard, cfg)
    # series 0..5 on shard 0, 6..11 on shard 1 (6 series each)
    for off, c in enumerate(gauge_containers(n_series=6, n_samples=120)):
        store.ingest("ds", 0, c, off)
    b2 = gauge_containers(n_series=6, n_samples=120, seed=43)
    # shift tags so shard 1 has different instances
    from filodb_tpu.core.record import RecordBuilder, decode_container
    from filodb_tpu.core.schemas import DatasetOptions
    rb = RecordBuilder(DEFAULT_SCHEMAS["gauge"], DatasetOptions())
    for c in b2:
        for rec in decode_container(c, DEFAULT_SCHEMAS):
            tags = dict(rec.tags, instance=str(int(rec.tags["instance"]) + 6))
            rb.add(rec.timestamp, rec.values, tags)
    for off, c in enumerate(rb.containers()):
        store.ingest("ds", 1, c, off)
    for off, c in enumerate(counter_containers(n_series=3, n_samples=120)):
        store.ingest("ds", 0, c, 100 + off)
    for off, c in enumerate(histogram_containers(n_series=2, n_samples=60)):
        store.ingest("ds", 0, c, 200 + off)
    return store


@pytest.fixture()
def ctx(ms):
    return ExecContext(ms, QueryContext(query_id="t1"))


def leaf(metric, shard=0, start=START_TS, end=START_TS + 2_000_000):
    return MultiSchemaPartitionsExec("ds", shard, [eq("_metric_", metric)],
                                     start, end)


def grid(start=START_TS + 300_000, end=START_TS + 900_000):
    return dict(start_ms=start, step_ms=STEP, end_ms=end)


class TestLeafAndWindowing:
    def test_raw_scan(self, ctx):
        plan = leaf("heap_usage")
        res = plan.execute(ctx)
        assert len(res.batches) == 1
        raw = res.batches[0]
        assert len(raw.keys) == 6
        assert raw.batch.row_counts[:6].sum() == 6 * 120

    def test_periodic_rate_matches_oracle(self, ctx):
        g = grid()
        plan = leaf("http_requests_total")
        plan.add_transformer(PeriodicSamplesMapper(
            window_ms=60_000, function=RangeFunctionId.RATE, **g))
        res = plan.execute(ctx)
        b = res.batches[0]
        assert isinstance(b, PeriodicBatch)
        assert b.num_series == 3
        # oracle comparison on one series
        shard = ctx.memstore.get_shard("ds", 0)
        look = shard.lookup_partitions([eq("_metric_", "http_requests_total")],
                                       0, MAX)
        i = int(np.argwhere([t == b.keys[0] for t in
                             [shard.partitions[int(p)].tags
                              for p in look.part_ids]])[0][0])
        part = shard.partitions[int(look.part_ids[i])]
        ts, vals = part.read_range(0, MAX)
        expect = oracle.range_fn("rate", ts, vals, g["start_ms"], g["end_ms"],
                                 STEP, 60_000)
        np.testing.assert_allclose(b.np_values()[0], expect, rtol=1e-9,
                                   equal_nan=True)

    def test_instant_selector_default_lookback(self, ctx):
        plan = leaf("heap_usage")
        plan.add_transformer(PeriodicSamplesMapper(**grid()))
        res = plan.execute(ctx)
        b = res.batches[0]
        # dense data: every step has the last sample within 5m
        assert np.isfinite(b.np_values()).all()

    def test_offset(self, ctx):
        g = grid()
        p1 = leaf("heap_usage")
        p1.add_transformer(PeriodicSamplesMapper(
            window_ms=120_000, function=RangeFunctionId.SUM_OVER_TIME,
            offset_ms=60_000, **g))
        res1 = p1.execute(ctx)
        g2 = dict(g)
        g2["start_ms"] -= 60_000
        g2["end_ms"] -= 60_000
        p2 = leaf("heap_usage")
        p2.add_transformer(PeriodicSamplesMapper(
            window_ms=120_000, function=RangeFunctionId.SUM_OVER_TIME, **g2))
        res2 = p2.execute(ctx)
        np.testing.assert_allclose(res1.batches[0].np_values(),
                                   res2.batches[0].np_values(), equal_nan=True)
        # but reported at the unshifted grid
        assert res1.batches[0].steps.start == g["start_ms"]

    def test_sample_limit(self, ms):
        strict = ExecContext(ms, QueryContext(sample_limit=10))
        plan = leaf("heap_usage")
        plan.add_transformer(PeriodicSamplesMapper(**grid()))
        with pytest.raises(QueryError, match="limit"):
            plan.execute(strict)


class TestAggregation:
    def run_agg(self, ctx, op, params=(), by=(), without=(), metric="heap_usage",
                fn=RangeFunctionId.SUM_OVER_TIME):
        children = []
        for shard in (0, 1):
            p = leaf(metric, shard)
            p.add_transformer(PeriodicSamplesMapper(
                window_ms=60_000, function=fn, **grid()))
            p.add_transformer(AggregateMapReduce(op, params, by, without))
            children.append(p)
        root = ReduceAggregateExec(children, op, params)
        root.add_transformer(AggregatePresenter(op, params))
        return root.execute(ctx)

    def oracle_values(self, ctx, metric="heap_usage"):
        """[S, T] sum_over_time values across both shards + their keys."""
        out_keys, rows = [], []
        g = grid()
        for shard_num in (0, 1):
            shard = ctx.memstore.get_shard("ds", shard_num)
            look = shard.lookup_partitions([eq("_metric_", metric)], 0, MAX)
            for pid in look.part_ids:
                part = shard.partitions[int(pid)]
                ts, vals = part.read_range(0, MAX)
                rows.append(oracle.range_fn("sum_over_time", ts, vals,
                                            g["start_ms"], g["end_ms"], STEP,
                                            60_000))
                out_keys.append(part.tags)
        return out_keys, np.stack(rows)

    def test_sum_cross_shard(self, ctx):
        res = self.run_agg(ctx, AggregationOperator.SUM)
        keys, vals = self.oracle_values(ctx)
        expect = np.nansum(vals, axis=0)
        assert res.batches[0].num_series == 1
        np.testing.assert_allclose(res.batches[0].np_values()[0], expect,
                                   rtol=1e-9)

    def test_sum_by_ns(self, ctx):
        res = self.run_agg(ctx, AggregationOperator.SUM, by=("_ns_",))
        keys, vals = self.oracle_values(ctx)
        b = res.batches[0]
        for i, gk in enumerate(b.keys):
            members = [j for j, t in enumerate(keys)
                       if t["_ns_"] == gk["_ns_"]]
            expect = np.nansum(vals[members], axis=0)
            np.testing.assert_allclose(b.np_values()[i], expect, rtol=1e-9)

    def test_avg_and_count(self, ctx):
        res_a = self.run_agg(ctx, AggregationOperator.AVG)
        res_c = self.run_agg(ctx, AggregationOperator.COUNT)
        keys, vals = self.oracle_values(ctx)
        np.testing.assert_allclose(res_a.batches[0].np_values()[0],
                                   np.nanmean(vals, axis=0), rtol=1e-9)
        np.testing.assert_allclose(res_c.batches[0].np_values()[0],
                                   np.sum(np.isfinite(vals), axis=0).astype(float))

    def test_min_max_stddev(self, ctx):
        keys, vals = self.oracle_values(ctx)
        for op, fn in ((AggregationOperator.MIN, np.nanmin),
                       (AggregationOperator.MAX, np.nanmax),
                       (AggregationOperator.STDDEV,
                        lambda v, axis: np.nanstd(v, axis=axis))):
            res = self.run_agg(ctx, op)
            np.testing.assert_allclose(res.batches[0].np_values()[0],
                                       fn(vals, axis=0), rtol=1e-8)

    def test_topk(self, ctx):
        res = self.run_agg(ctx, AggregationOperator.TOPK, params=(3,))
        keys, vals = self.oracle_values(ctx)
        b = res.batches[0]
        # at each step, union of reported finite values == top-3 of oracle
        got = b.np_values()
        for t in range(got.shape[1]):
            col = got[:, t]
            top_got = np.sort(col[np.isfinite(col)])
            expect = np.sort(vals[:, t])[-3:]
            np.testing.assert_allclose(top_got, expect, rtol=1e-9)
        # result series carry original labels
        assert all("instance" in k for k in b.keys)

    def test_quantile(self, ctx):
        res = self.run_agg(ctx, AggregationOperator.QUANTILE, params=(0.5,))
        keys, vals = self.oracle_values(ctx)
        np.testing.assert_allclose(res.batches[0].np_values()[0],
                                   np.nanquantile(vals, 0.5, axis=0), rtol=1e-9)

    def test_count_values(self, ctx):
        res = self.run_agg(ctx, AggregationOperator.COUNT_VALUES,
                           params=("val",), fn=RangeFunctionId.COUNT_OVER_TIME)
        b = res.batches[0]
        assert all("val" in k for k in b.keys)
        keys, _ = self.oracle_values(ctx)
        # every step's counts sum to the total series count
        total = np.nansum(b.np_values(), axis=0)
        assert (total == len(keys)).all()


class TestJoinsAndScalars:
    def periodic(self, metric, shard=0, fn=None):
        p = leaf(metric, shard)
        p.add_transformer(PeriodicSamplesMapper(
            window_ms=60_000 if fn else None, function=fn, **grid()))
        return p

    def test_binary_join_one_to_one(self, ctx):
        lhs = self.periodic("heap_usage")
        rhs = self.periodic("heap_usage")
        join = BinaryJoinExec([lhs, rhs], 1, BinaryOperator.ADD)
        res = join.execute(ctx)
        b = res.batches[0]
        assert b.num_series == 6
        single = self.periodic("heap_usage").execute(ctx).batches[0]
        np.testing.assert_allclose(
            sorted(b.np_values()[:, 0]),
            sorted(2 * single.np_values()[:len(single.keys), 0]))
        assert all("_metric_" not in k for k in b.keys)

    def test_join_on_mismatch_drops(self, ctx):
        lhs = self.periodic("heap_usage", shard=0)
        rhs = self.periodic("heap_usage", shard=1)  # different instances
        join = BinaryJoinExec([lhs, rhs], 1, BinaryOperator.ADD)
        res = join.execute(ctx)
        assert res.batches[0].num_series == 0

    def test_set_and_or_unless(self, ctx):
        lhs = self.periodic("heap_usage", shard=0)
        rhs = self.periodic("heap_usage", shard=0)
        for op, expect in ((BinaryOperator.LAND, 6), (BinaryOperator.LOR, 6),
                           (BinaryOperator.LUNLESS, 0)):
            ex = SetOperatorExec([self.periodic("heap_usage"),
                                  self.periodic("heap_usage")], 1, op)
            res = ex.execute(ctx)
            got = res.batches[0].num_series if res.batches else 0
            assert got == expect, op

    def test_scalar_operation(self, ctx):
        p = self.periodic("heap_usage")
        p.add_transformer(ScalarOperationMapper("MUL", 2.0))
        res = p.execute(ctx)
        single = self.periodic("heap_usage").execute(ctx).batches[0]
        np.testing.assert_allclose(res.batches[0].np_values()[:len(single.keys)],
                                   2 * single.np_values()[:len(single.keys)],
                                   equal_nan=True)

    def test_scalar_comparison_filters(self, ctx):
        p = self.periodic("heap_usage")
        p.add_transformer(ScalarOperationMapper("GTR", 50.0))
        res = p.execute(ctx)
        v = res.batches[0].np_values()
        fin = v[np.isfinite(v)]
        assert (fin > 50).all()

    def test_scalar_binary_exec(self, ctx):
        g = grid()
        ex = ScalarBinaryOperationExec(BinaryOperator.ADD, 1.0, 2.0,
                                      g["start_ms"], STEP, g["end_ms"])
        res = ex.execute(ctx)
        assert (np.asarray(res.batches[0].values) == 3.0).all()

    def test_time_scalar(self, ctx):
        g = grid()
        ex = TimeScalarGeneratorExec(ScalarFunctionId.TIME, g["start_ms"],
                                     STEP, g["end_ms"])
        res = ex.execute(ctx)
        v = np.asarray(res.batches[0].values)
        assert v[0] == g["start_ms"] / 1000.0

    def test_fixed_scalar(self, ctx):
        g = grid()
        ex = ScalarFixedDoubleExec(42.0, g["start_ms"], STEP, g["end_ms"])
        res = ex.execute(ctx)
        assert (np.asarray(res.batches[0].values) == 42.0).all()


class TestTransformers:
    def periodic(self, ctx, metric="heap_usage", fn=None):
        p = MultiSchemaPartitionsExec("ds", 0, [eq("_metric_", metric)],
                                      START_TS, START_TS + 2_000_000)
        p.add_transformer(PeriodicSamplesMapper(
            window_ms=60_000 if fn else None, function=fn, **grid()))
        return p

    def test_instant_function(self, ctx):
        p = self.periodic(ctx)
        p.add_transformer(InstantVectorFunctionMapper(InstantFunctionId.ABS))
        res = p.execute(ctx)
        assert (res.batches[0].np_values()[np.isfinite(res.batches[0].np_values())] >= 0).all()

    def test_histogram_quantile_via_hist_schema(self, ctx):
        p = self.periodic(ctx, metric="req_latency",
                          fn=RangeFunctionId.RATE)
        p.add_transformer(InstantVectorFunctionMapper(
            InstantFunctionId.HISTOGRAM_QUANTILE, (0.9,)))
        res = p.execute(ctx)
        b = res.batches[0]
        v = b.np_values()[:len(b.keys)]
        assert np.isfinite(v).any()
        assert (v[np.isfinite(v)] >= 0).all()

    def test_sum_over_histograms_bucketwise(self, ctx):
        """sum(rate(hist)) aggregates bucket-wise (reference:
        HistSumRowAggregator) and histogram_quantile applies on top —
        the BASELINE config-2 query shape."""
        from filodb_tpu.ops import histogram_ops
        from filodb_tpu.query.aggregators import AggPartialBatch
        from filodb_tpu.query.logical import AggregationOperator
        from filodb_tpu.query.transformers import AggregateMapReduce, AggregatePresenter
        import jax.numpy as jnp

        # oracle: per-series hist rates, summed on host, then quantile
        per = self.periodic(ctx, metric="req_latency", fn=RangeFunctionId.RATE)
        rb = per.execute(ctx).batches[0]
        S = len(rb.keys)
        h = np.asarray(rb.hist)[:S]                       # [S, T, B]
        fin = np.isfinite(h[..., -1])
        want_hist = np.where(fin[..., None], h, 0.0).sum(axis=0)
        want_hist = np.where(fin.any(axis=0)[..., None], want_hist, np.nan)
        want_q = np.asarray(histogram_ops.hist_quantile(
            jnp.asarray(rb.bucket_tops), jnp.asarray(want_hist[None]), 0.99))[0]

        p = self.periodic(ctx, metric="req_latency", fn=RangeFunctionId.RATE)
        p.add_transformer(AggregateMapReduce(AggregationOperator.SUM))
        p.add_transformer(AggregatePresenter(AggregationOperator.SUM))
        p.add_transformer(InstantVectorFunctionMapper(
            InstantFunctionId.HISTOGRAM_QUANTILE, (0.99,)))
        res = p.execute(ctx)
        b = res.batches[0]
        got = b.np_values()[0]
        assert (np.isfinite(got) == np.isfinite(want_q)).all()
        both = np.isfinite(got)
        assert both.any()
        np.testing.assert_allclose(got[both], want_q[both], rtol=1e-6)

    def test_hist_sum_reduce_pads_bucket_widths(self, ctx):
        """Cross-shard reduce of histogram sums with different bucket
        schemes: narrower cumulative matrices edge-pad to the widest."""
        from filodb_tpu.ops.windows import StepRange
        from filodb_tpu.query.aggregators import (AggPartialBatch,
                                                  MomentAggregator)
        from filodb_tpu.query.logical import AggregationOperator

        steps = StepRange(0, 60_000, 60_000)
        agg = MomentAggregator(AggregationOperator.SUM)
        wide = AggPartialBatch(
            AggregationOperator.SUM, (), [{}], steps,
            {"hist_sum": np.ones((1, 2, 4)), "count": np.ones((1, 2))},
            bucket_tops=np.array([0.1, 0.5, 1.0, np.inf]))
        narrow = AggPartialBatch(
            AggregationOperator.SUM, (), [{}], steps,
            {"hist_sum": np.full((1, 2, 2), 2.0), "count": np.ones((1, 2))},
            bucket_tops=np.array([0.1, np.inf]))
        out = agg.reduce([wide, narrow])
        assert out.state["hist_sum"].shape == (1, 2, 4)
        # narrow's top bucket (total=2) edge-pads across the widened tail
        np.testing.assert_allclose(out.state["hist_sum"][0, 0], [3, 3, 3, 3])
        np.testing.assert_allclose(out.bucket_tops, [0.1, 0.5, 1.0, np.inf])
        pres = agg.present(out)
        assert pres.hist.shape == (1, 2, 4)

    def test_min_over_histograms_rejected(self, ctx):
        from filodb_tpu.query.logical import AggregationOperator
        from filodb_tpu.query.transformers import AggregateMapReduce
        from filodb_tpu.query.model import QueryError

        p = self.periodic(ctx, metric="req_latency", fn=RangeFunctionId.RATE)
        p.add_transformer(AggregateMapReduce(AggregationOperator.MIN))
        with pytest.raises(QueryError, match="histogram"):
            p.execute(ctx)

    def test_hist_to_prom_and_bucket_quantile(self, ctx):
        p = self.periodic(ctx, metric="req_latency",
                          fn=RangeFunctionId.SUM_OVER_TIME)
        p.add_transformer(MiscellaneousFunctionMapper(
            MiscellaneousFunctionId.HIST_TO_PROM_VECTORS))
        res = p.execute(ctx)
        b = res.batches[0]
        assert all("le" in k for k in b.keys)
        # now quantile over the exploded series
        hq = HistogramQuantileMapper(0.9)
        out = hq.apply([b], ctx)
        assert out[0].num_series == 2
        assert all("le" not in k for k in out[0].keys)

    def test_label_replace_and_join(self, ctx):
        p = self.periodic(ctx)
        p.add_transformer(MiscellaneousFunctionMapper(
            MiscellaneousFunctionId.LABEL_REPLACE,
            ("dst", "prefix-$1", "instance", "(.*)")))
        res = p.execute(ctx)
        assert all(k["dst"] == f"prefix-{k['instance']}"
                   for k in res.batches[0].keys)
        p2 = self.periodic(ctx)
        p2.add_transformer(MiscellaneousFunctionMapper(
            MiscellaneousFunctionId.LABEL_JOIN, ("joined", "-", "_ns_", "host")))
        res2 = p2.execute(ctx)
        assert all(k["joined"] == f"{k['_ns_']}-{k['host']}"
                   for k in res2.batches[0].keys)

    def test_sort(self, ctx):
        p = self.periodic(ctx)
        p.add_transformer(SortFunctionMapper(SortFunctionId.SORT_DESC))
        res = p.execute(ctx)
        v = res.batches[0].np_values()
        means = np.nanmean(v, axis=1)
        assert (np.diff(means) <= 1e-12).all()

    def test_absent_on_present_and_missing(self, ctx):
        p = self.periodic(ctx)
        p.add_transformer(AbsentFunctionMapper())
        res = p.execute(ctx)
        assert np.isnan(res.batches[0].np_values()).all()
        g = grid()
        p2 = MultiSchemaPartitionsExec("ds", 0, [eq("_metric_", "nope")],
                                       START_TS, START_TS + 2_000_000)
        p2.add_transformer(PeriodicSamplesMapper(**g))
        p2.add_transformer(AbsentFunctionMapper(
            filters=(eq("_metric_", "nope"),), start_ms=g["start_ms"],
            step_ms=STEP, end_ms=g["end_ms"]))
        res2 = p2.execute(ctx)
        assert (res2.batches[0].np_values() == 1.0).all()

    def test_stitch(self, ctx):
        g = grid()
        b1 = PeriodicBatch([{"a": "1"}],
                           __import__("filodb_tpu.ops.windows",
                                      fromlist=["StepRange"]).StepRange(
                               g["start_ms"], g["end_ms"], STEP),
                           np.array([[1.0, np.nan, 3.0] +
                                     [np.nan] * 58]))
        b2 = PeriodicBatch([{"a": "1"}], b1.steps,
                           np.array([[np.nan, 2.0, np.nan] + [4.0] * 58]))
        out = StitchRvsMapper().apply([b1, b2], ctx)
        np.testing.assert_allclose(out[0].np_values()[0][:4],
                                   [1.0, 2.0, 3.0, 4.0])


class TestMetadataExec:
    def test_part_keys_and_label_values(self, ctx):
        pk = PartKeysExec("ds", 0, [eq("_metric_", "heap_usage")], 0, MAX)
        res = pk.execute(ctx)
        assert len(res.batches[0]) == 6
        lv = LabelValuesExec("ds", 0, ["_ns_"], [], 0, MAX)
        res2 = lv.execute(ctx)
        assert "App-0" in res2.batches[0]["_ns_"]
        root = LabelValuesDistConcatExec([
            LabelValuesExec("ds", 0, ["instance"], [], 0, MAX),
            LabelValuesExec("ds", 1, ["instance"], [], 0, MAX)])
        res3 = root.execute(ctx)
        assert len(res3.batches[0]["instance"]) == 12

    def test_dist_concat(self, ctx):
        children = []
        for shard in (0, 1):
            p = leaf("heap_usage", shard)
            p.add_transformer(PeriodicSamplesMapper(**grid()))
            children.append(p)
        root = DistConcatExec(children)
        res = root.execute(ctx)
        assert sum(b.num_series for b in res.batches) == 12

    def test_print_tree(self, ctx):
        p = leaf("heap_usage")
        p.add_transformer(PeriodicSamplesMapper(**grid()))
        root = DistConcatExec([p])
        tree = root.print_tree()
        assert "DistConcatExec" in tree
        assert "MultiSchemaPartitionsExec" in tree
        assert "PeriodicSamplesMapper" in tree


class TestHistMaxSchema:
    """Histogram schema with a max column: the leaf pairs the hist kernel
    with the max plane (reference: histMaxRangeFunction — None ->
    LastSampleHistMax, sum_over_time -> SumAndMaxOverTime;
    SelectRawPartitionsExec.scala:52-63)."""

    @pytest.fixture(scope="class")
    def hm_store(self):
        from tests.data import hist_max_containers
        store = TimeSeriesMemStore()
        store.setup("hm", DEFAULT_SCHEMAS, 0)
        for off, c in enumerate(hist_max_containers(n_series=2,
                                                    n_samples=60)):
            store.ingest("hm", 0, c, off)
        return store

    def _raw(self, hm_store):
        sh = hm_store.get_shard("hm", 0)
        look = sh.lookup_partitions([eq("_metric_", "lat_hmax")], 0, MAX)
        out = {}
        for pid in look.part_ids:
            p = sh.partitions[int(pid)]
            ts, (buckets, rows) = p.read_range(0, MAX, 4)
            _, mx = p.read_range(0, MAX, 3)
            out[p.tags["instance"]] = (np.asarray(ts), np.asarray(rows),
                                       np.asarray(mx))
        return out

    def test_sum_over_time_pairs_hist_and_max(self, hm_store):
        raw = self._raw(hm_store)
        start, end, w = START_TS + 300_000, START_TS + 590_000, 300_000
        leaf = MultiSchemaPartitionsExec("hm", 0, [eq("_metric_", "lat_hmax")],
                                         start - w, end)
        leaf.add_transformer(PeriodicSamplesMapper(
            start, STEP, end, window_ms=w,
            function=RangeFunctionId.SUM_OVER_TIME))
        res = leaf.execute(ExecContext(hm_store))
        (b,) = res.batches
        assert b.hist is not None
        steps = np.asarray(b.steps.timestamps())
        for i, tags in enumerate(b.keys):
            ts, rows, mx = raw[tags["instance"]]
            for j, t in enumerate(steps):
                m = (ts > t - w) & (ts <= t)
                np.testing.assert_allclose(np.asarray(b.hist)[i, j],
                                           rows[m].sum(axis=0), rtol=1e-6)
                # values plane = max_over_time of the max column
                assert np.asarray(b.values)[i, j] == mx[m].max()

    def test_instant_selector_pairs_last_hist_and_last_max(self, hm_store):
        raw = self._raw(hm_store)
        start = end = START_TS + 590_000
        leaf = MultiSchemaPartitionsExec("hm", 0, [eq("_metric_", "lat_hmax")],
                                         start - 300_000, end)
        leaf.add_transformer(PeriodicSamplesMapper(start, STEP, end))
        res = leaf.execute(ExecContext(hm_store))
        (b,) = res.batches
        for i, tags in enumerate(b.keys):
            ts, rows, mx = raw[tags["instance"]]
            sel = ts <= start
            np.testing.assert_allclose(np.asarray(b.hist)[i, 0],
                                       rows[sel][-1], rtol=1e-6)
            assert np.asarray(b.values)[i, 0] == mx[sel][-1]

    def test_histogram_max_quantile_end_to_end(self, hm_store):
        from filodb_tpu.ops import histogram_ops
        import jax.numpy as jnp
        start, end, w = START_TS + 300_000, START_TS + 590_000, 300_000
        leaf = MultiSchemaPartitionsExec("hm", 0, [eq("_metric_", "lat_hmax")],
                                         start - w, end)
        leaf.add_transformer(PeriodicSamplesMapper(
            start, STEP, end, window_ms=w,
            function=RangeFunctionId.SUM_OVER_TIME))
        leaf.add_transformer(InstantVectorFunctionMapper(
            InstantFunctionId.HISTOGRAM_MAX_QUANTILE, (0.9,)))
        res = leaf.execute(ExecContext(hm_store))
        (b,) = res.batches
        got = np.asarray(b.values)
        assert np.isfinite(got).all()
        # oracle: hist_max_quantile over the paired planes
        leaf2 = MultiSchemaPartitionsExec("hm", 0,
                                          [eq("_metric_", "lat_hmax")],
                                          start - w, end)
        leaf2.add_transformer(PeriodicSamplesMapper(
            start, STEP, end, window_ms=w,
            function=RangeFunctionId.SUM_OVER_TIME))
        (b2,) = leaf2.execute(ExecContext(hm_store)).batches
        want = np.asarray(histogram_ops.hist_max_quantile(
            jnp.asarray(b2.bucket_tops), jnp.asarray(b2.hist),
            jnp.asarray(b2.values), 0.9))
        np.testing.assert_allclose(got, want, rtol=1e-6)


class _Probe(MultiSchemaPartitionsExec):
    """A leaf that answers with its own number after a short sleep, and
    counts how many of its kind run at once."""

    running = 0
    peak = 0
    lock = threading.Lock()

    def __init__(self, i, fail=False):
        super().__init__("ds", 0, [], 0, 0)
        self.i, self.fail = i, fail

    def execute(self, ctx):
        import time
        from filodb_tpu.query.model import QueryResult, QueryStats
        cls = _Probe
        with cls.lock:
            cls.running += 1
            cls.peak = max(cls.peak, cls.running)
        try:
            time.sleep(0.01)
            if self.fail:
                raise QueryError("t1", f"child {self.i} failed")
            return QueryResult("t1", [self.i], QueryStats())
        finally:
            with cls.lock:
                cls.running -= 1


class _Gather(DistConcatExec):
    def compose(self, results, ctx):
        return [b for r in results for b in r.batches]


class TestFanOut:
    """ISSUE 29: children run on this thread and on helpers of one
    shared pool, never on a pool made for the request."""

    @pytest.fixture(autouse=True)
    def _reset(self):
        _Probe.running = _Probe.peak = 0

    def test_results_keep_the_children_order_under_the_cap(self, ms):
        ctx = ExecContext(ms, QueryContext(query_id="t1"), parallelism=3)
        got = _Gather([_Probe(i) for i in range(20)]).execute(ctx).batches
        assert got == list(range(20))
        assert 2 <= _Probe.peak <= 3

    def test_one_child_and_serial_plans_use_no_helper(self, ms, monkeypatch):
        import filodb_tpu.query.exec as qe
        monkeypatch.setattr(qe, "_fanout_pool", lambda: pytest.fail(
            "a one-child or serial plan asked for the pool"))
        ctx = ExecContext(ms, QueryContext(query_id="t1"))
        assert _Gather([_Probe(7)]).execute(ctx).batches == [7]
        serial = _Gather([_Probe(i) for i in range(4)])
        serial.parallel_children = False
        assert serial.execute(ctx).batches == [0, 1, 2, 3]
        assert _Probe.peak == 1

    def test_a_failing_child_fails_the_plan_and_stops_the_rest(self, ms):
        ctx = ExecContext(ms, QueryContext(query_id="t1"), parallelism=2)
        kids = [_Probe(i, fail=(i == 1)) for i in range(40)]
        with pytest.raises(QueryError, match="child 1 failed"):
            _Gather(kids).execute(ctx)
        assert _Probe.running == 0

    def test_nested_plans_finish_on_a_pool_that_is_full(self, ms,
                                                        monkeypatch):
        """Sixteen queries at once, each a plan of plans, over a pool
        of two helpers: a helper that never starts costs concurrency
        only (the caller drains the queue itself), so nothing waits
        for a thread that waits for it."""
        import concurrent.futures
        import sys
        import filodb_tpu.query.exec as qe
        small = concurrent.futures.ThreadPoolExecutor(2)
        monkeypatch.setattr(qe, "_fanout_pool", lambda: small)
        out, errs = {}, []

        def query(q):
            try:
                ctx = ExecContext(ms, QueryContext(query_id="t1"))
                root = _Gather([_Gather([_Probe(10 * j + i)
                                         for i in range(4)])
                                for j in range(4)])
                out[q] = root.execute(ctx).batches
            except BaseException as e:  # noqa: BLE001 — asserted below
                errs.append(e)

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-4)
        try:
            ts = [threading.Thread(target=query, args=(q,))
                  for q in range(16)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in ts)
        finally:
            sys.setswitchinterval(old)
            small.shutdown(wait=True)
        assert not errs, errs
        want = [10 * j + i for j in range(4) for i in range(4)]
        assert all(out[q] == want for q in range(16))
