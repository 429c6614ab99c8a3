"""MeshAggregateExec: the fused ICI-collective serving path must be
observably identical to the per-shard scatter-gather path (reference
semantics: SingleClusterPlanner.scala:223-258 reduce tree == one psum).

Runs on the 8-device virtual CPU mesh from tests/conftest.py.
"""

import numpy as np
import pytest

from filodb_tpu.coordinator.planner import SingleClusterPlanner
from filodb_tpu.core.record import RecordBuilder, partition_hash, \
    shard_key_hash
from filodb_tpu.core.schemas import DEFAULT_SCHEMAS, DatasetOptions
from filodb_tpu.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.parallel.mesh import MeshEngine, make_mesh
from filodb_tpu.parallel.shardmap import ShardMapper
from filodb_tpu.promql.parser import query_range_to_logical_plan
from filodb_tpu.query.exec import ExecContext, IN_PROCESS
from filodb_tpu.query.model import QueryContext

BASE = 1_700_000_000_000
NUM_SHARDS = 4
N_SERIES = 24
N_ROWS = 120
STEP = 10_000


@pytest.fixture(scope="module")
def loaded():
    ms = TimeSeriesMemStore()
    opts = DatasetOptions()
    mapper = ShardMapper(NUM_SHARDS)
    for s in range(NUM_SHARDS):
        ms.setup("prom", DEFAULT_SCHEMAS, s)
    rng = np.random.default_rng(11)
    for i in range(N_SERIES):
        tags = {"_metric_": "mm", "inst": f"i{i}", "grp": f"g{i % 3}",
                "_ws_": "w", "_ns_": "n"}
        shard = mapper.ingestion_shard(shard_key_hash(tags, opts),
                                       partition_hash(tags, opts),
                                       2) % NUM_SHARDS
        b = RecordBuilder(DEFAULT_SCHEMAS["gauge"], opts,
                          container_size=1 << 20)
        ts = BASE + np.arange(N_ROWS) * STEP
        vals = np.cumsum(rng.random(N_ROWS))
        b.add_series(ts.tolist(), [vals.tolist()], tags)
        for off, c in enumerate(b.containers()):
            ms.get_shard("prom", shard).ingest_container(c, off)
    return ms, mapper


def _planner(mapper, mesh=False, dispatcher_for_shard=None):
    provider = None
    if mesh:
        engine = MeshEngine(make_mesh())
        provider = lambda: engine  # noqa: E731
    return SingleClusterPlanner("prom", mapper, DatasetOptions(),
                                spread_default=2,
                                dispatcher_for_shard=dispatcher_for_shard,
                                mesh_engine_provider=provider)


def _run(planner, ms, promql, start, end, step=30_000):
    plan = query_range_to_logical_plan(promql, start, step, end)
    ep = planner.materialize(plan, QueryContext())
    result = ep.execute(ExecContext(ms, QueryContext()))
    out = {}
    for b in result.batches:
        for tags, ts, vals in b.to_series():
            out[tuple(sorted(tags.items()))] = (np.asarray(ts),
                                                np.asarray(vals))
    return out


QUERIES = [
    'sum(rate(mm{_ws_="w",_ns_="n"}[2m]))',
    'count(mm{_ws_="w",_ns_="n"})',
    'avg by (grp)(mm{_ws_="w",_ns_="n"})',
    'max(rate(mm{_ws_="w",_ns_="n"}[2m]))',
    'min by (grp)(mm{_ws_="w",_ns_="n"})',
    'stddev(mm{_ws_="w",_ns_="n"})',
    'sum by (grp)(increase(mm{_ws_="w",_ns_="n"}[2m]))',
    'group(mm{_ws_="w",_ns_="n"})',
    'group by (grp)(mm{_ws_="w",_ns_="n"})',
]

# the non-psum RowAggregator family: k-heap merge, member pass-through
FAMILY_QUERIES = [
    'topk(2, rate(mm{_ws_="w",_ns_="n"}[2m]))',
    'topk(3, mm{_ws_="w",_ns_="n"})',
    'bottomk(2, mm{_ws_="w",_ns_="n"})',
    'topk by (grp) (2, mm{_ws_="w",_ns_="n"})',
    'count_values("v", mm{_ws_="w",_ns_="n"})',
    'count_values by (grp) ("v", mm{_ws_="w",_ns_="n"})',
]


class TestMeshPathEquivalence:
    @pytest.mark.parametrize("promql", QUERIES)
    def test_matches_per_shard_path(self, loaded, promql):
        ms, mapper = loaded
        start = BASE + 300_000
        end = BASE + 900_000
        plain = _run(_planner(mapper), ms, promql, start, end)
        fused = _run(_planner(mapper, mesh=True), ms, promql, start, end)
        assert set(fused) == set(plain)
        for k in plain:
            np.testing.assert_array_equal(fused[k][0], plain[k][0])
            np.testing.assert_allclose(fused[k][1], plain[k][1],
                                       rtol=1e-9, atol=1e-9,
                                       equal_nan=True, err_msg=str(k))

    def test_plan_shape_uses_mesh_node(self, loaded):
        ms, mapper = loaded
        planner = _planner(mapper, mesh=True)
        plan = query_range_to_logical_plan(
            'sum(rate(mm{_ws_="w",_ns_="n"}[2m]))',
            BASE + 300_000, 30_000, BASE + 900_000)
        tree = planner.materialize(plan, QueryContext()).print_tree()
        # all shards mesh-resident here => the fused ROOT (ISSUE 18)
        assert "MeshReduceExec" in tree
        assert "MultiSchemaPartitionsExec" not in tree  # all shards local

    @pytest.mark.parametrize("promql", [
        QUERIES[0],                           # sum(rate(...))
        'count(mm{_ws_="w",_ns_="n"})',       # COUNT exports only "count"
        'stddev(mm{_ws_="w",_ns_="n"})',
        'max by (grp)(mm{_ws_="w",_ns_="n"})',
        'group(mm{_ws_="w",_ns_="n"})',
        # non-psum family: mesh partial must merge with the remote
        # shard's host-mapped partial (k-heap / member union)
        'topk by (grp) (2, mm{_ws_="w",_ns_="n"})',
        'count_values("v", mm{_ws_="w",_ns_="n"})',
    ])
    def test_mixed_local_remote(self, loaded, promql):
        """Shards behind a non-in-process dispatcher stay per-shard
        children; their partials merge with the mesh partial — the state
        keys must line up for every operator."""
        ms, mapper = loaded

        class LoopbackDispatcher:
            """Not IN_PROCESS identity-wise, but executes locally."""

            def dispatch(self, plan, ctx):
                return plan.execute(ctx)

        lb = LoopbackDispatcher()

        def disp(shard):
            return lb if shard == 3 else IN_PROCESS

        plain = _run(_planner(mapper), ms, promql,
                     BASE + 300_000, BASE + 900_000)
        mixed_planner = _planner(mapper, mesh=True,
                                 dispatcher_for_shard=disp)
        plan = query_range_to_logical_plan(
            promql, BASE + 300_000, 30_000, BASE + 900_000)
        ep = mixed_planner.materialize(plan, QueryContext())
        tree = ep.print_tree()
        assert "MeshAggregateExec" in tree
        assert "MultiSchemaPartitionsExec" in tree  # the remote shard
        result = ep.execute(ExecContext(ms, QueryContext()))
        out = {}
        for b in result.batches:
            for tags, ts, vals in b.to_series():
                out[tuple(sorted(tags.items()))] = np.asarray(vals)
        assert set(out) == set(plain)
        for k in plain:
            np.testing.assert_allclose(out[k], plain[k][1],
                                       rtol=1e-9, equal_nan=True)

    @pytest.mark.parametrize("promql", FAMILY_QUERIES)
    def test_family_matches_per_shard_path(self, loaded, promql):
        """topk/bottomk/count_values mesh partials must be observably
        identical to the per-shard path (k-heap merge / exact member
        pass-through are lossless)."""
        ms, mapper = loaded
        start = BASE + 300_000
        end = BASE + 900_000
        plain = _run(_planner(mapper), ms, promql, start, end)
        fused = _run(_planner(mapper, mesh=True), ms, promql, start, end)
        assert set(fused) == set(plain) and plain
        for k in plain:
            np.testing.assert_allclose(fused[k][1], plain[k][1],
                                       rtol=1e-9, atol=1e-12,
                                       equal_nan=True, err_msg=str(k))

    def test_family_plan_uses_mesh_node(self, loaded):
        ms, mapper = loaded
        planner = _planner(mapper, mesh=True)
        for promql in (FAMILY_QUERIES[0], FAMILY_QUERIES[4],
                       'quantile(0.9, mm{_ws_="w",_ns_="n"})'):
            plan = query_range_to_logical_plan(
                promql, BASE + 300_000, 30_000, BASE + 900_000)
            tree = planner.materialize(plan, QueryContext()).print_tree()
            assert "MeshReduceExec" in tree, promql

    def test_quantile_digest_close_to_exact(self, loaded):
        """Up to ``exact_members`` a group the mesh quantile partial is
        the members themselves, as the per-shard path's is at this
        cardinality (PR 34; a t-digest sketch past it, on both): the
        same answer, identical shape/keys."""
        ms, mapper = loaded
        start, end = BASE + 300_000, BASE + 900_000
        for promql in ('quantile(0.9, mm{_ws_="w",_ns_="n"})',
                       'quantile by (grp) (0.5, mm{_ws_="w",_ns_="n"})'):
            plain = _run(_planner(mapper), ms, promql, start, end)
            fused = _run(_planner(mapper, mesh=True), ms, promql,
                         start, end)
            assert set(fused) == set(plain) and plain, promql
            for k in plain:
                pv, fv = plain[k][1], fused[k][1]
                assert (np.isfinite(pv) == np.isfinite(fv)).all(), k
                fin = np.isfinite(pv)
                np.testing.assert_allclose(fv[fin], pv[fin], rtol=1e-12,
                                           err_msg=f"{promql} {k}")

    def test_histogram_served_in_mesh_program(self, loaded):
        """First-class histogram sum runs IN the mesh program (bucket
        lanes + psum), identical to the per-shard host path."""
        from tests.data import histogram_containers

        ms2 = TimeSeriesMemStore()
        mapper = ShardMapper(NUM_SHARDS)
        for s in range(NUM_SHARDS):
            ms2.setup("prom", DEFAULT_SCHEMAS, s)
        for shard_num in (0, 1, 2):
            for off, c in enumerate(histogram_containers(
                    n_series=2, n_samples=40, metric="hq",
                    seed=shard_num)):
                ms2.get_shard("prom", shard_num).ingest_container(c, off)
        from tests.data import START_TS
        start, end = START_TS + 200_000, START_TS + 390_000
        for promql in ('sum(rate(hq{_ws_="demo",_ns_="App-0"}[2m]))',
                       'sum(increase(hq{_ws_="demo",_ns_="App-0"}[2m]))',
                       'sum(hq{_ws_="demo",_ns_="App-0"})'):
            plain = _run(_planner(mapper), ms2, promql, start, end)
            fused = _run(_planner(mapper, mesh=True), ms2, promql,
                         start, end)
            assert set(fused) == set(plain) and plain, promql
            for k in plain:
                np.testing.assert_allclose(fused[k][1], plain[k][1],
                                           rtol=1e-6, equal_nan=True,
                                           err_msg=f"{promql} {k}")

    def test_parameterized_op_over_histogram_falls_back_with_params(self):
        """topk over a histogram metric can't run in the hist mesh
        program (SUM-only); the per-shard fallback must carry the
        aggregation params (k) instead of dropping them."""
        from tests.data import START_TS, histogram_containers

        ms2 = TimeSeriesMemStore()
        mapper = ShardMapper(NUM_SHARDS)
        for s in range(NUM_SHARDS):
            ms2.setup("prom", DEFAULT_SCHEMAS, s)
        for shard_num in (0, 1):
            for off, c in enumerate(histogram_containers(
                    n_series=2, n_samples=40, metric="hp",
                    seed=shard_num)):
                ms2.get_shard("prom", shard_num).ingest_container(c, off)
        promql = 'topk(1, sum_over_time(hp{_ws_="demo",_ns_="App-0"}[1m]))'
        start, end = START_TS + 200_000, START_TS + 390_000
        plain = _run(_planner(mapper), ms2, promql, start, end)
        fused = _run(_planner(mapper, mesh=True), ms2, promql, start, end)
        assert set(fused) == set(plain)

    def test_group_present_program(self, loaded):
        """window_aggregate (present=True) must present GROUP as
        1-where-live, consistent with the partials path."""
        from filodb_tpu.core.chunk import build_batch
        from filodb_tpu.ops.windows import StepRange
        from filodb_tpu.query.logical import AggregationOperator as Agg

        rng = np.random.default_rng(3)
        ts = [np.arange(30, dtype=np.int64) * 10_000 + 5_000
              for _ in range(4)]
        vs = [np.cumsum(rng.random(30)) for _ in range(4)]
        batches = [build_batch(ts[:2], vs[:2]), build_batch(ts[2:], vs[2:])]
        gids = [np.array([0, 1], np.int32), np.array([0, 1], np.int32)]
        engine = MeshEngine(make_mesh())
        out = engine.window_aggregate(
            batches, gids, num_groups=2,
            srange=StepRange(100_000, 280_000, 30_000),
            window_ms=300_000, range_fn=None, agg_op=Agg.GROUP)
        assert out.shape[0] == 2
        assert np.all(out[np.isfinite(out)] == 1.0)
        assert np.isfinite(out).any()

    def test_histogram_shards_fall_back_to_host_path(self, loaded):
        """The mesh program is scalar-only; shards holding histogram data
        must be served by the per-shard host path, never dropped."""
        from tests.data import histogram_containers

        ms2 = TimeSeriesMemStore()
        mapper = ShardMapper(NUM_SHARDS)
        for s in range(NUM_SHARDS):
            ms2.setup("prom", DEFAULT_SCHEMAS, s)
        # histogram series spread over 2+ shards
        for shard_num in (0, 1, 2):
            for off, c in enumerate(histogram_containers(
                    n_series=2, n_samples=40, metric="hq",
                    seed=shard_num)):
                ms2.get_shard("prom", shard_num).ingest_container(c, off)
        promql = 'sum(rate(hq{_ws_="demo",_ns_="App-0"}[2m]))'
        from tests.data import START_TS
        start, end = START_TS + 200_000, START_TS + 390_000
        plain = _run(_planner(mapper), ms2, promql, start, end)
        fused = _run(_planner(mapper, mesh=True), ms2, promql, start, end)
        assert set(fused) == set(plain) and plain, "hist data dropped"
        for k in plain:
            np.testing.assert_allclose(fused[k][1], plain[k][1],
                                       rtol=1e-6, equal_nan=True)

    def test_single_local_shard_stays_per_shard(self, loaded):
        ms, mapper = loaded
        planner = _planner(mapper, mesh=True)
        planner.spread_default = 0  # one shard per shard key
        plan = query_range_to_logical_plan(
            'sum(mm{_ws_="w",_ns_="n"})', BASE + 300_000, 30_000,
            BASE + 600_000)
        tree = planner.materialize(plan, QueryContext()).print_tree()
        assert "MeshAggregateExec" not in tree
