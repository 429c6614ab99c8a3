"""Resident grid x mesh serving (parallel/meshgrid.py): the SPMD program
over per-shard HBM-resident plans must be observably identical to the
per-shard scatter-gather path, must actually TAKE the resident path, and
must move zero bytes host->device on a repeat query (reference semantics:
BlockManager.scala:142 resident serving x SingleClusterPlanner.scala:
223-258 scatter-gather).

Runs on the 8-device virtual CPU mesh from tests/conftest.py.
"""

import numpy as np
import pytest

import jax

from filodb_tpu.coordinator.planner import SingleClusterPlanner
from filodb_tpu.core.record import RecordBuilder, partition_hash, \
    shard_key_hash
from filodb_tpu.core.schemas import DEFAULT_SCHEMAS, DatasetOptions
from filodb_tpu.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.parallel import meshgrid
from filodb_tpu.parallel.mesh import MeshEngine, make_mesh
from filodb_tpu.parallel.shardmap import ShardMapper
from filodb_tpu.promql.parser import query_range_to_logical_plan
from filodb_tpu.query.exec import ExecContext
from filodb_tpu.query.model import QueryContext

BASE = 1_700_000_000_000
NUM_SHARDS = 4
N_SERIES = 24
N_ROWS = 120
STEP = 10_000


def _load(num_shards=NUM_SHARDS, n_series=N_SERIES, jitter_shards=(),
          seed=11, metric="mm"):
    """Regular 10s cadence (grid-eligible, uniform phase).  Shards in
    ``jitter_shards`` get per-sample in-bucket jitter: still dense and
    one-sample-per-bucket, but NOT uniform-phase — the dense/phase MEET
    path."""
    ms = TimeSeriesMemStore()
    opts = DatasetOptions()
    mapper = ShardMapper(num_shards)
    for s in range(num_shards):
        ms.setup("prom", DEFAULT_SCHEMAS, s)
    rng = np.random.default_rng(seed)
    for i in range(n_series):
        tags = {"_metric_": metric, "inst": f"i{i}", "grp": f"g{i % 3}",
                "_ws_": "w", "_ns_": "n"}
        shard = mapper.ingestion_shard(shard_key_hash(tags, opts),
                                       partition_hash(tags, opts),
                                       2) % num_shards
        b = RecordBuilder(DEFAULT_SCHEMAS["gauge"], opts,
                          container_size=1 << 20)
        ts = BASE + np.arange(N_ROWS) * STEP
        if shard in jitter_shards:
            ts = ts + rng.integers(1, STEP - 1, size=N_ROWS)
        vals = np.cumsum(rng.random(N_ROWS))
        b.add_series(ts.tolist(), [vals.tolist()], tags)
        for off, c in enumerate(b.containers()):
            ms.get_shard("prom", shard).ingest_container(c, off)
    return ms, mapper


def _planner(mapper, engine=None):
    provider = (lambda: engine) if engine is not None else None
    return SingleClusterPlanner("prom", mapper, DatasetOptions(),
                                spread_default=2,
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


def _assert_equiv(fused, plain):
    assert set(fused) == set(plain) and plain
    for k in plain:
        np.testing.assert_array_equal(fused[k][0], plain[k][0])
        np.testing.assert_allclose(fused[k][1], plain[k][1],
                                   rtol=1e-6, atol=1e-9,
                                   equal_nan=True, err_msg=str(k))


START = BASE + 300_000
END = BASE + 900_000

QUERIES = [
    'sum(rate(mm{_ws_="w",_ns_="n"}[2m]))',
    'sum by (grp)(rate(mm{_ws_="w",_ns_="n"}[2m]))',
    'count(mm{_ws_="w",_ns_="n"})',
    'avg by (grp)(sum_over_time(mm{_ws_="w",_ns_="n"}[1m]))',
    'max(rate(mm{_ws_="w",_ns_="n"}[2m]))',
    'min by (grp)(mm{_ws_="w",_ns_="n"})',
    'sum by (grp)(increase(mm{_ws_="w",_ns_="n"}[2m]))',
    # round 5 (VERDICT r4 #2): the non-distributive moment family
    'stddev by (grp)(rate(mm{_ws_="w",_ns_="n"}[2m]))',
    'stdvar(mm{_ws_="w",_ns_="n"})',
    'group by (grp)(mm{_ws_="w",_ns_="n"})',
]

# k-slot / member ops: exact equivalence (k-heap merge and value counts
# are lossless); quantile (exact up to exact_members) is tested separately
K_MEMBER_QUERIES = [
    'topk(3, rate(mm{_ws_="w",_ns_="n"}[2m]))',
    'bottomk(2, mm{_ws_="w",_ns_="n"})',
    'topk by (grp)(2, mm{_ws_="w",_ns_="n"})',
    'count_values("v", mm{_ws_="w",_ns_="n"})',
    'count_values by (grp)("v", mm{_ws_="w",_ns_="n"})',
]

# one representative per family for the zero-upload repeat contract
REPEAT_QUERIES = [
    'sum by (grp)(rate(mm{_ws_="w",_ns_="n"}[2m]))',
    'stddev by (grp)(mm{_ws_="w",_ns_="n"})',
    'topk(3, rate(mm{_ws_="w",_ns_="n"}[2m]))',
    'quantile(0.9, mm{_ws_="w",_ns_="n"})',
    'count_values("v", mm{_ws_="w",_ns_="n"})',
]


class TestResidentGridMesh:
    @pytest.mark.parametrize("promql", QUERIES)
    def test_equivalent_and_resident(self, promql):
        ms, mapper = _load()
        engine = MeshEngine(make_mesh())
        plain = _run(_planner(mapper), ms, promql, START, END)
        before = dict(meshgrid.STATS)
        fused = _run(_planner(mapper, engine), ms, promql, START, END)
        _assert_equiv(fused, plain)
        assert meshgrid.STATS["serves"] > before["serves"], \
            "resident grid-mesh path was not taken"

    @pytest.mark.parametrize("promql", K_MEMBER_QUERIES)
    def test_k_member_ops_equivalent_and_resident(self, promql):
        """topk/bottomk/count_values over resident lanes: lossless, so
        exact equivalence with the per-shard path — and the resident
        program must actually run."""
        ms, mapper = _load()
        engine = MeshEngine(make_mesh())
        plain = _run(_planner(mapper), ms, promql, START, END)
        before = dict(meshgrid.STATS)
        fused = _run(_planner(mapper, engine), ms, promql, START, END)
        _assert_equiv(fused, plain)
        assert meshgrid.STATS["serves"] > before["serves"], \
            "resident grid-mesh path was not taken"

    def test_quantile_resident_close_to_exact(self):
        """quantile over resident lanes is exact up to
        ``exact_members`` a group, as the per-shard path is at this
        cardinality (PR 34: the members gathered on each device, a
        t-digest sketch only past it) — the same answer, same keys,
        same NaN shape, resident program taken."""
        ms, mapper = _load()
        engine = MeshEngine(make_mesh())
        for promql in ('quantile(0.9, mm{_ws_="w",_ns_="n"})',
                       'quantile by (grp)(0.5, rate(mm{_ws_="w",'
                       '_ns_="n"}[2m]))'):
            plain = _run(_planner(mapper), ms, promql, START, END)
            before = dict(meshgrid.STATS)
            fused = _run(_planner(mapper, engine), ms, promql, START, END)
            assert meshgrid.STATS["serves"] > before["serves"], promql
            assert set(fused) == set(plain) and plain, promql
            for k in plain:
                pv, fv = plain[k][1], fused[k][1]
                assert (np.isfinite(pv) == np.isfinite(fv)).all(), k
                fin = np.isfinite(pv)
                np.testing.assert_allclose(fv[fin], pv[fin], rtol=1e-12,
                                           err_msg=f"{promql} {k}")

    @pytest.mark.parametrize("promql", REPEAT_QUERIES)
    def test_repeat_query_zero_host_upload(self, monkeypatch, promql):
        """The dashboard-refresh contract for EVERY aggregator family: a
        repeat query hits the assembly memo and performs NO host->device
        transfer at all."""
        ms, mapper = _load()
        engine = MeshEngine(make_mesh())
        planner = _planner(mapper, engine)
        first = _run(planner, ms, promql, START, END)
        before = dict(meshgrid.STATS)
        uploads = []
        real_put = jax.device_put

        def spy(x, *a, **kw):
            if isinstance(x, np.ndarray):
                uploads.append(x.nbytes)
            return real_put(x, *a, **kw)

        monkeypatch.setattr(jax, "device_put", spy)
        second = _run(planner, ms, promql, START, END)
        monkeypatch.undo()
        assert meshgrid.STATS["memo_hits"] > before["memo_hits"], \
            "repeat query re-assembled the mesh inputs"
        assert meshgrid.STATS["serves"] > before["serves"]
        assert uploads == [], \
            f"repeat query uploaded {sum(uploads)} bytes host->device"
        if "quantile" in promql:
            assert set(second) == set(first)
        else:
            _assert_equiv(second, first)

    def test_op_switch_reuses_assembly(self):
        """The assembled residents are op-independent: a dashboard
        switching sum -> topk -> stddev on the same selector re-uses the
        assembly (memo hit), compiling only the new program."""
        ms, mapper = _load()
        engine = MeshEngine(make_mesh())
        planner = _planner(mapper, engine)
        _run(planner, ms, QUERIES[1], START, END)
        before = dict(meshgrid.STATS)
        # same selector, same grouping (the garr layout is part of the
        # assembly): only the aggregator program changes
        _run(planner, ms, 'topk by (grp)(2, rate(mm{_ws_="w",_ns_="n"}'
                          '[2m]))', START, END)
        _run(planner, ms, 'stddev by (grp)(rate(mm{_ws_="w",_ns_="n"}'
                          '[2m]))', START, END)
        assert meshgrid.STATS["assembles"] == before["assembles"], \
            "op switch re-assembled the residents"
        assert meshgrid.STATS["memo_hits"] >= before["memo_hits"] + 2

    def test_filler_slices_shards_not_multiple_of_devices(self):
        """4 shards over the 8-device mesh: 4 filler slices must not
        perturb results (NaN lanes drop into the spare bucket)."""
        assert len(jax.devices()) == 8
        ms, mapper = _load(num_shards=4)
        engine = MeshEngine(make_mesh())
        plain = _run(_planner(mapper), ms, QUERIES[0], START, END)
        before = meshgrid.STATS["serves"]
        fused = _run(_planner(mapper, engine), ms, QUERIES[0], START, END)
        assert meshgrid.STATS["serves"] > before
        _assert_equiv(fused, plain)

    def test_multiple_plans_per_device(self):
        """A 2-device mesh with 4+ shards: ksub > 1 exercises the local
        accumulation loop and uneven per-device slice counts."""
        engine = MeshEngine(make_mesh(jax.devices()[:2]))
        ms, mapper = _load(num_shards=8, n_series=40)
        plain = _run(_planner(mapper), ms, QUERIES[1], START, END)
        before = meshgrid.STATS["serves"]
        fused = _run(_planner(mapper, engine), ms, QUERIES[1], START, END)
        assert meshgrid.STATS["serves"] > before
        _assert_equiv(fused, plain)

    def test_mixed_dense_phase_meet(self):
        """One shard uniform-phase, others jittered: the program must
        MEET to ts mode and stay correct."""
        ms, mapper = _load(jitter_shards=(1, 2))
        engine = MeshEngine(make_mesh())
        for promql in (QUERIES[0], QUERIES[6]):
            plain = _run(_planner(mapper), ms, promql, START, END)
            before = meshgrid.STATS["serves"]
            fused = _run(_planner(mapper, engine), ms, promql, START, END)
            assert meshgrid.STATS["serves"] > before
            _assert_equiv(fused, plain)

    def test_grid_ineligible_shard_falls_back_per_shard(self):
        """A shard whose cadence defeats the grid (two samples per
        bucket) must be served by the host-batch mesh path while the
        others stay resident — results identical, nothing dropped."""
        ms, mapper = _load()
        # shard 0: extra series at 5s cadence -> two samples per 10s
        # bucket -> grid disabled for that shard
        opts = DatasetOptions()
        tags = {"_metric_": "mm", "inst": "odd", "grp": "g0",
                "_ws_": "w", "_ns_": "n"}
        b = RecordBuilder(DEFAULT_SCHEMAS["gauge"], opts,
                          container_size=1 << 20)
        ts = BASE + np.arange(2 * N_ROWS) * (STEP // 2)
        b.add_series(ts.tolist(), [np.cumsum(
            np.ones(2 * N_ROWS)).tolist()], tags)
        for off, c in enumerate(b.containers()):
            ms.get_shard("prom", 0).ingest_container(c, off)
        engine = MeshEngine(make_mesh())
        plain = _run(_planner(mapper), ms, QUERIES[0], START, END)
        fused = _run(_planner(mapper, engine), ms, QUERIES[0], START, END)
        _assert_equiv(fused, plain)

    def test_unsupported_layout_still_correct(self):
        """An op whose layout defeats the resident composition (stddev
        over per-sample-jittered shards MEETs to ts mode; a shard with
        two samples per bucket defeats the grid entirely) must still be
        served correctly via fallback."""
        ms, mapper = _load(jitter_shards=(0, 1, 2, 3))
        engine = MeshEngine(make_mesh())
        promql = 'stddev(mm{_ws_="w",_ns_="n"})'
        plain = _run(_planner(mapper), ms, promql, START, END)
        fused = _run(_planner(mapper, engine), ms, promql, START, END)
        _assert_equiv(fused, plain)

    def test_histogram_shards_serve_resident(self):
        """First-class histogram sums run in the RESIDENT grid x mesh
        program (bucket lanes + psum over group*bucket slots),
        identical to the per-shard path."""
        from tests.data import START_TS, histogram_containers

        ms2 = TimeSeriesMemStore()
        mapper = ShardMapper(4)
        for s in range(4):
            ms2.setup("prom", DEFAULT_SCHEMAS, s)
        for shard_num in (0, 1, 2):
            for off, c in enumerate(histogram_containers(
                    n_series=2, n_samples=60, metric="hgm",
                    seed=shard_num)):
                ms2.get_shard("prom", shard_num).ingest_container(c, off)
        engine = MeshEngine(make_mesh())
        # start past the bare selector's 5m staleness lookback so the
        # resident plan's first window lands inside the staged grid
        start, end = START_TS + 320_000, START_TS + 500_000
        for promql in ('sum(rate(hgm{_ws_="demo",_ns_="App-0"}[2m]))',
                       'sum(hgm{_ws_="demo",_ns_="App-0"})'):
            plain = _run(_planner(mapper), ms2, promql, start, end)
            before = meshgrid.STATS["serves"]
            fused = _run(_planner(mapper, engine), ms2, promql,
                         start, end)
            assert meshgrid.STATS["serves"] > before, \
                f"hist query fell off the resident path: {promql}"
            _assert_equiv(fused, plain)

    def test_repin_invalidates_and_rebuilds(self):
        """Blocks built for a single-device planner (default device)
        survive pinning to device 0 but rebuild when re-pinned
        elsewhere; results stay identical throughout."""
        ms, mapper = _load(num_shards=2)
        plain = _run(_planner(mapper), ms, QUERIES[0], START, END)
        shard = ms.get_shard("prom", 0)
        shard.pin_grid_device(jax.devices()[3])
        engine = MeshEngine(make_mesh())
        fused = _run(_planner(mapper, engine), ms, QUERIES[0], START, END)
        _assert_equiv(fused, plain)


class TestCompressedResidentMesh:
    """ISSUE 3: the mesh path over COMPRESSED residents — blocks stay
    packed in HBM, uniform-phase plans never stage a ts plane, and the
    dashboard-refresh contract (memo hit, zero host decode, zero
    re-upload, zero block rebuilds) holds for the compressed form."""

    def _load_counters(self, num_shards=NUM_SHARDS, n_series=N_SERIES):
        """Integer-valued counters (XOR-compressible) on an exact 10s
        cadence with a per-series constant phase — compresses AND proves
        uniform-phase, so plans take the no-ts-plane mesh form."""
        ms = TimeSeriesMemStore()
        opts = DatasetOptions()
        mapper = ShardMapper(num_shards)
        for s in range(num_shards):
            ms.setup("prom", DEFAULT_SCHEMAS, s)
        rng = np.random.default_rng(23)
        for i in range(n_series):
            tags = {"_metric_": "cc", "inst": f"i{i}",
                    "grp": f"g{i % 3}", "_ws_": "w", "_ns_": "n"}
            shard = mapper.ingestion_shard(shard_key_hash(tags, opts),
                                           partition_hash(tags, opts),
                                           2) % num_shards
            b = RecordBuilder(DEFAULT_SCHEMAS["gauge"], opts,
                              container_size=1 << 20)
            ph = int(rng.integers(1, STEP))
            ts = BASE + np.arange(N_ROWS) * STEP - STEP + ph
            vals = (1_000_000
                    + np.cumsum(rng.integers(-500, 500, N_ROWS))
                    ).astype(np.float64)
            b.add_series(ts.tolist(), [vals.tolist()], tags)
            for off, c in enumerate(b.containers()):
                ms.get_shard("prom", shard).ingest_container(c, off)
        for s in range(num_shards):
            ms.get_shard("prom", s).flush_all()
        return ms, mapper

    def test_compressed_resident_repeat_memo_and_zero_rebuild(
            self, monkeypatch):
        ms, mapper = self._load_counters()
        engine = MeshEngine(make_mesh())
        planner = _planner(mapper, engine)
        promql = 'sum by (grp)(rate(cc{_ws_="w",_ns_="n"}[2m]))'
        plain = _run(_planner(mapper), ms, promql, START, END)
        first = _run(planner, ms, promql, START, END)
        _assert_equiv(first, plain)
        # the residents must actually BE compressed and uniform-phase
        comp_blocks = ts_elided = 0
        builds = 0
        for s in range(NUM_SHARDS):
            shard = ms.get_shard("prom", s)
            for cache in shard.device_caches.values():
                builds += cache.builds
                for blk in cache.blocks.values():
                    comp_blocks += isinstance(blk.vals, dict)
                    ts_elided += blk.ts is None
        assert comp_blocks > 0, "counter data did not pack"
        assert ts_elided > 0, "uniform-phase ts plane was not elided"
        before = dict(meshgrid.STATS)
        uploads = []
        real_put = jax.device_put

        def spy(x, *a, **kw):
            if isinstance(x, np.ndarray):
                uploads.append(x.nbytes)
            return real_put(x, *a, **kw)

        monkeypatch.setattr(jax, "device_put", spy)
        second = _run(planner, ms, promql, START, END)
        monkeypatch.undo()
        _assert_equiv(second, first)
        # repeat query: assembly memo hit, no host decode (no rebuild),
        # no re-upload — the compressed analog of the dense contract
        assert meshgrid.STATS["memo_hits"] > before["memo_hits"], \
            "repeat compressed query re-assembled the mesh inputs"
        assert uploads == [], \
            f"repeat compressed query uploaded {sum(uploads)} bytes"
        builds2 = sum(c.builds for s in range(NUM_SHARDS)
                      for c in ms.get_shard("prom", s)
                      .device_caches.values())
        assert builds2 == builds, "repeat query re-decoded host chunks"

    def test_phase_plans_stage_no_ts_plane(self):
        """Uniform-phase mesh plans carry ts=None — the staged resident
        is the value plane only (half the HBM of the ts-streaming
        form), and the SPMD program ships a 1-row dummy instead."""
        ms, mapper = self._load_counters()
        devices = list(make_mesh().devices.flat)
        plans = []
        for s in range(NUM_SHARDS):
            shard = ms.get_shard("prom", s)
            shard.pin_grid_device(devices[s % len(devices)])
            res = shard.lookup_partitions([], 0, 2**62)
            ids = res.part_ids
            if len(ids) == 0:
                continue
            from filodb_tpu.query.logical import RangeFunctionId as F
            plan = shard.mesh_grid_plan(
                ids, F.RATE, BASE + 300_000, 10, 30_000, 120_000,
                list(range(len(ids))))
            if plan is not None:
                plans.append(plan)
        assert plans, "no shard produced a mesh plan"
        for p in plans:
            assert p.phase is not None
            assert p.ts is None, "phase-mode plan staged a ts plane"
            assert p.vals.shape[0] > 0

    def test_compressed_hist_blocks_serve_through_mesh(self):
        """ISSUE 14: histogram bucket planes stay PACKED at rest and the
        grid x mesh path stages (decodes) them on device — the served
        answer is identical to the per-shard scatter-gather path."""
        from filodb_tpu.codecs import histcodec
        from filodb_tpu.core.histogram import GeometricBuckets

        hb = 8
        ms = TimeSeriesMemStore()
        opts = DatasetOptions()
        mapper = ShardMapper(4)
        for s in range(4):
            ms.setup("prom", DEFAULT_SCHEMAS, s)
        rng = np.random.default_rng(29)
        buckets = GeometricBuckets(2.0, 2.0, hb)
        for i in range(12):
            tags = {"_metric_": "hcc", "inst": f"i{i}",
                    "_ws_": "w", "_ns_": "n"}
            shard = mapper.ingestion_shard(shard_key_hash(tags, opts),
                                           partition_hash(tags, opts),
                                           2) % 4
            b = RecordBuilder(DEFAULT_SCHEMAS["prom-histogram"], opts,
                              container_size=1 << 20)
            ph = int(rng.integers(1, STEP))
            cum = np.zeros(hb, np.int64)
            for t in range(N_ROWS):
                cum += 128 * rng.integers(1, 8, hb)
                vals = 2 ** 23 + np.cumsum(cum)
                blob = histcodec.encode_hist_value(buckets, vals)
                b.add(int(BASE + t * STEP - STEP + ph),
                      (float(vals[-1]), float(vals[-1]), blob), tags)
            for off, c in enumerate(b.containers()):
                ms.get_shard("prom", shard).ingest_container(c, off)
        for s in range(4):
            ms.get_shard("prom", s).flush_all()
        engine = MeshEngine(make_mesh())
        promql = 'sum(rate(hcc{_ws_="w",_ns_="n"}[2m]))'
        plain = _run(_planner(mapper), ms, promql, START, END)
        before = meshgrid.STATS["serves"]
        fused = _run(_planner(mapper, engine), ms, promql, START, END)
        assert meshgrid.STATS["serves"] > before, \
            "compressed hist query fell off the resident mesh path"
        _assert_equiv(fused, plain)
        comp = sum(isinstance(blk.vals, dict)
                   for s in range(4)
                   for cache in ms.get_shard("prom", s)
                   .device_caches.values()
                   for blk in cache.blocks.values())
        assert comp > 0, "hist bucket planes did not pack at rest"
