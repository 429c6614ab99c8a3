"""Disk persistence, checkpointed recovery, and on-demand paging.

Mirrors the reference's persistence/recovery test strategy (reference:
cassandra ColumnStoreSpec, TimeSeriesMemStoreSpec recovery cases,
OnDemandPagingShard paging) against the sqlite-backed stores.
"""

import numpy as np
import pytest

from filodb_tpu.core.chunk import ChunkSet, ChunkSetInfo, encode_chunkset
from filodb_tpu.core.filters import ColumnFilter, Equals
from filodb_tpu.core.record import RecordBuilder
from filodb_tpu.core.schemas import DEFAULT_SCHEMAS
from filodb_tpu.core.storeconfig import StoreConfig
from filodb_tpu.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.memstore.odp import OnDemandPagingShard, QueryLimitExceeded
from filodb_tpu.store.columnstore import PartKeyRecord
from filodb_tpu.store.persistence import (DiskColumnStore, DiskMetaStore,
                                          pack_vectors, unpack_vectors)

BASE = 1_700_000_000_000


@pytest.fixture
def disk(tmp_path):
    return DiskColumnStore(str(tmp_path / "chunks.db"))


@pytest.fixture
def meta(tmp_path):
    return DiskMetaStore(str(tmp_path / "meta.db"))


def _mk_chunkset(pk=b"pk1", n=100, t0=BASE, seed=0):
    rng = np.random.default_rng(seed)
    ts = t0 + np.cumsum(rng.integers(9_000, 11_000, n))
    vals = np.cumsum(rng.random(n))
    schema = DEFAULT_SCHEMAS["gauge"]
    return encode_chunkset(schema, pk, ts.astype(np.int64), [vals]), ts, vals


def _builder_data(n_series=6, n_rows=300, metric="heap_usage",
                  container_size=1024 * 1024):
    schema = DEFAULT_SCHEMAS["gauge"]
    builder = RecordBuilder(schema, container_size=container_size)
    rng = np.random.default_rng(1)
    truth = {}
    for s in range(n_series):
        tags = {"__name__": metric, "job": "app", "instance": f"i{s}",
                "_ws_": "demo", "_ns_": "ns"}
        ts = BASE + np.cumsum(rng.integers(9_000, 11_000, n_rows))
        vals = np.cumsum(rng.random(n_rows))
        truth[f"i{s}"] = (ts.astype(np.int64), vals.copy())
        for t, v in zip(ts, vals):
            builder.add(int(t), [float(v)], tags)
    return builder.containers(), truth


def test_vector_blob_roundtrip():
    vs = [b"", b"abc", b"\x00" * 100, bytes(range(256))]
    assert unpack_vectors(pack_vectors(vs)) == vs


class TestDiskColumnStore:
    def test_chunk_roundtrip(self, disk):
        cs, ts, vals = _mk_chunkset()
        disk.write_chunks("ds", 0, [cs], ingestion_time=123)
        got = list(disk.read_raw_partitions("ds", 0, [b"pk1"], 0, 2**62))
        assert len(got) == 1
        pk, chunks = got[0]
        assert pk == b"pk1"
        assert chunks[0].info == cs.info
        assert chunks[0].vectors == cs.vectors  # byte-exact

    def test_time_range_filter(self, disk):
        cs1, ts1, _ = _mk_chunkset(n=50, t0=BASE)
        cs2, ts2, _ = _mk_chunkset(n=50, t0=BASE + 10**9, seed=1)
        disk.write_chunks("ds", 0, [cs1, cs2])
        got = list(disk.read_raw_partitions("ds", 0, [b"pk1"],
                                            BASE, BASE + 10**6))
        assert len(got[0][1]) == 1
        assert got[0][1][0].info.chunk_id == cs1.info.chunk_id

    def test_ingestion_time_scan(self, disk):
        cs1, *_ = _mk_chunkset(pk=b"a")
        cs2, *_ = _mk_chunkset(pk=b"b", seed=2)
        disk.write_chunks("ds", 0, [cs1], ingestion_time=100)
        disk.write_chunks("ds", 0, [cs2], ingestion_time=200)
        got = list(disk.chunksets_by_ingestion_time("ds", 0, 150, 250))
        assert [c.partkey for c in got] == [b"b"]

    def test_partkeys(self, disk):
        recs = [PartKeyRecord(f"pk{i}".encode(), BASE, BASE + i, 3)
                for i in range(5)]
        disk.write_part_keys("ds", 3, recs)
        got = sorted(disk.scan_part_keys("ds", 3), key=lambda r: r.partkey)
        assert [r.partkey for r in got] == [r.partkey for r in recs]
        assert got[2].end_time == BASE + 2
        # upsert updates end time
        disk.write_part_keys("ds", 3, [PartKeyRecord(b"pk0", BASE, BASE + 99, 3)])
        got = {r.partkey: r for r in disk.scan_part_keys("ds", 3)}
        assert got[b"pk0"].end_time == BASE + 99

    def test_shard_isolation(self, disk):
        cs, *_ = _mk_chunkset()
        disk.write_chunks("ds", 0, [cs])
        assert list(disk.read_raw_partitions("ds", 1, [b"pk1"], 0, 2**62)) == []
        assert disk.num_chunks("ds", 0) == 1

    def test_delete_part_keys(self, disk):
        cs, *_ = _mk_chunkset()
        disk.write_chunks("ds", 0, [cs])
        disk.write_part_keys("ds", 0, [PartKeyRecord(b"pk1", 0, 1, 0)])
        disk.delete_part_keys("ds", 0, [b"pk1"])
        assert list(disk.scan_part_keys("ds", 0)) == []
        assert disk.num_chunks("ds", 0) == 0

    def test_reopen_persists(self, tmp_path):
        path = str(tmp_path / "c.db")
        store = DiskColumnStore(path)
        cs, *_ = _mk_chunkset()
        store.write_chunks("ds", 0, [cs])
        store.shutdown()
        store2 = DiskColumnStore(path)
        got = list(store2.read_raw_partitions("ds", 0, [b"pk1"], 0, 2**62))
        assert got[0][1][0].vectors == cs.vectors


class TestDiskMetaStore:
    def test_checkpoints(self, meta):
        meta.write_checkpoint("ds", 1, 0, 100)
        meta.write_checkpoint("ds", 1, 1, 150)
        meta.write_checkpoint("ds", 1, 0, 200)  # upsert
        assert meta.read_checkpoints("ds", 1) == {0: 200, 1: 150}
        assert meta.read_earliest_checkpoint("ds", 1) == 150
        assert meta.read_highest_checkpoint("ds", 1) == 200
        assert meta.read_checkpoints("ds", 2) == {}

    def test_datasets(self, meta):
        meta.write_dataset("prom", '{"num_shards": 8}')
        assert meta.read_dataset("prom") == '{"num_shards": 8}'
        assert meta.list_datasets() == ["prom"]
        assert meta.read_dataset("nope") is None

    def test_memory_store_shared_across_threads(self):
        """Regression: a ':memory:' store must serve every thread from ONE
        database (plain :memory: sqlite is per-connection-private)."""
        import threading

        meta = DiskMetaStore(":memory:")
        meta.write_checkpoint("ds", 0, 1, 42)
        got: dict = {}

        def worker():
            try:
                got["cp"] = meta.read_checkpoints("ds", 0)
            except Exception as e:  # noqa: BLE001
                got["err"] = e

        t = threading.Thread(target=worker)
        t.start()
        t.join()
        assert "err" not in got, got
        assert got["cp"] == {1: 42}


class TestRecovery:
    def test_restart_recovers_index_and_skips_persisted(self, tmp_path):
        """Full crash/restart cycle: flush → checkpoint → restart →
        recover_index + recover_stream with watermark skipping
        (reference: SURVEY.md §3.4)."""
        disk = DiskColumnStore(str(tmp_path / "c.db"))
        meta = DiskMetaStore(str(tmp_path / "m.db"))
        containers, truth = _builder_data()
        cfg = StoreConfig(groups_per_shard=4)

        store = TimeSeriesMemStore(disk, meta)
        store.setup("prom", DEFAULT_SCHEMAS, 0, cfg)
        for off, c in enumerate(containers):
            store.ingest("prom", 0, c, offset=off)
        store.get_shard("prom", 0).flush_all()
        n_persisted = disk.num_chunks("prom", 0)
        assert n_persisted > 0

        # --- restart ---
        store2 = TimeSeriesMemStore(disk, meta)
        shard2 = store2.setup("prom", DEFAULT_SCHEMAS, 0, cfg)
        assert store2.recover_index("prom", 0) == len(truth)
        replayed = store2.recover_stream(
            "prom", 0, [(off, c) for off, c in enumerate(containers)])
        # every record was already persisted+checkpointed: all skipped
        assert replayed == 0
        assert shard2.stats.rows_skipped > 0

        # queries work via ODP paging of the persisted chunks
        res = shard2.lookup_partitions(
            [ColumnFilter("_metric_", Equals("heap_usage"))], 0, 2**62)
        assert len(res.part_ids) == len(truth)
        tags_list, batch = shard2.scan_batch(res.part_ids, 0, 2**62)
        assert len(tags_list) == len(truth)
        by_inst = {t["instance"]: i for i, t in enumerate(tags_list)}
        for inst, (ts, vals) in truth.items():
            i = by_inst[inst]
            n = len(ts)
            got_ts = np.asarray(batch.timestamps)[i][:n]
            got_vals = np.asarray(batch.values)[i][:n]
            np.testing.assert_array_equal(got_ts, ts)
            np.testing.assert_allclose(got_vals, vals)

    def test_partial_recovery_replays_tail(self, tmp_path):
        """Records after the checkpoint replay; records before skip."""
        disk = DiskColumnStore(str(tmp_path / "c.db"))
        meta = DiskMetaStore(str(tmp_path / "m.db"))
        containers, truth = _builder_data(n_series=4, n_rows=200,
                                          container_size=8192)
        cfg = StoreConfig(groups_per_shard=2)

        store = TimeSeriesMemStore(disk, meta)
        store.setup("prom", DEFAULT_SCHEMAS, 0, cfg)
        # ingest+flush only the first half of the containers
        half = max(len(containers) // 2, 1)
        for off in range(half):
            store.ingest("prom", 0, containers[off], offset=off)
        store.get_shard("prom", 0).flush_all()

        store2 = TimeSeriesMemStore(disk, meta)
        shard2 = store2.setup("prom", DEFAULT_SCHEMAS, 0, cfg)
        store2.recover_index("prom", 0)
        replayed = store2.recover_stream(
            "prom", 0, [(off, c) for off, c in enumerate(containers)])
        assert replayed > 0  # the unflushed tail was re-ingested
        # no duplicates: per-series row count equals the source
        res = shard2.lookup_partitions(
            [ColumnFilter("_metric_", Equals("heap_usage"))], 0, 2**62)
        tags_list, batch = shard2.scan_batch(res.part_ids, 0, 2**62)
        counts = np.asarray(batch.row_counts)[:len(tags_list)]
        for i, t in enumerate(tags_list):
            assert counts[i] == len(truth[t["instance"]][0]), t


class TestOnDemandPaging:
    def _setup(self, tmp_path, **cfg_kw):
        disk = DiskColumnStore(str(tmp_path / "c.db"))
        meta = DiskMetaStore(str(tmp_path / "m.db"))
        store = TimeSeriesMemStore(disk, meta)
        cfg = StoreConfig(groups_per_shard=2, **cfg_kw)
        shard = store.setup("prom", DEFAULT_SCHEMAS, 0, cfg)
        assert isinstance(shard, OnDemandPagingShard)
        containers, truth = _builder_data(n_series=5, n_rows=250)
        for off, c in enumerate(containers):
            store.ingest("prom", 0, c, offset=off)
        shard.flush_all()
        return disk, shard, truth

    def test_evict_then_query_pages_back(self, tmp_path):
        disk, shard, truth = self._setup(tmp_path)
        n_evicted = shard.evict_partitions(3)
        assert n_evicted == 3
        assert shard.num_partitions == len(truth) - 3
        res = shard.lookup_partitions(
            [ColumnFilter("_metric_", Equals("heap_usage"))], 0, 2**62)
        assert len(res.part_ids) == len(truth)  # index kept evicted entries
        tags_list, batch = shard.scan_batch(res.part_ids, 0, 2**62)
        assert len(tags_list) == len(truth)
        assert shard.stats.partitions_paged == 3
        by_inst = {t["instance"]: i for i, t in enumerate(tags_list)}
        for inst, (ts, vals) in truth.items():
            i = by_inst[inst]
            np.testing.assert_array_equal(
                np.asarray(batch.timestamps)[i][:len(ts)], ts)

    def test_page_cache_reuse(self, tmp_path):
        disk, shard, truth = self._setup(tmp_path)
        shard.evict_partitions(2)
        res = shard.lookup_partitions(
            [ColumnFilter("_metric_", Equals("heap_usage"))], 0, 2**62)
        shard.scan_batch(res.part_ids, 0, 2**62)
        paged_once = shard.stats.partitions_paged
        shard.scan_batch(res.part_ids, 0, 2**62)
        assert shard.stats.partitions_paged == paged_once  # cache hit

    def test_deferred_publish_lands_in_page_cache(self, tmp_path):
        """The fused cold scan returns its batch BEFORE partition
        skeletons publish to the page cache (side thread); the very next
        query must join that publish and hit the cache — never re-page
        (reference: DemandPagedChunkStore pages via futures, but a
        paged-in chunk is immediately servable)."""
        disk, shard, truth = self._setup(tmp_path)
        shard.evict_partitions(len(truth))
        res = shard.lookup_partitions(
            [ColumnFilter("_metric_", Equals("heap_usage"))], 0, 2**62)
        ids = list(res.part_ids) + res.missing_partkeys
        tags_list, _ = shard.scan_batch(ids, 0, 2**62)
        assert len(tags_list) == len(truth)
        # stats count eagerly, with the triggering query
        assert shard.stats.partitions_paged == len(truth)
        shard.scan_batch(ids, 0, 2**62)
        assert shard.stats.partitions_paged == len(truth)  # cache hit
        assert len(shard.paged) == len(truth)              # published

    def test_pop_cancels_deferred_publish(self, tmp_path):
        """pop() and a gen-guarded put_many are safe in EITHER order: an
        evict's invalidation must never be overwritten by a deferred
        publish built from a pre-eviction disk read."""
        from filodb_tpu.memstore.odp import _PagedPartitions
        cache = _PagedPartitions(1 << 20)
        g = cache.gen
        cache.pop(1)                 # invalidation after guard capture
        cache.put_many([(1, "x", 10), (2, "z", 10)], gen_guard=g)
        assert cache.get(1) is None  # dropped: stale snapshot of 1 ...
        assert cache.get(2) == "z"   # ... but unrelated keys still land
        cache.put_many([(1, "y", 10)], gen_guard=cache.gen)
        assert cache.get(1) == "y"   # fresh guard: lands
        # pre-capture pops don't cancel
        cache.pop(3)
        g2 = cache.gen
        cache.put_many([(3, "w", 10)], gen_guard=g2)
        assert cache.get(3) == "w"

    def test_failed_publish_is_counted_not_silent(self, tmp_path,
                                                  monkeypatch):
        from filodb_tpu import native
        if native.batch_decoder() is None:
            pytest.skip("native disabled")   # publish exists only fused
        disk, shard, truth = self._setup(tmp_path)
        shard.evict_partitions(len(truth))
        monkeypatch.setattr(
            shard, "_materialize_paged",
            lambda *a, **k: (_ for _ in ()).throw(RuntimeError("boom")))
        res = shard.lookup_partitions(
            [ColumnFilter("_metric_", Equals("heap_usage"))], 0, 2**62)
        ids = list(res.part_ids) + res.missing_partkeys
        tags_list, _ = shard.scan_batch(ids, 0, 2**62)
        assert len(tags_list) == len(truth)   # the query itself succeeds
        shard._join_materialize()
        assert shard.stats.page_publish_errors == 1

    def test_page_cache_bytes_config(self, tmp_path):
        disk, shard, truth = self._setup(tmp_path,
                                         page_cache_bytes=7 << 20)
        assert shard.paged.max_bytes == 7 << 20

    def test_undersized_page_cache_still_scans(self, tmp_path):
        """A page cache too small for the working set must still serve
        scans correctly (the triggering query holds its own refs); only
        cache reuse is lost."""
        disk, shard, truth = self._setup(tmp_path, page_cache_bytes=1)
        shard.evict_partitions(len(truth))
        res = shard.lookup_partitions(
            [ColumnFilter("_metric_", Equals("heap_usage"))], 0, 2**62)
        ids = list(res.part_ids) + res.missing_partkeys
        tags_list, batch = shard.scan_batch(ids, 0, 2**62)
        by_inst = {t["instance"]: i for i, t in enumerate(tags_list)}
        for inst, (ts, vals) in truth.items():
            i = by_inst[inst]
            np.testing.assert_array_equal(
                np.asarray(batch.timestamps)[i][:len(ts)], ts)

    def test_reingest_after_evict_reuses_part_id(self, tmp_path):
        disk, shard, truth = self._setup(tmp_path)
        before = {t: pid for pid, t in
                  ((pid, p.tags["instance"]) for pid, p in shard.partitions.items())}
        shard.evict_partitions(len(truth))
        schema = DEFAULT_SCHEMAS["gauge"]
        builder = RecordBuilder(schema)
        last_ts = int(max(ts[-1] for ts, _ in truth.values()))
        builder.add(last_ts + 60_000, [1.5],
                    {"__name__": "heap_usage", "job": "app", "instance": "i0",
                     "_ws_": "demo", "_ns_": "ns"})
        for c in builder.containers():
            shard.ingest_container(c, offset=10_000)
        assert shard.part_set[
            next(pk for pk, pid in shard.part_set.items()
                 if pid == before["i0"])] == before["i0"]

    def test_paged_partitions_serve_device_grid(self, tmp_path):
        """Once a dashboard pages evicted history in, repeat hits must
        serve from the DEVICE GRID (reference: DemandPagedChunkStore
        pages straight into block memory and serves identically)."""
        from filodb_tpu.ops.windows import StepRange
        from filodb_tpu.query import rangefns
        from filodb_tpu.query.logical import RangeFunctionId as F

        disk = DiskColumnStore(str(tmp_path / "c.db"))
        meta = DiskMetaStore(str(tmp_path / "m.db"))
        store = TimeSeriesMemStore(disk, meta)
        shard = store.setup("prom", DEFAULT_SCHEMAS, 0,
                            StoreConfig(groups_per_shard=2))
        step = 10_000
        t0 = 1_700_000_000_000
        n_rows = 120
        schema = DEFAULT_SCHEMAS["gauge"]
        builder = RecordBuilder(schema)
        rng = np.random.default_rng(5)
        for s in range(6):
            tags = {"__name__": "pg", "job": "app", "instance": f"i{s}",
                    "_ws_": "demo", "_ns_": "ns"}
            ts = t0 + np.arange(n_rows, dtype=np.int64) * step
            vals = np.cumsum(rng.random(n_rows))
            for t, v in zip(ts, vals):
                builder.add(int(t), [float(v)], tags)
        for off, c in enumerate(builder.containers()):
            shard.ingest_container(c, off)
        shard.flush_all()
        shard.evict_partitions(6)
        assert shard.num_partitions == 0

        flt = [ColumnFilter("_metric_", Equals("pg"))]
        res = shard.lookup_partitions(flt, 0, 2**62)
        assert len(res.part_ids) == 6
        # first hit: pages chunks back from the column store
        tags_list, batch = shard.scan_batch(res.part_ids, 0, 2**62)
        assert shard.stats.partitions_paged == 6
        # repeat hit: the grid must serve the PAGED partitions
        steps0 = t0 + 120_000
        nsteps = 40
        got = shard.scan_grid(res.part_ids, F.RATE, steps0, nsteps,
                              step, 120_000)
        assert got is not None, "grid did not serve paged partitions"
        gtags, vals, _tops = got
        sr = StepRange(steps0, steps0 + (nsteps - 1) * step, step)
        oracle = np.asarray(rangefns.apply_range_function(
            batch, sr, 120_000, F.RATE))
        order = {t["instance"]: i for i, t in enumerate(tags_list)}
        for i, t in enumerate(gtags):
            j = order[t["instance"]]
            np.testing.assert_allclose(vals[i], oracle[j], rtol=1e-9,
                                       equal_nan=True)

    def test_page_evict_invalidates_grid_plan(self, tmp_path):
        """LRU pressure dropping a paged partition must invalidate grid
        plans that referenced it — a repeat query falls back (and
        re-pages), never serves stale/empty lanes."""
        from filodb_tpu.query.logical import RangeFunctionId as F

        disk = DiskColumnStore(str(tmp_path / "c.db"))
        meta = DiskMetaStore(str(tmp_path / "m.db"))
        store = TimeSeriesMemStore(disk, meta)
        shard = store.setup("prom", DEFAULT_SCHEMAS, 0,
                            StoreConfig(groups_per_shard=2))
        step = 10_000
        t0 = 1_700_000_000_000
        builder = RecordBuilder(DEFAULT_SCHEMAS["gauge"])
        for s in range(4):
            tags = {"__name__": "pe", "job": "app", "instance": f"i{s}",
                    "_ws_": "demo", "_ns_": "ns"}
            for r in range(100):
                builder.add(t0 + r * step, [float(s * 100 + r)], tags)
        for off, c in enumerate(builder.containers()):
            shard.ingest_container(c, off)
        shard.flush_all()
        shard.evict_partitions(4)
        flt = [ColumnFilter("_metric_", Equals("pe"))]
        res = shard.lookup_partitions(flt, 0, 2**62)
        shard.scan_batch(res.part_ids, 0, 2**62)     # page everything in
        epoch_before = shard.removal_epoch
        got = shard.scan_grid(res.part_ids, F.RATE, t0 + 120_000, 20,
                              step, 120_000)
        assert got is not None
        # simulate LRU pressure: shrink the cache and add an entry
        shard.paged.max_bytes = 1
        shard.paged.put(999_999, object(), 10)       # forces eviction
        assert shard.removal_epoch > epoch_before
        got2 = shard.scan_grid(res.part_ids, F.RATE, t0 + 120_000, 20,
                               step, 120_000)
        if got2 is not None:
            # re-validated and re-served (e.g. repaged): must be correct
            _t2, v2, _ = got2
            _t1, v1, _ = got
            np.testing.assert_allclose(v2, v1, rtol=1e-9, equal_nan=True)

    def test_evicted_lane_pruned_from_block_build(self, tmp_path):
        """Regression (round-4 ADVICE, medium): a grid block built while a
        laned partition is page-evicted must PRUNE that lane — never cache
        an all-NaN lane still mapped to the partition (it would serve
        'provably empty' for history that exists on disk once the
        partition pages back in; a re-paged partition instead gets a
        fresh lane, forcing a rebuild)."""
        from filodb_tpu.query.logical import RangeFunctionId as F

        disk = DiskColumnStore(str(tmp_path / "c.db"))
        meta = DiskMetaStore(str(tmp_path / "m.db"))
        store = TimeSeriesMemStore(disk, meta)
        shard = store.setup("prom", DEFAULT_SCHEMAS, 0,
                            StoreConfig(groups_per_shard=2))
        step = 10_000
        t0 = 1_700_000_000_000
        builder = RecordBuilder(DEFAULT_SCHEMAS["gauge"])
        for s in range(4):
            tags = {"__name__": "nl", "job": "app", "instance": f"i{s}",
                    "_ws_": "demo", "_ns_": "ns"}
            for r in range(100):
                builder.add(t0 + r * step, [float(s * 100 + r)], tags)
        for off, c in enumerate(builder.containers()):
            shard.ingest_container(c, off)
        shard.flush_all()
        shard.evict_partitions(4)
        flt = [ColumnFilter("_metric_", Equals("nl"))]
        res = shard.lookup_partitions(flt, 0, 2**62)
        shard.scan_batch(res.part_ids, 0, 2**62)       # page everything in
        got = shard.scan_grid(res.part_ids, F.RATE, t0 + 120_000, 20,
                              step, 120_000)
        assert got is not None
        cache = next(iter(shard.device_caches.values()))
        assert cache.blocks, "grid serve left no resident blocks"
        bi, blk = next(iter(cache.blocks.items()))
        victim = int(res.part_ids[-1])
        assert victim in cache.lane_of
        old_lane = cache.lane_of[victim]
        shard.paged.pop(victim)                        # LRU drop, mid-flight
        shard.bump_removal_epoch()
        # rebuilding with the lane unmaterializable must PRUNE it AND
        # fail THIS build (an in-flight pre-eviction prep must fall
        # back, never read a cached NaN lane) …
        assert cache._build(bi, blk.lanes) is None
        assert victim not in cache.lane_of
        # … while the NEXT build succeeds — a permanent eviction cannot
        # wedge future builds
        assert cache._build(bi, blk.lanes) is not None
        # … a re-appearing partition gets a FRESH lane, so the stale NaN
        # lane can never serve it, and end-to-end results stay correct
        cache.blocks.clear()
        cache._open.clear()
        res2 = shard.lookup_partitions(flt, 0, 2**62)
        shard.scan_batch(res2.part_ids, 0, 2**62)      # re-page victim
        got2 = shard.scan_grid(res2.part_ids, F.RATE, t0 + 120_000, 20,
                               step, 120_000)
        if victim in cache.lane_of:        # re-laned: must be a new slot
            assert cache.lane_of[victim] > old_lane
        if got2 is not None:
            t1, v1, _ = got
            t2, v2, _ = got2
            o1 = {t["instance"]: v1[i] for i, t in enumerate(t1)}
            for i, t in enumerate(t2):
                np.testing.assert_allclose(v2[i], o1[t["instance"]],
                                           rtol=1e-9, equal_nan=True)

    def test_query_data_cap(self, tmp_path):
        disk, shard, truth = self._setup(tmp_path,
                                         max_data_per_shard_query=16)
        res = shard.lookup_partitions(
            [ColumnFilter("_metric_", Equals("heap_usage"))], 0, 2**62)
        with pytest.raises(QueryLimitExceeded):
            shard.scan_batch(res.part_ids, 0, 2**62)

    def test_concurrent_scans_thread_safe(self, tmp_path):
        """ODP shards are queried from concurrent HTTP handler threads:
        paging + the LRU must tolerate parallel scans (regression for the
        unlocked _PagedPartitions / in-place chunk-list mutation)."""
        import threading

        disk, shard, truth = self._setup(tmp_path)
        shard.evict_partitions(3)
        f = [ColumnFilter("_metric_", Equals("heap_usage"))]
        res = shard.lookup_partitions(f, 0, 2**62)
        errs: list = []

        def worker():
            try:
                for _ in range(5):
                    tags_list, batch = shard.scan_batch(res.part_ids, 0, 2**62)
                    assert len(tags_list) == len(truth)
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=worker) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs, errs

    def test_backfill_snapshots_leave_live_partition_untouched(self, tmp_path):
        """Older on-disk chunks of a recovery-tail resident are served via a
        read-only snapshot; the live partition (single-writer: the ingest
        thread) must never be mutated from the query path."""
        disk = DiskColumnStore(str(tmp_path / "c.db"))
        meta = DiskMetaStore(str(tmp_path / "m.db"))
        containers, truth = _builder_data(n_series=4, n_rows=200,
                                          container_size=8192)
        cfg = StoreConfig(groups_per_shard=2)
        store = TimeSeriesMemStore(disk, meta)
        store.setup("prom", DEFAULT_SCHEMAS, 0, cfg)
        half = max(len(containers) // 2, 1)
        for off in range(half):
            store.ingest("prom", 0, containers[off], offset=off)
        store.get_shard("prom", 0).flush_all()

        store2 = TimeSeriesMemStore(disk, meta)
        shard2 = store2.setup("prom", DEFAULT_SCHEMAS, 0, cfg)
        store2.recover_index("prom", 0)
        store2.recover_stream(
            "prom", 0, [(off, c) for off, c in enumerate(containers)])
        chunk_counts = {pid: len(p.chunks)
                        for pid, p in shard2.partitions.items()}
        f = [ColumnFilter("_metric_", Equals("heap_usage"))]
        res = shard2.lookup_partitions(f, 0, 2**62)
        for _ in range(2):  # second scan exercises the cached backfill
            tags_list, batch = shard2.scan_batch(res.part_ids, 0, 2**62)
            counts = np.asarray(batch.row_counts)[:len(tags_list)]
            for i, t in enumerate(tags_list):
                assert counts[i] == len(truth[t["instance"]][0]), t
        for pid, p in shard2.partitions.items():
            assert len(p.chunks) == chunk_counts[pid]  # not mutated


    def test_narrow_then_wide_query_sees_full_history(self, tmp_path):
        """Regression: a narrow first query must not truncate what a later
        wide query sees (paged partitions hold full history)."""
        disk, shard, truth = self._setup(tmp_path)
        shard.evict_partitions(len(truth))
        some_ts = truth["i0"][0]
        narrow_end = int(some_ts[50])
        f = [ColumnFilter("_metric_", Equals("heap_usage"))]
        res = shard.lookup_partitions(f, 0, narrow_end)
        shard.scan_batch(res.part_ids, 0, narrow_end)
        # now the wide query: every series must return all rows
        res = shard.lookup_partitions(f, 0, 2**62)
        tags_list, batch = shard.scan_batch(res.part_ids, 0, 2**62)
        counts = np.asarray(batch.row_counts)
        by_inst = {t["instance"]: i for i, t in enumerate(tags_list)}
        for inst, (ts, _) in truth.items():
            assert counts[by_inst[inst]] == len(ts), inst

    def test_repeated_eviction_reclaims_memory(self, tmp_path):
        """Regression: ghost (already-evicted) index ids must not starve
        later evictions."""
        disk, shard, truth = self._setup(tmp_path)
        assert shard.evict_partitions(2) == 2
        assert shard.evict_partitions(2) == 2
        assert shard.num_partitions == len(truth) - 4


    def test_small_page_cache_does_not_drop_series(self, tmp_path):
        """Regression: partitions paged during one scan must all survive it
        even when their combined bytes exceed the page cache."""
        disk, shard, truth = self._setup(tmp_path)
        shard.evict_partitions(len(truth))
        shard.paged.max_bytes = 1  # pathological: cache holds ~one partition
        f = [ColumnFilter("_metric_", Equals("heap_usage"))]
        res = shard.lookup_partitions(f, 0, 2**62)
        tags_list, batch = shard.scan_batch(res.part_ids, 0, 2**62)
        assert len(tags_list) == len(truth)

    def test_evict_pending_data_feeds_downsampler_and_itime(self, tmp_path):
        """Regression: unflushed rows persisted during eviction must carry a
        real ingestion time and flow through the streaming downsampler."""
        from filodb_tpu.downsample import MemoryDownsamplePublisher
        disk, shard, truth = self._setup(tmp_path)
        pub = MemoryDownsamplePublisher()
        shard.enable_downsampling(pub, (60_000,))
        # add fresh unflushed rows to one series
        schema = DEFAULT_SCHEMAS["gauge"]
        b = RecordBuilder(schema)
        last = int(max(ts[-1] for ts, _ in truth.values()))
        b.add(last + 60_000, [7.0],
              {"__name__": "heap_usage", "job": "app", "instance": "i0",
               "_ws_": "demo", "_ns_": "ns"})
        for c in b.containers():
            shard.ingest_container(c, offset=99)
        before = disk.num_chunks("prom", 0)
        shard.evict_partitions(len(truth))
        assert disk.num_chunks("prom", 0) > before
        assert sum(len(v) for v in pub.published.values()) > 0
        # the eviction-persisted chunk is visible to ingestion-time scans
        import time as _t
        now = int(_t.time() * 1000)
        got = list(disk.chunksets_by_ingestion_time(
            "prom", 0, now - 3_600_000, now + 3_600_000))
        assert len(got) >= 1


class TestBulkPageIn:
    """The vectorized ODP cold path (bulk sqlite read + native framed
    decode + fused batch assembly, VERDICT r4 missing #4) must be
    bit-identical to the per-partition generic path in every shape:
    pure-cold fused, range-trimmed, ragged, multi-chunk, and repeats."""

    def _fresh(self, tmp_path, n_series=24, rows_of=None, name="c"):
        """Ingest ragged per-series data, flush, and return a FRESH
        index-only store (pure cold) plus the ground truth."""
        disk = DiskColumnStore(str(tmp_path / f"{name}.db"))
        meta = DiskMetaStore(str(tmp_path / f"{name}m.db"))
        store = TimeSeriesMemStore(disk, meta)
        cfg = StoreConfig(max_chunks_size=120)   # multi-chunk partitions
        store.setup("prom", DEFAULT_SCHEMAS, 0, cfg)
        schema = DEFAULT_SCHEMAS["gauge"]
        builder = RecordBuilder(schema, container_size=1 << 20)
        rng = np.random.default_rng(7)
        truth = {}
        for s in range(n_series):
            n = rows_of(s) if rows_of else 150 + 17 * (s % 9)
            tags = {"__name__": "bulk_metric", "job": "app",
                    "instance": f"i{s}", "_ws_": "demo", "_ns_": "ns"}
            ts = BASE + np.cumsum(rng.integers(9_000, 11_000, n))
            vals = np.cumsum(rng.random(n))
            truth[f"i{s}"] = (ts.astype(np.int64), vals.copy())
            for t, v in zip(ts, vals):
                builder.add(int(t), [float(v)], tags)
        sh = store.get_shard("prom", 0)
        for off, c in enumerate(builder.containers()):
            sh.ingest_container(c, off)
        sh.flush_all(ingestion_time=1000)
        cold = TimeSeriesMemStore(disk, meta)
        cold.setup("prom", DEFAULT_SCHEMAS, 0, cfg)
        assert cold.recover_index("prom", 0) == n_series
        return cold.get_shard("prom", 0), truth

    @staticmethod
    def _scan(shard, start=0, end=2**62):
        res = shard.lookup_partitions(
            [ColumnFilter("_metric_", Equals("bulk_metric"))], 0, 2**62)
        ids = list(res.part_ids) + res.missing_partkeys
        return shard.scan_batch(ids, start, end)

    @staticmethod
    def _rows_by_inst(tags, batch):
        out = {}
        for i, t in enumerate(tags):
            c = int(batch.row_counts[i])
            out[t["instance"]] = (
                np.asarray(batch.timestamps[i][:c]),
                np.asarray(batch.values[i][:c]))
        return out

    def _compare(self, tmp_path, start=0, end=2**62, rows_of=None):
        from filodb_tpu import native
        shard, truth = self._fresh(tmp_path, rows_of=rows_of, name="a")
        tags, batch = self._scan(shard, start, end)
        got = self._rows_by_inst(tags, batch)
        # generic oracle: same data, native batch decoder disabled
        shard2, _ = self._fresh(tmp_path, rows_of=rows_of, name="b")
        saved = native._batch_dec
        native._batch_dec = None
        try:
            tags2, batch2 = self._scan(shard2, start, end)
        finally:
            native._batch_dec = saved
        want = self._rows_by_inst(tags2, batch2)
        assert set(got) == set(want) == set(truth)
        for inst in want:
            np.testing.assert_array_equal(got[inst][0], want[inst][0])
            np.testing.assert_array_equal(got[inst][1], want[inst][1])
        return shard, got, truth

    def test_pure_cold_fused_matches_generic(self, tmp_path):
        shard, got, truth = self._compare(tmp_path)
        assert shard.stats.partitions_paged == len(truth)
        for inst, (ts, vals) in truth.items():
            np.testing.assert_array_equal(got[inst][0], ts)
            np.testing.assert_allclose(got[inst][1], vals)

    def test_range_trimmed_cold_matches_generic(self, tmp_path):
        # a window strictly inside the data defeats the fused path and
        # exercises the vectorized global-mask trim
        start = BASE + 400_000
        end = BASE + 1_300_000
        shard, got, truth = self._compare(tmp_path, start, end)
        for inst, (ts, vals) in truth.items():
            m = (ts >= start) & (ts <= end)
            np.testing.assert_array_equal(got[inst][0], ts[m])
            np.testing.assert_allclose(got[inst][1], vals[m])

    def test_uniform_rows_fused(self, tmp_path):
        # equal row counts take the reshape/no-mask branch
        shard, got, truth = self._compare(tmp_path, rows_of=lambda s: 200)
        for inst, (ts, vals) in truth.items():
            np.testing.assert_array_equal(got[inst][0], ts)

    def test_warm_repeat_serves_from_cache(self, tmp_path):
        shard, truth = self._fresh(tmp_path)
        t1, b1 = self._scan(shard)
        paged = shard.stats.partitions_paged
        t2, b2 = self._scan(shard)
        assert shard.stats.partitions_paged == paged   # no re-page
        r1, r2 = self._rows_by_inst(t1, b1), self._rows_by_inst(t2, b2)
        for inst in r1:
            np.testing.assert_array_equal(r1[inst][0], r2[inst][0])
            np.testing.assert_array_equal(r1[inst][1], r2[inst][1])

    def test_duplicate_ids_fall_back_consistently(self, tmp_path):
        shard, truth = self._fresh(tmp_path)
        res = shard.lookup_partitions(
            [ColumnFilter("_metric_", Equals("bulk_metric"))], 0, 2**62)
        ids = list(res.part_ids)
        dup = ids + ids[:3]
        tags, batch = shard.scan_batch(dup, 0, 2**62)
        assert len(tags) == len(dup)
        # the duplicated series' rows must appear twice, identically
        first = {t["instance"]: i for i, t in enumerate(tags[:len(ids)])}
        for k, t in enumerate(tags[len(ids):]):
            i = first[t["instance"]]
            np.testing.assert_array_equal(
                np.asarray(batch.timestamps[len(ids) + k]),
                np.asarray(batch.timestamps[i]))

    def test_page_decode_matches_unpack(self, tmp_path):
        """Native framed-row decode == Python unpack + per-chunk decode."""
        from filodb_tpu import native
        from filodb_tpu.core.chunk import decode_chunkset
        nb = native.batch_decoder()
        if nb is None:
            pytest.skip("native disabled")
        schema = DEFAULT_SCHEMAS["gauge"]
        rng = np.random.default_rng(3)
        blobs, counts, want_ts, want_v = [], [], [], []
        for s in range(17):
            n = 30 + 11 * s
            ts = BASE + np.cumsum(rng.integers(1_000, 2_000, n))
            vals = np.cumsum(rng.random(n))
            cs = encode_chunkset(schema, b"pk%d" % s,
                                 ts.astype(np.int64), [vals])
            blobs.append(pack_vectors(cs.vectors))
            counts.append(n)
            dts, dcols = decode_chunkset(schema, cs)
            want_ts.append(dts)
            want_v.append(dcols[0])
        flats = nb.page_decode(blobs, counts, [(0, False), (1, True)])
        assert flats is not None
        np.testing.assert_array_equal(flats[0], np.concatenate(want_ts))
        np.testing.assert_array_equal(flats[1], np.concatenate(want_v))
        # placed decode into a padded [S, R] matrix
        R = max(counts) + 5
        ts2d = np.empty((len(blobs), R), dtype=np.int64)
        v2d = np.empty((len(blobs), R), dtype=np.float64)
        starts = np.arange(len(blobs), dtype=np.int64) * R
        ok = nb.page_decode_into(blobs, counts,
                                 [(0, False, ts2d), (1, True, v2d)], starts)
        assert ok
        for i, n in enumerate(counts):
            np.testing.assert_array_equal(ts2d[i, :n], want_ts[i])
            np.testing.assert_array_equal(v2d[i, :n], want_v[i])

    def test_corrupt_framing_falls_back(self, tmp_path):
        from filodb_tpu import native
        nb = native.batch_decoder()
        if nb is None:
            pytest.skip("native disabled")
        assert nb.page_decode([b"\x01"], [10], [(0, False)]) is None
        out = np.empty((1, 16), dtype=np.int64)
        assert not nb.page_decode_into(
            [b"\xff\xff"], [10], [(0, False, out)],
            np.zeros(1, dtype=np.int64))

    def test_full_scan_ignores_unselected_schema_rows(self, tmp_path):
        """The full-shard range scan over-returns rows of partitions the
        query never asked for; a foreign-schema row that sorts FIRST
        must not disable the bulk path (its schema hash is not the
        reference hash — regression for h0-from-rows[0])."""
        disk = DiskColumnStore(str(tmp_path / "f.db"))
        meta = DiskMetaStore(str(tmp_path / "fm.db"))
        store = TimeSeriesMemStore(disk, meta)
        cfg = StoreConfig()
        store.setup("prom", DEFAULT_SCHEMAS, 0, cfg)
        schema = DEFAULT_SCHEMAS["gauge"]
        builder = RecordBuilder(schema, container_size=1 << 20)
        rng = np.random.default_rng(11)
        n_series, n = 300, 40    # >256 so the full-scan heuristic fires
        for s in range(n_series):
            ts = BASE + np.cumsum(rng.integers(9_000, 11_000, n))
            for t, v in zip(ts, np.cumsum(rng.random(n))):
                builder.add(int(t), [float(v)],
                            {"__name__": "fs_metric", "job": "app",
                             "instance": f"i{s}", "_ws_": "demo",
                             "_ns_": "ns"})
        sh = store.get_shard("prom", 0)
        for off, c in enumerate(builder.containers()):
            sh.ingest_container(c, off)
        sh.flush_all(ingestion_time=1000)
        # foreign-schema chunk whose partkey sorts before every real one
        cs, _, _ = _mk_chunkset(pk=b"\x00\x00early", n=10)
        cs.schema_hash = 0xBEEF
        disk.write_chunks("prom", 0, [cs], ingestion_time=1000)
        cold = TimeSeriesMemStore(disk, meta)
        cold.setup("prom", DEFAULT_SCHEMAS, 0, cfg)
        assert cold.recover_index("prom", 0) == n_series
        shard = cold.get_shard("prom", 0)
        res = shard.lookup_partitions(
            [ColumnFilter("_metric_", Equals("fs_metric"))], 0, 2**62)
        tags, batch = shard.scan_batch(list(res.part_ids), 0, 2**62)
        assert len(tags) == n_series
        # the bulk path served it (not the per-partition fallback)
        assert shard.stats.partitions_paged == n_series
        assert not np.isnan(
            np.asarray(batch.values[0][:int(batch.row_counts[0])])).any()
