"""Device-resource observability (ISSUE 4): HBM residency ledger,
JIT compile telemetry + recompile-storm detection, flight recorder,
and the /admin/device | /admin/flightrecorder | /admin/config routes.

The load-bearing invariant is LEDGER RECONCILIATION: at any quiescent
point, the ledger's per-owner byte totals must equal the sum of
``nbytes`` over the device arrays actually held by the caches it
accounts for — across block commit, repeat-query reuse,
overflow-eviction, epoch purges, and ODP page-in/out churn.  A drifting
ledger is worse than none (operators size HBM budgets from it).
"""

import collections
import gc
import json
import time
import urllib.parse
import urllib.request

import numpy as np
import pytest

from filodb_tpu.core.filters import ColumnFilter, Equals
from filodb_tpu.core.record import RecordBuilder, decode_container
from filodb_tpu.core.schemas import DEFAULT_SCHEMAS
from filodb_tpu.core.storeconfig import StoreConfig
from filodb_tpu.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.query.logical import RangeFunctionId as F
from filodb_tpu.utils import devicewatch
from filodb_tpu.utils.devicewatch import (COMPILE_WATCH, FLIGHT,
                                          KERNEL_TIMER, LEDGER,
                                          CompileWatch, FlightRecorder,
                                          KernelTimer, device_metrics)

STEP = 60_000
T0 = 1_700_000_040_000
WINDOW = 300_000
K = WINDOW // STEP


def _mk_shard(dataset, n_series=6, n_rows=50, seed=0, ms=None, **cfg_kw):
    """Regular (one sample per bucket) series so the device grid serves."""
    ms = ms or TimeSeriesMemStore()
    cfg = StoreConfig(**cfg_kw)
    shard = ms.setup(dataset, DEFAULT_SCHEMAS, 0, cfg)
    rng = np.random.default_rng(seed)
    b = RecordBuilder(DEFAULT_SCHEMAS["prom-counter"])
    for i in range(n_series):
        tags = {"__name__": "req_total", "instance": f"i{i}", "_ws_": "w",
                "_ns_": "n"}
        ts = T0 + np.arange(n_rows, dtype=np.int64) * STEP
        vals = np.cumsum(rng.random(n_rows) * 5)
        for t, v in zip(ts, vals):
            b.add(int(t), [float(v)], tags)
    for off, c in enumerate(b.containers()):
        shard.ingest(decode_container(c, DEFAULT_SCHEMAS), off)
    shard.flush_all()
    return ms, shard


def _ids(shard, metric="req_total"):
    return shard.lookup_partitions(
        [ColumnFilter("_metric_", Equals(metric))], 0, 2**62).part_ids


def _expected_grid_bytes(cache) -> dict:
    """Walk a DeviceGridCache's resident device arrays: what the ledger
    MUST show for this owner, by format."""
    by_fmt: collections.Counter = collections.Counter()
    blocks = list(cache.blocks.values()) \
        + list(cache._open.values())
    for blk in blocks:
        if blk.ts is not None:
            by_fmt["dense"] += int(blk.ts.nbytes)
        elif blk.ts_desc is not None:
            by_fmt["compressed"] += int(blk.ts_desc["phase"].nbytes)
        if isinstance(blk.vals, dict):
            by_fmt["compressed"] += sum(int(a.nbytes)
                                        for a in blk.vals.values())
        else:
            by_fmt["dense"] += int(blk.vals.nbytes)
    for _host, dev in cache._phase_memo.values():
        by_fmt["scratch"] += int(dev.nbytes)
    for memo in cache._mesh_stage_memo.values():
        _pid, ts_st, val_st = memo[0], memo[1], memo[2]
        if ts_st is not None:
            by_fmt["mesh-staged"] += int(ts_st.nbytes)
        by_fmt["mesh-staged"] += int(val_st.nbytes)
    return dict(by_fmt)


def _assert_reconciled(cache):
    """Ledger per-format totals == walked device-array bytes, exactly."""
    gc.collect()   # run finalizers of any just-dropped arrays
    got = {fmt: row["bytes"]
           for fmt, row in LEDGER.owners().get(cache.owner, {}).items()
           if row["bytes"]}
    want = {fmt: n for fmt, n in _expected_grid_bytes(cache).items() if n}
    assert got == want, f"ledger drift for {cache.owner}: " \
                        f"ledger={got} actual={want}"


def _grid_cache(shard):
    caches = list(shard.device_caches.values())
    assert caches, "grid never built"
    return caches[0]


# ---------------------------------------------------------------------------
# ledger unit behavior
# ---------------------------------------------------------------------------


class TestLedger:
    def test_track_and_release_on_gc(self):
        owner = "test:unit-release"
        a = LEDGER.device_put(np.zeros(1024, np.float32), owner=owner,
                              fmt="dense")
        assert LEDGER.owners()[owner]["dense"]["bytes"] == a.nbytes
        hw = LEDGER.owners()[owner]["dense"]["high_watermark"]
        assert hw == a.nbytes
        del a
        gc.collect()
        assert LEDGER.owners()[owner]["dense"]["bytes"] == 0
        # the watermark survives the release (peak sizing signal)
        assert LEDGER.owners()[owner]["dense"]["high_watermark"] == hw

    def test_noop_put_is_not_double_counted(self):
        owner = "test:unit-noop"
        a = LEDGER.device_put(np.zeros(256, np.int32), owner=owner,
                              fmt="dense")
        b = LEDGER.device_put(a, owner="test:unit-noop-other", fmt="dense")
        assert b is a                      # jax no-op put
        assert "test:unit-noop-other" not in LEDGER.owners()
        assert LEDGER.owners()[owner]["dense"]["bytes"] == a.nbytes
        LEDGER.track(a, owner=owner, fmt="dense")   # idempotent re-track
        assert LEDGER.owners()[owner]["dense"]["bytes"] == a.nbytes

    def test_eviction_attribution(self):
        c0 = device_metrics()["evictions"].value(owner="test:unit-evict",
                                                 reason="budget_overflow")
        LEDGER.note_eviction("test:unit-evict", "budget_overflow", n=3,
                             nbytes=123)
        assert device_metrics()["evictions"].value(
            owner="test:unit-evict", reason="budget_overflow") == c0 + 3
        kinds = [e for e in FLIGHT.events(kind="evict")
                 if e.get("owner") == "test:unit-evict"]
        assert kinds and kinds[-1]["bytes"] == 123

    def test_disabled_wrapper_is_passthrough(self):
        devicewatch.set_enabled(False)
        try:
            a = LEDGER.device_put(np.zeros(64), owner="test:unit-off",
                                  fmt="dense")
            assert "test:unit-off" not in LEDGER.owners()
            assert np.asarray(a).shape == (64,)
        finally:
            devicewatch.set_enabled(True)


# ---------------------------------------------------------------------------
# compile telemetry + storm detector
# ---------------------------------------------------------------------------


class TestCompileWatch:
    def test_jit_counts_compiles_per_shape(self):
        calls = {"n": 0}

        def f(x):
            calls["n"] += 1
            return x * 2

        prog = "test.unit_jit"
        wrapped = devicewatch.jit(f, program=prog)
        m = device_metrics()["jit_compiles"]
        c0 = m.value(program=prog)
        np.testing.assert_allclose(wrapped(np.ones(4, np.float32)),
                                   np.full(4, 2.0, np.float32))
        wrapped(np.ones(4, np.float32))           # cached: no new compile
        assert m.value(program=prog) == c0 + 1
        wrapped(np.ones(8, np.float32))           # new shape: compiles
        assert m.value(program=prog) == c0 + 2
        rows = [r for r in COMPILE_WATCH.table() if r["program"] == prog]
        assert rows and rows[0]["compiles"] >= 2
        assert "float32[4]" in ";".join(rows[0]["last_shape_key"]
                                        for r in rows) \
            or "float32[8]" in rows[0]["last_shape_key"]

    def test_storm_detector_fires_on_shape_cycling(self):
        cw = CompileWatch(storm_shapes=4, storm_window_s=300.0)
        prog = "test.unit_storm"
        for i in range(4):
            cw.note_compile(prog, 0.01, f"float32[{i}]")
        assert prog in cw.active_storms()
        row = [r for r in cw.table() if r["program"] == prog][0]
        assert row["storms"] == 1 and row["distinct_shapes"] == 4
        # one storm per window, not one per compile
        cw.note_compile(prog, 0.01, "float32[99]")
        assert [r for r in cw.table()
                if r["program"] == prog][0]["storms"] == 1

    def test_grid_query_shape_cycling_trips_the_detector(self):
        """E2E: a dashboard leaking nsteps into the program signature is
        THE storm the detector exists for — cycle query shapes through
        the device grid and watch it fire."""
        ms, shard = _mk_shard("dw_storm")
        ids = _ids(shard)
        old = (COMPILE_WATCH.storm_shapes, COMPILE_WATCH.storm_window_s)
        COMPILE_WATCH.configure(storm_shapes=4, storm_window_s=600.0)
        try:
            steps0 = T0 + (K - 1) * STEP
            served = 0
            for nsteps in range(40, 45):          # 5 distinct shapes
                got = shard.scan_grid(ids, F.RATE, steps0, nsteps, STEP,
                                      WINDOW)
                served += got is not None
            assert served == 5, "grid fast path did not serve"
            storms = COMPILE_WATCH.active_storms()
            assert any(p.startswith(("devicestore.", "grid."))
                       for p in storms), storms
            assert any(e["kind"] == "jit.storm"
                       for e in FLIGHT.events(kind="jit.storm"))
        finally:
            COMPILE_WATCH.configure(storm_shapes=old[0],
                                    storm_window_s=old[1])


# ---------------------------------------------------------------------------
# kernel flight deck: sampled device-time ledger + regression sentry
# (ISSUE 15)
# ---------------------------------------------------------------------------


def _kt_row(program):
    rows = [r for r in KERNEL_TIMER.table() if r["program"] == program]
    return rows[0] if rows else None


@pytest.fixture()
def kt_config():
    """Snapshot + restore the process-wide KernelTimer knobs so tests
    can crank the sample rate / sentry windows without leaking."""
    kt = KERNEL_TIMER
    old = (kt.sample_1_in, kt.regression_factor,
           kt.regression_window_s, kt.baseline_min_samples)
    yield kt
    kt.configure(sample_1_in=old[0],
                 regression_factor=old[1], regression_window_s=old[2],
                 baseline_min_samples=old[3])


@pytest.fixture()
def roof_table(monkeypatch):
    """The roof table with a row for the device the tests run on (the
    CPU has no published HBM peak, so the table itself has none)."""
    monkeypatch.setitem(devicewatch.HBM_ROOF_BYTES_PER_S,
                        devicewatch.device_kind(), 1e9)
    return devicewatch.HBM_ROOF_BYTES_PER_S


class TestKernelTimer:
    def test_every_launch_counts_and_1_in_n_samples(self, kt_config):
        kt_config.configure(sample_1_in=4)
        prog = "test.kt_count"
        f = devicewatch.jit(lambda x: x + 1, program=prog)
        for _ in range(9):
            f(np.ones(4, np.float32))
        row = _kt_row(prog)
        assert row["launches"] == 9
        # sampled launches are 1, 5, 9; launch 1 compiled (a compiling
        # launch is host trace time, never folded) -> 2 folded samples
        assert row["sampled"] == 2
        assert row["ewma_device_s"] is not None
        assert row["device_seconds"] > 0
        assert sum(row["seconds_histogram"].values()) == 2
        assert device_metrics()["kernel_launches"].value(
            program=prog) == row["launches"]
        assert device_metrics()["kernel_seconds"].value(
            program=prog) == pytest.approx(row["device_seconds"],
                                           abs=1e-6)

    def test_sample_rate_zero_disables_sampling_not_counting(self,
                                                             kt_config):
        kt_config.configure(sample_1_in=0)
        prog = "test.kt_off"
        f = devicewatch.jit(lambda x: x * 2, program=prog)
        for _ in range(5):
            f(np.ones(4, np.float32))
        row = _kt_row(prog)
        assert row["launches"] == 5 and row["sampled"] == 0
        assert device_metrics()["kernel_launches"].value(program=prog) == 5

    def test_disabled_devicewatch_is_passthrough(self, kt_config):
        kt_config.configure(sample_1_in=1)
        prog = "test.kt_killswitch"
        f = devicewatch.jit(lambda x: x - 1, program=prog)
        f(np.ones(4, np.float32))          # compile while enabled
        devicewatch.set_enabled(False)
        try:
            f(np.ones(4, np.float32))
            # bytes notes freeze with the switch too — accumulating
            # against a frozen launch count would permanently inflate
            # achieved-bytes/s after a disable/enable cycle
            KERNEL_TIMER.note_bytes(prog, 4096)
        finally:
            devicewatch.set_enabled(True)
        row = _kt_row(prog)
        assert row["launches"] == 1   # the disabled launch is
        # invisible everywhere (same contract as the ledger/compile
        # wrappers): counting resumes with the switch
        assert row["bytes_total"] == 0

    def test_bytes_join_yields_roofline_fraction(self, kt_config,
                                                 roof_table):
        kt = KernelTimer(sample_1_in=1, baseline_min_samples=100)
        kt.note_bytes("p", 4_000)
        kt._fold("p", 0.001, "k")          # 4000 B / launch... but
        # launches=0 until tick(); note_bytes alone must not divide by 0
        row = [r for r in kt.table() if r["program"] == "p"][0]
        assert row["roofline_fraction"] is None
        assert kt.tick("p")
        kt._fold("p", 0.001, "k")
        row = [r for r in kt.table() if r["program"] == "p"][0]
        # 4000 bytes / 1 launch / ewma(0.001 s) / roof(1e9 B/s)
        assert row["achieved_bytes_per_s"] == pytest.approx(4e6, rel=0.01)
        assert row["roofline_fraction"] == pytest.approx(4e-3, rel=0.01)

    def test_device_the_table_lacks_gets_no_roofline(self, kt_config,
                                                     monkeypatch):
        """No assumed peak: on a device_kind the table has no row for,
        /admin/kernels leaves roofline_fraction out altogether."""
        monkeypatch.delitem(devicewatch.HBM_ROOF_BYTES_PER_S,
                            devicewatch.device_kind(), raising=False)
        kt = KernelTimer(sample_1_in=1, baseline_min_samples=100)
        kt.note_bytes("p", 4_000)
        assert kt.tick("p")
        kt._fold("p", 0.001, "k")
        row = [r for r in kt.table() if r["program"] == "p"][0]
        assert row["achieved_bytes_per_s"] == pytest.approx(4e6, rel=0.01)
        assert "roofline_fraction" not in row
        assert devicewatch.hbm_roof() is None
        assert devicewatch.kernel_summary()["hbm_roof_bytes_per_s"] is None

    def test_roof_table_has_the_v5e(self):
        assert devicewatch.HBM_ROOF_BYTES_PER_S["TPU v5 lite"] == 819e9
        assert all(v > 1e11 for v in
                   devicewatch.HBM_ROOF_BYTES_PER_S.values())

    def test_baseline_store_merge_and_persist(self, kt_config):
        saved = {}
        kt = KernelTimer(sample_1_in=1, baseline_min_samples=2)
        kt.attach_baseline_store(
            load_fn=lambda: {"p": 0.001},
            save_fn=lambda prog, s: saved.__setitem__(prog, s))
        # learned EWMA above the persisted floor: the floor wins
        kt._fold("p", 0.004, "k")
        kt._fold("p", 0.004, "k")
        row = [r for r in kt.table() if r["program"] == "p"][0]
        assert row["baseline_s"] == pytest.approx(0.001)
        # a genuine improvement ratchets down AND persists (>=5% better)
        for _ in range(40):
            kt._fold("p", 0.0001, "k")
        row = [r for r in kt.table() if r["program"] == "p"][0]
        assert row["baseline_s"] < 0.001
        # persistence is rate-limited to >=5% improvements, so the
        # stored floor may lag the live baseline by up to that margin
        assert saved and saved["p"] == pytest.approx(row["baseline_s"],
                                                     rel=0.06)

    def test_regression_sentry_episode_lifecycle(self, kt_config):
        """The ISSUE 15 chaos contract: an injected sustained slowdown
        fires EXACTLY one kernel.regression episode; recovery re-arms;
        a second slowdown is a second episode."""
        from filodb_tpu.integrity.faultinject import (
            clear_kernel_slowdown, inject_kernel_slowdown)
        kt_config.configure(sample_1_in=1, baseline_min_samples=4,
                            regression_window_s=0.1,
                            regression_factor=1.5)
        prog = "test.kt_sentry"
        f = devicewatch.jit(lambda x: x * 3, program=prog)
        arr = np.ones(8, np.float32)
        for _ in range(8):
            f(arr)
        row = _kt_row(prog)
        assert row["baseline_s"] is not None and not row["regressed"]
        m = device_metrics()
        assert m["kernel_regressions"].value(program=prog) == 0
        assert m["kernel_regressed"].value(program=prog) == 0.0

        def regression_events():
            return [e for e in FLIGHT.events(kind="kernel.regression")
                    if e.get("program") == prog]

        inject_kernel_slowdown(prog, 0.02)
        try:
            for _ in range(60):
                f(arr)
                if _kt_row(prog)["regressed"]:
                    break
            row = _kt_row(prog)
            assert row["regressed"] and row["episodes"] == 1
            assert len(regression_events()) == 1
            assert m["kernel_regressions"].value(program=prog) == 1
            assert m["kernel_regressed"].value(program=prog) == 1.0
            # sustained slowness does NOT re-fire within the episode
            for _ in range(10):
                f(arr)
            assert len(regression_events()) == 1
            assert m["kernel_regressions"].value(program=prog) == 1
        finally:
            clear_kernel_slowdown(prog)
        for _ in range(100):
            f(arr)
            if not _kt_row(prog)["regressed"]:
                break
        assert not _kt_row(prog)["regressed"]
        assert m["kernel_regressed"].value(program=prog) == 0.0
        assert any(e.get("program") == prog
                   for e in FLIGHT.events(kind="kernel.recovery"))
        # re-armed: a second slowdown opens a SECOND episode
        inject_kernel_slowdown(prog, 0.02)
        try:
            for _ in range(60):
                f(arr)
                if _kt_row(prog)["regressed"]:
                    break
            assert _kt_row(prog)["episodes"] == 2
            assert len(regression_events()) == 2
        finally:
            clear_kernel_slowdown(prog)
        for _ in range(100):
            f(arr)
            if not _kt_row(prog)["regressed"]:
                break

    def test_loaded_baseline_survives_a_cold_fast_sample(self,
                                                         kt_config):
        """Review fix: a restart resets the EWMA, so the FIRST sample
        (ew = dt exactly) of a mixed-shape program must not ratchet a
        loaded healthy baseline down to one tiny query's time — that
        floor persists min-wins forever and would page every normal
        launch as a regression."""
        saved = {}
        kt = KernelTimer(sample_1_in=1, baseline_min_samples=4,
                         regression_window_s=1e9)
        kt.attach_baseline_store(
            load_fn=lambda: {"p": 0.002},
            save_fn=lambda prog, s: saved.__setitem__(prog, s))
        kt._fold("p", 0.0003, "k")         # one cold tiny-shape sample
        row = [r for r in kt.table() if r["program"] == "p"][0]
        assert row["baseline_s"] == pytest.approx(0.002)
        assert not saved
        # a WARMED sustained improvement still ratchets
        for _ in range(10):
            kt._fold("p", 0.0003, "k")
        row = [r for r in kt.table() if r["program"] == "p"][0]
        assert row["baseline_s"] < 0.002

    def test_baseline_never_ratchets_up(self, kt_config):
        kt = KernelTimer(sample_1_in=1, baseline_min_samples=2,
                         regression_window_s=1e9)
        kt._fold("p", 0.001, "k")
        kt._fold("p", 0.001, "k")
        base = [r for r in kt.table() if r["program"] == "p"][0]
        for _ in range(20):
            kt._fold("p", 0.01, "k")       # sustained slow
        after = [r for r in kt.table() if r["program"] == "p"][0]
        assert after["baseline_s"] == base["baseline_s"]


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


class TestFlightRecorder:
    def test_ring_is_bounded_and_ordered(self):
        fr = FlightRecorder(capacity=32)
        for i in range(100):
            fr.record("tick", i=i)
        events = fr.events()
        assert len(events) == 32
        assert [e["i"] for e in events] == list(range(68, 100))
        assert [e["seq"] for e in events] == sorted(e["seq"]
                                                    for e in events)

    def test_kind_filter_and_limit(self):
        fr = FlightRecorder(capacity=64)
        for i in range(10):
            fr.record("a", i=i)
            fr.record("b", i=i)
        assert [e["i"] for e in fr.events(kind="a", limit=3)] == [7, 8, 9]

    def test_dump_to_log_never_raises(self, caplog):
        fr = FlightRecorder(capacity=16)
        fr.record("boom", detail="x" * 10)
        fr.dump_to_log("unit test")
        assert any("flight recorder dump" in r.message
                   for r in caplog.records)

    def test_resize_keeps_recent_events(self):
        fr = FlightRecorder(capacity=64)
        for i in range(40):
            fr.record("tick", i=i)
        fr.resize(16)
        assert [e["i"] for e in fr.events()] == list(range(24, 40))
        assert fr.capacity == 16


# ---------------------------------------------------------------------------
# ledger reconciliation, end to end
# ---------------------------------------------------------------------------


class TestLedgerReconciliation:
    def test_commit_query_repeat_reconciles(self):
        ms, shard = _mk_shard("dw_rec1")
        ids = _ids(shard)
        steps0 = T0 + (K - 1) * STEP
        got = shard.scan_grid(ids, F.RATE, steps0, 40, STEP, WINDOW)
        assert got is not None
        cache = _grid_cache(shard)
        _assert_reconciled(cache)
        # repeat query: zero new commits, still reconciled
        before = LEDGER.owners().get(cache.owner, {})
        assert shard.scan_grid(ids, F.RATE, steps0, 40, STEP,
                               WINDOW) is not None
        _assert_reconciled(cache)
        assert LEDGER.owners().get(cache.owner, {}) == before

    def test_overflow_eviction_reconciles_and_attributes(self):
        # 3 blocks of data with a budget that holds ~1.5 uncompressed
        # blocks (131072 B each): querying the tail after the head
        # forces oldest-first reclaim
        ms, shard = _mk_shard("dw_rec2", n_rows=300,
                              device_cache_bytes=200_000,
                              device_cache_compress=False)
        ids = _ids(shard)
        steps0 = T0 + (K - 1) * STEP
        assert shard.scan_grid(ids, F.RATE, steps0, 100, STEP,
                               WINDOW) is not None
        cache = _grid_cache(shard)
        _assert_reconciled(cache)
        ev = device_metrics()["evictions"]
        c0 = ev.value(owner=cache.owner, reason="budget_overflow")
        # late window: covers the last block only; earlier blocks are
        # over budget and must go
        late0 = T0 + 290 * STEP
        assert shard.scan_grid(ids, F.RATE, late0, 8, STEP,
                               WINDOW) is not None
        assert cache.evictions > 0
        assert ev.value(owner=cache.owner,
                        reason="budget_overflow") > c0
        _assert_reconciled(cache)

    def test_epoch_purge_on_new_data_reconciles(self):
        ms, shard = _mk_shard("dw_rec3")
        ids = _ids(shard)
        steps0 = T0 + (K - 1) * STEP
        assert shard.scan_grid(ids, F.RATE, steps0, 40, STEP,
                               WINDOW) is not None
        cache = _grid_cache(shard)
        ev0 = device_metrics()["evictions"].value(owner=cache.owner,
                                                  reason="epoch_purge")
        # new samples freeze into the covered range -> stale blocks purge
        b = RecordBuilder(DEFAULT_SCHEMAS["prom-counter"])
        tags = {"__name__": "req_total", "instance": "i0", "_ws_": "w",
                "_ns_": "n"}
        for r in range(50, 60):
            b.add(int(T0 + r * STEP), [float(r)], tags)
        for off, c in enumerate(b.containers()):
            shard.ingest(decode_container(c, DEFAULT_SCHEMAS), off + 100)
        shard.flush_all()
        assert device_metrics()["evictions"].value(
            owner=cache.owner, reason="epoch_purge") > ev0
        _assert_reconciled(cache)
        # and the grid still serves (rebuilt blocks reconcile too)
        assert shard.scan_grid(ids, F.RATE, steps0, 40, STEP,
                               WINDOW) is not None
        _assert_reconciled(cache)

    def test_odp_churn_reconciles_and_registers_pool(self, tmp_path):
        from filodb_tpu.store.persistence import (DiskColumnStore,
                                                  DiskMetaStore)
        disk = DiskColumnStore(str(tmp_path / "c.db"))
        meta = DiskMetaStore(str(tmp_path / "m.db"))
        ms = TimeSeriesMemStore(disk, meta)
        ms_, shard = _mk_shard("dw_odp", ms=ms, groups_per_shard=2)
        ids = _ids(shard)
        steps0 = T0 + (K - 1) * STEP
        assert shard.scan_grid(ids, F.RATE, steps0, 40, STEP,
                               WINDOW) is not None
        cache = _grid_cache(shard)
        _assert_reconciled(cache)
        # page-out: evicting partitions purges their ledgered blocks
        assert shard.evict_partitions(3) == 3
        _assert_reconciled(cache)
        ev = device_metrics()["evictions"]
        assert ev.value(owner=shard._ledger_owner,
                        reason="epoch_purge") > 0
        # page back in (ODP), then the grid rebuilds from paged parts
        ids2 = _ids(shard)
        tags_list, _batch = shard.scan_batch(
            list(ids2) + shard.lookup_partitions(
                [ColumnFilter("_metric_", Equals("req_total"))],
                0, 2**62).missing_partkeys, 0, 2**62)
        assert shard.stats.partitions_paged >= 3
        pools = LEDGER.pools()
        assert shard._ledger_owner in pools
        assert pools[shard._ledger_owner]["bytes"] > 0
        assert pools[shard._ledger_owner]["budget"] == \
            shard.paged.max_bytes
        got = shard.scan_grid(_ids(shard), F.RATE, steps0, 40, STEP,
                              WINDOW)
        assert got is not None
        _assert_reconciled(cache)
        assert any(e["kind"] == "odp.pagein"
                   for e in FLIGHT.events(kind="odp.pagein"))

    def test_query_stats_carry_hbm_delta(self):
        """A cold grid query commits blocks; its QueryStats must show
        the positive residency delta, and a warm repeat ~zero."""
        from filodb_tpu.query.exec import ExecContext, _ACTIVE
        from filodb_tpu.query.model import QueryStats
        ms, shard = _mk_shard("dw_delta")
        ids = _ids(shard)
        steps0 = T0 + (K - 1) * STEP
        ctx = ExecContext(ms)
        _ACTIVE.ctx = ctx
        try:
            assert shard.scan_grid(ids, F.RATE, steps0, 40, STEP,
                                   WINDOW) is not None
        finally:
            _ACTIVE.ctx = None
        stats = QueryStats()
        ctx.fold_into(stats)
        cache = _grid_cache(shard)
        assert stats.hbm_resident_delta_bytes == \
            sum(_expected_grid_bytes(cache).values())
        ctx2 = ExecContext(ms)
        _ACTIVE.ctx = ctx2
        try:
            assert shard.scan_grid(ids, F.RATE, steps0, 40, STEP,
                                   WINDOW) is not None
        finally:
            _ACTIVE.ctx = None
        stats2 = QueryStats()
        ctx2.fold_into(stats2)
        assert stats2.hbm_resident_delta_bytes == 0


# ---------------------------------------------------------------------------
# HTTP endpoints
# ---------------------------------------------------------------------------


def _get_json(port, path, **params):
    qs = urllib.parse.urlencode(params)
    url = f"http://127.0.0.1:{port}{path}" + (f"?{qs}" if qs else "")
    try:
        with urllib.request.urlopen(url, timeout=10) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _post_json(port, path, **params):
    data = urllib.parse.urlencode(params).encode()
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}",
                                 data=data, method="POST")
    req.add_header("Content-Type", "application/x-www-form-urlencoded")
    with urllib.request.urlopen(req, timeout=10) as resp:
        return resp.status, json.loads(resp.read())


def _get_text(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=10) as resp:
        return resp.status, resp.read().decode()


@pytest.fixture(scope="module")
def server():
    from filodb_tpu.coordinator.planner import SingleClusterPlanner
    from filodb_tpu.core.schemas import DatasetOptions
    from filodb_tpu.http.server import DatasetBinding, FiloHttpServer
    from filodb_tpu.parallel.shardmap import ShardMapper, ShardStatus

    mapper = ShardMapper(1)
    mapper.register_node(range(1), "local")
    mapper.update_status(0, ShardStatus.ACTIVE)
    ms, _shard = _mk_shard("dw_http")
    planner = SingleClusterPlanner("dw_http", mapper, DatasetOptions(),
                                   spread_default=0)
    srv = FiloHttpServer()
    srv.bind_dataset(DatasetBinding("dw_http", ms, planner))
    port = srv.start()
    yield port, ms
    srv.shutdown()


class TestEndpoints:
    def _warm(self, port):
        code, body = _get_json(
            port, "/promql/dw_http/api/v1/query_range",
            query='sum(rate(req_total{_ws_="w",_ns_="n"}[5m]))',
            start=str((T0 + (K - 1) * STEP) // 1000),
            end=str((T0 + 45 * STEP) // 1000), step="60s", stats="true")
        assert code == 200 and body["data"]["result"]
        return body

    def test_admin_device_reconciles(self, server):
        port, ms = server
        self._warm(port)
        code, body = _get_json(port, "/admin/device")
        assert code == 200
        data = body["data"]
        shard = ms.shards("dw_http")[0]
        cache = _grid_cache(shard)
        gc.collect()
        owners = data["ledger"]["owners"]
        got = {fmt: row["bytes"] for fmt, row in
               owners.get(cache.owner, {}).items() if row["bytes"]}
        want = {fmt: n for fmt, n in _expected_grid_bytes(cache).items()
                if n}
        assert got == want
        rows = [r for r in data["arenas"]["dw_http"]
                if r["arena"] == "device-grid"]
        assert rows and rows[0]["bytes_resident"] > 0
        assert rows[0]["budget"] == cache.budget
        assert data["compile"]["programs"], "compile table empty"
        assert "devices" in data and "flight_recorder" in data

    def test_stats_carry_hbm_delta_field(self, server):
        port, _ms = server
        body = self._warm(port)
        samples = body["data"]["stats"]["samples"]
        assert "hbmResidentDeltaBytes" in samples

    def test_metrics_exposition_has_device_families(self, server):
        port, _ms = server
        self._warm(port)
        code, text = _get_text(port, "/metrics")
        assert code == 200
        assert "filodb_device_hbm_bytes{" in text
        assert "filodb_jit_compiles_total{" in text
        assert "filodb_device_evictions_total" in text \
            or "# TYPE filodb_device_evictions_total" in text
        assert "filodb_process_resident_memory_bytes" in text
        assert "filodb_process_open_fds" in text
        assert "filodb_process_threads" in text
        assert "filodb_process_uptime_seconds" in text
        assert "filodb_process_gc_collections{" in text

    def test_flightrecorder_endpoint(self, server):
        port, _ms = server
        self._warm(port)
        code, body = _get_json(port, "/admin/flightrecorder", limit=1000)
        assert code == 200
        kinds = {e["kind"] for e in body["data"]["events"]}
        assert "query.start" in kinds and "query.end" in kinds
        assert "jit.compile" in kinds
        code, body = _get_json(port, "/admin/flightrecorder",
                               kind="query.end", limit=5)
        assert all(e["kind"] == "query.end"
                   for e in body["data"]["events"])

    def test_admin_config_get_and_post(self, server):
        from filodb_tpu.utils.forensics import TRACE_STORE
        port, _ms = server
        code, body = _get_json(port, "/admin/config")
        assert code == 200
        data = body["data"]
        assert data["datasets"]["dw_http"]["device_cache_bytes"] > 0
        assert "slow-query-threshold-s" in data["observability"]
        old = TRACE_STORE.slow_threshold_s
        try:
            code, body = _post_json(port, "/admin/config",
                                    **{"slow-query-threshold-s": "7.5"})
            assert code == 200
            assert body["data"]["observability"][
                "slow-query-threshold-s"] == 7.5
            assert TRACE_STORE.slow_threshold_s == 7.5
        finally:
            TRACE_STORE.slow_threshold_s = old
        code, _body = _get_json(port, "/admin/config",
                                **{"slow-query-threshold-s": "-1"})
        assert code == 400


# ---------------------------------------------------------------------------
# kernel flight deck over HTTP: /admin/kernels, stats devicePrograms,
# /debug/device_profilez (ISSUE 15)
# ---------------------------------------------------------------------------


class TestKernelDeckEndpoints:
    def _warm(self, port, stats="true"):
        code, body = _get_json(
            port, "/promql/dw_http/api/v1/query_range",
            query='sum(rate(req_total{_ws_="w",_ns_="n"}[5m]))',
            start=str((T0 + (K - 1) * STEP) // 1000),
            end=str((T0 + 45 * STEP) // 1000), step="60s", stats=stats)
        assert code == 200 and body["data"]["result"]
        return body

    def test_device_programs_reconcile_with_device_compute(
            self, server, kt_config):
        """ISSUE 15 acceptance: on a sampled query the per-program
        devicePrograms seconds sum to (at most, within tolerance) the
        device_compute stage bucket that wraps the same launches."""
        port, _ms = server
        kt_config.configure(sample_1_in=1)
        self._warm(port)                       # compiles never fold
        body = self._warm(port)
        stats = body["data"]["stats"]
        dp = stats["devicePrograms"]
        assert dp, "sampled query carried no devicePrograms split"
        assert all(v >= 0 for v in dp.values())
        total = sum(dp.values())
        assert total > 0
        # the sampled block_until_ready waits run INSIDE the
        # device_compute wall-time window; tolerance covers the
        # perf_counter stamps around the wrapper
        assert total <= stats["timings"]["device_compute"] + 0.005

    def test_admin_kernels_joins_and_reconciles_exactly(self, server,
                                                        kt_config,
                                                        roof_table):
        port, _ms = server
        kt_config.configure(sample_1_in=1)
        self._warm(port)
        self._warm(port)
        code, body = _get_json(port, "/admin/kernels")
        assert code == 200
        data = body["data"]
        assert data["sample_1_in"] == 1
        assert data["hbm_roof_bytes_per_s"] == 1e9
        assert data["device_kind"] == devicewatch.device_kind()
        rows = {r["program"]: r for r in data["programs"]}
        # a devicestore program THIS test's 1-in-1 queries sampled
        # (earlier tests at the default rate leave bytes-only rows)
        served = [r for p, r in rows.items()
                  if p.startswith("devicestore.") and r["bytes_total"]
                  and r["ewma_device_s"] is not None]
        assert served, f"no sampled devicestore program: {sorted(rows)}"
        row = served[0]
        # the compile-table join and the live roofline position
        assert row["compiles"] >= 1
        assert row["ewma_device_s"] is not None
        assert row["roofline_fraction"] is not None \
            and row["roofline_fraction"] > 0
        # launches x sample-rate reconciliation, EXACT: the table's
        # launch count is counted on every launch, as is the counter
        m = device_metrics()["kernel_launches"]
        for program, r in rows.items():
            assert m.value(program=program) == r["launches"], program

    def test_roofline_degrades_and_row_flags_regression(self, server,
                                                        kt_config,
                                                        roof_table):
        """ISSUE 15 acceptance: an injected slowdown on the serving
        program degrades its /admin/kernels roofline fraction and flips
        the row's sentry state."""
        from filodb_tpu.integrity.faultinject import (
            clear_kernel_slowdown, inject_kernel_slowdown)
        port, _ms = server
        kt_config.configure(sample_1_in=1, baseline_min_samples=2,
                            regression_window_s=0.05,
                            regression_factor=1.5)
        launches0 = {r["program"]: r["launches"]
                     for r in KERNEL_TIMER.table()}
        for _ in range(4):
            self._warm(port)
        code, body = _get_json(port, "/admin/kernels")
        rows = {r["program"]: r for r in body["data"]["programs"]}
        # the program THIS query actually launches (in a full-suite run
        # other devicestore programs carry history but never launch
        # here, so slowing them would never sample)
        prog, before = next(
            (p, r) for p, r in rows.items()
            if p.startswith("devicestore.") and r["roofline_fraction"]
            and r["launches"] > launches0.get(p, 0))
        inject_kernel_slowdown(prog, 0.01)
        try:
            for _ in range(30):
                self._warm(port, stats="false")
                if _kt_row(prog)["regressed"]:
                    break
        finally:
            clear_kernel_slowdown(prog)
        code, body = _get_json(port, "/admin/kernels")
        row = {r["program"]: r
               for r in body["data"]["programs"]}[prog]
        assert row["regressed"] and row["episodes"] >= 1
        assert row["roofline_fraction"] < before["roofline_fraction"]
        # recover so the shared timer leaves the fixture healthy
        for _ in range(100):
            self._warm(port, stats="false")
            if not _kt_row(prog)["regressed"]:
                break
        assert not _kt_row(prog)["regressed"]

    def test_device_profilez_captures_and_shares_single_flight(self,
                                                               server):
        import os
        port, _ms = server
        code, body = _get_json(port, "/debug/device_profilez",
                               seconds="0.05")
        assert code == 200, body
        data = body["data"]
        assert os.path.isdir(data["trace_dir"])
        assert data["files"] >= 1, "trace capture produced no files"
        # ONE single-flight guard across BOTH profile surfaces: with
        # the lock held, host and device profiling each answer 503
        from filodb_tpu.utils import forensics
        assert forensics._PROFILE_LOCK.acquire(blocking=False)
        try:
            code, _b = _get_json(port, "/debug/profilez", seconds="0.05")
            assert code == 503
            code, _b = _get_json(port, "/debug/device_profilez",
                                 seconds="0.05")
            assert code == 503
        finally:
            forensics._PROFILE_LOCK.release()

    def test_a_capture_holds_the_leaf_spans_and_no_python_tracer(
            self, tmp_path):
        """/debug/device_profilez starts the profiler as benchmark/run.py
        does: the Python tracer off (it slows the threads it records),
        the host tracer keeping TraceMe events — so the leaf stage spans
        lie on the capture's host plane, and an enclosing stage does
        not."""
        import glob
        import os
        import threading
        from jax.profiler import ProfileData
        from filodb_tpu.utils import forensics
        from filodb_tpu.utils.observability import TRACER
        got = {}
        t = threading.Thread(target=lambda: got.update(
            forensics.device_profile(seconds=0.6,
                                     trace_root=str(tmp_path))))
        t.start()
        deadline = time.time() + 0.9
        while time.time() < deadline and t.is_alive():
            with TRACER.stage("device_compute", leaf=False):
                with TRACER.stage("grid.select"):
                    time.sleep(0.005)
        t.join()
        xplane = glob.glob(os.path.join(
            got["trace_dir"], "plugins", "profile", "*", "*.xplane.pb"))
        assert xplane, os.listdir(got["trace_dir"])
        names = {e.name
                 for p in ProfileData.from_file(xplane[0]).planes
                 if p.name.startswith("/host:")
                 for ln in p.lines for e in ln.events}
        assert "grid.select" in names
        assert "device_compute" not in names
        assert not any(n.startswith("$") for n in names), \
            "the Python tracer was on"

    def test_device_trace_dirs_are_retention_bounded(self, tmp_path):
        """Review fix: repeated captures must not fill the disk — at
        most DEVICE_TRACE_RETAIN capture dirs survive, oldest pruned."""
        import os
        from filodb_tpu.utils import forensics
        old = forensics.DEVICE_TRACE_RETAIN
        forensics.DEVICE_TRACE_RETAIN = 2
        try:
            for _ in range(4):
                got = forensics.device_profile(seconds=0.05,
                                               trace_root=str(tmp_path))
            assert got["retained"] == 2
            dirs = [e for e in os.listdir(tmp_path)
                    if e.startswith("trace-")]
            assert len(dirs) == 2, sorted(dirs)
            # the newest capture always survives its own prune
            assert os.path.basename(got["trace_dir"]) in dirs
        finally:
            forensics.DEVICE_TRACE_RETAIN = old

    def test_admin_config_kernel_knobs(self, server, kt_config):
        port, _ms = server
        code, body = _get_json(port, "/admin/config")
        assert code == 200
        obs = body["data"]["observability"]
        assert "kernel-sample-1-in" in obs
        assert not any("roof" in k for k in obs)   # a table, not a knob
        code, body = _post_json(port, "/admin/config",
                                **{"kernel-sample-1-in": "8",
                                   "kernel-regression-factor": "2.0",
                                   "kernel-baseline-min-samples": "5"})
        assert code == 200
        obs = body["data"]["observability"]
        assert obs["kernel-sample-1-in"] == 8
        assert obs["kernel-regression-factor"] == 2.0
        assert obs["kernel-baseline-min-samples"] == 5
        assert KERNEL_TIMER.sample_1_in == 8

    def test_metrics_exposition_has_kernel_families(self, server,
                                                    kt_config):
        port, _ms = server
        kt_config.configure(sample_1_in=1)
        self._warm(port)
        code, text = _get_text(port, "/metrics")
        assert code == 200
        assert "filodb_kernel_launches_total{" in text
        assert "filodb_kernel_device_seconds{" in text
        assert "filodb_kernel_roofline_fraction{" in text
