"""Data-plane observability (ISSUE 6): cardinality explorer, watermark
ledger, self-scrape, memo eviction, shard-health emission.

The load-bearing assertion is the PR 9-style reconciliation guarantee:
/admin/cardinality totals must match a full part-key-index walk exactly
under concurrent series create/evict/purge, and per-tenant counts must
agree with SeriesQuota occupancy."""

import json
import threading
import time
import urllib.error
import urllib.parse
import urllib.request

import numpy as np
import pytest

from filodb_tpu.core.record import RecordBuilder, decode_container
from filodb_tpu.core.schemas import DEFAULT_SCHEMAS
from filodb_tpu.http.server import DatasetBinding, FiloHttpServer
from filodb_tpu.memstore.cardinality import Ewma, build_report
from filodb_tpu.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.memstore.watermarks import WatermarkLedger
from filodb_tpu.parallel.shardmap import ShardMapper, ShardStatus
from filodb_tpu.utils.observability import REGISTRY
from filodb_tpu.workload.quota import SeriesQuota

BASE = 1_700_000_000_000
MAX = np.iinfo(np.int64).max


def _get(port, path, **params):
    qs = urllib.parse.urlencode(params)
    url = f"http://127.0.0.1:{port}{path}" + (f"?{qs}" if qs else "")
    try:
        with urllib.request.urlopen(url, timeout=30) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _one_row_container(tags, ts):
    b = RecordBuilder(DEFAULT_SCHEMAS["gauge"], container_size=1 << 16)
    b.add(int(ts), [1.0], tags)
    return list(b.containers())


# ---------------------------------------------------------------------------
# cardinality explorer
# ---------------------------------------------------------------------------


class TestCardinalityReconciliation:
    def test_report_matches_index_walk_under_concurrent_churn(self):
        """The acceptance-criteria e2e: mutators create/evict/purge
        while readers hammer /admin/cardinality; every mid-churn report
        is internally consistent (one atomic snapshot per shard), and
        at quiescence the totals match a full index walk and the
        SeriesQuota occupancy exactly."""
        ms = TimeSeriesMemStore()
        ms.setup("card", DEFAULT_SCHEMAS, 0)
        sh = ms.get_shard("card", 0)
        quota = SeriesQuota(dataset="card")
        sh.series_quota = quota

        srv = FiloHttpServer()
        srv.bind_dataset(DatasetBinding("card", ms, planner=None,
                                        quota=quota))
        port = srv.start()
        errors: list[str] = []
        stop = threading.Event()

        def mutate():
            off = 0
            for i in range(250):
                # 4 new series per round, 4 tenants
                for k in range(4):
                    tags = {"__name__": "churn_m", "u": f"s{i}_{k}",
                            "_ws_": "w", "_ns_": f"t{(i + k) % 4}"}
                    for c in _one_row_container(tags, BASE + i * 1000):
                        sh.ingest_container(c, off)
                        off += 1
                if i % 9 == 5:
                    # stop the oldest few, then evict them
                    for pid in list(sh.partitions)[:3]:
                        sh.index.update_end_time(pid, BASE + i * 1000)
                    sh.evict_partitions(3)
                if i % 13 == 7:
                    sh.purge_expired(retention_ms=60_000,
                                     now_ms=BASE + i * 1000)

        def read():
            while not stop.is_set():
                code, body = _get(port, "/admin/cardinality",
                                  dataset="card", topk=5)
                if code != 200:
                    errors.append(f"HTTP {code}: {body}")
                    return
                data = body["data"]
                if sum(data["tenants"].values()) \
                        != data["total_active_series"]:
                    errors.append(f"tenant sum != total: {data}")
                    return
                for row in data["shards"]:
                    if sum(row["tenants"].values()) != row["active_series"]:
                        errors.append(f"shard-level mismatch: {row}")
                        return

        readers = [threading.Thread(target=read) for _ in range(3)]
        mt = threading.Thread(target=mutate)
        for t in readers:
            t.start()
        mt.start()
        mt.join()
        stop.set()
        for t in readers:
            t.join()
        srv.shutdown()
        assert not errors, errors

        # quiescent: full index walk (ground truth from the raw tag
        # dicts, NOT the refcounts the report is built on)
        walk_tenants: dict[str, int] = {}
        for pid in list(sh.index._tags):
            tags = sh.index._tags[pid]
            t = tags.get("_ns_", "")
            walk_tenants[t] = walk_tenants.get(t, 0) + 1
        walk_total = len(sh.index._tags)
        assert walk_total > 0
        assert sh.stats.partitions_evicted > 0
        assert sh.stats.partitions_purged > 0

        report = build_report("card", ms.shards("card"), topk=5)
        assert report["total_active_series"] == walk_total
        assert report["tenants"] == walk_tenants
        # per-value label counts agree with a walk over every label
        snap_active, snap_labels = sh.index.cardinality_snapshot()
        walk_labels: dict[str, dict[str, int]] = {}
        for pid in list(sh.index._tags):
            for k, v in sh.index._tags[pid].items():
                walk_labels.setdefault(k, {})
                walk_labels[k][v] = walk_labels[k].get(v, 0) + 1
        assert snap_active == walk_total
        assert snap_labels == walk_labels
        # SeriesQuota occupancy agrees with the report's tenant counts
        assert quota.snapshot()["active"] == walk_tenants

    def test_churn_counters_and_rates(self):
        ms = TimeSeriesMemStore()
        ms.setup("churn2", DEFAULT_SCHEMAS, 0)
        sh = ms.get_shard("churn2", 0)
        for i in range(10):
            tags = {"__name__": "m", "u": str(i), "_ws_": "w", "_ns_": "n"}
            for c in _one_row_container(tags, BASE + i):
                sh.ingest_container(c, i)
        sh.purge_expired(retention_ms=1, now_ms=BASE + 10_000_000)
        assert sh.cardinality.created_total == 10
        assert sh.cardinality.removed_total == 10
        assert sh.cardinality.create_ewma.rate() > 0
        created = REGISTRY.counter("filodb_index_churn_created_total")
        assert created.value(dataset="churn2", shard=0) == 10
        removed = REGISTRY.counter("filodb_index_churn_removed_total")
        assert removed.value(dataset="churn2", shard=0,
                             reason="purge") == 10
        active = REGISTRY.gauge("filodb_index_cardinality_active_series")
        assert active.value(dataset="churn2", shard=0) == 0

    def test_topk_ranking_and_bounds(self):
        ms = TimeSeriesMemStore()
        ms.setup("rank", DEFAULT_SCHEMAS, 0)
        sh = ms.get_shard("rank", 0)
        off = 0
        for i in range(12):
            tags = {"__name__": "m", "hi_card": f"v{i}",
                    "lo_card": f"g{i % 2}", "_ws_": "w", "_ns_": "n"}
            for c in _one_row_container(tags, BASE + i):
                sh.ingest_container(c, off)
                off += 1
        report = build_report("rank", ms.shards("rank"), topk=2)
        row = report["shards"][0]
        # hi_card (12 values) must outrank lo_card (2 values)
        assert row["top_labels"][0]["label"] == "hi_card"
        assert row["top_labels"][0]["values"] == 12
        assert len(row["top_labels"]) == 2          # topk bounds labels
        assert len(row["top_labels"][0]["top_values"]) == 2  # and values

    def test_ewma_decays(self):
        e = Ewma(halflife_s=0.05)
        e.note(100)
        r0 = e.rate()
        assert r0 > 0
        time.sleep(0.15)
        assert e.rate() < r0 / 4


# ---------------------------------------------------------------------------
# watermark ledger
# ---------------------------------------------------------------------------


def _ingest_rows(sh, n, start_off=0):
    for i in range(n):
        tags = {"__name__": "wm", "u": str(i), "_ws_": "w", "_ns_": "n"}
        for c in _one_row_container(tags, BASE + i * 1000):
            sh.ingest_container(c, start_off + i)


class TestWatermarkLedger:
    def test_chain_and_lag(self):
        ms = TimeSeriesMemStore()
        ms.setup("wm1", DEFAULT_SCHEMAS, 0)
        sh = ms.get_shard("wm1", 0)
        _ingest_rows(sh, 20)
        sh.flush_all()
        wm = WatermarkLedger(node="n0")
        wm.watch("wm1", ms, end_offset_fn=lambda s: 25)
        row = wm.sample()["datasets"]["wm1"]["shards"][0]
        assert row["watermarks"]["ingested"] == 19
        assert row["watermarks"]["broker_end"] == 25
        # flush_all checkpoints at latest_offset on every group
        assert row["watermarks"]["flushed"] == 19
        assert row["watermarks"]["checkpoint"] == 19
        assert row["lag"]["rows"] == 5
        assert row["lag"]["seconds"] > 0
        g = REGISTRY.gauge("filodb_ingest_lag_rows")
        assert g.value(dataset="wm1", shard=0, node="n0") == 5
        off = REGISTRY.gauge("filodb_ingest_watermark_offset")
        assert off.value(dataset="wm1", shard=0, node="n0",
                         stage="broker_end") == 25

    def test_stall_fires_once_per_episode_and_rearms(self):
        ms = TimeSeriesMemStore()
        ms.setup("wm2", DEFAULT_SCHEMAS, 0)
        sh = ms.get_shard("wm2", 0)
        _ingest_rows(sh, 5)
        head = [20]
        wm = WatermarkLedger(stall_window_s=0.05, node="n1")
        wm.watch("wm2", ms, end_offset_fn=lambda s: head[0])
        stalls = REGISTRY.counter("filodb_ingest_stalls_total")
        before = stalls.value(dataset="wm2", shard=0, node="n1")
        assert wm.sample()["datasets"]["wm2"]["shards"][0]["stalled"] \
            is False
        time.sleep(0.06)
        assert wm.sample()["datasets"]["wm2"]["shards"][0]["stalled"] \
            is True
        wm.sample()  # still stalled; must not double-count
        assert stalls.value(dataset="wm2", shard=0, node="n1") \
            == before + 1
        from filodb_tpu.utils.devicewatch import FLIGHT
        evs = [e for e in FLIGHT.events(kind="ingest.stall")
               if e.get("dataset") == "wm2"]
        assert evs and evs[-1]["lag_rows"] > 0
        # progress re-arms: ingest more, then stall again -> 2nd episode
        _ingest_rows(sh, 5, start_off=5)
        assert wm.sample()["datasets"]["wm2"]["shards"][0]["stalled"] \
            is False
        time.sleep(0.06)
        assert wm.sample()["datasets"]["wm2"]["shards"][0]["stalled"] \
            is True
        assert stalls.value(dataset="wm2", shard=0, node="n1") \
            == before + 2

    def test_close_removes_exported_gauge_rows(self):
        """ISSUE 9 regression: a dead server's ledger rows — above all
        a lingering ``filodb_ingest_stalled=1`` — must leave the
        process registry on close, or the self-monitoring alert rules
        scraping it fire on a node that no longer exists."""
        ms = TimeSeriesMemStore()
        ms.setup("wmclose", DEFAULT_SCHEMAS, 0)
        _ingest_rows(ms.get_shard("wmclose", 0), 5)
        wm = WatermarkLedger(stall_window_s=0.01, node="nx")
        wm.watch("wmclose", ms, end_offset_fn=lambda s: 20)
        wm.sample()
        time.sleep(0.02)
        wm.sample()
        stalled = REGISTRY.gauge("filodb_ingest_stalled")
        assert stalled.value(dataset="wmclose", shard=0, node="nx") == 1

        def gauge_rows(dataset):
            # the LEDGER's gauge family only (the memstore's own
            # cardinality gauges have their own close path; counters,
            # ``stalls_total`` and the shard's ``series_total``, are
            # history)
            return [ln for ln in REGISTRY.expose_text().splitlines()
                    if f'dataset="{dataset}"' in ln
                    and ln.startswith("filodb_ingest_")
                    and not ln.split("{")[0].endswith("_total")]

        assert gauge_rows("wmclose")
        wm.close()
        # every GAUGE row is gone (the cumulative stalls_total counter
        # stays — counters are history, gauges are state)
        assert gauge_rows("wmclose") == []
        # unwatch alone drops that dataset's rows too
        ms2 = TimeSeriesMemStore()
        ms2.setup("wmun", DEFAULT_SCHEMAS, 0)
        _ingest_rows(ms2.get_shard("wmun", 0), 5)
        wm2 = WatermarkLedger(node="ny")
        wm2.watch("wmun", ms2, end_offset_fn=lambda s: 20)
        wm2.sample()
        assert gauge_rows("wmun")
        wm2.unwatch("wmun")
        assert gauge_rows("wmun") == []

    def test_caught_up_shard_never_stalls(self):
        ms = TimeSeriesMemStore()
        ms.setup("wm3", DEFAULT_SCHEMAS, 0)
        sh = ms.get_shard("wm3", 0)
        _ingest_rows(sh, 5)
        wm = WatermarkLedger(stall_window_s=0.01, node="n2")
        wm.watch("wm3", ms, end_offset_fn=lambda s: 5)  # head == ingested+1
        time.sleep(0.03)
        row = wm.sample()["datasets"]["wm3"]["shards"][0]
        assert row["lag"]["rows"] == 0 and row["stalled"] is False

    def test_admin_shards_endpoint_and_flush_snapshot(self):
        from filodb_tpu.memstore.flush import FlushScheduler
        ms = TimeSeriesMemStore()
        ms.setup("wm4", DEFAULT_SCHEMAS, 0)
        sh = ms.get_shard("wm4", 0)
        _ingest_rows(sh, 10)
        sched = FlushScheduler(sh, flush_interval_ms=60_000)
        sh.flush_scheduler = sched
        srv = FiloHttpServer(node_name="wm4-node")
        srv.bind_dataset(DatasetBinding("wm4", ms, planner=None))
        port = srv.start()
        try:
            code, body = _get(port, "/admin/shards")
            assert code == 200
            ds = body["data"]["datasets"]["wm4"]
            row = ds["shards"][0]
            assert row["watermarks"]["ingested"] == 9
            assert "flush" in row
            assert row["flush"]["pending"] == 0
            assert body["data"]["node"] == "wm4-node"
            assert ds["totals"]["queryable"] == 1
            # runtime stall-window knob via /admin/config
            code, body = _get(port, "/admin/config",
                              **{"ingest-stall-window-s": "7.5"})
            assert code == 200
            assert body["data"]["dataplane"]["ingest-stall-window-s"] == 7.5
            assert srv.watermarks.stall_window_s == 7.5
        finally:
            srv.shutdown()
            sched.close(flush_remaining=False)


# ---------------------------------------------------------------------------
# self-scrape
# ---------------------------------------------------------------------------


class TestSelfScrape:
    def test_parse_exposition_grammar(self):
        from filodb_tpu.gateway.selfscrape import parse_exposition
        text = (
            "# TYPE x counter\n"
            "x_total 41\n"
            'x_labeled{a="1",b="two"} 2.5\n'
            'x_esc{v="a\\"b\\\\c\\nd"} 1\n'
            'hist_bucket{le="+Inf"} 7\n'
            "weird_inf +Inf\n"
            "weird_nan NaN\n")
        got = {name: (labels, v)
               for name, labels, v in parse_exposition(text)}
        assert got["x_total"] == ({}, 41.0)
        assert got["x_labeled"][0] == {"a": "1", "b": "two"}
        assert got["x_esc"][0] == {"v": 'a"b\\c\nd'}
        assert got["hist_bucket"][0] == {"le": "+Inf"}
        assert got["weird_inf"][1] == float("inf")
        assert got["weird_nan"][1] != got["weird_nan"][1]  # NaN

    def test_scrape_publishes_through_gateway_path(self):
        from filodb_tpu.gateway.selfscrape import SelfScraper
        from filodb_tpu.gateway.server import ShardingPublisher
        g = REGISTRY.gauge("selfscrape_probe_gauge")
        g.set(42.5, role="probe")
        published: list = []
        mapper = ShardMapper(1)
        pub = ShardingPublisher(DEFAULT_SCHEMAS["gauge"], mapper,
                                lambda s, c: published.append(c), spread=0)
        sc = SelfScraper(pub, interval_s=60,
                         default_tags={"_ws_": "filodb", "_ns_": "node-x",
                                       "instance": "node-x"})
        n = sc.scrape_once()
        assert n > 10 and published
        # decode the containers back: the probe gauge must be present
        # with its exact value and merged tags
        found = []
        for c in published:
            for rec in decode_container(c, DEFAULT_SCHEMAS):
                if rec.tags.get("_metric_") == "selfscrape_probe_gauge":
                    found.append(rec)
        assert found
        rec = found[0]
        assert rec.values[0] == 42.5
        assert rec.tags["role"] == "probe"
        assert rec.tags["_ws_"] == "filodb"
        assert rec.tags["instance"] == "node-x"
        scrapes = REGISTRY.counter("filodb_selfscrape_scrapes_total")
        assert scrapes.value() >= 1

    def test_nonfinite_samples_skipped(self):
        from filodb_tpu.gateway.selfscrape import SelfScraper
        seen: list = []

        class Pub:
            def add_sample(self, metric, tags, ts, value):
                seen.append((metric, value))

            def flush(self):
                return 0

        sc = SelfScraper(Pub(), expose_fn=lambda: "a_inf +Inf\nb_ok 1\n")
        assert sc.scrape_once() == 1
        assert seen == [("b_ok", 1.0)]


# ---------------------------------------------------------------------------
# gateway memo eviction (satellite: no re-parse stampede on label flood)
# ---------------------------------------------------------------------------


class TestHeadMemoEviction:
    def test_evict_memo_half_keeps_newest(self):
        from filodb_tpu.gateway.influx import evict_memo_half
        memo = {f"k{i}": i for i in range(100)}
        evict_memo_half(memo)
        assert len(memo) == 50
        assert "k0" not in memo and "k99" in memo and "k50" in memo

    def test_label_flood_keeps_memo_bounded(self, monkeypatch):
        from filodb_tpu.gateway import influx
        monkeypatch.setattr(influx, "HEAD_MEMO_MAX", 16)
        memo: dict = {}
        # steady series first, then a flood of unique label values
        steady = "app_up,host=h0 value=1 1700000000000000000"
        recs = influx.parse_lines_fast(steady + "\n", memo)
        assert recs[0].tags == {"host": "h0"}
        flood = "\n".join(
            f"app_up,host=flood{i} value=1 1700000000000000000"
            for i in range(100))
        recs = influx.parse_lines_fast(flood + "\n", memo)
        assert len(recs) == 100
        # memo stayed bounded (never wiped to zero, never unbounded)
        assert 0 < len(memo) <= 16
        # the newest flood entries survived the evictions
        assert any(k.startswith("app_up,host=flood9") for k in memo)
        # and parses remain CORRECT after eviction churn
        recs = influx.parse_lines_fast(steady + "\n", memo)
        assert recs[0].tags == {"host": "h0"}
        assert recs[0].fields == {"value": 1.0}

    def test_gateway_series_memo_flood_bounded(self, monkeypatch):
        from filodb_tpu.gateway import influx
        from filodb_tpu.gateway.server import ShardingPublisher
        monkeypatch.setattr(influx, "HEAD_MEMO_MAX", 32)
        mapper = ShardMapper(2)
        pub = ShardingPublisher(DEFAULT_SCHEMAS["gauge"], mapper,
                                lambda s, c: None, spread=0)
        total = 0
        for burst in range(4):
            lines = "\n".join(
                f"flood_m,host=b{burst}x{i} value=1.0 "
                f"1700000000000000000" for i in range(50))
            total += pub.ingest_influx_batch(lines + "\n")
        assert total == 200
        assert 0 < len(pub._series_memo) <= 32
        assert pub.parse_errors == 0


# ---------------------------------------------------------------------------
# shard-health emission (satellite: ShardMapper status transitions)
# ---------------------------------------------------------------------------


class TestShardMapperHealth:
    def test_lifecycle_queryable_semantics(self):
        m = ShardMapper(4, dataset="health1")
        assert m.status(0) is ShardStatus.UNASSIGNED
        assert not m.status(0).queryable
        m.register_node([0], "node-a")
        assert m.status(0) is ShardStatus.ASSIGNED
        assert not m.status(0).queryable
        m.update_status(0, ShardStatus.RECOVERY, progress=40)
        assert m.status(0).queryable          # recovery serves reads
        assert m.state(0).recovery_progress == 40
        m.update_status(0, ShardStatus.ACTIVE)
        assert m.status(0).queryable
        assert m.state(0).recovery_progress == 0
        m.update_status(0, ShardStatus.DOWN)
        assert not m.status(0).queryable
        assert m.active_shards() == []

    def test_unassign_resets_progress(self):
        m = ShardMapper(2, dataset="health2")
        m.register_node([1], "n")
        m.update_status(1, ShardStatus.RECOVERY, progress=70)
        m.unassign(1)
        st = m.state(1)
        assert st.status is ShardStatus.UNASSIGNED
        assert st.recovery_progress == 0
        assert st.node is None

    def test_update_status_emits_metric_and_event(self):
        from filodb_tpu.utils.devicewatch import FLIGHT
        m = ShardMapper(2, dataset="health3")
        m.register_node([0], "n")
        code = REGISTRY.gauge("filodb_shard_status_code")
        prog = REGISTRY.gauge("filodb_shard_recovery_progress")
        trans = REGISTRY.counter("filodb_shard_status_transitions_total")
        before = trans.value(dataset="health3", status="Recovery")
        m.update_status(0, ShardStatus.RECOVERY, progress=55)
        assert code.value(dataset="health3", shard=0) == 2
        assert prog.value(dataset="health3", shard=0) == 55
        assert trans.value(dataset="health3",
                           status="Recovery") == before + 1
        evs = [e for e in FLIGHT.events(kind="shard.status")
               if e.get("dataset") == "health3"]
        assert evs and evs[-1]["status"] == "Recovery" \
            and evs[-1]["prev"] == "Assigned"
        # re-applying the same status (status-poller sweeps) is silent
        n_evs = len(FLIGHT.events(kind="shard.status"))
        m.update_status(0, ShardStatus.RECOVERY, progress=55)
        assert len(FLIGHT.events(kind="shard.status")) == n_evs
        assert trans.value(dataset="health3",
                           status="Recovery") == before + 1
        # progress-only change refreshes the gauge without a transition
        m.update_status(0, ShardStatus.RECOVERY, progress=80)
        assert prog.value(dataset="health3", shard=0) == 80
        assert trans.value(dataset="health3",
                           status="Recovery") == before + 1

    def test_anonymous_mapper_stays_silent(self):
        from filodb_tpu.utils.devicewatch import FLIGHT
        n_evs = len(FLIGHT.events(kind="shard.status"))
        m = ShardMapper(2)  # no dataset: ad-hoc tests
        m.register_node([0], "n")
        m.update_status(0, ShardStatus.ACTIVE)
        assert len(FLIGHT.events(kind="shard.status")) == n_evs


# ---------------------------------------------------------------------------
# CLI verbs
# ---------------------------------------------------------------------------


class TestCliVerbs:
    def test_cardinality_report_and_shards(self, capsys):
        from filodb_tpu.cli import main as cli_main
        ms = TimeSeriesMemStore()
        ms.setup("cliq", DEFAULT_SCHEMAS, 0)
        sh = ms.get_shard("cliq", 0)
        for i in range(6):
            tags = {"__name__": "m", "u": str(i), "_ws_": "w",
                    "_ns_": f"t{i % 2}"}
            for c in _one_row_container(tags, BASE + i):
                sh.ingest_container(c, i)
        srv = FiloHttpServer()
        srv.bind_dataset(DatasetBinding("cliq", ms, planner=None))
        port = srv.start()
        try:
            assert cli_main(["cardinality-report", "--server",
                             f"http://127.0.0.1:{port}",
                             "--dataset", "cliq", "--topk", "3"]) == 0
            out = capsys.readouterr().out
            assert "6 active series" in out
            assert "tenant t0" in out and "tenant t1" in out
            assert cli_main(["cardinality-report", "--server",
                             f"http://127.0.0.1:{port}",
                             "--dataset", "cliq", "--json"]) == 0
            data = json.loads(capsys.readouterr().out)
            assert data["total_active_series"] == 6
            assert cli_main(["shards", "--server",
                             f"http://127.0.0.1:{port}",
                             "--dataset", "cliq"]) == 0
            body = json.loads(capsys.readouterr().out)
            shards = body["data"]["datasets"]["cliq"]["shards"]
            assert shards[0]["watermarks"]["ingested"] == 5
            # unknown dataset surfaces the server's error, exit 1
            assert cli_main(["cardinality-report", "--server",
                             f"http://127.0.0.1:{port}",
                             "--dataset", "nope"]) == 1
            capsys.readouterr()
        finally:
            srv.shutdown()


# ---------------------------------------------------------------------------
# review-fix regressions
# ---------------------------------------------------------------------------


class TestReviewFixes:
    def test_steady_head_survives_interleaved_flood(self, monkeypatch):
        """Memo hits refresh recency, so a steady series touched every
        batch stays cached across flood-driven evictions (insertion
        order would evict the fleet first — the stampede)."""
        from filodb_tpu.gateway import influx
        monkeypatch.setattr(influx, "HEAD_MEMO_MAX", 16)
        memo: dict = {}
        steady = "fleet_up,host=h0 value=1 1700000000000000000"
        influx.parse_lines_fast(steady + "\n", memo)
        for burst in range(10):   # each burst overflows at least once
            flood = "\n".join(
                f"fleet_up,host=fl{burst}x{i} value=1 1700000000000000000"
                for i in range(12))
            influx.parse_lines_fast(flood + "\n", memo)
            # steady traffic between floods: the hit must re-rank it
            influx.parse_lines_fast(steady + "\n", memo)
            assert "fleet_up,host=h0" in memo, f"evicted at burst {burst}"
        assert len(memo) <= 16

    def test_tenant_gauge_row_removed_when_tenant_drains(self):
        from filodb_tpu.memstore.cardinality import sample_tenant_gauges
        ms = TimeSeriesMemStore()
        ms.setup("drain", DEFAULT_SCHEMAS, 0)
        sh = ms.get_shard("drain", 0)
        off = 0
        for tenant, n in (("keep", 3), ("gone", 2)):
            for i in range(n):
                tags = {"__name__": "m", "u": f"{tenant}{i}",
                        "_ws_": "w", "_ns_": tenant}
                for c in _one_row_container(tags, BASE + i):
                    sh.ingest_container(c, off)
                    off += 1
        sample_tenant_gauges("drain", ms.shards("drain"))
        gauge = REGISTRY.gauge("filodb_index_cardinality_tenant_series")
        assert gauge.value(dataset="drain", tenant="gone") == 2
        # drain tenant "gone": stop + evict its series
        for pid in list(sh.partitions):
            if sh.index.tags(pid)["_ns_"] == "gone":
                sh.index.update_end_time(pid, BASE)
        sh.evict_partitions(2)
        merged = sample_tenant_gauges("drain", ms.shards("drain"))
        assert merged == {"keep": 3}
        assert gauge.value(dataset="drain", tenant="gone") == 0.0
        rows = [ln for ln in gauge.expose() if 'dataset="drain"' in ln]
        assert not any('tenant="gone"' in ln for ln in rows), rows

    def test_shard_filtered_report_does_not_clobber_gauges(self):
        from filodb_tpu.memstore.cardinality import build_report
        ms = TimeSeriesMemStore()
        for s in (0, 1):
            ms.setup("fleet", DEFAULT_SCHEMAS, s)
        off = 0
        for s in (0, 1):
            sh = ms.get_shard("fleet", s)
            for i in range(4):
                tags = {"__name__": "m", "u": f"s{s}_{i}",
                        "_ws_": "w", "_ns_": "tX"}
                for c in _one_row_container(tags, BASE + i):
                    sh.ingest_container(c, off)
                    off += 1
        build_report("fleet", ms.shards("fleet"))   # full: sets gauges
        gauge = REGISTRY.gauge("filodb_index_cardinality_tenant_series")
        assert gauge.value(dataset="fleet", tenant="tX") == 8
        rep = build_report("fleet", ms.shards("fleet"), shard_num=0)
        assert rep["tenants"] == {"tX": 4}          # filtered view...
        assert gauge.value(dataset="fleet", tenant="tX") == 8  # ...gauge not

    def test_concurrent_samples_fire_one_stall(self):
        """Sampler thread + inline /admin/shards requests racing across
        the stall boundary must still count ONE episode."""
        ms = TimeSeriesMemStore()
        ms.setup("race", DEFAULT_SCHEMAS, 0)
        sh = ms.get_shard("race", 0)
        _ingest_rows(sh, 3)
        wm = WatermarkLedger(stall_window_s=0.05, node="rc")
        wm.watch("race", ms, end_offset_fn=lambda s: 50)
        stalls = REGISTRY.counter("filodb_ingest_stalls_total")
        before = stalls.value(dataset="race", shard=0, node="rc")
        wm.sample()                 # arm the episode
        time.sleep(0.07)
        barrier = threading.Barrier(8)

        def racer():
            barrier.wait()
            for _ in range(5):
                wm.sample()

        threads = [threading.Thread(target=racer) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert stalls.value(dataset="race", shard=0, node="rc") \
            == before + 1
