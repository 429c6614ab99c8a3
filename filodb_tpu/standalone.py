"""Standalone server: wire every subsystem into one process.

Capability match for the reference's FiloServer main (reference:
standalone/src/main/scala/filodb.standalone/FiloServer.scala:39,91 —
coordinatorActor -> metaStore.initialize -> cluster bootstrap -> cluster
singleton/shard assignment -> HTTP server -> SimpleProfiler.launch),
driven by a JSON config instead of HOCON:

    {
      "node": "node-0",
      "data-dir": "/var/filodb",          # omit for in-memory only
      "http-port": 8080,
      "gateway-port": 8009,               # omit to disable the Influx edge
      "broker": {"port": 9092, "data-dir": "/var/filodb/broker"},
                                          # embedded message broker (omit
                                          # to use an external one / none)
      "profiler": false,
      "workload": {"min-remote-budget-ms": 5},
                                          # node-wide workload knobs
      "result-cache": {                   # ISSUE 12 (doc/query-engine.md):
                                          # chunk-aligned partial
                                          # memoization + incremental
                                          # instant windows, every
                                          # dataset incl. rollup tiers
        "enabled": true, "max-bytes": 67108864,
        "segment": "1h",                  # default: the flush interval
        "instant": true
      },
      "coldstore": {                      # ISSUE 16 (doc/coldstore.md):
                                          # object-store cold tier —
                                          # flushed/rolled chunks age out
                                          # of local sqlite into the
                                          # bucket; queries page them
                                          # back on demand (CRC-verified)
        "enabled": true,
        "bucket-dir": "/var/filodb/coldstore",
                                          # default: {data-dir}/coldstore
        "retention": "30d",               # age-out cutoff; omit/0 =
                                          # manual only (cli age-out)
        "tick-interval-s": 3600,
        "fetch-timeout-s": 30,            # offline cap; queries use the
                                          # tighter deadline budget
        "datasets": ["prom_ds_3600000"]   # restrict; omit = all
      },
      "dataplane": {                      # ISSUE 6 (doc/observability.md)
        "watermark-sample-interval-s": 10,
        "ingest-stall-window-s": 30,
        "self-scrape": {"enabled": false, "interval-s": 10,
                        "dataset": "_system", "num-shards": 1}
      },
      "rules": {                          # ISSUE 9 (doc/rules.md)
        "groups": [...],                  # inline rule groups
        "files": ["/etc/filodb/rules.json"],
        "notifier": {"url": "http://alertmanager:9093/api/v2/alerts",
                     "timeout-s": 5, "retries": 3, "backoff-s": 0.25},
        "self-monitoring": {"enabled": true, "interval": "15s",
                            "for": "30s"}
                                          # the shipped pack over the
                                          # _system dataset; defaults on
                                          # whenever self-scrape is on
      },
      "datasets": [{
        "name": "prom", "num-shards": 4, "min-num-nodes": 1,
        "schema": "gauge", "spread": 1,
        "replication-factor": 1,          # ISSUE 7 (doc/ha.md): >1 puts
                                          # each shard on that many nodes
        "source": {"factory": "kafka", "host": "127.0.0.1",
                   "port": 9092, "topic": "prom"},
                                          # omit for the in-proc queue
        "store": {"flush-interval": "1h", "groups-per-shard": 8},
        "rollup": {                       # ISSUE 11 (doc/rollup.md):
                                          # continuous raw->1m->15m->1h
                                          # tiering + resolution-routed
                                          # queries; omit to disable
          "resolutions": ["1m", "15m", "1h"],
          "tick-interval-s": 30,
          "raw-retention": "0"            # 0 = raw keeps everything
        },
        "workload": {                     # ISSUE 5 (doc/workload.md);
                                          # every knob has a default —
                                          # the block is optional
          "admission": {"max-inflight-cost": 10000,
                        "tenant-max-concurrent": 32,
                        "priority-shares": {"low": 0.5, "default": 0.8,
                                            "high": 1.0}},
          "quota": {"tenant-label": "_ns_",
                    "default-max-series": 1000000,
                    "overrides": {"App-9": 1000}},
          "dispatch": {"timeout-cap-s": 60, "retries": 2,
                       "hedge": false}
        }
      }]
    }
"""

from __future__ import annotations

import json
import os
import pathlib
import signal
import sys
import threading
from typing import Optional

from filodb_tpu.coordinator.cluster import (FailureDetector, ShardManager,
                                            StatusPoller)
from filodb_tpu.coordinator.node import NodeCoordinator
from filodb_tpu.coordinator.planner import SingleClusterPlanner
from filodb_tpu.core.schemas import DEFAULT_SCHEMAS, DatasetOptions
from filodb_tpu.core.storeconfig import StoreConfig
from filodb_tpu.gateway.server import GatewayServer, ShardingPublisher
from filodb_tpu.http.server import DatasetBinding, FiloHttpServer
from filodb_tpu.ingest.stream import QueueStreamFactory
from filodb_tpu.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.utils.observability import REGISTRY, SimpleProfiler


class FiloServer:
    """One node: stores + shard manager + ingestion + HTTP (+ gateway)."""

    def __init__(self, config: dict):
        self.config = config
        self.node = config.get("node", "node-0")
        data_dir = config.get("data-dir")
        if data_dir:
            from filodb_tpu.store.persistence import (DiskColumnStore,
                                                      DiskMetaStore)
            self.colstore = DiskColumnStore(f"{data_dir}/chunks.db")
            self.metastore = DiskMetaStore(f"{data_dir}/meta.db")
        else:
            from filodb_tpu.store.columnstore import NullColumnStore
            from filodb_tpu.store.metastore import InMemoryMetaStore
            self.colstore = NullColumnStore()
            self.metastore = InMemoryMetaStore()
        # cold tier (ISSUE 16, doc/coldstore.md): object-bucket chunk
        # archive behind the local store.  The TieredColumnStore wrap
        # happens BEFORE the memstore exists, so ODP paging, flushes and
        # the split controller all see one merged ColumnStore; age-out
        # itself runs against the unwrapped local store.
        self.local_colstore = self.colstore
        self.cold_store = None
        self.ageout = None
        self._ageout_stop = threading.Event()
        self._ageout_thread: Optional[threading.Thread] = None
        cs_conf = config.get("coldstore") or {}
        if data_dir and cs_conf.get("enabled"):
            from filodb_tpu.coldstore import (AgeOutManager, ColdChunkStore,
                                              LocalFSBucket,
                                              TieredColumnStore)
            bucket_dir = cs_conf.get("bucket-dir") \
                or f"{data_dir}/coldstore"
            self.cold_store = ColdChunkStore(
                LocalFSBucket(bucket_dir),
                fetch_timeout_s=float(cs_conf.get("fetch-timeout-s",
                                                  30.0)))
            self.colstore = TieredColumnStore(self.local_colstore,
                                              self.cold_store)
            self.ageout = AgeOutManager(self.local_colstore,
                                        self.cold_store,
                                        metastore=self.metastore)
        self.memstore = TimeSeriesMemStore(self.colstore, self.metastore)
        self.manager = ShardManager(
            reassignment_min_interval_ms=int(
                config.get("reassignment-min-interval-ms", 0)))
        self.failure_detector = FailureDetector(
            self.manager,
            timeout_ms=int(config.get("failure-detector-timeout-ms",
                                      10_000)))
        self.coordinator = NodeCoordinator(self.node, self.memstore)
        self.stream_factory = QueueStreamFactory()
        self.http = FiloHttpServer(port=config.get("http-port", 0),
                                   node_name=self.node,
                                   shard_manager=self.manager,
                                   running_shards=self._running_shards)
        self.gateways: list[GatewayServer] = []
        self.broker = None  # embedded BrokerServer when configured
        self.query_schedulers: dict[str, object] = {}
        self.admission_controllers: dict[str, object] = {}
        self.status_poller: Optional[StatusPoller] = None
        self.profiler: Optional[SimpleProfiler] = None
        # data-plane observability (ISSUE 6): watermark ledger + sampler
        # + optional self-telemetry scraper; the remote-write publishers
        # per dataset double as the self-scrape ingest edge
        self.watermarks = None
        self.watermark_sampler = None
        self.selfscraper = None
        # fleet workload insights (ISSUE 19): per-fingerprint ledger +
        # tenant SLO tracker + fleet aggregator behind /admin/insights
        # and /admin/fleet; wired in _setup_insights()
        self.slo_tracker = None
        self.insights_fleet = None
        # rule engine (ISSUE 9): continuous recording/alerting rules
        # evaluated through the normal query path (doc/rules.md)
        self.rule_engine = None
        self.rule_notifier = None
        # rollup engine (ISSUE 11, doc/rollup.md): continuous
        # raw->1m->15m->1h tiering into <ds>_ds_<res> datasets +
        # resolution-routed serving; created on the first dataset with
        # a "rollup" block
        self.rollup_engine = None
        # cluster-wide rollup tier closure gossip (ROADMAP 2b): peers'
        # /__health rollup payloads land here via the StatusPoller so
        # the resolution router stitches at the CLUSTER boundary
        from filodb_tpu.memstore.watermarks import TierWatermarks
        self.tier_watermarks = TierWatermarks(node=self.node)
        # query-frontend result cache (ISSUE 12, doc/query-engine.md):
        # one ResultCache per dataset (tiers included), embedded in the
        # serving planner; the top-level "result-cache" block opts in
        self.result_caches: dict[str, object] = {}
        self.write_publishers: dict[str, ShardingPublisher] = {}
        # dataset -> raw container publish fn (queue push / broker
        # produce / ReplicaFanout): the rollup engine emits rolled
        # containers through the TIER dataset's publish path so they
        # ride the same replication as any ingest
        self._publish_fns: dict[str, object] = {}
        self._global_gateway_claimed = False
        # datasets fed by the in-proc queue: the only legal targets of
        # the replica container-push edge (POST /ingest, ISSUE 7)
        self._queue_push_datasets: set = set()
        # dual-write fanouts, retained so shutdown can stop their peer
        # delivery lanes (a dead node must not keep POSTing to peers)
        self._replica_fanouts: list = []
        # elastic resharding (ISSUE 13, coordinator/split.py): live
        # power-of-two shard splits.  Per-dataset transport/spread/tier
        # maps feed the controller; the memstore setup hook installs the
        # split half-filters on shards the instant they are created.
        self._transports: dict[str, str] = {}
        self._spreads: dict[str, int] = {}
        self._tiers: dict[str, list] = {}
        from filodb_tpu.coordinator.split import SplitController
        self.split_controller = SplitController(
            self.node, self.manager, self.memstore, self.colstore,
            self.metastore,
            peers=self.config.get("peers", {}),
            resync=self.resync_all,
            transport_for=lambda ds: self._transports.get(ds, "queue"),
            tiers_for=lambda ds: list(self._tiers.get(ds, ())),
            fresh_nodes=self.failure_detector.fresh_nodes,
            spread_for=lambda ds: self._spreads.get(ds, 1))
        self.memstore.shard_setup_hook = self._on_shard_setup
        self.http.split = self.split_controller
        self.http.split_progress = self.split_controller.split_progress
        # (dataset, shard) -> first legal push offset (above persisted
        # checkpoints), resolved once per shard on first peer push
        self._push_offset_floor: dict = {}
        self.http.ingest_sink = self._ingest_push
        self._started = threading.Event()

    def _ingest_push(self, dataset: str, shard: int,
                     container: bytes) -> int:
        """Receiver side of the replica dual-write fanout: a peer's
        container lands on this node's in-proc ingest queue.  The
        stream's offset numbering is fast-forwarded past this node's
        persisted checkpoints FIRST — a push landing before the
        restarted consumer's own ``create(offset=resume_from)`` would
        otherwise be numbered below the recovery watermark and silently
        skipped as already-persisted."""
        if dataset not in self._queue_push_datasets:
            raise ValueError(
                f"dataset {dataset!r} does not accept container pushes "
                f"(broker-sourced or unknown)")
        # total_shards: a peer that committed a split before this node
        # adopted it may already push child-shard containers (ISSUE 13)
        num_shards = self.manager.mapper(dataset).total_shards
        if not 0 <= shard < num_shards:
            # out-of-range pushes would ACK into a consumerless queue
            # (silent loss + unbounded memory).  A valid shard this
            # node does not CURRENTLY hold is accepted on purpose —
            # membership gossip may lag the sender's view, and the
            # queue is drained once the replica assignment lands.
            raise ValueError(
                f"shard {shard} out of range for {dataset!r} "
                f"({num_shards} shards)")
        stream = self.stream_factory.stream_for(dataset, shard)
        key = (dataset, shard)
        floor = self._push_offset_floor.get(key)
        if floor is None:
            try:
                cps = self.metastore.read_checkpoints(dataset, shard)
            except Exception:  # noqa: BLE001 — meta store not ready
                # transient failure: use 0 for THIS push but do not
                # cache it — a cached 0 would defeat the fast-forward
                # forever even after the metastore becomes readable
                cps = None
            if cps is None:
                floor = 0
            else:
                floor = self._push_offset_floor[key] = \
                    (max(cps.values()) + 1) if cps else 0
        if floor:
            stream.ensure_offset(floor)
        return stream.push(container)

    @staticmethod
    def _device_count() -> int:
        try:
            import jax
            return jax.local_device_count()
        except Exception:  # noqa: BLE001 — no backend: host-only serving
            return 1

    def _running_shards(self, dataset: str) -> list[int]:
        ic = self.coordinator.ingestion.get(dataset)
        return ic.running_shards() if ic is not None else []

    def _on_shard_setup(self, dataset: str, shard) -> None:
        """memstore hook: every freshly-created shard picks up its split
        policy (half filters) before any ingest, and raw-dataset shards
        born from a split attach to the live rollup engine so their
        flushes tier exactly like their parents'."""
        self.split_controller.on_shard_setup(dataset, shard)
        eng = self.rollup_engine
        if eng is not None and dataset in eng.datasets() \
                and shard.rollup_listener is None:
            try:
                eng.attach_shard(dataset, shard)
            except Exception:  # noqa: BLE001 — engine mid-shutdown
                pass

    def resync_all(self) -> None:
        """Reconcile every dataset's running shards with the mapper,
        holding back split children whose local clone has not landed
        (they would replay from nothing)."""
        for ds in self.manager.datasets():
            shards = self.manager.mapper(ds).runnable_shards_for_node(
                self.node)
            shards = self.split_controller.startable_shards(ds, shards)
            self.coordinator.resync(ds, shards)

    def start(self) -> int:
        """Bring the node up; returns the HTTP port."""
        broker_conf = self.config.get("broker")
        if broker_conf is not None:
            from filodb_tpu.ingest.broker import BrokerServer
            self.broker = BrokerServer(
                port=int(broker_conf.get("port", 0)),
                data_dir=broker_conf.get("data-dir"))
            self.broker.start()
        self.metastore.initialize()
        # in-flight split records load BEFORE datasets: each dataset's
        # mapper replays its persisted split topology at setup, so a
        # restarted coordinator resumes (or can abort) instead of
        # wedging mid-split (ISSUE 13)
        self.split_controller.load_persisted()
        self.failure_detector.heartbeat(self.node)
        up = REGISTRY.gauge("filodb_node_up")
        up.set(1.0, node=self.node)
        # slow-query forensics threshold (seconds); completed queries
        # slower than this keep their span tree in /admin/slowlog.
        # Runtime-adjustable afterwards via POST /admin/config.
        thr = self.config.get("slow-query-threshold-s")
        if thr is not None:
            from filodb_tpu.utils.forensics import TRACE_STORE
            TRACE_STORE.slow_threshold_s = float(thr)
        # device-resource observability (ISSUE 4): storm-detector tuning
        # + flight-recorder sizing from the "devicewatch" config block,
        # and the crash hooks that dump the black box on an unhandled
        # exception shutdown
        from filodb_tpu.utils import devicewatch
        devicewatch.configure(self.config.get("devicewatch"))
        devicewatch.install_crash_hooks()
        # kernel flight deck (ISSUE 15): regression-sentry baselines
        # persist in the metastore KV (ratcheted downward only), so a
        # restart does not relearn a regressed program's slow state as
        # its baseline — the persisted healthy floor wins the merge
        _meta = self.metastore
        devicewatch.KERNEL_TIMER.attach_baseline_store(
            load_fn=lambda: {
                k.split(":", 1)[1]: float(v)
                for k, v in _meta.list_kv("kernel_baseline:").items()},
            save_fn=lambda program, seconds: _meta.write_kv(
                f"kernel_baseline:{program}", repr(float(seconds))))
        # node-wide workload knob: the /execplan refusal floor guards
        # ONE HTTP server, so it lives at the config top level (a
        # per-dataset spelling would silently be last-bound-wins)
        wl_top = self.config.get("workload", {})
        if "min-remote-budget-ms" in wl_top:
            self.http.min_remote_budget_ms = int(
                wl_top["min-remote-budget-ms"])
        # data-plane observability (ISSUE 6, doc/observability.md):
        # the watermark ledger exists BEFORE datasets so _setup_dataset
        # can watch each one with its broker/queue end-offset source
        from filodb_tpu.memstore.watermarks import (WatermarkLedger,
                                                    WatermarkSampler)
        dp = self.config.get("dataplane", {})
        self.watermarks = WatermarkLedger(
            stall_window_s=float(dp.get("ingest-stall-window-s", 30.0)),
            node=self.node)
        self.http.watermarks = self.watermarks

        for ds_conf in self.config.get("datasets", []):
            self._setup_dataset(ds_conf)

        # self-telemetry (ISSUE 6 pillar 3): scrape this node's own
        # exposition into a Prometheus-schema dataset through the normal
        # gateway ingest path, so node health is PromQL-queryable
        ss = dp.get("self-scrape") or {}
        if ss.get("enabled"):
            sys_ds = ss.get("dataset", "_system")
            if sys_ds not in self.manager.datasets():
                # the synthesized dataset never claims the node's global
                # Influx gateway port — that edge belongs to user data
                claimed = self._global_gateway_claimed
                self._global_gateway_claimed = True
                try:
                    self._setup_dataset({
                        "name": sys_ds,
                        "num-shards": int(ss.get("num-shards", 1)),
                        "min-num-nodes": 1, "schema": "gauge", "spread": 0,
                        "store": ss.get("store", {})})
                finally:
                    self._global_gateway_claimed = claimed
            from filodb_tpu.gateway.selfscrape import SelfScraper
            self.selfscraper = SelfScraper(
                self.write_publishers[sys_ds],
                interval_s=float(ss.get("interval-s", 10.0)),
                default_tags={"_ws_": "filodb", "_ns_": self.node,
                              "instance": self.node})
            self.selfscraper.start()
        self.watermark_sampler = WatermarkSampler(
            self.watermarks,
            interval_s=float(dp.get("watermark-sample-interval-s", 10.0)))
        self.watermark_sampler.start()

        self._setup_insights()
        self._setup_rules(ss)
        if self.rollup_engine is not None:
            self.rollup_engine.start()

        # cold-tier age-out loop (ISSUE 16): periodic retention passes
        # move closed local chunks into the bucket.  Only when a
        # retention is configured — without one the tier is read/manual
        # only (cli.py age-out)
        cs_conf = self.config.get("coldstore") or {}
        if self.ageout is not None and cs_conf.get("retention") \
                and str(cs_conf["retention"]) not in ("0", ""):
            from filodb_tpu.http.model import parse_duration_ms
            retention_ms = parse_duration_ms(str(cs_conf["retention"]))
            if retention_ms > 0:
                self._ageout_thread = threading.Thread(
                    target=self._ageout_loop,
                    args=(retention_ms,
                          float(cs_conf.get("tick-interval-s", 3600.0))),
                    name="coldstore-ageout", daemon=True)
                self._ageout_thread.start()

        port = self.http.start()
        self.split_controller.start()
        peers = self.config.get("peers", {})
        if peers:
            # cross-node status gossip + automatic failover (reference:
            # StatusActor/ShardMapper snapshots + Akka failure detector)
            def resync_all():
                # split participant duties first: an adopted topology
                # may need child clones before the resync can start
                # their consumers (ISSUE 13)
                self.split_controller.reconcile()
                self.resync_all()

            def local_watermarks(ds: str) -> dict:
                return {sh.shard_num: sh.latest_offset
                        for sh in self.memstore.shards(ds)}

            self.status_poller = StatusPoller(
                self.manager, self.failure_detector, peers, self.node,
                interval_s=float(self.config.get(
                    "status-poll-interval-s", 2.0)),
                on_assignment_change=resync_all,
                local_running=self._running_shards,
                local_watermarks=local_watermarks,
                tier_watermarks=self.tier_watermarks)
            self.status_poller.start()
        if self.insights_fleet is not None:
            # AFTER http.start(): peers answer /admin/insights only
            # once their server is up, and start() no-ops peerless
            self.insights_fleet.start()
        if self.config.get("profiler"):
            self.profiler = SimpleProfiler()
            self.profiler.start()
        self._started.set()
        return port

    def _ageout_loop(self, retention_ms: int, tick_s: float) -> None:
        """Background retention passes over every dataset (tier
        datasets included — each tier dataset gets its OWN age-out
        watermark, the per-tier retention floor the resolution router
        stitches at).  A failed pass logs and retries next tick; the
        failed shard's watermark never advances past unarchived data."""
        import logging
        log = logging.getLogger("filodb.coldstore")
        only = set((self.config.get("coldstore") or {})
                   .get("datasets") or ())
        while not self._ageout_stop.wait(tick_s):
            for ds in list(self.manager.datasets()):
                if only and ds not in only:
                    continue
                if self._ageout_stop.is_set():
                    return
                try:
                    self.ageout.run(ds, retention_ms)
                except Exception:  # noqa: BLE001 — keep the loop alive
                    log.exception("cold-tier age-out pass failed for %s "
                                  "(will retry next tick)", ds)

    def _setup_insights(self) -> None:
        """Fleet workload insights (ISSUE 19, doc/observability.md):
        the per-fingerprint workload ledger, the declarative tenant SLO
        tracker, and the fleet aggregator that merges peers' raw
        snapshots into /admin/fleet.  Always on (the ledger is a few
        hundred KB of ints); ``insights.enabled: false`` or the runtime
        knob turns the per-query accounting off."""
        conf = self.config.get("insights") or {}
        from filodb_tpu.insights.ledger import WorkloadLedger
        from filodb_tpu.utils.observability import insights_metrics
        ledger = WorkloadLedger(
            node=self.node,
            max_entries=int(conf.get("max-entries", 512)),
            co_window_ms=float(conf.get("co-arrival-window-ms", 250.0)),
            enabled=bool(conf.get("enabled", True)))
        self.http.insights = ledger
        # resident-fingerprint gauge as a set_fn: the row exists (at 0)
        # from startup, so dashboards and rules see the ramp, not a
        # label set born mid-incident
        insights_metrics()["fingerprints"].set_fn(ledger.fingerprints,
                                                  node=self.node)
        slo_conf = conf.get("slo") or {}
        objectives = []
        from filodb_tpu.insights.slo import SloObjective, SloTracker
        for i, obj in enumerate(slo_conf.get("objectives") or []):
            objectives.append(SloObjective.from_config(obj, i))
        if objectives:
            self.slo_tracker = SloTracker(
                objectives, node=self.node,
                fast_window_s=float(slo_conf.get("fast-window-s", 300.0)),
                slow_window_s=float(slo_conf.get("slow-window-s",
                                                 3600.0)))
            self.http.slo = self.slo_tracker
        from filodb_tpu.insights.fleet import FleetAggregator
        # fleet-poll-interval-s <= 0 (the default) = on-demand: no
        # background peer chatter; each /admin/fleet read polls.  Set
        # it > 0 to keep the console cache warm between reads.
        self.insights_fleet = FleetAggregator(
            self.node, self.config.get("peers", {}),
            self.http._insights_raw,
            interval_s=float(conf.get("fleet-poll-interval-s", 0.0)),
            timeout_s=float(conf.get("fleet-poll-timeout-s", 2.0)),
            stale_after_s=float(conf.get("fleet-stale-after-s", 60.0)))
        self.http.fleet = self.insights_fleet

    def _setup_rules(self, selfscrape_conf: dict) -> None:
        """Rule engine (ISSUE 9, doc/rules.md): inline groups + rule
        files + the shipped self-monitoring pack (on whenever
        self-scrape is on).  A broken rule config refuses startup —
        silently running a subset of the configured rules is worse
        than not starting."""
        rules_conf = self.config.get("rules") or {}
        from filodb_tpu.rules.config import (load_rule_config,
                                             load_rule_file)
        groups: list = []
        if rules_conf.get("groups"):
            groups.extend(load_rule_config(
                {"groups": rules_conf["groups"]}, source="config"))
        for path in rules_conf.get("files", []):
            groups.extend(load_rule_file(path))
        sm = rules_conf.get("self-monitoring") or {}
        if selfscrape_conf.get("enabled") and sm.get("enabled", True):
            from filodb_tpu.rules.selfmon import selfmon_pack
            groups.extend(load_rule_config(
                selfmon_pack(
                    interval=str(sm.get("interval", "15s")),
                    for_=str(sm.get("for", "30s")),
                    dataset=selfscrape_conf.get("dataset", "_system"),
                    window=str(sm.get("window", "2m"))),
                source="builtin:self-monitoring"))
        # tenant SLO burn alerts (ISSUE 19): shipped whenever SLO
        # objectives are configured AND self-scrape feeds filodb_slo_*
        # into a queryable dataset (the burn gauges ride the same
        # exposition the selfmon pack evaluates against)
        slo_rules = rules_conf.get("slo-burn") or {}
        if selfscrape_conf.get("enabled") and self.http.slo is not None \
                and slo_rules.get("enabled", True):
            from filodb_tpu.rules.selfmon import slo_pack
            groups.extend(load_rule_config(
                slo_pack(
                    interval=str(slo_rules.get("interval", "15s")),
                    for_=str(slo_rules.get("for", "30s")),
                    dataset=selfscrape_conf.get("dataset", "_system")),
                source="builtin:slo-burn"))
        if not groups:
            return
        nconf = rules_conf.get("notifier") or {}
        if nconf.get("url"):
            from filodb_tpu.rules.notifier import WebhookNotifier
            self.rule_notifier = WebhookNotifier(
                nconf["url"],
                timeout_s=float(nconf.get("timeout-s", 5.0)),
                retries=int(nconf.get("retries", 3)),
                backoff_s=float(nconf.get("backoff-s", 0.25)))
        from filodb_tpu.rules.engine import RuleEngine
        ds_names = [d["name"] for d in self.config.get("datasets", [])]
        self.rule_engine = RuleEngine(
            groups,
            binding_for=self.http.datasets.get,
            publisher_for=self.write_publishers.get,
            default_dataset=ds_names[0] if ds_names else "",
            notifier=self.rule_notifier,
            node=self.node,
            incremental=bool(rules_conf.get("incremental", True)))
        self.http.rules = self.rule_engine
        self.rule_engine.start()

    def _setup_dataset(self, ds_conf: dict) -> None:
        name = ds_conf["name"]
        num_shards = int(ds_conf.get("num-shards", 4))
        spread = int(ds_conf.get("spread", 1))
        store_cfg = StoreConfig.from_config(ds_conf.get("store", {}))
        if hasattr(self.metastore, "write_dataset"):
            self.metastore.write_dataset(name, json.dumps(ds_conf))

        # per-dataset source: "broker"/"kafka" reads topic partitions from
        # a message broker (reference: sourcefactory =
        # KafkaIngestionStreamFactory); default is the in-proc queue
        source_conf = dict(ds_conf.get("source", {}))
        factory_name = source_conf.pop("factory", None)
        broker_producer = None
        if factory_name in ("broker", "kafka"):
            from filodb_tpu.ingest.broker import (BrokerClient,
                                                  BrokerIngestionStreamFactory,
                                                  BrokerProducer)
            if self.broker is not None:
                source_conf.setdefault("port", self.broker.port)
            ds_factory = BrokerIngestionStreamFactory(
                topic=source_conf.pop("topic", name), **source_conf)
            # shard -> partition folds modulo the topic's creation-time
            # partition count: a live split doubles SERVING shards while
            # child s+N keeps consuming partition s (ISSUE 13)
            ds_factory.base_partitions = num_shards
            client = BrokerClient(ds_factory.host, ds_factory.port)
            broker_producer = BrokerProducer(client, ds_factory.topic or name,
                                             num_shards)
        elif factory_name is not None:
            from filodb_tpu.ingest.stream import source_factory
            ds_factory = source_factory(factory_name, **source_conf)
        else:
            ds_factory = self.stream_factory

        rf = int(ds_conf.get("replication-factor", 1))
        self.manager.setup_dataset(name, num_shards,
                                   int(ds_conf.get("min-num-nodes", 1)),
                                   replication_factor=rf)
        mapper = self.manager.mapper(name)
        source_is_broker = factory_name in ("broker", "kafka")
        self._transports[name] = "broker" if source_is_broker else "queue"
        self._spreads[name] = spread
        # a persisted in-flight split re-applies its topology NOW, so
        # the resync below already sees children + split policy
        self.split_controller.restore_dataset(name)
        ic = self.coordinator.setup_dataset(
            name, DEFAULT_SCHEMAS, ds_factory, store_cfg,
            event_sink=self.manager.publish_event,
            # recovery promotion gate (ISSUE 7): a rejoining replica is
            # promoted only once it reaches the group's gossiped head.
            # BROKER sources only: replicas share one partition log, so
            # their offsets are comparable.  Queue-transport replicas
            # number their own independent queues (deliveries dropped
            # while a node was down leave a permanent gap), so gating
            # on a peer's offset would wedge a rejoined node in
            # Recovery forever — they promote at the local checkpoint
            # head instead (best-effort transport, doc/ha.md).
            group_head_fn=(lambda shard, _m=mapper: _m.group_head(shard))
            if rf > 1 and source_is_broker else None)
        shards = self.split_controller.startable_shards(
            name, mapper.runnable_shards_for_node(self.node))
        ic.resync(shards)
        # workload management (ISSUE 5): admission + quota + dispatch
        # tuning from the per-dataset "workload" block
        wl_conf = dict(ds_conf.get("workload", {}))
        # peers: node -> http endpoint; shards owned by peers dispatch
        # remotely (reference: ActorPlanDispatcher per shard owner)
        peers = self.config.get("peers", {})
        disp = None
        if peers:
            from filodb_tpu.coordinator.dispatch import dispatcher_factory
            disp = dispatcher_factory(mapper, peers, local_node=self.node,
                                      dispatch_config=wl_conf.get(
                                          "dispatch"))
        # ICI-collective serving: fuse local multi-shard aggregates into
        # one SPMD mesh program.  Auto-on when >1 device is visible
        # (multi-chip); override per dataset with "mesh": true/false.
        mesh_conf = ds_conf.get("mesh")
        mesh_provider = None
        if mesh_conf or (mesh_conf is None and self._device_count() > 1):
            from filodb_tpu.parallel.mesh import default_engine
            mesh_provider = default_engine
        # mesh query fabric (ISSUE 18): when every child shard of an
        # aggregate is mesh-resident here, the plan root is ONE fused
        # device program (scan -> window -> aggregate -> cross-shard
        # psum -> present).  "mesh-fused": false pins the PR 17 shape
        # (mesh partials + host reduce) without turning the mesh off.
        mesh_fused = bool(ds_conf.get("mesh-fused", True))
        # per-shard-key spread overrides (reference: filodb-defaults
        # `spread-assignment`): "spread-assignment":
        #   [{"keys": {"_ws_": "demo", "_ns_": "App-0"}, "spread": 3}]
        spread_provider = None
        if ds_conf.get("spread-assignment"):
            from filodb_tpu.coordinator.planner import \
                spread_provider_from_config
            spread_provider = spread_provider_from_config(
                ds_conf["spread-assignment"], spread)
        planner = SingleClusterPlanner(name, mapper, DatasetOptions(),
                                       spread_default=spread,
                                       spread_provider=spread_provider,
                                       dispatcher_for_shard=disp,
                                       mesh_engine_provider=mesh_provider,
                                       mesh_fused=mesh_fused)
        # query-frontend result cache (ISSUE 12): the wrapper is always
        # installed (a disabled cache is one boolean per materialize)
        # so POST /admin/config can enable it at runtime; it sits BELOW
        # the rollup router on purpose — tier selection stays upstream,
        # and each tier dataset's own wrapper memoizes its segments
        rc_conf = self.config.get("result-cache") or {}
        from filodb_tpu.http.model import parse_duration_ms
        from filodb_tpu.query.resultcache import (ResultCache,
                                                  ResultCachingPlanner)
        cache = ResultCache(
            name,
            max_bytes=int(rc_conf.get("max-bytes", 64 * 1024 * 1024)),
            enabled=bool(rc_conf.get("enabled", False)))
        seg_ms = parse_duration_ms(rc_conf["segment"]) \
            if "segment" in rc_conf else store_cfg.flush_interval_ms
        planner = ResultCachingPlanner(
            name, planner, self.memstore, cache, segment_ms=seg_ms,
            routing_token_fn=mapper.routing_token,
            instant=bool(rc_conf.get("instant", True)))
        self.result_caches[name] = cache
        schema = DEFAULT_SCHEMAS[ds_conf.get("schema", "gauge")]
        peers_conf = self.config.get("peers", {})
        if broker_producer is not None:
            # the broker's shared partition log IS the replicated
            # stream: one produce, every replica consumes at its own
            # offset (reference: Kafka replicated ingest)
            publish = broker_producer.publish
        elif rf > 1 and peers_conf:
            # queue transport + replicas: dual-write each container to
            # every replica — local queue for this node, the peers'
            # POST /ingest container edge for the rest (ISSUE 7)
            from filodb_tpu.gateway.server import (ReplicaFanout,
                                                   http_container_push)
            self._queue_push_datasets.add(name)
            per_node = {self.node:
                        (lambda s, c, _n=name:
                         self.stream_factory.stream_for(_n, s).push(c))}
            for peer, endpoint in peers_conf.items():
                if peer != self.node:
                    per_node[peer] = http_container_push(endpoint, name)
            publish = ReplicaFanout(name, mapper, per_node,
                                    local_node=self.node)
            self._replica_fanouts.append(publish)
        else:
            self._queue_push_datasets.add(name)
            publish = lambda s, c, _n=name: self.stream_factory.stream_for(  # noqa: E731
                _n, s).push(c)
        self._publish_fns[name] = publish
        # Prometheus remote-write edge shares the gateway sharding rules
        # (and doubles as the self-telemetry ingest edge, ISSUE 6)
        wpub = ShardingPublisher(schema, mapper, publish, spread=spread)
        self.write_publishers[name] = wpub
        # watermark ledger source: the broker head when this dataset
        # consumes from a broker, the in-proc queue head otherwise
        if self.watermarks is not None:
            if broker_producer is not None:
                # split children consume their parent's partition, so
                # their broker head is the parent partition's (ISSUE 13)
                end_fn = (lambda shard, _c=client, _n=num_shards,
                          _t=ds_factory.topic or name:
                          _c.end_offset(_t, shard % _n))
            elif ds_factory is self.stream_factory:
                end_fn = (lambda shard, _n=name:
                          self.stream_factory.stream_for(
                              _n, shard).end_offset())
            else:
                end_fn = None
            self.watermarks.watch(name, self.memstore, mapper=mapper,
                                  end_offset_fn=end_fn)

        def write_router(labels, ts, vals, _pub=wpub):
            metric = labels.get("__name__", "")
            tags = {k: v for k, v in labels.items() if k != "__name__"}
            for t, v in zip(ts, vals):
                _pub.add_sample(metric, tags, int(t), float(v))
            _pub.flush()

        # bounded query scheduler per dataset (reference: QueryActor's
        # priority mailbox + dedicated query pool)
        from filodb_tpu.query.scheduler import QueryScheduler
        qconf = ds_conf.get("query", {})
        qsched = QueryScheduler(
            num_workers=int(qconf.get("workers", 4)),
            max_queued=int(qconf.get("max-queued", 256)),
            name=f"query-{name}")
        # dispatched leaf plans get their own pool: coordinator queries
        # block on remote leaves, so a shared pool would deadlock
        leaf_sched = QueryScheduler(
            num_workers=int(qconf.get("leaf-workers",
                                      qconf.get("workers", 4))),
            max_queued=int(qconf.get("max-queued", 256)),
            name=f"leaf-{name}")
        self.query_schedulers[name] = qsched
        self.query_schedulers[f"{name}/leaf"] = leaf_sched
        # cost-based admission in front of the scheduler (ISSUE 5):
        # present by default — a node with no overload defense is the
        # failure mode this subsystem exists to close; "admission":
        # {"enabled": false} opts out
        adm_conf = dict(wl_conf.get("admission", {}))
        admission = None
        if adm_conf.get("enabled", True):
            from filodb_tpu.workload.admission import AdmissionController
            from filodb_tpu.workload.cost import CostModel
            admission = AdmissionController(
                CostModel(),
                dataset=name,
                max_inflight_cost=float(
                    adm_conf.get("max-inflight-cost", 10_000.0)),
                priority_shares=adm_conf.get("priority-shares"),
                tenant_max_concurrent=int(
                    adm_conf.get("tenant-max-concurrent", 32)),
                tenant_max_inflight_cost=adm_conf.get(
                    "tenant-max-cost"),
                workers=int(qconf.get("workers", 4)))
            self.admission_controllers[name] = admission
        # active-series cardinality quota, shared by every local shard
        # of this dataset and the gateway edge (workload/quota.py)
        quota = None
        q_conf = wl_conf.get("quota")
        if q_conf:
            from filodb_tpu.workload.quota import SeriesQuota
            quota = SeriesQuota(
                dataset=name,
                tenant_label=q_conf.get("tenant-label", "_ns_"),
                default_limit=q_conf.get("default-max-series"),
                overrides=q_conf.get("overrides"))
            for sh in self.memstore.shards(name):
                sh.series_quota = quota
            quota.refresh_from_index(
                *(sh.index for sh in self.memstore.shards(name)))
            wpub.quota = quota
        # fleet batching tier (ISSUE 20, filodb_tpu/batching): one
        # QueryBatcher per dataset, attached to every local shard —
        # the device stores offer eligible dispatches to it, so
        # concurrent shape-compatible queries share ONE vmapped launch.
        # On by default ("batching": {"enabled": false} opts out); the
        # ledger resolves lazily because _setup_insights runs after
        # datasets bind.
        bat_conf = dict(ds_conf.get("batching",
                                    self.config.get("batching", {})))
        from filodb_tpu.batching import QueryBatcher
        # a stack is no larger than the queries that run at once: the
        # query workers, and the leaf workers where peers send this node
        # leaves (more only where one query has several leaves of one
        # shape on one shard; they form a second group).  Every stack
        # size is a program of its own a plan shape, compiled and kept
        # in HBM
        reach = int(qconf.get("workers", 4))
        if self.config.get("peers"):
            reach += int(qconf.get("leaf-workers", qconf.get("workers", 4)))
        batcher = QueryBatcher(
            enabled=bool(bat_conf.get("enabled", True)),
            window_ms=float(bat_conf.get("window-ms", 3.0)),
            max_batch=min(int(bat_conf.get("max-batch", 8)), max(reach, 2)),
            hot_ttl_s=float(bat_conf.get("hot-ttl-s", 10.0)),
            dataset=name,
            ledger=lambda: self.http.insights)
        for sh in self.memstore.shards(name):
            sh.query_batcher = batcher
        # tiered-resolution serving (ISSUE 11, doc/rollup.md): stand up
        # the <ds>_ds_<res> tier datasets as REAL datasets (replicated,
        # flushed through the checksummed store, queryable), wire the
        # rollup engine over this dataset's flush stream, and wrap the
        # serving planner in the resolution router
        planner = self._setup_rollup(ds_conf, name, num_shards, spread, rf,
                                     mapper, schema, planner, admission)
        self.http.bind_dataset(DatasetBinding(name, self.memstore, planner,
                                              write_router=write_router,
                                              scheduler=qsched,
                                              leaf_scheduler=leaf_sched,
                                              admission=admission,
                                              quota=quota,
                                              resultcache=cache,
                                              batcher=batcher))

        gw_port = ds_conf.get("gateway-port")
        if gw_port is None and not self._global_gateway_claimed:
            # the top-level port can serve exactly one dataset; additional
            # datasets need their own gateway-port
            gw_port = self.config.get("gateway-port")
            if gw_port is not None:
                self._global_gateway_claimed = True
        if gw_port is not None:
            pub = ShardingPublisher(schema, mapper, publish, spread=spread,
                                    quota=quota)
            gw = GatewayServer(pub, port=int(gw_port))
            gw.start()
            self.gateways.append(gw)

    def _setup_rollup(self, ds_conf: dict, name: str, num_shards: int,
                      spread: int, rf: int, mapper, schema, planner,
                      admission):
        """Per-dataset rollup wiring (ISSUE 11).  Returns the serving
        planner — the resolution router when rollup is enabled, the
        original planner otherwise.  A broken rollup block refuses
        startup, like a broken rule config."""
        ro_conf = ds_conf.get("rollup")
        if ro_conf is None or ds_conf.get("_rollup_tier") \
                or not ro_conf.get("enabled", True):
            return planner
        from filodb_tpu.rollup.config import (RollupConfig,
                                              RollupConfigError)
        # self-downsampling schemas (prom-counter / prom-histogram roll
        # into their own shape, schemas.py) carry downsample=None but a
        # downsample_schema NAME — they tier since ISSUE 14
        if schema.downsample is None \
                and not (schema.data.downsamplers
                         and schema.data.downsample_schema):
            raise RollupConfigError(
                f"dataset {name!r} (schema {ds_conf.get('schema')!r}) "
                f"has no downsample schema — rollup cannot tier it")
        cfg = RollupConfig.from_config(ro_conf)
        from filodb_tpu.downsample.dsstore import ds_dataset_name
        # tier datasets split in LOCKSTEP with their source (ISSUE 13):
        # the SplitController doubles them in the same phase machine
        self._tiers[name] = [ds_dataset_name(name, r)
                             for r in cfg.resolutions_ms]
        tier_planners: dict[int, object] = {}
        publish_for: dict[int, object] = {}
        tier_schema = schema.data.downsample_schema \
            or ds_conf.get("schema", "gauge")
        for res in cfg.resolutions_ms:
            tname = ds_dataset_name(name, res)
            if tname not in self.manager.datasets():
                # tier datasets never claim the node's global gateway
                # port (the _system-dataset discipline) and always use
                # the in-proc queue transport: at rf>1 the generic
                # queue+peers branch gives them the PR 12 ReplicaFanout
                # dual-write, broker or not
                claimed = self._global_gateway_claimed
                self._global_gateway_claimed = True
                try:
                    self._setup_dataset({
                        "name": tname, "num-shards": num_shards,
                        "min-num-nodes": int(
                            ds_conf.get("min-num-nodes", 1)),
                        "schema": tier_schema, "spread": spread,
                        "replication-factor": rf,
                        "store": ro_conf.get("store",
                                             ds_conf.get("store", {})),
                        "query": ro_conf.get("query", {"workers": 2}),
                        "_rollup_tier": True})
                finally:
                    self._global_gateway_claimed = claimed
            tier_planners[res] = self.http.datasets[tname].planner
            publish_for[res] = self._publish_fns[tname]
        if self.rollup_engine is None:
            from filodb_tpu.rollup.engine import RollupEngine
            self.rollup_engine = RollupEngine(node=self.node)
            self.http.rollup = self.rollup_engine
        from filodb_tpu.core.schemas import DEFAULT_SCHEMAS
        self.rollup_engine.watch(
            name, self.memstore, DEFAULT_SCHEMAS, cfg, publish_for,
            column_store=self.colstore, meta_store=self.metastore,
            # only the shard's primary replica rolls it (the raw data
            # is identical on every replica; the EMITTED containers
            # replicate through the tier publish path — two emitters
            # would double-publish every record)
            owner_fn=(lambda s, _m=mapper, _n=self.node:
                      _m.coord_for_shard(s) == _n),
            admission=admission)
        from filodb_tpu.rollup.planner import RollupRouterPlanner

        def cluster_rolled_through(res: int, _e=self.rollup_engine,
                                   _n=name, _m=mapper,
                                   _tw=self.tier_watermarks,
                                   _node=self.node) -> int:
            """Cluster-wide stitch boundary (ROADMAP 2b): min over the
            shard owners' GOSSIPED closure watermarks — each owner is
            authoritative for the shards it rolls, so intra-shard
            series skew on peer shards can no longer open silent holes
            the delivered-stamp proxy missed, and a coordinator that
            owns no primaries can route rolled at all.  Still clamped
            by what the LOCAL tier replicas have had delivered (a
            boundary past undelivered data would stitch into a hole);
            any owner without gossip yet degrades to the local
            engine's conservative boundary, exactly the pre-gossip
            behavior."""
            local = _e.rolled_through(_n, res)
            owners = {_m.coord_for_shard(s)
                      for s in range(_m.num_shards)}
            peer_owners = owners - {_node, None}
            if not peer_owners:
                return local
            peer_min = _tw.cluster_min(_n, res, peer_owners)
            if peer_min is None:
                return local
            owned = _e.owned_rolled_through(_n, res)
            if _node in owners and owned is None:
                # this node rolls shards but its engine has not
                # computed a closure yet (pre-first-pass / restart):
                # None means "unknown", not "owns nothing" — trusting
                # peer_min alone would stitch past the local shards'
                # actual closure
                return local
            vals = [peer_min] + ([owned] if owned is not None else [])
            delivered = _e.delivered_through(_n, res)
            if delivered is not None:
                vals.append(delivered)
            elif self.memstore.shards(ds_dataset_name(_n, res)):
                # this node HOLDS tier replicas but nothing has been
                # delivered yet (restart window): a boundary past the
                # empty local tier data would stitch into a hole
                return local
            return min(vals)

        cold_floor = None
        if self.ageout is not None:
            # rolled-local / rolled-cold stitch boundary (ISSUE 16):
            # the TIER dataset's age-out floor — 0 until a pass
            # completes on every shard, so the cold leg only appears
            # once data is guaranteed archived
            def cold_floor(res: int, _a=self.ageout, _n=name) -> int:
                return _a.floor_ms(ds_dataset_name(_n, res))

        return RollupRouterPlanner(
            name, planner, tier_planners,
            rolled_through_fn=cluster_rolled_through,
            raw_retention_ms=cfg.raw_retention_ms,
            cold_floor_fn=cold_floor)

    def flush_all(self) -> int:
        n = 0
        for ds in self.manager.datasets():
            for sh in self.memstore.shards(ds):
                n += sh.flush_all()
        return n

    def shutdown(self) -> None:
        # stop the age-out loop FIRST: a migration pass mid-flight must
        # finish its current shard before the stores close under it
        self._ageout_stop.set()
        if self._ageout_thread is not None:
            self._ageout_thread.join(timeout=30)
        self.split_controller.stop()
        if self.rule_engine is not None:
            # stops the group loops AND closes the notifier — a dead
            # node must not keep evaluating or POSTing webhooks
            self.rule_engine.stop()
        if self.rollup_engine is not None:
            # stops the tier loops and removes the exported lag/stall
            # gauge rows — a dead node's stalled=1 must not feed the
            # self-monitoring alerts forever
            self.rollup_engine.stop()
        if self.watermark_sampler is not None:
            self.watermark_sampler.stop()
        if self.insights_fleet is not None:
            self.insights_fleet.stop()
        if self.selfscraper is not None:
            self.selfscraper.stop()
        if self.status_poller is not None:
            self.status_poller.stop()
        for gw in self.gateways:
            gw.shutdown()
        for fanout in self._replica_fanouts:
            fanout.close()
        self.coordinator.shutdown()
        self.http.shutdown()
        if self.watermarks is not None:
            # drop this node's exported watermark/stall gauge rows — a
            # dead node's stalled=1 must not feed alerting rules
            # forever.  AFTER http.shutdown(): a late /admin/shards
            # request would otherwise re-watch the emptied ledger and
            # resurrect the just-removed rows permanently
            self.watermarks.close()
        # same discipline for the insights/SLO gauge rows: AFTER
        # http.shutdown(), so no late query can re-register them
        if self.slo_tracker is not None:
            self.slo_tracker.close()
        if self.http.insights is not None:
            from filodb_tpu.utils.observability import insights_metrics
            insights_metrics()["fingerprints"].remove(node=self.node)
        for qs in self.query_schedulers.values():
            qs.shutdown()
        for ac in self.admission_controllers.values():
            ac.shutdown()
        if self.broker is not None:
            self.broker.shutdown()
        if self.profiler is not None:
            self.profiler.stop()
        self.colstore.shutdown()
        self.metastore.shutdown()


def enable_server_x64() -> None:
    """The x64 setting of a server process.  Epoch-ms step grids and
    sample timestamps are int64 on the general path (ops/windows.py,
    query/rangefns.py), so x64 is on whatever the backend.  The Pallas
    grid kernels take int32-rebased planes and are traced with 32-bit
    defaults inside that process (ops/grid.py ``_x32``): Mosaic accepts
    no i64 scalar."""
    import jax
    jax.config.update("jax_enable_x64", True)


def place_compile_cache() -> None:
    """Give JAX's persistent compilation cache a home before the first
    jit.  A deployment places it through ``JAX_COMPILATION_CACHE_DIR``
    (JAX reads the variable itself; nothing is set here).  Without the
    variable it lives at ``<checkout>/.jax_cache`` — a fixed path,
    because the path is part of the cache's key: a directory that moves
    between runs never hits."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    jax.config.update(
        "jax_compilation_cache_dir",
        str(pathlib.Path(__file__).resolve().parents[1] / ".jax_cache"))


def boot(config: dict) -> FiloServer:
    """Bring one server process up: JAX settings, compile cache, every
    subsystem, listening.  ``main`` and ``chip_smoke.py`` both come up
    through here, so the smoke proves the program users start."""
    enable_server_x64()
    place_compile_cache()
    from filodb_tpu.utils.observability import (install_gc_watch,
                                                install_stall_watch)
    install_gc_watch()
    install_stall_watch()
    server = FiloServer(config)
    server.start()
    return server


def main(argv=None) -> int:
    args = argv if argv is not None else sys.argv[1:]
    if not args:
        print("usage: python -m filodb_tpu.standalone <config.json>",
              file=sys.stderr)
        return 2
    with open(args[0]) as f:
        config = json.load(f)
    server = boot(config)
    print(f"FiloDB-TPU node {server.node} up: http={server.http.port} "
          f"datasets={server.manager.datasets()}")
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *a: stop.set())
    signal.signal(signal.SIGINT, lambda *a: stop.set())
    stop.wait()
    server.shutdown()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
