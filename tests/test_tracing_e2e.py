"""End-to-end query tracing across a 2-node (in-process) cluster.

ISSUE 2 acceptance: a query_range over HTTP with stats=true returns
per-stage timings, and /admin/traces/<trace_id> on the coordinator
shows ONE stitched span tree including the remote shard's spans
(propagated via the X-FiloDB-Trace-Id header + execplan-wire field)."""

import json
import time
import urllib.parse
import urllib.request

import numpy as np
import pytest

from filodb_tpu.coordinator.dispatch import (PARENT_SPAN_HEADER,
                                             TRACE_HEADER,
                                             dispatcher_factory)
from filodb_tpu.coordinator.planner import SingleClusterPlanner
from filodb_tpu.core.record import RecordBuilder, decode_container
from filodb_tpu.core.schemas import DEFAULT_SCHEMAS, DatasetOptions
from filodb_tpu.http.server import DatasetBinding, FiloHttpServer
from filodb_tpu.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.parallel.shardmap import ShardMapper, ShardStatus
from filodb_tpu.query.scheduler import QueryScheduler
from filodb_tpu.utils.forensics import TRACE_STORE

BASE = 1_700_000_000_000
STEP = 10_000


def _get(port, path, **params):
    qs = urllib.parse.urlencode(params)
    url = f"http://127.0.0.1:{port}{path}" + (f"?{qs}" if qs else "")
    try:
        with urllib.request.urlopen(url, timeout=30) as resp:
            return resp.status, json.loads(resp.read()), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


@pytest.fixture(scope="module")
def cluster():
    """Two memstores, half the shards each; BOTH nodes serve HTTP and
    node-a (the coordinator) dispatches node-b's shards over the wire.
    node-a runs a query scheduler, node-b a leaf scheduler, so trace
    context must survive both thread-pool handoffs."""
    num_shards = 4
    mapper = ShardMapper(num_shards)
    rng = np.random.default_rng(5)
    b = RecordBuilder(DEFAULT_SCHEMAS["prom-counter"])
    for i in range(8):
        tags = {"__name__": "trace_total", "instance": f"i{i}",
                "_ws_": "demo", "_ns_": "App-0"}
        ts = BASE + np.arange(300) * STEP
        vals = np.cumsum(rng.random(300))
        for t, v in zip(ts, vals):
            b.add(int(t), [float(v)], tags)
    by_shard = {}
    for off, c in enumerate(b.containers()):
        for rec in decode_container(c, DEFAULT_SCHEMAS):
            shard = mapper.ingestion_shard(rec.shard_hash, rec.part_hash, 1) \
                % num_shards
            by_shard.setdefault(shard, []).append((off, rec))
    used = sorted(by_shard)
    assert len(used) == 2
    shards_a = [used[0]] + [s for s in range(num_shards) if s not in used]
    shards_b = [used[1]]
    mapper.register_node(shards_a, "node-a")
    mapper.register_node(shards_b, "node-b")
    for s in range(num_shards):
        mapper.update_status(s, ShardStatus.ACTIVE)

    stores = {"node-a": TimeSeriesMemStore(), "node-b": TimeSeriesMemStore()}
    for ms in stores.values():
        for s in range(num_shards):
            ms.setup("prom", DEFAULT_SCHEMAS, s)
    for shard, recs in by_shard.items():
        node = mapper.coord_for_shard(shard)
        for off, rec in recs:
            stores[node].get_shard("prom", shard).ingest([rec], off)

    srv_b = FiloHttpServer()
    planner_b = SingleClusterPlanner("prom", mapper, DatasetOptions(),
                                     spread_default=1)
    leaf_sched = QueryScheduler(num_workers=2, name="e2e-leaf")
    srv_b.bind_dataset(DatasetBinding("prom", stores["node-b"], planner_b,
                                      leaf_scheduler=leaf_sched))
    port_b = srv_b.start()

    endpoints = {"node-b": f"http://127.0.0.1:{port_b}"}
    disp = dispatcher_factory(mapper, endpoints, local_node="node-a")
    planner_a = SingleClusterPlanner("prom", mapper, DatasetOptions(),
                                     spread_default=1,
                                     dispatcher_for_shard=disp)
    srv_a = FiloHttpServer()
    qsched = QueryScheduler(num_workers=2, name="e2e-query")
    srv_a.bind_dataset(DatasetBinding("prom", stores["node-a"], planner_a,
                                      scheduler=qsched))

    # ISSUE 15 satellite: a LOCAL-only dataset whose planner stack is
    # result-cache BELOW a (tier-less) rollup router — the standalone
    # composition — so the query.execute span must carry the router's
    # resolution decision (raw => "0") and the cache's hit/miss/partial
    # outcome.  All shards local (remote plans bypass the cache) and
    # chunks flushed (open segments are never memoized).
    from filodb_tpu.query.resultcache import (ResultCache,
                                              ResultCachingPlanner)
    from filodb_tpu.rollup.planner import RollupRouterPlanner
    ms_local = TimeSeriesMemStore()
    mapper_l = ShardMapper(1)
    mapper_l.register_node([0], "node-a")
    mapper_l.update_status(0, ShardStatus.ACTIVE)
    shard_l = ms_local.setup("proml", DEFAULT_SCHEMAS, 0)
    bl = RecordBuilder(DEFAULT_SCHEMAS["prom-counter"])
    for i in range(4):
        tags = {"__name__": "local_total", "instance": f"i{i}",
                "_ws_": "demo", "_ns_": "App-0"}
        vals = np.cumsum(rng.random(300))
        for t, v in zip(BASE + np.arange(300) * STEP, vals):
            bl.add(int(t), [float(v)], tags)
    for off, c in enumerate(bl.containers()):
        shard_l.ingest(decode_container(c, DEFAULT_SCHEMAS), off)
    shard_l.flush_all()
    cache_l = ResultCache("proml", enabled=True, max_bytes=32 << 20)
    planner_l = ResultCachingPlanner(
        "proml",
        SingleClusterPlanner("proml", mapper_l, DatasetOptions(),
                             spread_default=0),
        ms_local, cache_l, segment_ms=120_000,
        routing_token_fn=mapper_l.routing_token)
    planner_l = RollupRouterPlanner("proml", planner_l, {},
                                    rolled_through_fn=lambda r: 0)
    srv_a.bind_dataset(DatasetBinding("proml", ms_local, planner_l,
                                      resultcache=cache_l))
    port_a = srv_a.start()
    yield {"port_a": port_a, "port_b": port_b,
           "remote_shard": shards_b[0], "endpoints": endpoints}
    srv_a.shutdown()
    srv_b.shutdown()
    qsched.shutdown()
    leaf_sched.shutdown()


def _query_range(cluster, **extra):
    params = dict(
        query='sum(rate(trace_total{_ws_="demo",_ns_="App-0"}[2m]))',
        start=(BASE + 600_000) / 1000, end=(BASE + 1_200_000) / 1000,
        step="30s", **extra)
    return _get(cluster["port_a"], "/promql/prom/api/v1/query_range",
                **params)


def _flatten(nodes, out=None):
    out = [] if out is None else out
    for n in nodes:
        out.append(n)
        _flatten(n["children"], out)
    return out


class TestStatsResponse:
    def test_stats_true_shape(self, cluster):
        code, body, headers = _query_range(cluster, stats="true")
        assert code == 200 and body["status"] == "success"
        assert len(body["data"]["result"]) == 1
        stats = body["data"]["stats"]
        timings = stats["timings"]
        for key in ("plan", "queue", "scan", "total"):
            assert key in timings, f"missing stage bucket {key}: {timings}"
        assert timings["total"] >= timings["plan"] >= 0.0
        samples = stats["samples"]
        # 8 series x 300 rows scanned somewhere across the two nodes
        assert samples["samplesScanned"] > 0
        assert samples["bytesScanned"] > 0
        assert stats["traceId"]
        assert headers.get("X-FiloDB-Trace-Id") == stats["traceId"]

    def test_no_stats_by_default(self, cluster):
        code, body, headers = _query_range(cluster)
        assert code == 200
        assert "stats" not in body["data"]
        assert "X-FiloDB-Trace-Id" not in headers

    def test_instant_query_stats(self, cluster):
        code, body, _ = _get(
            cluster["port_a"], "/promql/prom/api/v1/query",
            query='count(trace_total{_ws_="demo",_ns_="App-0"})',
            time=(BASE + 900_000) / 1000, stats="true")
        assert code == 200
        assert "timings" in body["data"]["stats"]


class TestStitchedTrace:
    def test_remote_spans_joined_into_one_tree(self, cluster):
        code, body, _ = _query_range(cluster, stats="true")
        assert code == 200
        tid = body["data"]["stats"]["traceId"]
        code, tbody, _ = _get(cluster["port_a"], f"/admin/traces/{tid}")
        assert code == 200
        roots = tbody["data"]["spans"]
        assert len(roots) == 1, \
            f"expected ONE stitched tree, got roots " \
            f"{[r['name'] for r in roots]}"
        assert roots[0]["name"] == "query"
        flat = _flatten(roots)
        names = [n["name"] for n in flat]
        assert "query.execute" in names
        assert "query.plan" in names
        assert "scheduler.queue_wait" in names  # node-a's scheduler
        # the remote dispatch span exists and the remote shard's
        # execplan span hangs UNDER it (correct parentage across the
        # process boundary), tagged with the remote shard id
        http_nodes = [n for n in flat if n["name"] == "dispatch.http"]
        assert http_nodes, names
        remote_kids = _flatten(http_nodes[0]["children"])
        remote_exec = [n for n in remote_kids
                       if n["name"] == "execplan.execute"]
        assert remote_exec, \
            "remote shard's spans were not stitched under dispatch.http"
        assert any(n["tags"].get("shard") == str(cluster["remote_shard"])
                   for n in remote_exec)
        # the DATA NODE's leaf-scheduler queue-wait/run split must join
        # the tree too (trace attached before submit on the remote side)
        remote_names = {n["name"] for n in remote_kids}
        assert "scheduler.run" in remote_names, remote_names
        assert "scheduler.queue_wait" in remote_names, remote_names

    def test_unknown_trace_404(self, cluster):
        code, body, _ = _get(cluster["port_a"], "/admin/traces/deadbeef00")
        assert code == 404

    def test_execplan_response_carries_spans(self, cluster):
        """The wire half of stitching: a data node returns its spans for
        the originating trace with the /execplan response."""
        from filodb_tpu.query.exec import MultiSchemaPartitionsExec
        from filodb_tpu.query import wire
        from filodb_tpu.core.filters import ColumnFilter, Equals
        plan = MultiSchemaPartitionsExec(
            "prom", cluster["remote_shard"],
            [ColumnFilter("_metric_", Equals("trace_total"))],
            BASE, BASE + 600_000)
        payload = wire.serialize_plan(plan)
        tid = "e2e0wire0trace00"
        req = urllib.request.Request(
            f"http://127.0.0.1:{cluster['port_b']}/execplan",
            data=json.dumps(payload).encode(), method="POST",
            headers={"Content-Type": "application/json",
                     TRACE_HEADER: tid, PARENT_SPAN_HEADER: "c0ffee"})
        with urllib.request.urlopen(req, timeout=30) as resp:
            out = json.loads(resp.read())
        spans = out.get("spans")
        assert spans, "execplan response is missing its spans"
        assert all(s["trace_id"] == tid for s in spans)
        roots = [s for s in spans if s["parent_id"] == "c0ffee"]
        assert roots, "remote root span must parent onto the header span"
        # full stats travel on the wire too
        assert "timings" in out["stats"]
        assert out["stats"]["timings"].get("scan", 0) > 0


class TestSpanTagSatellites:
    """ISSUE 15 satellite: PRs 16-17 surfaced the rollup resolution and
    the result-cache outcome only under stats=true — the query.execute
    span (and therefore every /admin/slowlog entry) must carry them
    too."""

    def _local_query(self, cluster, query):
        return _get(cluster["port_a"], "/promql/proml/api/v1/query_range",
                    query=query, start=(BASE + 600_000) / 1000,
                    end=(BASE + 1_800_000) / 1000, step="30s",
                    stats="true")

    def _exec_tags(self, cluster, trace_id):
        code, tbody, _ = _get(cluster["port_a"],
                              f"/admin/traces/{trace_id}")
        assert code == 200
        flat = _flatten(tbody["data"]["spans"])
        ex = [n for n in flat if n["name"] == "query.execute"]
        assert ex, [n["name"] for n in flat]
        return ex[0]["tags"]

    def test_resolution_decision_tagged_even_for_raw(self, cluster):
        code, body, _ = self._local_query(
            cluster,
            'sum(rate(local_total{_ws_="demo",_ns_="App-0"}[2m]))')
        assert code == 200
        tags = self._exec_tags(cluster, body["data"]["stats"]["traceId"])
        # the router decided RAW: previously only stats=true could say
        # so; now the span names the decision (0 = raw)
        assert tags.get("resolution_ms") == "0", tags

    def test_resultcache_outcome_tagged(self, cluster):
        q = ('sum(rate(local_total{_ws_="demo",_ns_="App-0",'
             'instance!="zz"}[2m]))')
        # sight 1: doorkeeper only — the cache made no hit/miss
        # decision, so the span stays untagged
        code, body1, _ = self._local_query(cluster, q)
        assert code == 200
        tags1 = self._exec_tags(cluster,
                                body1["data"]["stats"]["traceId"])
        assert "resultcache" not in tags1, tags1
        # sight 2: split + store — everything recomputed => miss
        code, body2, _ = self._local_query(cluster, q)
        tags2 = self._exec_tags(cluster,
                                body2["data"]["stats"]["traceId"])
        assert tags2.get("resultcache") == "miss", tags2
        # sight 3: interior segments replay from the cache
        code, body3, _ = self._local_query(cluster, q)
        tags3 = self._exec_tags(cluster,
                                body3["data"]["stats"]["traceId"])
        assert tags3.get("resultcache") in ("hit", "partial"), tags3
        # the tag agrees with the stats=true split
        rc = body3["data"]["stats"]["resultCache"]
        assert rc["cachedSamples"] > 0
        if tags3["resultcache"] == "hit":
            assert rc["recomputedSamples"] == 0


class TestScatterGatherStages:
    """ISSUE 29: a non-leaf plan's wait for its children and its own
    reduce are stages — in the stage table, in ``timings`` under
    ``stats=true`` and in the trace, tagged with the fan-out."""

    @pytest.mark.parametrize("dataset,metric,children", [
        ("prom", "trace_total", 2),       # spread 1: two of four shards
        ("proml", "local_total", 1)])     # one shard: the inline child
    def test_fanout_and_compose(self, cluster, dataset, metric, children):
        from filodb_tpu.utils.observability import TRACER
        before = TRACER.stages.snapshot()
        code, body, _ = _get(
            cluster["port_a"], f"/promql/{dataset}/api/v1/query_range",
            query=f'sum(rate({metric}{{_ws_="demo",_ns_="App-0",'
                  f'instance!="sg"}}[2m]))',
            start=(BASE + 600_000) / 1000, end=(BASE + 1_200_000) / 1000,
            step="30s", stats="true")
        assert code == 200 and len(body["data"]["result"]) == 1
        timings = body["data"]["stats"]["timings"]
        assert timings["exec.fanout"] > 0.0 and timings["exec.compose"] > 0.0
        assert timings["exec.fanout"] + timings["exec.compose"] \
            <= timings["total"]
        after = TRACER.stages.snapshot()
        for name in ("exec.fanout", "exec.compose"):
            assert after[name]["count"] \
                == before.get(name, {"count": 0})["count"] + 1
            assert after[name]["wall_s"] > before.get(
                name, {"wall_s": 0.0})["wall_s"]
        tid = body["data"]["stats"]["traceId"]
        code, tbody, _ = _get(cluster["port_a"], f"/admin/traces/{tid}")
        assert code == 200
        flat = _flatten(tbody["data"]["spans"])
        (fan,) = [n for n in flat if n["name"] == "exec.fanout"]
        (comp,) = [n for n in flat if n["name"] == "exec.compose"]
        assert fan["tags"] == {"plan": "ReduceAggregateExec",
                               "children": str(children)}
        assert comp["tags"] == {"plan": "ReduceAggregateExec"}
        # the leaves ran inside the fan-out, the reduce after it
        under = {n["name"] for n in _flatten(fan["children"])}
        assert "execplan.execute" in under and "exec.compose" not in under


class TestForensicsEndpoints:
    def test_slowlog_captures_query(self, cluster):
        old = TRACE_STORE.slow_threshold_s
        TRACE_STORE.slow_threshold_s = 0.0
        try:
            code, body, _ = _query_range(cluster, stats="true")
            tid = body["data"]["stats"]["traceId"]
            code, slog, _ = _get(cluster["port_a"], "/admin/slowlog")
            assert code == 200
            entries = slog["data"]["entries"]
            mine = [e for e in entries if e["trace_id"] == tid]
            assert mine, "completed query missing from the slow log"
            assert mine[0]["query"].startswith("sum(rate(trace_total")
            assert mine[0]["duration_s"] > 0
            assert mine[0]["tree"], "slow-log entry lost its span tree"
        finally:
            TRACE_STORE.slow_threshold_s = old

    def test_profilez(self, cluster):
        code, body, _ = _get(cluster["port_a"], "/debug/profilez",
                             seconds="0.05")
        assert code == 200
        assert body["data"]["samples"] >= 0
        assert "frames" in body["data"]

    def test_metrics_expose_query_families(self, cluster):
        _query_range(cluster)
        url = f"http://127.0.0.1:{cluster['port_a']}/metrics"
        text = urllib.request.urlopen(url, timeout=10).read().decode()
        assert "filodb_query_request_seconds" in text
        assert 'endpoint="query_range"' in text
        assert "filodb_query_queue_depth" in text
        url_b = f"http://127.0.0.1:{cluster['port_b']}/metrics"
        text_b = urllib.request.urlopen(url_b, timeout=10).read().decode()
        assert "filodb_query_execplan_remote_seconds" in text_b


# ---------------------------------------------------------------------------
# The stage clock inside the served path (PR 27): the grid call split into
# stage spans, on the three paths a grid call takes
# ---------------------------------------------------------------------------

G_STEP = 60_000
G_T0 = 1_700_000_040_000
G_ROWS = 96
GRID_BUCKETS = ("grid.resolve", "grid.lock_wait", "grid.plan", "batch.wait",
                "grid.dispatch", "grid.device_wait", "grid.readback",
                "grid.select")


def _grid_server(path, monkeypatch):
    """One local shard of uniform-phase counters that f32 holds, served
    by the device grid on the ``solo`` (per-query launch of the decoded
    plane), ``packed`` (fused compressed-resident kernels, interpret
    mode here) or ``stacked`` (fleet batcher attached) path."""
    from filodb_tpu.batching import QueryBatcher, reset_batch_breaker
    from filodb_tpu.core.storeconfig import StoreConfig
    from filodb_tpu.memstore import devicestore
    packed = path == "packed"
    monkeypatch.setattr(devicestore, "_PACKED_INTERPRET", packed)
    monkeypatch.setattr(devicestore, "_PACKED_BROKEN", False)
    if packed:
        monkeypatch.setattr(devicestore.DeviceGridCache, "_val_dtype",
                            lambda self: np.float32)
    reset_batch_breaker()
    ms = TimeSeriesMemStore()
    shard = ms.setup("grid", DEFAULT_SCHEMAS, 0,
                     StoreConfig(device_cache_compress=packed))
    rng = np.random.default_rng(7)
    b = RecordBuilder(DEFAULT_SCHEMAS["prom-counter"])
    for i in range(8):
        tags = {"__name__": "c_total", "instance": f"i{i}",
                "_ws_": "w", "_ns_": "n"}
        ph = int(rng.integers(1, G_STEP))
        ts = G_T0 + np.arange(G_ROWS, dtype=np.int64) * G_STEP - G_STEP + ph
        vals = (2 ** 23 + 128 * np.cumsum(
            rng.integers(1, 8, G_ROWS))).astype(np.float64)
        b.add_series(ts, [vals], tags)
    for off, c in enumerate(b.containers()):
        shard.ingest(decode_container(c, DEFAULT_SCHEMAS), off)
    shard.flush_all()
    if path == "stacked":
        shard.query_batcher = QueryBatcher(
            enabled=True, window_ms=150.0, max_batch=4, hot_ttl_s=30.0,
            dataset="grid")
    mapper = ShardMapper(1)
    mapper.register_node([0], "local")
    mapper.update_status(0, ShardStatus.ACTIVE)
    srv = FiloHttpServer()
    srv.bind_dataset(DatasetBinding(
        "grid", ms, SingleClusterPlanner("grid", mapper, DatasetOptions(),
                                         spread_default=0)))
    return srv, srv.start()


def _grid_query(port, query, first_step=7, **extra):
    return _get(port, "/promql/grid/api/v1/query_range", query=query,
                start=(G_T0 + first_step * G_STEP) / 1000,
                end=(G_T0 + (first_step + 60) * G_STEP) / 1000, step="60s",
                stats="true", **extra)


def _launches(program):
    from filodb_tpu.utils.devicewatch import device_metrics
    return device_metrics()["kernel_launches"].value(program=program)


def _grid_split_tiles(timings) -> bool:
    """All eight keys are there, none negative, together no more than
    the enclosing ``device_compute``; True where they also tile it to
    5% + 1 ms.  (What lies between two spans is a thread switch away
    from any size on a loaded test host, so a caller asks for the
    tiling of most requests, not of each.)"""
    for key in GRID_BUCKETS:
        assert key in timings, f"missing {key}: {sorted(timings)}"
        assert timings[key] >= 0.0
    parts = sum(timings[k] for k in GRID_BUCKETS)
    whole = timings["device_compute"]
    assert parts <= whole + 1e-5, (parts, whole)     # 6-decimal rounding
    assert timings["scan"] >= whole
    return whole - parts <= 0.05 * whole + 0.001


@pytest.mark.parametrize("path", ["solo", "packed", "stacked"])
@pytest.mark.parametrize("query, program", [
    ('rate(c_total{_ws_="w",_ns_="n"}[5m])', "series"),
    ('sum(rate(c_total{_ws_="w",_ns_="n"}[5m]))', "grouped")])
def test_grid_call_splits_into_eight_stages(path, query, program,
                                            monkeypatch):
    import threading
    srv, port = _grid_server(path, monkeypatch)
    try:
        if path != "stacked":
            served = "devicestore." + program + \
                ("_packed" if path == "packed" else "")
            before = _launches(served)
            tiled = []
            for _ in range(6):       # the first is cold: stages, compiles
                code, body, _h = _grid_query(port, query)
                assert code == 200 and body["data"]["result"]
                tiled.append(_grid_split_tiles(
                    body["data"]["stats"]["timings"]))
            assert sum(tiled) >= 4, tiled
            # the path under test really served, one launch a request
            assert _launches(served) == before + 6
            assert body["data"]["stats"]["timings"]["batch.wait"] == 0.0
            return
        stacked = "devicestore." + program + "_batch"
        before = _launches(stacked)
        tiled = []
        for _round in range(12):            # a group forms off an overlap
            barrier = threading.Barrier(5)
            out = {}

            def ask(i):
                barrier.wait()
                out[i] = _grid_query(port, query, first_step=7 + i)
            threads = [threading.Thread(target=ask, args=(i,))
                       for i in range(5)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            for code, body, _h in out.values():
                assert code == 200 and body["data"]["result"]
                tiled.append(_grid_split_tiles(
                    body["data"]["stats"]["timings"]))
            if _launches(stacked) > before:
                break
        assert _launches(stacked) > before, "no stacked launch in 12 rounds"
        assert sum(tiled) >= 0.6 * len(tiled), tiled
        # a member's request shows the rendezvous, the stacked launch
        # itself folds into the leader's
        waits = [b["data"]["stats"]["timings"]["batch.wait"]
                 for _c, b, _h in out.values()]
        assert max(waits) > 0.0
    finally:
        srv.shutdown()


class TestStageClockEndpoints:
    @pytest.fixture(scope="class")
    def grid(self):
        mp = pytest.MonkeyPatch()
        srv, port = _grid_server("solo", mp)
        yield port
        srv.shutdown()
        mp.undo()

    QUERY = 'sum(rate(c_total{_ws_="w",_ns_="n"}[5m]))'

    def test_admin_device_has_the_stage_table(self, grid):
        _c, before, _h = _get(grid, "/admin/device")
        code, _b, _h = _grid_query(grid, self.QUERY)
        assert code == 200
        b = before["data"]["stages"]
        # the handler thread hands its stages over once its request has
        # ended, which is after the client has read the answer: a table
        # read right then may still lack the query's ``serialize``
        for _ in range(100):
            _c, after, _h = _get(grid, "/admin/device")
            a = after["data"]["stages"]
            if a.get("serialize", {"count": 0})["count"] \
                    > b.get("serialize", {"count": 0})["count"]:
                break
            time.sleep(0.02)
        for name in ("http.request", "http.encode", "http.write",
                     "query.plan", "scan", "device_compute", "serialize",
                     "grid.resolve", "grid.lock_wait", "grid.plan",
                     "grid.dispatch", "grid.device_wait", "grid.readback",
                     "grid.select"):
            assert set(a[name]) == {"count", "wall_s", "cpu_s"}, name
            assert a[name]["count"] > b.get(name, {"count": 0})["count"], \
                name
            # CPU is not clamped to the wall: the two clocks' grain
            assert 0.0 <= a[name]["cpu_s"] <= a[name]["wall_s"] + 1e-3, name
        assert a["grid.build"]["count"] >= 1      # the cold block's staging

    def test_a_served_request_annotates_work_and_not_waits(self, grid,
                                                           monkeypatch):
        """The profiler's host plane gets the leaves that do work; the
        wait for the grid lock is a stage and no annotation, so a
        device-idle gap takes the name of the plan that holds the lock."""
        from filodb_tpu.utils.observability import TRACER
        seen = []

        class Ann:
            def __init__(self, name):
                seen.append(name)

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        monkeypatch.setattr(TRACER, "_annotate", Ann)
        code, body, _h = _grid_query(grid, self.QUERY)
        assert code == 200
        assert {"grid.plan", "grid.dispatch", "grid.device_wait",
                "grid.readback", "serialize", "http.encode"} <= set(seen)
        assert not {"grid.lock_wait", "scan", "device_compute",
                    "scheduler.run", "http.request"} & set(seen)
        assert "grid.lock_wait" in body["data"]["stats"]["timings"]

    def test_metrics_has_the_two_stage_families(self, grid):
        _grid_query(grid, self.QUERY)
        url = f"http://127.0.0.1:{grid}/metrics"
        text = urllib.request.urlopen(url, timeout=10).read().decode()
        assert ('filodb_stage_seconds_total{kind="wall",'
                'stage="grid.dispatch"}') in text
        assert ('filodb_stage_seconds_total{kind="cpu",'
                'stage="http.request"}') in text
        assert 'filodb_stage_total{stage="http.encode"}' in text

    def test_encode_and_write_join_the_querys_trace(self, grid):
        code, body, headers = _grid_query(grid, self.QUERY)
        tid = body["data"]["stats"]["traceId"]
        assert headers.get("X-FiloDB-Trace-Id") == tid
        # the handler's thread hands its spans over when http.request
        # ends, which is after the client holds the answer
        for _ in range(200):
            _c, tbody, _h = _get(grid, f"/admin/traces/{tid}")
            roots = tbody["data"]["spans"]
            if [r["name"] for r in roots] == ["query"] and any(
                    n["name"] == "http.write"
                    for n in roots[0]["children"]):
                break
            time.sleep(0.01)
        assert [r["name"] for r in roots] == ["query"]
        kids = {n["name"]: n for n in roots[0]["children"]}
        assert {"query.plan", "serialize", "http.encode",
                "http.write"} <= set(kids)
        flat = {n["name"]: n for n in _flatten(roots)}
        assert {"scan", "device_compute", "grid.dispatch",
                "grid.device_wait", "grid.readback"} <= set(flat)
        assert flat["grid.dispatch"]["tags"]["program"] == \
            "devicestore.grouped"
        assert int(flat["grid.readback"]["tags"]["bytes"]) > 0
        assert all("cpu_s" in n for n in flat.values())
        # nothing timed after the body was built can be in the body
        assert not any(k.startswith("http.")
                       for k in body["data"]["stats"]["timings"])

    FRONT = ("http.spawn", "http.accept", "http.read")

    def _counts(self):
        """The four rows once no handler has anything left to hand over
        (a handler flushes after its client holds the answer)."""
        from filodb_tpu.utils.observability import TRACER
        none = {"count": 0, "wall_s": 0.0, "cpu_s": 0.0}
        last = None
        for _ in range(100):
            table = TRACER.stages.snapshot()
            rows = {name: table.get(name, none)
                    for name in self.FRONT + ("http.request",)}
            if rows == last:
                return rows
            last = rows
            time.sleep(0.05)
        return last

    def test_a_request_adds_one_of_each_front_stage(self, grid):
        """From ``accept`` to the route: the listener's thread start, the
        handler's way to ``handle()`` and the request line and headers,
        one span each a request; ``http.request`` as before."""
        n = 5
        before = self._counts()
        for _ in range(n):
            code, _b, _h = _grid_query(grid, self.QUERY)
            assert code == 200
        after = self._counts()
        for name in self.FRONT + ("http.request",):
            assert after[name]["count"] - before[name]["count"] == n, name
            assert after[name]["wall_s"] > before[name]["wall_s"], name
        # only http.request of the four reads the CPU clock
        assert after["http.request"]["cpu_s"] > before["http.request"]["cpu_s"]
        for name in self.FRONT:
            assert after[name]["cpu_s"] == 0.0, name

    def test_the_spawn_says_how_the_connection_reached_its_thread(
            self, grid, monkeypatch):
        """``http.spawn`` carries ``thread``: on a warm server, whose
        handler threads have gone back to wait, a request is handed to
        one of them (``standing``), and no thread is started for it."""
        from filodb_tpu.utils.observability import TRACER
        spawns = []
        monkeypatch.setattr(TRACER, "_reporters", TRACER._reporters + (
            lambda recs: spawns.extend(r.tags for r in recs
                                       if r.name == "http.spawn"),))
        _grid_query(grid, self.QUERY)
        self._counts()                  # its thread flushed, and went idle
        del spawns[:]
        code, _b, _h = _grid_query(grid, self.QUERY)
        assert code == 200
        TRACER.stages.snapshot()        # folds the deferred span in
        assert spawns == [{"thread": "standing"}]

    def test_accept_and_read_join_the_querys_trace(self, grid):
        code, body, _h = _grid_query(grid, self.QUERY)
        tid = body["data"]["stats"]["traceId"]
        for _ in range(200):
            _c, tbody, _h = _get(grid, f"/admin/traces/{tid}")
            roots = tbody["data"]["spans"]
            kids = {n["name"]: n for r in roots for n in r["children"]}
            if "http.read" in kids:
                break
            time.sleep(0.01)
        assert [r["name"] for r in roots] == ["query"]
        assert {"http.accept", "http.read", "http.encode"} <= set(kids)
        accept, read = kids["http.accept"], kids["http.read"]
        # the accept ends where the read begins, and both before the query
        assert accept["start_s"] <= read["start_s"] <= roots[0]["start_s"]
        assert read["start_s"] == pytest.approx(
            accept["start_s"] + accept["duration_s"], abs=1e-6)
        assert "http.spawn" not in kids       # the listener's: no trace

    def test_the_read_is_annotated_and_the_waits_are_not(self, grid,
                                                         monkeypatch):
        from filodb_tpu.utils.observability import TRACER
        seen = []

        class Ann:
            def __init__(self, name):
                seen.append(name)

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        monkeypatch.setattr(TRACER, "_annotate", Ann)
        code, _b, _h = _grid_query(grid, self.QUERY)
        assert code == 200
        assert "http.read" in seen
        assert not {"http.accept", "http.spawn"} & set(seen)

    def test_plan_bucket_is_the_plan_spans_duration(self, grid):
        _c, body, _h = _grid_query(grid, self.QUERY)
        tid = body["data"]["stats"]["traceId"]
        _c, tbody, _h = _get(grid, f"/admin/traces/{tid}")
        plan = [n for n in _flatten(tbody["data"]["spans"])
                if n["name"] == "query.plan"][0]
        assert body["data"]["stats"]["timings"]["plan"] == \
            pytest.approx(plan["duration_s"], abs=2e-6)

    def test_launch_and_compile_counters_read_as_before(self, grid):
        """What the benchmark's device_dispatches and compiles_in_window
        read: one launch of a devicestore program a served request, and
        the compile table's rows."""
        _grid_query(grid, self.QUERY)
        n0 = _launches("devicestore.grouped")
        _c, d0, _h = _get(grid, "/admin/device")
        _grid_query(grid, self.QUERY)
        assert _launches("devicestore.grouped") == n0 + 1
        _c, d1, _h = _get(grid, "/admin/device")
        rows = {p["program"]: p for p in d1["data"]["compile"]["programs"]}
        assert rows["devicestore.grouped"]["compiles"] >= 1
        assert sum(p["compiles"] for p in rows.values()) == sum(
            p["compiles"] for p in d0["data"]["compile"]["programs"])
        url = f"http://127.0.0.1:{grid}/metrics"
        text = urllib.request.urlopen(url, timeout=10).read().decode()
        assert 'filodb_kernel_launches_total{program="devicestore.grouped"}' \
            in text


def test_frontier_walk_is_a_stage_and_a_tag_of_the_plan(monkeypatch):
    """PR 28: the walk over every resident lane runs once per shard
    state, as the leaf stage ``grid.frontier`` inside ``grid.plan``; a
    plan that found the frontier memoized adds no such span, and says
    so in its ``frontier`` tag."""
    srv, port = _grid_server("solo", monkeypatch)
    query = 'sum(rate(c_total{_ws_="w",_ns_="n"}[5m]))'

    def ask(first_step):
        _c, dev, _h = _get(port, "/admin/device")
        before = dev["data"]["stages"].get("grid.frontier", {"count": 0})
        code, body, _h = _grid_query(port, query, first_step=first_step)
        assert code == 200 and body["data"]["result"]
        _c, dev, _h = _get(port, "/admin/device")
        after = dev["data"]["stages"].get("grid.frontier", {"count": 0})
        tid = body["data"]["stats"]["traceId"]
        _c, tbody, _h = _get(port, f"/admin/traces/{tid}")
        flat = _flatten(tbody["data"]["spans"])
        plan = [n for n in flat if n["name"] == "grid.plan"][0]
        return (after["count"] - before["count"], plan,
                body["data"]["stats"]["timings"])

    try:
        walked, plan, timings = ask(7)          # the first plan walks
        assert walked == 1 and plan["tags"]["frontier"] == "walk"
        assert [n["name"] for n in plan["children"]
                if n["name"] == "grid.frontier"] == ["grid.frontier"]
        assert 0.0 <= timings["grid.frontier"] <= timings["grid.plan"]
        for first_step in (8, 8):   # a plan-memo miss, then a plan-memo hit
            walked, plan, timings = ask(first_step)
            assert walked == 0 and plan["tags"]["frontier"] == "memo"
            assert not [n for n in plan["children"]
                        if n["name"] == "grid.frontier"]
            assert "grid.frontier" not in timings
    finally:
        srv.shutdown()
