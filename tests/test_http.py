"""HTTP API: Prometheus-compatible routes against a live threaded server.

Mirrors the reference's HTTP route specs (reference:
http/src/test/.../PrometheusApiRouteSpec.scala — parse -> plan -> execute
-> Prometheus JSON; HealthRoute / ClusterApiRoute specs).
"""

import json
import urllib.parse
import urllib.request

import numpy as np
import pytest

from filodb_tpu.coordinator.cluster import ShardManager
from filodb_tpu.coordinator.planner import SingleClusterPlanner
from filodb_tpu.core.record import RecordBuilder, decode_container
from filodb_tpu.core.schemas import DEFAULT_SCHEMAS, DatasetOptions
from filodb_tpu.http.model import parse_duration_ms, parse_time_ms
from filodb_tpu.http.server import DatasetBinding, FiloHttpServer
from filodb_tpu.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.parallel.shardmap import ShardMapper, ShardStatus

BASE = 1_700_000_000_000
STEP = 10_000


def _get(port, path, **params):
    qs = urllib.parse.urlencode(params)
    url = f"http://127.0.0.1:{port}{path}" + (f"?{qs}" if qs else "")
    try:
        with urllib.request.urlopen(url, timeout=10) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _post(port, path, **params):
    data = urllib.parse.urlencode(params).encode()
    url = f"http://127.0.0.1:{port}{path}"
    req = urllib.request.Request(url, data=data, method="POST")
    req.add_header("Content-Type", "application/x-www-form-urlencoded")
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


@pytest.fixture(scope="module")
def server():
    num_shards = 4
    mapper = ShardMapper(num_shards)
    mapper.register_node(range(num_shards), "local")
    ms = TimeSeriesMemStore()
    for s in range(num_shards):
        mapper.update_status(s, ShardStatus.ACTIVE)
        ms.setup("prom", DEFAULT_SCHEMAS, s)
    rng = np.random.default_rng(0)
    builder = RecordBuilder(DEFAULT_SCHEMAS["prom-counter"])
    for i in range(6):
        tags = {"__name__": "http_requests_total", "job": "api",
                "instance": f"i{i}", "_ws_": "demo", "_ns_": "App-0"}
        ts = BASE + np.arange(200) * STEP
        vals = np.cumsum(rng.random(200) * 5)
        for t, v in zip(ts, vals):
            builder.add(int(t), [float(v)], tags)
    spread = 1
    for off, c in enumerate(builder.containers()):
        per_shard = {}
        for rec in decode_container(c, DEFAULT_SCHEMAS):
            shard = mapper.ingestion_shard(rec.shard_hash, rec.part_hash,
                                           spread) % num_shards
            per_shard.setdefault(shard, []).append(rec)
        for shard, recs in per_shard.items():
            ms.get_shard("prom", shard).ingest(recs, off)

    mgr = ShardManager()
    mgr.setup_dataset("prom", num_shards, min_num_nodes=1)
    mgr.add_node("local")

    planner = SingleClusterPlanner("prom", mapper, DatasetOptions(),
                                   spread_default=spread)
    srv = FiloHttpServer(shard_manager=mgr)
    srv.bind_dataset(DatasetBinding("prom", ms, planner))
    port = srv.start()
    yield port
    srv.shutdown()


class TestQueryRange:
    def test_matrix_result(self, server):
        code, body = _get(server, "/promql/prom/api/v1/query_range",
                          query='sum(rate(http_requests_total{_ws_="demo",_ns_="App-0"}[2m]))',
                          start=(BASE + 600_000) / 1000,
                          end=(BASE + 1_200_000) / 1000, step="30s")
        assert code == 200
        assert body["status"] == "success"
        assert body["data"]["resultType"] == "matrix"
        result = body["data"]["result"]
        assert len(result) == 1  # sum() -> one series
        values = result[0]["values"]
        assert len(values) > 10
        ts0, v0 = values[0]
        assert float(v0) > 0  # positive rate of a counter
        # timestamps are unix seconds on the step grid
        assert abs(ts0 * 1000 - round(ts0 * 1000)) < 1e-6

    def test_raw_selector(self, server):
        code, body = _get(server, "/promql/prom/api/v1/query_range",
                          query='http_requests_total{job="api"}',
                          start=(BASE + 300_000) / 1000,
                          end=(BASE + 900_000) / 1000, step="10s")
        assert code == 200
        assert len(body["data"]["result"]) == 6
        metrics = {r["metric"]["instance"] for r in body["data"]["result"]}
        assert metrics == {f"i{i}" for i in range(6)}

    def test_post_form(self, server):
        code, body = _post(server, "/promql/prom/api/v1/query_range",
                           query='count(http_requests_total)',
                           start=(BASE + 600_000) / 1000,
                           end=(BASE + 700_000) / 1000, step="30s")
        assert code == 200
        vals = body["data"]["result"][0]["values"]
        assert all(v == "6" for _, v in vals)

    def test_post_json_numeric_params(self, server):
        """JSON bodies may carry numbers; they must behave like their
        query-string (string) equivalents."""
        url = (f"http://127.0.0.1:{server}"
               "/promql/prom/api/v1/query_range")
        req = urllib.request.Request(
            url, method="POST",
            data=json.dumps({"query": "count(http_requests_total)",
                             "start": (BASE + 600_000) / 1000,
                             "end": (BASE + 700_000) / 1000,
                             "step": 30}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req) as resp:
            body = json.loads(resp.read())
        assert body["status"] == "success"
        vals = body["data"]["result"][0]["values"]
        assert all(v == "6" for _, v in vals)

    def test_post_json_array_is_400(self, server):
        """A JSON array body is a client error, not a 500."""
        url = (f"http://127.0.0.1:{server}"
               "/promql/prom/api/v1/query_range")
        req = urllib.request.Request(
            url, method="POST", data=json.dumps([1, 2]).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req)
        assert ei.value.code == 400
        assert json.loads(ei.value.read())["errorType"] == "bad_data"

    def test_parse_error_is_400(self, server):
        code, body = _get(server, "/promql/prom/api/v1/query_range",
                          query='sum(rate(', start="1", end="2", step="15s")
        assert code == 400
        assert body["status"] == "error"

    def test_unknown_dataset_404(self, server):
        code, body = _get(server, "/promql/nope/api/v1/query_range",
                          query="up", start="1", end="2")
        assert code == 404


class TestInstantQuery:
    def test_vector_result(self, server):
        code, body = _get(server, "/promql/prom/api/v1/query",
                          query='http_requests_total{instance="i0"}',
                          time=(BASE + 900_000) / 1000)
        assert code == 200
        assert body["data"]["resultType"] == "vector"
        assert len(body["data"]["result"]) == 1
        t, v = body["data"]["result"][0]["value"]
        assert t == (BASE + 900_000) / 1000
        assert float(v) > 0

    def test_scalar(self, server):
        code, body = _get(server, "/promql/prom/api/v1/query",
                          query="scalar(count(http_requests_total))",
                          time=(BASE + 900_000) / 1000)
        assert code == 200
        assert body["data"]["resultType"] == "scalar"
        assert body["data"]["value"][1] == "6"


class TestMetadata:
    def test_labels(self, server):
        code, body = _get(server, "/promql/prom/api/v1/labels")
        assert code == 200
        assert "job" in body["data"] and "instance" in body["data"]

    def test_label_values(self, server):
        code, body = _get(server, "/promql/prom/api/v1/label/instance/values")
        assert code == 200
        assert body["data"] == [f"i{i}" for i in range(6)]

    def test_series(self, server):
        code, body = _get(server, "/promql/prom/api/v1/series",
                          **{"match[]": 'http_requests_total{instance=~"i[01]"}'})
        assert code == 200
        insts = sorted(s["instance"] for s in body["data"])
        assert insts == ["i0", "i1"]


class TestAdmin:
    def test_health(self, server):
        code, body = _get(server, "/__health")
        assert code == 200
        assert body["healthy"] is True
        statuses = {s["status"] for s in body["shards"]["prom"]}
        assert statuses <= {"Active", "Assigned", "Recovery"}

    def test_cluster_status(self, server):
        code, body = _get(server, "/api/v1/cluster/prom/status")
        assert code == 200
        assert len(body["data"]) == 4
        assert all(s["node"] == "local" for s in body["data"])

    def test_stop_start_shards(self, server):
        code, body = _post(server, "/api/v1/cluster/prom/stopshards",
                           shards="3")
        assert code == 200 and body["data"] == [3]
        code, body = _get(server, "/api/v1/cluster/prom/status")
        assert body["data"][3]["status"] == "Stopped"
        # startshards requires an unassigned shard: stopped keeps its node,
        # so this is a no-op returning []
        code, body = _post(server, "/api/v1/cluster/prom/startshards",
                           shards="3", node="local")
        assert code == 200 and body["data"] == []
        # missing node param on startshards is a 400, not a 500
        code, body = _post(server, "/api/v1/cluster/prom/startshards",
                           shards="3")
        assert code == 400


def test_param_parsing():
    assert parse_time_ms("1700000000") == 1_700_000_000_000
    assert parse_time_ms("1700000000.5") == 1_700_000_000_500
    assert parse_duration_ms("15s") == 15_000
    assert parse_duration_ms("1m") == 60_000
    assert parse_duration_ms("250ms") == 250
    assert parse_duration_ms("2h") == 7_200_000
    assert parse_duration_ms("30") == 30_000


def test_more_clients_than_five_connect_at_once_and_none_waits_a_second():
    """The stdlib's listen queue of 5 drops the SYNs of every client over
    it while the accept thread is away (a collection; panels that refresh
    together), and each such client waits out the kernel's retransmit, a
    whole second, before its request is read.  Twelve connect while
    nobody accepts for 0.2 s: every one is answered well inside a
    second."""
    import http.client
    import threading
    import time

    srv = FiloHttpServer(shard_manager=ShardManager())
    port = srv.start()
    took = []
    try:
        assert srv._httpd.request_queue_size >= 64
        # the accept thread, away: it does not accept until the gate opens
        accept_fn = srv._httpd.get_request
        gate = threading.Event()

        def away():
            gate.wait(5)
            return accept_fn()

        srv._httpd.get_request = away

        def one():
            t0 = time.time()
            c = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
            c.request("GET", "/__health")
            c.getresponse().read()
            c.close()
            took.append(time.time() - t0)

        clients = [threading.Thread(target=one) for _ in range(12)]
        for t in clients:
            t.start()
        time.sleep(0.2)
        gate.set()
        for t in clients:
            t.join(15)
    finally:
        srv.shutdown()
    assert len(took) == 12 and max(took) < 0.9, sorted(took)


# ----------------------------------------------- standing handler threads


def _health(port, query=""):
    url = f"http://127.0.0.1:{port}/__health" + (f"?{query}" if query else "")
    with urllib.request.urlopen(url, timeout=10) as resp:
        resp.read()
        return resp.status


def _wait_idle(srv, n=1):
    """Until ``n`` standing threads wait for a connection: the one that
    answered last has closed its socket and gone back to the queue."""
    import time
    deadline = time.monotonic() + 10
    while srv._httpd._idle.qsize() < n:
        assert time.monotonic() < deadline, "no handler thread went idle"
        time.sleep(0.002)


def _spy_route(srv, before):
    """Runs ``before(req)`` on the handler thread ahead of each route."""
    real = srv._handle_request

    def spy(req, method):
        before(req)
        real(req, method)

    srv._handle_request = spy


def test_sequential_requests_are_served_by_standing_threads():
    """The accept thread hands each connection to a thread that is
    already running: twenty requests one after another take at most two
    threads (the first, and one started while the first was still
    closing its socket), and all but those two starts are hand-offs."""
    import threading

    from filodb_tpu.http.server import _HANDOFFS
    srv = FiloHttpServer(shard_manager=ShardManager())
    idents = set()
    _spy_route(srv, lambda req: idents.add(threading.get_ident()))
    port = srv.start()
    standing = _HANDOFFS.value(thread="standing")
    try:
        for _ in range(20):
            assert _health(port) == 200
    finally:
        srv.shutdown()
    assert 1 <= len(idents) <= 2, idents
    assert _HANDOFFS.value(thread="standing") - standing >= 18


def test_a_burst_over_the_idle_threads_starts_more_and_is_all_served():
    """Thirty-two connections that are all inside a handler at once
    (each waits for the others at a barrier): with one or two threads
    idle the listener starts the rest, as many as are in flight; there
    is no cap a handler could wait behind."""
    import concurrent.futures
    import threading

    from filodb_tpu.http.server import _HANDOFFS
    srv = FiloHttpServer(shard_manager=ShardManager())
    barrier = threading.Barrier(32, timeout=10)
    _spy_route(srv, lambda req: "burst" in req.path and barrier.wait())
    port = srv.start()
    try:
        assert _health(port) == 200
        _wait_idle(srv)
        started = _HANDOFFS.value(thread="started")
        with concurrent.futures.ThreadPoolExecutor(32) as pool:
            codes = list(pool.map(lambda _: _health(port, "burst=1"),
                                  range(32)))
        assert codes == [200] * 32
        assert _HANDOFFS.value(thread="started") - started >= 30
        assert srv._httpd._started >= 32
    finally:
        srv.shutdown()


def test_a_handler_that_calls_its_own_server_is_answered():
    """A route that asks this same server (a node dispatching to itself,
    the fleet aggregator) holds its thread while it waits: with one
    thread idle, four nested calls each need one more, and get it."""
    import time

    srv = FiloHttpServer(shard_manager=ShardManager())

    def nest(req):
        if "hops=" in req.path:
            hops = int(req.path.rsplit("=", 1)[1])
            if hops:
                assert _health(port, f"hops={hops - 1}") == 200

    _spy_route(srv, nest)
    port = srv.start()
    try:
        assert _health(port) == 200
        _wait_idle(srv)
        t0 = time.monotonic()
        assert _health(port, "hops=4") == 200
        assert time.monotonic() - t0 < 10
        assert srv._httpd._started >= 5
    finally:
        srv.shutdown()


def test_shutdown_leaves_no_thread_of_the_listener():
    """``shutdown()`` stops every standing thread: the idle ones at once
    (woken and joined), one still closing its last socket right after."""
    import concurrent.futures
    import threading
    import time

    before = set(threading.enumerate())
    srv = FiloHttpServer(shard_manager=ShardManager())
    port = srv.start()
    with concurrent.futures.ThreadPoolExecutor(8) as pool:
        assert set(pool.map(lambda _: _health(port), range(24))) == {200}
    assert srv._httpd._started >= 1
    srv.shutdown()
    deadline = time.monotonic() + 10
    while set(threading.enumerate()) - before:
        assert time.monotonic() < deadline, \
            sorted(t.name for t in set(threading.enumerate()) - before)
        time.sleep(0.01)


def test_a_request_after_one_that_raised_sees_only_its_own_state(
        monkeypatch):
    """A standing thread serves request after request: one whose handler
    raised with a span open, a trace attached, a query's context active
    and ``_answered`` set leaves none of it to the next request on the
    same thread, and the next one's flush carries only its own spans."""
    import threading

    from filodb_tpu.query import exec as qexec
    from filodb_tpu.utils.observability import TRACER
    srv = FiloHttpServer(shard_manager=ShardManager())
    flushed = []            # (thread, [(span name, trace id)]) a flush
    monkeypatch.setattr(TRACER, "_reporters", TRACER._reporters + (
        lambda recs: flushed.append((threading.get_ident(), [
            (r.name, r.trace_id) for r in recs])),))
    seen = {}

    def route(req):
        if "boom" in req.path:
            seen["boom"] = threading.get_ident()
            srv._answered.tok = ("t-boom", "s-boom")
            with TRACER.attach(("t-boom", "s-boom")), \
                    TRACER.stage("boom.stage"), \
                    qexec.leaf_scan(qexec.ExecContext(TimeSeriesMemStore())):
                raise RuntimeError("boom")
        if "ok" in req.path:
            seen["ok"] = threading.get_ident()
            seen["state"] = (TRACER.current_trace_id(),
                             TRACER.current_span(), qexec.active_exec_ctx(),
                             srv._answered.tok)
            with TRACER.attach(("t-ok", "s-ok")), TRACER.stage("ok.stage"):
                pass

    _spy_route(srv, route)
    port = srv.start()
    try:
        assert _health(port) == 200
        _wait_idle(srv)
        with pytest.raises(Exception):     # the connection closed unanswered
            _health(port, "boom=1")
        _wait_idle(srv)
        assert _health(port, "ok=1") == 200
        _wait_idle(srv)
        assert srv._httpd._started == 1
    finally:
        srv.shutdown()
    assert seen["ok"] == seen["boom"]
    assert seen["state"] == (None, "http.request", None, None)
    mine = [spans for ident, spans in flushed if ident == seen["ok"]]
    ok = [spans for spans in mine if ("ok.stage", "t-ok") in spans]
    assert len(ok) == 1
    assert not [s for s in ok[0] if s[0] == "boom.stage" or s[1] == "t-boom"]
    assert any(("boom.stage", "t-boom") in spans for spans in mine)


def test_every_connection_is_handed_off_once_under_a_short_switch_interval():
    """Sixty-four clients against the accept thread and the standing
    threads with the interpreter switching every microsecond: every
    request is answered, and the hand-offs counted are the connections
    made, each once (a token lost or taken twice leaves a connection
    unserved or a thread serving two)."""
    import concurrent.futures
    import sys

    from filodb_tpu.http.server import _HANDOFFS
    srv = FiloHttpServer(shard_manager=ShardManager())
    port = srv.start()
    before = sum(_HANDOFFS.value(thread=k) for k in ("standing", "started"))
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with concurrent.futures.ThreadPoolExecutor(64) as pool:
            codes = list(pool.map(lambda _: _health(port), range(640),
                                  timeout=120))
        threads = srv._httpd._started
    finally:
        sys.setswitchinterval(old)
        srv.shutdown()
    assert codes == [200] * 640
    after = sum(_HANDOFFS.value(thread=k) for k in ("standing", "started"))
    assert after - before == 640
    # no more threads than connections in flight or closing at once
    assert 1 <= threads <= 128, threads
