"""Chaos e2e (ISSUE 7 acceptance criteria): a 3-node rf=2 cluster under
active ingest + queries survives a hard node kill with ZERO
``ShardUnavailable`` surfaced to clients and results bit-equal to a
no-fault oracle run; the killed node rejoins, replays from its own
checkpoint, is held in Recovery, and is promoted to Active only after
its watermark reaches the replica group's head — without double-counting
a single sample.  A partition (proxy blackhole) scenario rides along.

Marked slow-ish but kept in tier-1: this is THE acceptance test for the
replica-group layer.
"""

import json
import socket
import threading
import time
import urllib.error
import urllib.parse
import urllib.request

import numpy as np
import pytest

from filodb_tpu.core.record import RecordBuilder, decode_container
from filodb_tpu.core.schemas import DEFAULT_SCHEMAS
from filodb_tpu.ingest.broker import BrokerClient, BrokerServer
from filodb_tpu.integrity.faultinject import (FlakyTcpProxy,
                                              NodeChaosController)
from filodb_tpu.parallel.shardmap import ShardMapper
from filodb_tpu.standalone import FiloServer

BASE = 1_700_000_000_000
NUM_SHARDS = 4
NODES = ("ha-a", "ha-b", "ha-c")
# the frozen query window: fully ingested BEFORE any fault, so every
# query against it — oracle, mid-kill, post-rejoin — must be bit-equal
N_INSTANCES = 12
N_SAMPLES = 240            # 1s apart -> [BASE, BASE+240s)
WINDOW = (BASE + 60_000, BASE + 180_000)

# no shard-key matcher: the planner fans out to EVERY active shard, so
# the scatter-gather always crosses the replica group that excludes the
# coordinator — the kill is guaranteed to exercise failover routing
RATE_Q = 'sum(rate(ha_total[2m]))'
# duplicate-SENSITIVE shapes: a double-ingested sample changes these
COUNT_Q = 'sum(count_over_time(ha_total[1m]))'
SUM_Q = 'sum(sum_over_time(ha_total[1m]))'


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _get(port, path, timeout=30, **params):
    qs = urllib.parse.urlencode(params)
    url = f"http://127.0.0.1:{port}{path}" + (f"?{qs}" if qs else "")
    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read()), dict(resp.headers)
    except urllib.error.HTTPError as e:
        try:
            return e.code, json.loads(e.read()), dict(e.headers)
        except Exception:
            return e.code, {"error": str(e)}, {}


def _query(port, promql):
    return _get(port, "/promql/ha/api/v1/query_range", timeout=25,
                query=promql, start=WINDOW[0] / 1000, end=WINDOW[1] / 1000,
                step="15s")


def _node_config(node, http_port, broker_port, data_dir, peer_endpoints):
    return {
        "node": node,
        "http-port": http_port,
        "data-dir": str(data_dir),
        "peers": dict(peer_endpoints),
        "status-poll-interval-s": 0.25,
        "failure-detector-timeout-ms": 1_500,
        "dataplane": {"watermark-sample-interval-s": 3600},
        "datasets": [{
            "name": "ha", "num-shards": NUM_SHARDS, "min-num-nodes": 3,
            "replication-factor": 2, "schema": "gauge", "spread": 1,
            "source": {"factory": "broker", "port": broker_port,
                       "topic": "ha"},
            "store": {"flush-interval": "1h", "groups-per-shard": 4},
            "workload": {"dispatch": {"retries": 1, "backoff-s": 0.01,
                                      "timeout-cap-s": 10}},
        }],
    }


def _produce_frozen(client, route_mapper):
    """The oracle dataset: N_INSTANCES series x N_SAMPLES, routed by the
    same bit-splice the cluster uses, one container per (shard, batch)."""
    by_shard = {s: RecordBuilder(DEFAULT_SCHEMAS["gauge"],
                                 container_size=1 << 16)
                for s in range(NUM_SHARDS)}
    from filodb_tpu.core.record import partition_hash, shard_key_hash
    from filodb_tpu.core.schemas import DatasetOptions
    opts = DatasetOptions()
    rng = np.random.default_rng(7)
    n = 0
    for i in range(N_INSTANCES):
        tags = {"_metric_": "ha_total", "instance": f"i{i}",
                "_ws_": "w", "_ns_": "n"}
        shard = route_mapper.ingestion_shard(
            shard_key_hash(tags, opts), partition_hash(tags, opts),
            1) % NUM_SHARDS
        vals = np.cumsum(rng.random(N_SAMPLES))
        for k in range(N_SAMPLES):
            by_shard[shard].add(BASE + k * 1000, [float(vals[k])], tags)
            n += 1
    for s, b in by_shard.items():
        for c in b.containers():
            client.produce("ha", s, c)
    return n


def _bg_container(i):
    """Background-ingest traffic: timestamps BEYOND the frozen window so
    live ingest never perturbs the oracle comparison."""
    b = RecordBuilder(DEFAULT_SCHEMAS["gauge"], container_size=1 << 13)
    b.add(BASE + 400_000 + i * 250, [float(i)],
          {"__name__": "ha_bg", "instance": f"bg{i % 11}", "_ws_": "w",
           "_ns_": "n"})
    (out,) = b.containers()
    return out


def _broker_rows(client, shard, from_offset):
    """Exact sample rows held by the broker log at/above an offset."""
    rows = 0
    off = from_offset
    while True:
        batch = client.fetch("ha", shard, off, wait_ms=0)
        if not batch:
            return rows
        for o, msg in batch:
            rows += sum(1 for _ in decode_container(msg, DEFAULT_SCHEMAS))
            off = o + 1


def _canon(body):
    """Canonical form of a query_range result for bit-equality."""
    series = body["data"]["result"]
    return sorted((tuple(sorted(s["metric"].items())),
                   tuple((t, v) for t, v in s["values"]))
                  for s in series)


def _lag_zero(port, expect_rows):
    code, body, _ = _get(port, "/admin/shards", timeout=10)
    if code != 200:
        return False
    ds = body["data"]["datasets"].get("ha")
    if ds is None:
        return False
    total = sum(r["rows_ingested"] for r in ds["shards"])
    return total >= expect_rows


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    broker = BrokerServer(port=0)
    broker.start()
    client = BrokerClient(port=broker.port)
    client.create_topic("ha", NUM_SHARDS)

    route_mapper = ShardMapper(NUM_SHARDS)
    n_frozen = _produce_frozen(client, route_mapper)

    ports = {n: _free_port() for n in NODES}
    proxies = {n: FlakyTcpProxy(backend_port=ports[n]) for n in NODES}
    for p in proxies.values():
        p.start()
    # every node's view of its peers goes THROUGH the chaos proxies, so
    # partitions/stalls hit gossip and dispatch alike
    peer_eps = {n: f"http://127.0.0.1:{proxies[n].port}" for n in NODES}

    dirs = {n: tmp_path_factory.mktemp(n) for n in NODES}
    servers = {}
    chaos = NodeChaosController()
    for n in NODES:
        servers[n] = FiloServer(_node_config(n, ports[n], broker.port,
                                             dirs[n], peer_eps))
        servers[n].start()
        chaos.register(
            n,
            kill_fn=(lambda _s=servers[n]: (_s.http.shutdown(),
                                            _s.shutdown())),
            proxy=proxies[n])

    # convergence: every node ingested the frozen dataset on every shard
    # replica it holds, and the leader sees rf=2 live groups
    deadline = time.time() + 60
    converged = False
    while time.time() < deadline:
        leader = servers[NODES[0]]
        m = leader.manager.mapper("ha")
        groups_ok = all(len(m.live_replicas(s)) == 2
                        for s in range(NUM_SHARDS))
        rows_ok = all(
            sum(sh.stats.rows_ingested
                for sh in servers[n].memstore.shards("ha"))
            >= sum(N_SAMPLES for i in range(N_INSTANCES)
                   if _shard_of(route_mapper, i) in
                   set(m.shards_for_node(n)))
            for n in NODES)
        statuses_ok = all(
            r.status.value == "Active"
            for s in range(NUM_SHARDS) for r in m.live_replicas(s))
        if groups_ok and rows_ok and statuses_ok:
            converged = True
            break
        time.sleep(0.1)
    assert converged, "3-node rf=2 cluster never converged"

    yield {"servers": servers, "ports": ports, "proxies": proxies,
           "chaos": chaos, "client": client, "broker": broker,
           "dirs": dirs, "peer_eps": peer_eps, "n_frozen": n_frozen}

    for n, srv in servers.items():
        if not chaos.killed(n):
            try:
                srv.shutdown()
            except Exception:
                pass
    for p in proxies.values():
        p.shutdown()
    client.close()
    broker.shutdown()


def _shard_of(route_mapper, i):
    from filodb_tpu.core.record import partition_hash, shard_key_hash
    from filodb_tpu.core.schemas import DatasetOptions
    opts = DatasetOptions()
    tags = {"_metric_": "ha_total", "instance": f"i{i}",
            "_ws_": "w", "_ns_": "n"}
    return route_mapper.ingestion_shard(
        shard_key_hash(tags, opts), partition_hash(tags, opts),
        1) % NUM_SHARDS


class TestChaosKillFailoverRejoin:
    """One ordered scenario (method order matters: pytest runs them in
    definition order within the module-scoped cluster)."""

    def test_1_oracle_and_kill_failover(self, cluster):
        from filodb_tpu.utils.observability import REGISTRY
        ports = cluster["ports"]
        chaos = cluster["chaos"]
        client = cluster["client"]

        # ---- no-fault oracle run on the coordinator we will query
        oracles = {}
        for q in (RATE_Q, COUNT_Q, SUM_Q):
            code, body, headers = _query(ports["ha-a"], q)
            assert code == 200 and body["status"] == "success", body
            assert body["data"]["result"], f"oracle empty for {q}"
            assert headers.get("X-FiloDB-Partial-Data") is None
            oracles[q] = _canon(body)
        cluster["oracles"] = oracles

        # checkpoint everything so the killed node can later replay
        # from its own checkpoint (the rejoin acceptance criterion)
        for n in NODES:
            cluster["servers"][n].flush_all()

        # ---- background ingest: the cluster is live while we kill
        stop_produce = threading.Event()

        def produce_loop():
            i = 0
            while not stop_produce.is_set():
                shard = i % NUM_SHARDS
                try:
                    client.produce("ha", shard, _bg_container(i))
                except Exception:
                    pass
                i += 1
                time.sleep(0.002)

        producer = threading.Thread(target=produce_loop, daemon=True)
        producer.start()
        cluster["stop_produce"] = stop_produce
        cluster["producer"] = producer

        failover = REGISTRY.counter("filodb_dispatch_failover_total")
        failover_before = failover.total()

        # ---- queries in flight while the node dies
        results = []

        # a COUNT of answers on each side of the kill, not a rate of this
        # host: at least 5 before it, 16 after it, and the old 6 s
        killed_at = []              # len(results) when the node died

        def query_loop(seconds, after, wall):
            t0 = time.time()
            while time.time() < t0 + wall:
                if (killed_at and len(results) >= killed_at[0] + after
                        and time.time() >= t0 + seconds):
                    return
                q = (RATE_Q, COUNT_Q, SUM_Q)[len(results) % 3]
                code, body, headers = _query(ports["ha-a"], q)
                results.append((q, code, body, headers))
                time.sleep(0.05)

        qt = threading.Thread(target=query_loop, args=(6.0, 16, 180.0),
                              daemon=True)
        qt.start()
        t_kill = time.time() + 0.8
        while qt.is_alive() and (time.time() < t_kill or len(results) < 5):
            time.sleep(0.01)
        # what the coordinator's routing saw at the kill, for the
        # failover assertion's message: (node, status, head - watermark)
        m = cluster["servers"]["ha-a"].manager.mapper("ha")
        routes = {s: [(r.node, r.status.value,
                       m.group_head(s) - r.watermark)
                      for r in m.replicas(s)] for s in range(NUM_SHARDS)}
        chaos.kill("ha-b")          # hard node kill, mid-query, mid-ingest
        killed_at.append(len(results))
        qt.join(timeout=240)
        assert len(results) >= killed_at[0] + 16, \
            f"{len(results) - killed_at[0]} answers in 180 s after the kill"

        assert len(results) > 20
        bad = [(q, code) for q, code, body, _h in results if code != 200
               or body.get("status") != "success"]
        assert not bad, f"client-visible failures across the kill: {bad}"
        partial = [h for _q, _c, _b, h in results
                   if h.get("X-FiloDB-Partial-Data")]
        assert not partial, "partial results surfaced despite a live replica"
        # bit-equality of every mid-kill answer with the no-fault oracle
        for q, _code, body, _h in results:
            assert _canon(body) == oracles[q], \
                f"mid-kill result diverged from oracle for {q}"
        # and the kill actually exercised replica failover
        assert failover.total() > failover_before, \
            f"no failover happened — the kill never hit a routed replica " \
            f"({killed_at[0]} answers before it, {len(results)} in all; " \
            f"replica groups at the kill: {routes})"

    def test_2_survivors_demote_dead_replicas(self, cluster):
        servers = cluster["servers"]
        deadline = time.time() + 20
        demoted = False
        while time.time() < deadline:
            m = servers["ha-a"].manager.mapper("ha")
            dead = [s for s in range(NUM_SHARDS)
                    if any(r.node == "ha-b" and r.status.value == "Down"
                           for r in m.replicas(s))]
            held = [s for s in range(NUM_SHARDS)
                    if any(r.node == "ha-b" for r in m.replicas(s))]
            if held and len(dead) == len(held):
                demoted = True
                break
            time.sleep(0.1)
        assert demoted, "leader never demoted the killed node's replicas"
        # every shard still queryable from the surviving replica
        m = servers["ha-a"].manager.mapper("ha")
        for s in range(NUM_SHARDS):
            assert m.best_status(s).queryable
        # queries remain clean AFTER detection settled, too
        code, body, headers = _query(cluster["ports"]["ha-a"], COUNT_Q)
        assert code == 200
        assert headers.get("X-FiloDB-Partial-Data") is None
        assert _canon(body) == cluster["oracles"][COUNT_Q]

    def test_3_rejoin_recovers_and_promotes_at_group_head(self, cluster):
        ports = cluster["ports"]
        chaos = cluster["chaos"]
        servers = cluster["servers"]
        # freeze background ingest so the group head is stationary and
        # the promotion gate is exact
        cluster["stop_produce"].set()
        cluster["producer"].join(timeout=5)
        from filodb_tpu.utils.devicewatch import FLIGHT
        n_flight_before = len(FLIGHT.events(kind="shard.replica"))

        def start_b():
            srv = FiloServer(_node_config(
                "ha-b", ports["ha-b"], cluster["broker"].port,
                cluster["dirs"]["ha-b"], cluster["peer_eps"]))
            srv.start()
            servers["ha-b"] = srv
            chaos.register("ha-b",
                           kill_fn=(lambda _s=srv: (_s.http.shutdown(),
                                                    _s.shutdown())),
                           proxy=cluster["proxies"]["ha-b"])
            return srv

        srv_b = chaos.restart("ha-b", start_b)

        # the rejoined node replays from ITS OWN checkpoint: recovery
        # starts from persisted offsets, not zero
        deadline = time.time() + 45
        promoted = False
        saw_recovery = False
        while time.time() < deadline:
            evs = FLIGHT.events(kind="shard.replica")[n_flight_before:]
            b_evs = [e for e in evs if e.get("node") == "ha-b"
                     and e.get("dataset") == "ha"]
            saw_recovery = saw_recovery or any(
                e["status"] == "Recovery" for e in b_evs)
            m = servers["ha-a"].manager.mapper("ha")
            b_shards = [s for s in range(NUM_SHARDS)
                        if any(r.node == "ha-b" for r in m.replicas(s))]
            if b_shards and all(
                    m.state(s).replica("ha-b") is not None
                    and m.state(s).replica("ha-b").status.value == "Active"
                    for s in b_shards):
                promoted = True
                break
            time.sleep(0.1)
        assert promoted, "rejoined node never promoted back to Active"
        assert saw_recovery, \
            "rejoined node skipped the Recovery state entirely"

        # promotion only at the group head: b's ingested offsets reached
        # the max across the group on every shard it holds
        m = servers["ha-a"].manager.mapper("ha")
        for s in range(NUM_SHARDS):
            rep = m.state(s).replica("ha-b")
            if rep is None:
                continue
            sh = srv_b.memstore.get_shard("ha", s)
            assert sh.latest_offset >= m.group_head(s) - 1, \
                (s, sh.latest_offset, m.group_head(s))

        # replay came from the CHECKPOINT, not offset zero: for every
        # shard the rejoined node holds, its fresh ingest counter equals
        # exactly the broker rows AT AND ABOVE its resume offset
        # (min checkpoint + 1), and is strictly less than a from-zero
        # replay wherever the checkpoint covered data
        client = cluster["client"]
        m = servers["ha-a"].manager.mapper("ha")
        b_shards = [s for s in range(NUM_SHARDS)
                    if m.state(s).replica("ha-b") is not None]
        assert b_shards
        checked = 0
        for s in b_shards:
            cps = srv_b.metastore.read_checkpoints("ha", s)
            if not cps or min(cps.values()) <= 0:
                continue
            resume = min(cps.values()) + 1
            expected = _broker_rows(client, s, resume)
            from_zero = _broker_rows(client, s, 0)
            sh = srv_b.memstore.get_shard("ha", s)
            got = sh.stats.rows_ingested + sh.stats.rows_skipped
            assert got == expected, \
                (s, resume, got, expected, "replayed a different range")
            assert expected < from_zero, \
                (s, "checkpoint covered nothing — test setup broken")
            checked += 1
        assert checked > 0, "no checkpointed shard verified"

        # no double-counting: duplicate-sensitive queries served by the
        # REJOINED node are bit-equal to the no-fault oracle
        deadline = time.time() + 20
        ok = False
        while time.time() < deadline:
            code, body, _ = _query(ports["ha-b"], COUNT_Q)
            if code == 200 and body.get("status") == "success" \
                    and body["data"]["result"]:
                ok = _canon(body) == cluster["oracles"][COUNT_Q]
                if ok:
                    break
            time.sleep(0.2)
        assert ok, "rejoined node's answers diverge (double-counting?)"
        for q in (RATE_Q, SUM_Q):
            code, body, _ = _query(ports["ha-b"], q)
            assert code == 200
            assert _canon(body) == cluster["oracles"][q]

    def test_4_partition_mid_query_then_heal(self, cluster):
        """A partitioned (not killed) node: its proxy blackholes, peers
        fail over, clients stay clean; healing restores it."""
        ports = cluster["ports"]
        chaos = cluster["chaos"]
        chaos.stall("ha-c", n=2, stall_s=0.3)   # wedge a couple of
        code, body, _ = _query(ports["ha-a"], COUNT_Q)  # connections
        assert code == 200
        chaos.partition("ha-c")
        try:
            t_end = time.time() + 3.0
            while time.time() < t_end:
                for q in (RATE_Q, COUNT_Q, SUM_Q):
                    code, body, headers = _query(ports["ha-a"], q)
                    assert code == 200 and body["status"] == "success"
                    assert headers.get("X-FiloDB-Partial-Data") is None
                    assert _canon(body) == cluster["oracles"][q]
                time.sleep(0.1)
        finally:
            chaos.heal("ha-c")
        # after healing, ha-c's replicas return to service
        deadline = time.time() + 20
        back = False
        while time.time() < deadline:
            m = cluster["servers"]["ha-a"].manager.mapper("ha")
            c_reps = [r for s in range(NUM_SHARDS)
                      for r in m.replicas(s) if r.node == "ha-c"]
            if c_reps and all(r.status.value in ("Active", "Recovery")
                              for r in c_reps):
                back = True
                break
            time.sleep(0.1)
        assert back, "healed node never returned to service"
