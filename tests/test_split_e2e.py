"""Chaos e2e for elastic resharding (ISSUE 13 acceptance criteria).

A 3-node rf=2 broker-backed cluster under live ingest + a query loop
runs a live 4 -> 8 shard split and takes, mid-flight:

1. a HARD KILL of a node holding child replicas, mid-catch-up — the
   split keeps serving (children are invisible to fan-out, parent
   groups fail over exactly as PR 12 proved), and
2. a PARTITION of the coordinator during cutover — the phase machine
   stalls (the cutover gate requires every fresh peer to have adopted
   the phase generation), serving continues from the surviving view,
   and the split RESUMES to completion after heal.

Every answer across both faults is HTTP 200 and equal to a no-fault
unsplit oracle: BIT-equal on the duplicate-sensitive legs
(``count_over_time`` / ``sum_over_time`` over integer-valued samples —
one dropped or double-counted row changes them), and 1e-9-relative on
the float-sum rate leg (doubling the shard count legitimately regroups
the cross-shard reduce by the last ulp).  After completion the children
serve, ``/admin/shards`` + ``/admin/split`` report the doubled
topology, and the retired parents hold none of the migrated half.

Kept in tier-1: this is THE acceptance test for elastic resharding.
"""

import json
import socket
import threading
import time
import urllib.error
import urllib.parse
import urllib.request

import math
import numpy as np
import pytest

from filodb_tpu.core.record import (RecordBuilder, partition_hash,
                                    shard_key_hash)
from filodb_tpu.core.schemas import DEFAULT_SCHEMAS, DatasetOptions
from filodb_tpu.ingest.broker import BrokerClient, BrokerServer
from filodb_tpu.integrity.faultinject import (FlakyTcpProxy,
                                              NodeChaosController)
from filodb_tpu.parallel.shardmap import ShardMapper
from filodb_tpu.standalone import FiloServer

BASE = 1_700_000_000_000
NUM_SHARDS = 4
NODES = ("sp-a", "sp-b", "sp-c")   # sp-a is the lowest name -> leader
N_INSTANCES = 12
N_SAMPLES = 240
WINDOW = (BASE + 60_000, BASE + 180_000)

RATE_Q = 'sum(rate(sp_total[2m]))'
COUNT_Q = 'sum(count_over_time(sp_total[1m]))'
SUM_Q = 'sum(sum_over_time(sp_total[1m]))'


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
    return _get(port, "/promql/sp/api/v1/query_range", timeout=25,
                query=promql, start=WINDOW[0] / 1000, end=WINDOW[1] / 1000,
                step="15s")


def _canon(body):
    return sorted((tuple(sorted(s["metric"].items())),
                   tuple((t, v) for t, v in s["values"]))
                  for s in body["data"]["result"])


def _near(canon_a, canon_b, rel=1e-9):
    if len(canon_a) != len(canon_b):
        return False
    for (ka, va), (kb, vb) in zip(canon_a, canon_b):
        if ka != kb or len(va) != len(vb):
            return False
        for (ta, xa), (tb, xb) in zip(va, vb):
            if ta != tb or not math.isclose(float(xa), float(xb),
                                            rel_tol=rel, abs_tol=1e-12):
                return False
    return True


def _equalish(q, got, want):
    return _near(got, want) if q == RATE_Q else got == want


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
            "name": "sp", "num-shards": NUM_SHARDS, "min-num-nodes": 3,
            "replication-factor": 2, "schema": "gauge", "spread": 1,
            "source": {"factory": "broker", "port": broker_port,
                       "topic": "sp"},
            "store": {"flush-interval": "1h", "groups-per-shard": 4},
            "workload": {"dispatch": {"retries": 1, "backoff-s": 0.01,
                                      "timeout-cap-s": 10}},
        }],
    }


def _series_tags(i):
    return {"_metric_": "sp_total", "instance": f"i{i}",
            "_ws_": "w", "_ns_": "n"}


def _produce_frozen(client, route_mapper):
    """The oracle window: INTEGER-valued cumulative series, routed by
    the same bit-splice the cluster uses (exact float sums under any
    reduce grouping — the bit-equality substrate)."""
    by_shard = {s: RecordBuilder(DEFAULT_SCHEMAS["gauge"],
                                 container_size=1 << 16)
                for s in range(NUM_SHARDS)}
    opts = DatasetOptions()
    rng = np.random.default_rng(7)
    n = 0
    for i in range(N_INSTANCES):
        tags = _series_tags(i)
        shard = route_mapper.ingestion_shard(
            shard_key_hash(tags, opts), partition_hash(tags, opts),
            1) % NUM_SHARDS
        vals = np.cumsum(rng.integers(1, 1000, N_SAMPLES))
        for k in range(N_SAMPLES):
            by_shard[shard].add(BASE + k * 1000, [float(vals[k])], tags)
            n += 1
    for s, b in by_shard.items():
        for c in b.containers():
            client.produce("sp", s, c)
    return n


def _bg_container(i):
    """Live-ingest traffic: timestamps BEYOND the frozen window so the
    oracle comparison is never perturbed, varied shard keys so both
    halves of the split see traffic."""
    b = RecordBuilder(DEFAULT_SCHEMAS["gauge"], container_size=1 << 13)
    b.add(BASE + 400_000 + i * 250, [float(i)],
          {"__name__": f"sp_bg{i % 5}", "instance": f"bg{i % 11}",
           "_ws_": "w", "_ns_": "n"})
    (out,) = b.containers()
    return out


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    broker = BrokerServer(port=0)
    broker.start()
    client = BrokerClient(port=broker.port)
    client.create_topic("sp", NUM_SHARDS)

    route_mapper = ShardMapper(NUM_SHARDS)
    n_frozen = _produce_frozen(client, route_mapper)

    ports = {n: _free_port() for n in NODES}
    proxies = {n: FlakyTcpProxy(backend_port=ports[n]) for n in NODES}
    for p in proxies.values():
        p.start()
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
        chaos.attach_split_controller(n, servers[n].split_controller)

    # convergence: rf=2 groups live + all frozen rows ingested
    deadline = time.time() + 60
    converged = False
    while time.time() < deadline:
        m = servers[NODES[0]].manager.mapper("sp")
        groups_ok = all(len(m.live_replicas(s)) == 2
                        for s in range(NUM_SHARDS))
        statuses_ok = all(
            r.status.value == "Active"
            for s in range(NUM_SHARDS) for r in m.live_replicas(s))
        rows_ok = all(
            sum(sh.stats.rows_ingested
                for sh in servers[n].memstore.shards("sp")) > 0
            for n in NODES)
        totals = sum(sh.stats.rows_ingested
                     for n in NODES
                     for sh in servers[n].memstore.shards("sp"))
        if groups_ok and statuses_ok and rows_ok \
                and totals >= 2 * n_frozen:   # rf=2: every row twice
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


class TestChaosSplit:
    """One ordered scenario (pytest runs methods in definition order
    within the module-scoped cluster)."""

    def test_1_oracle_then_kill_child_node_mid_catchup(self, cluster):
        servers, ports, chaos = (cluster["servers"], cluster["ports"],
                                 cluster["chaos"])
        client = cluster["client"]

        # ---- no-fault, unsplit oracle on the coordinator
        oracles = {}
        for q in (RATE_Q, COUNT_Q, SUM_Q):
            code, body, headers = _query(ports["sp-a"], q)
            assert code == 200 and body["status"] == "success", body
            assert body["data"]["result"], f"oracle empty for {q}"
            assert headers.get("X-FiloDB-Partial-Data") is None
            oracles[q] = _canon(body)
        cluster["oracles"] = oracles

        # checkpoints exist -> children clone + replay from them
        for n in NODES:
            servers[n].flush_all()

        # ---- live ingest while the split runs
        stop_produce = threading.Event()

        def produce_loop():
            i = 0
            while not stop_produce.is_set():
                try:
                    client.produce("sp", i % NUM_SHARDS, _bg_container(i))
                except Exception:
                    pass
                i += 1
                time.sleep(0.002)

        producer = threading.Thread(target=produce_loop, daemon=True)
        producer.start()
        cluster["stop_produce"] = stop_produce
        cluster["producer"] = producer

        # ---- trigger the split on the leader, cutover held so the
        # kill deterministically lands mid-catch-up
        ctrl = servers["sp-a"].split_controller
        ctrl.hold("cutover")
        st = ctrl.trigger("sp", grace_s=2.0)
        assert st["phase"] == "catchup" and st["total_shards"] == 8

        # children registered on the parents' replica nodes, Recovery
        m = servers["sp-a"].manager.mapper("sp")
        assert m.total_shards == 8 and m.num_shards == NUM_SHARDS
        for child in range(NUM_SHARDS, 8):
            assert m.replicas(child), f"child {child} has no replicas"

        # wait until sp-b actually participates (clone marker or child
        # consumer running), so the kill hits real mid-catch-up state
        def b_participates():
            srv_b = servers["sp-b"]
            if any(srv_b.metastore.read_kv(f"splitclone::sp::{c}")
                   for c in range(NUM_SHARDS, 8)):
                return True
            return any(s >= NUM_SHARDS
                       for s in srv_b._running_shards("sp"))
        deadline = time.time() + 30
        while time.time() < deadline and not b_participates():
            time.sleep(0.1)
        assert b_participates(), "sp-b never joined the catch-up"

        # ---- queries in flight while a child-holding node dies
        results = []

        # a COUNT of answers on each side of the kill, not a rate of this
        # host: at least 5 before it, 16 after it, and the old 5 s
        killed_at = []              # len(results) when the node died

        def query_loop(seconds, after, wall):
            t0 = time.time()
            while time.time() < t0 + wall:
                if (killed_at and len(results) >= killed_at[0] + after
                        and time.time() >= t0 + seconds):
                    return
                q = (RATE_Q, COUNT_Q, SUM_Q)[len(results) % 3]
                code, body, headers = _query(ports["sp-a"], q)
                results.append((q, code, body, headers))
                time.sleep(0.05)

        qt = threading.Thread(target=query_loop, args=(5.0, 16, 180.0),
                              daemon=True)
        qt.start()
        t_kill = time.time() + 0.8
        while qt.is_alive() and (time.time() < t_kill or len(results) < 5):
            time.sleep(0.01)
        chaos.kill("sp-b")          # hard kill mid-catch-up
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
        assert not partial, "partial results despite a live replica"
        # pre-cutover topology: every answer BIT-equal (unchanged
        # reduce tree), duplicate-sensitive legs included
        for q, _code, body, _h in results:
            assert _canon(body) == oracles[q], \
                f"mid-kill result diverged from oracle for {q}"
        # the split is still in catch-up (cutover held + b down)
        assert ctrl.status("sp")["phase"] == "catchup"

    def test_2_rejoin_then_partition_coordinator_mid_cutover(self, cluster):
        servers, ports, chaos = (cluster["servers"], cluster["ports"],
                                 cluster["chaos"])
        oracles = cluster["oracles"]

        # ---- sp-b rejoins (replays from its checkpoints, re-clones /
        # resumes its children) — PR 12 machinery end to end
        def start_b():
            srv = FiloServer(_node_config(
                "sp-b", ports["sp-b"], cluster["broker"].port,
                cluster["dirs"]["sp-b"], cluster["peer_eps"]))
            srv.start()
            servers["sp-b"] = srv
            chaos.register("sp-b",
                           kill_fn=(lambda _s=srv: (_s.http.shutdown(),
                                                    _s.shutdown())),
                           proxy=cluster["proxies"]["sp-b"])
            chaos.attach_split_controller("sp-b", srv.split_controller)
            return srv

        chaos.restart("sp-b", start_b)

        # sp-b adopts the in-flight topology from gossip AND its parent
        # replicas promote back to Active (otherwise a later fault on
        # another replica has no healthy peer to fail over to)
        deadline = time.time() + 45
        rejoined = False
        while time.time() < deadline:
            m = servers["sp-a"].manager.mapper("sp")
            b_parents = [m.state(s).replica("sp-b")
                         for s in range(NUM_SHARDS)
                         if m.state(s).replica("sp-b") is not None]
            if servers["sp-b"].manager.mapper("sp").total_shards == 8 \
                    and b_parents \
                    and all(r.status.value == "Active"
                            for r in b_parents):
                rejoined = True
                break
            time.sleep(0.1)
        assert rejoined, "rejoined node never promoted back to Active"

        # ---- partition the coordinator at the cutover window.  The
        # chaos proxy cuts sp-a's INBOUND edge (peers cannot see it),
        # the classic asymmetric partition: the coordinator may commit
        # the cutover on its own majority view (harmless — parents
        # hold full supersets and generations are monotone), but the
        # cut-off peers MUST keep serving the old topology bit-equal,
        # and the DESTRUCTIVE phase (retire: parents purge) must never
        # advance while any reachable peer still lags the cutover
        # generation.
        ctrl = servers["sp-a"].split_controller
        chaos.partition("sp-a")
        chaos.release_split("sp-a", "cutover")
        t_end = time.time() + 3.0
        while time.time() < t_end:
            for q in (RATE_Q, COUNT_Q, SUM_Q):
                code, body, headers = _query(ports["sp-c"], q)
                assert code == 200 and body["status"] == "success"
                assert headers.get("X-FiloDB-Partial-Data") is None
                assert _canon(body) == oracles[q], \
                    f"mid-partition result diverged for {q}"
            time.sleep(0.1)
        phase = ctrl.status("sp")["phase"]
        assert phase in ("catchup", "serving"), \
            f"destructive phase {phase} advanced during the partition"
        # the cut-off peers cannot have adopted the cutover generation
        assert servers["sp-c"].manager.mapper("sp").num_shards \
            == NUM_SHARDS, "partitioned peer adopted the cutover"

        # ---- heal: the split resumes and runs to completion
        chaos.heal("sp-a")
        deadline = time.time() + 90
        while time.time() < deadline:
            if ctrl.status("sp")["phase"] == "complete":
                break
            time.sleep(0.2)
        assert ctrl.status("sp")["phase"] == "complete", \
            ctrl.status("sp")
        assert chaos.wait_split_phase("sp", "serving", 5)
        assert chaos.wait_split_phase("sp", "retire", 5)

    def test_3_children_serve_bit_equal_everywhere(self, cluster):
        servers, ports = cluster["servers"], cluster["ports"]
        oracles = cluster["oracles"]
        cluster["stop_produce"].set()
        cluster["producer"].join(timeout=5)

        # every node converged on the doubled topology
        deadline = time.time() + 30
        while time.time() < deadline:
            if all(servers[n].manager.mapper("sp").num_shards == 8
                   and servers[n].manager.mapper("sp").topology
                   .split_phase is None for n in NODES):
                break
            time.sleep(0.1)
        for n in NODES:
            m = servers[n].manager.mapper("sp")
            assert m.num_shards == 8, f"{n} still at {m.num_shards}"
            assert m.topology.split_phase is None

        # zero dropped, zero double-counted: duplicate-sensitive legs
        # BIT-equal from every node's query surface, served by the
        # post-split topology (children + filtered/purged parents)
        for n in NODES:
            deadline = time.time() + 30
            ok = False
            while time.time() < deadline and not ok:
                ok = True
                for q in (COUNT_Q, SUM_Q, RATE_Q):
                    code, body, _h = _query(ports[n], q)
                    if code != 200 or \
                            not _equalish(q, _canon(body), oracles[q]):
                        ok = False
                        time.sleep(0.2)
                        break
            assert ok, f"node {n} diverged from the unsplit oracle"

        # the children actually hold and serve the migrated half
        child_rows = sum(
            sh.stats.rows_ingested + sh.stats.rows_split_filtered
            for n in NODES
            for sh in servers[n].memstore.shards("sp")
            if sh.shard_num >= NUM_SHARDS)
        assert child_rows > 0, "children ingested nothing"

        # retired parents physically dropped the migrated half: no
        # parent partition rehashes to a child shard anymore
        from filodb_tpu.parallel.shardmap import shard_of_tags
        for n in NODES:
            for sh in servers[n].memstore.shards("sp"):
                if sh.shard_num >= NUM_SHARDS:
                    continue
                for part in sh.partitions.values():
                    assert shard_of_tags(part.tags, 8, 1) == sh.shard_num, \
                        (n, sh.shard_num, part.tags)

    def test_4_admin_surfaces_report_the_split(self, cluster):
        ports = cluster["ports"]
        code, body, _h = _get(ports["sp-a"], "/admin/split/sp", timeout=10)
        assert code == 200
        st = body["data"]
        assert st["phase"] == "complete"
        assert st["total_shards"] == 8
        assert st["cutover_seconds"] is not None
        code, body, _h = _get(ports["sp-a"], "/admin/shards", timeout=10)
        assert code == 200
        ds = body["data"]["datasets"]["sp"]
        assert ds["topology"]["num_shards"] == 8
        # the ledger shows the LOCALLY-held shards; children this node
        # holds appear alongside their parents
        held = {r["shard"] for r in ds["shards"]}
        assert any(s >= NUM_SHARDS for s in held), held
        # CLI status against the live server
        from filodb_tpu.cli import main as cli_main
        rc = cli_main(["split-status", "--server",
                       f"http://127.0.0.1:{ports['sp-a']}",
                       "--dataset", "sp", "--json"])
        assert rc == 0
