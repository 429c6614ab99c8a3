"""Replica-group HA unit coverage (ISSUE 7).

Placement: rf-aware assignment with node distinctness, degraded
placement when rf > live nodes (loud), per-replica demotion with
ShardDown + transition metrics, rejoin refresh.  Routing: the single
ReplicaSet.pick helper's status/lag/latency order, ReplicaDispatcher
failover within deadline budget, hedge retargeting a different replica,
both-replicas-down degrading to the honored partial-results path.
Ingest: ReplicaFanout dual-write, a generative convergence sweep
(replicas end bit-identical in index cardinality), recovery promotion
gated on the replica-group head, and promotion racing concurrent
evict/purge."""

import logging
import threading
import time

import numpy as np
import pytest

from filodb_tpu.coordinator.cluster import (FailureDetector, ShardDown,
                                            ShardManager)
from filodb_tpu.coordinator.dispatch import (HttpPlanDispatcher,
                                             ReplicaDispatcher,
                                             dispatcher_factory)
from filodb_tpu.coordinator.node import IngestionCoordinator
from filodb_tpu.coordinator.replicas import ReplicaSet
from filodb_tpu.core.record import RecordBuilder
from filodb_tpu.core.schemas import DEFAULT_SCHEMAS
from filodb_tpu.ingest.stream import QueueStreamFactory
from filodb_tpu.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.parallel.shardmap import ShardMapper, ShardStatus
from filodb_tpu.query.exec import (DistConcatExec, ExecContext,
                                   MultiSchemaPartitionsExec, PlanDispatcher)
from filodb_tpu.query.model import (QueryContext, QueryResult, QueryStats,
                                    ShardUnavailable)
from filodb_tpu.utils.observability import REGISTRY

BASE = 1_700_000_000_000


# ---------------------------------------------------------------------------
# Placement
# ---------------------------------------------------------------------------


class TestReplicatedPlacement:
    def test_rf2_places_each_shard_on_two_distinct_nodes(self):
        mgr = ShardManager()
        mgr.setup_dataset("ds", 4, min_num_nodes=2, replication_factor=2)
        mgr.add_node("a")
        mgr.add_node("b")
        m = mgr.mapper("ds")
        for s in range(4):
            nodes = m.replica_nodes(s)
            assert len(nodes) == 2
            assert len(set(nodes)) == 2, "same node twice in one group"
        # even spread: 4 shards x 2 copies over 2 nodes = 4 each
        assert len(m.shards_for_node("a")) == 4
        assert len(m.shards_for_node("b")) == 4

    def test_rf2_three_nodes_spreads_copies(self):
        mgr = ShardManager()
        mgr.setup_dataset("ds", 4, min_num_nodes=3, replication_factor=2)
        for n in ("a", "b", "c"):
            mgr.add_node(n)
        m = mgr.mapper("ds")
        loads = sorted(len(m.shards_for_node(n)) for n in ("a", "b", "c"))
        assert sum(loads) == 8                 # 4 shards x 2 replicas
        assert loads[-1] <= 3                  # ceil(8/3)
        for s in range(4):
            assert len(set(m.replica_nodes(s))) == 2

    def test_assignment_idempotent_at_rf2(self):
        mgr = ShardManager()
        mgr.setup_dataset("ds", 4, min_num_nodes=2, replication_factor=2)
        first = mgr.add_node("a")["ds"]
        again = mgr.add_node("a")["ds"]
        assert first == again

    def test_rf_above_live_nodes_degrades_loudly(self, caplog):
        from filodb_tpu.utils.devicewatch import FLIGHT
        mgr = ShardManager()
        with caplog.at_level(logging.WARNING,
                             logger="filodb_tpu.coordinator.cluster"):
            mgr.setup_dataset("lonely", 2, min_num_nodes=1,
                              replication_factor=2)
            mgr.add_node("only-node")
        m = mgr.mapper("lonely")
        for s in range(2):
            assert m.replica_nodes(s) == ["only-node"]  # degraded, serving
        assert any("degraded placement" in r.message for r in caplog.records)
        evs = [e for e in FLIGHT.events(kind="shard.degraded_placement")
               if e.get("dataset") == "lonely"]
        assert evs and evs[-1]["replication_factor"] == 2

    def test_remove_node_demotes_replica_publishes_sharddown(self):
        events = []
        trans = REGISTRY.counter("filodb_shard_status_transitions_total")
        mgr = ShardManager()
        mgr.subscribe(events.append)
        mgr.setup_dataset("rep1", 2, min_num_nodes=2, replication_factor=2)
        mgr.add_node("a")
        mgr.add_node("b")
        before = trans.value(dataset="rep1", status="Down")
        m = mgr.mapper("rep1")
        for s in range(2):
            for r in m.replicas(s):
                m.update_status(s, ShardStatus.ACTIVE, node=r.node)
        mgr.remove_node("a")
        downs = [e for e in events if isinstance(e, ShardDown)]
        assert {e.shard for e in downs} == {0, 1}
        assert all(e.node == "a" for e in downs)
        # named-mapper path: one Down transition per lost REPLICA
        assert trans.value(dataset="rep1", status="Down") == before + 2
        # the surviving replica keeps each shard queryable
        for s in range(2):
            assert m.best_status(s) is ShardStatus.ACTIVE
            live = m.live_replicas(s)
            assert [r.node for r in live] == ["b"]

    def test_failure_detector_check_drives_replica_demotion(self):
        clock = [100.0]
        events = []
        mgr = ShardManager(clock=lambda: clock[0])
        mgr.subscribe(events.append)
        mgr.setup_dataset("rep2", 2, min_num_nodes=2, replication_factor=2)
        fd = FailureDetector(mgr, timeout_ms=5_000, clock=lambda: clock[0])
        fd.heartbeat("a")
        fd.heartbeat("b")
        clock[0] += 3.0
        fd.heartbeat("b")
        clock[0] += 3.0
        assert fd.check() == ["a"]
        downs = [e for e in events if isinstance(e, ShardDown)]
        assert downs and all(e.node == "a" for e in downs)
        m = mgr.mapper("rep2")
        for s in range(2):
            assert all(r.node == "b" for r in m.live_replicas(s))

    def test_rejoin_refreshes_down_replica(self):
        mgr = ShardManager()
        mgr.setup_dataset("ds", 2, min_num_nodes=2, replication_factor=2)
        mgr.add_node("a")
        mgr.add_node("b")
        mgr.remove_node("a")   # no third node: groups degraded, a's
        m = mgr.mapper("ds")   # replicas stay marked Down
        for s in range(2):
            assert len(m.live_replicas(s)) == 1
        mgr.add_node("a")      # rejoin: same node picks its shards back
        for s in range(2):
            assert len(m.live_replicas(s)) == 2
            rep = m.state(s).replica("a")
            assert rep is not None
            assert rep.status is ShardStatus.ASSIGNED

    def test_losing_last_node_fires_degraded_warning(self, caplog):
        """Regression (review): removing the FINAL node — the worst
        placement transition of all — must still fire the loud
        degraded warning; the reassignment early-return (no survivors
        to move shards to) used to skip it."""
        from filodb_tpu.utils.devicewatch import FLIGHT
        mgr = ShardManager()
        mgr.setup_dataset("lastn", 2, min_num_nodes=1,
                          replication_factor=1)
        mgr.add_node("a")           # rf=1 met: placement healthy
        ev = lambda: len(
            [e for e in FLIGHT.events(kind="shard.degraded_placement")
             if e.get("dataset") == "lastn"])
        before = ev()
        with caplog.at_level(logging.WARNING,
                             logger="filodb_tpu.coordinator.cluster"):
            mgr.remove_node("a")
        assert ev() == before + 1
        assert any("degraded placement" in r.message
                   for r in caplog.records)

    def test_set_replicas_adopts_membership_keeps_local_status(self):
        m = ShardMapper(2, replication_factor=2)
        m.register_node([0], "a")
        m.update_status(0, ShardStatus.ACTIVE, node="a")
        changed = m.set_replicas(0, [
            {"node": "a", "status": "Assigned"},
            {"node": "c", "status": "Recovery", "watermark": 7}])
        assert changed
        assert m.replica_nodes(0) == ["a", "c"]
        # retained replica keeps LOCAL status; new one takes the leader's
        assert m.state(0).replica("a").status is ShardStatus.ACTIVE
        assert m.state(0).replica("c").status is ShardStatus.RECOVERY
        assert m.state(0).replica("c").watermark == 7
        assert not m.set_replicas(0, [{"node": "a"}, {"node": "c"}])

    def test_set_replicas_primary_demotion_fires_shard_transition(self):
        """Regression (review): a follower adopting a leader view that
        demotes the PRIMARY replica across the down boundary must emit
        the shard.status flight event — prev has to be read BEFORE the
        kept replicas are mutated in place, or the comparison sees the
        new status on both sides and the transition never fires.  The
        shard-level gauge meanwhile reports the SERVING view: the
        surviving Active peer keeps the shard green (a dead primary of
        a fully-served shard must not page)."""
        from filodb_tpu.utils.devicewatch import FLIGHT
        gauge = REGISTRY.gauge("filodb_shard_status_code")
        m = ShardMapper(1, dataset="adopt1", replication_factor=2)
        m.register_node([0], "a")
        m.register_node([0], "b")
        m.update_status(0, ShardStatus.ACTIVE, node="a")
        m.update_status(0, ShardStatus.ACTIVE, node="b")
        assert gauge.value(dataset="adopt1", shard=0) == 3  # Active
        m.set_replicas(0, [{"node": "a", "status": "Down"},
                           {"node": "b", "status": "Active"}])
        assert m.status(0) is ShardStatus.DOWN      # primary view
        assert m.best_status(0) is ShardStatus.ACTIVE
        assert gauge.value(dataset="adopt1", shard=0) == 3  # serving
        evs = [e for e in FLIGHT.events(kind="shard.status")
               if e.get("dataset") == "adopt1"]
        assert evs and evs[-1]["status"] == "Down" \
            and evs[-1]["prev"] == "Active"
        # both copies gone -> the gauge DOES go Down
        m.set_replicas(0, [{"node": "a", "status": "Down"},
                           {"node": "b", "status": "Down"}])
        assert gauge.value(dataset="adopt1", shard=0) == 6  # Down

    def test_displaced_replica_gauge_row_removed(self):
        """Regression (review): replacing a replica (rf=1 move, rf>1
        dead-copy replacement) must remove the displaced copy's
        filodb_shard_replica_status_code row, not export it forever."""
        gauge = REGISTRY.gauge("filodb_shard_replica_status_code")
        m = ShardMapper(2, dataset="disp1")
        m.register_node([0], "a")
        m.register_node([0], "b")           # rf=1 move: a displaced
        assert gauge.value(dataset="disp1", shard=0, node="b") == 1
        assert ("disp1", 0, "a") not in {
            (dict(k).get("dataset"), dict(k).get("shard"),
             dict(k).get("node")) for k in gauge._values}
        m2 = ShardMapper(2, dataset="disp2", replication_factor=2)
        m2.register_node([0], "a")
        m2.register_node([0], "b")
        m2.update_status(0, ShardStatus.DOWN, node="a")
        m2.register_node([0], "c")          # replaces the dead copy
        assert m2.replica_nodes(0) == ["c", "b"]
        assert ("disp2", 0, "a") not in {
            (dict(k).get("dataset"), dict(k).get("shard"),
             dict(k).get("node")) for k in gauge._values}

    def test_second_replica_addition_counts_a_transition(self):
        """Regression (review): adding a copy to a non-empty group must
        count its Unassigned->Assigned transition (the counter owns
        per-REPLICA transitions)."""
        trans = REGISTRY.counter("filodb_shard_status_transitions_total")
        m = ShardMapper(2, dataset="add2", replication_factor=2)
        m.register_node([0], "a")
        before = trans.value(dataset="add2", status="Assigned")
        m.register_node([0], "b")
        assert trans.value(dataset="add2", status="Assigned") == before + 1

    def test_leader_demotion_propagates_to_followers(self):
        """Regression (review): a follower adopting the leader's view
        must take leader-intent statuses that CROSS the down boundary —
        a demotion to Down (else the follower routes at a dead replica
        forever) and the later resurrection — while keeping its own
        liveness view within live states."""
        m = ShardMapper(1, replication_factor=2)
        m.register_node([0], "a")
        m.register_node([0], "b")
        m.update_status(0, ShardStatus.ACTIVE, node="a")
        m.update_status(0, ShardStatus.ACTIVE, node="b")
        # leader demoted b: follower adopts Down
        m.set_replicas(0, [{"node": "a", "status": "Active"},
                           {"node": "b", "status": "Down"}])
        assert m.state(0).replica("b").status is ShardStatus.DOWN
        # within live states the local view stays authoritative
        m.set_replicas(0, [{"node": "a", "status": "Recovery"},
                           {"node": "b", "status": "Down"}])
        assert m.state(0).replica("a").status is ShardStatus.ACTIVE
        # leader resurrected b after rejoin: follower adopts that too
        m.set_replicas(0, [{"node": "a", "status": "Active"},
                           {"node": "b", "status": "Assigned"}])
        assert m.state(0).replica("b").status is ShardStatus.ASSIGNED

    def test_error_replica_not_double_assigned(self):
        """Regression (review): an Error copy must not land a shard in
        BOTH the strategy's have and need sides (duplicate assignment +
        duplicate ShardAssignmentStarted events)."""
        from filodb_tpu.coordinator.cluster import (
            DefaultShardAssignmentStrategy, ShardAssignmentStarted)
        m = ShardMapper(2, replication_factor=2)
        m.register_node([0, 1], "n1")
        m.register_node([0, 1], "n2")
        m.update_status(0, ShardStatus.ERROR, node="n1")
        strat = DefaultShardAssignmentStrategy()
        got = strat.shard_assignments("n1", "ds", m, 2)
        assert len(got) == len(set(got)), got
        # and a full manager pass publishes ONE event per assignment
        mgr = ShardManager()
        mgr.setup_dataset("err1", 2, min_num_nodes=2,
                          replication_factor=2)
        events = []
        mgr.subscribe(events.append)
        mgr.add_node("n1")
        starts = [e for e in events
                  if isinstance(e, ShardAssignmentStarted)]
        assert len(starts) == len({(e.shard, e.node) for e in starts})

    def test_liveness_fallback_preserves_recovery_progress(self):
        """Regression (review): a peer health body without 'running'
        must not wipe its recovering replica's progress to 0 every
        sweep."""
        from filodb_tpu.coordinator.cluster import (FailureDetector,
                                                    ShardManager,
                                                    StatusPoller)
        mgr = ShardManager()
        det = FailureDetector(mgr, timeout_ms=1000)
        poller = StatusPoller(mgr, det, {"node-b": "http://x"}, "node-a")
        mgr.setup_dataset("ds", 2, min_num_nodes=2)
        det.heartbeat("node-b")
        m = mgr.mapper("ds")
        target = m.shards_for_node("node-b")[0]
        m.update_status(target, ShardStatus.RECOVERY, progress=40,
                        node="node-b")
        poller._apply_liveness("node-b", {"shards": {"ds": [
            {"shard": target, "status": "Recovery",
             "replicas": [{"node": "node-b", "status": "Recovery",
                           "progress": 40}]}]}})
        assert m.state(target).replica("node-b").recovery_progress == 40
        poller.stop()

    def test_liveness_live_branch_carries_gossiped_progress(self):
        """Regression (review, round 2): the NORMAL path — peer reports
        'running' — must adopt the peer's own gossiped recovery
        progress, not the locally-stored value.  The owner's recovery
        events never reach this node's ShardManager and register_node
        reset the local copy to 0 at rejoin, so without the adoption
        every non-owner surface showed a recovering replica stuck at 0%
        for the whole replay."""
        from filodb_tpu.coordinator.cluster import (FailureDetector,
                                                    ShardManager,
                                                    StatusPoller)
        mgr = ShardManager()
        det = FailureDetector(mgr, timeout_ms=1000)
        poller = StatusPoller(mgr, det, {"node-b": "http://x"}, "node-a")
        mgr.setup_dataset("ds", 2, min_num_nodes=2)
        det.heartbeat("node-b")
        m = mgr.mapper("ds")
        target = m.shards_for_node("node-b")[0]
        # local view: rejoin reset the replica's progress to 0
        m.update_status(target, ShardStatus.RECOVERY, progress=0,
                        node="node-b")
        poller._apply_liveness("node-b", {
            "running": {"ds": [target]},
            "shards": {"ds": [
                {"shard": target, "status": "Recovery",
                 "replicas": [{"node": "node-b", "status": "Recovery",
                               "progress": 65}]}]}})
        rep = m.state(target).replica("node-b")
        assert rep.status is ShardStatus.RECOVERY
        assert rep.recovery_progress == 65
        poller.stop()

    def test_watermarks_and_group_head(self):
        m = ShardMapper(1, replication_factor=2)
        m.register_node([0], "a")
        m.register_node([0], "b")
        assert m.group_head(0) == -1
        m.note_watermark(0, "a", 100)
        m.note_watermark(0, "b", 40)
        assert m.group_head(0) == 100
        m.note_watermark(0, "b", 30)   # watermarks never regress...
        assert m.state(0).replica("b").watermark == 40
        # ...EXCEPT across a rejoin (review regression): the node
        # restarts and replays from its checkpoint — the pre-crash
        # watermark is stale and must reset, or lag views hide the
        # replay regression forever
        m.update_status(0, ShardStatus.DOWN, node="b")
        m.register_node([0], "b")
        assert m.state(0).replica("b").watermark == -1
        # same rule on followers adopting a leader's resurrection
        m2 = ShardMapper(1, replication_factor=2)
        m2.register_node([0], "a")
        m2.register_node([0], "b")
        m2.note_watermark(0, "b", 10_000)
        m2.update_status(0, ShardStatus.DOWN, node="b")
        m2.set_replicas(0, [{"node": "a", "status": "Active"},
                            {"node": "b", "status": "Assigned",
                             "watermark": -1}])
        assert m2.state(0).replica("b").watermark == -1


# ---------------------------------------------------------------------------
# Routing: ReplicaSet.pick
# ---------------------------------------------------------------------------


class TestReplicaSetPick:
    def _mapper(self):
        m = ShardMapper(1, replication_factor=3)
        for n in ("a", "b", "c"):
            m.register_node([0], n)
        return m

    def test_active_before_recovery_recovery_only_without_active(self):
        m = self._mapper()
        m.update_status(0, ShardStatus.ACTIVE, node="a")
        m.update_status(0, ShardStatus.RECOVERY, node="b")
        m.update_status(0, ShardStatus.ACTIVE, node="c")
        rs = ReplicaSet(m)
        # a recovering copy is NEVER picked while an Active peer exists
        assert set(rs.pick(0)) == {"a", "c"}
        m.update_status(0, ShardStatus.DOWN, node="a")
        m.update_status(0, ShardStatus.DOWN, node="c")
        assert rs.pick(0) == ["b"]     # no Active: Recovery serves

    def test_down_replicas_never_picked(self):
        m = self._mapper()
        for n in ("a", "b", "c"):
            m.update_status(0, ShardStatus.DOWN, node=n)
        assert ReplicaSet(m).pick(0) == []

    def test_watermark_lag_orders_active_replicas(self):
        m = self._mapper()
        for n in ("a", "b", "c"):
            m.update_status(0, ShardStatus.ACTIVE, node=n)
        m.note_watermark(0, "a", 10_000)
        m.note_watermark(0, "b", 5_000)    # far behind the head
        m.note_watermark(0, "c", 10_000)
        order = ReplicaSet(m, lag_tolerance_rows=256).pick(0)
        assert order.index("b") == 2       # the laggard ranks last
        assert set(order[:2]) == {"a", "c"}

    def test_unknown_watermark_ranks_worst_when_peers_are_known(self):
        """Regression (review): a replica whose watermark has not been
        gossiped yet (-1) must not tie with the group head and win on
        latency — it may be arbitrarily diverged."""
        m = self._mapper()
        for n in ("a", "b", "c"):
            m.update_status(0, ShardStatus.ACTIVE, node=n)
        m.note_watermark(0, "a", 10_000)
        m.note_watermark(0, "c", 9_999)
        # b unknown, and even LOCAL (latency 0): still ranks last
        order = ReplicaSet(m, local_node="b").pick(0)
        assert order[-1] == "b", order

    def test_small_lag_jitter_does_not_flap(self):
        m = self._mapper()
        for n in ("a", "b", "c"):
            m.update_status(0, ShardStatus.ACTIVE, node=n)
        m.note_watermark(0, "a", 10_000)
        m.note_watermark(0, "b", 9_990)    # in-flight rows, not a lag
        m.note_watermark(0, "c", 10_000)
        order = ReplicaSet(m, lag_tolerance_rows=256).pick(0)
        assert order == ["a", "b", "c"]    # stable name order, no demotion

    def test_local_node_preferred_then_calibrated_latency(self):
        m = self._mapper()
        for n in ("a", "b", "c"):
            m.update_status(0, ShardStatus.ACTIVE, node=n)
        lat = {"a": 0.5, "b": 0.001, "c": None}
        rs = ReplicaSet(m, local_node="c", latency_fn=lat.get)
        assert rs.pick(0)[0] == "c"        # local first (no hop)
        rs2 = ReplicaSet(m, latency_fn=lat.get)
        assert rs2.pick(0) == ["b", "a", "c"]  # calibrated before unknown

    def test_recovery_never_serves_while_group_has_active(self):
        """Regression (review): the Recovery gate is over the WHOLE
        group — excluding the (slow/just-failed) Active replica must
        NOT let a mid-replay Recovery copy answer with stale windows;
        the caller fails loudly instead."""
        m = self._mapper()
        m.update_status(0, ShardStatus.ACTIVE, node="a")
        m.update_status(0, ShardStatus.RECOVERY, node="b")
        m.update_status(0, ShardStatus.DOWN, node="c")
        rs = ReplicaSet(m)
        assert rs.pick(0, exclude=["a"]) == []
        assert rs.alternate(0, exclude=["a"]) is None
        # once the Active copy is DEMOTED (no Active anywhere), the
        # Recovery copy may serve
        m.update_status(0, ShardStatus.DOWN, node="a")
        assert rs.pick(0) == ["b"]

    def test_exclude_and_alternate(self):
        m = self._mapper()
        for n in ("a", "b", "c"):
            m.update_status(0, ShardStatus.ACTIVE, node=n)
        rs = ReplicaSet(m)
        assert rs.pick(0, exclude=["a"]) == ["b", "c"]
        assert rs.alternate(0, exclude=["a", "b"]) == "c"
        assert rs.alternate(0, exclude=["a", "b", "c"]) is None

    def test_startup_fallback_serves_assigned(self):
        m = self._mapper()                 # all replicas still Assigned
        assert ReplicaSet(m).pick(0) == ["a", "b", "c"]


# ---------------------------------------------------------------------------
# Routing: failover dispatch
# ---------------------------------------------------------------------------


class _FakeDispatcher(PlanDispatcher):
    def __init__(self, name, fail=False, log=None):
        self.name = name
        self.fail = fail
        self.log = log if log is not None else []

    def dispatch(self, plan, ctx):
        self.log.append(self.name)
        if self.fail:
            raise ShardUnavailable("q", f"remote dispatch to {self.name} "
                                        f"failed after 1 attempt(s)")
        return QueryResult("q", [], QueryStats())


def _rf2_mapper(statuses=("Active", "Active")):
    m = ShardMapper(1, replication_factor=2)
    m.register_node([0], "a")
    m.register_node([0], "b")
    for node, st in zip(("a", "b"), statuses):
        m.update_status(0, ShardStatus(st), node=node)
    return m


class TestFailoverDispatch:
    def _plan(self, qctx=None):
        return MultiSchemaPartitionsExec("prom", 0, [], BASE, BASE + 1000,
                                         query_context=qctx)

    def test_failover_to_next_replica_on_shard_unavailable(self):
        from filodb_tpu.utils.devicewatch import FLIGHT
        failover = REGISTRY.counter("filodb_dispatch_failover_total")
        before = failover.value(reason="unreachable")
        m = _rf2_mapper()
        log = []
        fakes = {"a": _FakeDispatcher("a", fail=True, log=log),
                 "b": _FakeDispatcher("b", fail=False, log=log)}
        rd = ReplicaDispatcher("prom", 0, ReplicaSet(m),
                               lambda s, n: fakes[n])
        out = rd.dispatch(self._plan(), ExecContext(TimeSeriesMemStore(),
                                                    QueryContext()))
        assert isinstance(out, QueryResult)
        assert log == ["a", "b"]
        assert failover.value(reason="unreachable") == before + 1
        evs = [e for e in FLIGHT.events(kind="dispatch.failover")
               if e.get("dataset") == "prom"]
        assert evs and evs[-1]["from_node"] == "a" \
            and evs[-1]["to_node"] == "b"

    def test_failover_reason_comes_from_the_raise_site_tag(self):
        """Regression (review): urllib's '[Errno 111] Connection
        refused' in an exhausted-retries message must classify as
        'unreachable'; only a tagged 503 work-refusal counts as
        'refused'."""
        failover = REGISTRY.counter("filodb_dispatch_failover_total")
        before_un = failover.value(reason="unreachable")
        before_ref = failover.value(reason="refused")
        m = _rf2_mapper()

        class _TaggedFail(PlanDispatcher):
            def __init__(self, reason=None):
                self.reason = reason

            def dispatch(self, plan, ctx):
                e = ShardUnavailable(
                    "q", "remote dispatch to x failed after 2 "
                         "attempt(s): <urlopen error [Errno 111] "
                         "Connection refused>")
                if self.reason:
                    e.reason = self.reason
                raise e

        ok = _FakeDispatcher("b")
        rd = ReplicaDispatcher(
            "prom", 0, ReplicaSet(m),
            lambda s, n: _TaggedFail() if n == "a" else ok)
        rd.dispatch(self._plan(), ExecContext(TimeSeriesMemStore(),
                                              QueryContext()))
        assert failover.value(reason="unreachable") == before_un + 1
        assert failover.value(reason="refused") == before_ref
        rd2 = ReplicaDispatcher(
            "prom", 0, ReplicaSet(m),
            lambda s, n: _TaggedFail("refused") if n == "a" else ok)
        rd2.dispatch(self._plan(), ExecContext(TimeSeriesMemStore(),
                                               QueryContext()))
        assert failover.value(reason="refused") == before_ref + 1

    def test_whole_group_down_raises_shard_unavailable(self):
        m = _rf2_mapper()
        log = []
        fakes = {"a": _FakeDispatcher("a", fail=True, log=log),
                 "b": _FakeDispatcher("b", fail=True, log=log)}
        rd = ReplicaDispatcher("prom", 0, ReplicaSet(m),
                               lambda s, n: fakes[n])
        with pytest.raises(ShardUnavailable):
            rd.dispatch(self._plan(), ExecContext(TimeSeriesMemStore(),
                                                  QueryContext()))
        assert log == ["a", "b"]           # every replica was tried

    def test_failover_respects_exhausted_deadline(self):
        m = _rf2_mapper()
        log = []
        fakes = {"a": _FakeDispatcher("a", fail=True, log=log),
                 "b": _FakeDispatcher("b", fail=False, log=log)}
        qctx = QueryContext(submit_time_ms=int(time.time() * 1000))
        qctx.deadline_ms = int(time.time() * 1000) - 1   # already gone
        rd = ReplicaDispatcher("prom", 0, ReplicaSet(m),
                               lambda s, n: fakes[n])
        with pytest.raises(ShardUnavailable):
            rd.dispatch(self._plan(qctx),
                        ExecContext(TimeSeriesMemStore(), qctx))
        assert log == ["a"]                # no budget left to fail over

    def test_both_replicas_down_partial_results_path_honored(self):
        """The acceptance edge: with the WHOLE group dead, the query
        still degrades to the PR 10 partial-results contract when (and
        only when) the client opted in."""
        m = _rf2_mapper()
        f = dispatcher_factory(
            m, {"a": "http://127.0.0.1:1", "b": "http://127.0.0.1:1"},
            local_node="coordinator",
            dispatch_config={"retries": 0, "backoff-s": 0.0})
        rd = f(0)
        assert isinstance(rd, ReplicaDispatcher)
        qctx = QueryContext(allow_partial_results=True)
        leaf = MultiSchemaPartitionsExec("prom", 0, [], BASE, BASE + 1000,
                                         query_context=qctx, dispatcher=rd)
        root = DistConcatExec([leaf], qctx)
        res = root.execute(ExecContext(TimeSeriesMemStore(), qctx))
        assert res.batches == []
        assert res.stats.shards_down == 1
        # without the opt-in: loud failure
        qctx2 = QueryContext(allow_partial_results=False)
        leaf2 = MultiSchemaPartitionsExec("prom", 0, [], BASE, BASE + 1000,
                                          query_context=qctx2, dispatcher=rd)
        with pytest.raises(ShardUnavailable):
            DistConcatExec([leaf2], qctx2).execute(
                ExecContext(TimeSeriesMemStore(), qctx2))

    def test_missing_endpoint_failover_is_counted(self):
        """Regression (review): skipping a replica because its node has
        no endpoint is a failover too — counted + flight-recorded, not
        silent."""
        failover = REGISTRY.counter("filodb_dispatch_failover_total")
        before = failover.value(reason="no_endpoint")
        m = _rf2_mapper()
        log = []
        fakes = {"a": None,
                 "b": _FakeDispatcher("b", fail=False, log=log)}
        rd = ReplicaDispatcher("prom", 0, ReplicaSet(m),
                               lambda s, n: fakes[n])
        out = rd.dispatch(self._plan(), ExecContext(TimeSeriesMemStore(),
                                                    QueryContext()))
        assert isinstance(out, QueryResult) and log == ["b"]
        assert failover.value(reason="no_endpoint") == before + 1

    def test_failover_excludes_burned_replicas_from_hedge(self):
        """Regression (review): after a failover, the hedge retarget
        hook must not aim the duplicate at the replica that JUST
        failed (plan.replica_exclude threads the tried set)."""
        m = ShardMapper(1, replication_factor=3)
        for n in ("a", "b", "c"):
            m.register_node([0], n)
            m.update_status(0, ShardStatus.ACTIVE, node=n)
        f = dispatcher_factory(
            m, {"a": "http://127.0.0.1:41011",
                "b": "http://127.0.0.1:41012",
                "c": "http://127.0.0.1:41013"},
            local_node="coordinator",
            dispatch_config={"retries": 0, "hedge": True})
        rd = f(0)
        d_b = rd.dispatcher_for_node(0, "b")
        plan = self._plan()
        plan.replica_exclude = ["a"]   # the failover loop burned a
        alt = d_b.hedge_alternate(plan)
        assert alt == "http://127.0.0.1:41013", alt

    def test_hedge_skips_alias_of_inflight_endpoint(self):
        """Regression (review): two node names resolving to ONE
        endpoint (misconfiguration) must not emit hedge_retarget
        telemetry for a duplicate ``_send_hedged`` would discard as
        same-endpoint — the walk continues to a genuinely different
        replica and telemetry fires only for the real retarget."""
        failover = REGISTRY.counter("filodb_dispatch_failover_total")
        before = failover.value(reason="hedge_retarget")
        m = ShardMapper(1, replication_factor=3)
        for n in ("a", "b", "c"):
            m.register_node([0], n)
            m.update_status(0, ShardStatus.ACTIVE, node=n)
        # b is an alias of a's endpoint; ranking visits b before c
        f = dispatcher_factory(
            m, {"a": "http://127.0.0.1:41031",
                "b": "http://127.0.0.1:41031/",
                "c": "http://127.0.0.1:41033"},
            local_node="coordinator",
            dispatch_config={"retries": 0, "hedge": True})
        rd = f(0)
        d_a = rd.dispatcher_for_node(0, "a")
        alt = d_a.hedge_alternate(self._plan())
        assert alt == "http://127.0.0.1:41033", alt
        assert failover.value(reason="hedge_retarget") == before + 1

    def test_hedge_walks_past_endpointless_replica(self):
        """Regression (review): when the best alternate has no
        configured endpoint, the hedge walks to the NEXT replica
        (like the failover loop's no_endpoint continue) instead of
        degrading to a same-endpoint duplicate at the wedged node."""
        m = ShardMapper(1, replication_factor=3)
        for n in ("a", "b", "c"):
            m.register_node([0], n)
            m.update_status(0, ShardStatus.ACTIVE, node=n)
        # all-Active + no latency data ranks by node name: b before c;
        # b has NO endpoint, so the hedge must walk on to c
        f = dispatcher_factory(
            m, {"a": "http://127.0.0.1:41021",
                "c": "http://127.0.0.1:41023"},
            local_node="coordinator",
            dispatch_config={"retries": 0, "hedge": True})
        rd = f(0)
        d_a = rd.dispatcher_for_node(0, "a")
        alt = d_a.hedge_alternate(self._plan())
        assert alt == "http://127.0.0.1:41023", alt

    def test_factory_returns_legacy_shapes_at_rf1(self):
        from filodb_tpu.query.exec import IN_PROCESS
        m = ShardMapper(2)
        m.register_node([0], "a")
        m.register_node([1], "b")
        f = dispatcher_factory(m, {"b": "http://x:1"}, local_node="a")
        assert f(0) is IN_PROCESS
        assert isinstance(f(1), HttpPlanDispatcher)

    def test_hedged_duplicate_retargets_other_replica(self, monkeypatch):
        """The hedge's second request goes to a DIFFERENT replica,
        selected through ReplicaSet.pick (via the alternate hook)."""
        m = _rf2_mapper()
        f = dispatcher_factory(
            m, {"a": "http://127.0.0.1:41001", "b": "http://127.0.0.1:41002"},
            local_node="coordinator",
            dispatch_config={"retries": 0, "hedge": True,
                             "hedge-min-s": 0.01})
        rd = f(0)
        assert isinstance(rd, ReplicaDispatcher)
        primary = rd.dispatcher_for_node(0, "a")
        for _ in range(32):                # arm the p99 trigger
            primary._note_latency(0.001)
        sent = []
        payload = {"query_id": "q", "batches": [], "stats": {}}

        def fake_send(body, headers, timeout_s, endpoint=None):
            sent.append(endpoint or primary.endpoint)
            if endpoint is None:
                time.sleep(0.5)            # primary wedged: hedge fires
            return payload

        monkeypatch.setattr(primary, "_send_once", fake_send)
        out = primary.dispatch(self._plan(),
                               ExecContext(TimeSeriesMemStore(),
                                           QueryContext()))
        assert isinstance(out, QueryResult)
        assert "http://127.0.0.1:41002" in sent, \
            f"hedge never retargeted the peer replica: {sent}"


# ---------------------------------------------------------------------------
# Ingest: dual-write fanout + convergence
# ---------------------------------------------------------------------------


def _mk_stores(mapper, nodes, dataset="prom"):
    stores = {}
    offsets = {}
    per_node = {}
    for node in nodes:
        ms = TimeSeriesMemStore()
        for s in range(mapper.num_shards):
            ms.setup(dataset, DEFAULT_SCHEMAS, s)
        stores[node] = ms

        def push(shard, container, _ms=ms, _node=node):
            key = (_node, shard)
            off = offsets.get(key, -1) + 1
            offsets[key] = off
            _ms.get_shard(dataset, shard).ingest_container(container, off)

        per_node[node] = push
    return stores, per_node


class TestHealthServingView:
    def test_one_dead_replica_keeps_health_green(self):
        """Regression (review): /__health reports the SERVING view at
        the shard level — one dead copy of a fully-served rf=2 shard
        must not flip healthy:false (503) on every surviving node and
        let a load balancer drain a cluster that serves all data."""
        import json as _json
        import urllib.request

        from filodb_tpu.http.server import FiloHttpServer
        mgr = ShardManager()
        mgr.setup_dataset("hlth", 2, min_num_nodes=2,
                          replication_factor=2)
        mgr.add_node("a")
        mgr.add_node("b")
        m = mgr.mapper("hlth")
        for s in range(2):
            for r in m.replicas(s):
                m.update_status(s, ShardStatus.ACTIVE, node=r.node)
        mgr.remove_node("a")           # demotes a's replicas to Down
        srv = FiloHttpServer(shard_manager=mgr)
        port = srv.start()
        try:
            body = _json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/__health", timeout=10).read())
        finally:
            srv.shutdown()
        assert body["healthy"] is True
        assert {s["status"] for s in body["shards"]["hlth"]} == {"Active"}
        # per-replica truth still rides in the replicas rows (gossip)
        rep_statuses = {r["status"] for s in body["shards"]["hlth"]
                        for r in s["replicas"]}
        assert "Down" in rep_statuses


class TestReplicaFanout:
    def test_dual_write_reaches_every_replica(self):
        from filodb_tpu.gateway.server import ReplicaFanout, ShardingPublisher
        m = ShardMapper(2, replication_factor=2)
        m.register_node([0, 1], "a")
        m.register_node([0, 1], "b")
        stores, per_node = _mk_stores(m, ("a", "b"))
        pub = ShardingPublisher(DEFAULT_SCHEMAS["gauge"], m,
                                ReplicaFanout("prom", m, per_node,
                                              local_node="a"),
                                spread=1)
        for i in range(50):
            pub.add_sample("up", {"instance": f"i{i}", "_ws_": "w",
                                  "_ns_": "n"}, BASE + i * 1000, float(i))
        pub.flush()
        assert pub.publish.drain(timeout_s=10), "peer lane never drained"
        rows = {n: sum(sh.stats.rows_ingested
                       for sh in stores[n].shards("prom"))
                for n in ("a", "b")}
        assert rows["a"] == rows["b"] == 50

    def test_one_failing_replica_does_not_block_the_other(self):
        from filodb_tpu.gateway.server import ReplicaFanout
        fails = REGISTRY.counter(
            "filodb_ingest_replica_publish_failures_total")
        before = fails.value(dataset="prom", node="b")
        m = ShardMapper(1, replication_factor=2)
        m.register_node([0], "a")
        m.register_node([0], "b")
        got = []

        def boom(shard, container):
            raise OSError("replica b unreachable")

        fan = ReplicaFanout("prom", m,
                            {"a": lambda s, c: got.append(c), "b": boom},
                            local_node="a")
        # local delivered synchronously; the peer's failure happens on
        # its own lane and is counted there
        assert fan(0, b"container") == 2   # local sync + lane-accepted
        assert got == [b"container"]
        fan.drain(timeout_s=10)
        assert fails.value(dataset="prom", node="b") == before + 1

    def test_down_replica_not_dual_written(self):
        """Regression (review): a terminal Down copy stops receiving
        containers (no pinned lane / per-container failure churn for a
        permanently dead peer); delivery resumes when it rejoins."""
        from filodb_tpu.gateway.server import ReplicaFanout
        m = ShardMapper(1, replication_factor=2)
        m.register_node([0], "a")
        m.register_node([0], "b")
        got = {"a": [], "b": []}
        fan = ReplicaFanout("downskip", m,
                            {"a": lambda s, c: got["a"].append(c),
                             "b": lambda s, c: got["b"].append(c)},
                            local_node="a")
        m.update_status(0, ShardStatus.DOWN, node="b")
        assert fan(0, b"c1") == 1
        m.update_status(0, ShardStatus.ASSIGNED, node="b")  # rejoined
        assert fan(0, b"c2") == 2
        assert fan.drain(timeout_s=10)
        assert got["a"] == [b"c1", b"c2"]
        assert got["b"] == [b"c2"]

    def test_stopped_replica_not_dual_written(self):
        """Regression (review): an operator-STOPPED replica's ingestion
        consumer is not running (runnable_shards_for_node), so dual-
        writing to it would buffer containers into an unbounded queue
        nothing drains; delivery resumes when the shard restarts."""
        from filodb_tpu.gateway.server import ReplicaFanout
        m = ShardMapper(1, replication_factor=2)
        m.register_node([0], "a")
        m.register_node([0], "b")
        got = {"a": [], "b": []}
        fan = ReplicaFanout("stopskip", m,
                            {"a": lambda s, c: got["a"].append(c),
                             "b": lambda s, c: got["b"].append(c)},
                            local_node="a")
        m.update_status(0, ShardStatus.STOPPED, node="b")
        assert fan(0, b"c1") == 1
        m.update_status(0, ShardStatus.ACTIVE, node="b")   # restarted
        assert fan(0, b"c2") == 2
        assert fan.drain(timeout_s=10)
        assert got["a"] == [b"c1", b"c2"]
        assert got["b"] == [b"c2"]

    def test_all_terminal_group_is_not_rerouted_to_local(self):
        """Regression (review, 2 rounds): when EVERY assigned replica is
        terminal the containers are dropped LOUDLY — one failure-counter
        inc per container under node="(all-terminal)" and one flight
        event per episode — not silently buffered into the local node's
        consumerless queue (the copies rejoin from their own
        checkpoints, never from a bystander's queue).  The local
        fallback fires only while the shard is assigned NOWHERE
        (startup), and the episode re-arms once a copy comes back."""
        from filodb_tpu.gateway.server import ReplicaFanout
        from filodb_tpu.utils.devicewatch import FLIGHT
        fails = REGISTRY.counter(
            "filodb_ingest_replica_publish_failures_total")
        before = fails.value(dataset="allterm", node="(all-terminal)")
        ev_count = lambda: len(
            [e for e in FLIGHT.events(kind="ingest.replica_publish_failed")
             if e.get("dataset") == "allterm"
             and e.get("node") == "(all-terminal)"])
        ev_before = ev_count()
        m = ShardMapper(1, replication_factor=2)
        got = {"a": [], "b": [], "c": []}
        fan = ReplicaFanout("allterm", m,
                            {n: (lambda s, c, _n=n: got[_n].append(c))
                             for n in ("a", "b", "c")},
                            local_node="c")
        # unassigned anywhere: the startup fallback keeps data flowing
        assert fan(0, b"boot") == 1
        assert got["c"] == [b"boot"]
        m.register_node([0], "a")
        m.register_node([0], "b")
        m.update_status(0, ShardStatus.DOWN, node="a")
        m.update_status(0, ShardStatus.DOWN, node="b")
        assert fan(0, b"outage") == 0      # dropped loudly, not rerouted
        assert fan(0, b"outage2") == 0
        # per-container counter, once-per-episode flight event
        assert fails.value(dataset="allterm",
                           node="(all-terminal)") == before + 2
        assert ev_count() == ev_before + 1
        # a copy rejoins: delivery resumes and the episode re-arms
        m.update_status(0, ShardStatus.ASSIGNED, node="a")
        assert fan(0, b"back") == 1
        m.update_status(0, ShardStatus.DOWN, node="a")
        assert fan(0, b"outage3") == 0
        assert ev_count() == ev_before + 2
        assert fan.drain(timeout_s=10)
        assert got["a"] == [b"back"] and not got["b"]
        assert got["c"] == [b"boot"]

    def test_close_stops_peer_lanes(self):
        """Regression (review): FiloServer.shutdown closes the fanout —
        a 'killed' in-process node must not keep delivering buffered
        containers to surviving peers from beyond the grave."""
        from filodb_tpu.gateway.server import ReplicaFanout
        m = ShardMapper(1, replication_factor=2)
        m.register_node([0], "a")
        m.register_node([0], "b")
        gate = threading.Event()
        got = []

        def slow_peer(shard, container):
            gate.wait(5)
            got.append(container)

        fan = ReplicaFanout("closer", m,
                            {"a": lambda s, c: None, "b": slow_peer},
                            local_node="a")
        for i in range(8):
            fan(0, b"c%d" % i)             # b's lane buffers behind gate
        lane_threads = [ln._thread for ln in fan._lanes.values()]
        fan.close()
        gate.set()
        for t in lane_threads:
            t.join(timeout=5)
        assert all(not t.is_alive() for t in lane_threads)
        # at most the single in-flight delivery landed; the queued rest
        # were dropped by close(), and post-close publishes are refused
        assert len(got) <= 1
        assert fan(0, b"late") == 0

    def test_wedged_peer_never_stalls_the_gateway(self):
        """Regression (review, 2 rounds): a peer that blocks forever
        fills its own bounded lane and overflows — counted per container
        but flight-recorded only ONCE per episode (per-container events
        would evict every other diagnostic from the bounded ring during
        exactly the incident window) — while the gateway publish path
        and the local replica stay fast."""
        from filodb_tpu.gateway.server import ReplicaFanout
        from filodb_tpu.utils.devicewatch import FLIGHT
        fails = REGISTRY.counter(
            "filodb_ingest_replica_publish_failures_total")
        before = fails.value(dataset="wedge", node="b")
        ev_count = lambda: len(
            [e for e in FLIGHT.events(kind="ingest.replica_publish_failed")
             if e.get("dataset") == "wedge" and e.get("node") == "b"])
        ev_before = ev_count()
        m = ShardMapper(1, replication_factor=2)
        m.register_node([0], "a")
        m.register_node([0], "b")
        wedge = threading.Event()
        got = []

        def stuck(shard, container):
            wedge.wait()                   # a peer that never answers

        fan = ReplicaFanout("wedge", m,
                            {"a": lambda s, c: got.append(c), "b": stuck},
                            local_node="a", max_queued_per_peer=4)
        t0 = time.perf_counter()
        for i in range(20):
            fan(0, b"c%d" % i)
        elapsed = time.perf_counter() - t0
        assert elapsed < 1.0, f"gateway stalled {elapsed:.1f}s on a " \
                              f"wedged peer"
        assert len(got) == 20              # local replica got everything
        # overflow drops were counted loudly (lane bound 4 + 1 in-flight)
        assert fails.value(dataset="wedge", node="b") >= before + 10
        # ... but ONE flight event for the whole episode
        assert ev_count() == ev_before + 1
        # peer unwedges and drains: the successful deliveries re-arm
        # the SAME fanout's episode, so the next outage records again
        wedge.set()
        assert fan.drain(timeout_s=10)
        wedge.clear()
        fails2 = fails.value(dataset="wedge", node="b")
        for i in range(10):                # lane bound 4 + 1 in-flight
            fan(0, b"d%d" % i)
        assert fails2 < fails.value(dataset="wedge", node="b")
        assert ev_count() == ev_before + 2
        wedge.set()
        fan.close()

    def test_generative_dual_written_replicas_converge(self):
        """Generative sweep (satellite): random series/label churn
        dual-written through the fanout leaves both replicas with
        IDENTICAL index cardinality snapshots."""
        from filodb_tpu.gateway.server import ReplicaFanout, ShardingPublisher
        rng = np.random.default_rng(1234)
        m = ShardMapper(4, replication_factor=2)
        m.register_node([0, 1, 2, 3], "a")
        m.register_node([0, 1, 2, 3], "b")
        stores, per_node = _mk_stores(m, ("a", "b"))
        pub = ShardingPublisher(DEFAULT_SCHEMAS["gauge"], m,
                                ReplicaFanout("prom", m, per_node,
                                              local_node="a"),
                                spread=1)
        metrics = [f"gen_m{k}" for k in range(7)]
        for _round in range(20):
            for _ in range(int(rng.integers(5, 40))):
                tags = {"instance": f"i{int(rng.integers(0, 50))}",
                        "zone": f"z{int(rng.integers(0, 4))}",
                        "_ws_": "w", "_ns_": f"App-{int(rng.integers(0, 3))}"}
                pub.add_sample(str(rng.choice(metrics)), tags,
                               BASE + int(rng.integers(0, 10_000_000)),
                               float(rng.random()))
            pub.flush()
        assert pub.publish.drain(timeout_s=10)
        snaps = {}
        for node in ("a", "b"):
            snaps[node] = [stores[node].get_shard("prom", s)
                           .index.cardinality_snapshot()
                           for s in range(4)]
        assert snaps["a"] == snaps["b"]
        total = sum(active for active, _ in snaps["a"])
        assert total > 0


class TestContainerPushEdge:
    def test_http_push_lands_on_the_peer_stream(self):
        from filodb_tpu.gateway.server import http_container_push
        from filodb_tpu.http.server import FiloHttpServer
        from filodb_tpu.ingest.stream import QueueStreamFactory
        factory = QueueStreamFactory()
        srv = FiloHttpServer()
        srv.ingest_sink = lambda ds, shard, c: \
            factory.stream_for(ds, shard).push(c)
        port = srv.start()
        try:
            push = http_container_push(f"http://127.0.0.1:{port}", "prom")
            push(1, b"\x01container-bytes")
            stream = factory.stream_for("prom", 1)
            assert stream.end_offset() == 1
            # unknown routes 404 / empty bodies 400, loudly
            import urllib.error
            import urllib.request
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/ingest/prom/1", data=b"",
                method="POST")
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(req, timeout=5)
            assert e.value.code == 400
        finally:
            srv.shutdown()

    def test_push_to_sinkless_server_is_404(self):
        from filodb_tpu.gateway.server import http_container_push
        from filodb_tpu.http.server import FiloHttpServer
        import urllib.error
        srv = FiloHttpServer()
        port = srv.start()
        try:
            push = http_container_push(f"http://127.0.0.1:{port}", "prom")
            with pytest.raises(urllib.error.HTTPError) as e:
                push(0, b"x")
            assert e.value.code == 404
        finally:
            srv.shutdown()

    def test_push_offsets_fast_forward_past_checkpoints(self):
        """Regression (review): a peer container pushed BEFORE the
        restarted consumer fast-forwards its queue must still be
        numbered above the recovery checkpoints — an offset below the
        group watermark would be silently skipped as already
        persisted, losing brand-new data."""
        from filodb_tpu.standalone import FiloServer
        srv = FiloServer({"node": "cpf", "datasets": []})
        srv.metastore.initialize()
        srv.manager.setup_dataset("cp", 2, 1)
        srv._queue_push_datasets.add("cp")
        for g in range(4):
            srv.metastore.write_checkpoint("cp", 0, g, 500)
        off = srv._ingest_push("cp", 0, b"fresh-container")
        assert off >= 501, off
        # and the floor is applied before the FIRST push only once
        assert srv._ingest_push("cp", 0, b"next") == off + 1
        # out-of-range shards are refused, never ACKed into a
        # consumerless queue (review regression)
        with pytest.raises(ValueError, match="out of range"):
            srv._ingest_push("cp", 9999, b"lost-forever")

    def test_push_floor_not_cached_on_transient_metastore_failure(self):
        """Regression (review): a checkpoint read failing during the
        first push (metastore not ready at restart) must NOT cache a
        floor of 0 — the fast-forward protection has to recover on the
        next push once the metastore is readable."""
        from filodb_tpu.standalone import FiloServer
        srv = FiloServer({"node": "cpf2", "datasets": []})
        srv.metastore.initialize()
        srv.manager.setup_dataset("cq", 1, 1)
        srv._queue_push_datasets.add("cq")
        for g in range(4):
            srv.metastore.write_checkpoint("cq", 0, g, 500)
        real = srv.metastore.read_checkpoints
        calls = {"n": 0}

        def flaky(ds, shard):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("meta store not ready")
            return real(ds, shard)

        srv.metastore.read_checkpoints = flaky
        srv._ingest_push("cq", 0, b"early")  # read failed: floor 0 ...
        assert ("cq", 0) not in srv._push_offset_floor  # ... NOT cached
        off = srv._ingest_push("cq", 0, b"late")  # retried, caught up
        assert off >= 501, off
        assert srv._push_offset_floor[("cq", 0)] == 501
        """Two FiloServer nodes, NO broker: rf=2 over the in-proc queue
        transport dual-writes every gateway container to the peer via
        the POST /ingest edge — both replicas end with the same rows."""
        import socket

        from filodb_tpu.standalone import FiloServer

        def free_port():
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                return s.getsockname()[1]

        ports = {"qa-a": free_port(), "qa-b": free_port()}
        peers = {n: f"http://127.0.0.1:{p}" for n, p in ports.items()}
        servers = {}
        try:
            for n in ("qa-a", "qa-b"):
                servers[n] = FiloServer({
                    "node": n, "http-port": ports[n], "peers": peers,
                    "status-poll-interval-s": 0.2,
                    "datasets": [{"name": "qd", "num-shards": 2,
                                  "min-num-nodes": 2,
                                  "replication-factor": 2,
                                  "schema": "gauge", "spread": 1}]})
                servers[n].start()
            deadline = time.time() + 30
            m = servers["qa-a"].manager.mapper("qd")
            while time.time() < deadline:
                if all(len(m.live_replicas(s)) == 2 for s in range(2)) \
                        and all(r.status is ShardStatus.ACTIVE
                                for s in range(2)
                                for r in m.live_replicas(s)):
                    break
                time.sleep(0.05)
            assert all(len(m.live_replicas(s)) == 2 for s in range(2))
            pub = servers["qa-a"].write_publishers["qd"]
            from filodb_tpu.gateway.server import ReplicaFanout
            assert isinstance(pub.publish, ReplicaFanout)
            for i in range(40):
                pub.add_sample("dw_m", {"instance": f"i{i}", "_ws_": "w",
                                        "_ns_": "n"}, BASE + i * 1000,
                               float(i))
            pub.flush()
            deadline = time.time() + 20
            while time.time() < deadline:
                rows = [sum(sh.stats.rows_ingested
                            for sh in servers[n].memstore.shards("qd"))
                        for n in ("qa-a", "qa-b")]
                if rows[0] >= 40 and rows[1] >= 40:
                    break
                time.sleep(0.05)
            assert rows[0] >= 40 and rows[1] >= 40, \
                f"dual-write did not reach both replicas: {rows}"
        finally:
            for srv in servers.values():
                srv.shutdown()


# ---------------------------------------------------------------------------
# Recovery promotion: group head + evict/purge races
# ---------------------------------------------------------------------------


def _container(i, metric="rec_m", n_inst=13):
    b = RecordBuilder(DEFAULT_SCHEMAS["gauge"], container_size=1 << 14)
    b.add(BASE + i * 1000, [float(i)],
          {"__name__": metric, "u": f"s{i % n_inst}", "_ws_": "w",
           "_ns_": "n"})
    (out,) = b.containers()
    return out


class TestGroupHeadPromotion:
    def test_recovery_holds_until_group_head_reached(self):
        factory = QueueStreamFactory()
        store = TimeSeriesMemStore()
        store.setup("prom", DEFAULT_SCHEMAS, 0)
        for g in range(store.get_shard("prom", 0).num_groups):
            store.meta.write_checkpoint("prom", 0, g, 5)
        stream = factory.stream_for("prom", 0)
        for i in range(10):                       # offsets 0..9
            stream.push(_container(i))
        head = {"v": 14}
        events = []
        ic = IngestionCoordinator(
            "n", "prom", DEFAULT_SCHEMAS, store, factory,
            event_sink=events.append, recovery_report_interval=1,
            group_head_fn=lambda shard: head["v"])
        ic.start_ingestion(0)
        deadline = time.time() + 5
        while time.time() < deadline:
            if store.get_shard("prom", 0).latest_offset >= 9:
                break
            time.sleep(0.01)
        time.sleep(0.05)
        from filodb_tpu.coordinator.cluster import (IngestionStarted,
                                                    RecoveryInProgress)
        # consumed past the LOCAL checkpoint head (5) but the group head
        # (14) is ahead: the replica must still be recovering
        assert not any(isinstance(e, IngestionStarted) for e in events)
        assert any(isinstance(e, RecoveryInProgress) and 0 < e.progress_pct
                   for e in events)
        for i in range(10, 15):                   # offsets 10..14 = head
            stream.push(_container(i))
        deadline = time.time() + 5
        while time.time() < deadline:
            if any(isinstance(e, IngestionStarted) for e in events):
                break
            time.sleep(0.01)
        assert any(isinstance(e, IngestionStarted) for e in events), \
            "never promoted after reaching the group head"
        ic.stop_all()

    def test_promotion_races_concurrent_evict_and_purge(self):
        """Satellite edge: recovery replay with concurrent evict/purge
        churn must neither wedge promotion nor corrupt the index."""
        factory = QueueStreamFactory()
        store = TimeSeriesMemStore()
        store.setup("prom", DEFAULT_SCHEMAS, 0)
        for g in range(store.get_shard("prom", 0).num_groups):
            store.meta.write_checkpoint("prom", 0, g, 10)
        stream = factory.stream_for("prom", 0)
        n = 300
        for i in range(n):
            stream.push(_container(i, n_inst=37))
        from filodb_tpu.coordinator.cluster import IngestionStarted
        events = []
        promoted = threading.Event()

        def sink(e):
            events.append(e)
            if isinstance(e, IngestionStarted):
                promoted.set()

        ic = IngestionCoordinator(
            "n", "prom", DEFAULT_SCHEMAS, store, factory,
            event_sink=sink, recovery_report_interval=5,
            group_head_fn=lambda shard: n - 1)
        stop = threading.Event()
        churn_errors = []

        def churn():
            sh = store.get_shard("prom", 0)
            while not stop.is_set():
                try:
                    sh.evict_partitions(2)
                    sh.purge_expired(retention_ms=1,
                                     now_ms=BASE + 10_000_000_000)
                except Exception as e:  # noqa: BLE001
                    churn_errors.append(e)
                time.sleep(0.001)

        t = threading.Thread(target=churn, daemon=True)
        t.start()
        ic.start_ingestion(0)
        # the event itself, under a cap sized for a host that runs six
        # test workers: promotion that never comes still fails below
        promoted.wait(timeout=90)
        stop.set()
        t.join(timeout=5)
        ic.stop_all()
        assert not churn_errors, churn_errors
        assert any(isinstance(e, IngestionStarted) for e in events), \
            "promotion wedged by concurrent evict/purge"
        sh = store.get_shard("prom", 0)
        active, by_label = sh.index.cardinality_snapshot()
        assert active == sh.index.active_series_count()


# ---------------------------------------------------------------------------
# /admin/shards per-replica view
# ---------------------------------------------------------------------------


class TestAdminShardsReplicaView:
    def test_rows_list_replica_node_status_and_lag(self):
        from filodb_tpu.memstore.watermarks import WatermarkLedger
        m = ShardMapper(1, dataset="admrep", replication_factor=2)
        m.register_node([0], "a")
        m.register_node([0], "b")
        m.update_status(0, ShardStatus.ACTIVE, node="a")
        m.update_status(0, ShardStatus.RECOVERY, progress=60, node="b")
        m.note_watermark(0, "a", 1000)
        m.note_watermark(0, "b", 400)
        store = TimeSeriesMemStore()
        store.setup("admrep", DEFAULT_SCHEMAS, 0)
        ledger = WatermarkLedger(node="a")
        ledger.watch("admrep", store, mapper=m)
        tree = ledger.sample()
        row = tree["datasets"]["admrep"]["shards"][0]
        reps = {r["node"]: r for r in row["replicas"]}
        assert reps["a"]["status"] == "Active"
        assert reps["a"]["lag_rows"] == 0
        assert reps["b"]["status"] == "Recovery"
        assert reps["b"]["recovery_progress"] == 60
        assert reps["b"]["lag_rows"] == 600
        # shard-level fields show the SERVING view (review regression):
        # a dead PRIMARY must not report a served shard as down
        m.update_status(0, ShardStatus.DOWN, node="a")
        m.update_status(0, ShardStatus.ACTIVE, node="b")
        tree = ledger.sample()
        row = tree["datasets"]["admrep"]["shards"][0]
        assert row["status"] == "Active"
        assert row["queryable"] is True
        assert row["owner"] == "b"
        assert tree["datasets"]["admrep"]["totals"]["queryable"] == 1
