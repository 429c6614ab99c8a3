"""ShardMapper: record -> shard bit-splice, spread fan-out, shard status.

Pure-function port-of-concept of the reference's ShardMapper
(reference: coordinator/src/main/scala/filodb.coordinator/ShardMapper.scala:
26-46 — shard = f(shardKeyHash upper bits, partitionHash lower bits, spread);
queryShards returns the 2^spread shards holding one shard key) plus the
ShardStatus lifecycle (ShardStatus.scala:54-94).  TPU mapping: a shard is a
slice of the mesh's data axis; ``coord_for_shard`` is the host/device owner.

Replica groups (ISSUE 7): each shard is held by up to
``replication_factor`` DISTINCT nodes; :class:`ReplicaState` tracks
per-replica status, recovery progress, and ingest watermark (the
gossiped ``latest_offset``, feeding the group head that gates recovery
promotion and the failover router's lag ordering).  The legacy
single-copy surface (``coord_for_shard`` / ``status`` / ``state``)
reads the shard's PRIMARY (first) replica, so ``replication_factor=1``
behaves exactly as before.

Elastic resharding (ISSUE 13): because the shard is a hash bit-splice,
doubling ``num_shards`` sends every series of parent shard ``s`` to
either ``s`` or ``s + N`` (N = old count) — for EVERY spread setting
(the new mask bit comes from the shard-key hash when spread <= log2 N,
and from the modulo fold otherwise; tests/test_split.py sweeps this).
The mapper therefore carries a :class:`Topology`: the SERVING shard
count (``num_shards``, the hash-mask base queries and gateways use),
the TOTAL registered shard states (``total_shards``, which includes
in-flight split children holding Recovery replica groups), and a
monotone ``topology_generation`` every serving-path memo keyed on shard
ids must validate against (gateway series memos, result-cache routing
tokens — the ``topology-generation`` filolint rule).  All topology
transitions swap ONE immutable Topology object, so unlocked readers
always see a consistent (num_shards, generation, split-phase) triple.
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Optional, Sequence


class ShardStatus(enum.Enum):
    UNASSIGNED = "Unassigned"
    ASSIGNED = "Assigned"
    RECOVERY = "Recovery"
    ACTIVE = "Active"
    ERROR = "Error"
    STOPPED = "Stopped"
    DOWN = "Down"

    @property
    def queryable(self) -> bool:
        return self in (ShardStatus.ACTIVE, ShardStatus.RECOVERY)


# stable numeric codes for the filodb_shard_status_code gauge (dashboards
# need an orderable value; enum order here is the lifecycle order)
_STATUS_CODE = {
    ShardStatus.UNASSIGNED: 0, ShardStatus.ASSIGNED: 1,
    ShardStatus.RECOVERY: 2, ShardStatus.ACTIVE: 3, ShardStatus.ERROR: 4,
    ShardStatus.STOPPED: 5, ShardStatus.DOWN: 6,
}

_HEALTH_METRICS = None


def _health_m() -> dict:
    global _HEALTH_METRICS
    if _HEALTH_METRICS is None:
        from filodb_tpu.utils.observability import shard_health_metrics
        _HEALTH_METRICS = shard_health_metrics()
    return _HEALTH_METRICS


@dataclasses.dataclass(frozen=True)
class Topology:
    """One immutable topology view (ISSUE 13).  ``num_shards`` is the
    SERVING count — the hash-mask base ingestion and query fan-out use;
    ``total_shards`` additionally counts in-flight split children
    (Recovery replica groups catching up but not yet routed).  The
    ``generation`` is monotone across every transition (prepare,
    cutover, retire-complete, abort) so consumers that memoize per-shard
    state can validate with one int compare, and gossip adoption is a
    simple newest-wins."""

    num_shards: int
    total_shards: int
    generation: int = 0
    # split bookkeeping while a split is in flight; phase is one of
    # "catchup" (children replaying, queries still route the parents),
    # "serving" (cutover done: 2N-way routing, parents exclude their
    # migrated half at scan time), "retire" (grace elapsed: parents
    # purge migrated data).  None = no split in flight.
    split_phase: Optional[str] = None
    split_base: Optional[int] = None
    split_spread: Optional[int] = None
    # the generation at which THIS split instance was prepared — a
    # process-wide-unique id for the split (generations are strictly
    # monotone), so per-node KV markers written during one split can
    # never satisfy a later split of the same dataset
    split_epoch: Optional[int] = None

    def query_shards(self, shard_key_hash: int, spread: int) -> list[int]:
        """All 2^spread shards that can hold one shard key under THIS
        topology view — the planner computes fan-out from its per-query
        snapshot, never from the live mapper, so a cutover committing
        mid-plan cannot mix old fan-out with new exclusions."""
        n = self.num_shards
        base = shard_key_hash & ((n - 1) & ~((1 << spread) - 1))
        return [(base | i) % n for i in range(1 << spread)]

    def parent_exclusion(self, shard: int) -> Optional[tuple[int, int]]:
        """(total_shards, ingest_spread) when ``shard`` is a split
        parent whose migrated half must be EXCLUDED from its scans —
        active from cutover until the split completes (the parent holds
        a full superset until retire purges it; serving it unfiltered
        would double-count every migrated series against its child)."""
        if self.split_phase in ("serving", "retire") \
                and self.split_base is not None \
                and shard < self.split_base:
            return self.total_shards, self.split_spread or 0
        return None

    def as_payload(self) -> dict:
        """Wire form for /__health gossip."""
        out = {"num_shards": self.num_shards,
               "total_shards": self.total_shards,
               "generation": self.generation}
        if self.split_phase is not None:
            out["split"] = {"phase": self.split_phase,
                            "base": self.split_base,
                            "spread": self.split_spread,
                            "epoch": self.split_epoch}
        return out


def shard_of_tags(tags, total: int, spread: int, options=None) -> int:
    """The shard a series' tags route to under a ``total``-shard
    topology — the SAME bit-splice the gateway uses at ingest, so split
    membership (parent half vs child half) is decided by one pure
    function everywhere (child ingest filters, parent scan exclusion,
    retire purge, the generative rehash sweep)."""
    from filodb_tpu.core.record import partition_hash, shard_key_hash
    from filodb_tpu.core.schemas import DatasetOptions
    opts = options or DatasetOptions()
    shash = shard_key_hash(tags, opts)
    phash = partition_hash(tags, opts)
    mask = (total - 1) & ~((1 << spread) - 1)
    return ((shash & mask) | (phash & ((1 << spread) - 1))) % total


@dataclasses.dataclass
class ReplicaState:
    """One node's copy of one shard."""

    node: str
    status: ShardStatus = ShardStatus.ASSIGNED
    recovery_progress: int = 0  # percent
    # last gossiped ingested offset (-1 = unknown); feeds group_head()
    watermark: int = -1


class ShardState:
    """Per-shard replica group.  The legacy single-copy attributes
    (``status`` / ``node`` / ``recovery_progress``) read the PRIMARY
    (first) replica so rf=1 callers see exactly the old shape."""

    __slots__ = ("replicas",)

    def __init__(self, status: ShardStatus = ShardStatus.UNASSIGNED,
                 node: Optional[str] = None, recovery_progress: int = 0):
        self.replicas: list[ReplicaState] = []
        if node is not None:
            self.replicas.append(ReplicaState(node, status,
                                              recovery_progress))

    def replica(self, node: str) -> Optional[ReplicaState]:
        for r in self.replicas:
            if r.node == node:
                return r
        return None

    # -- legacy single-copy view (primary replica) --------------------------

    @property
    def status(self) -> ShardStatus:
        return self.replicas[0].status if self.replicas \
            else ShardStatus.UNASSIGNED

    @property
    def node(self) -> Optional[str]:
        return self.replicas[0].node if self.replicas else None

    @property
    def recovery_progress(self) -> int:
        return self.replicas[0].recovery_progress if self.replicas else 0

    @property
    def best_status(self) -> ShardStatus:
        """The most-servable status across replicas: a shard with ANY
        Active replica serves normally even while a peer recovers."""
        best = ShardStatus.UNASSIGNED
        rank = {ShardStatus.ACTIVE: 6, ShardStatus.RECOVERY: 5,
                ShardStatus.ASSIGNED: 4, ShardStatus.STOPPED: 3,
                ShardStatus.ERROR: 2, ShardStatus.DOWN: 1,
                ShardStatus.UNASSIGNED: 0}
        for r in self.replicas:
            if rank[r.status] > rank[best]:
                best = r.status
        return best

    def serving_replica(self) -> Optional[ReplicaState]:
        """The replica holding the best (serving) status — THE
        definition every operator surface (/admin/shards,
        /api/v1/cluster status) reports, so the views cannot drift."""
        best = self.best_status
        return next((r for r in self.replicas if r.status is best), None)


class ShardMapper:
    def __init__(self, num_shards: int, dataset: str = "",
                 replication_factor: int = 1):
        if num_shards <= 0 or num_shards & (num_shards - 1):
            raise ValueError(f"num_shards {num_shards} must be a power of 2")
        if replication_factor < 1:
            raise ValueError(
                f"replication_factor {replication_factor} must be >= 1")
        self.replication_factor = replication_factor
        # named mappers (cluster-managed) emit shard-health metrics and
        # flight events on status changes; anonymous ones (ad-hoc
        # tests) stay silent
        self.dataset = dataset
        self._states = [ShardState() for _ in range(num_shards)]
        # ONE atomically-swapped object carries (serving count, total
        # count, generation, split phase) — see Topology above.  All
        # split transitions happen under the ShardManager lock; readers
        # are unlocked and rely on the swap being atomic.
        self._topology = Topology(num_shards, num_shards)

    # -- topology (ISSUE 13) ------------------------------------------------

    @property
    def num_shards(self) -> int:
        """SERVING shard count — the hash-mask base for ingestion
        routing and query fan-out.  During a split this stays at the
        parent count until cutover commits."""
        return self._topology.num_shards

    @property
    def total_shards(self) -> int:
        """Registered shard states including in-flight split children —
        the range every replica/status/watermark surface (gossip,
        /__health, ledger) must sweep, or catching-up children would be
        invisible to the promotion gate."""
        return len(self._states)

    @property
    def topology(self) -> Topology:
        return self._topology

    @property
    def topology_generation(self) -> int:
        return self._topology.generation

    def begin_split(self, spread: int = 0) -> Topology:
        """PREPARE: double the registered shard space.  Child shard
        ``s + N`` is created UNASSIGNED for every parent ``s``; serving
        routing (``num_shards``) is untouched, so queries and gateways
        keep running on the parent topology while children catch up.
        Bumps the generation (shard-keyed memos revalidate)."""
        t = self._topology
        if t.split_phase is not None:
            raise ValueError(f"dataset {self.dataset!r} already has a "
                             f"split in flight (phase {t.split_phase})")
        base = t.num_shards
        self._states = self._states + [ShardState() for _ in range(base)]
        self._topology = Topology(base, 2 * base, t.generation + 1,
                                  split_phase="catchup", split_base=base,
                                  split_spread=spread,
                                  split_epoch=t.generation + 1)
        return self._topology

    def register_split_child(self, shard: int, nodes: Sequence[str]) -> None:
        """Register a child shard's replica group in RECOVERY — the
        state the PR 12 promotion gate expects a replaying copy in."""
        st = self._states[shard]
        prev = st.status
        st.replicas = [ReplicaState(n, ShardStatus.RECOVERY) for n in nodes]
        for n in nodes:
            self._note_replica(shard, n, ShardStatus.UNASSIGNED,
                               ShardStatus.RECOVERY, 0)
        if prev is not st.status:
            self._note_status(shard, prev, st.status, st.recovery_progress)

    def commit_split(self) -> Topology:
        """CUTOVER: atomically flip serving to the doubled topology.
        From this generation on, query fan-out covers the children and
        parents exclude their migrated half at scan time
        (``Topology.parent_exclusion``); gateways rehash their memos on
        the generation bump.  The parents still hold a full superset of
        the data (retire purges it later), so abort remains lossless."""
        t = self._topology
        if t.split_phase != "catchup":
            raise ValueError(f"cannot commit split from phase "
                             f"{t.split_phase!r}")
        self._topology = Topology(t.total_shards, t.total_shards,
                                  t.generation + 1, split_phase="serving",
                                  split_base=t.split_base,
                                  split_spread=t.split_spread,
                                  split_epoch=t.split_epoch)
        return self._topology

    def retire_split(self) -> Topology:
        """RETIRE: the grace window elapsed — participants purge the
        parents' migrated halves and install the parents' retain-half
        ingest filters."""
        t = self._topology
        if t.split_phase != "serving":
            raise ValueError(f"cannot retire split from phase "
                             f"{t.split_phase!r}")
        self._topology = dataclasses.replace(t, generation=t.generation + 1,
                                             split_phase="retire")
        return self._topology

    def finish_split(self) -> Topology:
        """COMPLETE: every parent purged its migrated half — drop the
        split bookkeeping (and with it the scan exclusions)."""
        t = self._topology
        self._topology = Topology(t.num_shards, len(self._states),
                                  t.generation + 1)
        return self._topology

    def abort_split(self) -> Topology:
        """ABORT, from any in-flight phase: children are dropped,
        serving flips back to the parent topology, and the parents —
        which held a full superset throughout — simply keep serving.
        Lossless by construction."""
        t = self._topology
        if t.split_phase is None:
            return t
        base = t.split_base or t.num_shards
        for s in range(base, len(self._states)):
            for r in self._states[s].replicas:
                self._note_replica(s, r.node, r.status,
                                   ShardStatus.UNASSIGNED, 0)
        self._states = self._states[:base]
        self._topology = Topology(base, base, t.generation + 1)
        return self._topology

    def adopt_topology(self, payload: dict) -> bool:
        """Gossip adoption (newest generation wins, strictly monotone):
        reconcile the local shard space + topology with a peer's
        ``Topology.as_payload()``.  Returns True when anything changed.
        Works from any peer, not just the leader — split phases are
        driven by the coordinator that owns the split record, and every
        transition only ever bumps the generation."""
        gen = int(payload.get("generation", 0))
        t = self._topology
        if gen <= t.generation:
            return False
        total = int(payload.get("total_shards", t.total_shards))
        num = int(payload.get("num_shards", t.num_shards))
        if total > len(self._states):
            self._states = self._states + [
                ShardState() for _ in range(total - len(self._states))]
        elif total < len(self._states):
            for s in range(total, len(self._states)):
                for r in self._states[s].replicas:
                    self._note_replica(s, r.node, r.status,
                                       ShardStatus.UNASSIGNED, 0)
            self._states = self._states[:total]
        sp = payload.get("split") or {}
        self._topology = Topology(num, total, gen,
                                  split_phase=sp.get("phase"),
                                  split_base=sp.get("base"),
                                  split_spread=sp.get("spread"),
                                  split_epoch=sp.get("epoch"))
        return True

    def split_parent_of(self, shard: int) -> Optional[int]:
        """The parent of an in-flight split child, else None."""
        t = self._topology
        if t.split_phase is not None and t.split_base is not None \
                and shard >= t.split_base:
            return shard - t.split_base
        return None

    # -- hashing ------------------------------------------------------------

    def shard_hash_mask(self, spread: int) -> int:
        return (self.num_shards - 1) & ~((1 << spread) - 1)

    def part_hash_mask(self, spread: int) -> int:
        return (1 << spread) - 1

    def ingestion_shard(self, shard_key_hash: int, part_hash: int,
                        spread: int) -> int:
        """Upper bits from the shard-key hash, lower ``spread`` bits from the
        partition hash (reference: ShardMapper.ingestionShard)."""
        return ((shard_key_hash & self.shard_hash_mask(spread))
                | (part_hash & self.part_hash_mask(spread)))

    def query_shards(self, shard_key_hash: int, spread: int) -> list[int]:
        """All 2^spread shards that can hold series of one shard key."""
        base = shard_key_hash & self.shard_hash_mask(spread)
        return [base | i for i in range(1 << spread)]

    # -- assignment / status ------------------------------------------------

    def register_node(self, shards: Sequence[int], node: str) -> None:
        """Add ``node`` as a replica of each shard (refreshing it to
        ASSIGNED when already present).  With ``replication_factor=1``
        the replica set is REPLACED — the legacy single-owner move
        semantics (leader-view adoption, reassignment).  With rf>1 a
        full group replaces its least-healthy non-live replica (the
        failover reassignment path) and never holds the same node
        twice."""
        for s in shards:
            st = self._states[s]
            prev = st.status
            rep = st.replica(node)
            if rep is not None:
                r_prev = rep.status
                rep.status = ShardStatus.ASSIGNED
                rep.recovery_progress = 0
                if r_prev in (ShardStatus.DOWN, ShardStatus.ERROR):
                    # rejoin: the node restarted and replays from its
                    # checkpoint — its pre-crash watermark is stale and
                    # max-only note_watermark would pin it forever,
                    # hiding the replay regression from lag views
                    rep.watermark = -1
                self._note_replica(s, node, r_prev, ShardStatus.ASSIGNED, 0)
            elif self.replication_factor == 1:
                for old in st.replicas:  # displaced: gauge row removed
                    self._note_replica(s, old.node, old.status,
                                       ShardStatus.UNASSIGNED, 0)
                st.replicas = [ReplicaState(node)]
                self._note_replica(s, node, ShardStatus.UNASSIGNED,
                                   ShardStatus.ASSIGNED, 0)
            else:
                if len(st.replicas) >= self.replication_factor:
                    # replace a dead copy; refuse to displace live ones
                    dead = [i for i, r in enumerate(st.replicas)
                            if r.status in (ShardStatus.DOWN,
                                            ShardStatus.ERROR)]
                    if not dead:
                        continue
                    old = st.replicas[dead[0]]
                    self._note_replica(s, old.node, old.status,
                                       ShardStatus.UNASSIGNED, 0)
                    # copy-swap, never in-place: /health and the
                    # watermark ledger iterate st.replicas WITHOUT the
                    # manager lock and must always see a complete group
                    reps = list(st.replicas)
                    reps[dead[0]] = ReplicaState(node)
                    st.replicas = reps
                else:
                    st.replicas = st.replicas + [ReplicaState(node)]
                self._note_replica(s, node, ShardStatus.UNASSIGNED,
                                   ShardStatus.ASSIGNED, 0)
            self._note_status(s, prev, st.status, st.recovery_progress)

    def update_status(self, shard: int, status: ShardStatus,
                      progress: int = 0, node: Optional[str] = None) -> None:
        """Update ONE replica's status: the replica owned by ``node``
        when given (ignored if that node holds no copy), else the
        primary replica (the only one at rf=1)."""
        if not 0 <= shard < len(self._states):
            return  # a discarded split child's dying consumer reporting
        st = self._states[shard]
        rep = st.replica(node) if node is not None \
            else (st.replicas[0] if st.replicas else None)
        if rep is None:
            return
        prev_shard, prev_progress_shard = st.status, st.recovery_progress
        r_prev, r_prev_progress = rep.status, rep.recovery_progress
        rep.status = status
        rep.recovery_progress = progress
        if r_prev is not status or r_prev_progress != progress:
            self._note_replica(shard, rep.node, r_prev, status, progress)
        if prev_shard is not st.status \
                or prev_progress_shard != st.recovery_progress:
            self._note_status(shard, prev_shard, st.status,
                              st.recovery_progress)

    def set_replicas(self, shard: int, rows: Sequence[dict]) -> bool:
        """Adopt a leader-snapshot replica group wholesale (gossip:
        every node caches the singleton's ShardMapper snapshots).
        ``rows``: ``[{"node", "status", "progress", "watermark"}]``.
        Membership is replaced; replicas this node already tracked keep
        their LOCAL status (per-replica liveness is per-node ground
        truth), newly-learned replicas take the leader's status.
        Returns True when membership changed."""
        st = self._states[shard]
        # shard-level prev BEFORE any mutation: kept replicas are
        # updated in place below, so reading st.status afterwards would
        # compare the new primary status with itself and never fire the
        # shard-level transition (gauge + flight event) on adoption
        prev = st.status
        want = [r for r in rows if r.get("node")]
        want_nodes = [r["node"] for r in want]
        have_nodes = [r.node for r in st.replicas]
        changed = set(want_nodes) != set(have_nodes)
        keep = {r.node: r for r in st.replicas if r.node in want_nodes}
        terminal = (ShardStatus.DOWN, ShardStatus.STOPPED)
        new_reps: list[ReplicaState] = []
        for row in want:
            node = row["node"]
            rep = keep.get(node)
            if rep is None:
                try:
                    status = ShardStatus(row.get("status"))
                except ValueError:
                    status = ShardStatus.ASSIGNED
                rep = ReplicaState(node, status,
                                   int(row.get("progress") or 0),
                                   int(row.get("watermark", -1)))
                self._note_replica(shard, node, ShardStatus.UNASSIGNED,
                                   status, rep.recovery_progress)
            else:
                rep.watermark = max(rep.watermark,
                                    int(row.get("watermark", -1)))
                try:
                    leader_status = ShardStatus(row.get("status"))
                except ValueError:
                    leader_status = None
                if leader_status is not None and \
                        (leader_status in terminal) \
                        != (rep.status in terminal):
                    # leader INTENT (demotion to Down/Stopped, or the
                    # resurrection of a rejoined node) crosses the
                    # down boundary and must propagate to followers —
                    # keeping the local stale Active would route every
                    # query at a dead replica forever.  WITHIN live
                    # states (Active/Recovery/Assigned) the local
                    # liveness view of the peer stays authoritative.
                    r_prev = rep.status
                    rep.status = leader_status
                    rep.recovery_progress = int(row.get("progress") or 0)
                    # boundary crossing also RESETS the watermark to
                    # the leader's view: a resurrected node replays
                    # from its checkpoint, and max-merging would pin
                    # its pre-crash offset forever
                    rep.watermark = int(row.get("watermark", -1))
                    self._note_replica(shard, node, r_prev, leader_status,
                                       rep.recovery_progress)
            new_reps.append(rep)
        for rep in st.replicas:
            if rep.node not in want_nodes:
                self._note_replica(shard, rep.node, rep.status,
                                   ShardStatus.UNASSIGNED, 0)
        st.replicas = new_reps
        if prev is not st.status:
            self._note_status(shard, prev, st.status, st.recovery_progress)
        else:
            # newly-learned replicas were noted BEFORE the swap, when
            # best_status couldn't see them yet — refresh after it can
            self._refresh_shard_gauge(shard)
        return changed

    def note_watermark(self, shard: int, node: str, offset: int) -> None:
        """Record a replica's gossiped ingested offset (silent: the
        watermark ledger owns the metric surface for offsets)."""
        if not 0 <= shard < len(self._states):
            return  # split child gossip racing local topology adoption
        rep = self._states[shard].replica(node)
        if rep is not None:
            rep.watermark = max(rep.watermark, int(offset))

    def group_head(self, shard: int) -> int:
        """The replica group's ingest head: the max gossiped watermark
        across the group (-1 when nothing is known).  A recovering
        replica is promoted only once its own offset reaches this.

        Split children (ISSUE 13) replay their PARENT's partition, so
        their offsets live in the parent's domain — the head folds the
        parent group in, which is exactly the PR 12 promotion gate:
        a child is promoted only once it has replayed past everything
        any parent replica has ingested."""
        if not 0 <= shard < len(self._states):
            return -1  # post-abort race: discarded child
        st = self._states[shard]
        wms = [r.watermark for r in st.replicas]
        head = max(wms) if wms else -1
        parent = self.split_parent_of(shard)
        if parent is not None:
            pwms = [r.watermark for r in self._states[parent].replicas]
            if pwms:
                head = max(head, max(pwms))
        return head

    def routing_token(self) -> int:
        """Cheap hash of the replica-routing state: membership and
        per-replica status across every shard, FOLDED with the topology
        generation (ISSUE 13 satellite) — a completed split doubles the
        shard layout without necessarily changing any replica row the
        old token hashed, and a result-cache entry sliced on the retired
        layout must not survive the cutover.  Any failover-relevant
        transition (node death, demotion, promotion, reassignment)
        changes it too, so consumers that memoize answers computed under
        one routing view (query/resultcache.py) can key validity on it
        without subscribing to shard events.  Watermarks are excluded
        on purpose — they advance with every ingested row."""
        t = self._topology
        acc = [(t.generation, t.num_shards, t.split_phase)]
        for shard, st in enumerate(self._states):
            for r in st.replicas:      # copy-swap lists: safe to iterate
                acc.append((shard, r.node, r.status.value))
        return hash(tuple(acc))

    def unassign(self, shard: int, node: Optional[str] = None) -> None:
        """Drop a replica (``node`` given) or the whole group."""
        st = self._states[shard]
        prev = st.status
        if node is not None:
            rep = st.replica(node)
            if rep is None:
                return
            # copy-swap (unlocked readers iterate st.replicas)
            st.replicas = [r for r in st.replicas if r is not rep]
            self._note_replica(shard, node, rep.status,
                               ShardStatus.UNASSIGNED, 0)
        else:
            for r in st.replicas:
                self._note_replica(shard, r.node, r.status,
                                   ShardStatus.UNASSIGNED, 0)
            st.replicas = []
        if prev is not st.status:
            self._note_status(shard, prev, st.status, st.recovery_progress)

    def _note_status(self, shard: int, prev: ShardStatus,
                     status: ShardStatus, progress: int) -> None:
        """Shard-health emission (ISSUE 6): gauge + transition counter +
        flight event, ONLY on real changes (the status poller re-applies
        identical statuses every sweep — those must not spam the ring).
        Anonymous mappers (no dataset name) skip it entirely."""
        if not self.dataset:
            return
        m = _health_m()
        self._refresh_shard_gauge(shard)
        m["recovery_progress"].set(progress, dataset=self.dataset,
                                   shard=shard)
        if prev is not status:
            # the transition COUNTER is owned by the per-replica path
            # (_note_replica) — at rf=1 replica transitions == shard
            # transitions, and at rf>1 every lost/recovered copy counts
            from filodb_tpu.utils.devicewatch import FLIGHT
            FLIGHT.record("shard.status", dataset=self.dataset, shard=shard,
                          status=status.value, prev=prev.value,
                          progress=progress)

    def _refresh_shard_gauge(self, shard: int) -> None:
        """filodb_shard_status_code reports the SERVING view (best
        replica), matching /admin/shards, /api/v1/cluster and /__health
        — a dead primary with a surviving Active peer must not page
        'shard down' for a fully-served shard.  Refreshed after every
        replica transition, since any copy's change can move the best."""
        if not self.dataset:
            return
        _health_m()["status_code"].set(
            _STATUS_CODE[self._states[shard].best_status],
            dataset=self.dataset, shard=shard)

    def _note_replica(self, shard: int, node: str, prev: ShardStatus,
                      status: ShardStatus, progress: int) -> None:
        """Per-replica health emission (ISSUE 7): the replica-status
        gauge row is keyed by node so operators can see ONE copy down
        while the shard gauge (serving view) stays green.  rf=1 named
        mappers emit both rows — the replica row is the per-copy truth,
        the shard row the serving view."""
        if not self.dataset:
            return
        m = _health_m()
        self._refresh_shard_gauge(shard)
        if status is ShardStatus.UNASSIGNED:
            m["replica_status_code"].remove(dataset=self.dataset,
                                            shard=shard, node=node)
        else:
            m["replica_status_code"].set(_STATUS_CODE[status],
                                         dataset=self.dataset, shard=shard,
                                         node=node)
        if prev is not status:
            m["transitions"].inc(dataset=self.dataset, status=status.value)
            from filodb_tpu.utils.devicewatch import FLIGHT
            FLIGHT.record("shard.replica", dataset=self.dataset, shard=shard,
                          node=node, status=status.value, prev=prev.value,
                          progress=progress)

    def coord_for_shard(self, shard: int) -> Optional[str]:
        return self._states[shard].node

    _EMPTY_STATE = ShardState()

    def replicas(self, shard: int) -> list[ReplicaState]:
        """The shard's replica group (live view; do not mutate).
        Out-of-range reads (a query planned pre-abort racing the
        shard-space truncation) see an empty group, never an error."""
        states = self._states
        return states[shard].replicas if 0 <= shard < len(states) else []

    def replica_nodes(self, shard: int) -> list[str]:
        return [r.node for r in self._states[shard].replicas]

    def live_replicas(self, shard: int) -> list[ReplicaState]:
        """Replicas not in a terminal Down/Error state — the copies the
        assignment strategy counts toward the replication factor."""
        return [r for r in self._states[shard].replicas
                if r.status not in (ShardStatus.DOWN, ShardStatus.ERROR)]

    def status(self, shard: int) -> ShardStatus:
        return self._states[shard].status

    def best_status(self, shard: int) -> ShardStatus:
        return self._states[shard].best_status

    def state(self, shard: int) -> ShardState:
        """The full per-shard state row (status + owner + recovery
        progress + replicas) for health/watermark views.  Out-of-range
        (post-abort race) returns an empty Unassigned row."""
        states = self._states
        return states[shard] if 0 <= shard < len(states) \
            else self._EMPTY_STATE

    def active_shards(self, shards: Optional[Sequence[int]] = None) -> list[int]:
        """Shards with at least one queryable replica.  A caller's
        range may briefly exceed the shard space when a split abort
        truncates it mid-query — those ids are simply not active."""
        states = self._states
        rng = range(self.num_shards) if shards is None else shards
        return [s for s in rng
                if 0 <= s < len(states) and states[s].best_status.queryable]

    def all_nodes(self) -> set:
        return {r.node for st in self._states for r in st.replicas}

    def shards_for_node(self, node: str) -> list[int]:
        """Shards where ``node`` holds a LIVE (non-Down/Error) replica
        — the same liveness rule as ``live_replicas``, so the
        assignment strategy's ``have`` and ``need`` sides can never
        disagree about one copy."""
        dead = (ShardStatus.DOWN, ShardStatus.ERROR)
        return [i for i, st in enumerate(self._states)
                if any(r.node == node and r.status not in dead
                       for r in st.replicas)]

    def runnable_shards_for_node(self, node: str) -> list[int]:
        """Shards this node should actually be ingesting: its replica
        exists and is not held in an operator STOPPED / leader DOWN
        state (the one place this exclusion policy lives — resync and
        self-heal both consult it)."""
        out = []
        for i, st in enumerate(self._states):
            rep = st.replica(node)
            if rep is not None and rep.status not in (ShardStatus.STOPPED,
                                                      ShardStatus.DOWN):
                out.append(i)
        return out

    @property
    def num_assigned(self) -> int:
        return sum(1 for st in self._states
                   if st.status != ShardStatus.UNASSIGNED)
