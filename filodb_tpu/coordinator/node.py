"""Per-node coordination: ingestion lifecycle + dataset wiring.

Capability match for the reference's per-node actors (reference:
coordinator/src/main/scala/filodb.coordinator/NodeCoordinatorActor.scala:47
— creates per-dataset ingestion/query handlers; IngestionActor.scala:57 —
resync to assigned shards (:113-167), startIngestion = memStore.setup +
recoverIndex + checkpoint read -> recovery with progress events (:293) ->
normalIngestion (:236), stop/teardown).  Actors become plain objects +
one ingestion thread per shard; shard events flow to the ShardManager's
event hub instead of an Akka event stream.
"""

from __future__ import annotations

import threading
import time
import traceback
from typing import Callable, Optional, Sequence

from filodb_tpu.coordinator.cluster import (IngestionError, IngestionStarted,
                                            IngestionStopped,
                                            RecoveryInProgress, ShardEvent)
from filodb_tpu.core.schemas import Schemas
from filodb_tpu.core.storeconfig import StoreConfig
from filodb_tpu.ingest.stream import IngestionStreamFactory
from filodb_tpu.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.utils.observability import TRACER


class IngestionCoordinator:
    """Drives one dataset's shard ingestion on this node (reference:
    IngestionActor)."""

    def __init__(self, node: str, dataset: str, schemas: Schemas,
                 memstore: TimeSeriesMemStore,
                 stream_factory: IngestionStreamFactory,
                 config: Optional[StoreConfig] = None,
                 event_sink: Optional[Callable[[ShardEvent], None]] = None,
                 recovery_report_interval: int = 10,
                 group_head_fn: Optional[Callable[[int], int]] = None):
        self.node = node
        self.dataset = dataset
        self.schemas = schemas
        self.memstore = memstore
        self.stream_factory = stream_factory
        self.config = config
        self.event_sink = event_sink or (lambda e: None)
        self.recovery_report_interval = recovery_report_interval
        # replica-group promotion gate (ISSUE 7): shard -> the group's
        # gossiped ingest head.  A recovering replica stays RECOVERY
        # until its own offset reaches max(local checkpoint head, group
        # head) — so a rejoining node is not promoted to Active while a
        # caught-up peer is still measurably ahead.  None = rf=1
        # behavior (local checkpoint head only).
        self.group_head_fn = group_head_fn
        self._threads: dict[int, threading.Thread] = {}
        self._stops: dict[int, threading.Event] = {}
        self._streams: dict[int, object] = {}  # live stream per shard for teardown
        self._lock = threading.Lock()

    # ------------------------------------------------------------ lifecycle

    def resync(self, assigned_shards: Sequence[int]) -> None:
        """Reconcile running shards with the assignment (reference:
        IngestionActor.resync :113-167): start missing, stop extras."""
        with self._lock:
            running = set(self._threads)
        target = set(assigned_shards)
        for s in sorted(target - running):
            self.start_ingestion(s)
        for s in sorted(running - target):
            self.stop_ingestion(s)

    def start_ingestion(self, shard: int, blocking: bool = False) -> None:
        """setup -> recover index -> checkpointed recovery -> normal
        ingestion (reference: startIngestion :170, doRecovery :293).

        The memstore SETUP runs synchronously here, before the ingest
        thread spawns: a query dispatched right after assignment must
        find the shard registered (empty, possibly still recovering) —
        never race an async setup into 'shard not set up' failures."""
        stop = threading.Event()
        with self._lock:
            if shard in self._threads:
                return
            # has_shard+setup under the lock: two concurrent starts for the
            # same shard would otherwise both pass the check and the loser
            # raise ValueError out of setup (round-4 ADVICE). The except
            # keeps repeat starts idempotent even against setups from
            # OUTSIDE this ingester (tests / manual admin calls).
            if not self.memstore.has_shard(self.dataset, shard):
                try:
                    self.memstore.setup(self.dataset, self.schemas, shard,
                                        self.config)
                except ValueError:
                    # tolerated ONLY as the already-set-up race (setups
                    # from outside this ingester); a genuine setup
                    # failure must not register a dead ingest thread
                    if not self.memstore.has_shard(self.dataset, shard):
                        raise
            self._stops[shard] = stop
            if blocking:
                self._threads[shard] = threading.current_thread()
            else:
                t = threading.Thread(target=self._run_shard,
                                     args=(shard, stop),
                                     name=f"ingest-{self.dataset}-{shard}",
                                     daemon=True)
                self._threads[shard] = t
        if blocking:
            # adopt the shard's ingest-thread identity for the duration so
            # the single-writer assertions hold in blocking mode too
            cur = threading.current_thread()
            old_name = cur.name
            cur.name = f"ingest-{self.dataset}-{shard}"
            try:
                self._run_shard(shard, stop)
            finally:
                cur.name = old_name
        else:
            t.start()

    def stop_ingestion(self, shard: int) -> None:
        import time as _time
        with self._lock:
            stop = self._stops.get(shard)
            t = self._threads.get(shard)
        if stop is not None:
            stop.set()
        # the stream registers shortly after thread start; wait for it so
        # teardown can wake a consumer blocked on an empty queue (otherwise
        # a zombie consumer would keep draining the shared stream)
        deadline = _time.monotonic() + 2.0
        stream = None
        while _time.monotonic() < deadline:
            with self._lock:
                stream = self._streams.get(shard)
            if stream is not None or t is None or not t.is_alive():
                break
            _time.sleep(0.01)
        if stream is not None:
            stream.teardown()
        if t is not None and t is not threading.current_thread() \
                and t.is_alive():
            t.join(timeout=5.0)
            if t.is_alive():
                # still draining a large backlog: leave it tracked so a
                # restart cannot spawn a second consumer on the same
                # stream; the thread's own finally runs _cleanup on exit
                return
        self._cleanup(shard)

    def _cleanup(self, shard: int) -> None:
        with self._lock:
            self._threads.pop(shard, None)
            self._stops.pop(shard, None)
            self._streams.pop(shard, None)

    def stop_all(self) -> None:
        with self._lock:
            shards = list(self._threads)
        for s in shards:
            self.stop_ingestion(s)

    def running_shards(self) -> list[int]:
        with self._lock:
            return sorted(s for s, t in self._threads.items() if t.is_alive())

    # ------------------------------------------------------------- internals

    def _run_shard(self, shard: int, stop: threading.Event) -> None:
        flush_sched = None
        try:
            # setup already ran synchronously in start_ingestion
            self.memstore.recover_index(self.dataset, shard)

            # checkpointed recovery: replay from the earliest checkpoint;
            # per-group watermarks skip already-persisted records
            resume_from, highest = self.memstore.prepare_recovery(
                self.dataset, shard)
            stream = self.stream_factory.create(self.dataset, shard,
                                                offset=resume_from)
            with self._lock:
                self._streams[shard] = stream
            if stop.is_set():
                # stopped between start and stream registration: ensure a
                # sentinel exists (close is idempotent-until-delivered),
                # then fall through to the loop so it gets consumed —
                # never leave a stale sentinel for the next consumer
                stream.teardown()
            sh = self.memstore.get_shard(self.dataset, shard)
            # single-writer-per-shard tripwire (reference: FiloSchedulers
            # assertThreadName on the ingest scheduler); installed always —
            # the check itself no-ops unless assertions are enabled, and
            # installing unconditionally avoids order dependence on when
            # enable_assertions() is called
            from filodb_tpu.utils.schedulers import ingest_check_for
            sh.ingest_sched_check = ingest_check_for(self.dataset, shard)

            recovering = resume_from is not None
            if recovering:
                self.event_sink(RecoveryInProgress(self.dataset, shard,
                                                   self.node, 0))
            else:
                self.event_sink(IngestionStarted(self.dataset, shard,
                                                 self.node))
            # pipelined time-boundary flushes ride the ingest loop
            # (reference: ingestStream interleaves createFlushTasks,
            # TimeSeriesMemStore.scala:106-129); encode+IO run on the
            # flush executor, never this thread
            from filodb_tpu.memstore.flush import FlushScheduler
            if sh.config.flush_interval_ms > 0:
                flush_sched = FlushScheduler(
                    sh, sh.config.flush_interval_ms,
                    parallelism=sh.config.flush_task_parallelism)
                # expose the live pipeline to the watermark ledger
                # (/admin/shards flush-queue depth/age, ISSUE 6)
                sh.flush_scheduler = flush_sched
            n_since_report = 0
            # the group head only advances on the ~2 s gossip sweeps, so
            # the promotion target is refreshed on the report cadence
            # below — recomputing it per replayed record would put a
            # replica scan + max() in the bulk catch-up hot loop
            target = self._promotion_target(shard, highest) \
                if recovering else 0
            # the loop runs until the stream ends: a finite source drains,
            # a live queue delivers the teardown sentinel.  No early exit —
            # dequeued elements are always ingested (at-least-once) and the
            # sentinel is always consumed (no stale sentinel for the next
            # consumer of a shared stream).
            for offset, container in stream.get():
                t_in = getattr(stream, "last_arrived", None)
                samples = sh.ingest_container(container, offset)
                if t_in is not None:
                    # from the container's arrival at the edge to the
                    # epoch bump that made its rows readable: the wall
                    # is the visibility lag (a wait and the work above)
                    TRACER.record("ingest.visible", time.time() - t_in,
                                  start_s=t_in, stage=True,
                                  samples=samples)
                if flush_sched is not None:
                    flush_sched.note_ingested()
                if recovering:
                    n_since_report += 1
                    report_due = (n_since_report
                                  >= self.recovery_report_interval)
                    if report_due:
                        n_since_report = 0
                        target = self._promotion_target(shard, highest)
                    if offset >= target:
                        recovering = False
                        self.event_sink(IngestionStarted(self.dataset, shard,
                                                         self.node))
                    elif report_due:
                        lo = resume_from or 0
                        span = max(target - lo, 1)
                        pct = min(int(100 * (offset - lo) / span), 99)
                        self.event_sink(RecoveryInProgress(
                            self.dataset, shard, self.node, pct))
            if recovering:
                # drained before reaching the last checkpoint (short replay)
                self.event_sink(IngestionStarted(self.dataset, shard,
                                                 self.node))
            if stop.is_set():
                # stream drained in response to a stop/teardown: the shard
                # really is stopped.  A finite source draining on its own
                # (CSV load) leaves the shard ACTIVE and queryable.
                self.event_sink(IngestionStopped(self.dataset, shard,
                                                 node=self.node))
        except Exception as e:  # noqa: BLE001 — report, don't kill the node
            traceback.print_exc()
            self.event_sink(IngestionError(self.dataset, shard, str(e),
                                           node=self.node))
        finally:
            if flush_sched is not None:
                try:
                    # drain in-flight flush tasks only; buffered rows stay
                    # queryable and flush on the next boundary or via the
                    # explicit flush surface (matches the reference: stop
                    # does not force a flush)
                    flush_sched.close(flush_remaining=False)
                except Exception:  # noqa: BLE001 — never mask the cause
                    traceback.print_exc()
                finally:
                    flush_sched.shard.flush_scheduler = None
            self._cleanup(shard)

    def _promotion_target(self, shard: int, highest: int) -> int:
        """The offset a recovering replica must reach before promotion:
        the local checkpoint head, raised to the replica group's
        gossiped head when one is known (ISSUE 7)."""
        if self.group_head_fn is None:
            return highest
        try:
            return max(highest, int(self.group_head_fn(shard)))
        except Exception:  # noqa: BLE001 — gossip mid-shutdown
            return highest

    def flush_loop(self, shard: int, stop: threading.Event,
                   interval_s: float) -> None:
        """Optional periodic flush driver (reference: time-boundary flush
        scheduling, TimeSeriesShard.scala:804-846)."""
        while not stop.wait(interval_s):
            self.memstore.flush(self.dataset, shard)


class NodeCoordinator:
    """Per-node entry point: one IngestionCoordinator per dataset plus the
    query surface (reference: NodeCoordinatorActor creating
    IngestionActor + QueryActor per dataset)."""

    def __init__(self, node: str, memstore: TimeSeriesMemStore):
        self.node = node
        self.memstore = memstore
        self.ingestion: dict[str, IngestionCoordinator] = {}
        self.planners: dict[str, object] = {}

    def setup_dataset(self, dataset: str, schemas: Schemas,
                      stream_factory: IngestionStreamFactory,
                      config: Optional[StoreConfig] = None,
                      event_sink=None,
                      group_head_fn=None) -> IngestionCoordinator:
        ic = IngestionCoordinator(self.node, dataset, schemas, self.memstore,
                                  stream_factory, config, event_sink,
                                  group_head_fn=group_head_fn)
        self.ingestion[dataset] = ic
        return ic

    def resync(self, dataset: str, assigned_shards: Sequence[int]) -> None:
        self.ingestion[dataset].resync(assigned_shards)

    def shutdown(self) -> None:
        for ic in self.ingestion.values():
            ic.stop_all()
