"""Query admission and scheduling: submit-time priority + bounded pool.

Capability match for the reference's QueryActor machinery (reference:
coordinator/src/main/scala/filodb.coordinator/QueryActor.scala:28-40 —
a priority mailbox ordering queries by ``submitTime`` so the oldest
query runs first; :112-131 — queries execute on a dedicated,
instrumented query scheduler, never on the ingest or network threads).

Here that is a :class:`QueryScheduler` per dataset: a bounded priority
queue (admission control — a full queue rejects instead of buffering
unboundedly) feeding a fixed pool of query worker threads.  Queries
whose queue wait already exceeded their timeout are failed without
executing (the reference relinquishes them the same way), so a backlog
drains fast instead of doing dead work.
"""

from __future__ import annotations

import heapq
import itertools
import threading
import time
from concurrent.futures import Future
from concurrent.futures import TimeoutError as _FutureTimeout
from typing import Callable, Optional

from filodb_tpu.query.model import QueryError
from filodb_tpu.utils.observability import TRACER


class QueryRejected(QueryError):
    """Admission control rejection (queue full / scheduler down)."""


class QueryScheduler:
    """Bounded priority-queue executor for one dataset's queries."""

    def __init__(self, num_workers: int = 4, max_queued: int = 256,
                 name: str = "query", registry=None):
        if num_workers <= 0 or max_queued <= 0:
            raise ValueError("num_workers and max_queued must be positive")
        self.name = name
        self.max_queued = max_queued
        self._heap: list = []
        self._counter = itertools.count()  # FIFO tiebreak for equal times
        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        self._shutdown = False
        self._workers = [threading.Thread(target=self._run,
                                          name=f"{name}-worker-{i}",
                                          daemon=True)
                         for i in range(num_workers)]
        for w in self._workers:
            w.start()
        reg = registry
        if reg is None:
            from filodb_tpu.utils.observability import REGISTRY as reg
        # saturation visibility (ISSUE 2 satellite): queue depth is a
        # live gauge backed by queue_depth(), rejections (full/shutdown)
        # count per reason — both visible on /metrics before overload
        # becomes timeouts
        self._m_depth = reg.gauge("filodb_query_queue_depth")
        self._m_done = reg.counter("filodb_queries_executed_total")
        self._m_rejected = reg.counter("filodb_queries_rejected_total")
        self._m_timed_out = reg.counter("filodb_queries_queue_timeout_total")
        self._m_wait = reg.histogram("filodb_query_queue_wait_seconds")
        self._m_run = reg.histogram("filodb_query_run_seconds")
        # deadline-expired-in-queue drops (ISSUE 5 satellite): dead work
        # is discarded at dequeue, never executed
        self._m_expired = reg.counter("filodb_query_sched_expired_total")
        self._m_depth.set_fn(self.queue_depth, scheduler=name)

    # ------------------------------------------------------------- submit

    def submit(self, fn: Callable, submit_time_ms: Optional[int] = None,
               timeout_ms: int = 30_000,
               deadline_ms: Optional[int] = None) -> Future:
        """Enqueue a query; earliest ``submit_time_ms`` runs first
        (reference: priority mailbox by submitTime).  Raises
        :class:`QueryRejected` when the queue is full.

        ``deadline_ms`` is the query's ABSOLUTE wall-clock deadline
        (epoch ms, workload/deadline.py): a query that sat in the queue
        past it is dropped at dequeue instead of executed.  It is NOT
        derived from ``submit_time_ms`` — callers use submit time as a
        pure priority key (cross-node it is the ORIGIN's clock), so only
        an explicit deadline is trusted against this node's clock."""
        st = submit_time_ms if submit_time_ms else int(time.time() * 1000)
        fut: Future = Future()
        # trace context captured HERE travels to the worker thread so
        # the queue-wait/run-time split stitches into the query's tree
        token = TRACER.capture()
        entry = (st, next(self._counter), time.monotonic(), timeout_ms,
                 deadline_ms, token, fn, fut)
        with self._lock:
            if self._shutdown:
                self._m_rejected.inc(scheduler=self.name, reason="shutdown")
                raise QueryRejected("", "query scheduler is shut down")
            if len(self._heap) >= self.max_queued:
                self._m_rejected.inc(scheduler=self.name, reason="full")
                raise QueryRejected(
                    "", f"query queue full ({self.max_queued})")
            heapq.heappush(self._heap, entry)
            self._work.notify()
        return fut

    def execute(self, fn: Callable, submit_time_ms: Optional[int] = None,
                timeout_ms: int = 30_000,
                deadline_ms: Optional[int] = None):
        """Submit and wait — the synchronous API the HTTP layer uses.
        The timeout covers queue wait + execution."""
        fut = self.submit(fn, submit_time_ms, timeout_ms, deadline_ms)
        try:
            return fut.result(timeout=timeout_ms / 1000.0)
        except _FutureTimeout:
            # pre-3.11 concurrent.futures.TimeoutError is NOT the
            # builtin TimeoutError; catching the builtin missed it and
            # leaked the raw future timeout to the HTTP layer
            fut.cancel()
            raise QueryError("", f"query timed out after {timeout_ms}ms")

    def queue_depth(self) -> int:
        with self._lock:
            return len(self._heap)

    # ------------------------------------------------------------- workers

    def _run(self) -> None:
        while True:
            with self._work:
                while not self._heap and not self._shutdown:
                    self._work.wait()
                if self._shutdown and not self._heap:
                    return
                (_, _, enq_mono, timeout_ms, deadline_ms, token, fn,
                 fut) = heapq.heappop(self._heap)
            waited = time.monotonic() - enq_mono
            self._m_wait.observe(waited)
            if token[0] is not None:
                # synthetic span: the wait happened in the queue, not on
                # any thread — report it parented on the submitter's span
                TRACER.record("scheduler.queue_wait", waited,
                              trace_id=token[0], parent_id=token[1],
                              stage=True, scheduler=self.name)
            if deadline_ms and time.time() * 1000.0 > deadline_ms:
                # ISSUE 5 satellite: the submit-time deadline expired
                # while queued — the caller (local client or upstream
                # coordinator hop) stopped waiting; executing would be
                # pure dead work.  Dropped with QueryRejected, counted.
                self._m_expired.inc(scheduler=self.name)
                if not fut.cancelled():
                    try:
                        fut.set_exception(QueryRejected(
                            "", f"query deadline expired after "
                                f"{int(waited * 1000)}ms in queue; "
                                f"dropped without executing"))
                    except Exception:  # lost the race to a cancel
                        pass
                continue
            if waited * 1000.0 > timeout_ms:
                # dead work: the client already timed out (reference
                # QueryActor discards overdue queries).  The future may
                # already be CANCELLED (execute()'s timeout cancels it) —
                # set_exception would raise InvalidStateError and kill
                # this worker thread permanently.
                self._m_timed_out.inc(scheduler=self.name)
                if not fut.cancelled():
                    try:
                        fut.set_exception(QueryError(
                            "", f"query spent {int(waited * 1000)}ms in "
                                f"queue, exceeding its {timeout_ms}ms "
                                f"timeout"))
                    except Exception:  # lost the race to a cancel
                        pass
                continue
            if not fut.set_running_or_notify_cancel():
                continue  # cancelled while queued
            t_run = time.monotonic()
            try:
                with TRACER.attach(token), \
                        TRACER.stage("scheduler.run", leaf=False,
                                     cpu=True, scheduler=self.name):
                    out = fn()
                fut.set_result(out)
            except BaseException as e:  # noqa: BLE001 — surface via future
                fut.set_exception(e)
            finally:
                self._m_run.observe(time.monotonic() - t_run)
                self._m_done.inc(scheduler=self.name)

    def shutdown(self, wait: bool = True) -> None:
        # deregister the depth callback: the global gauge must not keep
        # this scheduler (heap, queued closures) alive or keep exporting
        # a row for a dead instance
        self._m_depth.remove(scheduler=self.name)
        with self._lock:
            self._shutdown = True
            # fail whatever is still queued
            pending = self._heap
            self._heap = []
            self._work.notify_all()
        for *_, fut in pending:
            if not fut.cancelled():
                try:
                    fut.set_exception(
                        QueryRejected("", "scheduler shut down"))
                except Exception:  # cancelled concurrently
                    pass
        if wait:
            for w in self._workers:
                w.join(timeout=5)
