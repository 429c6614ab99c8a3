"""Query batcher: co-arrival rendezvous at the device-dispatch boundary.

The serving path offers every eligible device dispatch to the shard's
``QueryBatcher`` (``TimeSeriesShard.query_batcher``, attached by the
standalone wiring).  Queries whose fused plans share a batch key —
same resident planes, same ``GridQuery`` signature, same grid shape,
differing only in the traced ``(row0, steps0)`` start — are stacked
and launched as ONE vmapped device program; each member receives its
own slice of the single readback, bit-equal to what its solo launch
would have produced.

Gating is adaptive so a lone query never waits:

* an OPEN group for the key exists  -> join it (deadline permitting);
* the key is HOT (a real group formed recently) or another dispatch
  for the key is in flight right now -> lead a new group and hold the
  co-arrival window;
* otherwise -> pure passthrough: the solo closure runs immediately,
  tracked only so a concurrent twin can detect the overlap and
  bootstrap the first group.

Every member still holds its own admission permit and deadline: a
query whose remaining budget cannot afford the window joins no batch,
and the leader re-checks each member's budget at stack time — expired
or permit-released members are dropped from the stack and fall back
to the ordinary per-query chain (where the deadline tripwires fire
exactly as today).  Any batched-path error trips a process breaker
(PR 22 ladder discipline): the group demotes to per-query launches
and the batcher becomes a passthrough until ``reset_batch_breaker``.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from filodb_tpu.utils.devicewatch import FLIGHT
from filodb_tpu.utils.observability import TRACER, batch_metrics
from filodb_tpu.workload import deadline as wdl

_BATCH_BROKEN = False


def batching_broken() -> bool:
    return _BATCH_BROKEN


def reset_batch_breaker() -> None:
    """Close the batched-path breaker (ops verb / tests)."""
    global _BATCH_BROKEN
    _BATCH_BROKEN = False


def _pad_pow2(n: int, cap: int) -> int:
    """Smallest power of two >= n (capped): bounds the compile count of
    the vmapped programs to log2(max_batch)+1 leading-axis shapes."""
    p = 1
    while p < n and p < cap:
        p *= 2
    return min(p, cap)


class _Group:
    """One forming batch: members stack under the batcher lock; the
    leader launches once the group is full or the window expires."""

    __slots__ = ("key", "members", "open", "full", "done", "results")

    def __init__(self, key):
        self.key = key
        self.members: list = []
        self.open = True
        self.full = threading.Event()
        self.done = threading.Event()
        # list parallel to members (None = fall back solo), or None
        # when the whole group demoted
        self.results = None


class _Member:
    __slots__ = ("row0", "steps0", "qctx")

    def __init__(self, row0, steps0, qctx):
        self.row0, self.steps0, self.qctx = row0, steps0, qctx


class QueryBatcher:
    """Per-dataset rendezvous for vmapped execution of concurrent
    shape-compatible queries (ISSUE 20 tentpole)."""

    def __init__(self, *, enabled: bool = True, window_ms: float = 3.0,
                 max_batch: int = 8, hot_ttl_s: float = 10.0,
                 slack_ms: float = 25.0, dataset: str = "",
                 ledger=None):
        self.enabled = bool(enabled)
        self.window_ms = float(window_ms)
        self.max_batch = int(max_batch)
        self.hot_ttl_s = float(hot_ttl_s)
        # extra deadline budget a joiner must hold beyond the window
        # (covers the stacked launch + readback)
        self.slack_ms = float(slack_ms)
        self.dataset = dataset
        # WorkloadLedger for realized group sizes, or a zero-arg
        # callable resolving to one (the standalone wiring installs the
        # configured ledger AFTER datasets bind)
        self.ledger = ledger
        self._lock = threading.Lock()
        self._groups: dict = {}       # key -> open _Group
        self._inflight: dict = {}     # key -> concurrent solo dispatches
        self._hot: dict = {}          # key -> monotonic expiry
        self._m = batch_metrics()
        self._peak = 0

    # ------------------------------------------------------------ config

    def configure(self, *, enabled=None, window_ms=None, max_batch=None,
                  hot_ttl_s=None) -> None:
        if enabled is not None:
            self.enabled = bool(enabled)
        if window_ms is not None:
            self.window_ms = float(window_ms)
        if max_batch is not None:
            self.max_batch = max(1, int(max_batch))
        if hot_ttl_s is not None:
            self.hot_ttl_s = float(hot_ttl_s)

    def stack_sizes(self) -> list:
        """Every leading-axis size a stacked launch can have (the
        powers of two ``_pad_pow2`` pads to): what the caller compiles
        when a plan shape first stacks, so that no co-arrival later
        waits for a compile."""
        return sorted({_pad_pow2(n, self.max_batch)
                       for n in range(2, self.max_batch + 1)})

    def snapshot(self) -> dict:
        return {"enabled": self.enabled, "window_ms": self.window_ms,
                "max_batch": self.max_batch,
                "hot_ttl_s": self.hot_ttl_s,
                "breaker_open": _BATCH_BROKEN,
                "realized_peak": self._peak}

    # ---------------------------------------------------------- dispatch

    def dispatch(self, key, row0, steps0, qctx, batch_launch, solo):
        """Offer one device dispatch to the batching tier.

        Returns the member's result (its slice of the stacked launch,
        or the solo result when the batcher ran the passthrough), or
        None when the caller must run its own solo fallback — the
        existing per-query chain, bit-identical to a batcher-less
        serve.  ``batch_launch(row0s, steps0s)`` must return the
        stacked readback with the member axis leading."""
        if not self.enabled:
            return None
        if _BATCH_BROKEN:
            self._m["fallbacks"].inc(dataset=self.dataset,
                                     reason="breaker")
            return None
        window_ms = self.window_ms
        if qctx is not None and getattr(qctx, "deadline_ms", 0):
            if wdl.remaining_ms(qctx) < window_ms + self.slack_ms:
                # remaining budget can't afford the co-arrival window:
                # this query joins no batch (ISSUE 20 contract)
                self._m["fallbacks"].inc(dataset=self.dataset,
                                         reason="deadline")
                return None
        now = time.monotonic()
        lead = False
        with self._lock:
            g = self._groups.get(key)
            if g is not None and g.open:
                my = len(g.members)
                g.members.append(_Member(row0, steps0, qctx))
                if len(g.members) >= self.max_batch:
                    g.open = False
                    self._groups.pop(key, None)
                    g.full.set()
            elif (self._hot.get(key, 0.0) > now
                  or self._inflight.get(key, 0) > 0):
                g = _Group(key)
                g.members.append(_Member(row0, steps0, qctx))
                self._groups[key] = g
                my, lead = 0, True
            else:
                # cold, no concurrent twin: pure passthrough — but
                # tracked, so an overlapping arrival bootstraps the
                # first group for this key
                self._inflight[key] = self._inflight.get(key, 0) + 1
                g = None
        if g is None:
            try:
                return solo()
            finally:
                with self._lock:
                    n = self._inflight.get(key, 1) - 1
                    if n > 0:
                        self._inflight[key] = n
                    else:
                        self._inflight.pop(key, None)
        if lead:
            self._lead(g, window_ms, batch_launch)
        else:
            # a member's rendezvous wait holds the leader's window AND
            # its stacked launch: dispatch, device and readback run on
            # the leader's thread and show in the leader's request; a
            # pure wait, so no leaf on the profiler's host plane
            with TRACER.stage("batch.wait", leaf=False,
                              role="member") as sp:
                done = g.done.wait(timeout=window_ms / 1000.0 + 60.0)
                sp.tag(members=len(g.members))
            if not done:
                self._m["fallbacks"].inc(dataset=self.dataset,
                                         reason="timeout")
                return None
        res = g.results[my] if g.results is not None else None
        return res

    # ------------------------------------------------------------ leader

    def _lead(self, g, window_ms, batch_launch) -> None:
        end = time.monotonic() + window_ms / 1000.0
        with TRACER.stage("batch.wait", role="leader") as sp:
            while not g.full.is_set():
                left = end - time.monotonic()
                if left <= 0:
                    break
                g.full.wait(left)
            sp.tag(members=len(g.members))
        with self._lock:
            g.open = False
            if self._groups.get(g.key) is g:
                self._groups.pop(g.key, None)
        try:
            self._launch_group(g, batch_launch)
        except Exception as e:     # demote the whole group
            global _BATCH_BROKEN
            _BATCH_BROKEN = True
            g.results = None
            FLIGHT.record("breaker.trip", breaker="query_batch",
                          error=repr(e)[:200])
            self._m["fallbacks"].inc(len(g.members),
                                     dataset=self.dataset,
                                     reason="error")
            import logging
            logging.getLogger(__name__).exception(
                "batched query launch failed; demoting the group to "
                "per-query launches and opening the batch breaker")
        finally:
            g.done.set()

    def _launch_group(self, g, batch_launch) -> None:
        """Stack the group's live members and launch once.

        Admission/deadline discipline (batch-admission-discipline
        lint): every stacked member must still hold its admission
        permit and have deadline budget left — members whose permit
        was released or whose ``deadline_ms`` budget expired while the
        window was open are dropped from the stack and demote to the
        per-query chain, where the ordinary tripwires raise."""
        members = g.members
        if len(members) < 2:
            # window expired with no co-arrival: no batch win — the
            # lone member (the leader) runs its unchanged solo chain
            g.results = None
            self._m["fallbacks"].inc(dataset=self.dataset,
                                     reason="solo-window")
            return
        live = []
        for i, m in enumerate(members):
            qc = m.qctx
            permit = getattr(qc, "admission_permit", None)
            if permit is not None and getattr(permit, "released", False):
                continue           # admission window closed mid-batch
            if qc is not None and getattr(qc, "deadline_ms", 0) \
                    and wdl.remaining_ms(qc) <= 0:
                continue           # budget died while the window held
            live.append(i)
        dropped = len(members) - len(live)
        if dropped:
            self._m["fallbacks"].inc(dropped, dataset=self.dataset,
                                     reason="member-expired")
        if len(live) < 2:
            g.results = None
            if live:
                self._m["fallbacks"].inc(dataset=self.dataset,
                                         reason="solo-window")
            return
        b = len(live)
        padded = _pad_pow2(b, self.max_batch)
        idx = live + [live[0]] * (padded - b)
        row0s = np.asarray([members[i].row0 for i in idx])
        steps0s = np.asarray([members[i].steps0 for i in idx])
        out = batch_launch(row0s, steps0s)
        results = [None] * len(members)
        for j, i in enumerate(live):
            results[i] = out[j]
        g.results = results
        self._note_realized(g.key, members, live)

    def _note_realized(self, key, members, live) -> None:
        size = len(live)
        self._m["groups"].inc(dataset=self.dataset)
        self._m["members"].inc(size, dataset=self.dataset)
        if size > self._peak:
            self._peak = size
            self._m["peak"].set(size, dataset=self.dataset)
        now = time.monotonic()
        with self._lock:
            self._hot[key] = now + self.hot_ttl_s
            if len(self._hot) > 256:
                self._hot = {k: t for k, t in self._hot.items()
                             if t > now}
        ledger = self.ledger() if callable(self.ledger) else self.ledger
        if ledger is not None:
            seen = set()
            for i in live:
                bk = getattr(members[i].qctx, "batch_key", "")
                if bk and bk not in seen:
                    seen.add(bk)
                    ledger.note_batch(bk, size)
