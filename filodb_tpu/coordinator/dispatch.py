"""Cross-node plan dispatch over HTTP.

Capability match for the reference's ActorPlanDispatcher (reference:
exec/PlanDispatcher.scala:29-46 — Akka ask of a Kryo-serialized ExecPlan
to the shard's owning node; remote QueryActor executes and replies with
a QueryResult; SURVEY.md §3.1 'PROCESS BOUNDARY').  Here the transport
is HTTP POST /execplan with the JSON wire format
(filodb_tpu/query/wire.py); the receiving node executes against its own
memstore and returns the serialized result.
"""

from __future__ import annotations

import collections
import concurrent.futures
import json
import threading
import time
import urllib.request
from typing import Callable, Optional

from filodb_tpu.query.exec import ExecContext, PlanDispatcher
from filodb_tpu.query.model import QueryError, QueryResult, ShardUnavailable
from filodb_tpu.query.wire import (deserialize_plan, deserialize_result,
                                   serialize_plan, serialize_result)
from filodb_tpu.utils.observability import TRACER
from filodb_tpu.workload import deadline as dl

TRACE_HEADER = "X-FiloDB-Trace-Id"
PARENT_SPAN_HEADER = "X-FiloDB-Parent-Span"

_WM = None


def _wm() -> dict:
    """The filodb_dispatch_* metric objects, resolved once per process
    (no registry-lock lookups on the dispatch hot path)."""
    global _WM
    if _WM is None:
        from filodb_tpu.utils.observability import workload_metrics
        _WM = workload_metrics()
    return _WM


_HEDGE_POOL: Optional[concurrent.futures.ThreadPoolExecutor] = None
_HEDGE_POOL_LOCK = threading.Lock()


def _hedge_pool() -> concurrent.futures.ThreadPoolExecutor:
    global _HEDGE_POOL
    with _HEDGE_POOL_LOCK:
        if _HEDGE_POOL is None:
            _HEDGE_POOL = concurrent.futures.ThreadPoolExecutor(
                max_workers=16, thread_name_prefix="dispatch-hedge")
        return _HEDGE_POOL


class HttpPlanDispatcher(PlanDispatcher):
    """Ships a leaf plan to ``endpoint`` and returns its result.

    Trace context crosses the process boundary twice over: the
    ``trace_id`` rides the execplan wire dict (QueryContext field) AND
    the HTTP headers; the data node returns its spans with the result
    so the coordinator's TraceStore holds ONE stitched tree.

    Workload hardening (ISSUE 5):

    - every attempt's socket timeout is ``min(timeout_s cap, remaining
      deadline budget)`` — never a fixed constant (satellite #1 fix);
    - CONNECTION-level failures (refused/reset/DNS/socket timeout)
      retry up to ``max_retries`` times with exponential backoff, budget
      permitting; an HTTP response is never retried (the server spoke —
      re-asking multiplies load exactly when it must not);
    - with ``hedge=True`` a tail-slow first attempt triggers ONE hedged
      duplicate once it exceeds the dispatcher's observed p99 latency
      (read-only /execplan work is idempotent); first success wins;
    - a dispatch that exhausts retries raises :class:`ShardUnavailable`
      so scatter-gather can degrade to a warned partial result when the
      query allows it."""

    def __init__(self, endpoint: str, timeout_s: float = 60.0,
                 max_retries: int = 2, backoff_s: float = 0.05,
                 hedge: bool = False, hedge_min_s: float = 0.05,
                 hedge_warmup: int = 16, hedge_alternate=None):
        self.endpoint = endpoint.rstrip("/")
        self.timeout_s = timeout_s
        self.max_retries = max(int(max_retries), 0)
        self.backoff_s = float(backoff_s)
        self.hedge = bool(hedge)
        self.hedge_min_s = float(hedge_min_s)
        self.hedge_warmup = max(int(hedge_warmup), 1)
        # replica retarget hook (ISSUE 7): plan -> alternate ENDPOINT for
        # the hedged duplicate, chosen through ReplicaSet.pick (never an
        # ad-hoc list); None = hedge against the same endpoint (rf=1)
        self.hedge_alternate = hedge_alternate
        # recent successful-attempt latencies -> p99 hedge trigger
        self._lat: collections.deque = collections.deque(maxlen=128)
        self._lat_lock = threading.Lock()

    # -------------------------------------------------------------- transport

    def _note_latency(self, seconds: float) -> None:
        with self._lat_lock:
            self._lat.append(seconds)

    def hedge_delay_s(self) -> Optional[float]:
        """p99 of recent attempt latencies (floored at ``hedge_min_s``);
        None until ``hedge_warmup`` samples exist — hedging stays off
        until the trigger is data-driven."""
        with self._lat_lock:
            lat = sorted(self._lat)
        if len(lat) < self.hedge_warmup:
            return None
        return max(lat[min(int(0.99 * len(lat)), len(lat) - 1)],
                   self.hedge_min_s)

    def observed_p50_s(self) -> Optional[float]:
        """Median observed attempt latency — the calibrated-latency leg
        of ReplicaSet.pick's ordering (None until samples exist)."""
        with self._lat_lock:
            lat = sorted(self._lat)
        return lat[len(lat) // 2] if lat else None

    def _send_once(self, body: bytes, headers: dict,
                   deadline_timeout_s: float,
                   endpoint: Optional[str] = None) -> dict:
        req = urllib.request.Request(
            f"{endpoint or self.endpoint}/execplan", data=body,
            method="POST", headers=headers)
        t0 = time.perf_counter()
        with urllib.request.urlopen(req,
                                    timeout=deadline_timeout_s) as resp:
            payload = json.loads(resp.read())
        if endpoint is None:
            self._note_latency(time.perf_counter() - t0)
        return payload

    def _send_hedged(self, plan, make_body, headers: dict,
                     deadline_timeout_s: float) -> dict:
        """First attempt with a p99-armed hedge: when the primary is
        still in flight past the hedge delay, launch ONE duplicate and
        take whichever answers first.  With replicas, the duplicate
        retargets a DIFFERENT replica via the ``hedge_alternate`` hook
        (ReplicaSet.pick) — a wedged node cannot slow both requests.
        The WHOLE hedged attempt — hedge-delay wait included — stays
        inside ``deadline_timeout_s`` so a tail-latency storm cannot
        pin dispatch threads past the deadline they exist to enforce."""
        t_start = time.perf_counter()
        delay = self.hedge_delay_s()
        if delay is None or delay >= deadline_timeout_s:
            return self._send_once(make_body(), headers,
                                   deadline_timeout_s)
        pool = _hedge_pool()
        first = pool.submit(self._send_once, make_body(), headers,
                            deadline_timeout_s)
        try:
            return first.result(timeout=delay)
        except concurrent.futures.TimeoutError:
            pass  # tail-slow: hedge below
        m = _wm()
        m["dispatch_hedged"].inc(endpoint=self.endpoint)
        alt = self.hedge_alternate(plan) \
            if self.hedge_alternate is not None else None
        # retarget telemetry (counter + flight event) is emitted by the
        # hedge_alternate hook itself, where node NAMES are known — the
        # flight event's from/to domain must match _note_handoff's
        if alt is not None and alt.rstrip("/") == self.endpoint:
            alt = None
        # fresh body: the wire budget_ms re-encodes from what is left NOW
        second = pool.submit(self._send_once, make_body(), headers,
                             deadline_timeout_s, alt)
        pending = {first: "first", second: "second"}
        last_err: Optional[BaseException] = None
        while pending:
            budget_left = deadline_timeout_s \
                - (time.perf_counter() - t_start)
            if budget_left <= 0:
                break
            done, _ = concurrent.futures.wait(
                set(pending), timeout=budget_left,
                return_when=concurrent.futures.FIRST_COMPLETED)
            if not done:
                break
            for fut in done:
                tag = pending.pop(fut)
                err = fut.exception()
                if err is None:
                    if tag == "second":
                        m["dispatch_hedge_wins"].inc(
                            endpoint=self.endpoint)
                    return fut.result()
                last_err = err
        raise last_err if last_err is not None else TimeoutError(
            f"hedged dispatch to {self.endpoint} timed out")

    def _request(self, plan, make_body, headers: dict) -> dict:
        """Deadline-capped attempt loop: bounded retry-with-backoff on
        connection errors, optional p99 hedging on the first attempt.
        ``make_body`` re-serializes the plan PER ATTEMPT: the wire's
        relative ``budget_ms`` must reflect what is left NOW, not what
        was left before a failed attempt burned part of it — a stale
        body would let the data node re-anchor budget the coordinator
        already spent."""
        qctx = plan.query_context
        last_err: Optional[BaseException] = None
        for attempt in range(self.max_retries + 1):
            rem = dl.remaining_ms(qctx)
            if rem is not None and rem <= 0:
                if last_err is None:
                    raise dl.DeadlineExceeded(
                        qctx.query_id,
                        f"deadline exhausted before dispatch to "
                        f"{self.endpoint}")
                break  # budget gone mid-retry: report the transport error
            deadline_timeout_s = dl.budget_timeout_s(qctx, self.timeout_s)
            try:
                if attempt == 0 and self.hedge:
                    return self._send_hedged(plan, make_body, headers,
                                             deadline_timeout_s)
                return self._send_once(make_body(), headers,
                                       deadline_timeout_s)
            except urllib.error.HTTPError:
                raise  # the server answered: never retry (load-safe)
            except (urllib.error.URLError, OSError) as e:
                last_err = e
                if attempt < self.max_retries:
                    _wm()["dispatch_retries"].inc(endpoint=self.endpoint)
                    pause = self.backoff_s * (2 ** attempt)
                    rem = dl.remaining_ms(qctx)
                    if rem is not None:
                        pause = min(pause, max(rem / 1000.0, 0.0))
                    if pause > 0:
                        time.sleep(pause)
        _wm()["dispatch_failures"].inc(endpoint=self.endpoint)
        raise ShardUnavailable(
            qctx.query_id,
            f"remote dispatch to {self.endpoint} failed after "
            f"{self.max_retries + 1} attempt(s): {last_err}") from last_err

    # --------------------------------------------------------------- dispatch

    def dispatch(self, plan, ctx: ExecContext) -> QueryResult:
        tid = plan.query_context.trace_id or ctx.query_context.trace_id \
            or TRACER.current_trace_id()
        if tid and not plan.query_context.trace_id:
            plan.query_context.trace_id = tid
        with TRACER.span("dispatch.http", endpoint=self.endpoint,
                         plan=type(plan).__name__,
                         shard=getattr(plan, "shard", "")) as sp:
            # serialized per attempt (see _request): the wire budget_ms
            # is encoded at build time; all builds land in the
            # serialize timing bucket
            ser_box = [0.0]

            def make_body():
                t0 = time.perf_counter()
                body = json.dumps(serialize_plan(plan)).encode()
                ser_box[0] += time.perf_counter() - t0
                return body

            headers = {"Content-Type": "application/json"}
            if tid:
                headers[TRACE_HEADER] = tid
                headers[PARENT_SPAN_HEADER] = sp.span_id
            try:
                payload = self._request(plan, make_body, headers)
            except urllib.error.HTTPError as e:
                try:
                    err = json.loads(e.read()).get("error", "")
                except Exception:
                    err = f"HTTP {e.code}"
                if e.code == 503:
                    # the data node REFUSED the work (overload / budget
                    # too small to finish): transport-class failure, so
                    # allow_partial_results can degrade it
                    su = ShardUnavailable(
                        plan.query_context.query_id,
                        f"remote dispatch to {self.endpoint} refused: "
                        f"{err}")
                    su.reason = "refused"
                    raise su from e
                raise QueryError(plan.query_context.query_id,
                                 f"remote dispatch to {self.endpoint} "
                                 f"failed: {err}") from e
            t1 = time.perf_counter()
            spans = payload.get("spans") if isinstance(payload, dict) else None
            if tid and spans:
                try:
                    from filodb_tpu.utils.forensics import TRACE_STORE
                    TRACE_STORE.ingest_remote(tid, spans)
                except Exception:  # noqa: BLE001 — stitching is best-effort
                    pass
            result = deserialize_result(payload)
            ctx.note_timing("serialize",
                            ser_box[0] + (time.perf_counter() - t1))
            # remote stats fold into the coordinator's accounting exactly
            # like local leaves noting into the shared ctx
            ctx.absorb_stats(result.stats)
            return result

    def __repr__(self) -> str:
        return f"HttpPlanDispatcher({self.endpoint})"


def execplan_handler(memstore) -> Callable[..., dict]:
    """Server side: wire dict -> execute locally -> wire result.
    Transformers run here too (shard-local map/window work stays on the
    data node, as in the reference's remote QueryActor).  The originating
    query's trace context (wire field, or the HTTP headers passed as
    ``trace_parent``) is attached so this node's spans join the tree;
    they are shipped back under the ``spans`` key of the response."""

    def handle(payload: dict,
               trace_parent: Optional[tuple] = None) -> dict:
        plan = deserialize_plan(payload)
        tid = plan.query_context.trace_id or \
            (trace_parent[0] if trace_parent else None)
        # parent ONLY onto the caller's span id: any span still open on
        # this node (e.g. the leaf scheduler's run span enclosing this
        # handler) closes after the response's span list is built, so
        # parenting under it would orphan the whole remote subtree on
        # the coordinator in a real multi-process deployment
        parent_sid = trace_parent[1] if trace_parent else None
        ctx = ExecContext(memstore, plan.query_context)
        if not tid:
            return serialize_result(plan.execute(ctx))
        from filodb_tpu.utils.forensics import TRACE_STORE, span_to_dict
        with TRACER.attach((tid, parent_sid)):
            result = plan.execute(ctx)
        out = serialize_result(result)
        try:
            TRACER.flush()      # this thread's share of the trace
            out["spans"] = [span_to_dict(r)
                            for r in TRACE_STORE.spans_for(tid)]
        except Exception:  # noqa: BLE001 — span return is best-effort
            pass
        return out

    return handle


class ReplicaDispatcher(PlanDispatcher):
    """Failover router for one shard's replica group (ISSUE 7).

    Tries replicas in ReplicaSet.pick order; a TRANSPORT-level failure
    (``ShardUnavailable``: connect refused / retries exhausted / remote
    503 budget refusal) fails over to the next replica while deadline
    budget remains.  Only when the WHOLE group is exhausted does
    ``ShardUnavailable`` escape — the partial-results opt-in then
    degrades it exactly as before.  Every failover lands in the flight
    recorder (``dispatch.failover``) and
    ``filodb_dispatch_failover_total{reason=}``."""

    def __init__(self, dataset: str, shard: int, replica_set,
                 dispatcher_for_node: Callable[[int, str],
                                               Optional[PlanDispatcher]]):
        self.dataset = dataset
        self.shard = shard
        self.replica_set = replica_set
        self.dispatcher_for_node = dispatcher_for_node

    def dispatch(self, plan, ctx: ExecContext) -> QueryResult:
        order = self.replica_set.pick(self.shard)
        if not order:
            raise ShardUnavailable(
                plan.query_context.query_id,
                f"shard {self.shard} of {self.dataset} has no routable "
                f"replica (group down)")
        last_err: Optional[BaseException] = None
        for i, node in enumerate(order):
            if i > 0:
                rem = dl.remaining_ms(plan.query_context)
                if rem is not None and rem <= 0:
                    break  # budget gone: report the transport error
            # already-tried replicas are off limits for the hedge
            # retarget too (hedge_alternate_for reads this): a hedged
            # duplicate aimed at the replica that JUST failed would
            # nullify the hedge during the exact episode it exists for
            plan.replica_exclude = order[:i]
            d = self.dispatcher_for_node(self.shard, node)
            if d is None:
                last_err = ShardUnavailable(
                    plan.query_context.query_id,
                    f"shard {self.shard} replica on node {node!r} has no "
                    f"endpoint configured — refusing to serve it from "
                    f"the local store")
                last_err.reason = "no_endpoint"
                if i + 1 < len(order):
                    self._note_handoff(plan, node, order[i + 1],
                                       "no_endpoint", str(last_err))
                continue
            try:
                return d.dispatch(plan, ctx)
            except ShardUnavailable as e:
                last_err = e
                if i + 1 < len(order):
                    # the raise site tagged the failure class — never
                    # substring-match the message (urllib's "[Errno
                    # 111] Connection refused" reads as a work refusal)
                    self._note_handoff(plan, node, order[i + 1], e.reason,
                                       str(e))
        raise last_err if last_err is not None else ShardUnavailable(
            plan.query_context.query_id,
            f"shard {self.shard} of {self.dataset}: deadline exhausted "
            f"before any replica answered")

    def _note_handoff(self, plan, from_node: str, to_node: str,
                      reason: str, error: str) -> None:
        """Telemetry only — both nodes were already selected by pick();
        named to stay clear of the routing lint's site hints."""
        _wm()["dispatch_failover"].inc(reason=reason)
        from filodb_tpu.utils.devicewatch import FLIGHT
        FLIGHT.record("dispatch.failover", dataset=self.dataset,
                      shard=self.shard, from_node=from_node,
                      to_node=to_node, reason=reason,
                      trace_id=plan.query_context.trace_id or "",
                      error=error[:200])

    def __repr__(self) -> str:
        return f"ReplicaDispatcher({self.dataset}/{self.shard})"


def dispatcher_factory(mapper, endpoints: dict[str, str],
                       local_node: Optional[str] = None,
                       dispatch_config: Optional[dict] = None
                       ) -> Callable[[int], PlanDispatcher]:
    """shard -> dispatcher, from the ShardMapper's replica groups and a
    node -> endpoint map (the plug for
    SingleClusterPlanner.dispatcher_for_shard).  Single-copy shards keep
    the legacy shapes (IN_PROCESS / per-endpoint HttpPlanDispatcher);
    replicated shards route through a :class:`ReplicaDispatcher` whose
    candidate order — primary, failover, hedge retarget — always comes
    from ``ReplicaSet.pick``.  ``dispatch_config`` (the standalone
    ``workload.dispatch`` block) tunes the timeout cap / retries /
    hedging of the HTTP dispatchers it builds."""
    from filodb_tpu.coordinator.replicas import ReplicaSet
    from filodb_tpu.query.exec import IN_PROCESS

    cfg = dispatch_config or {}
    kwargs = dict(
        timeout_s=float(cfg.get("timeout-cap-s", 60.0)),
        max_retries=int(cfg.get("retries", 2)),
        backoff_s=float(cfg.get("backoff-s", 0.05)),
        hedge=bool(cfg.get("hedge", False)),
        hedge_min_s=float(cfg.get("hedge-min-s", 0.05)))
    cache: dict[str, HttpPlanDispatcher] = {}

    def latency_fn(node: str) -> Optional[float]:
        d = cache.get(node)
        return d.observed_p50_s() if d is not None else None

    replica_set = ReplicaSet(
        mapper, local_node=local_node, latency_fn=latency_fn,
        lag_tolerance_rows=int(cfg.get("lag-tolerance-rows", 256)))

    def hedge_alternate_for(plan, this_node: str) -> Optional[str]:
        """Endpoint for the hedged duplicate: the healthiest replica
        OTHER than the one already in flight AND the ones the failover
        loop already burned (plan.replica_exclude) — still via
        ReplicaSet.pick; None keeps same-endpoint hedging (rf=1)."""
        shard = getattr(plan, "shard", None)
        if shard is None:
            return None
        exclude = [this_node] + list(
            getattr(plan, "replica_exclude", ()))
        # walk down ReplicaSet.pick order past unusable candidates —
        # the local replica (serves in-process, not via a hedge POST)
        # and nodes with no configured endpoint — instead of degrading
        # to a same-endpoint hedge while a healthy remote peer idles
        # (mirrors the failover loop's no_endpoint continue)
        while True:
            node = replica_set.alternate(shard, exclude=exclude)
            if node is None or node == this_node:
                return None
            ep = endpoints.get(node)
            if node == local_node or ep is None:
                exclude = exclude + [node]
                continue
            this_ep = endpoints.get(this_node)
            if this_ep is not None \
                    and ep.rstrip("/") == this_ep.rstrip("/"):
                # two node names resolving to ONE endpoint
                # (misconfiguration): a "retarget" there is the same
                # wire target _send_hedged would discard — keep walking
                # for a genuinely different replica instead of emitting
                # ghost retarget telemetry for a hedge that never moves
                exclude = exclude + [node]
                continue
            # telemetry lives HERE, where node names are known: the
            # dispatch.failover event's from/to domain must match
            # ReplicaDispatcher._note_handoff (node names, not URLs)
            _wm()["dispatch_failover"].inc(reason="hedge_retarget")
            from filodb_tpu.utils.devicewatch import FLIGHT
            FLIGHT.record("dispatch.failover",
                          dataset=getattr(plan, "dataset", "") or "",
                          shard=shard, from_node=this_node, to_node=node,
                          reason="hedge_retarget",
                          trace_id=plan.query_context.trace_id or "")
            # normalized like HttpPlanDispatcher.__init__ — a trailing
            # slash would build "//execplan", missing the exact route
            return ep.rstrip("/")

    def http_for(node: str) -> Optional[HttpPlanDispatcher]:
        endpoint = endpoints.get(node)
        if endpoint is None:
            return None
        d = cache.get(node)
        if d is None:
            d = cache[node] = HttpPlanDispatcher(
                endpoint,
                hedge_alternate=lambda plan, _n=node:
                    hedge_alternate_for(plan, _n),
                **kwargs)
        return d

    def for_node(shard: int, node: str) -> Optional[PlanDispatcher]:
        if node == local_node:
            return IN_PROCESS
        return http_for(node)

    def for_shard(shard: int) -> PlanDispatcher:
        replicas = mapper.replicas(shard)
        if len(replicas) > 1:
            return ReplicaDispatcher(mapper.dataset, shard, replica_set,
                                     for_node)
        node = mapper.coord_for_shard(shard)
        if node is None or node == local_node:
            return IN_PROCESS
        d = http_for(node)
        if d is None:
            # a remote-owned shard with no known endpoint must FAIL the
            # query (or degrade to a warned partial result when the
            # query opts in), never silently scan an empty local store
            return _UnroutableDispatcher(shard, node)
        return d

    def mesh_feed(shard: int) -> bool:
        """True when THIS node's resident copy feeds the mesh fabric for
        ``shard`` (ISSUE 18): the replica choice routes through
        ``ReplicaSet.pick`` — the local copy serves the fused program
        iff it is the healthiest candidate, so a recovering or lagging
        local replica never silently feeds stale device grids."""
        order = replica_set.pick(shard)
        return bool(order) and order[0] == local_node

    for_shard.mesh_feed = mesh_feed
    return for_shard


class _UnroutableDispatcher(PlanDispatcher):
    def __init__(self, shard: int, node: str):
        self.shard = shard
        self.node = node

    def dispatch(self, plan, ctx) -> QueryResult:
        su = ShardUnavailable(
            plan.query_context.query_id,
            f"shard {self.shard} is owned by node {self.node!r} but no "
            f"endpoint is configured for it — refusing to serve it from "
            f"the local store")
        su.reason = "no_endpoint"
        raise su
