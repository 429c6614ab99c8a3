"""HTTP API server: Prometheus-compatible query routes + cluster admin.

Capability match for the reference's HTTP layer (reference:
http/src/main/scala/filodb/http/FiloHttpServer.scala:22 combining
PrometheusApiRoute.scala:24-60 — /promql/<ds>/api/v1/query_range|query:
parse -> LogicalPlan2Query ask -> Prom JSON; ClusterApiRoute.scala:14 —
/api/v1/cluster status/startshards/stopshards; HealthRoute.scala:13 —
__health returning shard statuses).  stdlib ThreadingHTTPServer replaces
akka-http; the planner/memstore stand in for the coordinator ask.
"""

from __future__ import annotations

import contextlib
import functools
import json
import math
import queue
import threading
import time
import urllib.parse
from dataclasses import dataclass, field
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

import numpy as np

from filodb_tpu.coordinator.planner import QueryPlanner
from filodb_tpu.http.model import (error_response, parse_duration_ms,
                                   parse_time_ms, stats_payload,
                                   to_prom_matrix, to_prom_vector)
from filodb_tpu.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.promql.parser import (ParseError,
                                      query_range_to_logical_plan,
                                      query_to_logical_plan)
from filodb_tpu.query.exec import ExecContext
from filodb_tpu.query.model import (QueryContext, QueryError,
                                    ShardUnavailable)
from filodb_tpu.utils.observability import (REGISTRY, TRACER,
                                            insights_metrics,
                                            query_metrics,
                                            workload_metrics)
from filodb_tpu.workload import deadline as wdl

# remote-storage body limits (unauthenticated endpoints; snappy copy
# elements amplify ~21x, so both sides are bounded)
_MAX_REMOTE_COMPRESSED = 16 * 1024 * 1024
_MAX_REMOTE_UNCOMPRESSED = 128 * 1024 * 1024

_METRICS = query_metrics()
_WORKLOAD_M = workload_metrics()
_INSIGHTS_M = insights_metrics()
# connections a handler thread took: ``standing`` from the accept
# thread's queue, ``started`` the first of a thread started for it
_HANDOFFS = REGISTRY.counter(
    "filodb_http_handoffs_total",
    "connections handed to a standing handler thread or to one started "
    "for them, by thread")


def _timed(endpoint: str):
    """Route-handler latency decorator: EVERY ``_route`` handler must
    wear one so no endpoint is dark (lint-enforced by
    tests/test_sentinel_lint.py::test_route_handlers_record_latency)."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(self, *a, **kw):
            t0 = time.perf_counter()
            code = "error"
            try:
                out = fn(self, *a, **kw)
                code = str(out[0]) if isinstance(out, tuple) else "200"
                return out
            finally:
                _METRICS["request_seconds"].observe(
                    time.perf_counter() - t0, endpoint=endpoint)
                _METRICS["requests"].inc(endpoint=endpoint, code=code)
        wrapper._timed_endpoint = endpoint
        return wrapper

    return deco


def _parse_downsample(v) -> int:
    """``?downsample=<pixels>`` — target horizontal resolution for the
    M4 query-time decimator (doc/coldstore.md).  Absent/empty -> 0
    (off); anything not a positive integer is a client error (400)."""
    if v is None or str(v).strip() == "":
        return 0
    try:
        px = int(str(v).strip())
    except ValueError:
        raise ValueError(f"downsample must be a positive integer pixel "
                         f"count, got {v!r}") from None
    if px <= 0:
        raise ValueError(f"downsample must be > 0, got {px}")
    if px > 1 << 20:
        # more pixels than any display: almost certainly a unit error,
        # and the bin math degenerates to per-sample bins anyway
        raise ValueError(f"downsample {px} exceeds the 1048576-pixel cap")
    return px


class _Listener(ThreadingHTTPServer):
    """The stdlib server with a listen queue a node can live with, and
    handler threads that stand.

    The stdlib asks for a queue of 5: with more clients than that
    connecting while the accept thread is away (a collection stops it
    half a second; a dashboard's panels refresh together) the kernel
    drops the SYNs over the queue and each such client waits out a
    retransmit, 1 s and then 3, before its request is even read — nine
    simultaneous connections (eight sessions and a writer) lose three
    that way.

    The stdlib starts a thread a connection, and ``Thread.start`` waits
    until the new thread has run, which needs the interpreter, on the
    one thread every request passes.  Here a handler thread, once
    started, stays: it serves its connection, says it is idle and waits
    on ``_handoff`` for the next.  The accept thread hands a connection
    to an idle one; where none is idle it starts one, as the stdlib
    does, so a handler that calls this same server is never left
    waiting for a thread.  The threads are as many as the most
    connections ever in flight at once."""

    request_queue_size = 128

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        # accepted connections for the standing threads, and the idle
        # ones, each once for every wait on ``_handoff`` it is about to
        # make: both C queues, so neither side waits for the other
        self._handoff: queue.SimpleQueue = queue.SimpleQueue()
        self._idle: queue.SimpleQueue = queue.SimpleQueue()
        self._started = 0         # threads started: all stand till close

    def get_request(self):
        """``accept``, with the moment it returned beside the address:
        the handler's ``http.accept`` span begins there."""
        request, address = super().get_request()
        return request, (address, time.perf_counter())

    def process_request(self, request, client_address):
        """The ``http.spawn`` stage: the connection put on the queue of
        an idle standing thread (tag ``thread`` = ``standing``), or, with
        none idle, a thread started for it (``started``), which waits in
        ``Thread.start`` until the new thread has run.  While it lasts
        the accept loop accepts nothing: wall x request rate is the
        listener's busy share in the hand-off.  This thread is every
        request's serial path, so it only reads two clocks and leaves
        the span to the next flush of another thread (``defer``)."""
        wall, t0 = time.time(), time.perf_counter()
        try:
            self._idle.get_nowait()
        except queue.Empty:
            how = "started"
            t = threading.Thread(target=self._stand,
                                 args=((request, client_address),),
                                 name=f"filo-http-{self._started}",
                                 daemon=True)
            self._started += 1
            t.start()
        else:
            how = "standing"
            self._handoff.put((request, client_address))
        TRACER.defer("http.spawn", time.perf_counter() - t0,
                     start_s=wall, stage=True, thread=how)

    def _stand(self, job) -> None:
        """A handler thread's life: the connection it was started for,
        then each one handed to it, until ``server_close``'s ``None``.
        The stdlib's ``process_request_thread`` serves each: the handler,
        ``handle_error``, the socket closed."""
        me, how = threading.current_thread(), "started"
        while job is not None:
            _HANDOFFS.inc(thread=how)
            self.process_request_thread(*job)
            self._idle.put(me)
            job, how = self._handoff.get(), "standing"

    def server_close(self) -> None:
        """Closes the socket, tells every standing thread to stop and
        joins those idle now.  One still serving a request stops after
        it: a client that never sends its request line would otherwise
        hold the close for ever."""
        super().server_close()
        idle = []
        with contextlib.suppress(queue.Empty):
            while True:
                idle.append(self._idle.get_nowait())
        for _ in range(self._started):
            self._handoff.put(None)
        self._started = 0
        for t in idle:
            t.join()

    def finish_request(self, request, client_address):
        address, accepted = client_address
        self.RequestHandlerClass(request, address, self, accepted)


@dataclass
class DatasetBinding:
    """Everything the HTTP layer needs to serve one dataset."""

    dataset: str
    memstore: TimeSeriesMemStore
    planner: QueryPlanner
    metric_column: str = "_metric_"  # DatasetOptions.metric_column
    # remote-write ingest hook: (labels, ts_list, val_list) -> None; when
    # None the /api/v1/write endpoint 400s for this dataset
    write_router: Optional[object] = None
    # query admission/scheduling (query/scheduler.py): when set, queries
    # run on its bounded worker pool instead of the HTTP handler thread
    # (reference: QueryActor's priority mailbox + query scheduler)
    scheduler: Optional[object] = None
    # SEPARATE pool for dispatched leaf ExecPlans: coordinator queries
    # block on remote leaves, so sharing one pool across nodes would
    # deadlock under load (all workers waiting on leaves queued behind
    # them).  Leaf plans never re-dispatch, so this pool cannot cycle.
    leaf_scheduler: Optional[object] = None
    # workload management (ISSUE 5, filodb_tpu/workload): cost-based
    # admission controller in front of the scheduler (None = admit all)
    # and the dataset's active-series cardinality quota (admin views +
    # runtime config; enforcement lives on the shards/gateway)
    admission: Optional[object] = None
    quota: Optional[object] = None
    # query-frontend result cache (query/resultcache.py): the
    # ResultCache instance embedded in this dataset's planner wrapper;
    # None = the dataset serves uncached (admin views + runtime config)
    resultcache: Optional[object] = None
    # fleet batching tier (ISSUE 20, filodb_tpu/batching): the
    # QueryBatcher this dataset's shards rendezvous in; None = every
    # dispatch runs the per-query chain (admin views + runtime config)
    batcher: Optional[object] = None


@dataclass
class FiloHttpServer:
    """Route table + server lifecycle (reference: FiloHttpServer.start)."""

    port: int = 0  # 0 = ephemeral
    host: str = "127.0.0.1"
    node_name: Optional[str] = None  # reported in /__health for bootstrap
    shard_manager: Optional[object] = None  # coordinator.cluster.ShardManager
    # dataset -> list of shards this node is actively ingesting; reported
    # in /__health as ground truth for peer status gossip (StatusPoller)
    running_shards: Optional[object] = None
    # a remote /execplan arriving with less deadline budget than this
    # cannot plausibly finish — refuse it outright (workload/deadline.py)
    min_remote_budget_ms: int = wdl.MIN_REMOTE_BUDGET_MS
    # ingest watermark ledger backing /admin/shards (ISSUE 6); the
    # standalone server installs a configured one (broker end offsets,
    # stall window), bare servers get a lazy default over their bindings
    watermarks: Optional[object] = None
    # replica dual-write receiver (ISSUE 7): (dataset, shard, container)
    # -> offset, backing POST /ingest/<ds>/<shard> for queue-transport
    # replication; None = the route 404s (broker transports do not
    # need it — the shared partition log is the replicated stream)
    ingest_sink: Optional[object] = None
    # the rule engine (ISSUE 9, filodb_tpu/rules): backs /api/v1/rules,
    # /api/v1/alerts, and /admin/rules; None = empty payloads (a node
    # with no rules configured still answers the Prometheus API shape)
    rules: Optional[object] = None
    # the rollup engine (ISSUE 11, filodb_tpu/rollup): backs
    # /admin/rollup; None = the route 404s (no rollup on this node)
    rollup: Optional[object] = None
    # the elastic-resharding controller (ISSUE 13, coordinator/split.py):
    # backs /admin/split/<ds> (trigger / status / abort); None = 404
    split: Optional[object] = None
    # callable returning this node's per-dataset split progress (clone /
    # retire markers) for the /__health gossip the controller gates on
    split_progress: Optional[object] = None
    # fleet workload insights (ISSUE 19, filodb_tpu/insights): the
    # per-fingerprint workload ledger behind /admin/insights.  PER
    # SERVER, not process-wide (the WatermarkLedger lesson: in-process
    # multi-node tests must not share one table); the standalone server
    # installs a configured one, bare servers get a lazy default
    insights: Optional[object] = None
    # tenant SLO tracker (insights/slo.py); None = no objectives
    # configured (queries are not matched, /admin/insights omits SLO)
    slo: Optional[object] = None
    # fleet aggregator (insights/fleet.py) behind /admin/fleet; a
    # peerless default is created lazily so single-node /admin/fleet
    # still serves the merged-local view
    fleet: Optional[object] = None
    datasets: dict = field(default_factory=dict)
    _httpd: Optional[ThreadingHTTPServer] = None
    _thread: Optional[threading.Thread] = None
    _wm_lock: threading.Lock = field(default_factory=threading.Lock)
    _ins_lock: threading.Lock = field(default_factory=threading.Lock)
    # (trace id, root span id) of the query the request on THIS thread
    # ran: http.encode / http.write join that query's trace
    _answered: threading.local = field(default_factory=threading.local)

    def bind_dataset(self, binding: DatasetBinding) -> None:
        self.datasets[binding.dataset] = binding

    # ------------------------------------------------------------- lifecycle

    def start(self) -> int:
        """Start serving; returns the bound port."""
        server = self

        class Handler(BaseHTTPRequestHandler):
            def __init__(self, request, client_address, listener,
                         accepted):
                self.accepted = accepted    # perf_counter after accept
                self.read = None
                super().__init__(request, client_address, listener)

            def log_message(self, fmt, *args):  # silence stdlib logging
                pass

            def handle(self):
                # the hand-off (or the thread start), this thread's wait
                # for the interpreter and setup() lie behind this line;
                # the request line, the headers and the dispatch to do_*
                # ahead of it
                self.handled = time.perf_counter()
                self.read = TRACER.stage("http.read").opened(self.handled)
                try:
                    super().handle()
                finally:
                    if self.read is not None:   # no route ran
                        self.read.closed()

            def front_spans(self) -> tuple:
                """``http.accept`` and ``http.read``, closed now: the
                connection's first request alone has them."""
                read, self.read = self.read, None
                if read is None:
                    return ()
                read.closed()
                accept = TRACER.stage("http.accept", leaf=False) \
                    .opened(self.accepted).closed(self.handled)
                return (accept, read)

            def do_GET(self):
                server._handle(self, "GET")

            def do_POST(self):
                server._handle(self, "POST")

        self._httpd = _Listener((self.host, self.port), Handler)
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        name="filo-http", daemon=True)
        self._thread.start()
        return self.port

    def shutdown(self) -> None:
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()

    # --------------------------------------------------------------- routing

    def _handle(self, req: BaseHTTPRequestHandler, method: str) -> None:
        """One request as the ``http.request`` stage, from entry to
        after the write.  It encloses the others, so it is no leaf.  The
        spans before it (``http.accept``, ``http.read``) end here and
        join the trace of the query it ran, as ``http.encode`` does."""
        front = req.front_spans()
        self._answered.tok = None
        with TRACER.stage("http.request", leaf=False, cpu=True,
                          method=method):
            try:
                self._handle_request(req, method)
            finally:
                TRACER.adopt(self._answered.tok, front)

    def _handle_request(self, req: BaseHTTPRequestHandler,
                        method: str) -> None:
        if req.path.split("?")[0] == "/metrics":
            # plain-text route handled entirely outside the JSON error
            # epilogue; generation errors become a 500, write errors on a
            # dead socket are swallowed (no second send_response)
            try:
                from filodb_tpu.utils.observability import REGISTRY
                code, text = 200, REGISTRY.expose_text().encode()
            except Exception as e:  # noqa: BLE001 — bad reporter/gauge fn
                code, text = 500, f"metrics exposition failed: {e}\n".encode()
            try:
                req.send_response(code)
                req.send_header("Content-Type", "text/plain; version=0.0.4")
                req.send_header("Content-Length", str(len(text)))
                req.end_headers()
                req.wfile.write(text)
            except Exception:  # noqa: BLE001 — socket already unusable
                pass
            return
        if req.path.split("?")[0] == "/execplan" and method == "POST":
            self._handle_execplan(req)
            return
        if req.path.split("?")[0].startswith("/ingest/") and method == "POST":
            self._handle_ingest_push(req)
            return
        bare = req.path.split("?")[0]
        if method == "POST" and (bare.endswith("/api/v1/read")
                                 or bare.endswith("/api/v1/write")):
            self._handle_remote(req, bare)
            return
        retry_after = None
        try:
            parsed = urllib.parse.urlparse(req.path)
            multi = urllib.parse.parse_qs(parsed.query)
            if method == "POST":
                ln = int(req.headers.get("Content-Length") or 0)
                if ln:
                    body = req.rfile.read(ln).decode()
                    ctype = req.headers.get("Content-Type", "")
                    if "json" in ctype:
                        decoded = json.loads(body)
                        if not isinstance(decoded, dict):
                            raise ValueError(
                                "request body must be a JSON object")
                        # JSON numbers arrive as int/float; route handlers
                        # expect query-string semantics (everything str)
                        for k, v in decoded.items():
                            if isinstance(v, list):
                                multi.setdefault(k, []).extend(
                                    x if isinstance(x, str) else str(x)
                                    for x in v)
                            else:
                                multi.setdefault(k, []).append(
                                    v if isinstance(v, str) else str(v))
                    else:
                        for k, v in urllib.parse.parse_qs(body).items():
                            multi.setdefault(k, []).extend(v)
            params = {k: v[0] for k, v in multi.items()}
            code, payload = self._route(parsed.path, params, multi)
        except QueryError as e:
            from filodb_tpu.query.scheduler import QueryRejected
            from filodb_tpu.workload.admission import AdmissionRejected
            if isinstance(e, AdmissionRejected):
                # shed by admission control: 429 + a Retry-After hint
                # derived from the estimated drain time, so well-behaved
                # clients back off instead of hammering
                code, payload = 429, error_response("throttled", str(e))
                retry_after = e.retry_after_s
            elif isinstance(e, QueryRejected):
                # queue-level rejection: overloaded, not a bad request
                code, payload = 503, error_response("unavailable", str(e))
            elif isinstance(e, ShardUnavailable):
                # a shard's node is down/unreachable (and the query did
                # not opt into partial results): service, not client
                code, payload = 503, error_response("unavailable", str(e))
            elif isinstance(e, wdl.DeadlineExceeded):
                # budget ran out mid-execution: an overload/timeout
                # outcome (503), never a malformed request (400)
                code, payload = 503, error_response("timeout", str(e))
            else:
                code, payload = 400, error_response("bad_data", str(e))
        except (ParseError, ValueError, KeyError) as e:
            code, payload = 400, error_response("bad_data", str(e))
        except Exception as e:  # noqa: BLE001
            code, payload = 500, error_response("internal", str(e))
        # the body is built, so these two can be in no answer's stats:
        # they live in the stage table, and in the trace of the query
        # this request ran (under its root span) where it ran one
        tok = self._answered.tok
        with TRACER.attach(tok), TRACER.stage("http.encode"):
            data = json.dumps(payload).encode()
        try:
            with TRACER.attach(tok), \
                    TRACER.stage("http.write", bytes=len(data)):
                req.send_response(code)
                req.send_header("Content-Type", "application/json")
                if retry_after is not None:
                    req.send_header("Retry-After",
                                    str(int(math.ceil(retry_after))))
                if isinstance(payload, dict) and payload.get("warnings"):
                    # partial-data flag as a header too, so load balancers /
                    # caches can act on it without parsing the body
                    req.send_header("X-FiloDB-Partial-Data", "true")
                trace_id = None
                if isinstance(payload, dict) \
                        and isinstance(payload.get("data"), dict) \
                        and isinstance(payload["data"].get("stats"), dict):
                    trace_id = payload["data"]["stats"].get("traceId")
                if trace_id:
                    # lets the client jump straight to /admin/traces/<id>
                    req.send_header("X-FiloDB-Trace-Id", str(trace_id))
                req.send_header("Content-Length", str(len(data)))
                req.end_headers()
                req.wfile.write(data)
        except Exception:  # noqa: BLE001 — client disconnected mid-response
            pass

    def _handle_execplan(self, req: BaseHTTPRequestHandler) -> None:
        """Cross-node dispatch receiver (reference: remote QueryActor
        executing a serialized ExecPlan, QueryActor.scala:220)."""
        t0 = time.perf_counter()
        try:
            from filodb_tpu.coordinator.dispatch import (PARENT_SPAN_HEADER,
                                                         TRACE_HEADER)
            ln = int(req.headers.get("Content-Length") or 0)
            payload = json.loads(req.rfile.read(ln))
            # trace context propagates via headers AND the execplan-wire
            # qctx field; the handler prefers the wire field
            tp = (req.headers.get(TRACE_HEADER),
                  req.headers.get(PARENT_SPAN_HEADER))
            tp = tp if tp[0] else None
            binding = self.datasets.get(payload.get("dataset"))
            qctx = payload.get("qctx") or {}
            # deadline propagation (ISSUE 5): the wire carries the
            # REMAINING budget; work that cannot plausibly finish in
            # what is left is refused here, before any execution — the
            # coordinator treats the refusal as a transport failure so
            # allow_partial_results can degrade it
            budget_ms = qctx.get("budget_ms")
            if binding is None:
                code, out = 404, error_response(
                    "bad_data", f"unknown dataset {payload.get('dataset')}")
            elif budget_ms is not None \
                    and budget_ms < self.min_remote_budget_ms:
                _WORKLOAD_M["deadline_refused"].inc()
                code, out = 503, error_response(
                    "unavailable",
                    f"refusing /execplan work with {budget_ms}ms deadline "
                    f"budget left (node minimum "
                    f"{self.min_remote_budget_ms}ms)")
            else:
                from filodb_tpu.coordinator.dispatch import execplan_handler
                handler = execplan_handler(binding.memstore)
                if binding.leaf_scheduler is not None:
                    # leaf execution queues with the ORIGINAL query's
                    # submit time and deadline (carried in the plan's
                    # query context) so cross-node priority and
                    # overdue-drop hold (reference: the remote
                    # QueryActor's mailbox orders by submitTime).
                    # Attach the caller's trace BEFORE submit so the
                    # scheduler's capture() sees it and this node's
                    # queue-wait/run spans join the stitched tree.
                    wire_tid = qctx.get("trace_id") or None
                    token = (tp[0], tp[1]) if tp else (wire_tid, None)
                    timeout_ms = qctx.get("timeout_ms") or 30_000
                    deadline_ms = None
                    if budget_ms is not None:
                        # re-anchor the budget on THIS node's clock:
                        # both the scheduler's dequeue drop and the
                        # execution tripwire enforce it locally
                        timeout_ms = min(timeout_ms, budget_ms)
                        deadline_ms = int(time.time() * 1000) + budget_ms
                    with TRACER.attach(token):
                        out = binding.leaf_scheduler.execute(
                            lambda: handler(payload, tp),
                            submit_time_ms=qctx.get("submit_time_ms")
                            or None,
                            timeout_ms=timeout_ms,
                            deadline_ms=deadline_ms)
                else:
                    out = handler(payload, tp)
                code = 200
        except QueryError as e:
            from filodb_tpu.query.scheduler import QueryRejected
            if isinstance(e, QueryRejected):
                code, out = 503, error_response("unavailable", str(e))
            else:
                code, out = 400, error_response("bad_data", str(e))
        except Exception as e:  # noqa: BLE001
            code, out = 500, error_response("internal", str(e))
        _METRICS["execplan_seconds"].observe(time.perf_counter() - t0)
        data = json.dumps(out).encode()
        try:
            req.send_response(code)
            req.send_header("Content-Type", "application/json")
            req.send_header("Content-Length", str(len(data)))
            req.end_headers()
            req.wfile.write(data)
        except Exception:  # noqa: BLE001 — client went away
            pass

    def _handle_ingest_push(self, req: BaseHTTPRequestHandler) -> None:
        """Replica dual-write receiver (ISSUE 7): a peer gateway POSTs a
        raw record container for one shard; it lands on this node's
        ingest stream exactly like a locally-published one."""
        t0 = time.perf_counter()
        try:
            parts = [p for p in req.path.split("?")[0].split("/") if p]
            ln = int(req.headers.get("Content-Length") or 0)
            body = req.rfile.read(ln) if ln else b""
            if self.ingest_sink is None or len(parts) != 3:
                code, out = 404, error_response(
                    "bad_data", "container-push ingest not enabled here")
            elif not body:
                code, out = 400, error_response("bad_data",
                                                "empty container")
            else:
                offset = self.ingest_sink(parts[1], int(parts[2]), body)
                code, out = 200, {"status": "success",
                                  "offset": offset}
        except (ValueError, KeyError) as e:
            code, out = 400, error_response("bad_data", str(e))
        except Exception as e:  # noqa: BLE001
            code, out = 500, error_response("internal", str(e))
        _METRICS["request_seconds"].observe(time.perf_counter() - t0,
                                            endpoint="ingest_push")
        _METRICS["requests"].inc(endpoint="ingest_push", code=str(code))
        data = json.dumps(out).encode()
        try:
            req.send_response(code)
            req.send_header("Content-Type", "application/json")
            req.send_header("Content-Length", str(len(data)))
            req.end_headers()
            req.wfile.write(data)
        except Exception:  # noqa: BLE001 — client went away
            pass

    def _handle_remote(self, req: BaseHTTPRequestHandler, path: str) -> None:
        """Prometheus remote-storage endpoints: snappy'd protobuf over
        POST (reference: PrometheusApiRoute.scala:38-60 `/read` +
        remote-storage.proto wire contract).  `/write` additionally
        accepts remote-write as an ingest edge into the bound memstore."""
        from filodb_tpu.utils import snappy

        try:
            parts = [p for p in path.split("/") if p]
            ds = parts[1] if len(parts) >= 2 and parts[0] == "promql" else ""
            binding = self.datasets.get(ds)
            if binding is None:
                code, body, ctype = 404, json.dumps(error_response(
                    "bad_data", f"unknown dataset {ds}")).encode(), \
                    "application/json"
            else:
                ln = int(req.headers.get("Content-Length") or 0)
                if ln > _MAX_REMOTE_COMPRESSED:
                    raise QueryError(
                        "", f"request body {ln} bytes exceeds limit "
                            f"{_MAX_REMOTE_COMPRESSED}")
                raw = snappy.decompress(req.rfile.read(ln),
                                        max_len=_MAX_REMOTE_UNCOMPRESSED)
                if path.endswith("/read"):
                    body = snappy.compress(self._remote_read(binding, raw))
                    code, ctype = 200, "application/x-protobuf"
                else:
                    n = self._remote_write(binding, raw)
                    body, ctype = json.dumps(
                        {"status": "success", "samples": n}).encode(), \
                        "application/json"
                    code = 200
        except (QueryError, ValueError, KeyError) as e:
            code, ctype = 400, "application/json"
            body = json.dumps(error_response("bad_data", str(e))).encode()
        except Exception as e:  # noqa: BLE001
            code, ctype = 500, "application/json"
            body = json.dumps(error_response("internal", str(e))).encode()
        try:
            req.send_response(code)
            req.send_header("Content-Type", ctype)
            if ctype == "application/x-protobuf":
                req.send_header("Content-Encoding", "snappy")
            req.send_header("Content-Length", str(len(body)))
            req.end_headers()
            req.wfile.write(body)
        except Exception:  # noqa: BLE001 — client went away
            pass

    def _remote_read(self, b: DatasetBinding, raw: bytes) -> bytes:
        """Execute each remote query as a RawSeries plan; stream raw
        samples back as prompb TimeSeries."""
        from filodb_tpu.http import remote as pb
        from filodb_tpu.http.model import public_tags
        from filodb_tpu.query.logical import IntervalSelector, RawSeries
        from filodb_tpu.query.model import RawBatch

        queries = pb.decode_read_request(raw)
        per_query: list[list[bytes]] = []
        for q in queries:
            filters = pb.matchers_to_filters(q.matchers, b.metric_column)
            plan = RawSeries(IntervalSelector(q.start_ms, q.end_ms),
                             tuple(filters))
            result, _tid = self._exec(b, plan, query="remote_read")
            series: list[bytes] = []
            for batch in result.batches:
                if not isinstance(batch, RawBatch) or batch.batch is None:
                    continue
                for i, tags in enumerate(batch.keys):
                    n = int(batch.batch.row_counts[i])
                    ts = batch.batch.timestamps[i][:n]
                    vals = batch.batch.values[i][:n]
                    # clamp to the query range (lookback may widen scans)
                    mask = (ts >= q.start_ms) & (ts <= q.end_ms)
                    if not mask.any():
                        continue
                    series.append(pb.encode_time_series(
                        public_tags(tags, b.metric_column),
                        ts[mask], vals[mask]))
            per_query.append(series)
        return pb.encode_read_response(per_query)

    def _remote_write(self, b: DatasetBinding, raw: bytes) -> int:
        """Remote-write edge: decode WriteRequest and ingest into the
        bound memstore's shards via the gateway sharding rules."""
        from filodb_tpu.http import remote as pb

        if b.write_router is None:
            raise QueryError("remote write not enabled for this dataset")
        series = pb.decode_write_request(raw)
        n = 0
        for labels, ts, vals in series:
            b.write_router(labels, ts, vals)
            n += len(ts)
        return n

    def _route(self, path: str, params: dict,
               multi: Optional[dict] = None) -> tuple[int, dict]:
        multi = multi if multi is not None else {k: [v] for k, v in params.items()}
        parts = [p for p in path.split("/") if p]
        if path == "/__health":
            return self._health()
        if len(parts) >= 4 and parts[0] == "promql" and parts[2] == "api":
            ds = parts[1]
            binding = self.datasets.get(ds)
            if binding is None:
                return 404, error_response("bad_data", f"unknown dataset {ds}")
            endpoint = parts[4] if len(parts) > 4 else ""
            if endpoint == "query_range":
                return self._query_range(binding, params)
            if endpoint == "query":
                return self._query_instant(binding, params)
            if endpoint == "labels":
                return self._labels(binding, params)
            if endpoint == "label" and len(parts) >= 7 and parts[6] == "values":
                return self._label_values(binding, parts[5], params, multi)
            if endpoint == "series":
                return self._series(binding, params, multi)
        if len(parts) == 3 and parts[0] == "api" and parts[1] == "v1" \
                and parts[2] == "rules":
            return self._rules_api()
        if len(parts) == 3 and parts[0] == "api" and parts[1] == "v1" \
                and parts[2] == "alerts":
            return self._alerts_api()
        if len(parts) >= 3 and parts[0] == "api" and parts[2] == "cluster":
            return self._cluster(parts[3:], params)
        if len(parts) == 2 and parts[0] == "admin" \
                and parts[1] == "rules":
            return self._admin_rules()
        if len(parts) == 2 and parts[0] == "admin" \
                and parts[1] == "rollup":
            return self._admin_rollup()
        if len(parts) == 3 and parts[0] == "admin" \
                and parts[1] == "chunkmeta":
            return self._chunkmeta(parts[2], params)
        if len(parts) == 2 and parts[0] == "admin" \
                and parts[1] == "integrity":
            return self._integrity()
        if len(parts) == 2 and parts[0] == "admin" \
                and parts[1] == "slowlog":
            return self._slowlog(params)
        if len(parts) == 2 and parts[0] == "admin" \
                and parts[1] == "device":
            return self._device()
        if len(parts) == 2 and parts[0] == "admin" \
                and parts[1] == "kernels":
            return self._kernels()
        if len(parts) == 2 and parts[0] == "admin" \
                and parts[1] == "flightrecorder":
            return self._flightrecorder(params)
        if len(parts) == 2 and parts[0] == "admin" \
                and parts[1] == "config":
            return self._config(params)
        if len(parts) == 2 and parts[0] == "admin" \
                and parts[1] == "workload":
            return self._workload()
        if len(parts) == 2 and parts[0] == "admin" \
                and parts[1] == "resultcache":
            return self._resultcache(params)
        if len(parts) == 2 and parts[0] == "admin" \
                and parts[1] == "cardinality":
            return self._cardinality(params)
        if len(parts) == 2 and parts[0] == "admin" \
                and parts[1] == "shards":
            return self._shards(params)
        if len(parts) == 2 and parts[0] == "admin" \
                and parts[1] == "insights":
            return self._insights(params)
        if len(parts) == 2 and parts[0] == "admin" \
                and parts[1] == "fleet":
            return self._fleet(params)
        if len(parts) >= 2 and parts[0] == "admin" and parts[1] == "split":
            return self._split(parts[2:], params)
        if len(parts) == 3 and parts[0] == "admin" and parts[1] == "traces":
            return self._traces(parts[2])
        if len(parts) == 2 and parts[0] == "debug" \
                and parts[1] == "profilez":
            return self._profilez(params)
        if len(parts) == 2 and parts[0] == "debug" \
                and parts[1] == "device_profilez":
            return self._device_profilez(params)
        return 404, error_response("bad_data", f"unknown route {path}")

    # ------------------------------------------------------- rule engine

    @_timed("rules_api")
    def _rules_api(self) -> tuple[int, dict]:
        """Prometheus ``/api/v1/rules``: every group's rules with their
        rendered exprs, health, and live alert instances (doc/rules.md)."""
        data = self.rules.rules_payload() if self.rules is not None \
            else {"groups": []}
        return 200, {"status": "success", "data": data}

    @_timed("alerts_api")
    def _alerts_api(self) -> tuple[int, dict]:
        """Prometheus ``/api/v1/alerts``: live pending/firing alerts."""
        data = self.rules.alerts_payload() if self.rules is not None \
            else {"alerts": []}
        return 200, {"status": "success", "data": data}

    @_timed("admin_rules")
    def _admin_rules(self) -> tuple[int, dict]:
        """The rule engine's live operational state: per-group eval
        timing/miss counts, per-rule health, incremental-window
        residency, and the notifier queue (doc/rules.md)."""
        if self.rules is None:
            return 404, error_response("bad_data",
                                       "no rule engine on this node")
        return 200, {"status": "success", "data": self.rules.admin_state()}

    @_timed("admin_rollup")
    def _admin_rollup(self) -> tuple[int, dict]:
        """The rollup engine's live state (doc/rollup.md): per-dataset
        tier ladder, per-shard cursor positions + lag vs the flush
        watermark, pass timing, rows written, tier errors."""
        if self.rollup is None:
            return 404, error_response("bad_data",
                                       "no rollup engine on this node")
        return 200, {"status": "success", "data": self.rollup.admin_state()}

    @_timed("split")
    def _split(self, parts: list, p: dict) -> tuple[int, dict]:
        """Elastic resharding surface (ISSUE 13, doc/ha.md):

        - ``GET  /admin/split``            — every split record's status
        - ``GET  /admin/split/<ds>``       — one dataset's split status
        - ``POST /admin/split/<ds>?action=start[&grace-s=]`` — trigger a
          live power-of-two split (N -> 2N)
        - ``POST /admin/split/<ds>?action=abort`` — lossless abort back
          to the parent topology
        """
        if self.split is None:
            return 404, error_response("bad_data",
                                       "no split controller on this node")
        if not parts:
            return 200, {"status": "success",
                         "data": self.split.admin_state()}
        ds = parts[0]
        action = str(p.get("action", "status"))
        try:
            if action == "start":
                state = self.split.trigger(
                    ds, grace_s=float(p.get("grace-s", 30.0)))
            elif action == "abort":
                state = self.split.abort(ds, reason=str(
                    p.get("reason", "operator abort")))
            elif action == "status":
                state = self.split.status(ds)
                if state is None:
                    return 404, error_response(
                        "bad_data", f"no split record for {ds!r}")
            else:
                return 400, error_response("bad_data",
                                           f"unknown action {action!r}")
        except ValueError as e:
            return 409, error_response("conflict", str(e))
        except KeyError:
            return 404, error_response("bad_data", f"unknown dataset {ds!r}")
        return 200, {"status": "success", "data": state}

    # ------------------------------------------------------ query forensics

    @_timed("slowlog")
    def _slowlog(self, p: dict) -> tuple[int, dict]:
        """Recent completed queries over the slow threshold, newest
        first, each with its full stitched span tree (doc/observability.md)."""
        from filodb_tpu.utils.forensics import TRACE_STORE
        limit = max(1, min(int(p.get("limit", 50)), 1000))
        entries = TRACE_STORE.slowlog()[-limit:][::-1]
        return 200, {"status": "success", "data": {
            "threshold_s": TRACE_STORE.slow_threshold_s,
            "entries": entries}}

    @_timed("traces")
    def _traces(self, trace_id: str) -> tuple[int, dict]:
        """One recent trace as a span tree (remote shards' spans are
        stitched in by the dispatch layer)."""
        from filodb_tpu.utils.forensics import TRACE_STORE
        tree = TRACE_STORE.tree(trace_id)
        if not tree:
            return 404, error_response("bad_data",
                                       f"unknown trace {trace_id}")
        return 200, {"status": "success",
                     "data": {"traceId": trace_id, "spans": tree}}

    @_timed("profilez")
    def _profilez(self, p: dict) -> tuple[int, dict]:
        """On-demand sampling profile: blocks this handler thread for
        ``seconds`` (bounded, single-flight) and returns hot frames."""
        from filodb_tpu.utils import forensics
        try:
            data = forensics.profile(seconds=float(p.get("seconds", 2.0)))
        except forensics.ProfilerBusy as e:
            return 503, error_response("unavailable", str(e))
        return 200, {"status": "success", "data": data}

    # ------------------------------------------------- device observability

    @_timed("device")
    def _device(self) -> tuple[int, dict]:
        """Device-resource view (ISSUE 4): the HBM residency ledger tree
        (per-owner/format byte totals, watermarks), per-dataset arena
        budgets (device grid caches + ODP page caches), the per-device
        reconciliation vs ``memory_stats()``, and the JIT compile table
        with recompile-storm state (doc/observability.md)."""
        from filodb_tpu.utils import devicewatch
        data = devicewatch.device_summary()
        arenas: dict = {}
        for ds, b in self.datasets.items():
            rows = []
            for sh in b.memstore.shards(ds):
                for _key, cache in sorted(
                        getattr(sh, "device_caches", {}).items()):
                    rows.append({
                        "shard": sh.shard_num, "arena": "device-grid",
                        "owner": cache.owner, "budget": cache.budget,
                        "bytes_resident": cache.bytes_resident,
                        "blocks": len(cache.blocks),
                        "builds": cache.builds, "hits": cache.hits,
                        "evictions": cache.evictions})
                paged = getattr(sh, "paged", None)
                if paged is not None:
                    rows.append({
                        "shard": sh.shard_num, "arena": "odp-page-cache",
                        "owner": getattr(sh, "_ledger_owner", ""),
                        "budget": paged.max_bytes,
                        "bytes_resident": paged._bytes,
                        "partitions": len(paged)})
            arenas[ds] = rows
        data["arenas"] = arenas
        return 200, {"status": "success", "data": data}

    @_timed("kernels")
    def _kernels(self) -> tuple[int, dict]:
        """The kernel flight deck (ISSUE 15): per-program launches,
        compiles, sampled EWMA device time, achieved GB/s vs the
        device's published HBM peak, and regression-sentry state — the live
        counterpart of doc/kernel.md's static roofline table."""
        from filodb_tpu.utils import devicewatch
        return 200, {"status": "success",
                     "data": devicewatch.kernel_summary()}

    @_timed("device_profilez")
    def _device_profilez(self, p: dict) -> tuple[int, dict]:
        """On-demand ``jax.profiler`` device trace capture: records for
        ``seconds`` (bounded) into a server-side directory and returns
        the path — the hook a training/inference stack points
        TensorBoard's profile plugin at.  Shares ONE single-flight
        guard with ``/debug/profilez``: a host stack-sampling run and a
        device trace interleaving would attribute each other's
        overhead."""
        from filodb_tpu.utils import forensics
        try:
            data = forensics.device_profile(
                seconds=float(p.get("seconds", 2.0)))
        except forensics.ProfilerBusy as e:
            return 503, error_response("unavailable", str(e))
        except forensics.DeviceProfilerUnavailable as e:
            return 501, error_response("unavailable", str(e))
        return 200, {"status": "success", "data": data}

    @_timed("flightrecorder")
    def _flightrecorder(self, p: dict) -> tuple[int, dict]:
        """The black box on demand: recent structured events (ingest
        batches, flushes, evictions, compiles, page-ins, breaker trips,
        query start/end), oldest first.  ``limit`` / ``kind`` filter."""
        from filodb_tpu.utils.devicewatch import FLIGHT
        limit = max(1, min(int(p.get("limit", 500)), 10_000))
        events = FLIGHT.events(limit=limit, kind=p.get("kind"))
        return 200, {"status": "success", "data": {
            "capacity": FLIGHT.capacity, "events": events}}

    @_timed("config")
    def _config(self, p: dict) -> tuple[int, dict]:
        """Effective configuration dump + runtime-adjustable
        observability knobs.  POST (or params) with
        ``slow-query-threshold-s`` / ``jit-storm-shapes`` /
        ``jit-storm-window-s`` / ``flight-recorder-size`` applies the
        new value immediately (no restart); the response always shows
        the effective values after any change."""
        import dataclasses as _dc
        from filodb_tpu.utils import devicewatch
        from filodb_tpu.utils.forensics import TRACE_STORE
        if "slow-query-threshold-s" in p:
            thr = float(p["slow-query-threshold-s"])
            if thr <= 0:
                return 400, error_response(
                    "bad_data", "slow-query-threshold-s must be > 0")
            TRACE_STORE.slow_threshold_s = thr
        # trace head-sampling (ISSUE 19): fraction of NORMAL
        # (sub-threshold) traces retained in /admin/traces — raising it
        # during an investigation must not require a restart
        if "trace-sample-rate" in p:
            rate = float(p["trace-sample-rate"])
            if not 0.0 <= rate <= 1.0:
                return 400, error_response(
                    "bad_data", "trace-sample-rate must be in [0, 1]")
            TRACE_STORE.sample_rate = rate
        # workload-insights knobs (ISSUE 19): the ledger is killable
        # and the co-arrival window tunable without a restart
        if "insights-enabled" in p:
            self._ensure_insights().enabled = \
                str(p["insights-enabled"]).lower() in ("true", "1")
        if "insights-co-arrival-window-ms" in p:
            window = float(p["insights-co-arrival-window-ms"])
            if window <= 0:
                return 400, error_response(
                    "bad_data",
                    "insights-co-arrival-window-ms must be > 0")
            self._ensure_insights().co_window_ms = window
        devicewatch.COMPILE_WATCH.configure(
            storm_shapes=p.get("jit-storm-shapes"),
            storm_window_s=p.get("jit-storm-window-s"))
        if "flight-recorder-size" in p:
            devicewatch.FLIGHT.resize(int(p["flight-recorder-size"]))
        # kernel flight deck (ISSUE 15): sampling rate and
        # regression-sentry tuning are runtime-adjustable — raising the
        # sample rate during an incident must not require a restart
        devicewatch.KERNEL_TIMER.configure(
            sample_1_in=p.get("kernel-sample-1-in"),
            regression_factor=p.get("kernel-regression-factor"),
            regression_window_s=p.get("kernel-regression-window-s"),
            baseline_min_samples=p.get("kernel-baseline-min-samples"))
        # workload knobs (ISSUE 5): admission budgets + quota limits are
        # runtime-adjustable across every bound dataset — overload
        # response must not require a restart
        if any(k in p for k in ("admission-max-inflight-cost",
                                "admission-tenant-max-concurrent",
                                "admission-enabled")):
            enabled = None
            if "admission-enabled" in p:
                enabled = str(p["admission-enabled"]).lower() in ("true",
                                                                  "1")
            for b in self.datasets.values():
                if b.admission is not None:
                    b.admission.configure(
                        max_inflight_cost=p.get(
                            "admission-max-inflight-cost"),
                        tenant_max_concurrent=p.get(
                            "admission-tenant-max-concurrent"),
                        enabled=enabled)
        if "quota-default-max-series" in p:
            for b in self.datasets.values():
                if b.quota is not None:
                    b.quota.configure(
                        default_limit=int(p["quota-default-max-series"]))
        if "min-remote-budget-ms" in p:
            self.min_remote_budget_ms = int(p["min-remote-budget-ms"])
        # result-cache knobs (query/resultcache.py): enable/disable and
        # resize at runtime across every bound dataset — a cache gone
        # wrong must be killable without a restart
        if "result-cache-enabled" in p or "result-cache-max-bytes" in p:
            enabled = None
            if "result-cache-enabled" in p:
                enabled = str(p["result-cache-enabled"]).lower() \
                    in ("true", "1")
            max_bytes = p.get("result-cache-max-bytes")
            for b in self.datasets.values():
                if b.resultcache is not None:
                    b.resultcache.configure(
                        enabled=enabled,
                        max_bytes=int(max_bytes)
                        if max_bytes is not None else None)
        # fleet-batching knobs (ISSUE 20, filodb_tpu/batching): the
        # co-arrival window, group-size cap, and the tier itself are
        # runtime-adjustable across every bound dataset — a batcher
        # gone wrong must be killable without a restart
        if any(k in p for k in ("batch-enabled", "batch-window-ms",
                                "batch-max-size", "batch-hot-ttl-s")):
            enabled = None
            if "batch-enabled" in p:
                enabled = str(p["batch-enabled"]).lower() in ("true", "1")
            window_ms = None
            if "batch-window-ms" in p:
                window_ms = float(p["batch-window-ms"])
                if window_ms <= 0:
                    return 400, error_response(
                        "bad_data", "batch-window-ms must be > 0")
            max_batch = None
            if "batch-max-size" in p:
                max_batch = int(p["batch-max-size"])
                if max_batch < 1:
                    return 400, error_response(
                        "bad_data", "batch-max-size must be >= 1")
            for b in self.datasets.values():
                if b.batcher is not None:
                    b.batcher.configure(
                        enabled=enabled, window_ms=window_ms,
                        max_batch=max_batch,
                        hot_ttl_s=p.get("batch-hot-ttl-s"))
        # data-plane knob (ISSUE 6): how long a lagging shard's ingested
        # offset may sit still before an ingest.stall event fires
        if "ingest-stall-window-s" in p:
            window = float(p["ingest-stall-window-s"])
            if window <= 0:
                return 400, error_response(
                    "bad_data", "ingest-stall-window-s must be > 0")
            self._ensure_watermarks().stall_window_s = window
        stores: dict = {}
        for ds, b in self.datasets.items():
            shards = b.memstore.shards(ds)
            if shards:
                stores[ds] = _dc.asdict(shards[0].config)
        workload: dict = {}
        for ds, b in self.datasets.items():
            row: dict = {}
            if b.admission is not None:
                snap = b.admission.snapshot()
                row["admission"] = {k: snap[k] for k in (
                    "enabled", "max_inflight_cost", "priority_shares",
                    "tenant_max_concurrent", "tenant_max_inflight_cost")}
            if b.quota is not None:
                qs = b.quota.snapshot()
                row["quota"] = {k: qs[k] for k in (
                    "tenant_label", "default_limit", "overrides")}
            workload[ds] = row
        rcache: dict = {}
        for ds, b in self.datasets.items():
            if b.resultcache is not None:
                snap = b.resultcache.snapshot()
                rcache[ds] = {k: snap[k] for k in ("enabled", "max_bytes")}
        batching: dict = {}
        for ds, b in self.datasets.items():
            if b.batcher is not None:
                batching[ds] = b.batcher.snapshot()
        return 200, {"status": "success", "data": {
            "datasets": stores,
            "workload": {"min-remote-budget-ms": self.min_remote_budget_ms,
                         "datasets": workload},
            "result-cache": rcache,
            "batching": batching,
            "dataplane": {
                "ingest-stall-window-s":
                    self._ensure_watermarks().stall_window_s,
            },
            "insights": {
                "enabled": self._ensure_insights().enabled,
                "max-entries": self._ensure_insights().max_entries,
                "co-arrival-window-ms":
                    self._ensure_insights().co_window_ms,
                "fingerprints": self._ensure_insights().fingerprints(),
            },
            "observability": {
                "slow-query-threshold-s": TRACE_STORE.slow_threshold_s,
                "trace-sample-rate": TRACE_STORE.sample_rate,
                "jit-storm-shapes":
                    devicewatch.COMPILE_WATCH.storm_shapes,
                "jit-storm-window-s":
                    devicewatch.COMPILE_WATCH.storm_window_s,
                "flight-recorder-size": devicewatch.FLIGHT.capacity,
                "devicewatch-enabled": devicewatch.enabled(),
                "kernel-sample-1-in":
                    devicewatch.KERNEL_TIMER.sample_1_in,
                "kernel-regression-factor":
                    devicewatch.KERNEL_TIMER.regression_factor,
                "kernel-regression-window-s":
                    devicewatch.KERNEL_TIMER.regression_window_s,
                "kernel-baseline-min-samples":
                    devicewatch.KERNEL_TIMER.baseline_min_samples,
            }}}

    @_timed("workload")
    def _workload(self) -> tuple[int, dict]:
        """Operational view of the workload-management subsystem
        (ISSUE 5): per-dataset admission state (inflight cost, tenant
        budgets, calibration), cardinality-quota occupancy, and the
        query schedulers' depth (doc/workload.md)."""
        out: dict = {}
        for ds, b in self.datasets.items():
            row: dict = {}
            if b.admission is not None:
                row["admission"] = b.admission.snapshot()
            if b.quota is not None:
                row["quota"] = b.quota.snapshot()
            if b.scheduler is not None:
                row["queue_depth"] = b.scheduler.queue_depth()
            if b.leaf_scheduler is not None:
                row["leaf_queue_depth"] = b.leaf_scheduler.queue_depth()
            out[ds] = row
        return 200, {"status": "success", "data": {
            "min_remote_budget_ms": self.min_remote_budget_ms,
            "datasets": out}}

    @_timed("resultcache")
    def _resultcache(self, p: dict) -> tuple[int, dict]:
        """The query-frontend result cache's live state
        (doc/query-engine.md): per-dataset entry/byte residency with
        the exact-reconciliation proof, hit/miss/eviction/invalidation
        counters, and the resident instant windows.  ``clear=true``
        flushes every dataset's cache (operator action)."""
        clear = str(p.get("clear", "")).lower() in ("true", "1")
        out: dict = {}
        for ds, b in self.datasets.items():
            if b.resultcache is None:
                continue
            if clear:
                b.resultcache.clear()
            snap = b.resultcache.snapshot()
            accounted, walked = b.resultcache.reconcile()
            snap["reconcile"] = {"accounted_bytes": accounted,
                                 "walked_bytes": walked,
                                 "exact": accounted == walked}
            out[ds] = snap
        if not out:
            return 404, error_response("bad_data",
                                       "no result cache on this node")
        return 200, {"status": "success", "data": {"datasets": out}}

    # ------------------------------------------------- data-plane routes

    @_timed("cardinality")
    def _cardinality(self, p: dict) -> tuple[int, dict]:
        """The cardinality explorer (ISSUE 6): per-shard top-k label
        names x values by active-series count, per-tenant breakdown,
        and churn rates — every number derived from one atomic index
        snapshot per shard, so totals reconcile exactly with a full
        index walk even under concurrent create/evict/purge
        (doc/observability.md)."""
        from filodb_tpu.memstore.cardinality import build_report
        ds = p.get("dataset")
        if ds is None and len(self.datasets) == 1:
            ds = next(iter(self.datasets))
        binding = self.datasets.get(ds)
        if binding is None:
            return 404, error_response("bad_data",
                                       f"unknown dataset {ds}")
        topk = max(1, min(int(p.get("topk", 10)), 100))
        shard_num = int(p["shard"]) if "shard" in p else None
        tenant_label = binding.quota.tenant_label \
            if binding.quota is not None else "_ns_"
        report = build_report(ds, binding.memstore.shards(ds), topk=topk,
                              tenant_label=tenant_label,
                              shard_num=shard_num)
        return 200, {"status": "success", "data": report}

    @_timed("shards")
    def _shards(self, p: dict) -> tuple[int, dict]:
        """The ingest-plane health tree (ISSUE 6): per-shard watermark
        chain (broker_end -> ingested -> flushed -> checkpoint), lag in
        rows/seconds, flush-queue depth/age, mapper status + recovery
        progress, and stall flags.  Sampling here also advances stall
        detection, so polling the endpoint IS monitoring."""
        return 200, {"status": "success",
                     "data": self._ensure_watermarks().sample()}

    def _ensure_watermarks(self):
        """Lazy default ledger over the bound datasets (bare servers in
        tests); the standalone server installs a configured one before
        start().  Locked: two concurrent first requests must not each
        build a ledger and silently discard one's stall state."""
        with self._wm_lock:
            if self.watermarks is None:
                from filodb_tpu.memstore.watermarks import WatermarkLedger
                self.watermarks = WatermarkLedger(node=self.node_name or "")
            # sync datasets bound AFTER the ledger was built — without
            # touching already-configured watches (the standalone ledger
            # carries broker end-offset sources a re-watch would lose)
            wm = self.watermarks
            watched = set(wm.watching())
            for ds, b in self.datasets.items():
                if ds in watched:
                    continue
                mapper = None
                if self.shard_manager is not None:
                    try:
                        mapper = self.shard_manager.mapper(ds)
                    except KeyError:
                        mapper = None
                wm.watch(ds, b.memstore, mapper=mapper)
            return wm

    # -------------------------------------------- fleet workload insights

    def _ensure_insights(self):
        """Lazy default workload ledger (bare servers in tests); the
        standalone server installs a configured one before start().
        Same double-create discipline as :meth:`_ensure_watermarks`."""
        ins = self.insights
        if ins is not None:
            return ins
        with self._ins_lock:
            if self.insights is None:
                from filodb_tpu.insights.ledger import WorkloadLedger
                self.insights = WorkloadLedger(node=self.node_name or "")
            return self.insights

    def _insights_raw(self) -> dict:
        """The raw MERGEABLE bundle behind ``/admin/insights?raw=true``
        — also what FleetAggregator peers fetch.  Every section is
        either exactly mergeable (insights, slo: fixed bucket bounds,
        int counters) or summable/per-node (watermarks, replicas,
        kernels); nothing here derives from the wall clock, so two
        snapshots of a quiesced node are bit-identical (the fleet-merge
        e2e contract)."""
        ins = self._ensure_insights()
        bundle: dict = {"node": self.node_name or "",
                        "insights": ins.snapshot(),
                        "slo": self.slo.snapshot()
                        if self.slo is not None else None}
        try:
            wm = self._ensure_watermarks().sample()
            bundle["watermarks"] = {
                ds: dict(d.get("totals") or {})
                for ds, d in (wm.get("datasets") or {}).items()}
        except Exception:  # noqa: BLE001 — store mid-shutdown
            bundle["watermarks"] = {}
        replicas: dict = {}
        if self.shard_manager is not None:
            for ds in self.shard_manager.datasets():
                try:
                    m = self.shard_manager.mapper(ds)
                except KeyError:
                    continue
                statuses = [m.best_status(s).value
                            for s in range(m.num_shards)]
                replicas[ds] = {
                    "shards": m.num_shards,
                    "active": sum(1 for s in statuses if s == "Active"),
                    "down": sum(1 for s in statuses
                                if s not in ("Active", "Recovery",
                                             "Assigned"))}
        else:
            for ds, b in self.datasets.items():
                n = len(b.memstore.shards(ds))
                replicas[ds] = {"shards": n, "active": n, "down": 0}
        bundle["replicas"] = replicas
        try:
            from filodb_tpu.utils import devicewatch
            ks = devicewatch.kernel_summary()
            rows = ks.get("programs") or []
            bundle["kernels"] = {
                "enabled": bool(ks.get("enabled")),
                "programs": len(rows),
                "launches": sum(int(r.get("launches") or 0)
                                for r in rows),
                "regressed": sum(1 for r in rows if r.get("regressed"))}
        except Exception:  # noqa: BLE001 — devicewatch unavailable
            bundle["kernels"] = {"enabled": False, "programs": 0,
                                 "launches": 0, "regressed": 0}
        return bundle

    @_timed("insights")
    def _insights(self, p: dict) -> tuple[int, dict]:
        """Per-fingerprint workload analytics (ISSUE 19 pillar 1).
        Default: the human view — top-k fingerprints by ``sort``
        (cost|latency|count|qps|errors), per-tenant rollup, batching
        headroom, SLO rows.  ``raw=true``: the mergeable bundle the
        fleet console aggregates."""
        if str(p.get("raw", "")).lower() in ("true", "1", "yes"):
            return 200, {"status": "success", "data": self._insights_raw()}
        from filodb_tpu.insights import ledger as _il
        try:
            top = int(p.get("top", 20))
            if top <= 0:
                raise ValueError
        except (TypeError, ValueError):
            return 400, error_response("bad_data",
                                       "top must be a positive integer")
        sort = str(p.get("sort", "cost"))
        if sort not in ("cost", "latency", "count", "qps", "errors"):
            return 400, error_response(
                "bad_data", f"unknown sort {sort!r} (want cost|latency"
                            f"|count|qps|errors)")
        ins = self._ensure_insights()
        data = _il.view(ins.snapshot(), top=top, sort=sort)
        data["node"] = self.node_name or ""
        data["enabled"] = ins.enabled
        if self.slo is not None:
            data["slo"] = self.slo.rows()
        return 200, {"status": "success", "data": data}

    @_timed("fleet")
    def _fleet(self, p: dict) -> tuple[int, dict]:
        """The one-pane cluster console (ISSUE 19 pillar 3): the merged
        fleet tree from this node's aggregator.  ``refresh=true`` forces
        a synchronous peer poll first.  A node without peers serves the
        merged-local view through the same shape."""
        if self.fleet is None:
            # peerless aggregator: single-node deployments and bare
            # test servers still get the /admin/fleet tree shape
            from filodb_tpu.insights.fleet import FleetAggregator
            with self._ins_lock:
                if self.fleet is None:
                    self.fleet = FleetAggregator(
                        self.node_name or "", {}, self._insights_raw)
        refresh = str(p.get("refresh", "")).lower() in ("true", "1", "yes")
        return 200, {"status": "success",
                     "data": self.fleet.tree(refresh=refresh)}

    @_timed("integrity")
    def _integrity(self) -> tuple[int, dict]:
        """Operational view of the data-integrity subsystem: global
        counters, the quarantine registry, and per-shard corruption /
        invariant state (doc/integrity.md)."""
        from filodb_tpu.integrity import QUARANTINE
        from filodb_tpu.utils.observability import integrity_metrics
        m = integrity_metrics()
        shards: dict = {}
        for ds, b in self.datasets.items():
            rows = []
            for sh in b.memstore.shards(ds):
                st = sh.stats
                paged = getattr(sh, "paged", None)
                row = {"shard": sh.shard_num,
                       "chunks_corrupt": st.chunks_corrupt,
                       "chunks_quarantined": st.chunks_quarantined,
                       "page_decode_corrupt":
                           getattr(st, "page_decode_corrupt", 0),
                       "integrity_failed": sh.integrity_failed}
                if paged is not None:
                    try:
                        paged.check_invariants()
                        row["paged_cache_invariants"] = "ok"
                    except Exception as e:  # noqa: BLE001 — report, not raise
                        row["paged_cache_invariants"] = str(e)
                rows.append(row)
            shards[ds] = rows
        return 200, {"status": "success", "data": {
            "counters": {name: metric.total()
                         for name, metric in m.items()},
            "quarantine": QUARANTINE.summary(),
            "quarantined": QUARANTINE.items(),
            "shards": shards}}

    @_timed("chunkmeta")
    def _chunkmeta(self, ds: str, p: dict) -> tuple[int, dict]:
        """Chunk-level metadata for matching series (reference: the
        RawChunkMeta logical plan + CLI decodeChunkInfo debugging)."""
        from filodb_tpu.promql.parser import parse_selector
        from filodb_tpu.query.logical import RawChunkMeta

        binding = self.datasets.get(ds)
        if binding is None:
            return 404, error_response("bad_data", f"unknown dataset {ds}")
        if "match[]" not in p:
            return 400, error_response("bad_data", "match[] required")
        filters = parse_selector(p["match[]"])
        start = parse_time_ms(p.get("start", "0"))
        end = parse_time_ms(p.get("end", str(2**62 // 1000)))
        plan = RawChunkMeta(filters=tuple(filters), start_ms=start,
                            end_ms=end)
        result, _tid = self._exec(binding, plan, query=p["match[]"])
        data = [row for b in result.batches for row in b]
        return 200, {"status": "success", "data": data}

    # ---------------------------------------------------------- query routes

    @staticmethod
    def _stats_wanted(p: dict) -> bool:
        return str(p.get("stats", "")).lower() in ("true", "1", "all")

    def _finish_query(self, result, trace_id: str, p: dict, build) -> dict:
        """Build the response body under the ``serialize`` stage (the
        span is the bucket's source, in the query's own trace) and
        attach data.stats (Prometheus stats=true shape)."""
        with TRACER.attach(self._answered.tok), \
                TRACER.stage("serialize") as ser:
            body = build()
        if self._stats_wanted(p):
            result.stats.add_timing("serialize", ser.duration_s)
            body["data"]["stats"] = stats_payload(result.stats, trace_id)
        return body

    @_timed("query_range")
    def _query_range(self, b: DatasetBinding, p: dict) -> tuple[int, dict]:
        query = p["query"]
        start = parse_time_ms(p["start"])
        end = parse_time_ms(p["end"])
        step = parse_duration_ms(p.get("step", "15s"))
        plan = query_range_to_logical_plan(query, start, step, end)
        result, trace_id = self._exec(b, plan, query=query, params=p)
        return 200, self._finish_query(
            result, trace_id, p,
            lambda: to_prom_matrix(result, b.metric_column))

    @_timed("query")
    def _query_instant(self, b: DatasetBinding, p: dict) -> tuple[int, dict]:
        import time as _time
        query = p["query"]
        # Prometheus default: evaluate at current server time when omitted
        time_ms = parse_time_ms(p["time"]) if "time" in p \
            else int(_time.time() * 1000)
        plan = query_to_logical_plan(query, time_ms)
        result, trace_id = self._exec(b, plan, query=query, params=p)
        return 200, self._finish_query(
            result, trace_id, p,
            lambda: to_prom_vector(result, time_ms, b.metric_column))

    @staticmethod
    def _query_context(p: dict) -> QueryContext:
        """Per-query context from request params: timeout (caps the
        end-to-end deadline budget), tenant/priority admission identity,
        and the partial-results opt-in.  The absolute deadline is minted
        HERE — every downstream wait, dispatch, and remote hop only ever
        decrements it (workload/deadline.py)."""
        import time as _time
        timeout_ms = parse_duration_ms(p["timeout"]) if "timeout" in p \
            else 30_000
        qctx = QueryContext(
            submit_time_ms=int(_time.time() * 1000),
            trace_id=TRACER.new_trace_id(),
            timeout_ms=timeout_ms,
            tenant=str(p.get("tenant", "")),
            priority=str(p.get("priority", "default")),
            allow_partial_results=str(
                p.get("allow_partial_results", "")).lower()
            in ("true", "1"),
            # tiered-resolution serving (doc/rollup.md): let clients
            # pin raw / a specific tier; default lets the router pick
            resolution_pref=str(p.get("resolution", "")),
            # ?downsample=<pixels>: visualization-grade M4 decimation
            # applied query-time at the exec root (doc/coldstore.md)
            downsample_pixels=_parse_downsample(p.get("downsample")))
        return wdl.mint(qctx)

    def _admit(self, b: DatasetBinding, ep, qctx: QueryContext):
        """The admission front door: every query handler reaches
        execution through ``_exec`` -> ``_admit`` (lint-enforced by
        tests/test_sentinel_lint.py::test_query_handlers_route_through_
        admission).  Estimates the plan's cost from the part-key index
        and asks the controller for a permit; sheds with
        AdmissionRejected (HTTP 429 + Retry-After) instead of queueing
        work that would rot."""
        if b.admission is None or not b.admission.enabled:
            # the runtime kill switch (admission-enabled=false) must
            # remove the COST MODEL from the hot path too — disabling
            # admission during an incident is exactly when a
            # misbehaving estimator must stop being consulted
            return contextlib.nullcontext()
        cost = b.admission.cost_model.estimate(ep, b.memstore)
        return b.admission.admit(qctx, cost)

    def _exec(self, b: DatasetBinding, plan, query: str = "",
              params: Optional[dict] = None):
        """Plan + admit + execute with a fresh per-query trace: mints
        the trace_id every downstream span (and remote dispatch) joins,
        splits plan/queue wall-time into the stats buckets, and feeds
        the slow-query log on completion.  Returns (result, trace_id).

        Planning happens on the ENTRY thread so the admission
        controller can price the materialized plan before any queueing;
        only execution rides the scheduler pool."""
        import time as _time
        from filodb_tpu.utils.forensics import TRACE_STORE
        qctx = self._query_context(params or {})
        t0 = _time.perf_counter()

        # workload insights (ISSUE 19): key the query ONCE on the entry
        # thread — (fingerprint, batch key) are pure functions of the
        # plan, and the co-arrival window must see arrivals, not
        # completions
        ins = self._ensure_insights()
        ins_keys = None
        if ins.enabled:
            try:
                from filodb_tpu.insights.ledger import plan_keys
                ins_keys = plan_keys(b.dataset, plan, query)
                ins.note_arrival(ins_keys[1])
                # fleet batching (ISSUE 20): carry the batch key on the
                # query context so the batcher's realized group sizes
                # land next to this key's co-arrival headroom estimate
                qctx.batch_key = ins_keys[1]
            except Exception:  # noqa: BLE001 — insights never fail a query
                ins_keys = None

        from filodb_tpu.utils.devicewatch import FLIGHT
        FLIGHT.record("query.start", trace_id=qctx.trace_id,
                      dataset=b.dataset, query=query[:200])
        try:
            # ONE root span per query on the entry thread: the
            # scheduler's queue-wait/run spans and the exec tree all
            # parent under it, so /admin/traces shows a single tree
            with TRACER.attach((qctx.trace_id, None)), \
                    TRACER.span("query", dataset=b.dataset,
                                query=query) as root:
                self._answered.tok = (qctx.trace_id, root.span_id)
                # the span is the plan bucket's source: no second clock
                with TRACER.stage("query.plan") as plan_span:
                    ep = b.planner.materialize(plan, qctx)
                if qctx.downsample_pixels:
                    # ?downsample=<pixels>: M4 decimation at the exec
                    # ROOT — after aggregation/functions, so the pixel
                    # budget applies to what the client actually plots
                    from filodb_tpu.query.transformers import \
                        DownsampleMapper
                    from filodb_tpu.utils.observability import \
                        downsample_metrics
                    ep.add_transformer(
                        DownsampleMapper(pixels=qctx.downsample_pixels))
                    downsample_metrics()["queries"].inc(dataset=b.dataset)
                if not qctx.tenant:
                    from filodb_tpu.workload.admission import plan_tenant
                    qctx.tenant = plan_tenant(ep)

                def run():
                    t_run = _time.perf_counter()
                    # parent onto wherever this runs: the scheduler
                    # worker's span when queued, the root span inline
                    tok = TRACER.capture()
                    if tok[0] is None:
                        tok = (qctx.trace_id, None)
                    with TRACER.attach(tok):
                        with TRACER.span("query.execute",
                                         dataset=b.dataset,
                                         query=query) as sp:
                            res = ep.execute(ExecContext(b.memstore, qctx))
                            if res.stats.hbm_resident_delta_bytes:
                                # devicewatch: residency this query
                                # committed/released, on the trace too
                                sp.tag(hbm_delta_bytes=res.stats
                                       .hbm_resident_delta_bytes)
                            if res.stats.device_programs:
                                # kernel flight deck: the per-program
                                # device-time split, so a slow-query
                                # trace names the offending kernel
                                sp.tag(device_programs=";".join(
                                    f"{k}={v * 1e3:.3f}ms" for k, v in
                                    sorted(res.stats
                                           .device_programs.items())))
                            if qctx.rollup_resolution_ms \
                                    or qctx.rollup_routed:
                                # tiered serving: the router's decision
                                # (0 = it chose raw) on the span; the
                                # stats keep reporting only real tiers
                                if qctx.rollup_resolution_ms:
                                    res.stats.resolution_ms = \
                                        qctx.rollup_resolution_ms
                                sp.tag(resolution_ms=qctx
                                       .rollup_resolution_ms)
                            if qctx.rollup_tiers:
                                # storage-tier attribution (ISSUE 16):
                                # which stitched legs actually served —
                                # raw / rolled-local / rolled-cold —
                                # in canonical oldest-first order
                                from filodb_tpu.rollup.planner import \
                                    canonical_tiers
                                res.stats.tiers = canonical_tiers(
                                    qctx.rollup_tiers)
                                sp.tag(tiers=res.stats.tiers)
                            rc_c = res.stats.resultcache_cached_samples
                            rc_r = res.stats \
                                .resultcache_recomputed_samples
                            if rc_c or rc_r:
                                # result cache: hit (all from memoized
                                # partials) / partial / miss, on the
                                # span so slowlog shows cache behavior
                                sp.tag(resultcache="hit" if not rc_r
                                       else ("partial" if rc_c
                                             else "miss"))
                    res.stats.add_timing("plan", plan_span.duration_s)
                    # queue = scheduler wait ONLY (t_submit is stamped
                    # right before submission below): planning and
                    # admission run on the entry thread and must not
                    # inflate this bucket, or sum(buckets) > total
                    res.stats.add_timing("queue", t_run - t_submit)
                    return res

                with self._admit(b, ep, qctx):
                    t_submit = _time.perf_counter()
                    if b.scheduler is not None:
                        result = b.scheduler.execute(
                            run, qctx.submit_time_ms, qctx.timeout_ms,
                            deadline_ms=qctx.deadline_ms)
                    else:
                        result = run()
        except BaseException as e:
            fail_s = _time.perf_counter() - t0
            FLIGHT.record("query.end", trace_id=qctx.trace_id,
                          dataset=b.dataset, error=repr(e)[:200],
                          seconds=round(fail_s, 6))
            TRACER.flush()
            TRACE_STORE.note_complete(qctx.trace_id, fail_s,
                                      query=query, dataset=b.dataset,
                                      error=repr(e))
            self._note_insight(b, ins, ins_keys, qctx, query, fail_s,
                               error=e)
            raise
        total_s = _time.perf_counter() - t0
        result.stats.timings.setdefault("total", total_s)
        FLIGHT.record("query.end", trace_id=qctx.trace_id,
                      dataset=b.dataset, seconds=round(total_s, 6))
        # the entry thread's share of the trace, the root span too: the
        # trace is whole in the store before the answer leaves
        TRACER.flush()
        TRACE_STORE.note_complete(qctx.trace_id, total_s, query=query,
                                  dataset=b.dataset)
        self._note_insight(b, ins, ins_keys, qctx, query, total_s,
                           stats=result.stats)
        return result, qctx.trace_id

    def _note_insight(self, b: DatasetBinding, ins, keys, qctx,
                      query: str, total_s: float, stats=None,
                      error=None) -> None:
        """Fold one finished query into the workload ledger + SLO
        tracker.  Sheds (admission refusals, expired deadlines) are
        classified by reason; everything here is best-effort and never
        fails the query."""
        if keys is None or not ins.enabled:
            return
        try:
            shed = ""
            outcome = "ok"
            if error is not None:
                outcome = "error"
                from filodb_tpu.workload.admission import AdmissionRejected
                if isinstance(error, AdmissionRejected):
                    shed = getattr(error, "reason", "") or "overload"
                    outcome = "shed"
                elif isinstance(error, wdl.DeadlineExceeded):
                    shed = "deadline_exceeded"
                    outcome = "shed"
            rc = ""
            samples = dev_n = hbm = 0
            dev_s = 0.0
            if stats is not None:
                samples = int(stats.samples_scanned)
                hbm = sum(stats.hbm_read_bytes.values())
                dev_n = len(stats.device_programs)
                dev_s = sum(stats.device_programs.values())
                rc_c = stats.resultcache_cached_samples
                rc_r = stats.resultcache_recomputed_samples
                if rc_c or rc_r:
                    rc = "hit" if not rc_r else ("partial" if rc_c
                                                 else "miss")
            dropped = ins.note(
                keys[0], query=query, dataset=b.dataset,
                tenant=qctx.tenant or "", latency_s=total_s,
                error=error is not None, samples=samples,
                resultcache=rc, device_programs=dev_n, device_s=dev_s,
                hbm_bytes=hbm, shed_reason=shed, batch_key=keys[1])
            _INSIGHTS_M["noted"].inc(dataset=b.dataset, outcome=outcome)
            if dropped:
                _INSIGHTS_M["dropped"].inc(dropped,
                                           node=self.node_name or "")
            if self.slo is not None:
                self.slo.observe(qctx.tenant or "", qctx.priority,
                                 total_s, error=error is not None)
        except Exception:  # noqa: BLE001 — insights never fail a query
            pass

    # ------------------------------------------------------- metadata routes

    def _time_range(self, p: dict) -> tuple[int, int]:
        start = parse_time_ms(p["start"]) if "start" in p else 0
        end = parse_time_ms(p["end"]) if "end" in p else np.iinfo(np.int64).max
        return start, end

    @_timed("labels")
    def _labels(self, b: DatasetBinding, p: dict) -> tuple[int, dict]:
        start, end = self._time_range(p)
        names: set[str] = set()
        for sh in b.memstore.shards(b.dataset):
            names.update(sh.label_names(start=start, end=end))
        return 200, {"status": "success", "data": sorted(names)}

    @_timed("label_values")
    def _label_values(self, b: DatasetBinding, label: str, p: dict,
                      multi: Optional[dict] = None) -> tuple[int, dict]:
        start, end = self._time_range(p)
        matches = (multi or {}).get("match[]") or \
            (multi or {}).get("match") or []
        if matches:
            # Prometheus API: match[] restricts the series the values
            # come from (union over selectors); the remote metadata
            # exec relies on this for filtered failover routing
            from filodb_tpu.promql.parser import parse_selector
            vals: set = set()
            for match in matches:
                filters = parse_selector(match)
                for sh in b.memstore.shards(b.dataset):
                    vals.update(sh.label_values(label, filters, start,
                                                end))
            return 200, {"status": "success", "data": sorted(vals)}
        vals = b.memstore.label_values(b.dataset, label, start=start, end=end)
        return 200, {"status": "success", "data": vals}

    @_timed("series")
    def _series(self, b: DatasetBinding, p: dict,
                multi: dict) -> tuple[int, dict]:
        from filodb_tpu.core.record import parse_partkey
        from filodb_tpu.http.model import public_tags
        from filodb_tpu.promql.parser import parse_selector
        start, end = self._time_range(p)
        matches = multi.get("match[]") or multi.get("match") or []
        if not matches:
            return 400, error_response("bad_data", "match[] required")
        seen: set[tuple] = set()
        out = []
        for match in matches:  # union over all selectors (Prometheus API)
            filters = parse_selector(match)
            for sh in b.memstore.shards(b.dataset):
                res = sh.lookup_partitions(filters, start, end)
                for pid in res.part_ids:
                    part = sh._partition_for_scan(int(pid))
                    tags = part.tags if part is not None \
                        else parse_partkey(sh.index.partkey(int(pid)))
                    key = tuple(sorted(tags.items()))
                    if key not in seen:
                        seen.add(key)
                        out.append(public_tags(tags, b.metric_column))
                # evicted/on-disk series surface as missing partkeys on
                # the in-memory-only shard
                for pk in res.missing_partkeys:
                    tags = parse_partkey(pk)
                    key = tuple(sorted(tags.items()))
                    if key not in seen:
                        seen.add(key)
                        out.append(public_tags(tags, b.metric_column))
        return 200, {"status": "success", "data": out}

    # --------------------------------------------------------- admin routes

    @_timed("health")
    def _health(self) -> tuple[int, dict]:
        """Shard statuses per dataset (reference: HealthRoute returning
        ShardStatus list).  Each row carries the full replica group
        (ISSUE 7) — the status poller gossips membership, per-replica
        status, and ingest watermarks from this payload."""
        out = {}
        topology = {}
        if self.shard_manager is not None:
            for ds in self.shard_manager.datasets():
                m = self.shard_manager.mapper(ds)
                # SERVING view at the shard level (best replica): one
                # dead copy of an otherwise fully-served shard must not
                # flip healthy:false and let a load balancer drain a
                # cluster that serves 100% of the data.  Per-replica
                # truth rides in the "replicas" rows, which is what the
                # gossip consumers read on replicated payloads.
                # total_shards: in-flight split children gossip their
                # Recovery groups + watermarks here too (ISSUE 13)
                out[ds] = [
                    {"shard": s, "status": m.best_status(s).value,
                     "node": m.coord_for_shard(s),
                     "replicas": [
                         {"node": r.node, "status": r.status.value,
                          "progress": r.recovery_progress,
                          "watermark": r.watermark}
                         for r in m.replicas(s)]}
                    for s in range(m.total_shards)]
                if m.total_shards > m.num_shards:
                    # catching-up split children must not flip the node
                    # unhealthy (they are not serving yet); the healthy
                    # flag judges the SERVING shards only
                    for row in out[ds][m.num_shards:]:
                        row["in_flight_child"] = True
                topology[ds] = m.topology.as_payload()
        else:
            for ds, b in self.datasets.items():
                out[ds] = [{"shard": sh.shard_num, "status": "Active",
                            "node": "local"}
                           for sh in b.memstore.shards(ds)]
        healthy = all(st["status"] in ("Active", "Recovery", "Assigned")
                      for sts in out.values() for st in sts
                      if not st.get("in_flight_child")) if out else True
        body = {"healthy": healthy, "shards": out}
        if topology:
            body["topology"] = topology
        if self.split_progress is not None:
            try:
                body["split_progress"] = self.split_progress()
            except Exception:  # noqa: BLE001 — controller mid-shutdown
                pass
        if self.running_shards is not None:
            body["running"] = {ds: self.running_shards(ds)
                               for ds in (out or self.datasets)}
        # per-shard ingested offsets: the peer-side source for replica
        # watermarks (group head = max across the group)
        wms: dict = {}
        for ds, b in self.datasets.items():
            try:
                wms[ds] = {sh.shard_num: sh.latest_offset
                           for sh in b.memstore.shards(ds)}
            except Exception:  # noqa: BLE001 — store mid-shutdown
                continue
        if wms:
            body["watermarks"] = wms
        # rollup tier closure watermarks for the shards THIS node rolls
        # (ROADMAP 2b): peers fold them into their TierWatermarks store
        # so a multi-node coordinator stitches raw/rolled at the
        # CLUSTER-wide boundary instead of its local engine's
        if self.rollup is not None:
            try:
                rolled = self.rollup.rolled_snapshot()
            except Exception:  # noqa: BLE001 — engine mid-shutdown
                rolled = {}
            if rolled:
                body["rollup"] = rolled
        if self.node_name:
            body["node"] = self.node_name
        return (200 if healthy else 503), body

    @_timed("cluster")
    def _cluster(self, parts: list[str], params: dict) -> tuple[int, dict]:
        """/api/v1/cluster/<ds>/status|startshards|stopshards (reference:
        ClusterApiRoute)."""
        if self.shard_manager is None:
            return 404, error_response("bad_data", "no cluster manager")
        if not parts:
            return 200, {"status": "success",
                         "data": self.shard_manager.datasets()}
        ds = parts[0]
        action = parts[1] if len(parts) > 1 else "status"
        m = self.shard_manager.mapper(ds)
        if action == "status":
            # SERVING view (ISSUE 7): a shard with any queryable
            # replica reports that status — a dead primary must not
            # show a served shard as down; the replicas list carries
            # each copy's own truth
            rows = []
            for s in range(m.num_shards):
                st = m.state(s)
                best = st.best_status
                serving = st.serving_replica()
                rows.append({
                    "shard": s, "status": best.value,
                    "node": serving.node if serving is not None
                    else st.node,
                    "replicas": [{"node": r.node,
                                  "status": r.status.value,
                                  "watermark": r.watermark}
                                 for r in st.replicas]})
            return 200, {"status": "success", "data": rows}
        shards = [int(s) for s in str(params.get("shards", "")).split(",") if s]
        if action == "startshards":
            done = self.shard_manager.start_shards(ds, shards,
                                                   params["node"])
            return 200, {"status": "success", "data": done}
        if action == "stopshards":
            done = self.shard_manager.stop_shards(ds, shards)
            return 200, {"status": "success", "data": done}
        return 404, error_response("bad_data", f"unknown action {action}")
