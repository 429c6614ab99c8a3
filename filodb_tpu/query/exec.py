"""ExecPlan tree: scatter-gather physical plans.

Mirrors the reference's ExecPlan machinery (reference: query/src/main/scala/
filodb/query/exec/ExecPlan.scala:40,278,337): ``execute`` = do_execute then
apply transformers then enforce limits; non-leaf plans dispatch children via
their PlanDispatcher and compose.  The in-process dispatcher is the local
path; the shard/mesh dispatchers live in filodb_tpu.parallel.
"""

from __future__ import annotations

import collections
import concurrent.futures
import contextlib
import dataclasses
import threading
from typing import Optional, Sequence

import numpy as np

from filodb_tpu.core.filters import ColumnFilter
from filodb_tpu.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.ops import instant as instant_ops
from filodb_tpu.ops.windows import StepRange
from filodb_tpu.query.aggregators import AggPartialBatch, aggregator_for
from filodb_tpu.query import logical as lp
from filodb_tpu.query.logical import (AggregationOperator, BinaryOperator,
                                      Cardinality, ScalarFunctionId)
from filodb_tpu.query.model import (PeriodicBatch, QueryContext, QueryError,
                                    QueryResult, QueryStats, RawBatch,
                                    ScalarResult, ShardUnavailable,
                                    concat_periodic)
from filodb_tpu.query.transformers import RangeVectorTransformer, _drop_metric
from filodb_tpu.utils.observability import TRACER

# helpers of every non-leaf plan's fan-out, process-wide: a pool made
# and torn down inside each request starts a thread a child, and a
# thread's start waits for the interpreter's turn (PERF.md, PR 29)
FANOUT_THREADS = 32
_FANOUT_POOL: Optional[concurrent.futures.ThreadPoolExecutor] = None
_FANOUT_POOL_LOCK = threading.Lock()


def _fanout_pool() -> concurrent.futures.ThreadPoolExecutor:
    global _FANOUT_POOL
    pool = _FANOUT_POOL
    if pool is None:
        with _FANOUT_POOL_LOCK:
            pool = _FANOUT_POOL
            if pool is None:
                pool = _FANOUT_POOL = concurrent.futures.ThreadPoolExecutor(
                    FANOUT_THREADS, thread_name_prefix="exec-fanout")
    return pool


# the ExecContext of the scan running on THIS thread: lower layers that
# have no ctx parameter (ODP page-in, predecode) attribute their stage
# timings / page-in counters to the active query through it
_ACTIVE = threading.local()


def active_exec_ctx() -> Optional["ExecContext"]:
    return getattr(_ACTIVE, "ctx", None)


@contextlib.contextmanager
def leaf_scan(ctx: "ExecContext"):
    """What a data leaf runs under (a per-shard leaf, the mesh node that
    scans every local shard at once): the leaf owns the "scan" stage
    bucket; lower layers without a ctx parameter (ODP page-in,
    predecode) attribute theirs through the active-ctx thread-local
    installed here, and the stage spans that end inside the scan land
    in this query's timings under their own names."""
    prev = getattr(_ACTIVE, "ctx", None)
    _ACTIVE.ctx = ctx
    try:
        with TRACER.stage("scan", leaf=False, timings=ctx):
            yield
    finally:
        _ACTIVE.ctx = prev


# the steps of one grid call (doc/observability.md "Stage spans"): a
# request the grid served carries all eight, 0.0 for a step its path
# skipped, so that their means are over the same requests and add up
# to device_compute's
GRID_STAGES = ("grid.resolve", "grid.lock_wait", "grid.plan", "batch.wait",
               "grid.dispatch", "grid.device_wait", "grid.readback",
               "grid.select")


@dataclasses.dataclass
class ExecContext:
    """What a plan needs to run locally: the data source + query knobs."""

    memstore: TimeSeriesMemStore
    query_context: QueryContext = dataclasses.field(default_factory=QueryContext)
    parallelism: int = 8
    # quarantined-chunk exclusions noted by leaf scans anywhere in the
    # plan tree (children run concurrently but share this ctx); the root
    # folds the total into QueryStats so the API layer can emit a
    # partial-data warning
    _corrupt_excluded: int = 0
    _corrupt_lock: object = dataclasses.field(
        default_factory=threading.Lock, repr=False)
    # per-stage wall-time + scan-volume accounting (ISSUE 2): leaves and
    # the ODP/device layers note into the shared ctx; remote dispatch
    # absorbs the data node's totals; the root folds the accumulated
    # numbers into its QueryResult's stats (same pattern as
    # corrupt_chunks_excluded — the outermost plan returns last)
    _timings: dict = dataclasses.field(default_factory=dict, repr=False)
    _counters: dict = dataclasses.field(default_factory=dict, repr=False)
    # per-program measured device seconds from launches the kernel
    # timer SAMPLED while this ctx was active (ISSUE 15): the split of
    # the device_compute bucket that names the offending kernel
    _device_programs: dict = dataclasses.field(default_factory=dict,
                                               repr=False)

    # shards degraded to empty results because their dispatch failed and
    # the query allows partial results (ISSUE 5); folds into
    # QueryStats.shards_down for the partial-data warning + header
    _shards_down: int = 0

    def note_corrupt_excluded(self, n: int) -> None:
        with self._corrupt_lock:
            self._corrupt_excluded += n

    def corrupt_excluded(self) -> int:
        return self._corrupt_excluded

    def note_shard_down(self, n: int = 1) -> None:
        with self._corrupt_lock:
            self._shards_down += n

    def note_timing(self, stage: str, seconds: float) -> None:
        self.note_timings(((stage, seconds),))

    def note_timings(self, walls) -> None:
        """``(stage, seconds)`` pairs under one taking of the lock: the
        ``scan`` stage hands over every stage that ended inside it."""
        timings = self._timings
        with self._corrupt_lock:
            for stage, seconds in walls:
                timings[stage] = timings.get(stage, 0.0) + seconds

    def ensure_timings(self, stages: Sequence[str]) -> None:
        """Every one of ``stages`` is a key of the timings, 0.0 where
        nothing was noted under it."""
        with self._corrupt_lock:
            for stage in stages:
                self._timings.setdefault(stage, 0.0)

    def note_counts(self, samples: int = 0, chunks: int = 0,
                    bytes_: int = 0, pages: int = 0,
                    hbm_dense: int = 0, hbm_compressed: int = 0,
                    hbm_delta: int = 0, hbm_hist: int = 0) -> None:
        with self._corrupt_lock:
            c = self._counters
            if samples:
                c["samples"] = c.get("samples", 0) + samples
            if chunks:
                c["chunks"] = c.get("chunks", 0) + chunks
            if bytes_:
                c["bytes"] = c.get("bytes", 0) + bytes_
            if pages:
                c["pages"] = c.get("pages", 0) + pages
            if hbm_dense:
                c["hbm_dense"] = c.get("hbm_dense", 0) + hbm_dense
            if hbm_compressed:
                c["hbm_compressed"] = c.get("hbm_compressed", 0) \
                    + hbm_compressed
            if hbm_hist:
                # histogram bucket planes served compressed (ISSUE 14)
                c["hbm_hist"] = c.get("hbm_hist", 0) + hbm_hist
            if hbm_delta:
                # signed: the devicewatch ledger credits commits and
                # debits frees caused while this query was active
                c["hbm_delta"] = c.get("hbm_delta", 0) + hbm_delta

    def note_device_program(self, program: str, seconds: float) -> None:
        """Kernel flight deck (utils/devicewatch.KernelTimer): fold a
        sampled launch's measured device seconds into this query's
        per-program split (data.stats.devicePrograms)."""
        with self._corrupt_lock:
            d = self._device_programs
            d[program] = d.get(program, 0.0) + seconds

    def note_resultcache(self, cached: int = 0, recomputed: int = 0) -> None:
        """Result-cache accounting (query/resultcache.py): result
        samples served from memoized partials vs samples re-scanned on
        the fresh/miss path — surfaced under data.stats.resultCache."""
        with self._corrupt_lock:
            c = self._counters
            if cached:
                c["rc_cached"] = c.get("rc_cached", 0) + cached
            if recomputed:
                c["rc_recomputed"] = c.get("rc_recomputed", 0) + recomputed

    def note_cold(self, chunks: int = 0, bytes_: int = 0) -> None:
        """Cold-tier accounting (filodb_tpu/coldstore): chunks/bytes
        this query pulled from the object bucket — surfaced under
        data.stats.coldTier so a slow cold panel is tellable from a
        warm one."""
        with self._corrupt_lock:
            c = self._counters
            if chunks:
                c["cold_chunks"] = c.get("cold_chunks", 0) + chunks
            if bytes_:
                c["cold_bytes"] = c.get("cold_bytes", 0) + bytes_

    def note_downsample(self, points_in: int = 0, points_out: int = 0) -> None:
        """?downsample= accounting (query/transformers.DownsampleMapper):
        finite points entering the M4 kernel vs pixel-exact points kept."""
        with self._corrupt_lock:
            c = self._counters
            c["ds_in"] = c.get("ds_in", 0) + points_in
            c["ds_out"] = c.get("ds_out", 0) + points_out

    def counter(self, name: str) -> int:
        with self._corrupt_lock:
            return self._counters.get(name, 0)

    def absorb_stats_from(self, other: "ExecContext") -> None:
        """Fold a nested sub-context's accumulated accounting into this
        one (the result cache runs fresh segments / delta fetches with
        their own ctx so per-segment volumes are exact)."""
        st = QueryStats()
        other.fold_into(st)
        st.corrupt_chunks_excluded = other.corrupt_excluded()
        self.absorb_stats(st)

    def absorb_stats(self, stats: QueryStats) -> None:
        """Fold a REMOTE child's stats into this query's accounting
        (local children share the ctx and need no absorb)."""
        self.note_counts(samples=stats.samples_scanned,
                         chunks=stats.chunks_scanned,
                         bytes_=stats.bytes_scanned, pages=stats.pages_in,
                         hbm_dense=stats.hbm_read_bytes.get("dense", 0),
                         hbm_compressed=stats.hbm_read_bytes.get(
                             "compressed", 0),
                         hbm_hist=stats.hbm_read_bytes.get(
                             "compressed-hist", 0),
                         hbm_delta=stats.hbm_resident_delta_bytes)
        self.note_resultcache(cached=stats.resultcache_cached_samples,
                              recomputed=stats.resultcache_recomputed_samples)
        if stats.cold_chunks_paged or stats.cold_bytes_read:
            self.note_cold(chunks=stats.cold_chunks_paged,
                           bytes_=stats.cold_bytes_read)
        if stats.downsample_points_in or stats.downsample_points_out:
            self.note_downsample(points_in=stats.downsample_points_in,
                                 points_out=stats.downsample_points_out)
        if stats.corrupt_chunks_excluded:
            self.note_corrupt_excluded(stats.corrupt_chunks_excluded)
        if stats.shards_down:
            self.note_shard_down(stats.shards_down)
        for k, v in stats.timings.items():
            self.note_timing(k, v)
        for k, v in stats.device_programs.items():
            self.note_device_program(k, v)

    def fold_into(self, stats: QueryStats) -> None:
        """Write the accumulated per-stage totals into an outgoing
        QueryResult's stats (overwrite: the ctx holds running totals)."""
        with self._corrupt_lock:
            stats.timings = dict(self._timings)
            c = self._counters
            stats.samples_scanned = c.get("samples", 0)
            stats.chunks_scanned = c.get("chunks", 0)
            stats.bytes_scanned = c.get("bytes", 0)
            stats.pages_in = c.get("pages", 0)
            stats.hbm_read_bytes = {
                k: c[ck] for k, ck in (("dense", "hbm_dense"),
                                       ("compressed", "hbm_compressed"),
                                       ("compressed-hist", "hbm_hist"))
                if c.get(ck)}
            stats.hbm_resident_delta_bytes = c.get("hbm_delta", 0)
            stats.resultcache_cached_samples = c.get("rc_cached", 0)
            stats.resultcache_recomputed_samples = c.get("rc_recomputed", 0)
            stats.cold_chunks_paged = c.get("cold_chunks", 0)
            stats.cold_bytes_read = c.get("cold_bytes", 0)
            stats.downsample_points_in = c.get("ds_in", 0)
            stats.downsample_points_out = c.get("ds_out", 0)
            stats.device_programs = dict(self._device_programs)
            stats.shards_down = self._shards_down


class PlanDispatcher:
    """Moves an ExecPlan to where its data lives (reference:
    PlanDispatcher.scala:20 — ActorPlanDispatcher / InProcessPlanDispatcher).
    """

    def dispatch(self, plan: "ExecPlan", ctx: ExecContext) -> QueryResult:
        raise NotImplementedError


class InProcessDispatcher(PlanDispatcher):
    def dispatch(self, plan, ctx):
        with TRACER.span("dispatch.inprocess",
                         plan=type(plan).__name__):
            return plan.execute(ctx)


IN_PROCESS = InProcessDispatcher()


class ExecPlan:
    def __init__(self, query_context: Optional[QueryContext] = None,
                 dispatcher: PlanDispatcher = IN_PROCESS):
        self.query_context = query_context or QueryContext()
        self.dispatcher = dispatcher
        self.transformers: list[RangeVectorTransformer] = []

    def add_transformer(self, t: RangeVectorTransformer) -> "ExecPlan":
        self.transformers.append(t)
        return self

    @property
    def children(self) -> Sequence["ExecPlan"]:
        return ()

    def do_execute(self, ctx: ExecContext) -> list:
        raise NotImplementedError

    def execute(self, ctx: ExecContext) -> QueryResult:
        # one span per plan node (reference: Kamon.spanBuilder in
        # ExecPlan.execute, ExecPlan.scala:99-126); tags carry the plan
        # type and, for data leaves, dataset/shard.  Span machinery
        # never raises into the query path — reporter failures are
        # swallowed by the tracer
        # deadline tripwire (ISSUE 5): one clock read per plan node so a
        # deep scatter-gather stops burning workers the moment its
        # end-to-end budget is gone (reference: queryTimeoutMillis
        # checked inside ExecPlan execution).  DeadlineExceeded is a
        # QueryError subclass the HTTP layer maps to 503, not 400 — a
        # timed-out query is an overload outcome, not a client bug.
        qctx = self.query_context
        if qctx.deadline_ms:
            from filodb_tpu.workload import deadline as dl
            dl.check(qctx, where=type(self).__name__)
        tags = {"plan": type(self).__name__}
        ds = getattr(self, "dataset", None)
        if ds is not None:
            tags["dataset"] = ds
            tags["shard"] = getattr(self, "shard", "")
        try:
            with TRACER.span("execplan.execute", **tags):
                batches = self._run(ctx)
                self._enforce_limits(batches, ctx)
                stats = self._collect_stats(batches)
                # quarantined-chunk exclusions accumulate on the shared
                # ctx; the outermost plan returns last, so its result
                # carries the whole tree's total for the partial-data
                # warning.  Stage timings/counters fold the same way.
                stats.corrupt_chunks_excluded = ctx.corrupt_excluded()
                ctx.fold_into(stats)
                return QueryResult(self.query_context.query_id, batches,
                                   stats)
        except QueryError:
            raise
        except Exception as e:  # noqa: BLE001 - plan failure surfaces as QueryError
            raise QueryError(self.query_context.query_id,
                             f"{type(self).__name__}: {e}") from e

    def _run(self, ctx: ExecContext) -> list:
        """This node's own work and its transformers."""
        batches = self.do_execute(ctx)
        for t in self.transformers:
            batches = t.apply(batches, ctx)
        return batches

    def _enforce_limits(self, batches, ctx):
        total = 0
        for b in batches:
            if isinstance(b, PeriodicBatch):
                total += len(b.keys) * b.steps.num_steps
        if total > ctx.query_context.sample_limit:
            raise QueryError(
                self.query_context.query_id,
                f"result samples {total} > limit {ctx.query_context.sample_limit}")

    @staticmethod
    def _collect_stats(batches) -> QueryStats:
        st = QueryStats()
        for b in batches:
            st.series_scanned += getattr(b, "num_series", 0)
        return st

    # -- debugging ----------------------------------------------------------

    def print_tree(self, level: int = 0) -> str:
        """Plan-shape dump used by planner tests (reference:
        ExecPlan.printTree)."""
        pad = "-" * level
        lines = [f"{pad}T~{type(t).__name__}" for t in reversed(self.transformers)]
        lines.append(f"{pad}E~{type(self).__name__}({self._args_str()})")
        for c in self.children:
            lines.append(c.print_tree(level + 1))
        return "\n".join(lines)

    def _args_str(self) -> str:
        return ""


class LeafExecPlan(ExecPlan):
    pass


class NonLeafExecPlan(ExecPlan):
    def __init__(self, children: Sequence[ExecPlan],
                 query_context: Optional[QueryContext] = None,
                 dispatcher: PlanDispatcher = IN_PROCESS,
                 parallel_children: bool = True):
        super().__init__(query_context, dispatcher)
        self._children = list(children)
        self.parallel_children = parallel_children

    @property
    def children(self) -> Sequence[ExecPlan]:
        return self._children

    def _run(self, ctx: ExecContext) -> list:
        """The scatter-gather's two stages: ``exec.fanout``, the wait
        for the children (their own stages name the work), and
        ``exec.compose``, this node's reduce and its transformers.  Both
        land in the query's timings, summed over the plan's non-leaf
        nodes."""
        name = type(self).__name__
        with TRACER.stage("exec.fanout", leaf=False, plan=name,
                          children=len(self._children)) as fan:
            results = self._dispatch_children(ctx)
        with TRACER.stage("exec.compose", plan=name) as comp:
            batches = self.compose(results, ctx)
            for t in self.transformers:
                batches = t.apply(batches, ctx)
        ctx.note_timings((("exec.fanout", fan.duration_s),
                          ("exec.compose", comp.duration_s)))
        return batches

    def _dispatch_children(self, ctx) -> list[QueryResult]:
        """Children run via their own dispatchers, concurrently (reference:
        NonLeafExecPlan.doExecute mapAsync, ExecPlan.scala:370-409).
        The trace context is captured here and re-attached on the pool
        threads so child spans parent onto this plan's span.

        A child whose dispatch fails at the TRANSPORT level
        (ShardUnavailable: shard's node down / unroutable) degrades to
        an empty result when the query set ``allow_partial_results`` —
        the root result then carries ``stats.shards_down`` and the API
        layer emits a Prometheus warning + the X-FiloDB-Partial-Data
        header (ISSUE 5; reference: PartialResults semantics)."""
        kids = self._children

        def one(c):
            try:
                return c.dispatcher.dispatch(c, ctx)
            except ShardUnavailable as e:
                if not ctx.query_context.allow_partial_results:
                    raise
                ctx.note_shard_down()
                TRACER.record("dispatch.shard_down", 0.0,
                              trace_id=ctx.query_context.trace_id or None,
                              shard=str(getattr(c, "shard", "")),
                              error=str(e)[:200])
                return QueryResult(c.query_context.query_id, [],
                                   QueryStats())

        if len(kids) <= 1 or not self.parallel_children:
            return [one(c) for c in kids]
        # at most ``parallelism`` children at a time: this thread runs
        # its share itself and helpers of the shared pool the rest, all
        # drawing from one queue.  A helper that starts late finds the
        # queue empty, so a busy pool costs concurrency, never progress
        # (a nested plan's helpers cannot wait for each other)
        results: list = [None] * len(kids)
        todo = collections.deque(range(len(kids)))
        failed: list = []

        def drain():
            while True:
                try:
                    i = todo.popleft()
                except IndexError:
                    return
                try:
                    results[i] = one(kids[i])
                except BaseException as e:  # noqa: BLE001 — re-raised by the caller below
                    failed.append(e)
                    todo.clear()
                    return

        token = TRACER.capture()

        def helper():
            with TRACER.attach(token):
                drain()

        pool = _fanout_pool()
        helpers = [pool.submit(helper)
                   for _ in range(min(len(kids), ctx.parallelism) - 1)]
        drain()
        for f in helpers:
            if not f.cancel():
                f.result()
        if failed:
            raise failed[0]
        return results

    def compose(self, results: list[QueryResult], ctx) -> list:
        raise NotImplementedError


# ---------------------------------------------------------------------------
# Leaves
# ---------------------------------------------------------------------------

class MultiSchemaPartitionsExec(LeafExecPlan):
    """Leaf scan: index lookup + device batch materialization (reference:
    exec/MultiSchemaPartitionsExec.scala:27 + SelectRawPartitionsExec)."""

    def __init__(self, dataset: str, shard: int,
                 filters: Sequence[ColumnFilter], start_ms: int, end_ms: int,
                 column: Optional[str] = None,
                 query_context: Optional[QueryContext] = None,
                 dispatcher: PlanDispatcher = IN_PROCESS,
                 reshard_to: Optional[tuple] = None):
        super().__init__(query_context, dispatcher)
        self.dataset = dataset
        self.shard = shard
        self.filters = list(filters)
        self.start_ms = start_ms
        self.end_ms = end_ms
        self.column = column
        # elastic resharding (ISSUE 13): (total_shards, ingest_spread)
        # stamped by the planner when this shard is a split PARENT whose
        # migrated half must be excluded from the scan (the child serves
        # it).  Plan-time stamping keeps one query on one topology view
        # even when the cutover commits mid-flight; travels the wire
        # with the leaf (query/wire.py).
        self.reshard_to = tuple(reshard_to) if reshard_to else None

    def do_execute(self, ctx: ExecContext) -> list:
        with leaf_scan(ctx):
            shard = ctx.memstore.get_shard(self.dataset, self.shard)
            lookup = shard.lookup_partitions(
                self.filters, self.start_ms, self.end_ms)
            if self.reshard_to is not None:
                lookup = shard.filter_resharded(lookup, *self.reshard_to)
            try:
                batches = self._do_scan(ctx, shard, lookup)
                self._note_batch_counts(ctx, batches)
                return batches
            finally:
                # AFTER the scan, so corruption detected by this very
                # query already counts toward its own partial-data
                # warning
                self._note_quarantined(ctx, shard, lookup.part_ids)

    @staticmethod
    def _note_batch_counts(ctx: ExecContext, batches) -> None:
        """Scan-volume accounting from what the leaf actually returned."""
        samples = nbytes = 0
        for b in batches:
            if isinstance(b, PeriodicBatch):
                samples += len(b.keys) * b.steps.num_steps
                nbytes += getattr(b.values, "nbytes", 0)
            elif isinstance(b, RawBatch) and b.batch is not None:
                samples += int(np.asarray(b.batch.row_counts).sum())
                nbytes += getattr(b.batch.values, "nbytes", 0)
            elif isinstance(b, AggPartialBatch):
                for v in b.state.values():
                    nbytes += getattr(v, "nbytes", 0)
        if samples or nbytes:
            ctx.note_counts(samples=samples, bytes_=nbytes)

    @staticmethod
    def _grid_timed(fn, *args, **kw):
        """Run a device-grid serving call under the ``device_compute``
        stage: the enclosing bucket that the grid's own stage spans
        (``GRID_STAGES``) split."""
        ctx = active_exec_ctx()
        if ctx is not None:
            ctx.ensure_timings(GRID_STAGES)
        with TRACER.stage("device_compute", leaf=False, cpu=True):
            return fn(*args, **kw)

    def _do_scan(self, ctx: ExecContext, shard, lookup) -> list:
        schema = None
        if lookup.first_schema_hash is not None:
            schema = shard.schemas.by_hash(lookup.first_schema_hash)
        column_id = None
        if self.column is not None and schema is not None:
            column_id = schema.data.column(self.column).id
        elif schema is not None:
            # schema-driven rewrites AFTER discovery, BEFORE scanning
            # (reference: MultiSchemaPartitionsExec.finalizePlan :41-85)
            served = self._try_schema_rewrite(shard, lookup.part_ids, schema)
            if served is not None:
                return served
        served = self._try_device_grid(shard, lookup.part_ids, column_id)
        if served is not None:
            return served
        tags, batch = shard.scan_batch(lookup.part_ids, self.start_ms,
                                       self.end_ms, column_id)
        return [RawBatch(tags, batch)]

    def _note_quarantined(self, ctx: ExecContext, shard, part_ids) -> None:
        """Partial-data tripwire: quarantined chunks among the scanned
        series AND overlapping this query's time range mean the result
        excludes data — now and on every re-query (quarantine persists
        until cleared).  A corrupt chunk outside the window excluded
        nothing from THIS result, so it must not flag it.  O(1) when
        the quarantine is empty, the overwhelmingly common case."""
        from filodb_tpu.integrity import QUARANTINE
        if not QUARANTINE:
            return
        pks = []
        for pid in part_ids:
            try:
                pks.append(shard.index.partkey(int(pid)))
            except KeyError:
                continue
        n = QUARANTINE.count_overlapping(pks, self.start_ms, self.end_ms)
        if n:
            ctx.note_corrupt_excluded(n)

    # -- downsample-gauge & hist-max schema rewrites ------------------------

    def _first_mapper(self):
        from filodb_tpu.query.transformers import PeriodicSamplesMapper
        if not self.transformers:
            return None
        mapper = self.transformers[0]
        if not isinstance(mapper, PeriodicSamplesMapper):
            return None
        if not mapper.well_formed:
            return None
        return mapper

    def _try_schema_rewrite(self, shard, part_ids, schema):
        """ds-gauge column selection + range-function swap, and hist+max
        column pairing (see filodb_tpu.query.dsrewrite).  Returns leaf
        batches (already stepped — the mapper passes them through) or
        None when no rewrite applies."""
        from filodb_tpu.query import dsrewrite
        mapper = self._first_mapper()
        if mapper is None or len(part_ids) == 0:
            return None
        if dsrewrite.is_ds_gauge(schema.data):
            return self._execute_ds_gauge(shard, part_ids, schema, mapper)
        if dsrewrite.hist_max_column(schema.data) is not None:
            return self._execute_hist_max(shard, part_ids, schema, mapper)
        return None

    def _scan_stepped(self, shard, part_ids, steps, window_ms, func, cid,
                      fargs=()):
        """One column read + windowed range function, grid-served when
        possible: returns (tags, values, bucket_tops) with values
        [len(tags), T] ([len(tags), T, hb] for hist columns)."""
        from filodb_tpu.query import rangefns
        got = self._grid_timed(shard.scan_grid, part_ids, func, steps.start,
                               steps.num_steps, steps.step, window_ms, cid,
                               fargs=fargs)
        if got is not None:
            return got
        tags, batch = shard.scan_batch(part_ids, self.start_ms, self.end_ms,
                                       cid)
        if batch is None or not tags:
            return None
        vals = np.asarray(rangefns.apply_range_function(
            batch, steps, window_ms, func, fargs))
        tops = np.asarray(batch.bucket_tops) if batch.hist is not None \
            else None
        # scan_batch pads the series axis; trim to the real tag rows so
        # paired two-column reads stay row-aligned
        return tags, vals[:len(tags)], tops

    @staticmethod
    def _align_pair(got_a, got_b):
        """Row-align two independently scanned planes by series tags.
        One plane can be grid-served ([n, T] exact) while the other
        fell back to scan_batch, and a partition evicted between the
        two scans can drop a row from one side only — intersect on the
        tag identity so series are never cross-paired."""
        tags_a, va, tops_a = got_a
        tags_b, vb, _ = got_b
        if tags_a == tags_b:
            return tags_a, va, vb, tops_a
        def key(t):
            return tuple(sorted(t.items()))
        idx_b = {key(t): i for i, t in enumerate(tags_b)}
        keep_a, keep_b, tags = [], [], []
        for i, t in enumerate(tags_a):
            j = idx_b.get(key(t))
            if j is not None:
                keep_a.append(i)
                keep_b.append(j)
                tags.append(t)
        if not tags:
            return None
        return tags, np.asarray(va)[keep_a], np.asarray(vb)[keep_b], tops_a

    def _execute_ds_gauge(self, shard, part_ids, schema, mapper):
        from filodb_tpu.query import dsrewrite
        from filodb_tpu.query.logical import RangeFunctionId as F
        rw = dsrewrite.ds_gauge_rewrite(mapper.function)
        if rw is None:
            return None        # default avg column is already correct
        cols, func = rw
        steps, report = mapper.step_ranges()
        window = mapper.effective_window_ms
        if func is not None:
            cid = schema.data.column(cols[0]).id
            got = self._scan_stepped(shard, part_ids, steps, window, func,
                                     cid, tuple(mapper.function_args))
            if got is None:
                return []
            tags, vals, _ = got
            return [PeriodicBatch(tags, report, vals)]
        # AvgWithSumAndCountOverTime: sum(period sums) / sum(period counts)
        sum_cid = schema.data.column("sum").id
        cnt_cid = schema.data.column("count").id
        got_s = self._scan_stepped(shard, part_ids, steps, window,
                                   F.SUM_OVER_TIME, sum_cid)
        got_c = self._scan_stepped(shard, part_ids, steps, window,
                                   F.SUM_OVER_TIME, cnt_cid)
        if got_s is None or got_c is None:
            return []
        pair = self._align_pair(got_s, got_c)
        if pair is None:
            return []
        tags, sums, counts, _ = pair
        with np.errstate(invalid="ignore", divide="ignore"):
            vals = np.where(counts > 0, sums / counts, np.nan)
        return [PeriodicBatch(tags, report, vals)]

    def _execute_hist_max(self, shard, part_ids, schema, mapper):
        """Histogram schema with a max column: pair the hist kernel with
        the max column so histogram_max_quantile sees both planes
        (reference: histMaxRangeFunction — None -> LastSampleHistMax,
        sum_over_time -> SumAndMaxOverTime)."""
        from filodb_tpu.query import dsrewrite
        from filodb_tpu.query.logical import RangeFunctionId as F
        if mapper.function not in (None, F.SUM_OVER_TIME):
            return None        # rate/increase etc: hist column only
        steps, report = mapper.step_ranges()
        window = mapper.effective_window_ms
        hist_cid = schema.data.value_column_id
        max_cid = dsrewrite.hist_max_column(schema.data)
        max_func = None if mapper.function is None else F.MAX_OVER_TIME
        got_h = self._scan_stepped(shard, part_ids, steps, window,
                                   mapper.function, hist_cid)
        if got_h is None:
            return []
        got_m = self._scan_stepped(shard, part_ids, steps, window,
                                   max_func, max_cid)
        if got_m is None:
            return []
        pair = self._align_pair(got_h, got_m)
        if pair is None:
            return []
        tags, hvals, mvals, tops = pair
        return [PeriodicBatch(tags, report, mvals, hist=hvals,
                              bucket_tops=tops)]

    # derived from the mesh table so the single-device fused path and the
    # grid x mesh path can never diverge on which ops are fused
    from filodb_tpu.parallel.meshgrid import GRID_MESH_OPS as _MESH_OPS
    _GRID_AGG_OPS = {op.name: v for op, v in _MESH_OPS.items()}
    del _MESH_OPS

    def _try_device_grid(self, shard, part_ids, column_id):
        """Serve leaf + PeriodicSamplesMapper straight from the shard's
        device-resident grid (memstore/devicestore.py) when the first
        transformer is an eligible windowed rate/increase.  Emits the
        already-stepped PeriodicBatch; the mapper passes it through.
        When an AggregateMapReduce follows the mapper, the aggregation
        is fused ON DEVICE too: only [G, T] partials cross the host
        link."""
        from filodb_tpu.query.transformers import (AggregateMapReduce,
                                                   PeriodicSamplesMapper)
        if not self.transformers or len(part_ids) == 0:
            return None
        mapper = self.transformers[0]
        if not isinstance(mapper, PeriodicSamplesMapper):
            return None
        if not mapper.well_formed:
            return None   # half-specified windowing: general path decides
        # bare instant selector: the staleness lookback is a
        # last-sample-in-window scan the grid serves directly
        window_ms = mapper.effective_window_ms
        steps, report = mapper.step_ranges()
        mapred = self.transformers[1] if len(self.transformers) > 1 else None
        if isinstance(mapred, AggregateMapReduce) and not mapred.params \
                and mapred.operator.name in self._GRID_AGG_OPS:
            served = self._try_grid_aggregated(shard, part_ids, column_id,
                                               mapper, mapred, steps, report,
                                               window_ms)
            if served is not None:
                return served
        got = self._grid_timed(shard.scan_grid, part_ids, mapper.function,
                               steps.start, steps.num_steps, steps.step,
                               window_ms, column_id,
                               fargs=tuple(mapper.function_args))
        if got is None:
            return None
        tags, vals, tops = got
        if vals.ndim == 3:      # histogram column: per-bucket [S, T, hb]
            return [PeriodicBatch(tags, report,
                                  np.full(vals.shape[:2], np.nan),
                                  hist=vals, bucket_tops=tops)]
        return [PeriodicBatch(tags, report, vals)]

    def _try_grid_aggregated(self, shard, part_ids, column_id, mapper,
                             mapred, steps, report, window_ms):
        from filodb_tpu.query.aggregators import (AggPartialBatch,
                                                  grouping_key)
        union: dict[tuple, int] = {}
        if not mapred.by and not mapred.without:
            # global aggregate: one group, skip the per-series key walk
            # (missing partitions are detected by the cache's plan walk)
            union[()] = 0
            gids = [0] * len(part_ids)
        else:
            gids = []
            for pid in part_ids:
                part = shard.grid_partition(int(pid))
                if part is None:
                    return None
                key = tuple(sorted(grouping_key(part.tags, mapred.by,
                                                mapred.without).items()))
                gids.append(union.setdefault(key, len(union)))
        state = self._grid_timed(
            shard.scan_grid_grouped, part_ids, mapper.function, steps.start,
            steps.num_steps, steps.step, window_ms, gids,
            max(len(union), 1), self._GRID_AGG_OPS[mapred.operator.name],
            column_id, fargs=tuple(mapper.function_args))
        if state is None:
            return None
        # the fused path never materializes per-series batches, so the
        # scanned volume is accounted here: S series x T steps of
        # windowed input went through the device program
        ctx = active_exec_ctx()
        if ctx is not None:
            ctx.note_counts(samples=len(part_ids) * steps.num_steps)
        tops = state.pop("bucket_tops", None)
        return [AggPartialBatch(mapred.operator, (),
                                [dict(k) for k in union], report, state,
                                bucket_tops=tops)]

    def _args_str(self) -> str:
        return f"dataset={self.dataset}, shard={self.shard}, " \
               f"filters={self.filters}, start={self.start_ms}, end={self.end_ms}"


class EmptyResultExec(LeafExecPlan):
    def do_execute(self, ctx):
        return []


class PartKeysExec(LeafExecPlan):
    """Metadata: series keys matching filters (reference:
    exec/MetadataExecPlan.scala PartKeysExec)."""

    def __init__(self, dataset: str, shard: int,
                 filters: Sequence[ColumnFilter], start_ms: int, end_ms: int,
                 query_context=None, dispatcher: PlanDispatcher = IN_PROCESS,
                 reshard_to: Optional[tuple] = None):
        super().__init__(query_context, dispatcher)
        self.dataset = dataset
        self.shard = shard
        self.filters = list(filters)
        self.start_ms = start_ms
        self.end_ms = end_ms
        # split-parent exclusion, as on MultiSchemaPartitionsExec — a
        # migrated series must be listed by its child only
        self.reshard_to = tuple(reshard_to) if reshard_to else None

    def do_execute(self, ctx):
        shard = ctx.memstore.get_shard(self.dataset, self.shard)
        keys = shard.part_keys(self.filters, self.start_ms, self.end_ms)
        if self.reshard_to is not None:
            from filodb_tpu.parallel.shardmap import shard_of_tags
            total, spread = self.reshard_to
            keys = [t for t in keys
                    if shard_of_tags(t, total, spread) == self.shard]
        return [keys]


class SelectChunkInfosExec(LeafExecPlan):
    """Chunk-level metadata for matching partitions (reference:
    exec/SelectChunkInfosExec.scala): per series, the frozen chunks'
    id/rows/time-range/encoded-bytes plus the write-buffer row count —
    the observability surface for retention and compression debugging."""

    def __init__(self, dataset: str, shard: int,
                 filters: Sequence[ColumnFilter], start_ms: int, end_ms: int,
                 query_context=None, dispatcher: PlanDispatcher = IN_PROCESS):
        super().__init__(query_context, dispatcher)
        self.dataset = dataset
        self.shard = shard
        self.filters = list(filters)
        self.start_ms = start_ms
        self.end_ms = end_ms

    def do_execute(self, ctx):
        shard = ctx.memstore.get_shard(self.dataset, self.shard)
        lookup = shard.lookup_partitions(self.filters, self.start_ms,
                                         self.end_ms)
        out = []
        for pid in lookup.part_ids:
            part = shard.partitions.get(int(pid))
            if part is None:
                continue
            chunks = []
            for cs in part.chunks:
                info = cs.info
                if info.end_time < self.start_ms or \
                        info.start_time > self.end_ms:
                    continue
                chunks.append({
                    "chunk_id": int(info.chunk_id),
                    "num_rows": int(info.num_rows),
                    "start_time": int(info.start_time),
                    "end_time": int(info.end_time),
                    "bytes": int(cs.nbytes)})
            out.append({"tags": part.tags, "shard": self.shard,
                        "buffer_rows": int(part._buf_n),
                        "chunks": chunks})
        return [out]

    def _args_str(self) -> str:
        return f"dataset={self.dataset}, shard={self.shard}, " \
               f"filters={self.filters}"


class LabelValuesExec(LeafExecPlan):
    def __init__(self, dataset: str, shard: int, label_names: Sequence[str],
                 filters: Sequence[ColumnFilter], start_ms: int, end_ms: int,
                 query_context=None, dispatcher: PlanDispatcher = IN_PROCESS):
        super().__init__(query_context, dispatcher)
        self.dataset = dataset
        self.shard = shard
        self.label_names = list(label_names)
        self.filters = list(filters)
        self.start_ms = start_ms
        self.end_ms = end_ms

    def do_execute(self, ctx):
        shard = ctx.memstore.get_shard(self.dataset, self.shard)
        return [{label: shard.label_values(label, self.filters, self.start_ms,
                                           self.end_ms)
                 for label in self.label_names}]


# ---------------------------------------------------------------------------
# Scalar leaves
# ---------------------------------------------------------------------------

class ScalarFixedDoubleExec(LeafExecPlan):
    def __init__(self, scalar: float, start_ms: int, step_ms: int, end_ms: int,
                 query_context=None, dispatcher: PlanDispatcher = IN_PROCESS):
        super().__init__(query_context, dispatcher)
        self.scalar = scalar
        self.steps = StepRange(start_ms, end_ms, step_ms)

    def do_execute(self, ctx):
        return [ScalarResult(self.steps,
                             np.full(self.steps.num_steps, self.scalar))]


class TimeScalarGeneratorExec(LeafExecPlan):
    """time(), hour(), minute()... as per-step scalars (reference:
    exec/TimeScalarGeneratorExec.scala:91)."""

    def __init__(self, function: ScalarFunctionId, start_ms: int, step_ms: int,
                 end_ms: int, query_context=None,
                 dispatcher: PlanDispatcher = IN_PROCESS):
        super().__init__(query_context, dispatcher)
        self.function = function
        self.steps = StepRange(start_ms, end_ms, step_ms)

    def do_execute(self, ctx):
        secs = np.asarray(self.steps.timestamps(), dtype=np.float64) / 1000.0
        if self.function == ScalarFunctionId.TIME:
            vals = secs
        else:
            fn = instant_ops.INSTANT_FUNCTIONS[self.function.value]
            import jax.numpy as jnp
            vals = np.asarray(fn(jnp.asarray(secs[None, :] * 1000.0)))[0]
        return [ScalarResult(self.steps, vals)]


# ---------------------------------------------------------------------------
# Non-leaves
# ---------------------------------------------------------------------------

class ReduceAggregateExec(NonLeafExecPlan):
    """Cross-shard (or cross-slice) aggregation reduce (reference:
    ReduceAggregateExec, AggrOverRangeVectors.scala:19-66)."""

    def __init__(self, children, operator: AggregationOperator,
                 params: tuple = (), query_context=None,
                 dispatcher: PlanDispatcher = IN_PROCESS):
        super().__init__(children, query_context, dispatcher)
        self.operator = operator
        self.params = params

    def compose(self, results, ctx):
        partials = [b for r in results for b in r.batches
                    if isinstance(b, AggPartialBatch)]
        # already-presented batches (a fused MeshReduceExec child does
        # its reduce+present on device) pass through untouched instead
        # of being silently dropped by the partial filter
        presented = [b for r in results for b in r.batches
                     if not isinstance(b, AggPartialBatch)]
        if not partials:
            return presented
        agg = aggregator_for(self.operator)
        return [agg.reduce(partials)] + presented

    def _args_str(self):
        return f"operator={self.operator.name}"


class DistConcatExec(NonLeafExecPlan):
    """Concatenate child results (reference: DistConcatExec.scala:12)."""

    def compose(self, results, ctx):
        return [b for r in results for b in r.batches]


class StitchRvsExec(NonLeafExecPlan):
    """Concat + stitch split series (reference: StitchRvsExec.scala:61)."""

    def compose(self, results, ctx):
        from filodb_tpu.query.transformers import StitchRvsMapper
        batches = [b for r in results for b in r.batches]
        return StitchRvsMapper().apply(batches, ctx)


class BinaryJoinExec(NonLeafExecPlan):
    """Hash join on `on`/`ignoring` labels (reference:
    BinaryJoinExec.scala:37).  lhs children come first in the children list;
    ``lhs_count`` splits them."""

    def __init__(self, children, lhs_count: int, operator: BinaryOperator,
                 cardinality: Cardinality = Cardinality.ONE_TO_ONE,
                 on: tuple = (), ignoring: tuple = (), include: tuple = (),
                 query_context=None, dispatcher: PlanDispatcher = IN_PROCESS,
                 bool_mode: bool = False):
        super().__init__(children, query_context, dispatcher)
        self.lhs_count = lhs_count
        self.operator = operator
        self.cardinality = cardinality
        self.on = tuple(on)
        self.ignoring = tuple(ignoring)
        self.include = tuple(include)
        self.bool_mode = bool_mode

    def _join_key(self, tags: dict) -> tuple:
        if self.on:
            return tuple((k, tags.get(k, "")) for k in sorted(self.on))
        drop = set(self.ignoring) | {"_metric_", "__name__"}
        return tuple(sorted((k, v) for k, v in tags.items() if k not in drop))

    def compose(self, results, ctx):
        lhs_b = concat_periodic([b for r in results[:self.lhs_count]
                                 for b in r.batches
                                 if isinstance(b, PeriodicBatch)])
        rhs_b = concat_periodic([b for r in results[self.lhs_count:]
                                 for b in r.batches
                                 if isinstance(b, PeriodicBatch)])
        if lhs_b is None or rhs_b is None:
            return []
        lv, rv = lhs_b.np_values(), rhs_b.np_values()
        # hash side = the "one" side (reference puts smaller on build side)
        rkeys: dict[tuple, int] = {}
        for i, t in enumerate(rhs_b.keys):
            k = self._join_key(t)
            if k in rkeys and self.cardinality == Cardinality.ONE_TO_ONE:
                raise QueryError(self.query_context.query_id,
                                 "duplicate series on right side of join")
            rkeys.setdefault(k, i)
        out_keys, rows = [], []
        seen: set[tuple] = set()
        many_on_left = self.cardinality != Cardinality.ONE_TO_MANY
        for i, t in enumerate(lhs_b.keys):
            k = self._join_key(t)
            j = rkeys.get(k)
            if j is None:
                continue
            if self.cardinality == Cardinality.ONE_TO_ONE:
                if k in seen:
                    raise QueryError(self.query_context.query_id,
                                     "duplicate series on left side of join")
                seen.add(k)
            res = np.asarray(instant_ops.apply_binary(
                self.operator.name, lv[i], rv[j], self.bool_mode))
            key = self._result_key(t, rhs_b.keys[j])
            out_keys.append(key)
            rows.append(res)
        T = lhs_b.steps.num_steps
        vals = np.stack(rows) if rows else np.empty((0, T))
        return [PeriodicBatch(out_keys, lhs_b.steps, vals)]

    def _result_key(self, lt: dict, rt: dict) -> dict:
        if self.operator.is_comparison:
            if self.bool_mode:  # bool comparisons drop the metric name
                return {k: v for k, v in lt.items()
                        if k not in ("_metric_", "__name__")}
            return dict(lt)
        if self.on:
            key = {k: lt.get(k, "") for k in self.on if k in lt}
        else:
            drop = set(self.ignoring) | {"_metric_", "__name__"}
            key = {k: v for k, v in lt.items() if k not in drop}
        for k in self.include:
            if k in rt:
                key[k] = rt[k]
        return key

    def _args_str(self):
        return f"operator={self.operator.name}, on={self.on}, " \
               f"ignoring={self.ignoring}"


class SetOperatorExec(NonLeafExecPlan):
    """and/or/unless set operators (reference: SetOperatorExec.scala:31)."""

    def __init__(self, children, lhs_count: int, operator: BinaryOperator,
                 on: tuple = (), ignoring: tuple = (),
                 query_context=None, dispatcher: PlanDispatcher = IN_PROCESS):
        super().__init__(children, query_context, dispatcher)
        self.lhs_count = lhs_count
        self.operator = operator
        self.on = tuple(on)
        self.ignoring = tuple(ignoring)

    def _join_key(self, tags: dict) -> tuple:
        if self.on:
            return tuple((k, tags.get(k, "")) for k in sorted(self.on))
        drop = set(self.ignoring) | {"_metric_", "__name__"}
        return tuple(sorted((k, v) for k, v in tags.items() if k not in drop))

    def compose(self, results, ctx):
        lhs_b = concat_periodic([b for r in results[:self.lhs_count]
                                 for b in r.batches
                                 if isinstance(b, PeriodicBatch)])
        rhs_b = concat_periodic([b for r in results[self.lhs_count:]
                                 for b in r.batches
                                 if isinstance(b, PeriodicBatch)])
        op = self.operator
        if lhs_b is None:
            if op == BinaryOperator.LOR and rhs_b is not None:
                return [rhs_b]
            return []
        if rhs_b is None:
            return [] if op == BinaryOperator.LAND else [lhs_b]
        rset = {self._join_key(t) for t in rhs_b.keys}
        lv = lhs_b.np_values()
        if op == BinaryOperator.LAND:
            idx = [i for i, t in enumerate(lhs_b.keys)
                   if self._join_key(t) in rset]
            return [PeriodicBatch([lhs_b.keys[i] for i in idx], lhs_b.steps,
                                  lv[idx] if idx else np.empty((0, lv.shape[1])))]
        if op == BinaryOperator.LUNLESS:
            idx = [i for i, t in enumerate(lhs_b.keys)
                   if self._join_key(t) not in rset]
            return [PeriodicBatch([lhs_b.keys[i] for i in idx], lhs_b.steps,
                                  lv[idx] if idx else np.empty((0, lv.shape[1])))]
        # or: all of lhs + rhs series whose join key not present on lhs
        lset = {self._join_key(t) for t in lhs_b.keys}
        rv = rhs_b.np_values()
        ridx = [i for i, t in enumerate(rhs_b.keys)
                if self._join_key(t) not in lset]
        keys = list(lhs_b.keys) + [rhs_b.keys[i] for i in ridx]
        vals = np.concatenate([lv[:len(lhs_b.keys)],
                               rv[ridx] if ridx else np.empty((0, rv.shape[1]))])
        return [PeriodicBatch(keys, lhs_b.steps, vals)]


class ScalarBinaryOperationExec(LeafExecPlan):
    """Pure scalar arithmetic tree (reference:
    ScalarBinaryOperationExec.scala)."""

    def __init__(self, operator: BinaryOperator, lhs, rhs,
                 start_ms: int, step_ms: int, end_ms: int,
                 query_context=None, dispatcher: PlanDispatcher = IN_PROCESS):
        super().__init__(query_context, dispatcher)
        self.operator = operator
        self.lhs = lhs
        self.rhs = rhs
        self.steps = StepRange(start_ms, end_ms, step_ms)

    def _eval(self, side, ctx) -> np.ndarray:
        if isinstance(side, (int, float)):
            return np.full(self.steps.num_steps, float(side))
        if isinstance(side, lp.ScalarBinaryOperation):
            # nested scalar expression: evaluate inline (reference:
            # ScalarBinaryOperationExec evaluates nested operands itself)
            nested = ScalarBinaryOperationExec(
                side.operator, side.lhs, side.rhs, self.steps.start,
                self.steps.step, self.steps.end, self.query_context)
            lv = nested._eval(side.lhs, ctx)
            rv = nested._eval(side.rhs, ctx)
            return np.asarray(instant_ops.apply_binary(
                side.operator.name, lv, rv, False))
        if isinstance(side, lp.ScalarFixedDoublePlan):
            return np.full(self.steps.num_steps, float(side.scalar))
        res = side.execute(ctx) if isinstance(side, ExecPlan) else None
        if res is not None:
            b = res.batches[0]
            return np.asarray(b.values)
        raise QueryError("", f"bad scalar operand {side}")

    def do_execute(self, ctx):
        lv = self._eval(self.lhs, ctx)
        rv = self._eval(self.rhs, ctx)
        vals = np.asarray(instant_ops.apply_binary(self.operator.name, lv, rv,
                                                   False))
        return [ScalarResult(self.steps, vals)]


class LabelValuesDistConcatExec(NonLeafExecPlan):
    """Merge per-shard label-value maps."""

    def compose(self, results, ctx):
        merged: dict[str, set] = {}
        for r in results:
            for b in r.batches:
                if isinstance(b, dict):
                    for label, vals in b.items():
                        merged.setdefault(label, set()).update(vals)
        return [{label: sorted(v) for label, v in merged.items()}]


class PartKeysDistConcatExec(NonLeafExecPlan):
    def compose(self, results, ctx):
        seen = set()
        out = []
        for r in results:
            for b in r.batches:
                for tags in b:
                    k = tuple(sorted(tags.items()))
                    if k not in seen:
                        seen.add(k)
                        out.append(tags)
        return [out]
