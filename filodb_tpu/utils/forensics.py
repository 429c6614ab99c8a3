"""Query forensics: recent trace trees, slow-query log, on-demand profiler.

Grows the orphaned tracing layer (utils/observability.py) into the
subsystem the reference operates with: Kamon's span reporters feed a
trace view, the SpanLogReporter surfaces slow operations, and
SimpleProfiler answers "where is the time going right now"
(reference: KamonLogger.scala:146, SimpleProfiler.java).

Everything here is bounded and lock-cheap: the query path only appends
span records; trees are assembled at read time (/admin endpoints)."""

from __future__ import annotations

import collections
import random
import threading
import time
from typing import Optional, Sequence

from filodb_tpu.utils.observability import (SpanRecord, TRACER,
                                            query_metrics)


def span_to_dict(rec: SpanRecord) -> dict:
    """JSON-safe span for the /execplan response and admin endpoints."""
    return {"name": rec.name, "start_s": rec.start_s,
            "duration_s": rec.duration_s, "cpu_s": rec.cpu_s,
            "tags": {k: str(v) for k, v in rec.tags.items()},
            "error": rec.error, "trace_id": rec.trace_id,
            "span_id": rec.span_id, "parent_id": rec.parent_id}


def span_from_dict(d: dict) -> SpanRecord:
    return SpanRecord(d.get("name", ""), float(d.get("start_s", 0.0)),
                      float(d.get("duration_s", 0.0)),
                      dict(d.get("tags", {})), None,
                      error=d.get("error"), trace_id=d.get("trace_id"),
                      span_id=d.get("span_id", ""),
                      parent_id=d.get("parent_id"),
                      cpu_s=float(d.get("cpu_s", 0.0)))


class TraceStore:
    """Bounded store of completed spans grouped by trace id.

    Registered as a TRACER reporter: every span carrying a trace id
    lands here (spans without one — background flushes, gateway batches
    outside a query — are skipped).  ``ingest_remote`` merges the spans
    a data node returned with its /execplan response, so the
    coordinator holds ONE stitched tree per scatter-gather query."""

    def __init__(self, max_traces: int = 256, max_spans_per_trace: int = 512,
                 slowlog_size: int = 128,
                 slow_threshold_s: float = 1.0,
                 sample_rate: float = 0.0):
        self.max_traces = max_traces
        self.max_spans_per_trace = max_spans_per_trace
        self.slow_threshold_s = slow_threshold_s
        # head-sampling (ISSUE 19): retain this fraction of NORMAL
        # (sub-threshold) traces too, flagged sampled=true, so the
        # retained set is fleet-representative instead of slow-only.
        # Default 0 (off); runtime-adjustable via POST /admin/config
        # trace-sample-rate.
        self.sample_rate = float(sample_rate)
        self._traces: collections.OrderedDict[str, list[SpanRecord]] = \
            collections.OrderedDict()
        self._slowlog: collections.deque = collections.deque(
            maxlen=slowlog_size)
        self._lock = threading.Lock()

    # -------------------------------------------------------------- writes

    def report(self, recs: Sequence[SpanRecord]) -> None:
        """TRACER reporter hook (exceptions are swallowed upstream): the
        spans one thread finished since its last flush, under one taking
        of the lock."""
        traces = self._traces
        with self._lock:
            for rec in recs:
                if not rec.trace_id:
                    continue
                spans = traces.get(rec.trace_id)
                if spans is None:
                    spans = traces[rec.trace_id] = []
                    while len(traces) > self.max_traces:
                        traces.popitem(last=False)
                if len(spans) < self.max_spans_per_trace:
                    spans.append(rec)

    def ingest_remote(self, trace_id: str, spans: list[dict]) -> None:
        """Merge spans shipped back by a remote /execplan execution.
        Dedup by span id UNDER the lock: a node serving several leaves
        of one query returns its whole per-trace span set with each
        response, and two dispatch threads may merge concurrently."""
        recs = []
        for d in spans:
            try:
                rec = span_from_dict(d)
            except (TypeError, ValueError):
                continue
            rec.trace_id = trace_id
            recs.append(rec)
        with self._lock:
            cur = self._traces.get(trace_id)
            if cur is None:
                cur = self._traces[trace_id] = []
                while len(self._traces) > self.max_traces:
                    self._traces.popitem(last=False)
            have = {r.span_id for r in cur}
            for rec in recs:
                if rec.span_id and rec.span_id in have:
                    continue
                if len(cur) >= self.max_spans_per_trace:
                    break
                cur.append(rec)
                have.add(rec.span_id)

    def note_complete(self, trace_id: Optional[str], duration_s: float,
                      query: str = "", dataset: str = "",
                      error: Optional[str] = None) -> None:
        """Called once per finished query at the entry point; slow ones
        keep their whole span tree in the slow-query ring.  Fast ones
        are head-sampled at ``sample_rate`` (flagged sampled=true) so a
        low always-on fraction of NORMAL traces is retained too."""
        if not trace_id:
            return
        sampled = False
        if duration_s < self.slow_threshold_s:
            rate = self.sample_rate
            if rate <= 0.0 or random.random() >= rate:
                return
            sampled = True
        else:
            try:
                query_metrics()["slow_queries"].inc(dataset=dataset)
            except Exception:  # noqa: BLE001 — forensics never fails a query
                pass
        entry = {"trace_id": trace_id, "query": query, "dataset": dataset,
                 "duration_s": duration_s, "when_s": time.time(),
                 "error": error, "sampled": sampled,
                 "tree": self.tree(trace_id)}
        try:
            # a slow query DURING a recompile storm is usually slow
            # BECAUSE of it: flag the programs so the operator reading
            # /admin/slowlog doesn't chase the wrong stage (ISSUE 4)
            from filodb_tpu.utils.devicewatch import COMPILE_WATCH
            storms = COMPILE_WATCH.active_storms()
            if storms:
                entry["recompile_storms"] = storms
        except Exception:  # noqa: BLE001 — forensics never fails a query
            pass
        with self._lock:
            self._slowlog.append(entry)

    # --------------------------------------------------------------- reads

    def spans_for(self, trace_id: str) -> list[SpanRecord]:
        with self._lock:
            return list(self._traces.get(trace_id, ()))

    def trace_ids(self) -> list[str]:
        with self._lock:
            return list(self._traces)

    def tree(self, trace_id: str) -> list[dict]:
        """Spans nested by parent span id.  Spans whose parent is not in
        the trace (or None) are roots; remote subtrees therefore hang
        off the coordinator's dispatch span that minted their parent."""
        spans = self.spans_for(trace_id)
        by_id = {}
        for rec in spans:
            d = span_to_dict(rec)
            d["children"] = []
            by_id[rec.span_id] = d
        roots = []
        for rec in spans:
            node = by_id[rec.span_id]
            parent = by_id.get(rec.parent_id) if rec.parent_id else None
            if parent is not None and parent is not node:
                parent["children"].append(node)
            else:
                roots.append(node)
        for d in by_id.values():
            d["children"].sort(key=lambda c: c["start_s"])
        roots.sort(key=lambda c: c["start_s"])
        return roots

    def slowlog(self) -> list[dict]:
        with self._lock:
            return list(self._slowlog)

    def clear(self) -> None:
        with self._lock:
            self._traces.clear()
            self._slowlog.clear()


TRACE_STORE = TraceStore()
TRACER.add_reporter(TRACE_STORE.report)


# ONE single-flight guard for BOTH profile surfaces (/debug/profilez
# host stack sampling AND /debug/device_profilez jax device traces): a
# host sampling run and a device trace capture interleaving would
# attribute each other's overhead to the profiled workload (ISSUE 15)
_PROFILE_LOCK = threading.Lock()


class ProfilerBusy(RuntimeError):
    """A profile run is already in flight (single-flight guard)."""


class DeviceProfilerUnavailable(RuntimeError):
    """jax's profiler cannot run here (no jax / backend refused)."""


def profile(seconds: float = 2.0, sample_interval_s: float = 0.005,
            top_k: int = 30) -> dict:
    """Run the sampling profiler for ``seconds`` and return aggregated
    hot frames (the /debug/profilez payload; reference: SimpleProfiler
    launched at server start, here on demand).  Single-flight: the
    endpoint is unauthenticated and each run costs a sampling thread
    walking every stack, so concurrent requests are refused rather
    than stacked."""
    from filodb_tpu.utils.observability import SimpleProfiler
    if not _PROFILE_LOCK.acquire(blocking=False):
        raise ProfilerBusy("a profile run is already in progress")
    try:
        seconds = max(0.05, min(float(seconds), 60.0))
        prof = SimpleProfiler(sample_interval_s=sample_interval_s,
                              report_interval_s=1e9)
        prof.start()
        time.sleep(seconds)
        prof.stop()
    finally:
        _PROFILE_LOCK.release()
    counts = prof.snapshot()
    total = max(1, prof._samples)
    frames = [{"file": f.rsplit("/", 1)[-1], "function": fn,
               "samples": n, "pct": round(100.0 * n / total, 2)}
              for (f, fn), n in sorted(counts.items(),
                                       key=lambda kv: -kv[1])[:top_k]]
    return {"seconds": seconds, "samples": total, "frames": frames}


# how many device trace capture dirs to retain under the trace root:
# XLA traces of a busy device run tens to hundreds of MB and the host
# profiler's sibling endpoint writes nothing, so an unbounded capture
# dir would let a polling script fill the server's disk over a long
# incident — oldest captures are pruned before each new one
DEVICE_TRACE_RETAIN = 8


def device_profile(seconds: float = 2.0,
                   trace_root: Optional[str] = None) -> dict:
    """Capture a ``jax.profiler`` device trace for ``seconds`` into a
    server-side directory and return its path (the
    ``/debug/device_profilez`` payload; ISSUE 15) — the exact hook a
    training/inference stack needs to see what the accelerator actually
    executed (XLA program timeline, per-op device time), where the host
    profiler above only sees the Python frames waiting on it.

    Single-flight on the SAME ``_PROFILE_LOCK`` as :func:`profile`:
    the two captures interleaving would attribute each other's
    overhead.  The sleep inside the held lock is the design — the lock
    IS the "one profile at a time" contract, acquired non-blocking so
    contenders get ``ProfilerBusy`` (HTTP 503) instead of queueing."""
    import os
    import tempfile
    if not _PROFILE_LOCK.acquire(blocking=False):
        raise ProfilerBusy("a profile run is already in progress")
    try:
        seconds = max(0.05, min(float(seconds), 60.0))
        try:
            import jax
            profiler = jax.profiler
        except Exception as e:  # noqa: BLE001 — host-only deployment
            raise DeviceProfilerUnavailable(
                f"jax profiler unavailable: {e}") from e
        root = trace_root or os.path.join(tempfile.gettempdir(),
                                          "filodb-device-traces")
        os.makedirs(root, exist_ok=True)
        _prune_trace_dirs(root, keep=DEVICE_TRACE_RETAIN - 1)
        path = tempfile.mkdtemp(
            prefix=time.strftime("trace-%Y%m%d-%H%M%S-"), dir=root)
        try:
            # as benchmark/run.py starts it: the Python tracer off (it
            # would slow the very threads it records), the host tracer
            # at the level that keeps TraceMe events — the leaf stage
            # spans (TRACER.stage) land on the capture's host plane
            opts = profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            profiler.start_trace(path, profiler_options=opts)
        except Exception as e:  # noqa: BLE001 — backend refused
            raise DeviceProfilerUnavailable(
                f"device trace capture failed to start: {e}") from e
        try:
            time.sleep(seconds)
        finally:
            try:
                profiler.stop_trace()
            except Exception:  # noqa: BLE001 — capture dir still useful
                pass
        files = sum(len(fs) for _r, _d, fs in os.walk(path))
        return {"seconds": seconds, "trace_dir": path, "files": files,
                "retained": DEVICE_TRACE_RETAIN}
    finally:
        _PROFILE_LOCK.release()


def _prune_trace_dirs(root: str, keep: int) -> None:
    """Drop the oldest capture dirs so at most ``keep`` remain (the
    timestamped ``trace-*`` prefix makes lexical order chronological).
    Runs under the profile lock, so captures never race the sweep."""
    import os
    import shutil
    try:
        dirs = sorted(e for e in os.listdir(root)
                      if e.startswith("trace-")
                      and os.path.isdir(os.path.join(root, e)))
    except OSError:
        return
    for name in dirs[:max(0, len(dirs) - max(0, keep))]:
        try:
            shutil.rmtree(os.path.join(root, name))
        except OSError:  # noqa: PERF203 — an operator mid-copy wins
            pass
