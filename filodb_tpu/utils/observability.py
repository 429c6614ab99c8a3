"""Observability: metrics registry, tracing spans, sampling profiler.

Capability match for the reference's Kamon-based instrumentation
(reference: coordinator/.../KamonLogger.scala:146 metric/span log
reporters; Kamon.spanBuilder use throughout ExecPlan.execute
ExecPlan.scala:99-126 and flush TimeSeriesShard.scala:888-891;
core/.../Perftools.scala:53 timing spans; standalone/.../
SimpleProfiler.java sampling profiler launched at server start).

Everything is stdlib: counters/gauges/histograms with Prometheus text
exposition (replacing Kamon's embedded Prometheus server), thread-local
span stacks with a pluggable reporter, and a sys._current_frames-based
sampling profiler."""

from __future__ import annotations

import bisect
import collections
import dataclasses
import itertools
import json
import os
import random
import sys
import threading
import time
import traceback
from typing import Callable, Mapping, Optional, Sequence

# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------

_BUCKETS = (0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 10.0, 30.0)


class Counter:
    def __init__(self, name: str, help_: str = ""):
        self.name, self.help = name, help_
        self._values: dict[tuple, float] = collections.defaultdict(float)
        self._lock = threading.Lock()

    def inc(self, amount: float = 1.0, **labels) -> None:
        key = tuple(sorted(labels.items()))
        with self._lock:
            self._values[key] += amount

    def value(self, **labels) -> float:
        return self._values.get(tuple(sorted(labels.items())), 0.0)

    def total(self) -> float:
        """Sum across every label set (admin summaries)."""
        with self._lock:
            return sum(self._values.values())

    def expose(self) -> list[str]:
        with self._lock:  # concurrent inc() may insert new label sets
            items = sorted(self._values.items())
        out = [f"# TYPE {self.name} counter"]
        for key, v in items:
            out.append(f"{self.name}{_fmt_labels(key)} {_fmt_val(v)}")
        return out


class Gauge:
    def __init__(self, name: str, help_: str = ""):
        self.name, self.help = name, help_
        self._values: dict[tuple, float] = {}
        self._fns: dict[tuple, Callable[[], float]] = {}
        self._lock = threading.Lock()

    def set(self, value: float, **labels) -> None:
        with self._lock:
            self._values[tuple(sorted(labels.items()))] = value

    def set_fn(self, fn: Callable[[], float], **labels) -> None:
        """Lazily-sampled gauge (e.g. memory usage at scrape time)."""
        with self._lock:
            self._fns[tuple(sorted(labels.items()))] = fn

    def value(self, **labels) -> float:
        key = tuple(sorted(labels.items()))
        if key in self._fns:
            return float(self._fns[key]())
        return self._values.get(key, 0.0)

    def remove(self, **labels) -> None:
        """Drop one label set (both value and set_fn).  Components that
        register bound-method callbacks MUST call this on shutdown or
        the registry keeps them (and everything they capture) alive and
        keeps exporting rows for dead instances."""
        key = tuple(sorted(labels.items()))
        with self._lock:
            self._fns.pop(key, None)
            self._values.pop(key, None)

    def total(self) -> float:
        """Sum across every label set (admin summaries).  ``set_fn``
        callbacks run OUTSIDE the gauge lock: a callback that touches
        this same gauge (or blocks on something that does) must not
        deadlock the scrape."""
        with self._lock:
            vals = sum(self._values.values())
            fns = list(self._fns.values())
        return vals + sum(fn() for fn in fns)

    def expose(self) -> list[str]:
        out = [f"# TYPE {self.name} gauge"]
        with self._lock:  # snapshot under the lock, call fns outside it
            vals = list(self._values.items())
            fns = list(self._fns.items())
        items = vals + [(k, fn()) for k, fn in fns]
        for key, v in sorted(items):
            out.append(f"{self.name}{_fmt_labels(key)} {_fmt_val(v)}")
        return out


class Histogram:
    """Cumulative-bucket histogram (seconds by convention)."""

    def __init__(self, name: str, help_: str = "",
                 buckets: Sequence[float] = _BUCKETS):
        self.name, self.help = name, help_
        self.buckets = tuple(sorted(buckets))
        # per-bucket RAW counts (one extra slot for > last bucket);
        # observe() is on every hot path, so it does ONE bisect + ONE
        # increment — the cumulative le-counts are computed at expose()
        self._counts: dict[tuple, list[int]] = {}
        self._sums: dict[tuple, float] = collections.defaultdict(float)
        self._totals: dict[tuple, int] = collections.defaultdict(int)
        self._lock = threading.Lock()

    def observe(self, value: float, **labels) -> None:
        key = tuple(sorted(labels.items()))
        # first bucket b with value <= b (buckets are sorted ascending);
        # len(buckets) = the +Inf overflow slot
        i = bisect.bisect_left(self.buckets, value)
        with self._lock:
            counts = self._counts.get(key)
            if counts is None:
                counts = self._counts[key] = [0] * (len(self.buckets) + 1)
            counts[i] += 1
            self._sums[key] += value
            self._totals[key] += 1

    def expose(self) -> list[str]:
        with self._lock:  # concurrent observe() may insert new label sets
            counts = {k: list(v) for k, v in self._counts.items()}
            sums = dict(self._sums)
            totals = dict(self._totals)
        out = [f"# TYPE {self.name} histogram"]
        for key in sorted(counts):
            cum = 0
            for i, b in enumerate(self.buckets):
                cum += counts[key][i]
                lk = key + (("le", repr(b)),)
                out.append(f"{self.name}_bucket{_fmt_labels(lk)} {cum}")
            lk = key + (("le", "+Inf"),)
            out.append(f"{self.name}_bucket{_fmt_labels(lk)} {totals[key]}")
            out.append(f"{self.name}_sum{_fmt_labels(key)} "
                       f"{_fmt_val(sums[key])}")
            out.append(f"{self.name}_count{_fmt_labels(key)} {totals[key]}")
        return out


def _escape_label(v) -> str:
    """Prometheus exposition escaping: backslash, quote, newline."""
    return str(v).replace("\\", "\\\\").replace('"', '\\"') \
        .replace("\n", "\\n")


def _fmt_labels(key: tuple) -> str:
    if not key:
        return ""
    inner = ",".join(f'{k}="{_escape_label(v)}"' for k, v in key)
    return "{" + inner + "}"


def _fmt_val(v: float) -> str:
    import math
    if math.isnan(v):
        return "NaN"
    if math.isinf(v):
        return "+Inf" if v > 0 else "-Inf"
    return str(int(v)) if v == int(v) else repr(v)


class MetricsRegistry:
    """Process-wide named metrics + Prometheus text exposition (replaces
    Kamon's metric registry + embedded Prometheus reporter)."""

    def __init__(self) -> None:
        self._metrics: dict[str, object] = {}
        self._lock = threading.Lock()

    def counter(self, name: str, help_: str = "") -> Counter:
        return self._get(name, lambda: Counter(name, help_), Counter)

    def gauge(self, name: str, help_: str = "") -> Gauge:
        return self._get(name, lambda: Gauge(name, help_), Gauge)

    def histogram(self, name: str, help_: str = "",
                  buckets: Sequence[float] = _BUCKETS) -> Histogram:
        return self._get(name, lambda: Histogram(name, help_, buckets),
                         Histogram)

    def collector(self, name: str, obj) -> None:
        """Register anything with ``expose() -> list[str]`` under
        ``name`` (a table that keeps its own totals)."""
        self._get(name, lambda: obj, type(obj))

    def _get(self, name, ctor, cls):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = ctor()
            elif not isinstance(m, cls):
                raise TypeError(f"metric {name} already registered as "
                                f"{type(m).__name__}")
            return m

    def expose_text(self) -> str:
        """Prometheus text format for a /metrics endpoint."""
        lines = []
        with self._lock:
            metrics = list(self._metrics.values())
        for m in metrics:
            lines.extend(m.expose())
        return "\n".join(lines) + "\n"


REGISTRY = MetricsRegistry()


def integrity_metrics() -> dict:
    """Canonical integrity counters (filodb_tpu/integrity): one place
    defines the metric names so the corruption funnel, the /metrics
    exposition, and /admin/integrity can never drift apart.  Labels:
    ``dataset``/``shard`` when the detection site knows them."""
    return {
        "checksum_failures": REGISTRY.counter(
            "filodb_integrity_checksum_failures_total",
            "chunk blobs whose stored CRC32C did not match on read-back"),
        "decode_failures": REGISTRY.counter(
            "filodb_integrity_decode_failures_total",
            "chunk vectors whose native/numpy decode hit a -1 sentinel"),
        "chunks_verified": REGISTRY.counter(
            "filodb_integrity_chunks_verified_total",
            "chunk blobs checksum-verified on page-in/read-back"),
        "chunks_quarantined": REGISTRY.gauge(
            "filodb_integrity_quarantined_chunks",
            "chunks currently excluded from serving by the quarantine"),
        "invariant_failures": REGISTRY.counter(
            "filodb_integrity_invariant_failures_total",
            "eviction/reclaim bookkeeping invariant violations"),
        "partial_queries": REGISTRY.counter(
            "filodb_integrity_partial_query_results_total",
            "queries answered with a partial-data warning"),
    }


def query_metrics() -> dict:
    """Canonical query-pipeline metrics (ISSUE 2): one place defines the
    names so the HTTP layer, scheduler, and docs can never drift."""
    return {
        "request_seconds": REGISTRY.histogram(
            "filodb_query_request_seconds",
            "HTTP route handler latency by endpoint"),
        "requests": REGISTRY.counter(
            "filodb_query_requests_total",
            "HTTP requests by endpoint and status code"),
        "run_seconds": REGISTRY.histogram(
            "filodb_query_run_seconds",
            "query execution time on a scheduler worker (excl. queue)"),
        "slow_queries": REGISTRY.counter(
            "filodb_query_slow_total",
            "completed queries over the slow-query threshold"),
        "execplan_seconds": REGISTRY.histogram(
            "filodb_query_execplan_remote_seconds",
            "remote /execplan leaf execution latency"),
        "hbm_read_bytes": REGISTRY.counter(
            "filodb_query_hbm_read_bytes_total",
            "device-grid HBM bytes read serving queries, by resident "
            "format (label format=dense|compressed)"),
    }


def ingest_metrics() -> dict:
    """Canonical gateway-ingest metrics."""
    return {
        "samples": REGISTRY.counter(
            "filodb_ingest_samples_total",
            "samples accepted by the gateway sharding publisher"),
        "parse_errors": REGISTRY.counter(
            "filodb_ingest_parse_errors_total",
            "malformed influx lines rejected by the gateway"),
        "batch_seconds": REGISTRY.histogram(
            "filodb_ingest_batch_seconds",
            "gateway batch ingest latency (parse -> route -> build)"),
        "replica_publishes": REGISTRY.counter(
            "filodb_ingest_replica_publishes_total",
            "containers delivered per replica by the dual-write fanout"),
        "replica_publish_failures": REGISTRY.counter(
            "filodb_ingest_replica_publish_failures_total",
            "per-replica container deliveries that failed (the replica "
            "lags and must catch up from its checkpoint/broker)"),
        "series": REGISTRY.counter(
            "filodb_ingest_series_total",
            "series of the containers a shard ingested, by the path they "
            "took (path=bulk: a container at a time; path=series: one "
            "at a time)"),
    }


def flush_metrics() -> dict:
    """Canonical memstore-flush metrics."""
    return {
        "flush_seconds": REGISTRY.histogram(
            "filodb_flush_seconds",
            "run_flush_task latency (encode + IO + checkpoint)"),
        "chunks": REGISTRY.counter(
            "filodb_flush_chunks_total", "chunksets written by flushes"),
        "failures": REGISTRY.counter(
            "filodb_flush_failures_total",
            "flush tasks that raised (work requeued)"),
        # ISSUE 6 satellite: the pipeline's backlog was never observable
        "queue_depth": REGISTRY.gauge(
            "filodb_flush_queue_depth",
            "flush tasks submitted but not yet completed, per shard"),
        "last_age": REGISTRY.gauge(
            "filodb_flush_last_age_seconds",
            "seconds since the most recent completed flush on any group "
            "of the shard (since scheduler start when none completed)"),
    }


def index_metrics() -> dict:
    """Canonical part-key-index cardinality metrics (ISSUE 6): active
    series, per-tenant occupancy, and series churn — one place defines
    the names so the tracker, /admin/cardinality, and
    doc/observability.md can never drift."""
    return {
        "active_series": REGISTRY.gauge(
            "filodb_index_cardinality_active_series",
            "series currently alive in the part-key index, per shard"),
        "labels": REGISTRY.gauge(
            "filodb_index_cardinality_labels",
            "distinct label names carried by alive series, per shard"),
        "tenant_series": REGISTRY.gauge(
            "filodb_index_cardinality_tenant_series",
            "alive series per tenant (tenant-label value; untagged "
            "series pool under the empty tenant)"),
        "created": REGISTRY.counter(
            "filodb_index_churn_created_total",
            "new series assigned a part id, per shard"),
        "removed": REGISTRY.counter(
            "filodb_index_churn_removed_total",
            "series removed from the index, per shard and reason "
            "(evict | purge)"),
        "create_rate": REGISTRY.gauge(
            "filodb_index_churn_create_rate_per_s",
            "exponentially-decayed series-creation rate, per shard"),
        "remove_rate": REGISTRY.gauge(
            "filodb_index_churn_remove_rate_per_s",
            "exponentially-decayed series-removal rate, per shard"),
    }


def watermark_metrics() -> dict:
    """Canonical ingest-watermark metrics (ISSUE 6): the per-shard
    monotone offset chain broker_end -> ingested -> flushed ->
    checkpoint, its lag in rows and seconds, and stall detection."""
    return {
        "offset": REGISTRY.gauge(
            "filodb_ingest_watermark_offset",
            "per-shard ingest watermark chain by stage "
            "(broker_end | ingested | flushed | checkpoint)"),
        "lag_rows": REGISTRY.gauge(
            "filodb_ingest_lag_rows",
            "records the broker holds that this shard has not ingested"),
        "lag_seconds": REGISTRY.gauge(
            "filodb_ingest_lag_seconds",
            "seconds since the shard's newest ingested sample, while "
            "row lag is nonzero (0 when caught up)"),
        "stalls": REGISTRY.counter(
            "filodb_ingest_stalls_total",
            "stall episodes: a lagging shard whose ingested offset made "
            "no progress for the stall window"),
        "stalled": REGISTRY.gauge(
            "filodb_ingest_stalled",
            "1 while the shard counts as stalled, else 0 — the level "
            "the self-monitoring alert rules watch (a counter's label "
            "set is born at 1, invisible to increase())"),
    }


def split_metrics() -> dict:
    """Elastic-resharding metrics (ISSUE 13, coordinator/split.py):
    phase progression, child replay volume, cutover latency, aborts."""
    return {
        "phase": REGISTRY.gauge(
            "filodb_split_phase",
            "live shard-split phase as a code: 0=none 1=prepare "
            "2=catchup 3=serving(cutover done) 4=retire 5=complete "
            "6=aborted"),
        "replayed_rows": REGISTRY.gauge(
            "filodb_split_replayed_rows",
            "rows the split children have ingested so far (catch-up "
            "replay + dual-ingested live rows, summed across local "
            "children)"),
        "cutover_seconds": REGISTRY.gauge(
            "filodb_split_cutover_seconds",
            "wall seconds the last cutover took from gate-pass to the "
            "committed topology flip"),
        "aborts": REGISTRY.counter(
            "filodb_split_aborts_total",
            "split aborts (lossless rollbacks to the parent topology)"),
        "generation": REGISTRY.gauge(
            "filodb_split_generation",
            "the dataset's current topology generation (bumps on "
            "prepare / cutover / retire-complete / abort)"),
    }


def shard_health_metrics() -> dict:
    """Canonical shard-status metrics (ISSUE 6): numeric status code,
    recovery progress, and transition counts, emitted by
    ShardMapper.update_status on every real change."""
    return {
        "status_code": REGISTRY.gauge(
            "filodb_shard_status_code",
            "shard status as a code: 0=Unassigned 1=Assigned 2=Recovery "
            "3=Active 4=Error 5=Stopped 6=Down"),
        "recovery_progress": REGISTRY.gauge(
            "filodb_shard_recovery_progress",
            "recovery replay progress percent (0 outside recovery)"),
        "transitions": REGISTRY.counter(
            "filodb_shard_status_transitions_total",
            "status transitions by dataset and new status"),
        "replica_status_code": REGISTRY.gauge(
            "filodb_shard_replica_status_code",
            "per-REPLICA shard status code (same encoding as "
            "filodb_shard_status_code), keyed by holding node"),
    }


def selfscrape_metrics() -> dict:
    """Canonical self-telemetry metrics (ISSUE 6): the node scraping its
    own /metrics exposition into the ``_system`` dataset."""
    return {
        "scrapes": REGISTRY.counter(
            "filodb_selfscrape_scrapes_total",
            "self-scrape passes over the node's own exposition"),
        "samples": REGISTRY.counter(
            "filodb_selfscrape_samples_total",
            "samples published into the self-telemetry dataset"),
        "errors": REGISTRY.counter(
            "filodb_selfscrape_errors_total",
            "self-scrape passes that raised (skipped, never fatal)"),
        "duration": REGISTRY.gauge(
            "filodb_selfscrape_last_scrape_seconds",
            "wall time of the most recent self-scrape pass"),
    }


def workload_metrics() -> dict:
    """Canonical workload-management metrics (ISSUE 5): admission,
    cardinality quotas, deadline enforcement, and dispatch retry/hedge —
    one place defines the names so the controller, the shards, the
    gateway edge, and doc/workload.md can never drift."""
    return {
        "admitted": REGISTRY.counter(
            "filodb_admission_admitted_total",
            "queries admitted, by dataset and priority class"),
        "rejected": REGISTRY.counter(
            "filodb_admission_rejected_total",
            "queries shed with 429, by dataset/priority/reason "
            "(expired|deadline|overload|tenant_concurrency|tenant_cost)"),
        "inflight_cost": REGISTRY.gauge(
            "filodb_admission_inflight_cost",
            "estimated cost units currently admitted and running"),
        "estimated_cost": REGISTRY.histogram(
            "filodb_admission_estimated_cost_units",
            "pre-execution cost estimate per query (series-chunk units)",
            buckets=(1, 10, 100, 1_000, 10_000, 100_000, 1_000_000)),
        "sched_expired": REGISTRY.counter(
            "filodb_query_sched_expired_total",
            "queries dropped at dequeue because their deadline expired "
            "while queued (never executed)"),
        "deadline_refused": REGISTRY.counter(
            "filodb_query_deadline_refused_total",
            "remote /execplan work refused because the remaining budget "
            "could not cover it"),
        "partial_shards": REGISTRY.counter(
            "filodb_query_partial_shard_results_total",
            "queries answered partially because >=1 shard was down "
            "(allow_partial_results)"),
        "dispatch_retries": REGISTRY.counter(
            "filodb_dispatch_retries_total",
            "remote dispatch attempts retried after connection errors"),
        "dispatch_hedged": REGISTRY.counter(
            "filodb_dispatch_hedged_total",
            "remote dispatches that launched a hedged second request"),
        "dispatch_hedge_wins": REGISTRY.counter(
            "filodb_dispatch_hedge_wins_total",
            "hedged dispatches where the SECOND request answered first"),
        "dispatch_failures": REGISTRY.counter(
            "filodb_dispatch_failures_total",
            "remote dispatches that failed after exhausting retries"),
        "dispatch_failover": REGISTRY.counter(
            "filodb_dispatch_failover_total",
            "leaf dispatches retargeted at another replica, by reason "
            "(refused|unreachable|no_endpoint|hedge_retarget)"),
        "quota_active": REGISTRY.gauge(
            "filodb_quota_active_series",
            "active (alive-in-index) series per dataset/tenant"),
        "quota_limit": REGISTRY.gauge(
            "filodb_quota_limit_series",
            "configured active-series limit per dataset/tenant"),
        "quota_rejected": REGISTRY.counter(
            "filodb_quota_rejected_series_total",
            "new series rejected because their tenant is over quota"),
        "quota_dropped_samples": REGISTRY.counter(
            "filodb_quota_dropped_samples_total",
            "samples dropped (edge or shard) for over-quota new series"),
    }


def rule_metrics() -> dict:
    """Canonical rule-engine metrics (ISSUE 9, filodb_tpu/rules): group
    evaluation health, write-back volume, alert state transitions,
    notifier outcomes, and incremental-window residency — one place
    defines the names so the engine, /admin/rules, and doc/rules.md can
    never drift."""
    return {
        "eval_seconds": REGISTRY.histogram(
            "filodb_rule_eval_seconds",
            "wall time of one rule-group evaluation pass, per group"),
        "evals": REGISTRY.counter(
            "filodb_rule_evals_total",
            "rule evaluations by group and outcome (ok | failed)"),
        "missed": REGISTRY.counter(
            "filodb_rule_evals_missed_total",
            "scheduled group evaluations skipped because the previous "
            "pass overran the interval"),
        "lag": REGISTRY.gauge(
            "filodb_rule_eval_lag_seconds",
            "how far the group's last pass started behind its cadence"),
        "last_eval": REGISTRY.gauge(
            "filodb_rule_last_eval_timestamp_seconds",
            "unix time of the group's most recent evaluation pass"),
        "samples": REGISTRY.counter(
            "filodb_rule_samples_written_total",
            "recorded/ALERTS samples written back through the gateway "
            "publisher, per group"),
        "stale": REGISTRY.counter(
            "filodb_rule_series_stale_total",
            "recording-rule output series that vanished between "
            "evaluations (export stopped, state dropped)"),
        "transitions": REGISTRY.counter(
            "filodb_rule_alert_transitions_total",
            "alert state transitions by group and new state "
            "(pending | firing | resolved | inactive)"),
        "alerts_active": REGISTRY.gauge(
            "filodb_rule_alerts",
            "alert instances currently held, by group and state"),
        "notifications": REGISTRY.counter(
            "filodb_rule_notifications_total",
            "webhook notifier sends by outcome "
            "(delivered | failed | dropped)"),
        "notify_retries": REGISTRY.counter(
            "filodb_rule_notification_retries_total",
            "webhook delivery attempts retried after an error"),
        "incr_samples": REGISTRY.counter(
            "filodb_rule_incremental_samples_total",
            "newly-arrived samples consumed by incremental window "
            "state (vs re-scanning the full range), per group"),
        "incr_series": REGISTRY.gauge(
            "filodb_rule_incremental_series",
            "input series currently resident in incremental window "
            "state, per group"),
    }


def rollup_metrics() -> dict:
    """Canonical rollup-subsystem metrics (filodb_tpu/rollup): tick
    health, tier lag/stall, emission volume, routing — one place
    defines the names so the engine, the router, /admin/rollup, and
    doc/rollup.md can never drift."""
    return {
        "passes": REGISTRY.counter(
            "filodb_rollup_passes_total",
            "rollup scheduler passes completed, per dataset"),
        "pass_seconds": REGISTRY.histogram(
            "filodb_rollup_pass_seconds",
            "wall time of one rollup pass (consume + reduce + emit)"),
        "samples": REGISTRY.counter(
            "filodb_rollup_samples_written_total",
            "rolled records emitted into the tier datasets, per "
            "dataset and resolution"),
        "lag": REGISTRY.gauge(
            "filodb_rollup_lag_seconds",
            "newest consumed raw sample time minus the tier's newest "
            "emitted period stamp, per dataset/shard/resolution"),
        "errors": REGISTRY.counter(
            "filodb_rollup_tier_errors_total",
            "tier emission passes that raised (retried next tick)"),
        "deferred": REGISTRY.counter(
            "filodb_rollup_deferred_total",
            "rollup passes deferred by admission control (the rollup "
            "class yielded to user traffic)"),
        "stalled": REGISTRY.gauge(
            "filodb_rollup_stalled",
            "1 while a tier makes no progress past the stall window "
            "with work pending, else 0 — the LEVEL the self-monitoring "
            "alert rules watch (a counter's label set is born at 1, "
            "invisible to increase())"),
        "buffered": REGISTRY.gauge(
            "filodb_rollup_buffered_samples",
            "raw samples resident in rollup closure buffers, per "
            "dataset/shard"),
        "routed": REGISTRY.counter(
            "filodb_rollup_queries_routed_total",
            "queries the resolution router served from a rolled tier, "
            "per dataset and resolution (resolution=raw counts "
            "rollup-eligible queries that stayed raw)"),
        "tier_served": REGISTRY.counter(
            "filodb_rollup_tier_legs_total",
            "stitch legs materialized per storage tier "
            "(raw | rolled-local | rolled-cold), per dataset — a "
            "stitched query counts once per tier it actually read"),
    }


def resultcache_metrics() -> dict:
    """Canonical query-frontend result-cache metrics
    (query/resultcache.py): hit/miss traffic, resident bytes, LRU
    evictions, and epoch/digest invalidations — one place defines the
    names so the cache, /admin/resultcache, and doc/observability.md
    can never drift."""
    return {
        "hits": REGISTRY.counter(
            "filodb_resultcache_hits_total",
            "queries (or query segments) served from memoized partials, "
            "per dataset and kind (range segment | instant window)"),
        "misses": REGISTRY.counter(
            "filodb_resultcache_misses_total",
            "cacheable segments/windows that had to be computed fresh, "
            "per dataset and kind"),
        "skipped": REGISTRY.counter(
            "filodb_resultcache_skipped_total",
            "queries that bypassed the cache, per dataset and reason "
            "(shape|remote|range|open|instant-*)"),
        "bytes": REGISTRY.gauge(
            "filodb_resultcache_bytes",
            "resident bytes of memoized partials + instant window "
            "state, per dataset (reconciles exactly with a walk of the "
            "live entries)"),
        "evictions": REGISTRY.counter(
            "filodb_resultcache_evictions_total",
            "entries dropped to stay under the byte budget, per "
            "dataset and reason"),
        "invalidations": REGISTRY.counter(
            "filodb_resultcache_invalidations_total",
            "entries discarded / window states reset because their "
            "validity inputs changed, per dataset and reason "
            "(chunks|quarantine|routing|series|regressed)"),
        "bypass": REGISTRY.counter(
            "filodb_result_cache_bypass_total",
            "range/instant queries that bypassed the result cache "
            "entirely, per dataset and reason (remote = plan spans "
            "non-local shards, the known federation coherence gap; "
            "disabled = cache switched off; unfingerprintable = shape "
            "has no canonical fingerprint)"),
    }


def odp_metrics() -> dict:
    """Canonical on-demand-paging metrics."""
    return {
        "pagein_seconds": REGISTRY.histogram(
            "filodb_odp_pagein_seconds",
            "page-in latency (store read + decode + materialize)"),
        "partitions": REGISTRY.counter(
            "filodb_odp_partitions_paged_total",
            "partitions re-materialized from the column store"),
        "chunks": REGISTRY.counter(
            "filodb_odp_chunks_paged_total",
            "chunks read back from the column store"),
    }


def coldstore_metrics() -> dict:
    """Canonical cold-tier metrics (filodb_tpu/coldstore): bucket fetch
    traffic + failure classes, age-out volume, and the per-shard
    watermark level — one place defines the names so the store, the
    age-out loop, cli verbs, and doc/coldstore.md can never drift."""
    return {
        "fetches": REGISTRY.counter(
            "filodb_coldstore_fetches_total",
            "objects fetched from the cold bucket (cache-miss reads; "
            "prefetched objects count once, at prefetch time)"),
        "fetch_bytes": REGISTRY.counter(
            "filodb_coldstore_fetch_bytes_total",
            "object bytes fetched from the cold bucket"),
        "fetch_corrupt": REGISTRY.counter(
            "filodb_coldstore_fetch_corrupt_total",
            "fetched objects failing their key CRC (truncated or "
            "bit-rotted in the bucket) — quarantined, never served, "
            "per dataset"),
        "fetch_timeouts": REGISTRY.counter(
            "filodb_coldstore_fetch_timeouts_total",
            "fetches refused because the deadline-derived timeout "
            "expired (stalled backend or exhausted query budget)"),
        "fetch_missing": REGISTRY.counter(
            "filodb_coldstore_fetch_missing_total",
            "fetches of objects deleted between listing and get "
            "(served as absent rows, not errors)"),
        "aged_chunks": REGISTRY.counter(
            "filodb_coldstore_aged_chunks_total",
            "chunk rows migrated local -> cold by age-out passes, "
            "per dataset"),
        "aged_bytes": REGISTRY.counter(
            "filodb_coldstore_aged_bytes_total",
            "blob bytes migrated local -> cold, per dataset"),
        "watermark": REGISTRY.gauge(
            "filodb_coldstore_ageout_watermark_ms",
            "cutoff (epoch ms) of the last completed age-out pass, per "
            "dataset/shard — chunks ending before it are archived"),
    }


def downsample_metrics() -> dict:
    """Visualization downsampling (?downsample=<pixels>, ops/grid.py
    m4_grid): how often panels opt in and the point-volume reduction."""
    return {
        "queries": REGISTRY.counter(
            "filodb_downsample_queries_total",
            "range queries that requested M4 pixel downsampling"),
        "points_in": REGISTRY.counter(
            "filodb_downsample_points_in_total",
            "finite samples entering the downsampler"),
        "points_out": REGISTRY.counter(
            "filodb_downsample_points_out_total",
            "pixel-exact samples kept (<= 4 per pixel bin per series)"),
    }


def insights_metrics() -> dict:
    """Canonical workload-insights metrics (ISSUE 19,
    filodb_tpu/insights): ledger volume + the fleet aggregator's poll
    health — one place defines the names so the ledger,
    /admin/insights, /admin/fleet, and doc/observability.md can never
    drift."""
    return {
        "noted": REGISTRY.counter(
            "filodb_insights_queries_total",
            "query completions folded into the workload ledger, per "
            "dataset and outcome (ok | error | shed)"),
        "fingerprints": REGISTRY.gauge(
            "filodb_insights_fingerprints",
            "distinct plan fingerprints resident in the ledger, per "
            "node (bounded; evictions show in *_dropped_total)"),
        "dropped": REGISTRY.counter(
            "filodb_insights_dropped_total",
            "least-recently-updated fingerprint entries evicted to "
            "stay under the ledger bound, per node"),
        "fleet_polls": REGISTRY.counter(
            "filodb_insights_fleet_polls_total",
            "fleet-aggregator snapshot fetches, per peer and outcome "
            "(ok | error)"),
    }


def batch_metrics() -> dict:
    """Canonical fleet-batching metrics (ISSUE 20,
    filodb_tpu/batching): realized vmapped group sizes next to the
    ledger's co-arrival headroom estimate, plus the fallback ladder —
    one place defines the names so the batcher, /admin/insights,
    doc/observability.md and the benchmark's ``device_dispatches``
    (benchmark/run.py reads ``filodb_batch_members_total``) agree."""
    return {
        "groups": REGISTRY.counter(
            "filodb_batch_groups_total",
            "batched (vmapped) device launches serving >= 2 queries, "
            "per dataset"),
        "members": REGISTRY.counter(
            "filodb_batch_members_total",
            "queries served from a batched launch, per dataset "
            "(members/groups = realized mean batch size)"),
        "fallbacks": REGISTRY.counter(
            "filodb_batch_fallbacks_total",
            "dispatches demoted to the per-query chain, per dataset "
            "and reason (breaker | deadline | solo-window | "
            "member-expired | timeout | error)"),
        "peak": REGISTRY.gauge(
            "filodb_batch_realized_peak",
            "largest realized batch size since start, per dataset "
            "(compare against the insights ledger's co-arrival peak)"),
    }


def slo_metrics() -> dict:
    """Canonical tenant-SLO metrics (ISSUE 19, insights/slo.py).  The
    burn rates are LEVEL gauges on purpose — the filodb_ingest_stalled
    lesson: a counter's label set is born at 1, invisible to a rules
    ``increase()``, while a pre-registered gauge row shows the full
    0 -> burning edge to the self-monitoring alert rules."""
    return {
        "requests": REGISTRY.counter(
            "filodb_slo_requests_total",
            "queries matched against an SLO objective, per "
            "objective/tenant/node"),
        "breaches": REGISTRY.counter(
            "filodb_slo_breaches_total",
            "matched queries that were BAD (errored or exceeded the "
            "objective's latency threshold)"),
        "fast_burn": REGISTRY.gauge(
            "filodb_slo_fast_burn",
            "error-budget burn rate over the fast window (bad fraction "
            "/ budget); the SLO rule pack pages above 14.4"),
        "slow_burn": REGISTRY.gauge(
            "filodb_slo_slow_burn",
            "error-budget burn rate over the slow window; the SLO "
            "rule pack warns above 6"),
        "budget": REGISTRY.gauge(
            "filodb_slo_error_budget",
            "configured error budget (1 - availability target) per "
            "objective — a constant level, exported so dashboards can "
            "plot burn against it"),
    }


# ---------------------------------------------------------------------------
# Process-level metrics (ISSUE 4 satellite): node dashboards read RSS /
# FDs / threads / uptime / GC pressure from the SAME /metrics endpoint,
# no separate node exporter required.  All gauges are set_fn-sampled at
# scrape time; /proc reads are linux-only and degrade to 0 elsewhere.
# ---------------------------------------------------------------------------

_PROCESS_START_S = time.time()
_PAGE_SIZE = 4096
try:
    import os as _os
    _PAGE_SIZE = _os.sysconf("SC_PAGE_SIZE")
except (ImportError, ValueError, OSError):  # pragma: no cover - non-posix
    pass


def _rss_bytes() -> float:
    try:
        with open("/proc/self/statm") as f:
            return float(f.read().split()[1]) * _PAGE_SIZE
    except OSError:  # pragma: no cover - non-linux
        try:
            import resource
            return float(resource.getrusage(
                resource.RUSAGE_SELF).ru_maxrss) * 1024.0
        except Exception:  # noqa: BLE001
            return 0.0


def _open_fds() -> float:
    try:
        import os
        return float(len(os.listdir("/proc/self/fd")))
    except OSError:  # pragma: no cover - non-linux
        return 0.0


def process_metrics() -> dict:
    """Canonical ``filodb_process_*`` family: RSS, open FDs, thread
    count, start time / uptime, and per-generation GC collections.
    Registered once at import so every /metrics scrape carries them."""
    import gc

    rss = REGISTRY.gauge("filodb_process_resident_memory_bytes",
                         "resident set size of this process")
    rss.set_fn(_rss_bytes)
    fds = REGISTRY.gauge("filodb_process_open_fds",
                         "open file descriptors")
    fds.set_fn(_open_fds)
    threads = REGISTRY.gauge("filodb_process_threads",
                             "live python threads")
    threads.set_fn(lambda: float(threading.active_count()))
    start = REGISTRY.gauge("filodb_process_start_time_seconds",
                           "unix time the process started")
    start.set(_PROCESS_START_S)
    uptime = REGISTRY.gauge("filodb_process_uptime_seconds",
                            "seconds since process start")
    uptime.set_fn(lambda: time.time() - _PROCESS_START_S)
    gens = REGISTRY.gauge("filodb_process_gc_collections",
                          "garbage collections per generation")
    for gen in range(3):
        gens.set_fn(
            (lambda g: lambda: float(gc.get_stats()[g]["collections"]))(
                gen), generation=str(gen))
    return {"rss": rss, "open_fds": fds, "threads": threads,
            "start_time": start, "uptime": uptime,
            "gc_collections": gens}


process_metrics()


class PeriodicThread:
    """Daemon loop calling ``fn`` every ``interval_s`` until stopped;
    exceptions print and the loop continues (the shared harness for
    background samplers — watermark sampling, self-scrape — so the
    stop/join/backoff behavior lives in one place)."""

    def __init__(self, fn: Callable[[], object], interval_s: float,
                 name: str):
        self.fn = fn
        self.interval_s = float(interval_s)
        self.name = name
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        if self._thread is not None:
            return

        def loop():
            while not self._stop.wait(self.interval_s):
                try:
                    self.fn()
                except Exception:  # noqa: BLE001 — keep looping, loudly
                    traceback.print_exc()

        self._thread = threading.Thread(target=loop, name=self.name,
                                        daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None


# ---------------------------------------------------------------------------
# Tracing spans
# ---------------------------------------------------------------------------


def _new_id() -> str:
    """64-bit random hex id (trace ids on the wire)."""
    return f"{random.getrandbits(64):016x}"


# span ids count up from a random start per process: as good as random
# where nodes' spans meet in one trace, at a third of the price
_SPAN_IDS = itertools.count(random.getrandbits(63))


def _new_span_id() -> str:
    return f"{next(_SPAN_IDS):016x}"


@dataclasses.dataclass
class SpanRecord:
    name: str
    start_s: float                 # time.time() stamped when the span began
    duration_s: float
    tags: dict
    parent: Optional[str]          # parent span NAME (log reporters)
    error: Optional[str] = None
    # trace stitching (ISSUE 2): ids travel across threads and nodes so
    # a scatter-gather fan-out reassembles into one tree
    trace_id: Optional[str] = None
    span_id: str = ""
    parent_id: Optional[str] = None
    # the thread's own CPU seconds inside the span (time.thread_time):
    # wall less CPU is what the thread WAITED — for the GIL, the device,
    # a lock, a socket.  Read by the stages that ask for it (the ones
    # that enclose others, and grid.plan); 0.0 everywhere else: the
    # clock is a system call, 15-30 us where a sandbox's kernel answers
    # it, and the served path is short of exactly that.
    cpu_s: float = 0.0


class StageTable:
    """Process-wide totals per stage name: ``{count, wall_s, cpu_s}``,
    one lock, one dict.  Every *stage* span folds in here, so work
    outside any request's body (encode, write, the collector, set-up's
    block builds) has a number too.  Exposed as
    ``filodb_stage_seconds_total{stage,kind}`` / ``filodb_stage_total
    {stage}`` on /metrics and as the ``stages`` block of /admin/device."""

    def __init__(self) -> None:
        self._rows: dict[str, list] = {}
        self._lock = threading.Lock()
        # called before a read takes the lock: the tracer drains what a
        # collector hook left for it (Tracer.defer)
        self.before_read: Optional[Callable[[], None]] = None

    def add_all(self, spans) -> None:
        """Fold every span of ``spans`` in under one taking of the lock
        (a thread hands over what it finished since its last flush)."""
        rows = self._rows
        with self._lock:
            for sp in spans:
                row = rows.get(sp.name)
                if row is None:
                    rows[sp.name] = [1, sp.duration_s, sp.cpu_s]
                else:
                    row[0] += 1
                    row[1] += sp.duration_s
                    row[2] += sp.cpu_s

    def snapshot(self) -> dict:
        if self.before_read is not None:
            self.before_read()
        with self._lock:
            return {name: {"count": r[0], "wall_s": r[1], "cpu_s": r[2]}
                    for name, r in sorted(self._rows.items())}

    def expose(self) -> list[str]:
        rows = self.snapshot()
        out = ["# TYPE filodb_stage_seconds_total counter"]
        for name, r in rows.items():
            for kind in ("wall", "cpu"):
                key = (("kind", kind), ("stage", name))
                out.append(f"filodb_stage_seconds_total{_fmt_labels(key)} "
                           f"{_fmt_val(r[kind + '_s'])}")
        out.append("# TYPE filodb_stage_total counter")
        for name, r in rows.items():
            out.append(f"filodb_stage_total{_fmt_labels((('stage', name),))}"
                       f" {r['count']}")
        return out


def _jax_annotation():
    """``jax.profiler.TraceAnnotation``, or None where jax is missing:
    a leaf stage entered through it lies on the host plane of whatever
    profiler session is live, on the same clock as the ``XLA Ops``."""
    try:
        from jax.profiler import TraceAnnotation
        return TraceAnnotation
    except Exception:  # noqa: BLE001 — host-only deployment
        return None


class _ThreadState:
    """What one thread carries: its open spans, the finished ones not
    yet handed over, the trace context, and where stages' wall goes."""

    __slots__ = ("stack", "depth", "done", "trace_id", "parent_hint",
                 "timings")

    def __init__(self) -> None:
        self.stack: list = []
        self.depth = 0          # open spans, across attached stacks too
        self.done: list = []
        self.trace_id = self.parent_hint = self.timings = None


class Tracer:
    """Thread-local span stack + pluggable reporters (replaces Kamon
    span propagation via Kamon.runWithSpan).

    Each thread carries a trace context: a ``trace_id`` minted at the
    query entry point plus the current span's id.  ``capture()`` /
    ``attach()`` move that context across thread pools (scheduler
    workers, scatter-gather child dispatch), and the dispatch layer
    moves it across processes via an HTTP header + execplan-wire field.

    A span opened with :meth:`stage` is the served path's stage clock
    (doc/observability.md "Stage spans"): it also folds into ``stages``
    and into the ``timings`` of the query it ran for, and a *leaf* stage
    is entered as a profiler annotation.

    In place a span does the least it can: two clock reads, a push and
    a pop.  A finished span then waits on its thread until the thread's
    outermost span ends (or a ``scan`` stage, whose query reads its
    timings next; or :meth:`flush` is called; or ``MAX_WAITING`` have
    gathered), and :meth:`_flush` does the rest for all that waited, in
    one loop: ids, records, the stage table, the timings, the
    reporters.  Code that runs once a request runs cold, at several
    times its price in a loop; the flush's loop runs warm, and takes
    each lock once: two or three times a request on a thread.
    """

    MAX_WAITING = 64

    def __init__(self) -> None:
        self._local = threading.local()
        # replaced, never mutated: _flush reads it without the lock
        self._reporters: tuple[Callable[[Sequence[SpanRecord]], None],
                               ...] = ()
        self._lock = threading.Lock()
        self.stages = StageTable()
        self.stages.before_read = self.flush_deferred
        # name -> context manager for leaf stages; resolved on first use
        self._annotate = _jax_annotation
        # synthetic spans left by code that may take no lock (defer)
        self._deferred: collections.deque = collections.deque()

    def add_reporter(self,
                     fn: Callable[[Sequence[SpanRecord]], None]) -> None:
        """``fn`` is handed the records of the spans a thread finished
        since its last flush, oldest first."""
        with self._lock:
            self._reporters = self._reporters + (fn,)

    def _state(self) -> _ThreadState:
        try:
            return self._local.st
        except AttributeError:
            st = self._local.st = _ThreadState()
            return st

    def current_span(self) -> Optional[str]:
        stack = self._state().stack
        return stack[-1].name if stack else None

    def current_span_id(self) -> Optional[str]:
        st = self._state()
        return st.stack[-1].span_id if st.stack else st.parent_hint

    def current_trace_id(self) -> Optional[str]:
        return self._state().trace_id

    @staticmethod
    def new_trace_id() -> str:
        return _new_id()

    def capture(self) -> tuple:
        """(trace_id, span_id) token for cross-thread propagation."""
        return self.current_trace_id(), self.current_span_id()

    def attach(self, token):
        """Install a captured trace context on this thread: spans opened
        inside parent onto ``token``'s span id and carry its trace id.
        The span stack is swapped for a FRESH one — the context is
        foreign, so an unrelated span already open on this thread (e.g.
        a scheduler worker's own span) must not capture the parentage."""
        return _Attached(self._state(), token)

    def span(self, name: str, **tags):
        return _Span(self, name, tags)

    def stage(self, name: str, leaf: bool = True, cpu: bool = False,
              timings=None, **tags):
        """A span that is also a stage: its name is the key of the stage
        table and of ``QueryStats.timings``.  ``leaf=False`` for a stage
        that encloses other stages (``device_compute``, ``scan``,
        ``http.request``, ``scheduler.run``) or only waits for another
        thread (``grid.lock_wait``, a batch member's ``batch.wait``):
        leaves alone are annotated on the profiler's host plane, because
        an enclosing span would cover every device-idle gap and name
        none, and a wait would name the symptom and not the work that
        holds the host.  ``cpu=True`` reads the thread's CPU clock as
        well (``SpanRecord.cpu_s``).  ``timings`` (anything with
        ``note_timings``: a query's ``ExecContext``) takes the wall of
        this stage and of every stage that ends inside it on its thread,
        under their names."""
        return _Span(self, name, tags, True, leaf, cpu, timings)

    def record(self, name: str, duration_s: float,
               trace_id: Optional[str] = None,
               parent_id: Optional[str] = None, *,
               start_s: Optional[float] = None, cpu_s: float = 0.0,
               stage: bool = False, **tags) -> SpanRecord:
        """Report a synthetic span that did not run on this thread
        (queue wait measured by a worker, a remote node's spans).
        ``start_s`` is when it began on ``time.time()``'s clock; a
        caller that only knows it just ended leaves it out.
        ``stage=True`` folds it into the stage table as well."""
        if start_s is None:
            start_s = time.time() - duration_s
        rec = SpanRecord(name, start_s, duration_s, tags, None,
                         trace_id=trace_id, span_id=_new_span_id(),
                         parent_id=parent_id, cpu_s=cpu_s)
        if stage:
            self.stages.add_all((rec,))
        self._report((rec,))
        return rec

    def defer(self, name: str, duration_s: float, **kw) -> None:
        """:meth:`record` for a caller that may take no lock and call no
        reporter: a ``gc.callbacks`` hook runs on whichever thread set
        the collection off, at whatever point it had reached, and that
        can be inside the stage table's lock or a reporter's; the HTTP
        accept thread is every request's serial path.  The span is
        reported by the next flush on any thread, or the next read of
        the stage table."""
        self._deferred.append((name, duration_s, kw))

    def flush_deferred(self) -> None:
        q = self._deferred
        while q:
            try:
                name, duration_s, kw = q.popleft()
            except IndexError:      # another thread took the last one
                return
            self.record(name, duration_s, **kw)

    def _annotation_class(self):
        ann = self._annotate
        if ann is _jax_annotation:
            ann = self._annotate = _jax_annotation()
        return ann

    @property
    def annotating(self) -> bool:
        """False once leaves can no longer be annotated (jax missing, or
        the annotation failed): a profile then names no idle gap after a
        program stage, however busy the program is."""
        return self._annotate is not None

    def _leaf_annotation(self, name: str):
        """An entered profiler annotation for ``name``, or None: jax
        missing or the annotation failing degrades to no annotation,
        never to an error on the request.  The first failure is said
        once on stderr: from then on no trace of this process carries
        a program name on its host plane."""
        try:
            ann = self._annotation_class()
            if ann is None:
                return None
            cm = ann(name)
            cm.__enter__()
            return cm
        except Exception as e:  # noqa: BLE001 — tracing never fails the work
            if self._annotate is not None:
                self._annotate = None
                try:
                    print(f"tracer: leaf annotations off for this process: "
                          f"{name}: {e!r}", file=sys.stderr, flush=True)
                except Exception:  # noqa: BLE001
                    pass
            return None

    def adopt(self, token, spans: Sequence["_Span"]) -> None:
        """Hand ``spans``, opened and closed off the span stack
        (:meth:`_Span.opened`), to this thread's next flush, in the
        trace of ``token`` (``capture``'s ``(trace id, span id)``, or
        None): they fold into the stage table with what the thread
        finishes next, under no lock of their own."""
        st = self._state()
        trace_id, parent_id = token or (None, None)
        for sp in spans:
            sp.trace_id, sp.parent_hint = trace_id, parent_id
        st.done[:0] = spans
        if not st.depth:
            self._flush(st)

    def flush(self) -> None:
        """Hand over what this thread has finished now: a reader of the
        trace store calls it first, so that the trace it reads is whole
        as far as this thread goes."""
        st = self._state()
        if st.done:
            self._flush(st)

    def _flush(self, st: _ThreadState) -> None:
        """Hand over the spans this thread finished: the stages to the
        table and to their queries' timings, a record of each to the
        reporters."""
        done, st.done = st.done, []
        recs, stages = [], []
        # spans keep perf_counter's reading; this is time.time() less it
        epoch = time.time() - time.perf_counter()
        for sp in done:
            parent = sp.parent
            if parent is None:
                pname, pid = None, sp.parent_hint
            else:
                pname, pid = parent.name, parent.span_id
            recs.append(SpanRecord(sp.name, epoch + sp._t0, sp.duration_s,
                                   sp.tags, pname, sp.error, sp.trace_id,
                                   sp.span_id, pid, sp.cpu_s))
            if sp.stage:
                stages.append(sp)
        if stages:
            self.stages.add_all(stages)
            sink, walls = None, []
            for sp in stages:       # runs of one query's stages
                if sp.sink is not sink:
                    if walls:
                        sink.note_timings(walls)
                    sink, walls = sp.sink, []
                if sink is not None:
                    walls.append((sp.name, sp.duration_s))
            if walls:
                sink.note_timings(walls)
        if self._deferred:
            self.flush_deferred()
        self._report(recs)

    def _report(self, recs: Sequence[SpanRecord]) -> None:
        for fn in self._reporters:
            try:
                fn(recs)
            except Exception:  # noqa: BLE001 — reporters must not break work
                traceback.print_exc()


class _Attached:
    """``Tracer.attach``'s context manager (a class: it is entered a few
    times a request)."""

    __slots__ = ("_st", "_new", "_old")

    def __init__(self, st: _ThreadState, token):
        self._st = st
        self._new = token if token else (None, None)

    def __enter__(self):
        st = self._st
        self._old = (st.trace_id, st.parent_hint, st.stack)
        st.trace_id, st.parent_hint = self._new
        st.stack = []

    def __exit__(self, *exc):
        st = self._st
        st.trace_id, st.parent_hint, st.stack = self._old
        return False


class _Span:
    """One timed interval: wall (``perf_counter``) and, where asked for,
    the thread's CPU (``thread_time``), started at ``start_s`` on
    ``time.time()``'s clock.  ``duration_s`` / ``cpu_s`` stay readable
    after exit, so a caller that owes a stats bucket takes it from the
    span instead of timing the same interval again.  Its ``SpanRecord``
    is made when the thread flushes (``Tracer._flush``), its start put
    on ``time.time()``'s clock there."""

    duration_s = cpu_s = 0.0
    parent = None           # the span this one began inside, if any
    parent_hint = None      # ... or the attached context's span id
    error = trace_id = sink = None
    _ann = _sid = None

    def __init__(self, tracer: Tracer, name: str, tags: dict,
                 stage: bool = False, leaf: bool = False,
                 cpu: bool = False, timings=None):
        self.tracer = tracer
        self.name = name
        self.tags = tags
        self.stage = stage
        self.leaf = stage and leaf
        self.cpu = cpu
        self.timings = timings

    @property
    def span_id(self) -> str:
        """Made when first asked for: by a child's record, a capture
        across threads, or this span's own record."""
        sid = self._sid
        if sid is None:
            sid = self._sid = _new_span_id()
        return sid

    def __enter__(self):
        tracer = self.tracer
        try:
            st = tracer._local.st
        except AttributeError:
            st = tracer._state()
        self._st = st
        stack = st.stack
        if stack:
            self.parent = stack[-1]
        else:
            self.parent_hint = st.parent_hint
        stack.append(self)
        st.depth += 1
        if self.timings is not None:
            self._outer_timings, st.timings = st.timings, self.timings
        if self.leaf:
            self._ann = tracer._leaf_annotation(self.name)
        self._t0 = time.perf_counter()
        if self.cpu:
            self._c0 = time.thread_time()
        return self

    def tag(self, **tags):
        self.tags.update(tags)
        return self

    # for an interval that no ``with`` block can delimit (the wait for
    # a lock ends INSIDE the block that holds it)
    begin = __enter__

    def end(self) -> None:
        self.__exit__(None, None, None)

    def opened(self, t0: Optional[float] = None) -> "_Span":
        """Open the interval OFF the thread's span stack, at ``t0`` (a
        ``perf_counter`` reading, taken on any thread) or now: for an
        interval that begins before the code that owns it runs, or on
        another thread (a request's accept and read, before its
        handler's first span).  :meth:`closed` ends it and
        ``Tracer.adopt`` hands it to a thread's flush.  No CPU clock."""
        if self.leaf:
            self._ann = self.tracer._leaf_annotation(self.name)
        self._t0 = time.perf_counter() if t0 is None else t0
        return self

    def closed(self, t1: Optional[float] = None) -> "_Span":
        """End an :meth:`opened` interval at ``t1`` or now."""
        self.duration_s = (time.perf_counter() if t1 is None else t1) \
            - self._t0
        if self._ann is not None:
            self._exit_annotation()
        return self

    def _exit_annotation(self) -> None:
        try:  # spans must NEVER raise into the instrumented path
            self._ann.__exit__(None, None, None)
        except Exception:  # noqa: BLE001
            pass
        self._ann = None

    def __exit__(self, exc_type, exc, tb):
        self.duration_s = time.perf_counter() - self._t0
        if self.cpu:
            # not held to the wall: where the CPU clock ticks in steps
            # (10 ms under gVisor) one span reads high or low by a tick,
            # and only the sums over many spans mean anything
            self.cpu_s = max(time.thread_time() - self._c0, 0.0)
        if self._ann is not None:
            self._exit_annotation()
        if exc is not None:
            self.error = repr(exc)
        st = self._st
        self.trace_id = st.trace_id
        flush = False
        if self.stage:
            self.sink = st.timings
            if self.timings is not None:
                # its query reads the timings once the scan is done
                st.timings, flush = self._outer_timings, True
        if st.stack:
            st.stack.pop()
        done = st.done
        done.append(self)
        st.depth = depth = max(st.depth - 1, 0)
        if flush or not depth or len(done) >= Tracer.MAX_WAITING:
            self.tracer._flush(st)
        return False


TRACER = Tracer()
REGISTRY.collector("filodb_stage", TRACER.stages)


class GcPauseWatch:
    """``gc.callbacks`` hook: Python's collector stops every thread of
    the process while it runs, and a full collection over a large heap
    (the index of 100 000 series) takes tenths of a second.  Each
    collection of generation 2, and any of a younger generation over
    ``SLOW_S``, becomes a ``gc.pause`` stage span (tag ``generation``; a
    full one also a profiler annotation, so a device-idle gap it caused
    carries its name).  Every collection's seconds are in
    ``filodb_gc_pause_seconds_total{generation}``.

    The hook runs on whichever thread set the collection off, at
    whatever point it had reached, and that thread may hold the stage
    table's lock, the trace store's or a counter's.  So the hook takes
    no lock and calls no reporter: it adds to its own totals and leaves
    the span with ``Tracer.defer``.  A serving process collects
    generation 0 some 1 500 times a second; that path is two clock
    reads and an add.

    One slot of state is enough: the interpreter runs one collection at
    a time, and both callbacks of it on the collecting thread."""

    SLOW_S = 0.010
    name = "filodb_gc_pause_seconds_total"

    def __init__(self, tracer: "Tracer" = TRACER):
        self._tracer = tracer
        tracer._annotation_class()      # no import from inside the hook
        self.seconds = [0.0, 0.0, 0.0]  # by generation; the hook alone adds
        # each full collection's start and end: odd while one runs.  A
        # collection runs its finalizers' Python code before its end, and
        # another thread may take the interpreter then
        self.full_edges = 0
        self._t0 = self._wall0 = self._cpu0 = self._ann = None

    def __call__(self, phase: str, info: dict) -> None:
        gen = info["generation"]
        if phase == "start":
            if gen == 2:
                self.full_edges += 1
                self._ann = self._tracer._leaf_annotation("gc.pause")
                self._cpu0 = time.thread_time()
            self._wall0 = time.time()
            self._t0 = time.perf_counter()
            return
        if self._t0 is None:
            return              # installed in the middle of a collection
        dur = time.perf_counter() - self._t0
        self._t0 = None
        self.seconds[gen] += dur
        if gen == 2:
            self.full_edges += 1
        if gen < 2 and dur <= self.SLOW_S:
            return
        cpu = 0.0               # the CPU clock is read for full ones only
        if gen == 2:
            cpu = max(time.thread_time() - self._cpu0, 0.0)
            ann, self._ann = self._ann, None
            if ann is not None:
                try:
                    ann.__exit__(None, None, None)
                except Exception:  # noqa: BLE001 — tracing never fails work
                    pass
        tracer = self._tracer
        tracer.defer("gc.pause", dur,
                     trace_id=tracer.current_trace_id(),
                     parent_id=tracer.current_span_id(),
                     start_s=self._wall0, cpu_s=cpu, stage=True,
                     generation=gen, collected=info.get("collected", 0))

    def expose(self) -> list[str]:
        out = [f"# TYPE {self.name} counter"]
        for gen, secs in enumerate(self.seconds):
            if secs:
                key = (("generation", gen),)
                out.append(f"{self.name}{_fmt_labels(key)} {_fmt_val(secs)}")
        return out


_GC_WATCH: Optional[GcPauseWatch] = None


def install_gc_watch() -> GcPauseWatch:
    """Install the process's one :class:`GcPauseWatch` (idempotent)."""
    global _GC_WATCH
    import gc
    if _GC_WATCH is None:
        _GC_WATCH = GcPauseWatch()
        REGISTRY.collector(GcPauseWatch.name, _GC_WATCH)
    if _GC_WATCH not in gc.callbacks:
        gc.callbacks.append(_GC_WATCH)
    return _GC_WATCH


class StallWatch:
    """What stops the process for seconds: a daemon thread that sleeps
    ``TICK_S`` at a time and, where it wakes more than ``LIMIT_S`` late,
    reports a ``host.stall`` stage span (its wall is the lateness) and
    one line on stderr that says WHAT KIND of stop it was, from three
    clocks read before and after: the process's CPU over all threads (a
    thread that kept the interpreter lock and computed reads about the
    wall; one that kept it inside a blocking call, or a machine that did
    not run, reads near zero), the machine's busy, idle and stolen
    seconds summed over its CPUs (``/proc/stat``: where they add up to
    less than the wall times the CPUs the machine itself was not run),
    and the collector's seconds (a full collection is no news) — then
    the top frames of every thread as they stand when the watch got to
    run again: the thread that held everything up is usually still at
    the call it was in.  A collection of half a second stays under the
    limit; the watch costs a wake-up a tick.

    A tick's lateness is also a sample of what it costs a thread to get
    the interpreter back under the load of that moment: the stage
    ``interp.wait`` (``count`` the ticks, ``wall_s`` the lateness).  The
    reference is read right before the wait, so one sample is one
    reacquisition, from the wait's due end to the first bytecode after
    it: the switch interval a waiter sits out before it may ask the
    holder to drop, and any C call that holds the lock without
    checking, are in it; the watch's own clock and ``/proc/stat`` reads
    are not.  A tick that a full collection overlapped (one ran, began
    or ended between the two reads), or that ran over the limit, is no
    sample: the collector's seconds are ``gc.pause``'s and a stop's are
    ``host.stall``'s, and one such tick would outweigh hundreds of
    others in the mean.  The sample also holds the timer's own lateness
    in waking the thread, which an idle process reads alone.  It
    carries no trace id, so no trace keeps it."""

    TICK_S = 0.1
    LIMIT_S = 1.0

    def __init__(self, tracer: "Tracer" = TRACER, out=None):
        self._tracer, self._out = tracer, out
        self.stalls: list[dict] = []       # the last few, newest last
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    @staticmethod
    def _machine() -> Optional[tuple]:
        """(busy, idle, stolen) seconds over all CPUs since boot."""
        try:
            with open("/proc/stat") as f:
                v = [int(x) for x in f.readline().split()[1:9]]
            hz = float(os.sysconf("SC_CLK_TCK"))
            return ((v[0] + v[1] + v[2] + v[5] + v[6]) / hz,
                    (v[3] + v[4]) / hz, v[7] / hz)
        except (OSError, ValueError, IndexError):
            return None

    def _collected(self) -> float:
        return sum(_GC_WATCH.seconds) if _GC_WATCH is not None else 0.0

    def _full_edges(self) -> int:
        return _GC_WATCH.full_edges if _GC_WATCH is not None else 0

    def start(self) -> "StallWatch":
        if self._thread is None:
            self._thread = threading.Thread(target=self._run, daemon=True,
                                            name="stall-watch")
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()

    def _run(self) -> None:
        while not self._stop.is_set():
            wall0 = time.time()
            cpu0, mach0, gc0, full0 = (time.process_time(),
                                       self._machine(), self._collected(),
                                       self._full_edges())
            t0 = time.perf_counter()
            self._stop.wait(self.TICK_S)
            late = time.perf_counter() - t0 - self.TICK_S
            if self._stop.is_set():
                return
            if late > self.LIMIT_S:
                self._report(wall0, late, time.process_time() - cpu0,
                             mach0, self._machine(),
                             self._collected() - gc0)
            elif self._full_edges() == full0 and not full0 % 2:
                self._tracer.record("interp.wait", max(late, 0.0),
                                    stage=True)

    def _report(self, wall0, late, cpu, mach0, mach1, collected) -> None:
        note = {"late_s": round(late, 3), "process_cpu_s": round(cpu, 3),
                "collector_s": round(collected, 3)}
        if mach0 is not None and mach1 is not None:
            note.update(zip(("machine_busy_s", "machine_idle_s",
                             "machine_stolen_s"),
                            (round(b - a, 3) for a, b in zip(mach0, mach1))))
            note["cpus"] = os.cpu_count()
        names = {t.ident: t.name for t in threading.enumerate()}
        frames = []
        for ident, frame in sys._current_frames().items():
            top = []
            while frame is not None and len(top) < 6:
                code = frame.f_code
                top.append(f"{os.path.basename(code.co_filename)}:"
                           f"{frame.f_lineno}:{code.co_name}")
                frame = frame.f_back
            frames.append(f"{names.get(ident, ident)}[{' < '.join(top)}]")
        self.stalls = self.stalls[-7:] + [dict(note, at=wall0)]
        try:
            self._tracer.record("host.stall", late, start_s=wall0,
                                stage=True, **note)
            print(f"host stall: {json.dumps(note)} threads: "
                  + "; ".join(sorted(frames)),
                  file=self._out or sys.stderr, flush=True)
        except Exception:  # noqa: BLE001 — the watch never fails the node
            pass


_STALL_WATCH: Optional[StallWatch] = None


def install_stall_watch() -> StallWatch:
    """Start the process's one :class:`StallWatch` (idempotent)."""
    global _STALL_WATCH
    if _STALL_WATCH is None:
        _STALL_WATCH = StallWatch().start()
    return _STALL_WATCH


# ---------------------------------------------------------------------------
# Sampling profiler
# ---------------------------------------------------------------------------


class SimpleProfiler:
    """Background stack-sampling profiler (reference:
    standalone/src/main/java/filodb/standalone/SimpleProfiler.java —
    samples thread stacks periodically, aggregates hottest frames, and
    reports every interval)."""

    def __init__(self, sample_interval_s: float = 0.01,
                 report_interval_s: float = 60.0,
                 top_k: int = 20,
                 report_fn: Optional[Callable[[str], None]] = None):
        self.sample_interval_s = sample_interval_s
        self.report_interval_s = report_interval_s
        self.top_k = top_k
        self.report_fn = report_fn or print
        self._counts: collections.Counter = collections.Counter()
        self._samples = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.Lock()

    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()
        self._thread = threading.Thread(target=self._run, name="profiler",
                                        daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None

    def _run(self) -> None:
        own = threading.get_ident()
        next_report = time.monotonic() + self.report_interval_s
        while not self._stop.wait(self.sample_interval_s):
            frames = sys._current_frames()
            with self._lock:
                self._samples += 1
                for tid, frame in frames.items():
                    if tid == own:
                        continue
                    code = frame.f_code
                    self._counts[(code.co_filename, code.co_name)] += 1
            if time.monotonic() >= next_report:
                self.report_fn(self.report())
                next_report = time.monotonic() + self.report_interval_s

    def report(self) -> str:
        with self._lock:
            total = self._samples or 1
            top = self._counts.most_common(self.top_k)
        lines = [f"profiler: {self._samples} samples"]
        for (fname, func), n in top:
            short = fname.rsplit("/", 1)[-1]
            lines.append(f"  {100.0 * n / total:5.1f}% {short}:{func}")
        return "\n".join(lines)

    def snapshot(self) -> Mapping:
        with self._lock:
            return dict(self._counts)
