"""Prometheus HTTP API data model: results -> Prometheus JSON.

Capability match for the reference's PrometheusModel (reference:
prometheus/src/main/scala/filodb/prometheus/query/PrometheusModel.scala:12
— QueryResult -> matrix/vector JSON; histogram -> bucket series) and the
PromQueryResponse shapes (query/.../PromQueryResponse.scala).
"""

from __future__ import annotations

import math

import numpy as np

from filodb_tpu.query.model import (PeriodicBatch, QueryResult, RawBatch,
                                    ScalarResult)


def _fmt(v: float) -> str:
    """Prometheus value formatting: shortest repr, NaN as \"NaN\"."""
    if math.isnan(v):
        return "NaN"
    if math.isinf(v):
        return "+Inf" if v > 0 else "-Inf"
    if v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return repr(v)


def public_tags(tags: dict, metric_column: str = "_metric_") -> dict:
    """Internal metric column -> Prometheus ``__name__`` on the way out
    (reference: PrometheusModel metric-name conversion)."""
    out = dict(tags)
    if metric_column in out:
        out["__name__"] = out.pop(metric_column)
    return out


# Arrays cross into Python lists once a batch (``.tolist()``), never a
# cell: a NumPy scalar indexed, divided or ``float()``ed a cell costs
# more than the rest of the answer (doc/observability.md, "The API edge").
def _seconds(ts_ms) -> list:
    return (np.asarray(ts_ms) / 1000.0).tolist()


def _step_seconds(steps, upto_ms=None) -> list:
    """The step grid in seconds (to ``upto_ms`` where given) from a
    ``range``: the ``arange`` of ``steps.timestamps()`` would be the one
    call of an answer that drops the interpreter lock."""
    end = steps.end if upto_ms is None else min(steps.end, upto_ms)
    return [t / 1000.0 for t in range(steps.start, end + 1, steps.step)]


def _floats(vals) -> list:
    a = np.asarray(vals)    # .tolist() widens a float of any width exactly
    return (a if a.dtype.kind == "f" else a.astype(np.float64)).tolist()


def _cells(secs: list, row: list) -> list:
    """One series' ``values`` from two lists of Python floats: a
    ``(seconds, text)`` for every cell that is not NaN (a JSON array, as
    a list would be), the text exactly :func:`_fmt`'s.  Whole numbers and
    fractions are written in place; only an infinity pays the call.

    A tuple, not a list: a tuple of a float and a str leaves the cyclic
    collector's books at the first collection that meets it, where a
    list stays tracked, and the cells of the answers in flight are then
    promoted into the old generation until they pace its full
    collections (a 60-step, 128-series answer is 7 680 cells)."""
    return [(t, "%d" % v if v.is_integer() and -1e15 < v < 1e15
             else repr(v) if -math.inf < v < math.inf else _fmt(v))
            for t, v in zip(secs, row) if v == v]


def _attach_warnings(resp: dict, result: QueryResult) -> dict:
    """Prometheus-style ``warnings`` for partial results: quarantined
    (corrupt) chunks were excluded from the scan, or a shard's node was
    unreachable and the query opted into ``allow_partial_results`` —
    the caller gets real data plus a loud flag, never wrong values and
    never silence.  The HTTP server mirrors this as an
    X-FiloDB-Partial-Data header."""
    warnings = []
    n = result.stats.corrupt_chunks_excluded
    if n:
        warnings.append(
            f"partial data: {n} corrupt chunk(s) quarantined and "
            f"excluded from results (see /admin/integrity)")
        from filodb_tpu.utils.observability import integrity_metrics
        integrity_metrics()["partial_queries"].inc()
    down = result.stats.shards_down
    if down:
        warnings.append(
            f"partial data: {down} shard(s) unreachable; their series "
            f"are missing from results (allow_partial_results)")
        from filodb_tpu.utils.observability import workload_metrics
        workload_metrics()["partial_shards"].inc()
    if warnings:
        resp["warnings"] = warnings
    return resp


def to_prom_matrix(result: QueryResult,
                   metric_column: str = "_metric_") -> dict:
    """Range-query response (resultType=matrix)."""
    out = []

    def series(tags: dict, secs: list, row: list, column: str) -> None:
        values = _cells(secs, row)
        if values:                      # an all-NaN series is omitted
            out.append({"metric": public_tags(tags, column),
                        "values": values})

    for b in result.batches:
        if isinstance(b, PeriodicBatch):
            secs = _step_seconds(b.steps)
            for tags, row in zip(b.keys, _floats(b.np_values())):
                series(tags, secs, row, metric_column)
        elif isinstance(b, ScalarResult):
            series({}, _step_seconds(b.steps), _floats(b.values),
                   metric_column)
        elif isinstance(b, RawBatch) and b.batch is not None:
            # a raw export has always renamed the default column only
            # (ROADMAP D14)
            for tags, n, secs, row in zip(
                    b.keys, np.asarray(b.batch.row_counts).tolist(),
                    _seconds(b.batch.timestamps), _floats(b.batch.values)):
                series(tags, secs[:n], row[:n], "_metric_")
    return _attach_warnings(
        {"status": "success",
         "data": {"resultType": "matrix", "result": out}}, result)


def to_prom_vector(result: QueryResult, time_ms: int,
                   metric_column: str = "_metric_") -> dict:
    """Instant-query response (resultType=vector): last value at/before
    the evaluation timestamp."""
    out = []
    at = time_ms / 1000.0
    for b in result.batches:
        if isinstance(b, PeriodicBatch):
            upto = len(_step_seconds(b.steps, time_ms))
            rows = _floats(b.np_values()[:, :upto])
            for tags, row in zip(b.keys, rows):
                last = next((v for v in reversed(row) if v == v), None)
                if last is not None:
                    out.append({"metric": public_tags(tags, metric_column),
                                "value": [at, _fmt(last)]})
        elif isinstance(b, ScalarResult):
            vals = _floats(b.values)
            if vals:
                return _attach_warnings(
                    {"status": "success",
                     "data": {"resultType": "scalar",
                              "value": [at, _fmt(vals[-1])]}}, result)
    return _attach_warnings(
        {"status": "success",
         "data": {"resultType": "vector", "result": out}}, result)


def error_response(error_type: str, message: str) -> dict:
    return {"status": "error", "errorType": error_type, "error": message}


def stats_payload(stats, trace_id: str = "") -> dict:
    """``stats=true`` response block (Prometheus-compatible placement:
    ``data.stats.timings`` / ``data.stats.samples``).  Timings are the
    per-stage wall-time buckets in seconds (plan/queue/scan/decode/
    device_compute/serialize/total); samples are the scan-volume
    counters merged up the exec tree, remote shards included."""
    return {
        "timings": {k: round(float(v), 6)
                    for k, v in sorted(stats.timings.items())},
        "samples": {
            "samplesScanned": int(stats.samples_scanned),
            "seriesScanned": int(stats.series_scanned),
            "chunksScanned": int(stats.chunks_scanned),
            "bytesScanned": int(stats.bytes_scanned),
            "pagesIn": int(stats.pages_in),
            "corruptChunksExcluded": int(stats.corrupt_chunks_excluded),
            # shards degraded to empty results under
            # allow_partial_results (workload subsystem)
            "shardsDown": int(stats.shards_down),
            # device-grid HBM reads under device_compute, by resident
            # format — shows whether compressed residents serve traffic
            "hbmReadBytes": {k: int(v)
                             for k, v in sorted(
                                 stats.hbm_read_bytes.items())},
            # net ledger-tracked HBM residency change this query caused
            # (devicewatch: blocks committed minus freed; 0 when warm)
            "hbmResidentDeltaBytes": int(stats.hbm_resident_delta_bytes),
        },
        # tiered-resolution serving (doc/rollup.md): the coarsest rolled
        # tier that served (part of) this query; 0 = raw only
        "resolutionMs": int(getattr(stats, "resolution_ms", 0)),
        # storage tiers the stitched plan actually materialized legs
        # for, oldest first ("rolled-cold+rolled-local+raw"); '' when
        # the dataset has no router (doc/coldstore.md)
        "tiers": str(getattr(stats, "tiers", "")),
        # cold tier (doc/coldstore.md): chunks/bytes paged back from
        # the object bucket for this query; 0/0 = cold-miss-free
        "coldTier": {
            "chunksPaged": int(getattr(stats, "cold_chunks_paged", 0)),
            "bytesRead": int(getattr(stats, "cold_bytes_read", 0)),
        },
        # ?downsample=<pixels> M4 decimation: finite points entering
        # the mapper vs pixel-exact points kept (<= ~4x pixels/series)
        "downsample": {
            "pointsIn": int(getattr(stats, "downsample_points_in", 0)),
            "pointsOut": int(getattr(stats, "downsample_points_out", 0)),
        },
        # kernel flight deck (ISSUE 15, doc/observability.md): measured
        # device seconds per wrapped program from the launches SAMPLED
        # during this query — the per-program split of the
        # device_compute timing bucket (names the offending kernel)
        "devicePrograms": {k: round(float(v), 6)
                           for k, v in sorted(getattr(
                               stats, "device_programs", {}).items())},
        # query-frontend result cache (doc/query-engine.md): result
        # samples served from memoized immutable-chunk partials vs
        # samples re-scanned fresh this evaluation
        "resultCache": {
            "cachedSamples": int(getattr(
                stats, "resultcache_cached_samples", 0)),
            "recomputedSamples": int(getattr(
                stats, "resultcache_recomputed_samples", 0)),
        },
        "traceId": trace_id,
    }


# ---------------------------------------------------------------------------
# Parameter parsing (Prometheus API conventions)
# ---------------------------------------------------------------------------

_DUR_UNITS = {"ms": 1, "s": 1000, "m": 60_000, "h": 3_600_000,
              "d": 86_400_000, "w": 7 * 86_400_000, "y": 365 * 86_400_000}


def parse_time_ms(v: str) -> int:
    """Unix seconds (possibly fractional) -> epoch millis."""
    return int(float(v) * 1000)


def parse_duration_ms(v: str) -> int:
    """'15s' / '1m' / '250ms' / plain seconds -> millis."""
    s = v.strip()
    for unit in ("ms", "y", "w", "d", "h", "m", "s"):
        if s.endswith(unit):
            return int(float(s[:-len(unit)]) * _DUR_UNITS[unit])
    return int(float(s) * 1000)
