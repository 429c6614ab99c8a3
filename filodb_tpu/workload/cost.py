"""Pre-execution query cost model, calibrated online.

Capability match for the reference's per-query resource estimation
(reference: the QuerySession/QueryConfig sample limits plus the
coordinator's plan-time shard fan-out knowledge), made quantitative so
the admission controller (workload/admission.py) can shed load BEFORE
dead work starts.

The unit of cost is a **series-chunk**: one matched series crossing one
chunk-sized window of the query's time range.  For each data leaf the
estimate is

    cost = index_hits x ceil(range / chunk_window) x op_weight

- ``index_hits`` comes from the part-key index (the same cached
  ``lookup_partitions`` walk the scan itself would do first — repeated
  dashboard shapes hit the shard's lookup cache, so estimation is a
  dict probe in steady state);
- the chunk-window count models scan volume growth with time range;
- ``op_weight`` multiplies per attached transformer (a histogram
  quantile costs more per series-chunk than a passthrough).

Leaves whose shard lives on another node (no local memstore shard)
cannot consult an index; they inherit the mean hits of the resolvable
leaves — scatter-gather children are near-uniform by construction
(spread-sharded), so this is the right prior.

**Online calibration** (ISSUE 5 tentpole): every admitted query reports
its observed wall time back via :meth:`observe`, and the model predicts
seconds from units as ``fixed + per_unit x units``, both parts learned
from those (units, seconds) pairs: exponentially weighted means, the
variance of the units and their covariance with the seconds are all the
state.  A query the device path serves costs about the same whatever it
selects (a namespace sum of ~770 units and a workspace-wide sum of
~614 000 both take tens of milliseconds: launch, readback and the
interpreter, not the lanes), so a line through the origin learned on the
small ones priced the large ones at half a minute and admission shed
them with the chips idle (PERF.md section 6, PR 33 and PR 34).  Where
every observed query costs the same, or the seconds really are
proportional to the units, the fixed part is 0 and the model is the
through-the-origin one it was.
"""

from __future__ import annotations

import math
import threading
from typing import Optional

# default chunk window: matches the gauge StoreConfig's one-hour flush
# cadence order of magnitude but deliberately finer so short dashboards
# still see range-proportional cost
DEFAULT_CHUNK_WINDOW_MS = 600_000

# per-transformer multiplicative weights (class name -> weight); the
# absolute scale is irrelevant — calibration absorbs it — only the
# RATIOS matter for cross-query fairness
OP_WEIGHTS = {
    "PeriodicSamplesMapper": 1.0,
    "AggregateMapReduce": 1.2,
    "AggregatePresenter": 1.0,
    "InstantVectorFunctionMapper": 1.1,
    "HistogramQuantileMapper": 2.5,
    "ScalarOperationMapper": 1.05,
    "SortFunctionMapper": 1.1,
    "AbsentFunctionMapper": 1.05,
    "MiscellaneousFunctionMapper": 1.1,
    "VectorFunctionMapper": 1.0,
    "StitchRvsMapper": 1.1,
}

# heavy range functions pay extra per series-chunk
RANGE_FN_WEIGHTS = {
    "HOLT_WINTERS": 2.0,
    "PREDICT_LINEAR": 1.5,
    "QUANTILE_OVER_TIME": 2.0,
    "MAD_OVER_TIME": 2.0,
}

_DEFAULT_HITS = 8.0  # prior for an unresolvable (remote) leaf


class CostModel:
    """Estimates cost units per ExecPlan and calibrates units->seconds."""

    def __init__(self, chunk_window_ms: int = DEFAULT_CHUNK_WINDOW_MS,
                 sec_per_unit: float = 2e-5, alpha: float = 0.2):
        self.chunk_window_ms = max(int(chunk_window_ms), 1)
        # seconds = _fixed + _per_unit x units on THIS node.  Before any
        # query has been observed the line goes through the origin at a
        # deliberately optimistic slope, so cold admission never sheds;
        # a few observed queries converge it
        self._fixed = 0.0
        self._per_unit = float(sec_per_unit)
        self._alpha = float(alpha)
        self._observed = 0
        # exponentially weighted moments of the observed (units, seconds)
        self._mean_x = self._mean_y = self._var_x = self._cov_xy = 0.0
        self._lock = threading.Lock()

    # ------------------------------------------------------------ estimation

    def estimate(self, plan, memstore=None) -> float:
        """Cost units for an ExecPlan tree (>= 1.0 always — even a
        metadata query occupies a worker)."""
        leaves: list[tuple[object, Optional[float]]] = []
        self._collect(plan, memstore, leaves)
        resolved = [h for _p, h in leaves if h is not None]
        fallback = (sum(resolved) / len(resolved)) if resolved \
            else _DEFAULT_HITS
        total = 0.0
        for leaf, hits in leaves:
            h = hits if hits is not None else fallback
            total += h * self._chunks(leaf) * self._weight(leaf)
        return max(total, 1.0)

    def estimate_seconds(self, cost: float, queries: int = 1) -> float:
        """Predicted seconds of ``queries`` queries of ``cost`` units in
        all: each pays the fixed part, every unit the per-unit part."""
        return queries * self._fixed + cost * self._per_unit

    @property
    def sec_per_unit(self) -> float:
        return self._per_unit

    @property
    def fixed_seconds(self) -> float:
        return self._fixed

    @property
    def observations(self) -> int:
        return self._observed

    # ------------------------------------------------------------ calibration

    def observe(self, cost: float, seconds: float) -> None:
        """Fold one completed query's (estimated cost, measured wall
        seconds) into the moments and refit the line.

        UPWARD moves are rate-limited: shed queries never observe, so a
        single compile-inflated cold-start sample that overshoots the
        shed threshold could otherwise wedge admission into rejecting a
        whole traffic class with nothing left to pull the estimate back
        down.  The seconds are held to what moves the prediction AT
        THIS COST by at most 4x a step while every query costs the same
        (the first sample: 4x the prior).  A genuinely slow node still
        converges geometrically; downward (faster-than-believed) moves
        are unrestricted."""
        if cost <= 0 or seconds < 0:
            return
        a = self._alpha
        with self._lock:
            believed = self._fixed + cost * self._per_unit
            if self._observed == 0:
                self._mean_x, self._mean_y = cost, min(seconds,
                                                       4.0 * believed)
            else:
                seconds = min(seconds, believed * (3.0 + a) / a)
                dx, dy = cost - self._mean_x, seconds - self._mean_y
                self._mean_x += a * dx
                self._mean_y += a * dy
                self._var_x = (1.0 - a) * (self._var_x + a * dx * dx)
                self._cov_xy = (1.0 - a) * (self._cov_xy + a * dx * dy)
            self._observed += 1
            self._fit()

    def _fit(self) -> None:  # holds-lock: _lock
        """The weighted least-squares line through the moments, held to
        fixed >= 0 and per_unit >= 0: a slope below 0 (the larger
        queries were the faster ones) reads as all fixed cost, a line
        that would cross below 0 seconds as none.  One cost observed so
        far says nothing about the split: through the origin, as the
        prior is."""
        through_origin = self._mean_y / self._mean_x
        if self._var_x <= 1e-12 * self._mean_x * self._mean_x:
            slope = through_origin
        else:
            slope = min(max(self._cov_xy / self._var_x, 0.0),
                        through_origin)
        self._per_unit = slope
        self._fixed = max(self._mean_y - slope * self._mean_x, 0.0)

    # -------------------------------------------------------------- internals

    def _collect(self, plan, memstore, out: list) -> None:
        """Walk the exec tree collecting (leaf, index_hits|None)."""
        shard = getattr(plan, "shard", None)
        filters = getattr(plan, "filters", None)
        if filters is not None and isinstance(shard, int):
            out.append((plan, self._leaf_hits(plan, shard, memstore)))
            return
        shards = getattr(plan, "shards", None)
        if filters is not None and isinstance(shards, (list, tuple)):
            # mesh-fused local multi-shard leaf: sum per-shard hits
            hits = [self._leaf_hits(plan, s, memstore) for s in shards]
            known = [h for h in hits if h is not None]
            out.append((plan, sum(known) if known else None))
            return
        for child in getattr(plan, "children", ()) or ():
            self._collect(child, memstore, out)

    @staticmethod
    def _leaf_hits(plan, shard: int, memstore) -> Optional[float]:
        if memstore is None:
            return None
        try:
            sh = memstore.get_shard(plan.dataset, shard)
            lookup = sh.lookup_partitions(list(plan.filters), plan.start_ms,
                                          plan.end_ms)
            return float(len(lookup.part_ids) + len(lookup.missing_partkeys))
        except Exception:  # noqa: BLE001 — remote/unreachable shard
            return None

    def _chunks(self, leaf) -> float:
        start = getattr(leaf, "start_ms", 0)
        end = getattr(leaf, "end_ms", 0)
        return float(max(1, math.ceil(max(end - start, 0)
                                      / self.chunk_window_ms)))

    @staticmethod
    def _weight(leaf) -> float:
        w = 1.0
        for t in getattr(leaf, "transformers", ()):
            w *= OP_WEIGHTS.get(type(t).__name__, 1.0)
            fn = getattr(t, "function", None)
            name = getattr(fn, "name", None)
            if name in RANGE_FN_WEIGHTS:
                w *= RANGE_FN_WEIGHTS[name]
        return w
