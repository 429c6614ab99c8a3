"""Cost-based admission control: shed load BEFORE it queues to death.

Capability match for the reference's overload defenses (reference:
QueryActor's bounded priority mailbox + queryTimeoutMillis relinquish,
and the cluster's per-namespace QuotaSource) combined into one front
door: every query the HTTP layer is about to schedule first passes
``AdmissionController.admit``, which knows

- the query's **estimated cost** (workload/cost.py) and **remaining
  deadline budget** (workload/deadline.py),
- the node's **calibrated cost of a query** (seconds = a fixed part +
  a per-unit part, workload/cost.py) and its workers,
- what is already **in flight** globally, per tenant, and per priority
  class.

A query is shed with HTTP 429 + ``Retry-After`` (never queued to rot)
when any of these hold:

- its deadline already expired (reason ``expired``);
- the estimated queue delay — the predicted seconds of what is in
  flight and of the query itself, over the workers —
  exceeds the remaining budget (reason ``deadline``): executing it
  would be dead work by construction;
- admitting it would push inflight cost past its priority class's
  ceiling (reason ``overload``).  Ceilings are FRACTIONS of the global
  budget ({low: 0.5, default: 0.8, high: 1.0} by default), so bulk/
  dashboard traffic saturates at 80% and interactive high-priority
  queries always find reserved headroom — the bounded-p50 guarantee the
  overload e2e test asserts;
- the tenant is over its concurrent-query or inflight-cost budget
  (reasons ``tenant_concurrency`` / ``tenant_cost``): one tenant's
  scatter-gather storm cannot starve the rest.

``admit`` returns a context-manager permit; releasing it feeds the
measured wall time back into the cost model's calibration loop.
"""

from __future__ import annotations

import contextlib
import math
import threading
import time
from typing import Optional

from filodb_tpu.query.model import QueryContext
from filodb_tpu.query.scheduler import QueryRejected
from filodb_tpu.workload import deadline as dl
from filodb_tpu.workload.cost import CostModel

DEFAULT_PRIORITY_SHARES = {"low": 0.5, "default": 0.8, "high": 1.0,
                           # the rule engine's dedicated class (ISSUE 9):
                           # BELOW "low", so a pathological rule group
                           # saturates at 40% of the budget and can
                           # never starve interactive traffic
                           "rules": 0.4,
                           # the rollup scheduler's class (ISSUE 11):
                           # below even "rules" — tiering is the most
                           # deferrable work in the system (a deferred
                           # tick just retries; closure semantics make
                           # catch-up lossless)
                           "rollup": 0.3}


class AdmissionRejected(QueryRejected):
    """Shed by admission control: the HTTP layer maps this to
    429 Too Many Requests with a ``Retry-After`` hint."""

    def __init__(self, query_id: str, message: str, reason: str,
                 retry_after_s: float = 1.0):
        super().__init__(query_id, message)
        self.reason = reason
        self.retry_after_s = max(float(retry_after_s), 1.0)


def _metrics():
    from filodb_tpu.utils.observability import workload_metrics
    return workload_metrics()


class AdmissionController:
    """Per-dataset admission front door (one per DatasetBinding)."""

    def __init__(self, cost_model: Optional[CostModel] = None,
                 dataset: str = "",
                 max_inflight_cost: float = 10_000.0,
                 priority_shares: Optional[dict] = None,
                 tenant_max_concurrent: int = 32,
                 tenant_max_inflight_cost: Optional[float] = None,
                 workers: int = 4,
                 enabled: bool = True):
        self.cost_model = cost_model or CostModel()
        self.dataset = dataset
        self.max_inflight_cost = float(max_inflight_cost)
        # partial configs MERGE over the defaults: a shares dict naming
        # only {"high": 1.0} must not strip the "default" class every
        # unlabelled query lands in
        self.priority_shares = dict(DEFAULT_PRIORITY_SHARES)
        self.priority_shares.update(priority_shares or {})
        self.tenant_max_concurrent = int(tenant_max_concurrent)
        self.tenant_max_inflight_cost = tenant_max_inflight_cost
        self.workers = max(int(workers), 1)
        self.enabled = bool(enabled)
        self._lock = threading.Lock()
        self._inflight_cost = 0.0
        self._inflight_queries = 0
        self._tenant_cost: dict[str, float] = {}
        self._tenant_running: dict[str, int] = {}
        m = _metrics()
        self._m_admitted = m["admitted"]
        self._m_rejected = m["rejected"]
        self._m_inflight = m["inflight_cost"]
        self._m_est = m["estimated_cost"]
        self._m_inflight.set_fn(lambda: self._inflight_cost,
                                dataset=dataset)

    # ------------------------------------------------------------- lifecycle

    def configure(self, max_inflight_cost=None, tenant_max_concurrent=None,
                  tenant_max_inflight_cost=None, enabled=None) -> None:
        """Runtime knob updates (POST /admin/config)."""
        if max_inflight_cost is not None:
            self.max_inflight_cost = float(max_inflight_cost)
        if tenant_max_concurrent is not None:
            self.tenant_max_concurrent = int(tenant_max_concurrent)
        if tenant_max_inflight_cost is not None:
            self.tenant_max_inflight_cost = float(tenant_max_inflight_cost)
        if enabled is not None:
            self.enabled = bool(enabled)

    def shutdown(self) -> None:
        self._m_inflight.remove(dataset=self.dataset)

    # -------------------------------------------------------------- admission

    def queue_delay_est_s(self, extra_cost: float = 0.0) -> float:
        """Expected wait before a query of ``extra_cost`` units would
        COMPLETE (0: before a worker is free), given what is already in
        flight: the model's seconds for all of it, each query's fixed
        part and every unit's share, spread over the workers."""
        return self.cost_model.estimate_seconds(
            self._inflight_cost + extra_cost,
            self._inflight_queries + (extra_cost > 0)) / self.workers

    def admit(self, qctx: QueryContext, cost: float):
        """Admit or raise :class:`AdmissionRejected`.  Returns a context
        manager releasing the budget and calibrating the cost model."""
        if not self.enabled:
            return contextlib.nullcontext()
        cost = max(float(cost), 1.0)
        self._m_est.observe(cost, dataset=self.dataset)
        tenant = qctx.tenant or "default"
        priority = qctx.priority or "default"
        share = self.priority_shares.get(priority)
        if share is None:  # unknown class -> the default class's share
            share = self.priority_shares.get("default", 1.0)
        rem_ms = dl.remaining_ms(qctx)
        with self._lock:
            if rem_ms is not None and rem_ms <= 0:
                self._reject(qctx, tenant, priority, "expired", 1.0,
                             f"deadline expired {-rem_ms}ms ago on arrival")
            est_delay_s = self.queue_delay_est_s(cost)
            if rem_ms is not None and est_delay_s * 1000.0 > rem_ms:
                self._reject(
                    qctx, tenant, priority, "deadline",
                    math.ceil(est_delay_s),
                    f"estimated queue delay {est_delay_s * 1000:.0f}ms "
                    f"exceeds the {rem_ms}ms deadline budget left")
            ceiling = share * self.max_inflight_cost
            if self._inflight_cost + cost > ceiling:
                over = self._inflight_cost + cost - ceiling
                self._reject(
                    qctx, tenant, priority, "overload",
                    math.ceil(self.cost_model.estimate_seconds(over, 0)
                              / self.workers),
                    f"inflight cost {self._inflight_cost:.0f} + "
                    f"{cost:.0f} exceeds the {priority!r} ceiling "
                    f"{ceiling:.0f} (of {self.max_inflight_cost:.0f})")
            if self._tenant_running.get(tenant, 0) \
                    >= self.tenant_max_concurrent:
                self._reject(
                    qctx, tenant, priority, "tenant_concurrency",
                    math.ceil(self.queue_delay_est_s()
                              / self.tenant_max_concurrent) or 1,
                    f"tenant {tenant!r} already runs "
                    f"{self.tenant_max_concurrent} concurrent queries")
            tcost = self._tenant_cost.get(tenant, 0.0)
            if self.tenant_max_inflight_cost is not None \
                    and tcost + cost > self.tenant_max_inflight_cost:
                self._reject(
                    qctx, tenant, priority, "tenant_cost", 1.0,
                    f"tenant {tenant!r} inflight cost {tcost:.0f} + "
                    f"{cost:.0f} exceeds its budget "
                    f"{self.tenant_max_inflight_cost:.0f}")
            self._inflight_cost += cost
            self._inflight_queries += 1
            self._tenant_cost[tenant] = tcost + cost
            self._tenant_running[tenant] = \
                self._tenant_running.get(tenant, 0) + 1
        self._m_admitted.inc(dataset=self.dataset, priority=priority)
        return _Permit(self, tenant, cost, qctx)

    def _reject(self, qctx, tenant, priority, reason, retry_after_s,
                detail) -> None:
        self._m_rejected.inc(dataset=self.dataset, priority=priority,
                             reason=reason)
        raise AdmissionRejected(
            qctx.query_id,
            f"query shed by admission control ({reason}): {detail}",
            reason, retry_after_s)

    def _release(self, tenant: str, cost: float, seconds: float) -> None:
        with self._lock:
            self._inflight_cost = max(self._inflight_cost - cost, 0.0)
            self._inflight_queries = max(self._inflight_queries - 1, 0)
            left = self._tenant_cost.get(tenant, 0.0) - cost
            if left <= 1e-9:
                self._tenant_cost.pop(tenant, None)
            else:
                self._tenant_cost[tenant] = left
            n = self._tenant_running.get(tenant, 0) - 1
            if n <= 0:
                self._tenant_running.pop(tenant, None)
            else:
                self._tenant_running[tenant] = n
        self.cost_model.observe(cost, seconds)

    # ----------------------------------------------------------------- admin

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "enabled": self.enabled,
                "max_inflight_cost": self.max_inflight_cost,
                "priority_shares": dict(self.priority_shares),
                "tenant_max_concurrent": self.tenant_max_concurrent,
                "tenant_max_inflight_cost": self.tenant_max_inflight_cost,
                "inflight_cost": self._inflight_cost,
                "inflight_queries": self._inflight_queries,
                "tenant_inflight_cost": dict(self._tenant_cost),
                "tenant_running": dict(self._tenant_running),
                "sec_per_unit": self.cost_model.sec_per_unit,
                "sec_fixed": self.cost_model.fixed_seconds,
                "calibration_observations": self.cost_model.observations,
            }


class _Permit:
    """Releases admitted budget on exit and calibrates the cost model
    with the measured wall time.

    While held, the permit is stamped onto the query's
    ``QueryContext.admission_permit`` (fleet batching tier, ISSUE 20):
    a batch leader re-checks ``released`` at stack time, so a query
    whose admission window closed mid-batch is dropped from the stack
    instead of executing outside it."""

    def __init__(self, ctrl: AdmissionController, tenant: str, cost: float,
                 qctx: Optional[QueryContext] = None):
        self._ctrl = ctrl
        self._tenant = tenant
        self.cost = cost
        self._t0 = 0.0
        self._qctx = qctx
        self.released = False

    def __enter__(self):
        self._t0 = time.perf_counter()
        if self._qctx is not None:
            self._qctx.admission_permit = self
        return self

    def __exit__(self, exc_type, exc, tb):
        self.released = True
        if self._qctx is not None \
                and self._qctx.admission_permit is self:
            self._qctx.admission_permit = None
        self._ctrl._release(self._tenant, self.cost,
                            time.perf_counter() - self._t0)
        return False


def tenant_of(filters, shard_key_columns=("_ws_", "_ns_")) -> str:
    """Derive the tenant identity from a query's shard-key equality
    filters (the reference keys its quotas the same way: workspace/
    namespace).  Empty string when the query names no tenant."""
    from filodb_tpu.core.filters import equals_value
    parts = []
    for col in shard_key_columns:
        v = equals_value(list(filters), col)
        if v is not None:
            parts.append(v)
    return "/".join(parts)


def plan_tenant(plan) -> str:
    """Tenant of a logical/exec plan tree: the first leaf carrying
    shard-key filters decides (scatter-gather children share them)."""
    filters = getattr(plan, "filters", None)
    if filters:
        t = tenant_of(filters)
        if t:
            return t
    for attr in ("children", ):
        for child in getattr(plan, attr, ()) or ():
            t = plan_tenant(child)
            if t:
                return t
    for attr in ("vectors", "series", "raw_series", "lhs", "rhs"):
        child = getattr(plan, attr, None)
        if child is not None and not isinstance(child, (int, float)):
            t = plan_tenant(child)
            if t:
                return t
    return ""
