"""Device-resource observability: HBM ledger, JIT telemetry, flight recorder.

PR 7 made the *host* side of a query visible (traces, per-stage stats,
slowlog); the resource that actually bounds the north star — TPU HBM —
stayed dark: `DeviceGrid` arenas track ``bytes_resident``/``evictions``
internally and the ODP page cache enforces a byte budget, but nothing
exposed who holds the memory, why it got evicted, or what compiled
when.  This module is the device-side counterpart, three pillars:

1. **HBM residency ledger** (:class:`HbmLedger`, singleton ``LEDGER``):
   every ``jax.device_put``/resident-plane commit in ``filodb_tpu/``
   routes through :meth:`HbmLedger.device_put` / :meth:`HbmLedger.track`
   (lint-enforced by tests/test_sentinel_lint.py), tagged with an owner
   (shard/schema/column) and a format (``dense``/``compressed``/
   ``mesh-staged``/``scratch``).  Tracked bytes are released by a
   ``weakref.finalize`` on the device array — exactly when JAX frees the
   buffer — so per-owner totals stay byte-accurate through eviction and
   GC without any explicit release calls.  Host-side byte pools that
   behave like arenas (the ODP page cache) register a sampling callback
   instead.  Exposed as ``filodb_device_hbm_bytes{owner,format}``,
   high-watermark gauges, and eviction-attribution counters
   (``filodb_device_evictions_total{owner,reason}``), reconciled against
   ``device.memory_stats()`` where the backend provides it.

2. **Compile telemetry** (:class:`CompileWatch`, singleton
   ``COMPILE_WATCH``): :func:`jit` wraps ``jax.jit`` for the entry
   points in devicestore/mesh/ops, detecting compiles via the jitted
   callable's cache growth (no per-call key hashing on the hot path) and
   recording per-program compile count, wall time, and an abstract-shape
   key.  A recompile-storm detector flags programs compiling more than N
   distinct shapes within a window — the classic JAX production failure
   — in the log, the ``filodb_jit_recompile_storms_total`` counter, and
   the slow-query log entries (utils/forensics.py).

3. **Flight recorder** (:class:`FlightRecorder`, singleton ``FLIGHT``):
   a bounded lock-free ring of recent structured events (ingest batches,
   flushes, evictions, compiles, ODP page-ins, breaker trips, query
   start/end) dumped on demand by ``/admin/flightrecorder`` and
   auto-dumped to the log on integrity failure or unhandled-exception
   shutdown — the black box for postmortems.

4. **Kernel flight deck** (:class:`KernelTimer`, singleton
   ``KERNEL_TIMER``; ISSUE 15): sampled *device-time* accounting for
   every :func:`jit`-wrapped program.  Every launch counts (exactly);
   every Nth launch per program (``kernel-sample-1-in``, default 64)
   additionally times ``block_until_ready`` on the result and folds the
   measured seconds into a per-program EWMA + streaming log-histogram.
   Joined with the per-plan ``hbm_read_bytes`` notes from devicestore,
   that yields a LIVE achieved-bytes/s per program against the
   serving device's published HBM peak (``HBM_ROOF_BYTES_PER_S``, a
   table keyed by jax's ``device_kind``; a device the table lacks gets
   no roofline fraction rather than an assumed one).  A regression sentry compares each program's EWMA
   against a learned baseline (seeded after a quiet warmup, ratcheted
   DOWNWARD only, persisted in the metastore KV by the standalone
   server): sustained >= 1.5x degradation over a window fires ONE
   ``kernel.regression`` flight event per episode (re-armed on
   recovery — the recompile-storm episode discipline), counted in
   ``filodb_kernel_regressions_total{program}`` and levelled in
   ``filodb_kernel_regressed{program}`` for the self-monitoring alert
   rules.  ``/admin/kernels`` joins this ledger with the compile table.

Everything is stdlib + jax-optional: with no jax importable the ledger
wrapper falls back to identity and the compile wrapper to the plain
function, so host-only deployments lose nothing.
"""

from __future__ import annotations

import bisect
import functools
import itertools
import logging
import threading
import time
import weakref
from typing import Callable, Optional

_LOG = logging.getLogger("filodb.devicewatch")

# kill switch: set_enabled(False) turns every wrapper into a pass-through
# (operators set it via the standalone "devicewatch" config block)
_ENABLED = True


def set_enabled(flag: bool) -> None:
    global _ENABLED
    _ENABLED = bool(flag)


def enabled() -> bool:
    return _ENABLED


# ---------------------------------------------------------------------------
# metric handles, resolved once (hot paths must not take the registry lock)
# ---------------------------------------------------------------------------

_METRICS = None


def device_metrics() -> dict:
    """Canonical device-resource metrics: one place defines the names so
    the ledger, /metrics, and /admin/device can never drift."""
    global _METRICS
    if _METRICS is None:
        from filodb_tpu.utils.observability import REGISTRY
        _METRICS = {
            "hbm_bytes": REGISTRY.gauge(
                "filodb_device_hbm_bytes",
                "ledger-tracked device-resident bytes by owner and "
                "resident format"),
            "hbm_watermark": REGISTRY.gauge(
                "filodb_device_hbm_high_watermark_bytes",
                "high watermark of ledger-tracked bytes by owner/format"),
            "evictions": REGISTRY.counter(
                "filodb_device_evictions_total",
                "device/pool resident evictions by owner and reason "
                "(budget_overflow | epoch_purge | integrity_quarantine)"),
            "jit_compiles": REGISTRY.counter(
                "filodb_jit_compiles_total",
                "XLA program compiles by wrapped jit entry point"),
            "jit_seconds": REGISTRY.histogram(
                "filodb_jit_compile_seconds",
                "wall time of calls that compiled a new program "
                "(trace + lower + compile)"),
            "jit_storms": REGISTRY.counter(
                "filodb_jit_recompile_storms_total",
                "recompile storms detected (program exceeded the "
                "distinct-shape threshold within the window)"),
            "kernel_launches": REGISTRY.counter(
                "filodb_kernel_launches_total",
                "wrapped-program launches (every launch counts; "
                "reconciles exactly with the /admin/kernels table)"),
            "kernel_seconds": REGISTRY.counter(
                "filodb_kernel_device_seconds",
                "measured device seconds of SAMPLED launches "
                "(block_until_ready wall time, 1-in-N per program)"),
            "kernel_roofline": REGISTRY.gauge(
                "filodb_kernel_roofline_fraction",
                "live achieved HBM bytes/s per program as a fraction "
                "of the device's published HBM peak"),
            "kernel_regressions": REGISTRY.counter(
                "filodb_kernel_regressions_total",
                "kernel-regression episodes: sustained EWMA device "
                "time >= factor x learned baseline"),
            "kernel_regressed": REGISTRY.gauge(
                "filodb_kernel_regressed",
                "1 while the program's EWMA device time counts as "
                "regressed vs its learned baseline, else 0 — the LEVEL "
                "the self-monitoring alert rules watch (a counter's "
                "label set is born at 1, invisible to increase())"),
        }
    return _METRICS


# ---------------------------------------------------------------------------
# 1. HBM residency ledger
# ---------------------------------------------------------------------------


class HbmLedger:
    """Process-wide accounting of device-resident bytes by owner/format.

    ``track`` registers a device array under ``(owner, fmt)`` and arms a
    ``weakref.finalize`` that gives the bytes back when JAX frees the
    buffer; totals therefore reconcile exactly with the set of live
    tracked arrays at any point (tests/test_devicewatch.py asserts this
    across commit -> query -> overflow-eviction -> ODP churn).  The
    active query's ExecContext is credited/debited so QueryStats carries
    the HBM delta a query caused."""

    def __init__(self) -> None:
        # the ledger sits between the device caches and the metrics
        # registry in the repo's lock hierarchy (filolint lockorder.py
        # holds these; a future back-edge is a build failure):
        # lock-order: DeviceGridCache._lock < HbmLedger._lock
        # lock-order: HbmLedger._lock < MetricsRegistry._lock
        self._lock = threading.Lock()
        # (owner, fmt) -> live bytes / high watermark / live array count
        self._bytes: dict[tuple, int] = {}
        self._marks: dict[tuple, int] = {}
        self._counts: dict[tuple, int] = {}
        # per-device live bytes (reconciliation vs device.memory_stats)
        self._dev_bytes: dict[str, int] = {}
        # id(arr) -> finalizer: dedups repeat track() of one array and
        # keeps the finalize object alive
        self._fins: dict[int, object] = {}
        # host byte pools that behave like arenas (ODP page cache):
        # name -> (bytes_fn, budget_fn or None)
        self._pools: dict[str, tuple] = {}

    # ------------------------------------------------------------- tracking

    def device_put(self, x, device=None, *, owner: str,
                   fmt: str = "dense"):
        """``jax.device_put`` + ledger registration.  The ONLY sanctioned
        way to move bytes onto the accelerator from ``filodb_tpu/``
        (lint-enforced); a put of an already-resident array is a no-op
        in jax and is NOT re-tracked (its original owner keeps it)."""
        import jax
        out = jax.device_put(x, device)
        if _ENABLED and out is not x:
            self.track(out, owner=owner, fmt=fmt)
        return out

    def track(self, arr, *, owner: str, fmt: str = "dense") -> None:
        """Register an already-device-resident array (e.g. the output of
        a staging jit program).  Idempotent per array identity."""
        if not _ENABLED or arr is None:
            return
        try:
            nbytes = int(arr.nbytes)
            key = id(arr)
        except Exception:  # noqa: BLE001 — tracers/odd leaves: not resident
            return
        dev = self._device_label(arr)
        lkey = (owner, fmt)
        with self._lock:
            if key in self._fins:
                return
            try:
                fin = weakref.finalize(arr, self._untrack, key, lkey, dev,
                                       nbytes)
            except TypeError:
                return            # object without weakref support
            fin.atexit = False    # no dump of bookkeeping at interpreter exit
            self._fins[key] = fin
            total = self._bytes.get(lkey, 0) + nbytes
            self._bytes[lkey] = total
            self._counts[lkey] = self._counts.get(lkey, 0) + 1
            if total > self._marks.get(lkey, 0):
                self._marks[lkey] = total
                device_metrics()["hbm_watermark"].set(total, owner=owner,
                                                      format=fmt)
            self._dev_bytes[dev] = self._dev_bytes.get(dev, 0) + nbytes
            # gauge write stays UNDER the ledger lock: a concurrent
            # finalizer's set racing a deferred set here would leave the
            # exported residency permanently stale (internally-ordered
            # totals must reach the gauge in the same order)
            device_metrics()["hbm_bytes"].set(total, owner=owner,
                                              format=fmt)
        self._note_query_delta(nbytes)

    def _untrack(self, key: int, lkey: tuple, dev: str,
                 nbytes: int) -> None:
        """weakref.finalize callback: the buffer was freed."""
        with self._lock:
            self._fins.pop(key, None)
            total = self._bytes.get(lkey, 0) - nbytes
            self._bytes[lkey] = total
            self._counts[lkey] = self._counts.get(lkey, 0) - 1
            self._dev_bytes[dev] = self._dev_bytes.get(dev, 0) - nbytes
            try:
                # under the lock, same ordering argument as track()
                device_metrics()["hbm_bytes"].set(total, owner=lkey[0],
                                                  format=lkey[1])
            except Exception:  # noqa: BLE001 — interpreter teardown
                return
        self._note_query_delta(-nbytes)

    @staticmethod
    def _device_label(arr) -> str:
        try:
            devs = getattr(arr, "devices", None)
            if callable(devs):
                ds = sorted(str(d) for d in devs())
                return ds[0] if len(ds) == 1 else "+".join(ds)
        except Exception:  # noqa: BLE001
            pass
        return "unknown"

    @staticmethod
    def _note_query_delta(nbytes: int) -> None:
        """Attribute a residency change to the query that caused it (the
        finalizer runs inline on CPython refcount drops, so eviction
        debits land on the evicting query's thread too)."""
        try:
            from filodb_tpu.query.exec import active_exec_ctx
            ctx = active_exec_ctx()
            if ctx is not None:
                ctx.note_counts(hbm_delta=nbytes)
        except Exception:  # noqa: BLE001 — accounting never breaks work
            pass

    # -------------------------------------------------------------- pools

    def register_pool(self, name: str, bytes_fn: Callable[[], int],
                      budget_fn: Optional[Callable[[], int]] = None,
                      fmt: str = "odp-page-cache") -> None:
        """Register a host-side byte pool (sampled at read time).  The
        pool shows in the ledger tree and as
        ``filodb_device_hbm_bytes{owner=<name>,format=<fmt>}``."""
        with self._lock:
            self._pools[name] = (bytes_fn, budget_fn, fmt)
        device_metrics()["hbm_bytes"].set_fn(
            lambda: float(self._pool_bytes(name)), owner=name, format=fmt)

    def deregister_pool(self, name: str) -> None:
        with self._lock:
            pool = self._pools.pop(name, None)
        if pool is not None:
            # remove under the fmt the pool REGISTERED with — a
            # hardcoded label here leaked the set_fn (and its captured
            # instance) for every non-default fmt
            device_metrics()["hbm_bytes"].remove(owner=name,
                                                 format=pool[2])

    def _pool_bytes(self, name: str) -> int:
        pool = self._pools.get(name)
        if pool is None:
            return 0
        try:
            return int(pool[0]())
        except Exception:  # noqa: BLE001 — pool owner shut down
            return 0

    # ----------------------------------------------------------- evictions

    def note_eviction(self, owner: str, reason: str, n: int = 1,
                      nbytes: int = 0) -> None:
        """Attribute an eviction: ``budget_overflow`` (arena over its
        byte budget), ``epoch_purge`` (data changed: freeze/repin/
        invalidation), or ``integrity_quarantine``."""
        if not _ENABLED:
            return
        device_metrics()["evictions"].inc(n, owner=owner, reason=reason)
        FLIGHT.record("evict", owner=owner, reason=reason, n=n,
                      bytes=nbytes)

    # ------------------------------------------------------------- reading

    def owners(self) -> dict:
        """{owner: {format: {bytes, high_watermark, arrays}}} snapshot."""
        with self._lock:
            keys = set(self._bytes) | set(self._marks)
            out: dict = {}
            for owner, fmt in sorted(keys):
                out.setdefault(owner, {})[fmt] = {
                    "bytes": self._bytes.get((owner, fmt), 0),
                    "high_watermark": self._marks.get((owner, fmt), 0),
                    "arrays": self._counts.get((owner, fmt), 0),
                }
        return out

    def total_bytes(self, owner: Optional[str] = None) -> int:
        with self._lock:
            return sum(v for (o, _f), v in self._bytes.items()
                       if owner is None or o == owner)

    def pools(self) -> dict:
        with self._lock:
            names = list(self._pools.items())
        out = {}
        for name, (bytes_fn, budget_fn, _fmt) in names:
            row = {"bytes": 0}
            try:
                row["bytes"] = int(bytes_fn())
                if budget_fn is not None:
                    row["budget"] = int(budget_fn())
            except Exception:  # noqa: BLE001 — pool owner shut down
                pass
            out[name] = row
        return out

    def reconcile(self) -> dict:
        """Per-device ledger totals vs ``device.memory_stats()`` where
        the backend reports it (TPU/GPU ``bytes_in_use``); the gap is
        XLA scratch + untracked allocations."""
        with self._lock:
            dev_bytes = dict(self._dev_bytes)
        out = {}
        stats_by_label = {}
        try:
            import jax
            for d in jax.devices():
                stats_by_label[str(d)] = d.memory_stats()
        except Exception:  # noqa: BLE001 — no backend
            pass
        for label in sorted(set(dev_bytes) | set(stats_by_label)):
            row = {"ledger_bytes": dev_bytes.get(label, 0)}
            st = stats_by_label.get(label)
            if isinstance(st, dict) and "bytes_in_use" in st:
                row["bytes_in_use"] = int(st["bytes_in_use"])
                row["untracked_bytes"] = \
                    row["bytes_in_use"] - row["ledger_bytes"]
                if "bytes_limit" in st:
                    row["bytes_limit"] = int(st["bytes_limit"])
            out[label] = row
        return out


LEDGER = HbmLedger()


# ---------------------------------------------------------------------------
# 2. JIT compile telemetry + recompile-storm detector
# ---------------------------------------------------------------------------


class CompileWatch:
    """Per-program compile table + storm detection.

    A *storm* is one program compiling >= ``storm_shapes`` distinct
    shapes within ``storm_window_s`` — in JAX that means some query/data
    axis is leaking into the abstract shape (unpadded lanes, per-request
    nsteps, ...) and every request pays a fresh XLA compile.  Detection
    logs once per storm, bumps the storm counter, and stays "active" for
    one window so the slow-query log can flag affected entries."""

    def __init__(self, storm_shapes: int = 8,
                 storm_window_s: float = 60.0):
        self.storm_shapes = int(storm_shapes)
        self.storm_window_s = float(storm_window_s)
        self._lock = threading.Lock()
        # program -> row dict (compiles/seconds/shapes/recent/storms)
        self._progs: dict[str, dict] = {}

    def configure(self, storm_shapes: Optional[int] = None,
                  storm_window_s: Optional[float] = None) -> None:
        with self._lock:
            if storm_shapes is not None:
                self.storm_shapes = max(2, int(storm_shapes))
            if storm_window_s is not None:
                self.storm_window_s = max(1.0, float(storm_window_s))

    def note_compile(self, program: str, seconds: float,
                     shape_key: str) -> None:
        m = device_metrics()
        m["jit_compiles"].inc(program=program)
        m["jit_seconds"].observe(seconds, program=program)
        now = time.monotonic()
        storm = False
        with self._lock:
            row = self._progs.get(program)
            if row is None:
                row = self._progs[program] = {
                    "compiles": 0, "seconds": 0.0, "shapes": [],
                    "recent": [], "storms": 0, "storm_until": 0.0,
                    "last_key": ""}
            row["compiles"] += 1
            row["seconds"] += seconds
            row["last_key"] = shape_key
            if shape_key not in row["shapes"]:
                row["shapes"].append(shape_key)
                del row["shapes"][:-64]          # bound the key table
            recent = row["recent"]
            recent.append(now)
            cutoff = now - self.storm_window_s
            while recent and recent[0] < cutoff:
                recent.pop(0)
            if len(recent) >= self.storm_shapes \
                    and now >= row["storm_until"]:
                row["storms"] += 1
                row["storm_until"] = now + self.storm_window_s
                storm = True
        FLIGHT.record("jit.compile", program=program,
                      seconds=round(seconds, 6), key=shape_key)
        if storm:
            m["jit_storms"].inc(program=program)
            FLIGHT.record("jit.storm", program=program,
                          window_s=self.storm_window_s,
                          compiles_in_window=self.storm_shapes)
            _LOG.warning(
                "recompile storm: program %r compiled %d distinct shapes "
                "within %.0fs (last key %s) — some query/data axis is "
                "reaching the abstract shape; expect every request to "
                "pay a fresh XLA compile", program, self.storm_shapes,
                self.storm_window_s, shape_key)

    def active_storms(self) -> list[str]:
        """Programs inside a storm window right now (slowlog flag)."""
        now = time.monotonic()
        with self._lock:
            return [p for p, row in self._progs.items()
                    if row["storm_until"] > now]

    def table(self) -> list[dict]:
        """The /admin/device compile table, most-compiled first."""
        with self._lock:
            rows = [{"program": p, "compiles": r["compiles"],
                     "compile_seconds": round(r["seconds"], 6),
                     "distinct_shapes": len(r["shapes"]),
                     "storms": r["storms"],
                     "last_shape_key": r["last_key"]}
                    for p, r in self._progs.items()]
        rows.sort(key=lambda r: -r["compiles"])
        return rows


COMPILE_WATCH = CompileWatch()


# ---------------------------------------------------------------------------
# 2b. Kernel flight deck: sampled device-time ledger + regression sentry
# ---------------------------------------------------------------------------

# streaming-histogram bucket edges (seconds): powers of two from 1us to
# ~16s — wide enough for a CPU-interpret kernel, fine enough to tell a
# 2x regression from noise
_KHIST_EDGES = tuple(2.0 ** i * 1e-6 for i in range(25))


# published peak HBM bandwidth of one chip, bytes/s, keyed by jax's
# ``device_kind`` (Google Cloud TPU documentation, system architecture
# pages: v4 1228 GB/s, v5e 819 GB/s, v5p 2765 GB/s, v6e 1640 GB/s).
# A device that is not here has no roof: /admin/kernels then leaves
# ``roofline_fraction`` out instead of dividing by a guess.
HBM_ROOF_BYTES_PER_S = {
    "TPU v4": 1228e9,
    "TPU v5 lite": 819e9,
    "TPU v5": 2765e9,
    "TPU v6 lite": 1640e9,
}

def device_kind() -> Optional[str]:
    """``device_kind`` of the first device (None where jax cannot say)."""
    try:
        import jax
        return jax.devices()[0].device_kind
    except Exception:  # noqa: BLE001 — host-only deployment
        return None


def hbm_roof() -> Optional[float]:
    """The serving device's HBM peak from the table, or None."""
    return HBM_ROOF_BYTES_PER_S.get(device_kind())


class KernelTimer:
    """Per-program device-time ledger, sampled (ISSUE 15).

    Every wrapped launch counts (``launches`` advances on each call and
    reconciles exactly with ``filodb_kernel_launches_total``); every Nth
    launch per program is *sampled*: the wrapper times
    ``block_until_ready`` on the result and folds the wall seconds —
    which on an otherwise-idle device IS the dispatch+device time — into
    an EWMA, a streaming log-histogram, and the active query's
    per-program ``devicePrograms`` split.  Launches that compiled are
    never folded (trace+compile wall time is host work; the runtime
    compile telemetry above already accounts it).

    The **regression sentry**: once a program has ``baseline_min_samples``
    sampled launches its baseline seeds from the EWMA and thereafter
    ratchets DOWNWARD only (a program can only ever prove itself
    faster).  An EWMA sustained >= ``regression_factor`` x baseline for
    ``regression_window_s`` opens ONE episode: a ``kernel.regression``
    flight event, ``filodb_kernel_regressions_total{program}``, and the
    ``filodb_kernel_regressed{program}`` level flips to 1 until the EWMA
    recovers below the factor (re-armed — the recompile-storm episode
    discipline).  Baselines persist through an attached store (the
    standalone server wires the metastore KV) so a restart does not
    relearn a regressed program's slow state as its baseline: the
    persisted (healthy) floor wins.

    Deterministic fault hook: ``set_fault_delay(program, s)`` sleeps
    inside the sampled timing region — the injection point
    ``integrity/faultinject.py`` drives for the sentry chaos tests.
    """

    def __init__(self, sample_1_in: int = 64,
                 regression_factor: float = 1.5,
                 regression_window_s: float = 30.0,
                 baseline_min_samples: int = 8,
                 ewma_alpha: float = 0.25):
        self.sample_1_in = int(sample_1_in)
        self.regression_factor = float(regression_factor)
        self.regression_window_s = float(regression_window_s)
        self.baseline_min_samples = int(baseline_min_samples)
        self.ewma_alpha = float(ewma_alpha)
        self._lock = threading.Lock()
        self._rows: dict[str, dict] = {}
        self._fault: dict[str, float] = {}
        # baseline persistence hooks (standalone wires the metastore KV)
        self._baseline_save: Optional[Callable[[str, float], None]] = None

    def configure(self, sample_1_in: Optional[int] = None,
                  regression_factor: Optional[float] = None,
                  regression_window_s: Optional[float] = None,
                  baseline_min_samples: Optional[int] = None) -> None:
        with self._lock:
            if sample_1_in is not None:
                # 0 disables sampling entirely; 1 = time every launch
                self.sample_1_in = max(0, int(sample_1_in))
            if regression_factor is not None:
                self.regression_factor = max(1.01,
                                             float(regression_factor))
            if regression_window_s is not None:
                self.regression_window_s = max(0.0,
                                               float(regression_window_s))
            if baseline_min_samples is not None:
                self.baseline_min_samples = max(1,
                                                int(baseline_min_samples))

    def attach_baseline_store(self, load_fn: Optional[Callable] = None,
                              save_fn: Optional[Callable] = None) -> None:
        """Wire baseline persistence: ``load_fn() -> {program: seconds}``
        merged in now (min wins — a persisted healthy floor beats a
        freshly-relearned slow state), ``save_fn(program, seconds)``
        called on seed/ratchet (rate-limited to >=5% improvements)."""
        stored: dict = {}
        if load_fn is not None:
            try:
                stored = {str(k): float(v)
                          for k, v in (load_fn() or {}).items()}
            except Exception:  # noqa: BLE001 — a broken store loses
                stored = {}   # persistence, never serving
        with self._lock:
            self._baseline_save = save_fn
            for program, sec in stored.items():
                row = self._row_locked(program)
                if row["baseline"] is None or sec < row["baseline"]:
                    row["baseline"] = sec
                row["persisted_baseline"] = sec

    # ------------------------------------------------------- fault hook

    def set_fault_delay(self, program: str, seconds: float) -> None:
        with self._lock:
            self._fault[program] = float(seconds)

    def clear_fault_delay(self, program: str) -> None:
        with self._lock:
            self._fault.pop(program, None)

    # ----------------------------------------------------------- ledger

    def _row_locked(self, program: str) -> dict:
        row = self._rows.get(program)
        if row is None:
            row = self._rows[program] = {
                "launches": 0, "sampled": 0, "seconds": 0.0,
                "ewma": None, "hist": [0] * (len(_KHIST_EDGES) + 1),
                "last_key": "", "bytes": 0,
                "baseline": None, "persisted_baseline": None,
                "over_since": None, "regressed": False, "episodes": 0,
            }
        return row

    def tick(self, program: str) -> bool:
        """Count one launch; True when this launch should be sampled."""
        n = self.sample_1_in
        with self._lock:
            row = self._row_locked(program)
            row["launches"] += 1
            launch = row["launches"]
        device_metrics()["kernel_launches"].inc(program=program)
        return n > 0 and (launch - 1) % n == 0

    def note_bytes(self, program: str, nbytes: int) -> None:
        """Attribute the HBM bytes a serving program read (devicestore's
        per-plan hbm_read_bytes notes) — the numerator of the live
        achieved-bytes/s join.  Gated on the kill switch like the
        wrapper: with devicewatch off, launches freeze, and bytes
        accumulating against a frozen launch count would permanently
        inflate achieved-bytes/s after a disable/enable cycle."""
        if not _ENABLED or nbytes <= 0:
            return
        with self._lock:
            self._row_locked(program)["bytes"] += int(nbytes)

    def sample(self, program: str, out, t0: float,
               args: tuple = (), kwargs: Optional[dict] = None) -> None:
        """Time a sampled launch: wait for the result on device, fold
        the wall seconds since ``t0`` (the pre-dispatch stamp).  Runs
        OUTSIDE the timer lock — the wait can be milliseconds."""
        with self._lock:
            delay = self._fault.get(program)
        if delay:
            time.sleep(delay)   # deterministic faultinject slowdown
        try:
            import jax
            # first-leaf probe (outputs are uniformly concrete or
            # uniformly tracers): a wrapped program invoked inside an
            # OUTER trace returns tracers — trace time, not device time
            leaf = out
            while isinstance(leaf, (tuple, list)) and leaf:
                leaf = leaf[0]
            if isinstance(leaf, dict) and leaf:
                leaf = next(iter(leaf.values()))
            if isinstance(leaf, jax.core.Tracer):
                return
            jax.block_until_ready(out)
        except Exception:  # noqa: BLE001 — accounting never breaks work
            return
        dt = time.perf_counter() - t0
        # the CHEAP key (shapes + scalars, no dtype formatting): this
        # runs on the serving path per sample, where the full
        # descriptive _shape_key (compile-only) costs ~100us
        self._fold(program, dt, _sampled_key(args, kwargs or {}))

    def _fold(self, program: str, dt: float, shape_key: str) -> None:
        now = time.monotonic()
        fired = recovered = False
        persist = None
        with self._lock:
            row = self._row_locked(program)
            row["sampled"] += 1
            row["seconds"] += dt
            prev = row["ewma"]
            ew = dt if prev is None \
                else prev + self.ewma_alpha * (dt - prev)
            row["ewma"] = ew
            row["hist"][bisect.bisect_left(_KHIST_EDGES, dt)] += 1
            row["last_key"] = shape_key
            base = row["baseline"]
            if base is None:
                if row["sampled"] >= self.baseline_min_samples:
                    row["baseline"] = base = ew          # seed
            elif ew < base and row["sampled"] >= self.baseline_min_samples:
                # ratchet DOWN — but only once the EWMA has warmed: a
                # restart resets the EWMA, so the FIRST sample (ew = dt
                # exactly) of a mixed-shape program could otherwise
                # ratchet a loaded healthy baseline down to one tiny
                # query's time and page every normal launch thereafter
                row["baseline"] = base = ew
            last = row["persisted_baseline"]
            if base is not None and (last is None or base < last * 0.95):
                row["persisted_baseline"] = base
                persist = base
            if base is not None:
                if ew >= self.regression_factor * base:
                    if row["over_since"] is None:
                        row["over_since"] = now
                    elif not row["regressed"] and now - row["over_since"] \
                            >= self.regression_window_s:
                        row["regressed"] = True
                        row["episodes"] += 1
                        fired = True
                else:
                    row["over_since"] = None
                    if row["regressed"]:
                        row["regressed"] = False
                        recovered = True
            bytes_total = row["bytes"]
            launches = row["launches"]
            save = self._baseline_save
        m = device_metrics()
        m["kernel_seconds"].inc(dt, program=program)
        roof = hbm_roof()
        if roof and bytes_total and launches and ew > 0:
            m["kernel_roofline"].set(
                (bytes_total / launches) / ew / roof, program=program)
        if persist is not None:
            # seeding also exports the regressed=0 level row so the
            # alert rules see the healthy state before any episode.
            # A fire is impossible here: persist only happens on
            # seed (base = ew) or ratchet-down (ew < base), and both
            # contradict ew >= factor * base.
            m["kernel_regressed"].set(0.0, program=program)
            if save is not None:
                try:
                    save(program, persist)
                except Exception:  # noqa: BLE001 — persistence is
                    pass           # best-effort, never serving-fatal
        if fired:
            m["kernel_regressions"].inc(program=program)
            m["kernel_regressed"].set(1.0, program=program)
            FLIGHT.record("kernel.regression", program=program,
                          ewma_s=round(ew, 6),
                          baseline_s=round(base, 6),
                          factor=self.regression_factor)
            _LOG.warning(
                "kernel regression: program %r EWMA device time %.6fs "
                "is >= %.2fx its learned baseline %.6fs (sustained "
                "%.1fs) — check /admin/kernels for the roofline "
                "position and /admin/device for recompile storms",
                program, ew, self.regression_factor, base,
                self.regression_window_s)
        if recovered:
            m["kernel_regressed"].set(0.0, program=program)
            FLIGHT.record("kernel.recovery", program=program,
                          ewma_s=round(ew, 6),
                          baseline_s=round(base, 6))
        self._note_query_program(program, dt)

    @staticmethod
    def _note_query_program(program: str, dt: float) -> None:
        """Attribute a sampled launch's device seconds to the query
        running on this thread (QueryStats.devicePrograms split)."""
        try:
            from filodb_tpu.query.exec import active_exec_ctx
            ctx = active_exec_ctx()
            if ctx is not None:
                ctx.note_device_program(program, dt)
        except Exception:  # noqa: BLE001 — accounting never breaks work
            pass

    # ---------------------------------------------------------- reading

    def table(self) -> list[dict]:
        """The /admin/kernels ledger rows, most-launched first."""
        roof = hbm_roof()
        with self._lock:
            rows = []
            for program, r in self._rows.items():
                ew = r["ewma"]
                achieved = None
                if r["bytes"] and r["launches"] and ew:
                    achieved = (r["bytes"] / r["launches"]) / ew
                row = {
                    "program": program,
                    "launches": r["launches"],
                    "sampled": r["sampled"],
                    "device_seconds": round(r["seconds"], 6),
                    "ewma_device_s": round(ew, 6) if ew is not None
                    else None,
                    "bytes_total": r["bytes"],
                    "achieved_bytes_per_s": round(achieved, 1)
                    if achieved is not None else None,
                    "baseline_s": round(r["baseline"], 6)
                    if r["baseline"] is not None else None,
                    "regressed": r["regressed"],
                    "episodes": r["episodes"],
                    "last_shape_key": r["last_key"],
                    "seconds_histogram": {
                        ("+Inf" if i == len(_KHIST_EDGES)
                         else repr(_KHIST_EDGES[i])): n
                        for i, n in enumerate(r["hist"]) if n},
                }
                if roof:
                    row["roofline_fraction"] = round(achieved / roof, 6) \
                        if achieved is not None else None
                rows.append(row)
        rows.sort(key=lambda r: -r["launches"])
        return rows


KERNEL_TIMER = KernelTimer()


def kernel_summary() -> dict:
    """The /admin/kernels payload: the sampled device-time ledger joined
    with the compile table (one row per program carries launches,
    compiles, EWMA device time, achieved GB/s, roofline %, sentry
    state)."""
    compiles = {r["program"]: r for r in COMPILE_WATCH.table()}
    rows = KERNEL_TIMER.table()
    for row in rows:
        c = compiles.get(row["program"])
        row["compiles"] = c["compiles"] if c else 0
        row["compile_seconds"] = c["compile_seconds"] if c else 0.0
        row["storms"] = c["storms"] if c else 0
    return {
        "enabled": _ENABLED,
        "sample_1_in": KERNEL_TIMER.sample_1_in,
        "device_kind": device_kind(),
        "hbm_roof_bytes_per_s": hbm_roof(),
        "regression": {
            "factor": KERNEL_TIMER.regression_factor,
            "window_s": KERNEL_TIMER.regression_window_s,
            "baseline_min_samples": KERNEL_TIMER.baseline_min_samples,
        },
        "programs": rows,
    }


def _sampled_key(args: tuple, kwargs: dict) -> str:
    """Cheap shape key for SAMPLED launches: leaf shapes + small
    scalars, no dtype formatting — runs on the serving path once per
    sample, so it must stay in the tens of microseconds (the full
    descriptive :func:`_shape_key` is compile-only)."""
    try:
        from jax import tree_util
        leaves, _ = tree_util.tree_flatten((args, kwargs))
        parts = []
        for leaf in leaves[:32]:
            shape = getattr(leaf, "shape", None)
            parts.append(str(shape) if shape is not None
                         else str(leaf)[:16])
        if len(leaves) > 32:
            parts.append(f"...+{len(leaves) - 32}")
        return ";".join(parts)
    except Exception:  # noqa: BLE001 — key is best-effort description
        return "?"


def _shape_key(args: tuple, kwargs: dict) -> str:
    """Descriptive abstract-shape key, computed ONLY when a compile was
    detected (never on the cached hot path)."""
    try:
        from jax import tree_util
        leaves, treedef = tree_util.tree_flatten((args, kwargs))
        parts = []
        for leaf in leaves[:32]:
            shape = getattr(leaf, "shape", None)
            dtype = getattr(leaf, "dtype", None)
            if shape is not None and dtype is not None:
                parts.append(f"{dtype}[{','.join(map(str, shape))}]")
            else:
                parts.append(repr(leaf)[:32])
        if len(leaves) > 32:
            parts.append(f"...+{len(leaves) - 32}")
        return ";".join(parts)
    except Exception:  # noqa: BLE001 — key is best-effort description
        return "?"


def jit(fn=None, *, program: Optional[str] = None, **jit_kwargs):
    """Drop-in ``jax.jit`` replacement with compile telemetry.

    Usable exactly like the sites it replaces::

        @functools.partial(devicewatch.jit, static_argnames=("q",))
        def prog(...): ...
        staged = devicewatch.jit(fn)

    Compile detection reads the jitted callable's cache size (one
    attribute call per invocation; no argument hashing), so the wrapper
    adds ~1us to the hot path.  On jax builds without ``_cache_size``
    telemetry degrades to pass-through rather than guessing."""
    if fn is None:
        return functools.partial(jit, program=program, **jit_kwargs)
    import jax
    jitted = jax.jit(fn, **jit_kwargs)
    name = program or getattr(fn, "__name__", None) or repr(fn)
    cache_size = getattr(jitted, "_cache_size", None)
    if cache_size is None:  # pragma: no cover - older/newer jax API drift
        return jitted

    @functools.wraps(fn)
    def wrapper(*a, **kw):
        if not _ENABLED:
            return jitted(*a, **kw)
        before = cache_size()
        sampled = KERNEL_TIMER.tick(name)
        t0 = time.perf_counter()
        out = jitted(*a, **kw)
        if cache_size() > before:
            COMPILE_WATCH.note_compile(name, time.perf_counter() - t0,
                                       _shape_key(a, kw))
        elif sampled:
            # never fold a compiling launch: its wall time is host
            # trace+compile work, already on the compile telemetry —
            # a cold-start sample would poison the device-time EWMA
            # (and seed the sentry baseline from compile noise)
            KERNEL_TIMER.sample(name, out, t0, a, kw)
        return out

    wrapper._jitted = jitted   # AOT escape hatch (lower/trace)
    # the ledger key, readable off the callable: consumers that
    # attribute bytes to a program (devicestore._note_kernel_bytes)
    # derive the name from HERE instead of repeating the literal, so a
    # rename cannot decouple the bytes/launches join
    wrapper._program = name
    return wrapper


# ---------------------------------------------------------------------------
# 3. Flight recorder
# ---------------------------------------------------------------------------


class FlightRecorder:
    """Bounded lock-free ring of recent structured events.

    ``record`` is a counter fetch + one list-slot store (both atomic
    under the GIL), safe from any thread on any hot path.  A torn read
    in ``events`` can at worst miss/duplicate the oldest slot — fine
    for a postmortem buffer, and why there is no lock to convoy on."""

    def __init__(self, capacity: int = 2048):
        self._cap = max(16, int(capacity))
        self._buf: list = [None] * self._cap
        self._ctr = itertools.count()

    def resize(self, capacity: int) -> None:
        """Replace the ring (standalone config / POST /admin/config);
        old events are kept up to the new capacity.  The new buffer is
        fully built before any shared state is swapped, and record()
        indexes a local snapshot, so concurrent records during a resize
        can at worst land in the retiring buffer — never out of
        bounds."""
        events = self.events()
        cap = max(16, int(capacity))
        buf = [None] * cap
        ctr = itertools.count(len(events))
        for i, ev in enumerate(events[-cap:]):
            buf[i % cap] = (ev["t_s"], i, ev["kind"],
                            {k: v for k, v in ev.items()
                             if k not in ("t_s", "seq", "kind")})
        self._buf, self._ctr, self._cap = buf, ctr, cap

    @property
    def capacity(self) -> int:
        return self._cap

    def record(self, kind: str, **fields) -> None:
        if not _ENABLED:
            return
        i = next(self._ctr)
        buf = self._buf       # snapshot: a concurrent resize swaps the
        buf[i % len(buf)] = (time.time(), i, kind, fields)  # whole list

    def events(self, limit: Optional[int] = None,
               kind: Optional[str] = None) -> list[dict]:
        """Oldest-first JSON-safe dump."""
        rows = [e for e in list(self._buf) if e is not None]
        rows.sort(key=lambda e: e[1])
        if kind is not None:
            rows = [e for e in rows if e[2] == kind]
        if limit is not None:
            rows = rows[-int(limit):]
        return [{"t_s": t, "seq": seq, "kind": k, **fields}
                for t, seq, k, fields in rows]

    def dump_to_log(self, reason: str, limit: int = 200) -> None:
        """The black box hits the ground: write the recent event tail to
        the log (integrity failure / unhandled-exception shutdown)."""
        try:
            events = self.events(limit=limit)
            lines = [f"flight recorder dump ({reason}): "
                     f"{len(events)} recent events"]
            for ev in events:
                fields = " ".join(f"{k}={v}" for k, v in ev.items()
                                  if k not in ("t_s", "seq", "kind"))
                lines.append(f"  [{ev['t_s']:.3f}] #{ev['seq']} "
                             f"{ev['kind']} {fields}")
            _LOG.error("%s", "\n".join(lines))
        except Exception:  # noqa: BLE001 — the black box must never throw
            pass


FLIGHT = FlightRecorder()

_CRASH_HOOKS_INSTALLED = False


def install_crash_hooks() -> None:
    """Dump the flight recorder on unhandled exceptions (main thread and
    worker threads) before the previous hook runs — the reference's
    "what was the system doing in the seconds before the crash"."""
    global _CRASH_HOOKS_INSTALLED
    if _CRASH_HOOKS_INSTALLED:
        return
    _CRASH_HOOKS_INSTALLED = True
    import sys

    prev_sys = sys.excepthook

    def _sys_hook(exc_type, exc, tb):
        FLIGHT.dump_to_log(f"unhandled {exc_type.__name__}")
        prev_sys(exc_type, exc, tb)

    sys.excepthook = _sys_hook
    prev_thread = threading.excepthook

    def _thread_hook(args):
        FLIGHT.dump_to_log(
            f"unhandled {args.exc_type.__name__} in thread "
            f"{getattr(args.thread, 'name', '?')}")
        prev_thread(args)

    threading.excepthook = _thread_hook


def configure(conf: Optional[dict] = None) -> None:
    """Apply the standalone ``"devicewatch"`` config block:
    ``{"enabled": bool, "flight-recorder-size": int,
    "jit-storm-shapes": int, "jit-storm-window-s": float,
    "kernel-sample-1-in": int (0 disables sampling),
    "kernel-regression-factor": float,
    "kernel-regression-window-s": float,
    "kernel-baseline-min-samples": int}``."""
    conf = conf or {}
    if "enabled" in conf:
        from filodb_tpu.core.storeconfig import parse_bool
        set_enabled(parse_bool(conf["enabled"]))
    if "flight-recorder-size" in conf:
        FLIGHT.resize(int(conf["flight-recorder-size"]))
    COMPILE_WATCH.configure(
        storm_shapes=conf.get("jit-storm-shapes"),
        storm_window_s=conf.get("jit-storm-window-s"))
    KERNEL_TIMER.configure(
        sample_1_in=conf.get("kernel-sample-1-in"),
        regression_factor=conf.get("kernel-regression-factor"),
        regression_window_s=conf.get("kernel-regression-window-s"),
        baseline_min_samples=conf.get("kernel-baseline-min-samples"))


# ---------------------------------------------------------------------------
# /admin/device summary
# ---------------------------------------------------------------------------


def device_summary() -> dict:
    """The process-wide device-resource view: ledger tree, pools,
    per-device reconciliation, compile table, storm state.  The HTTP
    layer adds per-dataset arena budgets (it owns the bindings)."""
    from filodb_tpu.utils.observability import TRACER
    return {
        "enabled": _ENABLED,
        "ledger": {
            "owners": LEDGER.owners(),
            "total_bytes": LEDGER.total_bytes(),
            "pools": LEDGER.pools(),
        },
        "devices": LEDGER.reconcile(),
        "compile": {
            "programs": COMPILE_WATCH.table(),
            "active_storms": COMPILE_WATCH.active_storms(),
            "storm_shapes": COMPILE_WATCH.storm_shapes,
            "storm_window_s": COMPILE_WATCH.storm_window_s,
        },
        "flight_recorder": {"capacity": FLIGHT.capacity},
        # the stage clock's process-wide totals since start
        # ({count, wall_s, cpu_s} per stage span name)
        "stages": TRACER.stages.snapshot(),
        # False once leaf stages can no longer be annotated: a profile
        # then names no idle gap after the program's stages
        "annotations": TRACER.annotating,
    }
