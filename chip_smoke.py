#!/usr/bin/env python3
"""chip_smoke.py — does the served PromQL path still start on the chip?

Boots the server through the code ``python -m filodb_tpu.standalone``
runs (``standalone.boot``), in this one process (a chip belongs to one
process; nothing here starts a child that needs it), then drives it only
through what a user touches: Influx line protocol on the gateway port in,
``query_range`` over HTTP out, ``/metrics`` and ``/admin/device`` for the
counters.

    python chip_smoke.py              # one chip, full size (the driver's call)
    python chip_smoke.py --chips 4    # the mesh path on a four-chip host
    python chip_smoke.py --rehearse   # tiny, for a host WITHOUT a chip

Population (one chip): 800 namespaces x 128 instances = 102 400 counter
series x 1 h 4 min at a 15 s scrape cadence (255 rows, 26.1 M samples), made
from ``--seed``, every series with its own fixed scrape phase, ~1% of
them resetting once; flushed, then resident in HBM as compressed blocks.
Queries: the upstream QueryInMemoryBenchmark set (SURVEY.md §2.6 jmh/) —
raw selector, ``sum(rate(m[5m]))``, ``sum by (g)(rate(m[5m]))``,
``sum_over_time(m[5m])``, ``quantile(0.75, m)`` — over the last hour at
the scrape step (240 input rows: two blocks, the tallest 1024-lane tile),
plus the same ``sum(rate)`` over the last 25 min (inside one compressed
block: the fused packed kernels) and at upstream's 150 s step (the
strided path).  Every answer
is compared with the brute-force NumPy oracle (tests/oracle.py) on the
samples that were sent: per-series queries at every step, the 102 400-
series sums at every ``ORACLE_EVERY``-th step (the oracle is a Python
loop per window), rtol 1e-4 — device math is f32, the oracle f64.

The run FAILS (exit code 1, no result line) unless the device did the
serving: backend ``tpu``; ``devicestore.*`` programs launched; the
programs that served the rate queries hold a Mosaic kernel
(``tpu_custom_call``); no breaker open, no batch fallback on error; the
native codecs built from source; no HTTP answer an error or partial.

Last line of stdout on success, and only then::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import json
import pathlib
import socket
import sys
import threading
import time
import urllib.error
import urllib.parse
import urllib.request

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent

GSTEP_MS = 15_000                 # scrape cadence
ROWS = 255                        # buckets 1..255: the second 128-bucket
#                                   block is FULL, so it is kept compressed
#                                   (a block with an empty row stays raw —
#                                   its NaN breaks every lane's XOR class)
PANEL_STEPS = 221                 # "last hour": 240 input rows, two blocks
SHORT_STEPS = 100                 # "last 25 min": 119 rows inside block 1,
#                                   starting at row 9 of it (not 8-aligned)
WINDOW_MS = 300_000               # [5m]
BASE_MS = 1_700_000_010_000       # a 15 s boundary
ORACLE_EVERY = 20                 # wide sums: oracle steps 0, 20, ..., 220
RTOL = 1e-4
METRIC = "m"
GROUPS = 16

FAILURES: list[str] = []


def say(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    FAILURES.append(msg)
    say(f"FAIL: {msg}")


# --------------------------------------------------------------------- data

class Population:
    """The generated samples, kept on the host for the oracle."""

    def __init__(self, namespaces: int, per_ns: int, seed: int):
        rng = np.random.default_rng(seed)
        n = namespaces * per_ns
        self.n, self.per_ns = n, per_ns
        sid = np.arange(n)
        self.ns = sid // per_ns
        self.g = sid % GROUPS
        # each target keeps its own scrape offset inside the interval
        self.phase = rng.integers(1, GSTEP_MS, n)
        self.ts = (BASE_MS + np.arange(ROWS, dtype=np.int64)[None, :]
                   * GSTEP_MS + self.phase[:, None])
        # integer-valued counters, always 7 digits (fixed-width lines)
        start = rng.integers(1_000_000, 5_000_000, n)
        inc = rng.integers(0, 50, (n, ROWS))
        inc[:, 0] = 0
        vals = start[:, None] + np.cumsum(inc, axis=1)
        resets = rng.choice(n, max(1, n // 100), replace=False)
        at = rng.integers(ROWS // 4, ROWS - ROWS // 4, len(resets))
        for s, r in zip(resets, at):          # process restart: count anew
            vals[s, r:] = 1_000_000 + np.cumsum(inc[s, r:])
        need(vals.min() >= 1_000_000 and vals.max() < 10_000_000,
             "counter values left the 7-digit range")
        self.vals = vals.astype(np.float64)
        self.heads = [
            f"{METRIC},_ws_=demo,_ns_=App-{self.ns[s]:04d},"
            f"g=g{self.g[s]:02d},instance=i{s:07d} value="
            for s in range(n)]

    @staticmethod
    def _digits(arr: np.ndarray, width: int) -> np.ndarray:
        pows = 10 ** np.arange(width - 1, -1, -1, dtype=np.int64)
        return ((arr[:, None] // pows[None, :]) % 10 + 48).astype(np.uint8)

    def replays(self):
        """One Influx payload per namespace, each series' hour as one
        run of lines: a backfill, the order an exported file replays in.
        (Scrape order — one line per series per batch — loads at half
        the rate: the shard consumer then pays its per-series cost for
        every sample.  PERF.md, Findings.)"""
        head = np.frombuffer("".join(self.heads).encode(), np.uint8) \
            .reshape(self.n, -1)
        hw = head.shape[1]
        k = self.per_ns
        line = np.empty((k, ROWS, hw + 7 + 1 + 19 + 1), np.uint8)
        line[:, :, hw + 7] = ord(" ")
        line[:, :, -1] = ord("\n")
        for a in range(0, self.n, k):
            line[:, :, :hw] = head[a:a + k, None]
            line[:, :, hw:hw + 7] = self._digits(
                self.vals[a:a + k].astype(np.int64).ravel(), 7
            ).reshape(k, ROWS, 7)
            line[:, :, hw + 8:hw + 27] = self._digits(
                (self.ts[a:a + k] * 1_000_000).ravel(), 19
            ).reshape(k, ROWS, 19)
            yield line.tobytes()


def ingest(pop: Population, gw_port: int, shards, label: str) -> float:
    """Send the population through the Influx gateway; wait until the
    shards have ingested every row.  Returns the seconds it took."""
    t0 = time.perf_counter()
    want = pop.n * ROWS
    with socket.create_connection(("127.0.0.1", gw_port), timeout=60) as sk:
        for payload in pop.replays():
            sk.sendall(payload)
    deadline = time.time() + 600
    rows = 0
    while time.time() < deadline:
        rows = sum(sh.stats.rows_ingested for sh in shards())
        if rows >= want:
            break
        time.sleep(0.1)
    dt = time.perf_counter() - t0
    if rows != want:
        fail(f"{label}: ingested {rows} rows of {want}")
    say(f"ingest[{label}]: {pop.n} series x {ROWS} rows = {want} samples "
        f"through the gateway in {dt:.1f} s")
    return dt


# -------------------------------------------------------------------- oracle

def oracle_series(pop, sel, fn, start, end, step):
    """[len(sel), T] per-series values of the brute-force NumPy oracle
    (tests/oracle.py) on a step grid."""
    if str(ROOT / "tests") not in sys.path:
        sys.path.insert(0, str(ROOT / "tests"))
    import oracle
    return np.stack([oracle.range_fn(fn, pop.ts[s], pop.vals[s], start, end,
                                     step, WINDOW_MS) for s in sel])


# ---------------------------------------------------------------------- http

class Client:
    def __init__(self, port: int):
        self.base = f"http://127.0.0.1:{port}"

    def get(self, path: str, timeout: float = 600.0):
        try:
            with urllib.request.urlopen(self.base + path,
                                        timeout=timeout) as r:
                return r.status, dict(r.headers), r.read()
        except urllib.error.HTTPError as e:
            return e.code, dict(e.headers), e.read()

    def query_range(self, ds, query, start_ms, end_ms, step_ms):
        qs = urllib.parse.urlencode({
            "query": query, "start": start_ms / 1000, "end": end_ms / 1000,
            "step": f"{step_ms}ms", "timeout": "600s"})
        t0 = time.perf_counter()
        code, headers, body = self.get(
            f"/promql/{ds}/api/v1/query_range?{qs}")
        dt = time.perf_counter() - t0
        return code, headers, json.loads(body), dt

    def metric(self, name: str, **labels) -> float:
        """Sum of a counter's samples on /metrics matching ``labels``
        (a label value ending in * is a prefix match)."""
        _c, _h, body = self.get("/metrics")
        total = 0.0
        for ln in body.decode().splitlines():
            if not ln.startswith(name) or ln[len(name)] not in " {":
                continue
            got = dict(kv.split("=", 1) for kv in
                       ln[ln.find("{") + 1:ln.rfind("}")].split(",")
                       if "=" in kv) if "{" in ln else {}
            got = {k: v.strip('"') for k, v in got.items()}
            ok = all(got.get(k, "").startswith(v[:-1]) if v.endswith("*")
                     else got.get(k) == v for k, v in labels.items())
            if ok:
                total += float(ln.rsplit(" ", 1)[1])
        return total


def run_query(cl: Client, ds, name, query, start, end, step, check,
              warm: int = 3):
    """One query cold, then ``warm`` more times; ``check(result)`` holds
    the first answer to the oracle.  Returns (first_s, warm_median_s)."""
    code, headers, body, first = cl.query_range(ds, query, start, end, step)
    ok = code == 200 and body.get("status") == "success"
    if not ok:
        fail(f"{name}: HTTP {code} {str(body)[:300]}")
        return first, float("nan")
    if body.get("warnings") or "X-FiloDB-Partial-Data" in headers:
        fail(f"{name}: partial answer: {body.get('warnings')}")
    try:
        check(body["data"]["result"])
    except AssertionError as e:
        fail(f"{name}: {str(e)[:400]}")
    times = []
    for _ in range(warm):
        c2, _h, b2, dt = cl.query_range(ds, query, start, end, step)
        if c2 != 200 or b2.get("status") != "success":
            fail(f"{name}: warm repeat failed: HTTP {c2}")
        times.append(dt)
    med = float(np.median(times)) if times else float("nan")
    say(f"query[{name}]: first {first:.3f} s, warm median {med:.4f} s "
        f"({len(body['data']['result'])} series out)  {query}")
    return first, med


def matrix(result, nsteps, start, step, key=None):
    """Prometheus matrix -> {label value (or ''): [T] float, NaN where a
    step is absent}."""
    out = {}
    for row in result:
        v = np.full(nsteps, np.nan)
        for t, val in row["values"]:
            v[int(round((float(t) * 1000 - start) / step))] = float(val)
        out[row["metric"].get(key, "") if key else ""] = v
    return out


def panel() -> tuple[int, int]:
    """(start, end) ms of the "last hour" dashboard range."""
    end = BASE_MS + ROWS * GSTEP_MS
    return end - (PANEL_STEPS - 1) * GSTEP_MS, end


def need(cond, what) -> None:
    """``assert`` that survives ``python -O``."""
    if not cond:
        raise AssertionError(what)


def close(got, want, what):
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=0,
                               equal_nan=True, err_msg=what)


# ------------------------------------------------------------ query phases

def oracle_rates(pop: Population) -> np.ndarray:
    """[S, 12] per-series oracle rate(m[5m]) at every ORACLE_EVERY-th
    step of the last-hour panel: what the wide sums are held to (the
    steps between: finite and present)."""
    start, end = panel()
    t0 = time.perf_counter()
    rate_at = oracle_series(pop, range(pop.n), "rate", start, end,
                            ORACLE_EVERY * GSTEP_MS)
    say(f"oracle: rate for {pop.n} series at {rate_at.shape[1]} of "
        f"{PANEL_STEPS} steps in {time.perf_counter() - t0:.1f} s")
    return rate_at


def query_set(cl: Client, ds: str, pop: Population, rate_at: np.ndarray,
              after_rates=None):
    """The benchmark set against dataset ``ds``; only the two wide rate
    queries when ``after_rates`` is None.  ``after_rates()`` runs once
    the rate queries are answered, while the device store still holds
    their plans.  Returns {name: (first_s, warm_s)}."""
    start, end = panel()
    T = PANEL_STEPS
    every = np.arange(0, T, ORACLE_EVERY)
    out = {}

    def sum_rate(name, q_start, q_step):
        """``sum(rate(m[5m]))`` on any step grid inside the panel, held
        to the oracle wherever a query step is one of the oracle's."""
        nsteps = (end - q_start) // q_step + 1
        at = (q_start - start + np.arange(nsteps) * q_step) // GSTEP_MS
        hit = np.isin(at, every)

        def check(result):
            got = matrix(result, nsteps, q_start, q_step)[""]
            need(np.isfinite(got).all(), "sum(rate) has gaps")
            close(got[hit], rate_at.sum(axis=0)[at[hit] // ORACLE_EVERY],
                  name)
        out[name] = run_query(cl, ds, name, f"sum(rate({METRIC}[5m]))",
                              q_start, end, q_step, check)

    sum_rate("sum_rate_1h", start, GSTEP_MS)

    def by_g(result):
        got = matrix(result, T, start, GSTEP_MS, key="g")
        need(len(got) == GROUPS, f"{len(got)} groups, want {GROUPS}")
        for gi in range(GROUPS):
            v = got[f"g{gi:02d}"]
            need(np.isfinite(v).all(), f"g{gi:02d} has gaps")
            close(v[every], rate_at[pop.g == gi].sum(axis=0),
                  f"sum by (g) g{gi:02d}")
    out["sum_by_g_rate_1h"] = run_query(
        cl, ds, "sum_by_g_rate_1h", f"sum by (g)(rate({METRIC}[5m]))",
        start, end, GSTEP_MS, by_g)
    if after_rates is None:
        return out

    # inside the compressed block: the fused packed kernels
    sum_rate("sum_rate_25m", end - (SHORT_STEPS - 1) * GSTEP_MS, GSTEP_MS)
    # upstream's 150 s step: the strided path
    sum_rate("sum_rate_step150", start, 150_000)
    after_rates()

    # per-series queries on one namespace (upstream: 100 series a query;
    # here 128), every step against the oracle
    ns = pop.ns.max() // 2
    sel = np.flatnonzero(pop.ns == ns)
    nsf = f'_ws_="demo",_ns_="App-{ns:04d}"'

    def per_series(name, query, fn, which):
        def check(result):
            got = matrix(result, T, start, GSTEP_MS, key="instance")
            need(len(got) == len(which),
                 f"{len(got)} series, want {len(which)}")
            want = oracle_series(pop, which, fn, start, end, GSTEP_MS)
            for i, s in enumerate(which):
                close(got[f"i{s:07d}"], want[i], f"{name} i{s:07d}")
        out[name] = run_query(cl, ds, name, query, start, end, GSTEP_MS,
                              check)

    few = "|".join(f"{s:07d}" for s in sel[:5])
    per_series("raw_selector", f'{METRIC}{{{nsf},instance=~"i({few})"}}',
               "last", sel[:5])
    per_series("sum_over_time", f"sum_over_time({METRIC}{{{nsf}}}[5m])",
               "sum_over_time", sel)

    def quantile(result):
        got = matrix(result, T, start, GSTEP_MS)[""]
        last = oracle_series(pop, sel, "last", start, end, GSTEP_MS)
        close(got, np.quantile(last, 0.75, axis=0), "quantile(0.75, m)")
    out["quantile"] = run_query(cl, ds, "quantile",
                                f"quantile(0.75, {METRIC}{{{nsf}}})", start,
                                end, GSTEP_MS, quantile)
    return out


def fleet(cl: Client, ds: str, pop: Population) -> None:
    """Four shape-identical panels at once: the batching tier may stack
    them into one vmapped launch.  Whether it does depends on arrival
    skew, so the count is an observation; a batch launch that FAILS is
    fatal (checked with the breakers)."""
    start, end = panel()
    errs = []

    def one(ns):
        q = f'sum_over_time({METRIC}{{_ws_="demo",_ns_="App-{ns:04d}"}}[5m])'
        code, _h, body, _dt = cl.query_range(ds, q, start, end, GSTEP_MS)
        if code != 200 or body.get("status") != "success":
            errs.append(f"App-{ns:04d}: HTTP {code}")
    for _round in range(3):
        ts = [threading.Thread(target=one, args=(ns,))
              for ns in range(min(4, pop.ns.max() + 1))]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    if errs:
        fail(f"fleet: {errs[:3]}")
    say(f"fleet: 3 rounds of 4 concurrent panels; "
        f"devicestore.series_batch launches: "
        f"{cl.metric('filodb_kernel_launches_total', program='devicestore.series_batch'):.0f}")


# ------------------------------------------------- was the device hidden?

def served_programs_hold_kernels(server, ds: str, on_tpu: bool) -> None:
    """Re-lower the fused programs that served the rate queries, with
    the operands of the plans the device store memoized for them, and
    look for the Mosaic kernel in the compiled text."""
    from filodb_tpu.memstore import devicestore
    progs = devicestore._fused_progs()
    seen = set()
    for sh in server.memstore.shards(ds):
        for cache in sh.device_caches.values():
            for plan in list(cache._plan_memo.values()):
                if plan.q.op != "rate":
                    continue
                name = "grouped" if plan.packed is None else "grouped_packed"
                key = (name, plan.nrows, plan.lane_mult, plan.q.stride)
                if key in seen:
                    continue
                seen.add(key)
                if plan.packed is not None:
                    n_pk = int(plan.packed["first"].shape[0])
                    lowered = progs[name]._jitted.lower(
                        plan.packed, plan.steps0_rel,
                        np.zeros(n_pk, np.int32), q=plan.q,
                        row0=plan.packed_row0,
                        use_phase=plan.packed_use_phase, num_groups=1,
                        op="sum")
                else:
                    lowered = progs[name]._jitted.lower(
                        plan.ts_parts, plan.val_parts, plan.row0,
                        plan.steps0_rel, np.zeros(plan.ncols, np.int32),
                        plan.phase, q=plan.q, lanes=plan.lane_mult,
                        nrows=plan.nrows, num_groups=1, op="sum")
                has = "tpu_custom_call" in lowered.compile().as_text()
                say(f"program devicestore.{name} rows={plan.nrows} "
                    f"cols={plan.ncols} lane_tile={plan.lane_mult} "
                    f"stride={plan.q.stride} phase={plan.phase is not None}"
                    f": tpu_custom_call={'yes' if has else 'NO'}")
                if on_tpu and not has:
                    fail(f"devicestore.{name} served a rate query with no "
                         f"Pallas kernel in it")
    if not seen:
        fail("no memoized rate plan: the device grid served no rate query")


def mesh_programs_hold_kernels(on_tpu: bool) -> None:
    """The same look at the fused mesh program, re-lowered on the
    assembled residents the fabric memoized for the mesh dataset."""
    from filodb_tpu.parallel import meshgrid
    seen = 0
    for key, val in list(meshgrid._ASSEMBLY_MEMO.items()):
        mesh_key, q, mode, groups, nrows, lmax, ksub = key[:7]
        prog = meshgrid._grid_mesh_present_program(
            mesh_key, q, mode, ksub, nrows, lmax, groups, "sum", "sum")
        text = prog._jitted.lower(*val[:5]).compile().as_text()
        has = "tpu_custom_call" in text
        seen += 1
        say(f"program meshgrid.fused rows={nrows} lmax={lmax} ksub={ksub} "
            f"groups={groups} mode={mode}: "
            f"tpu_custom_call={'yes' if has else 'NO'}, "
            f"all-reduce={'yes' if 'all-reduce' in text else 'NO'}")
        if on_tpu and not has:
            fail("meshgrid.fused served with no Pallas kernel in it")
    if not seen:
        fail("no assembled mesh residents: the fabric served nothing")


def hidden_device_checks(cl: Client, on_tpu: bool, packed_panel: bool,
                         native_mods) -> None:
    from filodb_tpu.batching import batcher
    from filodb_tpu.memstore import devicestore
    from filodb_tpu.parallel import meshexec
    launches = cl.metric("filodb_kernel_launches_total",
                         program="devicestore.*")
    say(f"filodb_kernel_launches_total{{program=~'devicestore.*'}} = "
        f"{launches:.0f}")
    if launches <= 0:
        fail("no devicestore.* program was launched")
    packed = cl.metric("filodb_kernel_launches_total",
                       program="devicestore.grouped_packed")
    say(f"filodb_kernel_launches_total{{program='devicestore.grouped_packed'"
        f"}} = {packed:.0f}")
    if on_tpu and packed_panel and packed <= 0:
        fail("the panel inside one compressed block was not served by the "
             "fused packed program")
    if devicestore._PACKED_BROKEN:
        fail("_PACKED_BROKEN is set: the fused packed kernels failed")
    if meshexec.FABRIC_BREAKER["open"]:
        fail("FABRIC_BREAKER is open: a fused mesh program failed")
    if batcher.batching_broken():
        fail("the batch breaker is set: a batched launch failed")
    errs = cl.metric("filodb_batch_fallbacks_total", reason="error")
    if errs:
        fail(f"filodb_batch_fallbacks_total{{reason=error}} = {errs:.0f}")
    for mod in native_mods:
        if mod.build_error() is not None:
            fail(f"{mod.__name__} did not build: {mod.build_error()[:300]}")


def hbm(cl: Client) -> dict:
    _c, _h, body = cl.get("/admin/device")
    dev = json.loads(body)
    dev = dev.get("data", dev)
    per_dev = {k: v.get("ledger_bytes", 0)
               for k, v in dev["devices"].items()}
    say(f"HBM ledger: {dev['ledger']['total_bytes']} bytes resident; "
        f"per device: {per_dev}; by owner and format: "
        f"{json.dumps(dev['ledger']['owners'])}")
    return per_dev


# ----------------------------------------------------------------- the run

def rebuild_native() -> None:
    """Nothing runs that git would not commit: drop the ignored
    binaries so the import below builds them from native/src/*.cpp."""
    for name in ("_codecs.so", "_baseline.so"):
        (ROOT / "filodb_tpu" / "native" / name).unlink(missing_ok=True)


def dataset_conf(name: str, shards: int, cost: float, **extra) -> dict:
    return {"name": name, "num-shards": shards, "min-num-nodes": 1,
            "schema": "gauge", "spread": {1: 0, 4: 2}[shards],
            "gateway-port": 0,
            "store": {"flush-interval": "1h", "groups-per-shard": 8,
                      "device-cache-size": "4GB"},
            # sized for dashboards that touch the whole population: the
            # default ceiling (10 000 cost units) sheds a 102 400-series
            # panel outright
            "workload": {"admission": {"max-inflight-cost": cost}},
            **extra}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--namespaces", type=int, default=None,
                    help="namespaces of 128 series each (default 800; "
                         "fewer is a debugging size, not the smoke)")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size, for a host without a chip: the checks "
                         "that need the TPU are reported, not enforced")
    args = ap.parse_args()
    t_start = time.perf_counter()

    import jax
    on_tpu = jax.default_backend() == "tpu"
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    say(f"device: {json.dumps(device)}")
    if not on_tpu and not args.rehearse:
        say("no TPU: this is not a chip run (use --rehearse on a host "
            "without one)")
        return 3
    if len(devices) != args.chips:
        say(f"--chips {args.chips} but JAX sees {len(devices)} devices (a "
            f"rehearsal gets them from XLA_FLAGS="
            f"--xla_force_host_platform_device_count={args.chips})")
        return 3

    if not args.rehearse:
        rebuild_native()
    from filodb_tpu import native, standalone
    from filodb_tpu.native import baseline
    namespaces, per_ns = (2, 128) if args.rehearse else (800, 128)
    namespaces = args.namespaces or namespaces
    pop = Population(namespaces, per_ns, args.seed)
    cost = 100.0 * pop.n
    if args.chips == 1:
        datasets = [dataset_conf("prom", 1, cost)]
    else:
        # the mesh turns itself on when the server sees more than one
        # device (standalone._setup_dataset): "prom_mesh" leaves it so,
        # "prom_flat" is what it is compared with
        datasets = [dataset_conf("prom_mesh", 4, cost),
                    dataset_conf("prom_flat", 4, cost, mesh=False)]
    server = standalone.boot({"node": "chip-smoke", "http-port": 0,
                              "datasets": datasets})
    try:
        cl = Client(server.http.port)
        names = [d["name"] for d in datasets]
        setup = 0.0
        for name, gw in zip(names, server.gateways):
            setup += ingest(pop, gw.port,
                            lambda n=name: server.memstore.shards(n), name)
        t0 = time.perf_counter()
        chunks = server.flush_all()
        setup += time.perf_counter() - t0
        say(f"flush: {chunks} chunks frozen in "
            f"{time.perf_counter() - t0:.1f} s; set-up {setup:.1f} s")

        rate_at = oracle_rates(pop)
        if args.chips == 1:
            timings = query_set(
                cl, "prom", pop, rate_at, after_rates=lambda:
                served_programs_hold_kernels(server, "prom", on_tpu))
            fleet(cl, "prom", pop)
        else:
            timings = {}
            for name in names:
                got = query_set(cl, name, pop, rate_at)
                timings.update({f"{name}.{k}": v for k, v in got.items()})
            served_programs_hold_kernels(server, "prom_flat", on_tpu)
            mesh_programs_hold_kernels(on_tpu)
            mesh_launches = cl.metric("filodb_kernel_launches_total",
                                      program="meshgrid.*")
            say(f"filodb_kernel_launches_total{{program=~'meshgrid.*'}} = "
                f"{mesh_launches:.0f}")
            if mesh_launches <= 0:
                fail("no meshgrid.* program was launched: the mesh path "
                     "did not serve")
        per_dev = hbm(cl)
        if args.chips == 4:
            # "a+b+c+d" rows are the fabric's assembled global arrays
            used = [d for d, b in per_dev.items() if b > 0 and "+" not in d]
            if len(used) != 4:
                fail(f"resident bytes on {len(used)} single devices, "
                     f"want 4: {per_dev}")
        hidden_device_checks(cl, on_tpu, args.chips == 1, [native, baseline])
    finally:
        server.shutdown()

    report = {"device": device, "chips": args.chips,
              "rehearsal": args.rehearse, "seed": args.seed,
              "series": pop.n, "rows": ROWS, "setup_seconds": setup,
              "hbm_ledger_bytes": per_dev,
              "queries": {k: {"first_s": a, "warm_median_s": b}
                          for k, (a, b) in timings.items()},
              "total_seconds": time.perf_counter() - t_start,
              "failures": FAILURES}
    out = ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    tag = "rehearsal" if args.rehearse else f"{args.chips}chip"
    (out / f"chip_smoke_{tag}.json").write_text(json.dumps(report, indent=1))
    say(f"total {report['total_seconds']:.1f} s")
    if FAILURES:
        say(f"{len(FAILURES)} check(s) failed:")
        for f in FAILURES:
            say(f"  - {f}")
        return 1
    if args.rehearse:
        say("rehearsal passed (not a chip run)")
        say(json.dumps({"ok": True, "rehearsal": True, "device": device}))
        return 0
    say(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
