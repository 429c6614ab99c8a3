#!/usr/bin/env python3
"""benchmark/run.py — one cell of BENCHMARK.json, once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process boots the server (``filodb_tpu.standalone.boot``) and holds the
chip; the load generator is a child that touches neither JAX nor the program.
Set-up (all of it ``setup_s``): boot, population from the seed, containers
through ``POST /ingest/<dataset>/<shard>``, flush, the configuration's staging
panel (every series into the device store) and every distinct panel shape
issued twice, each panel in bursts, the mix for five seconds with all
sessions.  Then ``--seconds`` of the cell's traffic against
``query_range`` over HTTP.  After the window: the peak memory and the
counters are read, the device checks made, every answer of the window held to
the plain NumPy reference (``harness/compare.py``), and one JSON line printed.

Where the traffic file has a ``writer``, the cell ingests while it answers:
set-up also builds the stream's containers from the configuration's
``live_rows`` (``harness/loader.py``), the load generator posts them on
their schedule beside the sessions, in the concurrent warm and in the window,
and the write side is compared too (``writes_unacknowledged``,
``writer_behind_s``, ``writes_not_ingested``).

It fails — non-zero, no result line — where JAX's backend is not ``tpu``,
where the device count is not the cell's ``chips``, where the device is not in
``harness/peaks.json``, where a panel's earliest range, less its window,
starts before the loaded rows, or where the program is not in the checkout.

``--rehearse`` (the harness's own): the same code at 256 series on whatever
backend there is, for the self-tests.  It prints no metric.
``--control`` (the harness's own): after the comparison, put the degraded
references in the program's place and print what they read.
"""

from __future__ import annotations

import argparse
import collections
import concurrent.futures
import gc
import importlib
import importlib.util
import json
import os
import pathlib
import shutil
import struct
import subprocess
import sys
import time
import urllib.error
import urllib.request

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT))

TRACE_START_S = 2.0       # into the window
TRACE_SECONDS = 4.0
SETUP_TIMEOUT_S = 600
WARM_WINDOW_S = 5.0       # the mix, all sessions at once, before the window
CONTROL_KEYS = 60         # answers a panel that --control degrades
BURSTS = (8, 4, 2)        # requests of one panel sent together in set-up
GRACE_S = 60.0            # how long past the close an answer is waited for
SCRATCH = ROOT / ".bench_run"     # in .gitignore; traces, removed once read
BETWEEN_S = 5.0           # a writer's stream covers this much between the
#                           concurrent warm and the window, at the least

# the exact comparisons; every panel's ``rel_err`` has a limit of its own in
# the traffic file (``limits``), set from readings on the chip (PERF.md,
# section 2)
EXACT = {"absent_cells": 0, "series_off": 0, "unanswered": 0,
         "breakers_open": 0, "native_build_errors": 0}
# the breakers a failed device path sets: (module, attribute, is it open?).
# One that is not there any more fails the run: it cannot be read as closed.
BREAKERS = (
    ("filodb_tpu.memstore.devicestore", "_PACKED_BROKEN", bool),
    ("filodb_tpu.parallel.meshexec", "FABRIC_BREAKER",
     lambda b: bool(b["open"])),
    ("filodb_tpu.batching.batcher", "batching_broken", lambda f: bool(f())),
)
MOSAIC = "tpu_custom_call"      # in the HLO text of a Pallas kernel's op
# the device path's programs, by the name ``filodb_kernel_launches_total``
# gives them: the per-shard rung's and the mesh fabric's.  A launch of one
# answers a dispatch; a stacked one (``_batch`` in its name) answers several
# and is counted by its members; a helper stages or pads and answers none.
SERVING_FAMILIES = ("devicestore.", "meshgrid.")
STACKED = "_batch"
HELPERS = ("devicestore.mesh_stage", "meshgrid.pad")


def say(msg: str) -> None:
    print(msg, flush=True)


class Failed(Exception):
    """The run cannot give a result (no chip, a set-up step failed)."""


# ------------------------------------------------------------------ the cell

def load_cell(workload: str) -> dict:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise Failed(f"no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    traffic_file = HERE / "traffic" / f"{cell['traffic']}.json"
    from harness import traffic as traffic_mod
    traffic = traffic_mod.load(traffic_file)
    return {"bench": bench, "cell": cell, "limits": dict(EXACT),
            "config": json.loads((ROOT / conf["file"]).read_text()),
            "traffic_file": traffic_file, "traffic": traffic}


def stream_or_fail(ctx: dict, seconds: float) -> None:
    """A writer's stream is the configuration's live rows in real time: it
    has to last through the concurrent warm, the window and what lies
    between them."""
    block, pop = ctx["traffic"].get("writer"), ctx["config"]["population"]
    have = pop.get("live_rows", 0) * pop["scrape_ms"] / 1000.0
    if block and have < WARM_WINDOW_S + BETWEEN_S + seconds:
        raise Failed(f"the traffic has a writer and the configuration's "
                     f"live_rows hold {have:.0f} s of stream: fewer than the "
                     f"warm window, the window and {BETWEEN_S:.0f} s between")


def ranges_or_fail(ctx: dict) -> None:
    """Every range a panel can ask, less its window, lies in the loaded
    rows: a sliding end whose early steps looked back past the first row
    would compare cells that hold nothing."""
    from harness import traffic as traffic_mod
    pop = ctx["config"]["population"]
    for p in ctx["traffic"]["panels"]:
        oldest = traffic_mod.earliest_ms(p, pop)
        if oldest < pop["base_ms"]:
            raise Failed(f"panel {p['name']!r} reads from {oldest} ms, "
                         f"{pop['base_ms'] - oldest} ms before the first "
                         f"loaded row (base_ms {pop['base_ms']}): its "
                         f"earliest range less its window has to lie in "
                         f"the loaded rows")


def metric_names(bench: dict, section: str, workload: str) -> list:
    e2e = [m["name"] for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if section == "end_to_end":
        return e2e
    return [m["name"] for m in bench["per_layer"]
            if workload in m.get("workloads", [workload])
            and m["moves"] in e2e]


def read_per_layer(name: str, run: dict):
    """``metrics/<name>.json`` names the reader and its arguments; the reader
    is ``readers/<reader>.py``.  None: nothing to read, left out."""
    spec = json.loads((HERE / "metrics" / f"{name}.json").read_text())
    path = HERE / "readers" / f"{spec['reader']}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_reader_{spec['reader']}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read(run, **spec.get("args", {}))


# ---------------------------------------------------------------------- http

def http_get(port: int, path: str, timeout: float = 600.0) -> tuple:
    try:
        with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                    timeout=timeout) as r:
            return r.status, dict(r.headers), r.read()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read()


def admin_device(port: int) -> dict:
    _c, _h, body = http_get(port, "/admin/device")
    doc = json.loads(body)
    return doc.get("data", doc)


def device_dispatches(port: int) -> dict:
    """How many dispatches the device path has served, from /metrics, by who
    took them: the launches of each family's programs that answer one
    dispatch each (a leaf of the per-shard rung; a fused mesh launch, which
    answers all of a request's shards at once), and the ``members`` of the
    stacked launches (``filodb_batch_members_total``)."""
    _c, _h, body = http_get(port, "/metrics")
    served = dict.fromkeys(SERVING_FAMILIES + ("members",), 0.0)
    for ln in body.decode().splitlines():
        if ln.startswith("filodb_kernel_launches_total{"):
            program = ln.split('program="', 1)[1].split('"')[0]
            family = program.split(".")[0] + "."
            if family in served and STACKED not in program \
                    and program not in HELPERS:
                served[family] += float(ln.rsplit(" ", 1)[1])
        elif ln.startswith("filodb_batch_members_total"):
            served["members"] += float(ln.rsplit(" ", 1)[1])
    return served


# ------------------------------------------------------------------- set-up

def device_or_fail(chips: int, rehearse: bool) -> tuple:
    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    say(f"device: {json.dumps(device)}")
    if rehearse:
        return device, None
    if jax.default_backend() != "tpu":
        raise Failed("JAX's backend is not tpu: not a chip run (--rehearse "
                     "is the run for a host without one)")
    if len(devices) != chips:
        raise Failed(f"the cell asks for {chips} chip(s), JAX sees "
                     f"{len(devices)}")
    from harness import peaks
    try:
        return device, peaks.peaks_for(device["kind"])
    except peaks.UnknownDevice as e:
        raise Failed(str(e)) from e


def issue(port: int, req, what: str) -> float:
    """One request of set-up; anything but a whole answer fails the run."""
    t0 = time.perf_counter()
    code, headers, body = http_get(port, req.path)
    dt = time.perf_counter() - t0
    if code != 200 or "X-FiloDB-Partial-Data" in headers \
            or json.loads(body).get("status") != "success":
        raise Failed(f"{what}: HTTP {code} {body[:300]!r}")
    return dt


def set_up(ctx: dict, seed: int, rehearse: bool) -> dict:
    """Phases 1-5.  Returns the live pieces; ``ctx['phases']`` gets each
    phase's seconds."""
    from harness import loader, traffic as traffic_mod
    from harness.population import Population
    phases = ctx["phases"]
    conf, traffic = ctx["config"], ctx["traffic"]
    spec = dict(conf["population"])
    if rehearse:
        spec["namespaces"] = 2

    t0 = time.perf_counter()
    import jax
    # every program into the cache, also those that compile in under a
    # second, so that only a checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    from filodb_tpu import native, standalone
    from filodb_tpu.native import baseline
    server = standalone.boot(conf["server"])
    phases["boot"] = time.perf_counter() - t0
    port = server.http.port
    try:
        t0 = time.perf_counter()
        pop = Population(spec, seed)
        phases["population"] = time.perf_counter() - t0

        block = traffic.get("writer")
        routes = [] if block else None
        phases["ingest"] = loader.load(pop, server, conf["dataset"], port,
                                       say, routes)
        writer = None
        if block:
            t0 = time.perf_counter()
            SCRATCH.mkdir(exist_ok=True)
            writer = {"file": str(SCRATCH / "writer.bin")}
            made = loader.live_containers(pop, server, conf["dataset"],
                                          routes, block["batch_ms"],
                                          writer["file"])
            phases["writer_build"] = time.perf_counter() - t0
            say(f"writer: {json.dumps(made)} built in "
                f"{phases['writer_build']:.1f} s")
        t0 = time.perf_counter()
        chunks = server.flush_all()
        phases["flush"] = time.perf_counter() - t0
        say(f"flush: {chunks} chunks frozen in {phases['flush']:.1f} s")

        # a first answer builds the device cache (tens of seconds): set-up
        # waits for it; the window's requests keep the dashboard's timeout
        warm = traffic_mod.warm_requests(
            traffic, conf.get("staging", []), seed, spec, conf["dataset"],
            SETUP_TIMEOUT_S, ctx["stats"])
        first = {}
        for name, req in warm:
            first[name] = issue(port, req, f"first {name}")
            say(f"first[{name}]: {first[name]:.3f} s")
        phases["first_answer"] = first[warm[0][0]]
        phases["first_others"] = sum(first.values()) - phases["first_answer"]
        t0 = time.perf_counter()
        for name, req in warm:
            say(f"warm[{name}]: {issue(port, req, f'warm {name}'):.4f} s")
        phases["warm"] = time.perf_counter() - t0
        # panels of one shape that arrive together are stacked into one
        # launch, a program a stack size (2, 4, 8): each panel in bursts of
        # every size, twice (the batcher lets a first burst pass alone)
        t0 = time.perf_counter()
        bursts = traffic_mod.burst_requests(
            traffic, seed, spec, conf["dataset"], SETUP_TIMEOUT_S,
            ctx["stats"], BURSTS)
        with concurrent.futures.ThreadPoolExecutor(max(BURSTS)) as pool:
            for _ in range(2):
                for burst in bursts:
                    list(pool.map(lambda r: issue(port, r, "burst"), burst))
        phases["bursts"] = time.perf_counter() - t0
    except BaseException:
        server.shutdown()
        raise
    return {"server": server, "port": port, "pop": pop, "spec": spec,
            "native": [native, baseline], "writer": writer}


# ------------------------------------------------------------------- window

def run_window(ctx: dict, live: dict, seed: int, seconds: float,
               trace: bool) -> dict:
    """``seconds`` of the cell's traffic from the load-generator child."""
    conf = ctx["config"]
    spec = {"port": live["port"], "seed": seed, "seconds": seconds,
            "grace_s": GRACE_S, "traffic_file": str(ctx["traffic_file"]),
            "population": live["spec"], "dataset": conf["dataset"],
            "timeout_s": ctx["traffic"]["timeout_s"], "stats": ctx["stats"]}
    if live["writer"]:
        spec["writer"] = live["writer"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")    # it imports neither anyway
    proc = subprocess.Popen([sys.executable, str(HERE / "loadgen.py")],
                            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                            env=env)
    traced = None
    try:
        proc.stdin.write(json.dumps(spec).encode())
        proc.stdin.close()
        if trace:
            traced = capture_trace(seconds)
        blob = proc.stdout.read()
        rc = proc.wait(timeout=seconds + GRACE_S + 60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    if rc != 0 or len(blob) < 8:
        raise Failed(f"the load generator exited {rc} with {len(blob)} bytes")
    (n,) = struct.unpack(">Q", blob[:8])
    head = json.loads(blob[8:8 + n])
    at, bodies = 8 + n, {}
    for sha, ln in head["bodies"]:
        bodies[sha] = blob[at:at + ln]
        at += ln
    if live["writer"]:
        # one stream over the run's windows: the next goes on from here
        w = head["writer"]
        live["writer"].update(anchor=w["anchor"], acked=w["acked"])
        live["writes"] = live.get("writes", []) + head["writes"]
        if w["exhausted"]:
            raise Failed("the writer's stream ran out of live rows before "
                         "the window closed")
    return {"head": head, "bodies": bodies, "traced": traced}


class GcWatch:
    """The server process's collector pauses while a window runs: seconds and
    the longest pause of each generation (a diagnostic line, no metric)."""

    def __init__(self):
        self.t0, self.total, self.longest = 0.0, [0.0] * 3, [0.0] * 3

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self.t0 = time.perf_counter()
            return
        dt, g = time.perf_counter() - self.t0, info["generation"]
        self.total[g] += dt
        self.longest[g] = max(self.longest[g], dt)

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)


def window_shape(label: str, head: dict, watch: GcWatch) -> None:
    """How steady a window was inside: requests answered in each 5 s of it,
    the longest time with none, the collector's pauses."""
    done = sorted(r["t_done"] for r in head["requests"]
                  if r["status"] == 200 and r["t_done"] <= head["t_close"])
    t_open, t_close = head["t_open"], head["t_close"]
    slices = [0] * max(1, int((t_close - t_open + 4.999) // 5))
    for t in done:
        slices[min(len(slices) - 1, int((t - t_open) // 5))] += 1
    edges = [t_open] + done + [t_close]
    gap = max(b - a for a, b in zip(edges, edges[1:]))
    say(f"{label}: {len(done)} answered inside "
        f"({len(done) / (t_close - t_open):.4f} a second), by 5 s "
        f"{slices}, longest time without one {gap:.3f} s, gc seconds "
        f"{[round(x, 3) for x in watch.total]} longest "
        f"{[round(x, 3) for x in watch.longest]}")


def capture_trace(seconds: float) -> dict:
    """A few seconds of the window under the JAX profiler (this process holds
    the chip).  The Python tracer stays off: it would slow the eight server
    threads it records."""
    import jax
    shutil.rmtree(SCRATCH / "trace", ignore_errors=True)
    (SCRATCH / "trace").mkdir(parents=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    time.sleep(min(TRACE_START_S + 0.5, seconds / 4))
    jax.profiler.start_trace(str(SCRATCH / "trace"), profiler_options=opts)
    t0 = time.time()
    time.sleep(min(TRACE_SECONDS, seconds / 2))
    t1 = time.time()
    jax.profiler.stop_trace()
    return {"t0": t0, "t1": t1, "dir": str(SCRATCH / "trace")}


# --------------------------------------------------------------- after it

def judge(ctx: dict, live: dict, win: dict, controls: bool) -> dict:
    """Every answer of the window against the reference; returns the
    requests (with latency, stats, whether right), the numbers compared and
    the controls' readings.  The reference is computed once for each panel
    and namespace, over the union of the grids of the ends it was asked at
    (``compare.reference_by_end``): a mix whose ends slide makes most
    answers distinct."""
    from harness import compare
    traffic, pop, limits = ctx["traffic"], live["pop"], ctx["limits"]
    head, bodies = win["head"], win["bodies"]
    asked, chosen, gaps = {}, {}, {}
    per_panel = collections.Counter()
    unanswered = 0
    t0 = time.perf_counter()
    for r in head["requests"]:
        r["latency_s"] = r["t_done"] - r["t_send"]
        r["ok"], r["stats"] = False, None
        if r["status"] != 200 or r["partial"] or not r["sha1"]:
            unanswered += 1
            say(f"FAILED request: {json.dumps(r)[:300]} "
                f"{bodies.get(r['sha1'], b'')[:200]!r}")
            continue
        end_ms = r.get("end_ms")      # None: the panel's own newest row
        by_end = asked.setdefault((r["panel"], r["namespace"]), {})
        if end_ms not in by_end:
            by_end[end_ms] = []
            per_panel[r["panel"]] += 1       # the controls' distinct answers
            if controls and per_panel[r["panel"]] <= CONTROL_KEYS:
                chosen[r["key"]] = (r["panel"], r["namespace"], end_ms)
        by_end[end_ms].append(r)
    refs = {}
    for (pi, ns), by_end in asked.items():
        panel = traffic["panels"][pi]
        want = compare.reference_by_end(pop, panel, ns, list(by_end))
        for end_ms, reqs in by_end.items():
            parsed = {}
            for r in reqs:
                if r["sha1"] not in parsed:
                    try:
                        parsed[r["sha1"]] = compare.parse_matrix(
                            bodies[r["sha1"]], panel, live["spec"], end_ms)
                    except (ValueError, KeyError, TypeError) as e:
                        parsed[r["sha1"]] = None
                        say(f"UNREADABLE answer to {r['key']}: {e}")
                got = parsed[r["sha1"]]
                if got is None:
                    unanswered += 1
                    continue
                r["stats"] = (got[1] or {}).get("timings")
                gkey = (r["key"], r["sha1"])
                if gkey not in gaps:
                    gaps[gkey] = compare.gap(got[0], want[end_ms])
                g = gaps[gkey]
                r["ok"] = (g["rel_err"] <= panel["limits"]["rel_err"]
                           and g["absent_cells"] == 0
                           and g["series_off"] == 0)
            if reqs[0]["key"] in chosen:
                refs[reqs[0]["key"]] = want[end_ms]
    numbers = compare.worst_of(gaps.values())
    del numbers["rel_err"]             # held panel by panel
    for p in traffic["panels"]:
        limits[f"rel_err.{p['name']}"] = p["limits"]["rel_err"]
        numbers[f"rel_err.{p['name']}"] = 0.0
    for (key, _sha), g in gaps.items():
        name = "rel_err." + traffic["panels"][int(key.split(":")[0])]["name"]
        numbers[name] = max(numbers[name], g["rel_err"])
    numbers["unanswered"] = unanswered + head["hung_sessions"]
    say(f"comparison: {len(head['requests'])} requests, {len(gaps)} distinct "
        f"answers to {sum(per_panel.values())} distinct panels, "
        f"{len(asked)} reference runs, reference and comparison "
        f"{time.perf_counter() - t0:.1f} s")
    control = {}
    # an end at a time: the stale control remakes its population once an end
    order = sorted(chosen, key=lambda k: (chosen[k][2] is not None,
                                          chosen[k][2] or 0))
    for name in compare.CONTROLS if controls else ():
        got = {}
        for key in order:             # CONTROL_KEYS distinct answers a panel
            pi, ns, end_ms = chosen[key]
            got[key] = compare.gap(
                compare.control_answers(pop, name, traffic["panels"][pi],
                                        ns, end_ms), refs[key])
        worst: dict = {}
        for key, g in got.items():
            pname = traffic["panels"][chosen[key][0]]["name"]
            lo, hi, off = worst.get(pname, (float("inf"), 0.0, 0))
            worst[pname] = (min(lo, g["rel_err"]), max(hi, g["rel_err"]),
                            off + g["series_off"] + g["absent_cells"])
        control[name] = {k: {"least_rel_err": lo, "worst_rel_err": hi,
                             "series_or_cells_off": off}
                         for k, (lo, hi, off) in worst.items()}
        say(f"control[{name}]: {json.dumps(control[name])}")
    return {"numbers": numbers, "control": control}


def write_side(ctx: dict, live: dict, head: dict) -> dict:
    """What a window's writer is held to, read while the server still runs:
    every container answered 200, the schedule kept to within one batch, and
    the shards holding exactly the samples that were acknowledged (over the
    whole run: the concurrent warm's too), once the last of them has had its
    ``visible_after_ms``.  The schedule's lateness is held less the seconds
    in which the load generator itself stood (``loadgen.Stops``): a stop of
    the machine is no fault of the server, a stop of the server alone is."""
    from harness import loader
    block, w, stood = ctx["traffic"]["writer"], head["writer"], head["stops"]
    dataset, writes = ctx["config"]["dataset"], live["writes"]
    want = live["pop"].samples + sum(r["samples"] for r in writes
                                     if r["status"] == 200)
    settled = max([r["t_ack"] for r in writes] + [0.0]) \
        + block["visible_after_ms"] / 1000.0
    while loader.rows_ingested(live["server"], dataset) != want \
            and time.time() < settled:
        time.sleep(0.01)
    rows = loader.rows_ingested(live["server"], dataset)
    failed = sum(r["status"] != 200 for r in head["writes"]) + int(w["hung"])
    say(f"writer: {len(head['writes'])} containers in the window, {failed} "
        f"not acknowledged; batches due {w['batches_due']} sent "
        f"{w['batches_sent']} (caught up at the open {w['caught_up']}), "
        f"behind at most {w['behind_s_max']:.4f} s ({w['late_s_max']:.4f} "
        f"s before the load generator's own stops, {stood['stops']} of "
        f"{stood['stopped_s']:.4f} s); the shards hold {rows} "
        f"rows, {want} loaded and acknowledged")
    return {"numbers": {"writes_unacknowledged": failed,
                        "writer_behind_s": w["behind_s_max"],
                        "writes_not_ingested": abs(rows - want)},
            "limits": {"writes_unacknowledged": 0,
                       "writer_behind_s": block["batch_ms"] / 1000.0,
                       "writes_not_ingested": 0},
            "attempted": len(head["writes"]), "failed": failed}


def device_checks(native_mods) -> dict:
    """As chip_smoke.py: is a breaker of the device path open, did a native
    codec not build?  Raises where a breaker cannot be read."""
    out = {"breakers_open": 0, "native_build_errors": 0}
    for module, attr, is_open in BREAKERS:
        try:
            breaker = getattr(importlib.import_module(module), attr)
        except (ImportError, AttributeError) as e:
            raise Failed(f"breaker {module}.{attr} cannot be read: {e}") from e
        if is_open(breaker):
            out["breakers_open"] += 1
            say(f"BREAKER: {module}.{attr}")
    for mod in native_mods:
        if mod.build_error() is not None:
            out["native_build_errors"] += 1
            say(f"NATIVE: {mod.__name__}: {mod.build_error()[:300]}")
    return out


def bytes_in_use() -> list:
    """What each chip holds, by the runtime's own count (not the program's
    ledger); their sum is ``resident_bytes_per_sample``.  Read once every
    panel shape has been answered twice and before any concurrent traffic:
    what is stacked or memoized only when panels arrive together (2.4 MB or
    not, by arrival skew) would make it two-valued."""
    import jax
    gc.collect()          # answers' device buffers that only wait for it
    return [(d.memory_stats() or {}).get("bytes_in_use", 0)
            for d in jax.local_devices()]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    try:
        ctx = load_cell(args.workload)
        ctx["phases"] = {}
        ctx["stats"] = bool(args.trace)
        stream_or_fail(ctx, args.seconds)
        ranges_or_fail(ctx)
        device, peaks = device_or_fail(ctx["cell"]["chips"], args.rehearse)
        live = set_up(ctx, args.seed, args.rehearse)
    except (Failed, ImportError, FileNotFoundError, RuntimeError) as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    server, port = live["server"], live["port"]
    try:
        # programs that exist only under concurrency (panels of one shape
        # arriving together are stacked into one launch) compile here
        by_chip = bytes_in_use()
        in_use = sum(by_chip)
        t0 = time.perf_counter()
        warm = run_window(ctx, live, args.seed, WARM_WINDOW_S, False)["head"]
        ctx["phases"]["concurrent_warm"] = time.perf_counter() - t0
        say(f"concurrent warm: {len(warm['requests'])} requests, "
            f"{sum(r['status'] != 200 for r in warm['requests'])} not 200")
        before = admin_device(port)
        resident = before["ledger"]["total_bytes"]
        served_before = device_dispatches(port)
        setup_s = time.perf_counter() - t_start
        say("set-up: " + ", ".join(f"{k} {v:.1f} s"
                                   for k, v in ctx["phases"].items())
            + f"; setup_s {setup_s:.1f}")
        with GcWatch() as watch:
            win = run_window(ctx, live, args.seed, args.seconds,
                             bool(args.trace))
        window_shape("window", win["head"], watch)
        ends = collections.Counter(
            r["end_ms"] for r in win["head"]["requests"]
            if r["end_ms"] is not None)
        if ends:                # panels that end at ``now`` or slide
            say("panel ends in the window: "
                + json.dumps({str(e): ends[e] for e in sorted(ends)}))
        if win["head"]["stops"]["stops"]:   # the machine stood, or we did
            say("load generator stood: " + json.dumps(win["head"]["stops"]))
        after = admin_device(port)
        served = {k: v - served_before[k]
                  for k, v in device_dispatches(port).items()}
        say(f"device dispatches in the window: {json.dumps(served)}")
        import jax
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.local_devices())
        say(f"bytes in use: {in_use} warm {by_chip}, "
            f"{sum(bytes_in_use())} after the window")
        checks = device_checks(live["native"])
        wrote = write_side(ctx, live, win["head"]) if live["writer"] else None
    except Failed as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    finally:
        server.shutdown()
        if live["writer"]:
            pathlib.Path(live["writer"]["file"]).unlink(missing_ok=True)
    verdict = judge(ctx, live, win, args.control)

    head = win["head"]
    reqs = head["requests"]
    import numpy as np
    lat = [r["latency_s"] for r in reqs] or [float("nan")]
    in_window = sum(1 for r in reqs
                    if r["ok"] and r["t_done"] <= head["t_close"])
    samples = live["pop"].samples
    e2e = {"query_p50_ms": 1000 * float(np.percentile(lat, 50)),
           "query_p95_ms": 1000 * float(np.percentile(lat, 95)),
           "query_rate": in_window / args.seconds,
           "resident_bytes_per_sample": in_use / samples,
           "setup_s": setup_s}

    numbers = dict(verdict["numbers"], **{k: checks[k] for k in
                                          ("breakers_open",
                                           "native_build_errors")})
    limits = ctx["limits"]
    if wrote:
        numbers.update(wrote["numbers"])
        limits.update(wrote["limits"])
    # what can fail a run first comes first (the exact counts, the write
    # side, the device's share), each panel's rel_err after them
    late = [k for k in limits if k.startswith("rel_err.")]
    compared = {k: {"value": numbers[k], "limit": limits[k]}
                for k in limits if k not in late}
    # the device served the window: every answered request is at least one
    # dispatch that a program of the device path took
    answered = sum(1 for r in reqs if r["status"] == 200)
    compared["device_dispatches"] = {"value": sum(served.values()),
                                     "at_least": max(1, answered)}
    compared.update({k: {"value": numbers[k], "limit": limits[k]}
                     for k in late})

    bench = ctx["bench"]
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    result = {"correct": None, "attempted": len(reqs),
              "failed": numbers["unanswered"], "metrics": {},
              "device": dict(device, memory_peak_bytes=int(peak))}
    if wrote:
        result["writes_attempted"] = wrote["attempted"]
        result["writes_failed"] = wrote["failed"]
    if not args.trace:
        for name in metric_names(bench, "end_to_end", args.workload):
            result["metrics"][name] = {"value": e2e[name],
                                       "unit": units[name]}
    else:
        run = {"requests": reqs, "setup_phases": ctx["phases"],
               "samples": samples, "resident_bytes": in_use,
               "device_before": before,
               "device_after": after, "peaks": peaks, "trace": None,
               # a writer's records, a container each, and its summary
               "writes": head.get("writes", []), "writer": head.get("writer")}
        tr = win["traced"]
        if tr is not None:
            from harness import least_bytes, trace_reduce
            try:
                xplane = trace_reduce.find_xplane(tr["dir"])
                run["trace"] = trace_reduce.reduce(xplane,
                                                   tr["t1"] - tr["t0"])
            finally:
                shutil.rmtree(tr["dir"], ignore_errors=True)
            say("trace planes: " + json.dumps(run["trace"].pop("planes")))
            if not args.rehearse:
                # Pallas kernels ran on the device while it was traced
                compared["mosaic_kernel_s"] = {
                    "value": run["trace"]["mosaic_s"], "at_least": 1e-9}
            inside = [r for r in reqs
                      if tr["t0"] <= r["t_done"] <= tr["t1"] and r["ok"]]
            run["trace_requests"] = len(inside)
            run["trace_least_bytes"] = sum(
                least_bytes.request_bytes(
                    ctx["traffic"]["panels"][r["panel"]], live["spec"],
                    resident / samples) for r in inside)
            if run["trace"]["busy_s"]:
                result["device"]["busy_s"] = run["trace"]["busy_s"]
                result["device"]["window_s"] = run["trace"]["window_s"]
                result["device"]["busy_s_by_device"] = \
                    run["trace"]["busy_s_by_device"]
                result["breakdown"] = {
                    "device_ops": run["trace"]["device_ops"],
                    "idle_gaps": run["trace"]["idle_gaps"]}
        compiled = {p["program"]: p["compiles"]
                    for p in after["compile"]["programs"]}
        for p in before["compile"]["programs"]:
            compiled[p["program"]] -= p["compiles"]
        say(f"compiled in the window: "
            f"{json.dumps({k: v for k, v in compiled.items() if v})}")
        for name in metric_names(bench, "per_layer", args.workload):
            value = read_per_layer(name, run)
            if value is not None:
                result["metrics"][name] = {"value": value,
                                           "unit": units[name]}
    correct = bool(reqs) and all(
        c["value"] <= c["limit"] if "limit" in c
        else c["value"] >= c["at_least"] for c in compared.values())
    result["correct"] = correct
    result["compared"] = compared

    say(f"total {time.perf_counter() - t_start:.1f} s; window: "
        f"{len(reqs)} requests, {in_window} right inside it"
        + ("" if args.rehearse else "; " + ", ".join(
            f"{k} {v:.6g} {units[k]}" for k, v in e2e.items())))
    for k, c in compared.items():
        print(f"compared {k} {c['value']} "
              + (f"limit {c['limit']}" if "limit" in c
                 else f"at_least {c['at_least']}"), file=sys.stderr)
    sys.stderr.flush()
    if args.rehearse:
        # not a chip run: the metrics stay unprinted
        say(json.dumps(dict(
            {"rehearsal": True, "correct": correct, "attempted": len(reqs),
             "failed": numbers["unanswered"]},
            **{k: result[k] for k in ("writes_attempted", "writes_failed")
               if k in result},
            metric_names=sorted(result["metrics"]), device=device,
            compared=compared)))
        return 0 if correct else 1
    say(json.dumps(result))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
