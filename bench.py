"""North-star benchmark: PromQL samples-scanned/sec on one chip.

Workload: the QueryInMemoryBenchmark-equivalent hot path (reference:
jmh/src/main/scala/filodb.jmh/QueryInMemoryBenchmark.scala:45-249, scaled to
the BASELINE.json north-star config) — ``sum by (group)(rate(metric[5m]))``
over 1M series × 1h of samples, running the aligned-grid leaf kernel
(filodb_tpu/ops/grid.py): counter correction + windowed Prometheus rate +
grouped sum fused into one Pallas kernel.  This is the kernel the
device-resident serving path dispatches to when the layout invariant
holds; end-to-end served throughput is benchmarked separately in
benches/.

FOUR variants are measured and emitted (ISSUE 3; hist + topK ISSUE 14):

- ``dense``: the decoded-plane kernel (4 B/sample value plane, phase
  mode — no ts plane), the historical north-star number.
- ``compressed_resident``: the SAME query served from XOR-class packed
  residents (codecs/xorgrid.py, ~2.2 B/sample incl. meta), with the
  decode fused INSIDE the Pallas kernel (ops/grid.py
  rate_grid_grouped_packed) — the headline storage format measured on
  the headline path.  Equivalence against the ts-streaming kernel is
  asserted ON DEVICE before timing (like the phase-vs-ts check), and
  the workload's integer counters provably pack as one 16-bit class
  (residuals span <= bit 22 with >= 7 trailing zero bits), so group
  lanes stay contiguous.
- ``histogram_quantile``: BASELINE config 2 — ``histogram_quantile(
  0.99, sum(rate(latency_bucket[5m])) by (le))`` over packed HISTOGRAM
  bucket planes (xorgrid stride packs, ops/grid.py
  hist_quantile_grid_packed): VMEM decode + per-bucket rate + the
  banded-MXU bucket reduce + the le-interpolation in ONE program, only
  the [G, T] quantile plane read back.  Device equivalence vs the
  decoded-plane phase kernel + XLA bucket reduce + the shared
  hist_quantile math is asserted before timing.
- ``gdelt_topk``: BASELINE config 5 — the generic columnar
  scan->filter->topK program (ops/grid.py event_topk_grid_packed) over
  a two-column packed event table; equivalence vs the decoded-plane
  free kernel + XLA group reduce + top_k asserted before timing.
  Samples count BOTH scanned columns.
- ``mesh_fabric`` (ISSUE 18): the END-TO-END SPMD mesh query fabric —
  promql -> planner -> MeshReduceExec -> ONE shard_map program over
  N device-resident shards with the cross-shard psum on device.  Owns
  launches/query (must be exactly 1.0 warm, kernel-launch ledger at
  1-in-1 sampling) and achieved scan bytes/s; answers are asserted
  BIT-equal to the scatter-gather oracle before timing.
- ``query_batching`` (ISSUE 20): the fleet batching tier — K
  shape-identical concurrent queries rendezvoused by the QueryBatcher
  and executed as ONE vmapped device program.  Owns launches/query for
  a warm co-arrival fleet (must be <= ceil(K/max_batch)/K, kernel
  ledger at 1-in-1 sampling); every member's slice is asserted
  BIT-equal to its solo launch before timing.

The run FAILS (nonzero rc + machine-readable error JSON) if any
equivalence assertion trips or a measured variant regresses >20%
against the committed BASELINE.json floors — a bench regression
tripwire, not just a report.  A COMPILE/RUN failure of one of the two
NEW (ISSUE 14) variants is reported in its variants{} entry without
failing the legacy floors (their serving twin is breaker-guarded the
same way); a wrong ANSWER still fails loudly.

Protocol (see .claude/skills/verify/SKILL.md gotchas): data is generated
on-device from a scalar seed; the pipeline runs K statically-known
iterations, each forced by a ``float(...)`` readback; elapsed time subtracts
the measured 1-iteration variant so generation + RTT + readback cancel.
int32 timestamps / float32 values (TPU f64 is emulated).

Baseline: the reference publishes no absolute numbers and no JVM exists
in this environment (BASELINE.md), so ``vs_baseline`` is measured against
a multithreaded -O3 C++ implementation of the identical per-series /
per-window iterator workload (filodb_tpu/native/src/baseline.cpp — the
JVM-iterator-path proxy demanded by BASELINE.md's protocol), run on a
subsample and scaled per-sample.  Falls back to the single-core numpy
oracle below if no compiler is available.

Prints exactly ONE JSON line on stdout.
"""

import json
import os
import sys
import time

import numpy as np


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def fail(msg: str, rc: int = 4):
    """Tripwire exit: ONE machine-readable JSON error line + nonzero rc
    (the driver treats any nonzero rc as a bench failure)."""
    log(f"BENCH TRIPWIRE: {msg}")
    print(json.dumps({
        "metric": "PromQL samples scanned/sec (rate()+sum-by)",
        "value": 0.0, "unit": "samples/sec", "vs_baseline": 0.0,
        "error": msg,
    }))
    sys.stdout.flush()
    sys.exit(rc)


G = int(os.environ.get("FILODB_BENCH_GROUPS", 1_000))   # sum by (group)
PER = int(os.environ.get("FILODB_BENCH_PER_GROUP", 1_000))
S = G * PER                                             # real series
NB = int(os.environ.get("FILODB_BENCH_ROWS", 60))       # 1h at 1m resolution
ITERS = int(os.environ.get("FILODB_BENCH_ITERS", 40))
WINDOW_MS = 300_000                                     # rate(...[5m])
STEP_MS = 60_000
SUB = int(os.environ.get("FILODB_BENCH_NUMPY_SERIES", 2_000))
CPP_SUB = int(os.environ.get("FILODB_BENCH_CPP_SERIES", 100_000))
GL = 1_024                                              # lanes per group
T0 = 600_000

# histogram_quantile variant (BASELINE config 2): G_H le-groups x P_H
# series x HB cumulative buckets = 1,048,576 stored bucket columns
HB = int(os.environ.get("FILODB_BENCH_HIST_BUCKETS", 16))
G_H = int(os.environ.get("FILODB_BENCH_HIST_GROUPS", 1_024))
P_H = int(os.environ.get("FILODB_BENCH_HIST_PER_GROUP", 64))
# GDELT topK variant (BASELINE config 5): event lanes, actor groups, k
E_L = int(os.environ.get("FILODB_BENCH_EVENT_LANES", 262_144))
E_G = int(os.environ.get("FILODB_BENCH_EVENT_GROUPS", 4_096))
E_K = int(os.environ.get("FILODB_BENCH_EVENT_K", 10))
# mesh fabric variant (ISSUE 18): the END-TO-END fused serving path —
# planner -> MeshReduceExec -> ONE shard_map program over N resident
# shards.  Small by design: it measures launches/query and per-query
# overhead of the real fabric, not raw kernel FLOPs (those are the four
# variants above).
M_SHARDS = int(os.environ.get("FILODB_BENCH_MESH_SHARDS", 8))
M_SERIES = int(os.environ.get("FILODB_BENCH_MESH_SERIES", 192))
M_ROWS = int(os.environ.get("FILODB_BENCH_MESH_ROWS", 240))
M_ITERS = int(os.environ.get("FILODB_BENCH_MESH_ITERS", 12))
# fleet batching variant (ISSUE 20): K shape-identical concurrent
# queries through the QueryBatcher — a warm co-arrival group must cost
# ceil(K/max_batch) vmapped launches, bit-equal to solo execution
QB_FLEET = int(os.environ.get("FILODB_BENCH_BATCH_FLEET", 8))
QB_SERIES = int(os.environ.get("FILODB_BENCH_BATCH_SERIES", 64))
QB_ROWS = int(os.environ.get("FILODB_BENCH_BATCH_ROWS", 120))
QB_ITERS = int(os.environ.get("FILODB_BENCH_BATCH_ITERS", 6))


def main():
    import jax
    import jax.numpy as jnp

    from filodb_tpu.ops.grid import GridQuery, rate_grid_grouped

    dev = jax.devices()[0]
    log(f"device: {dev.platform} ({dev.device_kind})")
    if jax.default_backend() != "tpu":
        # hardware-absent CI: no throughput numbers are meaningful, but
        # BOTH variants still run end-to-end (tiny shapes, interpret
        # mode) so a broken kernel fails here, not only on the TPU
        _cpu_interpret_smoke()
        # the fabric + batching variants are backend-agnostic: run
        # their bit-equality and launch-count gates end-to-end even
        # without hardware
        _bench_mesh_fabric()
        _bench_query_batching()
        log("no TPU backend: interpret-mode variant smoke (all four "
            "kernel variants) + mesh-fabric + fleet-batching "
            "equivalence passed; skipping measurement")
        print(json.dumps({
            "metric": "PromQL samples scanned/sec (rate()+sum-by)",
            "value": 0.0, "unit": "samples/sec", "vs_baseline": 0.0,
            "error": "no TPU backend (interpret-mode equivalence smoke "
                     "of all four variants passed)",
        }))
        sys.stdout.flush()
        sys.exit(3)

    B = ((NB + 7) // 8) * 8                 # sublane-pad the bucket axis
    S_pad = G * GL
    steps_np = np.arange(T0 + WINDOW_MS, T0 + NB * STEP_MS, STEP_MS,
                        dtype=np.int32)
    T = len(steps_np)
    K = WINDOW_MS // STEP_MS
    # The generated workload satisfies the dense-lane contract (regular
    # scrapes: every live lane finite over all used rows, pad lanes
    # all-NaN) — verified on the device data below before timing.  This
    # is the same specialization the device store auto-detects from its
    # per-block fill ranges when serving real ingested data.
    q = GridQuery(nsteps=T, kbuckets=K, gstep_ms=STEP_MS, is_rate=True,
                  dense=True)

    def gen_body(seed):
        """On-device aligned-grid gen ([B, S] time-major): row c holds
        the sample with ts in (T0+(c-1)*step, T0+c*step].  Each series
        is scraped at a CONSTANT per-lane phase within its bucket —
        strictly more general than the reference benchmark data, whose
        producer emits exact-cadence timestamps identical across series
        (TestTimeseriesProducer.scala:128: ``startTime + n/numTs *
        10000``).  The store proves this uniform-phase layout per lane
        from block fill stats and serves it with the no-ts-plane phase
        kernels (memstore/devicestore.py); per-sample-jittered data
        falls back to the ts-streaming dense kernels."""
        key = jax.random.PRNGKey(seed)
        k1, k2 = jax.random.split(key)
        base = (jnp.arange(B, dtype=jnp.int32) * STEP_MS
                + T0 - STEP_MS)[:, None]
        # headroom below STEP_MS: the timing loop bumps phase by +i per
        # iteration (see pipeline) and phase must stay in (0, gstep]
        phase = jax.random.randint(k1, (1, S_pad), 1,
                                   STEP_MS - ITERS - 1, jnp.int32)
        ts = base + phase
        incr = jax.random.uniform(k2, (B, S_pad), jnp.float32, 0.0, 10.0)
        vals = jnp.cumsum(incr, axis=0)
        lane = jnp.arange(S_pad, dtype=jnp.int32) % GL
        mask = ((jnp.arange(B) < NB)[:, None]) & ((lane < PER)[None, :])
        # kernel contract: row 0 = first bucket of the first window
        return ts[1:], jnp.where(mask, vals, jnp.nan)[1:], phase[0]

    def pipeline(ts, vals, phase, bump):
        # the serving path reads back (sum, count) partials and applies
        # the count>0 mask host-side during the aggregator merge — the
        # kernel's deliverable is the two [G, T] partials.  The CSE-
        # defeating bump perturbs the [1, S] phase row (4 MB), NOT the
        # [B, S] values plane: serving reads RESIDENT values, and a
        # per-iteration ``vals + bump`` would materialize a fresh 250 MB
        # array each query — traffic the server never pays.
        return rate_grid_grouped(None, vals, int(steps_np[0]), q,
                                 group_lanes=GL, phase=phase + bump)

    def build(iters: int):
        def f(seed):
            ts, vals, phase = gen_body(seed)
            acc = jnp.float32(0.0)
            for i in range(iters):
                s, c = pipeline(ts, vals, phase, jnp.int32(i))
                acc = acc + s[0, 0] + s[G // 2, T // 2] + c[0, 0]
            return acc
        return jax.jit(f)

    # prove the dense-lane contract on the rows the kernel uses
    def check_dense(seed):
        _, vals, _ = gen_body(seed)
        fin_cnt = jnp.isfinite(vals[:T + K - 1]).sum(axis=0)
        return jnp.all((fin_cnt == 0) | (fin_cnt == T + K - 1))
    if not bool(jax.jit(check_dense)(0)):
        fail("generated data violates the dense-lane contract")

    # the phase kernels must agree with the ts-streaming kernels on the
    # real device (CI exercises them in interpret mode only)
    def check_phase_equiv(seed):
        ts, vals, phase = gen_body(seed)
        s_ph, c_ph = rate_grid_grouped(None, vals, int(steps_np[0]), q,
                                       group_lanes=GL, phase=phase)
        s_ts, c_ts = rate_grid_grouped(ts, vals, int(steps_np[0]), q,
                                       group_lanes=GL)
        rel = jnp.abs(s_ph - s_ts) / jnp.maximum(jnp.abs(s_ts), 1e-6)
        return jnp.nanmax(jnp.where(c_ts > 0, rel, 0.0)), \
            jnp.max(jnp.abs(c_ph - c_ts))
    rel_err, cnt_err = jax.jit(check_phase_equiv)(0)
    rel_err, cnt_err = float(rel_err), float(cnt_err)
    log(f"phase-vs-ts kernel max rel err: {rel_err:.2e}; "
        f"count err: {cnt_err}")
    if not (rel_err < 2e-5 and cnt_err == 0):
        fail(f"phase kernel diverged from ts kernel "
             f"(rel={rel_err:.2e}, cnt={cnt_err})")

    f_base, f_full = build(1), build(1 + ITERS)
    log("compiling (1 and %d iteration variants)..." % (1 + ITERS))
    _ = float(f_base(0))
    _ = float(f_full(0))

    def timed(f, reps=7):
        best = []
        for _ in range(reps):
            a = time.perf_counter()
            _ = float(f(0))
            best.append(time.perf_counter() - a)
        return float(np.median(best))

    log("timing...")
    t_base = timed(f_base)
    t_full = timed(f_full)
    elapsed = max(t_full - t_base, 1e-9)
    # row 0 is clipped to meet the kernel row contract: NB-1 real buckets
    samples_per_query = S * (NB - 1)
    tpu_rate = samples_per_query * ITERS / elapsed
    log(f"device: {tpu_rate:.3e} samples/sec "
        f"({ITERS} queries in {elapsed:.3f}s; base {t_base:.3f}s, "
        f"full {t_full:.3f}s)")
    dense_bps = (B - 1) * 4 / (NB - 1) + 32 / (NB - 1)   # vals + phase8

    # ---- compressed-resident variant (ISSUE 3 tentpole) -------------------
    from filodb_tpu.codecs import xorgrid
    from filodb_tpu.ops.grid import rate_grid_grouped_packed

    rows_need = T + K - 1
    assert rows_need == NB - 1

    def gen_packed(seed):
        """Integer-counter workload whose XOR residuals provably fit ONE
        16-bit class: start = 2^23 + 128*r0 (r0 < 2^15) pins the f32
        exponent; increments 128*d (d in [1, 8)) give >= 7 trailing
        zero bits and bound block growth under 2^17, so residual bits
        span [7, 22] -> blen <= 16 for every lane.  Single class =
        identity lane order = group lanes stay contiguous for the
        fused grouped kernel.  Same mask/phase discipline as gen_body;
        only the used rows are packed (a NaN tail row would put a wide
        value->NaN residual in every live lane)."""
        key = jax.random.PRNGKey(seed + 7)
        k1, k2, k3 = jax.random.split(key, 3)
        phase = jax.random.randint(k1, (1, S_pad), 1, STEP_MS - 1,
                                   jnp.int32)
        start = (2.0 ** 23) + 128.0 * jax.random.randint(
            k2, (1, S_pad), 0, 2 ** 15, jnp.int32).astype(jnp.float32)
        incr = 128.0 * jax.random.randint(
            k3, (B, S_pad), 1, 8, jnp.int32).astype(jnp.float32)
        vals = start + jnp.cumsum(incr, axis=0)
        lane = jnp.arange(S_pad, dtype=jnp.int32) % GL
        mask = (lane < PER)[None, :]
        base = (jnp.arange(B, dtype=jnp.int32) * STEP_MS
                + T0 - STEP_MS)[:, None]
        ts = base + phase
        return (ts[1:1 + rows_need],
                jnp.where(mask, vals, jnp.nan)[1:1 + rows_need], phase[0])

    log("packing compressed-resident variant...")
    ts_pk, vals_pk, phase_pk = jax.jit(gen_packed)(0)
    vals_np = np.asarray(jax.device_get(vals_pk))
    packed = xorgrid.pack_vals(vals_np, phase=np.asarray(phase_pk),
                               min_width=16)
    if packed is None:
        fail("compressed-resident workload did not pack (class-16 "
             "guarantee violated?)")
    if not (packed.planes["p16"].shape[1] == S_pad
            and packed.planes["raw"].shape[1] == 0
            and bool((packed.inv == np.arange(S_pad)).all())):
        fail("compressed-resident pack is not a single identity-order "
             "class plane; group contiguity contract violated")
    # bit-exact CPU oracle check on a slice before trusting the device
    chk = xorgrid.unpack_vals(packed)[:, :4096]
    if not (chk.view(np.uint32) == vals_np[:, :4096].view(np.uint32)).all():
        fail("xorgrid CPU decode is not bit-identical to the packed "
             "input")
    planes_dev = {k: jax.device_put(jnp.asarray(v))
                  for k, v in packed.planes.items()}
    pk_read_bytes = sum(int(packed.planes[k].nbytes)
                        for k in ("p16", "m16"))
    pk_bps = pk_read_bytes / samples_per_query
    log(f"packed: {pk_read_bytes / 2**20:.1f} MiB resident "
        f"({pk_bps:.2f} B/sample vs {dense_bps:.2f} dense)")

    # in-bench DEVICE equivalence: the fused-decode kernel must agree
    # with the ts-streaming kernel on the same (decoded) data — the
    # compressed-resident analog of the phase-vs-ts check above
    def check_packed_equiv(planes):
        s_pk, c_pk = rate_grid_grouped_packed(planes, int(steps_np[0]), q,
                                              group_lanes=GL)
        s_ts, c_ts = rate_grid_grouped(ts_pk, vals_pk, int(steps_np[0]),
                                       q, group_lanes=GL)
        rel = jnp.abs(s_pk - s_ts) / jnp.maximum(jnp.abs(s_ts), 1e-6)
        return jnp.nanmax(jnp.where(c_ts > 0, rel, 0.0)), \
            jnp.max(jnp.abs(c_pk - c_ts))
    pk_rel, pk_cnt = jax.jit(check_packed_equiv)(planes_dev)
    pk_rel, pk_cnt = float(pk_rel), float(pk_cnt)
    log(f"packed-vs-ts kernel max rel err: {pk_rel:.2e}; "
        f"count err: {pk_cnt}")
    if not (pk_rel < 2e-5 and pk_cnt == 0):
        fail(f"compressed-resident kernel diverged from ts kernel "
             f"(rel={pk_rel:.2e}, cnt={pk_cnt})")

    def build_packed(iters: int):
        @jax.jit
        def f(planes):
            acc = jnp.float32(0.0)
            for i in range(iters):
                # distinct steps0 constants defeat CSE across the
                # unrolled queries; phase mode never reads it, exactly
                # like serving (resident meta is never perturbed)
                s, c = rate_grid_grouped_packed(
                    planes, int(steps_np[0]) + i, q, group_lanes=GL)
                acc = acc + s[0, 0] + s[G // 2, T // 2] + c[0, 0]
            return acc
        return f

    fp_base, fp_full = build_packed(1), build_packed(1 + ITERS)
    log("compiling packed variants...")
    _ = float(fp_base(planes_dev))
    _ = float(fp_full(planes_dev))
    log("timing packed...")
    tp_base = timed(lambda _s: fp_base(planes_dev))
    tp_full = timed(lambda _s: fp_full(planes_dev))
    pk_elapsed = max(tp_full - tp_base, 1e-9)
    pk_rate = samples_per_query * ITERS / pk_elapsed
    log(f"compressed-resident: {pk_rate:.3e} samples/sec "
        f"({ITERS} queries in {pk_elapsed:.3f}s)")

    # ---- histogram_quantile + GDELT-topK variants (ISSUE 14) --------------
    hist_var = _guarded_variant("histogram_quantile",
                                lambda: _bench_hist_quantile(timed))
    topk_var = _guarded_variant("gdelt_topk",
                                lambda: _bench_event_topk(timed))
    mesh_var = _guarded_variant("mesh_fabric", _bench_mesh_fabric)
    batch_var = _guarded_variant("query_batching", _bench_query_batching)

    # -- CPU baseline (C++ multithreaded JVM proxy) on a subsample ----------
    from filodb_tpu.native import baseline as cpp_baseline

    ts, vals, _phase = jax.jit(gen_body)(0)
    use_cpp = cpp_baseline.available()
    nsub = min(CPP_SUB if use_cpp else SUB, S)
    # real lanes (lane % GL < PER), walking whole groups first
    ngroups_needed = (nsub + PER - 1) // PER
    lanes = (np.arange(ngroups_needed)[:, None] * GL
             + np.arange(PER)[None, :]).ravel()[:nsub]
    lanes_j = jnp.asarray(lanes, dtype=jnp.int32)
    sub_ts = np.asarray(jax.device_get(ts[:, lanes_j])).astype(np.int64).T
    sub_vals = np.asarray(jax.device_get(vals[:, lanes_j])).astype(np.float64).T
    ids_np = np.zeros(nsub, dtype=np.int32)
    steps64 = steps_np.astype(np.int64)
    if use_cpp:
        nthreads = cpp_baseline.hw_threads()
        cpp_baseline.rate_sum(sub_ts[:64], sub_vals[:64], ids_np[:64], 1,
                              steps64, WINDOW_MS)       # warm (page-in)
        # best-of-3: this shared 1-core host swings >10x with co-tenant
        # load, and a slow baseline shot INFLATES vs_baseline — take the
        # least-contended run as the honest proxy of the machine
        np_elapsed = float("inf")
        for _ in range(3):
            a = time.perf_counter()
            cpp_out = cpp_baseline.rate_sum(sub_ts, sub_vals, ids_np, 1,
                                            steps64, WINDOW_MS)
            np_elapsed = min(np_elapsed, time.perf_counter() - a)
        np_rate = nsub * (NB - 1) / np_elapsed
        log(f"C++ baseline ({nthreads} threads): {np_rate:.3e} samples/sec "
            f"({nsub} series, best {np_elapsed:.3f}s of 3)")
        # cross-check vs the numpy oracle on a slice so the baseline can
        # never silently drift from the measured semantics
        ora = _numpy_rate_sum(sub_ts[:256], sub_vals[:256], ids_np[:256],
                              steps64)
        chk = cpp_baseline.rate_sum(sub_ts[:256], sub_vals[:256],
                                    ids_np[:256], 1, steps64, WINDOW_MS)
        assert np.allclose(ora, chk, rtol=1e-9, equal_nan=True), \
            "C++ baseline diverged from oracle"
    else:
        log(f"C++ baseline unavailable ({cpp_baseline.build_error()}); "
            "falling back to single-core numpy proxy")
        a = time.perf_counter()
        _numpy_rate_sum(sub_ts, sub_vals, ids_np, steps64)
        np_elapsed = time.perf_counter() - a
        np_rate = nsub * (NB - 1) / np_elapsed
        log(f"numpy proxy: {np_rate:.3e} samples/sec ({nsub} series, "
            f"{np_elapsed:.3f}s)")

    # ---- regression tripwire vs the committed BASELINE.json floors --------
    floors = {}
    try:
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BASELINE.json")) as fh:
            floors = json.load(fh).get("floors", {})
    except Exception as e:  # noqa: BLE001 — a missing floor disables the wire
        log(f"no BASELINE.json floors ({e}); regression tripwire off")
    measured = [("dense", tpu_rate), ("compressed_resident", pk_rate)]
    for name, var in (("histogram_quantile", hist_var),
                      ("gdelt_topk", topk_var)):
        if "samples_per_sec" in var:
            measured.append((name, var["samples_per_sec"]))
    regressions = [
        f"{name} {rate:.3e} < 80% of committed floor {floors[name]:.3e}"
        for name, rate in measured
        if floors.get(name) and rate < 0.8 * float(floors[name])]
    if regressions:
        fail("bench regression: " + "; ".join(regressions), rc=5)

    print(json.dumps({
        "metric": "PromQL samples scanned/sec (rate()+sum-by, "
                  f"{S} series, 1h range)",
        "value": round(tpu_rate, 1),
        "unit": "samples/sec",
        "vs_baseline": round(tpu_rate / np_rate, 2),
        "variants": {
            "dense": {
                "samples_per_sec": round(tpu_rate, 1),
                "bytes_per_sample": round(dense_bps, 2),
                "equiv_max_rel_err": rel_err,
            },
            "compressed_resident": {
                "samples_per_sec": round(pk_rate, 1),
                "bytes_per_sample": round(pk_bps, 2),
                "equiv_max_rel_err": pk_rel,
            },
            "histogram_quantile": hist_var,
            "gdelt_topk": topk_var,
            "mesh_fabric": mesh_var,
            "query_batching": batch_var,
        },
    }))


def _guarded_variant(name: str, run):
    """Run one NEW (ISSUE 14) variant.  A wrong ANSWER inside `run`
    calls fail() and exits nonzero like every other assertion; a
    COMPILE/RUN crash (a backend whose Mosaic build rejects the new
    kernels) is reported in the variant entry instead of sinking the
    legacy floors — the serving twin of these kernels is breaker-
    guarded the same way (memstore/devicestore.py _run_packed)."""
    try:
        return run()
    except SystemExit:
        raise
    except Exception as e:  # noqa: BLE001 — see docstring
        log(f"{name} variant failed to build/run: {e!r}")
        return {"error": f"{type(e).__name__}: {e}"}


def _c16_jax(key, rows: int, cols: int):
    """On-device integer-counter plane with the 16-bit-class guarantee
    (gen_packed's construction: pinned f32 exponent, >=7 trailing zero
    bits — ONE definition shared by every variant so the pack contract
    the bench measures can never drift between them)."""
    import jax
    import jax.numpy as jnp

    ka, kb = jax.random.split(key)
    start = (2.0 ** 23) + 128.0 * jax.random.randint(
        ka, (1, cols), 0, 2 ** 15, jnp.int32).astype(jnp.float32)
    incr = 128.0 * jax.random.randint(
        kb, (rows, cols), 1, 8, jnp.int32).astype(jnp.float32)
    return start + jnp.cumsum(incr, axis=0)


def _c16_np(rng, rows: int, cols: int):
    """Numpy twin of :func:`_c16_jax` for the interpret smoke."""
    start = (2 ** 23 + 128 * rng.integers(0, 2 ** 15, cols)) \
        .astype(np.float32)
    inc = 128 * rng.integers(1, 8, (rows, cols))
    return (start[None, :] + np.cumsum(inc, axis=0)).astype(np.float32)


def _hist_phase_series(rng_key, cols: int, hb: int, rows: int):
    """Hist bucket-plane gen: one :func:`_c16_jax` counter per bucket
    column, one constant scrape phase per SERIES (shared by its hb
    columns)."""
    import jax
    import jax.numpy as jnp

    k1, k2 = jax.random.split(rng_key)
    nser = cols // hb
    phase = jnp.repeat(
        jax.random.randint(k1, (nser,), 1, STEP_MS - 1, jnp.int32), hb)
    return _c16_jax(k2, rows, cols), phase


def _bench_hist_quantile(timed):
    """histogram_quantile(0.99, sum(rate(bucket[5m])) by (le-group))
    over packed hist residents — fused decode + banded bucket reduce +
    le-interpolation (ops/grid.py hist_quantile_grid_packed)."""
    import jax
    import jax.numpy as jnp

    from filodb_tpu.codecs import xorgrid
    from filodb_tpu.ops import histogram_ops
    from filodb_tpu.ops.grid import (GridQuery, hist_quantile_grid_packed,
                                     rate_grid)

    cols = G_H * P_H * HB
    group_lanes = P_H * HB
    K = WINDOW_MS // STEP_MS
    steps_np = np.arange(T0 + WINDOW_MS, T0 + NB * STEP_MS, STEP_MS,
                         dtype=np.int32)
    T = len(steps_np)
    rows_need = T + K - 1
    q = GridQuery(nsteps=T, kbuckets=K, gstep_ms=STEP_MS, is_rate=True,
                  dense=True)
    tops = np.concatenate([2.0 ** np.arange(HB - 1), [np.inf]])
    log(f"hist variant: packing {cols} bucket columns "
        f"({G_H} groups x {P_H} series x {HB} buckets)...")
    vals, phase = jax.jit(lambda s: _hist_phase_series(
        jax.random.PRNGKey(s + 11), cols, HB, rows_need))(0)
    vals_np = np.asarray(jax.device_get(vals))
    packed = xorgrid.pack_vals(vals_np, phase=np.asarray(phase),
                               min_width=16, stride=HB)
    if packed is None or not (
            packed.planes["p16"].shape[1] == cols
            and bool((packed.inv == np.arange(cols)).all())):
        fail("hist workload did not pack as one identity-order class "
             "plane (stride contract violated?)")
    chk = xorgrid.unpack_vals(packed)[:, :4096]
    if not (chk.view(np.uint32) == vals_np[:, :4096].view(np.uint32)).all():
        fail("xorgrid hist CPU decode not bit-identical")
    planes_dev = {k: jax.device_put(jnp.asarray(v))
                  for k, v in packed.planes.items()}
    bps = sum(int(packed.planes[k].nbytes) for k in ("p16", "m16")) \
        / (cols * (NB - 1))

    # device equivalence: fused hist program vs decoded-plane phase
    # kernel + XLA bucket reduce + the SAME hist_quantile math.  The
    # NaN pattern is compared EXPLICITLY — a bare nanmax would let a
    # liveness bug (wrong group NaN on one side) pass silently
    def check(planes):
        fused = hist_quantile_grid_packed(planes, int(steps_np[0]),
                                          jnp.asarray(tops), q, 0.99, HB,
                                          group_lanes=group_lanes)
        stepped = rate_grid(None, vals, int(steps_np[0]), q, lanes=1024,
                            phase=phase)                 # [T, cols]
        st = stepped.reshape(T, G_H, P_H, HB)
        hist_sum = jnp.nansum(st, axis=2).transpose(1, 0, 2)  # [G,T,HB]
        ref = histogram_ops.hist_quantile(jnp.asarray(tops), hist_sum,
                                          0.99)
        ff, fr = jnp.isfinite(fused), jnp.isfinite(ref)
        mism = jnp.sum(ff != fr)
        rel = jnp.where(ff & fr,
                        jnp.abs(fused - ref)
                        / jnp.maximum(jnp.abs(ref), 1e-6), 0.0)
        return jnp.max(rel), mism
    h_rel, h_mism = jax.jit(check)(planes_dev)
    h_rel, h_mism = float(h_rel), int(h_mism)
    log(f"hist fused-vs-XLA max rel err: {h_rel:.2e}; "
        f"NaN-pattern mismatches: {h_mism}")
    if not (h_rel < 2e-5 and h_mism == 0):
        fail(f"fused hist quantile diverged from the XLA decode path "
             f"(rel={h_rel:.2e}, nan_mismatch={h_mism})")

    def build(iters: int):
        @jax.jit
        def f(planes):
            acc = jnp.float32(0.0)
            for i in range(iters):
                out = hist_quantile_grid_packed(
                    planes, int(steps_np[0]) + i, jnp.asarray(tops), q,
                    0.99, HB, group_lanes=group_lanes)
                acc = acc + out[0, 0] + out[G_H // 2, T // 2]
            return acc
        return f
    fb, ff = build(1), build(1 + ITERS)
    log("compiling hist variants...")
    _ = float(fb(planes_dev))
    _ = float(ff(planes_dev))
    log("timing hist...")
    el = max(timed(lambda _s: ff(planes_dev))
             - timed(lambda _s: fb(planes_dev)), 1e-9)
    samples = cols * (NB - 1)
    rate = samples * ITERS / el
    log(f"histogram_quantile: {rate:.3e} samples/sec "
        f"({ITERS} queries in {el:.3f}s)")
    return {"samples_per_sec": round(rate, 1),
            "bytes_per_sample": round(bps, 2),
            "equiv_max_rel_err": h_rel}


def _bench_event_topk(timed):
    """topk(k, sum_over_time(value[w]) by (actor)) with a last-value
    filter on a second column — the generic columnar scan-filter-topK
    program (ops/grid.py event_topk_grid_packed)."""
    import jax
    import jax.numpy as jnp

    from filodb_tpu.codecs import xorgrid
    from filodb_tpu.ops.grid import (GridQuery, event_topk_grid_packed,
                                     rate_grid)

    K = WINDOW_MS // STEP_MS
    steps_np = np.arange(T0 + WINDOW_MS, T0 + NB * STEP_MS, STEP_MS,
                         dtype=np.int32)
    T = len(steps_np)
    rows_need = T + K - 1
    qs = GridQuery(nsteps=T, kbuckets=K, gstep_ms=STEP_MS, op="sum",
                   is_rate=False, dense=True)
    ql = GridQuery(nsteps=T, kbuckets=K, gstep_ms=STEP_MS, op="last",
                   is_rate=False, dense=True)
    log(f"event variant: packing 2 columns x {E_L} lanes "
        f"({E_G} groups, k={E_K})...")

    def gen(seed):
        key = jax.random.PRNGKey(seed + 23)
        k1, k2 = jax.random.split(key)
        return (_c16_jax(k1, rows_need, E_L),
                _c16_jax(k2, rows_need, E_L))
    vals, fvals = jax.jit(gen)(0)
    vals_np = np.asarray(jax.device_get(vals))
    fvals_np = np.asarray(jax.device_get(fvals))
    pk_v = xorgrid.pack_vals(vals_np, min_width=16)
    pk_f = xorgrid.pack_vals(fvals_np, min_width=16)
    if pk_v is None or pk_f is None \
            or not (pk_v.inv == np.arange(E_L)).all() \
            or not (pk_f.inv == np.arange(E_L)).all():
        fail("event workload did not pack as identity-order class planes")
    dev_v = {k: jax.device_put(jnp.asarray(v))
             for k, v in pk_v.planes.items()}
    dev_f = {k: jax.device_put(jnp.asarray(v))
             for k, v in pk_f.planes.items()}
    # actor groups are contiguous lane runs: the banded group_width
    # form reduces with a reshape-sum — no [lanes, G] one-hot operand
    per = E_L // E_G
    thresh = float(np.median(fvals_np[-1]))
    bps = (sum(int(pk_v.planes[k].nbytes) for k in ("p16", "m16"))
           + sum(int(pk_f.planes[k].nbytes) for k in ("p16", "m16"))) \
        / (2 * E_L * (NB - 1))

    # NaN pattern compared explicitly, like the hist gate above
    def check(dv, df):
        f_vals, f_idx = event_topk_grid_packed(
            dv, int(steps_np[0]), qs, E_K, None, E_G,
            filt_packed=df, filt_op="gt", filt_thresh=thresh,
            filt_q=ql, group_width=per)
        sv = rate_grid(None, vals, int(steps_np[0]), qs, lanes=1024)
        sf = rate_grid(None, fvals, int(steps_np[0]), ql, lanes=1024)
        masked = jnp.where(sf > thresh, sv, jnp.nan)
        fin = jnp.isfinite(masked)
        gs = jnp.where(fin, masked, 0.0).reshape(T, E_G, per).sum(2)
        gc = fin.reshape(T, E_G, per).sum(2)
        ranked = jnp.where(gc > 0, gs, -jnp.inf)
        r_vals, _r_idx = jax.lax.top_k(ranked, E_K)
        r_vals = jnp.where(jnp.isfinite(r_vals), r_vals, jnp.nan)
        ff_, fr_ = jnp.isfinite(f_vals), jnp.isfinite(r_vals)
        mism = jnp.sum(ff_ != fr_)
        rel = jnp.where(ff_ & fr_,
                        jnp.abs(f_vals - r_vals)
                        / jnp.maximum(jnp.abs(r_vals), 1e-6), 0.0)
        return jnp.max(rel), mism
    t_rel, t_mism = jax.jit(check)(dev_v, dev_f)
    t_rel, t_mism = float(t_rel), int(t_mism)
    log(f"event topk fused-vs-XLA max rel err: {t_rel:.2e}; "
        f"NaN-pattern mismatches: {t_mism}")
    if not (t_rel < 2e-5 and t_mism == 0):
        fail(f"fused event topK diverged from the XLA decode path "
             f"(rel={t_rel:.2e}, nan_mismatch={t_mism})")

    def build(iters: int):
        @jax.jit
        def f(dv, df):
            acc = jnp.float32(0.0)
            for i in range(iters):
                tv, ti = event_topk_grid_packed(
                    dv, int(steps_np[0]) + i, qs, E_K, None, E_G,
                    filt_packed=df, filt_op="gt", filt_thresh=thresh,
                    filt_q=ql, group_width=per)
                acc = acc + tv[0, 0] + ti[T // 2, 0].astype(jnp.float32)
            return acc
        return f
    fb, ff = build(1), build(1 + ITERS)
    log("compiling event variants...")
    _ = float(fb(dev_v, dev_f))
    _ = float(ff(dev_v, dev_f))
    log("timing event topk...")
    el = max(timed(lambda _s: ff(dev_v, dev_f))
             - timed(lambda _s: fb(dev_v, dev_f)), 1e-9)
    samples = 2 * E_L * (NB - 1)          # both scanned columns count
    rate = samples * ITERS / el
    log(f"gdelt_topk: {rate:.3e} samples/sec "
        f"({ITERS} queries in {el:.3f}s)")
    return {"samples_per_sec": round(rate, 1),
            "bytes_per_sample": round(bps, 2),
            "equiv_max_rel_err": t_rel}


def _bench_mesh_fabric():
    """SPMD mesh query fabric (ISSUE 18): ``sum by (grp)(metric)`` over
    M_SHARDS device-resident shards served END-TO-END — promql parse ->
    planner -> MeshReduceExec -> ONE compiled shard_map program with the
    cross-shard psum on device and a single [G, T] readback.  Unlike the
    kernel variants above this runs the real serving stack, so the
    numbers it owns are launches/query (from the kernel-launch ledger at
    1-in-1 sampling — MUST be exactly 1.0 warm) and achieved scan
    bytes/s.  Device equivalence vs the scatter-gather oracle is
    asserted BIT-exact before timing: the workload is dyadic (integer
    multiples of 1/8, group sums < 2^24 eighths) so every sum is exact
    in BOTH f32 (TPU grid planes) and f64 (host oracle) at any
    summation order."""
    from filodb_tpu.coordinator.planner import SingleClusterPlanner
    from filodb_tpu.core.record import RecordBuilder
    from filodb_tpu.core.schemas import DEFAULT_SCHEMAS, DatasetOptions
    from filodb_tpu.memstore.memstore import TimeSeriesMemStore
    from filodb_tpu.parallel import meshgrid
    from filodb_tpu.parallel.mesh import MeshEngine, make_mesh
    from filodb_tpu.parallel.shardmap import ShardMapper, shard_of_tags
    from filodb_tpu.promql.parser import query_range_to_logical_plan
    from filodb_tpu.query.exec import ExecContext
    from filodb_tpu.query.model import QueryContext
    from filodb_tpu.utils.devicewatch import KERNEL_TIMER, device_metrics

    base, gstep = 1_700_000_000_000, 10_000
    spread = max(M_SHARDS.bit_length() - 1, 0)
    start = base + 300_000                  # 5m lookback stays in-span
    end = base + (M_ROWS - 1) * gstep
    log(f"mesh fabric: {M_SERIES} series over {M_SHARDS} shards x "
        f"{M_ROWS} rows...")
    ms = TimeSeriesMemStore()
    opts = DatasetOptions()
    mapper = ShardMapper(M_SHARDS)
    for s in range(M_SHARDS):
        ms.setup("prom", DEFAULT_SCHEMAS, s)
    rng = np.random.default_rng(101)
    for i in range(M_SERIES):
        tags = {"_metric_": "mf", "inst": f"i{i}", "grp": f"g{i % 16}",
                "_ws_": "w", "_ns_": "n"}
        shard = shard_of_tags(tags, M_SHARDS, spread, opts)
        b = RecordBuilder(DEFAULT_SCHEMAS["gauge"], opts,
                          container_size=1 << 20)
        ts = base + np.arange(M_ROWS) * gstep
        dyadic = rng.integers(1, 1 << 15, M_ROWS).astype(np.float64) / 8.0
        b.add_series(ts.tolist(), [dyadic.tolist()], tags)
        for off, c in enumerate(b.containers()):
            ms.get_shard("prom", shard).ingest_container(c, off)

    def planner(mesh: bool):
        provider = None
        if mesh:
            engine = MeshEngine(make_mesh())
            provider = lambda: engine  # noqa: E731
        return SingleClusterPlanner("prom", mapper, DatasetOptions(),
                                    spread_default=spread,
                                    mesh_engine_provider=provider)

    lp = query_range_to_logical_plan(
        'sum by (grp)(mf{_ws_="w",_ns_="n"})', start, 30_000, end)

    def run(pl):
        res = pl.materialize(lp, QueryContext()) \
            .execute(ExecContext(ms, QueryContext()))
        out = {}
        for bt in res.batches:
            for tg, tss, vs in bt.to_series():
                out[tuple(sorted(tg.items()))] = (np.asarray(tss),
                                                  np.asarray(vs))
        return out

    fused_pl, oracle_pl = planner(True), planner(False)
    got, want = run(fused_pl), run(oracle_pl)
    if set(got) != set(want) or not want:
        fail("mesh fabric answered a different series set than the "
             "scatter-gather oracle")
    for k in want:
        ga = np.asarray(got[k][1], dtype=np.float64)
        wa = np.asarray(want[k][1], dtype=np.float64)
        if not (np.array_equal(np.isnan(ga), np.isnan(wa))
                and ga.tobytes() == wa.tobytes()):
            fail(f"mesh fabric NOT bit-equal to scatter-gather for {k}")
    serves0 = meshgrid.STATS["fused_serves"]
    prev = KERNEL_TIMER.sample_1_in
    KERNEL_TIMER.configure(sample_1_in=1)
    try:
        run(fused_pl)                       # warm under 1-in-1 sampling
        c = device_metrics()["kernel_launches"]
        before = c.total()
        a = time.perf_counter()
        for _ in range(M_ITERS):
            run(fused_pl)
        el = max(time.perf_counter() - a, 1e-9)
        launches = (c.total() - before) / M_ITERS
    finally:
        KERNEL_TIMER.configure(sample_1_in=prev)
    if meshgrid.STATS["fused_serves"] <= serves0:
        fail("mesh fabric never took the fused rung (fallback served "
             "the bench workload)")
    if launches != 1.0:
        fail(f"warm mesh-fabric query is not ONE compiled launch "
             f"(measured {launches:.2f}/query)")
    # every step scans its 5m lookback window from the f32 grid plane
    nsteps = (end - start) // 30_000 + 1
    samples = M_SERIES * nsteps * (300_000 // gstep)
    rate = samples * M_ITERS / el
    log(f"mesh_fabric: {launches:.1f} launch/query, {rate:.3e} "
        f"samples/sec ({M_ITERS} queries in {el:.3f}s)")
    return {"launches_per_query": launches,
            "samples_per_sec": round(rate, 1),
            "bytes_per_sec": round(rate * 4, 1),   # f32 resident plane
            "equiv": "bitwise"}


def _bench_query_batching():
    """Fleet batching tier (ISSUE 20): QB_FLEET shape-identical
    concurrent ``rate()`` range queries (same resident planes, same
    grid shape, starts shifted by i*step) dispatched through the
    ``QueryBatcher`` from barrier-released threads.  A warm co-arrival
    fleet must cost ceil(K/max_batch) vmapped device launches — ONE
    stacked program + ONE readback for the whole group, counted by the
    kernel-launch ledger at 1-in-1 sampling — and every member's slice
    is asserted BIT-equal to its solo (batcher-less) launch before
    anything is timed.  Backend-agnostic: the gates run on CPU CI too."""
    import threading

    from filodb_tpu.batching import QueryBatcher, reset_batch_breaker
    from filodb_tpu.core.filters import ColumnFilter, Equals
    from filodb_tpu.core.record import RecordBuilder, decode_container
    from filodb_tpu.core.schemas import DEFAULT_SCHEMAS
    from filodb_tpu.memstore.memstore import TimeSeriesMemStore
    from filodb_tpu.query.logical import RangeFunctionId as F
    from filodb_tpu.utils.devicewatch import KERNEL_TIMER, device_metrics

    base, step, window = 1_700_000_040_000, 60_000, 300_000
    kbuckets = window // step
    fleet = QB_FLEET
    log(f"query batching: fleet of {fleet} over {QB_SERIES} series x "
        f"{QB_ROWS} rows...")
    ms = TimeSeriesMemStore()
    shard = ms.setup("prom", DEFAULT_SCHEMAS, 0)
    rng = np.random.default_rng(7)
    b = RecordBuilder(DEFAULT_SCHEMAS["prom-counter"])
    for i in range(QB_SERIES):
        tags = {"__name__": "fleet_total", "instance": f"i{i}",
                "_ws_": "w", "_ns_": "n"}
        ts = (base + np.arange(QB_ROWS, dtype=np.int64) * step - step + 1
              + rng.integers(0, 30_000, size=QB_ROWS))
        vals = np.cumsum(rng.random(QB_ROWS) * 5)
        for t, v in zip(ts, vals):
            b.add(int(t), [float(v)], tags)
    for off, c in enumerate(b.containers()):
        shard.ingest(decode_container(c, DEFAULT_SCHEMAS), off)
    shard.flush_all()
    pids = shard.lookup_partitions(
        [ColumnFilter("_metric_", Equals("fleet_total"))], 0,
        2**62).part_ids
    steps0 = base + (kbuckets - 1) * step
    nsteps = QB_ROWS - kbuckets - fleet - 1
    starts = [steps0 + i * step for i in range(fleet)]

    # solo oracle: the per-query chain with no batcher attached
    solos = []
    for s0 in starts:
        got = shard.scan_grid(pids, F.RATE, s0, nsteps, step, window)
        if got is None:
            fail("fleet-batching bench workload declined the grid path")
        solos.append(np.asarray(got[1]))

    reset_batch_breaker()
    bat = QueryBatcher(enabled=True, window_ms=1_000.0, max_batch=fleet,
                       hot_ttl_s=60.0, dataset="prom")
    shard.query_batcher = bat

    def fleet_round():
        barrier = threading.Barrier(fleet)
        outs = [None] * fleet

        def worker(i, s0):
            barrier.wait()
            got = shard.scan_grid(pids, F.RATE, s0, nsteps, step,
                                  window)
            outs[i] = None if got is None else np.asarray(got[1])

        ths = [threading.Thread(target=worker, args=(i, s0))
               for i, s0 in enumerate(starts)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        return outs

    try:
        # bootstrap: a cold key only groups off a detected overlap, so
        # round until the key is hot (also warms the padded-B compile)
        for _ in range(10):
            fleet_round()
            if bat.snapshot()["realized_peak"] >= 2:
                break
        if bat.snapshot()["realized_peak"] < 2:
            fail("fleet-batching bench never formed a co-arrival group")
        fleet_round()        # one hot round: warm the full-B compile
        prev = KERNEL_TIMER.sample_1_in
        KERNEL_TIMER.configure(sample_1_in=1)
        try:
            c = device_metrics()["kernel_launches"]
            before = c.total()
            a = time.perf_counter()
            rounds = []
            for _ in range(QB_ITERS):
                rounds.append(fleet_round())
            el = max(time.perf_counter() - a, 1e-9)
            launches = (c.total() - before) / (QB_ITERS * fleet)
        finally:
            KERNEL_TIMER.configure(sample_1_in=prev)
        for outs in rounds:
            for i, out in enumerate(outs):
                if out is None or out.tobytes() != solos[i].tobytes():
                    fail(f"fleet-batching member {i} is NOT bit-equal "
                         f"to its solo launch")
    finally:
        shard.query_batcher = None
    budget = -(-fleet // bat.max_batch) / fleet       # ceil(K/max)/K
    if launches > budget:
        fail(f"warm fleet of {fleet} cost {launches:.3f} launches/query "
             f"(> {budget:.3f} = ceil(K/max_batch)/K): the co-arrival "
             f"group is not ONE stacked launch")
    samples = QB_SERIES * nsteps * kbuckets
    rate = samples * QB_ITERS * fleet / el
    realized = bat.snapshot()["realized_peak"]
    log(f"query_batching: {launches:.3f} launches/query (fleet={fleet}, "
        f"peak group={realized}), {rate:.3e} samples/sec")
    return {"launches_per_query": round(launches, 4),
            "fleet": fleet, "peak_group": realized,
            "samples_per_sec": round(rate, 1),
            "equiv": "bitwise"}


def _cpu_interpret_smoke():
    """Tiny end-to-end run of EVERY north-star variant in Pallas
    interpret mode (the hardware-absent CI clause): dense phase kernel
    vs the fused compressed-resident kernel on identical data, grouped
    partials must agree; the hist-quantile and event-topK programs run
    against their XLA decode oracles the same way."""
    import jax
    import jax.numpy as jnp

    from filodb_tpu.codecs import xorgrid
    from filodb_tpu.ops.grid import (GridQuery, rate_grid_grouped,
                                     rate_grid_grouped_packed)

    rng = np.random.default_rng(0)
    rows, gl, groups = 64, 128, 8      # rows >= 64: meta amortized past
    #                                    the packer's >=25% threshold
    L = gl * groups
    start = (2 ** 23 + 128 * rng.integers(0, 2 ** 15, L)).astype(np.float32)
    inc = 128 * rng.integers(1, 8, (rows, L))
    vals = (start[None, :] + np.cumsum(inc, axis=0)).astype(np.float32)
    phase = rng.integers(1, STEP_MS, L).astype(np.int32)
    packed = xorgrid.pack_vals(vals, phase=phase, min_width=16)
    assert packed is not None and (packed.inv == np.arange(L)).all(), \
        "smoke workload failed the single-class pack contract"
    planes = {k: jnp.asarray(v) for k, v in packed.planes.items()}
    T, K = 20, 5
    q = GridQuery(nsteps=T, kbuckets=K, gstep_ms=STEP_MS, is_rate=True,
                  dense=True)
    s_d, c_d = rate_grid_grouped(None, jnp.asarray(vals[:T + K - 1]), 0,
                                 q, group_lanes=gl, interpret=True,
                                 phase=phase)
    s_p, c_p = rate_grid_grouped_packed(planes, 0, q, group_lanes=gl,
                                        interpret=True)
    rel = float(np.nanmax(np.abs(np.asarray(s_p) - np.asarray(s_d))
                          / np.maximum(np.abs(np.asarray(s_d)), 1e-6)))
    cnt = float(np.max(np.abs(np.asarray(c_p) - np.asarray(c_d))))
    log(f"interpret smoke: dense-vs-compressed rel={rel:.2e} cnt={cnt}")
    if not (rel < 1e-5 and cnt == 0):
        fail(f"interpret-mode variant smoke diverged (rel={rel:.2e}, "
             f"cnt={cnt})")
    _hist_topk_interpret_smoke(rng, T, K, q)


def _hist_topk_interpret_smoke(rng, T, K, q):
    """Interpret-mode twins of the hist-quantile and event-topK
    variants: fused programs vs their XLA decode oracles on tiny
    shapes, so a broken new kernel fails in CPU CI, not only on TPU."""
    import jax.numpy as jnp

    from filodb_tpu.codecs import xorgrid
    from filodb_tpu.ops import histogram_ops
    from filodb_tpu.ops.grid import (GridQuery, event_topk_grid_packed,
                                     hist_quantile_grid_packed,
                                     rate_grid_ref)

    rows = 64          # >= T+K-1; 64 amortizes the meta tiles past the
    #                    packer's >=25% threshold (the kernel decodes
    #                    the whole block and slices the query rows)
    used = T + K - 1
    # hist: 4 groups x 8 series x 4 buckets
    hb, per, gh = 4, 8, 4
    cols = gh * per * hb
    hv = _c16_np(rng, rows, cols)
    phase = np.repeat(rng.integers(1, STEP_MS, cols // hb), hb) \
        .astype(np.int32)
    pk = xorgrid.pack_vals(hv, phase=phase, min_width=16, stride=hb)
    assert pk is not None and (pk.inv == np.arange(cols)).all(), \
        "hist smoke failed the stride pack contract"
    planes = {k: jnp.asarray(v) for k, v in pk.planes.items()}
    tops = np.concatenate([2.0 ** np.arange(hb - 1), [np.inf]])
    fused = np.asarray(hist_quantile_grid_packed(
        planes, 0, jnp.asarray(tops), q, 0.9, hb, group_lanes=per * hb,
        interpret=True))
    stepped = np.asarray(rate_grid_ref(None, jnp.asarray(hv[:used]), 0,
                                       q, phase=phase))
    hs = stepped.reshape(T, gh, per, hb).sum(2).transpose(1, 0, 2)
    ref = np.asarray(histogram_ops.hist_quantile(
        jnp.asarray(tops), jnp.asarray(hs), 0.9))
    h_rel = float(np.nanmax(np.abs(fused - ref)
                            / np.maximum(np.abs(ref), 1e-6)))
    log(f"interpret smoke: hist fused-vs-XLA rel={h_rel:.2e}")
    if not h_rel < 1e-5:
        fail(f"interpret-mode hist quantile smoke diverged "
             f"(rel={h_rel:.2e})")
    # event topK: 256 lanes, 8 contiguous groups (the banded
    # group_width form the TPU variant measures), filter column, k=3
    el, eg, k = 256, 8, 3
    v = _c16_np(rng, rows, el)
    fv = _c16_np(rng, rows, el)
    pv, pf = (xorgrid.pack_vals(x, min_width=16) for x in (v, fv))
    dv = {kk: jnp.asarray(a) for kk, a in pv.planes.items()}
    df = {kk: jnp.asarray(a) for kk, a in pf.planes.items()}
    qs = GridQuery(nsteps=T, kbuckets=K, gstep_ms=STEP_MS, op="sum",
                   is_rate=False, dense=True)
    ql = GridQuery(nsteps=T, kbuckets=K, gstep_ms=STEP_MS, op="last",
                   is_rate=False, dense=True)
    thr = float(np.median(fv[used - 1]))   # ~half the lanes pass
    tv, _ti = event_topk_grid_packed(
        dv, 0, qs, k, None, eg, filt_packed=df,
        filt_op="gt", filt_thresh=thr, filt_q=ql, interpret=True,
        group_width=el // eg)
    sv = np.asarray(rate_grid_ref(None, jnp.asarray(v[:used]), 0, qs))
    sf = np.asarray(rate_grid_ref(None, jnp.asarray(fv[:used]), 0, ql))
    masked = np.where(sf > thr, sv, np.nan)
    fin = np.isfinite(masked)
    gs = np.where(fin, masked, 0.0).reshape(T, eg, el // eg).sum(2)
    gc = fin.reshape(T, eg, el // eg).sum(2)
    ranked = np.where(gc > 0, gs, -np.inf)
    want = -np.sort(-ranked, axis=1)[:, :k]
    want = np.where(np.isfinite(want), want, np.nan)
    got = np.asarray(tv)
    if (np.isfinite(got) != np.isfinite(want)).any():
        fail("interpret-mode event topK smoke: NaN-rank pattern "
             "diverged from the XLA oracle")
    fin2 = np.isfinite(want)
    t_rel = float(np.max(np.abs(got[fin2] - want[fin2])
                         / np.maximum(np.abs(want[fin2]), 1e-6),
                         initial=0.0))
    log(f"interpret smoke: event topk fused-vs-XLA rel={t_rel:.2e}")
    if not t_rel < 1e-5:
        fail(f"interpret-mode event topK smoke diverged "
             f"(rel={t_rel:.2e})")


def _numpy_rate_sum(ts, vals, ids, steps):
    """Per-series, per-window iterator implementation — the reference's
    ChunkedRateFunction shape (binary search + per-window pass), single core."""
    S_, R_ = ts.shape
    T_ = len(steps)
    G_ = ids.max() + 1 if len(ids) else 1
    out = np.zeros((G_, T_))
    cnt = np.zeros((G_, T_))
    for s in range(S_):
        t_row, v_row = ts[s], vals[s]
        fin = np.isfinite(v_row)
        t_row, v_row = t_row[fin], v_row[fin]
        if len(t_row) < 2:
            continue
        corr = np.concatenate([[0.0], np.cumsum(np.maximum(
            v_row[:-1] - v_row[1:], 0.0))])
        v_adj = v_row + corr
        for j, st in enumerate(steps):
            lo = np.searchsorted(t_row, st - WINDOW_MS, side="right")
            hi = np.searchsorted(t_row, st, side="right")
            if hi - lo < 2:
                continue
            t1, t2 = t_row[lo], t_row[hi - 1]
            if t2 == t1:
                continue
            delta = v_adj[hi - 1] - v_adj[lo]
            n = hi - lo
            avg_dur = (t2 - t1) / (n - 1)
            ext_start = min(st - WINDOW_MS + avg_dur / 2, float(t1)) \
                if t1 - (st - WINDOW_MS) <= avg_dur * 1.1 else t1 - avg_dur / 2
            ext_end = max(st - avg_dur / 2, float(t2)) \
                if st - t2 <= avg_dur * 1.1 else t2 + avg_dur / 2
            rate = delta * ((ext_end - ext_start) / (t2 - t1)) / (WINDOW_MS / 1000.0)
            g = ids[s]
            out[g, j] += rate
            cnt[g, j] += 1
    return np.where(cnt > 0, out, np.nan)


if __name__ == "__main__":
    main()
