"""Device-grid histogram query throughput (BASELINE config 2).

Times the fused kernel pipeline the serving path dispatches for
``histogram_quantile(0.99, sum(rate(latency_bucket[5m])) by (le))`` on
first-class histogram columns: per-bucket counter-corrected rate (the
scalar dense-lane grid kernel over hb bucket lanes per series), the
bucket-wise one-hot-matmul sum on device, then histogram_quantile over
the [T, hb] partials — only the [T] quantile series is read back.

Reference analog: jmh/.../HistogramQueryBenchmark.scala:36 (quantile
query over HistogramColumn); the reference iterates row-by-row through
section-encoded hist vectors, this runs one fused device program.

Runs on JAX's default backend (TPU under the driver; CPU elsewhere —
shapes are scaled down on CPU so the suite stays fast).
"""

import sys
import pathlib
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import numpy as np  # noqa: E402

from benches.common import emit, log  # noqa: E402

STEP_MS = 60_000
WINDOW_MS = 300_000
K = WINDOW_MS // STEP_MS
HB = 16                 # buckets per histogram
T0 = 600_000
REPS = 5


def main():
    import jax
    import jax.numpy as jnp

    from filodb_tpu.ops import histogram_ops
    from filodb_tpu.ops.grid import GridQuery

    on_tpu = jax.default_backend() == "tpu"
    # CPU shape must stay large enough that the timed full-minus-base
    # difference is well above timer noise (a too-small shape reports
    # a nonsense rate)
    n_series = 64_000 if on_tpu else 8_192
    nb = 64             # padded bucket-row axis
    n_rows = 60
    ncols = n_series * HB
    log(f"histogram device bench: {n_series} series x {HB} buckets "
        f"({jax.default_backend()})")

    steps_np = np.arange(T0 + WINDOW_MS, T0 + n_rows * STEP_MS, STEP_MS,
                         dtype=np.int32)
    T = len(steps_np)
    q = GridQuery(nsteps=T, kbuckets=K, gstep_ms=STEP_MS, is_rate=True,
                  dense=True)
    tops = np.cumsum(np.full(HB, 2.0)) ** 2.0
    tops[-1] = np.inf

    def gen(seed):
        """[nb, ncols] cumulative bucket counters, dense rows."""
        key = jax.random.PRNGKey(seed)
        k1, k2 = jax.random.split(key)
        base = (jnp.arange(nb, dtype=jnp.int32) * STEP_MS
                + T0 - STEP_MS + 1)[:, None]
        ts = base + jax.random.randint(k1, (nb, ncols), 0, 30_000, jnp.int32)
        incr = jax.random.uniform(k2, (nb, ncols), jnp.float32, 0.0, 4.0)
        # cumulative over buckets (monotone in bucket axis) and over time
        per_bucket = jnp.cumsum(incr.reshape(nb, n_series, HB), axis=2)
        vals = jnp.cumsum(per_bucket, axis=0).reshape(nb, ncols)
        live = (jnp.arange(nb) < n_rows)[:, None]
        return ts[1:], jnp.where(live, vals, jnp.nan)[1:]

    # group lanes so bucket j of every series lands in group j: the
    # serving path (devicestore.scan_rate_grouped) builds garr the same
    # way; here series*HB columns -> HB groups needs a transposed
    # layout, so generate with buckets contiguous per series and reduce
    # with a one-hot matmul like _grouped_reduce does
    garr = jnp.asarray(np.tile(np.arange(HB, dtype=np.int32), n_series))
    onehot = (garr[:, None] == jnp.arange(HB)[None, :]).astype(jnp.float32)

    def pipeline(ts, vals, bump):
        # per-bucket rate on the scalar dense kernel: [T, ncols].
        # group_lanes must divide ncols; use 1024-wide tiles with the
        # per-column group map applied in the reduce (not the kernel).
        from filodb_tpu.ops.grid import rate_grid_auto
        stepped = rate_grid_auto(ts, vals + bump, int(steps_np[0]), q,
                                 lanes=1024)
        fin = jnp.isfinite(stepped)
        vz = jnp.where(fin, stepped, 0.0)
        hp = jax.lax.Precision.HIGHEST
        sums = jnp.matmul(vz, onehot, precision=hp)          # [T, HB]
        quant = histogram_ops.hist_quantile(jnp.asarray(tops),
                                            sums[None], 0.99)[0]
        return quant                                          # [T]

    def build(iters):
        def f(seed):
            ts, vals = gen(seed)
            acc = jnp.float32(0.0)
            for i in range(iters):
                out = pipeline(ts, vals, jnp.float32(i))
                # every step must stay live or XLA prunes the reduce +
                # quantile down to the handful of steps read back
                acc = acc + jnp.nansum(out)
            return acc
        return jax.jit(f)

    iters = 10 if on_tpu else 2
    f_base, f_full = build(1), build(1 + iters)
    log("compiling...")
    _ = float(f_base(0))
    _ = float(f_full(0))
    best = []
    for _ in range(REPS):
        a = time.perf_counter()
        _ = float(f_full(0))
        b = time.perf_counter()
        _ = float(f_base(0))
        c = time.perf_counter()
        best.append(max((b - a) - (c - b), 1e-9))
    elapsed = float(np.median(best))
    hist_samples = n_series * (n_rows - 1) * iters
    bucket_samples = hist_samples * HB
    emit("hist device-grid sum(rate)+quantile", hist_samples / elapsed,
         "hist samples/sec", bucket_samples_per_sec=round(
             bucket_samples / elapsed, 1))


if __name__ == "__main__":
    main()
