"""Batch downsampler rollup throughput (BASELINE config 4).

The offline raw -> 1m -> 15m -> 1h rollup the reference runs as a Spark
job (reference: spark-jobs/.../DownsamplerMain.scala:43 ->
BatchDownsampler.downsampleBatch): pages raw chunks from the column
store, applies the per-schema ChunkDownsamplers, writes downsample
datasets back.  Here the same kernels run under the in-repo batch
driver over (shard x ingestion-time) splits.

Two metrics:
- downsample kernels (griddown.period_reduce — the reshape segment
  reduce serving ALL of dMin/dMax/dSum/dCount/dAvg/dLast in one
  dispatch), measured in a subprocess on the DEFAULT jax backend (the
  TPU under the bench driver);
- the full rollup end-to-end on CPU, including record build, re-ingest
  into the downsample datasets, chunk encode, and the sqlite column
  store write — the Spark-job analog, dominated by persistence.
"""

import os
import subprocess
import sys
import pathlib
import tempfile

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import numpy as np  # noqa: E402

from benches.common import emit, force_cpu_x64, log, timed  # noqa: E402


def kernel_main():
    """Runs on the default backend: measure the period segment-reduce
    (bench.py timing protocol: on-device gen, unrolled iterations,
    readback-forced, 1-iter variant subtracted)."""
    import json
    import time

    import jax
    import jax.numpy as jnp

    from filodb_tpu.downsample.griddown import _period_reduce_impl

    B, S, K = 720, 16_384, 12          # 1h of 5s scrapes -> 1m periods
    ITERS = 20
    P = B // K

    def gen(seed):
        return jax.random.uniform(jax.random.PRNGKey(seed), (B, S),
                                  jnp.float32 if jax.default_backend()
                                  != "cpu" else jnp.float64)

    def build(iters):
        def f(seed):
            vals = gen(seed)
            acc = 0.0
            for i in range(iters):
                out = _period_reduce_impl(vals + i, P, K)
                acc = acc + out["sum"][0, 0] + out["min"][P // 2, 7] \
                    + out["last"][P - 1, 1]
            return acc
        return jax.jit(f)

    f1, fN = build(1), build(1 + ITERS)
    float(f1(0)); float(fN(0))

    def t(f, reps=5):
        best = []
        for _ in range(reps):
            a = time.perf_counter()
            float(f(0))
            best.append(time.perf_counter() - a)
        return float(np.median(best))

    el = max(t(fN) - t(f1), 1e-9)
    rate = B * S * ITERS / el
    print(json.dumps({"rate": rate, "backend": jax.default_backend()}))


if os.environ.get("FILODB_DS_KERNEL") == "1":
    kernel_main()
    sys.exit(0)

force_cpu_x64()

from filodb_tpu.core.record import RecordBuilder  # noqa: E402
from filodb_tpu.core.schemas import DEFAULT_SCHEMAS, DatasetOptions  # noqa: E402
from filodb_tpu.downsample import BatchDownsampler  # noqa: E402
from filodb_tpu.memstore.memstore import TimeSeriesMemStore  # noqa: E402
from filodb_tpu.store.persistence import (DiskColumnStore,  # noqa: E402
                                          DiskMetaStore)

N_SERIES = 500
N_ROWS = 4320            # 6h of 5s scrapes: the reference downsampler's
#                          typical batch window (userTimeOverride 6h
#                          batches, DownsamplerMain.scala)
T0 = 1_600_000_000_000
STEP = 5_000
RESOLUTIONS = (60_000, 900_000, 3_600_000)   # 1m / 15m / 1h


def main():
    rng = np.random.default_rng(0)
    with tempfile.TemporaryDirectory() as tmp:
        from filodb_tpu.core.storeconfig import StoreConfig
        disk = DiskColumnStore(str(pathlib.Path(tmp) / "c.db"))
        meta = DiskMetaStore(str(pathlib.Path(tmp) / "m.db"))
        store = TimeSeriesMemStore(disk, meta)
        # hourly raw chunks (720 rows at 5s cadence), the reference's
        # flush-interval chunk geometry
        store.setup("prom", DEFAULT_SCHEMAS, 0,
                    StoreConfig(max_chunks_size=720))
        b = RecordBuilder(DEFAULT_SCHEMAS["gauge"], DatasetOptions())
        ts = T0 + np.arange(N_ROWS, dtype=np.int64) * STEP
        for i in range(N_SERIES):
            tags = {"_metric_": "disk_io", "instance": f"i{i}",
                    "_ws_": "w", "_ns_": "n"}
            b.add_series(ts, [rng.random(N_ROWS) * 100], tags)
        for off, c in enumerate(b.containers()):
            store.ingest("prom", 0, c, offset=off)
        store.get_shard("prom", 0).flush_all(ingestion_time=1000)
        total = N_SERIES * N_ROWS
        log(f"{total} raw samples flushed; rolling up to "
            f"{[r // 60000 for r in RESOLUTIONS]} min resolutions")

        def rollup():
            job = BatchDownsampler("prom", DEFAULT_SCHEMAS, disk,
                                   resolutions_ms=RESOLUTIONS)
            written = job.run_shard(0, 0, 2**62)
            assert all(written[r] > 0 for r in RESOLUTIONS)
            return written

        t = timed(rollup, reps=3)
        emit("batch downsampler rollup incl. persistence (raw->1m/15m/1h)",
             total / t, "raw samples/sec")

    # kernel-stage metric on the default backend (subprocess: this
    # process already forced CPU)
    import json
    env = dict(os.environ, FILODB_DS_KERNEL="1")
    proc = subprocess.run([sys.executable, __file__], env=env,
                          capture_output=True, text=True, timeout=600)
    line = proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() else ""
    try:
        got = json.loads(line)
        emit("downsample period-reduce kernels", got["rate"],
             "raw samples/sec", backend=got["backend"])
    except (ValueError, KeyError):
        log(f"kernel subprocess failed: {proc.stderr[-400:]}")


if __name__ == "__main__":
    main()
