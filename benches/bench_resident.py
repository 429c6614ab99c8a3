"""Compressed HBM residents: a 24 h x 100k-series dashboard served FULLY
resident (round-5 VERDICT #4).

The reference's defining trick is serving compressed BinaryVectors in
place from bounded block memory (memory/BlockManager.scala:142,
doc/compression.md).  Here grid blocks hold XOR-class value planes and
elide uniform-phase ts planes; the serving program decodes them ON
DEVICE.  This bench stages a full day of minutely integer-valued gauges
for >=100k series, asserts the whole window is resident (no rebuilds on
repeat queries), and reports resident bytes/sample + the window
multiplier vs the decoded layout.

Env: FILODB_RES_SERIES (default 102400), FILODB_RES_HOURS (default 24),
FILODB_RES_BACKEND=tpu to serve from the real device (default: CPU).
"""

import os
import sys
import pathlib

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import numpy as np  # noqa: E402

from benches.common import emit, force_cpu_x64, log, timed  # noqa: E402

if os.environ.get("FILODB_RES_BACKEND") != "tpu":
    force_cpu_x64()

from filodb_tpu.core.filters import ColumnFilter, Equals  # noqa: E402
from filodb_tpu.core.record import RecordBuilder  # noqa: E402
from filodb_tpu.core.schemas import DEFAULT_SCHEMAS, DatasetOptions  # noqa: E402
from filodb_tpu.core.storeconfig import StoreConfig  # noqa: E402
from filodb_tpu.memstore.devicestore import BLOCK_BUCKETS  # noqa: E402
from filodb_tpu.memstore.memstore import TimeSeriesMemStore  # noqa: E402
from filodb_tpu.query.logical import RangeFunctionId as F  # noqa: E402

N_SERIES = int(os.environ.get("FILODB_RES_SERIES", 102_400))
HOURS = int(os.environ.get("FILODB_RES_HOURS", 24))
STEP = 60_000
BASE = 1_700_000_040_000
N_ROWS = HOURS * 60
WINDOW = 300_000
K = WINDOW // STEP


def main():
    store = TimeSeriesMemStore()
    cfg = StoreConfig(grid_step_ms=STEP, max_chunks_size=N_ROWS,
                      device_cache_bytes=8 << 30,
                      max_data_per_shard_query=1 << 40)
    sh = store.setup("prom", DEFAULT_SCHEMAS, 0, cfg)
    b = RecordBuilder(DEFAULT_SCHEMAS["gauge"], DatasetOptions(),
                      container_size=8 << 20)
    rng = np.random.default_rng(0)
    ts = BASE + np.arange(N_ROWS, dtype=np.int64) * STEP
    log(f"ingesting {N_SERIES} series x {N_ROWS} rows "
        f"({N_SERIES * N_ROWS / 1e6:.0f}M samples)...")
    for i in range(N_SERIES):
        # integer-valued gauge walk (bytes/requests/connections — the
        # common production shape)
        vals = (1_000_000
                + np.cumsum(rng.integers(-500, 500, size=N_ROWS))
                ).astype(np.float64)
        b.add_series(ts, [vals],
                     {"_metric_": "res_dash", "inst": f"i{i}",
                      "_ws_": "w", "_ns_": "n"})
        if (i + 1) % (1 << 14) == 0:
            for off, c in enumerate(b.containers()):
                sh.ingest_container(c, off)
            log(f"  {i + 1}/{N_SERIES}")
    for off, c in enumerate(b.containers()):
        sh.ingest_container(c, off)
    sh.flush_all(ingestion_time=1000)

    res = sh.lookup_partitions([ColumnFilter("_metric_", Equals("res_dash"))],
                               0, 2**62)
    assert len(res.part_ids) == N_SERIES
    steps0 = BASE + (K + 1) * STEP
    nsteps = N_ROWS - K - 2
    gids = [0] * N_SERIES

    # the TPU grid serves <=1024 input rows per program (VMEM tile
    # bound, ops/grid.py MAX_GRID_ROWS); a full day at 1-min cadence is
    # 1440 rows, which the query layer time-splits.  Serve the window
    # as panel queries the way the planner would — every panel must hit
    # the SAME resident blocks with zero rebuilds.
    panel = min(nsteps, 1024 - K)
    panels = []
    s = 0
    while s < nsteps:
        n = min(panel, nsteps - s)
        panels.append((steps0 + s * STEP, n))
        s += n

    def serve():
        outs = []
        for st0, n in panels:
            got = sh.scan_grid_grouped(res.part_ids, F.RATE, st0, n,
                                       STEP, WINDOW, gids, 1, "sum")
            assert got is not None, "dashboard fell off the resident path"
            outs.append(got)
        return outs

    serve()                                    # stage + compile
    cache = next(iter(sh.device_caches.values()))
    builds = cache.builds
    t = timed(serve, reps=3)
    assert cache.builds == builds, "repeat queries rebuilt blocks"
    assert cache.evictions == 0, "window did not fit the budget"

    resident = sum(blk.nbytes for blk in cache.blocks.values())
    raw_cells = sum(BLOCK_BUCKETS * blk.width
                    for blk in cache.blocks.values())
    decoded_layout = raw_cells * (4 + 8)       # int32 ts + f64 vals
    samples = N_SERIES * N_ROWS
    total = N_SERIES * (nsteps - 1 + K)
    emit("resident dashboard serve (24h window, fully resident)",
         total / t, "samples/sec", series=N_SERIES, hours=HOURS)
    emit("resident HBM bytes per sample", resident / samples, "bytes",
         resident_mb=round(resident / 2**20, 1))
    emit("resident window multiplier vs decoded layout",
         decoded_layout / resident, "x",
         note="ts plane elided + XOR-class value planes")


if __name__ == "__main__":
    main()
