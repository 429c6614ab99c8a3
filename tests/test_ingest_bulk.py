"""A container's common series are ingested a container at a time
(``TimeSeriesShard._ingest_bulk``), the rest a series at a time.

Two shards are built alike and fed the same containers: one as the program
runs, one with the bulk step declining every series (the per-series path,
the reference).  After every container they must hold the same state: the
stats, every partition's write buffer, pending buffers, chunks and
high-water mark, the dirty sets of every flush group, the index's active
marks, and the device grid's open-block cells (host fill ranges and device
planes), its frontier and what it has seen.  Each case also says how many
series the bulk path must take, so that a case cannot pass by declining.
"""

import dataclasses
import functools

import numpy as np
import pytest

from data import START_TS, histogram_containers
from filodb_tpu.core.filters import ColumnFilter, Equals
from filodb_tpu.core.record import RecordBuilder
from filodb_tpu.core.schemas import DEFAULT_SCHEMAS
from filodb_tpu.core.storeconfig import StoreConfig
from filodb_tpu.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.native import ingestfast
from filodb_tpu.query.logical import RangeFunctionId as F
from filodb_tpu.utils.observability import REGISTRY
from filodb_tpu.workload.quota import SeriesQuota

pytestmark = pytest.mark.skipif(
    not ingestfast.available(), reason="native lib unavailable")

STEP = 15_000
BASE = 1_700_000_010_000          # on the 15 s grid
N, PER = 48, 8                    # series, series a namespace
LOADED = 40                       # rows loaded and flushed before a case
ROWS = LOADED + 160


class Data:
    """Whole-number counters, a scrape every 15 s at a phase of each
    series' own; series ``N..`` exist only where a case posts them."""

    def __init__(self, seed: int = 39):
        rng = np.random.default_rng(seed)
        n = N + PER
        self.phase = rng.integers(1, STEP, n)
        self.ts = BASE + np.arange(ROWS)[None, :] * STEP + self.phase[:, None]
        self.vals = (rng.integers(1_000_000, 5_000_000, n)[:, None]
                     + np.cumsum(rng.integers(0, 50, (n, ROWS)), axis=1)
                     ).astype(np.float64)

    @staticmethod
    def tags(s: int) -> dict:
        return {"_metric_": "m", "_ws_": "demo", "_ns_": f"App-{s // PER}",
                "instance": f"i{s:05d}"}

    def container(self, cells) -> bytes:
        """{series: row indices, in the order given}."""
        b = RecordBuilder(DEFAULT_SCHEMAS["gauge"], container_size=1 << 30)
        for s, rows in cells.items():
            rows = np.asarray(rows, np.int64)
            b.add_series(self.ts[s, rows], [self.vals[s, rows]],
                         self.tags(s))
        (blob,) = b.containers()
        return blob


def _shard(config: StoreConfig, bulk: bool):
    shard = TimeSeriesMemStore().setup("prom", DEFAULT_SCHEMAS, 0, config)
    if not bulk:
        # the reference: the bulk step takes nothing, every series with
        # rows goes the per-series way
        shard._ingest_bulk = lambda dec, schema, ts_s, cols_s, n_l, at_l, \
            groups_r: ([u for u, k in enumerate(n_l) if k], 0)
    return shard


def _stage(shard, d: Data) -> None:
    """What a serving node holds when a live container arrives: the loaded
    rows flushed, every series staged on the device grid, one live row in
    every buffer and an OPEN block built over it."""
    shard.ingest_container(d.container({s: range(LOADED)
                                        for s in range(N)}), 0)
    shard.flush_all()
    ids = _ids(shard)
    _plan(shard, ids, LOADED)
    shard.ingest_container(d.container({s: [LOADED] for s in range(N)}), 1)
    _plan(shard, ids, LOADED + 1)
    (cache,) = shard.device_caches.values()
    assert cache._open, "no open block to append into"


def _ids(shard):
    return shard.lookup_partitions(
        [ColumnFilter("_metric_", Equals("m"))], 0, 2**62).part_ids


def _plan(shard, ids, rows: int) -> None:
    end = BASE + rows * STEP
    assert shard.scan_grid(ids, F.RATE, end - 4 * 60_000, 5, 60_000,
                           300_000) is not None


def _plain(x):
    """Buffers, lists and device planes as comparable lists (NaN: None)."""
    if isinstance(x, (list, tuple)):
        return [_plain(v) for v in x]
    a = np.asarray(x)
    if a.dtype.kind == "f":
        return np.where(np.isnan(a), None, a).tolist()
    return a.tolist()


def state(shard) -> dict:
    parts = {}
    for pk, pid in shard.part_set.items():
        p = shard.partitions.get(pid)
        if p is None:
            parts[pk] = None
            continue
        n = p._buf_n
        parts[pk] = dict(
            pid=pid, cls=type(p).__name__, n=n,
            ts=_plain(p._buf_ts[:n]),
            cols=None if p._buf_cols is None
            else [_plain(c[:n]) for c in p._buf_cols],
            pending=[(_plain(pb.ts), _plain(pb.cols)) for pb in p._pending],
            chunks=[(cs.info.start_time, cs.info.end_time, cs.info.num_rows)
                    for cs in p.chunks],
            high=p.latest_timestamp, dropped=p.out_of_order_dropped)
    grids = {}
    for key, c in shard.device_caches.items():
        grids[key] = dict(
            open={bi: [_plain(getattr(b, f)) for f in
                       ("ts", "vals", "fcnt", "fmin", "fmax", "pmin", "pmax",
                        "hi_ts")] for bi, b in c._open.items()},
            frozen=sorted(c.blocks), frontier=c._frontier[1],
            seen=c._seen_hi, pend=len(c._pend), appends=c.appends,
            disabled=c.disabled_until_version)
    return dict(
        parts=parts, stats=dataclasses.asdict(shard.stats),
        dirty=[sorted(g) for g in shard._dirty_partkeys],
        ends=shard.index._end_arr[:shard._next_part_id].tolist(),
        at=(shard.latest_ingest_ts, shard.latest_offset, shard.ingest_epoch),
        grids=grids)


# ------------------------------------------------------------------- cases
#
# A case: (store config, whether to stage the device grid, a function of
# (data, shard) run on both shards before the containers, the containers
# as functions of the data, the series the bulk path takes in each)

def _every(rows):
    return lambda d: d.container({s: rows for s in range(N)})


def _one_row(d):
    return d.container({s: [LOADED + 1] for s in range(N)})


def _out_of_order(d):
    cells = {s: [LOADED + 1] for s in range(N)}
    cells[0] = [LOADED]                  # equal to the high-water mark
    cells[1] = [LOADED - 3]              # older
    cells[2] = [LOADED + 2, LOADED + 1]  # out of order among themselves
    cells[3] = [LOADED + 1, LOADED + 1]  # the same timestamp twice
    cells[4] = [LOADED + 1, LOADED + 2]
    return d.container(cells)


def _page_out(d, shard):
    # index-only entries, as a recovered or paged-out series leaves them
    for s in (5, 17, 29, 41):
        pk = next(pk for pk, pid in shard.part_set.items()
                  if shard.index.tags(pid)["instance"] == f"i{s:05d}")
        del shard.partitions[shard.part_set[pk]]


def _stopped(d, shard):
    # series the index holds as stopped: a row marks them active again
    for pid in (6, 7, 30):
        shard.index.update_end_time(pid, BASE + LOADED * STEP)


def _flushed(d, shard):
    # every buffer emptied and the frontier walked (no row buffered): the
    # live rows are each buffer's first, and they set the frontier
    shard.flush_all()
    _plan(shard, _ids(shard), LOADED + 1)
    (cache,) = shard.device_caches.values()
    assert cache._frontier[1] is None


def _watermark(d, shard):
    shard.group_watermarks[1] = 10          # group 1 persisted past 9


def _quota(d, shard):
    shard.series_quota = SeriesQuota("prom", overrides={f"App-{N // PER}": 0})


CASES = {
    # the live shape: one row a series, every series known
    "one_row": (StoreConfig(), True, None, [_one_row], [N]),
    # set-up's shape: many rows a series
    "many_rows": (StoreConfig(), True, None,
                  [_every(range(LOADED + 1, LOADED + 11))], [N]),
    "new_series": (StoreConfig(), True, None, [lambda d: d.container(
        {s: [LOADED + 1] for s in range(N + PER)})], [N]),
    "paged_out": (StoreConfig(), True, _page_out, [_one_row], [N - 4]),
    "stopped": (StoreConfig(), True, _stopped, [_one_row], [N]),
    "flushed": (StoreConfig(), True, _flushed, [_one_row, _one_row], [N, 0]),
    "traced": (StoreConfig(trace_filters={"instance": "i00003"}), True, None,
               [_one_row], [N - 1]),
    "out_of_order": (StoreConfig(), True, None, [_out_of_order], [N - 4]),
    # the offsets of the case's containers are 10, 11, ...: group 1's rows
    # of offset 10 are skipped
    "watermark": (StoreConfig(groups_per_shard=4), True, _watermark,
                  [_one_row, _every([LOADED + 2])], [None, N]),
    # an over-quota tenant's new series drop; the known ones are bulk
    "quota": (StoreConfig(), True, _quota, [lambda d: d.container(
        {s: [LOADED + 1] for s in range(N + PER)})], [N]),
    # write buffers of 6 rows, 1 in each after staging: 4, then 6 (two
    # rows into 4: room exactly), 7 (three rows: must freeze midway, the
    # per-series path), 5; then a full buffer takes one row (freeze)
    "capacity": (StoreConfig(max_chunks_size=6), True, None, [
        _every(range(LOADED + 1, LOADED + 4)),
        lambda d: d.container(
            {s: (range(LOADED + 4, LOADED + 6) if s < 16 else
                 range(LOADED + 4, LOADED + 7) if s < 32 else
                 [LOADED + 4]) for s in range(N)}),
        _every([LOADED + 7])], [N, N - 16, N - 16]),
    # no device grid: set-up's load, before any query
    "no_grid": (StoreConfig(), False, None,
                [_every(range(LOADED)), _one_row], [0, N]),
}


@functools.lru_cache(maxsize=None)
def ran(name: str) -> dict:
    config, grid, prep, containers, want = CASES[name]
    d = Data()
    out = {"steps": []}
    shards = [_shard(config, bulk) for bulk in (True, False)]
    for shard in shards:
        if grid:
            _stage(shard, d)
        if prep is not None:
            prep(d, shard)
    series = REGISTRY.counter("filodb_ingest_series_total")
    for k, make in enumerate(containers):
        blob = make(d)
        before = series.value(dataset="prom", shard=0, path="bulk")
        got = [shard.ingest_container(blob, 10 + k) for shard in shards]
        out["steps"].append(dict(
            added=got, bulk=[s._batch_bulk for s in shards],
            counted=series.value(dataset="prom", shard=0, path="bulk")
            - before,
            states=[state(s) for s in shards]))
    out["want"] = want
    return out


@pytest.mark.parametrize("name", list(CASES))
def test_bulk_and_per_series_leave_the_same_state(name):
    out = ran(name)
    for k, step in enumerate(out["steps"]):
        assert step["added"][0] == step["added"][1], k
        bulk, ref = step["states"]
        for key in bulk:
            assert bulk[key] == ref[key], (k, key)


@pytest.mark.parametrize("name", list(CASES))
def test_the_bulk_path_takes_the_series_it_should(name):
    out = ran(name)
    for k, (step, want) in enumerate(zip(out["steps"], out["want"])):
        assert step["bulk"][1] == 0
        if want is not None:
            assert step["bulk"][0] == want, k
        # the counter moves by what the span's tag says
        assert step["counted"] == step["bulk"][0], k


def test_the_histogram_schema_takes_the_per_series_path():
    """A histogram column is written by ``ingest_block``'s own path."""
    first = histogram_containers(n_series=4, n_samples=20)
    more = histogram_containers(n_series=4, n_samples=3,
                                start=START_TS + 20 * 10_000)
    shards = [_shard(StoreConfig(), bulk) for bulk in (True, False)]
    for k, blob in enumerate(first + more):
        for shard in shards:
            shard.ingest_container(blob, k)
        assert shards[0]._batch_bulk == 0
        assert state(shards[0]) == state(shards[1]), k
    assert shards[0].stats.rows_ingested == 4 * 23


def test_a_flush_on_another_thread_never_sees_a_torn_row():
    """Containers written in bulk while another thread flushes every
    group over and over (an admin ``flush_all`` beside the consumer), with
    the interpreter switching threads every few microseconds: each series
    ends with every row once, in order, and the counts agree."""
    import sys
    import threading
    d = Data()
    shard = _shard(StoreConfig(groups_per_shard=4), True)
    shard.ingest_container(d.container({s: range(LOADED) for s in range(N)}),
                           0)
    stop, bulk = threading.Event(), []

    def flusher():
        while not stop.is_set():
            shard.flush_all()

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    thread = threading.Thread(target=flusher)
    try:
        thread.start()
        for row in range(LOADED, ROWS):
            shard.ingest_container(d.container({s: [row] for s in range(N)}),
                                   1 + row)
            bulk.append(shard._batch_bulk)
    finally:
        stop.set()
        thread.join(60)
        sys.setswitchinterval(interval)
    assert not thread.is_alive()
    assert shard.stats.rows_ingested == N * ROWS
    assert shard.stats.out_of_order_dropped == 0
    assert sum(bulk) == N * (ROWS - LOADED)
    for pk, pid in shard.part_set.items():
        part = shard.partitions[pid]
        s = int(shard.index.tags(pid)["instance"][1:])
        ts, vals = part.read_range(0, 2**62)
        assert ts.tolist() == d.ts[s].tolist(), s
        assert vals.tolist() == d.vals[s].tolist(), s


def _lanes(shard, cache, series) -> np.ndarray:
    pid = {shard.index.tags(p)["instance"]: p for p in shard.partitions}
    return np.array([cache.lane_of[pid[f"i{s:05d}"]] for s in series])


def test_a_container_across_a_block_boundary_reaches_both_blocks():
    """Half the rows in the open block, half 100 buckets on (the next
    block of the grid, which nothing of the shard reached before): each
    row lands in its own block, the new one opened empty."""
    d = Data()
    shard = _shard(StoreConfig(), True)
    _stage(shard, d)
    late = LOADED + 100
    shard.ingest_container(d.container(
        {s: [late] if s % 2 else [LOADED + 1] for s in range(N)}), 10)
    assert shard._batch_bulk == N
    (cache,) = shard.device_caches.values()
    assert sorted(cache._open) == [0, 1]
    odd, even = range(1, N, 2), range(0, N, 2)
    for blk, here, there, row in ((cache._open[0], even, odd, LOADED + 1),
                                  (cache._open[1], odd, even, late)):
        assert blk.hi_ts[_lanes(shard, cache, here)].tolist() \
            == d.ts[list(here), row].tolist()
        assert (blk.hi_ts[_lanes(shard, cache, there)]
                < d.ts[list(there), row]).all()


def test_rows_the_open_block_holds_already_are_skipped_not_a_fault():
    """A plan's build can stage rows the hook has queued too: appended
    again, they are skipped, and the cache stays enabled."""
    d = Data()
    shard = _shard(StoreConfig(), True)
    _stage(shard, d)           # the open block was built over row LOADED
    (cache,) = shard.device_caches.values()
    lanes = _lanes(shard, cache, range(N))
    before = cache.appends
    with cache._lock:
        assert cache._append_cells(0, cache._open[0], lanes,
                                   d.ts[:N, LOADED], d.vals[:N, LOADED])
    assert cache.appends == before
    assert cache.disabled_until_version == -1
