"""The live edge: a shard that ingests staggered scrapes while it answers
panels ending at "now" (PR 37; the deployment ``jmh-live-1shard`` of
``benchmark/configs``, at a small size on the CPU).

A server is booted as the benchmark boots it (``FiloServer``, one shard,
the record-container edge), a seeded population is loaded and flushed, and
the live rows are posted container by container through
``POST /ingest/<dataset>/<shard>``.  After each step the benchmark's four
panels, ending at the newest bucket edge that every series has reached, are
asked through ``query_range`` and held cell by cell to ``tests/oracle.py``:
the raw selector and ``quantile`` exactly, ``sum(rate)`` and
``sum_over_time`` to the f64 the CPU path keeps.  Beside the answers,
COUNTS: the open block is appended to and never rebuilt for an ingest epoch
(``cache.builds``), the frozen frontier is maintained and not walked
(``frontier_walks``), what stays resident is the open block's planes.

A scenario runs ONCE (a server each); the tests read what it recorded.
"""

import collections
import functools
import json
import threading
import time
import urllib.parse
import urllib.request

import numpy as np
import pytest

import oracle
from filodb_tpu.core.record import RecordBuilder
from filodb_tpu.core.schemas import DEFAULT_SCHEMAS
from filodb_tpu.memstore import devicestore
from filodb_tpu.memstore.devicestore import BLOCK_BUCKETS
from filodb_tpu.standalone import FiloServer

STEP = 15_000
BASE = 1_700_000_010_000          # on the 15 s grid
NS, PER = 64, 8
WINDOW = 300_000
PANEL_STEPS, PANEL_STEP = 8, 60_000     # 7 * 4 + 20 = 48 bucket rows
SERVER = {
    "node": "live-edge", "http-port": 0,
    "datasets": [{"name": "prom", "num-shards": 1, "min-num-nodes": 1,
                  "schema": "gauge", "spread": 0, "gateway-port": 0,
                  "mesh": False,
                  "store": {"flush-interval": "1h", "groups-per-shard": 4},
                  "workload": {"admission": {"max-inflight-cost": 10 ** 9}}}]}
SEL = 'm{{_ws_="demo",_ns_="{ns}"}}'
PANELS = {
    "raw": SEL,
    "sum_rate": "sum(rate(" + SEL + "[5m]))",
    "quantile": "quantile(0.75, " + SEL + ")",
    "sum_over_time": "sum_over_time(" + SEL + "[5m])",
}
STAGING = "sum(rate(m[5m]))"      # every series into the device store


class Data:
    """``ns x PER`` counter series, a scrape every 15 s at a phase of the
    series' own, ``rows`` loaded and ``live`` streamed; whole numbers."""

    def __init__(self, rows: int, live: int, seed: int, extra: int = 0,
                 extra_from: int = 0, ns: int = NS):
        rng = np.random.default_rng(seed)
        self.rows, self.live, self.ns = rows, live, ns
        self.loaded = ns * PER               # the series set-up loads
        self.n = n = self.loaded + extra
        self.phase = rng.integers(1, STEP, n)
        self.ts = (BASE + np.arange(rows + live, dtype=np.int64)[None, :]
                   * STEP + self.phase[:, None])
        inc = rng.integers(0, 50, (n, rows + live))
        self.vals = (rng.integers(1_000_000, 5_000_000, n)[:, None]
                     + np.cumsum(inc, axis=1)).astype(np.float64)
        # the ``extra`` series: first seen in a live container, at row
        # ``extra_from``
        self.first_row = np.zeros(n, np.int64)
        self.first_row[self.loaded:] = extra_from
        # rows that never arrive (dropped by ingest): not the oracle's
        self.gone = np.zeros((n, rows + live), bool)

    def tags(self, s: int) -> dict:
        ns = s // PER if s < self.loaded else (s - self.loaded) % self.ns
        return {"_metric_": "m", "_ws_": "demo", "_ns_": f"App-{ns:04d}",
                "instance": f"i{s:07d}"}

    def members(self, ns: int) -> list:
        return [s for s in range(self.n)
                if self.tags(s)["_ns_"] == f"App-{ns:04d}"]

    def edge(self, row: int) -> int:
        """The right edge of the bucket that holds every series' row."""
        return BASE + (row + 1) * STEP

    def container(self, cells) -> tuple:
        """(bytes, samples) of one record container holding ``cells``:
        {series: rows}, a series' rows in the order given."""
        b = RecordBuilder(DEFAULT_SCHEMAS["gauge"], container_size=1 << 30)
        n = 0
        for s, rows in cells.items():
            rows = np.asarray(rows, dtype=np.int64)
            n += b.add_series(self.ts[s, rows], [self.vals[s, rows]],
                              self.tags(s))
        (blob,) = b.containers()
        return blob, n

    def by_second(self, row: int) -> list:
        """Live row ``row`` as a producer a second posts it: fifteen
        containers, each the series whose scrape fell in that second."""
        sec = (self.phase - 1) // 1000
        return [{int(s): [row] for s in np.flatnonzero(
            (sec == k) & (self.first_row <= row))}
            for k in range(STEP // 1000)]


class Node:
    """A booted server and what the tests read of it."""

    def __init__(self, data: Data):
        self.data = data
        self.server = FiloServer(json.loads(json.dumps(SERVER)))
        self.server.start()
        self.port = self.server.http.port
        self.shard = self.server.memstore.shards("prom")[0]
        self.visible = np.zeros(data.n, np.int64)   # rows a series has
        loaded = {s: np.arange(data.rows) for s in range(data.loaded)}
        self.post(loaded)
        self.server.flush_all()
        self.visible[:data.loaded] = data.rows
        self.mismatches: dict = {}
        self.asked = 0

    def close(self) -> None:
        self.server.shutdown()

    @property
    def cache(self):
        (cache,) = self.shard.device_caches.values()
        return cache

    def post(self, cells, expect=None) -> None:
        """One container through the edge; waits for the shard's consumer
        (the 200 means it is queued) to have ingested it: the rows
        counted, the open blocks told, the epoch moved."""
        blob, n = self.data.container(cells)
        n = n if expect is None else expect
        shard = self.shard
        rows, epoch, offset = (shard.stats.rows_ingested,
                               shard.ingest_epoch, shard.latest_offset)
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.port}/ingest/prom/0", data=blob,
            headers={"Content-Type": "application/octet-stream"},
            method="POST")
        with urllib.request.urlopen(req, timeout=60) as r:
            assert r.status == 200 and "offset" in json.loads(r.read())
        deadline = time.time() + 60
        while time.time() < deadline and (
                shard.latest_offset == offset
                or (n and shard.ingest_epoch == epoch)):
            time.sleep(0.001)
        assert shard.stats.rows_ingested == rows + n
        for s, rws in cells.items():
            self.visible[s] = max(self.visible[s], int(np.max(rws)) + 1)

    def query(self, panel: str, ns: int, end_ms: int) -> dict:
        """{instance or "": [PANEL_STEPS] floats (NaN: no sample)}."""
        q = PANELS.get(panel, STAGING).format(ns=f"App-{ns:04d}")
        start = end_ms - (PANEL_STEPS - 1) * PANEL_STEP
        qs = urllib.parse.urlencode({
            "query": q, "start": start / 1000, "end": end_ms / 1000,
            "step": f"{PANEL_STEP}ms"})
        with urllib.request.urlopen(
                f"http://127.0.0.1:{self.port}/promql/prom/api/v1/"
                f"query_range?{qs}", timeout=60) as r:
            assert "X-FiloDB-Partial-Data" not in r.headers
            doc = json.loads(r.read())
        assert doc["status"] == "success"
        steps = start + np.arange(PANEL_STEPS) * PANEL_STEP
        out = {}
        for series in doc["data"]["result"]:
            vals = np.full(PANEL_STEPS, np.nan)
            for t, v in series["values"]:
                at = np.flatnonzero(steps == int(round(float(t) * 1000)))
                vals[at[0]] = float(v)
            out[series["metric"].get("instance", "")] = vals
        return out

    def series(self, panel: str, s: int, rows: int, end_ms: int):
        """The oracle's per-series leaf of ``panel`` for series ``s``
        holding its first ``rows`` rows."""
        d = self.data
        lo = d.first_row[s]
        if rows <= lo:
            return None
        keep = ~d.gone[s, lo:rows]
        fn = {"raw": "last", "quantile": "last", "sum_rate": "rate",
              "sum_over_time": "sum_over_time"}[panel]
        return oracle.range_fn(
            fn, d.ts[s, lo:rows][keep], d.vals[s, lo:rows][keep],
            end_ms - (PANEL_STEPS - 1) * PANEL_STEP, end_ms, PANEL_STEP,
            WINDOW)

    def expected(self, panel: str, ns: int, end_ms: int) -> dict:
        """The oracle's answer over the rows posted and ingested."""
        d = self.data
        per = {}
        for s in d.members(ns):
            leaf = self.series(panel, s, self.visible[s], end_ms)
            if leaf is not None:
                per[d.tags(s)["instance"]] = leaf
        if panel in ("raw", "sum_over_time"):
            return {k: v for k, v in per.items() if np.isfinite(v).any()}
        stack = np.array(list(per.values()))
        with np.errstate(all="ignore"):
            if panel == "sum_rate":
                agg = np.where(np.isfinite(stack).any(axis=0),
                               np.nansum(stack, axis=0), np.nan)
            else:
                agg = np.nanquantile(stack, 0.75, axis=0)
        return {"": agg} if np.isfinite(agg).any() else {}

    def check(self, label: str, ns: int, end_ms: int) -> None:
        """Ask the four panels and record where one departs."""
        hits = self.cache.hits if self.shard.device_caches else 0
        for panel in PANELS:
            got = self.query(panel, ns, end_ms)
            want = self.expected(panel, ns, end_ms)
            bad = differs(panel, got, want)
            self.asked += 1
            if bad:
                self.mismatches.setdefault(panel, []).append(
                    f"{label}: {bad}")
        # the device grid served all four, no host fallback
        if self.cache.hits - hits < len(PANELS):
            self.mismatches.setdefault("device", []).append(
                f"{label}: {self.cache.hits - hits} of {len(PANELS)} "
                f"panels were planned by the device grid")


def differs(panel: str, got: dict, want: dict) -> str:
    if set(got) != set(want):
        return f"series {sorted(set(got) ^ set(want))[:4]} on one side only"
    for key, w in want.items():
        g = got[key]
        if (np.isfinite(g) != np.isfinite(w)).any():
            return f"{key}: steps present {np.isfinite(g)} vs " \
                   f"{np.isfinite(w)}"
        m = np.isfinite(w)
        exact = panel in ("raw", "quantile")
        if not np.allclose(g[m], w[m], rtol=0 if exact else 1e-9,
                           atol=0):
            return f"{key}: {g[m]} vs {w[m]}"
    return ""


# ------------------------------------------------------------- scenarios

def stream_rows(node: Node, rows, ns: int, label: str) -> None:
    """Live rows, a container a second; the panels after every
    container, ending at the newest edge every series has reached."""
    d = node.data
    for row in rows:
        for k, cells in enumerate(d.by_second(row)):
            if cells:
                node.post(cells)
            if k % 5 == 4:
                node.check(f"{label} row {row} second {k}", ns,
                           d.edge(row - 1))
        node.check(f"{label} row {row} whole", ns, d.edge(row))


def warm(node: Node, ns: int) -> dict:
    """The frozen panels first (what a node's dashboards did before),
    and the cache's counters once they are served."""
    d = node.data
    node.check("frozen", ns, d.edge(d.rows - 1))
    node.check("frozen again", ns, d.edge(d.rows - 1))
    c = node.cache
    return {"builds": c.builds, "walks": c.frontier_walks,
            "resident": c.bytes_resident, "opened": c.opened}


def finish(node: Node, before: dict, **more) -> dict:
    c = node.cache
    for f in c._rehearsals:
        f.result(timeout=120)
    planes = BLOCK_BUCKETS * next(iter(c._open.values())).lanes \
        * (4 + np.dtype(c._val_dtype()).itemsize) if c._open else 0
    return dict(
        mismatches=node.mismatches, asked=node.asked,
        builds=c.builds - before["builds"],
        walks=c.frontier_walks - before["walks"],
        opened=c.opened - before["opened"], open_blocks=sorted(c._open),
        appends=c.appends, grown=c.bytes_resident - before["resident"],
        planes=planes, dropped=node.shard.stats.out_of_order_dropped,
        **more)


def scenario_same_block(node: Node) -> dict:
    """60 loaded rows, 5 live: the open block is block 0's range (the
    loaded rows' own block, frozen until the first live row lands), so
    the first moved end needs ONE every-lane build of it; after that
    every container is an append into bucket rows other lanes filled."""
    before = warm(node, 3)
    stream_rows(node, range(60, 65), 3, "live")
    return finish(node, before)


def scenario_new_block(node: Node) -> dict:
    """126 loaded rows end one bucket short of block 0's last (bucket
    127): the first live row fills it, the second opens block 1 EMPTY
    (nothing of the shard lies there yet: no build), and every span then
    straddles the boundary: two open blocks in one program."""
    before = warm(node, 5)
    stream_rows(node, range(126, 132), 5, "live")
    return finish(node, before)


def scenario_counter_reset(node: Node) -> dict:
    """A process restart inside the live rows: the counter counts anew."""
    d = node.data
    for s in d.members(7)[:3]:
        d.vals[s, 62:] = 1_000_000 + np.cumsum(
            np.arange(d.vals.shape[1] - 62) + 3.0)
    before = warm(node, 7)
    stream_rows(node, range(60, 65), 7, "reset")
    return finish(node, before)


def scenario_new_series(node: Node, stage_all: bool = False) -> dict:
    """Eight series first seen in a live container: they get their lanes
    when a query first selects them, the open block takes their rows
    (``_stage_new_lanes``: the new lanes alone are read), and from then
    on their rows are appended like the others'."""
    d = node.data
    if stage_all:
        node.query("every_series", 0, d.edge(d.rows - 1))
    before = warm(node, 2)
    stream_rows(node, range(60, 62), 2, "before they appear")
    lanes = next(iter(node.cache._open.values())).lanes
    early = finish(node, before)
    stream_rows(node, range(62, 65), 2, "new series")
    wide = next(iter(node.cache._open.values())).lanes
    assert len(d.members(2)) == PER + 1
    return finish(node, before, lanes=(lanes, wide),
                  builds_before=early["builds"])


def scenario_new_series_width(node: Node) -> dict:
    """... and where every series was staged before (the benchmark's
    staging panel), the 513th lane passes the padded width: every block
    is let go and built again at the new width, once."""
    return scenario_new_series(node, stage_all=True)


def scenario_flush_midway(node: Node) -> dict:
    """A flush of EVERY group in the middle of the stream: no buffer
    holds a row any more, so the frozen block takes the open block's
    place (as for a node gone quiet), the frontier is walked once for
    the new state, and the next live row opens a block again."""
    before = warm(node, 11)
    stream_rows(node, range(60, 62), 11, "before the flush")
    node.server.flush_all()
    node.check("flushed", 11, node.data.edge(61))
    mid = finish(node, before)
    stream_rows(node, range(62, 65), 11, "after the flush")
    return finish(node, before, at_flush=mid)


def scenario_group_flush(node: Node) -> dict:
    """What the deployment does (``flush-interval`` 1h over
    ``groups-per-shard``): ONE group's buffers freeze in the middle of
    the stream, then another's.  The open block STAYS (its cells are the
    same samples whether a chunk or a write buffer holds them), the rows
    that follow are appended as before, and no every-lane build follows
    a freeze; the frontier is walked once a freeze."""
    before = warm(node, 11)
    stream_rows(node, range(60, 62), 11, "before the flush")
    node.shard.flush_group(0)
    node.check("one group flushed", 11, node.data.edge(61))
    mid = finish(node, before)
    stream_rows(node, range(62, 64), 11, "after the flush")
    node.shard.flush_group(2)
    stream_rows(node, range(64, 65), 11, "after the second")
    return finish(node, before, at_flush=mid)


def scenario_out_of_order(node: Node) -> dict:
    """A container whose rows are older than the write buffer, and one
    whose rows arrive out of order: dropped, as ``ingest`` drops them,
    and the open block holds no trace of them."""
    d = node.data
    before = warm(node, 13)
    stream_rows(node, range(60, 62), 13, "in order")
    members = d.members(13)
    node.post({s: [58, 59] for s in members}, expect=0)         # older
    node.post({s: [63, 62] for s in members[:4]}, expect=4)     # 62 drops
    # row 62 of those four never arrives: the oracle's view lacks it too
    d.gone[members[:4], 62] = True
    node.post({s: [62, 63] for s in members[4:]})
    node.check("after the drops", 13, d.edge(63))
    return finish(node, before)


SCENARIOS = {
    "same_block": (scenario_same_block, 60, 5, 0),
    "new_block": (scenario_new_block, 126, 6, 0),
    "counter_reset": (scenario_counter_reset, 60, 5, 0),
    "new_series": (scenario_new_series, 60, 5, NS),      # from row 62
    "new_series_width": (scenario_new_series_width, 60, 5, NS),
    "flush_midway": (scenario_flush_midway, 60, 5, 0),
    "group_flush": (scenario_group_flush, 60, 5, 0),
    "out_of_order": (scenario_out_of_order, 60, 5, 0),
}


@functools.lru_cache(maxsize=None)
def ran(name: str) -> dict:
    fn, rows, live, extra = SCENARIOS[name]
    node = Node(Data(rows, live, seed=37 + len(name), extra=extra,
                     extra_from=rows + 2))
    try:
        return fn(node)
    finally:
        node.close()


@pytest.mark.parametrize("panel", list(PANELS) + ["device"])
@pytest.mark.parametrize("name", list(SCENARIOS))
def test_every_answer_is_the_oracles(name, panel):
    out = ran(name)
    assert out["asked"] >= 4 * 8
    assert not out["mismatches"].get(panel), out["mismatches"][panel][:3]


@pytest.mark.parametrize("name", list(SCENARIOS))
def test_the_open_block_is_appended_to_not_rebuilt(name):
    """Counts, not times.  Builds over every lane: at most one an open
    block that had to be made from rows the hook did not bring (the
    loaded rows' own block) or that a width change retired; never one an
    ingest epoch (dozens passed), never one a flush group's freeze."""
    out = ran(name)
    allowed = {"same_block": 1, "new_block": 1, "counter_reset": 1,
               "out_of_order": 1, "new_series": 1,
               # ... the width grew: the open block again, at 640 lanes
               "new_series_width": 2,
               # ... every group flushed: a frozen build of the range,
               # then an open one when rows land in it again
               "flush_midway": 3,
               # ... a group's freeze retires nothing
               "group_flush": 1}[name]
    assert 0 < out["appends"]
    assert out["builds"] <= allowed, out
    if name == "new_block":
        assert out["opened"] == 1 and out["open_blocks"] == [0, 1]
    if name == "new_series":
        assert out["lanes"] == (128, 128) and out["builds_before"] == 1
    if name == "new_series_width":
        assert out["lanes"] == (512, 640) and out["builds_before"] == 1
    if name == "out_of_order":
        assert out["dropped"] == 2 * PER + 4
    if name == "group_flush":
        # nothing was built because of a freeze, and the block it found
        # open is the one that took the rows after it
        mid = out["at_flush"]
        assert mid["builds"] == out["builds"] == 1
        assert mid["open_blocks"] == out["open_blocks"] == [0]
        assert out["appends"] > mid["appends"] > 0


@pytest.mark.parametrize("name", list(SCENARIOS))
def test_the_frontier_is_maintained_not_walked(name):
    """A walk a freeze or a lane roster that changed, none an epoch."""
    out = ran(name)
    # (a lane assigned changes the roster the walk covers: one walk a
    # request that assigned one; ``new_block``'s row 126 is stamped 1 890 s
    # past BASE, where one flush group's 15-minute boundary of the hourly
    # flush interval falls: its chunks freeze, one walk)
    allowed = {"flush_midway": 1, "new_series": 1, "new_series_width": 1,
               "new_block": 1, "group_flush": 2}.get(name, 0)
    assert out["walks"] <= allowed, out


@pytest.mark.parametrize("name", ["same_block", "new_block",
                                  "counter_reset", "out_of_order"])
def test_what_stays_resident_is_the_open_blocks_planes(name):
    out = ran(name)
    assert out["planes"] > 0
    assert out["grown"] <= len(out["open_blocks"]) * out["planes"], out


# --------------------------------------------- queries against an ingest

@functools.lru_cache(maxsize=None)
def raced() -> dict:
    """Two query threads against the ingest stream: each answer of the
    per-series panels must be the oracle's at SOME prefix of every
    series' rows that includes all rows of the containers whose epoch
    bump had landed before the request was sent (no torn row, no row
    lost, no row of the future)."""
    d = Data(60, 6, seed=91)
    node = Node(d)
    try:
        ns, end = 9, d.edge(65)
        warm(node, ns)
        members = d.members(ns)
        epoch0 = node.shard.ingest_epoch
        plan = [cells for row in range(60, 66)
                for cells in d.by_second(row) if cells]
        # rows a member has once the first k containers are ingested
        after = np.zeros((len(plan) + 1, d.n), np.int64)
        after[0] = node.visible
        for k, cells in enumerate(plan):
            after[k + 1] = after[k]
            for s, rows in cells.items():
                after[k + 1, s] = rows[-1] + 1
        stop = threading.Event()
        bad, answers = [], [0]

        def ask(panel: str) -> None:
            while not stop.is_set():
                done = node.shard.ingest_epoch - epoch0
                got = node.query(panel, ns, end)
                answers[0] += 1
                for s in members:
                    key = d.tags(s)["instance"]
                    if key not in got or not any(
                            not differs(panel, {key: got[key]}, {
                                key: node.series(panel, s, rows, end)})
                            for rows in range(after[done, s],
                                              d.rows + d.live + 1)):
                        bad.append(f"{panel} {key} after {done} containers")

        threads = [threading.Thread(target=ask, args=(p,))
                   for p in ("raw", "sum_over_time")]
        for t in threads:
            t.start()
        for cells in plan:
            node.post(cells)
        stop.set()
        for t in threads:
            t.join(60)
        node.visible = after[-1].copy()
        node.check("settled", ns, end)
        return dict(finish(node, {"builds": 0, "walks": 0, "resident": 0,
                                  "opened": 0}),
                    bad=bad, answers=answers[0], containers=len(plan))
    finally:
        node.close()


def test_no_answer_is_torn_under_concurrent_ingest():
    out = raced()
    assert out["answers"] >= 4 and out["containers"] >= 60
    assert not out["bad"], out["bad"][:5]
    assert not out["mismatches"], out["mismatches"]


def test_concurrent_ingest_appends_and_never_rebuilds():
    out = raced()
    # (only the namespace asked has lanes: 8 series x 6 live rows, in as
    # many of the 90 containers or fewer)
    assert 6 <= out["appends"] <= 48
    assert out["builds"] <= 2          # block 0 frozen, then open, once


# ------------------------------------------- at the live cell's size

# 6 000 series: the live cell's containers hold ~6 800, one row each
LIVE_NS = 750


@functools.lru_cache(maxsize=None)
def at_scale() -> dict:
    """Containers of one row for every one of 6 000 known series (the
    live cell's shape), the four panels after each.  COUNTS a container:
    the grid's bulk hook and its one-series hook, ``ingest_block`` (the
    per-series path's write), the append program's launches, and the
    series the bulk path took by ``filodb_ingest_series_total``."""
    from filodb_tpu.memstore.partition import TimeSeriesPartition
    from filodb_tpu.utils.observability import REGISTRY
    d = Data(60, 4, seed=39, ns=LIVE_NS)
    node = Node(d)
    calls = collections.Counter()
    real_block = TimeSeriesPartition.ingest_block

    def block(part, *args):
        calls["ingest_block"] += 1
        return real_block(part, *args)

    try:
        warm(node, 3)
        node.query("every_series", 0, d.edge(d.rows - 1))   # stage all
        cache = node.cache
        for name in ("note_append_rows", "note_append"):
            real = getattr(cache, name)
            setattr(cache, name, lambda *a, _n=name, _f=real: (
                calls.update([_n]), _f(*a))[1])
        TimeSeriesPartition.ingest_block = block
        series = REGISTRY.counter("filodb_ingest_series_total")
        per = []
        for row in range(d.rows, d.rows + d.live):
            was = (calls.copy(), cache.appends,
                   series.value(dataset="prom", shard=0, path="bulk"))
            node.post({s: [row] for s in range(d.n)})
            # (the counter moves just after the epoch bump post waits for)
            deadline = time.time() + 10
            while time.time() < deadline and series.value(
                    dataset="prom", shard=0, path="bulk") - was[2] < d.n:
                time.sleep(0.001)
            per.append(dict(
                calls=dict(calls - was[0]), appends=cache.appends - was[1],
                bulk=series.value(dataset="prom", shard=0, path="bulk")
                - was[2]))
            node.check(f"row {row}", 3, d.edge(row))
        return dict(per=per, mismatches=node.mismatches, asked=node.asked,
                    n=d.n)
    finally:
        TimeSeriesPartition.ingest_block = real_block
        node.close()


def test_at_the_live_cells_size_every_answer_is_the_oracles():
    out = at_scale()
    assert out["n"] >= 6000 and out["asked"] >= 4 * 6
    assert not out["mismatches"], {k: v[:3]
                                   for k, v in out["mismatches"].items()}


@pytest.mark.parametrize("what", ["hook", "append", "ingest_block",
                                  "counter"])
def test_a_live_container_is_written_a_container_at_a_time(what):
    """Counts, not times, a container: the bulk hook ONCE and the
    one-series hook never; one launch of the append program (the first
    container's rows reach no open block: its range is the loaded rows'
    frozen block until the panels after it build it open); no
    ``ingest_block`` for a known one-row series; the counter's ``bulk``
    moves by the container's series."""
    out = at_scale()
    for k, got in enumerate(out["per"]):
        if what == "hook":
            assert got["calls"].get("note_append_rows") == 1, got
            assert "note_append" not in got["calls"], got
        if what == "append":
            assert got["appends"] == (0 if k == 0 else 1), got
        if what == "ingest_block":
            assert "ingest_block" not in got["calls"], got
        if what == "counter":
            assert got["bulk"] == out["n"], got


# -------------------------------------------------- the pieces, directly

def test_append_program_writes_cells_and_drops_the_padding():
    import jax.numpy as jnp
    cells = devicestore.APPEND_CELLS
    ts = jnp.zeros((BLOCK_BUCKETS, 16), jnp.int32)
    vals = jnp.full((BLOCK_BUCKETS, 16), jnp.nan, jnp.float32)
    idx = np.full((3, cells), BLOCK_BUCKETS, np.int32)
    idx[:, :3] = [[5, 5, 127], [0, 15, 7], [1001, 1002, 1003]]
    v = np.zeros(cells, np.float32)
    v[:3] = [1.5, 2.5, 3.5]
    ts2, vals2 = devicestore._tail_append(ts, vals, idx, v)
    assert np.asarray(ts2)[[5, 5, 127], [0, 15, 7]].tolist() \
        == [1001, 1002, 1003]
    assert np.asarray(vals2)[[5, 5, 127], [0, 15, 7]].tolist() \
        == [1.5, 2.5, 3.5]
    assert int(np.isfinite(np.asarray(vals2)).sum()) == 3
    assert int(np.asarray(ts).sum()) == 0       # the input planes stand


def test_the_hook_costs_a_shard_without_a_grid_one_test():
    """Set-up's load runs before any query: no device cache, and the
    partition's hook returns at the shard's first line."""
    from filodb_tpu.core.storeconfig import StoreConfig
    from filodb_tpu.memstore.memstore import TimeSeriesMemStore
    shard = TimeSeriesMemStore().setup("prom", DEFAULT_SCHEMAS, 0,
                                       StoreConfig())
    d = Data(4, 0, seed=1)
    blob, n = d.container({s: np.arange(4) for s in range(16)})
    assert shard.ingest_container(blob, 0) == n
    assert not shard.device_caches
    part = next(iter(shard.partitions.values()))
    assert part.on_append == shard._on_rows_appended



def test_a_cache_made_while_a_container_ingests_does_not_break_the_hook():
    """A query's thread adds a device cache (the first query of another
    column) while the ingest thread walks them a series: the hook reads
    a snapshot, so the container is ingested whole."""
    d = Data(60, 2, seed=5)
    node = Node(d)
    try:
        warm(node, 1)
        shard, cache = node.shard, node.cache
        told = cache.note_append

        def and_a_cache_appears(*args):
            # what ``device_cache`` does on a query's thread
            shard.device_caches.setdefault(("another", 9), cache)
            return told(*args)

        cache.note_append = and_a_cache_appears
        try:
            node.post({s: [60] for s in d.members(1)})
        finally:
            cache.note_append = told
            shard.device_caches.pop(("another", 9), None)
        node.check("after", 1, d.edge(59))
        assert not node.mismatches, node.mismatches
    finally:
        node.close()


def test_a_request_waits_for_the_rehearsal_and_compiles_nothing():
    """A plan that reads an open block while its programs are still
    being compiled waits for the helpers; one over frozen blocks, or
    with nothing pending, does not."""
    from concurrent.futures import Future
    from types import SimpleNamespace as NS_
    cache = devicestore.DeviceGridCache.__new__(devicestore.DeviceGridCache)
    pending = Future()
    cache._rehearsals = [pending]
    frozen = NS_(segs=(NS_(hi_ts=None),))
    live = NS_(segs=(NS_(hi_ts=None), NS_(hi_ts=np.zeros(1))))
    cache._await_rehearsals(frozen)                 # returns at once
    done = threading.Event()
    waiter = threading.Thread(
        target=lambda: (cache._await_rehearsals(live), done.set()))
    waiter.start()
    assert not done.wait(0.2)
    pending.set_result(None)
    assert done.wait(10)
    waiter.join(10)
    cache._await_rehearsals(live)                   # nothing pending now


@pytest.mark.parametrize("lead", [64, 8])
def test_recipes_are_kept_by_shape_and_both_segment_counts_rehearsed(lead):
    """``new_block``: block 1 opened under a dashboard of four panels:
    what was remembered does not depend on where a span began, and every
    remembered call is launched over the open block with the most and
    the fewest segments a span of its length covers (48 rows: two and
    one), solo and stacked: the most when the block opens, the fewest
    ``REHEARSE_LEAD_ROWS`` before the row where the count changes (row
    47: with a lead of 64 at once, with one of 8 by the append that
    writes row 39)."""
    d = Data(126, 3, seed=11)
    node = Node(d)
    launched = []
    real = devicestore._rehearse_call
    devicestore._rehearse_call = lambda call, stack: (
        launched.append((call[0], len(call[2]), call[3], stack)),
        real(call, stack))
    try:
        warm(node, 5)
        node.cache.REHEARSE_LEAD_ROWS = lead
        stream_rows(node, range(126, 129), 5, "live")
        c = node.cache

        def settled() -> set:
            for f in c._rehearsals:
                f.result(timeout=120)
            return {n for _k, n, _r, _s in launched}

        keys = list(c._recipes)
        assert all(len(k) == 6 for k in keys) and 2 <= len(keys) <= 8
        blk = c._open[1]
        if lead == 8:
            # (block 0, open since row 126, is past its own row 39: its
            # one-segment calls were launched; block 1's wait)
            settled()
            before = len(launched)
            assert len(blk.later) == len(keys)
            # what waits pins no plane of the block
            assert not any(hasattr(x, "shape") for job in blk.later
                           for x in job[1])
            with c._lock:
                c._rehearse_due(1, blk, 38)
            settled()
            assert len(launched) == before and len(blk.later) == len(keys)
            with c._lock:
                c._rehearse_due(1, blk, 39)
            settled()
            assert len(launched) >= before + len(keys)
        assert settled() == {1, 2} and not blk.later
        assert {s for _k, _n, _r, s in launched} >= {None, 2}
        assert not node.mismatches, node.mismatches
    finally:
        devicestore._rehearse_call = real
        node.close()

