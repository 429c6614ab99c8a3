"""What a cell that ingests while it answers stands on, piece by piece and
without a server: a population's live rows leave its loaded rows as they
were; a panel that ends at ``now`` ends at the writer's visible watermark,
floored, and names its answer by that end; the reference by an end reads
nothing past it; the stale control bites at the request's own end; the
writer's watermark stops at the first batch that was not acknowledged."""

import json
import struct
import time

import numpy as np
import pytest
from conftest import BENCH
from harness import compare, traffic
from harness.population import Population

import loadgen

SPEC = dict(json.loads((BENCH / "configs" / "jmh-inmem-1shard.json")
                       .read_text())["population"], namespaces=3)
LIVE = dict(SPEC, live_rows=6)
MIX = traffic.load(BENCH / "traffic" / "jmh-queries.json")


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 7])
def test_live_rows_leave_the_loaded_rows_as_they_were(seed):
    frozen, live = Population(SPEC, seed), Population(LIVE, seed)
    rows = frozen.rows
    assert live.rows == rows and live.ts.shape[1] == rows + 6
    assert (live.ts[:, :rows] == frozen.ts).all()
    assert (live.vals[:, :rows] == frozen.vals).all()
    assert (live.reset_series == frozen.reset_series).all()
    assert live.samples == frozen.samples and live.end_ms == frozen.end_ms
    assert live.live_samples == live.n * 6 and frozen.live_samples == 0
    # the same scrape goes on: every live row past the loaded edge, a
    # scrape interval apart, the counters rising
    assert (live.ts[:, rows:] > live.end_ms).all()
    assert (np.diff(live.ts, axis=1) == live.scrape_ms).all()
    assert (np.diff(live.vals[:, rows - 1:], axis=1) >= 0).all()
    assert (np.diff(live.vals[:, rows - 1:], axis=1) > 0).any()


def now_panel(name="sum_rate", edge_ms=15000) -> dict:
    panel = next(p for p in MIX["panels"] if p["name"] == name)
    return dict(panel, end="now", edge_ms=edge_ms)


def test_a_panel_that_ends_at_now_ends_at_the_floored_watermark():
    newest = traffic.newest_ms(SPEC)
    frozen = next(p for p in MIX["panels"] if p["name"] == "sum_rate")
    panel = now_panel()
    was = traffic.request_for(frozen, 1, 2, SPEC, "prom", 30, False)
    assert was.end_ms is None and was.key == "1:2"
    # before a writer has started: the frozen panel's range, a key of its own
    at_rest = traffic.request_for(panel, 1, 2, SPEC, "prom", 30, False)
    assert at_rest.path == was.path and at_rest.end_ms == newest
    assert at_rest.key == f"1:2:{newest}"
    for visible, end in ((newest, newest), (newest + 14_999, newest),
                         (newest + 15_000, newest + 15_000),
                         (newest + 44_000, newest + 30_000)):
        req = traffic.request_for(panel, 1, 2, SPEC, "prom", 30, False,
                                  visible)
        assert req.end_ms == end and req.key == f"1:2:{end}"
        assert f"end={end / 1000}" in req.path
        assert traffic.panel_range(panel, SPEC, req.end_ms)[:2] \
            == (end - 22 * 150_000, end)
    # a watermark moves a frozen panel nowhere
    assert traffic.request_for(frozen, 1, 2, SPEC, "prom", 30, False,
                               newest + 44_000).path == was.path


def test_a_session_reads_the_watermark_when_it_draws_a_request():
    newest, clock = traffic.newest_ms(SPEC), [0]
    mix = dict(MIX, panels=[now_panel(p["name"]) for p in MIX["panels"]])
    seq = traffic.session(mix, 5, 0, SPEC, "prom", 30, False,
                          lambda: newest + clock[0])
    ends = []
    for clock[0] in (0, 16_000, 31_000):
        ends.append(next(seq).end_ms)
    assert ends == [newest, newest + 15_000, newest + 30_000]


@pytest.mark.parametrize("bad", [
    {"end": "yesterday"}, {"end": "now"}, {"end": "now", "edge_ms": 0}])
def test_a_panel_with_no_such_end_is_refused(bad, tmp_path):
    doc = json.loads((BENCH / "traffic" / "jmh-queries.json").read_text())
    doc["panels"][0].update(bad)
    (tmp_path / "t.json").write_text(json.dumps(doc))
    with pytest.raises(ValueError, match="end is"):
        traffic.load(tmp_path / "t.json")


@pytest.mark.parametrize("bad", [
    {"edge": "influx", "batch_ms": 1000, "visible_after_ms": 0},
    {"edge": "containers", "batch_ms": 0, "visible_after_ms": 0}])
def test_a_writer_of_no_such_kind_is_refused(bad, tmp_path):
    doc = json.loads((BENCH / "traffic" / "jmh-queries.json").read_text())
    doc["writer"] = bad
    (tmp_path / "t.json").write_text(json.dumps(doc))
    with pytest.raises(ValueError, match="writer"):
        traffic.load(tmp_path / "t.json")


@pytest.mark.parametrize("panel", MIX["panels"], ids=lambda p: p["name"])
@pytest.mark.parametrize("ahead", [0, 2, 6])
def test_the_reference_by_an_end_reads_nothing_past_it(panel, ahead):
    """Over all the rows and ending ``ahead`` scrapes past the loaded edge
    it is, bit for bit, the reference over a population that ends there."""
    live = Population(LIVE, 11)
    end = live.end_ms + ahead * live.scrape_ms
    cut = Population(dict(SPEC, rows=SPEC["rows"] + ahead), 11)
    cut.ts = live.ts[:, :cut.rows]
    cut.vals = live.vals[:, :cut.rows]
    ns = int(live.ns[live.reset_series[0]])
    got = compare.reference_answer(live, panel, ns, end_ms=end)
    want = compare.reference_answer(cut, panel, ns)
    assert got.keys() == want.keys()
    for k in want:
        assert np.array_equal(got[k], want[k], equal_nan=True)
    if ahead:       # and it is not the frozen answer moved along
        frozen = compare.reference_answer(live, panel, ns)
        assert compare.gap(got, frozen)["rel_err"] > 0


def test_the_stale_control_bites_at_the_requests_own_end():
    live = Population(LIVE, 11)
    raw = next(p for p in MIX["panels"] if p["name"] == "raw")
    for end in (None, live.end_ms + 3 * live.scrape_ms):
        want = compare.reference_answer(live, raw, 1, end_ms=end)
        got = compare.control_answers(live, "one_scrape_stale", raw, 1, end)
        g = compare.gap(got, want)
        assert g["rel_err"] > 0 and g["series_off"] == 0
        # only the newest step is off: the row before it is still there
        assert all(np.array_equal(got[k][:-1], want[k][:-1]) for k in want)
    # at the loaded edge it is what it was: the last loaded row unseen
    frozen = Population(SPEC, 11)
    was = frozen.vals.copy()
    was[:, -1] = was[:, -2]
    assert np.array_equal(compare._stale(frozen, frozen.end_ms), was)


def writer_with(tmp_path, acked: list, batch_ms=1000, visible_after_ms=2000):
    head = json.dumps({"batches": 90, "containers": []}).encode()
    (tmp_path / "w.bin").write_bytes(struct.pack(">Q", len(head)) + head)
    return loadgen.Writer(
        {"edge": "containers", "batch_ms": batch_ms,
         "visible_after_ms": visible_after_ms},
        {"file": str(tmp_path / "w.bin"), "anchor": 0.0, "acked": acked},
        0, "prom", 1_000_000)


def test_the_watermark_is_what_was_acknowledged_long_enough_ago(tmp_path):
    now = time.time()
    w = writer_with(tmp_path, [now - 9, now - 8, now - 0.5, now - 7])
    assert w.visible_ms() == 1_000_000 + 2 * 1000     # the third: too fresh
    w.acked[2] = now - 3
    assert w.visible_ms() == 1_000_000 + 4 * 1000
    # a batch that was not acknowledged stops it for good
    w = writer_with(tmp_path, [now - 9, None, now - 9])
    assert w.visible_ms() == 1_000_000 + 1000
    assert writer_with(tmp_path, []).visible_ms() == 1_000_000


@pytest.mark.parametrize("batch_ms", [1000, 15000, 40000])
def test_the_stream_is_the_live_rows_dealt_to_their_batches(batch_ms,
                                                            tmp_path):
    """Decoded with the program's own reader: every live sample once, in the
    batch its stamp falls in, in its series' shard, a series' rows in time
    order; nothing of the loaded rows."""
    import sys
    import types

    from conftest import ROOT
    sys.path.insert(0, str(ROOT))
    from filodb_tpu.core.record import (canonical_partkey, decode_container,
                                        partition_hash, shard_key_hash)
    from filodb_tpu.core.schemas import DEFAULT_SCHEMAS, DatasetOptions
    from harness import loader

    pop, opt = Population(LIVE, 13), DatasetOptions()
    routes = [(s % 3, shard_key_hash(t, opt), partition_hash(t, opt),
               canonical_partkey(t))
              for s, t in ((s, pop.tags(s)) for s in range(pop.n))]
    server = types.SimpleNamespace(
        config={"datasets": [{"name": "prom", "schema": "gauge"}]})
    made = loader.live_containers(pop, server, "prom", routes, batch_ms,
                                  tmp_path / "w.bin")
    with open(tmp_path / "w.bin", "rb") as f:
        (n,) = struct.unpack(">Q", f.read(8))
        index = json.loads(f.read(n))
        blob = f.read()
    assert made["batches"] == index["batches"] == -(-6 * 15000 // batch_ms)
    assert made["samples"] == pop.live_samples and made["bytes"] == len(blob)
    seen = dict.fromkeys(range(pop.n), 0)
    for k, shard, samples, at, ln in index["containers"]:
        assert 0 <= k < index["batches"]
        recs = list(decode_container(blob[at:at + ln], DEFAULT_SCHEMAS))
        assert len(recs) == samples
        for r in recs:
            assert pop.end_ms + k * batch_ms < r.timestamp \
                <= pop.end_ms + (k + 1) * batch_ms
            s = int(r.tags["instance"][1:])
            j = pop.rows + seen[s]
            assert routes[s][0] == shard
            assert (r.timestamp, r.values[0]) == (pop.ts[s, j],
                                                  pop.vals[s, j])
            seen[s] += 1
    assert set(seen.values()) == {6}


def test_a_stop_is_what_overlaps_the_span_asked():
    stops = loadgen.Stops()
    stops.spans = [(10.0, 11.5), (20.0, 20.25)]
    assert stops.within(0.0, 100.0) == 1.75
    assert stops.within(11.0, 20.1) == pytest.approx(0.6)
    assert stops.within(12.0, 19.0) == 0.0
    head = stops.head()
    assert head["stops"] == 2 and head["stopped_s"] == 1.75
    assert head["longest"][0] == [10.0, 1.5]


def test_a_stop_of_the_process_is_seen():
    """The load generator's process stopped for 0.4 s (SIGSTOP, as a machine
    that is not run stops it) keeps a span of about that length."""
    import os
    import signal
    import subprocess
    import sys
    code = ("import json, sys, time; sys.path.insert(0, sys.argv[1]); "
            "import loadgen; s = loadgen.Stops(); s.thread.start(); "
            "print('up', flush=True); time.sleep(1.5); s.done.set(); "
            "s.thread.join(); print(json.dumps(s.head()))")
    proc = subprocess.Popen([sys.executable, "-c", code, str(BENCH)],
                            stdout=subprocess.PIPE, text=True)
    try:
        assert proc.stdout.readline().strip() == "up"
        time.sleep(0.3)
        os.kill(proc.pid, signal.SIGSTOP)
        time.sleep(0.4)
        os.kill(proc.pid, signal.SIGCONT)
        head = json.loads(proc.stdout.readline())
    finally:
        proc.wait(timeout=10)
    assert head["stops"] == 1 and 0.3 < head["stopped_s"] < 0.6, head


@pytest.mark.parametrize("stood", [False, True])
def test_lateness_over_a_stop_of_the_load_generator_is_not_held(tmp_path,
                                                                 stood):
    """Batch 0's POST takes 0.45 s of a 0.1 s schedule: batch 1 starts
    ~0.35 s late.  Where the load generator itself stood over those
    seconds the lateness is the machine's; where it ran on, the server's."""
    head = json.dumps({"batches": 2, "containers": [[0, 0, 1, 0, 1],
                                                    [1, 0, 1, 1, 1]]})
    (tmp_path / "w.bin").write_bytes(
        struct.pack(">Q", len(head)) + head.encode() + b"ab")
    anchor = time.time() + 0.05
    stops = loadgen.Stops()
    if stood:
        stops.spans = [(anchor + 0.1, anchor + 0.6)]
    w = loadgen.Writer({"edge": "containers", "batch_ms": 100,
                        "visible_after_ms": 0},
                       {"file": str(tmp_path / "w.bin"), "anchor": anchor,
                        "acked": []}, 0, "prom", 1_000_000, stops)

    def post(shard, container, until):
        if container == b"a":
            time.sleep(0.45)
        return 200
    w.post = post
    w.run(anchor - 0.01, anchor + 5.0, 1.0)
    assert [r["batch"] for r in w.records] == [0, 1]
    assert w.late_s_max > 0.3
    if stood:
        assert w.behind_s_max < 0.05
    else:
        assert w.behind_s_max == w.late_s_max
