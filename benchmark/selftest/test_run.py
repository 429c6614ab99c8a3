"""``run.py`` end to end at the rehearsal size (256 series, the CPU backend,
a child process): every cell; the timed path broken underneath; a cell, a
configuration, a mix, a metric and a reader added as new files only, one of
them a cell that ingests while it answers, one a mix whose panels slide; a
checkout without the program; a host without the chip."""

import json
import shutil

import pytest
from conftest import BENCH, HERE, ROOT, run_child

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


def last_json(proc):
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    assert lines, proc.stdout[-2000:] + proc.stderr[-2000:]
    return json.loads(lines[-1])


def failing(out: dict) -> set:
    return {k for k, c in out["compared"].items()
            if ("limit" in c and c["value"] > c["limit"])
            or ("at_least" in c and c["value"] < c["at_least"])}


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_of_every_cell(cell, trace):
    p = run_child([BENCH / "run.py", "--workload", cell, "--seed",
                   2 ** 31 + 17, "--seconds", 3, "--trace", trace,
                   "--rehearse"])
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    out = last_json(p)
    assert out["rehearsal"] is True and out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 8
    # no number of a rehearsal stands under a metric's name
    assert "metrics" not in out
    section = "per_layer" if trace else "end_to_end"
    known = {m["name"] for m in BENCHMARK[section]}
    assert out["metric_names"] and set(out["metric_names"]) <= known
    # each number compared stands beside its limit, last on stderr
    tail = p.stderr.strip().splitlines()[-len(out["compared"]):]
    assert all(ln.startswith("compared ") for ln in tail), tail
    # what can fail a run first comes first: the answers' rel_err last
    names = list(out["compared"])
    first_rel = min(i for i, k in enumerate(names) if k.startswith("rel_"))
    assert all(k.startswith("rel_err.") for k in names[first_rel:]), names
    assert "device_dispatches" in names[:first_rel]
    if cell == "jmh1.live-edge":
        assert {"writes_unacknowledged", "writer_behind_s",
                "writes_not_ingested"} <= set(names[:first_rel]), names


def test_a_host_without_the_chip_prints_no_result():
    p = run_child([BENCH / "run.py", "--workload", CELLS[0], "--seed", 1,
                   "--seconds", 1, "--trace", 0])
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


@pytest.mark.parametrize("fault", ["answer_altered", "half_the_series_lost",
                                   "served_by_the_host"])
def test_a_broken_timed_path_comes_out_as_not_correct(fault):
    p = run_child([HERE / "broken_run.py", fault, "--workload", CELLS[0],
                   "--seed", 23, "--seconds", 3, "--trace", 0, "--rehearse"])
    out = last_json(p)
    assert out["correct"] is False, out
    assert p.returncode == 1
    if fault == "served_by_the_host":
        # the answers are right; only the count of device dispatches fails
        assert failing(out) == {"device_dispatches"}, out["compared"]
        assert out["compared"]["device_dispatches"]["value"] == 0
    else:
        assert failing(out) - {"device_dispatches"}, out["compared"]


def test_a_breaker_that_is_gone_gives_no_result():
    p = run_child([HERE / "broken_run.py", "breaker_renamed", "--workload",
                   CELLS[0], "--seed", 23, "--seconds", 2, "--trace", 0,
                   "--rehearse"])
    assert p.returncode == 3
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    assert "cannot be read" in p.stderr


@pytest.fixture()
def copy(tmp_path):
    """BENCHMARK.json and the benchmark's directory alone, as a check of a
    later PR has them, with the program linked in beside."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


def with_program(copy):
    (copy / "filodb_tpu").symlink_to(ROOT / "filodb_tpu")
    return copy / "benchmark", json.loads((copy / "BENCHMARK.json")
                                          .read_text())


def sum_panel(name: str, fn: str, steps: int, step_ms: int, **more) -> dict:
    inner = "{metric}" + ("{{_ns_=\"{namespace}\"}}" if "select" in more
                          else "")
    return dict({"name": name, "weight": 1, "steps": steps,
                 "step_ms": step_ms, "end": "newest",
                 "query": f"sum({fn}({inner}[5m]))",
                 "limits": {"rel_err": 1e-9},
                 "reference": {"fn": fn, "window_ms": 300000,
                               "aggregate": "sum"}}, **more)


def dispatched_by(proc) -> dict:
    """Who took the window's dispatches, from the harness's own line."""
    mark = "device dispatches in the window: "
    return json.loads(next(ln for ln in proc.stdout.splitlines()
                           if ln.startswith(mark))[len(mark):])


def test_four_devices_count_what_the_fabric_dispatched(copy):
    """Every panel a ``sum`` over two or more shards of ``dev-4shard``, one
    of them over all four with no draw: on four devices the planner roots
    each in the mesh fabric, whose launches are ``meshgrid.*`` and no
    ``devicestore.*`` program at all."""
    bench, doc = with_program(copy)
    (bench / "traffic" / "sum-panels.json").write_text(json.dumps({
        "name": "sum-panels", "loop": "closed", "sessions": 4, "think_ms": 0,
        "cycle": 3, "timeout_s": 30, "panels": [
            sum_panel("wide_rate", "rate", 23, 150000),
            sum_panel("ns_rate", "rate", 23, 150000,
                      select={"draw": "uniform", "over": "namespaces"}),
            sum_panel("ns_sot", "sum_over_time", 50, 30000,
                      select={"draw": "uniform", "over": "namespaces"})]}))
    doc["workloads"].append({"name": "dev4.sums", "config": "dev-4shard",
                             "traffic": "sum-panels", "chips": 4,
                             "why": "a test"})
    (copy / "BENCHMARK.json").write_text(json.dumps(doc))
    p = run_child([bench / "run.py", "--workload", "dev4.sums", "--seed", 7,
                   "--seconds", 3, "--trace", 0, "--rehearse"], cwd=copy,
                  devices=4)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    out = last_json(p)
    assert out["correct"] is True and out["device"]["count"] == 4
    served = out["compared"]["device_dispatches"]
    assert served["value"] >= served["at_least"] > 8
    # who took them: the fabric; the one family that PR 26-32's harness
    # counted (and the stacked members) stays below the answered requests
    by = dispatched_by(p)
    assert by["meshgrid."] >= served["at_least"]
    assert by["devicestore."] + by["members"] < served["at_least"]


def add_live_cell(bench, doc: dict, batch_ms: int = 1000) -> str:
    """What a later PR brings for a cell that ingests while it answers, as
    new files and entries: a configuration with ``live_rows``, a mix with a
    ``writer`` and two panels that end at ``now`` (an aggregate and a
    ``raw``), the cell, and a metric with a reader of its own over the
    writer's records."""
    conf = json.loads((bench / "configs" / "jmh-inmem-1shard.json")
                      .read_text())
    conf["name"] = "live-1shard"
    conf["population"]["live_rows"] = 4          # a minute of stream
    conf["guarantees"].append("a sample is queryable within 1000 ms of the "
                              "200 its container was answered with")
    (bench / "configs" / "live-1shard.json").write_text(json.dumps(conf))
    doc["configs"].append({"name": "live-1shard", "source": "a test",
                           "file": "benchmark/configs/live-1shard.json",
                           "reduced": ["retention"], "why": "a test"})
    # an end on the store's 15 s bucket grid, as the accepted panels' ends
    # are: off it (``edge_ms`` 1000) the device store declines the plan and
    # the host path answers (right, and ``device_dispatches`` reads 0)
    now = {"end": "now", "edge_ms": 15000, "weight": 1, "steps": 23,
           "step_ms": 150000,
           "select": {"draw": "uniform", "over": "namespaces"}}
    sel = "{metric}{{_ws_=\"{workspace}\",_ns_=\"{namespace}\"}}"
    (bench / "traffic" / "live-panels.json").write_text(json.dumps({
        "name": "live-panels", "loop": "closed", "sessions": 3, "think_ms": 0,
        "cycle": 2, "timeout_s": 30,
        "writer": {"edge": "containers", "batch_ms": batch_ms,
                   "visible_after_ms": 1000},
        "panels": [
            dict(now, name="sum_rate", query=f"sum(rate({sel}[5m]))",
                 limits={"rel_err": 1e-9},
                 reference={"fn": "rate", "window_ms": 300000,
                            "aggregate": "sum"}),
            dict(now, name="raw", query=sel, limits={"rel_err": 0},
                 reference={"fn": "last", "window_ms": 300000,
                            "aggregate": "none", "key": "instance"})]}))
    doc["workloads"].append({"name": "live.edge", "config": "live-1shard",
                             "traffic": "live-panels", "chips": 1,
                             "why": "a test"})
    (bench / "readers" / "write_ack.py").write_text(
        "def read(run):\n"
        "    acks = [w['t_ack'] - w['t_send'] for w in run['writes']\n"
        "            if w['status'] == 200]\n"
        "    return 1e3 * sum(acks) / len(acks) if acks else None\n")
    (bench / "metrics" / "write_ack_ms.json").write_text(json.dumps(
        {"name": "write_ack_ms", "reader": "write_ack"}))
    doc["per_layer"].append({"name": "write_ack_ms", "unit": "ms",
                             "better": "lower", "source": "host_clock",
                             "layer": "Shard ingest + flush",
                             "moves": "query_p95_ms",
                             "workloads": ["live.edge"]})
    return "live.edge"


def add_slide_cell(bench, doc: dict, within_ms: int = 2_400_000) -> str:
    """What a later PR brings for a mix whose panels slide, as data alone:
    a traffic file and the cell.  "Last 15 minutes" panels (60 steps of
    15 s, 5 min windows) whose ends fall on the 15 s grid up to
    ``within_ms`` before the newest row, one of them drawn by recency, and
    a workspace-wide sum over the last hour that slides a little."""
    sel = "{metric}{{_ws_=\"{workspace}\",_ns_=\"{namespace}\"}}"
    last15 = {"end": "slide", "edge_ms": 15000, "weight": 1, "steps": 60,
              "step_ms": 15000,
              "select": {"draw": "uniform", "over": "namespaces"}}
    (bench / "traffic" / "slide-panels.json").write_text(json.dumps({
        "name": "slide-panels", "loop": "closed", "sessions": 3,
        "think_ms": 0, "cycle": 3, "timeout_s": 30, "panels": [
            dict(last15, name="sum_rate", query=f"sum(rate({sel}[5m]))",
                 slide={"within_ms": within_ms, "draw": "uniform"},
                 limits={"rel_err": 1e-9},
                 reference={"fn": "rate", "window_ms": 300000,
                            "aggregate": "sum"}),
            dict(last15, name="raw", query=sel, limits={"rel_err": 0},
                 slide={"within_ms": within_ms, "draw": "zipf", "s": 1.1},
                 reference={"fn": "last", "window_ms": 300000,
                            "aggregate": "none", "key": "instance"}),
            {"name": "wide_sot", "weight": 1, "steps": 23,
             "step_ms": 150000, "end": "slide", "edge_ms": 15000,
             "slide": {"within_ms": 180000, "draw": "uniform"},
             "query": "sum(sum_over_time({metric}[5m]))",
             "limits": {"rel_err": 1e-9},
             "reference": {"fn": "sum_over_time", "window_ms": 300000,
                           "aggregate": "sum"}}]}))
    doc["workloads"].append({"name": "jmh1.slide",
                             "config": "jmh-inmem-1shard",
                             "traffic": "slide-panels", "chips": 1,
                             "why": "a test"})
    return "jmh1.slide"


def test_a_program_that_ignores_the_end_is_not_correct(copy):
    """``query_range`` answered as if its end were the newest row, on the
    steps asked: every answer at a slid end is another end's numbers."""
    bench, doc = with_program(copy)
    cell = add_slide_cell(bench, doc)
    (copy / "BENCHMARK.json").write_text(json.dumps(doc))
    p = run_child([bench / "selftest" / "broken_run.py", "end_ignored",
                   "--workload", cell, "--seed", 37, "--seconds", 4,
                   "--trace", 0, "--rehearse"], cwd=copy)
    out = last_json(p)
    assert out["correct"] is False and p.returncode == 1, out
    bad = failing(out)
    assert bad & {"absent_cells", "rel_err.raw", "rel_err.sum_rate",
                  "rel_err.wide_sot"}, out["compared"]
    assert out["compared"]["unanswered"]["value"] == 0
    assert len(ends_in(p)) > 3


def test_a_slide_past_the_loaded_rows_prints_no_result(copy):
    """60 steps of 15 s over 5 min windows read 1 185 s back from their
    end, and 255 rows are 3 825 s: a slide of 45 min leaves the data."""
    bench, doc = with_program(copy)
    cell = add_slide_cell(bench, doc, within_ms=2_700_000)
    (copy / "BENCHMARK.json").write_text(json.dumps(doc))
    p = run_child([bench / "run.py", "--workload", cell, "--seed", 1,
                   "--seconds", 3, "--trace", 0, "--rehearse"], cwd=copy)
    assert p.returncode == 3 and "before the first loaded row" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def ends_in(proc) -> dict:
    """Where the window's ``now`` panels ended, and how often."""
    mark = "panel ends in the window: "
    return json.loads(next(ln for ln in proc.stdout.splitlines()
                           if ln.startswith(mark))[len(mark):])


def test_a_writer_that_hands_nothing_on_is_not_correct(copy):
    """The container edge answers 200 and the shards never see the rows:
    the count of rows ingested fails, and so do the answers at a moved
    end."""
    bench, doc = with_program(copy)
    cell = add_live_cell(bench, doc)
    (copy / "BENCHMARK.json").write_text(json.dumps(doc))
    p = run_child([bench / "selftest" / "broken_run.py", "writes_swallowed",
                   "--workload", cell, "--seed", 29, "--seconds", 14,
                   "--trace", 0, "--rehearse"], cwd=copy)
    out = last_json(p)
    assert out["correct"] is False and p.returncode == 1, out
    assert out["writes_attempted"] > 0 and out["writes_failed"] == 0
    bad = failing(out)
    assert "writes_not_ingested" in bad, out["compared"]
    assert bad & {"absent_cells", "rel_err.raw", "rel_err.sum_rate"}, bad
    assert len(ends_in(p)) >= 2


def test_a_server_that_holds_a_container_is_not_correct(copy):
    """The edge holds one container 2.5 s while the load generator runs on:
    the writer falls behind by more than a batch, and no stop of its own
    excuses that."""
    bench, doc = with_program(copy)
    cell = add_live_cell(bench, doc)
    (copy / "BENCHMARK.json").write_text(json.dumps(doc))
    p = run_child([bench / "selftest" / "broken_run.py", "ingest_held",
                   "--workload", cell, "--seed", 37, "--seconds", 8,
                   "--trace", 0, "--rehearse"], cwd=copy)
    out = last_json(p)
    assert out["correct"] is False and p.returncode == 1, out
    assert failing(out) == {"writer_behind_s"}, out["compared"]
    assert out["compared"]["writer_behind_s"]["value"] > 1.2
    assert "load generator stood" not in p.stdout


def test_a_writer_that_cannot_keep_its_schedule_is_not_correct(copy):
    """A container a millisecond: the stream stated is not the stream
    offered, and the run says so."""
    bench, doc = with_program(copy)
    cell = add_live_cell(bench, doc, batch_ms=1)
    (copy / "BENCHMARK.json").write_text(json.dumps(doc))
    p = run_child([bench / "run.py", "--workload", cell, "--seed", 31,
                   "--seconds", 4, "--trace", 0, "--rehearse"], cwd=copy)
    out = last_json(p)
    assert out["correct"] is False and p.returncode == 1, out
    behind = out["compared"]["writer_behind_s"]
    assert behind["limit"] == 0.001 < behind["value"], behind


def test_a_stream_shorter_than_the_run_prints_no_result(copy):
    bench, doc = with_program(copy)
    cell = add_live_cell(bench, doc)
    (copy / "BENCHMARK.json").write_text(json.dumps(doc))
    p = run_child([bench / "run.py", "--workload", cell, "--seed", 1,
                   "--seconds", 51, "--trace", 0, "--rehearse"], cwd=copy)
    assert p.returncode == 3 and "live_rows" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_only_the_benchmark_in_the_directory_prints_no_result(copy):
    p = run_child([copy / "benchmark" / "run.py", "--workload", CELLS[0],
                   "--seed", 1, "--seconds", 1, "--trace", 0, "--rehearse"],
                  cwd=copy)
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_a_later_pr_adds_files_and_entries_and_edits_nothing(copy):
    """Also a cell on four chips whose panels are all aggregates, two of
    them with no draw: the fabric serves it (four virtual devices here); a
    one-chip cell that ingests while it answers; and a one-chip cell whose
    panels slide, a traffic file and an entry alone.  Nothing the benchmark
    has is edited for any."""
    bench, doc = with_program(copy)
    had = {f: f.read_bytes() for f in bench.rglob("*") if f.is_file()}
    live = add_live_cell(bench, doc)
    slide = add_slide_cell(bench, doc)
    # a configuration: two shards of the same population
    conf = json.loads((bench / "configs" / "jmh-inmem-1shard.json")
                      .read_text())
    conf["name"] = "two-shards"
    conf["server"]["datasets"][0].update({"num-shards": 2, "spread": 1})
    (bench / "configs" / "two-shards.json").write_text(json.dumps(conf))
    doc["configs"].append({"name": "two-shards", "source": "a test",
                           "file": "benchmark/configs/two-shards.json",
                           "reduced": ["retention"], "why": "a test"})
    # a traffic mix: data only
    (bench / "traffic" / "avg-panels.json").write_text(json.dumps({
        "name": "avg-panels", "loop": "closed", "sessions": 3, "think_ms": 0,
        "cycle": 3, "timeout_s": 30, "panels": [
            {"name": "sum_sot", "weight": 1, "steps": 50, "step_ms": 30000,
             "query": "sum(sum_over_time({metric}[5m]))", "end": "newest",
             "limits": {"rel_err": 1e-9},
             "reference": {"fn": "sum_over_time", "window_ms": 300000,
                           "aggregate": "sum"}},
            {"name": "by_g", "weight": 1, "steps": 221, "step_ms": 15000,
             "query": "sum by (g)(rate({metric}[5m]))", "end": "newest",
             "limits": {"rel_err": 1e-9},
             "reference": {"fn": "rate", "window_ms": 300000,
                           "aggregate": "sum", "by": "g"}},
            {"name": "hot_ns", "weight": 1, "steps": 23, "step_ms": 150000,
             "query": "sum(rate({metric}{{_ns_=\"{namespace}\"}}[5m]))",
             "end": "newest", "limits": {"rel_err": 1e-9},
             "select": {"draw": "zipf", "s": 1.1, "over": "namespaces"},
             "reference": {"fn": "rate", "window_ms": 300000,
                           "aggregate": "sum"}}]}))
    doc["workloads"].append({"name": "two.avg", "config": "two-shards",
                             "traffic": "avg-panels", "chips": 4,
                             "why": "a test"})
    # a per-layer metric with a reader of its own, in this cell alone
    (bench / "readers" / "slowest.py").write_text(
        "def read(run, scale):\n"
        "    return scale * max(r['latency_s'] for r in run['requests'])\n")
    (bench / "metrics" / "slowest_ms.json").write_text(json.dumps(
        {"name": "slowest_ms", "reader": "slowest", "args": {"scale": 1e3}}))
    doc["per_layer"].append({"name": "slowest_ms", "unit": "ms",
                             "better": "lower", "source": "host_clock",
                             "layer": "HTTP front end",
                             "moves": "query_p95_ms",
                             "workloads": ["two.avg"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(doc))
    p = run_child([bench / "run.py", "--workload", "two.avg", "--seed", 5,
                   "--seconds", 3, "--trace", 1, "--rehearse"], cwd=copy,
                  devices=4)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    out = last_json(p)
    assert out["correct"] is True and "slowest_ms" in out["metric_names"]
    assert out["device"]["count"] == 4 and dispatched_by(p)["meshgrid."] > 0
    # the cell that ingests while it answers: the writer posted and was
    # acknowledged, the panels' ends moved with what it made visible, every
    # answer was right by its own end, and the writes reached a reader
    p = run_child([bench / "run.py", "--workload", live, "--seed", 5,
                   "--seconds", 14, "--trace", 1, "--rehearse"], cwd=copy)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    out = last_json(p)
    assert out["correct"] is True and out["writes_attempted"] > 0
    assert out["writes_failed"] == 0 and not failing(out)
    assert {"writes_unacknowledged", "writer_behind_s",
            "writes_not_ingested"} <= set(out["compared"])
    assert out["compared"]["writes_unacknowledged"]["value"] == 0
    assert len(ends_in(p)) >= 2
    assert "write_ack_ms" in out["metric_names"]
    # the cell whose panels slide: many ends, every answer right by its own
    p = run_child([bench / "run.py", "--workload", slide, "--seed", 5,
                   "--seconds", 4, "--trace", 1, "--rehearse"], cwd=copy)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    out = last_json(p)
    assert out["correct"] is True and not failing(out)
    assert out["compared"]["unanswered"]["value"] == 0
    assert len(ends_in(p)) > 3
    # and the old cells report neither new cell's metric, nor the write side
    p = run_child([bench / "run.py", "--workload", CELLS[0], "--seed", 5,
                   "--seconds", 2, "--trace", 1, "--rehearse"], cwd=copy)
    out = last_json(p)
    assert not {"slowest_ms", "write_ack_ms"} & set(out["metric_names"])
    assert "writes_attempted" not in out
    assert not [k for k in out["compared"] if k.startswith("write")]
    # nothing that was there was edited
    assert all(f.read_bytes() == b for f, b in had.items())
