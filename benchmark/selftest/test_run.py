"""``run.py`` end to end at the rehearsal size (256 series, the CPU backend,
a child process): every cell; the timed path broken underneath; a cell, a
configuration, a mix, a metric and a reader added as new files only; a
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
    failing = {k for k, c in out["compared"].items()
               if ("limit" in c and c["value"] > c["limit"])
               or ("at_least" in c and c["value"] < c["at_least"])}
    if fault == "served_by_the_host":
        # the answers are right; only the count of device dispatches fails
        assert failing == {"device_dispatches"}, out["compared"]
        assert out["compared"]["device_dispatches"]["value"] == 0
    else:
        assert failing - {"device_dispatches"}, out["compared"]


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


def test_only_the_benchmark_in_the_directory_prints_no_result(copy):
    p = run_child([copy / "benchmark" / "run.py", "--workload", CELLS[0],
                   "--seed", 1, "--seconds", 1, "--trace", 0, "--rehearse"],
                  cwd=copy)
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_a_later_pr_adds_files_and_entries_and_edits_nothing(copy):
    """Also a cell on four chips whose panels are all aggregates, two of
    them with no draw: the fabric serves it (four virtual devices here), and
    nothing the benchmark has is edited for it."""
    bench, doc = with_program(copy)
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
    # and the old cells do not report the new cell's metric
    p = run_child([bench / "run.py", "--workload", CELLS[0], "--seed", 5,
                   "--seconds", 2, "--trace", 1, "--rehearse"], cwd=copy)
    assert "slowest_ms" not in last_json(p)["metric_names"]
