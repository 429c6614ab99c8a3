"""BENCHMARK.json against the contract's limits on names, units and files."""

import json
import re

import pytest
from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def every_name():
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCHMARK[section]:
            yield e["name"]
    for w in BENCHMARK["workloads"]:
        yield w["config"]
        yield w["traffic"]
    for c in BENCHMARK["configs"]:
        yield from c["reduced"]


@pytest.mark.parametrize("name", sorted(set(every_name())))
def test_name_is_made_of_allowed_characters(name):
    assert NAME.match(name), name


@pytest.mark.parametrize("metric", BENCHMARK["end_to_end"]
                         + BENCHMARK["per_layer"], ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert UNIT.match(metric["unit"]), metric["unit"]
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    if "bound" in metric:
        assert 0.01 <= metric["bound"] <= 0.25
        assert metric["source"] in ("host_clock", "device_trace")
    else:
        assert metric["moves"] in {m["name"] for m in BENCHMARK["end_to_end"]}
        spec = json.loads((BENCH / "metrics" / f"{metric['name']}.json")
                          .read_text())
        assert spec["name"] == metric["name"]
        assert (BENCH / "readers" / f"{spec['reader']}.py").is_file()


def test_names_are_unique_and_keys_exact():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    for section in ("configs", "workloads"):
        names = [e["name"] for e in BENCHMARK[section]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in BENCHMARK["end_to_end"]
               + BENCHMARK["per_layer"]]
    assert len(metrics) == len(set(metrics))
    assert "setup_s" in metrics
    pairs = [(w["config"], w["traffic"]) for w in BENCHMARK["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert 1 <= BENCHMARK["run_seconds"] <= 51
    assert len(json.dumps(BENCHMARK)) < 64 * 1024


@pytest.mark.parametrize("cell", BENCHMARK["workloads"],
                         ids=lambda w: w["name"])
def test_cell_finds_its_files(cell):
    assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
    conf = next(c for c in BENCHMARK["configs"] if c["name"] == cell["config"])
    assert conf["file"].startswith(BENCHMARK["paths"][0] + "/")
    doc = json.loads((ROOT / conf["file"]).read_text())
    assert doc["name"] == conf["name"]
    assert set(conf["reduced"]) == set(doc["reduced"])
    assert doc["guarantees"] and doc["assumed"] and doc["source"]
    assert (BENCH / "traffic" / f"{cell['traffic']}.json").is_file()
