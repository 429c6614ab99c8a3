"""``dev4.mesh-wide`` at the rehearsal size on FOUR virtual devices, where
the program's mesh fabric serves it as it does on a four-chip host
(``test_run.py``'s rehearsal of every cell runs it on one device, where the
per-shard rung serves and no ``mesh.*`` span occurs): every request is one
``meshgrid.*`` launch, the traced line names every metric the cell brought,
and a ``quantile`` taken from a sketch comes out not correct by that one
number."""

import json

import pytest
from conftest import BENCH, ROOT, run_child
from test_run import dispatched_by, last_json

CELL = "dev4.mesh-wide"
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
MESH_METRICS = sorted(m["name"] for m in BENCHMARK["per_layer"]
                      if m.get("workloads") == [CELL])

# run.py with the fabric's quantile a t-digest sketch whatever the member
# count, as it was before the program could serve this cell (PERF.md section
# 6, PR 33 and PR 34): planted as ``broken_run.py`` plants its faults
SKETCH = f"""
import sys
sys.path.insert(0, {str(BENCH)!r})
sys.path.insert(0, {str(ROOT)!r})
import run
from filodb_tpu.parallel import meshgrid
meshgrid._exact_width = lambda prep, plans: None
raise SystemExit(run.main(sys.argv[1:]))
"""


def rehearse(*first, trace: int, seed: int):
    return run_child([*first, "--workload", CELL, "--seed", seed,
                      "--seconds", 3, "--trace", trace, "--rehearse"],
                     devices=4)


def test_the_cell_brought_seven_metrics_of_the_fabric():
    assert len(MESH_METRICS) == 7 and all(n.startswith("mesh_")
                                          for n in MESH_METRICS)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_fabric_serves_every_request_of_the_cell(trace):
    p = rehearse(BENCH / "run.py", trace=trace, seed=2 ** 31 + 34)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    out = last_json(p)
    assert out["correct"] is True and out["failed"] == 0
    assert out["device"]["count"] == 4
    served = out["compared"]["device_dispatches"]
    assert served["value"] >= served["at_least"] > 8
    by = dispatched_by(p)
    assert by["meshgrid."] >= served["at_least"]
    assert by["devicestore."] == by["members"] == 0
    assert out["compared"]["rel_err.ns_quantile"]["value"] <= 1e-12
    if trace:
        assert set(MESH_METRICS) <= set(out["metric_names"])
        assert "compiled in the window: {}" in p.stdout


def test_a_quantile_from_a_sketch_is_not_correct():
    p = rehearse("-c", SKETCH, trace=0, seed=2 ** 31 + 35)
    out = last_json(p)
    assert out["correct"] is False and p.returncode == 1
    failing = {k for k, c in out["compared"].items()
               if ("limit" in c and c["value"] > c["limit"])
               or ("at_least" in c and c["value"] < c["at_least"])}
    assert failing == {"rel_err.ns_quantile"}, out["compared"]
    assert dispatched_by(p)["meshgrid."] \
        >= out["compared"]["device_dispatches"]["at_least"]
