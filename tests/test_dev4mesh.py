"""The deployment of ``benchmark/configs/dev-4shard-4chip.json`` at a size a
test holds: ``dev-4shard`` with a shard a device, served by the mesh fabric.
The server runs in a CHILD on four virtual CPU devices
(``tests/dev4mesh_child.py``; this process has eight), once for the module;
the tests read its report.

Held here: the four panels of ``hicard-wide`` against the brute-force oracle
at the benchmark's limits, ``quantile`` exact at 128 members over two shards
and a sketch at 129; the plan's root and its one ``meshgrid.*`` launch a
request; what the fabric keeps resident while 48 namespaces are asked in
turn; the fabric's stage spans; and, in this process, the cost model that
admission prices the device path with."""

import json
import os
import pathlib
import subprocess
import sys
import time

import pytest

from filodb_tpu.parallel.meshexec import MESH_STAGES
from filodb_tpu.query.model import QueryContext
from filodb_tpu.workload.admission import (AdmissionController,
                                           AdmissionRejected)
from filodb_tpu.workload.cost import CostModel

ROOT = pathlib.Path(__file__).resolve().parents[1]
TRAFFIC = json.loads((ROOT / "benchmark" / "traffic"
                      / "hicard-wide.json").read_text())
PANELS = [p["name"] for p in TRAFFIC["panels"]]
CHILD_TIMEOUT_S = 240        # ~20 s here; the suite's own limit is far off
SPANS = MESH_STAGES + ("mesh.plan_build", "mesh.stage", "mesh.assemble")


@pytest.fixture(scope="module")
def report():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable,
                        str(ROOT / "tests" / "dev4mesh_child.py")],
                       env=env, capture_output=True, text=True,
                       timeout=CHILD_TIMEOUT_S)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4
    return out


# ------------------------------------------- (a) answers against the oracle

@pytest.mark.parametrize("name", PANELS)
def test_served_panel_against_the_oracle(report, name):
    limit = next(p for p in TRAFFIC["panels"]
                 if p["name"] == name)["limits"]["rel_err"]
    assert limit <= (1e-12 if "quantile" in name else 2e-6)
    g = report["panels"][name]["gap"]
    assert g["series_off"] == 0 and g["absent_cells"] == 0
    assert g["rel_err"] <= limit, g


def test_quantile_past_exact_members_is_the_sketch(report):
    """128 members over two shards sit ON ``exact_members`` and are exact
    (the case above, to 1e-12); one more and the fabric sketches, as the
    per-shard rung does: the boundary is where the aggregator says."""
    m = report["members_129"]
    assert m["members"] == m["exact_members"] + 1 == 129
    assert m["gap"]["series_off"] == 0 and m["gap"]["absent_cells"] == 0
    assert m["gap"]["rel_err"] > 1e-6, m
    assert m["launched"].get("meshgrid.quantile") == 1.0
    assert "meshgrid.members" not in m["launched"]


# ----------------------------------------------- (b) the root, the launches

@pytest.mark.parametrize("name", PANELS)
def test_the_root_is_the_fabric_and_a_request_is_one_launch(report, name):
    got = report["panels"][name]
    assert got["root"] == "MeshReduceExec"
    assert len(got["shards"]) == (4 if name.startswith("wide") else 2)
    program = "meshgrid.members" if "quantile" in name else "meshgrid.fused"
    assert got["launched"] == {program: 1.0}
    assert got["rung"] == ("partial" if "quantile" in name else "fused")
    assert report["fallbacks"] == 0


@pytest.mark.parametrize("name", PANELS)
def test_a_panel_asked_again_builds_no_shard_plan(report, name):
    """Through the server: the first request of a panel builds a shard
    plan a shard it selects on (the wide sums share the staging panel's
    lookup, not its plans), the same panel asked again builds none."""
    first, again = report["panels"][name]["built"]
    assert first == (4 if name.startswith("wide") else 2)
    assert again == 0


# ------------------------------------------------------ (c) the cost model

SMALL, WIDE = (770.0, 0.05), (614_000.0, 0.06)    # (units, seconds)


def qctx(deadline_in_ms: int) -> QueryContext:
    now = int(time.time() * 1000)
    q = QueryContext(submit_time_ms=now, timeout_ms=deadline_in_ms)
    q.deadline_ms = now + deadline_in_ms
    return q


def test_cost_model_prices_a_fixed_cost_path():
    """The device path's seconds hardly grow with its units: a namespace
    sum and a workspace-wide one, fed in turn, are each predicted within
    3x, and 8 of them in flight are admitted under a 30 s deadline (the
    through-the-origin model priced the wide one at ~37 s and shed it:
    PERF.md section 6, PR 33)."""
    cm = CostModel()
    for _ in range(40):
        cm.observe(*SMALL)
        cm.observe(*WIDE)
    for units, seconds in (SMALL, WIDE):
        assert seconds / 3 <= cm.estimate_seconds(units) <= seconds * 3
    ctrl = AdmissionController(cm, max_inflight_cost=81_920_000.0,
                               workers=4)
    permits = [ctrl.admit(qctx(30_000), (SMALL, WIDE)[i % 2][0])
               for i in range(8)]
    assert ctrl.snapshot()["inflight_queries"] == 8
    assert ctrl.queue_delay_est_s(WIDE[0]) < 1.0
    for p in permits:
        with p:
            pass
    ctrl.shutdown()


def test_cost_model_sheds_a_load_that_grows_with_its_units():
    """Seconds proportional to units: the line goes through the origin as
    the parent's EWMA of seconds a unit did, and the deadline check sheds
    where it shed."""
    per_unit = 6e-5
    cm = CostModel()
    for units in (770.0, 9_000.0, 614_000.0) * 12:
        cm.observe(units, units * per_unit)
    assert cm.fixed_seconds <= 1e-9
    assert cm.sec_per_unit == pytest.approx(per_unit, rel=1e-6)
    ctrl = AdmissionController(cm, max_inflight_cost=81_920_000.0,
                               workers=4)
    # 614 000 units x 6e-5 s = 36.8 s of work: 9.2 s a worker, admitted
    # under 30 s; the fourth such query in flight is past it
    held = [ctrl.admit(qctx(30_000), 614_000.0) for _ in range(3)]
    with pytest.raises(AdmissionRejected) as exc:
        ctrl.admit(qctx(30_000), 614_000.0)
    assert exc.value.reason == "deadline"
    with ctrl.admit(qctx(30_000), 770.0):       # a small one still fits
        pass
    for p in held:
        with p:
            pass
    ctrl.shutdown()


# -------------------------------------- (d) what the fabric keeps resident

def test_residents_do_not_follow_the_namespaces_asked(report):
    """48 namespaces, a sum and a quantile each, on both pairs of shards:
    the assembly memo holds what it held after the first, nothing was
    assembled again, and only the small rows memo turned over."""
    held = report["held"]
    assert len(held) == 48
    first, last = held[0], held[-1]
    assert last["bytes"] == first["bytes"] > 0
    assert last["entries"] == first["entries"]
    assert last["assembles"] == first["assembles"]
    assert {h["bytes"] for h in held} == {first["bytes"]}
    assert last["rows"] > first["rows"]


# ------------------------------------------------------------ (e) the spans

@pytest.mark.parametrize("span", SPANS)
def test_span_in_timings_and_in_the_stage_table(report, span):
    assert span in report["stages"]
    if span != "mesh.stage":          # staged by set-up's staging panel,
        assert span in report["timings"]   # whose stats nobody asked for
    else:
        assert span in report["stages_after_first_answer"]
