"""The child of ``tests/test_dev4mesh.py``: the deployment of
``benchmark/configs/dev-4shard-4chip.json`` at a size a test holds, on FOUR
virtual CPU devices (the parent starts this file with
``XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu``; the
tests' own process has eight).  It boots the server through
``standalone.boot``, loads 48 namespaces x 128 instances through the
container edge, asks the panels of ``benchmark/traffic/hicard-wide.json``
over HTTP and prints ONE line of JSON: what the tests assert on."""

import json
import pathlib
import sys
import urllib.parse
import urllib.request

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "benchmark"))
sys.path.insert(0, str(ROOT / "tests"))

import oracle  # noqa: E402
from harness import compare, loader, traffic  # noqa: E402
from harness.population import Population  # noqa: E402

CONF = json.loads((ROOT / "benchmark" / "configs"
                   / "dev-4shard-4chip.json").read_text())
TRAFFIC = traffic.load(ROOT / "benchmark" / "traffic" / "hicard-wide.json")
SPEC = dict(CONF["population"], namespaces=48)
DATASET = CONF["dataset"]


def get(port: int, path: str) -> bytes:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=120) as r:
        assert "X-FiloDB-Partial-Data" not in r.headers
        return r.read()


def launches(port: int) -> dict:
    """``filodb_kernel_launches_total`` by program."""
    out = {}
    for ln in get(port, "/metrics").decode().splitlines():
        if ln.startswith("filodb_kernel_launches_total{"):
            program = ln.split('program="', 1)[1].split('"')[0]
            out[program] = out.get(program, 0.0) + float(ln.rsplit(" ", 1)[1])
    return out


def plan_builds(port: int) -> int:
    """How many ``mesh.plan_build`` spans have finished: the shard plans
    the fabric BUILT (a reuse opens none)."""
    stages = json.loads(get(port, "/admin/device"))["data"]["stages"]
    return stages.get("mesh.plan_build", {}).get("count", 0)


def launched(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0.0) for k, v in after.items()
            if v != before.get(k, 0.0)}


def oracle_answer(pop, panel: dict, sel) -> dict:
    ref = panel["reference"]
    start, end, step, _n = traffic.panel_range(panel, SPEC)
    per = np.stack([oracle.range_fn(ref["fn"], pop.ts[s], pop.vals[s], start,
                                    end, step, ref["window_ms"])
                    for s in sel])
    if ref["aggregate"] == "sum":
        return {"": per.sum(axis=0)}
    return {"": np.quantile(per, ref["q"], axis=0)}


def rung_of(port: int, stats: dict) -> str:
    """The ``rung`` tag of the answer's ``mesh.present`` span."""
    nodes = json.loads(get(port, f"/admin/traces/{stats['traceId']}")
                       )["data"]["spans"]
    while nodes:
        n = nodes.pop()
        nodes.extend(n["children"])
        if n["name"] == "mesh.present":
            return n["tags"]["rung"]
    return ""


def main() -> dict:
    import jax

    from filodb_tpu import standalone
    from filodb_tpu.parallel import meshgrid
    from filodb_tpu.promql.parser import query_range_to_logical_plan
    from filodb_tpu.query.aggregators import QuantileAggregator
    from filodb_tpu.query.model import QueryContext
    report = {"devices": len(jax.devices()), "panels": {}}
    pop = Population(SPEC, 2 ** 31 + 34)
    server = standalone.boot(CONF["server"])
    port = server.http.port
    try:
        loader.load(pop, server, DATASET, port, lambda msg: None)
        server.flush_all()
        for i, panel in enumerate(CONF["staging"]):
            req = traffic.request_for(panel, -1 - i, -1, SPEC, DATASET, 120,
                                      False)
            assert json.loads(get(port, req.path))["status"] == "success"
        binding = server.http.datasets[DATASET]
        ns = int(pop.ns[pop.reset_series[0]])
        timings, first_stages = set(), None

        def ask(pi: int, namespace: int):
            panel = TRAFFIC["panels"][pi]
            req = traffic.request_for(panel, pi, namespace, SPEC, DATASET,
                                      TRAFFIC["timeout_s"], True)
            got, stats = compare.parse_matrix(get(port, req.path), panel,
                                              SPEC)
            timings.update(stats["timings"])
            return got, stats

        # (a), (b): every panel against the oracle, its plan's root, the
        # launches it took
        for pi, panel in enumerate(TRAFFIC["panels"]):
            start, end, step, _n = traffic.panel_range(panel, SPEC)
            query = panel["query"].format(
                metric=SPEC["metric"], workspace=SPEC["workspace"],
                namespace=pop.ns_name(ns))
            plan = binding.planner.materialize(
                query_range_to_logical_plan(query, start, step, end),
                QueryContext())
            cold = plan_builds(port)
            ask(pi, ns)                  # the programs compile here
            before, built = launches(port), plan_builds(port)
            got, stats = ask(pi, ns)
            if first_stages is None:
                first_stages = sorted(json.loads(get(
                    port, "/admin/device"))["data"]["stages"])
            g = compare.gap(got, oracle_answer(
                pop, panel, compare.selection(pop, panel, ns)))
            report["panels"][panel["name"]] = {
                "gap": g, "root": type(plan).__name__,
                "shards": sorted(plan.shards),
                "launched": launched(before, launches(port)),
                "built": [built - cold, plan_builds(port) - built],
                "rung": rung_of(port, stats)}

        # the boundary: 129 members, the namespace and one instance of its
        # neighbour, is past ``exact_members``
        qi = next(i for i, p in enumerate(TRAFFIC["panels"])
                  if p["name"] == "ns_quantile")
        panel = TRAFFIC["panels"][qi]
        other = (ns + 1) % SPEC["namespaces"]
        wanted = list(compare.selection(pop, panel, ns)) \
            + [int(compare.selection(pop, panel, other)[0])]
        query = 'quantile(0.75, %s{_ws_="%s",_ns_=~"%s|%s",instance=~"%s"})' \
            % (SPEC["metric"], SPEC["workspace"], pop.ns_name(ns),
               pop.ns_name(other),
               "|".join(pop.instance_name(s) for s in wanted))
        start, end, step, _n = traffic.panel_range(panel, SPEC)
        before = launches(port)
        got, _stats = compare.parse_matrix(get(
            port, f"/promql/{DATASET}/api/v1/query_range?"
            + urllib.parse.urlencode({
                "query": query, "start": start / 1000, "end": end / 1000,
                "step": f"{step}ms", "stats": "true"})), panel, SPEC)
        report["members_129"] = {
            "exact_members": QuantileAggregator.exact_members,
            "members": len(wanted),
            "gap": compare.gap(got, oracle_answer(pop, panel, wanted)),
            "launched": launched(before, launches(port))}

        # (d): what the fabric keeps resident does not follow the lanes asked
        si = next(i for i, p in enumerate(TRAFFIC["panels"])
                  if p["name"] == "ns_sum_rate")
        held = []
        for namespace in range(SPEC["namespaces"]):
            ask(si, namespace)
            ask(qi, namespace)
            held.append({"bytes": meshgrid.assembled_bytes(),
                         "entries": len(meshgrid._ASSEMBLY_MEMO),
                         "assembles": meshgrid.STATS["assembles"],
                         "rows": len(meshgrid._ROWS_MEMO)})
        report["held"] = held
        # (e): the spans
        report["timings"] = sorted(timings)
        report["stages"] = sorted(json.loads(get(
            port, "/admin/device"))["data"]["stages"])
        report["stages_after_first_answer"] = first_stages
        report["fallbacks"] = meshgrid.STATS["fallbacks"]
    finally:
        server.shutdown()
    return report


if __name__ == "__main__":
    print(json.dumps(main()))
