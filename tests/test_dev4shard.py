"""The deployment of ``benchmark/configs/dev-4shard.json`` at a size a test
holds: four shards on one node at spread 1, 32 namespaces x 128 instances
loaded through the container edge into a booted server, the four
``jmh-queries`` panels served over HTTP.

Held here: the served answers against the brute-force oracle at the
benchmark's limits; the same answers from a one-shard boot; the routing
(a namespace panel has exactly the two leaves ``query_shards`` names, and
they hold all of its series); the cross-shard ``quantile`` on its exact
path, at the member count where it ends."""

import json
import pathlib
import sys
import urllib.error
import urllib.parse
import urllib.request

import numpy as np
import pytest

import oracle
from filodb_tpu.core.record import shard_key_hash
from filodb_tpu.core.schemas import DatasetOptions
from filodb_tpu.promql.parser import query_range_to_logical_plan
from filodb_tpu.query.aggregators import QuantileAggregator
from filodb_tpu.query.exec import MultiSchemaPartitionsExec
from filodb_tpu.query.model import QueryContext
from filodb_tpu.standalone import FiloServer

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "benchmark"
sys.path.insert(0, str(BENCH))
from harness import compare, loader, traffic  # noqa: E402
from harness.population import Population  # noqa: E402

DEV4 = json.loads((BENCH / "configs" / "dev-4shard.json").read_text())
JMH1 = json.loads((BENCH / "configs" / "jmh-inmem-1shard.json").read_text())
TRAFFIC = traffic.load(BENCH / "traffic" / "jmh-queries.json")
SPEC = dict(DEV4["population"], namespaces=32)
PANELS = [pytest.param(i, id=p["name"])
          for i, p in enumerate(TRAFFIC["panels"])]


def boot(conf: dict, pop: Population) -> FiloServer:
    """The configuration's server block, booted as its cell runs it: on
    ONE device.  The tests' eight virtual devices would turn the mesh
    fabric on (``standalone``: auto-on with more than one device) and
    make every aggregate a ``MeshReduceExec``: that deployment is
    ``dev-4shard-4chip``'s, held by ``tests/test_dev4mesh.py``."""
    block = json.loads(json.dumps(conf["server"]))
    for ds in block["datasets"]:
        ds["mesh"] = False
    server = FiloServer(block)
    server.start()
    try:
        loader.load(pop, server, conf["dataset"], server.http.port,
                    lambda msg: None)
        server.flush_all()
        # as the benchmark's set-up: the configuration's staging panel
        # first, every series of every shard into the device store
        for i, panel in enumerate(conf["staging"]):
            req = traffic.request_for(panel, -1 - i, -1, SPEC,
                                      conf["dataset"], 120, False)
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{server.http.port}{req.path}",
                    timeout=120) as r:
                assert json.loads(r.read())["status"] == "success"
    except BaseException:
        server.shutdown()
        raise
    return server


@pytest.fixture(scope="module")
def pop():
    return Population(SPEC, 2 ** 31 + 29)


@pytest.fixture(scope="module")
def dev4(pop):
    assert DEV4["population"] == JMH1["population"]
    ds = DEV4["server"]["datasets"][0]
    assert (ds["num-shards"], ds["spread"], ds["min-num-nodes"]) == (4, 1, 1)
    server = boot(DEV4, pop)
    yield server
    server.shutdown()


@pytest.fixture(scope="module")
def jmh1(pop):
    server = boot(JMH1, pop)
    yield server
    server.shutdown()


def namespace_with_reset(pop) -> int:
    return int(pop.ns[pop.reset_series[0]])


def ask(server, pop, pi: int, ns: int, stats: bool = False):
    """(``{series key: [steps] values}``, the answer's ``stats``) of panel
    ``pi`` over namespace ``ns``, as the benchmark's client asks it."""
    req = traffic.request_for(TRAFFIC["panels"][pi], pi, ns, SPEC,
                              DEV4["dataset"], TRAFFIC["timeout_s"], stats)
    with urllib.request.urlopen(
            f"http://127.0.0.1:{server.http.port}{req.path}",
            timeout=120) as r:
        assert "X-FiloDB-Partial-Data" not in r.headers
        body = r.read()
    return compare.parse_matrix(body, TRAFFIC["panels"][pi], SPEC)


def oracle_answer(pop, panel: dict, ns: int) -> dict:
    ref = panel["reference"]
    start, end, step, _n = traffic.panel_range(panel, SPEC)
    sel = compare.selection(pop, panel, ns)
    per = np.stack([oracle.range_fn(ref["fn"], pop.ts[s], pop.vals[s], start,
                                    end, step, ref["window_ms"])
                    for s in sel])
    if ref["aggregate"] == "sum":
        return {"": per.sum(axis=0)}
    if ref["aggregate"] == "quantile":
        return {"": np.quantile(per, ref["q"], axis=0)}
    return {pop.instance_name(s): per[i] for i, s in enumerate(sel)}


@pytest.mark.parametrize("pi", PANELS)
def test_served_panel_against_the_oracle(dev4, pop, pi):
    panel = TRAFFIC["panels"][pi]
    ns = namespace_with_reset(pop)
    got, _stats = ask(dev4, pop, pi, ns)
    g = compare.gap(got, oracle_answer(pop, panel, ns))
    assert g["series_off"] == 0 and g["absent_cells"] == 0
    assert g["rel_err"] <= panel["limits"]["rel_err"], g


@pytest.mark.parametrize("pi", PANELS)
def test_one_shard_boot_gives_the_same_answer(dev4, jmh1, pop, pi):
    """Sharding changes no answer: the layout is all the two
    configurations differ in."""
    for ns in (namespace_with_reset(pop), 0, SPEC["namespaces"] - 1):
        four, _s = ask(dev4, pop, pi, ns)
        one, _s = ask(jmh1, pop, pi, ns)
        g = compare.gap(four, one)
        assert g["series_off"] == 0 and g["absent_cells"] == 0
        assert g["rel_err"] <= 1e-12, (ns, g)


def leaves_of(plan) -> list:
    if isinstance(plan, MultiSchemaPartitionsExec):
        return [plan]
    return [leaf for c in plan.children for leaf in leaves_of(c)]


@pytest.mark.parametrize("pi", PANELS)
def test_a_namespace_panel_has_the_two_leaves_query_shards_names(
        dev4, pop, pi):
    binding = dev4.http.datasets[DEV4["dataset"]]
    mapper = dev4.manager.mapper(DEV4["dataset"])
    panel = TRAFFIC["panels"][pi]
    start, end, step, _n = traffic.panel_range(panel, SPEC)
    seen = set()
    for ns in range(SPEC["namespaces"]):
        query = panel["query"].format(metric=SPEC["metric"],
                                      workspace=SPEC["workspace"],
                                      namespace=pop.ns_name(ns))
        plan = binding.planner.materialize(
            query_range_to_logical_plan(query, start, step, end),
            QueryContext())
        leaves = leaves_of(plan)
        shash = shard_key_hash(pop.tags(ns * pop.per_ns), DatasetOptions())
        want = mapper.query_shards(shash, 1)
        assert len(want) == 2
        assert sorted(leaf.shard for leaf in leaves) == sorted(want)
        # ... and every series of the namespace is on one of the two
        held = {}
        for shard in range(mapper.num_shards):
            lookup = dev4.memstore.get_shard(DEV4["dataset"], shard) \
                .lookup_partitions(leaves[0].filters, leaves[0].start_ms,
                                   leaves[0].end_ms)
            if len(lookup.part_ids):
                held[shard] = len(lookup.part_ids)
        assert sorted(held) == sorted(want), (ns, held, want)
        assert sum(held.values()) == pop.per_ns
        seen.update(want)
    assert seen == set(range(mapper.num_shards))


def plane_shapes(block) -> tuple:
    vals = block.vals
    if isinstance(vals, dict):
        return tuple(sorted((k, tuple(v.shape)) for k, v in vals.items()))
    return (("dense", tuple(vals.shape)),)


def test_the_four_shards_have_one_set_of_program_shapes(dev4, pop):
    """Shards of one dataset agree on their block width and, block by
    block, on the widths of their compressed class planes
    (memstore/gridshapes.py), so a serving program compiles once for all
    of them: a panel that has run on one pair of shards compiles nothing
    on the other pair."""
    from filodb_tpu.utils.devicewatch import COMPILE_WATCH
    shards = dev4.memstore.shards(DEV4["dataset"])
    assert sorted(sh.num_partitions for sh in shards) \
        == [992, 1008, 1040, 1056]              # 1024 or 1152 lanes each
    caches = [c for sh in shards for c in sh.device_caches.values()]
    assert len(caches) == 4
    assert {b.lanes for c in caches for b in c.blocks.values()} == {1152}
    packed = 0
    for bi in caches[0].blocks:
        shapes = {plane_shapes(c.blocks[bi]) for c in caches}
        assert len(shapes) == 1, (bi, shapes)
        packed += isinstance(caches[0].blocks[bi].vals, dict)
    assert packed                       # a compressed block was compared

    def compiles() -> int:
        return sum(p["compiles"] for p in COMPILE_WATCH.table()
                   if p["program"].startswith("devicestore."))
    mapper = dev4.manager.mapper(DEV4["dataset"])
    pair_of = {}
    for ns in range(SPEC["namespaces"]):
        shash = shard_key_hash(pop.tags(ns * pop.per_ns), DatasetOptions())
        pair_of.setdefault(tuple(mapper.query_shards(shash, 1)), ns)
    assert len(pair_of) == 2
    (_a, ns_a), (_b, ns_b) = sorted(pair_of.items())
    for pi in range(len(TRAFFIC["panels"])):
        ask(dev4, pop, pi, ns_a)
    loaded = compiles()
    for pi in range(len(TRAFFIC["panels"])):
        ask(dev4, pop, pi, ns_b)
    assert compiles() == loaded


@pytest.mark.parametrize("written, compressed", [
    ("dataset", True), (" Dataset ", True), (True, True), ("true", True),
    (False, False), ("off", False)])
def test_the_configuration_says_it_counts_on_agreed_shapes(written,
                                                           compressed):
    """``device-cache-compress: dataset`` in the configuration's store
    block: compressed planes, as ``true`` gives them (no switch), in a
    word that a program whose shards cannot agree on their shapes reads
    as no boolean and refuses to start on (``parse_bool``, which read the
    key before PR 29): the cell is not measured on that program."""
    from filodb_tpu.core.storeconfig import StoreConfig, parse_bool
    store = DEV4["server"]["datasets"][0]["store"]
    assert store["device-cache-compress"] == "dataset"
    assert "device-cache-compress" not in \
        JMH1["server"]["datasets"][0]["store"]
    got = StoreConfig.from_config(dict(store,
                                       **{"device-cache-compress": written}))
    assert got.device_cache_compress is compressed
    assert got == StoreConfig.from_config(
        dict(store, **{"device-cache-compress": compressed}))
    with pytest.raises(ValueError):
        parse_bool("dataset")
    with pytest.raises(ValueError):
        StoreConfig.from_config({"device-cache-compress": "datasets"})


@pytest.fixture()
def reduces(monkeypatch):
    """Every state the cross-shard quantile reduce returned."""
    out = []
    inner = QuantileAggregator.reduce

    def reduce(self, partials):
        res = inner(self, partials)
        out.append((partials, res))
        return res
    monkeypatch.setattr(QuantileAggregator, "reduce", reduce)
    return out


def present_span(server, stats: dict) -> dict:
    """The ``quantile.present`` span of the answer that carried ``stats``."""
    with urllib.request.urlopen(
            f"http://127.0.0.1:{server.http.port}/admin/traces/"
            f"{stats['traceId']}", timeout=30) as r:
        nodes = json.loads(r.read())["data"]["spans"]
    found = []
    while nodes:
        n = nodes.pop()
        nodes.extend(n["children"])
        if n["name"] == "quantile.present":
            found.append(n)
    span, = found
    return span


def test_quantile_over_a_split_namespace_is_exact(dev4, pop, reduces,
                                                  monkeypatch):
    """128 instances over two shards sit ON the edge of the exact path:
    the members of the two partials sum to ``exact_members``.  One fewer
    allowed, or one more asked for, and the answer is a t-digest sketch;
    the span ``quantile.present`` says which path served."""
    pi = next(i for i, p in enumerate(TRAFFIC["panels"])
              if p["name"] == "quantile")
    panel = TRAFFIC["panels"][pi]
    ns = namespace_with_reset(pop)
    _got, stats = ask(dev4, pop, pi, ns, stats=True)
    (partials, res), = reduces
    assert len(partials) == 2
    assert sum(p.state["members"].shape[1] for p in partials) == pop.per_ns
    assert "members" in res.state and "td_means" not in res.state
    steps = str(panel["steps"])
    assert present_span(dev4, stats)["tags"] == {
        "path": "exact", "groups": "1", "members": str(pop.per_ns),
        "steps": steps}
    # a group of 129: the namespace and one instance of its neighbour
    del reduces[:]
    other = (ns + 1) % SPEC["namespaces"]
    wanted = list(compare.selection(pop, panel, ns)) \
        + [compare.selection(pop, panel, other)[0]]
    query = 'quantile(0.75, %s{_ws_="%s",_ns_=~"%s|%s",instance=~"%s"})' % (
        SPEC["metric"], SPEC["workspace"], pop.ns_name(ns),
        pop.ns_name(other), "|".join(pop.instance_name(s) for s in wanted))
    start, end, step, _n = traffic.panel_range(panel, SPEC)
    with urllib.request.urlopen(
            f"http://127.0.0.1:{dev4.http.port}/promql/{DEV4['dataset']}"
            "/api/v1/query_range?" + urllib.parse.urlencode(
                {"query": query, "start": start / 1000, "end": end / 1000,
                 "step": f"{step}ms", "stats": "true"}), timeout=120) as r:
        got, stats = compare.parse_matrix(r.read(), panel, SPEC)
    (_partials, res), = reduces
    assert "td_means" in res.state
    assert present_span(dev4, stats)["tags"] == {
        "path": "sketch", "groups": "1", "members": str(pop.per_ns + 1),
        "steps": steps}
    want = np.quantile(np.stack([oracle.range_fn(
        "last", pop.ts[s], pop.vals[s], start, end, step,
        panel["reference"]["window_ms"]) for s in wanted]), 0.75, axis=0)
    assert compare.gap(got, {"": want})["rel_err"] < 0.05  # a sketch's
    # the namespace alone, one member short of the budget: a sketch too
    del reduces[:]
    monkeypatch.setattr(QuantileAggregator, "exact_members", pop.per_ns - 1)
    _got, stats = ask(dev4, pop, pi, ns, stats=True)
    (_partials, res), = reduces
    assert "td_means" in res.state
    assert present_span(dev4, stats)["tags"]["path"] == "sketch"


@pytest.mark.parametrize("q", ["-0.1", "1.5"])
def test_quantile_outside_the_unit_interval_is_bad_data(dev4, pop, q):
    """What the served path has always answered, from ``np.nanquantile``'s
    own refusal: 400 ``bad_data`` in NumPy's words, not +-Inf."""
    panel = next(p for p in TRAFFIC["panels"] if p["name"] == "quantile")
    req = traffic.request_for(
        dict(panel, query=panel["query"].replace("0.75", q)), 0,
        namespace_with_reset(pop), SPEC, DEV4["dataset"],
        TRAFFIC["timeout_s"], False)
    with pytest.raises(urllib.error.HTTPError) as refused:
        urllib.request.urlopen(
            f"http://127.0.0.1:{dev4.http.port}{req.path}", timeout=120)
    assert refused.value.code == 400
    doc = json.loads(refused.value.read())
    assert doc["errorType"] == "bad_data"
    assert "Quantiles must be in the range [0, 1]" in doc["error"]
