"""What a mix whose panels slide stands on, piece by piece and without a
server: a sliding panel ends on its grid, within its reach of the newest
row, and names its answer by that end; its ends are dealt from a deck, the
same for every seed; the reference at a slid end reads nothing past it and
is the same whether reckoned end by end or once over the union of the
ends; the stale control bites at the request's own end; a sliding panel
that is not whole, or whose ranges leave the loaded rows, is refused."""

import collections
import itertools
import json

import numpy as np
import pytest
from conftest import BENCH
from harness import compare, traffic
from harness.population import Population

import run

SPEC = dict(json.loads((BENCH / "configs" / "jmh-inmem-1shard.json")
                       .read_text())["population"], namespaces=3)
MIX = traffic.load(BENCH / "traffic" / "jmh-queries.json")
NEWEST = traffic.newest_ms(SPEC)
EDGE = 15_000


def sliding(name="sum_rate", within_ms=60_000, draw="uniform", **more):
    """A panel of ``jmh-queries`` moved back by up to ``within_ms``."""
    panel = next(p for p in MIX["panels"] if p["name"] == name)
    return dict(panel, end="slide", edge_ms=EDGE,
                slide=dict({"within_ms": within_ms, "draw": draw}, **more))


def mix_of(*panels) -> dict:
    return dict(MIX, panels=list(panels), cycle=len(panels))


def panel_requests(mix, seed, pi, n, index=0):
    """The first ``n`` requests of panel ``pi`` in a session."""
    seq = traffic.session(mix, seed, index, SPEC, "prom", 30, False)
    return list(itertools.islice((r for r in seq if r.panel == pi), n))


@pytest.mark.parametrize("draw", [{"draw": "uniform"},
                                  {"draw": "zipf", "s": 1.1}],
                         ids=["uniform", "zipf"])
def test_a_sliding_panel_ends_on_its_grid_within_its_reach(draw):
    panel = sliding(within_ms=300_000, **draw)
    reqs = panel_requests(mix_of(panel), 3, 0, 200)
    ends = {r.end_ms for r in reqs}
    assert NEWEST in ends                                    # k = 0
    assert all(NEWEST - 300_000 <= e <= NEWEST for e in ends)
    assert all((NEWEST - e) % EDGE == 0 for e in ends)
    assert len(ends) == 21 if draw["draw"] == "uniform" else len(ends) > 3


def test_key_and_path_carry_the_slid_end():
    panel = sliding()
    for back in range(5):
        end = NEWEST - back * EDGE
        req = traffic.request_for(panel, 1, 2, SPEC, "prom", 30, False,
                                  None, back)
        assert req.end_ms == end and req.key == f"1:2:{end}"
        assert f"end={end / 1000}" in req.path
        start = end - 22 * 150_000
        assert f"start={start / 1000}" in req.path
        assert traffic.panel_range(panel, SPEC, req.end_ms)[:2] \
            == (start, end)
    # a watermark moves a sliding panel nowhere: its end is its own draw
    assert traffic.end_for(panel, SPEC, NEWEST + 44_000, 2) \
        == NEWEST - 2 * EDGE


def test_set_up_sends_a_sliding_panel_at_the_newest_end():
    mix = mix_of(sliding(), sliding("raw", 120_000, "zipf", s=1.1))
    warm = traffic.warm_requests(mix, [], 7, SPEC, "prom", 600, False)
    bursts = traffic.burst_requests(mix, 7, SPEC, "prom", 600, False,
                                    (8, 4, 2))
    sent = [r for _n, r in warm] + [r for b in bursts for r in b]
    assert sent and {r.end_ms for r in sent} == {NEWEST}


@pytest.mark.parametrize("draw,want", [
    ({"draw": "uniform"}, list(range(9))),
    ({"draw": "zipf", "s": 1.1}, traffic.zipf_deck(9, 1.1))],
    ids=["uniform", "zipf"])
def test_one_deck_of_ends_is_the_draws_multiset(draw, want):
    panel = sliding(within_ms=8 * EDGE, **draw)
    assert sorted(traffic.slide_deck(panel)) == sorted(want)
    deck = len(want)
    for seed in (1, 2 ** 31 + 5):
        reqs = panel_requests(mix_of(panel), seed, 0, 3 * deck)
        for c in range(3):              # every deck a session deals whole
            got = [(NEWEST - r.end_ms) // EDGE
                   for r in reqs[c * deck:(c + 1) * deck]]
            assert sorted(got) == sorted(want)
    if draw["draw"] == "zipf":          # rank 0 is the newest end, the most
        counts = collections.Counter(want)
        assert counts[0] == max(counts.values()) > counts[8]


def test_two_seeds_deal_the_same_work_in_another_order():
    """Over a whole deck of namespaces and of ends the multisets are the
    seed's to order, not to choose; a panel with no draw over namespaces is
    dealt whole as (namespace, end)."""
    ns_panel = sliding(within_ms=4 * EDGE)                  # 3 x 5 ends
    wide = dict(sliding("sum_rate", 4 * EDGE), name="wide", select=None)
    mix = mix_of(ns_panel, wide)
    runs = {}
    for seed in (11, 2 ** 31 + 11):
        ns_reqs = panel_requests(mix, seed, 0, 15)
        wide_reqs = panel_requests(mix, seed, 1, 5)
        runs[seed] = ([(r.namespace, r.end_ms) for r in ns_reqs],
                      [(r.namespace, r.end_ms) for r in wide_reqs])
    (a_ns, a_wide), (b_ns, b_wide) = runs.values()
    for one in (0, 1):                  # namespaces, then ends
        assert sorted(x[one] for x in a_ns) == sorted(x[one] for x in b_ns)
    assert sorted(a_wide) == sorted(b_wide)
    assert {e for _n, e in a_wide} == {NEWEST - k * EDGE for k in range(5)}
    assert a_ns != b_ns and a_wide != b_wide


@pytest.mark.parametrize("panel", MIX["panels"], ids=lambda p: p["name"])
@pytest.mark.parametrize("back", [1, 7, 40])
def test_the_reference_at_a_slid_end_reads_nothing_past_it(panel, back):
    """Ending ``back`` edges before the newest row it is, bit for bit, the
    reference over a population whose rows stop there; and not the newest
    end's answer."""
    pop = Population(SPEC, 11)
    end = pop.end_ms - back * EDGE
    rows = (end - pop.base_ms) // pop.scrape_ms
    cut = Population(dict(SPEC, rows=rows), 11)
    cut.ts, cut.vals = pop.ts[:, :rows], pop.vals[:, :rows]
    ns = int(pop.ns[pop.reset_series[0]])
    got = compare.reference_answer(pop, panel, ns, end_ms=end)
    want = compare.reference_answer(cut, panel, ns)
    assert got.keys() == want.keys()
    for k in want:
        assert np.array_equal(got[k], want[k], equal_nan=True)
    newest = compare.reference_answer(pop, panel, ns)
    assert compare.gap(got, newest)["rel_err"] > 0


@pytest.mark.parametrize("panel", [
    *MIX["panels"],
    dict(MIX["panels"][1], name="wide", select=None),
    dict(MIX["panels"][1], name="by_g", select=None,
         reference={"fn": "rate", "window_ms": 300000, "aggregate": "sum",
                    "by": "g"})], ids=lambda p: p["name"])
def test_the_reference_over_the_union_of_ends_is_the_same(panel):
    """What the judge computes once a panel and namespace, sliced by end,
    is what it would compute end by end: the numbers compared stay."""
    pop = Population(dict(SPEC, namespaces=160), 3)
    ends = [pop.end_ms - k * EDGE for k in (0, 1, 2, 7, 10, 33, 60)] \
        + [None]
    by_end = compare.reference_by_end(pop, panel, 5, ends)
    assert set(by_end) == set(ends)
    for e in ends:
        want = compare.reference_answer(pop, panel, 5, end_ms=e)
        assert by_end[e].keys() == want.keys()
        for k in want:
            assert np.array_equal(by_end[e][k], want[k], equal_nan=True)


def test_the_stale_control_bites_at_the_requests_own_slid_end():
    pop = Population(SPEC, 11)
    raw = next(p for p in MIX["panels"] if p["name"] == "raw")
    for end in (pop.end_ms - 3 * EDGE, pop.end_ms - 17 * EDGE):
        want = compare.reference_answer(pop, raw, 1, end_ms=end)
        got = compare.control_answers(pop, "one_scrape_stale", raw, 1, end)
        g = compare.gap(got, want)
        assert g["rel_err"] > 0 and g["series_off"] == 0
        # only the newest step is off: the row before it is still there
        assert all(np.array_equal(got[k][:-1], want[k][:-1]) for k in want)


@pytest.mark.parametrize("bad", [
    {"edge_ms": 0},
    {"edge_ms": 15000.0},
    {"slide": None},
    {"slide": {"within_ms": 20000, "draw": "uniform"}},
    {"slide": {"within_ms": 0, "draw": "uniform"}},
    {"slide": {"within_ms": 60000.5, "draw": "uniform"}},
    {"slide": {"within_ms": 60000, "draw": "normal"}},
    {"slide": {"within_ms": 60000, "draw": "zipf"}}])
def test_a_sliding_panel_that_is_not_whole_is_refused(bad, tmp_path):
    doc = json.loads((BENCH / "traffic" / "jmh-queries.json").read_text())
    doc["panels"][0].update({"end": "slide", "edge_ms": EDGE, "slide": {
        "within_ms": 60_000, "draw": "uniform"}}, **bad)
    (tmp_path / "t.json").write_text(json.dumps(doc))
    with pytest.raises(ValueError, match="end is"):
        traffic.load(tmp_path / "t.json")


def test_a_slide_past_the_loaded_rows_is_refused():
    """The rows begin at ``base_ms``: 255 of 15 s are 3 825 s, and a panel
    of 23 steps of 150 s over 5 min windows reads 3 600 s back from its
    end; it may slide 225 s, not 240."""
    def ctx(within_ms):
        return {"config": {"population": SPEC},
                "traffic": mix_of(MIX["panels"][0],
                                  sliding(within_ms=within_ms))}
    assert traffic.earliest_ms(sliding(within_ms=225_000), SPEC) \
        == SPEC["base_ms"]
    run.ranges_or_fail(ctx(225_000))
    with pytest.raises(run.Failed, match="before the first loaded row"):
        run.ranges_or_fail(ctx(240_000))
    # the accepted mixes read inside the loaded rows
    for name in ("jmh-queries", "jmh-queries-live", "hicard-wide"):
        mix = traffic.load(BENCH / "traffic" / f"{name}.json")
        run.ranges_or_fail({"config": {"population": SPEC},
                            "traffic": mix})
