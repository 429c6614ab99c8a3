"""The one general traffic generator: a traffic file (``traffic/<name>.json``)
plus ``--seed`` gives every session's endless sequence of requests.

Standard library only, so the load-generator child (which must never touch
JAX or the program) and the parent (which warms the shapes and computes the
reference) draw the SAME sequence from the same file and seed.

A traffic file holds ``loop`` (``closed``), ``sessions``, ``think_ms``,
``timeout_s`` (what the client sends with every request), ``cycle`` and
``panels``.  A panel is a PromQL template with its ``weight`` (how many of the
``cycle`` requests are this panel), its range (``steps`` x ``step_ms`` ending
at the newest row), an optional ``select`` draw, the ``reference`` it is held
to and the ``limits`` of that comparison.  Every seed gets the same work in
another order: a session plays shuffles of one cycle that holds each panel
``weight`` times, and a draw over the namespaces plays shuffles of one fixed
deck of ranks (``uniform``: every namespace once; ``zipf``: the inverse CDF at
evenly spaced quantiles), with the rank -> namespace map permuted by the seed.
"""

from __future__ import annotations

import json
import random
import urllib.parse

ZIPF_DECK = 240        # draws in one deck of ranks


def load(path) -> dict:
    with open(path) as f:
        t = json.load(f)
    if t.get("loop") != "closed":
        raise ValueError(f"{path}: only closed loops are generated yet")
    if sum(p["weight"] for p in t["panels"]) != t["cycle"]:
        raise ValueError(f"{path}: weights do not add up to the cycle")
    for p in t["panels"]:
        if "rel_err" not in p.get("limits", {}):
            raise ValueError(f"{path}: panel {p.get('name')!r} has no "
                             f"limits.rel_err for the comparison")
    return t


def deck_of(sel: dict, n: int) -> list:
    """One deck of ranks in [0, n) for a panel's ``select``."""
    if sel.get("over") != "namespaces":
        raise ValueError(f"unknown select {sel}")
    if sel["draw"] == "uniform":
        return list(range(n))
    if sel["draw"] == "zipf":
        return zipf_deck(n, float(sel["s"]))
    raise ValueError(f"unknown select {sel}")


def zipf_deck(n: int, s: float, size: int = ZIPF_DECK) -> list:
    """``size`` ranks in [0, n): the Zipf(s) inverse CDF at the quantiles
    (i + 0.5) / size — the same multiset for every seed."""
    w = [1.0 / (k + 1) ** s for k in range(n)]
    total = sum(w)
    cdf, acc = [], 0.0
    for x in w:
        acc += x / total
        cdf.append(acc)
    deck, k = [], 0
    for i in range(size):
        q = (i + 0.5) / size
        while k < n - 1 and cdf[k] < q:
            k += 1
        deck.append(k)
    return deck


class Request:
    __slots__ = ("panel", "namespace", "path")

    def __init__(self, panel: int, namespace: int, path: str):
        self.panel, self.namespace, self.path = panel, namespace, path

    @property
    def key(self) -> str:
        """Names the distinct answer: requests with one key are due the
        same body."""
        return f"{self.panel}:{self.namespace}"


def panel_range(panel: dict, pop_spec: dict) -> tuple:
    """(start_ms, end_ms, step_ms, steps) of a panel over the population."""
    if panel.get("end", "newest") != "newest":
        raise ValueError("a panel ends at the newest row")
    end = pop_spec["base_ms"] + pop_spec["rows"] * pop_spec["scrape_ms"]
    step, steps = panel["step_ms"], panel["steps"]
    return end - (steps - 1) * step, end, step, steps


def request_for(panel: dict, pi: int, namespace: int, pop_spec: dict,
                dataset: str, timeout_s: int, stats: bool) -> Request:
    per = pop_spec["per_namespace"]
    sel = panel.get("select") or {}
    first = max(namespace, 0) * per
    instances = "|".join(f"{s:07d}" for s in
                         range(first, first + int(sel.get("instances", 0))))
    query = panel["query"].format(
        metric=pop_spec["metric"], workspace=pop_spec["workspace"],
        namespace=f"App-{max(namespace, 0):04d}", instances=instances)
    start, end, step, _n = panel_range(panel, pop_spec)
    args = {"query": query, "start": start / 1000, "end": end / 1000,
            "step": f"{step}ms", "timeout": f"{timeout_s}s"}
    if stats:
        args["stats"] = "true"
    return Request(pi, namespace,
                   f"/promql/{dataset}/api/v1/query_range?"
                   + urllib.parse.urlencode(args))


def namespace_map(traffic: dict, seed: int, pop_spec: dict) -> list:
    """Zipf rank -> namespace: one permutation a seed, shared by its sessions
    and by set-up."""
    ns_map = list(range(pop_spec["namespaces"]))
    random.Random(f"{seed}/{traffic['name']}/namespaces").shuffle(ns_map)
    return ns_map


def session(traffic: dict, seed: int, index: int, pop_spec: dict,
            dataset: str, timeout_s: int, stats: bool):
    """The endless request sequence of session ``index``."""
    rng = random.Random(f"{seed}/{traffic['name']}/{index}")
    ns_map = namespace_map(traffic, seed, pop_spec)
    cycle = [pi for pi, p in enumerate(traffic["panels"])
             for _ in range(p["weight"])]
    decks = {}
    while True:
        rng.shuffle(cycle)
        for pi in list(cycle):
            sel = traffic["panels"][pi].get("select")
            ns = -1
            if sel:
                deck = decks.get(pi)
                if not deck:
                    deck = decks[pi] = deck_of(sel, pop_spec["namespaces"])
                    rng.shuffle(deck)
                ns = ns_map[deck.pop()]
            yield request_for(traffic["panels"][pi], pi, ns, pop_spec,
                              dataset, timeout_s, stats)


def warm_requests(traffic: dict, staging: list, seed: int, pop_spec: dict,
                  dataset: str, timeout_s: int, stats: bool) -> list:
    """One request a distinct panel shape: what set-up issues twice.  The
    configuration's ``staging`` panels come first (panel index -1 - i): they
    bring the device store to the state a node is in once it has served for
    a while, every series of the shard staged."""
    ns_map = namespace_map(traffic, seed, pop_spec)
    before = [(-1 - i, p) for i, p in enumerate(staging)]
    return [(p["name"],
             request_for(p, pi, ns_map[0] if p.get("select") else -1,
                         pop_spec, dataset, timeout_s, stats))
            for pi, p in before + list(enumerate(traffic["panels"]))]


def burst_requests(traffic: dict, seed: int, pop_spec: dict, dataset: str,
                   timeout_s: int, stats: bool, sizes) -> list:
    """For each panel and each size, that many requests of the panel: what
    set-up sends together.  A panel with a ``select`` is sent over different
    namespaces, then over one (two sessions do ask one namespace at once now
    and then); a panel without a draw has ONE request, which sessions playing
    it send together all the time: that many copies of it."""
    ns_map = namespace_map(traffic, seed, pop_spec)

    def bursts_of(panel: dict, k: int) -> list:
        if not panel.get("select"):
            return [[-1] * k]
        return [[ns_map[i * spread % len(ns_map)] for i in range(k)]
                for spread in (1, 0)]
    return [[request_for(p, pi, ns, pop_spec, dataset, timeout_s, stats)
             for ns in burst]
            for pi, p in enumerate(traffic["panels"])
            for k in sizes for burst in bursts_of(p, k)]
