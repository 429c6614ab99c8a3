"""The one general traffic generator: a traffic file (``traffic/<name>.json``)
plus ``--seed`` gives every session's endless sequence of requests.

Standard library only, so the load-generator child (which must never touch
JAX or the program) and the parent (which warms the shapes and computes the
reference) draw the SAME sequence from the same file and seed.

A traffic file holds ``loop`` (``closed``), ``sessions``, ``think_ms``,
``timeout_s`` (what the client sends with every request), ``cycle`` and
``panels``.  A panel is a PromQL template with its ``weight`` (how many of the
``cycle`` requests are this panel), its range (``steps`` x ``step_ms`` ending
at one of three ends, below), an optional ``select`` draw, the ``reference``
it is held to and the ``limits`` of that comparison.  Every seed gets the
same work in another order: a session plays shuffles of one cycle that holds
each panel ``weight`` times, and a draw over the namespaces plays shuffles of
one fixed deck of ranks (``uniform``: every namespace once; ``zipf``: the
inverse CDF at evenly spaced quantiles), with the rank -> namespace map
permuted by the seed.

A panel's ``end`` is one of:

``newest``  the newest loaded bucket edge, the same for every request;
``now``     the newest row a writer has made visible, floored to the
            panel's ``edge_ms`` (a mix with a ``writer``, below);
``slide``   ``newest - k * edge_ms``, ``k`` in ``[0, within_ms // edge_ms]``
            by ``"slide": {"within_ms": W, "draw": "uniform" | "zipf",
            "s": <zipf only>}``: a dashboard whose time range was moved
            back.  ``k`` is dealt as the namespaces are: a session keeps a
            deck of ``k`` a sliding panel (``deck_of``'s draws over the
            ``W // edge_ms + 1`` positions), shuffled by its own generator
            and drawn apart from the namespace deck, so a request is
            (namespace, end).  Rank 0 is the newest end and the rank -> ``k``
            map is the same for every seed: recency is what a Zipf draw
            over ends models.  A panel that does not slide draws nothing
            for its end.

Set-up warms shapes, not ends: ``warm_requests`` and ``burst_requests`` send
a sliding panel at ``k = 0``, as they send every panel at one end.
``setup_s`` is what a restarted node needs before it answers; the ends are
the traffic that node then meets, and a program that compiles once an end
pays for it in the window, where ``compiles_in_window`` shows it.

An optional ``writer`` block makes the mix one of queries AND writes:
``{"edge": "containers", "batch_ms": B, "visible_after_ms": V}``.  The stream
is the population's own ``live_rows`` in real time: the synthetic clock stands
at the newest loaded bucket edge when the writer first starts and runs one to
one with the wall clock; batch ``k`` holds every sample stamped in
``(edge + k B, edge + (k + 1) B]`` and is due when the clock reaches its end.
A sample counts as visible ``V`` after its batch's last 200
(``loadgen.Writer``).
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
        ends_at(p)
    w = t.get("writer")
    if w is not None and (w.get("edge") != "containers"
                          or int(w["batch_ms"]) < 1
                          or int(w["visible_after_ms"]) < 0):
        raise ValueError(f"{path}: a writer posts to the container edge, "
                         f"every batch_ms >= 1, visible_after_ms >= 0")
    return t


def ends_at(panel: dict) -> str:
    """``newest``, ``now`` or ``slide``; a panel that ends at ``now`` or
    slides says on which grid (``edge_ms``) its ends fall, one that slides
    how far back (``slide.within_ms``, a whole multiple of it) and by which
    draw."""
    end = panel.get("end", "newest")
    edge = panel.get("edge_ms", 0)
    if end not in ("newest", "now", "slide") \
            or (end != "newest" and not (isinstance(edge, int) and edge > 0)):
        raise ValueError(f"panel {panel.get('name')!r}: end is 'newest', or "
                         f"'now' or 'slide' with an edge_ms")
    if end == "slide":
        sl = panel.get("slide") or {}
        within = sl.get("within_ms")
        if not (isinstance(within, int) and within > 0
                and within % edge == 0) \
                or sl.get("draw") not in ("uniform", "zipf") \
                or (sl["draw"] == "zipf" and not float(sl.get("s", 0)) > 0):
            raise ValueError(f"panel {panel.get('name')!r}: end is 'slide' "
                             f"with slide.within_ms a positive whole multiple "
                             f"of edge_ms and a draw 'uniform' or 'zipf' "
                             f"(with its s)")
    return end


def newest_ms(pop_spec: dict) -> int:
    """The newest loaded bucket edge (``Population.end_ms``)."""
    return pop_spec["base_ms"] + pop_spec["rows"] * pop_spec["scrape_ms"]


def end_for(panel: dict, pop_spec: dict, visible_ms=None, back: int = 0):
    """The ``end`` a request of ``panel`` carries: None where it ends at
    the newest loaded row; where it ends at ``now``, the visible watermark
    (``visible_ms``; the loaded edge before a writer has started) floored to
    the panel's ``edge_ms`` past that edge; where it slides, ``back`` edges
    before the newest row."""
    end = ends_at(panel)
    if end == "newest":
        return None
    newest, edge = newest_ms(pop_spec), panel["edge_ms"]
    if end == "slide":
        return newest - back * edge
    if visible_ms is None:
        return newest
    return newest + max(0, int(visible_ms) - newest) // edge * edge


def earliest_ms(panel: dict, pop_spec: dict) -> int:
    """The oldest instant any answer of ``panel`` reads: the start of its
    earliest range less its reference's window."""
    back = panel["slide"]["within_ms"] if ends_at(panel) == "slide" else 0
    start = panel_range(panel, pop_spec, newest_ms(pop_spec) - back)[0]
    return start - int(panel["reference"]["window_ms"])


def deck_of(sel: dict, n: int) -> list:
    """One deck of ranks in [0, n) for a panel's ``select``."""
    if sel.get("over") != "namespaces":
        raise ValueError(f"unknown select {sel}")
    return ranks(sel, n)


def slide_deck(panel: dict) -> list:
    """One deck of ``k`` for a panel that slides: ``deck_of``'s draws over
    its ``within_ms // edge_ms + 1`` ends, rank 0 the newest."""
    sl = panel["slide"]
    return ranks(sl, sl["within_ms"] // panel["edge_ms"] + 1)


def ranks(draw: dict, n: int) -> list:
    """One deck of ranks in [0, n) by its ``draw``: ``uniform`` (each rank
    once) or ``zipf`` (``zipf_deck`` with its ``s``)."""
    if draw["draw"] == "uniform":
        return list(range(n))
    if draw["draw"] == "zipf":
        return zipf_deck(n, float(draw["s"]))
    raise ValueError(f"unknown draw {draw}")


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
    __slots__ = ("panel", "namespace", "path", "end_ms")

    def __init__(self, panel: int, namespace: int, path: str, end_ms=None):
        self.panel, self.namespace, self.path = panel, namespace, path
        self.end_ms = end_ms      # None: the panel ends at the newest row

    @property
    def key(self) -> str:
        """Names the distinct answer: requests with one key are due the
        same body."""
        if self.end_ms is None:
            return f"{self.panel}:{self.namespace}"
        return f"{self.panel}:{self.namespace}:{self.end_ms}"


def panel_range(panel: dict, pop_spec: dict, end_ms=None) -> tuple:
    """(start_ms, end_ms, step_ms, steps) of a panel over the population,
    ending at ``end_ms`` (default: the newest loaded row)."""
    end = newest_ms(pop_spec) if end_ms is None else int(end_ms)
    step, steps = panel["step_ms"], panel["steps"]
    return end - (steps - 1) * step, end, step, steps


def request_for(panel: dict, pi: int, namespace: int, pop_spec: dict,
                dataset: str, timeout_s: int, stats: bool,
                visible_ms=None, back: int = 0) -> Request:
    per = pop_spec["per_namespace"]
    sel = panel.get("select") or {}
    first = max(namespace, 0) * per
    instances = "|".join(f"{s:07d}" for s in
                         range(first, first + int(sel.get("instances", 0))))
    query = panel["query"].format(
        metric=pop_spec["metric"], workspace=pop_spec["workspace"],
        namespace=f"App-{max(namespace, 0):04d}", instances=instances)
    end_ms = end_for(panel, pop_spec, visible_ms, back)
    start, end, step, _n = panel_range(panel, pop_spec, end_ms)
    args = {"query": query, "start": start / 1000, "end": end / 1000,
            "step": f"{step}ms", "timeout": f"{timeout_s}s"}
    if stats:
        args["stats"] = "true"
    return Request(pi, namespace,
                   f"/promql/{dataset}/api/v1/query_range?"
                   + urllib.parse.urlencode(args), end_ms)


def namespace_map(traffic: dict, seed: int, pop_spec: dict) -> list:
    """Zipf rank -> namespace: one permutation a seed, shared by its sessions
    and by set-up."""
    ns_map = list(range(pop_spec["namespaces"]))
    random.Random(f"{seed}/{traffic['name']}/namespaces").shuffle(ns_map)
    return ns_map


def session(traffic: dict, seed: int, index: int, pop_spec: dict,
            dataset: str, timeout_s: int, stats: bool, visible=None):
    """The endless request sequence of session ``index``.  ``visible()``
    (a writer's watermark, read when a request is drawn, which is when it is
    sent) gives a panel that ends at ``now`` its end; a panel that slides
    draws its ``k`` from a deck of its own."""
    rng = random.Random(f"{seed}/{traffic['name']}/{index}")
    ns_map = namespace_map(traffic, seed, pop_spec)
    cycle = [pi for pi, p in enumerate(traffic["panels"])
             for _ in range(p["weight"])]
    decks, backs = {}, {}
    while True:
        rng.shuffle(cycle)
        for pi in list(cycle):
            panel = traffic["panels"][pi]
            sel = panel.get("select")
            ns = -1
            if sel:
                deck = decks.get(pi)
                if not deck:
                    deck = decks[pi] = deck_of(sel, pop_spec["namespaces"])
                    rng.shuffle(deck)
                ns = ns_map[deck.pop()]
            back = 0
            if panel.get("end") == "slide":
                deck = backs.get(pi)
                if not deck:
                    deck = backs[pi] = slide_deck(panel)
                    rng.shuffle(deck)
                back = deck.pop()
            yield request_for(panel, pi, ns, pop_spec, dataset, timeout_s,
                              stats, visible() if visible else None, back)


def warm_requests(traffic: dict, staging: list, seed: int, pop_spec: dict,
                  dataset: str, timeout_s: int, stats: bool) -> list:
    """One request a distinct panel shape: what set-up issues twice (a
    sliding panel at ``k = 0``).  The configuration's ``staging`` panels come
    first (panel index -1 - i): they bring the device store to the state a
    node is in once it has served for a while, every series of the shard
    staged."""
    ns_map = namespace_map(traffic, seed, pop_spec)
    before = [(-1 - i, p) for i, p in enumerate(staging)]
    return [(p["name"],
             request_for(p, pi, ns_map[0] if p.get("select") else -1,
                         pop_spec, dataset, timeout_s, stats))
            for pi, p in before + list(enumerate(traffic["panels"]))]


def burst_requests(traffic: dict, seed: int, pop_spec: dict, dataset: str,
                   timeout_s: int, stats: bool, sizes) -> list:
    """For each panel and each size, that many requests of the panel (a
    sliding one at ``k = 0``): what set-up sends together.  A panel with a
    ``select`` is sent over different namespaces, then over one (two
    sessions do ask one namespace at once now and then); a panel without a
    draw has ONE request, which sessions playing it send together all the
    time: that many copies of it."""
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
