"""The comparison that decides ``correct``: every answer of the window against
the plain reference (``harness/reference/``), and the controls that have to
come out as not correct.

NumPy only; nothing of the program.  The reference runs after the window has
closed, over blocks of series so that a 102 400-series panel fits.
"""

from __future__ import annotations

import importlib
import json

import numpy as np

from . import traffic as traffic_mod
from .reference import aggregate as agg
from .reference import windows

BLOCK = 16_384          # series a reference block


def ref_fn(name: str):
    """``harness/reference/<name>.py`` — found by the name in the traffic
    file, so a new function is a new file."""
    return importlib.import_module(f"harness.reference.{name}").series


def selection(pop, panel: dict, namespace: int) -> np.ndarray:
    """The series a panel's selector matches."""
    sel = panel.get("select")
    if not sel:
        return np.arange(pop.n)
    first = namespace * pop.per_ns
    k = int(sel.get("instances", 0)) or pop.per_ns
    return np.arange(first, first + k)


def grid_of(panel: dict, pop_spec: dict, end_ms=None) -> np.ndarray:
    """The instants of a panel's steps, ending at ``end_ms``."""
    start, _end, step, steps = traffic_mod.panel_range(panel, pop_spec,
                                                       end_ms)
    return start + np.arange(steps, dtype=np.int64) * step


def reference_answer(pop, panel: dict, namespace: int, vals=None,
                     keep=None, end_ms=None) -> dict:
    """{key: [T]} float64, over ALL the population's rows, loaded and live:
    PromQL at a step reads only samples at or before it, so a panel that
    ends at ``end_ms`` (default: the newest loaded row) is due every sample
    up to there, whatever had arrived.  ``vals`` replaces the population's
    values and ``keep`` masks series out: the controls' way in."""
    return on_grid(pop, panel, namespace, grid_of(panel, pop.spec, end_ms),
                   vals, keep)


def reference_by_end(pop, panel: dict, namespace: int, ends) -> dict:
    """{end_ms: ``reference_answer`` at that end} for many ends of one panel
    and namespace, computed once over the union of their grids and sliced
    by end.  The numbers are the same: a step's cell is reckoned from its
    own instant alone, and an aggregate across series is taken step by
    step."""
    grids = {e: grid_of(panel, pop.spec, e) for e in ends}
    union = np.unique(np.concatenate(list(grids.values())))
    whole = on_grid(pop, panel, namespace, union)
    return {e: {k: v[np.searchsorted(union, g)] for k, v in whole.items()}
            for e, g in grids.items()}


def on_grid(pop, panel: dict, namespace: int, grid: np.ndarray, vals=None,
            keep=None) -> dict:
    """The reference at the instants ``grid``."""
    ref = panel["reference"]
    sel = selection(pop, panel, namespace)
    if keep is not None:
        sel = sel[keep[sel]]
    vals = pop.vals if vals is None else vals
    fn = ref_fn(ref["fn"])
    labels = {"g": np.array([f"g{g:02d}" for g in pop.g[sel]]),
              "instance": np.array([pop.instance_name(s) for s in sel])} \
        if (ref["aggregate"] == "none" or "by" in ref) else {}
    if ref["aggregate"] == "sum":
        into: dict = {}
        for a in range(0, len(sel), BLOCK):
            blk = sel[a:a + BLOCK]
            per = fn(pop.ts[blk], vals[blk], grid, ref["window_ms"],
                     pop.scrape_ms)
            agg.sum_parts(ref, per, {k: v[a:a + BLOCK]
                                     for k, v in labels.items()}, into)
        return agg.sum_done(into)
    per = fn(pop.ts[sel], vals[sel], grid, ref["window_ms"], pop.scrape_ms)
    return agg.aggregate(ref, per, labels)


def parse_matrix(body: bytes, panel: dict, pop_spec: dict,
                 end_ms=None) -> tuple:
    """A Prometheus matrix body -> ({key: [T] float, NaN where absent},
    stats or None), on the grid of a panel that ends at ``end_ms``.  Raises
    ValueError on anything else."""
    doc = json.loads(body)
    if doc.get("status") != "success":
        raise ValueError(f"status {doc.get('status')}: "
                         f"{str(doc.get('error'))[:200]}")
    if doc.get("warnings"):
        raise ValueError(f"warnings: {doc['warnings']}")
    data = doc["data"]
    if data.get("resultType") != "matrix":
        raise ValueError(f"resultType {data.get('resultType')}")
    start, _end, step, steps = traffic_mod.panel_range(panel, pop_spec,
                                                       end_ms)
    ref = panel["reference"]
    label = ref.get("by") or ref.get("key")
    out = {}
    for row in data["result"]:
        v = np.full(steps, np.nan)
        tv = np.array(row["values"], dtype=np.float64).reshape(-1, 2)
        at = np.rint((tv[:, 0] * 1000 - start) / step).astype(np.int64)
        if ((at < 0) | (at >= steps)).any():
            raise ValueError("a sample outside the asked range")
        v[at] = tv[:, 1]
        key = row["metric"].get(label, "") if label else ""
        if key in out:
            raise ValueError(f"two series with {label}={key!r}")
        out[key] = v
    return out, data.get("stats")


def gap(got: dict, want: dict) -> dict:
    """The numbers of one answer against its reference."""
    keys_off = len(set(got) ^ set(want))
    worst, absent = 0.0, 0
    for k in set(got) & set(want):
        g, w = got[k], want[k]
        absent += int((np.isfinite(g) != np.isfinite(w)).sum())
        both = np.isfinite(g) & np.isfinite(w)
        if both.any():
            err = np.abs(g[both] - w[both]) / np.maximum(np.abs(w[both]),
                                                         1e-300)
            worst = max(worst, float(err.max()))
    return {"rel_err": worst, "absent_cells": absent, "series_off": keys_off}


def worst_of(gaps) -> dict:
    out = {"rel_err": 0.0, "absent_cells": 0, "series_off": 0}
    for g in gaps:
        out["rel_err"] = max(out["rel_err"], g["rel_err"])
        out["absent_cells"] += g["absent_cells"]
        out["series_off"] += g["series_off"]
    return out


# ---------------------------------------------------------------- controls

def _bf16(vals: np.ndarray) -> np.ndarray:
    import ml_dtypes
    return vals.astype(ml_dtypes.bfloat16).astype(np.float64)


def _stale(pop, end_ms: int) -> np.ndarray:
    """Every series' newest row at or before ``end_ms`` reads as the row
    before it: one scrape not yet visible at the request's end."""
    vals = pop.vals.copy()
    newest = windows.rows_upto(pop.ts, np.array([end_ms]),
                               pop.scrape_ms)[:, 0] - 1
    s = np.flatnonzero(newest >= 1)
    vals[s, newest[s]] = vals[s, newest[s] - 1]
    return vals


def control_answers(pop, name: str, panel: dict, namespace: int,
                    end_ms=None) -> dict:
    """The reference put in the program's place and degraded:

    ``bfloat16``    the value planes kept in the precision below float32
    ``one_series_lost``  the selection's last series left out (a partial
                    answer given as a whole one)
    ``one_scrape_stale`` every series' newest row at or before the request's
                    end not yet visible
    """
    if name == "one_series_lost":
        keep = np.ones(pop.n, bool)
        keep[selection(pop, panel, namespace)[-1]] = False
        return reference_answer(pop, panel, namespace, keep=keep,
                                end_ms=end_ms)
    if name not in CONTROLS:
        raise ValueError(f"unknown control {name!r}")
    # the whole population, made once (the stale one: once an end, the
    # newest end kept)
    made = pop.__dict__.setdefault("_control_vals", {})
    if name == "bfloat16":
        if name not in made:
            made[name] = _bf16(pop.vals)
        vals = made[name]
    else:
        end = pop.end_ms if end_ms is None else int(end_ms)
        if made.get(name, (None,))[0] != end:
            made[name] = (end, _stale(pop, end))
        vals = made[name][1]
    return reference_answer(pop, panel, namespace, vals=vals, end_ms=end_ms)


CONTROLS = ("bfloat16", "one_series_lost", "one_scrape_stale")
