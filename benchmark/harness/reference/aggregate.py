"""The aggregation a panel's per-series values are held to: ``sum`` (all or
``by`` a label), ``quantile`` across series, or ``none`` (per series)."""

from __future__ import annotations

import numpy as np


def sum_parts(ref: dict, per_series: np.ndarray, labels: dict,
              into: dict) -> None:
    """Add one block of series to the running sums ``into``
    ({key: [total, present]}): partial sums of a sum add up, so a wide
    panel is summed block by block."""
    if "by" in ref:
        by = np.asarray(labels[ref["by"]])
        groups = {str(v): by == v for v in np.unique(by)}
    else:
        groups = {"": slice(None)}
    for key, rows in groups.items():
        x = per_series[rows]
        fin = np.isfinite(x)
        tot, seen = into.get(key, (0.0, False))
        into[key] = (tot + np.where(fin, x, 0.0).sum(axis=0),
                     seen | fin.any(axis=0))


def sum_done(into: dict) -> dict:
    """NaN where every series is absent (PromQL aggregates over the series
    present at a step)."""
    return {k: np.where(seen, tot, np.nan) for k, (tot, seen) in into.items()}


def aggregate(ref: dict, per_series: np.ndarray, labels: dict) -> dict:
    """``per_series`` [S, T] and ``labels`` {name: [S] of str} -> {key: [T]}.
    The key is '' for one output series, else the value of the label the
    answer's series are told apart by."""
    how = ref["aggregate"]
    if how == "sum":
        into: dict = {}
        sum_parts(ref, per_series, labels, into)
        return sum_done(into)
    if how == "quantile":
        with np.errstate(invalid="ignore"):
            return {"": np.nanquantile(per_series, ref["q"], axis=0)}
    if how == "none":
        key = np.asarray(labels[ref["key"]])
        return {str(k): per_series[i] for i, k in enumerate(key)}
    raise ValueError(f"unknown aggregate {how!r}")
