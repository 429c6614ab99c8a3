"""The least bytes a request has to move through HBM, whatever program serves
it: the function the roofline share's numerator comes from.

For each request: (lanes its selector matches) x (rows its windows cover) x
(bytes a resident cell costs, read from the HBM ledger at set-up and never
more than an f32 cell) + its output.  A lower bound on purpose: it can
under-read and never passes what any correct program moves, so a program that
computes 102 400 lanes to return 128 shows as a fraction of a percent.
"""

from __future__ import annotations

F32 = 4


def rows_covered(panel: dict, scrape_ms: int) -> int:
    steps, step = panel["steps"], panel["step_ms"]
    window = panel["reference"]["window_ms"]
    span = min((steps - 1) * step + window, steps * window)
    return span // scrape_ms


def lanes_selected(panel: dict, pop_spec: dict) -> int:
    sel = panel.get("select")
    if not sel:
        return pop_spec["namespaces"] * pop_spec["per_namespace"]
    return int(sel.get("instances", 0)) or pop_spec["per_namespace"]


def outputs(panel: dict, pop_spec: dict) -> int:
    ref = panel["reference"]
    if ref["aggregate"] == "none":
        return lanes_selected(panel, pop_spec)
    return pop_spec["groups"] if "by" in ref else 1


def request_bytes(panel: dict, pop_spec: dict,
                  resident_bytes_per_sample: float) -> float:
    cell = min(resident_bytes_per_sample, F32)
    return (lanes_selected(panel, pop_spec)
            * rows_covered(panel, pop_spec["scrape_ms"]) * cell
            + outputs(panel, pop_spec) * panel["steps"] * F32)
