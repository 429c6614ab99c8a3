"""The change of the program's stage table (``stages`` of ``/admin/device``:
``{count, wall_s, cpu_s}`` for each stage span's name, totals since the
process started) between the document read when the window opens and the one
read when it has closed.  ``field`` summed over ``spans``, times ``scale``,
over the change of ``per``'s ``count`` where ``per`` is given (a mean a span);
``since="start"`` reads the closing document alone (set-up's stages).  None
where a document has no ``stages`` block, or ``per`` did not run: a program
without the stage clock is left out, not read as zero."""


def read(run, spans: list, field: str, per: str = None, since: str = "window",
         scale: float = 1.0):
    if since not in ("window", "start"):
        raise ValueError(f"stage_delta: unknown since={since!r}")
    after = (run.get("device_after") or {}).get("stages")
    before = {} if since == "start" \
        else (run.get("device_before") or {}).get("stages")
    if after is None or before is None:
        return None

    def change(name: str, what: str) -> float:
        return after.get(name, {}).get(what, 0.0) \
            - before.get(name, {}).get(what, 0.0)
    total = sum(change(name, field) for name in spans)
    if per is None:
        return scale * total
    n = change(per, "count")
    return scale * total / n if n > 0 else None
