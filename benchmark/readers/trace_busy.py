"""From the reduced device trace: the device's busy time over the requests
completed in the traced window (``ms_per_query``), or its idle share of the
window (``idle_pct``).  Nothing to read without a device trace."""


def read(run, **kw):
    how = kw["as"]
    tr = run.get("trace")
    if not tr or not tr.get("busy_s"):
        return None
    if how == "idle_pct":
        return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
    if how == "ms_per_query":
        n = run.get("trace_requests", 0)
        return 1000.0 * tr["busy_s"] / n if n else None
    raise ValueError(f"trace_busy: unknown as={how!r}")
