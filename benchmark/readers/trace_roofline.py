"""The least time the chip could take for the traced requests (their least
bytes, ``harness/least_bytes.py``, over the peak of ``harness/peaks.json``)
as a share of the device's busy time in the traced window.  Nothing to read
without a device trace; never 0 and never clamped."""


def read(run, peak: str):
    tr = run.get("trace")
    if not tr or not tr.get("busy_s") or not run.get("trace_least_bytes"):
        return None
    least_s = run["trace_least_bytes"] / run["peaks"][peak]
    return 100.0 * least_s / tr["busy_s"]
