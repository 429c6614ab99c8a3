"""The least time the chips could take for the traced requests (their least
bytes, ``harness/least_bytes.py``: the bytes of ALL chips, whatever program
serves) over the peak of ``harness/peaks.json`` times the chips that were
traced, as a share of the busy time a chip (the mean over the device planes)
in the traced window.  Nothing to read without a device trace; never 0 and
never clamped."""


def read(run, peak: str):
    tr = run.get("trace")
    if not tr or not tr.get("busy_s") or not run.get("trace_least_bytes"):
        return None
    least_s = run["trace_least_bytes"] / (run["peaks"][peak] * tr["devices"])
    return 100.0 * least_s / tr["busy_s"]
