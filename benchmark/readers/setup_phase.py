"""Seconds of the named set-up phases, or the samples loaded over them."""


def read(run, phases: list, **kw):
    how = kw["as"]
    if any(p not in run["setup_phases"] for p in phases):
        return None
    secs = sum(run["setup_phases"][p] for p in phases)
    if how == "seconds":
        return secs
    if how == "samples_per_second":
        return run["samples"] / secs if secs > 0 else None
    raise ValueError(f"setup_phase: unknown as={how!r}")
