"""A program counter's change across the window.  ``compiles``: the compile
table of ``/admin/device`` (every program's ``compiles``, summed), read when
the window opens and when it has closed."""


def read(run, counter: str):
    if counter != "compiles":
        raise ValueError(f"counter_delta: unknown counter {counter!r}")

    def total(doc):
        return sum(p.get("compiles", 0)
                   for p in doc.get("compile", {}).get("programs", []))
    before, after = run.get("device_before"), run.get("device_after")
    if before is None or after is None:
        return None
    return float(total(after) - total(before))
