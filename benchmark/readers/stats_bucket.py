"""Mean, in ms, of one ``QueryStats`` stage bucket over the window's answers
(``stats=true``), optionally ``minus`` another bucket it contains."""


def read(run, bucket: str, minus: str = None):
    vals = [r["stats"][bucket] - (r["stats"].get(minus, 0.0) if minus else 0.0)
            for r in run["requests"]
            if r.get("stats") and bucket in r["stats"]]
    if not vals:
        return None
    return 1000.0 * sum(vals) / len(vals)
