"""Mean, in ms, of the client's latency less what the answer's own stage
buckets cover (``minus``): what a request spends outside the query engine —
accept, thread start, GIL wait, ``json.dumps``, the socket."""


def read(run, minus: list):
    vals = [r["latency_s"] - sum(r["stats"].get(b, 0.0) for b in minus)
            for r in run["requests"]
            if r.get("stats") and minus[0] in r["stats"]]
    if not vals:
        return None
    return 1000.0 * sum(vals) / len(vals)
