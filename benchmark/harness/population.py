"""The generated samples of one run: made from ``--seed``, loaded into the
server, and kept on the host for the plain reference.

The shape is the configuration's ``population`` block: ``namespaces`` x
``per_namespace`` counter series, ``rows`` samples each at ``scrape_ms``,
every series with its own fixed scrape phase inside the interval,
``reset_share`` of them restarting once.

Every seed loads the same set of series in another order: the value rows are
drawn once from the configuration's ``value_seed``, and ``--seed`` deals them
out to the series (and draws the scrape phases).  How well a series
compresses is a property of its values, so the bytes a run holds in HBM do
not depend on the seed, and ``resident_bytes_per_sample`` can carry a bound.

NumPy only: nothing of the program is imported here.
"""

from __future__ import annotations

import numpy as np


class Population:
    def __init__(self, spec: dict, seed: int):
        rng = np.random.default_rng(seed)
        fixed = np.random.default_rng(int(spec["value_seed"]))
        self.spec = spec
        self.per_ns = int(spec["per_namespace"])
        self.namespaces = int(spec["namespaces"])
        self.rows = rows = int(spec["rows"])
        self.scrape_ms = step = int(spec["scrape_ms"])
        self.base_ms = int(spec["base_ms"])
        self.groups = int(spec["groups"])
        self.metric = spec["metric"]
        self.workspace = spec["workspace"]
        n = self.n = self.namespaces * self.per_ns
        sid = np.arange(n)
        self.ns = sid // self.per_ns
        self.g = sid % self.groups
        # each target keeps its own scrape offset inside the interval
        self.phase = rng.integers(1, step, n)
        self.ts = (self.base_ms + np.arange(rows, dtype=np.int64)[None, :]
                   * step + self.phase[:, None])
        # integer-valued counters below 2**24, which the f32 planes the device
        # store keeps on a TPU hold exactly (the configuration's ``precision``);
        # ``value_scale`` other than 1 makes them doubles beyond f32
        start = fixed.integers(1_000_000, 5_000_000, n)
        inc = fixed.integers(0, 50, (n, rows))
        inc[:, 0] = 0
        vals = start[:, None] + np.cumsum(inc, axis=1)
        resets = fixed.choice(n, max(1, int(n * spec["reset_share"])),
                              replace=False)
        at = fixed.integers(rows // 4, rows - rows // 4, len(resets))
        for s, r in zip(resets, at):          # process restart: count anew
            vals[s, r:] = 1_000_000 + np.cumsum(inc[s, r:])
        if vals.min() < 1_000_000 or vals.max() >= 10_000_000:
            raise ValueError("counter values left the 7-digit range")
        dealt = rng.permutation(n)        # series s holds value row dealt[s]
        self.vals = vals[dealt].astype(np.float64) \
            * float(spec.get("value_scale", 1.0))
        self.reset_series = np.flatnonzero(np.isin(dealt, resets))

    @property
    def samples(self) -> int:
        return self.n * self.rows

    @property
    def end_ms(self) -> int:
        """The newest bucket edge: every panel's ``end``."""
        return self.base_ms + self.rows * self.scrape_ms

    def ns_name(self, ns: int) -> str:
        return f"App-{ns:04d}"

    def instance_name(self, s: int) -> str:
        return f"i{s:07d}"

    def tags(self, s: int) -> dict:
        return {"_ws_": self.workspace, "_ns_": self.ns_name(int(self.ns[s])),
                "g": f"g{int(self.g[s]):02d}",
                "instance": self.instance_name(s), "_metric_": self.metric}
