"""Shared helpers for the stress runners."""

from __future__ import annotations

import json
import sys
import time


def emit(metric: str, value, unit: str, **extra) -> None:
    print(json.dumps({"metric": metric,
                      "value": round(value, 1) if isinstance(value, float)
                      else value,
                      "unit": unit, **extra}), flush=True)


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def force_cpu_x64() -> None:
    """Stress runs are host-side: CPU backend, exact float64."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)


class Latencies:
    def __init__(self):
        self.samples: list[float] = []

    def time(self):
        t0 = time.perf_counter()
        return lambda: self.samples.append(time.perf_counter() - t0)

    def pct(self, p: float) -> float:
        if not self.samples:
            return float("nan")
        s = sorted(self.samples)
        return s[min(int(len(s) * p), len(s) - 1)]
