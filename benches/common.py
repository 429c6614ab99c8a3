"""Shared helpers for the benchmark suite (reference: jmh/ module's
common setup — TestTimeseriesProducer-style data, timed sections).

Each bench prints one JSON line per measured metric:
    {"metric": ..., "value": ..., "unit": ...}
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np


def emit(metric: str, value: float, unit: str, **extra) -> None:
    print(json.dumps({"metric": metric, "value": round(value, 1),
                      "unit": unit, **extra}), flush=True)


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def timed(fn, reps: int = 3) -> float:
    """Median wall time of fn() over reps."""
    outs = []
    for _ in range(reps):
        a = time.perf_counter()
        fn()
        outs.append(time.perf_counter() - a)
    return float(np.median(outs))


def force_cpu_x64() -> None:
    """Host-side benches run on the CPU backend with exact float64."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)
