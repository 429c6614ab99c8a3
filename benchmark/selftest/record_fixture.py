#!/usr/bin/env python3
"""Records ``fixture.xplane.pb``: a small device trace for the self-test of
``harness/trace_reduce.py``.  Run once on the chip (``chiprun -- python3
benchmark/selftest/record_fixture.py chiprun_out/fixture``): three launches
of one jitted program, 50 ms apart, inside a 0.4 s window."""

import glob
import json
import shutil
import sys
import time

import jax
import jax.numpy as jnp


def main(out: str) -> int:
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((2048, 2048), jnp.float32)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out + "/raw", profiler_options=opts)
    t0 = time.time()
    for _ in range(3):
        f(x).block_until_ready()
        time.sleep(0.05)
    time.sleep(0.2)
    t1 = time.time()
    jax.profiler.stop_trace()
    path = sorted(glob.glob(out + "/raw/plugins/profile/*/*.xplane.pb"))[-1]
    shutil.copy(path, out + "/fixture.xplane.pb")
    shutil.rmtree(out + "/raw")
    with open(out + "/fixture.json", "w") as fh:
        json.dump({"window_s": t1 - t0, "launches": 3,
                   "device_kind": jax.devices()[0].device_kind}, fh)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1]))
