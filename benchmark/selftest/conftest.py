"""Self-tests of the benchmark harness: ``python3 -m pytest benchmark/selftest -q``.
They run on a host without a chip; whatever boots the server does so in a
child process held to the CPU backend."""

import os
import pathlib
import subprocess
import sys

HERE = pathlib.Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))


def run_child(args: list, cwd=ROOT, timeout: int = 600, devices: int = 1):
    """A child held to the CPU backend: one device, or ``devices`` virtual
    ones (more than one puts the program's mesh fabric in the path)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    if devices > 1:
        env["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={devices}"
    return subprocess.run([sys.executable] + [str(a) for a in args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=timeout)
