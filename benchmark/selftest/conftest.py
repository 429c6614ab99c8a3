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


def run_child(args: list, cwd=ROOT, timeout: int = 600):
    """A child held to the CPU backend, one device."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable] + [str(a) for a in args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=timeout)
