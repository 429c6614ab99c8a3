"""chip_smoke.py must not rot between chip runs: its tiny CPU rehearsal
— every phase, at 256 series — runs here as one test.

A child process, because the script boots a whole server and sets
process-wide JAX configuration.  The child is held to the CPU backend, so
it never loads the TPU library (tests/test_chip_compile.py's worker may
hold it)."""

import json
import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _run(*args, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               # placed from outside: the program sets no cache dir
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    env.pop("XLA_FLAGS", None)      # one CPU device, as the script expects
    return subprocess.run([sys.executable, str(ROOT / "chip_smoke.py"),
                           *args], cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=600)


def test_rehearsal_passes_and_never_reports_a_chip(tmp_path):
    proc = _run("--rehearse", tmp_path=tmp_path)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["ok"] is True and last["rehearsal"] is True
    assert last["device"]["platform"] == "cpu"
    out = proc.stdout
    # every phase ran and said so
    for phase in ("ingest[prom]", "flush:", "oracle:", "query[sum_rate_1h]",
                  "query[sum_by_g_rate_1h]", "query[sum_rate_25m]",
                  "query[sum_rate_step150]", "program devicestore.grouped",
                  "query[raw_selector]", "query[sum_over_time]",
                  "query[quantile]", "fleet:", "HBM ledger:",
                  "filodb_kernel_launches_total"):
        assert phase in out, phase
    assert "FAIL" not in out


def test_without_a_chip_the_smoke_refuses_and_prints_no_result(tmp_path):
    proc = _run(tmp_path=tmp_path)
    assert proc.returncode == 3, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert '"ok"' not in proc.stdout
    assert not proc.stdout.strip().splitlines()[-1].startswith("{")
