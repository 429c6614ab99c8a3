"""The seam between the program and ``benchmark/``: the names the driver's
benchmark reads from the program, held by tier-1.

``benchmark/`` is the one yardstick (BENCHMARK.json ``paths``) and no PR
that changes the program may edit it, so a stage span renamed in a
refactor turns a per-layer metric into ``null`` with nothing else
noticing, and a breaker or module that is gone ends a run with no result.
This file reads ``benchmark/`` and edits nothing there.  PERF.md section 3
lists the same names.
"""

import ast
import importlib
import json
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
METRICS = sorted((ROOT / "benchmark" / "metrics").glob("*.json"))
# a stage span (and the ``timings`` bucket it fills), a deferred span, or
# a bucket written by hand
_EMIT = re.compile(
    r"""(?:\.stage|\.defer|\.add_timing|timings\.setdefault|_leaf_annotation)"""
    r"""\(\s*["']([\w.]+)["']""")


@pytest.fixture(scope="module")
def emitted() -> set:
    return {name for path in (ROOT / "filodb_tpu").rglob("*.py")
            for name in _EMIT.findall(path.read_text())}


def _names(args: dict) -> list:
    """The span / bucket names a metric's reader is given."""
    out = []
    for key in ("bucket", "spans", "per", "minus"):
        val = args.get(key, [])
        out += [val] if isinstance(val, str) else list(val)
    return out


def test_there_are_metrics_to_hold():
    assert len(METRICS) >= 30


@pytest.mark.parametrize("path", METRICS, ids=lambda p: p.stem)
def test_metric_reads_names_the_program_emits(path, emitted):
    metric = json.loads(path.read_text())
    assert (ROOT / "benchmark" / "readers"
            / f"{metric['reader']}.py").exists()
    missing = [n for n in _names(metric["args"]) if n not in emitted]
    assert not missing, \
        f"{path.stem}: no stage, span or add_timing named {missing} under " \
        f"filodb_tpu/: the metric would read null"


def _run_py_constant(name: str):
    """A module-level constant of ``benchmark/run.py``, evaluated from
    its source: importing run.py would import the harness."""
    tree = ast.parse((ROOT / "benchmark" / "run.py").read_text())
    for node in tree.body:
        if isinstance(node, ast.Assign) and node.targets[0].id == name:
            return eval(compile(ast.Expression(node.value), "run.py", "eval"))
    raise AssertionError(f"benchmark/run.py has no {name}")


_BREAKERS = _run_py_constant("BREAKERS")


def test_run_py_names_three_breakers():
    assert len(_BREAKERS) == 3


@pytest.mark.parametrize("module,attr,is_open", _BREAKERS,
                         ids=[b[1] for b in _BREAKERS])
def test_breaker_resolves_by_getattr(module, attr, is_open):
    """``run.py`` ends with no result where one cannot be read: each
    is there, and run.py's own test of it gives a plain yes or no."""
    breaker = getattr(importlib.import_module(module), attr)
    assert is_open(breaker) in (True, False)


def test_native_baseline_imports():
    """``run.py`` imports it and reads ``build_error()`` of it and of
    ``filodb_tpu.native`` (``native_build_errors``)."""
    from filodb_tpu import native
    from filodb_tpu.native import baseline
    for mod in (native, baseline):
        assert mod.build_error() is None or isinstance(mod.build_error(),
                                                       str)


def test_served_programs_are_devicestore_and_stacked_say_batch():
    """``device_dispatches`` counts launches of ``devicestore.*`` less
    those with ``_batch`` in the name, plus the batch members: the
    stacked programs, and only they, carry ``_batch``."""
    from filodb_tpu.memstore import devicestore
    from filodb_tpu.utils.observability import batch_metrics
    progs = devicestore._fused_progs()
    names = {key: fn._program for key, fn in progs.items()}
    assert set(names) >= {"series", "grouped", "series_batch",
                          "grouped_batch"}
    for key, name in names.items():
        assert name.startswith("devicestore."), (key, name)
        assert ("_batch" in name) == key.endswith("_batch"), (key, name)
    assert batch_metrics()["members"].name == "filodb_batch_members_total"
