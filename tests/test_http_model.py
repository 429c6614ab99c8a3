"""The API edge, byte for byte: ``to_prom_matrix`` / ``to_prom_vector``
against the cell-at-a-time implementation they replaced
(``tests/oracle.py`` ``prom_matrix`` / ``prom_vector``, ``http/model.py``
as it was before PR 32), and a count that says the batch-at-a-time form
makes no NumPy call a series or a cell."""

import gc
import json
import sys

import numpy as np
import pytest

from filodb_tpu.core.chunk import ChunkBatch
from filodb_tpu.http.model import (_fmt, public_tags, to_prom_matrix,
                                   to_prom_vector)
from filodb_tpu.query.model import (PeriodicBatch, QueryResult, QueryStats,
                                    RawBatch, ScalarResult, StepRange)
from tests import oracle

START, STEP = 1_700_000_000_000, 150_000
NAN, INF = float("nan"), float("inf")


def _steps(t: int) -> StepRange:
    return StepRange(START, START + (t - 1) * STEP, STEP)


def _keys(s: int, column: str = "_metric_") -> list:
    return [{column: "heap_usage", "_ws_": "demo", "_ns_": f"App-{i % 7}",
             "instance": f"Instance-{i}", "host": "H0", "g": str(i % 4)}
            for i in range(s)]


def _periodic(values, column: str = "_metric_") -> PeriodicBatch:
    values = np.asarray(values)
    return PeriodicBatch(_keys(values.shape[0], column),
                         _steps(values.shape[1]), values)


def _result(*batches, **stats) -> QueryResult:
    return QueryResult("q", list(batches), QueryStats(**stats))


def _raw(counts, rows: int = 16, seed: int = 32,
         column: str = "_metric_") -> RawBatch:
    """A padded leaf batch: TS_PAD-like stamps and NaN past each count."""
    rng = np.random.default_rng(seed)
    s = len(counts)
    ts = START + np.arange(rows, dtype=np.int64)[None, :] * 15_000 \
        + rng.integers(0, 999, (s, 1))
    vals = rng.integers(1, 10**7, (s, rows)) / rng.choice([1.0, 8.0], (s, 1))
    for i, n in enumerate(counts):
        ts[i, n:] = np.iinfo(np.int64).max
        vals[i, n:] = np.nan
    return RawBatch(_keys(s, column),
                    ChunkBatch(ts, vals, np.asarray(counts, dtype=np.int32)))


def _bits(dtype, n, seed) -> np.ndarray:
    """Every kind of float there is: ``n`` random bit patterns."""
    rng = np.random.default_rng(seed)
    if dtype == np.float64:
        return rng.integers(0, 2**64, n, dtype=np.uint64).view(np.float64)
    return rng.integers(0, 2**32, n, dtype=np.uint32).view(np.float32)


def _cases() -> dict:
    rng = np.random.default_rng(32)
    whole = rng.integers(10**6, 10**7, (128, 23)).astype(np.float64)
    scattered = whole / 7.0
    scattered[rng.random(scattered.shape) < 0.3] = NAN
    one_lost = whole[:5].copy()
    one_lost[2] = NAN
    edges = [1e15 - 1, 1e15, 1e15 + 2, -1e15, -(1e15 - 1), 1e16, 1e22,
             1.5e300, 2.0**53, 2.0**63, 123456789012345.0, 999999999999999.5]
    tiny = [5e-324, -5e-324, 2.2250738585072014e-308, 1e-310, 1e-5, 1e-4,
            0.1, 1 / 3]
    f32 = np.asarray([[0.1, 1 / 3, 16777216.0, 3.4028235e38, 1e-45, -0.0],
                      [NAN, INF, -INF, 1e6, 2.5, NAN]], dtype=np.float32)
    padded = _periodic(whole[:6])
    padded.keys = padded.keys[:4]            # values carries padding rows
    named = _periodic(whole[:2])
    named.keys[0]["__name__"] = "already_there"
    scalar = ScalarResult(_steps(5), np.asarray([1.0, NAN, 2.5, INF, 7e15]))
    raw_nan = _raw([0, 5, 16], seed=3)
    raw_nan.batch.values[1, :] = NAN         # inside its count too
    raw_nan.batch.values[2, 4] = NAN
    return {
        "cells-128x23-whole": _result(_periodic(whole)),
        "fractions-128x23": _result(_periodic(whole / 7.0)),
        "wide-128x230": _result(_periodic(
            rng.integers(0, 10**7, (128, 230)) / 4.0)),
        "one-series": _result(_periodic(whole[:1] * 0.001)),
        "one-step": _result(_periodic(whole[:9, :1])),
        "nan-scattered": _result(_periodic(scattered)),
        "nan-one-series-omitted": _result(_periodic(one_lost)),
        "nan-whole-result": _result(_periodic(np.full((4, 23), NAN))),
        "nan-at-the-newest-step": _result(_periodic(
            np.where(np.arange(23) > 20, NAN, whole[:8]))),
        "infinities": _result(_periodic(
            [[INF, 1.0, -INF], [-INF, NAN, INF], [NAN, INF, NAN]])),
        "zeros-of-both-signs": _result(_periodic(
            [[-0.0, 0.0, -0.5], [0.0, -0.0, -1.0]])),
        "1e15-and-above": _result(_periodic([edges, [-v for v in edges]])),
        "subnormals-and-small": _result(_periodic(
            [tiny, [-v for v in tiny]])),
        "random-bits-f64": _result(_periodic(
            _bits(np.float64, 64 * 23, 1).reshape(64, 23))),
        "random-bits-f32": _result(_periodic(
            _bits(np.float32, 64 * 23, 2).reshape(64, 23))),
        "f32-whole-counters": _result(_periodic(whole.astype(np.float32))),
        "f32-fractions-and-specials": _result(_periodic(f32)),
        "int64-plane": _result(_periodic(
            [[0, -3, 2**53 + 1], [10**15, 10**15 - 1, -(10**18)]])),
        "padded-series-axis": _result(padded),
        "no-batches": _result(),
        "no-series": _result(_periodic(np.empty((0, 23)))),
        "scalar": _result(scalar),
        "scalar-all-nan": _result(ScalarResult(_steps(3), np.full(3, NAN))),
        "scalar-newest-nan": _result(ScalarResult(
            _steps(3), np.asarray([1.5, 2.0, NAN]))),
        "scalar-f32": _result(ScalarResult(
            _steps(4), np.asarray([0.1, 3.0, -0.0, INF], dtype=np.float32))),
        "raw-counts-shorter-than-the-row": _result(_raw([16, 3, 9, 1])),
        "raw-empty-and-nan-series": _result(raw_nan),
        "raw-no-batch": _result(RawBatch([], None)),
        # a raw export renames the default column only (ROADMAP D14)
        "raw-metric-column-custom": _result(_raw([2, 2], column="name")),
        "metric-column-custom-present": _result(_periodic(whole[:3], "name")),
        "metric-column-absent": _result(_periodic(whole[:3], "other")),
        "metric-column-beside-__name__": _result(named),
        "several-batches": _result(_periodic(whole[:3]), scalar,
                                   _raw([4, 2]), _periodic(scattered[:3])),
        "warning-corrupt-chunks": _result(_periodic(whole[:2]),
                                          corrupt_chunks_excluded=3),
        "warning-shards-down": _result(_periodic(whole[:2]), shards_down=1),
        "warning-both": _result(scalar, corrupt_chunks_excluded=1,
                                shards_down=2),
    }


CASES = _cases()
# the column the served path would hand over: the dataset's
COLUMN = {"metric-column-custom-present": "name",
          "metric-column-absent": "name", "raw-metric-column-custom": "name"}


def test_there_are_cases_enough():
    assert len(CASES) >= 20


@pytest.mark.parametrize("case", CASES)
def test_matrix_is_the_oracles_byte_for_byte(case):
    result, column = CASES[case], COLUMN.get(case, "_metric_")
    got = json.dumps(to_prom_matrix(result, column))
    assert got == json.dumps(oracle.prom_matrix(result, column))
    assert '"NaN"' not in got                # a matrix drops the cell
    if case.startswith("warning"):
        assert '"warnings": ["partial data: ' in got


@pytest.mark.parametrize("case", CASES)
def test_vector_is_the_oracles_byte_for_byte(case):
    result, column = CASES[case], COLUMN.get(case, "_metric_")
    # before the first step, on a step, between two, at and past the end
    for time_ms in (START - 1, START, START + STEP + 1, START + 2 * STEP,
                    START + 22 * STEP, START + 10**9):
        assert json.dumps(to_prom_vector(result, time_ms, column)) == \
            json.dumps(oracle.prom_vector(result, time_ms, column)), time_ms


def test_an_answers_cells_leave_the_collectors_books():
    """A cell is a tuple of a float and a str, which the cyclic collector
    stops tracking at the first collection that meets it: the cells of
    the answers in flight are not promoted into the old generation,
    where they paced its full collections (PERF.md section 5,
    ``jmh1.sliding``).  ``json.dumps`` writes it as the array a list
    was (the byte-for-byte cases above)."""
    result = to_prom_matrix(CASES["cells-128x23-whole"])["data"]["result"]
    gc.collect()
    cells = [c for s in result for c in s["values"]]
    assert len(cells) == 128 * 23
    assert not any(gc.is_tracked(c) for c in cells)


def test_the_cases_say_what_their_names_say():
    """The cases' own teeth: what each is named for shows in the answer."""
    def series(case):
        return to_prom_matrix(CASES[case])["data"]["result"]
    assert len(series("cells-128x23-whole")) == 128
    assert all(len(s["values"]) == 23 and "." not in s["values"][0][1]
               for s in series("cells-128x23-whole"))
    assert len(series("nan-one-series-omitted")) == 4
    assert series("nan-whole-result") == series("scalar-all-nan") == []
    assert [v for _t, v in series("infinities")[0]["values"]] == \
        ["+Inf", "1", "-Inf"]
    assert [v for _t, v in series("zeros-of-both-signs")[0]["values"]] == \
        ["0", "0", "-0.5"]
    assert [v for _t, v in series("1e15-and-above")[0]["values"]][:3] == \
        ["999999999999999", "1000000000000000.0", "1000000000000002.0"]
    assert series("subnormals-and-small")[0]["values"][0][1] == "5e-324"
    assert series("f32-fractions-and-specials")[0]["values"][0][1] == \
        "0.10000000149011612"
    assert len(series("padded-series-axis")) == 4
    assert [len(s["values"]) for s in
            series("raw-counts-shorter-than-the-row")] == [16, 3, 9, 1]
    assert [len(s["values"]) for s in
            series("raw-empty-and-nan-series")] == [15]
    assert to_prom_matrix(CASES["metric-column-custom-present"], "name")[
        "data"]["result"][0]["metric"]["__name__"] == "heap_usage"
    assert "__name__" not in to_prom_matrix(
        CASES["metric-column-absent"], "name")["data"]["result"][0]["metric"]
    assert to_prom_vector(CASES["scalar-newest-nan"], START)["data"] == \
        {"resultType": "scalar", "value": [START / 1000.0, "NaN"]}


@pytest.mark.parametrize("v", [0.0, -0.0, 1.0, -7.0, 0.5, 1e15, 1e15 - 1,
                               1e16, 5e-324, NAN, INF, -INF, 1 / 3])
def test_fmt_and_public_tags_are_the_oracles(v):
    assert _fmt(v) == oracle.prom_fmt(v)
    for tags in ({}, {"a": "1"}, {"_metric_": "m", "z": "1", "a": "2"},
                 {"__name__": "n", "b": "1", "_metric_": "m", "c": "2"}):
        for column in ("_metric_", "__name__", "b", "missing"):
            got = public_tags(tags, column)
            assert list(got.items()) == \
                list(oracle.prom_public_tags(tags, column).items())
            assert got is not tags


def _numpy_c_calls(fn, *args) -> int:
    """``c_call`` events into NumPy while ``fn`` runs: its C functions and
    the methods of its arrays and scalars."""
    n = [0]

    def profile(_frame, event, arg):
        if event == "c_call":
            owner = getattr(arg, "__self__", None)
            n[0] += isinstance(owner, (np.ndarray, np.generic)) or \
                (getattr(arg, "__module__", None) or "").startswith("numpy")
    sys.setprofile(profile)
    try:
        fn(*args)
    finally:
        sys.setprofile(None)
    return n[0]


@pytest.mark.parametrize("build", [
    lambda v: (to_prom_matrix, _result(_periodic(v))),
    lambda v: (to_prom_vector, _result(_periodic(v)), START + 10**9),
    lambda v: (to_prom_matrix, _result(_raw([v.shape[1]] * v.shape[0],
                                            rows=v.shape[1]))),
], ids=["matrix-periodic", "vector-periodic", "matrix-raw"])
def test_no_numpy_call_a_series_or_a_cell(build):
    """Counted, not timed (as PR 30's ``quantile`` test counts): arrays
    cross into Python lists once a batch, so the calls into NumPy are as
    many for one series as for 128, for 23 steps as for 230."""
    rng = np.random.default_rng(32)
    _numpy_c_calls(*build(np.ones((1, 2))))  # first-call imports
    counts = {}
    for shape in [(1, 23), (128, 23), (128, 230)]:
        values = rng.integers(0, 10**7, shape) / 2.0
        values[rng.random(shape) < 0.1] = NAN
        counts[shape] = _numpy_c_calls(*build(values))
    assert len(set(counts.values())) == 1, counts
    assert 0 < counts[1, 23] < 40, counts
