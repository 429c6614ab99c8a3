"""t-digest sketch quantiles: accuracy, mergeability, bounded memory,
and the QuantileAggregator exact/sketch switchover.

Reference: exec/aggregator/RowAggregator.scala QuantileRowAggregator
(TDigest partials bounding memory at high cardinality).
"""

import sys

import numpy as np
import pytest

from filodb_tpu.query import tdigest
from filodb_tpu.query.aggregators import (AggPartialBatch,
                                          QuantileAggregator, aggregator_for)
from filodb_tpu.query.model import PeriodicBatch, StepRange

BASE = 1_700_000_000_000


def _batch(vals, keys=None):
    S, T = vals.shape
    keys = keys or [{"inst": f"i{s}", "g": f"g{s % 2}"} for s in range(S)]
    return PeriodicBatch(keys, StepRange(BASE, 60_000, T), vals)


class TestTDigestCore:
    @pytest.mark.parametrize("q", [0.01, 0.25, 0.5, 0.9, 0.99])
    @pytest.mark.parametrize("dist", ["uniform", "normal", "lognormal"])
    def test_accuracy_vs_exact(self, q, dist):
        rng = np.random.default_rng(42)
        n = 20_000
        if dist == "uniform":
            data = rng.uniform(0, 100, n)
        elif dist == "normal":
            data = rng.normal(50, 10, n)
        else:
            data = rng.lognormal(1.0, 1.0, n)
        vals = data.reshape(n, 1)                  # n series, 1 step
        d = tdigest.from_values(vals, np.zeros(n, dtype=np.int64), 1,
                                compression=128)
        got = float(tdigest.quantile(d, q)[0, 0])
        want = float(np.quantile(data, q))
        spread = np.quantile(data, 0.95) - np.quantile(data, 0.05)
        assert abs(got - want) <= 0.05 * spread + 1e-9, \
            f"{dist} q={q}: got {got}, want {want}"

    def test_merge_matches_single_build(self):
        rng = np.random.default_rng(0)
        a = rng.normal(0, 1, (500, 3))
        b = rng.normal(0, 1, (500, 3))
        ids_a = np.zeros(500, dtype=np.int64)
        d_all = tdigest.from_values(np.concatenate([a, b]),
                                    np.zeros(1000, dtype=np.int64), 1)
        d_m = tdigest.merge(tdigest.from_values(a, ids_a, 1),
                            tdigest.from_values(b, ids_a, 1))
        for q in (0.1, 0.5, 0.9):
            np.testing.assert_allclose(tdigest.quantile(d_m, q),
                                       tdigest.quantile(d_all, q),
                                       atol=0.15)

    def test_memory_bounded(self):
        S = 50_000
        rng = np.random.default_rng(1)
        vals = rng.random((S, 4))
        ids = rng.integers(0, 10, S)
        d = tdigest.from_values(vals, ids, 10, compression=128)
        # O(G*T*C): 10*4*64 floats *2 arrays = 40KB, NOT O(S*T)
        assert d.nbytes < 100_000
        q = tdigest.quantile(d, 0.5)
        assert q.shape == (10, 4)
        assert np.isfinite(q).all()
        assert np.all((q > 0.4) & (q < 0.6))       # median of U(0,1)

    def test_nan_and_empty_cells(self):
        vals = np.array([[1.0, np.nan], [3.0, np.nan]])
        d = tdigest.from_values(vals, np.zeros(2, dtype=np.int64), 2)
        q = tdigest.quantile(d, 0.5)
        assert np.isfinite(q[0, 0])
        assert np.isnan(q[0, 1])                   # no samples at step 1
        assert np.isnan(q[1]).all()                # group 1 empty

    def test_exact_small_inputs(self):
        """With few values, digest quantiles hit exact order statistics."""
        vals = np.array([[10.0], [20.0], [30.0]])
        d = tdigest.from_values(vals, np.zeros(3, dtype=np.int64), 1)
        assert float(tdigest.quantile(d, 0.0)[0, 0]) == 10.0
        assert float(tdigest.quantile(d, 1.0)[0, 0]) == 30.0
        assert abs(float(tdigest.quantile(d, 0.5)[0, 0]) - 20.0) < 1e-9

    def test_from_members_roundtrip(self):
        members = np.array([[[1.0, 2.0], [3.0, np.nan]]])  # [1, 2, 2]
        d = tdigest.from_members(members)
        q = tdigest.quantile(d, 0.5)
        assert abs(q[0, 0] - 2.0) < 1.1             # median of {1,3}
        assert abs(q[0, 1] - 2.0) < 1e-9            # single value 2.0


class TestQuantileAggregatorSwitch:
    def test_small_stays_exact(self):
        agg = aggregator_for(QuantileAggregator.op)
        vals = np.arange(12.0).reshape(4, 3)
        p = agg.map(_batch(vals), ("g",), (), (0.5,), 1000)
        assert "members" in p.state
        out = agg.present(agg.reduce([p]))
        assert out.values.shape == (2, 3)
        # exact median of {0,6} rows etc.
        np.testing.assert_allclose(out.values[0], [3.0, 4.0, 5.0])

    def test_large_switches_to_sketch(self):
        agg = QuantileAggregator()
        rng = np.random.default_rng(2)
        S = 2_000
        vals = rng.random((S, 2))
        keys = [{"inst": f"i{s}"} for s in range(S)]
        p = agg.map(_batch(vals, keys), (), (), (0.9,), 10_000)
        assert "td_means" in p.state
        assert p.state["td_means"].nbytes < 10_000  # 1 group * 2 steps * 64
        out = agg.present(agg.reduce([p]))
        np.testing.assert_allclose(out.values, 0.9, atol=0.03)

    def test_mixed_exact_and_sketch_reduce(self):
        agg = QuantileAggregator()
        rng = np.random.default_rng(3)
        small = rng.random((10, 2))
        big = rng.random((2_000, 2))
        p1 = agg.map(_batch(small, [{"inst": f"a{s}"} for s in range(10)]),
                     (), (), (0.5,), 10_000)
        p2 = agg.map(_batch(big, [{"inst": f"b{s}"} for s in range(2_000)]),
                     (), (), (0.5,), 10_000)
        assert "members" in p1.state and "td_means" in p2.state
        out = agg.present(agg.reduce([p1, p2]))
        np.testing.assert_allclose(out.values, 0.5, atol=0.03)

    def test_sketch_accuracy_through_full_pipeline(self):
        """Exact vs sketch on the same data: within t-digest tolerance."""
        agg = QuantileAggregator()
        rng = np.random.default_rng(4)
        S = 1_000
        vals = rng.normal(100, 15, (S, 3))
        keys = [{"inst": f"i{s}"} for s in range(S)]
        p = agg.map(_batch(vals, keys), (), (), (0.95,), 10_000)
        out = agg.present(agg.reduce([p]))
        want = np.quantile(vals, 0.95, axis=0)
        np.testing.assert_allclose(out.values[0], want, rtol=0.02)


# ---------------------------------------------------------------------------
# The exact present: one sort, bit for bit np.nanquantile (PR 30)
# ---------------------------------------------------------------------------


def _members(case: str) -> np.ndarray:
    """Dense member matrices [G, M, T] of random doubles (no whole
    numbers), NaN where a series is absent at a step."""
    rng = np.random.default_rng(sum(case.encode()))

    def scattered(shape, share=0.2):
        m = rng.normal(50.0, 1e3, shape)
        m[rng.random(shape) < share] = np.nan
        return m
    if case == "1x128x23":
        return scattered((1, 128, 23))
    if case == "1x128x23-full":                 # the benchmark's panel
        return scattered((1, 128, 23), share=0.0)
    if case == "1x1x5":
        return scattered((1, 1, 5))
    if case == "7x33x221":
        return scattered((7, 33, 221))
    if case == "column-nan":                    # a step nobody reported at
        m = scattered((7, 33, 221))
        m[:, :, 4] = np.nan
        m[2, :, 100:] = np.nan
        return m
    if case == "group-nan":
        m = scattered((7, 33, 221))
        m[3] = np.nan
        return m
    if case == "unequal-groups":                # the dense matrix pads
        sizes = [1, 33, 2, 17, 5, 32, 8]
        vals = scattered((sum(sizes), 221), share=0.1)
        keys = [{"inst": f"i{g}-{i}", "g": f"g{g}"}
                for g, n in enumerate(sizes) for i in range(n)]
        p = QuantileAggregator().map(_batch(vals, keys), ("g",), (), (0.5,),
                                     1000)
        assert p.state["members"].shape == (7, 33, 221)
        assert np.isnan(p.state["members"][0, 1:]).all()
        return p.state["members"]
    assert case == "inf-and-zero"               # inf - inf, the zero's sign
    return np.array([[[-0.0, np.inf, -np.inf, np.nan, 1.5, np.inf],
                      [np.nan, np.inf, -np.inf, np.nan, 2.5, 3.0],
                      [np.nan, 7.25, -np.inf, np.nan, np.nan, 0.1]]])


def _partial(members: np.ndarray, q: float) -> AggPartialBatch:
    G, _M, T = members.shape
    return AggPartialBatch(QuantileAggregator.op, (q,),
                           [{"g": str(g)} for g in range(G)],
                           StepRange(BASE, 60_000, T), {"members": members})


def _present(members: np.ndarray, q: float) -> np.ndarray:
    p = _partial(members, q)
    out = QuantileAggregator().present(p)
    assert out.keys == p.group_keys and out.steps == p.steps
    return out.values


@pytest.mark.filterwarnings("ignore::RuntimeWarning")   # nanquantile's own
@pytest.mark.parametrize("q", [0, 0.25, 0.5, 0.75, 0.99, 1])
@pytest.mark.parametrize("case", [
    "1x128x23", "1x128x23-full", "1x1x5", "7x33x221", "column-nan",
    "group-nan", "unequal-groups", "inf-and-zero"])
def test_exact_present_is_nanquantile_bit_for_bit(case, q):
    members = _members(case)
    want = np.nanquantile(members, q, axis=1)
    state = members.copy()
    got = _present(state, q)
    assert np.array_equal(state, members, equal_nan=True)   # sorted a copy
    assert got.shape == want.shape and got.dtype == want.dtype
    assert np.array_equal(got, want, equal_nan=True)
    # ... to the sign of a zero (the cases tie no zeros of two signs,
    # whose order no sort defines)
    assert np.array_equal(np.signbit(got), np.signbit(want))
    if case in ("column-nan", "group-nan"):
        assert np.isnan(got).any() and np.isfinite(got).any()


@pytest.mark.parametrize("q", [-0.1, 1.5, float("nan")])
def test_exact_present_refuses_q_outside_the_unit_interval(q):
    """As ``np.nanquantile`` did on the served path: a ``ValueError`` with
    NumPy's words, which the HTTP front end answers 400 ``bad_data``."""
    with pytest.raises(ValueError,
                       match=r"Quantiles must be in the range \[0, 1\]"):
        _present(_members("1x1x5"), q)


def _c_calls_in_present(members: np.ndarray) -> int:
    p, agg, n = _partial(members, 0.75), QuantileAggregator(), [0]

    def profile(_frame, event, _arg):
        n[0] += event == "c_call"
    sys.setprofile(profile)
    try:
        agg.present(p)
    finally:
        sys.setprofile(None)
    return n[0]


def test_exact_present_makes_no_call_per_step_or_group(monkeypatch):
    """Counted, not timed: every C call of the exact path is over whole
    arrays, so their number is the same for 23 steps and 2 300, for one
    group and seven, for one member and 128."""
    def no_loop(*_a, **_k):
        raise AssertionError("np.apply_along_axis: a Python call a slice")
    monkeypatch.setattr(np, "apply_along_axis", no_loop)
    rng = np.random.default_rng(30)
    _c_calls_in_present(rng.random((1, 2, 3)))      # first-call imports
    counts = {shape: _c_calls_in_present(rng.random(shape))
              for shape in [(1, 128, 23), (1, 128, 2300), (7, 128, 23),
                            (7, 1, 2300)]}
    assert len(set(counts.values())) == 1, counts
    assert 0 < counts[1, 128, 23] < 60, counts
