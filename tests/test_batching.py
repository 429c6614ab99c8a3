"""Fleet batching tier (ISSUE 20): vmapped execution of concurrent
shape-compatible queries.

The load-bearing assertion is the generative bit-equality sweep:
results served from a stacked (vmapped) device launch are BIT-equal
(``tobytes``) to the solo per-query launches they replace, across
seeds x window functions x group sizes (including non-power-of-two
groups that exercise the padding path).  Plus: admission/deadline
discipline at stack time (mixed-deadline groups, mid-batch expiry),
the breaker-trip demotion ladder, and the disabled-by-config true
passthrough."""

import threading
import time

import numpy as np
import pytest

from filodb_tpu.batching import (QueryBatcher, batching_broken,
                                 reset_batch_breaker)
from filodb_tpu.batching.batcher import _Group, _Member, _pad_pow2
from filodb_tpu.core.filters import ColumnFilter, Equals
from filodb_tpu.core.record import RecordBuilder, decode_container
from filodb_tpu.core.schemas import DEFAULT_SCHEMAS
from filodb_tpu.core.storeconfig import StoreConfig
from filodb_tpu.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.query.logical import RangeFunctionId as F
from filodb_tpu.query.model import QueryContext
from filodb_tpu.utils.observability import batch_metrics

STEP = 60_000
T0 = 1_700_000_040_000
WINDOW = 300_000
K = WINDOW // STEP


@pytest.fixture(autouse=True)
def _closed_breaker():
    reset_batch_breaker()
    yield
    reset_batch_breaker()


def _mk_shard(n_series=6, n_rows=50, jitter_max=30_000, seed=0):
    ms = TimeSeriesMemStore()
    shard = ms.setup("prom", DEFAULT_SCHEMAS, 0, StoreConfig())
    rng = np.random.default_rng(seed)
    b = RecordBuilder(DEFAULT_SCHEMAS["prom-counter"])
    for i in range(n_series):
        tags = {"__name__": "req_total", "instance": f"i{i}",
                "_ws_": "w", "_ns_": "n"}
        base = T0 + np.arange(n_rows, dtype=np.int64) * STEP - STEP + 1
        ts = base + rng.integers(0, max(jitter_max, 1), size=n_rows)
        vals = np.cumsum(rng.random(n_rows) * 5)
        for t, v in zip(ts, vals):
            b.add(int(t), [float(v)], tags)
    for off, c in enumerate(b.containers()):
        shard.ingest(decode_container(c, DEFAULT_SCHEMAS), off)
    shard.flush_all()
    return ms, shard


def _part_ids(shard):
    return shard.lookup_partitions(
        [ColumnFilter("_metric_", Equals("req_total"))], 0, 2**62).part_ids


def _concurrent(shard, part_ids, func, starts, nsteps):
    """Fire one scan_grid per start from barrier-released threads;
    returns {start_index: values array}."""
    barrier = threading.Barrier(len(starts))
    outs: dict = {}
    errs: list = []

    def worker(i, s0):
        try:
            barrier.wait()
            got = shard.scan_grid(part_ids, func, s0, nsteps, STEP,
                                  WINDOW)
            outs[i] = None if got is None else np.asarray(got[1])
        except Exception as e:       # surfaced by the caller
            errs.append(e)

    ts = [threading.Thread(target=worker, args=(i, s0))
          for i, s0 in enumerate(starts)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs, errs
    return outs


# ---------------------------------------------------------------------------
# the generative bit-equality sweep
# ---------------------------------------------------------------------------

SWEEP_FUNCS = [F.RATE, F.INCREASE, F.SUM_OVER_TIME, F.MAX_OVER_TIME]
SWEEP_SIZES = [2, 3, 8]          # 3 exercises the pad-to-power-of-two path


@pytest.mark.parametrize("seed", range(3))
def test_generative_bit_equality_sweep(seed):
    ms, shard = _mk_shard(seed=seed, jitter_max=1 + seed * 15_000)
    pids = _part_ids(shard)
    steps0 = T0 + (K - 1) * STEP
    # one more concurrent query than max_batch: a cold key bootstraps
    # off the overlap (passthrough + leader + joiners), so the first
    # group forms without any prior hotness
    n_conc = max(SWEEP_SIZES) + 1
    nsteps = 50 - K - n_conc
    for func in SWEEP_FUNCS:
        starts = [steps0 + i * STEP for i in range(n_conc)]
        # solo oracle: no batcher attached — today's per-query chain
        shard.query_batcher = None
        solos = {}
        for i, s0 in enumerate(starts):
            got = shard.scan_grid(pids, func, s0, nsteps, STEP, WINDOW)
            assert got is not None, f"grid declined func={func}"
            solos[i] = np.asarray(got[1])
        for size in SWEEP_SIZES:
            bat = QueryBatcher(enabled=True, window_ms=150.0,
                               max_batch=size, hot_ttl_s=30.0,
                               dataset="prom")
            shard.query_batcher = bat
            nq = size + 1
            # the bootstrap overlap is scheduling-dependent, so round
            # until a group forms; bit-equality must hold on EVERY
            # round, grouped or not
            for _round in range(12):
                outs = _concurrent(shard, pids, func, starts[:nq],
                                   nsteps)
                for i in range(nq):
                    assert outs[i] is not None
                    assert outs[i].tobytes() == solos[i].tobytes(), \
                        f"seed={seed} func={func} size={size} " \
                        f"member={i} round={_round}: batched result " \
                        f"differs from solo"
                if bat.snapshot()["realized_peak"] >= 2 and _round:
                    break       # grouped round verified bit-equal
            assert bat.snapshot()["realized_peak"] >= 2, \
                f"seed={seed} func={func} size={size}: no group formed"
    shard.query_batcher = None


def test_grouped_agg_batched_bit_equal():
    ms, shard = _mk_shard(n_series=8)
    pids = _part_ids(shard)
    steps0 = T0 + (K - 1) * STEP
    nsteps = 50 - K - 4
    gids = list(range(len(pids)))
    starts = [steps0 + i * STEP for i in range(4)]

    def run(s0):
        return shard.scan_grid_grouped(pids, F.RATE, s0, nsteps, STEP,
                                       WINDOW, gids, len(pids), "sum")

    shard.query_batcher = None
    solos = [run(s0) for s0 in starts]
    assert all(s is not None for s in solos)
    bat = QueryBatcher(enabled=True, window_ms=500.0, max_batch=4,
                       hot_ttl_s=30.0, dataset="prom")
    shard.query_batcher = bat
    outs: dict = {}
    for _round in range(12):
        outs.clear()
        barrier = threading.Barrier(len(starts))

        def worker(i, s0):
            barrier.wait()
            outs[i] = run(s0)

        ts = [threading.Thread(target=worker, args=(i, s0))
              for i, s0 in enumerate(starts)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        if bat.snapshot()["realized_peak"] >= 2 and _round:
            break
    assert bat.snapshot()["realized_peak"] >= 2
    for i, solo in enumerate(solos):
        got = outs[i]
        assert set(got) == set(solo)
        for op in solo:
            assert np.asarray(got[op]).tobytes() == \
                np.asarray(solo[op]).tobytes(), f"member={i} op={op}"
    shard.query_batcher = None


@pytest.mark.parametrize("grouped", [False, True],
                         ids=["series", "grouped"])
def test_a_shape_that_stacks_brings_every_stack_size_in(grouped):
    """ISSUE 29: the first co-arrival group of a plan shape compiles the
    shape's stacked programs of EVERY size, so that no later group,
    whatever its size, waits for a compile; a shape that never stacks
    (a lone query) compiles none."""
    from filodb_tpu.memstore.devicestore import _fused_progs
    _ms, shard = _mk_shard(n_series=5, seed=29)
    pids = _part_ids(shard)
    steps0 = T0 + (K - 1) * STEP
    nsteps = 17 if grouped else 19      # shapes no other test compiles
    gids = list(range(len(pids)))

    def run(s0):
        if grouped:
            return shard.scan_grid_grouped(pids, F.RATE, s0, nsteps, STEP,
                                           WINDOW, gids, len(pids), "sum")
        return shard.scan_grid(pids, F.SUM_OVER_TIME, s0, nsteps, STEP,
                               WINDOW)

    def together(n):
        barrier = threading.Barrier(n)
        outs: dict = {}

        def worker(i):
            barrier.wait()
            outs[i] = run(steps0 + i * STEP)

        ts = [threading.Thread(target=worker, args=(i,)) for i in range(n)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        assert len(outs) == n and all(o is not None for o in outs.values())

    bat = QueryBatcher(enabled=True, window_ms=150.0, max_batch=8,
                       hot_ttl_s=30.0, dataset="prom")
    assert bat.stack_sizes() == [2, 4, 8]
    assert QueryBatcher(max_batch=6).stack_sizes() == [2, 4, 6]
    assert QueryBatcher(max_batch=1).stack_sizes() == []
    shard.query_batcher = bat
    stacked = _fused_progs()["grouped_batch" if grouped
                             else "series_batch"]._jitted
    before = stacked._cache_size()
    assert run(steps0) is not None and run(steps0 + STEP) is not None
    assert stacked._cache_size() == before      # lone queries: no stack
    for _round in range(12):
        # three at once: one passes alone (a cold key), the next leads
        # a group, the third joins it
        together(3)
        if bat.snapshot()["realized_peak"] >= 2:
            break
    assert bat.snapshot()["realized_peak"] == 2
    assert stacked._cache_size() == before + 3  # sizes 2, 4 and 8
    for n in (4, 6, 9):                         # stacks of 3..8, padded
        for _round in range(4):
            together(n)
    assert bat.snapshot()["realized_peak"] > 2
    assert stacked._cache_size() == before + 3
    assert not batching_broken()
    shard.query_batcher = None


# ---------------------------------------------------------------------------
# config / passthrough
# ---------------------------------------------------------------------------


def test_disabled_by_config_is_true_passthrough():
    ms, shard = _mk_shard()
    pids = _part_ids(shard)
    steps0 = T0 + (K - 1) * STEP
    nsteps = 50 - K - 4
    starts = [steps0 + i * STEP for i in range(4)]
    shard.query_batcher = None
    solos = {i: np.asarray(shard.scan_grid(pids, F.RATE, s0, nsteps,
                                           STEP, WINDOW)[1])
             for i, s0 in enumerate(starts)}
    groups0 = batch_metrics()["groups"].total()
    bat = QueryBatcher(enabled=False, window_ms=500.0, max_batch=4,
                       dataset="prom")
    shard.query_batcher = bat
    for _ in range(2):
        outs = _concurrent(shard, pids, F.RATE, starts, nsteps)
    for i in range(4):
        assert outs[i].tobytes() == solos[i].tobytes()
    assert bat.snapshot()["realized_peak"] == 0
    assert not bat._groups and not bat._hot and not bat._inflight
    assert batch_metrics()["groups"].total() == groups0, \
        "disabled batcher must form no groups"
    # runtime re-enable via the same configure() the admin knob calls
    bat.configure(enabled=True)
    for _round in range(12):
        outs = _concurrent(shard, pids, F.RATE, starts, nsteps)
        for i in range(4):
            assert outs[i].tobytes() == solos[i].tobytes()
        if bat.snapshot()["realized_peak"] >= 2 and _round:
            break
    assert bat.snapshot()["realized_peak"] >= 2
    shard.query_batcher = None


# ---------------------------------------------------------------------------
# admission / deadline discipline (unit level on QueryBatcher)
# ---------------------------------------------------------------------------


class _FakePermit:
    def __init__(self, released=False):
        self.released = released


def _stack_launch(row0s, steps0s):
    """Synthetic stacked launch: member axis leading, value encodes
    (row0, steps0) so fan-out mistakes are visible."""
    return np.asarray([[r * 1000 + s] for r, s in
                       zip(np.asarray(row0s), np.asarray(steps0s))],
                      dtype=np.float64)


def _qctx_with(deadline_in_ms=None, permit=None):
    qc = QueryContext()
    if deadline_in_ms is not None:
        qc.deadline_ms = int(time.time() * 1000) + deadline_in_ms
    if permit is not None:
        qc.admission_permit = permit
    return qc


def test_pad_pow2():
    assert [_pad_pow2(n, 8) for n in (1, 2, 3, 5, 8, 9)] == \
        [1, 2, 4, 8, 8, 8]


def test_mixed_deadline_group_stacks_all_live_members():
    bat = QueryBatcher(enabled=True, window_ms=50.0, max_batch=8,
                       dataset="unit")
    g = _Group("k")
    # three live members with very different (but sufficient) budgets
    g.members = [_Member(1, 10, _qctx_with(deadline_in_ms=60_000)),
                 _Member(2, 20, _qctx_with(deadline_in_ms=600_000)),
                 _Member(3, 30, _qctx_with())]       # no deadline at all
    bat._launch_group(g, _stack_launch)
    assert [None if r is None else float(r[0]) for r in g.results] == \
        [1010.0, 2020.0, 3030.0]


def test_mid_batch_expiry_drops_members_from_the_stack():
    bat = QueryBatcher(enabled=True, window_ms=50.0, max_batch=8,
                       dataset="unit")
    fb0 = batch_metrics()["fallbacks"].total()
    g = _Group("k")
    g.members = [
        _Member(1, 10, _qctx_with(deadline_in_ms=60_000)),
        # permit released while the window was open
        _Member(2, 20, _qctx_with(permit=_FakePermit(released=True))),
        # deadline died while the window was open
        _Member(3, 30, _qctx_with(deadline_in_ms=-5)),
        _Member(4, 40, _qctx_with(deadline_in_ms=60_000)),
    ]
    bat._launch_group(g, _stack_launch)
    assert g.results[0] is not None and g.results[3] is not None
    assert g.results[1] is None and g.results[2] is None, \
        "expired members must be dropped from the stack"
    assert float(g.results[0][0]) == 1010.0
    assert float(g.results[3][0]) == 4040.0
    assert batch_metrics()["fallbacks"].total() == fb0 + 2


def test_group_of_expired_members_demotes_without_launching():
    bat = QueryBatcher(enabled=True, window_ms=50.0, max_batch=8,
                       dataset="unit")
    launched = []
    g = _Group("k")
    g.members = [_Member(1, 10, _qctx_with(deadline_in_ms=-5)),
                 _Member(2, 20, _qctx_with(deadline_in_ms=60_000))]
    bat._launch_group(g, lambda r, s: launched.append(1))
    assert g.results is None and not launched, \
        "<2 live members: the group demotes, nothing launches"


def test_short_deadline_joins_no_batch():
    bat = QueryBatcher(enabled=True, window_ms=100.0, max_batch=8,
                       dataset="unit")
    fb0 = batch_metrics()["fallbacks"].total()
    # remaining budget (40ms) cannot afford window (100ms) + slack
    got = bat.dispatch("k", 1, 10, _qctx_with(deadline_in_ms=40),
                       _stack_launch, lambda: "solo")
    assert got is None, "caller must run its own solo fallback"
    assert batch_metrics()["fallbacks"].total() == fb0 + 1


def test_cold_key_is_passthrough_solo():
    bat = QueryBatcher(enabled=True, window_ms=200.0, max_batch=8,
                       dataset="unit")
    t0 = time.monotonic()
    got = bat.dispatch("k", 1, 10, None, _stack_launch, lambda: "solo")
    assert got == "solo"
    assert time.monotonic() - t0 < 0.15, \
        "a cold key must not wait out the co-arrival window"
    assert not bat._groups


def test_solo_window_leader_falls_back():
    bat = QueryBatcher(enabled=True, window_ms=30.0, max_batch=8,
                       dataset="unit")
    fb0 = batch_metrics()["fallbacks"].total()
    bat._hot["k"] = time.monotonic() + 100.0     # force leading
    got = bat.dispatch("k", 1, 10, None, _stack_launch, lambda: "solo")
    assert got is None, "window expired alone: caller runs solo"
    assert batch_metrics()["fallbacks"].total() == fb0 + 1


def test_concurrent_twins_form_a_group():
    bat = QueryBatcher(enabled=True, window_ms=400.0, max_batch=2,
                       dataset="unit")
    bat._hot["k"] = time.monotonic() + 100.0
    outs: dict = {}
    barrier = threading.Barrier(2)

    def worker(i):
        barrier.wait()
        outs[i] = bat.dispatch("k", i + 1, (i + 1) * 10, None,
                               _stack_launch, lambda: "solo")

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    got = sorted(float(v[0]) for v in outs.values() if v is not None)
    assert got == [1010.0, 2020.0]
    assert bat.snapshot()["realized_peak"] == 2


# ---------------------------------------------------------------------------
# breaker ladder
# ---------------------------------------------------------------------------


def test_breaker_trip_demotes_group_and_opens_breaker():
    bat = QueryBatcher(enabled=True, window_ms=400.0, max_batch=2,
                       dataset="unit")
    bat._hot["k"] = time.monotonic() + 100.0
    fb0 = batch_metrics()["fallbacks"].total()

    def boom(row0s, steps0s):
        raise RuntimeError("vmapped program exploded")

    outs: dict = {}
    barrier = threading.Barrier(2)

    def worker(i):
        barrier.wait()
        outs[i] = bat.dispatch("k", i + 1, (i + 1) * 10, None, boom,
                               lambda: "solo")

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    # the whole group demoted: every member's caller runs its solo
    assert list(outs.values()) == [None, None]
    assert batching_broken(), "a batched-path error must open the breaker"
    assert batch_metrics()["fallbacks"].total() >= fb0 + 2
    assert bat.snapshot()["breaker_open"]
    # while open, every dispatch is an immediate fallback
    assert bat.dispatch("k", 9, 90, None, _stack_launch,
                        lambda: "solo") is None
    reset_batch_breaker()
    assert not batching_broken()
    got = bat.dispatch("k2", 1, 10, None, _stack_launch, lambda: "solo")
    assert got == "solo"     # cold key passthrough works again


def test_breaker_trip_end_to_end_serves_solo(monkeypatch):
    """A failing vmapped device program must demote to the per-query
    chain and serve bytes identical to an unbatched serve."""
    ms, shard = _mk_shard()
    pids = _part_ids(shard)
    steps0 = T0 + (K - 1) * STEP
    nsteps = 50 - K - 4
    starts = [steps0 + i * STEP for i in range(4)]
    shard.query_batcher = None
    solos = {i: np.asarray(shard.scan_grid(pids, F.RATE, s0, nsteps,
                                           STEP, WINDOW)[1])
             for i, s0 in enumerate(starts)}
    from filodb_tpu.memstore import devicestore as dvs
    dvs._fused_progs()          # populate the program memo first

    def boom(*a, **kw):
        raise RuntimeError("batched program failure injected")

    monkeypatch.setitem(dvs._FUSED_PROGS, "series_batch", boom)
    bat = QueryBatcher(enabled=True, window_ms=500.0, max_batch=4,
                       hot_ttl_s=30.0, dataset="prom")
    shard.query_batcher = bat
    for _ in range(2):
        outs = _concurrent(shard, pids, F.RATE, starts, nsteps)
    for i in range(4):
        assert outs[i] is not None
        assert outs[i].tobytes() == solos[i].tobytes(), \
            f"member {i}: demoted result differs from solo"
    shard.query_batcher = None
