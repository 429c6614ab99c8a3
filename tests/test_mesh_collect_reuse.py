"""What a fabric request needs from a shard is derived once a (lookup
result, shard state, query shape, grouping) and kept (ISSUE 35): a
repeated workspace-wide aggregate is handed the SAME ``MeshShardPlan``
objects and makes no array as wide as a shard's lanes inside
``mesh.collect``; whatever changes the shard's state makes the next
request build again, and its answer is the per-shard rung's bit for bit;
namespaces turning the memos over do not push the wide entries out.

Values are dyadic (integers scaled by 2^-3), so every f64 sum is exact at
any summation order and the fabric's psum, its host reduce and the
per-shard rung give identical bits (as in tests/test_meshfabric.py).

Runs on the 8-device virtual CPU mesh from tests/conftest.py.
"""

import numpy as np
import pytest

from filodb_tpu.coordinator.planner import SingleClusterPlanner
from filodb_tpu.core.record import RecordBuilder
from filodb_tpu.core.schemas import DEFAULT_SCHEMAS, DatasetOptions
from filodb_tpu.integrity import QUARANTINE
from filodb_tpu.memstore import devicestore
from filodb_tpu.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.memstore.shard import TimeSeriesShard
from filodb_tpu.parallel import meshgrid
from filodb_tpu.parallel.mesh import MeshEngine, make_mesh
from filodb_tpu.parallel.meshexec import MeshAggregateExec
from filodb_tpu.parallel.shardmap import ShardMapper, shard_of_tags
from filodb_tpu.promql.parser import query_range_to_logical_plan
from filodb_tpu.query.exec import ExecContext
from filodb_tpu.query.model import QueryContext
from filodb_tpu.utils.observability import TRACER
from tests import oracle

BASE = 1_700_000_000_000
STEP = 10_000
N_ROWS = 90
START, END = BASE + 300_000, BASE + 800_000
SHARDS, SPREAD = 4, 1
NAMESPACES, INSTANCES = 44, 6          # 264 series, ~66 a shard

WIDE_SUM = 'sum(sum_over_time(rm{_ws_="w"}[1m]))'
WIDE_RATE = 'sum(rate(rm{_ws_="w"}[2m]))'


def ns_sum(j: int) -> str:
    return 'sum(sum_over_time(rm{_ws_="w",_ns_="ns%d"}[1m]))' % j


def _tags(j: int, k: int, metric: str = "rm") -> dict:
    return {"_metric_": metric, "_ws_": "w", "_ns_": f"ns{j}",
            "inst": f"i{k}", "grp": f"g{k % 3}"}


def _ingest(ms, tags: dict, ts, vals) -> None:
    opts = DatasetOptions()
    b = RecordBuilder(DEFAULT_SCHEMAS["gauge"], opts, container_size=1 << 20)
    b.add_series(list(map(int, ts)), [list(map(float, vals))], tags)
    shard = ms.get_shard("prom", shard_of_tags(tags, SHARDS, SPREAD, opts))
    for off, c in enumerate(b.containers()):
        shard.ingest_container(c, off)


def _values(rng, n: int) -> np.ndarray:
    return rng.integers(1, 1 << 40, n).astype(np.float64) / 8.0


def _mk_store(seed: int = 35):
    ms = TimeSeriesMemStore()
    for s in range(SHARDS):
        ms.setup("prom", DEFAULT_SCHEMAS, s)
    rng = np.random.default_rng(seed)
    ts = BASE + np.arange(N_ROWS) * STEP
    data = {}
    for j in range(NAMESPACES):
        for k in range(INSTANCES):
            vals = _values(rng, N_ROWS)
            data[(j, k)] = vals
            _ingest(ms, _tags(j, k), ts, vals)
    return ms, ShardMapper(SHARDS), data


@pytest.fixture(scope="module")
def engine():
    return MeshEngine(make_mesh())


def _planner(mapper, engine=None):
    provider = (lambda: engine) if engine is not None else None
    return SingleClusterPlanner("prom", mapper, DatasetOptions(),
                                spread_default=SPREAD,
                                mesh_engine_provider=provider)


def _run(planner, ms, promql, start=START, end=END, step=30_000):
    plan = query_range_to_logical_plan(promql, start, step, end)
    ep = planner.materialize(plan, QueryContext())
    result = ep.execute(ExecContext(ms, QueryContext()))
    out = {}
    for b in result.batches:
        for tags, ts, vals in b.to_series():
            out[tuple(sorted(tags.items()))] = (np.asarray(ts),
                                                np.asarray(vals))
    return type(ep).__name__, out


def _assert_biteq(fused: dict, plain: dict, msg: str = "") -> None:
    assert set(fused) == set(plain) and plain, msg
    for k in plain:
        np.testing.assert_array_equal(fused[k][0], plain[k][0], err_msg=msg)
        a = np.asarray(fused[k][1], dtype=np.float64)
        b = np.asarray(plain[k][1], dtype=np.float64)
        assert np.array_equal(np.isnan(a), np.isnan(b)), f"{msg} {k}: NaNs"
        assert a.tobytes() == b.tobytes(), f"{msg} {k}: not bit-equal"


def _spans(name: str) -> int:
    return TRACER.stages.snapshot().get(name, {}).get("count", 0)


class Fabric:
    """One store behind the fabric and, for the same data, behind the
    per-shard rung; ``ask`` notes the ``MeshShardPlan`` objects a request
    was served from and how many it BUILT."""

    def __init__(self, engine, monkeypatch):
        self.ms, self.mapper, self.data = _mk_store()
        self.fabric = _planner(self.mapper, engine)
        self.plain = _planner(self.mapper)
        self.plans: list = []
        for name in ("serve_grid_mesh", "serve_grid_mesh_presented"):
            monkeypatch.setattr(meshgrid, name, self._noting(
                getattr(meshgrid, name)))

    def _noting(self, serve):
        def noted(engine, plans, *args, **kw):
            self.plans = list(plans)
            return serve(engine, plans, *args, **kw)
        return noted

    def ask(self, promql: str):
        """(answer, the MeshShardPlans it was served from, the
        ``mesh.plan_build`` spans it opened)."""
        self.plans = []
        before = _spans("mesh.plan_build")
        root, out = _run(self.fabric, self.ms, promql)
        assert root == "MeshReduceExec", root
        return out, self.plans, _spans("mesh.plan_build") - before

    def per_shard(self, promql: str) -> dict:
        root, out = _run(self.plain, self.ms, promql)
        assert root != "MeshReduceExec"
        return out

    def caches(self):
        for s in range(SHARDS):
            shard = self.ms.get_shard("prom", s)
            for cache in shard.device_caches.values():
                yield shard, cache


@pytest.fixture()
def fab(engine, monkeypatch):
    return Fabric(engine, monkeypatch)


# ----------------------------------------------------------- (a) the reuse

@pytest.mark.parametrize("promql", [WIDE_SUM, WIDE_RATE],
                         ids=["sum_over_time", "rate"])
def test_repeated_wide_aggregate_is_handed_the_same_plans(fab, promql):
    first, built, n_built = fab.ask(promql)
    assert len(built) == SHARDS and n_built == SHARDS
    again, reused, n_again = fab.ask(promql)
    assert n_again == 0, "a repeated wide request built a shard plan"
    assert len(reused) == SHARDS
    assert all(a is b for a, b in zip(built, reused))
    _assert_biteq(again, first)
    if promql == WIDE_SUM:
        _assert_biteq(first, fab.per_shard(promql))


def test_two_panels_over_one_selection_share_the_rows(fab):
    """The wide rate and the wide sum_over_time are two grid plans over
    one lookup: each builds its shard plans once, and the rows the
    fabric keeps for them are ONE entry (the lanes and the groups are
    the same, whatever function steps them)."""
    fab.ask(WIDE_SUM)
    rows = len(meshgrid._ROWS_MEMO)
    _out, plans, built = fab.ask('sum(count_over_time(rm{_ws_="w"}[1m]))')
    assert built == SHARDS
    assert len(meshgrid._ROWS_MEMO) == rows


# ----------------------------------------------------- (b) the invalidators

def _ingest_batch(fab):
    ts = BASE + (N_ROWS + np.arange(3)) * STEP
    for (j, k) in [(0, 0), (1, 1), (2, 2), (3, 3)]:
        _ingest(fab.ms, _tags(j, k), ts, _values(np.random.default_rng(j), 3))


def _freeze(fab):
    for s in range(SHARDS):
        fab.ms.get_shard("prom", s).flush_all()


def _evict(fab):
    _freeze(fab)                   # evicted data must be flushed
    fab.ask(WIDE_SUM)              # the freeze's own rebuild is not the point
    epochs = [fab.ms.get_shard("prom", s).removal_epoch
              for s in range(SHARDS)]
    for s in range(SHARDS):
        shard = fab.ms.get_shard("prom", s)
        assert shard.mark_stopped_series(2 ** 62, 1) > 0    # all stopped
        assert shard.evict_partitions(1) == 1
    assert [fab.ms.get_shard("prom", s).removal_epoch
            for s in range(SHARDS)] == [e + 1 for e in epochs]


def _quarantine(fab):
    assert QUARANTINE.quarantine(b"no-series-of-this-store", 1)


def _repin(fab):
    for _shard, cache in fab.caches():
        cache.note_repin()


def _widths(cache) -> set:
    return {b.lanes for b in cache.blocks.values()} \
        | {blk.lanes for blk in cache._open.values()}


def _widen(fab):
    """A namespace of 200 instances on two shards: their lane count
    passes the 128 the blocks were built at."""
    ts = BASE + np.arange(N_ROWS) * STEP
    rng = np.random.default_rng(77)
    widths = {id(c): _widths(c) for _s, c in fab.caches()}
    for k in range(200):
        _ingest(fab.ms, _tags(NAMESPACES, k), ts, _values(rng, N_ROWS))
    fab.widths_before = widths


INVALIDATORS = {"ingest_batch": _ingest_batch, "freeze": _freeze,
                "eviction": _evict, "quarantine": _quarantine,
                "note_repin": _repin, "lane_width_growth": _widen}


@pytest.fixture()
def no_quarantine():
    yield
    QUARANTINE.clear()


# whose state the invalidator moves: every shard's, or only the shards
# the new rows landed on (the others rightly keep what they had)
EVERY_SHARD = {"freeze", "eviction", "quarantine", "note_repin"}
MOVES_THE_ANSWER = {"eviction", "lane_width_growth"}   # the batch's rows
#                                                        lie past END


@pytest.mark.parametrize("what", sorted(INVALIDATORS))
def test_after_an_invalidation_the_next_request_builds(fab, what,
                                                       no_quarantine):
    first, built, _n = fab.ask(WIDE_SUM)
    _again, _plans, n = fab.ask(WIDE_SUM)
    assert n == 0
    INVALIDATORS[what](fab)
    after, rebuilt, n_rebuilt = fab.ask(WIDE_SUM)
    assert n_rebuilt >= (SHARDS if what in EVERY_SHARD else 2), \
        f"{what}: the stale plans were served again"
    assert sum(a is not b for a, b in zip(built, rebuilt)) == n_rebuilt
    _assert_biteq(after, fab.per_shard(WIDE_SUM), what)
    a, b = (next(iter(x.values()))[1] for x in (after, first))
    assert (a.tobytes() != b.tobytes()) == (what in MOVES_THE_ANSWER), what
    if what == "lane_width_growth":
        grown = [c for _s, c in fab.caches()
                 if _widths(c) != fab.widths_before[id(c)]]
        assert len(grown) >= 1, "no cache's blocks changed width"
    # and the rebuilt plans are kept in their turn
    _out, kept, n_kept = fab.ask(WIDE_SUM)
    assert n_kept == 0 and all(a is b for a, b in zip(rebuilt, kept))


# ------------------------------------------------------- (c) the retention

def test_forty_namespaces_leave_the_wide_entries_in_their_memos(
        fab, monkeypatch):
    _first, built, _n = fab.ask(WIDE_SUM)
    lookups = []
    real = TimeSeriesShard._lookup_partitions_uncached

    def counted(self, filters, *a, **kw):
        lookups.append(len(filters))
        return real(self, filters, *a, **kw)
    monkeypatch.setattr(TimeSeriesShard, "_lookup_partitions_uncached",
                        counted)
    total = 0
    for j in range(40):
        _out, plans, n = fab.ask(ns_sum(j))
        # six instances at spread 1: two shards (now and then all on one),
        # and a panel that never ran before always builds
        assert 1 <= len(plans) <= 2 and n == len(plans)
        total += n
    assert total > 60 and len(lookups) == 80     # two shards asked each
    del lookups[:]
    _again, reused, n_again = fab.ask(WIDE_SUM)
    assert n_again == 0, "the namespaces pushed the wide plans out"
    assert all(a is b for a, b in zip(built, reused))
    assert lookups == [], "the wide lookup was walked again"
    for _shard, cache in fab.caches():
        wide = [p for p in cache._preps.values()
                if len(p["ids"]) > 2 * INSTANCES]
        assert len(wide) == 1, "the wide lane resolution was dropped"
        assert len(cache._plan_memo) <= cache._plan_memo.cap
        assert len(cache._preps) <= cache._preps.cap


def test_a_memo_full_of_equal_entries_turns_over_one_at_a_time():
    from filodb_tpu.utils.costmemo import CostMemo
    memo = CostMemo(3)
    for i in range(3):
        memo.put(i, f"v{i}", 10)
    assert memo.get(0) == "v0"             # 0 is now the newest
    memo.put(3, "v3", 10)
    assert 1 not in memo and len(memo) == 3    # ONE left: the oldest
    assert [memo.get(k) for k in (0, 2, 3)] == ["v0", "v2", "v3"]
    # a dear entry outlives the cheap ones' turnover, not for ever
    memo.put("dear", "d", 100)
    for i in range(10, 22):
        memo.put(i, "cheap", 10)
        assert len(memo) == 3
    assert "dear" in memo
    for i in range(22, 60):
        memo.put(i, "cheap", 10)
    assert "dear" not in memo
    memo.clear()
    assert len(memo) == 0 and memo.get(59) is None


def test_a_full_memo_hashes_no_key_it_already_holds():
    """A lookup's key holds its filters, hashed in Python: a put that
    looked every kept key up again to find its victim (an OrderedDict's
    iterators do) cost 0.2 ms a request on the one-chip cells."""
    from filodb_tpu.utils.costmemo import CostMemo
    hashed = []

    class Key:
        def __init__(self, i):
            self.i = i

        def __hash__(self):
            hashed.append(self.i)
            return hash(self.i)

        def __eq__(self, other):
            return self.i == other.i
    memo = CostMemo(64)
    keys = [Key(i) for i in range(200)]
    for k in keys[:100]:
        memo.put(k, k.i, 128)
    del hashed[:]
    for k in keys[100:]:
        memo.put(k, k.i, 128)
        assert memo.get(k) == k.i
    assert len(memo) == 64
    assert set(hashed) <= set(range(36, 200))      # only what comes or goes
    assert len(hashed) <= 100 * 6


# ------------------------------------------------ (d) the operators' answers

def _unsorted_lanes(fab):
    """Namespace panels BEFORE the first wide one: their series take the
    low lanes, and the workspace-wide lookup (in partition-id order) is
    no longer in lane order, so the plan's pairs are sorted and
    ``pid_of_lane`` goes through ``order``."""
    for j in (NAMESPACES - 1, NAMESPACES // 2, 7):
        fab.ask(ns_sum(j))


OPERATORS = {
    "sum": WIDE_SUM,
    "quantile_exact_members": 'quantile(0.75, sum_over_time('
                              'rm{_ws_="w",_ns_=~"ns(1|2|3)"}[1m]))',
    "topk_reads_pid_of_lane": 'topk(3, sum_over_time(rm{_ws_="w"}[1m]))',
    "by_g": 'sum by (grp)(sum_over_time(rm{_ws_="w"}[1m]))',
    "count_values": 'count_values("v", rm{_ws_="w",_ns_="ns5"})',
}


@pytest.mark.parametrize("lanes", ["in_lane_order", "unsorted"])
@pytest.mark.parametrize("op", sorted(OPERATORS))
def test_operator_answers_as_the_per_shard_rung(fab, op, lanes):
    if lanes == "unsorted":
        _unsorted_lanes(fab)
    promql = OPERATORS[op]
    serves = meshgrid.STATS["serves"]
    got, plans, _n = fab.ask(promql)
    assert meshgrid.STATS["serves"] == serves + 1 and plans
    if lanes == "unsorted" and "ns" not in promql:
        assert any(p.order is not None for p in plans)
    _assert_biteq(got, fab.per_shard(promql), op)
    again, _plans, n = fab.ask(promql)
    assert n == 0
    _assert_biteq(again, got, op)


def test_wide_sum_against_the_oracle(fab):
    got, _plans, _n = fab.ask(WIDE_SUM)
    ts = BASE + np.arange(N_ROWS) * STEP
    want = sum(oracle.range_fn("sum_over_time", ts, vals, START, END,
                               30_000, 60_000)
               for vals in fab.data.values())
    (_key, (_ts, vals)), = got.items()
    np.testing.assert_array_equal(vals, want)


def test_pid_of_lane_resolves_what_was_asked(fab):
    _unsorted_lanes(fab)
    _out, plans, _n = fab.ask(WIDE_SUM)
    for plan in plans:
        lanes = plan.cols
        assert (lanes[1:] > lanes[:-1]).all()
        asked = sorted(int(plan.pid_of_lane(int(c))) for c in lanes)
        assert asked == sorted(int(p) for p in plan.part_ids)
        free = set(range(plan.ncols)) - set(lanes.tolist())
        assert all(plan.pid_of_lane(c) == -1 for c in list(free)[:5])


# ------------------------------------- (e) nothing as wide as the lanes

_MAKERS = ("full", "empty", "zeros", "ones", "asarray", "array", "arange",
           "where", "argsort", "stack", "concatenate", "fromiter",
           "flatnonzero")


def test_repeated_wide_request_makes_no_lane_wide_array_in_collect(
        fab, monkeypatch):
    fab.ask(WIDE_SUM)
    fab.ask(ns_sum(3))
    wide_lanes = min(len(fab.ms.get_shard("prom", s).lookup_partitions(
        [], 0, 2 ** 62).part_ids) for s in range(SHARDS))
    assert wide_lanes > 4 * INSTANCES
    inside, made, hashed = [False], [], []
    real_collect = MeshAggregateExec._collect_plans

    def collect(self, ctx):
        inside[0] = True
        try:
            return real_collect(self, ctx)
        finally:
            inside[0] = False
    monkeypatch.setattr(MeshAggregateExec, "_collect_plans", collect)

    def watching(name):
        real = getattr(np, name)

        def watched(*args, **kw):
            out = real(*args, **kw)
            if inside[0] and getattr(out, "size", 0) >= wide_lanes:
                made.append((name, out.size))
            return out
        return watched
    for name in _MAKERS:
        monkeypatch.setattr(np, name, watching(name))
    real_fp = devicestore._ids_fingerprint
    monkeypatch.setattr(devicestore, "_ids_fingerprint",
                        lambda ids: hashed.append(len(ids)) or real_fp(ids))

    _out, _plans, n = fab.ask(WIDE_SUM)
    assert n == 0
    assert made == [], f"lane-wide arrays made inside mesh.collect: {made}"
    assert hashed == [], "a kept lookup result was fingerprinted again"
    # the watch does see what it is there for: a namespace request that
    # never ran before builds, a first wide one of another shape too
    _out, _plans, n = fab.ask(ns_sum(9))
    assert n >= 1 and made == []           # 6 lanes: nothing lane-wide
    _out, _plans, n = fab.ask('sum(max_over_time(rm{_ws_="w"}[1m]))')
    assert n == SHARDS and made, "the watch saw no build"
    # ... and of fingerprints only the new namespace lookup's, once
    assert hashed and max(hashed) <= INSTANCES
