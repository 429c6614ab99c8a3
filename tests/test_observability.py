"""Metrics primitives, exposition-format correctness, trace forensics.

ISSUE 2 satellites: Prometheus text-exposition grammar + histogram
invariants, the Gauge set_fn-under-lock deadlock regression, scheduler
saturation metrics, and the TraceStore/slow-log/profiler units."""

import re
import sys
import threading
import time

import pytest

from filodb_tpu.utils.forensics import (TraceStore, profile, span_from_dict,
                                        span_to_dict)
from filodb_tpu.utils.observability import (REGISTRY, MetricsRegistry,
                                            SpanRecord, Tracer)

# ---------------------------------------------------------------------------
# Exposition-format grammar (satellite: line-by-line correctness)
# ---------------------------------------------------------------------------

_COMMENT_RE = re.compile(
    r"^# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* (counter|gauge|histogram)$")
_METRIC_RE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?:\{(?P<labels>.*)\})? "
    r"(?P<value>NaN|[+-]Inf|-?[0-9]+(?:\.[0-9]+)?(?:[eE][+-]?[0-9]+)?)$")
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:\\.|[^"\\])*)"')


def _assert_exposition_valid(text: str) -> None:
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("#"):
            assert _COMMENT_RE.match(line), f"bad comment line: {line!r}"
            continue
        m = _METRIC_RE.match(line)
        assert m, f"line does not match exposition grammar: {line!r}"
        labels = m.group("labels")
        if labels is not None:
            # every byte of the label block must be consumed by
            # well-formed name="escaped-value" pairs
            rebuilt = ",".join(f'{k}="{v}"'
                               for k, v in _LABEL_RE.findall(labels))
            assert rebuilt == labels, f"malformed labels in: {line!r}"


class TestExposition:
    def test_label_escaping(self):
        reg = MetricsRegistry()
        c = reg.counter("esc_total")
        c.inc(path='with"quote', other="back\\slash", nl="a\nb")
        text = reg.expose_text()
        _assert_exposition_valid(text)
        assert '\\"' in text and "\\\\" in text and "\\n" in text
        # no RAW newline inside any metric line
        for line in text.splitlines():
            assert "\n" not in line

    def test_full_registry_parses(self):
        # the PROCESS registry: whatever every subsystem registered must
        # come out grammatically valid, line by line
        REGISTRY.counter("exp_probe_total").inc(dataset="p", weird='q"x')
        REGISTRY.histogram("exp_probe_seconds").observe(0.2, lane="a\\b")
        _assert_exposition_valid(REGISTRY.expose_text())

    def test_histogram_invariants(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat_seconds", buckets=(0.01, 0.1, 1.0))
        for v in (0.001, 0.01, 0.05, 0.1, 0.5, 2.0, 100.0):
            h.observe(v, op="x")
        lines = reg.expose_text().splitlines()
        buckets = {}
        count = total_sum = None
        for ln in lines:
            m = _METRIC_RE.match(ln)
            if not m:
                continue
            if m.group("name") == "lat_seconds_bucket":
                le = dict(_LABEL_RE.findall(m.group("labels")))["le"]
                buckets[le] = float(m.group("value"))
            elif m.group("name") == "lat_seconds_count":
                count = float(m.group("value"))
            elif m.group("name") == "lat_seconds_sum":
                total_sum = float(m.group("value"))
        # le="b" means value <= b: boundary observations fall IN bucket
        assert buckets["0.01"] == 2          # 0.001, 0.01
        assert buckets["0.1"] == 4           # + 0.05, 0.1
        assert buckets["1.0"] == 5           # + 0.5
        assert buckets["+Inf"] == 7
        # cumulative monotone + count == +Inf bucket
        seq = [buckets["0.01"], buckets["0.1"], buckets["1.0"],
               buckets["+Inf"]]
        assert seq == sorted(seq)
        assert count == buckets["+Inf"] == 7
        assert total_sum == pytest.approx(sum(
            (0.001, 0.01, 0.05, 0.1, 0.5, 2.0, 100.0)))

    def test_histogram_unsorted_buckets_normalized(self):
        reg = MetricsRegistry()
        h = reg.histogram("uns_seconds", buckets=(1.0, 0.1, 0.01))
        assert h.buckets == (0.01, 0.1, 1.0)
        h.observe(0.05)
        assert h._counts[()][1] == 1  # bisect lands in the 0.1 bucket


class TestGaugeLock:
    def test_set_fn_touching_gauge_does_not_deadlock(self):
        """Regression (satellite 1): expose()/total() used to call the
        registered set_fn callbacks while holding the gauge lock, so a
        callback touching the same gauge deadlocked the scrape."""
        reg = MetricsRegistry()
        g = reg.gauge("self_referential")

        def cb():
            g.set(5.0, which="side_effect")  # takes the gauge lock
            return 7.0

        g.set_fn(cb, which="cb")
        out = []

        def scrape():
            out.append(g.expose())
            out.append(g.total())

        t = threading.Thread(target=scrape, daemon=True)
        t.start()
        t.join(timeout=5)
        assert not t.is_alive(), \
            "gauge scrape deadlocked calling its own set_fn"
        assert out[1] == 7.0 + 5.0


class TestSchedulerSaturationMetrics:
    def test_queue_depth_gauge_and_rejection_counter(self):
        from filodb_tpu.query.scheduler import QueryRejected, QueryScheduler
        s = QueryScheduler(num_workers=1, max_queued=2, name="satsched")
        try:
            gate = threading.Event()
            started = threading.Event()
            s.submit(lambda: started.set() or gate.wait(5))
            started.wait(5)
            s.submit(lambda: 1)
            s.submit(lambda: 2)
            depth = REGISTRY.gauge("filodb_query_queue_depth")
            assert depth.value(scheduler="satsched") == 2
            rej = REGISTRY.counter("filodb_queries_rejected_total")
            before = rej.value(scheduler="satsched", reason="full")
            with pytest.raises(QueryRejected):
                s.submit(lambda: 3)
            assert rej.value(scheduler="satsched",
                             reason="full") == before + 1
            gate.set()
        finally:
            s.shutdown()
        # shutdown must deregister the depth callback: no row for a
        # dead scheduler, no bound method keeping it alive
        text = "\n".join(REGISTRY.gauge("filodb_query_queue_depth")
                         .expose())
        assert 'scheduler="satsched"' not in text


# ---------------------------------------------------------------------------
# Trace forensics
# ---------------------------------------------------------------------------


class TestTraceStore:
    def _traced(self, store, fn):
        tracer = Tracer()
        tracer.add_reporter(store.report)
        tid = tracer.new_trace_id()
        with tracer.attach((tid, None)):
            fn(tracer)
        return tid

    def test_tree_nesting_and_untraced_spans_skipped(self):
        store = TraceStore()

        def work(tracer):
            with tracer.span("root", dataset="p"):
                with tracer.span("child"):
                    pass
                with tracer.span("child2"):
                    pass

        tid = self._traced(store, work)
        # spans with no trace id never enter the store
        store.report([SpanRecord("orphan", 0, 0.1, {}, None)])
        tree = store.tree(tid)
        assert len(tree) == 1 and tree[0]["name"] == "root"
        kids = [c["name"] for c in tree[0]["children"]]
        assert kids == ["child", "child2"]
        assert tid not in ("", None) and store.tree("nope") == []

    def test_slowlog_threshold(self):
        store = TraceStore(slow_threshold_s=0.5)
        tid = self._traced(
            store, lambda tr: tr.span("q").__enter__().__exit__(
                None, None, None))
        store.note_complete(tid, 0.1, query="fast")
        assert store.slowlog() == []
        store.note_complete(tid, 0.9, query="slow", dataset="prom")
        log = store.slowlog()
        assert len(log) == 1
        assert log[0]["query"] == "slow"
        assert log[0]["trace_id"] == tid
        assert log[0]["tree"] and log[0]["tree"][0]["name"] == "q"

    def test_ingest_remote_dedups_and_stitches(self):
        store = TraceStore()
        tid = "feedfeedfeedfeed"
        local = SpanRecord("dispatch.http", 0, 1.0, {}, None,
                           trace_id=tid, span_id="aaa")
        store.report([local])
        remote = [{"name": "execplan.execute", "start_s": 0.1,
                   "duration_s": 0.5, "tags": {"shard": "1"},
                   "trace_id": tid, "span_id": "bbb", "parent_id": "aaa"}]
        store.ingest_remote(tid, remote)
        store.ingest_remote(tid, remote)  # a second leaf returns it again
        spans = store.spans_for(tid)
        assert [r.span_id for r in spans] == ["aaa", "bbb"]
        tree = store.tree(tid)
        assert tree[0]["name"] == "dispatch.http"
        assert tree[0]["children"][0]["name"] == "execplan.execute"

    def test_bounded_traces(self):
        store = TraceStore(max_traces=4)
        for i in range(10):
            store.report([SpanRecord("s", 0, 0.1, {}, None,
                                     trace_id=f"t{i}", span_id=f"id{i}")])
        assert len(store.trace_ids()) == 4
        assert store.trace_ids()[-1] == "t9"

    def test_span_dict_roundtrip(self):
        rec = SpanRecord("n", 1.0, 2.0, {"a": 1}, None, error="E",
                         trace_id="t", span_id="s", parent_id="p")
        back = span_from_dict(span_to_dict(rec))
        assert back.name == "n" and back.trace_id == "t"
        assert back.span_id == "s" and back.parent_id == "p"
        assert back.error == "E" and back.tags == {"a": "1"}


def test_profile_returns_hot_frames():
    stop = threading.Event()

    def burn():
        while not stop.is_set():
            sum(i * i for i in range(500))

    t = threading.Thread(target=burn, daemon=True)
    t.start()
    try:
        out = profile(seconds=0.15, sample_interval_s=0.002)
    finally:
        stop.set()
        t.join(1)
    assert out["samples"] >= 1
    assert out["frames"] and {"file", "function", "samples", "pct"} <= \
        set(out["frames"][0])


def test_tracer_ids_and_attach():
    tracer = Tracer()
    recs = []
    tracer.add_reporter(recs.extend)
    tid = tracer.new_trace_id()
    with tracer.attach((tid, "parenthint")):
        with tracer.span("outer"):
            token = tracer.capture()
            with tracer.span("inner"):
                pass
    assert [r.name for r in recs] == ["inner", "outer"]
    inner, outer = recs
    assert outer.trace_id == inner.trace_id == tid
    assert outer.parent_id == "parenthint"  # hint parents the root span
    assert inner.parent_id == outer.span_id
    assert token == (tid, outer.span_id)
    # outside the attach the thread is clean again
    assert tracer.current_trace_id() is None


# ---------------------------------------------------------------------------
# The stage clock (PR 27): wall + CPU, the stage table, leaf annotations
# ---------------------------------------------------------------------------


class _FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: records enter/exit."""

    log: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _FakeAnnotation.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        _FakeAnnotation.log.append(("exit", self.name))
        return False


@pytest.fixture()
def tracer():
    t = Tracer()
    t.recs = []
    t.add_reporter(t.recs.extend)
    _FakeAnnotation.log = []
    t._annotate = _FakeAnnotation
    return t


def _burn(seconds: float) -> None:
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        sum(i * i for i in range(500))


class TestStageClock:
    def test_cpu_is_near_zero_across_a_sleep(self, tracer):
        with tracer.stage("sleeper", cpu=True):
            time.sleep(0.05)
        rec = tracer.recs[0]
        assert rec.duration_s >= 0.05
        assert 0.0 <= rec.cpu_s < 0.01, "a sleeping thread burns no CPU"

    def test_cpu_follows_wall_when_the_thread_is_busy(self, tracer):
        with tracer.stage("burner", leaf=False, cpu=True) as sp:
            _burn(0.03)
        # at most its wall, up to the two clocks' grain: the span does
        # not clamp (a clock that ticks in steps would read low in sum)
        assert 0.03 <= sp.cpu_s <= sp.duration_s + 0.002
        assert tracer.recs[0].cpu_s == sp.cpu_s

    def test_only_a_stage_that_asks_reads_the_cpu_clock(self, tracer,
                                                        monkeypatch):
        """The CPU clock is a system call (6-15 us where gVisor answers
        it): plain spans and stages that do not ask never pay it."""
        calls = []
        real = time.thread_time
        monkeypatch.setattr(time, "thread_time",
                            lambda: calls.append(1) or real())
        with tracer.span("execplan.execute"):
            pass
        with tracer.stage("grid.readback"):
            pass
        with tracer.stage("scan", leaf=False):
            pass
        assert calls == []
        assert [r.cpu_s for r in tracer.recs] == [0.0, 0.0, 0.0]
        with tracer.stage("grid.plan", cpu=True):
            pass
        assert len(calls) == 2               # enter and exit, no cache

    def test_cpu_is_not_clamped_to_the_wall(self, tracer, monkeypatch):
        """A CPU clock in 10 ms ticks overshoots a short span as often
        as it undershoots: clamping would bias the sums low."""
        ticks = iter([0.00, 0.01])
        monkeypatch.setattr(time, "thread_time", lambda: next(ticks))
        with tracer.stage("grid.plan", cpu=True) as sp:
            pass
        assert sp.cpu_s == pytest.approx(0.01) and sp.duration_s < 0.01
        assert tracer.stages.snapshot()["grid.plan"]["cpu_s"] == \
            pytest.approx(0.01)

    def test_spans_wait_for_the_threads_outermost_one_to_end(self, tracer):
        """Finished spans are handed over in one list: when the thread's
        outermost span ends, when a stage that carries a query's timings
        does, when ``flush`` is called, or when 64 have gathered."""
        batches = []
        tracer.add_reporter(lambda recs: batches.append(
            [r.name for r in recs]))

        class Timings:
            got = []

            def note_timings(self, walls):
                self.got.extend(name for name, _ in walls)

        with tracer.stage("http.request", leaf=False):
            with tracer.attach((tracer.new_trace_id(), None)), \
                    tracer.span("query"):
                with tracer.stage("scan", leaf=False, timings=Timings()):
                    with tracer.stage("grid.plan"):
                        pass
                    with tracer.stage("grid.dispatch"):
                        pass
                    assert batches == [] and tracer.stages.snapshot() == {}
                assert batches == [["grid.plan", "grid.dispatch", "scan"]]
                assert Timings.got == ["grid.plan", "grid.dispatch", "scan"]
                with tracer.stage("serialize"):
                    pass
            assert batches[1:] == []     # an attached stack emptied: no
            tracer.flush()
            assert batches[1:] == [["serialize", "query"]]
            for _ in range(tracer.MAX_WAITING):
                with tracer.stage("http.encode"):
                    pass
            assert batches[2:] == [["http.encode"] * tracer.MAX_WAITING]
        assert batches[3:] == [["http.request"]]
        with tracer.stage("grid.build"):            # outermost: at once
            pass
        assert batches[4:] == [["grid.build"]]
        assert tracer.stages.snapshot()["grid.plan"]["count"] == 1

    def test_a_wait_only_stage_is_no_leaf(self, tracer):
        waited = tracer.stage("grid.lock_wait", leaf=False).begin()
        waited.end()
        with tracer.stage("batch.wait", leaf=False, role="member"):
            pass
        assert _FakeAnnotation.log == []
        assert set(tracer.stages.snapshot()) == {"grid.lock_wait",
                                                 "batch.wait"}

    def test_start_is_stamped_at_enter(self, tracer):
        before = time.time()
        with tracer.span("s"):
            time.sleep(0.03)
        after = time.time()
        rec = tracer.recs[0]
        assert before <= rec.start_s <= after - 0.03
        # a synthetic span takes its start from the caller, or ends now
        given = tracer.record("synthetic", 2.0, start_s=123.0)
        assert given.start_s == 123.0 and given.cpu_s == 0.0
        ended_now = tracer.record("synthetic", 2.0)
        assert ended_now.start_s == pytest.approx(time.time() - 2.0, abs=0.5)

    def test_duration_stays_readable_after_exit(self, tracer):
        with tracer.stage("query.plan") as sp:
            time.sleep(0.01)
        assert sp.duration_s >= 0.01
        assert sp.duration_s == tracer.recs[0].duration_s

    def test_begin_and_end_delimit_what_no_with_block_can(self, tracer):
        waited = tracer.stage("grid.lock_wait").begin()
        assert tracer.current_span() == "grid.lock_wait"
        waited.end()
        assert tracer.current_span() is None
        assert tracer.stages.snapshot()["grid.lock_wait"]["count"] == 1

    def test_a_plain_span_stays_out_of_the_stage_table(self, tracer):
        with tracer.span("query.execute"):
            pass
        assert tracer.stages.snapshot() == {}
        assert _FakeAnnotation.log == []

    def test_stage_table_adds_up_under_eight_threads(self, tracer):
        def work():
            for _ in range(200):
                with tracer.stage("grid.select"):
                    pass
                tracer.record("scheduler.queue_wait", 0.5, stage=True)
        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        table = tracer.stages.snapshot()
        assert table["grid.select"]["count"] == 1600
        sel = [r for r in tracer.recs if r.name == "grid.select"]
        assert table["grid.select"]["wall_s"] == pytest.approx(
            sum(r.duration_s for r in sel), rel=1e-9)
        assert table["grid.select"]["cpu_s"] == pytest.approx(
            sum(r.cpu_s for r in sel), rel=1e-9, abs=1e-12)
        assert table["scheduler.queue_wait"] == {
            "count": 1600, "wall_s": pytest.approx(800.0), "cpu_s": 0.0}

    def test_a_leaf_is_annotated_and_an_enclosing_stage_is_not(self, tracer):
        with tracer.stage("device_compute", leaf=False):
            with tracer.stage("grid.dispatch"):
                pass
            with tracer.stage("grid.readback"):
                pass
        assert _FakeAnnotation.log == [
            ("enter", "grid.dispatch"), ("exit", "grid.dispatch"),
            ("enter", "grid.readback"), ("exit", "grid.readback")]
        assert set(tracer.stages.snapshot()) == {
            "device_compute", "grid.dispatch", "grid.readback"}

    def test_a_failing_annotation_degrades_to_none(self, tracer):
        def broken(name):
            raise RuntimeError("no profiler here")
        tracer._annotate = broken
        with tracer.stage("grid.plan"):
            pass
        assert tracer._annotate is None      # not tried again
        with tracer.stage("grid.plan"):
            pass
        assert tracer.stages.snapshot()["grid.plan"]["count"] == 2

    def test_jax_missing_means_no_annotation(self, tracer, monkeypatch):
        from filodb_tpu.utils import observability as obs
        monkeypatch.setattr(obs, "_jax_annotation", lambda: None)
        tracer._annotate = obs._jax_annotation
        with tracer.stage("grid.plan"):
            pass
        assert tracer.recs and tracer.recs[0].error is None

    def test_a_stage_inside_a_query_lands_in_its_timings(self, tracer):
        from filodb_tpu.memstore.memstore import TimeSeriesMemStore
        from filodb_tpu.query import exec as qexec
        ctx = qexec.ExecContext(TimeSeriesMemStore())
        with tracer.stage("query.plan"):            # before the scan
            pass
        with tracer.span("execplan.execute"):
            with tracer.stage("scan", leaf=False, timings=ctx):
                with tracer.stage("grid.select"):
                    time.sleep(0.002)
                with tracer.stage("device_compute", leaf=False):
                    with tracer.stage("grid.select"):
                        pass
                with tracer.span("odp.pagein"):     # a plain span: no key
                    pass
            with tracer.stage("serialize"):         # after it
                pass
        assert set(ctx._timings) == {"grid.select", "device_compute",
                                     "scan"}
        walls = {}
        for r in tracer.recs:
            walls[r.name] = walls.get(r.name, 0.0) + r.duration_s
        for name in ctx._timings:
            assert ctx._timings[name] == pytest.approx(walls[name])

    def test_nested_scans_each_take_their_own_stages(self, tracer):
        from filodb_tpu.memstore.memstore import TimeSeriesMemStore
        from filodb_tpu.query import exec as qexec
        outer = qexec.ExecContext(TimeSeriesMemStore())
        inner = qexec.ExecContext(TimeSeriesMemStore())
        with tracer.stage("scan", leaf=False, timings=outer):
            with tracer.stage("grid.plan"):
                pass
            with tracer.stage("scan", leaf=False, timings=inner):
                with tracer.stage("grid.dispatch"):
                    pass
            with tracer.stage("grid.select"):
                pass
        assert set(inner._timings) == {"grid.dispatch", "scan"}
        assert set(outer._timings) == {"grid.plan", "grid.select", "scan"}

    def test_a_stage_outside_any_query_does_not_raise(self, tracer):
        with tracer.stage("http.encode"):
            pass
        assert tracer.stages.snapshot()["http.encode"]["count"] == 1

    def test_the_scan_stage_feeds_the_query_it_runs_for(self):
        from filodb_tpu.query import exec as qexec
        assert len(qexec.GRID_STAGES) == 8
        ctx = qexec.ExecContext(None)
        ctx.note_timings((("grid.plan", 0.25), ("grid.plan", 0.5),
                          ("scan", 1.0)))
        ctx.note_timing("scan", 1.0)
        assert ctx._timings == {"grid.plan": 0.75, "scan": 2.0}

    def test_stage_families_expose_in_valid_grammar(self, tracer):
        with tracer.stage('odd"name'):
            pass
        tracer.record("scheduler.queue_wait", 0.25, stage=True)
        text = "\n".join(tracer.stages.expose()) + "\n"
        _assert_exposition_valid(text)
        assert ('filodb_stage_seconds_total{kind="wall",'
                'stage="scheduler.queue_wait"} 0.25') in text
        assert 'filodb_stage_total{stage="scheduler.queue_wait"} 1' in text
        assert 'kind="cpu"' in text

    def test_process_registry_carries_the_stage_families(self):
        from filodb_tpu.utils.observability import TRACER
        with TRACER.stage("test.registry_probe", leaf=False):
            pass
        text = REGISTRY.expose_text()
        _assert_exposition_valid(text)
        assert 'filodb_stage_total{stage="test.registry_probe"}' in text

    def test_span_dict_roundtrip_keeps_cpu(self):
        rec = SpanRecord("grid.plan", 10.0, 0.5, {}, None, trace_id="t",
                         span_id="s", cpu_s=0.125)
        assert span_to_dict(rec)["cpu_s"] == 0.125
        assert span_from_dict(span_to_dict(rec)).cpu_s == 0.125
        assert span_from_dict({"name": "old-node"}).cpu_s == 0.0

    def test_the_cpu_clock_is_the_threads_to_the_digit(self, tracer,
                                                       monkeypatch):
        """``cpu_s`` is ``thread_time``'s difference over the span, and
        the stage row sums it unchanged (``host_cpu_ms_per_query``)."""
        ticks = iter([1.0, 1.25])
        monkeypatch.setattr(time, "thread_time", lambda: next(ticks))
        with tracer.stage("http.request", leaf=False, cpu=True) as sp:
            pass
        assert sp.cpu_s == 0.25
        assert tracer.stages.snapshot()["http.request"] == {
            "count": 1, "wall_s": sp.duration_s, "cpu_s": 0.25}

    # -- spans that begin before their thread's first span

    def test_a_span_opened_off_the_stack_joins_the_next_flush(self, tracer):
        """``opened`` / ``closed`` / ``adopt``: an interval that began on
        another thread or before the code that owns it; it folds in with
        what its thread finishes next, in the trace it is given."""
        batches = []
        tracer.add_reporter(lambda recs: batches.append(
            [r.name for r in recs]))
        t0 = time.perf_counter()
        time.sleep(0.002)
        t1 = time.perf_counter()
        accept = tracer.stage("http.accept", leaf=False).opened(t0) \
            .closed(t1)
        read = tracer.stage("http.read").opened(t1)
        assert _FakeAnnotation.log == [("enter", "http.read")]
        read.closed()
        assert _FakeAnnotation.log[-1] == ("exit", "http.read")
        assert accept.duration_s == pytest.approx(t1 - t0)
        assert tracer.current_span() is None       # never on the stack
        token = (tracer.new_trace_id(), "00000000000000aa")
        with tracer.stage("http.request", leaf=False):
            tracer.adopt(token, (accept, read))
            assert batches == []                   # waits for the flush
        assert batches == [["http.accept", "http.read", "http.request"]]
        recs = {r.name: r for r in tracer.recs}
        for name in ("http.accept", "http.read"):
            assert recs[name].trace_id == token[0]
            assert recs[name].parent_id == token[1]
        assert recs["http.read"].start_s == pytest.approx(
            recs["http.accept"].start_s + accept.duration_s, abs=1e-6)
        assert tracer.stages.snapshot()["http.accept"]["count"] == 1
        # outside any span, adopting flushes at once
        tracer.adopt(None, (tracer.stage("http.read").opened().closed(),))
        assert tracer.stages.snapshot()["http.read"]["count"] == 2
        assert tracer.recs[-1].trace_id is None

    # -- a failing annotation is said, once, and shown

    def test_a_failing_annotation_is_said_once(self, tracer, capsys):
        raised = []

        def flaky(name):
            if not raised:
                raised.append(name)
                raise RuntimeError("profiler gone")
            return _FakeAnnotation(name)

        tracer._annotate = flaky
        assert tracer.annotating
        for _ in range(3):
            with tracer.stage("grid.dispatch"):
                pass
        err = capsys.readouterr().err
        assert err.count("leaf annotations off") == 1
        assert "profiler gone" in err and "grid.dispatch" in err
        assert not tracer.annotating
        assert tracer.stages.snapshot()["grid.dispatch"]["count"] == 3

    def test_admin_device_shows_whether_leaves_are_annotated(self,
                                                             monkeypatch):
        from filodb_tpu.utils import devicewatch
        from filodb_tpu.utils.observability import TRACER
        monkeypatch.setattr(TRACER, "_annotate", _FakeAnnotation)
        assert devicewatch.device_summary()["annotations"] is True
        monkeypatch.setattr(TRACER, "_annotate", None)
        summary = devicewatch.device_summary()
        assert summary["annotations"] is False and "stages" in summary


class TestStallWatch:
    """A stop of the interpreter for longer than the limit becomes a
    ``host.stall`` span and one line that says what kind of stop."""

    def _stalled(self, tracer, hold):
        import io
        from filodb_tpu.utils.observability import StallWatch
        out = io.StringIO()
        watch = StallWatch(tracer, out=out)
        watch.TICK_S, watch.LIMIT_S = 0.02, 0.15
        watch.start()
        try:
            time.sleep(0.1)            # ticks that are on time: no news
            assert watch.stalls == []
            hold()
            deadline = time.time() + 5
            while not watch.stalls and time.time() < deadline:
                time.sleep(0.01)
        finally:
            watch.stop()
        return watch, out.getvalue()

    def test_a_held_interpreter_is_a_stall_with_its_cpu(self, tracer):
        import re

        def hold():                    # one C call that keeps the lock
            re.match(r"(a*)*b", "a" * 24)

        t0 = time.perf_counter()
        hold()
        took = time.perf_counter() - t0
        if took < 0.3:
            pytest.skip(f"the hold took only {took:.2f} s here")
        watch, line = self._stalled(tracer, hold)
        (stall,) = watch.stalls[:1]
        assert stall["late_s"] > 0.15
        # a thread computed: the process's CPU is of the wall's order
        assert stall["process_cpu_s"] > 0.5 * stall["late_s"]
        row = tracer.stages.snapshot()["host.stall"]
        assert row["count"] >= 1 and row["wall_s"] >= stall["late_s"] * 0.99
        assert line.startswith("host stall: {") and "MainThread[" in line

    def test_ticks_on_time_report_nothing(self, tracer):
        watch, line = self._stalled(tracer, lambda: time.sleep(0.3))
        assert watch.stalls == [] and line == ""
        assert "host.stall" not in tracer.stages.snapshot()

    def test_boot_installs_one(self):
        from filodb_tpu.utils import observability as obs
        a = obs.install_stall_watch()
        assert obs.install_stall_watch() is a and a._thread.is_alive()

    # -- interp.wait: every tick's lateness, one reacquisition a sample

    def _sampling(self, tracer, tick=0.01):
        from filodb_tpu.utils.observability import StallWatch
        watch = StallWatch(tracer, out=None)
        watch.TICK_S = tick
        return watch

    @staticmethod
    def _row(tracer):
        return tracer.stages.snapshot().get(
            "interp.wait", {"count": 0, "wall_s": 0.0})

    def test_each_tick_is_one_interp_wait(self, tracer, monkeypatch):
        watch = self._sampling(tracer)
        ticks = []
        monkeypatch.setattr(watch, "_collected", lambda: ticks.append(1)
                            or 0.0)
        watch.start()
        time.sleep(0.2)
        watch.stop()
        watch._thread.join(5)
        assert not watch._thread.is_alive()
        row = self._row(tracer)
        # the last tick's wait is cut short by the stop and samples nothing
        assert len(ticks) - 1 <= row["count"] <= len(ticks)
        assert row["count"] >= 5
        assert row["wall_s"] >= 0.0
        assert "host.stall" not in tracer.stages.snapshot()
        # no trace id: no trace keeps ten records a second
        assert all(r.trace_id is None for r in tracer.recs)

    def test_a_held_interpreter_reads_about_the_switch_interval(
            self, tracer):
        """A thread that runs pure Python keeps the interpreter until a
        waiter has sat out the switch interval and asked for it: each
        sample reads at least most of that interval."""
        interval = 0.02
        old = sys.getswitchinterval()
        watch = self._sampling(tracer)
        watch.start()
        try:
            time.sleep(0.05)
            sys.setswitchinterval(interval)
            before = self._row(tracer)
            end = time.perf_counter() + 0.6
            n = 0
            while time.perf_counter() < end:    # bytecode, no release
                n += 1
            after = self._row(tracer)
        finally:
            sys.setswitchinterval(old)
            watch.stop()
            watch._thread.join(5)
        assert not watch._thread.is_alive()
        count = after["count"] - before["count"]
        assert count >= 3
        mean = (after["wall_s"] - before["wall_s"]) / count
        assert mean >= 0.6 * interval, (mean, count)

    def test_the_sample_leaves_out_the_watchs_own_reads(self, tracer,
                                                        monkeypatch):
        """``/proc/stat``, the process clock and the collector's seconds
        are read before the reference: a slow read is no lateness."""
        watch = self._sampling(tracer)
        slow = 0.1
        monkeypatch.setattr(watch, "_machine",
                            lambda: time.sleep(slow) or None)
        watch.start()
        time.sleep(0.5)
        watch.stop()
        watch._thread.join(5)
        assert not watch._thread.is_alive()
        row = self._row(tracer)
        assert row["count"] >= 2
        assert row["wall_s"] / row["count"] < slow / 2

    @pytest.mark.parametrize("collector,sampled", [
        ("a full one began and ended", False),
        ("a full one runs its finalizers", False),
        ("young ones only", True)])
    def test_a_tick_a_full_collection_overlapped_is_no_sample(
            self, tracer, monkeypatch, collector, sampled):
        """A full collection's seconds are ``gc.pause``'s: one such tick
        would outweigh hundreds of others in the mean.  A collection runs
        its finalizers' Python code before its end, so the watch may read
        in the middle of one.  A young one is part of the holder's turn
        and stays in."""
        from filodb_tpu.utils import observability as obs

        class Collector:
            seconds = [0.0, 0.0, 0.0]

            def __init__(self):
                self.reads, self.edges = 0, 0

            @property
            def full_edges(self):
                self.reads += 1
                if collector == "a full one began and ended":
                    self.edges += 2
                elif collector == "a full one runs its finalizers":
                    self.edges = 1
                return self.edges

        gc_watch = Collector()
        monkeypatch.setattr(obs, "_GC_WATCH", gc_watch)
        watch = self._sampling(tracer)
        watch.start()
        time.sleep(0.2)
        watch.stop()
        watch._thread.join(5)
        assert not watch._thread.is_alive()
        assert gc_watch.reads >= 10             # five ticks at least
        count = self._row(tracer)["count"]
        assert (count >= 5) if sampled else (count == 0), count

    def test_a_tick_over_the_limit_is_a_stall_and_no_sample(self, tracer):
        import io
        from filodb_tpu.utils.observability import StallWatch
        out = io.StringIO()
        watch = StallWatch(tracer, out=out)
        watch.TICK_S, watch.LIMIT_S = 0.01, -1.0    # every tick is over
        watch.start()
        time.sleep(0.2)
        watch.stop()
        watch._thread.join(5)
        table = tracer.stages.snapshot()
        assert table["host.stall"]["count"] >= 3
        assert "interp.wait" not in table
        assert out.getvalue().count("host stall: {") == len(
            [r for r in tracer.recs if r.name == "host.stall"])


class TestGcPauseWatch:
    def _watch(self, tracer):
        from filodb_tpu.utils.observability import GcPauseWatch
        return GcPauseWatch(tracer)

    def test_a_forced_full_collection_is_recorded(self, tracer):
        import gc
        watch = self._watch(tracer)
        gc.callbacks.append(watch)
        try:
            gc.collect()
        finally:
            gc.callbacks.remove(watch)
        # the hook reports nothing itself: the span waits for a flush
        assert tracer.recs == [] and len(tracer._deferred) == 1
        assert watch.full_edges == 2            # began and ended: even
        row = tracer.stages.snapshot()["gc.pause"]     # a read drains it
        recs = [r for r in tracer.recs if r.name == "gc.pause"]
        assert len(recs) == 1
        assert recs[0].tags["generation"] == 2
        assert 0.0 <= recs[0].cpu_s <= recs[0].duration_s + 0.002
        assert row["count"] == 1
        assert row["wall_s"] == pytest.approx(recs[0].duration_s)
        assert watch.seconds[2] == pytest.approx(recs[0].duration_s)
        # a full collection is a leaf on the profiler's clock too
        assert _FakeAnnotation.log == [("enter", "gc.pause"),
                                       ("exit", "gc.pause")]

    @pytest.mark.parametrize("held", ["stage_table", "trace_store"])
    def test_a_collection_under_a_tracing_lock_cannot_deadlock(
            self, tracer, held):
        """The hook runs on whichever thread set the collection off, at
        whatever point it had reached: inside the stage table's lock
        or the trace store's.  Neither is reentrant, so the hook may
        take neither."""
        import gc
        store = TraceStore()
        tracer.add_reporter(store.report)
        lock = {"stage_table": tracer.stages._lock,
                "trace_store": store._lock}[held]
        watch = self._watch(tracer)
        done = threading.Event()

        def collect_under_the_lock():
            with tracer.attach((tracer.new_trace_id(), None)):
                with tracer.stage("http.request", leaf=False):
                    gc.callbacks.append(watch)
                    try:
                        with lock:
                            gc.collect()
                    finally:
                        gc.callbacks.remove(watch)
            done.set()

        t = threading.Thread(target=collect_under_the_lock, daemon=True)
        t.start()
        assert done.wait(10.0), "the collector's hook waited for a lock " \
                                "its own thread holds"
        # and the span was not lost: the thread's next flush carried it
        # into the trace it interrupted
        pause = [r for r in tracer.recs if r.name == "gc.pause"]
        assert len(pause) == 1 and pause[0].trace_id
        assert [r.name for r in store.spans_for(pause[0].trace_id)] == [
            "gc.pause", "http.request"]
        assert tracer.stages.snapshot()["gc.pause"]["count"] == 1

    def test_a_quick_young_collection_only_counts(self, tracer):
        """No span, no lock: two clock reads and an add."""
        watch = self._watch(tracer)
        watch("start", {"generation": 0})
        watch("stop", {"generation": 0, "collected": 3})
        assert tracer.recs == [] and _FakeAnnotation.log == []
        assert not tracer._deferred
        assert watch.seconds[0] > 0.0 and watch.seconds[1:] == [0.0, 0.0]
        watch("start", {"generation": 2})
        watch("stop", {"generation": 2, "collected": 0})
        tracer.flush_deferred()
        assert [r.tags["generation"] for r in tracer.recs] == [2]

    def test_a_slow_young_collection_is_recorded(self, tracer):
        watch = self._watch(tracer)
        watch("start", {"generation": 1})
        time.sleep(watch.SLOW_S * 1.5)
        watch("stop", {"generation": 1, "collected": 0})
        with tracer.span("the next span on any thread"):
            pass
        assert [r.tags["generation"] for r in tracer.recs
                if r.name == "gc.pause"] == [1]
        assert tracer.recs[0].duration_s > watch.SLOW_S
        assert tracer.recs[0].cpu_s == 0.0     # read for full ones only

    def test_a_stop_without_its_start_is_ignored(self, tracer):
        self._watch(tracer)("stop", {"generation": 2})
        tracer.flush_deferred()
        assert tracer.recs == []

    def test_seconds_by_generation_expose_in_valid_grammar(self, tracer):
        watch = self._watch(tracer)
        watch.seconds[:] = [0.25, 0.0, 1.5]
        text = "\n".join(watch.expose()) + "\n"
        _assert_exposition_valid(text)
        assert 'filodb_gc_pause_seconds_total{generation="0"} 0.25' in text
        assert 'generation="2"} 1.5' in text and 'generation="1"' not in text

    def test_install_is_idempotent(self):
        import gc
        from filodb_tpu.utils.observability import install_gc_watch
        try:
            a = install_gc_watch()
            b = install_gc_watch()
            assert a is b and gc.callbacks.count(a) == 1
        finally:
            gc.callbacks.remove(a)
