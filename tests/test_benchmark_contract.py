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
    r"""(?:\.stage|\.defer|(?:TRACER|_tracer)\.record|\.add_timing"""
    r"""|timings\.setdefault|_leaf_annotation)\(\s*["']([\w.]+)["']""")


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
    missing = [n for n in _names(metric.get("args", {}))
               if n not in emitted]
    assert not missing, \
        f"{path.stem}: no stage, span or add_timing named {missing} under " \
        f"filodb_tpu/: the metric would read null"


@pytest.mark.parametrize("field", ["count", "wall_s", "cpu_s"])
def test_stage_rows_carry_the_field_the_readers_read(field):
    """``stage_delta`` reads a row's ``field`` out of ``stages`` of
    ``/admin/device``, and reads a missing one as 0: a column renamed
    would read 0 in every cell, not ``null``."""
    from filodb_tpu.utils.devicewatch import device_summary
    from filodb_tpu.utils.observability import TRACER
    with TRACER.stage("test.contract_probe", leaf=False, cpu=True):
        pass
    row = device_summary()["stages"]["test.contract_probe"]
    assert field in row and row["count"] >= 1
    read = {json.loads(p.read_text())["args"].get("field") for p in METRICS
            if json.loads(p.read_text())["reader"] == "stage_delta"}
    assert read <= {"count", "wall_s", "cpu_s"}


@pytest.mark.parametrize("span", [
    "http.spawn", "http.accept", "http.read", "interp.wait"])
def test_front_end_and_interpreter_stage_is_emitted(span, emitted):
    """The front end's spans from ``accept`` to the route and the
    interpreter's wait (doc/observability.md "Stage spans"), read
    by ``http_spawn_ms``, ``http_accept_ms``, ``http_read_ms`` and
    ``interp_wait_ms`` in every cell."""
    assert span in emitted


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


# every ``devicewatch.jit(..., program="<name>")`` of a source file
_NAMED = re.compile(r"""devicewatch\.jit,?[^)]*?program=["']([\w.]+)["']""",
                    re.S)
_FAMILY_SOURCES = {
    "devicestore.": ROOT / "filodb_tpu" / "memstore" / "devicestore.py",
    "meshgrid.": ROOT / "filodb_tpu" / "parallel" / "meshgrid.py"}
_HELPERS = _run_py_constant("HELPERS")
_STACKED = _run_py_constant("STACKED")


def test_run_py_counts_the_two_families_with_a_source():
    assert set(_run_py_constant("SERVING_FAMILIES")) == set(_FAMILY_SOURCES)
    assert sorted(_HELPERS) == ["devicestore.mesh_stage", "meshgrid.pad"]


@pytest.mark.parametrize("family", sorted(_FAMILY_SOURCES))
def test_every_program_of_a_family_is_named_for_it(family):
    """``device_dispatches`` finds a family's launches by the prefix of
    ``filodb_kernel_launches_total{program=...}``, leaves out the helper
    that answers no request by its exact name, and counts a stacked
    launch by its members: every serving program of ``meshgrid.py`` is
    ``meshgrid.*`` through ``devicewatch.jit``, none of them stacked (a
    fused mesh launch answers one request), and each family's helper is
    there once under the name ``run.py`` leaves out (the cases of
    ``benchmark/selftest/test_program_names.py``, held by tier-1)."""
    names = _NAMED.findall(_FAMILY_SOURCES[family].read_text())
    assert len(names) >= 7, names
    assert all(n.startswith(family) for n in names), names
    helper, = [h for h in _HELPERS if h.startswith(family)]
    assert names.count(helper) == 1, names
    stacked = sorted(n for n in names if _STACKED in n)
    if family == "meshgrid.":
        assert not stacked
        # the rungs dev4.mesh-wide is served by, and the sketch past
        # ``exact_members``
        assert {"meshgrid.fused", "meshgrid.grouped", "meshgrid.members",
                "meshgrid.quantile"} <= set(names)
    else:
        assert stacked == ["devicestore.grouped_batch",
                           "devicestore.series_batch"]


@pytest.mark.parametrize("span", [
    "mesh.collect", "mesh.stage", "mesh.assemble", "mesh.dispatch",
    "mesh.device_wait", "mesh.readback", "mesh.present",
    "mesh.plan_build", "mesh.prepare"])
def test_fabric_stage_span_is_emitted(span, emitted):
    """The mesh fabric's stage spans (PR 34, PR 35; doc/observability.md
    "Stage spans"), read by the ``mesh_*`` metrics of ``dev4.mesh-wide``."""
    assert span in emitted


@pytest.mark.parametrize("span", [
    "grid.tail_append", "grid.tail_build", "ingest.container",
    "ingest.visible"])
def test_live_edge_stage_span_is_emitted(span, emitted):
    """The open block's and the ingest path's stage spans (PR 37;
    doc/observability.md "Stage spans"), read by ``tail_append_ms``,
    ``tail_builds``, ``ingest_container_ms`` and ``visible_lag_ms`` of
    ``jmh1.live-edge``."""
    assert span in emitted


@pytest.mark.parametrize("span", ["grid.packed"])
def test_sliding_stage_span_is_emitted(span, emitted):
    """The packed programs' launch span (doc/observability.md
    "Stage spans"), read by ``packed_per_query`` and
    ``packed_dispatch_ms`` of ``jmh1.sliding``."""
    assert span in emitted


def test_the_append_program_is_a_helper_that_answers_no_request():
    """``devicestore.tail_append`` writes an open block's cells for the
    ingest thread: one name, through ``devicewatch.jit``, never stacked.
    ``run.py``'s ``HELPERS`` does not name it yet (a ``model_config`` PR
    may not edit ``run.py``), so ``device_dispatches`` counts a launch a
    container too many: it holds "at least the answered requests" all the
    same, and the ``benchmark`` issue that adds the name finds it here."""
    names = _NAMED.findall(_FAMILY_SOURCES["devicestore."].read_text())
    assert names.count("devicestore.tail_append") == 1
    assert _STACKED not in "devicestore.tail_append"
    from filodb_tpu.memstore import devicestore
    import jax.numpy as jnp
    import numpy as np
    cells = devicestore.APPEND_CELLS
    devicestore._tail_append(
        jnp.zeros((devicestore.BLOCK_BUCKETS, 8), jnp.int32),
        jnp.zeros((devicestore.BLOCK_BUCKETS, 8), jnp.float32),
        np.full((3, cells), devicestore.BLOCK_BUCKETS, np.int32),
        np.zeros(cells, np.float32))
    assert devicestore._TAIL_APPEND_FN._program == "devicestore.tail_append"
    assert "tail_append" not in devicestore._fused_progs()


_T0 = 1_700_000_000_000


@pytest.fixture()
def live_server():
    """One shard, four whole-number series, a threaded server."""
    from filodb_tpu.coordinator.planner import SingleClusterPlanner
    from filodb_tpu.core.record import RecordBuilder, decode_container
    from filodb_tpu.core.schemas import DEFAULT_SCHEMAS, DatasetOptions
    from filodb_tpu.http.server import DatasetBinding, FiloHttpServer
    from filodb_tpu.memstore.memstore import TimeSeriesMemStore
    from filodb_tpu.parallel.shardmap import ShardMapper, ShardStatus
    mapper = ShardMapper(1)
    mapper.register_node([0], "local")
    mapper.update_status(0, ShardStatus.ACTIVE)
    ms = TimeSeriesMemStore()
    ms.setup("prom", DEFAULT_SCHEMAS, 0)
    builder = RecordBuilder(DEFAULT_SCHEMAS["prom-counter"])
    for i in range(4):
        tags = {"__name__": "heap_usage", "instance": f"i{i}",
                "_ws_": "demo", "_ns_": "App-0"}
        for row in range(60):
            builder.add(_T0 + row * 10_000, [float(1000 * i + row)], tags)
    for off, c in enumerate(builder.containers()):
        ms.get_shard("prom", 0).ingest(
            list(decode_container(c, DEFAULT_SCHEMAS)), off)
    srv = FiloHttpServer()
    srv.bind_dataset(DatasetBinding(
        "prom", ms, SingleClusterPlanner("prom", mapper, DatasetOptions(),
                                         spread_default=0)))
    port = srv.start()
    yield port
    srv.shutdown()


def _query_range(port: int) -> list:
    import urllib.parse
    import urllib.request
    qs = urllib.parse.urlencode({
        "query": 'heap_usage{_ws_="demo",_ns_="App-0"}',
        "start": _T0 / 1000 + 100, "end": _T0 / 1000 + 500, "step": "50s"})
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/promql/prom/api/v1/query_range?{qs}",
            timeout=30) as resp:
        return json.loads(resp.read())["data"]["result"]


def test_answer_altered_still_alters_what_a_client_reads(live_server,
                                                         monkeypatch):
    """``selftest/broken_run.py`` plants ``answer_altered`` by wrapping
    ``to_prom_matrix`` in ``http.model`` and ``http.server`` and editing
    ``values[k]`` of the first series of the dict it returns.  So the
    served path calls it through ``http/server.py``'s module global at
    request time, gets lists it can assign into, and answers with
    ``json.dumps`` of that dict: a writer that goes from arrays straight
    to bytes would leave the self-test's fault with no teeth."""
    import importlib.util
    import sys

    from filodb_tpu.http import model, server
    sound = _query_range(live_server)
    assert len(sound) == 4 and all(len(s["values"]) == 9 for s in sound)
    monkeypatch.setattr(sys, "path", list(sys.path))
    for mod in (model, server):               # put back when the test ends
        monkeypatch.setattr(mod, "to_prom_matrix", mod.to_prom_matrix)
    spec = importlib.util.spec_from_file_location(
        "_broken_run", ROOT / "benchmark" / "selftest" / "broken_run.py")
    broken_run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(broken_run)
    broken_run.plant("answer_altered")
    altered = _query_range(live_server)
    t, v = sound[0]["values"][4]
    assert altered[0]["values"][4] == [t, repr(float(v) * 1.0001)]
    del altered[0]["values"][4], sound[0]["values"][4]
    assert altered == sound                    # and nothing else moved


# ---------------------------------------------------------------------------
# the write side (PR 36's harness reads it; pinned here since PR 37)
# ---------------------------------------------------------------------------

@pytest.fixture()
def edge_server():
    """``standalone``'s server with the record-container edge, one shard."""
    from filodb_tpu.standalone import FiloServer
    server = FiloServer({
        "node": "contract", "http-port": 0,
        "datasets": [{"name": "prom", "num-shards": 1, "min-num-nodes": 1,
                      "schema": "gauge", "spread": 0, "gateway-port": 0,
                      "mesh": False}]})
    server.start()
    yield server
    server.shutdown()


def _container(rows):
    """One container as ``harness/loader.py`` builds the writer's: a
    series' records encoded once by ``add_series_hashed``, dealt out by
    ``append_encoded``; a 4-byte length, then ``record_dtype`` bytes a
    record."""
    import numpy as np

    from filodb_tpu.core.record import (RecordBuilder, canonical_partkey,
                                        partition_hash, record_dtype,
                                        shard_key_hash)
    from filodb_tpu.core.schemas import DEFAULT_SCHEMAS, DatasetOptions
    schema, options = DEFAULT_SCHEMAS["gauge"], DatasetOptions()
    tags = {"_metric_": "m", "_ws_": "demo", "_ns_": "App-0",
            "instance": "i0"}
    pk = canonical_partkey(tags)
    bld = RecordBuilder(schema, options, container_size=1 << 30)
    ts = _T0 + np.asarray(rows, dtype=np.int64) * 15_000 + 7
    bld.add_series_hashed(ts, [1000.0 + np.asarray(rows, dtype=np.float64)],
                          shard_key_hash(tags, options),
                          partition_hash(tags, options), pk)
    (blob,) = bld.containers()
    size = record_dtype(schema, len(pk)).itemsize
    assert len(blob) == 4 + len(rows) * size
    for i in range(len(rows)):
        bld.append_encoded(blob[4 + i * size:4 + (i + 1) * size], size, 1)
    (again,) = bld.containers()
    assert again == blob
    return blob


def _post(port: int, blob: bytes) -> dict:
    import urllib.request
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/ingest/prom/0", data=blob,
        headers={"Content-Type": "application/octet-stream"}, method="POST")
    with urllib.request.urlopen(req, timeout=30) as r:
        assert r.status == 200
        return json.loads(r.read())


def _ingested(server, want: int) -> int:
    import time
    (shard,) = server.memstore.shards("prom")
    deadline = time.time() + 30
    while shard.stats.rows_ingested < want and time.time() < deadline:
        time.sleep(0.002)
    return shard.stats.rows_ingested


def test_the_edge_answers_200_with_an_offset(edge_server):
    """``loadgen.Writer`` counts a container acknowledged by its 200;
    the body carries the stream ``offset`` it landed at."""
    first = _post(edge_server.http.port, _container(range(4)))
    second = _post(edge_server.http.port, _container(range(4, 6)))
    assert isinstance(first["offset"], int)
    assert second["offset"] == first["offset"] + 1


def test_rows_ingested_moves_by_the_containers_rows(edge_server):
    """``write_side`` holds ``server.memstore.shards(dataset)[i].stats
    .rows_ingested`` against the rows loaded plus every sample
    acknowledged: exact, so a container's rows count once each."""
    assert _ingested(edge_server, 0) == 0
    _post(edge_server.http.port, _container(range(4)))
    assert _ingested(edge_server, 4) == 4
    _post(edge_server.http.port, _container(range(4, 9)))
    assert _ingested(edge_server, 9) == 9
    _post(edge_server.http.port, _container(range(7, 9)))    # seen before
    import time
    time.sleep(0.2)
    assert _ingested(edge_server, 9) == 9


def test_a_query_range_reads_the_unflushed_rows(edge_server):
    """A panel that ends at ``now`` is answered from rows no flush has
    frozen: the acknowledged container's samples are in the answer once
    the shard's consumer has ingested them."""
    import urllib.parse
    import urllib.request
    port = edge_server.http.port
    _post(port, _container(range(30)))
    assert _ingested(edge_server, 30) == 30
    (shard,) = edge_server.memstore.shards("prom")
    assert shard.stats.chunks_flushed == 0

    def newest() -> float:
        qs = urllib.parse.urlencode({
            "query": 'm{_ws_="demo",_ns_="App-0"}',
            "start": _T0 / 1000 + 15 * 20, "end": _T0 / 1000 + 15 * 40,
            "step": "15s"})
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/promql/prom/api/v1/"
                f"query_range?{qs}", timeout=30) as resp:
            (series,) = json.loads(resp.read())["data"]["result"]
        return float(series["values"][-1][1])

    assert newest() == 1029.0
    _post(port, _container(range(30, 33)))
    assert _ingested(edge_server, 33) == 33
    assert newest() == 1032.0 and shard.stats.chunks_flushed == 0


def test_end_ignored_still_moves_what_a_client_reads(live_server,
                                                     monkeypatch):
    """``selftest/broken_run.py`` plants ``end_ignored`` by replacing
    ``http.server.FiloHttpServer._query_range(self, binding, params)``:
    it moves ``params``' ``start`` and ``end`` (seconds, as text) and
    takes the ``(code, dict)`` it returns back to the asked steps,
    ``[seconds, text]`` a value.  So the served path calls the method
    through the class at request time, with a mapping of the request's
    parameters, and answers with ``json.dumps`` of that dict: the fault
    that proves a sliding cell's ends are honoured keeps its teeth."""
    import inspect

    from filodb_tpu.http import server
    whole = server.FiloHttpServer._query_range
    assert len(inspect.signature(whole).parameters) == 3
    sound = _query_range(live_server)
    back = 100                       # seconds: two steps of the query

    def ignored(self, b, p):         # the fault's own moves
        moved = {k: str((int(float(p[k]) * 1000) + back * 1000) / 1000)
                 for k in ("start", "end")}
        code, body = whole(self, b, dict(p, **moved))
        for row in body["data"]["result"]:
            row["values"] = [[t - back, v] for t, v in row["values"]]
        return code, body
    monkeypatch.setattr(server.FiloHttpServer, "_query_range", ignored)
    moved = _query_range(live_server)
    for s, m in zip(sound, moved):
        assert [t for t, _v in s["values"]] == [t for t, _v in m["values"]]
        # a value two steps on answers for the step asked
        assert [v for _t, v in m["values"][:-2]] == \
            [v for _t, v in s["values"][2:]]
        assert all(isinstance(v, str) for _t, v in m["values"])
