"""Standalone server, CLI, and observability.

Mirrors the reference's server/CLI surface (reference: FiloServer.scala
startup ordering, CliMain.scala commands, KamonLogger reporters,
SimpleProfiler.java)."""

import json
import socket
import time
import urllib.request

import numpy as np
import pytest

from filodb_tpu.cli import main as cli_main
from filodb_tpu.standalone import FiloServer
from filodb_tpu.utils.observability import (REGISTRY, TRACER, MetricsRegistry,
                                            SimpleProfiler, Tracer)

BASE = 1_700_000_000_000


class TestMetrics:
    def test_counter_gauge_histogram(self):
        reg = MetricsRegistry()
        c = reg.counter("reqs_total")
        c.inc(dataset="prom")
        c.inc(2, dataset="prom")
        assert c.value(dataset="prom") == 3
        g = reg.gauge("mem_bytes")
        g.set(42.5, shard="0")
        assert g.value(shard="0") == 42.5
        g.set_fn(lambda: 7.0, shard="1")
        assert g.value(shard="1") == 7.0
        h = reg.histogram("latency_seconds")
        h.observe(0.003)
        h.observe(0.2)
        text = reg.expose_text()
        assert 'reqs_total{dataset="prom"} 3' in text
        assert "# TYPE latency_seconds histogram" in text
        assert 'latency_seconds_bucket{le="+Inf"} 2' in text
        assert "latency_seconds_count 2" in text

    def test_same_name_same_metric(self):
        reg = MetricsRegistry()
        assert reg.counter("x") is reg.counter("x")
        with pytest.raises(TypeError):
            reg.gauge("x")


class TestTracer:
    def test_nested_spans_report_parent(self):
        tracer = Tracer()
        records = []
        tracer.add_reporter(records.extend)
        with tracer.span("outer", dataset="prom"):
            with tracer.span("inner"):
                pass
        assert [r.name for r in records] == ["inner", "outer"]
        assert records[0].parent == "outer"
        assert records[1].parent is None
        assert records[1].tags == {"dataset": "prom"}

    def test_span_error_recorded(self):
        tracer = Tracer()
        records = []
        tracer.add_reporter(records.extend)
        with pytest.raises(ValueError):
            with tracer.span("bad"):
                raise ValueError("boom")
        assert "boom" in records[0].error


class TestProfiler:
    def test_samples_and_reports(self):
        prof = SimpleProfiler(sample_interval_s=0.002,
                              report_interval_s=3600)
        prof.start()
        t0 = time.time()
        while time.time() - t0 < 0.2:
            sum(i * i for i in range(1000))
        prof.stop()
        rep = prof.report()
        assert "samples" in rep
        assert prof.snapshot()  # captured at least one frame


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    data_dir = str(tmp_path_factory.mktemp("filodb"))
    config = {
        "node": "test-node",
        "data-dir": data_dir,
        "gateway-port": 0,
        "datasets": [{"name": "prom", "num-shards": 4, "min-num-nodes": 1,
                      "schema": "gauge", "spread": 1,
                      "store": {"groups-per-shard": 4}}],
    }
    srv = FiloServer(config)
    port = srv.start()
    yield srv, port
    srv.shutdown()


class TestFiloServer:
    def test_full_node_influx_to_promql(self, server):
        """One process end to end: Influx TCP -> ingestion threads ->
        PromQL over HTTP (the FiloServer.main wiring)."""
        srv, port = server
        gw_port = srv.gateways[0].port
        lines = []
        for i in range(5):
            for k in range(30):
                ts_ns = (BASE + k * 10_000) * 1_000_000
                lines.append(
                    f"node_cpu,_ws_=demo,_ns_=App-0,instance=i{i} "
                    f"value={50 + i + 0.1 * k} {ts_ns}")
        with socket.create_connection(("127.0.0.1", gw_port),
                                      timeout=10) as sk:
            sk.sendall(("\n".join(lines) + "\n").encode())
        deadline = time.time() + 15
        rows = 0
        while time.time() < deadline and rows < 150:
            rows = sum(sh.stats.rows_ingested
                       for sh in srv.memstore.shards("prom"))
            time.sleep(0.05)
        assert rows == 150
        qs = urllib.parse.urlencode({
            "query": 'count(node_cpu{_ws_="demo",_ns_="App-0"})',
            "start": BASE / 1000, "end": (BASE + 290_000) / 1000,
            "step": "30s"})
        url = f"http://127.0.0.1:{port}/promql/prom/api/v1/query_range?{qs}"
        body = json.loads(urllib.request.urlopen(url, timeout=60).read())
        assert body["status"] == "success"
        vals = body["data"]["result"][0]["values"]
        assert any(v == "5" for _, v in vals)

    def test_health_and_metrics_routes(self, server):
        srv, port = server
        body = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/__health", timeout=30).read())
        assert body["healthy"] is True
        assert len(body["shards"]["prom"]) == 4
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=30).read().decode()
        assert "filodb_node_up" in text

    def test_flush_persists_to_disk(self, server):
        srv, port = server
        n = srv.flush_all()
        assert n > 0
        assert srv.colstore.num_chunks("prom", 0) + \
            srv.colstore.num_chunks("prom", 1) + \
            srv.colstore.num_chunks("prom", 2) + \
            srv.colstore.num_chunks("prom", 3) > 0


import urllib.parse  # noqa: E402  (used above)


class TestCli:
    def test_create_list(self, tmp_path, capsys):
        d = str(tmp_path)
        assert cli_main(["create", "--data-dir", d, "--dataset", "events",
                         "--num-shards", "8"]) == 0
        assert cli_main(["list", "--data-dir", d]) == 0
        out = capsys.readouterr().out
        assert "events" in out

    def test_importcsv_and_persisted(self, tmp_path, capsys):
        d = str(tmp_path)
        csv_file = tmp_path / "data.csv"
        csv_file.write_text(
            "timestamp,value,metric,host,_ws_,_ns_\n" + "\n".join(
                f"{BASE + i * 10_000},{i * 1.5},disk_io,h{i % 2},demo,ns"
                for i in range(50)))
        assert cli_main(["importcsv", "--data-dir", d, "--dataset", "ev",
                         "--file", str(csv_file),
                         "--tag-columns", "metric,host,_ws_,_ns_"]) == 0
        out = capsys.readouterr().out
        assert "imported 50 rows" in out
        from filodb_tpu.store.persistence import DiskColumnStore
        disk = DiskColumnStore(f"{d}/chunks.db")
        assert disk.num_chunks("ev", 0) > 0

    def test_partkey_roundtrip(self, capsys):
        from filodb_tpu.core.record import canonical_partkey
        tags = {"_metric_": "up", "job": "api"}
        hexpk = canonical_partkey(tags).hex()
        assert cli_main(["partkey", hexpk]) == 0
        assert json.loads(capsys.readouterr().out) == tags
        assert cli_main(["make-partkey", json.dumps(tags)]) == 0
        assert capsys.readouterr().out.strip() == hexpk

    def test_decode_vector(self, capsys):
        from filodb_tpu.codecs import deltadelta
        ts = (BASE + np.arange(10) * 10_000).astype(np.int64)
        hexblob = deltadelta.encode(ts).hex()
        assert cli_main(["decode-vector", hexblob, "--limit", "3"]) == 0
        out = capsys.readouterr().out
        assert str(BASE) in out

    def test_query_against_live_server(self, server, capsys):
        srv, port = server
        assert cli_main(["labelvalues", "--server",
                         f"http://127.0.0.1:{port}", "--dataset", "prom",
                         "instance"]) == 0
        out = capsys.readouterr().out
        assert "i0" in out


class TestCompileCachePlacement:
    """standalone.place_compile_cache: placed from outside, or at one
    fixed path inside the checkout — never anywhere that moves."""

    @pytest.fixture
    def updates(self, monkeypatch):
        import jax
        seen = []
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: seen.append((k, v)))
        return seen

    def test_env_var_set_means_nothing_is_set_in_code(self, monkeypatch,
                                                      updates, tmp_path):
        from filodb_tpu import standalone
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        standalone.place_compile_cache()
        assert updates == []

    def test_unset_means_a_fixed_path_in_the_checkout(self, monkeypatch,
                                                      updates):
        import pathlib

        import filodb_tpu
        from filodb_tpu import standalone
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        standalone.place_compile_cache()
        standalone.place_compile_cache()
        checkout = pathlib.Path(filodb_tpu.__file__).resolve().parents[1]
        assert updates == [("jax_compilation_cache_dir",
                            str(checkout / ".jax_cache"))] * 2
