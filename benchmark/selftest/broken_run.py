#!/usr/bin/env python3
"""``run.py`` with the timed path broken underneath: the self-test sees
``correct`` come out false.

    broken_run.py <fault> <run.py's arguments>

``answer_altered``        one value of every answer off by one part in 10 000
                          where the answer is produced (``to_prom_matrix``)
``half_the_series_lost``  every index lookup returns half of its series
``served_by_the_host``    the device store declines every plan, so the host
                          path gives the (right) answers
``breaker_renamed``       a breaker of the device path is not where the
                          harness reads it
``writes_swallowed``      once set-up is done the container edge answers 200
                          and hands nothing on to the shards (a cell whose
                          traffic has a writer)
``ingest_held``           the first container the edge takes in the
                          measured window waits 2.5 s before it is handed on: the
                          server alone stands, the load generator runs on
                          (a cell whose traffic has a writer)
``end_ignored``           ``query_range`` is answered as if its ``end`` were
                          the newest loaded row, on the steps it asked for
                          (a mix whose panels slide)
"""

import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parent.parent))


def plant(fault: str) -> None:
    if fault == "answer_altered":
        from filodb_tpu.http import model, server
        whole = model.to_prom_matrix

        def altered(*a, **kw):
            body = whole(*a, **kw)
            for row in body["data"]["result"][:1]:
                t, v = row["values"][len(row["values"]) // 2]
                row["values"][len(row["values"]) // 2] = \
                    [t, repr(float(v) * 1.0001)]
            return body
        model.to_prom_matrix = altered
        if hasattr(server, "to_prom_matrix"):
            server.to_prom_matrix = altered
    elif fault == "half_the_series_lost":
        from filodb_tpu.memstore import shard
        whole = shard.TimeSeriesShard.lookup_partitions

        def half(self, *a, **kw):
            out = whole(self, *a, **kw)
            out.part_ids = out.part_ids[::2]
            return out
        shard.TimeSeriesShard.lookup_partitions = half
    elif fault == "served_by_the_host":
        from filodb_tpu.memstore import devicestore
        devicestore.DeviceGridCache._plan_locked = \
            lambda self, *a, **kw: None
    elif fault == "breaker_renamed":
        from filodb_tpu.memstore import devicestore
        del devicestore._PACKED_BROKEN
    elif fault == "writes_swallowed":
        import run
        whole = run.set_up

        def swallowed(*a, **kw):
            live = whole(*a, **kw)
            live["server"].http.ingest_sink = lambda dataset, shard, body: 0
            return live
        run.set_up = swallowed
    elif fault == "ingest_held":
        import threading
        import time

        import run
        whole_set_up, whole_window = run.set_up, run.run_window
        windows, once = [], threading.Event()

        def set_up(*a, **kw):
            live = whole_set_up(*a, **kw)
            sink = live["server"].http.ingest_sink

            def late(dataset, shard, body):
                if len(windows) > 1 and not once.is_set():   # the window's
                    once.set()
                    time.sleep(2.5)
                return sink(dataset, shard, body)
            live["server"].http.ingest_sink = late
            return live

        def run_window(*a, **kw):        # the concurrent warm, the window
            windows.append(1)
            return whole_window(*a, **kw)
        run.set_up, run.run_window = set_up, run_window
    elif fault == "end_ignored":
        import run
        from filodb_tpu.http import server
        from harness import traffic
        whole_set_up, whole = run.set_up, server.FiloHttpServer._query_range
        newest = []

        def set_up(*a, **kw):
            live = whole_set_up(*a, **kw)
            newest.append(traffic.newest_ms(live["spec"]))
            return live

        def ignored(self, b, p):
            back = newest[0] - int(float(p["end"]) * 1000) if newest else 0
            if not back:
                return whole(self, b, p)
            moved = {k: str((int(float(p[k]) * 1000) + back) / 1000)
                     for k in ("start", "end")}
            code, body = whole(self, b, dict(p, **moved))
            for row in body.get("data", {}).get("result", []):
                row["values"] = [[t - back / 1000, v] for t, v in
                                 row["values"]]
            return code, body
        run.set_up = set_up
        server.FiloHttpServer._query_range = ignored
    else:
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    import run
    plant(sys.argv[1])
    raise SystemExit(run.main(sys.argv[2:]))
