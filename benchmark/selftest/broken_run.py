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
    else:
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    import run
    plant(sys.argv[1])
    raise SystemExit(run.main(sys.argv[2:]))
