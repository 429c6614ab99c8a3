"""The least bytes the open block's appends have to write into HBM: the
function a roofline share of ``devicestore.tail_append`` takes its numerator
from.

A sample that reaches the device store is one cell of the open block: an
int32 timestamp and an f32 value, 8 bytes, whatever program writes it (a
scatter into a copy of the planes, a donated in-place update, a row
``dynamic_update_slice``).  A lower bound on purpose, as ``least_bytes`` is
for the reads: the program that copies both planes (2 x 52.4 MB at 102 400
lanes) to write 6 827 cells shows as a fraction of a percent.

No reader uses it yet: ``trace_reduce.reduce`` keeps the ten longest device
ops only, and the append's are not among them (PERF.md section 7 row 3).
The share is read once by hand from a raw trace (PERF.md section 5) and the
``benchmark`` issue that makes the trace keep every op's seconds finds the
count here.
"""

from __future__ import annotations

CELL_BYTES = 8          # int32 timestamp + f32 value


def append_bytes(writes: list, t0: float, t1: float) -> int:
    """Bytes the appends had to write for the containers acknowledged in
    ``[t0, t1]`` (``run["writes"]``: ``samples``, ``status``, ``t_ack`` a
    container; the traced window's wall-clock ends)."""
    return CELL_BYTES * sum(w["samples"] for w in writes
                            if w["status"] == 200 and t0 <= w["t_ack"] <= t1)


def append_roofline(writes: list, t0: float, t1: float, device_s: float,
                    hbm_bytes_per_s: float):
    """The share of the HBM roofline the append ops reached: least bytes
    over peak bytes a second, over the seconds the append's device ops took
    in ``[t0, t1]``.  None where no append ran."""
    if device_s <= 0:
        return None
    return append_bytes(writes, t0, t1) / hbm_bytes_per_s / device_s
