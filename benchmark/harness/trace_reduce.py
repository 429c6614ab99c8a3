"""From the JAX profiler's ``.xplane.pb`` to numbers: the one place where a
trace is read.

``reduce(path, window_s)`` clips every device event to the traced window
``[0, window_s]`` (trace time starts when ``start_trace`` returns) and gives

``busy_s``      seconds in which an operation ran, union of intervals,
                averaged over the device planes
``busy_s_by_device``  the same of each device plane, [plane's name, seconds]
``devices``     how many device planes the trace holds
``device_ops``  the ten operations with most device time, [name, seconds],
                summed over the chips, under the trace's own names cut short:
                ``<module>:<op>``, the module (``XLA Modules`` line, without
                its fingerprint) that the operation ran inside and the
                operation's name up to its `` = ``
``idle_gaps``   the ten longest intervals in which nothing ran on ANY
                device (what the planes' idle time has in common), each named
                by the host event (TraceMe, not the Python tracer) that
                covers most of it, where one covers half
``mosaic_s``    seconds of operations whose HLO text names ``tpu_custom_call``
                (a Pallas kernel), summed over the chips
``planes``      an inventory, for a reader to check the names against
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MOSAIC = "tpu_custom_call"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals: list) -> list:
    """Sorted, merged [(start, end)]."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def module_at(modules: list, starts: list, t: float) -> str:
    """Name of the module event that holds instant ``t``, or ''."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t <= modules[i][1]:
        return modules[i][2]
    return ""


def reduce(path: str, window_s: float) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    end_ns = window_s * 1e9
    planes = list(pd.planes)
    inventory = [[p.name, [ln.name for ln in p.lines]] for p in planes]
    devices = [p for p in planes if p.name.startswith(DEVICE_PREFIX)]
    per_op: dict = {}
    mosaic = 0.0
    busy, everywhere = [], []
    for p in devices:
        spans = []
        modules = sorted(
            (float(e.start_ns), float(e.start_ns) + float(e.duration_ns),
             re.sub(r"\(\d+\)$", "", e.name))
            for ln in p.lines if ln.name == "XLA Modules" for e in ln.events)
        starts = [m[0] for m in modules]
        for ln in p.lines:
            if ln.name != OPS_LINE:
                continue
            for e in ln.events:
                a = max(float(e.start_ns), 0.0)
                b = min(float(e.start_ns) + float(e.duration_ns), end_ns)
                if b <= a:
                    continue
                spans.append((a, b))
                mod = module_at(modules, starts, float(e.start_ns))
                name = f"{mod}:{e.name.split(' = ')[0][:80]}"
                per_op[name] = per_op.get(name, 0.0) + (b - a) / 1e9
                if MOSAIC in e.name:
                    mosaic += (b - a) / 1e9
        merged = union(spans)
        everywhere += merged
        busy.append(sum(b - a for a, b in merged) / 1e9)
    out = {"window_s": window_s, "devices": len(devices),
           "busy_s": (sum(busy) / len(busy)) if busy else None,
           "busy_s_by_device": [[p.name, s] for p, s in zip(devices, busy)],
           "device_ops": [[k, v] for k, v in sorted(
               per_op.items(), key=lambda kv: -kv[1])[:10]],
           "idle_gaps": [], "mosaic_s": mosaic, "planes": inventory}
    if not devices:
        return out
    gaps, at = [], 0.0
    for a, b in union(everywhere) + [(end_ns, end_ns)]:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    host = [(float(e.start_ns), float(e.start_ns) + float(e.duration_ns),
             e.name)
            for p in planes if p.name.startswith("/host:")
            for ln in p.lines if ln.name != "python"
            for e in ln.events if e.duration_ns > 0]
    for a, b in gaps:
        cover: dict = {}
        for s, t, name in host:
            o = min(b, t) - max(a, s)
            if o > 0:
                cover[name] = cover.get(name, 0.0) + o
        name = max(cover, key=cover.get) if cover else ""
        if not name or cover[name] < (b - a) / 2:
            name = "no host span"
        out["idle_gaps"].append([name, (b - a) / 1e9])
    return out
