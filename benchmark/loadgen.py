#!/usr/bin/env python3
"""The load generator: a child process of ``run.py`` that never imports JAX
or the program, so it never touches the chip (which belongs to the parent)
and shares no GIL with the server.

    loadgen.py <spec.json on stdin>  ->  one framed blob on stdout

It plays the traffic file's closed-loop sessions against the server's HTTP
port for ``seconds``, one thread a session, a new connection a request (the
server speaks HTTP/1.0).  A request sent inside the window is waited for,
up to ``grace_s`` past the close; its latency counts the wait.  Bodies are
kept as bytes, once a distinct body (by SHA-1), and handed back with the
per-request records after the window: nothing is parsed here and nothing is
written to disk.

Where the traffic file has a ``writer``, one more thread posts the stream's
containers (built by the parent, read here as bytes) to ``POST
/ingest/<dataset>/<shard>`` on their schedule, and the sessions' panels that
end at ``now`` end at what it has made visible (``Writer``).  A thread of
this process keeps its own stops (``Stops``): where the load generator was
not run, the machine stood, and the writer's lateness over those seconds is
not the server's.

Blob: 8 bytes big-endian length of the JSON head, the head
(``{"requests": [...], "bodies": [[sha1, length], ...], ...}``; with a writer
also ``writes``, a record a container, and ``writer``), then the bodies in
that order.
"""

from __future__ import annotations

import hashlib
import http.client
import json
import pathlib
import struct
import sys
import threading
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
from harness import traffic as traffic_mod  # noqa: E402


class Stops:
    """This process's own stops: a thread wakes every ``TICK_S`` and keeps
    each span in which it woke more than ``STOP_S`` late.  The load
    generator is a process of its own that shares nothing with the server,
    so a span in which it was not run is one in which the machine (or the
    harness) stood, whatever the server did."""

    TICK_S = 0.02
    STOP_S = 0.1

    def __init__(self):
        self.spans: list = []          # (from, to) on time.time()
        self.lock = threading.Lock()
        self.done = threading.Event()
        self.thread = threading.Thread(target=self.run, daemon=True)

    def run(self) -> None:
        prev = time.time()
        while not self.done.wait(self.TICK_S):
            now = time.time()
            if now - prev - self.TICK_S > self.STOP_S:
                with self.lock:
                    self.spans.append((prev + self.TICK_S, now))
            prev = now

    def within(self, t0: float, t1: float) -> float:
        """Seconds of ``[t0, t1]`` in which this process stood."""
        with self.lock:
            return sum(max(0.0, min(b, t1) - max(a, t0))
                       for a, b in self.spans)

    def head(self) -> dict:
        with self.lock:
            longest = sorted(self.spans, key=lambda s: s[0] - s[1])[:10]
            return {"stopped_s": sum(b - a for a, b in self.spans),
                    "stops": len(self.spans),
                    "longest": [[a, b - a] for a, b in longest]}


class Writer:
    """The traffic's scheduled writer: batch ``k`` of the stream is due when
    the synthetic clock (the newest loaded edge at ``anchor``, one to one
    with the wall clock from there) reaches the batch's end; it is posted
    then, never early, a new connection a container.  ``state`` is what the
    run's earlier window left (the concurrent warm and the window are two
    processes and one stream): the anchor, the next batch, and when each
    batch so far had its last 200 (None: not acknowledged).  What fell due
    between the two windows is posted at once when this one opens and is
    counted (``caught_up``), not held against the schedule.  A batch's
    lateness is held against it less the seconds in which this process
    stood (``stops``): ``behind_s_max`` is the most that is left, and
    ``late_s_max`` the most before that."""

    def __init__(self, block: dict, state: dict, port: int, dataset: str,
                 newest_ms: int, stops: "Stops | None" = None):
        with open(state["file"], "rb") as f:
            (n,) = struct.unpack(">Q", f.read(8))
            index = json.loads(f.read(n))
            blob = f.read()
        self.batches = index["batches"]
        self.by_batch: dict = {}
        for k, shard, samples, at, ln in index["containers"]:
            self.by_batch.setdefault(k, []).append(
                (shard, samples, blob[at:at + ln]))
        self.batch_ms = block["batch_ms"]
        self.visible_s = block["visible_after_ms"] / 1000.0
        self.port, self.dataset, self.newest_ms = port, dataset, newest_ms
        self.anchor = state.get("anchor")
        self.first = len(state.get("acked", []))
        self.acked = list(state.get("acked", []))     # by batch: t or None
        self.seen = 0          # batches visible, counted from the first
        self.lock = threading.Lock()
        self.records = []
        self.t_close, self.caught_up = 0.0, 0
        self.behind_s_max, self.exhausted = 0.0, False
        self.late_s_max = 0.0
        self.stops = stops if stops is not None else Stops()

    def visible_ms(self) -> int:
        """The synthetic time up to which every batch had its last 200 at
        least ``visible_after_ms`` ago."""
        now = time.time()
        with self.lock:
            while self.seen < len(self.acked) \
                    and self.acked[self.seen] is not None \
                    and self.acked[self.seen] + self.visible_s <= now:
                self.seen += 1
            return self.newest_ms + self.seen * self.batch_ms

    def post(self, shard: int, container: bytes, until: float) -> int:
        try:
            conn = http.client.HTTPConnection(
                "127.0.0.1", self.port,
                timeout=max(1.0, until - time.time()))
            try:
                conn.request("POST", f"/ingest/{self.dataset}/{shard}",
                             body=container, headers={
                                 "Content-Type": "application/octet-stream"})
                resp = conn.getresponse()
                resp.read()
                return resp.status
            finally:
                conn.close()
        except (OSError, http.client.HTTPException):
            return 0

    def run(self, t_open: float, t_close: float, grace: float) -> None:
        time.sleep(max(0.0, t_open - time.time()))
        if self.anchor is None:
            self.anchor = time.time()
        t_start, self.t_close = time.time(), t_close
        k = self.first
        while True:
            t_due = self.anchor + (k + 1) * self.batch_ms / 1000.0
            if t_due >= t_close:
                return
            if k >= self.batches:         # the live rows ran out: no stream
                self.exhausted = True
                return
            time.sleep(max(0.0, t_due - time.time()))
            t_first = time.time()
            if t_first >= t_close:        # behind by the rest of the window
                self.behind(t_due, t_first)
                return
            whole = True
            for shard, samples, container in self.by_batch.get(k, ()):
                t_send = time.time()
                status = self.post(shard, container, t_close + grace)
                t_ack = time.time()
                whole = whole and status == 200
                self.records.append({
                    "batch": k, "shard": shard, "samples": samples,
                    "t_due": t_due, "t_send": t_send, "t_ack": t_ack,
                    "status": status})
            if t_due < t_start:
                self.caught_up += 1
            else:
                self.behind(t_due, t_first)
            with self.lock:
                self.acked.append(time.time() if whole else None)
            k += 1

    def behind(self, t_due: float, t_first: float) -> None:
        late = t_first - t_due
        self.late_s_max = max(self.late_s_max, late)
        self.behind_s_max = max(self.behind_s_max,
                                late - self.stops.within(t_due, t_first))

    def head(self) -> dict:
        # batches whose end the clock reached before the window closed
        due = -(-(self.t_close - self.anchor) * 1000.0 // self.batch_ms) - 1
        return {"anchor": self.anchor, "acked": self.acked,
                "batches_due": max(0, int(due) - self.first),
                "batches_sent": len(self.acked) - self.first,
                "caught_up": self.caught_up, "exhausted": self.exhausted,
                "behind_s_max": self.behind_s_max,
                "late_s_max": self.late_s_max}


def play(spec: dict) -> tuple:
    traffic = traffic_mod.load(spec["traffic_file"])
    port, seconds = spec["port"], float(spec["seconds"])
    grace = float(spec["grace_s"])
    think = traffic.get("think_ms", 0) / 1000.0
    records, bodies = [], {}
    lock = threading.Lock()
    writer, stops = None, Stops()
    if traffic.get("writer"):
        writer = Writer(traffic["writer"], spec["writer"], port,
                        spec["dataset"],
                        traffic_mod.newest_ms(spec["population"]), stops)
    t_open = time.time() + 0.2            # every session starts together
    t_close = t_open + seconds

    def one_session(index: int) -> None:
        seq = traffic_mod.session(traffic, spec["seed"], index,
                                  spec["population"], spec["dataset"],
                                  spec["timeout_s"], spec["stats"],
                                  writer.visible_ms if writer else None)
        time.sleep(max(0.0, t_open - time.time()))
        n = 0
        while True:
            t_send = time.time()
            if t_send >= t_close:
                return
            req = next(seq)
            status, body, partial, err = 0, b"", False, ""
            try:
                conn = http.client.HTTPConnection(
                    "127.0.0.1", port,
                    timeout=max(1.0, t_close + grace - t_send))
                try:
                    conn.request("GET", req.path)
                    resp = conn.getresponse()
                    body = resp.read()
                    status = resp.status
                    partial = resp.getheader("X-FiloDB-Partial-Data") \
                        is not None
                finally:
                    conn.close()
            except (OSError, http.client.HTTPException) as e:
                err = repr(e)[:200]
            t_done = time.time()
            sha = hashlib.sha1(body).hexdigest() if body else ""
            with lock:
                if sha and sha not in bodies:
                    bodies[sha] = body
                records.append({
                    "session": index, "n": n, "panel": req.panel,
                    "namespace": req.namespace, "key": req.key,
                    "end_ms": req.end_ms, "t_send": t_send, "t_done": t_done,
                    "status": status, "partial": partial, "sha1": sha,
                    "error": err})
            n += 1
            if think:
                time.sleep(think)

    threads = [threading.Thread(target=one_session, args=(i,), daemon=True)
               for i in range(traffic["sessions"])]
    writing = [threading.Thread(target=writer.run, daemon=True,
                                args=(t_open, t_close, grace))] \
        if writer else []
    stops.thread.start()
    for t in threads + writing:
        t.start()
    for t in threads + writing:
        t.join(timeout=max(0.0, t_close + grace + 5 - time.time()))
    stops.done.set()
    stops.thread.join()
    hung = sum(t.is_alive() for t in threads)
    with lock:
        head = {"t_open": t_open, "t_close": t_close, "hung_sessions": hung,
                "requests": sorted(records, key=lambda r: r["t_send"]),
                "bodies": [[k, len(v)] for k, v in bodies.items()],
                "stops": stops.head()}
    if writer:
        head["writes"] = list(writer.records)
        head["writer"] = dict(writer.head(), hung=writing[0].is_alive())
    return head, list(bodies.values())


def main() -> int:
    spec = json.load(sys.stdin)
    head, bodies = play(spec)
    raw = json.dumps(head).encode()
    out = sys.stdout.buffer
    out.write(struct.pack(">Q", len(raw)))
    out.write(raw)
    for b in bodies:
        out.write(b)
    out.flush()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
