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

Blob: 8 bytes big-endian length of the JSON head, the head
(``{"requests": [...], "bodies": [[sha1, length], ...], ...}``), then the
bodies in that order.
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


def play(spec: dict) -> tuple:
    traffic = traffic_mod.load(spec["traffic_file"])
    port, seconds = spec["port"], float(spec["seconds"])
    grace = float(spec["grace_s"])
    think = traffic.get("think_ms", 0) / 1000.0
    records, bodies = [], {}
    lock = threading.Lock()
    t_open = time.time() + 0.2            # every session starts together
    t_close = t_open + seconds

    def one_session(index: int) -> None:
        seq = traffic_mod.session(traffic, spec["seed"], index,
                                  spec["population"], spec["dataset"],
                                  spec["timeout_s"], spec["stats"])
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
                    "t_send": t_send, "t_done": t_done, "status": status,
                    "partial": partial, "sha1": sha, "error": err})
            n += 1
            if think:
                time.sleep(think)

    threads = [threading.Thread(target=one_session, args=(i,), daemon=True)
               for i in range(traffic["sessions"])]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=max(0.0, t_close + grace + 5 - time.time()))
    hung = sum(t.is_alive() for t in threads)
    with lock:
        head = {"t_open": t_open, "t_close": t_close, "hung_sessions": hung,
                "requests": sorted(records, key=lambda r: r["t_send"]),
                "bodies": [[k, len(v)] for k, v in bodies.items()]}
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
