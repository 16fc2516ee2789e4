"""The load generator, run as a child process that never imports JAX.

    python3 benchmarks/chip/loadgen.py <plan.json> <out.json>

It uses the standard library alone, so it shares neither the server's
interpreter lock nor its chip.  ``plan.json`` holds the server's URL, the
window length, how long to wait past the window for answers (``grace_s``),
and the open-loop schedule (``requests``: a due offset in seconds and a body
each).  Each request is sent at its due time on a thread of its own, never
waiting for earlier answers.

Start-up handshake: the child prints ``ready`` once it has read the plan,
then waits for one line on its standard input; the window starts when that
line arrives.  Every request carries ``X-Trace-Id: r<index>`` so the server's
spans can be matched to it.  ``out.json`` records, per request, its due,
fired and done offsets from the window's start, the HTTP status and the
response body (or the error).  The child's last stdout line reports how
late it fired (``fired - due``).
"""
from __future__ import annotations

import http.client
import json
import sys
import threading
import time
import urllib.parse


def post(url: str, body: dict, trace_id: str, timeout: float):
    """One ``POST /query``; returns (status, parsed body or error text)."""
    parts = urllib.parse.urlsplit(url)
    conn = http.client.HTTPConnection(parts.hostname, parts.port,
                                      timeout=timeout)
    try:
        data = json.dumps(body).encode()
        conn.request("POST", "/query", body=data,
                     headers={"Content-Type": "application/json",
                              "X-Trace-Id": trace_id})
        resp = conn.getresponse()
        raw = resp.read()
        try:
            return resp.status, json.loads(raw)
        except ValueError:
            return resp.status, raw.decode(errors="replace")[:500]
    finally:
        conn.close()


class Recorder:
    """Per-request outcomes, written by many threads."""

    def __init__(self, t0: float):
        self.t0 = t0
        self.lock = threading.Lock()
        self.rows: dict = {}

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def send(self, index: int, due: float, body: dict, url: str,
             timeout: float) -> None:
        fired = self.now()
        try:
            status, payload = post(url, body, f"r{index}", timeout)
        except OSError as e:            # refused, reset, timed out
            status, payload = 0, f"{type(e).__name__}: {e}"
        done = self.now()
        with self.lock:
            self.rows[index] = {"index": index, "due": due, "fired": fired,
                                "done": done, "status": status,
                                "body": payload}


def open_loop(plan: dict, rec: Recorder) -> list:
    threads = []
    timeout = plan["seconds"] + plan["grace_s"]
    for index, (due, body) in enumerate(plan["requests"]):
        wait = due - rec.now()
        if wait > 0:
            time.sleep(wait)
        t = threading.Thread(target=rec.send, daemon=True,
                             args=(index, due, body, plan["url"], timeout))
        t.start()
        threads.append(t)
    return threads


def main(plan_path: str, out_path: str) -> None:
    with open(plan_path) as f:
        plan = json.load(f)
    print("ready", flush=True)
    sys.stdin.readline()
    rec = Recorder(time.perf_counter())
    threads = open_loop(plan, rec)
    deadline = rec.t0 + plan["seconds"] + plan["grace_s"]
    for t in threads:
        t.join(max(0.0, deadline - time.perf_counter()))
    with rec.lock:
        rows = [rec.rows[i] for i in sorted(rec.rows)]
        unfinished = sum(t.is_alive() for t in threads)
    with open(out_path, "w") as f:
        json.dump({"rows": rows, "unfinished": unfinished, "t0": rec.t0,
                   "closed_s": rec.now()}, f)
    late = sorted(r["fired"] - r["due"] for r in rows)
    print(json.dumps({"loadgen": {
        "sent": len(rows), "unfinished": unfinished,
        "late_p50_ms": 1e3 * late[len(late) // 2] if late else None,
        "late_max_ms": 1e3 * late[-1] if late else None}}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2])
