"""The crack path under use: a closed loop of analyst sessions, each query
folding the records it labels back into the index as representatives.

Not a cell of the benchmark: :mod:`control` runs it on the chip, after
its other readings, to measure what a cracking cell would see.  Over a
fresh engine, label store and server on the mounted index, ``clients``
threads each send one spec at a time (drawn by Zipf(s) quotas from the
mix's pool, with ``crack`` on) and wait for its answer, for ``seconds``.
It reports the queries completed in the window, the backend compiles in
it, the ``engine.crack`` and ``proxy.materialize`` spans, and the cracked
index's top-k against float64 distances from the final representatives.
"""
from __future__ import annotations

import itertools
import pathlib
import shutil
import tempfile
import threading
import time

import numpy as np

from benchmarks.chip import check, loadgen, mount, schedule


def quartiles(values: list) -> dict:
    v = np.sort(np.asarray(values, np.float64))
    if not len(v):
        return {}
    return {"n": len(v), "min": float(v[0]), "p50": float(np.median(v)),
            "p95": float(np.quantile(v, 0.95)), "max": float(v[-1])}


def probe(cfg, mix, corpus, index, seed: int, seconds: float,
          compile_clock, clients: int = 4) -> dict:
    workdir = pathlib.Path(tempfile.mkdtemp(prefix="chip-crack-"))
    server = None
    try:
        clock = mount.Clock()
        server, engine = mount.serve(cfg, corpus, index,
                                     str(workdir / "labels"), clock, 1 << 14)
        mount.warm_up(engine, mix, clock)
        pool = schedule.spec_pool(mix["pool"], seed)
        ranks = schedule.zipf_requests(len(pool), mix["pool"]["zipf_s"],
                                       4096, np.random.default_rng([seed, 4]))
        counter = itertools.count()
        rows, lock = [], threading.Lock()
        reps0 = index.n_reps
        compiles0 = compile_clock.read()["compiles"]
        t0 = time.perf_counter()
        end = t0 + seconds

        def client():
            while time.perf_counter() < end:
                i = next(counter)
                spec = dict(pool[int(ranks[i % len(ranks)])], crack=True)
                fired = time.perf_counter() - t0
                try:
                    status, body = loadgen.post(
                        server.url, {"specs": [spec]}, f"c{i}", timeout=600.0)
                except OSError as e:
                    status, body = 0, str(e)
                done = time.perf_counter() - t0
                with lock:
                    rows.append({"kind": spec["kind"], "fired": fired,
                                 "done": done, "status": status,
                                 "cracked": (body["results"][0]
                                             .get("n_cracked")
                                             if status == 200 else None)})

        threads = [threading.Thread(target=client, daemon=True)
                   for _ in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(seconds + 600.0)
        in_window = compile_clock.read()["compiles"] - compiles0
        spans = {"engine.crack": [], "proxy.materialize": []}
        for trace in server.obs.recorder.traces():
            for s in trace.to_dict()["spans"]:
                if s["name"] in spans and s["t1"] is not None:
                    spans[s["name"]].append(s["t1"] - s["t0"])
        server.shutdown()
        server = None
        engine.resident.invalidate()
        done = [r for r in rows if r["status"] == 200 and r["done"] <= seconds]
        after = check.topk_errs(check.topk_sample(index, seed))
        return {
            "seconds": seconds, "clients": clients, "sent": len(rows),
            "failed": sum(r["status"] != 200 for r in rows),
            "qps": len(done) / seconds,
            "compiles_in_window": in_window,
            "reps_added": index.n_reps - reps0,
            "crack_ms": [1e3 * v for v in spans["engine.crack"]],
            "materialize_ms": [1e3 * v for v in spans["proxy.materialize"]],
            "latency_s": quartiles([r["done"] - r["fired"] for r in rows]),
            "topk_after": after}
    finally:
        if server is not None:
            server.shutdown()
        shutil.rmtree(workdir, ignore_errors=True)
