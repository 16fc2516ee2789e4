"""Chip smoke: the TASTI served path, end to end, in one process on one TPU.

    python chip_smoke.py

Phases, in order; any failure exits non-zero before the result line:

1. device  -- JAX must report a TPU; any other platform exits non-zero.
2. build   -- mount one night-street stream of 1,000,000 records through the
              serving registry (7000 reps, k=8, 128-wide embeddings, cracking
              on, thread oracle replicas): data generation from the seed,
              pretrain, embed, FPF, distance top-k.
3. serve   -- a QueryServer on an ephemeral port answers aggregation,
              selection and limit requests over HTTP, then the aggregation
              again after the crack the earlier requests caused.
4. check   -- proxy scores came from the device (no host fallback), and the
              device scores of all three propagation modes agree with the
              float64 host reference on the same index snapshot.

Earlier lines report set-up facts (sizes, host-clock seconds per phase,
compile seconds, compile-cache directory, resident stats, peak device
memory); none of them is a benchmark number.  The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""
from __future__ import annotations

import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
WORKLOAD = "night-street"
#: the paper's corpus scale (~1M frames per stream, 7000 reps); n_train and
#: triplet_steps are the serving CLI's defaults
PAPER = dict(n_records=1_000_000, n_reps=7000, k=8, n_train=400,
             triplet_steps=400)
EMBED_DIM = 128
REQUESTS = [
    ("aggregation", {"kind": "aggregation", "score": "score_count",
                     "err": 0.05}),
    ("selection", {"kind": "selection", "score": "score_has_object",
                   "budget": 1000}),
    ("limit", {"kind": "limit", "score": "score_rare", "k_results": 10}),
    ("aggregation after crack", {"kind": "aggregation",
                                 "score": "score_count", "err": 0.05}),
]
#: numeric/top1: max |device - host| <= this x max |host|
SCORE_RTOL = 1e-4
#: categorical: least share of records whose vote agrees
VOTE_AGREEMENT = 0.999


class SmokeFailure(RuntimeError):
    pass


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def report(phase: str, **facts) -> None:
    print(f"[{phase}] " + json.dumps(facts, default=str), flush=True)


def device_check():
    """The chip JAX sees; exits non-zero on any platform but a TPU."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX reports platform "
                 f"{platform!r} ({len(devices)} device(s)); no fallback")
    report("device", jax=jax.__version__, platform=platform,
           kind=devices[0].device_kind, count=len(devices))
    return devices


class CompileClock:
    """Sums JAX's backend-compile seconds and persistent-cache hits/misses."""

    def __init__(self):
        import jax.monitoring as mon
        self.seconds = 0.0
        self.compiles = 0
        self.cache = {"hits": 0, "misses": 0}
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache["misses"] += 1

    def read(self) -> dict:
        return {"backend_compile_s": self.seconds, "compiles": self.compiles,
                "cache": dict(self.cache)}


def build(n_records: int, n_reps: int, k: int, n_train: int,
          triplet_steps: int):
    """Mount and load the workload the way ``repro.launch.serve_queries``
    does; returns the registry with the entry loaded."""
    from repro.serve.registry import WorkloadRegistry, WorkloadSpec
    registry = WorkloadRegistry()
    registry.declare(WorkloadSpec(
        name=WORKLOAD, dataset=WORKLOAD, n_records=n_records,
        n_train=n_train, n_reps=n_reps, k=k, triplet_steps=triplet_steps,
        oracle_backend="thread", crack=True))
    entry = registry.get(WORKLOAD)
    index = entry.engine.index
    require(index.n_records == n_records,
            f"index covers {index.n_records} records, want {n_records}")
    require(index.embeddings.shape[1] == EMBED_DIM,
            f"embedding width {index.embeddings.shape[1]}, want {EMBED_DIM}")
    require(index.k == k and index.topk_ids.shape == (n_records, k),
            f"top-k structures {index.topk_ids.shape}, want ({n_records}, {k})")
    report("build", records=index.n_records, reps=index.n_reps, k=index.k,
           embed_dim=index.embeddings.shape[1],
           seconds=entry.load_seconds)
    return registry


def serve(registry, timeout_s: float = 600.0) -> list:
    """Serve ``REQUESTS`` one at a time over HTTP; returns the result rows.
    Any non-200 answer, or a labeling query that labeled nothing, fails."""
    from repro.serve.client import QueryClient
    from repro.serve.server import QueryServer
    engine = registry.get(WORKLOAD).engine
    server = QueryServer(registry, port=0).start()
    rows = []
    try:
        client = QueryClient(server.url, timeout=timeout_s)
        client.wait_ready(30)
        for label, spec in REQUESTS:
            if label == "aggregation after crack":
                require(engine.index.version > 0,
                        "the earlier requests cracked nothing")
            t0 = time.perf_counter()
            out = client.query([spec])     # raises ServerError unless 200
            seconds = time.perf_counter() - t0
            row = out["results"][0]
            require(row["n_invocations"] > 0,
                    f"{label}: labeled nothing ({row})")
            rows.append(row)
            report("serve", request=label, status=200, seconds=seconds,
                   index_version=engine.index.version,
                   n_reps=engine.index.n_reps,
                   **{key: row.get(key) for key in (
                       "n_invocations", "n_oracle_fresh", "n_oracle_cached",
                       "n_cracked", "estimate", "n_selected")})
    finally:
        server.shutdown()
    return rows


def check(registry, require_pallas: bool) -> dict:
    """Device-path accounting and device-vs-host agreement per mode."""
    import numpy as np

    from repro.core import propagation
    from repro.kernels import resolve_impl
    engine = registry.get(WORKLOAD).engine
    resident = engine.resident
    require(engine.stats["proxy_device_computes"] > 0,
            f"no proxy computed on the device: {engine.stats}")
    require(resident.stats["fallbacks"] == 0,
            f"resident path fell back to the host: {resident.stats}")
    if require_pallas:
        require(resolve_impl("auto") == "pallas",
                "impl='auto' does not resolve to the Pallas kernels")

    index = engine.index
    wl = engine.workload
    rep_scores = index.rep_scores(wl.score_count)
    n_classes = int(wl.max_objects) + 1
    ids, d2 = index.topk_ids, index.topk_d2
    host = {
        "numeric": propagation.propagate_numeric(rep_scores, ids, d2),
        "top1": propagation.propagate_top1(rep_scores, ids, d2),
        "categorical": propagation.propagate_categorical(
            rep_scores, ids, d2, n_classes=n_classes).astype(np.float64),
    }
    agreement = {}
    for mode, want in host.items():
        got = resident.propagate(
            rep_scores, mode, version=index.version,
            n_classes=n_classes if mode == "categorical" else None)
        require(got is not None, f"{mode}: device path returned nothing")
        require(got.shape == want.shape and np.isfinite(got).all(),
                f"{mode}: device scores {got.shape}, finite="
                f"{bool(np.isfinite(got).all())}")
        if mode == "categorical":
            share = float(np.mean(got == want))
            agreement[mode] = {"agree": share}
            require(share >= VOTE_AGREEMENT,
                    f"categorical: {share} of votes agree")
        else:
            err = float(np.max(np.abs(got - want)))
            bound = SCORE_RTOL * float(np.max(np.abs(want)))
            agreement[mode] = {"max_abs_diff": err, "bound": bound}
            require(err <= bound, f"{mode}: max |device - host| {err} > "
                                  f"{bound}")
    report("check", proxy_device_computes=engine.stats[
        "proxy_device_computes"], resident=resident.stats,
        index_version=index.version, reps=index.n_reps,
        agreement=agreement)
    return agreement


def run(devices, sizes: dict, cache_dir: pathlib.Path,
        require_pallas: bool = True) -> dict:
    """build -> serve -> check on ``devices``; returns the result line."""
    before = len(list(cache_dir.glob("*"))) if cache_dir.is_dir() else 0
    clock = CompileClock()
    t0 = time.perf_counter()
    registry = build(**sizes)
    t1 = time.perf_counter()
    report("compile", after="build", **clock.read())
    rows = serve(registry)
    t2 = time.perf_counter()
    report("compile", after="serve", **clock.read())
    check(registry, require_pallas=require_pallas)
    t3 = time.perf_counter()

    after = len(list(cache_dir.glob("*"))) if cache_dir.is_dir() else 0
    stats = devices[0].memory_stats() or {}
    report("summary", seconds={"build": t1 - t0, "serve": t2 - t1,
                               "check": t3 - t2},
           requests=len(rows), compile=clock.read(),
           compile_cache={"dir": str(cache_dir), "entries_before": before,
                          "entries_after": after,
                          "from_env": bool(os.environ.get(
                              "JAX_COMPILATION_CACHE_DIR"))},
           peak_bytes_in_use=stats.get("peak_bytes_in_use"),
           bytes_in_use=stats.get("bytes_in_use"))
    return {"ok": True, "device": {"platform": devices[0].platform,
                                   "kind": devices[0].device_kind,
                                   "count": len(devices)}}


def main() -> None:
    devices = device_check()
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = pathlib.Path(enable_compile_cache())
    print(json.dumps(run(devices, PAPER, cache_dir)), flush=True)


if __name__ == "__main__":
    main()
