"""Set-up: mount one deployment from its configuration and a seed.

The corpus is the program's own synthetic workload, made from the seed; the
index is built by the program's ``build_tasti`` with the serving CLI's
budgets; the engine, a journaled label store and the HTTP server are the
stack ``WorkloadRegistry`` builds for a declared workload, with the seed
passed to the corpus.  Observability stays on, as the server's default.

Warm-up compiles the device propagation for every mode the mix uses, at the
index's shapes, through the engine's resident state, and leaves the
engine's proxy cache empty: the window's first query of each (score, mode)
materializes its proxy on the device, as on a freshly started server.
"""
from __future__ import annotations

import threading
import time

import numpy as np


class Clock:
    """Host-clock seconds per set-up phase."""

    def __init__(self):
        self.phases: dict = {}
        self._t = time.perf_counter()

    def lap(self, phase: str) -> None:
        now = time.perf_counter()
        self.phases[phase] = now - self._t
        self._t = now


class OracleCounter:
    """Counts the labels the corpus's target DNN hands out, at the oracle
    itself: the benchmark's own count, beside the program's accounting."""

    def __init__(self, corpus):
        self.labels = 0
        self._lock = threading.Lock()
        produce = corpus.target_dnn_batch

        def target_dnn_batch(ids):
            out = produce(ids)
            with self._lock:
                self.labels += len(out)
            return out

        corpus.target_dnn_batch = target_dnn_batch


def make_corpus(cfg: dict, seed: int):
    """The program's workload class named by the configuration, sized and
    seeded by it."""
    from repro.core import schema
    corpus = cfg["corpus"]
    cls = getattr(schema, corpus["class"])
    return cls(**{corpus["size_key"]: cfg["records"], "seed": seed,
                  **corpus["kwargs"]})


def build(cfg: dict, seed: int, clock: Clock):
    """The corpus and its index; returns (corpus, index)."""
    from repro.core.pipeline import build_tasti, cli_tasti_config

    corpus = make_corpus(cfg, seed)
    clock.lap("generate")
    index = build_tasti(corpus, cli_tasti_config(
        n_train=cfg["n_train"], n_reps=cfg["reps"], k=cfg["k"],
        triplet_steps=cfg["triplet_steps"])).index
    clock.lap("index")
    got = (index.n_records, index.n_reps, index.k, index.embeddings.shape[1])
    want = (cfg["records"], cfg["reps"], cfg["k"], cfg["embed_dim"])
    if got != want:
        raise RuntimeError(f"index (records, reps, k, width) {got}, "
                           f"configuration says {want}")
    return corpus, index


def serve(cfg: dict, corpus, index, store_stem: str, clock: Clock,
          trace_buffer: int):
    """A fresh engine, label store and server over the index; returns
    (server, engine)."""
    from repro.core.engine import QueryEngine
    from repro.obs import Observability
    from repro.serve.registry import WorkloadRegistry
    from repro.serve.server import QueryServer
    from repro.serve.store import LabelStore

    oracle = cfg["oracle"]
    engine = QueryEngine(index, corpus, max_oracle_batch=oracle["batch"],
                         oracle_replicas=oracle["replicas"],
                         oracle_backend=oracle["backend"])
    if not engine.resident.enabled:
        raise RuntimeError("the engine's device-resident scoring is off")
    store = LabelStore.for_index(store_stem, index)
    store.attach(engine.broker, engine)
    registry = WorkloadRegistry()
    registry.register(cfg["name"], engine, store=store)
    server_cfg = cfg["server"]
    server = QueryServer(
        registry, port=0, admission_window=server_cfg["admission_window_s"],
        max_workers=server_cfg["max_workers"],
        obs=Observability(enabled=True, trace_buffer=trace_buffer))
    server.start()
    clock.lap("serve")
    return server, engine


def proxy_modes(mix: dict) -> list:
    """The (score, propagation mode) pairs a mix's specs materialize."""
    mode = {"aggregation": "numeric", "selection": "numeric",
            "limit": "top1"}
    return sorted({(s, mode[k["kind"]]) for k in mix["pool"]["kinds"]
                   for s in k["scores"]})


def warm_up(engine, mix: dict, clock: Clock) -> None:
    """Compile and run the device propagation once per mode at the index's
    shapes, without filling the engine's proxy cache."""
    index = engine.index
    for mode in sorted({m for _, m in proxy_modes(mix)}):
        scores = np.linspace(0.0, 1.0, index.n_reps)
        out = engine.resident.propagate(scores, mode, version=index.version)
        if out is None or out.shape != (index.n_records,):
            raise RuntimeError(f"warm-up of the {mode} propagation failed")
    clock.lap("warm")
