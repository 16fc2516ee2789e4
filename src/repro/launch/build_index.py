"""Index-construction driver: build (or crack/update) a TASTI index over a
workload and persist it (versioned JSON + npz; see ``TastiIndex.save``).

    PYTHONPATH=src python -m repro.launch.build_index \
        --workload night-street --n-frames 8000 --variant T \
        --out /tmp/tasti/night_street

Query the saved index declaratively with ``repro.launch.query``.

At pod scale the embedding pass is the prefill-shaped workload hillclimbed in
EXPERIMENTS.md §Perf/B (``--backbone`` selects any assigned architecture as
the embedder; the default MLP matches the paper-scale reproduction).
"""
from __future__ import annotations

import argparse
import json
import time

from repro.core.pipeline import TastiConfig, build_tasti
from repro.core.schema import WORKLOAD_NAMES, make_workload
from repro.core.triplet import TripletConfig
from repro.launch.compile_cache import enable_compile_cache


def main(argv=None) -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="night-street",
                    choices=list(WORKLOAD_NAMES))
    ap.add_argument("--n-frames", type=int, default=8000)
    ap.add_argument("--variant", default="T", choices=["T", "PT"])
    ap.add_argument("--n-train", type=int, default=400)
    ap.add_argument("--n-reps", type=int, default=800)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--embed-dim", type=int, default=128)
    ap.add_argument("--triplet-steps", type=int, default=400)
    ap.add_argument("--backbone", default="mlp",
                    help="'mlp' or a config name (e.g. tasti-embedder)")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    wl = make_workload(args.workload, n_records=args.n_frames)
    cfg = TastiConfig(n_train=args.n_train, n_reps=args.n_reps, k=args.k,
                      embed_dim=args.embed_dim,
                      triplet=TripletConfig(steps=args.triplet_steps))
    t0 = time.time()
    system = build_tasti(wl, cfg, variant=args.variant)
    dt = time.time() - t0
    system.index.save(args.out)
    cost = system.index.cost
    print(json.dumps({
        "workload": wl.name,
        "records": len(wl.features),
        "variant": args.variant,
        "reps": system.index.n_reps,
        "k": system.index.k,
        "target_dnn_invocations": cost.target_invocations,
        "modeled_construction_s": round(cost.wall_clock_s(), 1),
        "actual_build_s_cpu": round(dt, 1),
        "out": args.out,
        "format_version": system.index.FORMAT_VERSION,
    }, indent=2))


if __name__ == "__main__":
    main()
