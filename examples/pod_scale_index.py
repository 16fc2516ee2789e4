"""Pod-scale index construction path: TASTI with a *transformer backbone*
embedder (the tasti-embedder config — swap in any of the 10 assigned archs),
then the build_index launcher, called in this process (a child would need
the chip this process already holds).

    PYTHONPATH=src python examples/pod_scale_index.py
"""
import tempfile

import numpy as np

from repro.core.embedder import EmbedderConfig
from repro.core.pipeline import TastiConfig, build_tasti
from repro.core.schema import make_workload
from repro.core.triplet import TripletConfig
from repro.launch import build_index


def main() -> None:
    wl = make_workload("night-street", n_frames=2000)
    # Note: build_tasti's embedder config is constructed internally from
    # TastiConfig; here we demonstrate the backbone path directly through a
    # smaller build (the backbone forward is the §Perf/B prefill workload).
    cfg = TastiConfig(n_train=150, n_reps=300, k=4,
                      triplet=TripletConfig(steps=100), pretrain_steps=40)
    sys_t = build_tasti(wl, cfg, variant="T")
    proxy = sys_t.proxy_scores(wl.score_count)
    rho2 = np.corrcoef(proxy, wl.counts.astype(float))[0, 1] ** 2
    print(f"[pod_scale_index] in-process build: rho^2={rho2:.3f}, "
          f"{sys_t.index.cost.target_invocations} target-DNN calls")

    with tempfile.TemporaryDirectory() as d:
        argv = ["--workload", "taipei", "--n-frames", "2000",
                "--n-train", "150", "--n-reps", "300",
                "--triplet-steps", "100", "--out", f"{d}/taipei_idx"]
        print("+ repro.launch.build_index", " ".join(argv))
        build_index.main(argv)


if __name__ == "__main__":
    main()
