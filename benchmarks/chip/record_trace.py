"""Record the small device trace that the trace-reduction test reads.

    python3 benchmarks/chip/record_trace.py <out_dir>

On a TPU only.  Compiles the served path's two kernels at small shapes
(``propagate`` in the numeric and top-1 modes, ``distance_topk``), then
traces three calls of each between two ``bench.sync`` annotations, and
writes the profiler's directory under ``<out_dir>``.  It prints one line per
trace plane and line with its event count and the most frequent event names,
so the names the reduction looks for can be read off a real trace.  The
committed fixture ``tests/fixtures/small.xplane.pb`` is the ``.xplane.pb``
file of one such run; ``tests/fixtures/small.json`` holds the calls made.
"""
from __future__ import annotations

import collections
import glob
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "src"))

#: (records, k, reps) of the propagate calls; (records, width, new reps) of
#: the distance_topk calls
PROPAGATE = (32768, 8, 1000)
DISTANCE = (32768, 128, 40)
CALLS = 3


def main(out_dir: str) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        sys.exit("record_trace: needs a TPU")
    from repro.kernels.distance_topk.ops import distance_topk
    from repro.kernels.propagate.ops import propagate

    n, k, c = PROPAGATE
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, c, (n, k)), jnp.int32)
    d2 = jnp.asarray(np.sort(rng.random((n, k)), 1), jnp.float32)
    nd, dim, cr = DISTANCE
    x = jnp.asarray(rng.normal(size=(nd, dim)), jnp.float32)
    r = jnp.asarray(rng.normal(size=(cr, dim)), jnp.float32)

    def calls():
        for _ in range(CALLS):
            for mode in ("numeric", "top1"):
                scores = jnp.asarray(rng.random(c), jnp.float32)
                propagate(scores, ids, d2, mode).block_until_ready()
            jax.block_until_ready(distance_topk(x, r, k))

    calls()                                   # compile outside the trace
    out = pathlib.Path(out_dir)
    jax.profiler.start_trace(str(out))
    with jax.profiler.TraceAnnotation("bench.sync"):
        t0 = time.perf_counter()
    calls()
    with jax.profiler.TraceAnnotation("bench.sync"):
        t1 = time.perf_counter()
    jax.profiler.stop_trace()

    path = sorted(glob.glob(str(out / "**" / "*.xplane.pb"), recursive=True))[-1]
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(path)
    for plane in profile.planes:
        for line in plane.lines:
            names = collections.Counter(e.name for e in line.events)
            print(json.dumps({"plane": plane.name, "line": line.name,
                              "events": sum(names.values()),
                              "top": names.most_common(8)}))
    print(json.dumps({"xplane": path, "host_window_s": t1 - t0,
                      "device_kind": jax.devices()[0].device_kind,
                      "propagate": {"records": n, "k": k, "reps": c,
                                    "modes": ["numeric", "top1"],
                                    "calls_per_mode": CALLS},
                      "distance_topk": {"records": nd, "width": dim,
                                        "reps": cr, "k": k,
                                        "calls": CALLS}}))


if __name__ == "__main__":
    if len(sys.argv) != 2:
        sys.exit("usage: python3 benchmarks/chip/record_trace.py <out_dir>")
    main(sys.argv[1])
