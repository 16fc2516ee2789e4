"""Find the highest rate a cell's deployment sustains, once.

    python3 benchmarks/chip/sweep.py --workload <cell> --seed <n>

On a TPU only.  Builds the cell's corpus and index once, then for each rate
in the mix's ``sweep.rates_qps`` (ascending) serves a fresh engine, label
store and server over that index and sends the mix at that rate for
``sweep.step_s`` seconds.  A rate is sustained when its p95 latency (from
the due time) meets the mix's ``sweep.p95_limit_ms``, at least 95% of its
requests are answered within the step, and the last answer comes no later
than a tenth of the step after it: the queue does not grow through the
step.  The sweep stops at the first rate that is not sustained.  It prints
one line per rate and, last, the highest sustained rate and 0.8 of it.
The cell's traffic file states 0.8 of the lowest rate that three seeds or
more sustain; :mod:`control` sweeps further seeds beside its readings.
The p95 limit is the deployment's: 1 s where an unloaded query answers
within a second, Nielsen's limit for an uninterrupted flow of thought,
else 10 s, his limit for keeping the user's attention (Usability
Engineering, 1993, ch. 5).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys
import tempfile

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from benchmarks.chip import latency, manifest, mount, run, schedule  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

#: share of a step's requests that must be answered within the step
ANSWERED_SHARE = 0.95
#: how long past the step its last answer may come, as a share of the step
DRAIN_SHARE = 0.1


def step(cfg, mix, corpus, index, rate: float, seed: int,
         workdir: pathlib.Path) -> dict:
    seconds = float(mix["sweep"]["step_s"])
    clock = mount.Clock()
    server, engine = mount.serve(cfg, corpus, index,
                                 str(workdir / f"labels-{rate}"), clock, 256)
    try:
        mount.warm_up(engine, mix, clock)
        at_rate = dict(mix, arrivals=dict(mix["arrivals"], rate_qps=rate))
        requests = schedule.open_loop_plan(at_rate, seed, seconds)
        plan = {"requests": requests, "url": server.url, "seconds": seconds,
                "grace_s": run.GRACE_S}
        child, out_path = run.start_loadgen(plan, workdir)
        run.run_window(child, seconds, None)
    finally:
        server.shutdown()
    rows = {r["index"]: r for r in json.loads(out_path.read_text())["rows"]}
    due = [d for d, _ in requests]
    lat = latency.latencies(due, rows, seconds + run.GRACE_S)
    answered = [r for r in rows.values() if latency.answered(r)]
    in_step = sum(r["done"] <= seconds for r in answered)
    last = max((r["done"] for r in answered), default=float("inf"))
    p95_ms = 1e3 * latency.percentile(lat, 95)
    return {"rate_qps": rate, "requests": len(due),
            "answered_in_step": in_step, "last_answer_s": last,
            "p50_ms": 1e3 * latency.percentile(lat, 50), "p95_ms": p95_ms,
            "sustained": (p95_ms <= mix["sweep"]["p95_limit_ms"]
                          and in_step >= ANSWERED_SHARE * len(due)
                          and last <= seconds * (1 + DRAIN_SHARE))}


def sweep(cfg, mix, corpus, index, seed: int) -> dict:
    """Every step up to the first rate not sustained, and the highest
    sustained rate (None if the lowest is not)."""
    workdir = pathlib.Path(tempfile.mkdtemp(prefix="chip-sweep-"))
    steps, best = [], None
    try:
        for rate in sorted(mix["sweep"]["rates_qps"]):
            row = step(cfg, mix, corpus, index, float(rate), seed, workdir)
            print(json.dumps({"step": row}), flush=True)
            steps.append(row)
            if not row["sustained"]:
                break
            best = rate
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    return {"steps": steps, "sustained_qps": best}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    bench = manifest.benchmark()
    cell = manifest.cell(bench, args.workload)
    cfg = manifest.config(cell["config"])
    mix = manifest.traffic(cell["traffic"])
    enable_compile_cache()
    run.require_chips(cell["chips"])
    clock = mount.Clock()
    corpus, index = mount.build(cfg, args.seed, clock)
    run.report("build", phases=clock.phases)
    best = sweep(cfg, mix, corpus, index, args.seed)["sustained_qps"]
    print(json.dumps({"sustained_qps": best,
                      "cell_rate_qps": None if best is None else 0.8 * best}))


if __name__ == "__main__":
    main()
