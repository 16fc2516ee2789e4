"""Readings that set the correctness limits, and the rate sweep and crack
probe that share their set-up, on the chip.

    python3 benchmarks/chip/control.py --workload <cell> --seeds <n> ... \
        [--fault-seconds <s> --fault-seeds <n> ...] \
        [--sweep-seeds <n> ...] [--crack-seconds <s> --crack-seeds <n> ...]

On a TPU only; the benchmark's own runs never run this.  Per seed it
mounts the cell's deployment at its own size (the same build a run's
set-up makes) and reads, against the plain reference:

* the index: ``topk_d2_err`` and ``topk_set_gap`` of the program's top-k
  and of the control, the top-k computed with float8 (e4m3) operands in
  the distance products, one precision below the bfloat16 products the
  device runs (the MXU's default precision for float32 inputs);
* with ``--fault-seeds``: a window of ``--fault-seconds`` at the cell's
  own load with a planted fault, an answer altered where it is produced
  (the oracle returns one altered label per call: a video frame gains an
  object, a text record's operator and predicate count change), checked
  (``answer_gap``, ``fresh_gap``); and, over that window's served
  proxies, the propagation's control: the reference in bfloat16, one
  precision below the float32 of the device path (``proxy_err``,
  ``top1_err``), beside the program's own readings;
* with ``--sweep-seeds``: the rate sweep of :mod:`sweep`;
* with ``--crack-seeds``: the crack probe of :mod:`crack`, last, since it
  changes the index.

The index readings print as soon as they are read; then one JSON line
per seed holds them all.
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

import numpy as np  # noqa: E402

from benchmarks.chip import check, crack, manifest, mount, reference, run, schedule, sweep  # noqa: E402,E501
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402


def altered(label):
    """One label, altered: a frame gains an object on the left, a text
    record's operator and predicate count move by one."""
    boxes = getattr(label, "boxes", None)
    if boxes is not None:
        extra = np.asarray([[0.1, 0.5]], boxes.dtype)
        return type(label)(boxes=np.concatenate([boxes, extra]))
    return type(label)(op=(label.op + 1) % 6,
                       n_predicates=(label.n_predicates + 1) % 5)


def plant_altered_label(corpus):
    """Alter the first label of every oracle call; returns the undo."""
    produce = corpus.target_dnn_batch

    def target_dnn_batch(ids):
        out = list(produce(ids))
        if out:
            out[0] = altered(out[0])
        return out

    corpus.target_dnn_batch = target_dnn_batch

    def undo():
        corpus.target_dnn_batch = produce

    return undo


def control_readings(loaded: check.Loaded) -> dict:
    return {"program": check.proxy_errs(loaded),
            "control": check.proxy_errs(loaded, reference.propagate_bf16)}


def fault_window(cfg, mix, truth, limits, corpus, index, seed: int,
                 seconds: float) -> dict:
    undo = plant_altered_label(corpus)
    oracle = mount.OracleCounter(corpus)
    workdir = pathlib.Path(tempfile.mkdtemp(prefix="chip-control-"))
    server = None
    try:
        clock = mount.Clock()
        server, engine = mount.serve(cfg, corpus, index,
                                     str(workdir / "labels"), clock, 256)
        mount.warm_up(engine, mix, clock)
        plan = {"requests": schedule.open_loop_plan(mix, seed, seconds),
                "url": server.url, "seconds": seconds,
                "grace_s": run.GRACE_S}
        child, out_path = run.start_loadgen(plan, workdir)
        t0, _, _ = run.run_window(child, seconds, None)
        args = argparse.Namespace(seconds=seconds, seed=seed)
        loaded = check.collect(server, engine, corpus,
                               json.loads(out_path.read_text()), plan, args,
                               mix, cfg, truth, t0, oracle.labels)
        server.shutdown()
        server = None
        fault = check.compare(loaded, limits)
    finally:
        if server is not None:
            server.shutdown()
        shutil.rmtree(workdir, ignore_errors=True)
        undo()
    out = control_readings(loaded)
    out["fault_altered_label"] = {k: v["value"] for k, v in fault.items()}
    return out


def one_seed(cfg, mix, truth, limits, seed: int, args,
             compile_clock) -> dict:
    clock = mount.Clock()
    corpus, index = mount.build(cfg, seed, clock)
    sample = check.topk_sample(index, seed)
    out = {"seed": seed, "build": clock.phases,
           "index": {"program": check.topk_errs(sample),
                     "control": check.topk_errs(sample, control=True)}}
    print(json.dumps(out), flush=True)
    if seed in args.sweep_seeds:
        out["sweep"] = sweep.sweep(cfg, mix, corpus, index, seed)
    if seed in args.fault_seeds:
        out.update(fault_window(cfg, mix, truth, limits, corpus, index, seed,
                                args.fault_seconds))
    if seed in args.crack_seeds:
        out["crack"] = crack.probe(cfg, mix, corpus, index, seed,
                                   args.crack_seconds, compile_clock)
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault-seconds", type=float, default=20.0)
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--sweep-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--crack-seconds", type=float, default=60.0)
    ap.add_argument("--crack-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    cell = manifest.cell(manifest.benchmark(), args.workload)
    cfg = manifest.config(cell["config"])
    mix = manifest.traffic(cell["traffic"])
    truth = manifest.truth_module(cell["config"])
    limits = manifest.limits()
    enable_compile_cache()
    run.require_chips(cell["chips"])
    compile_clock = run.CompileClock()
    for seed in args.seeds:
        print(json.dumps(one_seed(cfg, mix, truth, limits, seed, args,
                                  compile_clock)), flush=True)


if __name__ == "__main__":
    main()
