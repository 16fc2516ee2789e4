"""Shared set-up of the chip benchmark's CPU tests: import paths, and a
tiny copy of the benchmark's files that the harness can run on the CPU."""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[3]
HERE = ROOT / "benchmarks" / "chip"
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

#: sizes small enough for a test run; widths (k, embedding) as published
TINY = {"night-street-1m": {"records": 3000},
        "wikisql-80k": {"records": 4000}}
TINY_INDEX = {"reps": 150, "n_train": 60, "triplet_steps": 20}


def tiny_base(tmp: pathlib.Path, cell: str, rate: float = 4.0) -> tuple:
    """(benchmark dict, base dir) holding a copy of the benchmark's files
    with the cell's configuration cut to a test's size and a CPU entry in
    the peaks table (never reported: these tests emit no device metric)."""
    base = tmp / "chip"
    for d in ("configs", "traffic", "layer_metrics", "kernels"):
        shutil.copytree(HERE / d, base / d)
    shutil.copy(HERE / "limits.json", base / "limits.json")
    peaks = json.loads((HERE / "peaks.json").read_text())
    peaks["devices"]["cpu"] = {"flops_per_s": 1.0, "hbm_bytes_per_s": 1.0,
                               "hbm_bytes": 1.0}
    (base / "peaks.json").write_text(json.dumps(peaks))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = [w for w in bench["workloads"] if w["name"] == cell][0]
    cfg_path = base / "configs" / f"{entry['config']}.json"
    cfg = json.loads(cfg_path.read_text())
    cfg.update(TINY[entry["config"]], **TINY_INDEX)
    cfg_path.write_text(json.dumps(cfg))
    mix_path = base / "traffic" / f"{entry['traffic']}.json"
    mix = json.loads(mix_path.read_text())
    mix["arrivals"]["rate_qps"] = rate
    mix_path.write_text(json.dumps(mix))
    return bench, base


def cpu_devices(n: int):
    import jax
    return jax.devices()[:n]
