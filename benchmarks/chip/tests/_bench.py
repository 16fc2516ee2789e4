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


def merged(cfg: dict, part: dict) -> dict:
    """``cfg`` with ``part`` merged over it key by key, nested objects
    included."""
    out = dict(cfg)
    for key, value in part.items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            value = merged(out[key], value)
        out[key] = value
    return out


def tiny_base(tmp: pathlib.Path, cell: str, rate: float = 4.0,
              root: pathlib.Path = ROOT) -> tuple:
    """(benchmark dict, base dir) holding a copy of the benchmark's files
    under ``root`` with the cell's configuration cut to its own test size
    (its ``tiny``) and a CPU entry in the peaks table (never reported:
    these tests emit no device metric)."""
    src = root / "benchmarks" / "chip"
    base = tmp / "chip"
    for d in ("configs", "traffic", "layer_metrics", "kernels"):
        shutil.copytree(src / d, base / d)
    shutil.copy(src / "limits.json", base / "limits.json")
    peaks = json.loads((src / "peaks.json").read_text())
    peaks["devices"]["cpu"] = {"flops_per_s": 1.0, "hbm_bytes_per_s": 1.0,
                               "hbm_bytes": 1.0}
    (base / "peaks.json").write_text(json.dumps(peaks))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    entry = [w for w in bench["workloads"] if w["name"] == cell][0]
    cfg_path = base / "configs" / f"{entry['config']}.json"
    cfg = json.loads(cfg_path.read_text())
    cfg_path.write_text(json.dumps(merged(cfg, cfg["tiny"])))
    mix_path = base / "traffic" / f"{entry['traffic']}.json"
    mix = json.loads(mix_path.read_text())
    mix["arrivals"]["rate_qps"] = rate
    mix_path.write_text(json.dumps(mix))
    return bench, base


def cpu_devices(n: int):
    import jax
    return jax.devices()[:n]
