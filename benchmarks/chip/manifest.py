"""Find a cell's configuration, traffic mix and metric readers by name.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in files of its own; ``BENCHMARK.json`` names them and this
module finds them:

* ``configs/<config>.json``: the deployment's sizes, and ``configs/<config>.py``
  beside it, its plain reference of the corpus's true scores;
* ``traffic/<mix>.json``: the mix's parameters, read by :mod:`schedule`;
* ``layer_metrics/*.py``: one reader per per-layer metric, each declaring
  its ``NAME`` (files starting with ``_`` are shared helpers).

Unknown keys in a configuration or a mix are refused, so a typo never
silently falls back to a default.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]

CONFIG_KEYS = {"name", "source", "corpus", "records", "reps", "k",
               "embed_dim", "n_train", "triplet_steps", "oracle", "server",
               "precision", "published", "assumed", "reduced", "deployment"}
CORPUS_KEYS = {"class", "size_key", "kwargs"}
ORACLE_KEYS = {"backend", "replicas", "batch"}
SERVER_KEYS = {"max_workers", "admission_window_s"}
MIX_KEYS = {"name", "why", "arrivals", "pool", "sweep"}
ARRIVAL_KEYS = {"rate_qps", "cv"}
POOL_KEYS = {"size", "zipf_s", "kinds"}
KIND_KEYS = {"kind", "share", "scores", "err", "budget", "k_results"}


class ManifestError(ValueError):
    """A benchmark file names something that does not exist or carries a
    key this harness does not know."""


def _check_keys(what: str, d: dict, allowed: set, required: set = frozenset()):
    if not isinstance(d, dict):
        raise ManifestError(f"{what} must be a JSON object")
    unknown = set(d) - allowed
    if unknown:
        raise ManifestError(f"{what}: unknown keys {sorted(unknown)}")
    missing = set(required) - set(d)
    if missing:
        raise ManifestError(f"{what}: missing keys {sorted(missing)}")


def _read_json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise ManifestError(f"no file {path.relative_to(ROOT)}")
    with open(path) as f:
        return json.load(f)


def benchmark(root: pathlib.Path = ROOT) -> dict:
    return _read_json(root / "BENCHMARK.json")


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise ManifestError(f"no workload {name!r} in BENCHMARK.json; known: "
                        f"{[w['name'] for w in bench['workloads']]}")


def config(name: str, base: pathlib.Path = HERE) -> dict:
    cfg = _read_json(base / "configs" / f"{name}.json")
    _check_keys(f"config {name}", cfg, CONFIG_KEYS,
                CONFIG_KEYS - {"deployment"})
    if cfg["name"] != name:
        raise ManifestError(f"configs/{name}.json names itself "
                            f"{cfg['name']!r}")
    _check_keys(f"config {name} corpus", cfg["corpus"], CORPUS_KEYS,
                CORPUS_KEYS)
    _check_keys(f"config {name} oracle", cfg["oracle"], ORACLE_KEYS,
                ORACLE_KEYS)
    _check_keys(f"config {name} server", cfg["server"], SERVER_KEYS,
                SERVER_KEYS)
    return cfg


def truth_module(name: str, base: pathlib.Path = HERE):
    """The configuration's plain reference of true scores
    (``configs/<name>.py``)."""
    path = base / "configs" / f"{name}.py"
    if not path.is_file():
        raise ManifestError(f"no truth module configs/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_truth_{name.replace('-', '_').replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def traffic(name: str, base: pathlib.Path = HERE) -> dict:
    mix = _read_json(base / "traffic" / f"{name}.json")
    _check_keys(f"traffic {name}", mix, MIX_KEYS, {"name", "arrivals", "pool"})
    if mix["name"] != name:
        raise ManifestError(f"traffic/{name}.json names itself "
                            f"{mix['name']!r}")
    _check_keys(f"traffic {name} arrivals", mix.get("arrivals"),
                ARRIVAL_KEYS, ARRIVAL_KEYS)
    _check_keys(f"traffic {name} pool", mix["pool"], POOL_KEYS, POOL_KEYS)
    for i, kind in enumerate(mix["pool"]["kinds"]):
        _check_keys(f"traffic {name} kind {i}", kind, KIND_KEYS,
                    {"kind", "share", "scores"})
    return mix


def layer_metrics(names, base: pathlib.Path = HERE) -> dict:
    """NAME -> reader module for each name asked for, found by the ``NAME``
    each module under ``layer_metrics/`` declares."""
    found = {}
    for path in sorted((base / "layer_metrics").glob("[!_]*.py")):
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{path.stem.replace('.', '_')}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        name = getattr(module, "NAME", None)
        if name is None:
            raise ManifestError(f"layer_metrics/{path.name} declares no NAME")
        if name in found:
            raise ManifestError(f"two readers declare the metric {name!r}")
        found[name] = module
    missing = [n for n in names if n not in found]
    if missing:
        raise ManifestError(f"no reader under layer_metrics/ for {missing}")
    return {n: found[n] for n in names}


def kernel_counts(name: str, base: pathlib.Path = HERE):
    """The operation and byte counts of one kernel (``kernels/<name>.py``)."""
    path = base / "kernels" / f"{name}.py"
    if not path.is_file():
        raise ManifestError(f"no kernel counts kernels/{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_kernel_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def peaks(device_kind: str, base: pathlib.Path = HERE) -> dict:
    table = _read_json(base / "peaks.json")
    if device_kind not in table["devices"]:
        raise ManifestError(f"device kind {device_kind!r} is not in "
                            f"peaks.json ({sorted(table['devices'])})")
    return table["devices"][device_kind]


def limits(base: pathlib.Path = HERE) -> dict:
    """The limit of each number the correctness check compares."""
    return _read_json(base / "limits.json")["limits"]
