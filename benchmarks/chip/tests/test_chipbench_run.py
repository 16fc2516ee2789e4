"""The harness's phases at a tiny size on the CPU, called as functions:
set-up, the window through the load generator, and the correctness check.
The look for a chip is replaced by the CPU device; these runs emit no
device metric.  Then the same run with the timed path broken underneath,
once per fault the cells can have, must come out not correct."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from _bench import HERE, ROOT, cpu_devices, tiny_base

from benchmarks.chip import run  # noqa: E402

CELLS = [w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"]]
SECONDS = "3"


@pytest.fixture
def device_path(monkeypatch):
    # the device-resident scoring path (XLA reference off the TPU), as the
    # chip runs it, and no persistent compile cache
    monkeypatch.setenv("REPRO_RESIDENT_SCORING", "1")


def tiny_run(tmp_path, cell: str, seed: int = 2**31 + 5) -> dict:
    bench, base = tiny_base(tmp_path, cell)
    args = run.parse_args(["--workload", cell, "--seed", str(seed),
                           "--seconds", SECONDS, "--trace", "0"])
    return run.run_cell(args, bench, base, cpu_devices), bench


@pytest.mark.parametrize("cell", CELLS)
def test_a_tiny_run_is_correct(tmp_path, device_path, cell):
    line, bench = tiny_run(tmp_path, cell)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    want = {m["name"] for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(line["metrics"]) == want
    assert "busy_s" not in line["device"] and "breakdown" not in line
    assert list(line)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in line["checks"].values())


def _shift_estimate(monkeypatch):
    from repro.core.queries import aggregation
    summarize = aggregation.AggregationExecutor.summarize

    def shifted(self, raw):
        out = summarize(self, raw)
        out["estimate"] += 0.01
        return out

    monkeypatch.setattr(aggregation.AggregationExecutor, "summarize", shifted)


def _half_the_neighbours(monkeypatch):
    from repro.kernels.propagate import ops
    propagate = ops.propagate

    def half(rep_scores, topk_ids, topk_d2, mode, **kw):
        k = topk_ids.shape[1] // 2
        return propagate(rep_scores, topk_ids[:, :k], topk_d2[:, :k], mode,
                         **kw)

    monkeypatch.setattr(ops, "propagate", half)


def _altered_label(monkeypatch):
    from repro.core import schema

    from benchmarks.chip import control
    for cls in (schema.VideoWorkload, schema.TextWorkload):
        produce = cls.target_dnn_batch

        def target_dnn_batch(self, ids, produce=produce):
            out = list(produce(self, ids))
            if out:
                out[0] = control.altered(out[0])
            return out

        monkeypatch.setattr(cls, "target_dnn_batch", target_dnn_batch)


def _float8_index(monkeypatch):
    import jax.numpy as jnp

    from benchmarks.chip import reference
    from repro.core import index
    distance_topk = index.distance_topk

    def coarse(x, r, k, **kw):
        return distance_topk(jnp.asarray(reference._fp8(np.asarray(x))),
                             jnp.asarray(reference._fp8(np.asarray(r))), k,
                             **kw)

    monkeypatch.setattr(index, "distance_topk", coarse)


@pytest.mark.parametrize("fault,caught_by", [
    (_shift_estimate, "answer_gap"),
    (_half_the_neighbours, "proxy_err"),
    (_altered_label, "answer_gap"),
    (_float8_index, "topk_d2_err"),
])
def test_a_broken_timed_path_is_not_correct(tmp_path, device_path,
                                           monkeypatch, fault, caught_by):
    fault(monkeypatch)
    line, _ = tiny_run(tmp_path, CELLS[0], seed=11)
    assert not line["correct"]
    check = line["checks"][caught_by]
    assert check["value"] > check["limit"]


def test_a_cpu_is_refused():
    with pytest.raises(run.Refused, match="needs a TPU"):
        run.require_chips(1)


def _command(cwd) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELLS[0],
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_the_command_exits_non_zero_without_a_tpu():
    out = _command(ROOT)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout


def test_the_command_exits_non_zero_with_only_the_benchmark(tmp_path):
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _command(tmp_path)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout


def test_reference_replay_matches_a_hand_count():
    from benchmarks.chip import reference
    proxy = np.linspace(1.0, 0.0, 100)
    truth = (np.arange(100) % 7 == 0).astype(np.float64)
    ans, labeled = reference.limit(proxy, lambda ids: truth[ids], 3)
    assert ans["selected_head"] == [0, 7, 14]
    assert ans["n_invocations"] == 15 and len(labeled) == 16
