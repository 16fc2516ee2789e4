"""A configuration brings its own target labeler, test cut, checks and limits
in files of its own.  The fixture under ``fixtures/labeled/`` (a text corpus
labeled by a model, its plain reference, limits and traffic) gets a cell
from new files and entries in ``BENCHMARK.json`` alone; the labeler's logits
decide ``correct``; today's configurations keep the oracle as the program
made it; the harness refuses numbers and limits that do not pair up; and
the per-layer readers find every kernel's counts and the trace file."""
import importlib.util
import json
import shutil
import sys
import types

import numpy as np
import pytest

from _bench import HERE, ROOT, cpu_devices, merged, tiny_base

from benchmarks.chip import check, devtrace, manifest, mount, run  # noqa: E402

FIXTURE = HERE / "tests" / "fixtures"
LABELED = FIXTURE / "labeled"
CELL = "labeled-text.interactive"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())

_spec = importlib.util.spec_from_file_location("labeled_text_labeler",
                                               LABELED / "labeler.py")
LABELER = sys.modules[_spec.name] = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(LABELER)


def with_fixture(dest):
    """A checkout under ``dest`` holding the benchmark's files and, added to
    them, the fixture's configuration, truth module, traffic and entries in
    ``BENCHMARK.json``; returns its root."""
    root = dest / "checkout"
    shutil.copytree(HERE, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for d in ("configs", "traffic"):
        for path in (LABELED / d).iterdir():
            shutil.copy(path, root / "benchmarks" / "chip" / d / path.name)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for key, added in json.loads(
            (LABELED / "benchmark_entries.json").read_text()).items():
        bench[key] += added
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def labeled_run(tmp, mp, seed: int):
    """One tiny run of the fixture's cell, with its labeler installed into
    the program's schema as a program that has it would hold it; returns
    (result line, checkout root, base)."""
    from repro.core import schema
    mp.setenv("REPRO_RESIDENT_SCORING", "1")
    mp.setattr(schema, "LabeledTextWorkload", LABELER.LabeledTextWorkload,
               raising=False)
    root = with_fixture(tmp)
    bench, base = tiny_base(tmp, CELL, root=root)
    args = run.parse_args(["--workload", CELL, "--seed", str(seed),
                           "--seconds", "3", "--trace", "0"])
    return run.run_cell(args, bench, base, cpu_devices), root, base


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    with pytest.MonkeyPatch.context() as mp:
        return labeled_run(tmp_path_factory.mktemp("labeled"), mp,
                           seed=2**31 + 21)


def test_a_tiny_run_of_a_model_labeled_config_is_correct(sound):
    line, _, _ = sound
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    own = json.loads((LABELED / "configs" / "labeled-text.json").read_text())
    for name, entry in own["limits"].items():
        assert line["checks"][name]["limit"] == entry["limit"]
        assert 0 <= line["checks"][name]["value"] <= entry["limit"]
    assert set(manifest.limits()) < set(line["checks"])
    assert list(line)[-1] == "checks"


def _scaled_logits(mp):
    logits = LABELER.LabeledTextWorkload.logits
    mp.setattr(LABELER.LabeledTextWorkload, "logits",
               lambda self, x: np.float32(1.01) * logits(self, x))


def _float8_products(mp):
    import jax.numpy as jnp
    f8 = jnp.float8_e4m3fn

    def logits(self, x):
        h = jnp.tanh(jnp.dot(jnp.asarray(x, f8), self.w1.astype(f8),
                             preferred_element_type=jnp.float32))
        return np.asarray(jnp.dot(h.astype(f8), self.w2.astype(f8),
                                  preferred_element_type=jnp.float32))

    mp.setattr(LABELER.LabeledTextWorkload, "logits", logits)


def _altered_label(mp):
    produce = LABELER.LabeledTextWorkload.target_dnn_batch

    def target_dnn_batch(self, ids):
        out = produce(self, ids)
        if out:
            out[0] = dict(out[0], label=(out[0]["label"] + 1) % 6)
        return out

    mp.setattr(LABELER.LabeledTextWorkload, "target_dnn_batch",
               target_dnn_batch)


@pytest.mark.parametrize("fault,caught_by", [
    (_scaled_logits, "labeler_logit_err"),
    (_float8_products, "labeler_logit_err"),
    (_altered_label, "labeler_label_gap"),
])
def test_a_broken_labeler_is_not_correct(tmp_path, monkeypatch, fault,
                                         caught_by):
    fault(monkeypatch)
    line, _, _ = labeled_run(tmp_path, monkeypatch, seed=2**31 + 22)
    assert not line["correct"]
    check_ = line["checks"][caught_by]
    assert check_["value"] > check_["limit"]


def test_new_files_and_entries_alone_give_a_model_labeled_cell(sound):
    line, root, base = sound
    copy = root / "benchmarks" / "chip"
    for path in HERE.rglob("*"):
        rel = path.relative_to(HERE)
        if path.is_file() and rel.parts[0] != "tests" \
                and "__pycache__" not in rel.parts:
            assert (copy / rel).read_bytes() == path.read_bytes(), rel
    added = sorted(str(p.relative_to(copy)) for p in copy.rglob("*")
                   if p.is_file() and not (HERE / p.relative_to(copy))
                   .exists())
    assert added == ["configs/labeled-text.json", "configs/labeled-text.py",
                     "traffic/labeled-text-interactive.json"]
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        assert bench[key][:len(BENCH[key])] == BENCH[key]
    # the test cut came from the configuration's own ``tiny``, nested
    # objects merged key by key
    cut = json.loads((base / "configs" / "labeled-text.json").read_text())
    assert cut["records"] == 4000 and cut["reps"] == 150
    assert cut["corpus"]["kwargs"] == {"name": "labeled-text", "hidden": 128}
    assert cut["corpus"]["class"] == "LabeledTextWorkload"
    assert line["correct"]


def test_the_tiny_merge_keeps_what_it_does_not_name():
    cfg = {"a": 1, "b": {"c": 2, "d": {"e": 3, "f": 4}}}
    assert merged(cfg, {"b": {"d": {"e": 5}}, "g": 6}) == {
        "a": 1, "b": {"c": 2, "d": {"e": 5, "f": 4}}, "g": 6}
    assert cfg["b"]["d"]["e"] == 3


class _Built(Exception):
    pass


def _wrapped_at_build(monkeypatch, cfg, truth) -> bool:
    """Whether the corpus's oracle was wrapped when the index build began."""
    from repro.core import pipeline
    seen = []

    def build_tasti(corpus, _cfg):
        seen.append("target_dnn_batch" in vars(corpus))
        raise _Built

    monkeypatch.setattr(pipeline, "build_tasti", build_tasti)
    with pytest.raises(_Built):
        mount.build(merged(cfg, cfg["tiny"]), 3, mount.Clock(),
                    mount.served_outputs(truth))
    return seen[0]


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_todays_configs_install_no_served_output_wrapper(monkeypatch,
                                                         config):
    truth = manifest.truth_module(config)
    assert mount.served_outputs(truth) is None
    assert not hasattr(truth, "checks")
    assert not _wrapped_at_build(monkeypatch, manifest.config(config), truth)


def test_a_config_that_reads_served_outputs_records_them_from_set_up(
        monkeypatch):
    from repro.core import schema
    monkeypatch.setattr(schema, "LabeledTextWorkload",
                        LABELER.LabeledTextWorkload, raising=False)
    base = LABELED
    truth = manifest.truth_module("labeled-text", base)
    assert _wrapped_at_build(monkeypatch, manifest.config("labeled-text",
                                                          base), truth)
    corpus = LABELER.LabeledTextWorkload(n_records=200, seed=5, hidden=32)
    served = mount.served_outputs(truth)
    served.wrap(corpus)
    out = corpus.target_dnn_batch([7, 3])
    assert served.outputs == {7: out[0], 3: out[1]}


def _fixture_base(tmp_path):
    base = tmp_path / "chip"
    shutil.copytree(LABELED / "configs", base / "configs")
    shutil.copy(HERE / "limits.json", base / "limits.json")
    return base


def _drop_a_limit(cfg, module):
    del cfg["limits"]["labeler_label_gap"]
    return cfg, module


def _add_a_limit(cfg, module):
    cfg["limits"]["labeler_top5"] = {"limit": 1.0, "why": "no number"}
    return cfg, module


def _collide(cfg, module):
    cfg["limits"]["answer_gap"] = {"limit": 1.0, "why": "shared name"}
    return cfg, module.replace('"labeler_label_gap")',
                               '"labeler_label_gap", "answer_gap")')


def _drop_tiny(cfg, module):
    del cfg["tiny"]
    return cfg, module


def _checks_without_names(cfg, module):
    return cfg, module.replace("CHECKS = ", "NAMES = ")


@pytest.mark.parametrize("edit,refusal", [
    (_drop_a_limit, "no limit for the numbers"),
    (_add_a_limit, "have no number"),
    (_collide, "collide with limits.json"),
    (_drop_tiny, r"missing keys \['tiny'\]"),
    (_checks_without_names, "without CHECKS"),
])
def test_numbers_and_limits_that_do_not_pair_up_are_refused(tmp_path, edit,
                                                           refusal):
    base = _fixture_base(tmp_path)
    cfg_path = base / "configs" / "labeled-text.json"
    py_path = base / "configs" / "labeled-text.py"
    cfg, module = edit(json.loads(cfg_path.read_text()),
                       py_path.read_text())
    cfg_path.write_text(json.dumps(cfg))
    py_path.write_text(module)
    with pytest.raises(manifest.ManifestError, match=refusal):
        manifest.cell_limits(manifest.config("labeled-text", base),
                             manifest.truth_module("labeled-text", base),
                             base)


def test_numbers_the_checks_return_must_match_the_limits():
    assert check.judged({"a": 0.5}, {"a": 1.0}) == {
        "a": {"value": 0.5, "limit": 1.0}}
    with pytest.raises(manifest.ManifestError, match="have no limit"):
        check.judged({"a": 0.5, "b": 0.0}, {"a": 1.0})


def test_readers_get_every_kernels_counts_and_the_trace_file(capsys):
    seen = {}
    spy = types.SimpleNamespace(read=lambda ctx: seen.update(ctx))
    loaded = check.Loaded(
        due=[], specs=[], rows={}, wait_end=0.0, t0=0.0, proxies={},
        rep_ids=np.zeros(0, np.int64), topk_ids=np.zeros((0, 8), np.int64),
        topk_d2=np.zeros((0, 8)), truth=None)
    metrics, extra = check.layer_metrics(
        loaded, {"spy": spy}, [{"name": "spy", "unit": "%"}], FIXTURE,
        0.0, 0, {}, HERE)
    assert set(seen["kernels"]) == {p.stem for p in
                                    (HERE / "kernels").glob("*.py")}
    for counts in seen["kernels"].values():
        assert callable(counts.work)
    assert seen["xplane"] == devtrace.find_xplane(FIXTURE)
    assert metrics == {} and extra["device"]["busy_s"] > 0
    idle_lines = [ln for ln in capsys.readouterr().out.splitlines()
                  if ln.startswith("[idle] ")]
    assert len(idle_lines) == 1
    assert json.loads(idle_lines[0][len("[idle] "):])["idle_s"] > 0
