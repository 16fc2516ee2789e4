"""BENCHMARK.json and the files it names: found by name, unknown keys
refused."""
import json
import re

import pytest

from _bench import HERE, ROOT

from benchmarks.chip import manifest  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_every_cell_finds_its_files():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert set(manifest.layer_metrics(names)) == set(names)
    for w in BENCH["workloads"]:
        cfg = manifest.config(w["config"])
        mix = manifest.traffic(w["traffic"])
        truth = manifest.truth_module(w["config"])
        assert cfg["name"] == w["config"] and mix["name"] == w["traffic"]
        assert callable(truth.truth_of)
    for counts in ("propagate", "distance_topk"):
        assert callable(manifest.kernel_counts(counts).work)


def test_names_units_and_moves_follow_the_contract():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for entry in BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"] \
            + BENCH["per_layer"]:
        assert NAME.match(entry["name"]), entry["name"]
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert set(c["reduced"]) <= set(json.loads(
            (ROOT / c["file"]).read_text())["reduced"])


@pytest.mark.parametrize("what,load", [
    ("configs", manifest.config), ("traffic", manifest.traffic)])
def test_unknown_keys_are_refused(tmp_path, what, load):
    src = next((HERE / what).glob("*.json"))
    doc = json.loads(src.read_text())
    doc["colour"] = "blue"
    (tmp_path / what).mkdir()
    (tmp_path / what / src.name).write_text(json.dumps(doc))
    with pytest.raises(manifest.ManifestError, match="colour"):
        load(src.stem, tmp_path)


def test_unknown_names_are_refused(tmp_path):
    with pytest.raises(manifest.ManifestError):
        manifest.cell(BENCH, "no-such-cell")
    with pytest.raises(manifest.ManifestError):
        manifest.config("no-such-config")
    with pytest.raises(manifest.ManifestError):
        manifest.layer_metrics(["no.such_metric"])
    with pytest.raises(manifest.ManifestError, match="not in peaks"):
        manifest.peaks("TPU v99")


def test_a_reader_without_a_name_is_refused(tmp_path):
    (tmp_path / "layer_metrics").mkdir()
    (tmp_path / "layer_metrics" / "anon.py").write_text("X = 1\n")
    with pytest.raises(manifest.ManifestError, match="declares no NAME"):
        manifest.layer_metrics([], tmp_path)
