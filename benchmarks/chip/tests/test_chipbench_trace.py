"""The trace reduction, the kernels' work counts and the peaks table,
checked on a small trace recorded on a v5e (``record_trace.py``)."""
import json

import pytest

from _bench import HERE

from benchmarks.chip import devtrace, manifest  # noqa: E402

FIXTURE = HERE / "tests" / "fixtures"
META = json.loads((FIXTURE / "small.json").read_text())
PEAKS = manifest.peaks(META["device_kind"])


@pytest.fixture(scope="module")
def red():
    return devtrace.Reduction(str(FIXTURE / "small.xplane.pb"))


def test_window_and_busy_time(red):
    assert 0 < red.window_s <= 1.5 * META["host_window_s"]
    busy = red.busy_s()
    assert 0 < busy < red.window_s
    idle = sum(b - a for a, b in red.idle_gaps())
    assert abs(busy + idle - red.window_s) < 1e-6 * red.window_s + 1e-9
    names = [name for name, _ in red.top_ops()]
    assert names and all(isinstance(n, str) for n in names)


def _share(calls, counts, times):
    least = sum(max(w["flops"] / PEAKS["flops_per_s"],
                    w["bytes"] / PEAKS["hbm_bytes_per_s"])
                for w in (counts.work(**c) for c in calls))
    return 100.0 * least / sum(times)


def test_propagate_calls_and_roofline(red):
    counts = manifest.kernel_counts("propagate")
    times = red.module_calls(counts.MODULE)
    p = META["propagate"]
    calls = [{"records": p["records"], "k": p["k"], "reps": p["reps"],
              "mode": m} for m in p["modes"]] * p["calls_per_mode"]
    assert len(times) == len(calls)
    assert 0 < _share(calls, counts, times) <= 100


def test_distance_topk_calls_and_roofline(red):
    counts = manifest.kernel_counts("distance_topk")
    times = red.module_calls(counts.MODULE)
    d = META["distance_topk"]
    calls = [{"records": d["records"], "width": d["width"],
              "reps": d["reps"], "k": d["k"]}] * d["calls"]
    assert len(times) == len(calls)
    assert 0 < _share(calls, counts, times) <= 100


def test_a_share_above_100_percent_is_an_error(red):
    reader = manifest.layer_metrics(["propagate_roofline"])[
        "propagate_roofline"]
    counts = manifest.kernel_counts("propagate")
    n = len(red.module_calls(counts.MODULE))
    ctx = {"device": red, "kernels": {"propagate": counts},
           "peaks": {"flops_per_s": 1.0, "hbm_bytes_per_s": 1.0},
           "propagate_calls": [{"records": 10**9, "k": 8, "reps": 1,
                                "mode": "numeric"}] * n}
    with pytest.raises(ValueError, match="above 100%"):
        reader.read(ctx)


def test_a_reader_with_nothing_to_read_returns_nothing(red):
    readers = manifest.layer_metrics(["propagate_roofline", "device.idle"])
    ctx = {"device": None, "propagate_calls": []}
    assert readers["propagate_roofline"].read(ctx) is None
    assert readers["device.idle"].read(ctx) is None


def test_gaps_are_named_by_the_open_span(red):
    gaps = red.idle_gaps()
    a, b = max(gaps, key=lambda g: g[1] - g[0])
    offset = 100.0
    spans = [(a + offset - 1, b + offset + 1, "outer"),
             (a + offset, b + offset, "inner")]
    named = devtrace.label_gaps(gaps, offset, spans, n=1)
    assert named == [["inner", b - a]]
    assert devtrace.label_gaps(gaps, offset, [], n=1)[0][0] == "no span open"
