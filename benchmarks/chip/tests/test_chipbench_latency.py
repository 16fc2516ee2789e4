"""Latency over every request due in the window, failures as misses."""
from _bench import ROOT  # noqa: F401

from benchmarks.chip import latency  # noqa: E402


def _row(due, done, status=200):
    return {"due": due, "fired": due, "done": done, "status": status,
            "body": {"request": {"fresh": 3}} if status == 200 else "boom"}


def test_latency_runs_from_the_due_time_and_misses_rank_last():
    due = [0.0, 1.0, 2.0, 3.0]
    rows = {0: _row(0.0, 0.5), 1: _row(1.0, 1.1), 2: _row(2.0, 2.2, 500)}
    lat = latency.latencies(due, rows, wait_end=70.0)
    assert lat[:2] == [0.5, 0.10000000000000009]
    assert lat[2] == 68.0 and lat[3] == 67.0   # failed and never answered
    assert latency.percentile(lat, 50) == 0.5
    assert latency.percentile(lat, 95) == 68.0


def test_an_answer_after_the_wait_is_a_miss():
    lat = latency.latencies([0.0], {0: _row(0.0, 80.0)}, wait_end=70.0)
    assert lat == [70.0]


def test_nearest_rank_percentile():
    values = list(range(1, 201))
    assert latency.percentile(values, 95) == 190
    assert latency.percentile(values, 50) == 100
    assert latency.percentile([7.0], 95) == 7.0
