"""Arrival schedules and spec pools come from the seed alone, and every
seed gets the same work in another order."""
import collections
import json

import numpy as np
import pytest

from _bench import HERE

from benchmarks.chip import manifest, schedule  # noqa: E402

MIXES = sorted(p.stem for p in (HERE / "traffic").glob("*.json"))
BIG = 2**31 + 12345


def _work(plan):
    return collections.Counter(json.dumps(b, sort_keys=True) for _, b in plan)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_plan(name):
    mix = manifest.traffic(name)
    assert schedule.open_loop_plan(mix, BIG, 30.0) == \
        schedule.open_loop_plan(mix, BIG, 30.0)


@pytest.mark.parametrize("name", MIXES)
def test_seeds_share_the_work_and_the_gaps(name):
    mix = manifest.traffic(name)
    a = schedule.open_loop_plan(mix, 1, 30.0)
    b = schedule.open_loop_plan(mix, BIG, 30.0)
    strip = [{k: v for k, v in s["specs"][0].items() if k != "seed"}
             for _, s in a], [{k: v for k, v in s["specs"][0].items()
                               if k != "seed"} for _, s in b]
    count = [collections.Counter(json.dumps(s, sort_keys=True) for s in x)
             for x in strip]
    assert count[0] == count[1]
    gaps = [np.sort(np.diff([t for t, _ in p] + [30.0])) for p in (a, b)]
    np.testing.assert_allclose(gaps[0], gaps[1], rtol=0, atol=1e-9)
    assert [t for t, _ in a] != [t for t, _ in b]
    assert _work(a) != _work(b)          # the specs' sampling seeds differ


def test_arrivals_span_the_window_at_the_rate():
    due = schedule.arrival_offsets(5.0, 2.0, 40.0, 7)
    assert len(due) == 200 and due[0] == 0.0
    assert np.all(np.diff(due) > 0) and due[-1] < 40.0
    gaps = np.diff(due)
    assert gaps.std() / gaps.mean() > 1.5          # bursty at cv 2


def test_pool_composition_follows_the_shares():
    mix = manifest.traffic("video-interactive")
    pool = schedule.spec_pool(mix["pool"], 3)
    kinds = collections.Counter(s["kind"] for s in pool)
    assert kinds == {"aggregation": 154, "selection": 64, "limit": 38}
    assert {s["kind"] for s in pool[:5]} == {"aggregation", "selection",
                                              "limit"}
    errs = [s["err"] for s in pool if s["kind"] == "aggregation"]
    assert min(errs) == 0.02 and max(errs) == 0.05


def test_zipf_quotas_are_fixed():
    rng = np.random.default_rng(0)
    ranks = schedule.zipf_requests(256, 1.0, 300, rng)
    counts = np.bincount(ranks, minlength=256)
    assert counts.sum() == 300 and counts[0] == counts.max()
    again = np.bincount(schedule.zipf_requests(256, 1.0, 300,
                                               np.random.default_rng(9)),
                        minlength=256)
    assert np.array_equal(counts, again)
