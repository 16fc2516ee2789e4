"""Multi-query session tests: joint planning, shared stratified samples,
broker-prefetched combined flushes, combined budgets, exact per-spec
accounting under dedup, and cracking mid-session."""
import numpy as np
import pytest

from repro.core import propagation
from repro.core.engine import QueryEngine, QuerySpec
from repro.core.index import TastiIndex
from repro.core.queries.aggregation import stratified_order
from repro.core.schema import make_workload
from repro.core.session import QuerySession


@pytest.fixture(scope="module")
def wl():
    return make_workload("night-street", n_frames=1500)


@pytest.fixture()
def make_engine(wl):
    index = TastiIndex.build(wl.features, 150, wl.target_dnn_batch, k=4,
                             random_fraction=0.0, seed=0)

    def _make(**kw):
        return QueryEngine(index, wl, **kw)

    return _make


# -- stratified order ------------------------------------------------------
def test_stratified_order_is_balanced_permutation():
    rng = np.random.default_rng(0)
    proxy = rng.normal(size=1000)
    order = stratified_order(proxy, n_strata=10, seed=1)
    np.testing.assert_array_equal(np.sort(order), np.arange(1000))
    ranks = np.argsort(np.argsort(proxy))
    strata = (ranks * 10) // 1000
    for m in (50, 100, 400):
        counts = np.bincount(strata[order[:m]], minlength=10)
        assert counts.max() - counts.min() <= 1, (m, counts)


def test_stratified_order_tiny_inputs():
    assert len(stratified_order(np.asarray([0.3]), n_strata=10)) == 1
    order = stratified_order(np.arange(5.0), n_strata=10)
    np.testing.assert_array_equal(np.sort(order), np.arange(5))


# -- the engine's sample-order memo ----------------------------------------
def _agg(**kw):
    return QuerySpec(kind="aggregation", score="score_count", **kw)


def test_second_session_reuses_the_sample_order(make_engine):
    eng = make_engine()
    first = QuerySession(eng, [_agg(err=0.1)]).plan()
    second = QuerySession(eng, [_agg(err=0.05, seed=3)]).plan()
    assert eng.stats["sample_order_computes"] == 1
    assert eng.stats["sample_order_hits"] == 1
    expect = stratified_order(eng.proxy_for(first.plans[0]), 10, 0)
    np.testing.assert_array_equal(second.plans[0].shared_order, expect)
    assert second.plans[0].shared_order is first.plans[0].shared_order
    assert "10 strata, computed" in first.trace[1]
    assert "10 strata, reused" in second.trace[1]


@pytest.mark.parametrize("changed", [{"seed": 1}, {"n_strata": 5}])
def test_other_seed_or_strata_recompute_and_replace(make_engine, changed):
    eng = make_engine()
    plan = eng.plan(_agg())
    args = {"n_strata": 10, "seed": 0}
    eng.sample_order(plan, **args)
    order, reused = eng.sample_order(plan, **{**args, **changed})
    assert not reused
    np.testing.assert_array_equal(
        order, stratified_order(eng.proxy_for(plan), **{**args, **changed}))
    assert eng.sample_order(plan, **{**args, **changed})[1]
    # the entry was replaced: the first arguments compute again
    assert not eng.sample_order(plan, **args)[1]
    assert eng.stats["sample_order_computes"] == 3
    assert eng.stats["sample_order_hits"] == 1


def test_a_crack_recomputes_the_sample_order(make_engine, monkeypatch):
    import repro.core.engine as engine_mod
    eng = make_engine()
    plan = eng.plan(_agg())
    eng.sample_order(plan)
    version = eng.index.version
    assert eng.crack_with(np.arange(40)) > 0
    assert eng.index.version > version
    order, reused = eng.sample_order(plan)
    assert not reused
    np.testing.assert_array_equal(
        order, stratified_order(eng.proxy_for(plan), 10, 0))

    # an order computed across a crack is returned but not stored
    orig = engine_mod.stratified_order

    def cracking(*a, **kw):
        eng.crack_with(np.arange(40, 80))
        return orig(*a, **kw)

    eng.sample_order(plan, seed=1)  # replace the entry, so the next computes
    monkeypatch.setattr(engine_mod, "stratified_order", cracking)
    eng.sample_order(plan)
    monkeypatch.setattr(engine_mod, "stratified_order", orig)
    assert not eng.sample_order(plan)[1]
    assert eng.stats["sample_order_computes"] == 5
    assert eng.stats["sample_order_hits"] == 0


def test_the_shared_sample_order_is_read_only(make_engine):
    eng = make_engine()
    order, _ = eng.sample_order(eng.plan(_agg()))
    assert not order.flags.writeable
    with pytest.raises(ValueError):
        order[0] = 1


def test_threaded_sessions_over_one_engine_match_fresh_engines(make_engine):
    import threading
    # label reuse off: each session's fresh count is its own, whatever the
    # other sessions labeled first
    batches = [[_agg(err=0.1, seed=s, reuse_labels=False),
                _agg(err=0.06, seed=s + 1, reuse_labels=False)]
               for s in range(4)]
    want = [QuerySession(make_engine(), specs).execute().results
            for specs in batches]
    eng = make_engine()
    barrier = threading.Barrier(len(batches))
    got, errs = [None] * len(batches), []

    def run(i):
        try:
            barrier.wait(10)
            got[i] = QuerySession(eng, batches[i]).execute().results
        except BaseException as e:  # noqa: BLE001 - surfaced below
            errs.append(e)

    threads = [threading.Thread(target=run, args=(i,))
               for i in range(len(batches))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    for w, g in zip(want, got):
        for a, b in zip(w, g):
            assert a.estimate == b.estimate
            assert a.ci_half_width == b.ci_half_width
            assert a.n_oracle_fresh == b.n_oracle_fresh
            np.testing.assert_array_equal(a.raw.sampled_ids, b.raw.sampled_ids)
    stats = eng.stats
    assert stats["sample_order_computes"] >= 1
    assert stats["sample_order_computes"] + stats["sample_order_hits"] == 4


# -- accounting under dedup ------------------------------------------------
def test_record_labeled_in_spec_a_is_free_in_spec_b(make_engine):
    eng = make_engine()
    specs = [QuerySpec(kind="selection", score="score_has_object",
                       budget=150, seed=0),
             QuerySpec(kind="selection", score="score_has_object",
                       budget=150, seed=0)]
    out = QuerySession(eng, specs).execute()
    ra, rb = out.results
    assert ra.n_oracle_fresh > 0
    assert rb.n_oracle_fresh == 0          # identical sample: all free
    assert rb.n_oracle_cached == 150
    # counters stay exact under dedup + prefetch: every requested label is
    # either fresh-once or cached, per spec
    assert ra.n_oracle_fresh + ra.n_oracle_cached == 150
    assert out.stats["fresh_total"] == ra.n_oracle_fresh


def test_session_counters_match_engine_and_broker(make_engine):
    eng = make_engine()
    specs = [QuerySpec(kind="aggregation", score="score_count", err=0.1),
             QuerySpec(kind="selection", score="score_has_object",
                       budget=200, seed=1),
             QuerySpec(kind="limit", score="score_has_object", k_results=5)]
    out = QuerySession(eng, specs).execute()
    assert out.stats["fresh_total"] == sum(r.n_oracle_fresh
                                           for r in out.results)
    assert out.stats["cached_total"] == sum(r.n_oracle_cached
                                            for r in out.results)
    assert eng.broker.stats["fresh"] == out.stats["fresh_total"]
    assert eng.stats["label_fresh"] == out.stats["fresh_total"]
    # every result carries the session-level snapshot
    for i, r in enumerate(out.results):
        assert r.session["spec_index"] == i
        assert r.session["session_fresh_total"] == out.stats["fresh_total"]


def test_session_strictly_fewer_fresh_than_isolated(make_engine, wl):
    specs = [QuerySpec(kind="aggregation", score="score_has_object",
                       err=0.08, seed=0),
             QuerySpec(kind="aggregation", score="score_has_object",
                       err=0.05, seed=1),
             QuerySpec(kind="selection", score="score_has_object",
                       budget=300, seed=0),
             QuerySpec(kind="limit", score="score_has_object", k_results=5)]
    iso = [make_engine().execute(s) for s in specs]
    iso_fresh = sum(r.n_oracle_fresh for r in iso)
    out = QuerySession(make_engine(), specs).execute()
    assert out.stats["fresh_total"] < iso_fresh
    # answers stay faithful: aggregation estimates agree across modes
    assert abs(out.results[0].estimate - iso[0].estimate) < 0.1


def test_shared_stratified_sample_nests_aggregations(make_engine):
    eng = make_engine()
    specs = [QuerySpec(kind="aggregation", score="score_count", err=0.15,
                       seed=0),
             QuerySpec(kind="aggregation", score="score_count", err=0.05,
                       seed=7)]
    out = QuerySession(eng, specs).execute()
    a, b = (r.raw for r in out.results)
    small, large = sorted([set(a.sampled_ids.tolist()),
                           set(b.sampled_ids.tolist())], key=len)
    assert small <= large  # nested samples off the one shared order
    g = out.plan.groups[0]
    assert g.shared_order and len(out.plan.groups) == 1


def test_propagation_computed_once_per_mode_in_session(make_engine,
                                                       monkeypatch):
    eng = make_engine()
    calls = []
    orig = propagation.propagate_numeric

    def counting(*a, **kw):
        calls.append(1)
        return orig(*a, **kw)

    monkeypatch.setattr(propagation, "propagate_numeric", counting)
    specs = [QuerySpec(kind="aggregation", score="score_count", err=0.1),
             QuerySpec(kind="aggregation", score="score_count", err=0.05,
                       seed=3),
             QuerySpec(kind="selection", score="score_count", budget=100)]
    QuerySession(eng, specs).execute()
    assert len(calls) == 1  # one score fn, one numeric propagation


# -- combined budget -------------------------------------------------------
def test_combined_budget_caps_fresh_labels(make_engine):
    specs = [QuerySpec(kind="aggregation", score="score_count", err=0.001),
             QuerySpec(kind="selection", score="score_has_object",
                       budget=800, seed=2),
             QuerySpec(kind="limit", score="score_rare", k_results=10 ** 6)]
    budget = 400
    out = QuerySession(make_engine(), specs, budget=budget).execute()
    assert out.stats["fresh_total"] <= budget
    assert sum(out.plan.allocations) <= budget
    # the original specs are not mutated by the clamping
    assert specs[1].budget == 800 and specs[2].max_invocations == 0


def test_tiny_budget_never_overshoots(make_engine):
    # flooring allocations at one label each must not breach the cap
    specs = [QuerySpec(kind="selection", score="score_has_object",
                       budget=1000, seed=i) for i in range(5)]
    out = QuerySession(make_engine(), specs, budget=7).execute()
    assert sum(out.plan.allocations) <= 7
    assert out.stats["fresh_total"] <= 7
    with pytest.raises(ValueError, match="budget"):
        QuerySession(make_engine(), specs, budget=3).plan()


def test_crack_with_goes_through_broker(make_engine, wl, monkeypatch):
    eng = make_engine(max_oracle_batch=16)
    batches = []
    orig = wl.target_dnn_batch

    def spy(ids):
        batches.append(len(ids))
        return orig(ids)

    monkeypatch.setattr(wl, "target_dnn_batch", spy)
    added = eng.crack_with(np.arange(40))  # unlabeled: broker microbatches
    assert added > 0
    assert batches and max(batches) <= 16
    assert eng.broker.stats["fresh"] == 40
    assert eng.stats["label_fresh"] == 40


def test_budget_large_enough_leaves_specs_alone(make_engine):
    specs = [QuerySpec(kind="selection", score="score_has_object",
                       budget=100, seed=0)]
    out = QuerySession(make_engine(), specs, budget=10 ** 6).execute()
    assert out.results[0].n_invocations == 100


# -- cracking mid-session --------------------------------------------------
def test_crack_mid_session_invalidates_propagation_not_siblings(make_engine):
    eng = make_engine()
    version0 = eng.index.version
    specs = [QuerySpec(kind="aggregation", score="score_count", err=0.1,
                       crack=True),
             QuerySpec(kind="aggregation", score="score_count", err=0.1,
                       seed=5)]
    out = QuerySession(eng, specs, prefetch=False).execute()
    assert out.results[0].n_cracked > 0
    assert eng.index.version > version0
    assert out.stats["index_version_end"] > out.stats["index_version_start"]
    # the sibling spec re-propagated against the cracked index and stayed sane
    assert eng.stats["propagation_computes"] >= 2
    assert out.results[1].estimate is not None
    assert abs(out.results[1].estimate
               - float(np.mean(eng.workload.counts))) < 0.5


def test_prefetch_disabled_still_dedups(make_engine):
    eng = make_engine()
    specs = [QuerySpec(kind="selection", score="score_has_object",
                       budget=120, seed=0),
             QuerySpec(kind="selection", score="score_has_object",
                       budget=120, seed=0)]
    out = QuerySession(eng, specs, prefetch=False).execute()
    assert out.stats["prefetch_labels"] == 0
    assert out.results[1].n_oracle_fresh == 0


def test_reuse_labels_false_specs_skip_prefetch_and_pay_full(make_engine):
    eng = make_engine()
    specs = [QuerySpec(kind="selection", score="score_has_object",
                       budget=100, seed=0),
             QuerySpec(kind="selection", score="score_has_object",
                       budget=100, seed=0, reuse_labels=False)]
    out = QuerySession(eng, specs).execute()
    assert out.results[1].n_oracle_fresh == 100  # benchmark-fair accounting


def test_engine_routes_oracle_through_broker_microbatches(make_engine, wl,
                                                          monkeypatch):
    eng = make_engine(max_oracle_batch=16)
    batches = []
    orig = wl.target_dnn_batch

    def spy(ids):
        batches.append(len(ids))
        return orig(ids)

    monkeypatch.setattr(wl, "target_dnn_batch", spy)
    eng.execute(QuerySpec(kind="selection", score="score_has_object",
                          budget=100, seed=0))
    assert batches and max(batches) <= 16
    assert eng.broker.stats["batches"] == len(batches)


def test_empty_session_raises(make_engine):
    with pytest.raises(ValueError, match="no specs"):
        QuerySession(make_engine()).execute()
