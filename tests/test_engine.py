"""Declarative query engine tests: spec -> plan -> execute for all three
query kinds, memoized propagation (computed once per score fn, invalidated by
cracking), the shared oracle-label cache, the cracking feedback loop, and the
spec JSON round-trip."""
import json
import subprocess
import sys

import numpy as np
import pytest

from repro.core import propagation
from repro.core.engine import QueryEngine, QuerySpec
from repro.core.index import TastiIndex
from repro.core.queries.registry import registered_kinds
from repro.core.schema import make_workload


@pytest.fixture(scope="module")
def wl():
    return make_workload("night-street", n_frames=1500)


@pytest.fixture()
def engine(wl):
    # raw features as embeddings: cheap, and the engine mechanics under test
    # are independent of embedder quality
    index = TastiIndex.build(wl.features, 150, wl.target_dnn_batch, k=4,
                             random_fraction=0.0, seed=0)
    return QueryEngine(index, wl)


def test_registry_has_paper_kinds():
    assert {"aggregation", "selection", "limit"} <= set(registered_kinds())


def test_all_three_kinds_execute(engine, wl):
    agg = engine.execute(QuerySpec(kind="aggregation", score="score_count",
                                   err=0.1))
    assert agg.estimate is not None
    assert agg.ci_half_width is not None
    assert 0 < agg.n_invocations <= len(wl.features)
    assert abs(agg.estimate - wl.counts.mean()) < 0.5

    sel = engine.execute(QuerySpec(kind="selection", score="score_has_object",
                                   budget=200))
    assert sel.selected is not None and sel.threshold is not None
    assert sel.n_invocations == 200

    lim = engine.execute(QuerySpec(kind="limit", score="score_has_object",
                                   k_results=5))
    assert lim.selected is not None
    assert len(lim.selected) == 5
    assert all(wl.counts[lim.selected] > 0)


def test_auto_propagation_per_kind(engine):
    assert engine.plan(QuerySpec(kind="aggregation", score="score_count")
                       ).propagation == "numeric"
    assert engine.plan(QuerySpec(kind="limit", score="score_rare",
                                 k_results=3)).propagation == "top1"
    sel_plan = engine.plan(QuerySpec(kind="selection", score="score_has_object",
                                     budget=10))
    assert sel_plan.propagation == "numeric" and sel_plan.clip01
    # explicit mode beats the kind default
    assert engine.plan(QuerySpec(kind="aggregation", score="score_count",
                                 propagation="top1")).propagation == "top1"


def test_propagation_computed_once_and_crack_invalidates(engine, monkeypatch):
    calls = []
    orig = propagation.propagate_numeric

    def counting(*a, **kw):
        calls.append(1)
        return orig(*a, **kw)

    monkeypatch.setattr(propagation, "propagate_numeric", counting)
    engine.execute(QuerySpec(kind="aggregation", score="score_count", err=0.1))
    engine.execute(QuerySpec(kind="aggregation", score="score_count", err=0.1,
                             seed=1))
    assert len(calls) == 1  # second query hit the memoized proxy
    assert engine.stats["proxy_cache_hits"] >= 1

    engine.crack_with(np.arange(20))
    engine.execute(QuerySpec(kind="aggregation", score="score_count", err=0.1))
    assert len(calls) == 2  # crack bumped the index version -> recompute


def test_label_cache_shared_across_queries(engine):
    r1 = engine.execute(QuerySpec(kind="selection", score="score_has_object",
                                  budget=150, seed=0))
    assert r1.n_oracle_fresh > 0
    # identical sampling -> every label served from the session cache
    r2 = engine.execute(QuerySpec(kind="selection", score="score_has_object",
                                  budget=150, seed=0))
    assert r2.n_oracle_fresh == 0
    assert r2.n_oracle_cached > 0
    # a *different* score function still reuses the cached annotations
    r3 = engine.execute(QuerySpec(kind="aggregation", score="score_count",
                                  err=0.1, seed=0))
    r4 = engine.execute(QuerySpec(kind="aggregation", score="score_mean_x",
                                  err=0.1, seed=0))
    assert r4.n_oracle_cached > 0
    # reuse_labels=False bypasses the cache for fair method comparisons
    r5 = engine.execute(QuerySpec(kind="selection", score="score_has_object",
                                  budget=150, seed=0, reuse_labels=False))
    assert r5.n_oracle_fresh == 150


def test_crack_feedback_loop(engine):
    n_reps_before = engine.index.n_reps
    version_before = engine.index.version
    res = engine.execute(QuerySpec(kind="aggregation", score="score_count",
                                   err=0.1, crack=True))
    assert res.n_cracked > 0
    assert engine.index.n_reps == n_reps_before + res.n_cracked
    assert engine.index.version > version_before
    # post-crack proxies cover the new reps: next query plans cleanly
    res2 = engine.execute(QuerySpec(kind="aggregation", score="score_count",
                                    err=0.1, seed=2))
    assert res2.estimate is not None


def test_engine_crack_default(wl):
    index = TastiIndex.build(wl.features, 100, wl.target_dnn_batch, k=4,
                             random_fraction=0.0, seed=0)
    eng = QueryEngine(index, wl, crack=True)
    res = eng.execute(QuerySpec(kind="selection", score="score_has_object",
                                budget=100))
    assert res.n_cracked > 0
    # spec-level opt-out beats the engine default
    res2 = eng.execute(QuerySpec(kind="selection", score="score_has_object",
                                 budget=100, seed=3, crack=False))
    assert res2.n_cracked == 0


def test_categorical_propagation_mode(engine, wl):
    cat = engine.proxy_scores("score_count", mode="categorical",
                              n_classes=int(wl.counts.max()) + 1)
    assert set(np.unique(cat)) <= set(range(int(wl.counts.max()) + 1))
    # reachable from a spec too
    plan = engine.plan(QuerySpec(kind="aggregation", score="score_count",
                                 propagation="categorical",
                                 n_classes=int(wl.counts.max()) + 1))
    assert plan.propagation == "categorical"
    with pytest.raises(ValueError, match="n_classes"):
        engine.plan(QuerySpec(kind="aggregation", score="score_count",
                              propagation="categorical"))


def test_proxy_override_skips_propagation(engine, wl, monkeypatch):
    def boom(*a, **kw):  # propagation must not run for external proxies
        raise AssertionError("propagation ran for an external proxy")

    monkeypatch.setattr(propagation, "propagate_numeric", boom)
    proxy = np.zeros(len(wl.features))
    res = engine.execute(QuerySpec(kind="aggregation", score="score_count",
                                   proxy=proxy, err=0.1, use_cv=False))
    assert res.plan.propagation == "external"
    assert res.estimate is not None


def test_plan_validation_errors(engine):
    with pytest.raises(KeyError, match="unknown query kind"):
        engine.plan(QuerySpec(kind="nope", score="score_count"))
    with pytest.raises(ValueError, match="budget"):
        engine.plan(QuerySpec(kind="selection", score="score_has_object"))
    with pytest.raises(ValueError, match="k_results"):
        engine.plan(QuerySpec(kind="limit", score="score_rare"))
    with pytest.raises(ValueError, match="score"):
        engine.execute(QuerySpec(kind="aggregation"))
    with pytest.raises(ValueError, match="scoring method"):
        engine.plan(QuerySpec(kind="aggregation", score="not_a_method"))


def test_spec_json_roundtrip():
    spec = QuerySpec(kind="selection", score="score_has_object", budget=300,
                     recall_target=0.95, seed=7)
    d = json.loads(json.dumps(spec.to_dict()))
    spec2 = QuerySpec.from_dict(d)
    assert spec2 == spec
    with pytest.raises(ValueError, match="unknown QuerySpec fields"):
        QuerySpec.from_dict({"kind": "limit", "k_results": 3, "typo": 1})
    with pytest.raises(ValueError, match="kind"):
        QuerySpec.from_dict({"score": "score_count"})
    # non-serializable specs fail loudly instead of silently changing meaning
    with pytest.raises(ValueError, match="proxy"):
        QuerySpec(kind="aggregation", score="score_count",
                  proxy=np.zeros(4)).to_dict()
    with pytest.raises(ValueError, match="string"):
        QuerySpec(kind="aggregation", score=lambda s: 0.0).to_dict()


def test_reexecuting_a_plan_does_not_mutate_it(engine):
    plan = engine.plan(QuerySpec(kind="aggregation", score="score_count",
                                 err=0.1, crack=True))
    trace_before = list(plan.trace)
    r1 = engine.execute(plan)
    r2 = engine.execute(plan)
    assert plan.trace == trace_before          # caller's plan untouched
    assert r1.plan.trace is not r2.plan.trace  # each result owns its trace
    assert sum("cracked" in t for t in r1.plan.trace) <= 1


def test_facade_shims_share_engine_caches(wl):
    from repro.core.embedder import EmbedderConfig
    from repro.core.pipeline import TastiSystem
    index = TastiIndex.build(wl.features, 100, wl.target_dnn_batch, k=4,
                             random_fraction=0.0, seed=0)
    sv = TastiSystem(index=index, workload=wl, embed_params=None,
                     ecfg=EmbedderConfig(feature_dim=wl.features.shape[1]),
                     variant="T")
    p1 = sv.proxy_scores(wl.score_count)
    p2 = sv.proxy_scores(wl.score_count)
    np.testing.assert_array_equal(p1, p2)
    assert sv.engine.stats["propagation_computes"] == 1
    assert sv.engine.stats["proxy_cache_hits"] == 1
    # categorical mode is reachable through the legacy facade too
    cat = sv.proxy_scores(wl.score_count, mode="categorical",
                          n_classes=int(wl.counts.max()) + 1)
    assert cat.shape == (len(wl.features),)
    # legacy crack_with invalidates the engine cache
    sv.crack_with(np.arange(10))
    _ = sv.proxy_scores(wl.score_count)
    assert sv.engine.stats["propagation_computes"] == 3  # numeric + cat + re-numeric


def test_query_cli_smoke(tmp_path):
    import os
    import pathlib
    src = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    env = {**os.environ,
           "PYTHONPATH": src + os.pathsep + os.environ.get("PYTHONPATH", ""),
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache")}
    cmd = [sys.executable, "-m", "repro.launch.query",
           "--workload", "night-street", "--n-frames", "800", "--quick",
           "--crack", "--save-index", str(tmp_path / "idx"),
           "--spec", '{"kind": "aggregation", "score": "score_count", "err": 0.2}',
           "--spec", '{"kind": "limit", "score": "score_has_object", "k_results": 3}']
    out = subprocess.run(cmd, capture_output=True, text=True, timeout=600,
                         env=env)
    assert out.returncode == 0, out.stderr
    body = json.loads(out.stdout)
    assert [r["kind"] for r in body["results"]] == ["aggregation", "limit"]
    assert body["results"][0]["estimate"] is not None
    assert (tmp_path / "idx.meta.json").exists()
