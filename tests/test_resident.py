"""Device-resident proxy scoring (repro.core.resident) + the engine's
single-flight propagation: parity with the host path, crack invalidation
mid-serving, concurrent same-key sharing, and fallback policy (CPU default
off, env override, external-proxy specs untouched)."""
import threading

import numpy as np
import pytest

from repro.core import resident as resident_mod
from repro.core.engine import QueryEngine, QuerySpec
from repro.core.index import TastiIndex
from repro.core.resident import ResidentIndexState


class ToyWorkload:
    name = "toy"

    def __init__(self, n=300, d=12, seed=0):
        rng = np.random.default_rng(seed)
        self.features = rng.normal(size=(n, d)).astype(np.float32)
        self.truth = rng.random(n)

    def target_dnn_batch(self, ids):
        return [float(self.truth[int(i)]) for i in np.asarray(ids)]

    def score_id(self, a):
        return float(a)

    def score_cls(self, a):
        return float(a > 0.5)


@pytest.fixture()
def setup():
    wl = ToyWorkload()
    index = TastiIndex.build(wl.features, 30, wl.target_dnn_batch, k=4,
                             random_fraction=0.0, seed=0)
    return wl, index


pytestmark = pytest.mark.tier1


def test_cpu_defaults_to_host_path(setup, monkeypatch):
    monkeypatch.delenv(resident_mod.ENV_VAR, raising=False)
    wl, index = setup
    eng = QueryEngine(index, wl)
    import jax
    if jax.devices()[0].platform not in ("tpu", "gpu"):
        assert not eng.resident.enabled
        eng.proxy_scores("score_id")
        assert eng.stats["proxy_device_computes"] == 0
        assert eng.stats["propagation_computes"] == 1


def test_env_var_forces_resident(setup, monkeypatch):
    monkeypatch.setenv(resident_mod.ENV_VAR, "1")
    wl, index = setup
    eng = QueryEngine(index, wl)
    assert eng.resident.enabled
    eng.proxy_scores("score_id")
    assert eng.stats["proxy_device_computes"] == 1
    monkeypatch.setenv(resident_mod.ENV_VAR, "0")
    assert not QueryEngine(index, wl).resident.enabled


@pytest.mark.parametrize("mode,kw", [("numeric", {}), ("top1", {}),
                                     ("categorical", {"n_classes": 2})])
def test_resident_engine_matches_host_engine(setup, mode, kw):
    wl, index = setup
    host = QueryEngine(index, wl, resident=False)
    dev = QueryEngine(index, wl, resident=True)
    score = "score_cls" if mode == "categorical" else "score_id"
    h = host.proxy_scores(score, mode, **kw)
    d = dev.proxy_scores(score, mode, **kw)
    assert dev.stats["proxy_device_computes"] == 1
    if mode == "numeric":
        np.testing.assert_allclose(d, h, rtol=1e-5, atol=1e-6)
    elif mode == "categorical":
        np.testing.assert_array_equal(d, h)
    else:  # top1: same semantics at f32 (levels monotone)
        base = index.rep_scores(getattr(wl, score))[index.topk_ids[:, 0]]
        order = np.argsort(-d, kind="stable")
        assert not (np.diff(base[order].astype(np.float32)) > 0).any()


def test_crack_invalidates_resident_state(setup):
    """A crack mid-serving must drop the uploaded structures and the next
    propagation must reflect the post-crack index exactly (vs a host-path
    engine over the same index)."""
    wl, index = setup
    dev = QueryEngine(index, wl, resident=True)
    dev.proxy_scores("score_id")
    assert dev.resident._version == index.version
    v0 = index.version
    added = dev.crack_with(np.arange(30, 45))
    assert added > 0 and index.version > v0
    assert dev.resident._version is None  # on_crack listener dropped buffers
    d = dev.proxy_scores("score_id")
    assert dev.resident._version == index.version  # re-uploaded
    h = QueryEngine(index, wl, resident=False).proxy_scores("score_id")
    np.testing.assert_allclose(d, h, rtol=1e-5, atol=1e-6)


def test_version_mismatch_returns_none(setup):
    """ResidentIndexState.propagate refuses rep scores computed against a
    stale version (a crack raced the compute) so the engine retries."""
    wl, index = setup
    state = ResidentIndexState(index, enabled=True)
    scores = index.rep_scores(wl.score_id)
    stale = index.version - 1
    assert state.propagate(scores, "numeric", version=stale) is None
    assert state.propagate(scores, "numeric", version=index.version) is not None


def test_device_error_raises_instead_of_host_fallback(setup, monkeypatch):
    """A device/compile failure in the fused call surfaces to the caller:
    the resident path neither answers from the host nor disables itself."""
    import repro.kernels.propagate.ops as prop_ops

    def broken(*args, **kwargs):
        raise RuntimeError("device lost")

    monkeypatch.setattr(prop_ops, "propagate", broken)
    wl, index = setup
    eng = QueryEngine(index, wl, resident=True)
    with pytest.raises(RuntimeError, match="device lost"):
        eng.proxy_scores("score_id")
    assert eng.resident.enabled
    assert eng.resident.stats["fallbacks"] == 0
    assert eng.stats["proxy_device_computes"] == 0
    assert eng.stats["propagation_computes"] == 0


def test_disabled_state_is_inert(setup):
    wl, index = setup
    state = ResidentIndexState(index, enabled=False)
    assert state.propagate(index.rep_scores(wl.score_id), "numeric",
                           version=index.version) is None
    assert state.embeddings_device() is None


@pytest.mark.parametrize("resident", [False, True])
def test_single_flight_shares_one_compute(setup, resident):
    wl, index = setup
    eng = QueryEngine(index, wl, resident=resident)
    barrier = threading.Barrier(6)
    outs, errs = [], []

    def go():
        try:
            barrier.wait(5)
            outs.append(eng.proxy_scores("score_id"))
        except BaseException as e:  # noqa: BLE001 - surfaced below
            errs.append(e)

    threads = [threading.Thread(target=go) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    assert eng.stats["propagation_computes"] == 1
    assert eng.stats["proxy_cache_hits"] == 5
    assert all(o is outs[0] for o in outs)


def test_single_flight_distinct_keys_all_compute(setup):
    wl, index = setup
    eng = QueryEngine(index, wl)
    barrier = threading.Barrier(2)
    errs = []

    def go(score):
        try:
            barrier.wait(5)
            eng.proxy_scores(score)
        except BaseException as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=go, args=(s,))
               for s in ("score_id", "score_cls")]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    assert eng.stats["propagation_computes"] == 2


def test_single_flight_owner_error_propagates_to_waiters(setup):
    """A failing score fn must raise in *every* caller, not strand waiters
    on a flight that never lands."""
    wl, index = setup
    eng = QueryEngine(index, wl)
    barrier = threading.Barrier(4)
    errs = []

    def bad_score(a):
        raise RuntimeError("scorer exploded")

    def go():
        barrier.wait(5)
        try:
            eng.proxy_scores(bad_score, score_key="bad")
        except RuntimeError as e:
            errs.append(e)

    threads = [threading.Thread(target=go) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10)
    assert not any(t.is_alive() for t in threads), "waiters stranded"
    assert len(errs) == 4
    assert not eng._proxy_flights


def test_external_proxy_spec_skips_scoring_paths(setup):
    """Specs with a caller-provided proxy never touch propagation (host or
    resident) — the array is used as-is."""
    wl, index = setup
    eng = QueryEngine(index, wl, resident=True)
    proxy = np.linspace(0, 1, index.n_records)
    plan = eng.plan(QuerySpec(kind="selection", score="score_cls",
                              proxy=proxy, budget=20))
    assert plan.propagation == "external"
    got = eng.proxy_for(plan)
    np.testing.assert_array_equal(got, np.clip(proxy, 0, 1))
    assert eng.stats["propagation_computes"] == 0
    assert eng.stats["proxy_device_computes"] == 0


def test_resident_survives_empty_and_tiny_index():
    wl = ToyWorkload(n=40)
    index = TastiIndex.build(wl.features, 1, wl.target_dnn_batch, k=4,
                             random_fraction=0.0, seed=0)
    eng = QueryEngine(index, wl, resident=True)
    out = eng.proxy_scores("score_id")
    assert out.shape == (40,) and np.isfinite(out).all()
    # one rep: every record propagates exactly that rep's score
    np.testing.assert_allclose(out, wl.truth[index.rep_ids[0]], rtol=1e-6)
