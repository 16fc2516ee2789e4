"""The control at a test's size: the reference computed in bfloat16 fails
the limits that float32 arithmetic, as the device path states, meets."""
import numpy as np
import pytest

from _bench import ROOT  # noqa: F401

from benchmarks.chip import manifest, reference  # noqa: E402

LIMITS = manifest.limits()


@pytest.fixture(scope="module")
def index():
    rng = np.random.default_rng(4)
    x = rng.normal(size=(2048, 128))
    reps = x[rng.choice(2048, 300, replace=False)]
    d = ((x[:, None, :] - reps[None]) ** 2).sum(-1)
    ids = np.argsort(d, 1)[:, :8]
    d2 = np.take_along_axis(d, ids, 1)
    scores = rng.integers(0, 6, 300).astype(np.float64)
    return ids, d2, scores


def _f32_top1(scores, ids, d2):
    """Top-1 propagation in float32 arithmetic, as the device path runs."""
    d = np.sqrt(d2[:, 0].astype(np.float32))
    nudge = np.float32(reference.tie_break_eps(scores)) / (1 + d.max())
    return scores.astype(np.float32)[ids[:, 0]] - nudge * d


def test_bfloat16_numeric_propagation_fails_and_float32_passes(index):
    ids, d2, scores = index
    ref = reference.propagate(scores, ids, d2, "numeric")
    f32 = reference.propagate(scores, ids, d2.astype(np.float32), "numeric")
    low = reference.propagate_bf16(scores, ids, d2, "numeric")
    assert reference.proxy_err(low, ref) > LIMITS["proxy_err"]
    assert reference.proxy_err(f32.astype(np.float32), ref) \
        < LIMITS["proxy_err"]


def test_bfloat16_top1_propagation_fails_and_float32_passes(index):
    ids, d2, scores = index
    ref = reference.propagate(scores, ids, d2, "top1")
    low = reference.propagate_bf16(scores, ids, d2, "top1")
    f32 = _f32_top1(scores, ids, d2)
    assert reference.top1_err(low, ref, scores) > LIMITS["top1_err"]
    assert reference.top1_err(f32, ref, scores) < LIMITS["top1_err"]


def test_the_planted_fault_alters_one_label_per_call():
    from repro.core import schema

    from benchmarks.chip import control
    wl = schema.TextWorkload(n_records=50, seed=3)
    before = wl.target_dnn_batch([1, 2, 3])
    control.plant_altered_label(wl)
    after = wl.target_dnn_batch([1, 2, 3])
    assert after[1:] == before[1:] and after[0] != before[0]


@pytest.fixture(scope="module")
def embeddings():
    rng = np.random.default_rng(6)
    x = (0.3 * rng.normal(size=(1500, 128))).astype(np.float32)
    return x, x[rng.choice(1500, 200, replace=False)]


def _device_topk(x, reps, k=8, operands="bfloat16"):
    """The top-k as the device computes it: float32 norms and sums, the
    distance products' operands in bfloat16 (the MXU's default)."""
    import ml_dtypes

    def bf(a):
        return a.astype(getattr(ml_dtypes, operands, operands)).astype(a.dtype)

    d = np.maximum((x * x).sum(1)[:, None] + (reps * reps).sum(1)[None]
                   - 2.0 * (bf(x) @ bf(reps).T), 0.0)
    ids = np.argsort(d, axis=1, kind="stable")[:, :k]
    return ids, np.take_along_axis(d, ids, 1)


def test_float8_topk_fails_and_the_devices_precision_passes(embeddings):
    x, reps = embeddings
    low = reference.topk_gaps(x, reps, *reference.topk_fp8(x, reps, 8))
    assert any(low[n] > LIMITS[n] for n in low)
    dev = reference.topk_gaps(x, reps, *_device_topk(x, reps))
    assert all(dev[n] < LIMITS[n] for n in dev)
    exact = reference.topk_gaps(x, reps, *_device_topk(
        x.astype(np.float64), reps.astype(np.float64), operands="float64"))
    assert exact["topk_set_gap"] == 0.0 and exact["topk_d2_err"] < 1e-9


def test_a_repeated_neighbour_reads_as_a_set_gap(embeddings):
    x, reps = embeddings
    ids, d2 = _device_topk(x.astype(np.float64), reps.astype(np.float64),
                           operands="float64")
    ids[:, 1], d2[:, 1] = ids[:, 0], d2[:, 0]
    assert reference.topk_gaps(x, reps, ids, d2)["topk_set_gap"] > 0.0
