"""The plain reference: what a served run should have produced.

Straightforward numpy in float64, written from the TASTI paper's semantics
(§4.2 propagation, §4.3 query processing) and the serving defaults of one
request per session.  It imports nothing of the program.  Its inputs are
the corpus's ground truth (through the configuration's own truth module),
the index's representative ids and top-k structures, and the proxy scores
the program served, each checked here before anything else rests on it:

* :func:`topk_gaps` recomputes, for a sample of records, the squared
  distances to every representative from the index's embeddings, and
  holds the index's top-k distances and neighbour sets to them (§4.1);
* :func:`propagate` recomputes every proxy from the representatives' true
  scores and the index's top-k structures;
* :func:`replay` re-runs each served query over the served proxies, with
  true labels as the oracle, and reports the ids it labeled.

The controls are the same reference one precision below what the
configuration states: :func:`propagate_bf16`, bfloat16 for the float32
propagation, and :func:`topk_fp8`, float8 (e4m3) operands for the top-k's
distance products, which the device computes from bfloat16 operands (the
MXU's default precision for float32 inputs).
"""
from __future__ import annotations

import numpy as np

#: squared distance at or above which a top-k column is padding
PAD_DIST = 2.9e38
#: the propagation weight's epsilon, 1 / (distance + EPS)
EPS = 1e-6
#: serving defaults a one-spec session runs with
N_STRATA = 10
SESSION_SEED = 0
AGG_BATCH = 32
LIMIT_BATCH = 16


# -- the index's top-k (§4.1) -----------------------------------------------
#: rows per block of the float64 distance matrix
BLOCK_ROWS = 1024


def sq_dists(x: np.ndarray, reps: np.ndarray) -> np.ndarray:
    """(n, C) squared L2 distances in float64, by differences."""
    x = np.asarray(x, np.float64)
    reps = np.asarray(reps, np.float64)
    d = ((x * x).sum(1)[:, None] + (reps * reps).sum(1)[None]
         - 2.0 * x @ reps.T)
    return np.maximum(d, 0.0)


def topk_gaps(x: np.ndarray, reps: np.ndarray, topk_ids: np.ndarray,
              topk_d2: np.ndarray) -> dict:
    """How far the index's top-k for the records ``x`` lies from float64.

    ``topk_d2_err``: the largest gap between a stored squared distance and
    the float64 distance to the rep it names.  ``topk_set_gap``: the
    largest gap between the float64 distances of a row's stored neighbours,
    sorted, and the row's k smallest float64 distances; 0 when every row
    holds its true k nearest reps, ties aside.  Both are shares of the
    row's distance scale, ``|x|^2`` plus the median ``|r|^2``, the size of
    the terms whose rounding they measure.
    """
    k = topk_ids.shape[1]
    r_sq = float(np.median((np.asarray(reps, np.float64) ** 2).sum(1)))
    d2_err = set_gap = 0.0
    for lo in range(0, len(x), BLOCK_ROWS):
        xb = np.asarray(x[lo:lo + BLOCK_ROWS], np.float64)
        d = sq_dists(xb, reps)
        scale = ((xb * xb).sum(1) + r_sq)[:, None]
        ids = np.asarray(topk_ids[lo:lo + BLOCK_ROWS])
        named = np.take_along_axis(d, ids, 1)
        stored = np.asarray(topk_d2[lo:lo + BLOCK_ROWS], np.float64)
        d2_err = max(d2_err, float(np.max(np.abs(stored - named) / scale)))
        best = np.sort(np.partition(d, k - 1, axis=1)[:, :k], axis=1)
        # a repeated neighbour reads below the true next one: abs counts it
        set_gap = max(set_gap, float(np.max(
            np.abs(np.sort(named, axis=1) - best) / scale)))
    return {"topk_d2_err": d2_err, "topk_set_gap": set_gap}


def _fp8(a: np.ndarray) -> np.ndarray:
    """``a`` rounded to float8 e4m3 under one per-tensor scale that maps
    its largest magnitude to the format's largest finite value."""
    import ml_dtypes
    f8 = ml_dtypes.float8_e4m3fn
    a = np.asarray(a, np.float32)
    s = float(ml_dtypes.finfo(f8).max) / max(float(np.abs(a).max()), 1e-30)
    return (a * s).astype(f8).astype(np.float32) / s


def topk_fp8(x: np.ndarray, reps: np.ndarray, k: int) -> tuple:
    """The control: the top-k computed as the device computes it, norms
    and sums in float32, with the distance products' operands in float8
    e4m3; returns (topk_ids, topk_d2)."""
    x32 = np.asarray(x, np.float32)
    r32 = np.asarray(reps, np.float32)
    r_sq = (r32 * r32).sum(1)[None]
    rq = _fp8(r32)
    ids_out, d2_out = [], []
    for lo in range(0, len(x32), BLOCK_ROWS):
        xb = x32[lo:lo + BLOCK_ROWS]
        d = np.maximum((xb * xb).sum(1)[:, None] + r_sq
                       - 2.0 * (_fp8(xb) @ rq.T), 0.0)
        ids = np.argsort(d, axis=1, kind="stable")[:, :k]
        ids_out.append(ids)
        d2_out.append(np.take_along_axis(d, ids, 1))
    return np.concatenate(ids_out), np.concatenate(d2_out)


# -- propagation (§4.2) -------------------------------------------------------
def _weights(topk_d2: np.ndarray) -> np.ndarray:
    w = 1.0 / (np.sqrt(np.maximum(topk_d2, 0.0)) + EPS)
    return np.where(topk_d2 >= PAD_DIST, 0.0, w)


def tie_break_eps(rep_scores: np.ndarray) -> float:
    """Top-1 distance nudge: below half the smallest gap between distinct
    rep scores and at most 1e-6, so it only orders records of equal score."""
    levels = np.unique(rep_scores[np.isfinite(rep_scores)])
    gaps = np.diff(levels)
    return float(min(1e-6, 0.5 * gaps.min())) if len(gaps) else 1e-6


def propagate(rep_scores: np.ndarray, topk_ids: np.ndarray,
              topk_d2: np.ndarray, mode: str) -> np.ndarray:
    """(N,) proxy scores: the inverse-distance weighted mean of the k
    nearest reps (numeric), or the nearest rep's score less a distance
    tie-break (top1)."""
    rep_scores = np.asarray(rep_scores, np.float64)
    d2 = np.asarray(topk_d2, np.float64)
    if mode == "numeric":
        w = _weights(d2)
        return (w * rep_scores[topk_ids]).sum(1) / w.sum(1)
    if mode == "top1":
        d = np.sqrt(np.maximum(d2[:, 0], 0.0))
        return (rep_scores[topk_ids[:, 0]]
                - tie_break_eps(rep_scores) * d / (1.0 + d.max()))
    raise ValueError(f"the reference has no propagation mode {mode!r}")


def propagate_bf16(rep_scores: np.ndarray, topk_ids: np.ndarray,
                   topk_d2: np.ndarray, mode: str) -> np.ndarray:
    """:func:`propagate` with every operand and operation in bfloat16, on
    the default JAX device."""
    import jax.numpy as jnp
    bf = jnp.bfloat16
    s = jnp.asarray(rep_scores, bf)
    d2 = jnp.asarray(np.minimum(topk_d2, 3e38), bf)
    ids = jnp.asarray(topk_ids)
    eps = jnp.asarray(EPS, bf)
    if mode == "numeric":
        w = 1.0 / (jnp.sqrt(jnp.maximum(d2, 0)) + eps)
        w = jnp.where(d2 >= bf(PAD_DIST), bf(0), w)
        out = (w * s[ids]).sum(1, dtype=bf) / w.sum(1, dtype=bf)
    elif mode == "top1":
        d = jnp.sqrt(jnp.maximum(d2[:, 0], 0))
        nudge = jnp.asarray(tie_break_eps(np.asarray(rep_scores)), bf)
        out = s[ids[:, 0]] - nudge * d / (1 + d.max())
    else:
        raise ValueError(f"the reference has no propagation mode {mode!r}")
    return np.asarray(out.astype(jnp.float32), np.float64)


def proxy_err(served: np.ndarray, ref: np.ndarray) -> float:
    """Largest gap between a served and a reference numeric proxy score, as
    a share of the largest reference score's magnitude."""
    scale = max(float(np.max(np.abs(ref))), 1e-12)
    return float(np.max(np.abs(np.asarray(served, np.float64) - ref)) / scale)


def top1_err(served: np.ndarray, ref: np.ndarray,
             rep_scores: np.ndarray) -> float:
    """Largest gap between a served and a reference top-1 proxy score, in
    units of the distance tie-break's scale: the part of the score that
    orders records of equal score, which a gap relative to the scores
    themselves could not see."""
    return float(np.max(np.abs(np.asarray(served, np.float64) - ref))
                 / tie_break_eps(np.asarray(rep_scores, np.float64)))


# -- query processing (§4.3), one spec per session ---------------------------
def stratified_order(proxy: np.ndarray) -> np.ndarray:
    """The session's sample order for aggregations: ranks split into
    N_STRATA equal-frequency strata, a seeded permutation, then one record
    per stratum per round in a seeded stratum order."""
    n = len(proxy)
    strata_n = max(1, min(N_STRATA, n))
    rng = np.random.default_rng(SESSION_SEED)
    ranks = np.empty(n, np.int64)
    ranks[np.argsort(proxy, kind="stable")] = np.arange(n)
    strata = (ranks * strata_n) // n
    perm = rng.permutation(n)
    sp = strata[perm]
    within = np.empty(n, np.int64)
    for s in range(strata_n):
        members = np.flatnonzero(sp == s)
        within[members] = np.arange(len(members))
    round_pos = rng.permutation(strata_n)
    return perm[np.argsort(within * strata_n + round_pos[sp], kind="stable")]


def eb_half_width(var: float, width: float, n: int, delta: float) -> float:
    """Empirical-Bernstein half-width (Maurer and Pontil)."""
    log_term = np.log(3.0 / delta)
    return float(np.sqrt(2.0 * var * log_term / n)
                 + 3.0 * width * log_term / n)


def aggregate(proxy, order, truth, err, delta=0.05, min_samples=64):
    """EB-stopped mean with the proxy as control variate."""
    n = len(proxy)
    p_mean = float(proxy.mean())
    taken = 0
    f = np.empty(0)
    while taken < n:
        m = min(AGG_BATCH if taken else min_samples, n - taken)
        taken += m
        ids = order[:taken]
        f = truth(ids)
        p = proxy[ids]
        if len(f) >= 8:
            c = float(np.cov(f, p)[0, 1] / (p.var() + 1e-12))
            resid = f - c * p
            est = float(resid.mean() + c * p_mean)
            v = float(resid.var())
            width = float(resid.max() - resid.min()) + 1e-12
        else:
            est, v = float(f.mean()), float(f.var())
            width = float(f.max() - f.min()) + 1e-12
        hw = eb_half_width(v, width, taken, delta)
        if taken >= min_samples and hw <= err:
            break
    return {"estimate": est, "ci_half_width": hw,
            "n_invocations": taken}, order[:taken]


def select(proxy, truth, budget, seed, recall_target=0.9, delta=0.05):
    """SUPG recall-target selection by sqrt-proxy importance sampling."""
    n = len(proxy)
    budget = min(budget, n)
    p = np.clip(proxy, 0.0, 1.0)            # the selection proxy is clipped
    p = np.clip(p, 1e-6, 1.0)
    q = np.sqrt(p)
    q = q / q.sum()
    ids = np.random.default_rng(seed).choice(n, size=budget, replace=True,
                                             p=q)
    labels = truth(ids)
    w = 1.0 / (n * q[ids])
    wpos = w * labels
    total = wpos.sum()
    tau = float(np.min(p))
    if total > 0:
        z = np.sqrt(2.0 * np.log(1.0 / delta))
        t_var = wpos.var() * len(wpos)
        for t in np.unique(p[ids])[::-1]:
            above = p[ids] >= t
            m_var = wpos[above].var() * above.sum() if above.any() else 0.0
            se = np.sqrt(m_var + t_var) / max(total * np.sqrt(budget), 1e-9)
            if float(wpos[above].sum()) / total - z * se >= recall_target:
                tau = float(t)
                break
    selected = np.union1d(np.flatnonzero(p >= tau), ids[labels > 0.5])
    return {"threshold": tau, "n_selected": int(len(selected)),
            "selected_head": [int(i) for i in selected[:10]],
            "n_invocations": budget}, ids


def limit(proxy, truth, k_results):
    """Scan in descending proxy order, LIMIT_BATCH records per oracle call,
    until k_results matches."""
    order = np.argsort(-proxy, kind="stable")
    found = []
    examined = 0
    labeled = 0
    for start in range(0, len(order), LIMIT_BATCH):
        ids = order[start:start + LIMIT_BATCH]
        labeled = start + len(ids)
        done_at = len(ids)
        for j, lab in enumerate(truth(ids)):
            if lab > 0.5:
                found.append(int(ids[j]))
                if len(found) >= k_results:
                    done_at = j + 1
                    break
        examined += done_at
        if len(found) >= k_results:
            break
    return {"n_selected": len(found), "selected_head": found[:10],
            "n_invocations": examined}, order[:labeled]


def replay(spec: dict, proxies: dict, truth_of) -> tuple:
    """One served spec re-run: (answer fields, ids labeled).  ``proxies``
    maps (score, mode) to the served proxy; ``truth_of(score)`` gives the
    true-score function of ids."""
    kind, score = spec["kind"], spec["score"]
    truth = truth_of(score)
    if kind == "aggregation":
        proxy = proxies[(score, "numeric")]
        return aggregate(proxy, proxies[(score, "order")], truth,
                         spec["err"])
    if kind == "selection":
        return select(proxies[(score, "numeric")], truth, spec["budget"],
                      spec["seed"])
    if kind == "limit":
        return limit(proxies[(score, "top1")], truth, spec["k_results"])
    raise ValueError(f"the reference has no query kind {kind!r}")


def answer_gap(spec: dict, served: dict, ref: dict) -> float:
    """How far a served answer lies from the reference's: estimate and
    half-width in units of the spec's error bound, the threshold in proxy
    units, and 1 for any count or id that differs."""
    gap = 0.0
    for key in ("n_invocations", "n_selected", "selected_head"):
        if key in ref and served.get(key) != ref[key]:
            gap = max(gap, 1.0)
    for key in ("estimate", "ci_half_width"):
        if key in ref:
            gap = max(gap, abs(served.get(key, np.inf) - ref[key])
                      / spec["err"])
    if "threshold" in ref:
        gap = max(gap, abs(served.get("threshold", np.inf)
                           - ref["threshold"]))
    return float(gap)
