"""Traffic from a mix file and a seed: the spec pool, its popularity, and
the arrival schedule.

Every seed gets the same work in another order, so that runs on different
seeds spread no more than runs on one seed:

* the pool's composition is fixed by the mix: how many specs of each kind,
  which score each uses and its error bound or budget.  Only the specs'
  sampling seeds come from the run's seed;
* popularity is Zipf(s) over the pool's ranks, realised as fixed quotas
  (largest remainder) rather than random draws, so every run sends each
  spec the same number of times;
* arrival gaps are the quantiles of the mix's gamma renewal process
  (shape ``1/cv**2``; ``cv = 1`` is Poisson), scaled so the requests span
  the window exactly, and shuffled by the seed.

The order of requests, the order of gaps and the specs' sampling seeds are
what the seed changes.  The corpus itself is made from the seed as well.
"""
from __future__ import annotations

import numpy as np
from scipy import stats

#: spec fields a mix may set per kind, besides ``kind`` and ``score``
KIND_FIELDS = {
    "aggregation": ("err",),
    "selection": ("budget",),
    "limit": ("k_results",),
}


def largest_remainder(weights, total: int) -> np.ndarray:
    """Integer counts summing to ``total`` in proportion to ``weights``."""
    w = np.asarray(weights, np.float64)
    exact = total * w / w.sum()
    counts = np.floor(exact).astype(np.int64)
    short = total - int(counts.sum())
    # ties go to the lower index, so the split never depends on float noise
    order = np.lexsort((np.arange(len(w)), -(exact - counts)))
    counts[order[:short]] += 1
    return counts


def spec_pool(pool: dict, seed: int) -> list:
    """The pool's specs, most popular first.

    ``pool["kinds"]`` lists, per kind, its share of the pool, the scores it
    cycles through and either one value or a [low, high] range for its
    kind's field (spread evenly over that kind's specs).  Kinds are
    interleaved over the ranks in proportion to their shares, so every
    kind has specs among the most popular ones.
    """
    kinds = pool["kinds"]
    size = int(pool["size"])
    counts = largest_remainder([k["share"] for k in kinds], size)
    per_kind = []
    for kind, n in zip(kinds, counts):
        (field,) = KIND_FIELDS[kind["kind"]]
        value = kind[field]
        values = (np.linspace(value[0], value[1], n) if isinstance(value, list)
                  else np.full(n, value))
        specs = []
        for i in range(n):
            v = values[i]
            specs.append({"kind": kind["kind"],
                          "score": kind["scores"][i % len(kind["scores"])],
                          field: (round(float(v), 6) if field == "err"
                                  else int(v))})
        # the kind's own specs are spread over its ranks in a fixed order,
        # so the most popular aggregation is neither the tightest nor the
        # loosest bound
        order = np.argsort((np.arange(n) * 0.6180339887) % 1.0,
                           kind="stable")
        per_kind.append([specs[j] for j in order])
    # rank r goes to the kind furthest behind its share at that point
    taken = np.zeros(len(kinds))
    ranked = []
    for _ in range(size):
        lag = (len(ranked) + 1) * counts / size - taken
        lag[taken >= counts] = -np.inf
        k = int(np.argmax(lag))
        ranked.append(dict(per_kind[k][int(taken[k])]))
        taken[k] += 1
    rng = np.random.default_rng([seed, 1])
    for spec, s in zip(ranked, rng.integers(0, 2**31 - 1, size)):
        spec["seed"] = int(s)
    return ranked


def zipf_requests(n_specs: int, s: float, n_requests: int,
                  rng: np.random.Generator) -> np.ndarray:
    """Pool ranks of ``n_requests`` requests: Zipf(s) quotas in an order
    drawn from ``rng``."""
    weights = 1.0 / np.arange(1, n_specs + 1) ** s
    counts = largest_remainder(weights, n_requests)
    return rng.permutation(np.repeat(np.arange(n_specs), counts))


def arrival_offsets(rate: float, cv: float, seconds: float,
                    seed: int) -> np.ndarray:
    """``round(rate * seconds)`` due offsets in ``[0, seconds)``: gamma
    quantile gaps, shuffled by the seed, summing to the window."""
    n = max(1, int(round(rate * seconds)))
    shape = 1.0 / cv ** 2
    q = (np.arange(n) + 0.5) / n
    gaps = stats.gamma.ppf(q, shape)
    gaps = gaps * seconds / gaps.sum()
    gaps = np.random.default_rng([seed, 3]).permutation(gaps)
    # the first request is due at the window's start; the last gap is the
    # one after the last request
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def open_loop_plan(mix: dict, seed: int, seconds: float) -> list:
    """[(due offset, request body)] of the mix's open loop."""
    arrivals = mix["arrivals"]
    due = arrival_offsets(arrivals["rate_qps"], arrivals["cv"], seconds, seed)
    pool = spec_pool(mix["pool"], seed)
    ranks = zipf_requests(len(pool), mix["pool"]["zipf_s"], len(due),
                          np.random.default_rng([seed, 2]))
    return [(float(t), {"specs": [pool[r]]}) for t, r in zip(due, ranks)]

