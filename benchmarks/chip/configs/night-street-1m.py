"""True scores of the night-street corpus: what the target DNN answers for
each score the mixes use, read from the corpus's latent scenes (object
counts and positions).  Imports nothing of the program."""
from __future__ import annotations

import numpy as np


def truth_of(corpus, cfg: dict, score: str):
    """ids -> float64 true scores for the named score."""
    counts = np.asarray(corpus.counts, np.int64)
    rare = cfg["corpus"]["kwargs"]["rare_count"]

    def mean_x(ids):
        # the mean of the objects' x positions as the scene stores them
        # (float32); 0.5 for an empty frame
        out = np.empty(len(ids))
        for j, i in enumerate(ids):
            xs = corpus.scenes[int(i)].boxes[:, 0]
            out[j] = float(np.mean(xs)) if len(xs) else 0.5
        return out

    scores = {
        "score_count": lambda ids: counts[ids].astype(np.float64),
        "score_has_object": lambda ids: (counts[ids] > 0).astype(np.float64),
        "score_rare": lambda ids: (counts[ids] >= rare).astype(np.float64),
        "score_left_side": lambda ids: ((counts[ids] > 0)
                                        & (mean_x(ids) < 0.5)
                                        ).astype(np.float64),
        "score_mean_x": mean_x,
    }
    if score not in scores:
        raise KeyError(f"night-street has no true score {score!r}")
    fn = scores[score]
    return lambda ids: fn(np.asarray(ids, np.int64))
