"""True scores of the WikiSQL corpus: each question's SQL operator and
number of predicates, read from the corpus's latent records.  Imports
nothing of the program."""
from __future__ import annotations

import numpy as np

#: operator index of SELECT in the corpus's operator list
SELECT = 0


def truth_of(corpus, cfg: dict, score: str):
    """ids -> float64 true scores for the named score."""
    ops = np.asarray(corpus.ops, np.int64)
    preds = np.asarray(corpus.n_predicates, np.int64)
    scores = {
        "score_n_predicates": lambda ids: preds[ids].astype(np.float64),
        "score_is_select": lambda ids: (ops[ids] == SELECT).astype(np.float64),
    }
    if score not in scores:
        raise KeyError(f"wikisql has no true score {score!r}")
    fn = scores[score]
    return lambda ids: fn(np.asarray(ids, np.int64))
