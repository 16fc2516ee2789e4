"""Plain reference of the labeled-text corpus's target DNN: the two-layer
labeler's forward pass in float32 at matrix precision ``highest``, from
weights made anew from the corpus's seed.  Imports nothing of the program.

It reads what the oracle handed out (``SERVED``), and checks each served
label by its logits (``CHECKS``):

* ``labeler_logit_err``: max abs(served logit - reference logit) over every
  served id, over the largest abs(reference logit) among them;
* ``labeler_label_gap``: the widest gap by which the reference's logit of a
  served label lies below the reference's best for that id, on the same
  scale; a tie the labeler's precision breaks the other way reads within
  ``labeler_logit_err``'s reach, an altered label reads far beyond it.

A served id's true score is that of its served label, so the replay runs
over the labels the window served and this module checked; any other id's
is that of the reference's argmax.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

SERVED = True
CHECKS = ("labeler_logit_err", "labeler_label_gap")
#: rows per reference call
BLOCK = 4096


def reference_logits(corpus, cfg: dict, ids: np.ndarray) -> np.ndarray:
    """(len(ids), classes) float64 logits of the records ``ids``."""
    width = corpus.features.shape[1]
    hidden = cfg["corpus"]["kwargs"]["hidden"]
    k1, k2 = jax.random.split(jax.random.PRNGKey(corpus.seed))
    with jax.default_matmul_precision("highest"):
        w1 = jax.random.normal(k1, (width, hidden)) / np.sqrt(width)
        w2 = jax.random.normal(k2, (hidden, 6)) / np.sqrt(hidden)
        out = [np.asarray(jnp.tanh(jnp.asarray(
                   corpus.features[ids[i:i + BLOCK]], jnp.float32) @ w1) @ w2)
               for i in range(0, len(ids), BLOCK)]
    return np.concatenate(out or [np.zeros((0, 6))]).astype(np.float64)


def truth_of(corpus, cfg: dict, score: str, served: dict):
    """ids -> float64 true scores for the named score."""
    labels = {
        "score_is_select": lambda label: (label == 0).astype(np.float64),
        "score_op": lambda label: label.astype(np.float64),
    }
    if score not in labels:
        raise KeyError(f"labeled-text has no true score {score!r}")

    def fn(ids):
        ids = np.asarray(ids, np.int64)
        label = np.empty(len(ids), np.int64)
        hit = np.fromiter((int(i) in served for i in ids), bool, len(ids))
        label[hit] = [served[int(i)]["label"] for i in ids[hit]]
        if not hit.all():
            label[~hit] = reference_logits(corpus, cfg, ids[~hit]).argmax(1)
        return labels[score](label)

    return fn


def checks(corpus, cfg: dict, served: dict, seed: int) -> dict:
    ids = np.fromiter(sorted(served), np.int64, len(served))
    got = np.stack([served[int(i)]["logits"] for i in ids]).astype(np.float64)
    label = np.asarray([served[int(i)]["label"] for i in ids])
    ref = reference_logits(corpus, cfg, ids)
    scale = np.abs(ref).max()
    return {"labeler_logit_err": float(np.abs(got - ref).max() / scale),
            "labeler_label_gap": float(
                (ref.max(1) - ref[np.arange(len(ids)), label]).max() / scale)}
