"""A model-labeled text workload, standing in for a program whose target DNN
is a model: WikiSQL-like records (the program's ``TextWorkload``) labeled
by a seeded random two-layer network over the records' features, in
``jax.numpy`` with bfloat16 matrix products and float32 accumulation.  Each
label is the network's logits over the six SQL operators and their argmax.

A test installs :class:`LabeledTextWorkload` into ``repro.core.schema`` so
that a configuration can name it as its corpus class.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.schema import TextWorkload

#: classes the labeler scores: the corpus's six SQL operators
N_CLASSES = 6
#: rows per compiled call; a batch is padded up to a multiple of it
BATCH = 64


@partial(jax.jit, static_argnums=(1, 2))
def _weights(key, width: int, hidden: int):
    """Both layers' weights from one key, in the type they are served in."""
    k1, k2 = jax.random.split(key)
    w1 = jax.random.normal(k1, (width, hidden)) / np.sqrt(width)
    w2 = jax.random.normal(k2, (hidden, N_CLASSES)) / np.sqrt(hidden)
    return w1.astype(jnp.bfloat16), w2.astype(jnp.bfloat16)


@jax.jit
def _logits(w1, w2, x):
    h = jnp.tanh(jnp.dot(x.astype(jnp.bfloat16), w1,
                         preferred_element_type=jnp.float32))
    return jnp.dot(h.astype(jnp.bfloat16), w2,
                   preferred_element_type=jnp.float32)


@dataclass
class LabeledTextWorkload(TextWorkload):
    hidden: int = 1024

    def __post_init__(self):
        super().__post_init__()
        self.w1, self.w2 = _weights(jax.random.PRNGKey(self.seed),
                                    self.feature_dim, self.hidden)

    def logits(self, x: np.ndarray) -> np.ndarray:
        """(n, N_CLASSES) float32 logits of feature rows ``x``."""
        return np.asarray(_logits(self.w1, self.w2, x))

    def target_dnn_batch(self, ids) -> list:
        ids = np.asarray(ids, np.int64)
        rows = np.concatenate([ids, np.zeros(-len(ids) % BATCH, np.int64)])
        logits = self.logits(self.features[rows])[:len(ids)]
        return [{"logits": row, "label": int(np.argmax(row))}
                for row in logits]

    def score_is_select(self, out: dict) -> float:
        return 1.0 if out["label"] == 0 else 0.0

    def score_op(self, out: dict) -> float:
        return float(out["label"])
