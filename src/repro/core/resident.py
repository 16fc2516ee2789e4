"""Device-resident index state for the serving hot path.

Proxy-score materialization is O(N*k) arithmetic over index structures that
only change on a crack, so off-host execution is bandwidth-bound on the rep
structures — re-shipping ``topk_ids``/``topk_d2`` (and the embeddings) to the
accelerator per query would cost more than the propagation itself.  A
:class:`ResidentIndexState`, owned by :class:`repro.core.engine.QueryEngine`,
uploads them once and replays the fused propagate kernel
(:func:`repro.kernels.propagate.ops.propagate`) against the cached device
buffers; only the small (C,) rep-score vector moves per call.

Staleness is handled with the index's existing ``version`` counter: every
upload is stamped with the version it saw, every :meth:`propagate` call
carries the version the caller's rep scores were computed against, and any
mismatch (a crack landed in between) returns ``None`` so the engine falls
back to the host path for that attempt and retries against the new index.

Enablement: automatic on accelerators (TPU/GPU), off on CPU — the CPU
serving path keeps the float64 numpy propagation byte-identical to previous
releases.  Override with ``REPRO_RESIDENT_SCORING=1`` (force on; uses the
XLA reference off-TPU) or ``=0`` (force off).
"""
from __future__ import annotations

import os
import threading
from typing import Optional

import numpy as np

_TRUTHY = ("1", "true", "on", "force", "yes")
_FALSY = ("0", "false", "off", "no")

ENV_VAR = "REPRO_RESIDENT_SCORING"


def _default_enabled() -> bool:
    env = os.environ.get(ENV_VAR, "").strip().lower()
    if env in _TRUTHY:
        return True
    if env in _FALSY:
        return False
    import jax
    return jax.devices()[0].platform in ("tpu", "gpu")


class ResidentIndexState:
    """Keeps one index's embeddings + top-k rep structures on device.

    Thread-safe; all device handles are guarded by an internal lock, but the
    fused propagate call itself runs outside it (device arrays are
    immutable), so propagations over different score functions overlap.
    """

    def __init__(self, index, enabled: Optional[bool] = None,
                 block_n: int = 1024):
        self.index = index
        self.enabled = _default_enabled() if enabled is None else bool(enabled)
        self.block_n = int(block_n)
        self._lock = threading.Lock()
        self._version: Optional[int] = None   # version of uploaded structures
        self._topk_ids = None                 # device (N,k) int32
        self._topk_d2 = None                  # device (N,k) float32
        self._embeddings = None               # device (N,d); crack-immutable
        self.stats = {
            "uploads": 0,        # rep-structure uploads (initial + re-upload)
            "invalidations": 0,  # crack listeners dropping device state
            "fallbacks": 0,      # propagate() calls answered by the host path
        }

    # ------------------------------------------------------------------
    def invalidate(self) -> None:
        """Drop the uploaded rep structures (crack listener).  Correctness
        never depends on this — :meth:`propagate` version-checks every call —
        but dropping eagerly frees device memory for the re-upload."""
        with self._lock:
            if self._version is not None or self._topk_ids is not None:
                self.stats["invalidations"] += 1
            self._version = None
            self._topk_ids = None
            self._topk_d2 = None

    def embeddings_device(self):
        """The (N, d) embedding matrix on device (uploaded once; embeddings
        never change across cracks).  ``None`` when disabled."""
        if not self.enabled:
            return None
        import jax.numpy as jnp
        with self._lock:
            if self._embeddings is None:
                self._embeddings = jnp.asarray(self.index.embeddings)
            return self._embeddings

    def _structures(self, version: int):
        """Device (topk_ids, topk_d2) for ``version``, uploading if stale.
        Must be called with the index at that version (caller checks)."""
        with self._lock:
            if self._version != version:
                import jax.numpy as jnp
                self._topk_ids = jnp.asarray(
                    np.asarray(self.index.topk_ids, np.int32))
                self._topk_d2 = jnp.asarray(
                    np.asarray(self.index.topk_d2, np.float32))
                self._version = version
                self.stats["uploads"] += 1
            return self._topk_ids, self._topk_d2

    # ------------------------------------------------------------------
    def propagate(self, rep_scores: np.ndarray, mode: str, *, version: int,
                  n_classes: Optional[int] = None,
                  clip01: bool = False) -> Optional[np.ndarray]:
        """Fused device propagation of ``rep_scores`` (computed against index
        ``version``) -> (N,) float64, or ``None`` when the host path must
        answer (disabled, or the version raced with a crack).  A device or
        compile error raises: it is never answered from the host.
        """
        if not self.enabled:
            self.stats["fallbacks"] += 1
            return None
        if self.index.version != version:
            self.stats["fallbacks"] += 1
            return None          # crack landed since the caller snapshotted
        import jax.numpy as jnp
        from repro.kernels.propagate.ops import propagate as _propagate
        ids, d2 = self._structures(version)
        out = _propagate(jnp.asarray(rep_scores, jnp.float32), ids, d2,
                         mode, n_classes=n_classes, clip01=clip01,
                         block_n=self.block_n)
        return np.asarray(out, np.float64)
