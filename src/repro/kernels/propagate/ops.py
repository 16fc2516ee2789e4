"""jit'd fused propagation entry point: pads to block multiples, picks impl.

impl="auto": Pallas on TPU, XLA reference otherwise (interpret mode is a
correctness tool, not an execution path — CPU serving uses the float64 host
path in :mod:`repro.core.propagation`, and CPU benchmarks use the ref).

``rep_scores`` is donated on accelerators: the resident hot path materializes
a fresh (C,) score array per call and never reuses it, so the fused call can
recycle its buffer.  The big (N,k) rep structures are *not* donated — they
live across sessions in :class:`repro.core.resident.ResidentIndexState`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import resolve_impl
from repro.kernels.distance_topk.ops import PAD_DIST
from repro.kernels.propagate.kernel import CHUNK_C, propagate_pallas
from repro.kernels.propagate.ref import (
    propagate_categorical_ref,
    propagate_numeric_ref,
    propagate_top1_ref,
    tie_break_prescale,
)

MODES = ("numeric", "top1", "categorical")


def _propagate_impl(rep_scores, topk_ids, topk_d2, *, mode, n_classes, clip01,
                    impl, block_n, interpret):
    if impl == "xla":
        if mode == "numeric":
            return propagate_numeric_ref(rep_scores, topk_ids, topk_d2,
                                         clip01=clip01)
        if mode == "categorical":
            out = propagate_categorical_ref(rep_scores, topk_ids, topk_d2,
                                            n_classes)
            return jnp.clip(out, 0.0, 1.0) if clip01 else out
        if mode == "top1":
            return propagate_top1_ref(rep_scores, topk_ids, topk_d2,
                                      clip01=clip01)
        raise ValueError(f"unknown propagation mode {mode!r}")
    n = topk_ids.shape[0]
    pad = (-n) % block_n
    if pad:
        # in-range ids + PAD_DIST distances: padded rows compute garbage that
        # is sliced off, but never NaN/out-of-bounds
        topk_ids = jnp.pad(topk_ids, ((0, pad), (0, 0)))
        topk_d2 = jnp.pad(topk_d2, ((0, pad), (0, 0)),
                          constant_values=PAD_DIST)
    prescale = None
    if mode == "top1":
        # global reduction over real rows only — computed by XLA around the
        # row-blocked kernel
        prescale = tie_break_prescale(rep_scores, topk_d2[:n]).reshape(1)
    out = propagate_pallas(rep_scores, topk_ids, topk_d2, mode,
                           n_classes=n_classes or 0, clip01=clip01,
                           prescale=prescale, block_n=block_n,
                           interpret=interpret)
    return out[:n]


def _pad_reps(rep_scores: jax.Array) -> jax.Array:
    """Pad (C,) rep scores to a multiple of ``CHUNK_C`` outside the jit, so
    a crack that adds a few reps reuses the compiled program.  Ids never
    point at the pad, and repeating the last score adds no positive gap, so
    the top-1 tie-break scale is unchanged."""
    pad = (-rep_scores.shape[0]) % CHUNK_C
    if not pad:
        return rep_scores
    if rep_scores.shape[0] == 0:
        return jnp.zeros((pad,), rep_scores.dtype)
    return jnp.pad(rep_scores, (0, pad), mode="edge")


_STATIC = ("mode", "n_classes", "clip01", "impl", "block_n", "interpret")
_jit_plain = functools.partial(jax.jit, static_argnames=_STATIC)(
    _propagate_impl)
_jit_donate = functools.partial(jax.jit, static_argnames=_STATIC,
                                donate_argnums=(0,))(_propagate_impl)


@functools.lru_cache(maxsize=None)
def _donation_ok() -> bool:
    # buffer donation is a no-op (with a warning) on CPU
    return jax.devices()[0].platform in ("tpu", "gpu")


def propagate(rep_scores: jax.Array, topk_ids: jax.Array, topk_d2: jax.Array,
              mode: str, n_classes: int | None = None, clip01: bool = False,
              impl: str = "auto", block_n: int = 1024,
              interpret: bool = False, donate: bool | None = None
              ) -> jax.Array:
    """Fused device propagation: rep_scores (C,) -> proxy scores (N,) f32.

    ``mode`` is one of :data:`MODES`; ``n_classes`` is required for
    ``"categorical"``.  Padded top-k columns (squared distance at or above
    :data:`PAD_DIST`) carry zero weight, matching
    :mod:`repro.core.propagation`.  ``donate`` defaults to True on
    accelerators (rep_scores' buffer is recycled) and False on CPU.
    """
    if mode not in MODES:
        raise ValueError(f"unknown propagation mode {mode!r}")
    if mode == "categorical" and not n_classes:
        raise ValueError("categorical propagation needs n_classes")
    impl = resolve_impl(impl)
    if topk_ids.shape[0] == 0:          # empty index: avoid 0-size jit/grid
        return jnp.zeros((0,), jnp.float32)
    rep_scores = _pad_reps(jnp.asarray(rep_scores))
    fn = _jit_donate if (donate if donate is not None
                         else _donation_ok()) else _jit_plain
    return fn(rep_scores, topk_ids, topk_d2, mode=mode, n_classes=n_classes,
              clip01=clip01, impl=impl, block_n=block_n, interpret=interpret)
