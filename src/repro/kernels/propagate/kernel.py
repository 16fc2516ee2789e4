"""Pallas TPU kernels: fused score propagation over the cached top-k.

Propagation is O(N*k) arithmetic over index structures that never change
between cracks, so the serving hot path keeps ``topk_ids``/``topk_d2``
resident in device memory and runs one fused kernel per (score fn, mode):
each (BN,) row block reads its (BN, k) slice of the rep structures once from
HBM, gathers the (C,) rep-score vector (broadcast to every block), and
writes the (BN,) proxy slice — no (N, C) intermediate, no host round-trip.

Rep-score gathers are one-hot reductions over the (BN, C) comparison grid
(TPU-friendly: iota + where + sum on the VPU; no dynamic-gather primitive
inside the kernel), unrolled over the small static k.  Padded top-k columns
(squared distance at or above ``PAD_DIST``) carry zero weight, matching the
host path in :mod:`repro.core.propagation`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# python scalar: jnp constants can't be captured by kernels
PAD_DIST = 2.9e38
#: reps per step of the in-kernel gather loop (lane-aligned); ops.py pads
#: the rep-score row to a multiple of it
CHUNK_C = 512


def _gather_k(scores_ref, ids: jax.Array, cols) -> list:
    """scores_ref (1, Cp), ids (BN, k) -> [scores[ids[:, j]] for j in cols].

    One-hot reductions over (BN, CHUNK_C) comparison tiles, looped over the
    rep axis so the kernel's size (and its compile time) does not grow with
    C.  Exactly one rep matches each id, so adding the chunks' zeros leaves
    every gathered value bit-identical to a single reduction.
    """
    bn = ids.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (bn, CHUNK_C), 1)

    def body(step, acc):
        base = pl.multiple_of(step * CHUNK_C, CHUNK_C)
        chunk = scores_ref[:, pl.ds(base, CHUNK_C)].astype(jnp.float32)
        return tuple(
            a + jnp.sum(jnp.where(ids[:, j:j + 1] == base + lane, chunk, 0.0),
                        axis=1)
            for a, j in zip(acc, cols))

    n_steps = scores_ref.shape[1] // CHUNK_C
    init = tuple(jnp.zeros((bn,), jnp.float32) for _ in cols)
    return list(jax.lax.fori_loop(0, n_steps, body, init))


def _column_weight(d2_col: jax.Array, eps: float) -> jax.Array:
    d2 = jnp.maximum(d2_col, 0.0)
    w = 1.0 / (jnp.sqrt(d2) + eps)
    return jnp.where(d2_col >= PAD_DIST, 0.0, w)


def _numeric_kernel(scores_ref, ids_ref, d2_ref, out_ref, *, k: int,
                    eps: float, clip01: bool):
    ids = ids_ref[...]                               # (BN, k)
    d2 = d2_ref[...].astype(jnp.float32)             # (BN, k)
    gathered = _gather_k(scores_ref, ids, range(k))
    num = jnp.zeros((ids.shape[0],), jnp.float32)
    den = jnp.zeros((ids.shape[0],), jnp.float32)
    for j in range(k):                               # k static: unrolled
        w = _column_weight(d2[:, j], eps)
        num = num + w * gathered[j]
        den = den + w
    out = num / den
    if clip01:
        out = jnp.clip(out, 0.0, 1.0)
    out_ref[...] = out


def _categorical_kernel(scores_ref, ids_ref, d2_ref, out_ref, *, k: int,
                        n_classes: int, eps: float):
    ids = ids_ref[...]
    d2 = d2_ref[...].astype(jnp.float32)
    bn = ids.shape[0]
    gathered = _gather_k(scores_ref, ids, range(k))
    votes = jnp.zeros((bn, n_classes), jnp.float32)
    class_ids = jax.lax.broadcasted_iota(jnp.int32, (bn, n_classes), 1)
    for j in range(k):
        w = _column_weight(d2[:, j], eps)
        cls = gathered[j].astype(jnp.int32)
        votes = votes + jnp.where(cls[:, None] == class_ids, w[:, None], 0.0)
    out_ref[...] = jnp.argmax(votes, axis=1).astype(jnp.float32)


def _top1_kernel(scores_ref, ids_ref, d2_ref, pre_ref, out_ref, *,
                 clip01: bool):
    base, = _gather_k(scores_ref, ids_ref[...], [0])
    d = jnp.sqrt(jnp.maximum(d2_ref[...][:, 0].astype(jnp.float32), 0.0))
    out = base - pre_ref[0] * d
    if clip01:
        out = jnp.clip(out, 0.0, 1.0)
    out_ref[...] = out


def propagate_pallas(rep_scores: jax.Array, topk_ids: jax.Array,
                     topk_d2: jax.Array, mode: str, n_classes: int = 0,
                     clip01: bool = False, eps: float = 1e-6,
                     prescale: jax.Array = None, block_n: int = 1024,
                     interpret: bool = False) -> jax.Array:
    """rep_scores (C,), topk_ids/(d2) (N,k) -> (N,) propagated proxy.

    N % block_n == 0 and C % CHUNK_C == 0 required (ops.py pads; the 1-D
    (block_n,) output blocks must be multiples of 1024 on a TPU).
    ``prescale`` is the top-1 tie-break scalar (a (1,) array; see
    :func:`repro.kernels.propagate.ref.tie_break_prescale`) — it involves a
    global reduction over rows, so it is computed by XLA around the kernel.
    """
    n, k = topk_ids.shape
    c = rep_scores.shape[0]
    assert n % block_n == 0 and c % CHUNK_C == 0, (n, block_n, c)
    grid = (n // block_n,)
    common_specs = [
        pl.BlockSpec((1, c), lambda i: (0, 0)),          # full rep scores
        pl.BlockSpec((block_n, k), lambda i: (i, 0)),
        pl.BlockSpec((block_n, k), lambda i: (i, 0)),
    ]
    if mode == "numeric":
        kernel = functools.partial(_numeric_kernel, k=k, eps=eps,
                                   clip01=clip01)
        operands = (rep_scores, topk_ids, topk_d2)
        in_specs = common_specs
    elif mode == "categorical":
        kernel = functools.partial(_categorical_kernel, k=k,
                                   n_classes=n_classes, eps=eps)
        operands = (rep_scores, topk_ids, topk_d2)
        in_specs = common_specs
    elif mode == "top1":
        kernel = functools.partial(_top1_kernel, clip01=clip01)
        operands = (rep_scores, topk_ids, topk_d2, prescale)
        in_specs = common_specs + [pl.BlockSpec((1,), lambda i: (0,))]
    else:
        raise ValueError(f"unknown propagation mode {mode!r}")
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_n,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.float32),
        interpret=interpret,
    )(rep_scores.reshape(1, c), *operands[1:])
