"""jit'd wrapper: pads to block multiples, picks impl.

impl="auto": Pallas on TPU, XLA reference otherwise (interpret mode is a
correctness tool, not an execution path — CPU benchmarks use the ref).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import resolve_impl
from repro.kernels.distance_topk.kernel import distance_topk_pallas
from repro.kernels.distance_topk.ref import distance_topk_ref

#: Squared-distance sentinel marking padded top-k columns (k > n_reps).
#: Strictly larger than any real squared distance the kernels produce, and
#: finite in float32 so arithmetic on it stays NaN-free.  Consumers
#: (repro.core.propagation, repro.kernels.propagate) treat columns at or
#: above this value as absent: zero weight, never double-counted.
PAD_DIST = 2.9e38


def _pad_rows(a: jax.Array, mult: int):
    n = a.shape[0]
    pad = (-n) % mult
    if pad:
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
    return a, n


def _pad_rep_value(dtype, d: int) -> float:
    """Per-dimension fill value for padded representative rows.

    Padded reps must lose every top-k comparison, so their squared norm
    (computed in float32 by both impls) should dwarf real distances — but it
    must stay FINITE: the value must be representable in the embedding dtype
    (1e17 overflows float16 to inf, and inf - inf in the distance expansion
    yields NaNs that win the top-k), and d * value^2 must not overflow
    float32.
    """
    v = (1e36 / max(d, 1)) ** 0.5
    if jnp.issubdtype(dtype, jnp.inexact):
        v = min(v, float(jnp.finfo(dtype).max) / 4.0)
    return v


@functools.partial(jax.jit, static_argnames=("k", "impl", "block_n", "block_c",
                                             "interpret"))
def distance_topk(x: jax.Array, r: jax.Array, k: int, impl: str = "auto",
                  block_n: int = 256, block_c: int = 256,
                  interpret: bool = False):
    """x (N,D), r (C,D) -> (squared L2 dists (N,k), rep ids (N,k)), ascending.

    With fewer reps than k, the trailing ``k - n_reps`` columns are padding:
    their distance is the :data:`PAD_DIST` sentinel (ids tile the worst real
    entry so they stay in-range).  Weighted consumers must mask them out —
    tiling the worst *distance* instead would silently double-weight that
    rep in propagation.
    """
    impl = resolve_impl(impl)
    k_eff = min(k, r.shape[0])
    if impl == "xla":
        d, i = distance_topk_ref(x, r, k_eff)
    else:
        xp, n = _pad_rows(x, block_n)
        rp, c = _pad_rows(r, block_c)
        if rp.shape[0] != r.shape[0]:
            # padded reps must never win: offset their squared norm (finite
            # in r.dtype and in the float32 norm computation — see
            # _pad_rep_value)
            pad_rows = rp.shape[0] - r.shape[0]
            rp = jnp.concatenate(
                [rp[:c], jnp.full((pad_rows, r.shape[1]),
                                  _pad_rep_value(r.dtype, r.shape[1]),
                                  r.dtype)], 0)
        d, i = distance_topk_pallas(xp, rp, k_eff, block_n=block_n,
                                    block_c=block_c, interpret=interpret)
        d, i = d[:n], i[:n]
    if k_eff < k:  # fewer reps than k: sentinel distances, in-range ids
        pad_shape = (d.shape[0], k - k_eff)
        d = jnp.concatenate([d, jnp.full(pad_shape, PAD_DIST, d.dtype)], 1)
        last = (i[:, -1:] if k_eff
                else jnp.zeros((i.shape[0], 1), i.dtype))  # repless: id 0
        i = jnp.concatenate([i, jnp.broadcast_to(last, pad_shape)], 1)
    return d, i
