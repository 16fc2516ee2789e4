"""Pallas TPU kernel: blocked pairwise squared-L2 distances with a *running
top-k* — the bandwidth-optimal form of the paper's N x C distance computation
(DESIGN.md §3).

Instead of materializing the (N, C) distance matrix in HBM (the paper's
``NCD * c_D`` term as implemented on GPU), each (row-block i, col-block j)
grid step computes a (BN, BC) tile on the MXU (2 x BN x BC x D FLOPs via one
``dot``) and folds it into a per-row top-k held in VMEM across the j sweep —
O(N*k) HBM writes instead of O(N*C).

Top-k maintenance is sort-free (TPU-friendly): k rounds of (min,
first-index, mask) extract the k smallest of the fresh tile, which are then
merged with the running top-k through another k rounds over both lists of k
at once.  All ops are VPU-native (min/where/iota/sum); no sort, gather or
dynamic index inside the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_BIG = 3.0e38  # python scalar: jnp constants can't be captured by kernels
_BIG_COL = 2 ** 30  # above every column index of a tile


def _k_smallest(parts, k: int):
    """k smallest per row of the column-wise concatenation of ``parts``.

    ``parts`` is a sequence of (vals (BN, M_p) float32, ids (BN, M_p) int32);
    returns (vals (BN, k), ids (BN, k)) ascending.  Ties go to the earlier
    part, then the lower column — the order ``argmin`` over the
    concatenation would give.  k static rounds of (min, first-index, mask),
    all elementwise selects and lane reductions: no gather, no dynamic
    index and no unaligned lane concatenation inside the kernel.
    """
    bn = parts[0][0].shape[0]
    cols = [jax.lax.broadcasted_iota(jnp.int32, v.shape, 1) for v, _ in parts]
    slot = jax.lax.broadcasted_iota(jnp.int32, (bn, k), 1)
    out_v = jnp.zeros((bn, k), jnp.float32)
    out_i = jnp.zeros((bn, k), jnp.int32)
    vals = [v for v, _ in parts]
    for t in range(k):                                  # k static: unrolled
        mins = [jnp.min(v, axis=1, keepdims=True) for v in vals]
        m = functools.reduce(jnp.minimum, mins)         # (BN, 1)
        taken = jnp.zeros((bn, 1), jnp.bool_)
        sel = jnp.zeros((bn, 1), jnp.int32)
        for p, ((_, ids), col) in enumerate(zip(parts, cols)):
            here = jnp.logical_and(jnp.logical_not(taken), mins[p] == m)
            first = jnp.min(jnp.where(vals[p] == m, col, _BIG_COL), axis=1,
                            keepdims=True)
            hit = jnp.logical_and(here, col == first)   # one column at most
            sel = jnp.where(here, jnp.sum(jnp.where(hit, ids, 0), axis=1,
                                          keepdims=True), sel)
            vals[p] = jnp.where(hit, NEG_BIG, vals[p])
            taken = jnp.logical_or(taken, here)
        out_v = jnp.where(slot == t, m, out_v)
        out_i = jnp.where(slot == t, sel, out_i)
    return out_v, out_i


def _kernel(x_ref, r_ref, rsq_ref, val_ref, idx_ref, *, k: int, block_c: int):
    j = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)          # (BN, D)
    r = r_ref[...].astype(jnp.float32)          # (BC, D)
    xsq = jnp.sum(x * x, axis=1, keepdims=True)  # (BN, 1)
    d2 = (xsq + rsq_ref[...]
          - 2.0 * jax.lax.dot_general(
              x, r, (((1,), (1,)), ((), ())),
              preferred_element_type=jnp.float32))
    d2 = jnp.maximum(d2, 0.0)                   # (BN, BC)
    col_ids = (j * block_c
               + jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1))
    tile_v, tile_i = _k_smallest([(d2, col_ids)], k)

    @pl.when(j == 0)
    def _init():
        val_ref[...] = tile_v
        idx_ref[...] = tile_i

    @pl.when(j > 0)
    def _merge():
        new_v, new_i = _k_smallest([(val_ref[...], idx_ref[...]),
                                    (tile_v, tile_i)], k)
        val_ref[...] = new_v
        idx_ref[...] = new_i


def distance_topk_pallas(x: jax.Array, r: jax.Array, k: int,
                         block_n: int = 256, block_c: int = 256,
                         interpret: bool = False):
    """x (N,D), r (C,D) -> (squared dists (N,k), ids (N,k)) ascending.

    N % block_n == 0 and C % block_c == 0 are required (ops.py pads).
    """
    n, d = x.shape
    c = r.shape[0]
    assert n % block_n == 0 and c % block_c == 0, (n, c, block_n, block_c)
    # (1, C): a lane-dense row; x's squared norms are summed in the kernel
    rsq = jnp.sum(r.astype(jnp.float32) ** 2, axis=1)[None, :]
    grid = (n // block_n, c // block_c)
    return pl.pallas_call(
        functools.partial(_kernel, k=k, block_c=block_c),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_c, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, block_c), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, k), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, k), jnp.float32),
            jax.ShapeDtypeStruct((n, k), jnp.int32),
        ],
        interpret=interpret,
    )(x, r, rsq)
