"""Pallas TPU kernel for one FPF step (distance to newest rep + running min +
argmax), fused so each step makes a single pass over the embedding
matrix instead of three (DESIGN.md §3).

FPF is inherently sequential in the number of representatives C (each argmax
depends on the previous update); the TPU win is inside a step: the (BN, D)
embedding tile is read once from HBM, the new distances, the min with the
carried state, and the (max, argmax) reduction all happen in VMEM; the
sequential grid folds each block's (max, argmax) into one resident output
block, so nothing is left to reduce after the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


_BIG_ROW = 2 ** 30  # above every row index


def _splat(v: jax.Array) -> jax.Array:
    """(1, 1) -> (8, 128), across lanes first, then sublanes (Mosaic does
    not broadcast both at once)."""
    return jnp.broadcast_to(jnp.broadcast_to(v, (1, 128)), (8, 128))


def _kernel(x_ref, rep_ref, min_ref, newmin_ref, best_ref, *, block_n: int):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)          # (BN, D)
    rep = rep_ref[...].astype(jnp.float32)      # (1, D)
    diff = x - rep
    d2 = jnp.sum(diff * diff, axis=1)           # (BN,)
    new_min = jnp.minimum(min_ref[...], d2)
    newmin_ref[...] = new_min
    # block (max, first argmax) as a (1, 1) value: a compare-select and
    # lane/sublane reductions, no dynamic index into the vector
    col = new_min.reshape(1, block_n)
    rows = i * block_n + jax.lax.broadcasted_iota(jnp.int32, (1, block_n), 1)
    bmax = jnp.max(col, axis=1, keepdims=True)
    barg = jnp.min(jnp.where(col == bmax, rows, _BIG_ROW), axis=1,
                   keepdims=True)
    # running (max, argmax) over the sequential grid, held in one resident
    # lane-dense (8, 128) output block: row 0 the max, row 1 the argmax
    # (exact in float32 below 2**24 rows).  Ties keep the earlier block.
    sub = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0)
    fresh = jnp.where(sub == 0, _splat(bmax), _splat(barg.astype(jnp.float32)))

    @pl.when(i == 0)
    def _init():
        best_ref[...] = fresh

    @pl.when(i > 0)
    def _fold():
        best = best_ref[...]
        best_ref[...] = jnp.where(fresh[0:1, :] > best[0:1, :], fresh, best)


def fpf_update_pallas(x: jax.Array, rep: jax.Array, min_d2: jax.Array,
                      block_n: int = 1024, interpret: bool = False):
    """x (N,D), rep (D,), min_d2 (N,) -> (new_min (N,), argmax, max).

    N % block_n == 0 required (ops.py pads with -1 min so pads never win).
    """
    n, d = x.shape
    assert n % block_n == 0, (n, block_n)
    assert n < 2 ** 24, n                     # argmax rides in float32
    new_min, best = pl.pallas_call(
        functools.partial(_kernel, block_n=block_n),
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((block_n,), lambda i: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((block_n,), lambda i: (i,)),
            pl.BlockSpec((8, 128), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.float32),
            jax.ShapeDtypeStruct((8, 128), jnp.float32),
        ],
        interpret=interpret,
    )(x, rep.reshape(1, -1), min_d2)
    return new_min, best[1, 0].astype(jnp.int32), best[0, 0]
