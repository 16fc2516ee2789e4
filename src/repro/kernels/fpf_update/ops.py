"""jit'd wrapper for the FPF step kernel with padding + XLA fallback."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import resolve_impl
from repro.kernels.fpf_update.kernel import fpf_update_pallas
from repro.kernels.fpf_update.ref import fpf_update_ref


@functools.partial(jax.jit, static_argnames=("impl", "block_n", "interpret"))
def fpf_update(x: jax.Array, rep: jax.Array, min_d2: jax.Array,
               impl: str = "auto", block_n: int = 1024,
               interpret: bool = False):
    impl = resolve_impl(impl)
    if impl == "xla":
        return fpf_update_ref(x, rep, min_d2)
    n = x.shape[0]
    pad = (-n) % block_n
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        min_d2 = jnp.pad(min_d2, (0, pad), constant_values=-1.0)
    new_min, idx, val = fpf_update_pallas(x, rep, min_d2, block_n=block_n,
                                          interpret=interpret)
    return new_min[:n], idx, val
