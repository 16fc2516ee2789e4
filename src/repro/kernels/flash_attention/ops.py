"""jit'd wrapper: layout conversion (B,S,H,hd) <-> (B,H,S,hd), head-dim
padding to 128 multiples, seq padding to block multiples, impl selection."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import resolve_impl
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import flash_attention_ref


@functools.partial(jax.jit, static_argnames=("causal", "window", "impl",
                                             "block_q", "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, window: int = 0, impl: str = "auto",
                    block_q: int = 512, block_k: int = 512,
                    interpret: bool = False) -> jax.Array:
    """q (B,S,H,hd), k/v (B,Skv,Hk,hd) -> (B,S,H,hd)."""
    impl = resolve_impl(impl)
    if impl == "xla":
        return flash_attention_ref(q, k, v, causal=causal, window=window)

    b, s, h, hd = q.shape
    skv = k.shape[1]
    # layout: (B,H,S,hd)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    hd_pad = (-hd) % 128
    bq = min(block_q, max(s, 1))
    bk = min(block_k, max(skv, 1))
    sq_pad = (-s) % bq
    skv_pad = (-skv) % bk
    if hd_pad:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, 0), (0, hd_pad)))
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, 0), (0, hd_pad)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, 0), (0, hd_pad)))
    if sq_pad:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, sq_pad), (0, 0)))
    if skv_pad:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, skv_pad), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, skv_pad), (0, 0)))
    # scale uses the PADDED head dim inside the kernel; compensate so softmax
    # logits match the logical sqrt(hd)
    scale_fix = jnp.sqrt((hd + hd_pad) / hd).astype(qt.dtype)
    out = flash_attention_pallas(qt * scale_fix, kt, vt, causal=causal,
                                 window=window, block_q=bq, block_k=bk,
                                 seq_kv=skv, interpret=interpret)
    out = out[:, :, :s, :hd].transpose(0, 2, 1, 3)
    return out.astype(q.dtype)
