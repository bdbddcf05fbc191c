"""Jit'd public wrapper for the flash-decoding kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import dispatch_mode
from repro.kernels.decode_attention.kernel import decode_attention_grouped
from repro.kernels.decode_attention.ref import decode_attention_ref


@functools.partial(jax.jit, static_argnames=("scale", "block_t", "force"))
def decode_attention(q, k, v, pos, scale: float | None = None,
                     block_t: int = 512, force: str | None = None):
    """q: (B, H, Dh); k/v: (B, T, KV, Dh); pos: () — returns (B, H, Dh)."""
    b, h, dh = q.shape
    t, kv = k.shape[1], k.shape[2]
    g = h // kv
    qg = q.reshape(b, kv, g, dh)
    kh = k.swapaxes(1, 2)      # (B, KV, T, Dh)
    vh = v.swapaxes(1, 2)
    mode = dispatch_mode(force)
    if mode == "ref":
        out = decode_attention_ref(qg, kh, vh, pos, scale=scale)
    else:
        out = decode_attention_grouped(
            qg, kh, vh, pos, scale=scale, block_t=block_t,
            interpret=(mode == "interpret"))
    return out.reshape(b, h, dh)
