"""Jit'd public wrapper for the prefill flash-attention kernel.

Model code passes (B, S, H, Dh) activations; the kernel wants head-major
(B, H, S, Dh) / (B, KV, S, Dh).  Dispatch: Pallas kernel on TPU,
interpret-mode kernel when forced (tests), jnp oracle otherwise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import dispatch_mode
from repro.kernels.flash_attention.kernel import flash_attention_bhsd
from repro.kernels.flash_attention.ref import attention_ref


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k", "force"))
def flash_attention(q, k, v, causal: bool = True, scale: float | None = None,
                    block_q: int = 512, block_k: int = 512,
                    force: str | None = None):
    """q: (B, S, H, Dh); k/v: (B, S, KV, Dh) -> (B, S, H, Dh)."""
    mode = dispatch_mode(force)
    qh = q.swapaxes(1, 2)
    kh = k.swapaxes(1, 2)
    vh = v.swapaxes(1, 2)
    if mode == "ref":
        out = attention_ref(qh, kh, vh, causal=causal, scale=scale)
    else:
        out = flash_attention_bhsd(
            qh, kh, vh, causal=causal, scale=scale, block_q=block_q,
            block_k=block_k, interpret=(mode == "interpret"))
    return out.swapaxes(1, 2)
