"""Jit'd public wrapper: shape handling + platform dispatch."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import dispatch_mode
from repro.kernels.rmsnorm.kernel import rmsnorm_2d
from repro.kernels.rmsnorm.ref import rmsnorm_ref


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "force"))
def rmsnorm(x, scale, eps: float = 1e-5, block_rows: int = 256,
            force: str | None = None):
    """RMSNorm over the last dim of an arbitrarily-shaped x.

    ``force``: None (auto: kernel on TPU, interpret-kernel nowhere — oracle
    elsewhere), "kernel", "interpret", or "ref".
    """
    mode = dispatch_mode(force)
    if mode == "ref":
        return rmsnorm_ref(x, scale, eps)
    lead = x.shape[:-1]
    d = x.shape[-1]
    n = 1
    for s in lead:
        n *= int(s)
    x2 = x.reshape(max(n, 1), d)
    rows = x2.shape[0]
    br = min(block_rows, rows)
    pad = (-rows) % br
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    out = rmsnorm_2d(x2, scale, eps=eps, block_rows=br,
                     interpret=(mode == "interpret"))
    if pad:
        out = out[:rows]
    return out.reshape(*lead, d)
