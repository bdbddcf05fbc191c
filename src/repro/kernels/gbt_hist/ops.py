"""Jit'd public wrappers for the GBT histogram kernel (pads + dispatches)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import dispatch_mode
from repro.kernels.gbt_hist.kernel import gbt_hist as gbt_hist_kernel
from repro.kernels.gbt_hist.ref import gbt_hist_ref


@functools.partial(jax.jit, static_argnames=("n_bins", "block_f", "block_n",
                                             "force"))
def build_histograms(bins, grad, hess, n_bins: int, block_f: int = 8,
                     block_n: int = 512, force: str | None = None):
    """bins: (n, f) int32; grad/hess: (n,) -> (f, n_bins, 2) fp32."""
    mode = dispatch_mode(force)
    if mode == "ref":
        return gbt_hist_ref(bins, grad, hess, n_bins)
    n, f = bins.shape
    bn = min(block_n, max(8, n))
    pad_n = (-n) % bn
    bf = min(block_f, f)
    pad_f = (-f) % bf
    if pad_n or pad_f:
        bins = jnp.pad(bins, ((0, pad_n), (0, pad_f)))
        grad = jnp.pad(grad, (0, pad_n))
        hess = jnp.pad(hess, (0, pad_n))
    out = gbt_hist_kernel(bins, grad, hess, n_bins=n_bins, block_f=bf,
                          block_n=bn, interpret=(mode == "interpret"))
    return out[:f]


@functools.partial(jax.jit, static_argnames=("n_nodes", "n_bins", "block_f",
                                             "block_n", "force"))
def build_node_histograms(bins, grad, hess, node_id, n_nodes: int,
                          n_bins: int, block_f: int = 8, block_n: int = 512,
                          force: str | None = None):
    """Per-tree-node histograms: (n, f) bins + (n,) node ids ->
    (n_nodes, f, n_bins, 2).

    TPUs have no atomics, so node separation is zero-masked weights: one
    kernel pass per node with ``grad * (node_id == node)`` — a
    zero-weight row adds exactly 0.0 to every bin.  The node loop is
    unrolled inside this jit, so level-wise GBT growth issues a single
    XLA call per level instead of ``n_nodes`` host round trips.
    """
    outs = []
    for li in range(n_nodes):
        m = (node_id == li).astype(grad.dtype)
        outs.append(build_histograms(bins, grad * m, hess * m,
                                     n_bins=n_bins, block_f=block_f,
                                     block_n=block_n, force=force))
    return jnp.stack(outs)
