"""Expert-parallel MoE via shard_map (hillclimb iteration 3).

The GSPMD lowering of scatter-based MoE dispatch cannot partition a
scatter whose indices cross shards: it replicates the (E, C, D) dispatch
buffer on every device and combines contributions with full-buffer
all-reduces (~13 GB per MoE layer for llama4-maverick at train_4k;
measured 326 GB of all-reduce per period — see EXPERIMENTS.md §Perf).

This implementation makes the dispatch *local by construction*:

  device (i, j) holds tokens of data-shard i and experts of model-shard j
    1. route locally (router weights are replicated),
    2. keep only assignments to the local expert block [j*E_loc, ...),
    3. local sort -> rank -> capacity-bucketed local scatter,
    4. local expert FFN (weights already sharded over `model` on E),
    5. local combine back to token order, weighted by gate values,
    6. one psum over `model` sums each token's expert contributions.

Collectives per layer: a single (T_loc, D) psum (plus scalar aux-loss
psums) instead of replicated-buffer all-reduces.  Capacity semantics are
per-data-shard (capacity_factor applies within each shard), the standard
distributed-capacity variant (MaxText/GShard do the same).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig


def _axis_sizes(policy):
    n_model = 1
    for a in policy.model_axes:
        n_model *= policy.mesh.shape[a]
    n_data = 1
    for a in policy.data_axes:
        n_data *= policy.mesh.shape[a]
    return n_data, n_model


def ep_available(cfg: ModelConfig, policy, batch: int = 0,
                 seq: int = 0) -> bool:
    if policy is None:
        return False
    n_data, n_model = _axis_sizes(policy)
    if cfg.n_experts % n_model or n_model <= 1:
        return False
    if batch and seq:
        # tokens must shard over data on either the batch or seq dim
        return batch % n_data == 0 or seq % n_data == 0
    return True


def moe_ffn_ep(cfg: ModelConfig, params, x, policy):
    """x: (B, S, D) -> (out, aux).  Drop-in for moe.moe_ffn."""
    mesh = policy.mesh
    data_axes = tuple(policy.data_axes)
    model_ax = policy.model_axes[0]
    n_data, n_model = _axis_sizes(policy)
    e, k = cfg.n_experts, cfg.top_k
    e_loc = e // n_model
    d = cfg.d_model
    dtype = cfg.compute_dtype

    b, s, _ = x.shape
    t_loc = (b * s) // n_data
    cap = max(8, int(cfg.capacity_factor * k * t_loc / e) + 1)
    cap = ((cap + 7) // 8) * 8

    def local_fn(x_loc, router_w, wg, wu, wd):
        bl, sl, _ = x_loc.shape
        t = bl * sl
        xt = x_loc.reshape(t, d)
        logits = jnp.einsum("td,de->te", xt,
                            router_w.astype(dtype)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, k)
        if k > 1:
            gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1,
                                            keepdims=True)
        # aux loss from global stats (psum over data shards)
        me = jax.lax.pmean(jnp.mean(probs, axis=0), data_axes)
        ce = jax.lax.pmean(
            jnp.mean(jax.nn.one_hot(gate_idx[:, 0], e, dtype=jnp.float32),
                     axis=0), data_axes)
        aux = e * jnp.sum(me * ce)

        # local expert block
        j = jax.lax.axis_index(model_ax)
        e_start = j * e_loc
        flat_e = gate_idx.reshape(-1)
        flat_g = gate_vals.reshape(-1)
        flat_t = (jnp.repeat(jnp.arange(t), k) if k > 1
                  else jnp.arange(t))
        local = (flat_e >= e_start) & (flat_e < e_start + e_loc)
        le = jnp.where(local, flat_e - e_start, e_loc)   # e_loc = "dropped"
        order = jnp.argsort(le)
        se, st, sg = le[order], flat_t[order], flat_g[order]
        starts = jnp.searchsorted(se, jnp.arange(e_loc))
        rank = jnp.arange(se.shape[0]) - starts[jnp.clip(se, 0, e_loc - 1)]
        keep = (se < e_loc) & (rank < cap)
        slot_e = jnp.where(keep, se, 0)
        slot_c = jnp.where(keep, rank, 0)

        gathered = xt[st] * keep[:, None].astype(dtype)
        buf = jnp.zeros((e_loc, cap, d), dtype)
        buf = buf.at[slot_e, slot_c].add(gathered)

        g = jnp.einsum("ecd,edf->ecf", buf, wg.astype(dtype))
        u = jnp.einsum("ecd,edf->ecf", buf, wu.astype(dtype))
        h = jax.nn.silu(g) * u
        out_buf = jnp.einsum("ecf,efd->ecd", h, wd.astype(dtype))

        contrib = out_buf[slot_e, slot_c] \
            * (sg * keep).astype(dtype)[:, None]
        yt = jnp.zeros_like(xt)
        yt = yt.at[st].add(contrib)
        # sum each token's expert contributions across model shards
        yt = jax.lax.psum(yt, model_ax)
        return yt.reshape(bl, sl, d), aux

    w = params["experts"]
    batch_spec = data_axes if len(data_axes) > 1 else data_axes[0]
    if b % n_data == 0:
        x_spec = P(batch_spec, None, None)
    else:
        # small-batch serving (e.g. long-context bb=1): shard tokens on seq
        x_spec = P(None, batch_spec, None)
    out, aux = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(x_spec, P(), P(model_ax), P(model_ax), P(model_ax)),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, params["router"], w["w_gate"], w["w_up"], w["w_down"])
    return out, aux
