"""The program under test for a dense GQA configuration.

Builds the program's ``ModelConfig`` from a configuration file (the
registry entry, with every size of the file put over it) and hands the
benchmark's weights to the program in its own parameter layout.  The
program's tree holds the same arrays, so no weight is copied.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models import layers
from repro.models.config import ModelConfig

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}

# the layer scopes that the dense programs carry (``run.py``)
SCOPES = (layers.ATTENTION, layers.KV_WRITE, layers.MLP, layers.HEAD)

# the CPU tests' size: every flag of the configuration as published
SMALL = dict(hidden_size=64, intermediate_size=160, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
             vocab_size=300)


def model_config(conf: dict) -> ModelConfig:
    c, arch = conf["config"], conf["architecture"]
    d, h = c["hidden_size"], c["num_attention_heads"]
    return get_config(conf["registry_id"]).scaled(
        n_layers=c["num_hidden_layers"], d_model=d, n_heads=h,
        n_kv_heads=c["num_key_value_heads"], d_head=c.get("head_dim", d // h),
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=c["tie_word_embeddings"], qkv_bias=arch["qkv_bias"],
        qk_norm=arch["qk_norm"], sliding_window=None,
        param_dtype=DTYPES[c["torch_dtype"]],
        compute_dtype=DTYPES[c["torch_dtype"]])


def embedding_rows(cfg: ModelConfig) -> int:
    return cfg.padded_vocab


def program_params(model, w: dict) -> dict:
    """The program's parameter tree over the benchmark's weights ``w``
    (the layout of ``chipbench/reference/dense_gqa.py``).  Raises if the
    program's own layout has other leaves, shapes or dtypes."""
    attn = {k: w[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv",
                              "q_norm", "k_norm") if k in w}
    block = {"norm1": {"scale": w["attn_norm"]}, "attn": attn,
             "norm2": {"scale": w["mlp_norm"]},
             "mlp": {k: w[k] for k in ("w_gate", "w_up", "w_down")}}
    embed = {"tok_embed": w["embed"]}
    if "head" in w:
        embed["lm_head"] = w["head"]
    params = {"embed": embed, "final_norm": {"scale": w["final_norm"]},
              "blocks": (block,)}
    want = jax.eval_shape(model.init, jax.random.key(0))
    got = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or want != got:
        raise ValueError("the program's parameter layout differs from the "
                         "one this benchmark builds: "
                         f"{jax.tree.map(lambda s: s.shape, want)}")
    return params


def embed_bytes(params: dict) -> int:
    return params["embed"]["tok_embed"].nbytes
