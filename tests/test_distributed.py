"""Multi-device distribution tests (subprocess with fake host devices —
XLA locks the device count at first init, so these can't run in-process)."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent


def _run(script: str, n_dev: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={n_dev}")
    env["PYTHONPATH"] = str(REPO / "src")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


EP_MOE_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config
from repro.distributed.sharding import ShardingPolicy, use_policy
from repro.distributed.ep_moe import ep_available, moe_ffn_ep
from repro.launch.mesh import make_host_mesh
from repro.models import moe as moe_mod

# generous capacity so no tokens drop -> EP and GSPMD paths must agree
cfg = get_smoke_config("phi3.5-moe-42b-a6.6b").scaled(capacity_factor=8.0)
mesh = make_host_mesh(model=4)
policy = ShardingPolicy(mesh, data_axes=("data",), model_axes=("model",))
assert ep_available(cfg, policy)

key = jax.random.key(0)
params = moe_mod.init_moe(cfg, key)
x = jax.random.normal(jax.random.key(1), (4, 16, cfg.d_model), jnp.float32)
x = x.astype(cfg.compute_dtype)

# reference: single-device GSPMD-free path (policy None)
ref, aux_ref = jax.jit(lambda p, x: moe_mod.moe_ffn(cfg, p, x))(params, x)

def ep(p, xx):
    return moe_ffn_ep(cfg, p, xx, policy)

with use_policy(policy):
    out, aux = jax.jit(ep)(params, x)

err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32))))
aerr = abs(float(aux) - float(aux_ref))
print("MAXERR", err, "AUXERR", aerr)
assert err < 3e-2, err
assert aerr < 1e-3, (float(aux), float(aux_ref))
print("EP_MOE_OK")
"""


def test_ep_moe_matches_reference():
    out = _run(EP_MOE_SCRIPT)
    assert "EP_MOE_OK" in out, out


CP_COMPILE_SCRIPT = r"""
import jax, jax.numpy as jnp
from repro.configs import get_smoke_config
from repro.configs.shapes import ShapeSpec
from repro.distributed.sharding import ShardingPolicy, use_policy
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import build_step
from repro.models.transformer import Model

# 6 heads on a 4-wide model axis -> not divisible -> CP fallback engages
cfg = get_smoke_config("llama3.2-3b").scaled(
    n_heads=6, n_kv_heads=2, param_dtype=jnp.bfloat16)
mesh = make_host_mesh(model=4)
policy = ShardingPolicy(mesh, data_axes=("data",), serving=True,
                        serving_2d=False, cp_replicate_weights=True)
shape = ShapeSpec("p", seq_len=64, global_batch=4, kind="prefill")
model = Model(cfg)
step, in_sh, out_sh, args = build_step(model, policy, shape)
with use_policy(policy):
    compiled = jax.jit(step, in_shardings=in_sh,
                       out_shardings=out_sh).lower(*args).compile()
print("CP_COMPILE_OK", compiled.cost_analysis().get("flops"))
"""


def test_cp_policy_compiles_nondivisible_heads():
    out = _run(CP_COMPILE_SCRIPT)
    assert "CP_COMPILE_OK" in out, out


SERVE_STEP_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config
from repro.configs.shapes import ShapeSpec
from repro.distributed.sharding import ShardingPolicy, use_policy
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import build_serve_step
from repro.models.transformer import Model

cfg = get_smoke_config("qwen2.5-32b").scaled(param_dtype=jnp.bfloat16)
mesh = make_host_mesh(model=4)
policy = ShardingPolicy(mesh, data_axes=("data",), serving=True,
                        serving_2d=False)
shape = ShapeSpec("d", seq_len=64, global_batch=8, kind="decode")
model = Model(cfg)
step, in_sh, out_sh, args = build_serve_step(model, policy, shape)

# run it for real on the fake mesh: sharded decode must equal local decode
params = model.init(jax.random.key(0))
cache = model.init_cache(8, 64, filled=63)
toks = jnp.zeros((8, 1), jnp.int32)
local_logits, _ = jax.jit(model.decode_step)(params, cache, toks)
with use_policy(policy):
    sharded = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh)
    sh_logits, _ = sharded(params, cache, toks)
err = float(jnp.max(jnp.abs(local_logits.astype(jnp.float32)
                            - sh_logits.astype(jnp.float32))))
print("MAXERR", err)
assert err < 5e-2, err
print("SERVE_SHARDED_OK")
"""


def test_sharded_serve_step_matches_local():
    out = _run(SERVE_STEP_SCRIPT)
    assert "SERVE_SHARDED_OK" in out, out
