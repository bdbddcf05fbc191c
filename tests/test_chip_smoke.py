"""chip_smoke.py's phases on the CPU, at smoke size, with the script's own
checks (the chip runs them at full size)."""
import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys

import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config

REPO = pathlib.Path(__file__).resolve().parent.parent
SCRIPT = REPO / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_engine_phase_decode_matches_prefill(smoke):
    cfg = get_smoke_config(smoke.ENGINE_ARCH).scaled(
        param_dtype=jnp.bfloat16)
    out = smoke.engine_phase(cfg, batches=(1, 2), prompt_len=16,
                             new_tokens=6, check_steps=3)
    assert sorted(out["batches"]) == [1, 2]
    assert len(out["decode_vs_prefill"]) == 3
    assert max(out["decode_vs_prefill"]) <= smoke.LOGIT_RTOL_BF16
    assert max(out["decode_vs_prefill_f32"]) <= smoke.LOGIT_RTOL_F32
    # the control: a cache position one too far must fail the check
    assert min(out["shifted_by_one_f32"]) > smoke.LOGIT_RTOL_F32


def test_ala_phase_matches_cpu_reference(smoke):
    """Keeps ALA_CPU_REFERENCE, which the chip run is compared with, the
    CPU's result for the same seed (the CPU run is deterministic)."""
    out = smoke.ala_phase()
    smoke.ala_matches_reference(
        out, rtol=dict.fromkeys(smoke.ALA_CPU_REFERENCE, 1e-6))


SHARDED_SCRIPT = r"""
import importlib.util, jax, jax.numpy as jnp
from repro.configs import get_smoke_config
spec = importlib.util.spec_from_file_location("chip_smoke", {script!r})
cs = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cs)
assert len(jax.devices()) == 4
full = get_smoke_config(cs.SHARDED_ARCH).scaled(param_dtype=jnp.bfloat16)
out = cs.sharded_phase(full, full.scaled(n_layers=1), batch=8, ctx=64,
                       steps=4)
assert max(out["vs_local"]) <= cs.LOGIT_RTOL_BF16, out
print("SHARDED_SMOKE_OK")
"""


def _env(**extra) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env.update(extra)
    return env


def test_sharded_phase_matches_local_on_fake_devices():
    env = _env(XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=4"))
    out = subprocess.run(
        [sys.executable, "-c", SHARDED_SCRIPT.format(script=str(SCRIPT))],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARDED_SMOKE_OK" in out.stdout, out.stdout


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_main_refuses_without_tpu(where, tmp_path):
    """No TPU, or no repository around the script: non-zero exit and no
    ``ok`` line."""
    script = SCRIPT
    if where == "alone":
        script = tmp_path / SCRIPT.name
        shutil.copy(SCRIPT, script)
    env = _env(JAX_PLATFORMS="cpu")
    if where == "alone":
        env.pop("PYTHONPATH")
    out = subprocess.run([sys.executable, str(script)], env=env,
                         cwd=script.parent, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout, out.stdout
