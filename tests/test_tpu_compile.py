"""Compile for a described TPU v5e, with no chip attached.

The chip's compiler refuses what interpret mode and the CPU accept: tiles
that do not align, kernels that use too much fast memory, programs that do
not fit in HBM.  These tests compile the Pallas kernels at llama3.2-3b
widths and the serving engine's full-width prefill, so every change is
held to that compiler.  Nothing runs, so they say nothing about results
or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and pytest workers import
every test file.
"""
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import ops as da_ops
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.gbt_hist import ops as gh_ops
from repro.kernels.rmsnorm import ops as rms_ops
from repro.models.transformer import Model

REPO = pathlib.Path(__file__).resolve().parent.parent
HBM_BYTES = int(15.75 * 2 ** 30)     # what XLA lets one v5e program use


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_cases():
    bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    return {
        # (fn, args as (shape, dtype), static kwargs)
        "gbt_hist_512x8": (gh_ops.build_histograms,
                           [((512, 8), i32), ((512,), f32), ((512,), f32)],
                           dict(n_bins=16)),
        "gbt_hist_64x5": (gh_ops.build_histograms,
                          [((64, 5), i32), ((64,), f32), ((64,), f32)],
                          dict(n_bins=32)),
        "rmsnorm_2048x3072": (rms_ops.rmsnorm,
                              [((2048, 3072), bf16), ((3072,), bf16)], {}),
        "flash_attention_prefill": (
            fa_ops.flash_attention,
            [((1, 2048, 24, 128), bf16), ((1, 2048, 8, 128), bf16),
             ((1, 2048, 8, 128), bf16)],
            dict(block_q=512, block_k=512)),
        "decode_attention_4096": (
            da_ops.decode_attention,
            [((8, 24, 128), bf16), ((8, 4096, 8, 128), bf16),
             ((8, 4096, 8, 128), bf16), ((), i32)],
            dict(block_t=512)),
    }


@pytest.mark.parametrize("case", sorted(_kernel_cases()))
def test_kernel_compiles_for_v5e(case, one_chip):
    fn, args, kw = _kernel_cases()[case]
    structs = [_sds(shape, dtype, one_chip) for shape, dtype in args]
    compiled = fn.lower(*structs, force="kernel", **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_full_width_prefill_fits_one_v5e(one_chip):
    """The engine's B=8 prefill of llama3.2-3b at published widths, all
    layers, with bf16 weights, as chip_smoke.py serves it."""
    cs = _chip_smoke()
    cfg = cs.get_config(cs.ENGINE_ARCH).scaled(param_dtype=jnp.bfloat16)
    model = Model(cfg)
    params = jax.tree.map(
        lambda s: _sds(s.shape, s.dtype, one_chip),
        jax.eval_shape(model.init, jax.random.key(0)))
    b, ii = max(cs.ENGINE_BATCHES), cs.PROMPT_LEN
    tokens = _sds((b, ii), jnp.int32, one_chip)
    prefill = jax.jit(lambda p, t: model.prefill(
        p, {"tokens": t}, max_len=ii + cs.NEW_TOKENS))
    compiled = prefill.lower(params, tokens).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < HBM_BYTES, used
