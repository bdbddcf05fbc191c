"""Run the system's main path once on the chip and check what comes out.

One chip (no arguments), in one process:

  1. device gate: the first device is a TPU, and the kernel wrappers
     dispatch to the Pallas kernels (never to interpret mode);
  2. serving engine: llama3.2-3b at its published widths, all 28 layers,
     weights drawn from a seed and held in bf16, serves batches of 1 and 8
     requests (1024 prompt tokens, 32 new tokens) through
     ``ServingEngine.generate``; decode through the KV cache is checked
     against prefill logits;
  3. ALA pipeline: fit -> explore(4 chains) -> fit_error -> estimate_batch
     on the in-house dataset, checked against the CPU run of the same seed.

Four chips (``--chips 4``) run only the sharded decode path: llama3.1-8b
at its published config decodes on a 1x4 (data, model) mesh, and the same
sharded step cut to 4 layers is checked against one-device decode.

Everything else goes to earlier lines; the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
A failed check, or a host without a TPU, exits non-zero without it.

  python chip_smoke.py [--chips 4]
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.bench.datasets import (make_inhouse_dataset,  # noqa: E402
                                  train_test_split)
from repro.configs import get_config  # noqa: E402
from repro.configs.shapes import ShapeSpec  # noqa: E402
from repro.core.ala import ALA, ALAConfig  # noqa: E402
from repro.core.annealing import SAConfig  # noqa: E402
from repro.distributed.sharding import ShardingPolicy, use_policy  # noqa: E402
from repro.inference.engine import ServingEngine  # noqa: E402
from repro.kernels import dispatch_mode  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.launch.steps import build_serve_step  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.models.transformer import Model  # noqa: E402

# -- serving engine ----------------------------------------------------------
ENGINE_ARCH = "llama3.2-3b"
ENGINE_BATCHES = (1, 8)
PROMPT_LEN = 1024
NEW_TOKENS = 32
CHECK_STEPS = 3
# Decode through the cache against prefill at the same position, as the
# relative RMS difference of the logits.  Served in bf16, the two paths
# round activations at different points (the cache write, other matmul
# shapes and fusions): 7.4e-3 after 28 layers on a TPU v5e.  A cache
# position one too far adds little to that (8.9e-3 in all), because with
# random weights and prompts attention is close to an average over the
# context.  So the bf16 bound catches only gross faults, and the check runs
# again in float32 at full matmul precision, where rounding leaves about
# 1e-5 and the position one too far must fail it.  Sharded against
# one-device decode (bf16 partial sums before the all-reduce) is held to
# the bf16 bound.
LOGIT_RTOL_BF16 = 5e-2
LOGIT_RTOL_F32 = 1e-3

# -- ALA pipeline -------------------------------------------------------------
ALA_SEED = 0
ALA_GBT = dict(n_estimators=40, learning_rate=0.2, max_depth=4)
ALA_SA = dict(n_iters=6, gbt_kw=dict(n_estimators=20, learning_rate=0.3,
                                     max_depth=3))
ALA_CHAINS = 4
# The CPU run of this phase (tests/test_chip_smoke.py holds it to these).
ALA_CPU_REFERENCE = {
    "median_ape": 6.514109837758854,
    "err": 6.503351243768666,
    "confidence": 0.9970938627246991,
}
# Relative bounds against the CPU run.  TPU and CPU round float32 exp and
# reductions differently, and the pipeline turns such noise into discrete
# split and acceptance decisions: CPU runs whose Alg 2 fits were perturbed
# by 1e-7 or 1e-6 relative (48 seeds) moved the median APE by up to 1.3e-4
# and the Alg 7 error by up to 2.5e-2, and never moved the confidence.  A
# TPU v5e ran 2.3e-4, 3.5e-3 and 1.5e-8 off.  Each bound is about four times
# the largest move seen; the confidence bound also catches the distance
# kernel at bf16 matmul precision (1.6e-3).
ALA_RTOL = {"median_ape": 1e-3, "err": 1e-1, "confidence": 1e-6}

# -- four chips ---------------------------------------------------------------
SHARDED_ARCH = "llama3.1-8b"
SHARDED_CHIPS = 4
SHARDED_BATCH = 8
SHARDED_CTX = 4096
SHARDED_STEPS = 4
SHARDED_CUT_LAYERS = 4


class SmokeFailure(RuntimeError):
    """A phase produced a result that fails its check."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(phase: str, **fields) -> None:
    print(phase, " ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


def _fmt(xs) -> str:
    return ",".join(f"{x:.3e}" for x in xs)


def rel_rms(x, ref) -> float:
    x = np.asarray(x, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.sqrt(np.mean((x - ref) ** 2) / np.mean(ref ** 2)))


# -- 1. device gate -----------------------------------------------------------
def device_gate(chips: int) -> dict:
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found {d.platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: needs {chips} chips, found "
                         f"{len(devs)}")
    mode = dispatch_mode()
    if mode != "kernel":
        raise SystemExit(f"chip_smoke: kernels dispatch to {mode!r}")
    dev = {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}
    log("device", **dev, kernel_dispatch=mode)
    return dev


# -- 2. serving engine --------------------------------------------------------
def engine_config() -> ModelConfig:
    log("engine.config", note="float32 weights need 19.77 GB of the chip's "
        "15.75 GB at B=8 prefill; weights are held in bf16")
    return get_config(ENGINE_ARCH).scaled(param_dtype=jnp.bfloat16)


def init_params(model: Model, seed: int):
    """Jitted init: the eager vmap would hold float32 copies of whole
    (layers, d_model, d_ff) stacks before the cast."""
    return jax.block_until_ready(jax.jit(model.init)(jax.random.key(seed)))


def decode_vs_prefill(model: Model, params, prompts, tokens, steps: int,
                      max_len: int):
    """Relative RMS difference, per decode step, between the logits of
    decode through the cache and those of prefill over prompt plus the
    tokens fed so far, at the same position.  Also returns the same
    difference for a decode whose cache position is one too far: what the
    check must tell apart from rounding."""
    vocab = model.cfg.vocab_size
    prefill = jax.jit(model.prefill, static_argnames="max_len")
    last_logits = jax.jit(lambda p, t: model.prefill(p, {"tokens": t})[0])
    decode = jax.jit(model.decode_step, donate_argnums=(1,))
    _, cache = prefill(params, {"tokens": jnp.asarray(prompts)},
                       max_len=max_len)
    shifted = jax.tree.map(jnp.copy, cache)._replace(pos=cache.pos + 1)
    errs, shifted_errs = [], []
    for j in range(steps):
        tok = jnp.asarray(tokens[:, j:j + 1])
        logits, cache = decode(params, cache, tok)
        wrong, shifted = decode(params, shifted, tok)
        seq = np.concatenate([prompts, tokens[:, :j + 1]], axis=1)
        ref = last_logits(params, jnp.asarray(seq))
        got = np.asarray(logits[:, 0, :vocab], np.float32)
        want = np.asarray(ref[:, 0, :vocab], np.float32)
        check(np.isfinite(got).all() and np.isfinite(want).all(),
              f"non-finite logits at decode step {j}")
        errs.append(rel_rms(got, want))
        shifted_errs.append(rel_rms(wrong[:, 0, :vocab], want))
    return errs, shifted_errs


def engine_phase(cfg: ModelConfig, batches=ENGINE_BATCHES,
                 prompt_len: int = PROMPT_LEN, new_tokens: int = NEW_TOKENS,
                 check_steps: int = CHECK_STEPS, seed: int = 0) -> dict:
    model = Model(cfg)
    t0 = time.perf_counter()
    params = init_params(model, seed)
    log("engine.init", arch=cfg.name, layers=cfg.n_layers,
        d_model=cfg.d_model, heads=f"{cfg.n_heads}/{cfg.n_kv_heads}",
        d_ff=cfg.d_ff, vocab=cfg.vocab_size,
        weight_bytes=sum(x.nbytes for x in jax.tree.leaves(params)),
        s=time.perf_counter() - t0)
    engine = ServingEngine(model, params)
    rng = np.random.default_rng(seed)
    out = {"batches": {}}
    for b in batches:
        prompts = rng.integers(0, cfg.vocab_size, (b, prompt_len),
                               dtype=np.int32)
        t0 = time.perf_counter()
        first = engine.generate(prompts, new_tokens)
        first_s = time.perf_counter() - t0
        again = engine.generate(prompts, new_tokens)
        toks = first.tokens
        check(toks.shape == (b, new_tokens), f"B={b}: tokens {toks.shape}")
        check(bool(((toks >= 0) & (toks < cfg.vocab_size)).all()),
              f"B={b}: token ids out of range")
        check(np.array_equal(toks, again.tokens),
              f"B={b}: greedy decoding differs between two calls")
        log("engine.generate", B=b, ii=prompt_len, oo=new_tokens,
            first_call_s=first_s, prefill_s=again.prefill_s,
            decode_s=again.decode_s, tok_s=again.tokens_per_s)
        out["batches"][b] = dict(first_call_s=first_s,
                                 prefill_s=again.prefill_s,
                                 decode_s=again.decode_s,
                                 tok_s=again.tokens_per_s)
    # decode against prefill on the last batch served
    t0 = time.perf_counter()
    errs, shifted = decode_vs_prefill(model, params, prompts, toks,
                                      check_steps, prompt_len + new_tokens)
    log("engine.decode_vs_prefill", dtype="bf16", B=b, steps=check_steps,
        rel_rms=_fmt(errs), shifted_by_one=_fmt(shifted),
        tol=LOGIT_RTOL_BF16, s=time.perf_counter() - t0)
    check(max(errs) <= LOGIT_RTOL_BF16,
          f"bf16 decode logits differ from prefill: {errs}")
    # one row of the same batch: float32 activations need twice the memory
    t0 = time.perf_counter()
    f32 = Model(cfg.scaled(compute_dtype=jnp.float32))
    with jax.default_matmul_precision("highest"):
        errs32, shifted32 = decode_vs_prefill(
            f32, params, prompts[:1], toks[:1], check_steps,
            prompt_len + new_tokens)
    log("engine.decode_vs_prefill", dtype="f32", B=1, steps=check_steps,
        rel_rms=_fmt(errs32), shifted_by_one=_fmt(shifted32),
        tol=LOGIT_RTOL_F32, s=time.perf_counter() - t0)
    check(max(errs32) <= LOGIT_RTOL_F32,
          f"float32 decode logits differ from prefill: {errs32}")
    check(min(shifted32) > LOGIT_RTOL_F32,
          f"the check cannot see a position one too far: {shifted32}")
    out.update(decode_vs_prefill=errs, shifted_by_one=shifted,
               decode_vs_prefill_f32=errs32, shifted_by_one_f32=shifted32)
    stats = jax.devices()[0].memory_stats() or {}
    out["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    log("engine.memory", peak_bytes_in_use=out["peak_bytes_in_use"],
        bytes_limit=stats.get("bytes_limit"))
    return out


# -- 3. ALA pipeline ----------------------------------------------------------
def ala_phase(seed: int = ALA_SEED) -> dict:
    t0 = time.perf_counter()
    ds = make_inhouse_dataset(seed=seed)
    train, test = train_test_split(ds, test_frac=0.3, seed=seed)
    ala = ALA(ALAConfig(gbt_kw=dict(ALA_GBT), sa=SAConfig(**ALA_SA)))
    ala.fit(*train.workload)
    median_ape = ala.score(*test.workload)
    ala.explore(test.workload, n_chains=ALA_CHAINS)
    ala.fit_error()
    err, d_min, conf = ala.estimate_batch([test.workload])
    out = {"median_ape": float(median_ape), "err": float(err[0]),
           "confidence": float(conf[0])}
    check(all(np.isfinite(v) for v in out.values()),
          f"non-finite ALA result {out}")
    log("ala", rows=len(ds), subsets=len(ala.sa_log.subsets),
        d_min=float(d_min[0]), s=time.perf_counter() - t0,
        **{k: repr(v) for k, v in out.items()},
        **{k: round(v, 3) for k, v in ala.timings.items()})
    return out


def ala_matches_reference(out: dict, rtol: dict = ALA_RTOL) -> None:
    for k, want in ALA_CPU_REFERENCE.items():
        got = out[k]
        log("ala.parity", metric=k, chip=repr(got), cpu=repr(want),
            rel=abs(got - want) / abs(want), tol=rtol[k])
        check(abs(got - want) <= rtol[k] * abs(want),
              f"ALA {k}: {got} differs from the CPU run's {want}")


# -- 4. four chips ------------------------------------------------------------
def random_cache(model: Model, batch: int, ctx: int, filled: int, key):
    """A decode cache ``ctx`` deep whose first ``filled`` positions hold
    random K/V, as if a prompt had been prefilled."""
    cache = model.init_cache(batch, ctx, filled=filled)
    leaves, treedef = jax.tree.flatten(cache.blocks)
    keys = jax.random.split(key, len(leaves))
    blocks = treedef.unflatten([jax.random.normal(k, x.shape, x.dtype)
                                for k, x in zip(keys, leaves)])
    return cache._replace(blocks=blocks)


def sharded_decode(cfg: ModelConfig, policy: ShardingPolicy, batch: int,
                   ctx: int, steps: int, seed: int):
    """``steps`` greedy decode steps of ``build_serve_step`` on the
    policy's mesh.  Returns (per-step logits, tokens fed)."""
    model = Model(cfg)
    shape = ShapeSpec("smoke_decode", seq_len=ctx, global_batch=batch,
                      kind="decode")
    step, in_sh, out_sh, _ = build_serve_step(model, policy, shape)
    p_sh, c_sh, t_sh = in_sh
    params = jax.jit(model.init, out_shardings=p_sh)(jax.random.key(seed))
    cache = jax.jit(lambda k: random_cache(model, batch, ctx, ctx - steps, k),
                    out_shardings=c_sh)(jax.random.key(seed + 1))
    with use_policy(policy):
        serve = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                        donate_argnums=(1,))
        return _decode_loop(serve, params, cache, cfg.vocab_size, batch,
                            steps, None, lambda t: jax.device_put(t, t_sh))


def local_decode(cfg: ModelConfig, batch: int, ctx: int, steps: int,
                 seed: int, tokens):
    """The same steps through one-device ``Model.decode_step``, fed
    ``tokens`` (batch, steps)."""
    model = Model(cfg)
    params = jax.jit(model.init)(jax.random.key(seed))
    cache = jax.jit(lambda k: random_cache(model, batch, ctx, ctx - steps,
                                           k))(jax.random.key(seed + 1))
    decode = jax.jit(model.decode_step, donate_argnums=(1,))
    return _decode_loop(decode, params, cache, cfg.vocab_size, batch, steps,
                        tokens, jnp.asarray)


def _decode_loop(step, params, cache, vocab, batch, steps, tokens, place):
    """Feeds ``tokens`` when given, else greedy tokens after a first 0."""
    tok = np.zeros((batch, 1), np.int32)
    fed, logits = [], []
    for j in range(steps):
        if tokens is not None:
            tok = tokens[:, j:j + 1]
        fed.append(tok)
        lg, cache = step(params, cache, place(tok))
        lg = np.asarray(lg[:, 0, :vocab], np.float32)
        check(np.isfinite(lg).all(), f"non-finite logits at step {j}")
        logits.append(lg)
        tok = lg.argmax(axis=-1)[:, None].astype(np.int32)
    return logits, np.concatenate(fed, axis=1)


def sharded_phase(full: ModelConfig, cut: ModelConfig,
                  batch: int = SHARDED_BATCH, ctx: int = SHARDED_CTX,
                  steps: int = SHARDED_STEPS, seed: int = 0) -> dict:
    mesh = make_host_mesh(model=len(jax.devices()))
    policy = ShardingPolicy(mesh, data_axes=("data",), serving=True,
                            serving_2d=False)
    log("sharded.mesh", shape=dict(mesh.shape), arch=full.name)
    t0 = time.perf_counter()
    logits, _ = sharded_decode(full, policy, batch, ctx, steps, seed)
    log("sharded.full", layers=full.n_layers, d_model=full.d_model,
        B=batch, ctx=ctx, steps=steps, s=time.perf_counter() - t0)
    t0 = time.perf_counter()
    sh_logits, fed = sharded_decode(cut, policy, batch, ctx, steps, seed)
    loc_logits, _ = local_decode(cut, batch, ctx, steps, seed, fed)
    errs = [rel_rms(s, l) for s, l in zip(sh_logits, loc_logits)]
    log("sharded.vs_local", layers=cut.n_layers, B=batch, ctx=ctx,
        rel_rms=_fmt(errs), tol=LOGIT_RTOL_BF16, s=time.perf_counter() - t0)
    check(max(errs) <= LOGIT_RTOL_BF16,
          f"sharded logits differ from one-device decode: {errs}")
    stats = jax.devices()[0].memory_stats() or {}
    log("sharded.memory", peak_bytes_in_use=stats.get("peak_bytes_in_use"))
    return {"vs_local": errs}


def sharded_configs():
    full = get_config(SHARDED_ARCH).scaled(param_dtype=jnp.bfloat16)
    return full, full.scaled(n_layers=SHARDED_CUT_LAYERS)


# -- entry point --------------------------------------------------------------
def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--chips", type=int, choices=(1, SHARDED_CHIPS), default=1,
                   help="4: run only the sharded decode path")
    args = p.parse_args(argv)
    dev = device_gate(args.chips)
    log("compile_cache", dir=enable_compile_cache())
    hits = {"hits": 0, "misses": 0}

    def on_event(event: str, **kw) -> None:
        for k in hits:
            if event == f"/jax/compilation_cache/cache_{k}":
                hits[k] += 1

    jax.monitoring.register_event_listener(on_event)
    t0 = time.perf_counter()
    if args.chips == SHARDED_CHIPS:
        sharded_phase(*sharded_configs())
    else:
        engine_phase(engine_config())
        ala_matches_reference(ala_phase())
    log("total", s=time.perf_counter() - t0, cache_hits=hits["hits"],
        cache_misses=hits["misses"])
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
