import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: the jit'd
step for the production mesh must partition, compile, and report its
memory/cost analysis.  Results accumulate in ``results/dryrun/*.json`` so
the sweep is resumable (one process per cell via --arch/--shape flags, or
an in-process sweep with --all).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-3b \
      --shape train_4k [--multi-pod] [--collectives]
  PYTHONPATH=src python -m repro.launch.dryrun --all
"""
import argparse
import json
import pathlib
import re
import time
import traceback

import jax

from repro.configs import ARCHS, get_config
from repro.configs.shapes import SHAPES, cell_applicable, get_shape
from repro.distributed.sharding import ShardingPolicy, use_policy
from repro.launch.mesh import data_axes_of, make_production_mesh
from repro.launch.steps import build_step
from repro.models.transformer import Model

RESULTS = pathlib.Path(__file__).resolve().parents[3] / "results" / "dryrun"

# Match only lines whose RHS *op* is a collective: `%x = <shape> <op>(...)`.
# Fusions that merely consume a collective's result must not count.
_COLL_RE = re.compile(
    r"=\s*(\([^=]*?\)|\S+)\s+(all-gather|all-reduce|reduce-scatter|"
    r"all-to-all|collective-permute)(-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}


def _shape_bytes(text: str) -> int:
    """Sum byte sizes of all array shapes in an HLO result-type string."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_stats(hlo_text: str) -> dict:
    """Per-op-kind result-shape bytes for every collective in the HLO.

    Async ``-start`` ops return a (operand, dest) tuple; only the dest
    buffer counts.  Ops inside while bodies are counted once (see roofline
    extrapolation in repro.analysis.roofline for trip-count scaling).
    """
    stats: dict = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if m is None or "-done(" in line:
            continue
        result, kind, is_start = m.group(1), m.group(2), m.group(3)
        if is_start and result.startswith("("):
            # tuple result: count only the destination (last) shape
            shapes = _SHAPE_RE.findall(result)
            if shapes:
                dt, dims = shapes[-1]
                result = f"{dt}[{dims}]"
        nbytes = _shape_bytes(result)
        e = stats.setdefault(kind, {"count": 0, "bytes": 0})
        e["count"] += 1
        e["bytes"] += nbytes
    return stats


def run_cell(arch: str, shape_name: str, multi_pod: bool = False,
             collectives: bool = True, unroll_periods: int = 0,
             save: bool = True, policy_mode: str = "auto") -> dict:
    """Lower+compile one cell; returns the result record.

    ``policy_mode``: "auto" applies the hillclimbed sharding policy
    (TP-only serving weights when they fit, context-parallel serving for
    non-divisible head counts, shard_map EP MoE); "baseline" pins the
    paper-faithful pre-hillclimb policy for §Perf A/B records."""
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    ok, reason = cell_applicable(cfg, shape)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "unroll_periods": unroll_periods, "policy": policy_mode}
    if not ok:
        rec.update(status="skipped", reason=reason)
        _save(rec, save)
        return rec

    t0 = time.time()
    try:
        import jax.numpy as jnp
        mesh = make_production_mesh(multi_pod=multi_pod)
        serving = shape.kind != "train"
        tp = mesh.shape["model"]
        hbm_budget = 11e9
        # hillclimb #1: TP-only weights whenever they fit per-chip HBM —
        # 2D (data x model) weight sharding costs a full weight all-gather
        # per step and is reserved for models too big for TP alone.
        serving_2d = cfg.param_count() * 2 / tp > hbm_budget
        # hillclimb #2: context-parallel serving for archs whose head
        # count doesn't divide the TP width (replicate block weights over
        # model, shard the sequence end-to-end) — only when the replicated
        # weights actually fit alongside activations.
        cp = (serving and not cfg.attention_free
              and cfg.n_heads % tp != 0
              and cfg.param_count() * 2 <= 0.6 * hbm_budget)
        if policy_mode == "baseline":
            policy = ShardingPolicy(mesh, data_axes=data_axes_of(mesh),
                                    serving=serving, serving_2d=True,
                                    cp_replicate_weights=False,
                                    ep_moe=False)
        else:
            policy = ShardingPolicy(mesh, data_axes=data_axes_of(mesh),
                                    serving=serving, serving_2d=serving_2d,
                                    cp_replicate_weights=cp)
        if serving:
            # inference holds bf16 weights, sharded across the full slice
            cfg = cfg.scaled(param_dtype=jnp.bfloat16)
        if unroll_periods:
            overrides = {"n_layers": len(cfg.period) * unroll_periods}
            if cfg.is_encdec:
                overrides["n_encoder_layers"] = unroll_periods
            cfg = cfg.scaled(**overrides)
            model = Model(cfg, unroll=True)
        else:
            model = Model(cfg, remat=(shape.kind == "train"))
        step, in_sh, out_sh, args = build_step(model, policy, shape)
        donate = {"train": (0, 1), "decode": (1,), "prefill": ()}[shape.kind]
        with use_policy(policy):
            jitted = jax.jit(step, in_shardings=in_sh,
                             out_shardings=out_sh,
                             donate_argnums=donate)
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis() or {}
        rec.update(
            status="ok",
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            flops=float(cost.get("flops", -1.0)),
            bytes_accessed=float(cost.get("bytes accessed", -1.0)),
            memory={
                k: int(getattr(mem, k))
                for k in ("argument_size_in_bytes",
                          "output_size_in_bytes",
                          "temp_size_in_bytes",
                          "generated_code_size_in_bytes")
                if hasattr(mem, k)},
        )
        if collectives:
            rec["collectives"] = collective_stats(compiled.as_text())
        print(f"[dryrun] OK {arch} {shape_name} mesh={rec['mesh']} "
              f"lower={t_lower:.1f}s compile={t_compile:.1f}s "
              f"flops={rec['flops']:.3g}")
        if shape.kind != "skipped":
            print("  memory:", rec["memory"])
    except Exception as e:  # noqa: BLE001 — record the failure
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
        print(f"[dryrun] FAIL {arch} {shape_name}: {rec['error']}")
    _save(rec, save)
    return rec


def _save(rec: dict, save: bool):
    if not save:
        return
    RESULTS.mkdir(parents=True, exist_ok=True)
    tag = "u%d" % rec["unroll_periods"] if rec.get("unroll_periods") else ""
    if rec.get("policy") == "baseline":
        tag += "__pbase"
    name = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}{tag}.json"
    (RESULTS / name.replace("/", "_")).write_text(json.dumps(rec, indent=1))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCHS))
    ap.add_argument("--shape", choices=[s.name for s in SHAPES])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--unroll-periods", type=int, default=0,
                    help="compile an unrolled depth-N variant (roofline)")
    ap.add_argument("--no-collectives", action="store_true")
    ap.add_argument("--roofline", action="store_true",
                    help="compile u1+u2 unrolled variants for every "
                         "applicable single-pod cell")
    ap.add_argument("--policy", choices=("auto", "baseline"),
                    default="auto")
    args = ap.parse_args()

    if args.roofline:
        n_fail = 0
        for arch in ARCHS:
            for shape in SHAPES:
                for u in (1, 2):
                    rec = run_cell(arch, shape.name, multi_pod=False,
                                   collectives=True, unroll_periods=u,
                                   policy_mode=args.policy)
                    n_fail += rec["status"] == "error"
        print(f"[dryrun] roofline sweep done fail={n_fail}")
        raise SystemExit(1 if n_fail else 0)

    if args.all:
        n_ok = n_skip = n_fail = 0
        for multi_pod in (False, True):
            for arch in ARCHS:
                for shape in SHAPES:
                    rec = run_cell(arch, shape.name, multi_pod=multi_pod,
                                   collectives=not args.no_collectives,
                                   policy_mode=args.policy)
                    n_ok += rec["status"] == "ok"
                    n_skip += rec["status"] == "skipped"
                    n_fail += rec["status"] == "error"
        print(f"[dryrun] sweep done ok={n_ok} skip={n_skip} fail={n_fail}")
        raise SystemExit(1 if n_fail else 0)

    assert args.arch and args.shape, "--arch/--shape or --all"
    rec = run_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                   collectives=not args.no_collectives,
                   unroll_periods=args.unroll_periods,
                   policy_mode=args.policy)
    raise SystemExit(0 if rec["status"] in ("ok", "skipped") else 1)


if __name__ == "__main__":
    main()
