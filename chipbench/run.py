"""Chip benchmark: offline batched generation through ``ServingEngine.generate``.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the cell
asks for.  One process per run:

  1. device gate: the first device is a TPU, and there are enough of them;
     anything else exits non-zero with no result;
  2. JAX's persistent compilation cache at a fixed path in the checkout
     (``JAX_COMPILATION_CACHE_DIR`` where set);
  3. weights drawn on the device from the seed in one jitted call, in the
     dtype the configuration serves them in;
  4. warm-up: one batch of each prompt length of the cell's traffic, which
     compiles prefill, the first-token sample and the decode scan;
  5. the window: batches drawn from the traffic file and the seed, handed to
     ``generate`` back to back (closed loop) for ``--seconds`` and to the end
     of the mix's last round; with ``--trace 1`` under the profiler;
  6. the peak of device memory; with ``--trace 1`` the trace reduced to
     device time per batch (``chipbench/trace_reduce.py``) and, over the
     programs compiled again after the window, split by layer scope and
     engine phase (``chipbench/scopes.py``); the metrics (each read by its
     own file, ``chipbench/metrics/<name>.py``), then the comparison that
     decides ``correct`` (``chipbench/check.py``), outside set-up and window;
  7. the result: its checks on the last lines of standard error, and one
     JSON line last on standard output.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file of its own under ``chipbench/``, found by the
names in ``BENCHMARK.json``.  A configuration file names three modules by
``program``, ``reference`` and ``counts``; a new model family joins by new
modules under those names, which provide:

  ``chipbench/programs/<name>.py``, the program under test:
    ``model_config(conf) -> ModelConfig`` of ``repro.models``;
    ``embedding_rows(cfg) -> int``, the embedding rows the program holds;
    ``program_params(model, w) -> params``, the program's parameter tree
    over the reference's weights ``w``, raising where the layouts differ;
    ``embed_bytes(params) -> int``, the embedding table's bytes;
    ``SMALL``, the configuration keys and values of the CPU tests' size;
    ``SCOPES``, the layer scopes its compiled programs carry, each a name
    that ``repro.models.layers.SCOPES`` declares; a traced run splits the
    device time by these (``chipbench/scopes.py``);
  ``chipbench/reference/<name>.py``, the plain reference:
    ``Spec``, a hashable dataclass with at least ``vocab``;
    ``spec_from_config(conf, rows) -> Spec``;
    ``init_weights(spec, key, dtype=bfloat16) -> w``, jittable;
    ``gaps(spec, w, tokens, served, start) -> (best, at)``, jitted with
    ``spec`` and ``start`` static, and ``control_gaps(spec, w, tokens,
    served, start, kind)`` likewise (``chipbench/check.py``);
    ``logits(spec, w, tokens, dtype) -> (T, vocab)``, the full forward
    pass over one sequence;
    ``uncounted_params(spec) -> int``, the parameters of ``w`` that
    ``ModelConfig.param_count()`` leaves out;
  ``chipbench/counts/<name>.py``, what a call needs, from the shapes:
    ``prefill_flops(spec, batch, prompt_len)``;
    ``decode_flops(spec, batch, start, steps)`` for the steps writing
    positions ``start .. start + steps - 1``;
    ``decode_bytes(spec, batch, start, steps, weight_bytes, embed_bytes)``.

A reader sees each batch of the window as ``rec["batches"][i]``, a
``stats.BatchRecord``; its ``counters`` hold the numbers that the
program's ``generate`` reported for that call in its result's
``counters``, if any.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from chipbench import (check, gate, generator, scopes, stats,  # noqa: E402
                       trace_reduce)

BENCH_DIR = ROOT / "chipbench"
CACHE_DIR = ROOT / ".jax_cache"


# -- the cell -----------------------------------------------------------------
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    conf: dict               # the configuration file
    mix: generator.Mix
    params: dict             # chipbench/cells/<name>.json
    end_to_end: list         # entries of BENCHMARK.json that this cell reports
    per_layer: list


def load_cell(name: str, root: pathlib.Path = None) -> Cell:
    root = root or ROOT
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; the cells are "
                         f"{sorted(cells)}")
    w = cells[name]
    conf_file = {c["name"]: c["file"] for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name])
                 and m["moves"] in reported]
    return Cell(name=name, chips=int(w["chips"]),
                conf=json.loads((root / conf_file).read_text()),
                mix=generator.load_mix(w["traffic"],
                                       root / "chipbench" / "traffic"),
                params=json.loads((root / "chipbench" / "cells"
                                   / f"{name}.json").read_text()),
                end_to_end=e2e, per_layer=per_layer)


def part(kind: str, name: str):
    """``chipbench/<kind>/<name>.py``: a reference, count, program or
    metric module named by a configuration file or BENCHMARK.json."""
    return importlib.import_module(f"chipbench.{kind}.{name}")


def seed_key(seed: int):
    """A PRNG key that depends on all of ``seed``: ``jax.random.key``
    keeps only its low 32 bits."""
    import jax
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


# -- set-up -------------------------------------------------------------------
def enable_compile_cache() -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


@dataclasses.dataclass
class System:
    """The program under test, over the benchmark's weights."""
    engine: object
    vocab: int
    weight_bytes: int
    embed_bytes: int


def build(cell: Cell, seed: int):
    """Weights from the seed, and the engine over them.  Returns (spec,
    weights, system)."""
    import jax
    from repro.inference.engine import ServingEngine
    from repro.models.transformer import Model
    prog = part("programs", cell.conf["program"])
    ref = part("reference", cell.conf["reference"])
    cfg = prog.model_config(cell.conf)
    spec = ref.spec_from_config(cell.conf, prog.embedding_rows(cfg))
    weights = jax.jit(ref.init_weights, static_argnums=0)(spec, seed_key(seed))
    weights = jax.block_until_ready(weights)
    model = Model(cfg)
    params = prog.program_params(model, weights)
    system = System(
        engine=ServingEngine(model, params), vocab=cfg.vocab_size,
        weight_bytes=sum(x.nbytes for x in jax.tree.leaves(params)),
        embed_bytes=prog.embed_bytes(params))
    return spec, weights, system


def warm_up(system: System, batches: generator.Batches) -> None:
    """One batch of each prompt length: compiles every program the window
    runs (prefill per length, the first-token sample, the decode scan per
    cache depth)."""
    import numpy as np
    for ii in sorted(set(batches.mix.prompt_lens)):
        rng = np.random.default_rng([batches.seed, 2, ii])
        prompts = rng.integers(0, batches.vocab, (batches.size(ii), ii),
                               dtype=np.int32)
        system.engine.generate(prompts, batches.mix.output_len)


# -- the window ----------------------------------------------------------------
def run_window(system: System, batches: generator.Batches, seconds: float,
               annotate=None):
    """Closed loop: the next batch is handed over when the last returns,
    until ``seconds`` have passed and the last round of the mix is whole,
    so that every window serves its buckets in the same ratio.  Returns
    (records, served)."""
    records, served = [], []
    oo = batches.mix.output_len
    t_start = time.perf_counter()
    i = 0
    while True:
        ii, prompts = batches.batch(i)
        t0 = time.perf_counter()
        if annotate is None:
            res = system.engine.generate(prompts, oo)
        else:
            with annotate(f"generate ii={ii}"):
                res = system.engine.generate(prompts, oo)
        t1 = time.perf_counter()
        records.append(stats.BatchRecord(
            batch=prompts.shape[0], prompt_len=ii, output_len=oo,
            start_s=t0 - t_start, end_s=t1 - t_start,
            prefill_s=res.prefill_s, decode_s=res.decode_s,
            counters=dict(getattr(res, "counters", {}))))
        served.append(check.Served(prompts=prompts, tokens=res.tokens))
        i += 1
        if t1 - t_start >= seconds and i % batches.round_len == 0:
            return records, served


def traced_window(system, batches, seconds):
    """The window under the profiler.  Returns (records, served, the
    trace's planes)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    tmp = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        with jax.profiler.trace(tmp, profiler_options=opts):
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                records, served = run_window(
                    system, batches, seconds,
                    annotate=jax.profiler.TraceAnnotation)
        planes = trace_reduce.load_planes(tmp)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return records, served, planes


# -- the comparison -------------------------------------------------------------
def compare(cell: Cell, spec, weights, served, seed: int,
            control: str = None) -> dict:
    """The widest gap over the seed's sample of served requests.  With
    ``control`` (``int8``, ``fp8``), the plain reference computed at that
    precision is put in the program's place: at each position of the same
    prompts and served tokens, the gap of the token that it puts first."""
    ref = part("reference", cell.conf["reference"])
    picks = check.sample_requests(served, cell.params["check_requests"], seed)
    if control is None:
        gaps = lambda seq, toks, start: ref.gaps(spec, weights, seq, toks,
                                                 start)
    else:
        gaps = lambda seq, toks, start: ref.control_gaps(
            spec, weights, seq, toks, start, control)
    return check.widest_gap(served, picks, gaps)


# -- one run ----------------------------------------------------------------------
@dataclasses.dataclass
class Measured:
    spec: object
    weights: dict
    records: list
    served: list
    metrics: dict
    device: dict
    setup_s: float
    compiles_in_window: int
    per_layer_unread: list
    trace: dict = None       # the reduced trace of a traced run


def measure(cell: Cell, seed: int, seconds: float, trace: bool,
            device: dict, compiles, t_begin: float) -> Measured:
    """Set-up, the window, the memory peak and the metrics: everything of
    a run but the comparison.  The program's state is freed on return; the
    weights stay for the reference."""
    spec, weights, system = build(cell, seed)
    batches = generator.Batches(cell.mix, cell.params["batch_tokens"],
                                system.vocab, seed)
    warm_up(system, batches)
    setup_s = time.perf_counter() - t_begin
    before = compiles.compiles
    if trace:
        records, served, planes = traced_window(system, batches, seconds)
    else:
        records, served = run_window(system, batches, seconds)
    in_window = compiles.compiles - before
    device = dict(device, memory_peak_bytes=gate.memory_peak_bytes(cell.chips))
    unread, reduced = [], None
    if trace:
        reduced = trace_reduce.reduce_planes(planes, cell.chips)
        names = part("programs", cell.conf["program"]).SCOPES
        reduced["scopes"] = scopes.reduce_scopes(
            planes, scopes.program_maps(system.engine, records, names))
        metrics = read_metrics(cell.per_layer, metric_record(
            cell, spec, system, records, reduced, device["kind"]))
        unread = [m["name"] for m in cell.per_layer
                  if m["name"] not in metrics]
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        device["breakdown"] = reduced["breakdown"]
    else:
        metrics = read_metrics(cell.end_to_end,
                               {"batches": records, "setup_s": setup_s})
    return Measured(spec=spec, weights=weights, records=records,
                    served=served, metrics=metrics, device=device,
                    setup_s=setup_s, compiles_in_window=in_window,
                    per_layer_unread=unread, trace=reduced)


def judge(cell: Cell, m: Measured, seed: int, control: str = None):
    """The numbers compared, each with its limit, and what the reference
    saw.  Returns (checks, found)."""
    found = compare(cell, m.spec, m.weights, m.served, seed, control)
    checks = {
        "widest_gap": (found["widest_gap"],
                       cell.params["limits"]["widest_gap"]),
        "tokens_out_of_range": (
            check.tokens_out_of_range(m.served, m.spec.vocab), 0),
        "compiles_in_window": (m.compiles_in_window, 0),
        "span_excess_s": (max(stats.span_excess_s(m.records), 0.0), 0.0),
    }
    if m.device.get("window_s") is not None:
        checks["per_layer_unread"] = (len(m.per_layer_unread), 0)
    return checks, found


def read_metrics(entries, record: dict) -> dict:
    """Each entry's reader (``chipbench/metrics/<name>.py``) over the
    record; a reader that finds nothing to read returns None, and its
    metric is left out."""
    out = {}
    for m in entries:
        value = part("metrics", m["name"]).read(record)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def metric_record(cell, spec, system, records, reduced, device_kind) -> dict:
    """What the per-layer readers read: the traced batches with the device
    time of their programs, the counts and peaks for this configuration."""
    peaks = json.loads((BENCH_DIR / "peaks.json").read_text())
    if device_kind not in peaks:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"chipbench/peaks.json")
    return {"batches": records, "programs": reduced["batches"],
            "trace": reduced, "spec": spec,
            "counts": part("counts", cell.conf["counts"]),
            "peaks": peaks[device_kind], "weight_bytes": system.weight_bytes,
            "embed_bytes": system.embed_bytes}


def report_checks(checks: dict) -> None:
    for k, (value, limit) in checks.items():
        print(f"check {k}: {value!r} (limit {limit!r})", file=sys.stderr)


def main(argv=None, root: pathlib.Path = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", choices=("int8", "fp8"), default=None,
                   help="put the plain reference at this precision in the "
                        "program's place for the comparison (the control, "
                        "which has to come out not correct); the "
                        "benchmark's own runs never pass it")
    args = p.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("--seed must be a whole number >= 0")
    cell = load_cell(args.workload, root)

    try:
        device = gate.device_gate(cell.chips)
    except gate.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    enable_compile_cache()
    compiles = gate.CompileCounter()
    m = measure(cell, args.seed, args.seconds, bool(args.trace), device,
                compiles, T_PROCESS)
    checks, found = judge(cell, m, args.seed, args.control)
    correct = all(v <= lim for v, lim in checks.values())
    report_checks(checks)
    device = dict(m.device)
    breakdown = device.pop("breakdown", None)
    line = {"correct": correct, "attempted": sum(r.batch for r in m.records),
            "failed": 0, "metrics": m.metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["setup"] = {"setup_s": m.setup_s, "batches": len(m.records),
                     "tokens_compared": found["tokens_compared"],
                     "per_layer_unread": m.per_layer_unread,
                     "control": args.control,
                     "cache_hits": compiles.cache_hits,
                     "cache_misses": compiles.cache_misses}
    if m.trace is not None:
        line["setup"]["device_lag_s"] = m.trace["device_lag_s"]
        line["setup"]["split"] = scopes.summary(
            m.records, m.trace["batches"], m.trace["scopes"])
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in checks.items()}
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
