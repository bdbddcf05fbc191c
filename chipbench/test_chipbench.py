"""CPU tests of the chip benchmark (``chipbench/``).

Everything here runs without a chip: the traffic generator, the metric
arithmetic, the counts, the plain reference against the program at a small
size, the device gate, the trace reduction, the cells' files, whole runs of
the harness at a tiny size with the timed path broken underneath, and a
compile of each cell's largest programs for a described TPU v5e.

The TPU topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and pytest workers
import every test file.
"""
from __future__ import annotations

import json
import math
import pathlib
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import (check, gate, generator, run, scopes, stats,
                       trace_reduce)
from chipbench.counts import dense_gqa as dense_counts
from chipbench.programs import dense_gqa as dense_prog
from chipbench.reference import dense_gqa as dense_ref
from repro.models.transformer import Model

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = sorted(c["name"] for c in BENCH["configs"])
CELLS = [w["name"] for w in BENCH["workloads"]]
HBM_BYTES = int(15.75 * 2 ** 30)     # what XLA lets one v5e program use
BIG_SEED = 2 ** 31 + 12345


def conf(name: str) -> dict:
    return json.loads((ROOT / "chipbench" / "configs"
                       / f"{name}.json").read_text())


def triple(c: dict):
    """The configuration's own program, reference and counts modules."""
    return (run.part("programs", c["program"]),
            run.part("reference", c["reference"]),
            run.part("counts", c["counts"]))


def small(name: str, **sizes) -> dict:
    """The configuration at a CPU test's size (its program's ``SMALL``),
    every flag as published."""
    c = conf(name)
    c["config"].update(triple(c)[0].SMALL, **sizes)
    return c


# -- traffic ------------------------------------------------------------------
def test_traffic_is_deterministic_per_seed():
    mix = generator.load_mix("chat")
    a = generator.Batches(mix, 4 * mix.longest, 1000, BIG_SEED)
    b = generator.Batches(mix, 4 * mix.longest, 1000, BIG_SEED)
    c = generator.Batches(mix, 4 * mix.longest, 1000, BIG_SEED + 1)
    for i in (5, 0, 3):                       # any order gives the same batch
        ia, pa = a.batch(i)
        ib, pb = b.batch(i)
        assert ia == ib and np.array_equal(pa, pb)
        assert pa.shape == (4 * mix.longest // ia, ia)
        assert pa.dtype == np.int32
        assert 0 <= pa.min() and pa.max() < 1000
    assert not np.array_equal(a.batch(0)[1], c.batch(0)[1])


def test_every_seed_sends_the_same_sizes_in_rounds():
    mix = generator.Mix(prompt_lens=(8, 16, 32), weights=(1, 2, 1),
                        output_len=4)
    for seed in (0, 7, BIG_SEED):
        lens = [generator.Batches(mix, 64, 50, seed).prompt_len(i)
                for i in range(40)]
        for r in range(10):
            assert sorted(lens[4 * r:4 * r + 4]) == [8, 16, 16, 32]


def test_mix_files_load():
    for path in (ROOT / "chipbench" / "traffic").glob("*.json"):
        mix = generator.load_mix(path.stem)
        assert mix.output_len >= 2 and mix.longest in mix.prompt_lens


def test_batch_follows_the_token_budget():
    mix = generator.Mix(prompt_lens=(8, 16, 32), weights=(1, 2, 1),
                        output_len=4)
    b = generator.Batches(mix, 96, 50, 0)
    assert [b.size(ii) for ii in (8, 16, 32)] == [12, 6, 3]
    with pytest.raises(ValueError):
        generator.Batches(mix, 31, 50, 0)


# -- metric arithmetic -----------------------------------------------------------
def _records():
    # two batches of 4 requests, 10 tokens out; times in seconds
    return [stats.BatchRecord(batch=4, prompt_len=8, output_len=10,
                              start_s=0.0, end_s=1.0, prefill_s=0.1,
                              decode_s=0.81),
            stats.BatchRecord(batch=4, prompt_len=16, output_len=10,
                              start_s=1.0, end_s=2.5, prefill_s=0.3,
                              decode_s=1.08)]


def test_end_to_end_arithmetic():
    r = _records()
    assert stats.output_tok_s(r) == pytest.approx(80 / 2.5)
    rec = {"batches": r, "setup_s": 12.5}
    assert run.part("metrics", "output_tok_s").read(rec) == \
        pytest.approx(80 / 2.5)
    assert run.part("metrics", "setup_s").read(rec) == 12.5


def test_program_span_arithmetic():
    r = _records()
    assert stats.itl_ms(r) == pytest.approx(1e3 * 1.89 / 18)
    assert stats.span_excess_s(r) == pytest.approx(-0.09)
    bad = [stats.BatchRecord(4, 8, 10, 0.0, 1.0, 0.5, 0.7)]
    assert stats.span_excess_s(bad) == pytest.approx(0.2)


# -- counts --------------------------------------------------------------------------
def _tiny_spec(tied=False):
    return dense_ref.Spec(layers=2, d_model=4, n_heads=2, n_kv=1, d_head=2,
                          d_ff=8, vocab=10, rows=16, theta=1e4, eps=1e-6,
                          qkv_bias=False, qk_norm=False, tied=tied)


def test_counts_against_hand_worked_totals():
    s = _tiny_spec()
    # per token and layer: qkv 2*4*(2+2)*2=64, o 2*2*2*4=32, ffn 6*4*8=192
    per_tok = 64 + 32 + 192
    # prefill of 3 tokens, batch 2: causal pairs 6, attention 4*2*2*6=96
    want = 2 * (2 * (3 * per_tok + 96) + 2 * 4 * 10)
    assert dense_counts.prefill_flops(s, 2, 3) == want
    # decode steps writing positions 3 and 4: they attend to 4 and 5
    want = 2 * (2 * (2 * per_tok + 2 * 4 * 10) + 2 * (4 * 2 * 2) * (4 + 5))
    assert dense_counts.decode_flops(s, 2, 3, 2) == want
    # bytes: weights 1000 of which the table 128 (16 rows x 4 x 2 B);
    # gathered rows 2 x 4 x 2 B; KV per position 2 layers x 2 x 1 x 2 x 2 B
    w = 1000 - 128 + 16
    kv = 2 * 16 * ((3 + 4) + 2)
    assert dense_counts.decode_bytes(s, 2, 3, 2, 1000, 128) == 2 * w + kv
    tied = _tiny_spec(tied=True)
    assert dense_counts.decode_bytes(tied, 2, 3, 2, 1000, 128) == \
        2 * 1000 + kv


def test_step_counts_add_up():
    s = _tiny_spec()
    total = dense_counts.decode_flops(s, 3, 5, 4)
    assert total == sum(dense_counts.decode_flops(s, 3, p, 1)
                        for p in range(5, 9))
    nb = dense_counts.decode_bytes(s, 3, 5, 4, 1000, 128)
    assert nb == sum(dense_counts.decode_bytes(s, 3, p, 1, 1000, 128)
                     for p in range(5, 9))


# -- each configuration through its own modules -----------------------------------
# Each check takes a configuration file's contents and reaches its family's
# code only through the modules that the file names (``triple``), so that a
# family that brings its own modules goes through the same checks.
def _check_weight_bytes(c):
    prog, ref, _ = triple(c)
    cfg = prog.model_config(c)
    spec = ref.spec_from_config(c, prog.embedding_rows(cfg))
    w = jax.eval_shape(lambda k: ref.init_weights(spec, k), jax.random.key(0))
    n = sum(x.size for x in jax.tree.leaves(w)) - ref.uncounted_params(spec)
    assert n == cfg.param_count()
    params = prog.program_params(Model(cfg), w)
    assert sum(x.size for x in jax.tree.leaves(params)) == \
        n + ref.uncounted_params(spec)


def _check_counts_add_up_by_step(c):
    """What the metrics need of the counts: positive, and a call's decode
    steps add up to the steps one at a time."""
    prog, ref, counts = triple(c)
    cfg = prog.model_config(c)
    spec = ref.spec_from_config(c, prog.embedding_rows(cfg))
    assert counts.prefill_flops(spec, 2, 16) > 0
    total = counts.decode_flops(spec, 3, 5, 4)
    assert total > 0 and total == pytest.approx(
        sum(counts.decode_flops(spec, 3, p, 1) for p in range(5, 9)))
    wb, eb = 10 ** 9, 10 ** 8
    nb = counts.decode_bytes(spec, 3, 5, 4, wb, eb)
    assert nb > 0 and nb == pytest.approx(
        sum(counts.decode_bytes(spec, 3, p, 1, wb, eb) for p in range(5, 9)))


def _f32_program(prog, c):
    cfg = prog.model_config(c).scaled(param_dtype=jnp.float32,
                                      compute_dtype=jnp.float32)
    return cfg, Model(cfg)


def _check_reference_matches_program(c):
    """Prefill then decode through the cache, in float32, against the
    reference's teacher-forced logits at every position."""
    prog, ref, _ = triple(c)
    cfg, model = _f32_program(prog, c)
    spec = ref.spec_from_config(c, prog.embedding_rows(cfg))
    w = ref.init_weights(spec, jax.random.key(3), jnp.float32)
    params = prog.program_params(model, w)
    rng = np.random.default_rng(0)
    b, ii, steps = 2, 7, 5
    toks = rng.integers(0, spec.vocab, (b, ii + steps)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        logits, cache = model.prefill(params, {"tokens": toks[:, :ii]},
                                      max_len=ii + steps)
        got = [logits[:, 0, :spec.vocab]]
        for j in range(steps):
            logits, cache = model.decode_step(params, cache,
                                              toks[:, ii + j:ii + j + 1])
            got.append(logits[:, 0, :spec.vocab])
        got = np.stack(got, 1)
        for r in range(b):
            want = np.asarray(ref.logits(spec, w, jnp.asarray(toks[r]),
                                         jnp.float32))[ii - 1:]
            np.testing.assert_allclose(got[r], want, atol=2e-4, rtol=2e-4)


def _check_gaps_zero_on_greedy_tokens(c):
    prog, ref, _ = triple(c)
    cfg = prog.model_config(c)
    spec = ref.spec_from_config(c, prog.embedding_rows(cfg))
    w = ref.init_weights(spec, jax.random.key(4), jnp.float32)
    seq = np.arange(8, dtype=np.int32)       # the prompt
    for _ in range(4):                       # greedy from the reference itself
        z = ref.logits(spec, w, jnp.asarray(seq), jnp.float32)
        seq = np.append(seq, int(np.argmax(z[-1]))).astype(np.int32)
    served = seq[8:]
    best, at = ref.gaps(spec, w, jnp.asarray(seq[:-1]), jnp.asarray(served), 7)
    np.testing.assert_allclose(np.asarray(best) - np.asarray(at), 0, atol=1e-5)
    best, at = ref.gaps(spec, w, jnp.asarray(seq[:-1]),
                        jnp.asarray((served + 1) % spec.vocab), 7)
    assert (np.asarray(best) - np.asarray(at)).min() > 0


@pytest.mark.parametrize("name", CONFIGS)
def test_weight_bytes_match_param_count(name):
    _check_weight_bytes(conf(name))


@pytest.mark.parametrize("name", CONFIGS)
def test_counts_add_up_by_step(name):
    _check_counts_add_up_by_step(conf(name))


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_matches_program_prefill_and_decode(name):
    _check_reference_matches_program(small(name))


@pytest.mark.parametrize("name", CONFIGS)
def test_gaps_are_zero_on_the_references_own_greedy_tokens(name):
    _check_gaps_zero_on_greedy_tokens(small(name))


def test_quantize_rounds_per_output_channel():
    x = jnp.asarray([[1.0, -2.0], [0.5, 0.25]], jnp.float32)
    q = np.asarray(dense_ref._quantize(x, "int8", (0,)))
    # column scales 1/127 and 2/127: both columns keep their maxima
    assert q[0, 0] == 1.0 and q[0, 1] == -2.0
    assert abs(q[1, 1] - 0.25) <= 1 / 127
    assert np.asarray(dense_ref._quantize(x, "fp8", (0,)))[0, 1] == -2.0


def test_sample_requests_holds_a_longest_prompt():
    served = [check.Served(np.zeros((3, ii), np.int32),
                           np.zeros((3, 4), np.int32)) for ii in (8, 16, 8)]
    for seed in range(20):
        picks = check.sample_requests(served, 4, seed)
        assert picks[0][0] == 1 and len(set(picks)) == 4
    assert check.sample_requests(served, 4, 5) == \
        check.sample_requests(served, 4, 5)


def test_sample_requests_reaches_every_part_of_a_batch():
    served = [check.Served(np.zeros((b, ii), np.int32),
                           np.zeros((b, 4), np.int32))
              for b, ii in ((12, 8), (6, 16), (3, 32), (6, 16))]
    for seed in (0, 1, BIG_SEED):
        picks = check.sample_requests(served, 4, seed)
        assert served[picks[0][0]].prompts.shape[1] == 32
        parts = sorted(r * 4 // served[i].prompts.shape[0] for i, r in picks)
        assert parts == [0, 1, 2, 3], picks


# -- the device gate ------------------------------------------------------------------
def test_device_gate_refuses_a_cpu():
    with pytest.raises(gate.NoChip):
        gate.device_gate(1)


def test_run_refuses_a_cpu_with_no_result(capsys):
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    assert rc != 0
    assert capsys.readouterr().out == ""


# -- trace reduction --------------------------------------------------------------------
def _ev(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=k, events=v) for k, v in lines.items()])


def test_trace_reduce_on_a_small_trace():
    host = _plane("/host:CPU", {
        "main": [_ev(trace_reduce.WINDOW, 0, 1000),
                 _ev("generate ii=8", 100, 400),
                 _ev("PjitFunction(_decode_scan)", 250, 240),
                 _ev("generate ii=16", 600, 350)],
        "other": [_ev("unrelated", 0, 2000)]})
    dev = _plane("/device:TPU:0", {
        "XLA Modules": [_ev("jit_prefill", 120, 100), _ev("jit_argmax", 230, 10),
                        _ev("jit_decode", 310, 180), _ev("jit_prefill", 610, 100),
                        _ev("jit_decode", 720, 200)],
        "XLA Ops": [_ev("fusion.1", 120, 100), _ev("argmax", 230, 10),
                    _ev("dot.2", 310, 180), _ev("fusion.1", 610, 100),
                    _ev("dot.2", 720, 200)]})
    other = _plane("/device:TPU:1", {"XLA Ops": [_ev("x", 0, 1000)]})
    r = trace_reduce.reduce_planes([host, dev, other], chips=1)
    assert r["window_s"] == pytest.approx(1e-6)
    assert r["busy_s"] == pytest.approx(590e-9)
    assert r["batches"] == [
        {"decode_s": 180e-9, "prefill_s": 110e-9, "decode_program": "jit_decode"},
        {"decode_s": 200e-9, "prefill_s": 100e-9, "decode_program": "jit_decode"}]
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx(200e-9)
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps[0] == ["between batches", pytest.approx(120e-9)]
    assert ["generate ii=8 / PjitFunction(_decode_scan)",
            pytest.approx(70e-9)] in gaps
    assert len(gaps) == 6
    r2 = trace_reduce.reduce_planes([host, dev, other], chips=2)
    assert r2["busy_s"] == pytest.approx((590e-9 + 1000e-9) / 2)


def test_trace_reduce_needs_the_window_and_a_device():
    dev = _plane("/device:TPU:0", {"XLA Ops": []})
    with pytest.raises(ValueError):
        trace_reduce.reduce_planes([dev])
    host = _plane("/host:CPU", {"main": [_ev(trace_reduce.WINDOW, 0, 10)]})
    with pytest.raises(ValueError):
        trace_reduce.reduce_planes([host])


MS = 1_000_000      # ns


def _window_trace(prompt_lens, early=0.0):
    """A window of one batch per prompt length, 10 ms each and 0.5 ms
    apart.  In each: prefill (attention 2 ms, mlp 1 ms), an eager sample
    (0.1 ms), then the decode scan, a while of 5 ms holding attention 2,
    kv_write 0.5, mlp 1.5 and head 0.5 ms; every program starts at its
    host launch.  ``early``: how far the device's clock reads early."""
    host, modules, ops = [], [], []
    t = 1 * MS
    for k, ii in enumerate(prompt_lens):
        host.append(_ev(f"{trace_reduce.BATCH_PREFIX}{ii}", t, 10 * MS))
        for j, (name, start, dur, inner) in enumerate((
                ("jit__lambda", 0.1, 3.0, (("fusion.1", 2.0),
                                           ("fusion.2", 1.0))),
                ("jit_argmax", 3.3, 0.1, (("argmax.1", 0.1),)),
                ("jit__decode_scan", 3.5, 5.0, (
                    ("fusion.7", 2.0), ("fusion.8", 0.5), ("fusion.9", 1.5),
                    ("fusion.10", 0.5))))):
            a = t + start * MS
            host.append(_ev(f"{trace_reduce.EXECUTE} {name}", a, 1000))
            modules.append(_ev(f"{name}({3 * k + j})", a - early, dur * MS))
            if name == "jit__decode_scan":
                ops.append(_ev("%while.1 = while()", a - early, dur * MS))
            for op, d in inner:
                ops.append(_ev(f"%{op} = {op.split('.')[0]}()", a - early,
                               d * MS))
                a += d * MS
        t += 10.5 * MS
    host.append(_ev(trace_reduce.WINDOW, 0, t))
    return [_plane("/host:CPU", {"main": host}),
            _plane("/device:TPU:0", {"XLA Modules": modules,
                                     "XLA Ops": ops})]


def test_programs_stay_in_their_batch_when_the_device_reads_early():
    """The device's clock reads 0.9 ms early against the host's: every
    program is moved after its launch, so each batch keeps its own."""
    want = [{"decode_s": 5e-3, "prefill_s": 3.1e-3,
             "decode_program": f"jit__decode_scan({3 * k + 2})"}
            for k in range(3)]
    r = trace_reduce.reduce_planes(_window_trace((8, 16, 8), early=0.9 * MS))
    assert r["device_lag_s"] == pytest.approx(0.9e-3)
    assert r["batches"] == [{**b, "decode_s": pytest.approx(b["decode_s"]),
                             "prefill_s": pytest.approx(b["prefill_s"])}
                            for b in want]
    same = trace_reduce.reduce_planes(_window_trace((8, 16, 8)))
    assert same["device_lag_s"] == 0
    assert same["busy_s"] == pytest.approx(r["busy_s"])
    assert [t for _, t in same["breakdown"]["idle_gaps"]] == pytest.approx(
        [t for _, t in r["breakdown"]["idle_gaps"]])


# -- the cells' files ---------------------------------------------------------------------
# what each of a configuration's three modules provides (``run.py``)
INTERFACE = {
    "programs": ("model_config", "embedding_rows", "program_params",
                 "embed_bytes", "SMALL"),
    "reference": ("Spec", "spec_from_config", "init_weights", "gaps",
                  "control_gaps", "logits", "uncounted_params"),
    "counts": ("prefill_flops", "decode_flops", "decode_bytes"),
}


def _check_cell_files(c: run.Cell):
    assert c.chips == 1
    assert c.params["batch_tokens"] >= c.mix.longest
    assert c.params["check_requests"] >= 1
    assert c.params["limits"]["widest_gap"] > 0
    for key, kind in (("program", "programs"), ("reference", "reference"),
                      ("counts", "counts")):
        mod = run.part(kind, c.conf[key])
        assert [n for n in INTERFACE[kind] if not hasattr(mod, n)] == []
    assert {m["name"] for m in c.end_to_end} >= {"output_tok_s", "setup_s"}
    assert c.per_layer
    for m in c.per_layer:
        assert callable(run.part("metrics", m["name"]).read)
    for m in c.end_to_end:
        assert callable(run.part("metrics", m["name"]).read)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_to_its_files(cell):
    _check_cell_files(run.load_cell(cell))


def test_config_files_agree_with_benchmark_json():
    for entry in BENCH["configs"]:
        c = json.loads((ROOT / entry["file"]).read_text())
        assert c["source"] == entry["source"]
        assert sorted(c["reduced"]) == sorted(entry["reduced"])
    peaks = json.loads((ROOT / "chipbench" / "peaks.json").read_text())
    assert peaks["TPU v5 lite"]["flops_per_s"] == 197e12


def test_metric_readers_on_a_fabricated_record():
    s = _tiny_spec()
    recs = _records()
    rec = {"batches": recs, "spec": s, "counts": dense_counts,
           "programs": [{"prefill_s": 0.05, "decode_s": 0.5},
                        {"prefill_s": 0.2, "decode_s": 0.9}],
           "peaks": {"flops_per_s": 1e6, "hbm_bytes_per_s": 1e6},
           "trace": {"busy_s": 0.75, "window_s": 1.0},
           "weight_bytes": 1000, "embed_bytes": 128}
    flops = sum(dense_counts.decode_flops(s, 4, r.prompt_len, 9)
                for r in recs)
    assert run.part("metrics", "decode_mfu").read(rec) == \
        pytest.approx(100 * flops / (1.4 * 1e6))
    pf = (dense_counts.prefill_flops(s, 4, 8)
          + dense_counts.prefill_flops(s, 4, 16))
    assert run.part("metrics", "prefill_mfu").read(rec) == \
        pytest.approx(100 * pf / (0.25 * 1e6))
    assert run.part("metrics", "device_idle_share").read(rec) == \
        pytest.approx(25.0)
    roof = run.part("metrics", "decode_roofline").read(rec)
    least = sum(max(dense_counts.decode_flops(s, 4, p, 1),
                    dense_counts.decode_bytes(s, 4, p, 1, 1000, 128)) / 1e6
                for r in recs for p in range(r.prompt_len, r.prompt_len + 9))
    assert roof == pytest.approx(100 * least / 1.4)
    rec["programs"] = [None, None]
    for name in ("decode_mfu", "prefill_mfu", "decode_roofline"):
        assert run.part("metrics", name).read(rec) is None


# -- whole runs at a tiny size, on the CPU -----------------------------------------------
def _tiny_root(tmp_path, name="qwen3-0.6b", limit=0.05, sizes=None,
               mix=None, c=None):
    """A checkout with one cell, ``tiny.t``: the configuration ``name`` at
    the CPU tests' size (or the configuration file ``c``)."""
    root = tmp_path / "checkout"
    for d in ("configs", "cells", "traffic"):
        (root / "chipbench" / d).mkdir(parents=True)
    (root / "chipbench/configs/tiny.json").write_text(
        json.dumps(c or small(name, **(sizes or {}))))
    (root / "chipbench/traffic/t.json").write_text(json.dumps(mix or {
        "buckets": [{"prompt_len": 8, "weight": 1},
                    {"prompt_len": 16, "weight": 1}], "output_len": 6}))
    (root / "chipbench/cells/tiny.t.json").write_text(json.dumps(
        {"batch_tokens": 48, "check_requests": 4,
         "limits": {"widest_gap": limit}}))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"] = [{"name": "tiny", "source": "-", "reduced": [],
                         "file": "chipbench/configs/tiny.json", "why": "-"}]
    bench["workloads"] = [{"name": "tiny.t", "config": "tiny",
                           "traffic": "t", "chips": 1, "why": "-"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.t"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture
def cpu_run(monkeypatch):
    """``run.main`` on the CPU: the gate passes and the compile cache is
    left alone."""
    monkeypatch.setattr(gate, "device_gate", lambda chips: {
        "platform": "cpu", "kind": "cpu", "count": chips})
    monkeypatch.setattr(run, "enable_compile_cache", lambda: None)

    def go(root, capsys, seed=BIG_SEED, extra=()):
        rc = run.main(["--workload", "tiny.t", "--seed", str(seed),
                       "--seconds", "0.3", *extra], root=root)
        assert rc == 0
        out = capsys.readouterr()
        return json.loads(out.out.strip().splitlines()[-1]), out.err
    return go


def _check_tiny_run(root, capsys, cpu_run):
    line, err = cpu_run(root, capsys)
    assert line["correct"] is True, line["checks"]
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {"output_tok_s", "setup_s"}
    assert line["attempted"] % 9 == 0 and line["failed"] == 0  # 6 + 3
    assert line["setup"]["batches"] % 2 == 0        # whole rounds of the mix
    assert line["checks"]["compiles_in_window"]["value"] == 0
    assert err.strip().splitlines()[-1].startswith("check span_excess_s")


@pytest.mark.parametrize("name", CONFIGS)
def test_tiny_run_is_correct(name, tmp_path, capsys, cpu_run):
    _check_tiny_run(_tiny_root(tmp_path, name), capsys, cpu_run)


def _split_maps(prompt_lens):
    """The HLO scope maps of ``_window_trace``'s programs."""
    maps = {}
    for ii in set(prompt_lens):
        span = f"{trace_reduce.BATCH_PREFIX}{ii}"
        maps[(span, "jit__lambda")] = {"fusion.1": "attention",
                                       "fusion.2": "mlp"}
        maps[(span, "jit__decode_scan")] = {
            "while.1": "other", "fusion.7": "attention",
            "fusion.8": "kv_write", "fusion.9": "mlp", "fusion.10": "head"}
    return maps


def test_tiny_traced_run_reads_every_per_layer_metric(tmp_path, capsys,
                                                      cpu_run, monkeypatch):
    """``run.main --trace 1``: the window runs under the profiler, and its
    trace (here a fabricated TPU trace of the window's own batches, read
    0.9 ms early) is reduced, split by scope over the maps compiled after
    the window, and read by every per-layer metric of the cell."""
    monkeypatch.setattr(gate, "device_gate", lambda chips: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": chips})
    seen = {}
    real = run.run_window

    def run_window(*args, **kw):
        seen["records"], served = real(*args, **kw)
        return seen["records"], served
    lens = lambda: [r.prompt_len for r in seen["records"]]
    monkeypatch.setattr(run, "run_window", run_window)
    monkeypatch.setattr(trace_reduce, "load_planes", lambda path:
                        _window_trace(lens(), early=0.9 * MS))
    monkeypatch.setattr(scopes, "program_maps",
                        lambda engine, records: _split_maps(lens()))
    line, _ = cpu_run(_tiny_root(tmp_path), capsys, extra=("--trace", "1"))
    assert line["correct"] is True, line["checks"]
    assert line["setup"]["per_layer_unread"] == []
    assert set(line["metrics"]) == {m["name"] for m in BENCH["per_layer"]}
    got = {k: v["value"] for k, v in line["metrics"].items()}
    steps = 5                                   # output_len 6
    want = {"decode_attention_ms": 2.0, "decode_kv_write_ms": 0.5,
            "decode_mlp_ms": 1.5, "decode_head_ms": 0.5,
            "decode_other_ms": 0.5}
    for k, v in want.items():
        assert got[k] == pytest.approx(v / steps)
    assert got["prefill_attention_share"] == pytest.approx(100 * 2 / 3.1)
    assert got["engine_idle_ms"] == pytest.approx(10 - 3 - 0.1 - 5)
    assert line["setup"]["device_lag_s"] == pytest.approx(0.9e-3)
    split = line["setup"]["split"]
    assert split["batches_split"] == len(seen["records"])
    assert sum(split["decode_ms_per_step"].values()) == pytest.approx(
        split["decode_scan_ms_per_step"])
    assert line["device"]["window_s"] > line["device"]["busy_s"] > 0


class _Engine:
    """Returns empty tokens and, with ``counters``, the call's number."""

    def __init__(self, counters=True):
        self.calls, self.counters = 0, counters

    def generate(self, prompts, n):
        self.calls += 1
        res = types.SimpleNamespace(
            tokens=np.zeros((prompts.shape[0], n), np.int32),
            prefill_s=0.0, decode_s=0.0)
        if self.counters:
            res.counters = {"call": self.calls, "rows": prompts.shape[0]}
        return res


def test_counters_reach_the_readers_on_their_batch(monkeypatch):
    """What ``generate`` reports in its result's ``counters`` is on that
    call's batch record, where a per-layer reader finds it."""
    mix = generator.Mix(prompt_lens=(8, 16), weights=(1, 1), output_len=4)
    batches = generator.Batches(mix, 32, 50, BIG_SEED)
    system = run.System(engine=_Engine(), vocab=50, weight_bytes=0,
                        embed_bytes=0)
    records, _ = run.run_window(system, batches, 0.0)
    assert [r.counters for r in records] == [
        {"call": i + 1, "rows": r.batch} for i, r in enumerate(records)]
    reader = types.ModuleType("chipbench.metrics.second_batch_rows")
    reader.read = lambda rec: rec["batches"][1].counters["rows"]
    monkeypatch.setitem(sys.modules, reader.__name__, reader)
    got = run.read_metrics([{"name": "second_batch_rows", "unit": "1"}],
                           {"batches": records})
    assert got == {"second_batch_rows": {"value": records[1].batch,
                                         "unit": "1"}}
    system.engine = _Engine(counters=False)      # a result without them
    records, _ = run.run_window(system, batches, 0.0)
    assert all(r.counters == {} for r in records)


def _altered_token(monkeypatch):
    """A served token altered where it is produced: the sampler returns
    the token after the greedy one."""
    import repro.inference.engine as engine
    real = engine.sample

    def sample(logits, key, **kw):
        return (real(logits, key, **kw) + 1) % logits.shape[-1]
    monkeypatch.setattr(engine, "sample", sample)


def _stale_cache(monkeypatch):
    """Decode returns its cache unchanged: each step attends to the prompt
    alone and writes at the same position."""
    real = Model.decode_step

    def decode_step(self, params, cache, tokens):
        logits, _ = real(self, params, cache, tokens)
        return logits, cache
    monkeypatch.setattr(Model, "decode_step", decode_step)


def _half_batch(monkeypatch):
    """Half of each batch left out: the front half is served, and its
    answers are handed to the back half too."""
    import repro.inference.engine as engine
    real = engine.ServingEngine.generate

    def generate(self, prompts, n, max_len=None):
        half = (prompts.shape[0] + 1) // 2
        res = real(self, prompts[:half], n, max_len)
        res.tokens = np.concatenate(
            [res.tokens, res.tokens[:prompts.shape[0] - half]])
        return res
    monkeypatch.setattr(engine.ServingEngine, "generate", generate)


@pytest.mark.parametrize("fault", [_altered_token, _stale_cache, _half_batch])
def test_broken_timed_path_is_not_correct(fault, tmp_path, capsys, cpu_run,
                                          monkeypatch):
    fault(monkeypatch)
    line, _ = cpu_run(_tiny_root(tmp_path), capsys)
    assert line["correct"] is False
    assert line["checks"]["widest_gap"]["value"] > 0.05


def test_moved_span_is_not_correct(tmp_path, capsys, cpu_run, monkeypatch):
    """Work moved out of the program's two spans fails the run."""
    import repro.inference.engine as engine
    real = engine.ServingEngine.generate

    def generate(self, prompts, n, max_len=None):
        res = real(self, prompts, n, max_len)
        res.decode_s += 10.0
        return res
    monkeypatch.setattr(engine.ServingEngine, "generate", generate)
    line, _ = cpu_run(_tiny_root(tmp_path), capsys)
    assert line["correct"] is False
    assert line["checks"]["span_excess_s"]["value"] > 9


# the control's test size: wide enough that rounding the weights moves the
# logits well past bf16 rounding; the limit lies between the readings
# (PERF.md, "Correctness limits")
CONTROL_SIZES = dict(hidden_size=256, intermediate_size=512,
                     num_hidden_layers=4, vocab_size=2048)
CONTROL_MIX = {"buckets": [{"prompt_len": 16, "weight": 1},
                           {"prompt_len": 32, "weight": 1}],
               "output_len": 16}
CONTROL_LIMIT = 0.1


@pytest.mark.parametrize("seed", [1, 2, BIG_SEED])
def test_control_is_not_correct(seed, tmp_path, capsys, cpu_run):
    """The plain reference at fp8, put in the program's place, goes through
    the run's own comparison and limit and comes out not correct, where the
    program itself comes out correct."""
    root = _tiny_root(tmp_path, limit=CONTROL_LIMIT, sizes=CONTROL_SIZES,
                      mix=CONTROL_MIX)
    line, _ = cpu_run(root, capsys, seed=seed)
    assert line["correct"] is True, line["checks"]
    line, err = cpu_run(root, capsys, seed=seed, extra=("--control", "fp8"))
    assert line["correct"] is False, line["checks"]
    assert line["setup"]["control"] == "fp8"
    assert line["checks"]["widest_gap"]["value"] > CONTROL_LIMIT
    assert "check widest_gap" in err


def test_control_readings_judge_program_and_control(tmp_path):
    """``control.py`` judges one run for the program and for each control
    with the run's own checks and limits."""
    from chipbench import control
    root = _tiny_root(tmp_path, limit=CONTROL_LIMIT, sizes=CONTROL_SIZES,
                      mix=CONTROL_MIX)
    cell = run.load_cell("tiny.t", root)
    r = control.readings(cell, 3, 0.3, ["fp8"],
                         {"platform": "cpu", "kind": "cpu", "count": 1},
                         gate.CompileCounter())
    assert r["program"]["correct"] is True, r
    assert r["fp8"]["correct"] is False, r
    assert r["fp8"]["checks"]["widest_gap"][1] == CONTROL_LIMIT
    assert r["fp8"]["widest_gap"] > r["program"]["widest_gap"]


# -- compiles for a described TPU v5e -------------------------------------------------------
@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(no_persistent_cache):
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _used(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _check_largest_programs(c: run.Cell, one_chip):
    """The cell's largest prefill, its decode scan and its reference, as
    the run compiles them, each fit beside the weights on one chip."""
    from repro.inference.engine import ServingEngine
    prog, ref, _ = triple(c.conf)
    cfg = prog.model_config(c.conf)
    spec = ref.spec_from_config(c.conf, prog.embedding_rows(cfg))
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
    w = jax.tree.map(sds, jax.eval_shape(
        lambda k: ref.init_weights(spec, k), jax.random.key(0)))
    model = Model(cfg)
    params = prog.program_params(model, w)
    engine = ServingEngine(model, params)
    batches = generator.Batches(c.mix, c.params["batch_tokens"], 1, 0)
    oo = c.mix.output_len
    # prefill is largest at the longest prompt (its score matrix), the
    # decode scan at the largest cache
    ii = c.mix.longest
    toks = jax.ShapeDtypeStruct((batches.size(ii), ii), jnp.int32,
                                sharding=one_chip)
    pre = engine._prefill.lower(params, {"tokens": toks}, ii + oo).compile()
    kv_ii = max(c.mix.prompt_lens, key=lambda n: batches.size(n) * (n + oo))
    b = batches.size(kv_ii)
    toks = jax.ShapeDtypeStruct((b, kv_ii), jnp.int32, sharding=one_chip)
    _, cache = jax.eval_shape(lambda p, t: model.prefill(
        p, {"tokens": t}, max_len=kv_ii + oo), params, toks)
    first = jax.ShapeDtypeStruct((b, 1), jnp.int32, sharding=one_chip)
    dec = engine._decode_n.lower(params, jax.tree.map(sds, cache), first,
                                 oo - 1).compile()
    seq = jax.ShapeDtypeStruct((ii + oo - 1,), jnp.int32, sharding=one_chip)
    served = jax.ShapeDtypeStruct((oo,), jnp.int32, sharding=one_chip)
    check_ = ref.gaps.lower(spec, w, seq, served, ii - 1).compile()
    for name, compiled in (("prefill", pre), ("decode", dec),
                           ("reference", check_)):
        assert _used(compiled) < HBM_BYTES, (name, _used(compiled))
    assert math.isfinite(_used(pre))


@pytest.mark.parametrize("cell", CELLS)
def test_largest_programs_fit_one_v5e(cell, one_chip):
    _check_largest_programs(run.load_cell(cell), one_chip)


# -- a second family, through new modules alone -----------------------------------------
SECOND = "second_family"


class _Family(types.ModuleType):
    """A family's module under a name of its own: the names ``INTERFACE``
    lists for its kind and no others, taken from ``source``, with a record
    of those read."""

    def __init__(self, name, source, names):
        super().__init__(name)
        self._source, self._names, self.used = source, names, set()

    def __getattr__(self, key):
        if key not in self._names:
            raise AttributeError(f"{self.__name__} has no {key!r}")
        self.used.add(key)
        return getattr(self._source, key)


@pytest.fixture
def second_family(monkeypatch):
    """A configuration whose program, reference and counts are the modules
    ``chipbench.<kind>.second_family``, found only by name: dense GQA
    behind the interface alone."""
    mods = {}
    for kind, source in (("programs", dense_prog), ("reference", dense_ref),
                         ("counts", dense_counts)):
        mods[kind] = _Family(f"chipbench.{kind}.{SECOND}", source,
                             INTERFACE[kind])
        monkeypatch.setitem(sys.modules, mods[kind].__name__, mods[kind])
    c = small("qwen3-0.6b")
    c.update(program=SECOND, reference=SECOND, counts=SECOND)
    return c, mods


@pytest.mark.parametrize("check_", [
    "weight_bytes", "counts", "reference", "gaps", "cell_files",
    "largest_programs", "tiny_run"])
def test_second_family_goes_through_by_module_names(
        check_, second_family, tmp_path, capsys, cpu_run, request):
    """Every check that is run per configuration or per cell, and a whole
    run, take a configuration whose modules have other names, through new
    files and entries alone."""
    c, mods = second_family
    root = _tiny_root(tmp_path, c=c)
    cell = lambda: run.load_cell("tiny.t", root)
    checks = {
        "weight_bytes": lambda: _check_weight_bytes(c),
        "counts": lambda: _check_counts_add_up_by_step(c),
        "reference": lambda: _check_reference_matches_program(c),
        "gaps": lambda: _check_gaps_zero_on_greedy_tokens(c),
        "cell_files": lambda: _check_cell_files(cell()),
        "largest_programs": lambda: _check_largest_programs(
            cell(), request.getfixturevalue("one_chip")),
        "tiny_run": lambda: _check_tiny_run(root, capsys, cpu_run),
    }
    checks[check_]()
    assert mods["programs"].used and mods["reference"].used
    if check_ in ("counts", "cell_files"):
        assert mods["counts"].used
