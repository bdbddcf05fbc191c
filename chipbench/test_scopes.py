"""CPU tests of the split by layer and engine phase (``chipbench/scopes.py``).

A small fabricated trace checks the reduction; the tiny dense model's
compiled programs check that the program names every layer that the
reduction reads.
"""
from __future__ import annotations

import contextlib
import json
import pathlib
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import run, scopes, stats, trace_reduce
from repro.configs import get_config
from repro.inference import engine as engine_mod
from repro.inference.engine import ServingEngine
from repro.models import layers
from repro.models.transformer import Model

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
# the per-layer metrics that read the split
SPLIT = ("decode_attention_ms", "decode_kv_write_ms", "decode_mlp_ms",
         "decode_head_ms", "decode_other_ms", "prefill_attention_share",
         "engine_idle_ms")


def test_names_are_the_programs():
    assert scopes.SCOPES == layers.SCOPES
    assert scopes.PHASES == engine_mod.PHASES
    assert scopes.FIRST_TOKEN == engine_mod.FIRST_TOKEN
    assert scopes.HEAD == layers.HEAD


# -- HLO text -----------------------------------------------------------------------
HLO = """HloModule jit__decode_scan, is_scheduled=true, entry_computation_layout={()->f32[8]}

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %sin.1 = f32[8]{0} sine(%param_0), metadata={op_name="jit(f)/while/body/attention/sin" stack_frame_id=1}
  ROOT %cos.1 = f32[8]{0} cosine(%sin.1), metadata={op_name="jit(f)/while/body/attention/kv_write/cos"}
}

%fused_computation.2 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %sin.2 = f32[8]{0} sine(%param_0.1), metadata={op_name="jit(f)/while/body/attention/sin"}
}

ENTRY %main.3 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0), metadata={op_name="x"}
  %fusion.1 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2
  %fusion.3 = f32[8]{0} fusion(%fusion.2), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(f)/mlp/mul"}
  %copy.4 = f32[8]{0} copy(%fusion.3)
  ROOT %dot.5 = f32[8]{0} dot(%copy.4, %copy.4), metadata={op_name="jit(f)/head/jit(inner)/dot_general"}
}
"""


def test_hlo_scopes_on_a_small_text():
    got = scopes.hlo_scopes(HLO)
    assert scopes.module_name(HLO) == "jit__decode_scan"
    assert got["sin.1"] == "attention" and got["cos.1"] == "kv_write"
    # a fusion with no op_name: the one scope inside it, else other
    assert got["fusion.1"] == "other"          # attention and kv_write
    assert got["fusion.2"] == "attention"
    assert got["fusion.3"] == "mlp"            # its own op_name first
    assert got["copy.4"] == "other" and got["x"] == "other"
    assert got["dot.5"] == "head"
    assert scopes.scope_of("jit(f)/attention/kv_write/dynamic_update_slice") \
        == "kv_write"


# -- a fabricated trace ----------------------------------------------------------------
def _ev(name, start, dur, **stats_):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=stats_)


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=k, events=v) for k, v in lines.items()])


def _op(name):
    return f"%{name} = bf16[8]{{0}} {name.split('.')[0]}(bf16[8]{{0}} %p)"


def _trace(engine_spans=True, early=0, launches=False):
    """One batch: prefill 100-200 (attention 60, mlp 30, other 10), an
    eager sample 250-260, the decode scan 300-600: a while holding
    attention 40, kv_write 20, mlp 80, head 30 and an unscoped copy 50, so
    the while's own time is 80.  ``early``: the device's events read that
    much early; ``launches``: the host's launch of each program."""
    host = [_ev(trace_reduce.WINDOW, 0, 1000),
            _ev("generate ii=8", 90, 600)]
    if launches:
        host += [_ev(f"{trace_reduce.EXECUTE} linkage", t, 1)
                 for t in (100, 250, 300)]
    if engine_spans:
        host += [_ev("engine.prefill", 95, 10, call=1),
                 _ev("engine.first_token", 105, 160, call=1),
                 _ev("engine.decode", 265, 345, call=1),
                 _ev("engine.to_host", 610, 70, call=1)]
    dev = _plane("/device:TPU:0", {
        "XLA Modules": [_ev("jit__lambda(11)", 100, 100),
                        _ev("jit_argmax(12)", 250, 10),
                        _ev("jit__decode_scan(13)", 300, 300)],
        "XLA Ops": [_ev(_op("fusion.1"), 100, 60), _ev(_op("fusion.2"), 160, 30),
                    _ev(_op("copy.3"), 190, 10), _ev(_op("argmax.1"), 250, 10),
                    _ev(_op("while.1"), 300, 300),
                    _ev(_op("fusion.7"), 310, 40), _ev(_op("fusion.8"), 350, 20),
                    _ev(_op("fusion.9"), 380, 80), _ev(_op("fusion.10"), 470, 30),
                    _ev(_op("copy.11"), 520, 50)]})
    for line in dev.lines:
        for e in line.events:
            e.start_ns -= early
    return [_plane("/host:CPU", {"main": host}), dev]


MAPS = {("generate ii=8", "jit__lambda"):
        {"fusion.1": "attention", "fusion.2": "mlp", "copy.3": "other"},
        ("generate ii=8", "jit__decode_scan"):
        {"while.1": "other", "fusion.7": "attention", "fusion.8": "kv_write",
         "fusion.9": "mlp", "fusion.10": "head", "copy.11": "other"}}


def test_self_times_leave_out_nested_events():
    evs = [("while", 0, 100), ("a", 10, 30), ("inner", 12, 20), ("b", 40, 90),
           ("after", 100, 110)]
    assert scopes.self_times(evs) == [30, 12, 8, 50, 10]


def test_scope_reduction_on_a_small_trace():
    [split] = scopes.reduce_scopes(_trace(), MAPS)
    assert split["decode"] == pytest.approx({
        "attention": 40e-9, "kv_write": 20e-9, "mlp": 80e-9, "head": 30e-9,
        "other": 130e-9})                     # the while's own 80 + copy 50
    assert sum(split["decode"].values()) == pytest.approx(300e-9)
    # the eager sample inside engine.first_token counts as head
    assert split["prefill"] == pytest.approx({
        "attention": 60e-9, "mlp": 30e-9, "other": 10e-9, "head": 10e-9})
    # idle 90-100, 200-250, 260-300 and 600-690, by the phase over it
    assert split["idle"] == pytest.approx({
        "engine.prefill": 5e-9, "engine.first_token": 55e-9,
        "engine.decode": 45e-9, "engine.to_host": 70e-9,
        "outside phases": 15e-9})
    assert [k for k, _ in split["unscoped"]] == ["%while.1 while",
                                                 "%copy.11 copy"]


def test_device_events_read_early_are_moved_to_their_launches():
    [want] = scopes.reduce_scopes(_trace(launches=True), MAPS)
    assert want["device_lag_s"] == 0
    [got] = scopes.reduce_scopes(_trace(early=120, launches=True), MAPS)
    assert got["device_lag_s"] == pytest.approx(120e-9)
    for key in ("decode", "prefill", "idle"):
        assert got[key] == pytest.approx(want[key])
    # the prefill program read before its generate span: no split without
    # the launches to place it
    [lost] = scopes.reduce_scopes(_trace(early=120), MAPS)
    assert lost["prefill"].get("attention") is None


def test_idle_outside_engine_phases_and_unmatched_maps():
    [split] = scopes.reduce_scopes(_trace(engine_spans=False), MAPS)
    assert split["idle"] == pytest.approx({"outside phases": 190e-9})
    assert split["prefill"]["other"] == pytest.approx(20e-9)  # no phase
    wrong = dict(MAPS)
    wrong[("generate ii=8", "jit__decode_scan")] = {"while.1": "other"}
    assert scopes.reduce_scopes(_trace(), wrong) == [None]


def _record(split):
    recs = [stats.BatchRecord(batch=4, prompt_len=8, output_len=11,
                              start_s=0.0, end_s=1.0, prefill_s=0.1,
                              decode_s=0.5)]
    reduced = trace_reduce.reduce_planes(_trace(), chips=1)
    rec = {"batches": recs, "programs": reduced["batches"], "trace": reduced,
           "spec": None, "counts": None,
           "peaks": {"flops_per_s": 1e6, "hbm_bytes_per_s": 1e6},
           "weight_bytes": 1000, "embed_bytes": 128}
    if split is not None:
        rec["trace"] = dict(reduced, scopes=split)
    return rec


def test_new_readers_on_the_fabricated_trace():
    rec = _record(scopes.reduce_scopes(_trace(), MAPS))
    want = {"decode_attention_ms": 40e-6 / 10, "decode_kv_write_ms": 20e-6 / 10,
            "decode_mlp_ms": 80e-6 / 10, "decode_head_ms": 30e-6 / 10,
            "decode_other_ms": 130e-6 / 10,
            "prefill_attention_share": 100 * 60 / 110,
            "engine_idle_ms": 190e-6}
    entries = [m for m in BENCH["per_layer"] if m["name"] in SPLIT]
    assert [m["name"] for m in entries] == list(want) == list(SPLIT)
    got = run.read_metrics(entries, rec)
    assert {k: v["value"] for k, v in got.items()} == pytest.approx(want)
    # the five decode scopes add up to the decode scan's time per step
    decode = sum(want[m] for m in want if m.startswith("decode_"))
    assert decode == pytest.approx(
        1e3 * rec["programs"][0]["decode_s"] / 10)
    assert run.read_metrics(entries, _record(None)) == {}
    assert run.read_metrics(entries, _record([None])) == {}


def test_existing_readings_unchanged_by_the_split():
    """The engine's spans move none of trace_reduce's numbers (only the
    labels of idle gaps), and every per-layer metric that does not read
    the split reads the same with the split beside it."""
    plain = trace_reduce.reduce_planes(_trace(engine_spans=False), chips=1)
    spanned = trace_reduce.reduce_planes(_trace(), chips=1)
    for key in ("busy_s", "window_s", "batches"):
        assert spanned[key] == plain[key]
    assert spanned["breakdown"]["device_ops"] == \
        plain["breakdown"]["device_ops"]
    assert [t for _, t in spanned["breakdown"]["idle_gaps"]] == \
        [t for _, t in plain["breakdown"]["idle_gaps"]]
    from chipbench.counts import dense_gqa as counts
    from chipbench.reference import dense_gqa as ref
    spec = ref.Spec(layers=2, d_model=4, n_heads=2, n_kv=1, d_head=2,
                    d_ff=8, vocab=10, rows=16, theta=1e4, eps=1e-6,
                    qkv_bias=False, qk_norm=False, tied=False)
    without = _record(None)
    with_split = _record(scopes.reduce_scopes(_trace(), MAPS))
    for rec in (without, with_split):
        rec.update(spec=spec, counts=counts)
    entries = [m for m in BENCH["per_layer"] if m["name"] not in SPLIT]
    before = run.read_metrics(entries, without)
    assert set(before) == {m["name"] for m in entries}
    assert run.read_metrics(entries, with_split) == before


# -- the program's compiled names ---------------------------------------------------------
@pytest.fixture(scope="module")
def tiny_engine():
    cfg = get_config("qwen3-0.6b").scaled(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_head=16, d_ff=160,
        vocab_size=300)
    model = Model(cfg)
    return ServingEngine(model, model.init(jax.random.key(0)))


def test_compiled_programs_carry_every_scope(tiny_engine):
    recs = [stats.BatchRecord(2, 8, 4, 0.0, 1.0, 0.0, 0.0)]
    maps = scopes.program_maps(tiny_engine, recs)
    assert sorted(maps) == [("generate ii=8", "jit__decode_scan"),
                            ("generate ii=8", "jit__lambda")]
    for table in maps.values():
        assert set(table.values()) == set(scopes.SCOPES) | {scopes.OTHER}


def test_sample_lands_under_head(tiny_engine):
    """The decode scan's argmax over the vocabulary (a reduce with an s32
    result) is under ``head``."""
    cache = jax.eval_shape(lambda p, t: tiny_engine.model.prefill(
        p, {"tokens": t}, max_len=12)[1], tiny_engine.params,
        jax.ShapeDtypeStruct((2, 8), jnp.int32))
    text = tiny_engine._decode_n.lower(
        tiny_engine.params, cache, jax.ShapeDtypeStruct((2, 1), jnp.int32),
        3).compile().as_text()
    table = scopes.hlo_scopes(text)
    argmax = [m.group(1) for m in re.finditer(
        r"%([\w.\-]+) = \(f32\[2\]\{0\}, s32\[2\]\{0\}\) reduce\(", text)]
    assert argmax and all(table[n] == scopes.HEAD for n in argmax)


def test_maps_hold_this_programs_scopes_over_a_cached_program(
        tmp_path, monkeypatch):
    """The compile cache's key leaves ``op_name`` out: a program compiled
    before without the scopes (another checkout) is handed back with its
    own metadata.  The maps compile again with the metadata in the key."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    cfg = get_config("qwen3-0.6b").scaled(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_head=16, d_ff=160,
        vocab_size=300)
    params = Model(cfg).init(jax.random.key(0))
    recs = [stats.BatchRecord(2, 8, 4, 0.0, 1.0, 0.0, 0.0)]
    saved = {k: getattr(jax.config, k) for k in (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    try:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        cc.reset_cache()
        with monkeypatch.context() as m:
            m.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
            bare = scopes._program_maps(ServingEngine(Model(cfg), params), recs)
        assert all(set(t.values()) == {scopes.OTHER} for t in bare.values())
        engine = ServingEngine(Model(cfg), params)
        engine.generate(np.zeros((2, 8), np.int32), 4)   # runs the cached
        maps = scopes.program_maps(engine, recs)
        assert all(set(t.values()) == set(scopes.SCOPES) | {scopes.OTHER}
                   for t in maps.values())
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()
