"""The decode step and the prefill by model layer, and the device's idle
time inside ``generate`` by engine phase, from a profiler trace.

The program names its layers inside its compiled programs with
``jax.named_scope`` (``SCOPES``, as ``repro.models.layers.SCOPES`` has
them) and the phases of ``ServingEngine.generate`` with host spans
(``PHASES``, as ``repro.inference.engine.PHASES`` has them).  A TPU trace
names each device operation by its HLO instruction only, so the scope of
an operation is read from the compiled program's HLO text: the innermost
listed scope in the instruction's ``op_name``, or in the ``op_name`` of the
operations inside a fusion that carries none; an instruction under no
listed scope is ``other``.

For each ``generate`` span of the window (``trace_reduce.BATCH_PREFIX``):

  * ``decode`` and ``prefill``: self time by scope of the operations of
    the decode scan (the program that ends last, as in ``trace_reduce``)
    and of the programs before it.  Self time is an operation's duration
    less the part that operations nested inside it on the same line cover,
    so a ``while`` adds only its own control.  The first-token sample runs
    eagerly, as programs with no HLO text here; those that start inside an
    ``engine.first_token`` span count as ``head``;
  * ``idle``: the device-idle time inside the span by the engine phase
    that covers it, or ``outside phases``;
  * ``unscoped``: the decode scan's operations under no listed scope, with
    their self time.

A mapped program that runs an instruction its HLO text lacks gives its
batch no split, so a map that no longer matches the programs makes the
metrics read nothing rather than something wrong.

The device's events are moved later by ``trace_reduce.device_lag`` before
they are set against the host spans, as ``trace_reduce`` moves them.

``run.py --trace 1`` reduces its window's trace by ``reduce_scopes`` over
the maps of ``program_maps``, compiled after the window, and its readers
(``decode_*_ms``, ``prefill_attention_share``, ``engine_idle_ms``) read the
split; ``summary`` goes on its result line.
"""
from __future__ import annotations

import bisect
import re
from collections import Counter, defaultdict

from chipbench import trace_reduce

SCOPES = ("attention", "kv_write", "mlp", "head")
OTHER = "other"
HEAD = "head"
PHASES = ("engine.prefill", "engine.first_token", "engine.decode",
          "engine.to_host")
FIRST_TOKEN = "engine.first_token"
OUTSIDE = "outside phases"
TOP_UNSCOPED = 5

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (.*)$")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+) .*\{$")
_OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')
_CALLS = re.compile(r"\bfusion\(.*\bcalls=%([\w.\-]+)")


# -- HLO text -------------------------------------------------------------------
def scope_of(op_name: str) -> str:
    """The innermost listed scope on an ``op_name`` path, else ``other``."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return OTHER


def module_name(hlo_text: str) -> str:
    return hlo_text.split(None, 2)[1].rstrip(",")


def hlo_scopes(hlo_text: str) -> dict:
    """Instruction name -> scope for every instruction of a compiled
    program's HLO text (``Compiled.as_text()``).  A fusion with no
    ``op_name`` takes the one scope of the operations inside it that have
    one, else ``other``."""
    own, calls, inner = {}, {}, defaultdict(set)
    computation = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            computation = m.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        op = _OP_NAME.search(rest)
        own[name] = scope_of(op.group(1)) if op else None
        if op:
            inner[computation].add(own[name])
        c = _CALLS.search(rest)
        if c:
            calls[name] = c.group(1)
    out = {}
    for name, scope in own.items():
        if scope is None:
            found = inner.get(calls.get(name), set())
            scope = next(iter(found)) if len(found) == 1 else OTHER
        out[name] = scope
    return out


def _instruction(event_name: str) -> str:
    """``%fusion.3 = bf16[8]{0} fusion(...)`` (TPU) or ``fusion.3`` -> the
    instruction's name."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _op_kind(event_name: str) -> str:
    """The instruction and its opcode, without shapes."""
    name, _, rest = event_name.partition(" = ")
    m = re.search(r" ([a-z][\w\-]*)\(", rest)
    return f"{name} {m.group(1)}" if m else name


# -- the trace ----------------------------------------------------------------------
def self_times(events) -> list:
    """Each event's duration less what events nested inside it cover.
    ``events``: (name, start, end) on one line, sorted by start."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    own = [e[2] - e[1] for e in events]
    stack = []
    for i in order:
        _, a, b = events[i]
        while stack and events[stack[-1]][2] <= a:
            stack.pop()
        if stack and b <= events[stack[-1]][2]:
            own[stack[-1]] -= b - a
        stack.append(i)
    return own


def _idle_by_phase(ops, phases, s0, s1) -> dict:
    busy = trace_reduce._union([(a, b) for _, a, b in ops], s0, s1)
    edges = [s0] + [x for iv in busy for x in iv] + [s1]
    out = Counter()
    for g0, g1 in zip(edges[::2], edges[1::2]):
        covered = 0.0
        for name, p0, p1 in phases:
            part = min(g1, p1) - max(g0, p0)
            if part > 0:
                out[name] += part
                covered += part
        if g1 - g0 - covered > 0:
            out[OUTSIDE] += g1 - g0 - covered
    return {k: v / 1e9 for k, v in out.items()}


def reduce_scopes(planes, maps: dict) -> list:
    """Per ``generate`` span of the window, in order: the split described
    above, or None where the span ran no program or a map did not match.
    ``maps``: (span name, HLO module name) -> ``hlo_scopes`` of the
    program that the span ran under that name.  Reads the first TPU plane,
    as ``trace_reduce`` reads its batches."""
    planes = list(planes)
    (_, w0, w1), spans = trace_reduce._host_spans(planes)
    batch_spans = sorted((s for s in spans
                          if s[0].startswith(trace_reduce.BATCH_PREFIX)
                          and w0 <= s[1] < w1), key=lambda s: s[1])
    phases = [s for s in spans if s[0] in PHASES]
    device = min((p for p in planes
                  if trace_reduce.DEVICE_PLANE.match(p.name)),
                 key=lambda p: int(trace_reduce.DEVICE_PLANE.match(
                     p.name).group(1)), default=None)
    if device is None:
        raise ValueError("no TPU device plane in the trace")
    lines, lag = trace_reduce.device_lines(device, spans)
    modules = lines.get(trace_reduce.MODULES_LINE, [])
    ops = sorted(lines.get(trace_reduce.OPS_LINE, []), key=lambda e: e[1])
    starts = [e[1] for e in ops]
    own = self_times(ops)
    out = []
    for span, s0, s1 in batch_spans:
        mine = [m for m in modules if s0 <= m[1] < s1]
        inside = [p for p in phases if s0 <= p[1] < s1]
        split = _split(span, mine, inside, ops, starts, own, maps)
        if split is not None:
            lo, hi = bisect.bisect_left(starts, s0), bisect.bisect_left(
                starts, s1)
            split["idle"] = _idle_by_phase(ops[lo:hi], inside, s0, s1)
            split["device_lag_s"] = lag / 1e9
        out.append(split)
    return out


def _split(span, mine, phases, ops, starts, own, maps):
    if not mine:
        return None
    last = max(mine, key=lambda m: m[2])
    first = [(a, b) for name, a, b in phases if name == FIRST_TOKEN]
    decode, prefill, unscoped = Counter(), Counter(), Counter()
    for mod, m0, m1 in mine:
        table = maps.get((span, re.sub(r"\(\d+\)$", "", mod)))
        eager = HEAD if any(a <= m0 < b for a, b in first) else OTHER
        into = decode if (mod, m0, m1) == last else prefill
        for i in range(bisect.bisect_left(starts, m0),
                       bisect.bisect_left(starts, m1)):
            name = ops[i][0]
            if table is None:
                scope = eager
            elif _instruction(name) in table:
                scope = table[_instruction(name)]
            else:
                return None
            into[scope] += own[i] / 1e9
            if into is decode and scope == OTHER:
                unscoped[_op_kind(name)] += own[i] / 1e9
    return {"decode": dict(decode), "prefill": dict(prefill),
            "unscoped": unscoped.most_common(TOP_UNSCOPED)}


# -- what the readers read --------------------------------------------------------------
def decode_ms(rec: dict, scope: str):
    """Self time under ``scope`` in the decode scans of the traced batches,
    in ms per decode step; None where no batch was split or nothing ran
    under the scope."""
    secs = steps = 0.0
    for b, split in zip(rec["batches"], rec["trace"].get("scopes") or []):
        if split is None:
            continue
        secs += split["decode"].get(scope, 0.0)
        steps += b.output_len - 1
    return 1e3 * secs / steps if secs > 0 else None


# -- the maps, after the window -------------------------------------------------------------------------
def program_maps(engine, records) -> dict:
    """``hlo_scopes`` of the prefill and decode-scan programs that each
    prompt length of the window ran, keyed as ``reduce_scopes`` wants.

    Compiles them afresh: JAX's in-memory caches are cleared, and the
    persistent cache's key takes in the metadata.  The key leaves
    ``op_name`` out by default, so the cache can hand back a program
    compiled from other source (an older checkout) with the same
    instructions under that source's ``op_name``s, and the process keeps
    what it loaded."""
    import jax
    jax.clear_caches()
    key = jax.config.jax_compilation_cache_include_metadata_in_key
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    try:
        return _program_maps(engine, records)
    finally:
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          key)


def _program_maps(engine, records) -> dict:
    import jax
    import jax.numpy as jnp
    maps = {}
    for b, ii, oo in sorted({(r.batch, r.prompt_len, r.output_len)
                             for r in records}):
        toks = jax.ShapeDtypeStruct((b, ii), jnp.int32)
        pre = engine._prefill.lower(engine.params, {"tokens": toks},
                                    ii + oo).compile().as_text()
        _, cache = jax.eval_shape(
            lambda p, t: engine.model.prefill(p, {"tokens": t},
                                              max_len=ii + oo),
            engine.params, toks)
        cache = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), cache)
        first = jax.ShapeDtypeStruct((b, 1), jnp.int32)
        dec = engine._decode_n.lower(engine.params, cache, first,
                                     oo - 1).compile().as_text()
        for text in (pre, dec):
            maps[(f"{trace_reduce.BATCH_PREFIX}{ii}", module_name(text))] = \
                hlo_scopes(text)
    return maps


def summary(records, programs, split) -> dict:
    """The split over the window: decode ms per step by scope beside the
    decode scans' own device time per step (``trace_reduce``), prefill
    seconds by scope, idle ms per batch by phase, the largest unscoped
    decode operations in ms per step."""
    steps = sum(r.output_len - 1 for r, s in zip(records, split) if s)
    n = sum(1 for s in split if s)
    scans = sum(p["decode_s"] for p, s in zip(programs, split) if s)
    decode, prefill, idle, unscoped = Counter(), Counter(), Counter(), \
        Counter()
    for s in split:
        if s is None:
            continue
        decode.update(s["decode"])
        prefill.update(s["prefill"])
        idle.update(s["idle"])
        unscoped.update(dict(s["unscoped"]))
    per_step = lambda c: {k: 1e3 * v / max(steps, 1) for k, v in c.items()}
    return {"batches_split": n, "decode_ms_per_step": per_step(decode),
            "decode_scan_ms_per_step": 1e3 * scans / max(steps, 1),
            "prefill_s": dict(prefill),
            "idle_ms_per_batch": {k: 1e3 * v / max(n, 1)
                                  for k, v in idle.items()},
            "unscoped_decode_ms_per_step": [
                [k, 1e3 * v / max(steps, 1)]
                for k, v in unscoped.most_common(TOP_UNSCOPED)]}
