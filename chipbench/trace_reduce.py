"""From a profiler trace to the numbers the per-layer metrics read.

The run's window is one host span named ``WINDOW``, and each
``generate`` call inside it a span named ``generate ii=<prompt length>``,
both written by the harness (``jax.profiler.TraceAnnotation``) on the same
host thread.  On each TPU device plane:

  * busy time is the union of the intervals in which an operation of the
    line ``XLA Ops`` runs, inside the window; ``busy_s`` and ``window_s``
    are that union and the window's length, averaged over the chips used;
  * each batch's programs are the events of the line ``XLA Modules`` that
    start inside its ``generate`` span.  All of them finish before
    ``generate`` returns, since it waits for its tokens.  The one that ends
    last is the decode scan; the others are prefill and the first-token
    sample, the work that the engine's own ``prefill_s`` spans;
  * the breakdown gives the device operations that took most time, and
    the longest idle gaps, each labelled by what the host was doing: the
    shortest host span on the harness's thread that covers the gap's
    middle, under the ``generate`` span that holds it, if any.

The device's events can read up to about a millisecond early against the
host's spans (on a v5e, programs were seen to start up to 0.99 ms before
the host call that launched them), which would put a batch's programs into
the span before it.  So each device plane's events are moved later by the
least shift that puts every program after its launch (``device_lag``)
before they are set against the host spans.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

WINDOW = "chipbench window"
BATCH_PREFIX = "generate ii="
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
EXECUTE = "PJRT_LoadedExecutable_Execute"    # the host's launch of a program
TOP = 10


def _events(line):
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def _union(intervals, lo, hi):
    """Merged intervals of ``intervals`` clipped to [lo, hi]."""
    merged = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _host_spans(planes):
    """(window, spans on the harness's thread) from the host plane."""
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = _events(line)
            win = [e for e in evs if e[0] == WINDOW]
            if win:
                return win[0], evs
    raise ValueError(f"no host span {WINDOW!r} in the trace")


def device_lag(spans, modules) -> float:
    """How far, at least, the device's events read early against the
    host's clock: no program starts before the host call that launched it
    (``EXECUTE``), and the ``i``-th such call on the harness's thread
    launches the ``i``-th program.  0 where the counts differ."""
    launches = sorted(a for n, a, _ in spans if n.startswith(EXECUTE))
    starts = sorted(a for _, a, _ in modules)
    if len(launches) != len(starts):
        return 0.0
    return max([0.0] + [h - d for h, d in zip(launches, starts)])


def device_lines(plane, spans):
    """(events by line name, shift): a device plane's events moved later
    by its ``device_lag`` against the host ``spans``."""
    lines = {ln.name: _events(ln) for ln in plane.lines}
    lag = device_lag(spans, lines.get(MODULES_LINE, []))
    return {name: [(n, a + lag, b + lag) for n, a, b in evs]
            for name, evs in lines.items()}, lag


def _label(spans, t):
    """What the host was doing at time ``t``: the shortest covering span,
    under its ``generate`` span."""
    cover = [s for s in spans if s[1] <= t < s[2] and s[0] != WINDOW]
    if not cover:
        return "between batches"
    inner = min(cover, key=lambda s: s[2] - s[1])
    batch = [s for s in cover if s[0].startswith(BATCH_PREFIX)]
    if batch and inner is not batch[0]:
        return f"{batch[0][0]} / {inner[0]}"
    return inner[0]


def reduce_planes(planes, chips: int = 1) -> dict:
    planes = list(planes)
    (_, w0, w1), spans = _host_spans(planes)
    batch_spans = [s for s in spans
                   if s[0].startswith(BATCH_PREFIX) and w0 <= s[1] < w1]
    devices = sorted(
        ((int(DEVICE_PLANE.match(p.name).group(1)), p) for p in planes
         if DEVICE_PLANE.match(p.name)), key=lambda x: x[0])[:chips]
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    busy_total, op_time = 0.0, defaultdict(float)
    batches, gaps, lag0 = None, [], 0.0
    for k, (_, plane) in enumerate(devices):
        lines, lag = device_lines(plane, spans)
        ops = [e for e in lines.get(OPS_LINE, []) if e[2] > w0 and e[1] < w1]
        busy = _union([(a, b) for _, a, b in ops], w0, w1)
        busy_total += sum(b - a for a, b in busy)
        for name, a, b in ops:
            op_time[name] += min(b, w1) - max(a, w0)
        if k == 0:
            lag0 = lag
            batches = _batch_programs(batch_spans, lines.get(MODULES_LINE, []))
            edges = [w0] + [x for iv in busy for x in iv] + [w1]
            gaps = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": busy_total / len(devices) / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "batches": batches,
        "device_lag_s": lag0 / 1e9,
        "breakdown": {
            "device_ops": [[n, t / len(devices) / 1e9] for n, t in
                           sorted(op_time.items(), key=lambda x: -x[1])[:TOP]],
            "idle_gaps": [[_label(spans, (a + b) / 2), (b - a) / 1e9]
                          for a, b in gaps[:TOP]],
        },
    }


def _batch_programs(batch_spans, modules):
    """Per ``generate`` span: device seconds of the decode scan (the
    program that ends last) and of everything before it."""
    out = []
    for _, s0, s1 in sorted(batch_spans, key=lambda s: s[1]):
        mine = [m for m in modules if s0 <= m[1] < s1]
        if not mine:
            out.append(None)
            continue
        last = max(mine, key=lambda m: m[2])
        out.append({
            "decode_s": (last[2] - last[1]) / 1e9,
            "prefill_s": sum(m[2] - m[1] for m in mine if m is not last) / 1e9,
            "decode_program": last[0],
        })
    return out


def load_planes(path: str) -> list:
    """The planes of the one ``.xplane.pb`` that a profiler session wrote
    under ``path``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one trace under {path}, found {files}")
    return list(ProfileData.from_file(files[0]).planes)
