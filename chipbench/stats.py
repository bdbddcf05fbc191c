"""End-to-end metrics of a window, from its batch records.

A record is one ``ServingEngine.generate`` call of the window: its batch
size, prompt and output lengths, when it was handed over and when it
returned (seconds from the window's start, on the harness's clock), and
the program's own ``prefill_s`` and ``decode_s`` for it, and the counters
the program reported for the call (``GenerationResult.counters``, where the
program has them).
"""
from __future__ import annotations

import dataclasses
from typing import Sequence


@dataclasses.dataclass
class BatchRecord:
    batch: int
    prompt_len: int
    output_len: int
    start_s: float        # hand-off to generate
    end_s: float          # generate returned
    prefill_s: float      # prefill and the first token, as the program times it
    decode_s: float       # the other output_len - 1 tokens
    counters: dict = dataclasses.field(default_factory=dict)


def output_tok_s(records: Sequence[BatchRecord]) -> float:
    """Output tokens of every batch of the window over the time from the
    window's start to the last completion."""
    return (sum(r.batch * r.output_len for r in records)
            / max(r.end_s for r in records))


def itl_ms(records: Sequence[BatchRecord]) -> float:
    """Mean gap between output tokens: the program's decode time over
    decode steps."""
    return (1e3 * sum(r.decode_s for r in records)
            / sum(r.output_len - 1 for r in records))


def span_excess_s(records: Sequence[BatchRecord]) -> float:
    """How far the program's two spans exceed the harness's own wall time
    for the call, at most; above 0, work was moved out of what the harness
    sees."""
    return max(r.prefill_s + r.decode_s - (r.end_s - r.start_s)
               for r in records)
