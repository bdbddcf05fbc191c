"""Runtime tracers for the contracts static analysis can't see.

``jit-in-loop`` catches the *syntactic* recompile hazard; whether the
pow2 shape-bucketing contract actually holds at runtime (PR 5: growing
online data reuses XLA compiles after warmup) is only observable by
counting compilations.  :func:`assert_max_compiles` is that gate — a
context manager counting XLA compiles via ``jax.monitoring`` events,
used by the online/fleet smoke benchmarks to assert that post-warmup
epochs stay inside a fixed compile budget (the count is recorded in
the BENCH artifact).

Counting mechanics: a single process-global listener (registered
lazily, never unregistered — ``jax.monitoring`` only offers clear-all,
which would nuke other listeners) accumulates two monotone counters,
and each context manager diffs them around its block:

  * ``/jax/core/compile/backend_compile_duration`` — one event per
    actual XLA backend compile.
  * ``/jax/core/compile/jaxpr_to_mlir_module_duration`` — one event
    per lowering.  This is the fallback count: a persistent
    compilation cache can swallow the backend compile, but every new
    (program, shape) still traces and lowers, which is exactly the
    shape-bucketing violation the gate exists to catch.

``CompileReport.count`` is the max of the two — either event firing
means a shape bucket the warmup didn't cover.

:func:`nan_guard` is the second runtime tracer: fit/predict outputs
must never carry NaN (Alg 7/8 would silently propagate it into
confidence scores); +/-inf stays allowed by default because the
degenerate-log sentinel (d_min=inf, confidence=0.0) is a documented
output.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Iterator, Optional

import numpy as np

__all__ = [
    "CompileReport", "CompileBudgetExceeded", "assert_max_compiles",
    "count_compiles", "nan_guard",
]

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class CompileBudgetExceeded(AssertionError):
    """Raised when a block compiles more XLA programs than budgeted."""


class _CompileCounter:
    __slots__ = ("n_compiles", "n_lowerings")

    def __init__(self) -> None:
        self.n_compiles = 0
        self.n_lowerings = 0


_COUNTER: Optional[_CompileCounter] = None


def _get_counter() -> _CompileCounter:
    global _COUNTER
    if _COUNTER is None:
        from jax import monitoring

        counter = _CompileCounter()

        def _on_duration(key: str, duration: float, **kw) -> None:
            if key == _COMPILE_EVENT:
                counter.n_compiles += 1
            elif key == _LOWERING_EVENT:
                counter.n_lowerings += 1

        monitoring.register_event_duration_secs_listener(_on_duration)
        _COUNTER = counter
    return _COUNTER


@dataclasses.dataclass
class CompileReport:
    """What compiled inside an ``assert_max_compiles`` block."""
    limit: Optional[int] = None
    label: str = ""
    n_compiles: int = 0            # backend compiles (cache misses)
    n_lowerings: int = 0           # jaxpr->MLIR lowerings

    @property
    def count(self) -> int:
        """Effective compile count for the gate: max of backend
        compiles and lowerings (see module docstring)."""
        return max(self.n_compiles, self.n_lowerings)


@contextlib.contextmanager
def assert_max_compiles(n: Optional[int],
                        label: str = "") -> Iterator[CompileReport]:
    """Gate a block to at most ``n`` XLA compilations.

    Yields a :class:`CompileReport` that fills in on exit; raises
    :class:`CompileBudgetExceeded` when the block compiled (or
    re-lowered) more than ``n`` programs.  ``n=None`` counts without
    asserting.
    """
    counter = _get_counter()
    report = CompileReport(limit=n, label=label)
    c0, l0 = counter.n_compiles, counter.n_lowerings
    try:
        yield report
    finally:
        report.n_compiles = counter.n_compiles - c0
        report.n_lowerings = counter.n_lowerings - l0
    if n is not None and report.count > n:
        where = f" [{label}]" if label else ""
        raise CompileBudgetExceeded(
            f"compile budget exceeded{where}: {report.count} > {n} "
            f"(backend_compiles={report.n_compiles}, "
            f"lowerings={report.n_lowerings}) — a shape bucket the "
            f"warmup didn't cover, or jit built inside the hot path")


def count_compiles(label: str = ""):
    """``assert_max_compiles(None)``: count without asserting."""
    return assert_max_compiles(None, label=label)


def _first_bad_leaf(obj, path: str, allow_inf: bool):
    """Depth-first search for a NaN (or inf) leaf; returns its path."""
    if isinstance(obj, dict):
        for k, v in obj.items():
            bad = _first_bad_leaf(v, f"{path}[{k!r}]", allow_inf)
            if bad:
                return bad
        return None
    if isinstance(obj, (list, tuple)):
        for i, v in enumerate(obj):
            bad = _first_bad_leaf(v, f"{path}[{i}]", allow_inf)
            if bad:
                return bad
        return None
    try:
        arr = np.asarray(obj)
    except Exception:
        return None
    if arr.dtype.kind not in "fc":
        return None
    if np.isnan(arr).any():
        return f"{path}: NaN"
    if not allow_inf and np.isinf(arr).any():
        return f"{path}: inf"
    return None


def nan_guard(fn=None, *, label: Optional[str] = None,
              allow_inf: bool = True):
    """Wrap a fit/predict callable so non-finite outputs raise loudly.

    ``FloatingPointError`` names the function and the offending output
    leaf.  ``allow_inf=True`` by default: the Alg 8 degenerate-log
    sentinel legitimately returns (d_min=inf, confidence=0.0); NaN is
    never legitimate.  Usable bare (``@nan_guard``), with options
    (``@nan_guard(allow_inf=False)``), or inline
    (``nan_guard(eng.predict, label="online.predict")(rows)``).
    """
    def deco(f):
        name = label or getattr(f, "__qualname__", repr(f))

        @functools.wraps(f)
        def wrapped(*args, **kwargs):
            out = f(*args, **kwargs)
            bad = _first_bad_leaf(out, "output", allow_inf)
            if bad:
                raise FloatingPointError(
                    f"nan_guard[{name}]: non-finite fit output at "
                    f"{bad}")
            return out

        return wrapped

    return deco(fn) if fn is not None else deco
