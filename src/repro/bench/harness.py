"""Real wall-clock benchmarking of the JAX serving engine.

The paper's "custom inference benchmarking framework": sweep (ii, oo, bb),
run each combination ``reps`` times, record tokens/sec.  It builds the
smoke-size configs; each row's ``acc`` names the device kind that ran it
(``jax.devices()[0].device_kind``), so CPU rows never pass for chip
rows.  Output rows feed the same ALA pipeline as simulator data —
the framework is agnostic to where thpt came from.
"""
from __future__ import annotations

import itertools
from typing import Dict, Iterable, List, Optional, Sequence

import jax
import numpy as np

from repro.configs import get_smoke_config
from repro.core.dataset import Dataset
from repro.inference.engine import ServingEngine
from repro.models.transformer import Model

CPU_GRID_II = (16, 32, 64)
CPU_GRID_OO = (8, 16)
CPU_GRID_BB = (1, 2, 4, 8, 16)


def measure_arch(arch: str, grid_ii: Optional[Sequence[int]] = None,
                 grid_oo: Optional[Sequence[int]] = None,
                 grid_bb: Optional[Sequence[int]] = None,
                 reps: int = 2, seed: int = 0) -> Dataset:
    """Sweep the engine over a grid; ``None`` grids fall back to the CPU
    smoke defaults, so CLI overrides (``benchmarks/run.py --grid-ii ...``)
    and TPU-scale sweeps share this one code path."""
    grid_ii = CPU_GRID_II if grid_ii is None else tuple(grid_ii)
    grid_oo = CPU_GRID_OO if grid_oo is None else tuple(grid_oo)
    grid_bb = CPU_GRID_BB if grid_bb is None else tuple(grid_bb)
    cfg = get_smoke_config(arch)
    model = Model(cfg)
    params = model.init(jax.random.key(seed))
    engine = ServingEngine(model, params)
    acc = jax.devices()[0].device_kind
    rows: List[Dict] = []
    for ii, oo, bb in itertools.product(grid_ii, grid_oo, grid_bb):
        for r in engine.measure_throughput(ii, oo, bb, reps=reps,
                                           seed=seed):
            rows.append(dict(model=arch, acc=acc, acc_count=1,
                             back="repro-jax", prec="fp32", mode="serve",
                             ii=r["ii"], oo=r["oo"], bb=r["bb"],
                             thpt=r["thpt"]))
    return Dataset.from_rows(rows)
