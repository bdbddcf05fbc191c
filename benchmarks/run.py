"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (us_per_call = wall time of
the operation benchmarked; derived = the figure's headline metric) and
writes a JSON report to results/bench_report.json for EXPERIMENTS.md.

  fig2_exponential_fits   — Alg 2 database fit quality on the in-house grid
  fig3_param_prediction   — Alg 3 extrapolation to held-out (ii,oo) groups
  fig6_rq1_training_sets  — RQ1: 4 training-set designs -> error dists
  fig7_rq2_baselines      — RQ2: ALA vs LR/XGB/RF/GB (+ SA trajectory,
                            runtime scaling)
  fig8_rq3_model_zoo      — RQ3: per-architecture error across the 10-arch
                            suite dataset
  table1_rq4_uncertainty  — RQ4: predicted error / confidence / actual,
                            incl. the hardware-mismatch case
  perf_vmapped_fit        — beyond-paper: batched-LM fit vs scalar numpy
  perf_kernels            — kernel oracle timings (CPU reference path)
  sa_engine               — legacy serial SA vs the batched K-chain engine
                            (equal proposal budget; emits BENCH_sa.json)
  uncertainty_engine      — serial Alg 7+8 loop vs the batched SubsetBank
                            kernel at equal query count (>= 64 queries x
                            200 subsets; emits BENCH_uncertainty.json)
  serving_engine          — trace-driven continuous-batching fleet sim:
                            ALA-in-the-loop autoscaling vs the static-bb
                            baseline across >= 3 archs x arrival traces
                            (emits BENCH_serving.json; --smoke for CI)
  fleet_engine            — fleet-scale vectorized serving engine on a
                            3-tenant diurnal/flash workload (100k+
                            requests full-size) vs the heap engine, with
                            a hard >=50x events/s gate (emits
                            BENCH_fleet.json; --smoke for CI)
  online_engine           — epoch-by-epoch trace feed through the
                            OnlineALA incremental-refit engine vs a
                            from-scratch fit+fit_uncertainty on the
                            concatenated data every epoch: prediction
                            parity + speedup (emits BENCH_online.json;
                            --smoke for CI)
  transfer_engine         — cross-hardware ALA transfer: per-target
                            medAPE via the analytic roofline scaler,
                            strict cross- vs same-hardware confidence
                            ordering, and a mixed TPU+GPU fleet where
                            hardware-aware placement beats blind
                            (emits BENCH_transfer.json; --smoke for CI)
  obs_engine              — observability layer gates: <5% tracing
                            overhead at sample_rate=1.0 on the fleet
                            engine, heap/fleet span-statistic parity,
                            mergeable histogram shards, a monotone
                            confidence reliability curve from the
                            calibration audit, and a Perfetto-loadable
                            chrome trace (emits BENCH_obs.json;
                            --smoke for CI)
  wallclock_engine        — real JAX engine sweep via bench.harness
                            (honors --grid-ii/--grid-oo/--grid-bb/--reps)

Run everything:          PYTHONPATH=src python benchmarks/run.py
Run one benchmark:       PYTHONPATH=src python benchmarks/run.py sa_engine
Smoke-size a run:        PYTHONPATH=src python benchmarks/run.py \
                             serving_engine --smoke
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

RESULTS = pathlib.Path(__file__).resolve().parent.parent / "results"
REPORT: dict = {}
_ROWS: list = []
# CLI-provided knobs (argparse fills these in main); benchmarks read them
# so smoke runs and TPU runs share one code path
OPTS: dict = {"smoke": False, "grid_ii": None, "grid_oo": None,
              "grid_bb": None, "reps": None}


def _emit(name: str, us_per_call: float, derived: str):
    print(f"{name},{us_per_call:.1f},{derived}")
    _ROWS.append((name, us_per_call, derived))


def _timed(fn, *a, **kw):
    t0 = time.perf_counter()
    out = fn(*a, **kw)
    return out, (time.perf_counter() - t0) * 1e6


def _provenance(seed=None, **extra) -> dict:
    """Run provenance stamped into every results/BENCH_*.json: git SHA,
    JAX version + backend/device, wall-clock (UTC), and the scenario
    seed — enough to answer "which code, which machine, which run
    produced this number" from the artifact alone."""
    import datetime
    import platform
    import subprocess
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=pathlib.Path(__file__).resolve().parent,
            capture_output=True, text=True, timeout=10).stdout.strip()
    except Exception:
        sha = ""
    prov = {
        "git_sha": sha or "unknown",
        "python": platform.python_version(),
        "numpy": np.__version__,
        "wall_clock_utc": datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds"),
    }
    try:
        import jax
        prov["jax"] = jax.__version__
        prov["backend"] = jax.default_backend()
        prov["device"] = jax.devices()[0].device_kind
    except Exception:
        prov["jax"] = None
    if seed is not None:
        prov["seed"] = seed
    prov.update(extra)
    return prov


def _write_bench(filename: str, payload: dict, seed=None) -> None:
    """The one way benchmark artifacts reach results/: provenance
    stamped, parent dir ensured, stable JSON shape."""
    payload = dict(payload)
    payload["provenance"] = _provenance(seed=seed)
    RESULTS.mkdir(parents=True, exist_ok=True)
    (RESULTS / filename).write_text(json.dumps(payload, indent=1))


# ---------------------------------------------------------------------------
def _data():
    from repro.bench.datasets import load_or_make, train_test_split
    ds = load_or_make("inhouse")
    return ds, train_test_split(ds, test_frac=0.3, seed=0)


def fig2_exponential_fits():
    from repro.core.ala import ALA
    ds, (train, test) = _data()
    ala, us = _timed(lambda: ALA().fit(*train.workload))
    in_err = ala.score(*train.workload)
    REPORT["fig2"] = {"db_groups": len(ala.db), "train_median_ape": in_err,
                      "fit_db_s": ala.timings["fit_db_s"],
                      "fit_predictor_s": ala.timings["fit_predictor_s"]}
    _emit("fig2_exponential_fits", us,
          f"groups={len(ala.db)};train_medAPE={in_err:.2f}%")
    return ala, train, test


def fig3_param_prediction():
    """Hold out entire (ii,oo) groups; ML must extrapolate their params."""
    from repro.core.ala import ALA
    from repro.core.annealing import median_ape
    ds, _ = _data()
    ii, oo, bb, thpt = ds.workload
    rng = np.random.default_rng(7)
    pairs = np.unique(np.stack([ii, oo], 1), axis=0)
    held = pairs[rng.choice(len(pairs), size=max(4, len(pairs) // 5),
                            replace=False)]
    hmask = np.zeros(len(ii), bool)
    for p in held:
        hmask |= (ii == p[0]) & (oo == p[1])
    ala = ALA().fit(ii[~hmask], oo[~hmask], bb[~hmask], thpt[~hmask])
    (pred, us) = _timed(ala.predict, ii[hmask], oo[hmask], bb[hmask])
    err = median_ape(thpt[hmask], pred)
    REPORT["fig3"] = {"held_groups": len(held), "unseen_median_ape": err}
    _emit("fig3_param_prediction", us,
          f"unseen_pairs_medAPE={err:.2f}%")


def fig6_rq1_training_sets():
    from repro.core.ala import ALA
    from repro.bench.datasets import INHOUSE_BB, INHOUSE_II, INHOUSE_OO
    ds, _ = _data()
    ii, oo, bb, thpt = ds.workload
    rng = np.random.default_rng(0)

    def experiment_masks():
        # Exp1: broad balanced coverage (uniform 50% of rows)
        e1 = rng.random(len(ii)) < 0.5
        # Exp2: dense clusters spread across the range, all bb incl. large
        # (paper: "densely clustered metrics within specific regions")
        e2 = (np.isin(ii, (INHOUSE_II[0], INHOUSE_II[1], INHOUSE_II[4],
                           INHOUSE_II[7]))
              & np.isin(oo, (INHOUSE_OO[0], INHOUSE_OO[1], INHOUSE_OO[4],
                             INHOUSE_OO[5])))
        # Exp3: no large batch sizes (bb <= 32)
        e3 = bb <= 32
        # Exp4: sparse across the whole range (every other value per dim)
        e4 = (np.isin(ii, INHOUSE_II[::2]) & np.isin(oo, INHOUSE_OO[::2])
              & np.isin(bb, INHOUSE_BB[::2]))
        return {"exp1_broad": e1, "exp2_dense_clusters": e2,
                "exp3_no_large_bb": e3, "exp4_sparse": e4}

    out = {}
    for name, m in experiment_masks().items():
        ala, us = _timed(
            lambda m=m: ALA().fit(ii[m], oo[m], bb[m], thpt[m]))
        pred = ala.predict(ii[~m], oo[~m], bb[~m])
        ape = np.abs(pred - thpt[~m]) / np.maximum(np.abs(thpt[~m]), 1e-9) \
            * 100.0
        stats = {"median": float(np.median(ape)),
                 "p90": float(np.percentile(ape, 90)),
                 "mean": float(ape.mean()), "n_train": int(m.sum()),
                 "hist": np.histogram(np.clip(ape, 0, 100),
                                      bins=20)[0].tolist()}
        out[name] = stats
        _emit(f"fig6_rq1_{name}", us,
              f"medAPE={stats['median']:.2f}%;p90={stats['p90']:.1f}%")
    REPORT["fig6_rq1"] = out


def fig7_rq2_baselines(n_sa_iters: int = 40):
    from repro.core.ala import ALA
    from repro.core.annealing import (SAConfig, anneal, median_ape,
                                      subset_mask)
    from repro.core.baselines import make_baselines
    ds, (train, test) = _data()

    # (a) headline comparison on the train/test split
    comp = {}
    ala, us_ala = _timed(lambda: ALA().fit(*train.workload))
    comp["ALA"] = {"median_ape": ala.score(*test.workload),
                   "train_us": us_ala}
    for name, bl in make_baselines().items():
        _, us = _timed(bl.fit, *train.workload)
        e = median_ape(test.workload[3], bl.predict(*test.workload[:3]))
        comp[name] = {"median_ape": e, "train_us": us}
        _emit(f"fig7_rq2_{name}", us, f"medAPE={e:.2f}%")
    _emit("fig7_rq2_ALA", us_ala,
          f"medAPE={comp['ALA']['median_ape']:.2f}%")

    # (b) error over SA iterations: ALA vs baselines on the same subsets
    sa_cfg = SAConfig(n_iters=n_sa_iters, seed=0,
                      gbt_kw=dict(n_estimators=40, learning_rate=0.2,
                                  max_depth=4))
    log, us_sa = _timed(lambda: anneal(train.workload, test.workload,
                                       sa_cfg))
    ii, oo, bb, thpt = train.workload
    tii, too, tbb, tthpt = test.workload
    traj = {"ALA": list(map(float, log.errors))}
    for name, bl in make_baselines().items():
        errs = []
        for s in log.subsets:
            m = subset_mask(ii, oo, bb, s)
            if m.sum() < 4:
                errs.append(100.0)
                continue
            bl.fit(ii[m], oo[m], bb[m], thpt[m])
            errs.append(float(median_ape(tthpt,
                                         bl.predict(tii, too, tbb))))
        traj[name] = errs
    summary = {k: {"median": float(np.median(v)),
                   "final": float(v[-1])} for k, v in traj.items()}
    REPORT["fig7_rq2"] = {"comparison": comp,
                          "sa_median_by_method": summary,
                          "sa_trajectory": traj,
                          "sa_us": us_sa, "n_iters": n_sa_iters}
    _emit("fig7_rq2_sa_trajectory", us_sa,
          ";".join(f"{k}={v['median']:.1f}%" for k, v in summary.items()))
    return log


def fig8_rq3_model_zoo():
    from repro.core.registry import ModelRegistry
    from repro.bench.datasets import load_or_make, train_test_split
    suite = load_or_make("suite")
    out = {}
    us_total = 0.0
    for arch in np.unique(suite["model"]):
        sub = suite.filter(model=arch)
        tr, te = train_test_split(sub, 0.3, seed=1)
        reg = ModelRegistry()
        _, us = _timed(reg.fit, tr, n_estimators=60, learning_rate=0.15)
        us_total += us
        pred = reg.predict(te)
        ape = np.abs(pred - te["thpt"]) / np.maximum(te["thpt"], 1e-9) * 100
        out[str(arch)] = {"median": float(np.median(ape)),
                          "p90": float(np.percentile(ape, 90)),
                          "n": int(len(te))}
    REPORT["fig8_rq3"] = out
    worst = max(out.items(), key=lambda kv: kv[1]["median"])
    _emit("fig8_rq3_model_zoo", us_total,
          f"archs={len(out)};median_range="
          f"{min(v['median'] for v in out.values()):.1f}-"
          f"{worst[1]['median']:.1f}%;worst={worst[0]}")


def table1_rq4_uncertainty():
    from repro.core.ala import ALA
    from repro.core.annealing import SAConfig
    from repro.bench.datasets import load_or_make
    ds, (train, test) = _data()
    ala = ALA()
    ala.cfg.sa = SAConfig(n_iters=40, seed=3,
                          gbt_kw=dict(n_estimators=40, learning_rate=0.2,
                                      max_depth=4))
    ala.fit(*train.workload)
    ala.explore(test.workload)
    ala.fit_error()

    rows = {}

    def case(name, data, actual_err):
        (pe, conf), us = _timed(ala.estimate, data)
        rows[name] = {"predicted_error": float(pe),
                      "confidence": float(conf),
                      "actual_error": float(actual_err)}
        _emit(f"table1_rq4_{name}", us,
              f"pred={pe:.2f}%;conf={conf:.2f};actual={actual_err:.2f}%")

    # (1) same-model held-out subset (paper: "LLAMA Subset")
    case("llama_subset", test.workload, ala.score(*test.workload))

    # (2) different model family, same hardware (paper: Mistral 7B)
    suite = load_or_make("suite")
    other = suite.filter(model="llama3.2-3b", back="vllm-jax")
    ow = other.workload
    case("other_model_llama3.2-3b", ow, ala.score(*ow))

    # (3) hardware mismatch (paper: Qwen2-7B on Intel PVC)
    mis = load_or_make("mismatch")
    mw = mis.workload
    case("hw_mismatch_qwen_legacy", mw, ala.score(*mw))

    REPORT["table1_rq4"] = rows


def perf_vmapped_fit():
    """Beyond-paper: one vmapped-LM XLA call vs a python loop of scalar
    numpy LM fits (the scipy-curve_fit-style baseline)."""
    from repro.core.expmodel import exp_model, initial_params
    from repro.core.fit import fit_exponential_groups, fit_exponential_numpy
    rng = np.random.default_rng(0)
    groups = []
    for g in range(512):
        bbv = np.array([1, 2, 4, 8, 16, 32, 64, 128, 256], float)
        a, b = rng.uniform(100, 5000), rng.uniform(0.01, 0.3)
        c = rng.uniform(500, 20000)
        y = exp_model(bbv, a, b, min(c + a, 30000)) \
            * rng.lognormal(0, 0.03, len(bbv))
        groups.append((bbv, y, initial_params(bbv, y)))
    fit_exponential_groups(groups[:2])       # warm up compile
    _, us_batch = _timed(fit_exponential_groups, groups)
    t0 = time.perf_counter()
    for g in groups:
        fit_exponential_numpy(*g, iters=60)
    us_loop = (time.perf_counter() - t0) * 1e6
    REPORT["perf_vmapped_fit"] = {"groups": len(groups),
                                  "batched_us": us_batch,
                                  "loop_us": us_loop,
                                  "speedup": us_loop / max(us_batch, 1e-9)}
    _emit("perf_vmapped_fit", us_batch,
          f"speedup_vs_scalar_loop={us_loop / max(us_batch, 1e-9):.1f}x")


def perf_kernels():
    import jax
    import jax.numpy as jnp
    from repro.kernels.flash_attention import ops as fa
    from repro.kernels.decode_attention import ops as da
    from repro.kernels.rmsnorm import ops as rms
    from repro.kernels.gbt_hist import ops as gh

    key = jax.random.key(0)
    q = jax.random.normal(key, (1, 1024, 8, 64), jnp.float32)
    k = jax.random.normal(key, (1, 1024, 2, 64), jnp.float32)
    x = jax.random.normal(key, (4096, 1024), jnp.float32)
    scale = jnp.ones((1024,))
    bins = jax.random.randint(key, (8192, 8), 0, 64)
    g = jax.random.normal(key, (8192,))
    qd = jax.random.normal(key, (8, 16, 64), jnp.float32)
    kd = jax.random.normal(key, (8, 2048, 4, 64), jnp.float32)

    cases = {
        "flash_attention_1k": lambda: fa.flash_attention(
            q, k, k, force="ref").block_until_ready(),
        "decode_attention_2k": lambda: da.decode_attention(
            qd, kd, kd, jnp.array(2000), force="ref").block_until_ready(),
        "rmsnorm_4kx1k": lambda: rms.rmsnorm(
            x, scale, force="ref").block_until_ready(),
        "gbt_hist_8kx8": lambda: gh.build_histograms(
            bins, g, jnp.abs(g), n_bins=64,
            force="ref").block_until_ready(),
    }
    out = {}
    for name, fn in cases.items():
        fn()  # warmup/compile
        _, us = _timed(fn)
        out[name] = us
        _emit(f"perf_kernel_{name}", us, "cpu_reference_path")
    REPORT["perf_kernels_cpu_ref_us"] = out


def sa_engine(n_proposals: int = 60, n_chains: int = 4):
    """Legacy serial SA vs the batched K-chain engine at an equal
    proposal budget.  Both engines score subsets with the same inner
    GBT; the batched one wins on architecture: a fixed-shape masked LM
    solve (one XLA compile per process instead of one per padded subset
    shape), a shared fingerprint cache across chains, vectorized subset
    masking, and candidate/output-joint GBT growth.  Writes
    results/BENCH_sa.json."""
    from repro.core.annealing import SAConfig, anneal, anneal_batched
    ds, (train, test) = _data()
    gbt_kw = dict(n_estimators=40, learning_rate=0.2, max_depth=4)

    cfg_legacy = SAConfig(n_iters=n_proposals, seed=0, gbt_kw=gbt_kw)
    log_l, us_l = _timed(lambda: anneal(train.workload, test.workload,
                                        cfg_legacy))

    cfg_batched = SAConfig(n_iters=n_proposals // n_chains, seed=0,
                           gbt_kw=gbt_kw, n_chains=n_chains)
    log_b, us_b = _timed(lambda: anneal_batched(train.workload,
                                                test.workload, cfg_batched))

    speedup = us_l / max(us_b, 1e-9)
    out = {
        "n_proposals": n_proposals,
        "n_chains": n_chains,
        # best_error: what each engine reports (legacy = final chain
        # state; batched = global min).  best_ape: min over every logged
        # evaluation — the like-for-like quality comparison.
        "legacy": {"wall_s": us_l / 1e6,
                   "best_error": float(log_l.best_error),
                   "best_ape": float(min(log_l.errors)),
                   "n_evals": len(log_l.errors)},
        "batched": {"wall_s": us_b / 1e6,
                    "best_error": float(log_b.best_error),
                    "best_ape": float(min(log_b.errors)),
                    "n_evals": len(log_b.errors)},
        "speedup": speedup,
        "equal_or_better_ape": bool(min(log_b.errors) <= min(log_l.errors)),
    }
    REPORT["sa_engine"] = out
    _write_bench("BENCH_sa.json", out, seed=0)
    _emit("sa_engine_legacy", us_l, f"best_medAPE={log_l.best_error:.2f}%")
    _emit("sa_engine_batched", us_b,
          f"best_medAPE={log_b.best_error:.2f}%;speedup={speedup:.1f}x")
    return out


def uncertainty_engine(n_queries: int = 64, n_subsets: int = 200,
                       n_chains: int = 4):
    """Serial Alg 7+8 (one query at a time through the numpy reference)
    vs the batched engine (whole fleet through the jitted PackedForest
    + SubsetBank kernel) at equal query count.  The two paths share the
    fixed-bin contract, so results must agree to <= 1e-6.  Writes
    results/BENCH_uncertainty.json."""
    from repro.core.ala import ALA
    from repro.core.annealing import SAConfig
    ds, (train, test) = _data()

    # an SA log with >= n_subsets entries (chains + anchor + K*iters)
    n_iters = -(-(n_subsets - n_chains - 1) // n_chains)
    ala = ALA()
    ala.cfg.sa = SAConfig(n_iters=n_iters, seed=0, n_chains=n_chains,
                          gbt_kw=dict(n_estimators=30, learning_rate=0.2,
                                      max_depth=4))
    ala.fit(*train.workload)
    ala.explore(test.workload)
    ala.fit_error()
    bank = ala.bank(max_subsets=n_subsets)

    # fleet of query workloads: random row-subsets of the held-out split
    rng = np.random.default_rng(0)
    tw = test.workload
    queries = []
    for _ in range(n_queries):
        m = rng.random(len(tw[0])) < 0.6
        if m.sum() < 2:
            m[:2] = True
        queries.append(tuple(v[m] for v in tw))

    ala.estimate_batch(queries)     # warm up the two jitted shapes once
    (eb, db_, cb), us_b = _timed(ala.estimate_batch, queries)

    def serial():
        es, dss, cs = [], [], []
        for q in queries:
            e, d, c = ala.estimate_batch([q], backend="numpy")
            es.append(e[0]), dss.append(d[0]), cs.append(c[0])
        return np.asarray(es), np.asarray(dss), np.asarray(cs)

    (es, ds_, cs), us_s = _timed(serial)

    speedup = us_s / max(us_b, 1e-9)
    max_diff = float(max(np.abs(eb - es).max(), np.abs(db_ - ds_).max(),
                         np.abs(cb - cs).max()))
    out = {
        "n_queries": n_queries,
        "n_subsets": int(bank.n_subsets),
        "n_valid_subsets": int(bank.valid.sum()),
        "serial": {"wall_s": us_s / 1e6},
        "batched": {"wall_s": us_b / 1e6},
        "speedup": speedup,
        "max_abs_diff": max_diff,
        "parity_ok": bool(max_diff <= 1e-6),
        "confidence_range": [float(cb.min()), float(cb.max())],
        "predicted_error_range": [float(eb.min()), float(eb.max())],
    }
    REPORT["uncertainty_engine"] = out
    _write_bench("BENCH_uncertainty.json", out, seed=0)
    _emit("uncertainty_engine_serial", us_s, f"queries={n_queries}")
    _emit("uncertainty_engine_batched", us_b,
          f"speedup={speedup:.1f}x;max_abs_diff={max_diff:.2e}")
    return out


def serving_engine(smoke=None, ttft_slo_s: float = 2.0):
    """Trace-driven continuous-batching fleet sim: ALA-in-the-loop
    autoscaling vs a static-bb single-replica baseline, swept over
    arrival processes x trace shapes x >= 3 archs.  Per arch it also
    round-trips the simulated steady-state windows through the adapter
    into a registry fit.  Writes results/BENCH_serving.json."""
    import itertools
    from repro.configs import get_config
    from repro.core.ala import ALA
    from repro.core.annealing import SAConfig
    from repro.core.registry import ModelRegistry
    from repro.perfmodel.simulator import (ServingSetup, sample_throughput,
                                           throughput)
    from repro.perfmodel.hardware import TPU_V5E
    from repro.serving.adapter import windows_to_dataset
    from repro.serving.autoscaler import ALAAutoscaler, StaticPolicy
    from repro.serving.simulator import SimConfig, simulate
    from repro.serving.traces import TraceConfig, make_trace, mix

    smoke = OPTS["smoke"] if smoke is None else smoke
    archs = ("llama3.1-8b",) if smoke else (
        "llama3.1-8b", "qwen2.5-32b", "phi3.5-moe-42b-a6.6b")
    horizon = 12.0 if smoke else 40.0
    shape = mix(("chat", 0.6), ("summarize", 0.2), ("generate", 0.2))
    # representative shape for calibrating arrival rates per arch
    REF_II, REF_OO = 512, 192
    grid = list(itertools.product(
        (128, 512, 2048) if smoke else (128, 256, 512, 1024, 2048),
        (64, 256) if smoke else (64, 128, 256, 512),
        (1, 4, 16, 64) if smoke else (1, 2, 4, 8, 16, 32, 64, 128)))
    sa_iters = 4 if smoke else 10

    report = {"smoke": bool(smoke), "ttft_slo_s": ttft_slo_s, "archs": {}}
    for arch in archs:
        cfg = get_config(arch)
        chips = 8 if cfg.param_count() > 1e10 else 4
        setup = ServingSetup(cfg=cfg, hw=TPU_V5E, chips=chips)

        # ALA trained on a static roofline grid (the PR-1..3 pipeline)
        rng = np.random.default_rng(0)
        rows = [(ii, oo, bb, t) for ii, oo, bb in grid
                for t in sample_throughput(setup, ii, oo, bb, 2, rng)]
        gi, go, gb, gt = map(np.asarray, zip(*rows))
        te = rng.random(len(gi)) < 0.3
        ala = ALA()
        ala.cfg.sa = SAConfig(n_iters=sa_iters, seed=0, n_chains=4,
                              gbt_kw=dict(n_estimators=30,
                                          learning_rate=0.2, max_depth=4))
        ala.fit(gi[~te], go[~te], gb[~te], gt[~te])
        ala.explore((gi[te], go[te], gb[te], gt[te]))
        ala.fit_error()

        # arrival rates sized off single-replica capacity: the baseline
        # replica saturates during bursts, so scaling has to pay off
        cap_req_s = throughput(setup, REF_II, REF_OO, 64) / REF_OO
        scenarios = {"poisson": TraceConfig(
            arrival="poisson", rate=1.2 * cap_req_s, horizon_s=horizon,
            shape_mix=shape, seed=11)}
        if not smoke:
            scenarios["mmpp"] = TraceConfig(
                arrival="mmpp", rate=0.6 * cap_req_s,
                burst_rate=2.4 * cap_req_s, horizon_s=horizon,
                shape_mix=shape, seed=13)
            scenarios["gamma"] = TraceConfig(
                arrival="gamma", rate=1.0 * cap_req_s, cv=3.0,
                horizon_s=horizon, shape_mix=shape, seed=17)

        sim_cfg = SimConfig(setup=setup, batch_cap=64, n_replicas=1,
                            max_replicas=6)
        arch_out = {"chips": chips, "scenarios": {}}
        events = wall = 0.0
        hits = {"static": 0, "ala": 0}
        total = 0
        adapter_res = None
        for sname, tc in scenarios.items():
            tr = make_trace(tc)
            runs = {}
            for pname, policy in (
                    ("static", StaticPolicy(n_replicas=1, batch_cap=64)),
                    ("ala", ALAAutoscaler(ala=ala, max_replicas=6))):
                res, us = _timed(simulate, tr, sim_cfg, policy)
                events += res.n_events
                wall += us / 1e6
                n_ok = sum(1 for r in res.records
                           if r.ttft_s <= ttft_slo_s)
                hits[pname] += n_ok
                runs[pname] = {
                    "slo_attainment": n_ok / max(len(res.records), 1),
                    "goodput_tok_s": res.goodput_tok_s,
                    # completed-only view: every request completes in the
                    # fault-free runs, and the committed numbers predate
                    # the shed-aware (inf-counting) default
                    "p95_ttft_s": res.ttft_percentile(95,
                                                      on_missing="drop"),
                    "replica_seconds": res.replica_seconds,
                    "completed": len(res.completed)}
                if pname == "ala":
                    adapter_res = res
            total += len(tr)
            arch_out["scenarios"][sname] = dict(
                n_requests=len(tr), **runs)

        # adapter round-trip: simulated windows -> Dataset -> registry fit
        ds = windows_to_dataset(adapter_res, setup, arch,
                                window_s=horizon / 8.0)
        reg = ModelRegistry().fit(ds, n_estimators=20)
        pred = reg.predict(ds)
        arch_out["adapter"] = {
            "rows": len(ds),
            "fit_finite": bool(np.isfinite(pred).all()),
            "median_ape": float(np.median(
                np.abs(pred - ds["thpt"])
                / np.maximum(ds["thpt"], 1e-9) * 100.0))}
        arch_out["events_per_sec"] = events / max(wall, 1e-9)
        arch_out["static_attainment"] = hits["static"] / max(total, 1)
        arch_out["ala_attainment"] = hits["ala"] / max(total, 1)
        arch_out["ala_ge_static"] = bool(
            arch_out["ala_attainment"] >= arch_out["static_attainment"])
        report["archs"][arch] = arch_out
        _emit(f"serving_engine_{arch}", wall * 1e6,
              f"evps={arch_out['events_per_sec']:.0f};"
              f"slo_ala={arch_out['ala_attainment']:.3f};"
              f"slo_static={arch_out['static_attainment']:.3f}")

    report["all_ala_ge_static"] = all(
        a["ala_ge_static"] for a in report["archs"].values())
    # smoke runs get their own artifact/report key so the CI command never
    # clobbers the committed full-run numbers
    key = "serving_engine_smoke" if smoke else "serving_engine"
    REPORT[key] = report
    _write_bench(f"BENCH_serving{'_smoke' if smoke else ''}.json", report,
                 seed=11)
    return report


def fleet_engine(smoke=None):
    """Fleet-scale vectorized serving engine: a 3-tenant diurnal/flash
    workload (100k+ requests in the full run) through the time-bucketed
    array engine, with an in-run heap-engine baseline on a trace slice
    and a hard events/s speedup gate vs the committed BENCH_serving
    heap numbers.  Writes results/BENCH_fleet.json."""
    from repro.configs import get_config
    from repro.perfmodel.simulator import ServingSetup
    from repro.perfmodel.hardware import TPU_V5E
    from repro.serving.simulator import SimConfig, simulate
    from repro.serving.traces import (FleetTraceConfig, TenantConfig,
                                      TraceConfig, make_fleet_trace, mix)
    from repro.staticcheck.tracers import assert_max_compiles

    smoke = OPTS["smoke"] if smoke is None else smoke
    # the committed full-run heap baseline (BENCH_serving.json): the
    # >=50x acceptance gate is anchored to its best arch
    heap_evps_recorded = 7684.5
    horizon = 60.0 if smoke else 2000.0
    setup = ServingSetup(cfg=get_config("llama3.1-8b"), hw=TPU_V5E,
                         chips=4)
    fcfg = FleetTraceConfig(tenants=(
        TenantConfig(name="chat",
                     trace=TraceConfig(arrival="poisson", rate=30.0,
                                       shape_mix=mix(("chat", 1.0))),
                     ttft_slo_s=1.5, diurnal_amp=0.4),
        TenantConfig(name="summarize",
                     trace=TraceConfig(arrival="gamma", rate=8.0, cv=2.0,
                                       shape_mix=mix(("summarize", 1.0))),
                     ttft_slo_s=8.0),
        TenantConfig(name="generate",
                     trace=TraceConfig(arrival="mmpp", rate=12.0,
                                       burst_rate=24.0,
                                       shape_mix=mix(("generate", 1.0))),
                     ttft_slo_s=4.0, flash_crowds=2, flash_mult=3.0,
                     flash_dur_s=15.0),
    ), horizon_s=horizon, seed=42)
    tr = make_fleet_trace(fcfg)
    if not smoke:
        assert len(tr) >= 100_000, f"scenario too small: {len(tr)}"

    cfg = SimConfig(setup=setup, batch_cap=64, n_replicas=8,
                    max_replicas=8, bucket_s=0.5)
    # best-of-2: the first run pays numpy/caching warm-up.  The timed
    # rerun is also the pow2 shape-bucketing gate: every shape bucket
    # was compiled by the warm run, so a steady-state replay may not
    # trigger a single XLA compile (smoke hard-gates; the full run
    # records the count in the artifact)
    res, us = _timed(simulate, tr, cfg, engine="fleet")
    with assert_max_compiles(0 if smoke else None,
                             label="fleet_engine post-warmup") as cgate:
        res, us2 = _timed(simulate, tr, cfg, engine="fleet")
    us = min(us, us2)
    evps = res.n_events / (us / 1e6)

    # same-machine heap baseline on a slice of the same workload (the
    # full heap run at this scale would take minutes)
    heap_slice = tr.slice(0.0, 20.0 if smoke else 60.0)
    href, hus = _timed(simulate, heap_slice, cfg, engine="heap")
    heap_evps = href.n_events / (hus / 1e6)

    slo = fcfg.slo_map
    meta = res.meta_metrics(slo_map=slo)
    speedup_recorded = evps / heap_evps_recorded
    speedup_inrun = evps / max(heap_evps, 1e-9)
    report = {
        "smoke": bool(smoke),
        "n_requests": len(tr),
        "n_events": res.n_events,
        "horizon_s": horizon,
        "bucket_s": cfg.bucket_s,
        "n_replicas": cfg.n_replicas,
        "wall_s": us / 1e6,
        "events_per_sec": evps,
        "heap_baseline": {
            "slice_requests": len(heap_slice),
            "slice_events": href.n_events,
            "events_per_sec": heap_evps,
            "recorded_events_per_sec": heap_evps_recorded},
        "speedup_vs_recorded_heap": speedup_recorded,
        "speedup_vs_inrun_heap": speedup_inrun,
        "fleet_attainment": meta["fleet_attainment"],
        "jain_fairness": meta["jain_fairness"],
        "goodput_tok_s": meta["goodput_tok_s"],
        "shed_rate": meta["shed_rate"],
        "per_tenant": {t: {"n": m["n_requests"],
                           "attainment": m["attainment"],
                           "goodput_share": m["goodput_share"]}
                       for t, m in meta["per_tenant"].items()},
        "compiles_post_warmup": cgate.count,
        "compile_gate": {"limit": cgate.limit}}
    # hard gates: full runs must clear the ISSUE's 50x floor against
    # the committed heap numbers; smoke runs (CI boxes, tiny horizon)
    # gate on an absolute events/s floor instead
    if smoke:
        assert evps >= 50_000.0, f"fleet engine too slow: {evps:.0f} ev/s"
    else:
        assert speedup_recorded >= 50.0, (
            f"speedup {speedup_recorded:.1f}x < 50x vs recorded heap "
            f"baseline {heap_evps_recorded} ev/s")
    res.check_conservation()
    key = "fleet_engine_smoke" if smoke else "fleet_engine"
    REPORT[key] = report
    _write_bench(f"BENCH_fleet{'_smoke' if smoke else ''}.json", report,
                 seed=42)
    _emit(key, us,
          f"evps={evps:.0f};x_recorded={speedup_recorded:.0f};"
          f"x_inrun={speedup_inrun:.0f};"
          f"attain={meta['fleet_attainment']:.3f}")
    return report


def online_engine(smoke=None):
    """Streaming ALA: an epoch-by-epoch trace feed through the
    ``OnlineALA`` incremental-refit engine, against a from-scratch
    ``ModelRegistry.fit`` + ``fit_uncertainty`` on the full concatenated
    data every epoch.  Each epoch slices the arrival trace, simulates it
    with the ALA autoscaler attached to the online engine (drift
    evidence can force recalibration), adapts the steady-state windows
    into a Dataset delta, and ingests it.  Records prediction parity
    (incremental vs from-scratch must agree to <= 1e-6 on the serving
    path) and the cumulative refit speedup.  Writes
    results/BENCH_online.json."""
    from repro.configs import get_config
    from repro.core.annealing import SAConfig, median_ape
    from repro.core.online import OnlineALA, OnlineConfig
    from repro.core.registry import ModelRegistry
    from repro.perfmodel.simulator import (ServingSetup, sample_throughput,
                                           throughput)
    from repro.perfmodel.hardware import TPU_V5E, feature_row
    from repro.serving.adapter import TRACE_BACKEND, windows_to_dataset
    from repro.serving.autoscaler import ALAAutoscaler
    from repro.serving.simulator import SimConfig, simulate
    from repro.serving.traces import TraceConfig, make_trace, mix
    from repro.core.dataset import Dataset
    from repro.staticcheck.tracers import assert_max_compiles, nan_guard

    smoke = OPTS["smoke"] if smoke is None else smoke
    archs = ("llama3.1-8b",) if smoke else ("llama3.1-8b", "qwen2.5-32b")
    n_epochs = 3 if smoke else 8
    epoch_s = 10.0 if smoke else 20.0
    REF_II, REF_OO = 512, 192
    grid = [(ii, oo, bb) for ii in ((128, 512, 2048) if smoke else
                                    (128, 256, 512, 1024, 2048))
            for oo in ((64, 256) if smoke else (64, 128, 256, 512))
            for bb in ((1, 4, 16, 64) if smoke else
                       (1, 2, 4, 8, 16, 32, 64, 128))]
    sa = SAConfig(n_iters=8 if smoke else 20, n_chains=2, seed=0,
                  gbt_kw=dict(n_estimators=20, learning_rate=0.2,
                              max_depth=3))
    gbt_kw = dict(n_estimators=20, learning_rate=0.15)
    eng = OnlineALA(OnlineConfig(sa=sa, warm_iters=3 if smoke else 6,
                                 gbt_kw=dict(sa.gbt_kw)))

    setups, traces, scalers, combos = {}, {}, {}, {}
    seed_rows = []
    for arch in archs:
        cfg = get_config(arch)
        chips = 8 if cfg.param_count() > 1e10 else 4
        setups[arch] = ServingSetup(cfg=cfg, hw=TPU_V5E, chips=chips)
        rng = np.random.default_rng(0)
        # calibration grid stamped onto the trace combination so epochs
        # extend — not sit beside — the static seed fit
        seed_rows += [dict(model=arch, acc=TPU_V5E.name, acc_count=chips,
                           back=TRACE_BACKEND, prec="bf16", mode="serve",
                           ii=ii, oo=oo, bb=bb, thpt=float(t),
                           **feature_row(TPU_V5E))
                      for ii, oo, bb in grid
                      for t in sample_throughput(setups[arch], ii, oo, bb,
                                                 2, rng)]
        cap_req_s = throughput(setups[arch], REF_II, REF_OO, 64) / REF_OO
        traces[arch] = make_trace(TraceConfig(
            arrival="mmpp", rate=0.7 * cap_req_s,
            burst_rate=2.0 * cap_req_s, horizon_s=n_epochs * epoch_s,
            shape_mix=mix(("chat", 0.7), ("generate", 0.3)), seed=29))

    # untimed warmup: run both pipelines once on the seed data so the
    # jitted shape buckets are compiled before either side is timed
    # (otherwise whichever path runs first is charged XLA compile time)
    seed_ds = Dataset.from_rows(seed_rows)
    warm = OnlineALA(OnlineConfig(sa=sa, warm_iters=3,
                                  gbt_kw=dict(sa.gbt_kw)))
    warm.ingest(seed_ds, **gbt_kw)
    ModelRegistry().fit(seed_ds, **gbt_kw).fit_uncertainty(
        seed_ds, seed=0, sa_cfg=sa, **sa.gbt_kw)

    # epoch 0: ingest the seed grids (initial full-budget fits)
    rep0, us0 = _timed(eng.ingest, seed_ds, **gbt_kw)
    inc_wall = us0 / 1e6
    for arch in archs:
        combos[arch] = eng.combo_of(next(r for r in seed_rows
                                         if r["model"] == arch))
        scalers[arch] = ALAAutoscaler(ala=eng.ala_for(combos[arch]),
                                     online=eng, combo=combos[arch],
                                     max_replicas=4)

    def scratch_fit():
        full = eng.full_data()
        reg = ModelRegistry().fit(full, **gbt_kw)
        reg.fit_uncertainty(full, seed=0, sa_cfg=sa, **sa.gbt_kw)
        return reg, full

    (reg_s, full), us_s = _timed(scratch_fit)
    scratch_wall = us_s / 1e6
    epochs_out = [{"epoch": 0, "rows": len(seed_ds),
                   "incremental_s": inc_wall, "scratch_s": scratch_wall,
                   "refit": len(rep0.refit), "skipped": len(rep0.skipped),
                   "drifted": 0}]
    inc_refit = scratch_refit = 0.0     # epochs >= 1: the refit loop
    epoch_compiles: list = []           # XLA compiles per refit epoch
    compile_budget = None               # set by the first measured epoch

    for e in range(n_epochs):
        deltas = []
        # epochs alternate which arch serves, so "refit only what
        # changed" has something to skip in the multi-arch run
        serving = [archs[e % len(archs)]] if len(archs) > 1 else archs
        for arch in serving:
            tr = traces[arch].slice(e * epoch_s, (e + 1) * epoch_s)
            if not len(tr):
                continue
            res = simulate(tr, SimConfig(setup=setups[arch], batch_cap=64,
                                         n_replicas=1, max_replicas=4,
                                         t_start=e * epoch_s),
                           scalers[arch])
            try:
                deltas.append(windows_to_dataset(
                    res, setups[arch], arch,
                    window_s=epoch_s / (4.0 if smoke else 8.0)))
            except ValueError:
                continue          # no steady-state window this epoch
        if not deltas:
            continue
        delta = deltas[0]
        for d in deltas[1:]:
            delta = delta.concat(d)
        # pow2 shape-bucketing gate: after the first measured epoch
        # sets the budget, no later epoch may compile more XLA
        # programs than it did (+2 slack for pow2 bucket crossings as
        # the data grows).  Smoke hard-gates; the full run records the
        # per-epoch counts in the artifact instead.
        with assert_max_compiles(compile_budget if smoke else None,
                                 label=f"online epoch {e + 1}") as cr:
            rep, us_i = _timed(eng.ingest, delta, **gbt_kw)
            (reg_s, full), us_s = _timed(scratch_fit)
        epoch_compiles.append(cr.count)
        if compile_budget is None:
            compile_budget = cr.count + 2
        inc_wall += us_i / 1e6
        scratch_wall += us_s / 1e6
        inc_refit += us_i / 1e6
        scratch_refit += us_s / 1e6
        epochs_out.append({
            "epoch": e + 1, "rows": len(delta),
            "incremental_s": us_i / 1e6, "scratch_s": us_s / 1e6,
            "refit": len(rep.refit),
            "skipped": len(rep.skipped),
            "drifted": sum(1 for d in rep.drift.values() if d.drifted)})

    # parity on the serving path over every ingested row; nan_guard is
    # the runtime half of the contract checker — a NaN in either
    # predict path fails the benchmark with the offending leaf named
    p_inc = nan_guard(eng.predict, label="online.predict")(full)
    p_scr = nan_guard(reg_s.predict, label="scratch.predict")(full)
    parity = float(np.abs(p_inc - p_scr).max())
    med_inc = median_ape(full["thpt"].astype(np.float64), p_inc)
    med_scr = median_ape(full["thpt"].astype(np.float64), p_scr)
    _, _, conf_inc = eng.estimate(full, backend="numpy")
    speedup = scratch_wall / max(inc_wall, 1e-9)
    # epoch 0 is an identical full fit on both sides; the refit speedup
    # over epochs >= 1 is the number the online engine is for
    refit_speedup = scratch_refit / max(inc_refit, 1e-9)
    out = {
        "smoke": bool(smoke), "archs": list(archs), "n_epochs": n_epochs,
        "rows_total": len(full),
        "incremental_wall_s": inc_wall, "scratch_wall_s": scratch_wall,
        "speedup": speedup,
        "incremental_refit_s": inc_refit, "scratch_refit_s": scratch_refit,
        "refit_speedup": refit_speedup,
        "predict_parity_max_abs_diff": parity,
        "parity_ok": bool(parity <= 1e-6),
        "median_ape_incremental": med_inc,
        "median_ape_scratch": med_scr,
        "mean_confidence_incremental": float(np.mean(conf_inc)),
        "recalibration_requests": sum(len(s.recalibrations)
                                      for s in scalers.values()),
        "epoch_compiles": epoch_compiles,
        "compile_budget": compile_budget,
        "epochs": epochs_out,
    }
    key = "online_engine_smoke" if smoke else "online_engine"
    REPORT[key] = out
    _write_bench(f"BENCH_online{'_smoke' if smoke else ''}.json", out,
                 seed=29)
    _emit("online_engine_incremental", inc_refit * 1e6,
          f"medAPE={med_inc:.2f}%;parity={parity:.2e}")
    _emit("online_engine_scratch", scratch_refit * 1e6,
          f"medAPE={med_scr:.2f}%;refit_speedup={refit_speedup:.1f}x")
    return out


def fault_engine(smoke=None, ttft_slo_s: float = 2.0):
    """Fault-injection benchmark: the serving stack under three fault
    scenarios (crash storm, straggler epoch, telemetry corruption),
    comparing a static baseline against the online-ALA autoscaler with
    and without the robust-ingestion gate.  Every scenario corrupts the
    telemetry stream at least mildly, so the gated arm's advantage is
    measured everywhere, not just in the corruption scenario.  Fault
    timelines are seed-deterministic (the plan fingerprint is recorded
    and re-derived to prove it) and request conservation (admitted ==
    completed + shed) is asserted for every run — an inconsistency
    fails the benchmark, which is the CI smoke gate.  Writes
    results/BENCH_faults.json."""
    from repro.configs import get_config
    from repro.core.annealing import SAConfig
    from repro.core.dataset import Dataset
    from repro.core.online import OnlineALA, OnlineConfig
    from repro.perfmodel.simulator import ServingSetup, sample_throughput, \
        throughput
    from repro.perfmodel.hardware import TPU_V5E, feature_row
    from repro.serving.adapter import (TRACE_BACKEND, summarize_windows,
                                       windows_to_rows)
    from repro.serving.autoscaler import ALAAutoscaler, StaticPolicy
    from repro.serving.faults import FaultConfig, FaultInjector, FaultPlan
    from repro.serving.simulator import SimConfig, simulate
    from repro.serving.traces import TraceConfig, make_trace, mix

    smoke = OPTS["smoke"] if smoke is None else smoke
    arch = "llama3.1-8b"
    cfg = get_config(arch)
    chips = 4
    setup = ServingSetup(cfg=cfg, hw=TPU_V5E, chips=chips)
    n_epochs = 2 if smoke else 5
    epoch_s = 8.0 if smoke else 20.0
    horizon = n_epochs * epoch_s
    max_replicas = 5
    REF_II, REF_OO = 512, 192
    cap_req_s = throughput(setup, REF_II, REF_OO, 64) / REF_OO
    trace = make_trace(TraceConfig(
        arrival="mmpp", rate=1.5 * cap_req_s, burst_rate=3.0 * cap_req_s,
        horizon_s=horizon, shape_mix=mix(("chat", 0.7), ("generate", 0.3)),
        seed=41))

    # mild corruption rides along in every scenario; the third scenario
    # turns it up and switches the other fault classes off
    mild = dict(drop_p=0.03, dup_p=0.08, poison_nan_p=0.05,
                poison_scale_p=0.18)
    heavy = dict(drop_p=0.05, dup_p=0.10, poison_nan_p=0.10,
                 poison_scale_p=0.30)
    scenarios = {
        "crash_storm": FaultConfig(
            seed=7, horizon_s=horizon, n_replicas=max_replicas,
            mttf_s=0.9 * epoch_s, mttr_s=3.0, restart_warmup_s=1.0,
            **mild),
        # light background crashes ride along: replica failures scale
        # with fleet size, so panic over-provisioning (the poisoned
        # arm's failure mode) carries real exposure, as it would in a
        # production fleet
        "straggler_epoch": FaultConfig(
            seed=8, horizon_s=horizon, n_replicas=max_replicas,
            straggler_rate_hz=0.06, straggler_dur_s=0.6 * epoch_s,
            straggler_slow=4.0, mttf_s=2.5 * epoch_s, mttr_s=3.0,
            restart_warmup_s=1.0, **mild),
        "telemetry_corruption": FaultConfig(
            seed=9, horizon_s=horizon, n_replicas=max_replicas, **heavy),
    }

    grid = [(ii, oo, bb) for ii in ((128, 512, 2048) if smoke else
                                    (128, 256, 512, 1024, 2048))
            for oo in ((64, 256) if smoke else (64, 128, 256))
            for bb in (1, 4, 16, 64)]
    sa = SAConfig(n_iters=4 if smoke else 12, n_chains=2, seed=0,
                  gbt_kw=dict(n_estimators=20, learning_rate=0.2,
                              max_depth=3))
    gbt_kw = dict(n_estimators=20, learning_rate=0.15)
    rng = np.random.default_rng(0)
    # the prior is deliberately miscalibrated (derated throughput): the
    # online loop must *learn* true capacity from trace telemetry, which
    # is exactly the channel corruption attacks — a clean prior would
    # let the ungated arm coast on it and hide the poison
    PRIOR_DERATE = 0.5
    seed_rows = [dict(model=arch, acc=TPU_V5E.name, acc_count=chips,
                      back=TRACE_BACKEND, prec="bf16", mode="serve",
                      ii=ii, oo=oo, bb=bb, thpt=PRIOR_DERATE * float(t),
                      **feature_row(TPU_V5E))
                 for ii, oo, bb in grid
                 for t in sample_throughput(setup, ii, oo, bb, 1, rng)]
    seed_ds = Dataset.from_rows(seed_rows)

    def run_arm(pname: str, plan: FaultPlan):
        """One policy through the scenario's epochal loop.  Each arm
        gets a fresh injector from the SAME plan, so all arms face the
        identical crash/straggler timeline and corruption process."""
        inj = FaultInjector(plan)
        eng = scaler = None
        if pname != "static":
            eng = OnlineALA(OnlineConfig(
                sa=sa, warm_iters=3 if smoke else 5,
                gbt_kw=dict(sa.gbt_kw), gate=(pname == "ala_gated")))
            eng.ingest(seed_ds, **gbt_kw)
            combo = eng.combo_of(seed_rows[0])
            scaler = ALAAutoscaler(ala=eng.ala_for(combo), online=eng,
                                   combo=combo, max_replicas=max_replicas)
        agg = dict(admitted=0, completed=0, shed=0, retries=0,
                   slo_hits=0, out_toks=0.0, span_s=0.0,
                   replica_s=0.0, failed_s=0.0, n_quarantined=0)
        ttfts = []
        for e in range(n_epochs):
            tr = trace.slice(e * epoch_s, (e + 1) * epoch_s)
            if not len(tr):
                continue
            policy = (StaticPolicy(n_replicas=2, batch_cap=64)
                      if pname == "static" else scaler)
            res = simulate(tr, SimConfig(
                setup=setup, batch_cap=64, n_replicas=2,
                max_replicas=max_replicas, t_start=e * epoch_s,
                faults=inj, max_retries=2,
                shed_after_s=4.0 * ttft_slo_s), policy)
            res.check_conservation()          # the CI smoke gate
            acc = res.accounting()
            agg["admitted"] += acc["admitted"]
            agg["completed"] += acc["completed"]
            agg["shed"] += acc["shed"]
            agg["retries"] += res.n_retries
            agg["slo_hits"] += sum(
                1 for r in res.records
                if not r.shed and r.first_token_s is not None
                and r.ttft_s <= ttft_slo_s)
            agg["out_toks"] += sum(r.oo for r in res.completed)
            agg["span_s"] += res.sim_end_s - res.t_start
            den = res.replica_seconds / max(res.availability, 1e-9)
            agg["replica_s"] += res.replica_seconds
            agg["failed_s"] += den - res.replica_seconds
            ttfts += [r.ttft_s for r in res.records]
            if eng is not None:
                rows = windows_to_rows(
                    summarize_windows(res, window_s=epoch_s / 8.0),
                    setup, arch)
                rows, _ = inj.corrupt_rows(rows)
                if rows:
                    rep = eng.ingest(Dataset.from_rows(
                        rows, require_finite=None), **gbt_kw)
                    agg["n_quarantined"] += rep.n_quarantined
        den = agg["replica_s"] + agg["failed_s"]
        finite = np.asarray([t for t in ttfts if np.isfinite(t)])
        return {
            "slo_attainment": agg["slo_hits"] / max(agg["admitted"], 1),
            "goodput_tok_s": agg["out_toks"] / max(agg["span_s"], 1e-9),
            "availability": agg["replica_s"] / den if den > 0 else 1.0,
            "admitted": agg["admitted"], "completed": agg["completed"],
            "shed": agg["shed"], "retries": agg["retries"],
            "p95_ttft_completed_s": (float(np.percentile(finite, 95))
                                     if len(finite) else float("inf")),
            "n_quarantined": agg["n_quarantined"],
            "accounting_ok": agg["admitted"] == agg["completed"]
            + agg["shed"],
        }

    report = {"smoke": bool(smoke), "arch": arch, "chips": chips,
              "ttft_slo_s": ttft_slo_s, "n_epochs": n_epochs,
              "epoch_s": epoch_s, "n_requests": len(trace),
              "scenarios": {}}
    wall = 0.0
    for sname, fcfg in scenarios.items():
        plan = FaultPlan.build(fcfg)
        fp = plan.fingerprint()
        out = {"fingerprint": fp,
               "timeline_deterministic":
                   FaultPlan.build(fcfg).fingerprint() == fp,
               "n_crash_windows": len(plan.crashes),
               "n_straggler_windows": len(plan.stragglers),
               "policies": {}}
        for pname in ("static", "ala_ungated", "ala_gated"):
            arm, us = _timed(run_arm, pname, plan)
            wall += us / 1e6
            out["policies"][pname] = arm
            if not arm["accounting_ok"]:
                raise RuntimeError(
                    f"fault_engine[{sname}/{pname}]: accounting broken: "
                    f"admitted {arm['admitted']} != completed "
                    f"{arm['completed']} + shed {arm['shed']}")
        pol = out["policies"]
        out["gated_beats_static"] = bool(
            pol["ala_gated"]["slo_attainment"]
            >= pol["static"]["slo_attainment"])
        out["gated_beats_ungated"] = bool(
            pol["ala_gated"]["slo_attainment"]
            >= pol["ala_ungated"]["slo_attainment"])
        report["scenarios"][sname] = out
        _emit(f"fault_engine_{sname}", us,
              f"slo_gated={pol['ala_gated']['slo_attainment']:.3f};"
              f"slo_ungated={pol['ala_ungated']['slo_attainment']:.3f};"
              f"slo_static={pol['static']['slo_attainment']:.3f}")
    report["all_gated_wins"] = all(
        s["gated_beats_static"] and s["gated_beats_ungated"]
        for s in report["scenarios"].values())
    key = "fault_engine_smoke" if smoke else "fault_engine"
    REPORT[key] = report
    _write_bench(f"BENCH_faults{'_smoke' if smoke else ''}.json", report,
                 seed=41)
    return report


def transfer_engine(smoke=None, ttft_slo_s: float = 2.0):
    """Cross-hardware ALA transfer + heterogeneous fleet placement.

    (a) Fit the registry (+ uncertainty pipeline) on TPU-v5e rows only,
        then predict every other registered accelerator's ground-truth
        grid via registry transfer with the analytic roofline scaler —
        per-target-hardware medAPE.
    (b) Alg 8 confidence ordering: on *identical* workloads, the
        transferred (cross-hardware) confidence must be strictly below
        the same-hardware confidence for every target.
    (c) Mixed TPU+GPU fleet: the ALA autoscaler placing scale-up
        replicas by transfer-derated predictions (hardware-aware) vs the
        same controller cycling the pool blindly — shed-aware SLO
        attainment / replica-seconds, on both serving engines (parity
        reported).  Writes results/BENCH_transfer.json."""
    import itertools
    from repro.bench.datasets import FRAMEWORKS, _simulate
    from repro.configs import get_config
    from repro.core.annealing import SAConfig
    from repro.core.dataset import Dataset
    from repro.core.registry import ModelRegistry
    from repro.perfmodel.hardware import (PROFILES, feature_row,
                                          hardware_distance, profile)
    from repro.perfmodel.simulator import (ServingSetup, throughput,
                                           throughput_batch)
    from repro.serving.autoscaler import ALAAutoscaler
    from repro.serving.simulator import SimConfig, simulate
    from repro.serving.traces import TraceConfig, make_trace, mix

    smoke = OPTS["smoke"] if smoke is None else smoke
    model = "llama3.1-8b"
    source = "tpu-v5e"
    targets = ("tpu-v4", "gpu-a100-80g", "gpu-l4") if smoke else \
        tuple(sorted(n for n in PROFILES if n != source))
    chips = 4
    cfg = get_config(model)

    def setup_of(hw_name: str) -> ServingSetup:
        return ServingSetup(cfg=cfg, hw=profile(hw_name), chips=chips,
                            framework_eff=FRAMEWORKS["vllm-jax"])

    grid = list(itertools.product(
        (128, 512, 2048) if smoke else (128, 256, 512, 1024, 2048),
        (64, 256) if smoke else (64, 128, 256, 512),
        (1, 4, 16, 64) if smoke else (1, 2, 4, 8, 16, 32, 64, 128)))
    reps = 2 if smoke else 3
    sa_iters = 4 if smoke else 10

    rng = np.random.default_rng(0)
    src = Dataset.from_rows(_simulate(model, profile(source), grid, reps,
                                      rng, chips=chips))
    reg, us_fit = _timed(
        lambda: ModelRegistry().fit(src, n_estimators=25).fit_uncertainty(
            src, sa_cfg=SAConfig(n_iters=sa_iters, seed=0, n_chains=4,
                                 gbt_kw=dict(n_estimators=30,
                                             learning_rate=0.2,
                                             max_depth=4)),
            n_estimators=25))
    hw_i = reg._active_keys.index("acc")

    def scale_fn(combo, donor, ii, oo, bb):
        # analytic roofline transfer: the pure-descriptor throughput
        # ratio between target and donor hardware, per query point
        return (throughput_batch(setup_of(combo[hw_i]), ii, oo, bb)
                / throughput_batch(setup_of(donor[hw_i]), ii, oo, bb))

    report = {"smoke": bool(smoke), "source": source, "model": model,
              "targets": {}}
    src_med = float(np.median(np.abs(
        reg.predict(src) - src["thpt"]) / src["thpt"] * 100.0))
    report["source_median_ape"] = src_med
    # one shared same-workload query set for the confidence ordering:
    # identical (ii, oo, bb, thpt) rows relabeled per hardware
    q_idx = np.random.default_rng(1).choice(
        len(src), size=min(128, len(src)), replace=False)
    base_rows = [{k: src[k][i] for k in src.cols} for i in q_idx]
    _, _, conf_same = reg.estimate(Dataset.from_rows(base_rows))
    assert (conf_same > 0).all(), "source confidence degenerate"
    report["conf_same_median"] = float(np.median(conf_same))
    for tname in targets:
        tgt = Dataset.from_rows(_simulate(model, profile(tname), grid,
                                          reps, rng, chips=chips))
        pred, us_pred = _timed(reg.predict, tgt, transfer=True,
                               scale_fn=scale_fn)
        med = float(np.median(np.abs(pred - tgt["thpt"])
                              / tgt["thpt"] * 100.0))
        hw_cols = feature_row(tname)
        relab = Dataset.from_rows([{**r, "acc": tname, **hw_cols}
                                   for r in base_rows])
        _, _, conf_x = reg.estimate(relab, transfer=True)
        strict = bool((conf_x < conf_same).all())
        d_hw = hardware_distance(source, tname)
        report["targets"][tname] = {
            "transfer_median_ape": med,
            "hardware_distance": float(d_hw),
            "conf_cross_median": float(np.median(conf_x)),
            "strictly_lower_confidence": strict,
        }
        _emit(f"transfer_engine_{tname}", us_pred,
              f"medAPE={med:.2f}%;d_hw={d_hw:.2f};"
              f"conf_x={np.median(conf_x):.3f};strict={strict}")
        # CI gates: transfer must stay accurate (the analytic scaler
        # absorbs the roofline shift; residual is GBT fit error + noise)
        # and must never report >= the same-hardware confidence
        assert med < 20.0, f"{tname}: transfer medAPE {med:.1f}% >= 20%"
        assert strict, f"{tname}: cross-hardware confidence not < same"

    # --- (c) mixed TPU+GPU fleet: aware vs blind placement -----------------
    # Both arms run the SAME slot-cycled TPU+L4 SimConfig; the aware
    # controller overrides the slot hardware through Action.hardware
    # (transfer-derated predictions pick the TPU), the blind controller
    # emits hardware=None and inherits the mixed slot defaults.
    src_setup = setup_of(source)
    pool = (source, "gpu-l4")
    ala = next(iter(reg.combos.values())).ala
    hw_scale = {
        n: (lambda ii, oo, bb, n=n: float(
            throughput_batch(setup_of(n), [ii], [oo], [bb])[0]
            / throughput_batch(src_setup, [ii], [oo], [bb])[0]))
        for n in pool}
    horizon = 16.0 if smoke else 40.0
    shape = mix(("chat", 0.6), ("summarize", 0.2), ("generate", 0.2))
    cap_req_s = throughput(src_setup, 512, 192, 64) / 192
    tr = make_trace(TraceConfig(arrival="poisson", rate=2.0 * cap_req_s,
                                horizon_s=horizon, shape_mix=shape,
                                seed=29))
    sim_cfg = SimConfig(setup=src_setup, batch_cap=64, n_replicas=1,
                        max_replicas=6,
                        replica_setups=(src_setup, setup_of("gpu-l4")))

    def policy(kind: str) -> ALAAutoscaler:
        if kind == "blind":
            return ALAAutoscaler(ala=ala, max_replicas=6)
        return ALAAutoscaler(ala=ala, max_replicas=6, hardware_pool=pool,
                             fitted_hardware=source,
                             hardware_scale=hw_scale, placement="aware")

    fleet_out = {"pool": list(pool), "n_requests": len(tr), "arms": {}}
    for arm in ("aware", "blind"):
        per_engine = {}
        for engine in ("heap", "fleet"):
            res, us = _timed(simulate, tr, sim_cfg, policy(arm),
                             engine=engine)
            res.check_conservation()
            per_engine[engine] = {
                "slo_attainment": res.slo_attainment(ttft_slo_s),
                "goodput_tok_s": res.goodput_tok_s,
                "replica_seconds": res.replica_seconds,
                "n_shed": len(res.shed),
                "hardware": {h: sum(1 for v in res.replica_hw.values()
                                    if v == h)
                             for h in sorted(set(res.replica_hw.values()))},
                "wall_s": us / 1e6,
            }
        per_engine["parity_slo_diff"] = abs(
            per_engine["heap"]["slo_attainment"]
            - per_engine["fleet"]["slo_attainment"])
        fleet_out["arms"][arm] = per_engine
    aware = fleet_out["arms"]["aware"]["heap"]
    blind = fleet_out["arms"]["blind"]["heap"]
    fleet_out["aware_beats_blind"] = bool(
        aware["slo_attainment"] > blind["slo_attainment"]
        or (aware["slo_attainment"] >= blind["slo_attainment"]
            and aware["replica_seconds"] < blind["replica_seconds"]))
    report["fleet"] = fleet_out
    _emit("transfer_engine_fleet", us_fit,
          f"slo_aware={aware['slo_attainment']:.3f};"
          f"slo_blind={blind['slo_attainment']:.3f};"
          f"aware_wins={fleet_out['aware_beats_blind']}")
    # CI gates: placement must pay off, and the two engines must agree
    # on the heterogeneous scenario within the documented tolerance
    assert fleet_out["aware_beats_blind"], \
        "hardware-aware placement did not beat hardware-blind"
    for arm in ("aware", "blind"):
        d = fleet_out["arms"][arm]["parity_slo_diff"]
        assert d <= 0.1, f"{arm}: heap/fleet SLO parity diff {d:.3f} > 0.1"

    key = "transfer_engine_smoke" if smoke else "transfer_engine"
    REPORT[key] = report
    _write_bench(f"BENCH_transfer{'_smoke' if smoke else ''}.json", report,
                 seed=29)
    return report


def obs_engine(smoke=None, ttft_slo_s: float = 2.0):
    """Observability layer end-to-end, with hard gates.

    (1) Overhead: the 3-tenant fleet scenario runs untraced vs traced
    (``ObsConfig(sample_rate=1.0)``, spans derived post-run from the
    engine's own columns); full runs assert <5% throughput overhead.
    (2) Span parity: heap and fleet engines on the same seeded trace
    slice must emit equivalent span statistics (exact counts, TTFT/E2E
    percentiles within the bucket-quantization tolerance).
    (3) Mergeable histograms: per-tenant TTFT shards merge to the
    whole-stream quantile within one bin width, raw values never
    retained.  (4) Calibration: a miscalibrated-prior online loop
    (autoscaler ticks + ingest reports into one CalibrationAudit) must
    yield a monotone-binned confidence reliability curve.  Also writes
    a Perfetto-loadable Chrome trace of the multi-tenant run and
    results/BENCH_obs.json."""
    import dataclasses as _dc

    from repro.configs import get_config
    from repro.core.annealing import SAConfig
    from repro.core.dataset import Dataset
    from repro.core.online import OnlineALA, OnlineConfig
    from repro.obs import (CalibrationAudit, ObsConfig, StreamHist,
                           percentile_with_inf, write_chrome_trace,
                           write_jsonl)
    from repro.obs.tracing import queue_depth_series, span_hists, span_stats
    from repro.perfmodel.simulator import (ServingSetup, sample_throughput,
                                           throughput)
    from repro.perfmodel.hardware import TPU_V5E, feature_row
    from repro.serving.adapter import (TRACE_BACKEND, summarize_windows,
                                       windows_to_rows)
    from repro.serving.autoscaler import ALAAutoscaler
    from repro.serving.simulator import SimConfig, simulate
    from repro.serving.traces import (FleetTraceConfig, TenantConfig,
                                      TraceConfig, make_fleet_trace,
                                      make_trace, mix)

    smoke = OPTS["smoke"] if smoke is None else smoke
    suffix = "_smoke" if smoke else ""
    arch = "llama3.1-8b"
    setup = ServingSetup(cfg=get_config(arch), hw=TPU_V5E, chips=4)

    # -- (1) overhead gate on the multi-tenant fleet scenario ---------------
    horizon = 60.0 if smoke else 600.0
    fcfg = FleetTraceConfig(tenants=(
        TenantConfig(name="chat",
                     trace=TraceConfig(arrival="poisson", rate=30.0,
                                       shape_mix=mix(("chat", 1.0))),
                     ttft_slo_s=1.5, diurnal_amp=0.4),
        TenantConfig(name="summarize",
                     trace=TraceConfig(arrival="gamma", rate=8.0, cv=2.0,
                                       shape_mix=mix(("summarize", 1.0))),
                     ttft_slo_s=8.0),
        TenantConfig(name="generate",
                     trace=TraceConfig(arrival="mmpp", rate=12.0,
                                       burst_rate=24.0,
                                       shape_mix=mix(("generate", 1.0))),
                     ttft_slo_s=4.0, flash_crowds=2, flash_mult=3.0,
                     flash_dur_s=15.0),
    ), horizon_s=horizon, seed=42)
    tr = make_fleet_trace(fcfg)
    cfg = SimConfig(setup=setup, batch_cap=64, n_replicas=8,
                    max_replicas=8, bucket_s=0.5)
    cfg_obs = _dc.replace(cfg, obs=ObsConfig(sample_rate=1.0))
    simulate(tr, cfg, engine="fleet")               # warm-up
    base_us = min(_timed(simulate, tr, cfg, engine="fleet")[1]
                  for _ in range(3))
    res_obs, obs_us = _timed(simulate, tr, cfg_obs, engine="fleet")
    obs_us = min([obs_us] + [_timed(simulate, tr, cfg_obs,
                                    engine="fleet")[1] for _ in range(2)])
    overhead = obs_us / base_us - 1.0
    evps_base = res_obs.n_events / (base_us / 1e6)
    evps_obs = res_obs.n_events / (obs_us / 1e6)
    assert res_obs.spans is not None \
        and res_obs.spans.n == len(tr.requests), "span capture incomplete"
    # full runs gate at the ISSUE's 5%; smoke runs are sub-second on CI
    # boxes where timer noise alone exceeds that, so gate loosely there
    cap = 0.25 if smoke else 0.05
    assert overhead < cap, (
        f"tracing overhead {overhead * 100:.1f}% >= {cap * 100:.0f}% "
        f"at sample_rate=1.0")

    # -- (2) heap-vs-fleet span-statistic parity on a seeded slice ----------
    sl = tr.slice(0.0, 20.0 if smoke else 60.0)
    h = simulate(sl, cfg_obs, engine="heap")
    f = simulate(sl, cfg_obs, engine="fleet")
    sh, sf = span_stats(h.spans), span_stats(f.spans)
    assert sh["n_spans"] == sf["n_spans"], (sh["n_spans"], sf["n_spans"])
    assert sh["n_shed"] == sf["n_shed"], (sh["n_shed"], sf["n_shed"])
    assert sh["out_tokens"] == sf["out_tokens"]
    # fleet admissions are quantized to bucket boundaries: percentile
    # deltas are bounded by the bucket width plus the parity-test margin
    tol50 = cfg.bucket_s + 0.35
    tol95 = cfg.bucket_s + 1.0
    for k, tol in (("ttft_p50_s", tol50), ("ttft_p95_s", tol95),
                   ("e2e_p50_s", tol50), ("e2e_p95_s", tol95)):
        a, b = sh[k], sf[k]
        if np.isfinite(a) or np.isfinite(b):
            assert abs(a - b) <= tol, f"span parity {k}: {a} vs {b}"

    # -- (3) mergeable per-tenant histogram shards --------------------------
    shards = span_hists(res_obs.spans, n_bins=48,
                        by=res_obs.spans.tenant)
    merged = StreamHist.merged(shards.values())
    ttft_all = res_obs.spans.ttft_s()
    exact_p95 = percentile_with_inf(ttft_all, 95.0)
    hist_p95 = merged.quantile(95.0)
    fin = ttft_all[np.isfinite(ttft_all)]
    bin_w = ((fin.max() - fin.min()) / 46.0) if len(fin) else 0.0
    if np.isfinite(exact_p95):
        assert abs(hist_p95 - exact_p95) <= bin_w + 1e-9, (
            f"merged-shard p95 {hist_p95} vs exact {exact_p95} "
            f"(bin width {bin_w})")
    qd = queue_depth_series(res_obs.spans, bucket_s=cfg.bucket_s,
                            t_end=res_obs.sim_end_s)
    qd_hist = StreamHist.from_values(qd["depth"].astype(float), 32)

    # -- Perfetto-loadable trace of the multi-tenant run --------------------
    trace_path = RESULTS / f"obs_trace_fleet{suffix}.json"
    RESULTS.mkdir(parents=True, exist_ok=True)
    write_chrome_trace(res_obs, trace_path,
                       max_step_events=2000 if smoke else 20000,
                       max_span_events=500 if smoke else 5000)
    tj = json.loads(trace_path.read_text())
    assert tj["traceEvents"], "empty chrome trace"
    assert all("ph" in e and "pid" in e for e in tj["traceEvents"])

    # -- (4) calibration audit: miscalibrated prior, online loop ------------
    n_epochs = 3 if smoke else 6
    epoch_s = 10.0 if smoke else 20.0
    REF_II, REF_OO = 512, 192
    cap_req_s = throughput(setup, REF_II, REF_OO, 64) / REF_OO
    cal_tr = make_trace(TraceConfig(
        arrival="mmpp", rate=1.2 * cap_req_s, burst_rate=2.5 * cap_req_s,
        horizon_s=n_epochs * epoch_s,
        shape_mix=mix(("chat", 0.7), ("generate", 0.3)), seed=43))
    grid = [(ii, oo, bb)
            for ii in ((128, 512, 2048) if smoke else
                       (128, 256, 512, 1024, 2048))
            for oo in ((64, 256) if smoke else (64, 128, 256))
            for bb in (1, 4, 16, 64)]
    sa = SAConfig(n_iters=4 if smoke else 12, n_chains=2, seed=0,
                  gbt_kw=dict(n_estimators=20, learning_rate=0.2,
                              max_depth=3))
    gbt_kw = dict(n_estimators=20, learning_rate=0.15)
    rng = np.random.default_rng(0)
    # deliberately derated prior: early ticks are wrong (high APE) at
    # whatever confidence Alg 8 reports; mid-run recalibration from the
    # trace telemetry restores accuracy — exactly the spread a
    # reliability curve needs
    PRIOR_DERATE = 0.6
    seed_rows = [dict(model=arch, acc=TPU_V5E.name, acc_count=4,
                      back=TRACE_BACKEND, prec="bf16", mode="serve",
                      ii=ii, oo=oo, bb=bb, thpt=PRIOR_DERATE * float(t),
                      **feature_row(TPU_V5E))
                 for ii, oo, bb in grid
                 for t in sample_throughput(setup, ii, oo, bb, 1, rng)]
    obs_cal = ObsConfig()
    audit = CalibrationAudit(cfg=obs_cal)
    eng = OnlineALA(OnlineConfig(sa=sa, warm_iters=3 if smoke else 5,
                                 gbt_kw=dict(sa.gbt_kw)), audit=audit)
    eng.ingest(Dataset.from_rows(seed_rows), **gbt_kw)
    combo = eng.combo_of(seed_rows[0])
    scaler = ALAAutoscaler(ala=eng.ala_for(combo), online=eng,
                           combo=combo, max_replicas=4, audit=audit,
                           drift_window=4, drift_ape_threshold=25.0)
    for e in range(n_epochs):
        etr = cal_tr.slice(e * epoch_s, (e + 1) * epoch_s)
        if not len(etr):
            continue
        res = simulate(etr, SimConfig(
            setup=setup, batch_cap=64, n_replicas=2, max_replicas=4,
            t_start=e * epoch_s, control_interval_s=1.0), scaler)
        rows = windows_to_rows(
            summarize_windows(res, window_s=epoch_s / 8.0), setup, arch)
        if rows:
            eng.ingest(Dataset.from_rows(rows), **gbt_kw)
    cal = audit.summary()
    curve = cal["reliability"]
    n_ticks = cal["n_ticks"]
    assert n_ticks >= 5, f"calibration audit starved: {n_ticks} ticks"
    assert audit.counts.get("refit", 0) >= 1, "no ingest reports audited"
    acc = curve["bin_acc"]
    assert len(acc) >= 1 and all(
        acc[i] <= acc[i + 1] + 1e-12 for i in range(len(acc) - 1)), (
        f"reliability curve not monotone-binned: {curve}")
    events_path = RESULTS / f"obs_events{suffix}.jsonl"
    n_ev = write_jsonl(audit.events, events_path)

    key = f"obs_engine{suffix}" if smoke else "obs_engine"
    report = {
        "smoke": bool(smoke),
        "n_requests": len(tr),
        "n_events": res_obs.n_events,
        "overhead_frac": overhead,
        "overhead_cap": cap,
        "events_per_sec_untraced": evps_base,
        "events_per_sec_traced": evps_obs,
        "span_parity": {"heap": sh, "fleet": sf,
                        "tol_p50_s": tol50, "tol_p95_s": tol95},
        "hist_merge": {"exact_p95_s": exact_p95,
                       "merged_p95_s": hist_p95, "bin_width_s": bin_w,
                       "n_shards": len(shards)},
        "queue_depth": {"p50": qd_hist.quantile(50.0),
                        "p95": qd_hist.quantile(95.0),
                        "max": float(qd["depth"].max())
                        if len(qd["depth"]) else 0.0},
        "chrome_trace": {"file": trace_path.name,
                         "n_events": len(tj["traceEvents"])},
        "calibration": cal,
        "audit_events_file": events_path.name,
        "audit_events_written": n_ev,
        "meta": {k: v for k, v in
                 res_obs.meta_metrics(fcfg.slo_map).items()
                 if k != "per_tenant"},
        "per_tenant": res_obs.per_tenant(fcfg.slo_map),
    }
    REPORT[key] = report
    _write_bench(f"BENCH_obs{suffix}.json", report, seed=42)
    _emit(key, obs_us,
          f"overhead={overhead * 100:.1f}%;ticks={n_ticks};"
          f"rel_bins={len(acc)};trace_evs={len(tj['traceEvents'])}")
    return report


def wallclock_engine(arch: str = "qwen3-0.6b"):
    """Real JAX-engine sweep through bench.harness — the CLI grid/reps
    overrides and the module defaults share one code path."""
    from repro.bench.harness import measure_arch
    grids = (OPTS["grid_ii"], OPTS["grid_oo"], OPTS["grid_bb"])
    if OPTS["smoke"] and all(g is None for g in grids):
        grids = ((16,), (8,), (1, 2))
    # None falls through to measure_arch's own default (reps=2)
    reps = OPTS["reps"] if OPTS["reps"] is not None else 2
    ds, us = _timed(measure_arch, arch, *grids, reps=reps)
    med = float(np.median(ds["thpt"]))
    REPORT["wallclock_engine"] = {
        "arch": arch, "rows": len(ds), "reps": reps,
        "grids": [list(g) if g else None for g in grids],
        "median_tok_s": med}
    _emit("wallclock_engine", us, f"rows={len(ds)};median_tok_s={med:.1f}")


BENCHMARKS = {}


def main() -> None:
    def _csv_ints(s):
        return tuple(int(v) for v in s.split(",") if v)

    p = argparse.ArgumentParser(description=__doc__,
                                formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("names", nargs="*",
                   help="benchmarks to run (default: all)")
    p.add_argument("--smoke", action="store_true",
                   help="CI-sized runs (fewer archs, short horizons)")
    p.add_argument("--grid-ii", type=_csv_ints, default=None,
                   metavar="I1,I2,...")
    p.add_argument("--grid-oo", type=_csv_ints, default=None,
                   metavar="O1,O2,...")
    p.add_argument("--grid-bb", type=_csv_ints, default=None,
                   metavar="B1,B2,...")
    p.add_argument("--reps", type=int, default=None)
    args = p.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    OPTS.update(smoke=args.smoke, grid_ii=args.grid_ii,
                grid_oo=args.grid_oo, grid_bb=args.grid_bb, reps=args.reps)
    names = args.names
    for n in names:
        if n not in BENCHMARKS:
            print(f"unknown benchmark {n!r}; available: "
                  f"{', '.join(BENCHMARKS)}")
            raise SystemExit(2)
    print("name,us_per_call,derived")
    t0 = time.time()
    for name, fn in BENCHMARKS.items():
        if names and name not in names:
            continue
        fn()
    RESULTS.mkdir(parents=True, exist_ok=True)
    report_path = RESULTS / "bench_report.json"
    report = REPORT
    if names and report_path.exists():
        # partial run: merge into the aggregate instead of clobbering it
        try:
            report = {**json.loads(report_path.read_text()), **REPORT}
        except json.JSONDecodeError:
            pass
    report_path.write_text(json.dumps(report, indent=1))
    print(f"# total {time.time() - t0:.1f}s; report -> {report_path}")


BENCHMARKS.update({
    "fig2_exponential_fits": fig2_exponential_fits,
    "fig3_param_prediction": fig3_param_prediction,
    "fig6_rq1_training_sets": fig6_rq1_training_sets,
    "fig7_rq2_baselines": fig7_rq2_baselines,
    "fig8_rq3_model_zoo": fig8_rq3_model_zoo,
    "table1_rq4_uncertainty": table1_rq4_uncertainty,
    "perf_vmapped_fit": perf_vmapped_fit,
    "perf_kernels": perf_kernels,
    "sa_engine": sa_engine,
    "uncertainty_engine": uncertainty_engine,
    "serving_engine": serving_engine,
    "fleet_engine": fleet_engine,
    "online_engine": online_engine,
    "fault_engine": fault_engine,
    "transfer_engine": transfer_engine,
    "obs_engine": obs_engine,
    "wallclock_engine": wallclock_engine,
})


if __name__ == "__main__":
    main()
