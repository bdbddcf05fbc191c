"""repro-check: rule fixtures (positive / negative / suppressed per
rule), suppression auditing, the runtime tracers, the CLI surface, and
the self-check that the repo is clean at HEAD."""
import pathlib
import textwrap

import numpy as np
import pytest

from repro.staticcheck import (ALL_RULES, RULES_BY_NAME, check_paths,
                               check_source)
from repro.staticcheck.__main__ import main as cli_main

REPO = pathlib.Path(__file__).resolve().parents[1]


def names(findings, rule=None):
    return [f.rule for f in findings
            if rule is None or f.rule == rule]


def suppress_line(src: str, line: int, rule: str) -> str:
    lines = src.splitlines()
    lines[line - 1] += f"  # repro-check: disable={rule}"
    return "\n".join(lines) + "\n"


# One fixture triple per rule: (path, bad source, violation line,
# path+source that must be clean).  Paths matter — half the rules are
# scoped, and the negative case often exercises the scope boundary.
CASES = {
    "banned-solve": dict(
        path="src/repro/core/database.py",
        bad="import jax.numpy as jnp\nd = jnp.linalg.solve(A, r)\n",
        line=2,
        good=("src/repro/core/fit.py",           # the one exempt home
              "import numpy as np\nd = np.linalg.solve(A, r)\n")),
    "no-shim-import": dict(
        path="src/repro/serving/x.py",
        bad="from repro.perfmodel import tpu\n",
        line=1,
        good=("src/repro/serving/x.py",
              "from repro.perfmodel import hardware\n")),
    "unseeded-rng": dict(
        path="src/repro/core/x.py",
        bad="import numpy as np\nv = np.random.normal(0.0, 1.0)\n",
        line=2,
        good=("src/repro/core/x.py",
              "import numpy as np\nrng = np.random.default_rng(0)\n"
              "v = rng.normal(0.0, 1.0)\n")),
    "wallclock-in-sim": dict(
        path="src/repro/serving/x.py",
        bad="import time\nt0 = time.time()\n",
        line=2,
        good=("src/repro/serving/x.py",
              "import time\nt0 = time.perf_counter()\n")),
    "bench-provenance": dict(
        path="benchmarks/extra.py",
        bad="import json\n"
            "(RESULTS / 'BENCH_extra.json').write_text("
            "json.dumps(payload))\n",
        line=2,
        good=("benchmarks/extra.py",
              "import json\ndef _write_bench(filename, payload):\n"
              "    (RESULTS / filename).write_text("
              "json.dumps(payload))\n")),
    "float64-edges": dict(
        path="src/repro/obs/metrics.py",
        bad="import numpy as np\n"
            "def my_edges(lo, hi, n):\n"
            "    return np.linspace(lo, hi, n)\n",
        line=2,
        good=("src/repro/obs/metrics.py",
              "import numpy as np\n"
              "def my_edges(lo, hi, n):\n"
              "    return np.linspace(lo, hi, n).astype(np.float32)\n")),
    "jit-in-loop": dict(
        path="src/repro/core/x.py",
        bad="import jax\nfor s in shapes:\n"
            "    f = jax.jit(lambda x: x + s)\n",
        line=3,
        good=("src/repro/core/x.py",
              "import jax\ndef _make():\n"
              "    return jax.jit(lambda x: x)\n")),
    "mutable-default-config": dict(
        path="src/repro/serving/x.py",
        bad="import dataclasses\n@dataclasses.dataclass\nclass C:\n"
            "    xs: list = dataclasses.field(default=[1])\n",
        line=4,
        good=("src/repro/serving/x.py",
              "import dataclasses\n@dataclasses.dataclass\nclass C:\n"
              "    xs: tuple = (1,)\n"
              "    ys: list = dataclasses.field("
              "default_factory=list)\n")),
}


@pytest.mark.parametrize("rule", sorted(CASES))
def test_rule_positive(rule):
    c = CASES[rule]
    findings = check_source(c["bad"], c["path"])
    hits = [f for f in findings if f.rule == rule]
    assert hits, f"{rule} missed its seeded violation: {findings}"
    assert hits[0].line == c["line"]
    assert hits[0].path == c["path"]


@pytest.mark.parametrize("rule", sorted(CASES))
def test_rule_negative(rule):
    path, good = CASES[rule]["good"]
    assert not names(check_source(good, path), rule)


@pytest.mark.parametrize("rule", sorted(CASES))
def test_rule_suppressed(rule):
    c = CASES[rule]
    src = suppress_line(c["bad"], c["line"], rule)
    findings = check_source(src, c["path"])
    assert not names(findings, rule)
    # a *used* suppression must not be reported as unused
    assert not names(findings, "unused-suppression")


# ------------------------------------------------------- rule details
def test_banned_solve_catches_numpy_and_scipy_spellings():
    for mod in ("np", "numpy", "jnp", "jax.numpy", "scipy"):
        src = f"d = {mod}.linalg.solve(A, r)\n"
        assert names(check_source(src, "src/repro/core/online.py"),
                     "banned-solve")


def test_no_shim_import_all_spellings_and_scope():
    spellings = (
        "import repro.perfmodel.tpu\n",
        "from repro.perfmodel.tpu import TPU_V5E\n",
        "from repro.perfmodel import tpu\n",
        "import importlib\n"
        "m = importlib.import_module('repro.perfmodel.tpu')\n",
    )
    for src in spellings:
        assert names(check_source(src, "src/repro/core/x.py"),
                     "no-shim-import"), src
    # the shim itself and out-of-src callers (tests) are exempt
    assert not names(check_source(spellings[0],
                                  "src/repro/perfmodel/tpu.py"),
                     "no-shim-import")
    assert not names(check_source(spellings[0],
                                  "tests/test_hardware_transfer.py"),
                     "no-shim-import")


def test_unseeded_rng_spellings():
    bad = (
        "r = np.random.default_rng()\n",
        "r = np.random.default_rng(seed=None)\n",
        "import random\nrandom.seed(3)\n",
        "from random import choice\n",
        "from numpy.random import normal\n",
        "v = np.random.rand(4)\n",
    )
    for src in bad:
        assert names(check_source(src, "src/repro/core/x.py"),
                     "unseeded-rng"), src
    good = (
        "r = np.random.default_rng(0)\n",
        "r = np.random.default_rng(seed)\n",           # variable seed
        "r = np.random.default_rng([seed, idx])\n",    # spawn-key list
        "k = jax.random.split(key, 4)\n",
        "v = rng.random(4)\n",                         # generator draw
    )
    for src in good:
        assert not names(check_source(src, "src/repro/core/x.py"),
                         "unseeded-rng"), src
    # benchmarks and tests sit outside the seed-determinism scope
    assert not names(check_source(bad[0], "benchmarks/run.py"),
                     "unseeded-rng")


def test_wallclock_scope_and_spellings():
    for src in ("t = time.time()\n", "t = time.monotonic()\n",
                "t = datetime.now()\n",
                "t = datetime.datetime.now()\n",
                "from time import time\n"):
        assert names(check_source(src, "src/repro/perfmodel/x.py"),
                     "wallclock-in-sim"), src
    # launch/ measures real compile wall-clock; benchmarks stamp
    # provenance — both out of scope
    assert not names(check_source("t = time.time()\n",
                                  "src/repro/launch/dryrun.py"),
                     "wallclock-in-sim")
    assert not names(check_source("t = time.time()\n",
                                  "benchmarks/run.py"),
                     "wallclock-in-sim")


def test_bench_provenance_ignores_non_bench_dumps():
    src = "import json\npath.write_text(json.dumps(report))\n"
    assert not names(check_source(src, "benchmarks/run.py"),
                     "bench-provenance")


def test_jit_in_loop_decorator_and_shielding():
    deco = ("import jax\nfor s in shapes:\n"
            "    @jax.jit\n    def f(x):\n        return x\n")
    assert names(check_source(deco, "src/repro/core/x.py"),
                 "jit-in-loop")
    partial = ("import functools, jax\nwhile True:\n"
               "    f = functools.partial(jax.jit, "
               "static_argnames=('n',))(g)\n")
    assert names(check_source(partial, "src/repro/core/x.py"),
                 "jit-in-loop")
    # a def inside the loop shields jit calls in its body (they run
    # per call, not per iteration) ...
    shielded = ("import jax\nfor s in shapes:\n"
                "    def make(s=s):\n"
                "        return jax.jit(lambda x: x + s)\n")
    assert not names(check_source(shielded, "src/repro/core/x.py"),
                     "jit-in-loop")
    # ... and a loop *inside* a jitted function is the gbt idiom
    inner = ("import jax\n@jax.jit\ndef f(x):\n"
             "    for _ in range(3):\n        x = x + 1\n"
             "    return x\n")
    assert not names(check_source(inner, "src/repro/core/x.py"),
                     "jit-in-loop")


def test_mutable_default_catches_np_and_ctor_defaults():
    for default in ("np.zeros(3)", "dict()", "collections.deque()",
                    "{}", "[]"):
        src = ("import dataclasses\n@dataclasses.dataclass(frozen=True)\n"
               f"class C:\n    x: object = {default}\n")
        assert names(check_source(src, "src/repro/configs/x.py"),
                     "mutable-default-config"), default
    # non-dataclass classes keep their idioms
    plain = "class C:\n    registry = {}\n"
    assert not names(check_source(plain, "src/repro/configs/x.py"),
                     "mutable-default-config")


# ------------------------------------------------------ suppressions
def test_unused_suppression_detected():
    src = "x = 1  # repro-check: disable=banned-solve\n"
    findings = check_source(src, "src/repro/core/x.py")
    assert names(findings, "unused-suppression")


def test_unknown_rule_in_suppression_detected():
    src = "x = 1  # repro-check: disable=no-such-rule\n"
    findings = check_source(src, "src/repro/core/x.py")
    assert any("unknown rule" in f.message for f in findings)


def test_suppression_for_unselected_rule_tolerated():
    # --rule subset runs must not misread other rules' waivers
    src = ("import jax.numpy as jnp\n"
           "d = jnp.linalg.solve(A, r)"
           "  # repro-check: disable=banned-solve\n")
    only_shim = [RULES_BY_NAME["no-shim-import"]]
    assert not check_source(src, "src/repro/core/x.py", rules=only_shim)


def test_suppression_inside_string_is_content_not_waiver():
    src = 'doc = "# repro-check: disable=banned-solve"\n'
    assert not check_source(src, "src/repro/core/x.py")


def test_multi_rule_suppression_one_used_one_stale():
    src = ("import time\n"
           "t = time.time()"
           "  # repro-check: disable=wallclock-in-sim,banned-solve\n")
    findings = check_source(src, "src/repro/serving/x.py")
    assert not names(findings, "wallclock-in-sim")
    stale = names(findings, "unused-suppression")
    assert len(stale) == 1


def test_parse_error_is_a_finding():
    findings = check_source("def broken(:\n", "src/repro/core/x.py")
    assert names(findings, "parse-error")


# ------------------------------------------------------- self-check
def test_repo_clean_at_head():
    """The acceptance gate: repro-check over src/ and benchmarks/ (and
    the test tree) reports zero findings at HEAD."""
    res = check_paths([REPO / "src", REPO / "benchmarks",
                       REPO / "tests"], root=REPO)
    assert res.n_files > 80
    assert res.ok, "\n".join(f.format() for f in res.findings)


def test_every_rule_registered_and_documented():
    assert len(ALL_RULES) >= 8
    assert set(CASES) == {r.name for r in ALL_RULES}
    catalog = (REPO / "docs" / "static_analysis.md").read_text()
    for r in ALL_RULES:
        assert r.name and r.description and r.contract
        assert f"`{r.name}`" in catalog, \
            f"rule {r.name} missing from docs/static_analysis.md"


# -------------------------------------------------------------- CLI
def test_cli_list_rules(capsys):
    assert cli_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for r in ALL_RULES:
        assert r.name in out


def test_cli_finds_and_formats(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text("import time\nt = time.time()\n")
    # outside any repo root the scoped rules don't apply -> clean
    assert cli_main([str(bad)]) == 0
    # inside a synthetic repo layout the finding fires, with the
    # github annotation format CI consumes
    root = tmp_path / "fake"
    target = root / "src" / "repro" / "serving"
    target.mkdir(parents=True)
    (root / ".git").mkdir()
    f = target / "bad.py"
    f.write_text("import time\nt = time.time()\n")
    capsys.readouterr()
    assert cli_main(["--format=github", str(f)]) == 1
    out = capsys.readouterr().out
    assert "::error file=" in out and "wallclock-in-sim" in out


def test_cli_bad_invocations(capsys):
    assert cli_main(["--rule", "no-such-rule", "src"]) == 2
    assert cli_main(["definitely/not/a/path"]) == 2
    capsys.readouterr()


# ----------------------------------------------------------- tracers
def test_assert_max_compiles_counts_and_gates():
    jax = pytest.importorskip("jax")
    jnp = jax.numpy
    from repro.staticcheck.tracers import (CompileBudgetExceeded,
                                           assert_max_compiles,
                                           count_compiles)
    f = jax.jit(lambda x: x * 2.0)
    x8 = jnp.ones(8)
    f(x8)                                   # warmup compile
    with count_compiles("steady") as rep:
        f(x8)                               # cache hit
    assert rep.count == 0
    with assert_max_compiles(8, label="one new shape") as rep:
        f(jnp.ones(16))
    assert rep.count >= 1
    with pytest.raises(CompileBudgetExceeded, match="budget exceeded"):
        with assert_max_compiles(0, label="must not compile"):
            f(jnp.ones(32))


def test_nan_guard_names_offending_leaf():
    from repro.staticcheck.tracers import nan_guard

    @nan_guard
    def fit():
        return {"params": np.ones(3), "err": np.array([1.0, np.nan])}

    with pytest.raises(FloatingPointError, match=r"\['err'\]"):
        fit()


def test_nan_guard_inf_sentinel_allowed_by_default():
    from repro.staticcheck.tracers import nan_guard
    sentinel = nan_guard(lambda: (np.inf, 0.0))   # degenerate Alg 8
    assert sentinel() == (np.inf, 0.0)
    strict = nan_guard(lambda: (np.inf, 0.0), allow_inf=False)
    with pytest.raises(FloatingPointError):
        strict()


def test_nan_guard_passes_clean_output_through():
    from repro.staticcheck.tracers import nan_guard
    out = nan_guard(lambda: [np.arange(3), {"s": "text", "v": 1.5}])()
    assert out[1]["s"] == "text"
