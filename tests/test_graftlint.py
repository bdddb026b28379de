"""graftlint: tier-1 hazard gate + per-rule fixture corpus.

Two jobs:
1. Gate — the whole repo surface (package, tests, docs fences, tools,
   benches) must lint clean against the allowlist baseline, with no stale
   baseline entries. New hazards fail the suite the round they land.
2. Corpus — every rule has known-bad snippets that MUST fire and
   known-good twins that MUST stay silent, so a rule can't silently stop
   firing (disable any rule and its corpus test fails).
"""

import json
import os
import subprocess
import sys

import pytest

from avenir_tpu.analysis import load_baseline, run_paths
from avenir_tpu.analysis.rules import (ALL_RULES, DefaultInt64Rule,
                                       FoldUndonatedCarryRule,
                                       HostSyncInFoldRule,
                                       Int64LiteralInJnpRule,
                                       RecompileHazardRule,
                                       ShardedHostMaterializeRule,
                                       TracerLeakRule,
                                       UnseededStochasticTestRule)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GATED = ["avenir_tpu", "tests", "docs", "tools", "__graft_entry__.py"]


# ------------------------------------------------------------------- gate
def test_repo_lints_clean_against_baseline():
    report = run_paths([os.path.join(REPO, p) for p in GATED],
                       baseline=load_baseline(), root=REPO)
    assert not report.errors, [f.render() for f in report.errors]
    assert not report.findings, "\n" + "\n".join(
        f.render() for f in report.findings)
    assert not report.stale, [e.key for e in report.stale]
    assert len(report.scanned) > 50


def test_baseline_entries_all_used():
    """Every AST-tier allowlist entry must still excuse a live finding
    somewhere in the gated surface (stale entries are dead weight that
    would mask a regression landing in the same scope). The baseline is
    shared across tiers — flow/mem entries are enforced the same way by
    their own gate tests (stale detection is rule-active-aware)."""
    from avenir_tpu.analysis.rules import rule_ids

    baseline = load_baseline()
    assert baseline, "baseline file missing or empty"
    ast_ids = set(rule_ids())
    ast_entries = [e for e in baseline if e.key.split("::")[1] in ast_ids]
    assert ast_entries, "no AST-tier entries left in the baseline?"
    report = run_paths([os.path.join(REPO, p) for p in GATED],
                       baseline=baseline, root=REPO)
    assert len(report.suppressed) >= len(ast_entries)


# ------------------------------------------------- fixture corpus helpers
def _lint(tmp_path, source, rule_cls, name="snippet.py"):
    p = tmp_path / name
    p.write_text(source)
    report = run_paths([str(p)], rules=[rule_cls()], baseline=[],
                       root=str(tmp_path))
    assert not report.errors, [f.render() for f in report.errors]
    return report.findings


_INT64_BAD = """
import numpy as np

def fold(blocks):
    out = 0
    for b in blocks:
        idx = np.argsort(b)            # always-int64 index array
        acc = np.cumsum(b)             # 64-bit accumulator by default
        z = np.zeros(b.shape[0])       # float64 by default
        hits = [np.flatnonzero(r) for r in b]   # comprehension = loop
        out += z[idx[0]] + acc[-1] + len(hits)
    return out
"""

_INT64_GOOD = """
import numpy as np

def fold(blocks):
    base = np.arange(100)              # outside any loop: cold path
    out = 0
    for b in blocks:
        acc = np.cumsum(b, dtype=np.int32)
        z = np.zeros(b.shape[0], np.float32)
        keys = np.full(b.shape[0], "")          # dtype follows the str fill
        m = np.ones(b.shape[0], bool)           # positional narrow dtype
        out += z[0] + acc[-1] + m.sum() + (keys == "").sum()
    for u in np.argsort(base):                  # for-iter evaluates once
        out += u
    return out
"""


def test_default_int64_fires_on_bad(tmp_path):
    findings = _lint(tmp_path, _INT64_BAD, DefaultInt64Rule)
    assert {f.rule for f in findings} == {"default-int64"}
    assert len(findings) == 4, [f.render() for f in findings]
    assert all(f.scope == "fold" for f in findings)


def test_default_int64_silent_on_good(tmp_path):
    assert _lint(tmp_path, _INT64_GOOD, DefaultInt64Rule) == []


_SYNC_BAD = """
import jax
import jax.numpy as jnp
import numpy as np

@jax.jit
def kernel(x):
    return x.sum()

def fold(chunks):
    tot = 0.0
    for c in chunks:
        tot += float(kernel(jnp.asarray(c)))        # scalar sync
        tot += np.asarray(kernel(jnp.asarray(c)))   # array sync
        jax.device_get(c)                           # explicit sync
        tot += c.mean().item()                      # .item() sync
    return tot
"""

_SYNC_GOOD = """
import jax
import jax.numpy as jnp
import numpy as np

@jax.jit
def kernel(x):
    return x.sum()

def fold(chunks):
    tot = jnp.zeros((), jnp.float32)
    for c in chunks:
        tot = tot + kernel(jnp.asarray(c))   # stays on device
    return float(tot)                        # one sync, after the loop
"""


def test_host_sync_fires_on_bad(tmp_path):
    findings = _lint(tmp_path, _SYNC_BAD, HostSyncInFoldRule)
    assert {f.rule for f in findings} == {"host-sync-in-fold"}
    assert len(findings) == 4, [f.render() for f in findings]


def test_host_sync_silent_on_good(tmp_path):
    assert _lint(tmp_path, _SYNC_GOOD, HostSyncInFoldRule) == []


_RECOMPILE_BAD = """
import jax
import jax.numpy as jnp

def per_item(xs):
    out = []
    for x in xs:
        out.append(jax.jit(lambda v: v + 1)(x))   # fresh wrapper per iter
    return out

@jax.jit
def pad_to(x, n):
    return x + jnp.zeros(n)                       # traced param as shape

def make_step(m):
    width = m * 2
    @jax.jit
    def step(x):
        return x + jnp.ones(width)                # closure local as shape
    return step
"""

_RECOMPILE_GOOD = """
from functools import partial
import jax
import jax.numpy as jnp

@partial(jax.jit, static_argnames=("n",))
def pad_to(x, n):
    return x + jnp.zeros(n)                       # static: cache per bucket

@jax.jit
def doubled(x):
    n = x.shape[0]
    return x + jnp.zeros(n)                       # operand-derived shape

_WIDTH = 8

@jax.jit
def widened(x):
    return x + jnp.ones(_WIDTH)                   # module constant: stable
"""


def test_recompile_hazard_fires_on_bad(tmp_path):
    findings = _lint(tmp_path, _RECOMPILE_BAD, RecompileHazardRule)
    assert {f.rule for f in findings} == {"recompile-hazard"}
    scopes = {f.scope for f in findings}
    assert "per_item" in scopes                  # jit-in-loop
    assert "pad_to" in scopes                    # traced shape param
    assert "make_step.step" in scopes            # closure shape capture
    assert len(findings) == 3, [f.render() for f in findings]


def test_recompile_hazard_silent_on_good(tmp_path):
    assert _lint(tmp_path, _RECOMPILE_GOOD, RecompileHazardRule) == []


_LEAK_BAD = """
import jax

_cache = None

class Model:
    @jax.jit
    def step(self, x):
        self.state = x * 2                        # tracer onto instance
        return x

@jax.jit
def leak(x):
    global _cache                                 # tracer into module state
    _cache = x
    return x
"""

_LEAK_GOOD = """
import jax

class Model:
    @jax.jit
    def _step(self, x):
        return x * 2

    def update(self, x):
        self.state = self._step(x)   # store AFTER the jit boundary
        return self.state
"""


def test_tracer_leak_fires_on_bad(tmp_path):
    findings = _lint(tmp_path, _LEAK_BAD, TracerLeakRule)
    assert {f.rule for f in findings} == {"tracer-leak"}
    assert len(findings) == 2, [f.render() for f in findings]


def test_tracer_leak_silent_on_good(tmp_path):
    assert _lint(tmp_path, _LEAK_GOOD, TracerLeakRule) == []


_UNSEEDED_BAD = """
import numpy as np
import jax
import time

def test_mean_is_small():
    x = np.random.default_rng().normal(size=100)   # unseeded generator
    assert abs(x.mean()) < 0.5

def test_global_rng():
    x = np.random.normal(size=100)                 # global numpy state
    assert x.std() > 0

def test_clock_key():
    key = jax.random.key(int(time.time()))         # entropy-source key
    assert jax.random.uniform(key) < 1.0
"""

_UNSEEDED_GOOD = """
import numpy as np
import jax

def test_seeded():
    x = np.random.default_rng(7).normal(size=100)  # pinned generator
    key = jax.random.key(42)                       # pinned key
    keys = [jax.random.key(7 + i) for i in range(3)]   # deterministic expr
    assert abs(x.mean()) < 0.5 and len(keys) == 3 and key is not None

def helper_without_asserts():
    return np.random.normal(size=10)               # no assert in scope
"""


def test_unseeded_stochastic_fires_on_bad(tmp_path):
    findings = _lint(tmp_path, _UNSEEDED_BAD, UnseededStochasticTestRule)
    assert {f.rule for f in findings} == {"unseeded-stochastic-test"}
    assert len(findings) == 3, [f.render() for f in findings]


def test_unseeded_stochastic_silent_on_good(tmp_path):
    assert _lint(tmp_path, _UNSEEDED_GOOD, UnseededStochasticTestRule) == []


_SHARDED_BAD = """
import jax
import numpy as np
from avenir_tpu.parallel.mesh import shard_rows

def gather(mesh, arr, spec):
    xs = shard_rows(mesh, arr)
    host = np.asarray(xs)                          # gathers every shard
    direct = np.array(jax.device_put(arr, spec))   # direct wrap
    return host.sum() + direct.sum()
"""

_SHARDED_GOOD = """
import jax
import jax.numpy as jnp
import numpy as np
from avenir_tpu.parallel.mesh import shard_rows

def fine(mesh, arr, spec):
    xs = shard_rows(mesh, np.asarray(arr))   # prepares placement: host->dev
    on_dev = jnp.asarray(xs)                 # jnp view of a device array
    host = jax.device_get(xs)                # the sanctioned transfer
    plain = np.array(arr)                    # plain host array
    return on_dev.sum() + host.sum() + plain.sum()
"""


def test_sharded_host_materialize_fires_on_bad(tmp_path):
    findings = _lint(tmp_path, _SHARDED_BAD, ShardedHostMaterializeRule)
    assert {f.rule for f in findings} == {"sharded-host-materialize"}
    assert len(findings) == 2, [f.render() for f in findings]
    assert all(f.scope == "gather" for f in findings)


def test_sharded_host_materialize_silent_on_good(tmp_path):
    assert _lint(tmp_path, _SHARDED_GOOD, ShardedHostMaterializeRule) == []


_BIGLIT_BAD = """
import jax.numpy as jnp

def encode(ids):
    base = jnp.full((4,), 10_000_000_000)    # spelled-out wide literal
    mask = jnp.asarray(1 << 40)              # folded shift
    scale = jnp.array([2 ** 40])             # folded power inside a list
    return base + mask + scale
"""

_BIGLIT_GOOD = """
import jax.numpy as jnp
import numpy as np

def fine(ids):
    small = jnp.full((4,), 1 << 20)          # fits int32
    host = np.asarray([1 << 40])             # host numpy is 64-bit land
    f = jnp.asarray(2.5e12)                  # float literal, not an int
    nested = jnp.asarray(np.asarray([1 << 40]) & 0xFF)   # literal lives in
    return small.sum() + host.sum() + f + nested.sum()   # the host call
"""


def test_int64_literal_fires_on_bad(tmp_path):
    findings = _lint(tmp_path, _BIGLIT_BAD, Int64LiteralInJnpRule)
    assert {f.rule for f in findings} == {"int64-literal-in-jnp"}
    assert len(findings) == 3, [f.render() for f in findings]


def test_int64_literal_silent_on_good(tmp_path):
    assert _lint(tmp_path, _BIGLIT_GOOD, Int64LiteralInJnpRule) == []


_CARRY_BAD = """
import jax
import jax.numpy as jnp
from functools import partial

@jax.jit
def fold(acc, x):
    return acc + x.sum(axis=0)

@partial(jax.jit, donate_argnums=())
def fold_explicit_nodonate(acc, x):
    return acc + x.sum(axis=0)

class Miner:
    def run(self, chunks):
        self.acc = jnp.zeros((4,))
        for x in chunks:
            self.acc = fold(self.acc, x)        # undonated self-attr carry
        return self.acc

def count(chunks):
    acc = jnp.zeros((4,))
    for x in chunks:
        acc = fold_explicit_nodonate(acc, x)    # empty donate tuple = none
    return acc
"""

_CARRY_GOOD = """
import jax
import jax.numpy as jnp
from functools import partial

@partial(jax.jit, donate_argnums=(0,))
def fold(acc, x):
    return acc + x.sum(axis=0)

@jax.jit
def score(x):
    return x.sum(axis=0)

def count(chunks):
    acc = jnp.zeros((4,))
    for x in chunks:
        acc = fold(acc, x)          # donated carry: the sanctioned shape
        s = score(x)                # jitted call, but no carry argument
        acc = acc + s
    once = fold(acc, acc)           # carry shape, but not inside a loop
    return once
"""


def test_fold_undonated_carry_fires_on_bad(tmp_path):
    findings = _lint(tmp_path, _CARRY_BAD, FoldUndonatedCarryRule)
    assert {f.rule for f in findings} == {"fold-undonated-carry"}
    assert len(findings) == 2, [f.render() for f in findings]
    assert {f.scope for f in findings} == {"Miner.run", "count"}


def test_fold_undonated_carry_silent_on_good(tmp_path):
    assert _lint(tmp_path, _CARRY_GOOD, FoldUndonatedCarryRule) == []


def test_every_rule_has_corpus_coverage():
    """Each registered rule appears in this module's fixture corpus, so
    adding a rule without tests fails loudly."""
    covered = {"default-int64", "host-sync-in-fold", "recompile-hazard",
               "tracer-leak", "unseeded-stochastic-test",
               "sharded-host-materialize", "int64-literal-in-jnp",
               "fold-undonated-carry"}
    assert {r.rule_id for r in ALL_RULES} == covered


# ------------------------------------------------------- engine mechanics
def test_markdown_fences_lint_with_real_line_numbers(tmp_path):
    md = tmp_path / "tutorial.md"
    md.write_text(
        "# doc\n\nprose\n\n```python\nimport numpy as np\n"
        "x = np.random.normal(size=5)\nassert x.std() > 0\n```\n")
    findings = _lint(tmp_path, md.read_text(), UnseededStochasticTestRule,
                     name="tutorial2.md")
    assert len(findings) == 1
    # the fence starts at line 5 of the md file; the draw is line 7
    assert findings[0].line == 7
    assert findings[0].path.endswith("tutorial2.md")


def test_baseline_suppresses_and_goes_stale(tmp_path):
    from avenir_tpu.analysis.engine import BaselineEntry

    p = tmp_path / "mod.py"
    p.write_text(_INT64_BAD)
    key = "mod.py::default-int64::fold"
    entry = BaselineEntry(key, "test justification", 1)
    report = run_paths([str(p)], rules=[DefaultInt64Rule()],
                       baseline=[entry], root=str(tmp_path))
    assert not report.findings and len(report.suppressed) == 4

    p.write_text(_INT64_GOOD)
    report = run_paths([str(p)], rules=[DefaultInt64Rule()],
                       baseline=[BaselineEntry(key, "test", 1)],
                       root=str(tmp_path))
    assert [e.key for e in report.stale] == [key]


def test_baseline_file_requires_justifications(tmp_path):
    from avenir_tpu.analysis.engine import load_baseline as load

    bad = tmp_path / "baseline.txt"
    bad.write_text("a.py::default-int64::f\n")
    with pytest.raises(ValueError):
        load(str(bad))
    ok = tmp_path / "baseline2.txt"
    ok.write_text("# comment\n\na.py::default-int64::f -- because\n")
    entries = load(str(ok))
    assert len(entries) == 1 and entries[0].justification == "because"


# ------------------------------------------------------------------- CLI
def _cli(args, cwd):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "graftlint.py")] + args,
        capture_output=True, text=True, cwd=cwd, timeout=120)


def test_cli_exit_codes_and_json(tmp_path):
    (tmp_path / "bad.py").write_text(_INT64_BAD)
    proc = _cli(["bad.py", "--json"], str(tmp_path))
    assert proc.returncode == 1, proc.stderr
    rep = json.loads(proc.stdout)
    assert rep["counts"] == {"default-int64": 4}
    assert not rep["clean"]
    assert all(k in rep["findings"][0]
               for k in ("path", "line", "rule", "hint", "key"))

    base = tmp_path / "allow.txt"
    base.write_text("bad.py::default-int64::fold -- fixture\n")
    proc = _cli(["bad.py", "--baseline", str(base), "--json"], str(tmp_path))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert json.loads(proc.stdout)["suppressed"] == 4

    (tmp_path / "good.py").write_text(_INT64_GOOD)
    proc = _cli(["good.py", "--baseline", str(base)], str(tmp_path))
    assert proc.returncode == 0   # entry targets an unscanned file: not stale
    base.write_text("good.py::default-int64::fold -- now stale\n")
    proc = _cli(["good.py", "--baseline", str(base)], str(tmp_path))
    assert proc.returncode == 1 and "stale" in proc.stderr
    proc = _cli(["good.py", "--baseline", str(base), "--allow-stale"],
                str(tmp_path))
    assert proc.returncode == 0


def test_cli_rule_subset_and_unknown_rule(tmp_path):
    (tmp_path / "bad.py").write_text(_SYNC_BAD)
    proc = _cli(["bad.py", "--rules", "default-int64", "--no-baseline",
                 "--json"], str(tmp_path))
    assert proc.returncode == 0, proc.stdout   # sync findings filtered out
    proc = _cli(["bad.py", "--rules", "nope"], str(tmp_path))
    assert proc.returncode == 2


def test_cli_rule_subset_does_not_stale_other_rules_entries():
    """--rules tracer-leak must not report the default-int64/host-sync
    baseline entries as stale (their rules didn't run)."""
    proc = _cli(["avenir_tpu/", "--rules", "tracer-leak", "--json"], REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert json.loads(proc.stdout)["stale_baseline_entries"] == []


def test_cli_baseline_matches_from_any_cwd(tmp_path):
    """Finding keys anchor to the repo root, not os.getcwd(): the gate
    must pass no matter where the CLI is invoked from."""
    proc = _cli([os.path.join(REPO, "avenir_tpu"), "--json"], str(tmp_path))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rep = json.loads(proc.stdout)
    assert rep["clean"] and rep["suppressed"] >= 14


def test_cli_package_gate_matches_inprocess_gate():
    proc = _cli(["avenir_tpu/", "--json"], REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rep = json.loads(proc.stdout)
    assert rep["clean"] and rep["findings"] == []


def test_json_output_matches_golden(tmp_path):
    """Golden-file check of the --json schema: whoever reads the report
    (an operator's script, CI) parses these exact keys, so a schema drift
    must fail a test, not a reader three rounds later.
    The golden file is the FULL object for a fixed fixture — keys, value
    types, and stable values."""
    (tmp_path / "bad.py").write_text(_INT64_BAD)
    proc = _cli(["bad.py", "--no-baseline", "--json"], str(tmp_path))
    assert proc.returncode == 1, proc.stderr
    got = json.loads(proc.stdout)
    golden_path = os.path.join(REPO, "tests", "data",
                               "graftlint_json_golden.json")
    golden = json.load(open(golden_path))
    assert got == golden, (
        f"--json schema drifted from {golden_path}; if the change is "
        f"intentional, update the golden file AND every consumer")


def test_baseline_stale_roundtrip_cli(tmp_path):
    """The full allowlist lifecycle through the CLI: finding (exit 1) ->
    baselined (exit 0) -> code fixed, entry stale (exit 1) -> entry
    deleted (exit 0). Each transition is the exit-code contract's '1'
    meaning something different, so pin all four."""
    src = tmp_path / "mod.py"
    base = tmp_path / "allow.txt"
    src.write_text(_INT64_BAD)
    base.write_text("")
    assert _cli(["mod.py", "--baseline", str(base)],
                str(tmp_path)).returncode == 1
    base.write_text("mod.py::default-int64::fold -- accepted for the test\n")
    assert _cli(["mod.py", "--baseline", str(base)],
                str(tmp_path)).returncode == 0
    src.write_text(_INT64_GOOD)                     # hazard fixed
    proc = _cli(["mod.py", "--baseline", str(base)], str(tmp_path))
    assert proc.returncode == 1 and "stale" in proc.stderr
    base.write_text("")                             # entry deleted
    assert _cli(["mod.py", "--baseline", str(base)],
                str(tmp_path)).returncode == 0


def test_cli_exit_code_contract(tmp_path):
    """0 clean / 1 findings / 2 usage-or-trace-error — stable for CI."""
    (tmp_path / "good.py").write_text(_INT64_GOOD)
    (tmp_path / "bad.py").write_text(_INT64_BAD)
    assert _cli(["good.py", "--no-baseline"], str(tmp_path)).returncode == 0
    assert _cli(["bad.py", "--no-baseline"], str(tmp_path)).returncode == 1
    # usage errors: no paths / unknown rule / malformed baseline
    assert _cli([], str(tmp_path)).returncode == 2
    assert _cli(["good.py", "--rules", "nope"], str(tmp_path)).returncode == 2
    bad_base = tmp_path / "broken.txt"
    bad_base.write_text("no-justification-here\n")
    assert _cli(["good.py", "--baseline", str(bad_base)],
                str(tmp_path)).returncode == 2
