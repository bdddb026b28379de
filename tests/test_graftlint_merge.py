"""graftlint-merge: tier-1 gate + per-rule fixture corpus + merge audit.

Three jobs, mirroring the other analyzer test modules one layer over:
1. Gate — the gated repo surface lints clean under the merge rules and
   every streamed fold kernel in the manifest reports merge_validated:
   shard-merge byte-identical at P=2 AND P=4, checkpoint-resume
   byte-identical, overlap contract recorded (the tier's acceptance invariant,
   held here).
2. Corpus — every merge rule has a bad fixture that MUST fire and a
   good twin that MUST stay silent.
3. Contract — the auditor turns a too-small corpus into a
   merge-fold-algebra finding, run failures surface as MergeAuditError
   (CLI exit 2), merge findings round-trip through the shared baseline,
   the --merge CLI speaks the same JSON schema as the other modes, and
   --all runs the six tiers with one worst-of exit code.
"""

import json
import os
import subprocess
import sys

import pytest

from avenir_tpu.analysis import load_baseline
from avenir_tpu.analysis.engine import BaselineEntry, run_paths
from avenir_tpu.analysis.manifest import StreamKernelSpec, stream_entries
from avenir_tpu.analysis.merge import (ALL_MERGE_RULES, AUDIT_SHARDS,
                                       MERGE_AUDIT_RULE,
                                       MergeAuditError,
                                       MergeInplaceAliasedStateRule,
                                       MergeMissingOpRule,
                                       MergeOrderSensitiveFloatRule,
                                       MergeUnserializableCarryRule,
                                       audit_merge, merge_rule_ids,
                                       run_merge)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------- gate
def test_merge_gate_clean_and_all_stream_kernels_validated():
    report = run_merge(baseline=load_baseline(), root=REPO)
    assert not report.errors, [f.render() for f in report.errors]
    assert not report.findings, "\n" + "\n".join(
        f.render() for f in report.findings)
    assert not report.stale, [e.key for e in report.stale]
    audit = report.merge_audit
    assert len(audit) == len(stream_entries()) >= 8
    bad = [a["kernel"] for a in audit if not a["merge_validated"]]
    assert not bad, (bad, audit)
    for row in audit:
        assert row["jobs"], row["kernel"]
        assert [s["P"] for s in row["shards"]] == list(AUDIT_SHARDS)
        assert all(s["byte_identical"] for s in row["shards"]), row
        ck = row["checkpoint"]
        # the checkpoint really was MID-scan (carry partially built) and
        # really was serialized (state crossed a bytes boundary)
        assert ck["byte_identical"] and ck["state_bytes"] > 0, row
        assert 1 <= ck["checkpoint_after"] < ck["chunks"], row
        # additive count folds are NOT idempotent — the overlap probe
        # must record that contract for the straggler designs
        assert row["overlap"]["contract"] in ("non-idempotent",
                                              "overlap-insensitive"), row
        # the incremental leg ran through the REAL delta-scan driver:
        # append byte-identity, a genuine mid-delta kill, and a resume
        # that actually skipped the restored prefix
        assert row["incremental_validated"], row
        inc = row["incremental"]
        assert inc["byte_identical"] and inc["resume_interrupted"], row
        assert inc["skipped_bytes"] > 0 and inc["hit_blocks"] > 0, row
        assert 1 <= inc["prefix_blocks"] < inc["blocks"], row
        # the FUSED leg ran through the batched delta-scan driver
        # (run_incremental_shared, the job server's refresh path):
        # same append/kill/resume sequence, every job's carry restored
        fused = inc["fused"]
        assert fused["byte_identical"] and fused["resume_interrupted"], row
        assert fused["skipped_bytes"] > 0, row
        assert fused["jobs"] == len(row["jobs"]), row
        # the sharded-steal leg ran through the REAL block ledger
        # (avenir_tpu.dist): a boundary block folded by two workers
        # committed exactly once — the duplicate was rejected
        # first-commit-wins — and the plan-ordered merge reproduced
        # the cold scan's bytes
        assert row["shard_dedup_validated"], row
        sh = row["sharded"]
        assert sh["dup_rejected"] and sh["committed_once"], row
        assert sh["byte_identical"] and sh["blocks"] >= 4, row


def test_every_stream_entry_carries_fold_specs():
    from avenir_tpu.runner import stream_fold_ops

    for spec in stream_entries():
        assert spec.fold_specs, spec.name
        assert tuple(j for j, _p, _c in spec.fold_specs) == spec.jobs
        for job, _prefix, _conf in spec.fold_specs:
            ops = stream_fold_ops(job)          # raises if unregistered
            assert callable(ops.merge_states)


# ------------------------------------------------- fixture corpus helpers
def _lint(tmp_path, source, rule_cls, name="snippet.py"):
    p = tmp_path / name
    p.write_text(source)
    report = run_paths([str(p)], rules=[rule_cls()], baseline=[],
                       root=str(tmp_path))
    assert not report.errors, [f.render() for f in report.errors]
    return report.findings


_MISSING_BAD = """
class CountSink:
    def __init__(self):
        self.counts = {}

    def consume(self, chunk):
        for key in chunk:
            self.counts[key] = self.counts.get(key, 0) + 1

    def finish(self, out):
        return self.counts
"""

_MISSING_GOOD = """
class CountSink:
    def __init__(self):
        self.counts = {}

    def consume(self, chunk):
        for key in chunk:
            self.counts[key] = self.counts.get(key, 0) + 1

    def merge(self, other):
        for key, cnt in other.counts.items():
            self.counts[key] = self.counts.get(key, 0) + cnt
        return self

    def finish(self, out):
        return self.counts
"""


def test_merge_missing_op_fires_on_bad(tmp_path):
    findings = _lint(tmp_path, _MISSING_BAD, MergeMissingOpRule)
    assert {f.rule for f in findings} == {"merge-missing-op"}
    assert len(findings) == 1, [f.render() for f in findings]


def test_merge_missing_op_silent_on_good(tmp_path):
    assert _lint(tmp_path, _MISSING_GOOD, MergeMissingOpRule) == []


_FLOAT_BAD = """
import numpy as np

class MeanSink:
    def __init__(self):
        self.total = 0.0                  # float carry
        self.moments = np.zeros(4)        # float64 default

    def consume(self, chunk):
        self.total += chunk.sum()         # reassociates under merge: fires
        self.moments += chunk.mean(axis=0)  # same: fires

    def merge(self, other):
        self.total += other.total
        return self

    def finish(self, out):
        return self.total
"""

_FLOAT_GOOD = """
import numpy as np

class CountSink:
    def __init__(self):
        self.n = 0                        # int carry: exact
        self.counts = np.zeros(4, np.int64)

    def consume(self, chunk):
        self.n += len(chunk)              # int accumulation: silent
        self.counts += np.bincount(chunk, minlength=4)

    def merge(self, other):
        self.n += other.n
        self.counts += other.counts
        return self

    def finish(self, out):
        return self.n
"""


def test_order_sensitive_float_fires_on_bad(tmp_path):
    findings = _lint(tmp_path, _FLOAT_BAD, MergeOrderSensitiveFloatRule)
    assert {f.rule for f in findings} == {"merge-order-sensitive-float"}
    assert len(findings) == 2, [f.render() for f in findings]


def test_order_sensitive_float_silent_on_good(tmp_path):
    assert _lint(tmp_path, _FLOAT_GOOD, MergeOrderSensitiveFloatRule) == []


_ALIAS_BAD = """
_SHARED_CACHE = {}

class CachedSink:
    def __init__(self, key):
        self.state = []
        _SHARED_CACHE[key] = self.state   # carry aliased into a cache

    def consume(self, chunk):
        self.state.append(chunk)          # in-place growth: stale alias

    def merge(self, other):
        self.state.extend(other.state)
        return self

    def finish(self, out):
        return self.state
"""

_ALIAS_GOOD = """
_SHARED_CACHE = {}

class RebindSink:
    def __init__(self, key):
        self.state = ()
        _SHARED_CACHE[key] = key          # the KEY escapes, not the carry

    def consume(self, chunk):
        self.state = self.state + (chunk,)   # rebinds: old alias inert

    def merge(self, other):
        self.state = self.state + other.state
        return self

    def finish(self, out):
        return self.state
"""


def test_inplace_aliased_state_fires_on_bad(tmp_path):
    findings = _lint(tmp_path, _ALIAS_BAD, MergeInplaceAliasedStateRule)
    assert {f.rule for f in findings} == {"merge-inplace-aliased-state"}
    assert len(findings) == 1, [f.render() for f in findings]


def test_inplace_aliased_state_silent_on_good(tmp_path):
    assert _lint(tmp_path, _ALIAS_GOOD, MergeInplaceAliasedStateRule) == []


_SERIAL_BAD = """
class FileSink:
    def __init__(self, path):
        self.fh = open(path)              # open handle in the carry
        self.lines = (ln for ln in self.fh)   # and a live generator

    def consume(self, chunk):
        pass

    def merge(self, other):
        return self

    def finish(self, out):
        return sum(1 for _ in self.lines)
"""

_SERIAL_GOOD = """
class PathSink:
    def __init__(self, path):
        self.path = path                  # plain data: re-opened on use
        self.n = 0

    def consume(self, chunk):
        self.n += len(chunk)

    def merge(self, other):
        self.n += other.n
        return self

    def state_dict(self):
        return {"n": self.n}

    def load_state(self, state):
        self.n = int(state["n"])

    def finish(self, out):
        return self.n
"""


def test_unserializable_carry_fires_on_bad(tmp_path):
    findings = _lint(tmp_path, _SERIAL_BAD, MergeUnserializableCarryRule)
    assert {f.rule for f in findings} == {"merge-unserializable-carry"}
    assert len(findings) == 2, [f.render() for f in findings]


def test_unserializable_carry_silent_on_good(tmp_path):
    assert _lint(tmp_path, _SERIAL_GOOD, MergeUnserializableCarryRule) == []


def test_every_merge_rule_has_corpus_coverage():
    covered = {"merge-missing-op", "merge-order-sensitive-float",
               "merge-inplace-aliased-state", "merge-unserializable-carry"}
    assert {r.rule_id for r in ALL_MERGE_RULES} == covered
    assert set(merge_rule_ids()) == covered | {MERGE_AUDIT_RULE}


# ------------------------------------------------------------ the auditor
def test_auditor_flags_a_corpus_too_small_to_shard(tmp_path):
    spec = next(s for s in stream_entries() if s.name == "nb_stream")

    def tiny_prepare(workdir):
        ctx = spec.prepare(workdir)
        with open(ctx["csv"], "w") as fh:       # one row: one block
            fh.write("c0,low,low,low,poor,12,open\n")
        return ctx

    tiny = StreamKernelSpec(
        "tiny_nb", spec.path, spec.line, tiny_prepare, spec.run,
        jobs=spec.jobs, fold_specs=spec.fold_specs)
    row, finding = audit_merge(tiny)
    assert row["merge_validated"] is False
    assert row["incremental_validated"] is False
    assert row["shard_dedup_validated"] is False
    assert row["shards"] == [] and row["checkpoint"] is None
    assert row["incremental"] is None and row["sharded"] is None
    assert finding is not None and finding.rule == MERGE_AUDIT_RULE
    assert "too small" in finding.message


def test_auditor_wraps_run_failures_as_exit2_errors():
    spec = next(s for s in stream_entries() if s.name == "nb_stream")

    def boom(ctx, block_mb):
        raise ValueError("synthetic fold failure")

    broken = StreamKernelSpec(
        "boom_kernel", spec.path, spec.line, spec.prepare, boom,
        jobs=spec.jobs, fold_specs=spec.fold_specs)
    with pytest.raises(MergeAuditError, match="boom_kernel"):
        audit_merge(broken)


def test_auditor_requires_fold_specs():
    spec = next(s for s in stream_entries() if s.name == "nb_stream")
    bare = StreamKernelSpec(
        "bare_kernel", spec.path, spec.line, spec.prepare, spec.run,
        jobs=spec.jobs)                          # no fold_specs
    with pytest.raises(MergeAuditError, match="fold_specs"):
        audit_merge(bare)


def test_merge_findings_roundtrip_through_baseline(tmp_path):
    p = tmp_path / "mod.py"
    p.write_text(_MISSING_BAD)
    key = "mod.py::merge-missing-op::<module>"
    report = run_merge(paths=[str(p)], baseline=[
        BaselineEntry(key, "fixture", 1)], root=str(tmp_path), audit=False)
    assert not report.findings and len(report.suppressed) == 1

    p.write_text(_MISSING_GOOD)
    report = run_merge(paths=[str(p)], baseline=[
        BaselineEntry(key, "fixture", 1)], root=str(tmp_path), audit=False)
    assert [e.key for e in report.stale] == [key]


# -------------------------------------------------------------------- CLI
def _cli(args, cwd=REPO, env=None):
    e = dict(os.environ)
    if env:
        e.update(env)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "graftlint.py")] + args,
        capture_output=True, text=True, cwd=cwd, timeout=600, env=e)


def test_cli_merge_exit_code_contract_and_schema(tmp_path):
    # bad fixture + rule subset (audit skipped -> fast): findings = 1
    (tmp_path / "bad.py").write_text(_MISSING_BAD)
    proc = _cli(["--merge", "bad.py", "--rules", "merge-missing-op",
                 "--no-baseline", "--json"], cwd=str(tmp_path))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    rep = json.loads(proc.stdout)
    assert rep["counts"] == {"merge-missing-op": 1}
    assert rep["merge_audit"] == []           # subset skipped the audit
    # one schema across all modes: same top-level keys as the golden
    golden = json.load(open(os.path.join(
        REPO, "tests", "data", "graftlint_json_golden.json")))
    assert set(rep) == set(golden)
    assert "merge_audit" in golden

    # good twin: clean = 0
    (tmp_path / "good.py").write_text(_MISSING_GOOD)
    proc = _cli(["--merge", "good.py", "--rules", "merge-missing-op",
                 "--no-baseline"], cwd=str(tmp_path))
    assert proc.returncode == 0, proc.stdout + proc.stderr

    # usage errors = 2: unknown rule, and mixed tiers
    assert _cli(["--merge", "--rules", "nope"]).returncode == 2
    assert _cli(["--merge", "--ir"]).returncode == 2
    assert _cli(["--merge", "--flow"]).returncode == 2
    assert _cli(["--merge", "--mem"]).returncode == 2


def test_cli_all_worst_of_exit_and_combined_schema(tmp_path):
    # --all with a cross-tier rule subset: the bad fixture fires the
    # merge rule (exit 1), tiers with no selected rules are skipped —
    # the fast CI shape; the full depth is the operator's
    # `python tools/graftlint.py --all`
    (tmp_path / "bad.py").write_text(_MISSING_BAD)
    proc = _cli(["--all", "bad.py", "--rules",
                 "merge-missing-op,default-int64", "--no-baseline",
                 "--json"], cwd=str(tmp_path))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    rep = json.loads(proc.stdout)
    assert set(rep) == {"modes", "clean"} and rep["clean"] is False
    assert set(rep["modes"]) == {"ast", "ir", "flow", "mem", "merge",
                                 "proto", "race", "keys"}
    assert rep["modes"]["ir"] == {"skipped": True}
    assert rep["modes"]["merge"]["counts"] == {"merge-missing-op": 1}

    # good twin: every selected tier clean = 0
    (tmp_path / "good.py").write_text(_MISSING_GOOD)
    proc = _cli(["--all", "good.py", "--rules",
                 "merge-missing-op,default-int64", "--no-baseline"],
                cwd=str(tmp_path))
    assert proc.returncode == 0, proc.stdout + proc.stderr

    # usage errors = 2: --all combined with a single-tier flag
    assert _cli(["--all", "--merge"]).returncode == 2
    assert _cli(["--all", "--ir"]).returncode == 2
    # unknown rule still refused with --all (union of all six catalogs)
    assert _cli(["--all", "--rules", "nope"]).returncode == 2
