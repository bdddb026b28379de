"""graftlint --race: rules, interleave sites, the turnstile scheduler.

Four layers, mirroring the other tier test suites:

- the GATE: the real protocol surface is race-clean and every
  registered interleave site validates under schedule exploration
  (reduced depth/seeds here for suite wall time; the full
  configuration is the operator's `python tools/graftlint.py --all`);
- the REGISTRY: sched_point call sites and INTERLEAVE_SITES agree in
  both directions, and a mismatch in either direction fails loudly;
- the RULES: one bad/good fixture pair per static rule;
- the AUDITOR: schedules replay deterministically, and a deliberately
  racy check-then-act claim protocol FAILS with a concrete
  double-claim whose printed trace replays to the same verdict.
"""

import json
import os
import subprocess
import sys
import threading

import pytest

from avenir_tpu.analysis import load_baseline
from avenir_tpu.analysis.engine import BaselineEntry, run_paths
from avenir_tpu.analysis.race import (ALL_RACE_RULES, INTERLEAVE_SITES,
                                      RACE_AUDIT_RULE, CheckThenActRule,
                                      DeleteWhileCheckedOutRule,
                                      InterleaveSite,
                                      MonotonicPersistedRule,
                                      RaceAuditError,
                                      RmwSharedRecordRule,
                                      SITE_MODULE_ENV,
                                      StaleListdirSnapshotRule,
                                      _ActorPool, _replay_decider,
                                      _run_schedule, _seeded_decider,
                                      audit_interleavings,
                                      check_sched_registry,
                                      parse_schedule, race_rule_ids,
                                      run_race, sched_annotations)
from avenir_tpu.core.atomic import SCHED_ENV, sched_point

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------- gate
def test_race_gate_clean_and_all_sites_validated():
    report = run_race(baseline=load_baseline(), root=REPO,
                      depth=2, seeds=8)
    assert not report.errors, [f.render() for f in report.errors]
    assert not report.findings, "\n" + "\n".join(
        f.render() for f in report.findings)
    assert not report.stale, [e.key for e in report.stale]
    audit = report.race_audit
    # the N/N acceptance floor: every registered site, >= 8 of them
    assert len(audit) == len(INTERLEAVE_SITES) >= 8
    bad = [a["site"] for a in audit if not a["interleaving_validated"]]
    assert not bad, (bad, audit)
    for row in audit:
        # real schedules actually ran, and the row is anchored at the
        # site's first sched_point annotation in the code
        assert row["schedules"]["exhaustive"] == 4, row
        assert row["schedules"]["seeded"] == 8, row
        assert row["failing_schedule"] is None, row
        assert row["path"].endswith(".py") and row["line"] > 1, row


def test_registry_and_code_annotations_agree():
    refs = sched_annotations(REPO)
    want = set()
    for site in INTERLEAVE_SITES:
        want.update(site.sched)
    assert set(refs) == want
    assert check_sched_registry(REPO) == refs


def test_registry_fails_on_dangling_site_entry(monkeypatch):
    from avenir_tpu.analysis import race as race_mod

    ghost = InterleaveSite(
        "ghost.site", "nowhere.py", ("ghost.hook",),
        lambda root: None, (lambda root: {}, lambda root: {}),
        lambda *a: [])
    monkeypatch.setattr(race_mod, "INTERLEAVE_SITES",
                        list(INTERLEAVE_SITES) + [ghost])
    with pytest.raises(RaceAuditError, match="ghost.hook"):
        check_sched_registry(REPO)


def test_registry_fails_on_unregistered_hook(monkeypatch):
    from avenir_tpu.analysis import race as race_mod

    # dropping the cand.publish site leaves its sched_point call sites
    # in dist/driver.py and dist/worker.py orphaned — the cross-check
    # must refuse (an unstepped hook is a guaranteed actor stall)
    pruned = [s for s in INTERLEAVE_SITES if s.name != "cand.publish"]
    monkeypatch.setattr(race_mod, "INTERLEAVE_SITES", pruned)
    with pytest.raises(RaceAuditError, match="cand.publish"):
        check_sched_registry(REPO)


# ------------------------------------------------- fixture corpus helpers
def _lint(tmp_path, source, rule_cls, name="snippet.py"):
    p = tmp_path / name
    p.write_text(source)
    report = run_paths([str(p)], rules=[rule_cls()], baseline=[],
                       root=str(tmp_path))
    assert not report.errors, [f.render() for f in report.errors]
    return report.findings


_CTA_BAD = """
import os

def adopt(marker_path):
    if os.path.exists(marker_path):
        os.remove(marker_path)         # vanished under us -> OSError
"""

_CTA_GOOD = """
import os

def adopt(marker_path):
    try:
        os.remove(marker_path)         # EAFP: losing the race is fine
    except OSError:
        pass
"""


def test_check_then_act_fires_on_bad(tmp_path):
    findings = _lint(tmp_path, _CTA_BAD, CheckThenActRule)
    assert {f.rule for f in findings} == {"race-check-then-act"}


def test_check_then_act_silent_on_good(tmp_path):
    assert _lint(tmp_path, _CTA_GOOD, CheckThenActRule) == []


_RMW_BAD = """
import json
import os

def bump(counter_path):
    with open(counter_path) as fh:
        n = json.load(fh)["n"]
    tmp = counter_path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump({"n": n + 1}, fh)
    os.replace(tmp, counter_path)      # read-modify-write, no CAS
"""

_RMW_GOOD = '''
import json
import os

def bump(counter_path):
    """single-writer: one sweeper process owns the counter file."""
    with open(counter_path) as fh:
        n = json.load(fh)["n"]
    tmp = counter_path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump({"n": n + 1}, fh)
    os.replace(tmp, counter_path)
'''


def test_rmw_shared_record_fires_on_bad(tmp_path):
    findings = _lint(tmp_path, _RMW_BAD, RmwSharedRecordRule)
    assert {f.rule for f in findings} == {"race-rmw-shared-record"}


def test_rmw_shared_record_silent_on_declared_owner(tmp_path):
    assert _lint(tmp_path, _RMW_GOOD, RmwSharedRecordRule) == []


_LISTDIR_BAD = """
import os

def sweep(spool):
    for name in os.listdir(spool):
        os.remove(os.path.join(spool, name))   # entry may be claimed
"""

_LISTDIR_GOOD = """
import os

def sweep(spool):
    for name in os.listdir(spool):
        try:
            os.remove(os.path.join(spool, name))
        except OSError:
            continue                   # claimed by someone else
"""


def test_stale_listdir_fires_on_bad(tmp_path):
    findings = _lint(tmp_path, _LISTDIR_BAD, StaleListdirSnapshotRule)
    assert {f.rule for f in findings} == {"race-stale-listdir-snapshot"}


def test_stale_listdir_silent_on_good(tmp_path):
    assert _lint(tmp_path, _LISTDIR_GOOD, StaleListdirSnapshotRule) == []


_DELETE_BAD = """
import shutil

class Cache:
    def __init__(self):
        self.refcount = {}
        self.dirs = {}

    def evict_lru(self, victim):
        if not self.refcount.get(victim):
            return                     # guard discipline demonstrated
        shutil.rmtree(victim)

    def clear(self):
        for d in self.dirs:
            shutil.rmtree(d)           # ignores refcount entirely
"""

_DELETE_GOOD = """
import shutil

class Cache:
    def __init__(self):
        self.refcount = {}
        self.dirs = {}

    def evict_lru(self, victim):
        if not self.refcount.get(victim):
            return
        shutil.rmtree(victim)

    def clear(self):
        for d in self.dirs:
            if self.refcount.get(d):
                continue               # skip checked-out victims
            shutil.rmtree(d)
"""


def test_delete_while_checked_out_fires_on_bad(tmp_path):
    findings = _lint(tmp_path, _DELETE_BAD, DeleteWhileCheckedOutRule)
    assert {f.rule for f in findings} == {"race-delete-while-checked-out"}
    assert findings[0].scope == "Cache.clear"


def test_delete_while_checked_out_silent_on_good(tmp_path):
    assert _lint(tmp_path, _DELETE_GOOD, DeleteWhileCheckedOutRule) == []


def test_delete_rule_ignores_undemonstrated_guards(tmp_path):
    # "pin" in an attribute name alone is not a deletion guard: no
    # method gates a delete on it (the Fleet.pin_cores shape)
    src = """
import shutil

class Runner:
    def __init__(self, pin_cores):
        self.pin_cores = pin_cores

    def cleanup(self, d):
        shutil.rmtree(d)
"""
    assert _lint(tmp_path, src, DeleteWhileCheckedOutRule) == []


_MONO_BAD = """
import json
import time

def stamp_lease(path, host):
    rec = {"host": host, "claimed_at": time.monotonic()}
    with open(path, "w") as fh:
        json.dump(rec, fh)             # epoch is process-local
"""

_MONO_GOOD = """
import json
import time

def stamp_lease(path, host, t0):
    rec = {"host": host, "claimed_at": time.time(),
           "took_s": time.monotonic() - t0}
    with open(path, "w") as fh:
        json.dump(rec, fh)             # durations are fine
"""


def test_monotonic_persisted_fires_on_bad(tmp_path):
    findings = _lint(tmp_path, _MONO_BAD, MonotonicPersistedRule)
    assert {f.rule for f in findings} == {"race-monotonic-persisted"}


def test_monotonic_persisted_silent_on_durations(tmp_path):
    assert _lint(tmp_path, _MONO_GOOD, MonotonicPersistedRule) == []


def test_every_race_rule_has_corpus_coverage():
    covered = {"race-check-then-act", "race-rmw-shared-record",
               "race-stale-listdir-snapshot",
               "race-delete-while-checked-out",
               "race-monotonic-persisted"}
    assert {r.rule_id for r in ALL_RACE_RULES} == covered
    assert set(race_rule_ids()) == covered | {RACE_AUDIT_RULE}


# ------------------------------------------------------------ sched_point
def test_sched_point_is_a_noop_unarmed():
    assert SCHED_ENV not in os.environ
    sched_point("any.name")            # returns immediately


def test_sched_point_turnstile_handshake(tmp_path, monkeypatch):
    monkeypatch.setenv(SCHED_ENV, f"{tmp_path}:0")
    released = []

    def park():
        sched_point("probe.step")
        released.append(True)

    t = threading.Thread(target=park)
    t.start()
    try:
        ready = tmp_path / "ready.0.0000"
        for _ in range(4000):
            if ready.exists():
                break
            t.join(0.001)
        assert ready.exists(), "sched_point never parked"
        assert ready.read_text() == "probe.step"
        assert not released, "sched_point ran through without a grant"
        (tmp_path / "go.0.0000").write_text("go")
    finally:
        t.join(5)
    assert released == [True]


def test_parse_schedule_contract():
    assert parse_schedule("ledger.claim:01101") == ("ledger.claim",
                                                   [0, 1, 1, 0, 1])
    for bad in ("ledger.claim", "x:", ":01", "x:012", "x:ab"):
        with pytest.raises(ValueError):
            parse_schedule(bad)


# --------------------------------------------------- scheduler determinism
def test_seeded_schedule_replays_deterministically(tmp_path):
    site = next(s for s in INTERLEAVE_SITES if s.name == "ledger.claim")
    pool = _ActorPool(str(tmp_path / "pool"))
    try:
        runs = []
        for n in range(3):
            rd = tmp_path / f"r{n}"
            rd.mkdir()
            decider = (_seeded_decider(site.name, 7) if n < 2
                       else _replay_decider(runs[0][2]))
            runs.append(_run_schedule(pool, site, decider, str(rd)))
    finally:
        pool.close()
    (a0, b0, trace0, names0), (a1, b1, trace1, names1), \
        (a2, b2, trace2, names2) = runs
    # same seed => the identical grant sequence AND the identical
    # parked-step names — the property that makes a trace a repro
    assert trace0 == trace1 and names0 == names1
    # and replaying the recorded trace reproduces it exactly
    assert trace2 == trace0 and names2 == names0
    assert (a0["value"], b0["value"]) == (a1["value"], b1["value"]) \
        == (a2["value"], b2["value"])


def test_replay_divergence_is_an_audit_error(tmp_path):
    site = next(s for s in INTERLEAVE_SITES if s.name == "ledger.claim")
    pool = _ActorPool(str(tmp_path / "pool"))
    try:
        rd = tmp_path / "r0"
        rd.mkdir()
        # actor 7 never exists: the first grant cannot follow the trace
        with pytest.raises(RaceAuditError, match="diverged"):
            _run_schedule(pool, site, _replay_decider([7, 7, 7]),
                          str(rd))
    finally:
        pool.close()


# ---------------------------------------------- the deliberately racy site
_BAD_SITE_MODULE = """
import json
import os

from avenir_tpu.analysis.race import INTERLEAVE_SITES, InterleaveSite
from avenir_tpu.core.atomic import sched_point


def _seed(root):
    pass


def _claim(root, idx):
    path = os.path.join(root, "winner.json")
    sched_point("bad.claim")
    if not os.path.exists(path):       # the check
        sched_point("bad.claim")
        with open(path, "w") as fh:    # the act: no atomic claim between
            json.dump({"worker": idx}, fh)
        return {"won": True}
    return {"won": False}


def _verify(root, a, b, solo_a, solo_b):
    wins = int(a["won"]) + int(b["won"])
    if wins != 1:
        return [f"{wins} claim winners (exactly-one expected): "
                f"a concrete double-claim"]
    return []


BAD_CLAIM = InterleaveSite(
    "bad.claim", "bad_fixture.py", ("bad.claim",), _seed,
    (lambda root: _claim(root, 0), lambda root: _claim(root, 1)),
    _verify)

if all(s.name != "bad.claim" for s in INTERLEAVE_SITES):
    INTERLEAVE_SITES.append(BAD_CLAIM)
"""


def _load_bad_site(tmp_path, monkeypatch):
    (tmp_path / "race_bad_fixture_site.py").write_text(_BAD_SITE_MODULE)
    monkeypatch.setenv("PYTHONPATH", os.pathsep.join(
        p for p in (str(tmp_path), os.environ.get("PYTHONPATH")) if p))
    monkeypatch.setenv(SITE_MODULE_ENV, "race_bad_fixture_site")
    monkeypatch.syspath_prepend(str(tmp_path))
    import importlib
    mod = importlib.import_module("race_bad_fixture_site")
    # parent-side registration is a module-global append: undo after
    monkeypatch.setattr("avenir_tpu.analysis.race.INTERLEAVE_SITES",
                        list(INTERLEAVE_SITES))
    return mod.BAD_CLAIM


def test_auditor_fails_a_naive_check_then_act_claim(tmp_path,
                                                    monkeypatch):
    site = _load_bad_site(tmp_path, monkeypatch)
    rows, findings = audit_interleavings(sites=[site], depth=2, seeds=0)
    assert len(rows) == 1 and rows[0]["site"] == "bad.claim"
    assert rows[0]["interleaving_validated"] is False
    failing = rows[0]["failing_schedule"]
    assert failing and failing.startswith("bad.claim:")
    assert len(findings) == 1 and findings[0].rule == RACE_AUDIT_RULE
    # the failure is CONCRETE (a double-claim) and carries the repro
    assert "2 claim winners" in findings[0].message
    assert f"--schedule {failing}" in findings[0].message

    # ...and the printed trace replays DETERMINISTICALLY to the same
    # verdict: same failing schedule, same double-claim
    name, steps = parse_schedule(failing)
    rows2, findings2 = audit_interleavings(
        sites=[site], schedule=(name, steps))
    assert rows2[0]["interleaving_validated"] is False
    assert rows2[0]["failing_schedule"] == failing
    assert rows2[0]["schedules"] == {"exhaustive": 0, "seeded": 0,
                                     "replay": 1}
    assert "2 claim winners" in findings2[0].message


def test_interleaving_findings_are_never_baselinable(tmp_path,
                                                     monkeypatch):
    site = _load_bad_site(tmp_path, monkeypatch)
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    report = run_race(
        paths=[str(clean)],
        baseline=[BaselineEntry(
            f"bad_fixture.py::{RACE_AUDIT_RULE}::bad.claim",
            "trying to allowlist a schedule failure", 1)],
        root=str(tmp_path), sites=[site], depth=2, seeds=0)
    # the allowlist entry is ignored: the audit finding still fails
    assert [f.rule for f in report.findings] == [RACE_AUDIT_RULE]
    assert not report.suppressed


def test_unknown_replay_site_is_an_audit_error():
    with pytest.raises(RaceAuditError, match="no.such.site"):
        audit_interleavings(schedule=("no.such.site", [0, 1]))


def test_race_findings_roundtrip_through_baseline(tmp_path):
    p = tmp_path / "mod.py"
    p.write_text(_CTA_BAD)
    key = "mod.py::race-check-then-act::adopt"
    report = run_race(paths=[str(p)], baseline=[
        BaselineEntry(key, "fixture", 1)], root=str(tmp_path),
        audit=False)
    assert not report.findings and len(report.suppressed) == 1

    p.write_text(_CTA_GOOD)
    report = run_race(paths=[str(p)], baseline=[
        BaselineEntry(key, "fixture", 1)], root=str(tmp_path),
        audit=False)
    assert [e.key for e in report.stale] == [key]


# -------------------------------------------------------------------- CLI
def _cli(args, cwd=REPO, env=None):
    e = dict(os.environ)
    if env:
        e.update(env)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "graftlint.py")] + args,
        capture_output=True, text=True, cwd=cwd, timeout=600, env=e)


def test_cli_race_exit_code_contract_and_schema(tmp_path):
    # bad fixture + rule subset (audit skipped -> fast): findings = 1
    (tmp_path / "bad.py").write_text(_CTA_BAD)
    proc = _cli(["--race", "bad.py", "--rules",
                 "race-check-then-act", "--no-baseline", "--json"],
                cwd=str(tmp_path))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    rep = json.loads(proc.stdout)
    assert rep["counts"] == {"race-check-then-act": 1}
    assert rep["race_audit"] == []            # subset skipped the audit
    # one schema across all modes: same top-level keys as the golden
    golden = json.load(open(os.path.join(
        REPO, "tests", "data", "graftlint_json_golden.json")))
    assert set(rep) == set(golden)
    assert "race_audit" in golden

    # good twin: clean = 0
    (tmp_path / "good.py").write_text(_CTA_GOOD)
    proc = _cli(["--race", "good.py", "--rules",
                 "race-check-then-act", "--no-baseline"],
                cwd=str(tmp_path))
    assert proc.returncode == 0, proc.stdout + proc.stderr

    # usage errors = 2: unknown rule, mixed tiers, orphan/bad --schedule
    assert _cli(["--race", "--rules", "nope"]).returncode == 2
    assert _cli(["--race", "--proto"]).returncode == 2
    assert _cli(["--race", "--ir"]).returncode == 2
    assert _cli(["--schedule", "x:01", "bad.py"],
                cwd=str(tmp_path)).returncode == 2
    assert _cli(["--race", "--schedule", "not-a-trace", "good.py",
                 "--rules", "race-check-then-act"],
                cwd=str(tmp_path)).returncode == 2


def test_cli_all_parallel_fans_out_eight_tiers(tmp_path):
    # a cross-tier rule subset keeps the fan-out fast: only the two
    # named tiers run (as subprocesses), the rest report skipped, and
    # per-tier wall_s lands in the combined JSON
    (tmp_path / "bad.py").write_text(_CTA_BAD)
    proc = _cli(["--all", "--parallel", "bad.py", "--rules",
                 "race-check-then-act,default-int64", "--no-baseline",
                 "--json"], cwd=str(tmp_path))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    rep = json.loads(proc.stdout)
    assert set(rep) == {"modes", "clean"} and rep["clean"] is False
    assert set(rep["modes"]) == {"ast", "ir", "flow", "mem", "merge",
                                 "proto", "race", "keys"}
    for name in ("ir", "flow", "mem", "merge", "proto", "keys"):
        assert rep["modes"][name] == {"skipped": True}
    assert rep["modes"]["race"]["counts"] == {"race-check-then-act": 1}
    for name in ("ast", "race"):
        assert rep["modes"][name]["wall_s"] > 0

    # --parallel without --all is a usage error
    assert _cli(["--parallel", "bad.py"],
                cwd=str(tmp_path)).returncode == 2
