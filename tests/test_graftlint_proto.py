"""graftlint-proto: tier-1 gate + per-rule fixture corpus + crash audit.

Three jobs, mirroring the other analyzer test modules one layer over:
1. Gate — the shared-filesystem protocol surface lints clean under the
   proto rules and every registered commit site reports
   commit_point_validated: hard-killed at before-rename AND
   after-rename, recovery (re-run + startup sweep) byte-identical to
   the uncrashed run with no stranded tmp (the tier's acceptance invariant,
   held here).
2. Corpus — every proto rule has a bad fixture that MUST fire and a
   good twin that MUST stay silent.
3. Contract — the auditor fails a deliberately NON-atomic site (the
   double-folded append), flags a site whose publish never reaches the
   crash hook, the registry cross-check catches drift in both
   directions, proto findings round-trip through the shared baseline,
   and the --proto CLI speaks the same JSON schema and 0/1/2 exit
   contract as the other modes.
"""

import json
import os
import subprocess
import sys

import pytest

from avenir_tpu.analysis import load_baseline
from avenir_tpu.analysis.engine import BaselineEntry, run_paths
from avenir_tpu.analysis.proto import (ALL_PROTO_RULES, COMMIT_SITES,
                                       PROTO_AUDIT_RULE, CommitSite,
                                       NonatomicPublishRule,
                                       ProtoAuditError,
                                       SharedTmpNameRule,
                                       TmpLeakOnRaiseRule,
                                       TmpNotSiblingRule,
                                       TornReadUnguardedRule,
                                       UnboundedPollRule,
                                       WallClockDeadlineRule,
                                       audit_commit_points,
                                       check_site_registry,
                                       proto_rule_ids, run_proto,
                                       site_annotations)
from avenir_tpu.core.atomic import AFTER_RENAME, BEFORE_RENAME

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------- gate
def test_proto_gate_clean_and_all_commit_points_validated():
    report = run_proto(baseline=load_baseline(), root=REPO)
    assert not report.errors, [f.render() for f in report.errors]
    assert not report.findings, "\n" + "\n".join(
        f.render() for f in report.findings)
    assert not report.stale, [e.key for e in report.stale]
    audit = report.proto_audit
    # the N/N acceptance floor: every registered site, >= 10 of them
    assert len(audit) == len(COMMIT_SITES) >= 10
    bad = [a["site"] for a in audit if not a["commit_point_validated"]]
    assert not bad, (bad, audit)
    for row in audit:
        # both kill points really ran: the child died AT the hook
        # (exit 43), recovery re-ran the publish, and the artifact
        # came back byte-identical with no stranded tmp
        assert [s["stage"] for s in row["stages"]] == [BEFORE_RENAME,
                                                       AFTER_RENAME]
        for s in row["stages"]:
            assert s["crashed"] and s["recovered"], row
            assert s["byte_identical"] and s["tmp_clean"], row
        # rows are anchored at the real annotation in the code
        assert row["path"].endswith(".py") and row["line"] > 1, row


def test_registry_and_code_annotations_agree():
    refs = site_annotations(REPO)
    assert set(refs) == {s.name for s in COMMIT_SITES}
    # the cross-check passes on the real tree and returns the same map
    assert check_site_registry(REPO) == refs


def test_registry_cross_check_fails_on_dangling_entry(monkeypatch):
    from avenir_tpu.analysis import proto as proto_mod

    dangling = CommitSite("ghost.site", "nowhere.py", lambda root: None)
    monkeypatch.setattr(proto_mod, "COMMIT_SITES",
                        list(COMMIT_SITES) + [dangling])
    with pytest.raises(ProtoAuditError, match="ghost.site"):
        check_site_registry(REPO)


# ------------------------------------------------- fixture corpus helpers
def _lint(tmp_path, source, rule_cls, name="snippet.py"):
    p = tmp_path / name
    p.write_text(source)
    report = run_paths([str(p)], rules=[rule_cls()], baseline=[],
                       root=str(tmp_path))
    assert not report.errors, [f.render() for f in report.errors]
    return report.findings


_NONATOMIC_BAD = """
import json

def save(path, obj):
    with open(path, "w") as fh:        # readers see the torn write
        json.dump(obj, fh)
"""

_NONATOMIC_GOOD = """
import json
import os
import uuid

def save(path, obj):
    tmp = f"{path}.{uuid.uuid4().hex}.tmp"
    with open(tmp, "w") as fh:
        json.dump(obj, fh)
    os.replace(tmp, path)
"""


def test_nonatomic_publish_fires_on_bad(tmp_path):
    findings = _lint(tmp_path, _NONATOMIC_BAD, NonatomicPublishRule)
    assert {f.rule for f in findings} == {"proto-nonatomic-publish"}


def test_nonatomic_publish_silent_on_good(tmp_path):
    assert _lint(tmp_path, _NONATOMIC_GOOD, NonatomicPublishRule) == []


_SIBLING_BAD = """
import os
import tempfile

def save(path, payload):
    stage = tempfile.mkdtemp()         # maybe another filesystem
    tmp = os.path.join(stage, "stage.bin")
    with open(tmp, "wb") as fh:
        fh.write(payload)
    os.replace(tmp, path)              # EXDEV territory: not atomic
"""

_SIBLING_GOOD = """
import os
import uuid

def save(path, payload):
    tmp = f"{path}.{uuid.uuid4().hex}.tmp"   # sibling: same fs
    with open(tmp, "wb") as fh:
        fh.write(payload)
    os.replace(tmp, path)
"""


def test_tmp_not_sibling_fires_on_bad(tmp_path):
    findings = _lint(tmp_path, _SIBLING_BAD, TmpNotSiblingRule)
    assert {f.rule for f in findings} == {"proto-tmp-not-sibling"}


def test_tmp_not_sibling_silent_on_good(tmp_path):
    assert _lint(tmp_path, _SIBLING_GOOD, TmpNotSiblingRule) == []


_SHARED_TMP_BAD = """
import os

def publish(marker, pid):
    tmp = marker + ".tmp"              # every writer shares this name
    with open(tmp, "w") as fh:
        fh.write(str(pid))
    os.replace(tmp, marker)
"""

_SHARED_TMP_GOOD = """
import os
import uuid

def publish(marker, pid):
    tmp = f"{marker}.{uuid.uuid4().hex}.tmp"
    with open(tmp, "w") as fh:
        fh.write(str(pid))
    os.replace(tmp, marker)
"""


def test_shared_tmp_name_fires_on_bad(tmp_path):
    findings = _lint(tmp_path, _SHARED_TMP_BAD, SharedTmpNameRule)
    assert {f.rule for f in findings} == {"proto-shared-tmp-name"}


def test_shared_tmp_name_silent_on_good(tmp_path):
    assert _lint(tmp_path, _SHARED_TMP_GOOD, SharedTmpNameRule) == []


_TORN_BAD = """
import json

def load_row(path):
    with open(path) as fh:
        return json.load(fh)           # racing a deleter: crash
"""

_TORN_GOOD = """
import json

def load_row(path):
    try:
        with open(path) as fh:
            return json.load(fh)
    except (OSError, ValueError):
        return None                    # torn/absent record = absent
"""


def test_torn_read_fires_on_bad(tmp_path):
    findings = _lint(tmp_path, _TORN_BAD, TornReadUnguardedRule)
    assert {f.rule for f in findings} == {"proto-torn-read-unguarded"}


def test_torn_read_silent_on_good(tmp_path):
    assert _lint(tmp_path, _TORN_GOOD, TornReadUnguardedRule) == []


_POLL_BAD = """
import os
import time

def await_marker(path):
    while not os.path.exists(path):    # writer died? spin forever
        time.sleep(0.05)
"""

_POLL_GOOD = """
import os
import time

def await_marker(path, timeout_s):
    deadline = time.monotonic() + timeout_s
    while not os.path.exists(path):
        if time.monotonic() > deadline:
            raise TimeoutError(path)
        time.sleep(0.05)
"""


def test_unbounded_poll_fires_on_bad(tmp_path):
    findings = _lint(tmp_path, _POLL_BAD, UnboundedPollRule)
    assert {f.rule for f in findings} == {"proto-unbounded-poll"}


def test_unbounded_poll_silent_on_good(tmp_path):
    assert _lint(tmp_path, _POLL_GOOD, UnboundedPollRule) == []


_WALL_BAD = """
import time

def wait_for(flag_holder, patience_s):
    started = time.time()
    while not flag_holder.done:
        if time.time() - started > patience_s:   # NTP step breaks this
            return False
        pass
    return True
"""

_WALL_GOOD = """
import time

def wait_for(flag_holder, patience_s):
    started = time.monotonic()
    while not flag_holder.done:
        if time.monotonic() - started > patience_s:
            return False
        pass
    return True


def lease_expired(lease, ttl_s):
    # wall time COMPARED AGAINST A PERSISTED RECORD is the legitimate
    # use: claimed_at crossed a process boundary
    return time.time() - lease.claimed_at > ttl_s
"""


def test_wall_clock_deadline_fires_on_bad(tmp_path):
    findings = _lint(tmp_path, _WALL_BAD, WallClockDeadlineRule)
    assert {f.rule for f in findings} == {"proto-wall-clock-deadline"}


def test_wall_clock_deadline_silent_on_good(tmp_path):
    assert _lint(tmp_path, _WALL_GOOD, WallClockDeadlineRule) == []


_LEAK_BAD = """
import os
import uuid

def save(path, payload):
    tmp = f"{path}.{uuid.uuid4().hex}.tmp"
    with open(tmp, "wb") as fh:        # a raise here strands tmp
        fh.write(payload)
    os.replace(tmp, path)
"""

_LEAK_GOOD = """
import os
import uuid

def save(path, payload):
    tmp = f"{path}.{uuid.uuid4().hex}.tmp"
    try:
        with open(tmp, "wb") as fh:
            fh.write(payload)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.remove(tmp)
        except OSError:
            pass
        raise
"""


def test_tmp_leak_on_raise_fires_on_bad(tmp_path):
    findings = _lint(tmp_path, _LEAK_BAD, TmpLeakOnRaiseRule)
    assert {f.rule for f in findings} == {"proto-tmp-leak-on-raise"}


def test_tmp_leak_on_raise_silent_on_good(tmp_path):
    assert _lint(tmp_path, _LEAK_GOOD, TmpLeakOnRaiseRule) == []


def test_every_proto_rule_has_corpus_coverage():
    covered = {"proto-nonatomic-publish", "proto-tmp-not-sibling",
               "proto-shared-tmp-name", "proto-torn-read-unguarded",
               "proto-unbounded-poll", "proto-wall-clock-deadline",
               "proto-tmp-leak-on-raise"}
    assert {r.rule_id for r in ALL_PROTO_RULES} == covered
    assert set(proto_rule_ids()) == covered | {PROTO_AUDIT_RULE}


# ------------------------------------------------------------ the auditor
#: a deliberately NON-atomic publish: the "commit" is a bare append, so
#: the after-crash recovery re-append double-folds the row — the audit
#: must catch exactly this shape
_APPEND_CHILD = """
import os
from avenir_tpu.core.atomic import crash_point
path = os.path.join(r"__ROOT__", "rows.log")
with open(path, "a") as fh:
    fh.write("row\\n")
crash_point("bad.append", "before-rename")
crash_point("bad.append", "after-rename")
"""


def _append_run(root):
    with open(os.path.join(root, "rows.log"), "a") as fh:
        fh.write("row\n")


def test_auditor_fails_a_nonatomic_append_site():
    site = CommitSite("bad.append", "nowhere.py", _append_run,
                      child_source=_APPEND_CHILD)
    rows, findings = audit_commit_points(sites=[site])
    assert len(rows) == 1 and rows[0]["site"] == "bad.append"
    assert rows[0]["commit_point_validated"] is False
    # the crash DID happen at both hooks — the failure is the
    # double-folded artifact, not a missing hook
    stages = {s["stage"]: s for s in rows[0]["stages"]}
    assert stages[BEFORE_RENAME]["crashed"]
    assert not stages[BEFORE_RENAME]["byte_identical"]
    assert len(findings) == 1
    assert findings[0].rule == PROTO_AUDIT_RULE
    assert "bad.append" in findings[0].message


def test_auditor_flags_a_site_that_never_reaches_the_hook():
    # the publish exists, but crash_point is never consulted: the
    # child exits 0 instead of 43 — an unauditable commit point
    child = """
import os
with open(os.path.join(r"__ROOT__", "x.json"), "w") as fh:
    fh.write('{"ok": true}')
"""

    def run(root):
        with open(os.path.join(root, "x.json"), "w") as fh:
            fh.write('{"ok": true}')

    site = CommitSite("no.hook", "nowhere.py", run, child_source=child)
    rows, findings = audit_commit_points(sites=[site])
    assert rows[0]["commit_point_validated"] is False
    assert all(not s["crashed"] for s in rows[0]["stages"])
    assert findings and "never reached" in findings[0].message


def test_auditor_surfaces_driver_failures_as_audit_errors():
    def boom(root):
        raise ValueError("synthetic publish failure")

    site = CommitSite("boom.site", "nowhere.py", boom)
    with pytest.raises(ProtoAuditError, match="boom.site"):
        audit_commit_points(sites=[site])


def test_proto_findings_roundtrip_through_baseline(tmp_path):
    p = tmp_path / "mod.py"
    p.write_text(_NONATOMIC_BAD)
    key = "mod.py::proto-nonatomic-publish::save"
    report = run_proto(paths=[str(p)], baseline=[
        BaselineEntry(key, "fixture", 1)], root=str(tmp_path),
        audit=False)
    assert not report.findings and len(report.suppressed) == 1

    p.write_text(_NONATOMIC_GOOD)
    report = run_proto(paths=[str(p)], baseline=[
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


def test_cli_proto_exit_code_contract_and_schema(tmp_path):
    # bad fixture + rule subset (audit skipped -> fast): findings = 1
    (tmp_path / "bad.py").write_text(_NONATOMIC_BAD)
    proc = _cli(["--proto", "bad.py", "--rules",
                 "proto-nonatomic-publish", "--no-baseline", "--json"],
                cwd=str(tmp_path))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    rep = json.loads(proc.stdout)
    assert rep["counts"] == {"proto-nonatomic-publish": 1}
    assert rep["proto_audit"] == []           # subset skipped the audit
    # one schema across all modes: same top-level keys as the golden
    golden = json.load(open(os.path.join(
        REPO, "tests", "data", "graftlint_json_golden.json")))
    assert set(rep) == set(golden)
    assert "proto_audit" in golden

    # good twin: clean = 0
    (tmp_path / "good.py").write_text(_NONATOMIC_GOOD)
    proc = _cli(["--proto", "good.py", "--rules",
                 "proto-nonatomic-publish", "--no-baseline"],
                cwd=str(tmp_path))
    assert proc.returncode == 0, proc.stdout + proc.stderr

    # usage errors = 2: unknown rule, and mixed tiers
    assert _cli(["--proto", "--rules", "nope"]).returncode == 2
    assert _cli(["--proto", "--ir"]).returncode == 2
    assert _cli(["--proto", "--flow"]).returncode == 2
    assert _cli(["--proto", "--mem"]).returncode == 2
    assert _cli(["--proto", "--merge"]).returncode == 2
