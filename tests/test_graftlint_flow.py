"""graftlint-flow: tier-1 gate + per-rule fixture corpus + invariance audit.

Three jobs, mirroring tests/test_graftlint.py and test_graftlint_ir.py
one layer over:
1. Gate — the gated repo surface lints clean under the flow rules and
   every streamed fold kernel in the manifest reports
   invariance_validated under >= 3 chunk layouts + the adversarial
   scheduler (the tier's acceptance invariant, held here).
2. Corpus — every flow rule has a bad fixture that MUST fire and a good
   twin that MUST stay silent.
3. Contract — the invariance auditor catches drift, kernel run failures
   surface as FlowAuditError (CLI exit 2), flow findings round-trip
   through the shared baseline, and the --flow CLI speaks the same JSON
   schema as the other modes.
"""

import json
import os
import subprocess
import sys

import pytest

from avenir_tpu.analysis import load_baseline
from avenir_tpu.analysis.engine import BaselineEntry, run_paths
from avenir_tpu.analysis.flow import (ALL_FLOW_RULES, FLOW_AUDIT_RULE,
                                      BlockingIoInFoldRule, FlowAuditError,
                                      OrderSensitiveFoldRule,
                                      SharedStateUnlockedRule,
                                      UnboundedQueueGetRule,
                                      UnjoinedThreadRule, audit_stream,
                                      flow_rule_ids, run_flow)
from avenir_tpu.analysis.manifest import (StreamKernelSpec, stream_entries,
                                          stream_kernel_names)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------- gate
def test_flow_gate_clean_and_all_stream_kernels_invariant():
    report = run_flow(baseline=load_baseline(), root=REPO)
    assert not report.errors, [f.render() for f in report.errors]
    assert not report.findings, "\n" + "\n".join(
        f.render() for f in report.findings)
    assert not report.stale, [e.key for e in report.stale]
    audit = report.invariance_audit
    assert len(audit) == len(stream_kernel_names()) >= 6
    bad = [a["kernel"] for a in audit if not a["invariance_validated"]]
    assert not bad, (bad, audit)
    for row in audit:
        # >= 3 layouts that REALLY chunked differently, and both the
        # layout sweep and the adversarial scheduler were byte-identical
        assert len(row["layouts_mb"]) >= 3
        assert len(set(row["chunk_counts"])) >= 2, row
        assert row["layouts_byte_identical"] and \
            row["scheduler_byte_identical"], row


def test_stream_manifest_covers_the_streamed_fold_families():
    names = set(stream_kernel_names())
    assert {"nb_stream", "mi_stream", "markov_stream", "apriori_stream",
            "gsp_stream", "discriminant_stream"} <= names
    for spec in stream_entries():
        assert len(spec.layouts) >= 3, spec.name
        assert spec.path.endswith(".py") and spec.line > 0, spec.name


# ------------------------------------------------- fixture corpus helpers
def _lint(tmp_path, source, rule_cls, name="snippet.py"):
    p = tmp_path / name
    p.write_text(source)
    report = run_paths([str(p)], rules=[rule_cls()], baseline=[],
                       root=str(tmp_path))
    assert not report.errors, [f.render() for f in report.errors]
    return report.findings


_QGET_BAD = """
import queue
import threading

class Pump:
    def __init__(self):
        self.events = queue.Queue()

    def loop(self):
        while True:
            item = self.events.get()           # blocks forever on a hang
            if item is None:
                return

def drain(source):
    q = queue.Queue()
    alias = q
    while True:
        msg = alias.get()                      # alias of a queue: fires
        if msg is None:
            break
"""

_QGET_GOOD = """
import queue

class Pump:
    def __init__(self):
        self.events = queue.Queue()
        self.props = {}

    def loop(self, stop):
        while True:
            try:
                item = self.events.get(timeout=0.2)   # bounded: re-checks
            except queue.Empty:
                if stop.is_set():
                    return
                continue
            if item is None:
                return

    def snapshot(self):
        out = []
        try:
            while True:
                out.append(self.events.get_nowait())  # non-blocking
        except queue.Empty:
            pass
        return out, self.props.get("k")               # dict.get: silent
"""


def test_unbounded_queue_get_fires_on_bad(tmp_path):
    findings = _lint(tmp_path, _QGET_BAD, UnboundedQueueGetRule)
    assert {f.rule for f in findings} == {"flow-unbounded-queue-get"}
    assert len(findings) == 2, [f.render() for f in findings]
    assert {f.scope for f in findings} == {"Pump.loop", "drain"}


def test_unbounded_queue_get_silent_on_good(tmp_path):
    assert _lint(tmp_path, _QGET_GOOD, UnboundedQueueGetRule) == []


_THREAD_BAD = """
import threading

def fire(worker):
    threading.Thread(target=worker, daemon=True).start()   # unbindable

class Owner:
    def start(self, fn):
        self.t = threading.Thread(target=fn)
        self.t.start()                                     # never joined
"""

_THREAD_GOOD = """
import threading

class Owner:
    def start(self, fn):
        self.t = threading.Thread(target=fn)
        self.t.start()

    def stop(self):
        t, self.t = self.t, None
        t.join(timeout=5.0)            # alias-chain join counts

def run_to_completion(fn):
    t = threading.Thread(target=fn)
    t.start()
    t.join()
    return ",".join(["a", "b"])        # str.join is not a thread join
"""


def test_unjoined_thread_fires_on_bad(tmp_path):
    findings = _lint(tmp_path, _THREAD_BAD, UnjoinedThreadRule)
    assert {f.rule for f in findings} == {"flow-unjoined-thread"}
    assert len(findings) == 2, [f.render() for f in findings]


def test_unjoined_thread_silent_on_good(tmp_path):
    assert _lint(tmp_path, _THREAD_GOOD, UnjoinedThreadRule) == []


_SHARED_BAD = """
import threading

class Stream:
    def __init__(self):
        self.count = 0
        self.failed = []
        self.thread = None

    def _loop(self):
        while True:
            self.step()

    def step(self):
        self.count += 1                # reachable from the worker: fires
        self.failed.append("x")        # fires

    def start(self):
        self.thread = threading.Thread(target=self._loop)
        self.thread.start()

    def stop(self):
        self.thread.join()
"""

_SHARED_GOOD = """
import queue
import threading

class Stream:
    def __init__(self):
        self.count = 0
        self.out = queue.Queue()
        self._lock = threading.Lock()
        self.thread = None

    def _loop(self):
        while True:
            self.step()

    def step(self):
        with self._lock:
            self.count += 1            # lock-guarded: silent
        self.out.put("x")              # queue handoff: silent
        done = True                    # local: silent
        return done

    def start(self):
        self.thread = threading.Thread(target=self._loop)
        self.thread.start()

    def stop(self):
        self.thread.join()
"""


def test_shared_state_fires_on_bad(tmp_path):
    findings = _lint(tmp_path, _SHARED_BAD, SharedStateUnlockedRule)
    assert {f.rule for f in findings} == {"flow-shared-state-unlocked"}
    assert len(findings) == 2, [f.render() for f in findings]
    attrs = {f.message.split("`self.")[1].split("`")[0] for f in findings}
    assert attrs == {"count", "failed"}


def test_shared_state_silent_on_good(tmp_path):
    assert _lint(tmp_path, _SHARED_GOOD, SharedStateUnlockedRule) == []


_IO_BAD = """
import time
from avenir_tpu.core.stream import double_buffered

def fold(chunks, log_path):
    tot = 0
    for blk in double_buffered(chunks):
        with open(log_path, "a") as fh:        # per-chunk file IO
            fh.write(str(len(blk)))
        time.sleep(0.01)                       # per-chunk stall
        tot += len(blk)
    return tot
"""

_IO_GOOD = """
from avenir_tpu.core.stream import double_buffered

def fold(chunks, log_path):
    tot = 0
    sizes = []
    for blk in double_buffered(chunks):
        tot += len(blk)
        sizes.append(len(blk))
    with open(log_path, "a") as fh:            # after the loop: silent
        fh.write(",".join(map(str, sizes)))
    return tot
"""


def test_blocking_io_fires_on_bad(tmp_path):
    findings = _lint(tmp_path, _IO_BAD, BlockingIoInFoldRule)
    assert {f.rule for f in findings} == {"flow-blocking-io-in-fold"}
    assert len(findings) == 2, [f.render() for f in findings]


def test_blocking_io_silent_on_good(tmp_path):
    assert _lint(tmp_path, _IO_GOOD, BlockingIoInFoldRule) == []


_ORDER_BAD = """
import numpy as np
from avenir_tpu.core.stream import prefetched

def fold(chunks):
    acc = np.zeros(4)                  # dtype-less numpy: float64
    err = 0.0
    for c in prefetched(chunks):
        acc += c.mean(axis=0)          # reassociates with chunk layout
        err = err + float(c.std())     # x = x + ... form
    return acc, err
"""

_ORDER_GOOD = """
import numpy as np
from avenir_tpu.core.stream import prefetched

def fold(chunks):
    counts = np.zeros(4, np.int64)     # integer: exact in any grouping
    rows = 0
    parts = []
    for c in prefetched(chunks):
        counts += c.sum(axis=0)
        rows += len(c)                 # int accumulator: silent
        parts.append(c.mean())         # collected, not folded
    return counts, rows, float(np.sum(parts))
"""


def test_order_sensitive_fires_on_bad(tmp_path):
    findings = _lint(tmp_path, _ORDER_BAD, OrderSensitiveFoldRule)
    assert {f.rule for f in findings} == {"flow-order-sensitive-fold"}
    assert len(findings) == 2, [f.render() for f in findings]


def test_order_sensitive_silent_on_good(tmp_path):
    assert _lint(tmp_path, _ORDER_GOOD, OrderSensitiveFoldRule) == []


def test_every_flow_rule_has_corpus_coverage():
    covered = {"flow-unbounded-queue-get", "flow-unjoined-thread",
               "flow-shared-state-unlocked", "flow-blocking-io-in-fold",
               "flow-order-sensitive-fold"}
    assert {r.rule_id for r in ALL_FLOW_RULES} == covered
    assert set(flow_rule_ids()) == covered | {FLOW_AUDIT_RULE}


# ------------------------------------------------------ invariance auditor
def _toy_spec(run, name="toy_kernel", layouts=(64.0, 0.002, 0.0005)):
    def prepare(workdir):
        return {"dir": workdir}

    return StreamKernelSpec(name, "toy.py", 1, prepare, run,
                            layouts=tuple(layouts))


def test_auditor_validates_an_invariant_kernel():
    def run(ctx, block_mb):
        # chunk the fixed corpus by block_mb; integer sum is exact
        from avenir_tpu.core.stream import prefetched

        rows = list(range(100))
        per = max(1, int(block_mb * 1000))
        chunks = [rows[i:i + per] for i in range(0, len(rows), per)]
        return str(sum(s for c in prefetched(chunks, depth=1)
                       for s in c)).encode()

    row, finding = audit_stream(_toy_spec(run))
    assert row["invariance_validated"] is True and finding is None
    assert len(set(row["chunk_counts"])) >= 2


def test_auditor_catches_layout_drift():
    def run(ctx, block_mb):
        from avenir_tpu.core.stream import prefetched

        rows = list(range(100))
        per = max(1, int(block_mb * 1000))
        chunks = [rows[i:i + per] for i in range(0, len(rows), per)]
        n_chunks = sum(1 for _ in prefetched(chunks, depth=1))
        return str(n_chunks).encode()      # output depends on the layout

    row, finding = audit_stream(_toy_spec(run, name="drifty"))
    assert row["invariance_validated"] is False
    assert finding is not None and finding.rule == FLOW_AUDIT_RULE
    assert finding.scope == "drifty"


def test_auditor_requires_layouts_to_differ():
    def run(ctx, block_mb):
        return b"constant"                 # but nothing ever chunks

    row, finding = audit_stream(_toy_spec(run, name="vacuous"))
    assert row["chunk_counts"] == [0, 0, 0]
    assert row["invariance_validated"] is False
    assert finding is not None and "did not differ" in finding.message


def test_auditor_wraps_kernel_failures():
    def run(ctx, block_mb):
        raise ValueError("synthetic kernel failure")

    with pytest.raises(FlowAuditError, match="boomkern"):
        audit_stream(_toy_spec(run, name="boomkern"))


def test_auditor_restores_the_stream_hook():
    from avenir_tpu.core import stream

    def run(ctx, block_mb):
        assert stream._produce_hook is not None
        return b"ok" if block_mb else b""

    before = stream._produce_hook
    audit_stream(_toy_spec(run, name="hooky"))
    assert stream._produce_hook is before


def test_flow_findings_roundtrip_through_baseline(tmp_path):
    p = tmp_path / "mod.py"
    p.write_text(_SHARED_BAD)
    key = "mod.py::flow-shared-state-unlocked::Stream.step"
    report = run_flow(paths=[str(p)], baseline=[
        BaselineEntry(key, "fixture", 1)], root=str(tmp_path), audit=False)
    assert not report.findings and len(report.suppressed) == 2

    p.write_text(_SHARED_GOOD)
    report = run_flow(paths=[str(p)], baseline=[
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


def test_cli_flow_json_clean_and_schema():
    proc = _cli(["--flow", "--json"])
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rep = json.loads(proc.stdout)
    assert rep["clean"] and rep["findings"] == []
    audit = rep["invariance_audit"]
    assert len(audit) >= 6
    assert all(a["invariance_validated"] for a in audit)
    assert rep["payload_audit"] == []
    # one schema across all three modes: same top-level keys as the golden
    golden = json.load(open(os.path.join(
        REPO, "tests", "data", "graftlint_json_golden.json")))
    assert set(rep) == set(golden)


def test_cli_flow_exit_code_contract(tmp_path):
    # bad fixture + rule subset (audit skipped -> fast): findings = 1
    (tmp_path / "bad.py").write_text(_THREAD_BAD)
    proc = _cli(["--flow", "bad.py", "--rules", "flow-unjoined-thread",
                 "--no-baseline", "--json"], cwd=str(tmp_path))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    rep = json.loads(proc.stdout)
    assert rep["counts"] == {"flow-unjoined-thread": 2}
    assert rep["invariance_audit"] == []      # subset skipped the audit

    # good twin: clean = 0
    (tmp_path / "good.py").write_text(_THREAD_GOOD)
    proc = _cli(["--flow", "good.py", "--rules", "flow-unjoined-thread",
                 "--no-baseline"], cwd=str(tmp_path))
    assert proc.returncode == 0, proc.stdout + proc.stderr

    # usage errors = 2: unknown rule, and --ir + --flow together
    assert _cli(["--flow", "--rules", "nope"]).returncode == 2
    assert _cli(["--flow", "--ir"]).returncode == 2
