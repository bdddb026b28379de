"""graftlint-ir: tier-1 manifest gate + per-rule fixture corpus + audit.

Three jobs, mirroring tests/test_graftlint.py one layer down:
1. Gate — every manifest entry traces clean against the baseline and all
   8 distributed families report payload_model_validated on the virtual
   8-device mesh (the tier's acceptance invariant, held here).
2. Corpus — every IR rule has a hand-traced bad fixture that MUST fire
   and a good twin that MUST stay silent.
3. Contract — the payload auditor catches drift, trace failures surface
   as IRTraceError (CLI exit 2), and the --ir CLI speaks the same JSON
   schema as the AST mode.
"""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from avenir_tpu.analysis import load_baseline
from avenir_tpu.analysis.ir import (ALL_IR_RULES, PAYLOAD_RULE,
                                    CallbackInLoopRule,
                                    HostTransferInLoopRule, IRTraceError,
                                    Widen64BitRule, audit_family,
                                    check_jaxpr, ir_rule_ids, run_ir)
from avenir_tpu.analysis.manifest import (AUDIT_DEVICES, KernelSpec,
                                          family_names, manifest_entries)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------- gate
def test_manifest_gate_clean_and_all_families_validated():
    report = run_ir(baseline=load_baseline())
    assert not report.findings, "\n" + "\n".join(
        f.render() for f in report.findings)
    assert not report.stale, [e.key for e in report.stale]
    audit = report.payload_audit
    assert len(audit) == 8 == len(family_names())
    bad = [a["family"] for a in audit if not a["payload_model_validated"]]
    assert not bad, (bad, audit)
    # the headline numbers are pinned, not just self-consistent: nb's
    # [F,K,B]+[K] f32 psum and knn's candidate-merge all-gather
    by_name = {a["family"]: a for a in audit}
    assert by_name["nb_train"]["analytic_payload_bytes"] == 648
    assert by_name["knn_topk"]["mesh"] == {"data": 4, "model": 2}
    assert by_name["knn_topk"]["hlo_payload_bytes"] > 0
    assert by_name["bandit_select"]["collectives"] == []


def test_manifest_covers_every_distributed_family_and_hot_ops():
    from avenir_tpu.parallel.distributed import FAMILIES

    assert set(family_names()) == set(FAMILIES), (
        "a distributed family is missing from (or extra in) the manifest")
    names = {s.name for s in manifest_entries()}
    for required in ("bitset_contain_counts", "bitset_contain_mask",
                     "knn_topk_pallas", "keyed_reduce", "one_hot_count",
                     "weighted_split_score", "mutual_information"):
        assert required in names, required


# --------------------------------------------------- fixture corpus helpers
def _spec(name="snippet"):
    return KernelSpec(name, "snippet.py", 1, build=None)


def _ids(findings):
    return {f.rule for f in findings}


# ----------------------------------------------------- ir-callback-in-loop
def test_callback_in_loop_fires_on_bad():
    def bad(xs):
        def body(c, t):
            jax.debug.callback(lambda v: None, t)
            r = jax.pure_callback(
                lambda v: v, jax.ShapeDtypeStruct((), np.float32), t)
            return c + r, None
        out, _ = jax.lax.scan(body, jnp.float32(0.0), xs)
        return out

    jaxpr = jax.make_jaxpr(bad)(jax.ShapeDtypeStruct((4,), np.float32))
    findings = check_jaxpr(_spec(), jaxpr, [CallbackInLoopRule()])
    assert _ids(findings) == {"ir-callback-in-loop"}
    assert len(findings) == 2, [f.render() for f in findings]
    assert all(f.scope == "snippet" for f in findings)


def test_callback_outside_loop_silent():
    def good(xs):
        jax.debug.callback(lambda v: None, xs[0])   # once, before the loop

        def body(c, t):
            return c + t, None
        out, _ = jax.lax.scan(body, jnp.float32(0.0), xs)
        return out

    jaxpr = jax.make_jaxpr(good)(jax.ShapeDtypeStruct((4,), np.float32))
    assert check_jaxpr(_spec(), jaxpr, [CallbackInLoopRule()]) == []


# ------------------------------------------------ ir-host-transfer-in-loop
def test_host_transfer_in_loop_fires_on_bad():
    def bad(xs):
        def body(c, t):
            return c + jax.device_put(t), None
        out, _ = jax.lax.scan(body, jnp.float32(0.0), xs)
        return out

    jaxpr = jax.make_jaxpr(bad)(jax.ShapeDtypeStruct((4,), np.float32))
    findings = check_jaxpr(_spec(), jaxpr, [HostTransferInLoopRule()])
    assert _ids(findings) == {"ir-host-transfer-in-loop"}
    assert len(findings) == 1


def test_host_transfer_outside_loop_silent():
    def good(xs):
        placed = jax.device_put(xs)                 # once, before the loop

        def body(c, t):
            return c + t, None
        out, _ = jax.lax.scan(body, jnp.float32(0.0), placed)
        return out

    jaxpr = jax.make_jaxpr(good)(jax.ShapeDtypeStruct((4,), np.float32))
    assert check_jaxpr(_spec(), jaxpr, [HostTransferInLoopRule()]) == []


# ------------------------------------------------------------ ir-widen-64bit
def test_widen_64bit_fires_on_x64_trace():
    def bad(x):
        return x.astype(jnp.float64) + jnp.arange(4)   # f64 convert + i64 iota

    with jax.enable_x64(True):
        jaxpr = jax.make_jaxpr(bad)(jax.ShapeDtypeStruct((4,), np.float32))
    findings = check_jaxpr(_spec(), jaxpr, [Widen64BitRule()])
    assert _ids(findings) == {"ir-widen-64bit"}
    dtypes_hit = {f.message.split("materializes ")[1].split(" ")[0]
                  for f in findings}
    assert "float64" in dtypes_hit and "int64" in dtypes_hit


def test_widen_64bit_silent_on_narrow_trace():
    def good(x):
        return x.astype(jnp.float32) + jnp.arange(4, dtype=jnp.int32)

    jaxpr = jax.make_jaxpr(good)(jax.ShapeDtypeStruct((4,), np.float32))
    assert check_jaxpr(_spec(), jaxpr, [Widen64BitRule()]) == []


def test_every_ir_rule_has_corpus_coverage():
    covered = {"ir-widen-64bit", "ir-callback-in-loop",
               "ir-host-transfer-in-loop"}
    assert {r.rule_id for r in ALL_IR_RULES} == covered
    assert set(ir_rule_ids()) == covered | {PAYLOAD_RULE}


# ---------------------------------------------------------- payload auditor
def test_payload_auditor_catches_drift():
    """Seeded bad fixture for the headline rule: a family whose analytic
    model is off by 4 bytes must fail validation with a PAYLOAD_RULE
    finding (if this passes while the gate passes, the auditor is
    actually comparing, not rubber-stamping)."""
    nb = next(s for s in manifest_entries() if s.name == "nb_train")
    drifted = dataclasses.replace(
        nb, payload_model=lambda mesh: nb.payload_model(mesh) + 4)
    audit, finding = audit_family(drifted, jax.devices())
    assert audit["payload_model_validated"] is False
    assert finding is not None and finding.rule == PAYLOAD_RULE
    assert finding.scope == "nb_train"
    # and the honest model validates with no finding
    audit, finding = audit_family(nb, jax.devices())
    assert audit["payload_model_validated"] is True and finding is None


def test_run_ir_wraps_trace_failures():
    def boom(_mesh):
        raise ValueError("synthetic trace failure")

    entry = KernelSpec("boom", "x.py", 1, build=boom)
    with pytest.raises(IRTraceError, match="boom"):
        run_ir(entries=[entry], baseline=[])


# -------------------------------------------------------------------- CLI
def _cli(args, cwd=REPO, env=None):
    e = dict(os.environ)
    if env:
        e.update(env)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "graftlint.py")] + args,
        capture_output=True, text=True, cwd=cwd, timeout=600, env=e)


def test_cli_ir_json_clean_and_schema():
    proc = _cli(["--ir", "--json"])
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rep = json.loads(proc.stdout)
    assert rep["clean"] and rep["findings"] == []
    audit = rep["payload_audit"]
    assert len(audit) == 8
    assert all(a["payload_model_validated"] for a in audit)
    # one schema across both modes: same top-level keys as the AST golden
    golden = json.load(open(os.path.join(
        REPO, "tests", "data", "graftlint_json_golden.json")))
    assert set(rep) == set(golden)


def test_cli_ir_usage_and_trace_errors_exit_2():
    assert _cli(["--ir", "avenir_tpu/"]).returncode == 2   # paths + --ir
    assert _cli(["--ir", "--rules", "nope"]).returncode == 2
    # a too-small device pool is a trace error, not a clean/finding run:
    # pin 1 virtual device (via the explicit test override — a merely
    # INHERITED small XLA flag is raised to the audit size, so a
    # parent's own pool exports can't spuriously fail the audit)
    proc = _cli(["--ir"], env={"GRAFTLINT_IR_DEVICES": "1"})
    assert proc.returncode == 2, proc.stdout + proc.stderr
    assert "trace error" in proc.stderr


def test_cli_ir_raises_inherited_small_device_flag():
    """Any parent may export --xla_force_host_platform_device_count=<n>
    for its own mesh before it starts the audit; the graftlint --ir
    bootstrap must bump an inherited smaller count to the audit size
    instead of failing on it."""
    proc = _cli(["--ir", "--json"], env={
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rep = json.loads(proc.stdout)
    assert rep["clean"] and len(rep["payload_audit"]) == 8
