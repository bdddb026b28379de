"""graftlint CLI: `graftlint <paths>` (console script) or
`python tools/graftlint.py <paths>`.

Eight modes sharing one report/baseline/exit contract, plus ``--all``:

- AST (default): lint source paths with the rules.py catalog.
- IR (``--ir``, no paths): trace the kernel manifest
  (analysis/manifest.py), run the jaxpr rules and the collective-payload
  audit (analysis/ir.py) on the virtual 8-device mesh.
- Flow (``--flow``, paths optional — defaults to the gated repo
  surface): the host concurrency/determinism rules (analysis/flow.py)
  plus the chunk-invariance audit of the streamed fold kernels
  (manifest ``stream_entries()``).
- Mem (``--mem``, paths optional — same default surface): the memory-
  footprint rules (analysis/mem.py) plus the RSS/live-bytes footprint
  audit that proves the analytic memory model against sampled peak RSS
  for every streamed job at >= 2 block sizes.
- Merge (``--merge``, paths optional — same default surface): the
  fold-state merge-algebra rules (analysis/merge.py) plus the
  shard-merge/resume audit proving every streamed job's carry merges
  across P ∈ {2, 4} shards and checkpoint-resumes byte-identically.
- Proto (``--proto``, paths optional — defaults to the shared-
  filesystem protocol surface): the publish/read protocol-discipline
  rules (analysis/proto.py) plus the commit-point crash auditor that
  hard-kills a real publish per registered commit site at
  before-rename and after-rename and proves recovery byte-identical.
- Race (``--race``, paths optional — defaults to the multi-writer
  protocol surface): the cross-process race rules (analysis/race.py)
  plus the deterministic-interleaving explorer that steps two real
  actor subprocesses through every registered interleave site's
  sched_point schedule space and proves exactly-one-winner /
  conservation / solo byte-identity per schedule. A failing schedule
  prints a replayable trace; ``--schedule <site>:<digits>`` replays
  exactly that interleaving.
- Keys (``--keys``, paths optional — defaults to the cache-key
  surface): the cache-key completeness rules (analysis/keys.py) plus
  the stale-serve perturbation auditor that seeds every registered
  key site's cache cold, perturbs each registered input dimension one
  at a time, and proves view-affecting changes move the key with
  served bytes equal to a cold recompute, view-neutral changes keep
  the key and warm-hit byte-identically, and version-skewed manifests
  refuse-and-go-cold. A stale serve surfaces as ``keys-stale-serve``
  and is never allowlistable.
- All (``--all``): the eight tiers in ONE process — combined JSON
  under a ``modes`` key (each tier's report carries its ``wall_s``)
  and a single worst-of exit code (the operator's one command for
  the audits at full depth). ``--all --parallel`` fans the tiers
  out as subprocesses — same combined JSON, same worst-of exit, the
  wall clock of the slowest tier instead of the sum.

Exit-code contract (stable: the tests/test_graftlint*.py CLI tests
hold it, tier by tier):
  0  clean: no findings, no stale baseline entries, no parse errors
  1  findings — non-allowlisted findings, stale baseline entries, or
     parse errors in the linted sources
  2  usage-or-trace-error — bad flags/baseline format/unreadable input,
     a manifest entry that failed to trace/lower (--ir), a stream
     kernel that failed to run (--flow / --mem / --merge), a crash
     child / commit-site registry failure (--proto), an actor pool
     / scheduler / interleave-site registry failure (--race), or a
     perturbation driver / key-site registry failure (--keys)
``--all`` exits with the WORST code any tier produced.

`--json` prints one machine-readable object in every single-tier mode
(same schema: `payload_audit` is empty outside --ir, `invariance_audit`
outside --flow, `footprint_audit` outside --mem, `merge_audit` outside
--merge, `proto_audit` outside --proto, `race_audit` outside --race,
`key_audit` outside --keys);
``--all --json`` prints ``{"modes": {<tier>: <report>},
"clean": bool}`` with every tier's report under its name.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional

from avenir_tpu.analysis.engine import (default_baseline_path, load_baseline,
                                        run_paths)
from avenir_tpu.analysis.rules import ALL_RULES, rule_ids

#: the eight analysis tiers, in audit-cost order (cheapest first)
TIERS = ("ast", "ir", "flow", "mem", "merge", "proto", "race", "keys")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="graftlint",
        description="AST + IR JAX/TPU hazard analyzer (rule catalog: "
                    "docs/graftlint.md)")
    p.add_argument("paths", nargs="*",
                   help=".py/.md files or directories to lint (omit with "
                        "--ir)")
    p.add_argument("--ir", action="store_true",
                   help="lint the traceable-kernel manifest instead of "
                        "source paths: jaxpr rules + the distributed-family "
                        "collective-payload audit on the virtual 8-device "
                        "mesh")
    p.add_argument("--flow", action="store_true",
                   help="host concurrency/determinism analysis: the flow-* "
                        "rules over the paths (default: the gated repo "
                        "surface) + the chunk-invariance audit of the "
                        "streamed fold kernels")
    p.add_argument("--mem", action="store_true",
                   help="memory-footprint analysis: the mem-* rules over "
                        "the paths (default: the gated repo surface) + the "
                        "RSS footprint audit proving the analytic memory "
                        "model for every streamed job at >= 2 block sizes")
    p.add_argument("--merge", action="store_true",
                   help="fold-state merge-algebra analysis: the merge-* "
                        "rules over the paths (default: the gated repo "
                        "surface) + the shard-merge/resume audit proving "
                        "every streamed job's carry merges across shards "
                        "and checkpoint-resumes byte-identically")
    p.add_argument("--proto", action="store_true",
                   help="shared-filesystem protocol-discipline analysis: "
                        "the proto-* rules over the paths (default: the "
                        "protocol surface) + the commit-point crash audit "
                        "that hard-kills a real publish per registered "
                        "commit site at before-rename and after-rename and "
                        "proves recovery byte-identical with no stranded "
                        "tmp")
    p.add_argument("--race", action="store_true",
                   help="cross-process race analysis: the race-* rules "
                        "over the paths (default: the multi-writer "
                        "protocol surface) + the deterministic-"
                        "interleaving explorer that steps two real actor "
                        "subprocesses through every registered interleave "
                        "site's schedule space and proves exactly-one-"
                        "winner / conservation / solo byte-identity per "
                        "schedule")
    p.add_argument("--keys", action="store_true",
                   help="cache-key completeness analysis: the keys-* "
                        "rules over the paths (default: the cache-key "
                        "surface) + the stale-serve perturbation audit "
                        "that moves every registered input dimension of "
                        "every registered key site one at a time and "
                        "proves affecting changes move the key with "
                        "warm-served bytes equal to a cold recompute, "
                        "neutral changes warm-hit byte-identically, and "
                        "version-skewed manifests refuse-and-go-cold")
    p.add_argument("--schedule", default=None, metavar="SITE:DIGITS",
                   help="with --race: replay exactly one interleaving "
                        "trace (as printed by a failing schedule), e.g. "
                        "ledger.claim:01101")
    p.add_argument("--all", action="store_true", dest="all_tiers",
                   help="run all eight tiers in one process: combined "
                        "JSON (modes keyed by tier) and a single "
                        "worst-of exit code")
    p.add_argument("--parallel", action="store_true",
                   help="with --all: fan the tiers out as subprocesses "
                        "(same combined JSON and worst-of exit; per-tier "
                        "wall_s recorded either way)")
    p.add_argument("--baseline", default=None,
                   help="allowlist file (default: "
                        "avenir_tpu/analysis/graftlint_baseline.txt)")
    p.add_argument("--no-baseline", action="store_true",
                   help="report every finding, ignoring the allowlist")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="emit one JSON object instead of text")
    p.add_argument("--rules", default=None, metavar="ID[,ID...]",
                   help=f"comma-separated subset of: {', '.join(rule_ids())} "
                        f"(or the ir-* ids with --ir, the flow-* ids with "
                        f"--flow, the mem-* ids with --mem, the merge-* ids "
                        f"with --merge, the proto-* ids with --proto, the "
                        f"race-* ids with --race, the keys-* ids with "
                        f"--keys; --all accepts ids from "
                        f"any tier and skips tiers with none selected)")
    p.add_argument("--no-md", action="store_true",
                   help="skip ```python fences in .md files")
    p.add_argument("--allow-stale", action="store_true",
                   help="do not fail on baseline entries that no longer "
                        "match (use only while mid-refactor)")
    return p


def _bootstrap_ir_env() -> None:
    """Pin a CPU platform with enough virtual devices for the audit mesh
    BEFORE jax initializes (harmless no-op when the caller — e.g. the
    tier-1 test process — already initialized a big-enough pool).

    An inherited ``--xla_force_host_platform_device_count`` SMALLER than
    the audit needs is raised, not honored: a parent may legitimately
    export a small pool for its own mesh, and inheriting
    it would turn a clean audit into a spurious trace error.
    ``GRAFTLINT_IR_DEVICES`` overrides the target pool size explicitly
    (the too-small-pool CLI test uses it; a real run never should)."""
    from avenir_tpu.analysis.manifest import AUDIT_DEVICES

    if "jax" in sys.modules:
        return                       # too late; run_ir checks the pool size
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    want = AUDIT_DEVICES
    flag = "--xla_force_host_platform_device_count"
    flags = []
    for f in os.environ.get("XLA_FLAGS", "").split():
        if f.startswith(flag):
            try:
                want = max(want, int(f.split("=", 1)[1]))
            except (IndexError, ValueError):
                pass
        else:
            flags.append(f)
    override = os.environ.get("GRAFTLINT_IR_DEVICES")
    if override is not None:
        want = int(override)         # explicit override beats everything
    flags.append(f"{flag}={want}")
    os.environ["XLA_FLAGS"] = " ".join(flags)


def _report_root(args) -> Optional[str]:
    # finding keys must be cwd-independent so the baseline matches from
    # anywhere: anchor them to the repo root (the default baseline sits at
    # <root>/avenir_tpu/analysis/) or to an explicit baseline's directory
    if args.baseline:
        return os.path.dirname(os.path.abspath(args.baseline))
    if args.no_baseline:
        return None                  # cwd: keys are ephemeral anyway
    return os.path.dirname(os.path.dirname(os.path.dirname(
        default_baseline_path())))


def _print_report(report, is_ir: bool) -> None:
    for f in report.errors + report.findings:
        print(f.render())
    for e in report.stale:
        print(f"stale baseline entry (line {e.lineno}): {e.key} — the "
              f"finding it excused is gone; delete it", file=sys.stderr)
    unit = "kernel modules" if is_ir else "files"
    tail = ""
    if report.payload_audit:
        ok = sum(1 for a in report.payload_audit
                 if a["payload_model_validated"])
        tail = (f", payload audit {ok}/{len(report.payload_audit)} "
                f"families validated")
    if report.invariance_audit:
        ok = sum(1 for a in report.invariance_audit
                 if a["invariance_validated"])
        tail += (f", chunk-invariance audit {ok}/"
                 f"{len(report.invariance_audit)} stream kernels "
                 f"validated")
    if report.footprint_audit:
        ok = sum(1 for a in report.footprint_audit
                 if a["footprint_model_validated"])
        tail += (f", footprint audit {ok}/"
                 f"{len(report.footprint_audit)} streamed jobs "
                 f"validated")
    if report.merge_audit:
        ok = sum(1 for a in report.merge_audit if a["merge_validated"])
        tail += (f", merge audit {ok}/{len(report.merge_audit)} "
                 f"stream kernels validated")
    if report.proto_audit:
        ok = sum(1 for a in report.proto_audit
                 if a["commit_point_validated"])
        tail += (f", commit-point audit {ok}/"
                 f"{len(report.proto_audit)} commit sites validated")
    if report.race_audit:
        ok = sum(1 for a in report.race_audit
                 if a["interleaving_validated"])
        n_sched = sum(sum(a["schedules"].values())
                      for a in report.race_audit)
        tail += (f", interleaving audit {ok}/"
                 f"{len(report.race_audit)} sites validated over "
                 f"{n_sched} schedules")
    if report.key_audit:
        ok = sum(1 for a in report.key_audit if a["key_validated"])
        n_pert = sum(sum(a["perturbations"].values())
                     for a in report.key_audit)
        tail += (f", key-perturbation audit {ok}/"
                 f"{len(report.key_audit)} sites validated over "
                 f"{n_pert} perturbations")
    print(f"graftlint: {len(report.scanned)} {unit}, "
          f"{len(report.findings)} finding(s), "
          f"{len(report.suppressed)} allowlisted, "
          f"{len(report.stale)} stale baseline entr(y/ies)"
          + (f", {len(report.errors)} parse error(s)"
             if report.errors else "") + tail)


def _exit_code(report, args) -> int:
    if report.findings or report.errors:
        return 1
    if report.stale and not args.allow_stale:
        return 1
    return 0


def _tier_rule_ids() -> dict:
    """Every tier's known rule ids (audit pseudo-rules included) —
    the skip decision for a ``--rules`` subset, shared by the
    sequential and ``--parallel`` fan-outs."""
    from avenir_tpu.analysis.flow import flow_rule_ids
    from avenir_tpu.analysis.ir import ir_rule_ids
    from avenir_tpu.analysis.mem import mem_rule_ids
    from avenir_tpu.analysis.merge import merge_rule_ids
    from avenir_tpu.analysis.keys import keys_rule_ids
    from avenir_tpu.analysis.proto import proto_rule_ids
    from avenir_tpu.analysis.race import race_rule_ids

    return {"ast": rule_ids(), "ir": ir_rule_ids(),
            "flow": flow_rule_ids(), "mem": mem_rule_ids(),
            "merge": merge_rule_ids(), "proto": proto_rule_ids(),
            "race": race_rule_ids(), "keys": keys_rule_ids()}


def _run_all_parallel(args, wanted: Optional[List[str]]) -> int:
    """The ``--all --parallel`` mode: one subprocess per tier, same
    combined JSON (each tier's report under ``modes`` with its
    measured ``wall_s``) and the same worst-of exit as the sequential
    ``--all`` — but the wall clock of the slowest tier instead of the
    sum. Tier subprocesses re-enter this CLI in single-tier --json
    mode, so the per-tier contract is exactly the documented one."""
    import subprocess
    import time

    known = _tier_rule_ids()
    modes = {}
    worst = 0
    procs = []
    for name in TIERS:
        sub_wanted = None
        if wanted is not None:
            sub_wanted = [w for w in wanted if w in known[name]]
            if not sub_wanted:
                modes[name] = {"skipped": True}
                continue
        argv = [sys.executable, "-m", "avenir_tpu.analysis.cli",
                "--json"]
        if name == "ast":
            argv.extend(args.paths or _default_surface())
        else:
            argv.append(f"--{name}")
            if args.paths and name != "ir":
                argv.extend(args.paths)
        if args.no_baseline:
            argv.append("--no-baseline")
        elif args.baseline:
            argv.extend(["--baseline", args.baseline])
        if args.no_md:
            argv.append("--no-md")
        if args.allow_stale:
            argv.append("--allow-stale")
        if sub_wanted is not None:
            argv.extend(["--rules", ",".join(sub_wanted)])
        env = dict(os.environ)
        env.setdefault("JAX_PLATFORMS", "cpu")
        # -m avenir_tpu.analysis.cli must resolve even when the parent
        # was launched from outside the checkout (tools/graftlint.py
        # patches sys.path, which children don't inherit)
        pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        env["PYTHONPATH"] = os.pathsep.join(
            [pkg_root] + [p for p in env.get("PYTHONPATH", "").split(
                os.pathsep) if p])
        procs.append((name, time.monotonic(), subprocess.Popen(
            argv, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            env=env, text=True)))
    for name, t0, proc in procs:
        out, err = proc.communicate()
        wall = time.monotonic() - t0
        if proc.returncode not in (0, 1):
            tail = (err or out).strip()[-400:]
            print(f"graftlint [{name}]: {tail}", file=sys.stderr)
            modes[name] = {"error": tail, "wall_s": round(wall, 3)}
            worst = 2
            continue
        try:
            rep = json.loads(out)
        except ValueError:
            print(f"graftlint [{name}]: unparsable tier output",
                  file=sys.stderr)
            modes[name] = {"error": "unparsable tier output",
                           "wall_s": round(wall, 3)}
            worst = 2
            continue
        rep["wall_s"] = round(wall, 3)
        modes[name] = rep
        worst = max(worst, proc.returncode)
        if not args.as_json:
            print(f"-- {name} ({wall:.2f}s): "
                  f"{len(rep.get('findings', []))} finding(s), "
                  f"clean={rep.get('clean')}")
    clean = worst == 0
    if args.as_json:
        print(json.dumps({"modes": modes, "clean": clean}, indent=1))
    else:
        print(f"graftlint --all --parallel: "
              f"{sum(1 for m in modes.values() if 'skipped' in m)} "
              f"tier(s) skipped, worst exit {worst}")
    return worst


def _run_all(args, baseline, wanted: Optional[List[str]]) -> int:
    """The ``--all`` mode: eight tiers, one process, worst-of exit.

    A ``--rules`` subset skips every tier it names no rules of (its
    audit included only when the tier's audit pseudo-rule is named), so
    fixture-level CI checks stay fast; the full run is the operator's
    ``python tools/graftlint.py --all``."""
    if args.parallel:
        return _run_all_parallel(args, wanted)
    import time

    _bootstrap_ir_env()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from avenir_tpu.analysis.flow import (ALL_FLOW_RULES, FLOW_AUDIT_RULE,
                                          FlowAuditError, run_flow)
    from avenir_tpu.analysis.ir import (ALL_IR_RULES, IRTraceError,
                                        PAYLOAD_RULE, run_ir)
    from avenir_tpu.analysis.mem import (ALL_MEM_RULES, MEM_AUDIT_RULE,
                                         MemAuditError, run_mem)
    from avenir_tpu.analysis.merge import (ALL_MERGE_RULES, MERGE_AUDIT_RULE,
                                           MergeAuditError, run_merge)
    from avenir_tpu.analysis.proto import (ALL_PROTO_RULES, PROTO_AUDIT_RULE,
                                           ProtoAuditError, run_proto)
    from avenir_tpu.analysis.keys import (ALL_KEYS_RULES, KEYS_AUDIT_RULE,
                                          KeysAuditError, run_keys)
    from avenir_tpu.analysis.race import (ALL_RACE_RULES, RACE_AUDIT_RULE,
                                          RaceAuditError, run_race)

    paths = args.paths or None
    root = _report_root(args)
    md = not args.no_md

    def pick(rule_classes):
        if wanted is None:
            return [r() for r in rule_classes]
        return [r() for r in rule_classes if r.rule_id in wanted]

    def want_audit(audit_rule):
        return wanted is None or audit_rule in wanted

    modes = {}
    worst = 0
    runs = [
        ("ast", None, None,
         lambda: run_paths(paths or _default_surface(), rules=pick(ALL_RULES),
                           baseline=baseline, root=root, include_md=md),
         lambda: bool(pick(ALL_RULES))),
        ("ir", IRTraceError, "trace error",
         lambda: run_ir(rules=pick(ALL_IR_RULES), baseline=baseline,
                        audit=want_audit(PAYLOAD_RULE)),
         lambda: bool(pick(ALL_IR_RULES)) or want_audit(PAYLOAD_RULE)),
        ("flow", FlowAuditError, "stream audit error",
         lambda: run_flow(paths=paths, rules=pick(ALL_FLOW_RULES),
                          baseline=baseline, root=root, include_md=md,
                          audit=want_audit(FLOW_AUDIT_RULE)),
         lambda: bool(pick(ALL_FLOW_RULES)) or want_audit(FLOW_AUDIT_RULE)),
        ("mem", MemAuditError, "footprint audit error",
         lambda: run_mem(paths=paths, rules=pick(ALL_MEM_RULES),
                         baseline=baseline, root=root, include_md=md,
                         audit=want_audit(MEM_AUDIT_RULE)),
         lambda: bool(pick(ALL_MEM_RULES)) or want_audit(MEM_AUDIT_RULE)),
        ("merge", MergeAuditError, "merge audit error",
         lambda: run_merge(paths=paths, rules=pick(ALL_MERGE_RULES),
                           baseline=baseline, root=root, include_md=md,
                           audit=want_audit(MERGE_AUDIT_RULE)),
         lambda: bool(pick(ALL_MERGE_RULES)) or want_audit(MERGE_AUDIT_RULE)),
        ("proto", ProtoAuditError, "commit-point audit error",
         lambda: run_proto(paths=paths, rules=pick(ALL_PROTO_RULES),
                           baseline=baseline, root=root, include_md=md,
                           audit=want_audit(PROTO_AUDIT_RULE)),
         lambda: bool(pick(ALL_PROTO_RULES)) or want_audit(PROTO_AUDIT_RULE)),
        ("race", RaceAuditError, "interleaving audit error",
         lambda: run_race(paths=paths, rules=pick(ALL_RACE_RULES),
                          baseline=baseline, root=root, include_md=md,
                          audit=want_audit(RACE_AUDIT_RULE)),
         lambda: bool(pick(ALL_RACE_RULES)) or want_audit(RACE_AUDIT_RULE)),
        ("keys", KeysAuditError, "key-perturbation audit error",
         lambda: run_keys(paths=paths, rules=pick(ALL_KEYS_RULES),
                          baseline=baseline, root=root, include_md=md,
                          audit=want_audit(KEYS_AUDIT_RULE)),
         lambda: bool(pick(ALL_KEYS_RULES)) or want_audit(KEYS_AUDIT_RULE)),
    ]
    for name, err_cls, err_label, run, active in runs:
        if wanted is not None and not active():
            modes[name] = {"skipped": True}
            continue
        t0 = time.monotonic()
        try:
            report = run()
        except tuple(c for c in (err_cls, OSError) if c is not None) as e:
            label = err_label or "error"
            print(f"graftlint [{name}]: {label}: {e}", file=sys.stderr)
            modes[name] = {"error": str(e),
                           "wall_s": round(time.monotonic() - t0, 3)}
            worst = 2
            continue
        modes[name] = dict(report.to_json(),
                           wall_s=round(time.monotonic() - t0, 3))
        if not args.as_json:
            print(f"-- {name} " + "-" * (68 - len(name)))
            _print_report(report, is_ir=(name == "ir"))
        worst = max(worst, _exit_code(report, args))
    clean = worst == 0
    if args.as_json:
        print(json.dumps({"modes": modes, "clean": clean}, indent=1))
    else:
        print(f"graftlint --all: "
              f"{sum(1 for m in modes.values() if 'skipped' in m)} tier(s) "
              f"skipped, worst exit {worst}")
    return worst


def _default_surface() -> List[str]:
    from avenir_tpu.analysis.flow import default_flow_paths

    return default_flow_paths(os.getcwd())


def main(argv: Optional[List[str]] = None) -> int:
    parser = build_parser()
    args = parser.parse_args(argv)
    tier_flags = sum(1 for m in (args.ir, args.flow, args.mem, args.merge,
                                 args.proto, args.race, args.keys)
                     if m)
    if tier_flags > 1 or (args.all_tiers and tier_flags):
        print("graftlint: --ir, --flow, --mem, --merge, --proto, --race "
              "and --keys are separate analysis tiers; run them as "
              "separate invocations (or use --all for every tier at once)",
              file=sys.stderr)
        return 2
    if args.ir and args.paths:
        print("graftlint: --ir lints the kernel manifest; do not pass "
              "paths (run the two modes as two invocations)",
              file=sys.stderr)
        return 2
    if args.schedule and not args.race:
        print("graftlint: --schedule replays an interleaving trace and "
              "needs --race", file=sys.stderr)
        return 2
    if args.parallel and not args.all_tiers:
        print("graftlint: --parallel fans out the tiers and needs --all",
              file=sys.stderr)
        return 2
    if not args.all_tiers and not tier_flags and not args.paths:
        print("graftlint: pass paths to lint, or --ir / --flow / --mem / "
              "--merge / --proto / --race / --keys for the manifest "
              "audits (or --all for every tier)", file=sys.stderr)
        return 2

    if args.ir:
        _bootstrap_ir_env()
        from avenir_tpu.analysis.ir import (ALL_IR_RULES, IRTraceError,
                                            ir_rule_ids, run_ir)
        known = ir_rule_ids()
    elif args.flow:
        # the invariance audit runs real jobs: pin the CPU platform the
        # way every other analysis consumer does
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        from avenir_tpu.analysis.flow import (ALL_FLOW_RULES, FLOW_AUDIT_RULE,
                                              FlowAuditError, flow_rule_ids,
                                              run_flow)
        known = flow_rule_ids()
    elif args.mem:
        # the footprint audit runs real jobs too: same platform pin
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        from avenir_tpu.analysis.mem import (ALL_MEM_RULES, MEM_AUDIT_RULE,
                                             MemAuditError, mem_rule_ids,
                                             run_mem)
        known = mem_rule_ids()
    elif args.merge:
        # the shard-merge/resume audit drives real fold sinks: same pin
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        from avenir_tpu.analysis.merge import (ALL_MERGE_RULES,
                                               MERGE_AUDIT_RULE,
                                               MergeAuditError,
                                               merge_rule_ids, run_merge)
        known = merge_rule_ids()
    elif args.proto:
        # the commit-point audit spawns real publish jobs: same pin
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        from avenir_tpu.analysis.proto import (ALL_PROTO_RULES,
                                               PROTO_AUDIT_RULE,
                                               ProtoAuditError,
                                               proto_rule_ids, run_proto)
        known = proto_rule_ids()
    elif args.race:
        # the interleaving audit spawns real actor children: same pin
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        from avenir_tpu.analysis.race import (ALL_RACE_RULES,
                                              RACE_AUDIT_RULE,
                                              RaceAuditError,
                                              race_rule_ids, run_race)
        known = race_rule_ids()
    elif args.keys:
        # the perturbation audit runs real jobs over seeded roots: pin
        # the CPU platform the way every other audit consumer does
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        from avenir_tpu.analysis.keys import (ALL_KEYS_RULES,
                                              KEYS_AUDIT_RULE,
                                              KeysAuditError,
                                              keys_rule_ids, run_keys)
        known = keys_rule_ids()
    elif args.all_tiers:
        known = [rid for ids in _tier_rule_ids().values() for rid in ids]
    else:
        known = rule_ids()

    if args.rules:
        wanted = [r.strip() for r in args.rules.split(",") if r.strip()]
        unknown = set(wanted) - set(known)
        if unknown:
            print(f"graftlint: unknown rule(s): {', '.join(sorted(unknown))}",
                  file=sys.stderr)
            return 2
    else:
        wanted = None

    try:
        baseline = ([] if args.no_baseline
                    else load_baseline(args.baseline or
                                       default_baseline_path()))
    except ValueError as e:
        print(f"graftlint: {e}", file=sys.stderr)
        return 2

    if args.all_tiers:
        return _run_all(args, baseline, wanted)

    if args.ir:
        from avenir_tpu.analysis.ir import PAYLOAD_RULE
        ir_rules = ([r() for r in ALL_IR_RULES] if wanted is None
                    else [r() for r in ALL_IR_RULES if r.rule_id in wanted])
        audit = wanted is None or PAYLOAD_RULE in wanted
        try:
            report = run_ir(rules=ir_rules, baseline=baseline, audit=audit)
        except IRTraceError as e:
            print(f"graftlint: trace error: {e}", file=sys.stderr)
            return 2
    elif args.flow:
        flow_rules = ([r() for r in ALL_FLOW_RULES] if wanted is None
                      else [r() for r in ALL_FLOW_RULES
                            if r.rule_id in wanted])
        audit = wanted is None or FLOW_AUDIT_RULE in wanted
        try:
            report = run_flow(paths=args.paths or None, rules=flow_rules,
                              baseline=baseline, root=_report_root(args),
                              include_md=not args.no_md, audit=audit)
        except FlowAuditError as e:
            print(f"graftlint: stream audit error: {e}", file=sys.stderr)
            return 2
        except OSError as e:
            print(f"graftlint: cannot read input: {e}", file=sys.stderr)
            return 2
    elif args.mem:
        mem_rules = ([r() for r in ALL_MEM_RULES] if wanted is None
                     else [r() for r in ALL_MEM_RULES
                           if r.rule_id in wanted])
        audit = wanted is None or MEM_AUDIT_RULE in wanted
        try:
            report = run_mem(paths=args.paths or None, rules=mem_rules,
                             baseline=baseline, root=_report_root(args),
                             include_md=not args.no_md, audit=audit)
        except MemAuditError as e:
            print(f"graftlint: footprint audit error: {e}", file=sys.stderr)
            return 2
        except OSError as e:
            print(f"graftlint: cannot read input: {e}", file=sys.stderr)
            return 2
    elif args.merge:
        merge_rules = ([r() for r in ALL_MERGE_RULES] if wanted is None
                       else [r() for r in ALL_MERGE_RULES
                             if r.rule_id in wanted])
        audit = wanted is None or MERGE_AUDIT_RULE in wanted
        try:
            report = run_merge(paths=args.paths or None, rules=merge_rules,
                               baseline=baseline, root=_report_root(args),
                               include_md=not args.no_md, audit=audit)
        except MergeAuditError as e:
            print(f"graftlint: merge audit error: {e}", file=sys.stderr)
            return 2
        except OSError as e:
            print(f"graftlint: cannot read input: {e}", file=sys.stderr)
            return 2
    elif args.proto:
        proto_rules = ([r() for r in ALL_PROTO_RULES] if wanted is None
                       else [r() for r in ALL_PROTO_RULES
                             if r.rule_id in wanted])
        audit = wanted is None or PROTO_AUDIT_RULE in wanted
        try:
            report = run_proto(paths=args.paths or None, rules=proto_rules,
                               baseline=baseline, root=_report_root(args),
                               include_md=not args.no_md, audit=audit)
        except ProtoAuditError as e:
            print(f"graftlint: commit-point audit error: {e}",
                  file=sys.stderr)
            return 2
        except OSError as e:
            print(f"graftlint: cannot read input: {e}", file=sys.stderr)
            return 2
    elif args.race:
        race_rules = ([r() for r in ALL_RACE_RULES] if wanted is None
                      else [r() for r in ALL_RACE_RULES
                            if r.rule_id in wanted])
        audit = wanted is None or RACE_AUDIT_RULE in wanted
        schedule = None
        if args.schedule:
            from avenir_tpu.analysis.race import parse_schedule
            try:
                schedule = parse_schedule(args.schedule)
            except ValueError as e:
                print(f"graftlint: {e}", file=sys.stderr)
                return 2
        try:
            report = run_race(paths=args.paths or None, rules=race_rules,
                              baseline=baseline, root=_report_root(args),
                              include_md=not args.no_md, audit=audit,
                              schedule=schedule)
        except RaceAuditError as e:
            print(f"graftlint: interleaving audit error: {e}",
                  file=sys.stderr)
            return 2
        except OSError as e:
            print(f"graftlint: cannot read input: {e}", file=sys.stderr)
            return 2
    elif args.keys:
        keys_rules = ([r() for r in ALL_KEYS_RULES] if wanted is None
                      else [r() for r in ALL_KEYS_RULES
                            if r.rule_id in wanted])
        audit = wanted is None or KEYS_AUDIT_RULE in wanted
        try:
            report = run_keys(paths=args.paths or None, rules=keys_rules,
                              baseline=baseline, root=_report_root(args),
                              include_md=not args.no_md, audit=audit)
        except KeysAuditError as e:
            print(f"graftlint: key-perturbation audit error: {e}",
                  file=sys.stderr)
            return 2
        except OSError as e:
            print(f"graftlint: cannot read input: {e}", file=sys.stderr)
            return 2
    else:
        rules = (None if wanted is None
                 else [r() for r in ALL_RULES if r.rule_id in wanted])
        try:
            report = run_paths(args.paths, rules=rules, baseline=baseline,
                               root=_report_root(args),
                               include_md=not args.no_md)
        except OSError as e:
            print(f"graftlint: cannot read input: {e}", file=sys.stderr)
            return 2

    if args.as_json:
        print(json.dumps(report.to_json(), indent=1))
    else:
        _print_report(report, is_ir=args.ir)

    return _exit_code(report, args)


if __name__ == "__main__":
    sys.exit(main())
