"""Scaling-efficiency bench: distributed NB + KNN over 1/2/4/8-device meshes.

Prints ONE JSON line:
  {"metric": "scaling_efficiency_nb_knn", "value": <geomean efficiency at
   max devices>, "unit": "fraction_of_linear", "table": [...],
   "miner_tripwire": {...}}

A CPU instrument: it pins JAX_PLATFORMS=cpu and a virtual CPU device pool
in its own environment before JAX starts, so this process and every child
it starts (fleets, shard workers, solo arms) state the same platform and
none of them touches a chip. See avenir_tpu/parallel/scaling.py for what
the virtual numbers do and don't mean.

miner_tripwire: the two slowest streamed jobs of the 100M-row scale run
(frequentItemsApriori, candidateGenerationWithSelfJoin — STREAM_SCALE_r05
measured them at 320.7s/461.8s with rows:null, i.e. no throughput counter
at all) are exercised here over a small streamed corpus purely so their
Basic:Records / Basic:RowsPerSec counters are asserted non-null every
bench round. A regression that silently drops the counters — or tanks the
streamed rate — now fails/flags the bench instead of going unnoticed
until the next 100M-row run.
"""

import json
import os
import sys
import tempfile


def graftlint_tripwire() -> dict:
    """Run the graftlint CLI (--json) over the package, the --ir
    manifest audit, the --flow concurrency/invariance audit, the
    --mem footprint audit, the --merge shard-merge/resume audit,
    the --proto commit-point crash audit, the --race deterministic
    interleaving audit AND the --keys stale-serve perturbation
    audit, failing the bench on any
    non-allowlisted finding, stale baseline entry, trace error, a
    distributed family whose collective payload drifted off the
    scaling.py analytic model, a streamed fold kernel whose output
    bytes moved with the chunk layout, a streamed job whose measured
    peak RSS left the memory model's tolerance band, a fold state
    whose shard merge / checkpoint resume drifted a byte, a
    shared-filesystem commit site whose kill-injected recovery was
    not byte-identical, a cross-process interleave site with a
    losable schedule, or a cache key that stopped covering its view —
    hazard/traffic/determinism/footprint/
    merge-algebra/protocol/race/key regressions surface here every
    round, not at the next 100M-row run. The
    round's memory manifest (the job server's admission oracle) is
    re-derived and written next to the STREAM_SCALE_*.json records."""
    import os
    import subprocess

    root = os.path.dirname(os.path.abspath(__file__))

    def run(extra, what):
        proc = subprocess.run(
            [sys.executable, os.path.join(root, "tools", "graftlint.py")]
            + extra + ["--json"],
            capture_output=True, text=True, cwd=root, timeout=600)
        try:
            rep = json.loads(proc.stdout)
        except ValueError:
            raise RuntimeError(
                f"graftlint {what} emitted no JSON "
                f"(rc={proc.returncode}): {proc.stderr[-400:]}")
        if proc.returncode != 0 or not rep.get("clean"):
            raise RuntimeError(
                f"graftlint {what} regression: counts={rep.get('counts')} "
                f"stale={rep.get('stale_baseline_entries')} "
                f"errors={len(rep.get('errors', []))}")
        return rep

    ast_rep = run([os.path.join(root, "avenir_tpu")], "AST")
    ir_rep = run(["--ir"], "--ir")
    audit = ir_rep["payload_audit"]
    bad = [a["family"] for a in audit if not a["payload_model_validated"]]
    if bad or len(audit) < 8:
        raise RuntimeError(
            f"collective payload audit regression: "
            f"{len(audit)} families audited, drifted={bad}")
    flow_rep = run(["--flow"], "--flow")
    inv = flow_rep["invariance_audit"]
    drifted = [r["kernel"] for r in inv if not r["invariance_validated"]]
    # >= 8: the 6 one-job-one-scan fold kernels plus the 2 FUSED
    # shared-scan entries (shared_churn_stream, shared_seq_stream) — the
    # scan-sharing executor's byte-identity is re-proven every round
    if drifted or len(inv) < 8:
        raise RuntimeError(
            f"chunk-invariance audit regression: {len(inv)} stream "
            f"kernels audited, drifted={drifted}")
    mem_rep = run(["--mem"], "--mem")
    fp = mem_rep["footprint_audit"]
    unbanded = [r["kernel"] for r in fp
                if not r["footprint_model_validated"]]
    # same >= 8 floor as the invariance audit: every streamed fold
    # kernel (solo + fused) must re-prove the memory oracle per round
    if unbanded or len(fp) < 8:
        raise RuntimeError(
            f"footprint audit regression: {len(fp)} streamed jobs "
            f"audited, out-of-band={unbanded}")
    merge_rep = run(["--merge"], "--merge")
    ma = merge_rep["merge_audit"]
    unmerged = [r["kernel"] for r in ma if not r["merge_validated"]]
    # the sharded-steal leg of the same audit: a boundary block folded
    # through two workers' ledgers must commit exactly once (duplicate
    # rejected first-commit-wins) and merge to the cold bytes — the
    # avenir-shard dedup contract, 8/8 every round
    undeduped = [r["kernel"] for r in ma
                 if not r.get("shard_dedup_validated")]
    if undeduped:
        raise RuntimeError(
            f"sharded-steal dedup audit regression: a redundantly "
            f"folded block double-committed or drifted for {undeduped}")
    # same >= 8 floor: every streamed fold kernel (solo + fused) must
    # re-prove its shard-merge + checkpoint-resume byte-identity per
    # round — the standing gate the resumable-scan and multi-host
    # streaming work build on
    if unmerged or len(ma) < 8:
        raise RuntimeError(
            f"shard-merge audit regression: {len(ma)} streamed kernels "
            f"audited, drifted={unmerged}")
    # the delta-scan driver's leg of the same audit: append a tail to a
    # prefix corpus, run the real incremental driver (with a mid-delta
    # kill + resume), assert byte-identity vs the cold full scan — 8/8
    # incremental_validated every round
    unincr = [r["kernel"] for r in ma
              if not r.get("incremental_validated")]
    if unincr:
        raise RuntimeError(
            f"incremental-scan audit regression: append/resume output "
            f"drifted for {unincr}")
    # protocol leg (graftlint-proto): every registered shared-
    # filesystem commit site, hard-killed at before-rename and
    # after-rename, must recover byte-identical with no stranded tmp —
    # the atomic-publish discipline the fleet/ledger/spool/checkpoint
    # protocols all stand on, >= 10 sites every round
    proto_rep = run(["--proto"], "--proto")
    pa = proto_rep["proto_audit"]
    uncommitted = [r["site"] for r in pa
                   if not r["commit_point_validated"]]
    if uncommitted or len(pa) < 10:
        raise RuntimeError(
            f"commit-point audit regression: {len(pa)} commit sites "
            f"audited, failed={uncommitted}")
    # race leg (graftlint-race): every registered interleave site,
    # two real actor subprocesses stepped through the sched_point
    # schedule space (exhaustive-to-depth + seeded), must hold
    # exactly-one-winner / conservation / solo byte-identity under
    # EVERY schedule — the cross-process contract the crash audit
    # can't see, >= 8 sites every round, per-site schedule counts
    # recorded so a silently shrunken schedule space is visible
    race_rep = run(["--race"], "--race")
    ra = race_rep["race_audit"]
    losable = [r["site"] for r in ra if not r["interleaving_validated"]]
    if losable or len(ra) < 8:
        raise RuntimeError(
            f"interleaving audit regression: {len(ra)} interleave "
            f"sites audited, failed={losable}")
    race_schedules = {r["site"]: sum(r["schedules"].values())
                      for r in ra}
    if min(race_schedules.values()) < 8:
        raise RuntimeError(
            f"interleaving audit regression: schedule space shrank "
            f"below 8 per site: {race_schedules}")
    # keys leg (graftlint-keys): every registered cache-key site,
    # each registered input dimension perturbed one at a time over a
    # warm cache, must hold the key's contract — affecting moves the
    # key with warm serve == cold recompute, neutral warm-hits
    # byte-identically, a foreign format_version stamp goes cold —
    # >= 10 sites every round, per-site perturbation counts recorded
    # so a silently shrunken dimension set is visible
    keys_rep = run(["--keys"], "--keys")
    ka = keys_rep["key_audit"]
    stale = [r["site"] for r in ka if not r["key_validated"]]
    if stale or len(ka) < 10:
        raise RuntimeError(
            f"key-perturbation audit regression: {len(ka)} key sites "
            f"audited, failed={stale}")
    key_perturbations = {r["site"]: sum(r["perturbations"].values())
                         for r in ka}
    if min(key_perturbations.values()) < 2:
        raise RuntimeError(
            f"key-perturbation audit regression: dimension set shrank "
            f"below 2 per site: {key_perturbations}")
    # span-coverage leg (avenir-trace): every registered stream entry,
    # run under a captured recorder, must emit the mandatory span set
    # (read/parse/fold/finish) — an instrumentation point lost in a
    # refactor fails the bench this round, not the next profiling
    # session. Same >= 8 floor as the other stream-entry legs.
    from avenir_tpu.obs.coverage import audit_span_coverage

    cov = audit_span_coverage()
    blind = [r["kernel"] for r in cov if not r["span_coverage_validated"]]
    if blind or len(cov) < 8:
        raise RuntimeError(
            f"span-coverage audit regression: {len(cov)} stream entries "
            f"audited, blind={blind}")
    # re-derive the admission oracle and pin it next to the scale
    # records so the job-server work consumes a fresh artifact, not a
    # stale hand-written one
    from avenir_tpu.analysis.mem import memory_manifest

    manifest = memory_manifest()
    manifest["footprint_audit"] = fp
    with open(os.path.join(root, "MEMORY_MANIFEST.json"), "w") as fh:
        json.dump(manifest, fh, indent=1)
    return {"files": ast_rep["files_scanned"], "findings": 0,
            "allowlisted": ast_rep["suppressed"],
            "ir_findings": 0,
            "payload_families_validated": len(audit),
            "flow_findings": 0,
            "flow_allowlisted": flow_rep["suppressed"],
            "stream_kernels_validated": len(inv),
            "mem_findings": 0,
            "mem_allowlisted": mem_rep["suppressed"],
            "footprint_jobs_validated": len(fp),
            "merge_findings": 0,
            "merge_allowlisted": merge_rep["suppressed"],
            "merge_kernels_validated": len(ma),
            "incremental_kernels_validated": len(ma) - len(unincr),
            "shard_dedup_validated": len(ma) - len(undeduped),
            "proto_findings": 0,
            "proto_allowlisted": proto_rep["suppressed"],
            "commit_points_validated": len(pa),
            "race_findings": 0,
            "race_allowlisted": race_rep["suppressed"],
            "interleave_sites_validated": len(ra),
            "race_schedules_per_site": race_schedules,
            "keys_findings": 0,
            "keys_allowlisted": keys_rep["suppressed"],
            "key_sites_validated": len(ka),
            "key_perturbations_per_site": key_perturbations,
            "span_coverage_validated": len(cov),
            "memory_manifest": "MEMORY_MANIFEST.json"}


def miner_tripwire(rows: int = 20_000) -> dict:
    """Run both streamed miners over `rows` synthetic transactions and
    return their throughput counters; raises if either job comes back
    without a non-null Basic:Records (the VERDICT Weak-#3 regression).
    Also asserts the GSP support kernel's jit compile count stayed at its
    shape-bucket bound — the runtime cross-check that keeps graftlint's
    recompile-hazard rule honest."""
    import os
    import shutil
    import numpy as np
    from avenir_tpu.runner import run_job

    d = tempfile.mkdtemp(prefix="avenir_miner_tripwire_")
    try:
        path = os.path.join(d, "seq.csv")
        rng = np.random.default_rng(12)
        states = ["L", "M", "H"]
        with open(path, "w") as fh:
            for i in range(rows):
                up = i % 2 == 0
                s, toks = 1, []
                for _ in range(6):
                    p = [0.1, 0.3, 0.6] if up else [0.6, 0.3, 0.1]
                    s = int(np.clip(s + rng.choice([-1, 0, 1], p=p), 0, 2))
                    toks.append(states[s])
                fh.write(f"c{i},{'T' if up else 'F'},"
                         + ",".join(toks) + "\n")

        out = {}
        jobs = [
            ("frequentItemsApriori",
             {"fia.support.threshold": "0.3", "fia.item.set.length": "2",
              "fia.skip.field.count": "2", "fia.stream.block.size.mb": "1"}),
            ("candidateGenerationWithSelfJoin",
             {"cgs.support.threshold": "0.3", "cgs.item.set.length": "2",
              "cgs.skip.field.count": "2", "cgs.stream.block.size.mb": "1"}),
        ]
        for job, conf in jobs:
            res = run_job(job, conf, [path], os.path.join(d, job))
            recs = res.counters.get("Basic:Records")
            if recs is None or int(recs) != rows:
                raise RuntimeError(
                    f"{job} lost its throughput counter: "
                    f"Basic:Records={recs!r} (expected {rows}) — the "
                    f"streamed miners are untripwired")
            out[job] = {"rows": int(recs),
                        "rows_per_sec": res.counters.get("Basic:RowsPerSec")}
        from avenir_tpu.models.sequence import (_subseq_fold_kernel,
                                                _subseq_support_kernel)
        from avenir_tpu.utils.metrics import jit_cache_size

        compiles = (jit_cache_size(_subseq_support_kernel)
                    + jit_cache_size(_subseq_fold_kernel))
        # pow2-bucketed block/candidate axes keep distinct compiled shapes
        # logarithmic; a per-block recompile would blow far past this
        if compiles > 16:
            raise RuntimeError(
                f"GSP support kernel compiled {compiles} variants for one "
                f"small corpus — a recompile hazard the static rule missed")
        out["gsp_kernel_compiles"] = compiles

        # (c) encoded-block replay must actually be EXERCISED: per-k
        # re-scans of an unchanged corpus replay the pass-1 spill cache
        # (a fraction of the CSV bytes) instead of re-parsing. A silent
        # fallback to the re-parse path would still be correct — and
        # would quietly give back the per-k scan savings, so it fails
        # the bench here.
        from avenir_tpu.models.association import (FrequentItemsApriori,
                                                   StreamingTransactionSource)

        src = StreamingTransactionSource([path], skip_field_count=2,
                                         block_bytes=1 << 20)
        FrequentItemsApriori(0.3, 2).mine_stream(src)
        replays = src.cache_replays
        if replays < 1:
            raise RuntimeError(
                "miner per-k pass did not replay the encoded-block cache "
                "(fell back to CSV re-parse)")
        cache_bytes, csv_bytes = src.cache_nbytes, os.path.getsize(path)
        if cache_bytes >= csv_bytes:
            raise RuntimeError(
                f"encoded-block cache ({cache_bytes}B) is not smaller "
                f"than the CSV it replaces ({csv_bytes}B)")
        src.close()
        out["miner_cache"] = {"replays": replays,
                              "cache_bytes": cache_bytes,
                              "csv_bytes": csv_bytes}
        return out
    finally:
        shutil.rmtree(d, ignore_errors=True)


def incremental_tripwire(rows: int = 10_000_000, floor: float = 5.0) -> dict:
    """Delta-scan perf tripwire: after a ~1% append, run_incremental
    must reproduce the cold full re-scan's bytes while beating its wall
    time by `floor`x — the O(delta) claim of the incremental driver,
    re-proven at proxy scale every bench round (tools/stream_scale_check
    --incremental records the 10M/100M-row anchor; the merge auditor's
    incremental leg proves byte-identity on every family).

    Method: one cold pass through the driver seeds the fold-state
    checkpoint + block fingerprints (and warms the jit caches for both
    timed sides), then a 1% append, then the timed cold re-scan
    (run_job) vs the timed incremental refresh (run_incremental)."""
    import os
    import shutil
    import time

    from avenir_tpu.data import churn_schema, generate_churn
    from avenir_tpu.runner import run_incremental, run_job

    d = tempfile.mkdtemp(prefix="avenir_incr_tripwire_")
    try:
        blob = generate_churn(100_000, seed=21, as_csv=True)
        csv = os.path.join(d, "churn.csv")
        with open(csv, "w") as fh:
            for _ in range(max(rows // 100_000, 1)):
                fh.write(blob)
        schema = os.path.join(d, "churn.json")
        churn_schema().save(schema)
        conf = {"mut.feature.schema.file.path": schema,
                "mut.mutual.info.score.algorithms":
                    "mutual.info.maximization"}
        state = os.path.join(d, "state")
        run_incremental("mutualInformation", conf, [csv],
                        os.path.join(d, "out_seed.txt"), state_dir=state)
        appended = max(rows // 100, 1_000)
        with open(csv, "a") as fh:
            fh.write(generate_churn(appended, seed=22, as_csv=True))
        t0 = time.perf_counter()
        cold = run_job("mutualInformation", conf, [csv],
                       os.path.join(d, "out_cold.txt"))
        t_cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        incr = run_incremental("mutualInformation", conf, [csv],
                               os.path.join(d, "out_incr.txt"),
                               state_dir=state)
        t_incr = time.perf_counter() - t0
        with open(cold.outputs[0], "rb") as fa, \
                open(incr.outputs[0], "rb") as fb:
            if fa.read() != fb.read():
                raise RuntimeError(
                    "incremental refresh output drifted from the cold "
                    "full re-scan — the delta fold is wrong, not slow")
        if incr.counters.get("Resume:SkippedBytes", 0) <= 0 \
                or incr.counters.get("Cache:HitBlocks", 0) <= 0:
            raise RuntimeError(
                "incremental refresh did not restore a checkpoint / skip "
                "the unchanged prefix (it re-scanned cold)")
        speedup = t_cold / max(t_incr, 1e-9)
        if speedup < floor:
            raise RuntimeError(
                f"incremental refresh only {speedup:.2f}x faster than "
                f"the cold re-scan (floor {floor}x) — the O(delta) "
                f"append path regressed")
        return {"speedup": round(speedup, 2), "floor": floor,
                "t_cold_s": round(t_cold, 2),
                "t_incremental_s": round(t_incr, 2),
                "rows": rows, "appended_rows": appended,
                "skipped_bytes": int(incr.counters["Resume:SkippedBytes"]),
                "delta_blocks": int(incr.counters["Cache:DeltaBlocks"]),
                "outputs_byte_identical": True}
    finally:
        shutil.rmtree(d, ignore_errors=True)


def sidecar_tripwire(rows: int = 10_000_000, floor: float = 2.0) -> dict:
    """Columnar-sidecar perf tripwire: after one pass packs the sidecar,
    the fused churn trio's repeat scan must beat the cold CSV scan by
    `floor`x with byte-identical outputs, >= 1 Sidecar:HitBlocks on
    EVERY job, and ZERO `stream.parse` spans in a trace capture of the
    warm pass — then the other three repeat-scan surfaces (sharded
    workers, the incremental driver's cold seed, a job-server batch
    that must also PIN the sidecar under its warm-store budget) each
    re-prove the same parse-free replay over the same packed corpus.

    Method: the pack pass runs first (it also warms the jit caches for
    both timed sides at the real block shapes), then the timed cold
    scan (sidecar killed via conf) vs the timed warm replay."""
    import os
    import shutil
    import time

    from avenir_tpu.data import churn_schema, generate_churn
    from avenir_tpu.dist import run_sharded
    from avenir_tpu.native import sidecar as _sc
    from avenir_tpu.obs import trace
    from avenir_tpu.runner import run_incremental, run_shared

    d = tempfile.mkdtemp(prefix="avenir_sidecar_tripwire_")
    try:
        blob = generate_churn(100_000, seed=17, as_csv=True)
        csv = os.path.join(d, "churn.csv")
        with open(csv, "w") as fh:
            for _ in range(max(rows // 100_000, 1)):
                fh.write(blob)
        schema = os.path.join(d, "churn.json")
        churn_schema().save(schema)
        # block size scaled so the corpus tiles into ~12 blocks: the
        # sharded leg's planner only snaps its cuts onto verified
        # sidecar offsets when there are >= procs*factor (2*4) of them
        size_mb = os.path.getsize(csv) / (1 << 20)
        block = f"{max(size_mb / 12.0, 0.05):.3f}"
        scdir = os.path.join(d, "sidecar")
        trio = [("bayesianDistr", "bad"), ("mutualInformation", "mut"),
                ("fisherDiscriminant", "fid")]

        def conf(p, **extra):
            c = {f"{p}.feature.schema.file.path": schema,
                 f"{p}.stream.block.size.mb": block,
                 f"{p}.stream.sidecar.dir": scdir}
            if p == "mut":
                c["mut.mutual.info.score.algorithms"] = \
                    "mutual.info.maximization"
            c.update({f"{p}.{k}": v for k, v in extra.items()})
            return c

        def specs(tag, **extra):
            return [(j, conf(p, **extra), os.path.join(d, f"{tag}_{p}"))
                    for j, p in trio]

        def blobs_of(res):
            out = []
            for pa in sorted(res.outputs):
                with open(pa, "rb") as fh:
                    out.append(fh.read())
            return out

        import contextlib

        try:
            from bench import _host_core_lock
        except ImportError:                      # bench.py not importable
            _host_core_lock = contextlib.nullcontext
        with _host_core_lock():
            pack = run_shared(specs("pack"), [csv])
            # single-shot A/B is flappy on a steal-throttled dev box
            # (the autotune tripwire's lesson): time each side best-of-
            # two INTERLEAVED so one stolen scheduler slice cannot sink
            # the ratio — the min is the honest uncontended wall
            t_colds, t_warms = [], []
            colds, warms, recs = [], [], []
            for rnd in ("", "2"):
                t0 = time.perf_counter()
                colds.append(run_shared(
                    specs(f"cold{rnd}", **{"stream.sidecar": "false"}),
                    [csv]))
                t_colds.append(time.perf_counter() - t0)
                t0 = time.perf_counter()
                with trace.capture() as rec:
                    warms.append(run_shared(specs(f"warm{rnd}"), [csv]))
                t_warms.append(time.perf_counter() - t0)
                recs.append(rec)
            cold, warm = colds[0], warms[0]
            t_cold, t_warm = min(t_colds), min(t_warms)
        for j, _p in trio:
            blobs = blobs_of(pack[j])
            if any(blobs_of(res[j]) != blobs for res in colds + warms):
                raise RuntimeError(
                    f"sidecar replay output of {j} drifted from the cold "
                    f"CSV scan — the replay is wrong, not slow")
            for w in warms:
                if w[j].counters.get("Sidecar:HitBlocks", 0) < 1 \
                        or w[j].counters.get("Sidecar:DeltaBlocks",
                                             0) != 0:
                    raise RuntimeError(
                        f"warm pass of {j} did not replay the sidecar: "
                        f"{w[j].counters}")
        spans = [s for r in recs for s in r.spans()]
        parsed = sum(1 for s in spans if s.name == "stream.parse")
        replayed = sum(1 for s in spans
                       if s.name == "stream.sidecar.replay")
        if parsed or replayed < 1:
            raise RuntimeError(
                f"warm fused pass parsed {parsed} block(s) / replayed "
                f"{replayed} — the repeat scan is not parse-free")
        speedup = t_cold / max(t_warm, 1e-9)
        if speedup < floor:
            raise RuntimeError(
                f"sidecar repeat scan only {speedup:.2f}x faster than "
                f"the cold CSV scan (floor {floor}x) — the parse-free "
                f"replay regressed")
        mi_cold = blobs_of(cold["mutualInformation"])
        # sharded leg: the planner snaps onto verified sidecar offsets,
        # so every claimed range replays whole — the workers' own trace
        # captures ship the span counts home through the stats files
        shard = run_sharded("mutualInformation", conf("mut"), [csv],
                            os.path.join(d, "shard_out.txt"), procs=2)
        if blobs_of(shard) != mi_cold:
            raise RuntimeError("sharded sidecar replay output drifted")
        if shard.counters.get("Shard:ParseSpans", 1) != 0 \
                or shard.counters.get("Shard:ReplaySpans", 0) < 1 \
                or shard.counters.get("Sidecar:HitBlocks", 0) < 1:
            raise RuntimeError(
                f"sharded workers parsed on the happy replay path: "
                f"{shard.counters}")
        # incremental leg: a COLD seed over the packed corpus replays
        # every block (the delta feed rides the sidecar too)
        with trace.capture() as rec_i:
            incr = run_incremental(
                "mutualInformation", conf("mut"), [csv],
                os.path.join(d, "incr_out.txt"),
                state_dir=os.path.join(d, "incr_state"))
        if blobs_of(incr) != mi_cold:
            raise RuntimeError("incremental sidecar replay output drifted")
        i_parsed = sum(1 for s in rec_i.spans()
                       if s.name == "stream.parse")
        if i_parsed or incr.counters.get("Sidecar:HitBlocks", 0) < 1:
            raise RuntimeError(
                f"incremental cold seed parsed {i_parsed} block(s) over "
                f"a fully packed corpus: {incr.counters}")
        # warm-store leg: a served batch replays the sidecar AND pins it
        # under the server's byte budget (eviction = rmtree, by design)
        from avenir_tpu.server import JobRequest, JobServer

        with trace.capture() as rec_s:
            with JobServer(workers=1,
                           state_root=os.path.join(d, "srv_state")) as srv:
                tickets = [
                    srv.submit(JobRequest(j, conf(p), [csv],
                                          os.path.join(d, f"srv_{p}")))
                    for j, p in trio]
                served = {j: t.result(timeout=3600)
                          for (j, _p), t in zip(trio, tickets)}
                pinned = srv.warm.stats()["pinned_sources"]
        s_parsed = sum(1 for s in rec_s.spans()
                       if s.name == "stream.parse")
        for j, _p in trio:
            if blobs_of(served[j]) != blobs_of(cold[j]):
                raise RuntimeError(f"served sidecar replay of {j} drifted")
            if served[j].counters.get("Sidecar:HitBlocks", 0) < 1:
                raise RuntimeError(
                    f"served batch of {j} did not replay the sidecar: "
                    f"{served[j].counters}")
        if s_parsed or pinned < 1:
            raise RuntimeError(
                f"served batch parsed {s_parsed} block(s) / pinned "
                f"{pinned} sidecar(s) — the warm store is not the "
                f"sidecar's landlord")
        # the sidecar must OUTLIVE the server: shutdown drops pins, not
        # the on-disk cache (only a budget eviction rmtrees)
        sc_bytes = sum(_sc.sidecar_nbytes(os.path.join(scdir, n))
                       for n in os.listdir(scdir))
        if sc_bytes <= 0:
            raise RuntimeError(
                "the packed sidecar vanished after the server batch — "
                "shutdown must drop pins, not delete the disk cache")
        return {"speedup": round(speedup, 2), "floor": floor,
                "t_cold_s": round(t_cold, 2),
                "t_warm_s": round(t_warm, 2),
                "rows": rows, "block_mb": float(block),
                "sidecar_bytes": sc_bytes,
                "hit_blocks": {
                    j: int(warm[j].counters["Sidecar:HitBlocks"])
                    for j, _p in trio},
                "warm_parse_spans": parsed,
                "warm_replay_spans": replayed,
                "shard_parse_spans": int(
                    shard.counters["Shard:ParseSpans"]),
                "incremental_parse_spans": i_parsed,
                "server_parse_spans": s_parsed,
                "server_pinned_sidecars": int(pinned),
                "outputs_byte_identical": True}
    finally:
        shutil.rmtree(d, ignore_errors=True)


def shared_scan_tripwire(rows: int = 30_000) -> dict:
    """Exercise the scan-sharing executor every bench round: run
    nb + mi + discriminant over one churn corpus sequentially (three
    one-job-one-scan passes) and fused (ONE SharedScan pass), assert the
    outputs byte-identical, the fused wall time at least FLOOR x faster,
    and the NB fold's jit compile count still inside its shape-bucket
    bound on the shared path (fan-out must not add compiled variants —
    the sinks see the same chunk shapes the solo job saw)."""
    import os
    import shutil
    import time

    from avenir_tpu.data import churn_schema, generate_churn
    from avenir_tpu.runner import run_job, run_shared

    FLOOR = 1.3          # measured ~2x at tripwire scale on 1 CPU core
    d = tempfile.mkdtemp(prefix="avenir_shared_scan_")
    try:
        csv = os.path.join(d, "churn.csv")
        with open(csv, "w") as fh:
            fh.write(generate_churn(rows, seed=11, as_csv=True))
        schema = os.path.join(d, "churn.json")
        churn_schema().save(schema)
        conf = lambda p: {f"{p}.feature.schema.file.path": schema,  # noqa: E731
                          f"{p}.stream.block.size.mb": "0.1"}
        mi_conf = {**conf("mut"),
                   "mut.mutual.info.score.algorithms":
                       "mutual.info.maximization"}
        specs = [("bayesianDistr", conf("bad"), "nb"),
                 ("mutualInformation", mi_conf, "mi"),
                 ("fisherDiscriminant", conf("fid"), "fid")]
        # warmup at tiny scale so one-time jit compiles price neither side
        warm = os.path.join(d, "warm.csv")
        with open(warm, "w") as fh:
            fh.write(generate_churn(500, seed=12, as_csv=True))
        run_shared([(j, c, os.path.join(d, f"warm_{o}")) for j, c, o in specs],
                   [warm])
        # BOTH timed passes run under bench.py's host-core lock: a
        # concurrent drain landing on one side but not the other would
        # fake a speedup regression — the exact artifact class the r05
        # overlap_eff>1.0 lesson is about
        import contextlib

        try:
            from bench import _host_core_lock
        except ImportError:                      # bench.py not importable
            _host_core_lock = contextlib.nullcontext
        with _host_core_lock():
            t0 = time.perf_counter()
            seq_res = {j: run_job(j, c, [csv], os.path.join(d, f"seq_{o}"))
                       for j, c, o in specs}
            t_seq = time.perf_counter() - t0
            t0 = time.perf_counter()
            fused_res = run_shared(
                [(j, c, os.path.join(d, f"fus_{o}")) for j, c, o in specs],
                [csv])
            t_fused = time.perf_counter() - t0
        for j, _c, _o in specs:
            for a, b in zip(sorted(seq_res[j].outputs),
                            sorted(fused_res[j].outputs)):
                with open(a, "rb") as fa, open(b, "rb") as fb:
                    if fa.read() != fb.read():
                        raise RuntimeError(
                            f"shared-scan output of {j} differs from the "
                            f"one-job-one-scan output ({a} vs {b})")
        speedup = t_seq / max(t_fused, 1e-9)
        if speedup < FLOOR:
            raise RuntimeError(
                f"fused shared scan only {speedup:.2f}x faster than "
                f"sequential (floor {FLOOR}x) — scan sharing regressed")
        from avenir_tpu.models.naive_bayes import _fold_batch_kernel
        from avenir_tpu.utils.metrics import jit_cache_size

        nb_compiles = jit_cache_size(_fold_batch_kernel)
        # chunk shapes are corpus-derived: full blocks + one tail per
        # corpus (warmup, tripwire) x two dtype modes is far under this
        if nb_compiles > 12:
            raise RuntimeError(
                f"NB fold compiled {nb_compiles} variants on the shared "
                f"path — fan-out is defeating the compile cache")
        return {"speedup": round(speedup, 2), "floor": FLOOR,
                "t_sequential_s": round(t_seq, 2),
                "t_fused_s": round(t_fused, 2),
                "nb_fold_compiles": nb_compiles,
                "outputs_byte_identical": True}
    finally:
        shutil.rmtree(d, ignore_errors=True)


def obs_tripwire(rows: int = 10_000_000, ceiling: float = 1.03) -> dict:
    """Telemetry overhead + coverage tripwire: the fused churn trio
    (nb + mi + discriminant through ONE SharedScan) runs once with
    tracing OFF and once with tracing ON under a captured recorder; the
    traced run must stay within `ceiling`x of the untraced wall clock,
    the artifacts must be byte-identical, and the captured trace must
    hold >= 1 read/parse span per chunk plus >= chunk-count fold spans
    for EVERY job in the batch — always-on telemetry that either slowed
    the hot path or went blind fails the bench, not the next profiling
    session."""
    import os
    import shutil
    import time
    from collections import Counter

    from avenir_tpu.data import churn_schema, generate_churn
    from avenir_tpu.obs import trace
    from avenir_tpu.runner import run_shared

    d = tempfile.mkdtemp(prefix="avenir_obs_tripwire_")
    try:
        csv = os.path.join(d, "churn.csv")
        blob = generate_churn(100_000, seed=21, as_csv=True)
        with open(csv, "w") as fh:
            for _ in range(max(rows // 100_000, 1)):
                fh.write(blob)
        schema = os.path.join(d, "churn.json")
        churn_schema().save(schema)
        conf = lambda p: {f"{p}.feature.schema.file.path": schema,  # noqa: E731
                          f"{p}.stream.block.size.mb": "8"}
        mi_conf = {**conf("mut"),
                   "mut.mutual.info.score.algorithms":
                       "mutual.info.maximization"}
        specs = [("bayesianDistr", conf("bad"), "nb"),
                 ("mutualInformation", mi_conf, "mi"),
                 ("fisherDiscriminant", conf("fid"), "fid")]
        jobs = [j for j, _c, _o in specs]
        # warmup: one untimed pass over the REAL corpus, so jit compiles
        # for the actual chunk shapes and the page-cache fill price
        # neither timed side (a tiny-corpus warmup leaves the first
        # timed run paying the big-chunk compiles — a 3% bound cannot
        # survive that)
        run_shared([(j, c, os.path.join(d, f"warm_{o}"))
                    for j, c, o in specs], [csv])
        import contextlib

        try:
            from bench import _host_core_lock
        except ImportError:                      # bench.py not importable
            _host_core_lock = contextlib.nullcontext
        with _host_core_lock():
            prev = trace.set_enabled(False)
            try:
                t0 = time.perf_counter()
                off_res = run_shared(
                    [(j, c, os.path.join(d, f"off_{o}"))
                     for j, c, o in specs], [csv])
                t_off = time.perf_counter() - t0
            finally:
                trace.set_enabled(prev)
            with trace.capture() as rec:
                t0 = time.perf_counter()
                on_res = run_shared(
                    [(j, c, os.path.join(d, f"on_{o}"))
                     for j, c, o in specs], [csv])
                t_on = time.perf_counter() - t0
        for j in jobs:
            for a, b in zip(sorted(off_res[j].outputs),
                            sorted(on_res[j].outputs)):
                with open(a, "rb") as fa, open(b, "rb") as fb:
                    if fa.read() != fb.read():
                        raise RuntimeError(
                            f"tracing changed the output of {j} "
                            f"({b} vs {a}) — instrumentation must be "
                            f"observation-only")
        spans = rec.spans()
        chunks = next((int(sp.attrs["chunks"]) for sp in spans
                       if sp.name == "job.dispatch"), 0)
        names = Counter(sp.name for sp in spans)
        folds = Counter(sp.attrs.get("sink") for sp in spans
                        if sp.name == "stream.fold" and sp.attrs)
        if chunks < 1:
            raise RuntimeError("traced fused run recorded no job.dispatch "
                               "span — the scan executor went blind")
        blind = [j for j in jobs if folds.get(j, 0) < chunks]
        if (blind or names["stream.read"] < chunks
                or names["stream.parse"] < chunks):
            raise RuntimeError(
                f"trace coverage hole: {chunks} chunks scanned but "
                f"read={names['stream.read']} parse={names['stream.parse']} "
                f"folds={dict(folds)} (jobs missing folds: {blind})")
        overhead = t_on / max(t_off, 1e-9)
        if overhead > ceiling:
            raise RuntimeError(
                f"tracing overhead {overhead:.3f}x exceeds the "
                f"{ceiling}x ceiling (off {t_off:.2f}s, on {t_on:.2f}s) "
                f"— always-on telemetry is no longer cheap")
        return {"rows": rows, "ceiling": ceiling,
                "overhead_ratio": round(overhead, 4),
                "t_off_s": round(t_off, 2), "t_on_s": round(t_on, 2),
                "chunks": chunks,
                "spans": len(spans),
                "spans_dropped": rec.dropped,
                "outputs_byte_identical": True}
    finally:
        shutil.rmtree(d, ignore_errors=True)


def autotune_tripwire(rows: int = 10_000_000, floor: float = 1.15) -> dict:
    """Close-the-loop perf tripwire: the fused churn trio runs once
    under the STATIC default knobs (64MB blocks, depth-2 prefetch) with
    the autotuner recording its telemetry, then once under the knob
    triple the tuner chose from that telemetry — the tuned pass must
    beat the static one by `floor`x wall clock, the artifacts must be
    byte-identical (chunk invariance is the license to tune at all),
    and the chosen knobs are logged in the result so every round's
    record says WHAT the tuner did, not just that it won.

    Protocol: each side gets its own untimed warmup pass at its own
    knob values (chunk shapes differ between the sides, so jit compiles
    and page-cache fill must price neither), then the two timed passes
    run under the host-core lock back to back."""
    import os
    import shutil
    import time

    from avenir_tpu.data import churn_schema, generate_churn
    from avenir_tpu.runner import run_shared
    from avenir_tpu.tune import ProfileStore, corpus_digest

    d = tempfile.mkdtemp(prefix="avenir_autotune_tripwire_")
    try:
        csv = os.path.join(d, "churn.csv")
        blob = generate_churn(100_000, seed=41, as_csv=True)
        with open(csv, "w") as fh:
            for _ in range(max(rows // 100_000, 1)):
                fh.write(blob)
        schema = os.path.join(d, "churn.json")
        churn_schema().save(schema)
        tune_dir = os.path.join(d, "tune")
        # static defaults on purpose: no stream.* sizing keys, so the
        # untuned side runs exactly what an unconfigured job runs
        conf = lambda p: {f"{p}.feature.schema.file.path": schema}  # noqa: E731
        mi_conf = {**conf("mut"),
                   "mut.mutual.info.score.algorithms":
                       "mutual.info.maximization"}
        specs = [("bayesianDistr", conf("bad"), "nb"),
                 ("mutualInformation", mi_conf, "mi"),
                 ("fisherDiscriminant", conf("fid"), "fid")]
        jobs = [j for j, _c, _o in specs]
        prefixes = {"bayesianDistr": "bad", "mutualInformation": "mut",
                    "fisherDiscriminant": "fid"}
        # the autotune opt-in rides ONLY the timed static pass: its
        # recording/choosing is the tuner input, while the warmups and
        # the tuned side must not re-decide mid-measurement
        tuning_overlay = {
            j: {f"{prefixes[j]}.stream.autotune": "true",
                f"{prefixes[j]}.stream.autotune.dir": tune_dir}
            for j in jobs}

        def fused(tag, extra=None):
            return run_shared(
                [(j, {**c, **extra[j]} if extra else c,
                  os.path.join(d, f"{tag}_{o}")) for j, c, o in specs],
                [csv])

        import contextlib

        try:
            from bench import _host_core_lock
        except ImportError:                      # bench.py not importable
            _host_core_lock = contextlib.nullcontext

        # side A warmup (untuned: must not pre-seed the profile store)
        # + timed pass: static defaults, telemetry recorded, knobs
        # chosen into the profile store
        fused("warm_static")
        with _host_core_lock():
            t0 = time.perf_counter()
            static_res = fused("static", tuning_overlay)
            t_static = time.perf_counter() - t0
        profile_job = "+".join(sorted(jobs))
        prof = ProfileStore(tune_dir).load(profile_job,
                                           corpus_digest([csv]))
        chosen = dict((prof or {}).get("knobs") or {})
        reasons = list((prof or {}).get("reasons") or [])
        if not chosen:
            raise RuntimeError(
                "autotuner chose no knobs from the static pass's "
                "telemetry — the signal->policy leg is dead "
                f"(profile={prof})")
        # side B: the chosen triple pinned as explicit conf keys (the
        # second autotuned pass would apply exactly these — pinning
        # them keeps the timed side from ALSO re-deciding mid-flight)
        tuned_overlay = {
            j: {f"{prefixes[j]}.{k}": f"{v:g}" for k, v in chosen.items()}
            for j in jobs}
        # timed A/B, interleaved best-of-two per side: single-shot
        # timing on a shared host confounds the comparison with page
        # cache / allocator warming (whichever side runs LAST looks
        # faster) and scheduler jitter; alternating static and tuned
        # passes and taking each side's min cancels the monotone drift
        # and the worst of the noise. The extra static pass runs
        # UNTUNED so it cannot re-record into the profile store.
        fused("warm_tuned", tuned_overlay)
        with _host_core_lock():
            t0 = time.perf_counter()
            tuned_res = fused("tuned", tuned_overlay)
            t_tuned = time.perf_counter() - t0
            t0 = time.perf_counter()
            fused("static2")
            t_static = min(t_static, time.perf_counter() - t0)
            t0 = time.perf_counter()
            fused("tuned2", tuned_overlay)
            t_tuned = min(t_tuned, time.perf_counter() - t0)
        for j in jobs:
            if len(static_res[j].outputs) != len(tuned_res[j].outputs):
                raise RuntimeError(
                    f"tuned config changed the OUTPUT SET of {j}: "
                    f"{len(tuned_res[j].outputs)} files vs "
                    f"{len(static_res[j].outputs)}")
            for a, b in zip(sorted(static_res[j].outputs),
                            sorted(tuned_res[j].outputs)):
                with open(a, "rb") as fa, open(b, "rb") as fb:
                    if fa.read() != fb.read():
                        raise RuntimeError(
                            f"tuned config changed the output of {j} "
                            f"({b} vs {a}) — the tuner may only change "
                            f"speed, never bytes")
        speedup = t_static / max(t_tuned, 1e-9)
        if speedup < floor:
            raise RuntimeError(
                f"tuned config only {speedup:.2f}x the static default "
                f"(floor {floor}x; static {t_static:.2f}s, tuned "
                f"{t_tuned:.2f}s, knobs {chosen}) — the telemetry->knob "
                f"loop stopped paying")
        return {"rows": rows, "floor": floor,
                "speedup": round(speedup, 2),
                "t_static_s": round(t_static, 2),
                "t_tuned_s": round(t_tuned, 2),
                "chosen_knobs": chosen,
                "reasons": reasons,
                "outputs_byte_identical": True}
    finally:
        shutil.rmtree(d, ignore_errors=True)


def server_load(churn: str, seq: str, schema: str) -> list:
    """The canonical 6-request / 3-tenant mixed-kind open-loop load —
    (tenant, job, conf, corpus, tag) rows — shared by
    :func:`server_tripwire` and the ``tools/stream_scale_check.py
    --server`` anchor child so the anchor always measures exactly the
    load the tripwire gates."""
    conf = lambda p: {f"{p}.feature.schema.file.path": schema}  # noqa: E731
    mi_conf = {**conf("mut"),
               "mut.mutual.info.score.algorithms":
                   "mutual.info.maximization"}
    fia_conf = {"fia.support.threshold": "0.3",
                "fia.item.set.length": "2",
                "fia.skip.field.count": "2"}
    mst_conf = {"mst.model.states": "L,M,H",
                "mst.class.label.field.ord": "1",
                "mst.skip.field.count": "2",
                "mst.class.labels": "T,F"}
    return [
        ("a", "bayesianDistr", conf("bad"), churn, "nb"),
        ("b", "mutualInformation", mi_conf, churn, "mi"),
        ("c", "fisherDiscriminant", conf("fid"), churn, "fid"),
        ("c", "markovStateTransitionModel", mst_conf, seq, "mst"),
        ("a", "frequentItemsApriori", fia_conf, seq, "fia_a"),
        ("b", "frequentItemsApriori", fia_conf, seq, "fia_b"),
    ]


def server_tripwire(rows: int = 10_000_000, floor: float = 1.5,
                    budget_mb: float = 3072.0,
                    slack_mb: float = 512.0) -> dict:
    """Resident job-server perf tripwire: a synthetic open-loop load —
    3 tenants, 6 requests, MIXED job kinds (three Dataset-fold churn
    profilers, two byte-fold sequence jobs, one exact-duplicate mining
    request) — served by the JobServer must beat one-job-at-a-time
    sequential execution by `floor`x in jobs/min. The server's wins are
    exactly the PR's claims: the churn trio batches into ONE SharedScan,
    the sequence jobs into another, the duplicate coalesces into a copy,
    and compiles stay warm across dispatches. Every served artifact must
    be byte-identical to its solo-runner twin, and the admission layer
    must have kept the process inside its byte budget: peak RSS SAMPLED
    DURING THE SERVED PHASE (analysis/mem's /proc sampler — the phase
    admission actually controls; the unbudgeted sequential twin runs
    after it) stays under budget + slack, and the admission
    bookkeeping's priced peak never exceeded the budget."""
    import os
    import shutil
    import time

    import numpy as np

    from avenir_tpu.data import churn_schema, generate_churn
    from avenir_tpu.runner import run_job
    from avenir_tpu.server import JobRequest, JobServer

    d = tempfile.mkdtemp(prefix="avenir_server_tripwire_")
    try:
        churn = os.path.join(d, "churn.csv")
        blob = generate_churn(100_000, seed=31, as_csv=True)
        with open(churn, "w") as fh:
            for _ in range(max(rows // 100_000, 1)):
                fh.write(blob)
        schema = os.path.join(d, "churn.json")
        churn_schema().save(schema)
        seq = os.path.join(d, "seq.csv")
        rng = np.random.default_rng(32)
        states = ["L", "M", "H"]
        lines = []
        for i in range(100_000):
            up = i % 2 == 0
            s, toks = 1, []
            for _ in range(6):
                p = [0.1, 0.3, 0.6] if up else [0.6, 0.3, 0.1]
                s = int(np.clip(s + rng.choice([-1, 0, 1], p=p), 0, 2))
                toks.append(states[s])
            lines.append(f"c{i},{'T' if up else 'F'}," + ",".join(toks))
        seq_blob = "\n".join(lines) + "\n"
        with open(seq, "w") as fh:
            for _ in range(max(rows // 100_000, 1)):
                fh.write(seq_blob)

        load = server_load(churn, seq, schema)
        # warmup at tiny scale so one-time jit compiles price neither side
        warm_churn = os.path.join(d, "warm_churn.csv")
        with open(warm_churn, "w") as fh:
            fh.write(generate_churn(500, seed=33, as_csv=True))
        warm_seq = os.path.join(d, "warm_seq.csv")
        with open(warm_seq, "w") as fh:
            fh.write("\n".join(lines[:500]) + "\n")
        for _t, job, cf, corpus, tag in load[:5]:
            warm_in = warm_churn if corpus == churn else warm_seq
            run_job(job, cf, [warm_in], os.path.join(d, f"warm_{tag}"))

        import contextlib

        try:
            from bench import _host_core_lock
        except ImportError:                      # bench.py not importable
            _host_core_lock = contextlib.nullcontext
        from avenir_tpu.analysis.mem import _RssSampler

        with _host_core_lock():
            # served phase FIRST, its RSS sampled in isolation: the
            # sequential twin is deliberately unbudgeted, so a process-
            # lifetime peak would assert the wrong phase
            server = JobServer(budget_bytes=int(budget_mb * (1 << 20)),
                               workers=2,
                               state_root=os.path.join(d, "state"))
            tickets = {tag: server.submit(JobRequest(
                           job, cf, [corpus], os.path.join(d, f"srv_{tag}"),
                           tenant=tenant))
                       for tenant, job, cf, corpus, tag in load}
            t0 = time.perf_counter()
            with _RssSampler() as sampler:
                server.start()
                server.drain(timeout=7200)
            t_srv = time.perf_counter() - t0
            served = {tag: t.result(timeout=60)
                      for tag, t in tickets.items()}
            stats = server.stats()
            server.shutdown()
            t0 = time.perf_counter()
            seq_res = {tag: run_job(job, cf, [corpus],
                                    os.path.join(d, f"seq_{tag}"))
                       for _t, job, cf, corpus, tag in load}
            t_seq = time.perf_counter() - t0
        for _tenant, _job, _cf, _corpus, tag in load:
            a, b = seq_res[tag].outputs, served[tag].outputs
            if len(a) != len(b):
                raise RuntimeError(
                    f"served {tag} wrote {len(b)} outputs, solo twin "
                    f"wrote {len(a)}")
            for pa, pb in zip(sorted(a), sorted(b)):
                with open(pa, "rb") as fa, open(pb, "rb") as fb:
                    if fa.read() != fb.read():
                        raise RuntimeError(
                            f"served artifact of {tag} differs from its "
                            f"solo-runner twin ({pb} vs {pa})")
        speedup = t_seq / max(t_srv, 1e-9)
        if speedup < floor:
            raise RuntimeError(
                f"served load only {speedup:.2f}x sequential jobs/min "
                f"(floor {floor}x) — batching/warm-state regressed")
        peak_rss = sampler.peak_rss / (1 << 20)
        if peak_rss > budget_mb + slack_mb:
            raise RuntimeError(
                f"measured peak RSS {peak_rss:.0f}MB during the served "
                f"phase exceeded the {budget_mb:.0f}MB admission budget "
                f"+ {slack_mb:.0f}MB slack — admission is not holding "
                f"the ceiling")
        if stats["peak_priced_bytes"] > budget_mb * (1 << 20):
            raise RuntimeError(
                f"admission let priced in-flight bytes "
                f"({stats['peak_priced_bytes']:.0f}) past the budget")
        waits = sorted(r.counters["Server:QueueWaitMs"]
                       for r in served.values())
        batched = max(r.counters["Server:BatchSize"]
                      for r in served.values())
        if batched < 2:
            raise RuntimeError(
                "no request was batched — the scheduler never formed a "
                "shared scan from 6 compatible submissions")
        return {"rows": rows, "requests": len(load), "floor": floor,
                "jobs_per_min_sequential": round(
                    len(load) / (t_seq / 60.0), 2),
                "jobs_per_min_served": round(len(load) / (t_srv / 60.0), 2),
                "speedup": round(speedup, 2),
                "p50_queue_wait_ms": round(waits[len(waits) // 2], 1),
                "p99_queue_wait_ms": round(waits[-1], 1),
                "max_batch_size": int(batched),
                "coalesced": int(stats["coalesced"]),
                "peak_rss_mb": round(peak_rss, 1),
                "budget_mb": budget_mb,
                "outputs_byte_identical": True}
    finally:
        shutil.rmtree(d, ignore_errors=True)


def host_parallel_capacity(n: int = 2, secs: float = 2.0) -> float:
    """Measured parallel speedup this box delivers to `n` CPU-bound
    PROCESSES vs one (busy-loop probe). On a real `n`-core host this is
    ~n; on a steal-throttled CI container it can be far less (1.41
    measured on the 2-vCPU dev box) — and no fleet can beat the box it
    runs on, so the fleet tripwire gates against THIS number, never a
    hardcoded ideal the hardware cannot express."""
    import multiprocessing as mp
    import time

    def burn(out) -> None:
        t0 = time.perf_counter()
        x = 0
        while time.perf_counter() - t0 < secs:
            x += 1
        out.value = x

    def run(k: int) -> int:
        vals = [mp.Value("q", 0) for _ in range(k)]
        procs = [mp.Process(target=burn, args=(v,)) for v in vals]
        for proc in procs:
            proc.start()
        for proc in procs:
            proc.join()
        return sum(v.value for v in vals)

    solo = run(1)
    return run(n) / max(solo, 1)


def fleet_tripwire(rows: int = 10_000_000, floor: float = 1.5,
                   budget_mb: float = 3072.0,
                   min_hit_rate: float = 0.6, rounds: int = 2,
                   parallel_efficiency_floor: float = 0.75) -> dict:
    """Fleet scale-out tripwire: the SAME open-loop load (two corpora,
    `rounds` rounds of the 3-job churn-profiling trio each — 6*rounds
    requests) served by a 2-process fleet behind the affinity router
    must beat a 1-process server with the identical per-host config in
    jobs/min. The fleet's wins are exactly avenir-net's claims: the
    router keeps each corpus on one warm host (affinity hit-rate
    asserted ≥ `min_hit_rate` — round 2 must land on round 1's host),
    the two hosts scan their corpora in genuine process parallelism,
    and the per-host priced-bytes budget vector is never breached
    (router peaks AND each host's own admission peak checked). Every
    fleet-served artifact must be byte-identical to its solo-runner
    twin, and the per-host queue-wait p99s land in the bank row.

    The speedup gate is ``min(floor, capacity *
    parallel_efficiency_floor)``: each host is PINNED to one core (an
    unpinned single process borrows the whole box through XLA's
    intra-op threads, so a same-box fleet-vs-one comparison would
    measure core oversubscription, not scale-out) and the box's actual
    2-process capacity is probed first. On a box whose capacity reads
    under 1.5 (a steal-throttled CI container) the throughput leg is
    recorded, not asserted — no software can run two hosts 1.5x faster
    than one on ~1.3 cores — while a real multi-core host (capacity
    ~2.0) is held to the full `floor`; the deterministic legs (byte
    identity, affinity hit rate, budget vector) assert everywhere."""
    import os
    import shutil
    import time

    from avenir_tpu.data import churn_schema, generate_churn
    from avenir_tpu.net.fleet import Fleet
    from avenir_tpu.runner import run_job

    d = tempfile.mkdtemp(prefix="avenir_fleet_tripwire_")
    try:
        corpora = []
        for i, seed in enumerate((41, 43)):
            path = os.path.join(d, f"churn_{i}.csv")
            blob = generate_churn(100_000, seed=seed, as_csv=True)
            with open(path, "w") as fh:
                for _ in range(max(rows // 100_000, 1)):
                    fh.write(blob)
            corpora.append(path)
        schema = os.path.join(d, "churn.json")
        churn_schema().save(schema)
        conf = lambda p: {f"{p}.feature.schema.file.path": schema}  # noqa: E731
        mi_conf = {**conf("mut"), "mut.mutual.info.score.algorithms":
                   "mutual.info.maximization"}
        trio = [("bayesianDistr", "bad", conf("bad"), "nb"),
                ("mutualInformation", "mut", mi_conf, "mi"),
                ("fisherDiscriminant", "fid", conf("fid"), "fid")]
        load = []                      # (tag, request-object) rows
        for rnd in range(rounds):
            for ci, corpus in enumerate(corpora):
                for job, prefix, cf, short in trio:
                    tag = f"{short}_c{ci}_r{rnd}"
                    # the round tag is inert to the job but lands in
                    # the conf digest, so round 2 re-EXECUTES on its
                    # warm host (the affinity claim under test) instead
                    # of coalescing into round 1's artifact copy
                    cf_rnd = {**cf, f"{prefix}.bench.round": str(rnd)}
                    load.append((tag, {
                        "job": job, "conf": cf_rnd, "inputs": [corpus],
                        "tenant": f"tenant_{short}",
                        "output": os.path.join(d, "served", tag)}))
        warm = os.path.join(d, "warm.csv")
        with open(warm, "w") as fh:
            fh.write(generate_churn(500, seed=45, as_csv=True))

        import contextlib

        try:
            from bench import _host_core_lock
        except ImportError:                      # bench.py not importable
            _host_core_lock = contextlib.nullcontext

        # one CPU per host, pinned: an unpinned single process borrows
        # the whole box through XLA's intra-op threads, so the same-box
        # fleet-vs-one comparison would measure core oversubscription,
        # not scale-out — pinning makes host i a faithful proxy for a
        # separate machine with one serving core
        n_cores = os.cpu_count() or 2

        def run_arm(hosts: int) -> dict:
            root = os.path.join(d, f"arm_{hosts}h")
            fleet = Fleet(root, hosts=hosts, workers=1,
                          budget_mb=budget_mb, metrics_interval_s=0.5,
                          pin_cores=[i % n_cores for i in range(hosts)])
            with fleet:
                # warm every host's jit compiles OFF the clock, pinned
                # so warmup never perturbs the router's affinity map
                warm_names = []
                for h in range(hosts):
                    for job, _prefix, cf, short in trio:
                        warm_names.append(fleet.submit_to(h, {
                            "job": job, "conf": cf, "inputs": [warm],
                            "output": os.path.join(
                                root, f"warm_{h}_{short}")}))
                fleet.collect(warm_names, timeout=600)
                t0 = time.perf_counter()
                names = {tag: fleet.submit(dict(obj, output=os.path.join(
                             d, "served", f"{hosts}h_{tag}")))
                         for tag, obj in load}
                name_rows = fleet.collect(list(names.values()),
                                          timeout=7200)
                rows_by_tag = {tag: name_rows[name]
                               for tag, name in names.items()}
                dt = time.perf_counter() - t0
                snapshot = fleet.merged_metrics()
                router = fleet.router.snapshot()
                hit_rate = fleet.router.affinity_hit_rate()
            bad = [tag for tag, row in rows_by_tag.items()
                   if not row.get("ok")]
            if bad:
                raise RuntimeError(
                    f"{hosts}-host arm failed requests {bad}: "
                    f"{rows_by_tag[bad[0]].get('error')}")
            per_host = []
            for i in range(hosts):
                host_snap = os.path.join(root, f"host{i}",
                                         "metrics.json")
                with open(host_snap) as fh:
                    hs = json.load(fh)
                peak = hs["inflight"]["peak_priced_bytes"]
                if peak > budget_mb * (1 << 20):
                    raise RuntimeError(
                        f"host {i} admission peak {peak} breached its "
                        f"{budget_mb}MB budget-vector entry")
                per_host.append({
                    "host": i,
                    "p99_queue_wait_ms": hs["hists"].get(
                        "queue_wait_ms", {}).get("p99", 0.0),
                    "served": hs["stats"].get("served", 0.0),
                    "peak_priced_mb": round(peak / (1 << 20), 1)})
            for h in router["hosts"]:
                if h["peak_assigned_bytes"] > h["budget_bytes"]:
                    raise RuntimeError(
                        f"router assigned host {h['host']} past its "
                        f"budget-vector entry")
            return {"hosts": hosts, "wall_s": dt,
                    "jobs_per_min": len(load) / (dt / 60.0),
                    "hit_rate": hit_rate, "router": router["stats"],
                    "per_host": per_host, "rows": rows_by_tag,
                    "fleet_hists": snapshot.get("hists", {})}

        with _host_core_lock():
            # capacity is probed on BOTH sides of the arms and the MIN
            # taken: a steal-throttled box is non-stationary minute to
            # minute, and a probe that happened to catch a fast window
            # must not arm the throughput gate for arms that ran in a
            # slow one
            cap_before = host_parallel_capacity(2)
            solo = run_arm(1)
            fleet_arm = run_arm(2)
            capacity = min(cap_before, host_parallel_capacity(2))
        # byte-identity: every round-1 fleet-served artifact vs its
        # solo-runner twin (later rounds write the same bytes to other
        # paths); the served rows carry their artifact paths
        for tag, obj in load[:6]:
            twin = run_job(obj["job"], obj["conf"], obj["inputs"],
                           os.path.join(d, "twin", tag))
            served = fleet_arm["rows"][tag]["outputs"]
            if len(served) != len(twin.outputs):
                raise RuntimeError(
                    f"fleet served {tag} wrote {len(served)} outputs, "
                    f"solo twin wrote {len(twin.outputs)}")
            for pa, pb in zip(sorted(twin.outputs), sorted(served)):
                with open(pa, "rb") as fa, open(pb, "rb") as fb:
                    if fa.read() != fb.read():
                        raise RuntimeError(
                            f"fleet artifact of {tag} differs from its "
                            f"solo-runner twin ({pb} vs {pa})")
        speedup = solo["wall_s"] / max(fleet_arm["wall_s"], 1e-9)
        effective_floor = min(floor,
                              capacity * parallel_efficiency_floor)
        # the throughput leg asserts only where the box can EXPRESS
        # scale-out: a steal-throttled container whose 2-process
        # capacity probes read under 1.7 (1.16-1.6 observed on the
        # 2-vCPU dev box, minute to minute) cannot reliably run two
        # hosts 1.5x faster than one no matter what the software does —
        # there the measured speedup + capacity land in the bank as
        # evidence (the repo's "hardware rounds only" convention), and
        # the deterministic gates (byte identity, affinity, budget
        # vector) still run everywhere; a real multi-core host probes
        # ~1.9+ on both sides and is held to the floor
        throughput_gated = capacity >= 1.7
        if throughput_gated and speedup < effective_floor:
            raise RuntimeError(
                f"2-host fleet only {speedup:.2f}x the 1-host server "
                f"(floor {effective_floor:.2f}x = min({floor}, "
                f"{capacity:.2f} box capacity * "
                f"{parallel_efficiency_floor}); solo "
                f"{solo['wall_s']:.2f}s, fleet "
                f"{fleet_arm['wall_s']:.2f}s) — scale-out regressed")
        if fleet_arm["hit_rate"] < min_hit_rate:
            raise RuntimeError(
                f"affinity hit rate {fleet_arm['hit_rate']:.2f} under "
                f"the {min_hit_rate} floor — repeat corpora are not "
                f"returning to their warm host")
        return {"rows": rows, "requests": len(load), "floor": floor,
                "effective_floor": round(effective_floor, 2),
                "host_parallel_capacity": round(capacity, 2),
                "throughput_gated": throughput_gated,
                "speedup": round(speedup, 2),
                "jobs_per_min_solo": round(solo["jobs_per_min"], 2),
                "jobs_per_min_fleet": round(fleet_arm["jobs_per_min"],
                                            2),
                "affinity_hit_rate": round(fleet_arm["hit_rate"], 3),
                "router": fleet_arm["router"],
                "per_host": fleet_arm["per_host"],
                "outputs_byte_identical": True}
    finally:
        shutil.rmtree(d, ignore_errors=True)


def fleet_fault_tripwire(rows: int = 10_000_000,
                         budget_mb: float = 3072.0) -> dict:
    """Chaos harness for avenir-fault: the fleet's results contract
    must hold under dying hosts. Two deterministic legs (no throughput
    floor — re-execution is licensed by idempotency, so the claims are
    about LOSS and CONFLICT, not speed):

    **Chaos leg** — a 2-host fleet serves the churn trio over two
    corpora (6 requests); once the first result lands (mid-batch), the
    host holding the most unfinished leases is SIGKILLed. Every
    submitted request must still yield a result row (zero lost: the
    lease sweep requeues the stranded claims to the survivor), every
    artifact must be byte-identical to its solo-runner twin (zero
    conflicting: a late duplicate write is an identical write), at
    least one requeue must have fired, every lease must be released,
    and the killed host must restart and reintegrate (supervision
    restarts >= 1, state back to serving).

    **Hedging leg** — both hosts warmed (a measured served tail each),
    then one host SIGSTOPped and a fresh corpus submitted: the router
    places it on the stalled host, the front's pending-age signal
    blows past the fleet median, the request is MIRRORED to the
    healthy host (router hedges >= 1) and the first result wins — the
    row collects while the original host is still stopped, with zero
    requeues/restarts (a stall is not a death). After SIGCONT the late
    original rewrites identical bytes, asserted against the twin.

    Quick mode runs the 1M-row proxy; the full round the 10M one."""
    import os
    import shutil
    import signal
    import time

    from avenir_tpu.data import churn_schema, generate_churn
    from avenir_tpu.net.fault import FaultPolicy
    from avenir_tpu.net.fleet import Fleet
    from avenir_tpu.runner import run_job

    d = tempfile.mkdtemp(prefix="avenir_fleet_fault_")
    try:
        corpora = []
        for i, seed in enumerate((61, 67)):
            path = os.path.join(d, f"churn_{i}.csv")
            blob = generate_churn(100_000, seed=seed, as_csv=True)
            with open(path, "w") as fh:
                for _ in range(max(rows // 100_000, 1)):
                    fh.write(blob)
            corpora.append(path)
        schema = os.path.join(d, "churn.json")
        churn_schema().save(schema)
        conf = lambda p: {f"{p}.feature.schema.file.path": schema}  # noqa: E731
        mi_conf = {**conf("mut"), "mut.mutual.info.score.algorithms":
                   "mutual.info.maximization"}
        trio = [("bayesianDistr", "bad", conf("bad"), "nb"),
                ("mutualInformation", "mut", mi_conf, "mi"),
                ("fisherDiscriminant", "fid", conf("fid"), "fid")]
        load = []
        for ci, corpus in enumerate(corpora):
            for job, _prefix, cf, short in trio:
                tag = f"{short}_c{ci}"
                load.append((tag, {
                    "job": job, "conf": cf, "inputs": [corpus],
                    "tenant": f"tenant_{short}",
                    "output": os.path.join(d, "served", tag)}))
        warm = os.path.join(d, "warm.csv")
        with open(warm, "w") as fh:
            fh.write(generate_churn(500, seed=71, as_csv=True))
        n_cores = os.cpu_count() or 2
        pin = [i % n_cores for i in range(2)]

        # ---------------------------------------------------- chaos leg
        chaos_policy = FaultPolicy(
            poll_interval_s=0.1, lease_ttl_s=2.0,
            restart_backoff_base_s=0.5, heartbeat_timeout_s=60.0,
            hedge=False)
        fleet = Fleet(os.path.join(d, "chaos"), hosts=2, workers=1,
                      budget_mb=budget_mb, metrics_interval_s=0.5,
                      pin_cores=pin, fault_policy=chaos_policy)
        with fleet:
            warm_names = [fleet.submit_to(h, {
                "job": job, "conf": cf, "inputs": [warm],
                "output": os.path.join(d, "chaos", f"w_{h}_{short}")})
                for h in range(2) for job, _p, cf, short in trio]
            fleet.collect(warm_names, timeout=600)
            names = {tag: fleet.submit(obj) for tag, obj in load}
            # mid-batch: wait for the FIRST result, then kill the host
            # holding the most unfinished leases
            deadline = time.perf_counter() + 3600
            while not fleet.ready():
                if time.perf_counter() > deadline:
                    raise RuntimeError("no fleet result within 3600s")
                time.sleep(0.05)
            # victim selection: snapshot the lease table ONCE per try —
            # the sweep races this loop (rows land, leases drop), so an
            # empty snapshot or an already-gone pid retries, and if the
            # whole batch drains before any lease is caught the kill is
            # skipped CLEANLY (nothing left to strand) instead of
            # crashing the harness on max() of an empty dict /
            # os.kill(None)
            victim = victim_pid = None
            kill_deadline = time.perf_counter() + 60
            while victim_pid is None \
                    and time.perf_counter() < kill_deadline:
                held: dict = {}
                for lease_name in fleet._leases.names():
                    lease = fleet._leases.load(lease_name)
                    if lease is not None:
                        held[lease.host] = held.get(lease.host, 0) + 1
                if not held:
                    if not fleet._outstanding:
                        break          # batch drained: nothing to kill
                    time.sleep(0.02)
                    continue
                victim = max(held, key=held.get)
                victim_pid = fleet.host_pid(victim)
            killed = victim_pid is not None
            if killed:
                os.kill(victim_pid, signal.SIGKILL)
            name_rows = fleet.collect(list(names.values()),
                                      timeout=7200)
            rows_by_tag = {tag: name_rows[n] for tag, n in names.items()}
            bad = [t for t, r in rows_by_tag.items() if not r.get("ok")]
            if bad:
                raise RuntimeError(
                    f"chaos leg lost/failed requests {bad}: "
                    f"{rows_by_tag[bad[0]].get('error')}")
            chaos_snap = fleet.fault_snapshot()
            if killed and chaos_snap["stats"]["requeues"] < 1:
                raise RuntimeError(
                    "chaos leg: SIGKILL stranded no lease — the "
                    "requeue path never exercised")
            if chaos_snap["leases_outstanding"] != 0:
                raise RuntimeError(
                    f"chaos leg leaked "
                    f"{chaos_snap['leases_outstanding']} lease(s)")
            t0 = time.perf_counter()
            while killed:
                snap = fleet.fault_snapshot()
                ok_restart = (snap["stats"]["restarts"] >= 1
                              and snap["hosts"][victim]["state"]
                              == "serving")
                if ok_restart:
                    break
                if time.perf_counter() - t0 > 120:
                    raise RuntimeError(
                        f"killed host {victim} never reintegrated: "
                        f"{snap}")
                time.sleep(0.1)
        # stop() drained any late duplicate claims: compare EVERY
        # artifact (first-won rows and late identical rewrites alike)
        # against the solo twin — zero conflicting results
        for tag, obj in load:
            twin = run_job(obj["job"], obj["conf"], obj["inputs"],
                           os.path.join(d, "twin", tag))
            served = rows_by_tag[tag]["outputs"]
            if len(served) != len(twin.outputs):
                raise RuntimeError(
                    f"chaos leg {tag}: {len(served)} outputs vs twin's "
                    f"{len(twin.outputs)}")
            for pa, pb in zip(sorted(twin.outputs), sorted(served)):
                with open(pa, "rb") as fa, open(pb, "rb") as fb:
                    if fa.read() != fb.read():
                        raise RuntimeError(
                            f"chaos leg artifact of {tag} differs from "
                            f"its solo twin ({pb} vs {pa}) — a "
                            f"conflicting result")

        # -------------------------------------------------- hedging leg
        hedge_policy = FaultPolicy(
            poll_interval_s=0.1, hedge_multiple=2.0,
            hedge_floor_ms=500.0, lease_ttl_s=3600.0,
            heartbeat_timeout_s=3600.0)
        hedge_fleet = Fleet(os.path.join(d, "hedge"), hosts=2,
                            workers=1, budget_mb=budget_mb,
                            metrics_interval_s=0.5, pin_cores=pin,
                            fault_policy=hedge_policy)
        job, _prefix, cf, short = trio[0]
        with hedge_fleet:
            warm_names = [hedge_fleet.submit_to(h, {
                "job": job, "conf": cf, "inputs": [warm],
                "output": os.path.join(d, "hedge", f"w_{h}")})
                for h in range(2)]
            hedge_fleet.collect(warm_names, timeout=600)
            # the hedge gate reads each host's SERVED tail from its
            # heartbeat snapshot: let both catch up with the warmups
            # before freezing one (a stopped host cannot refresh its
            # own)
            t0 = time.perf_counter()
            while not all(n >= 1 for _p, n
                          in hedge_fleet._rolled_p99().values()):
                if time.perf_counter() - t0 > 60:
                    raise RuntimeError(
                        "host heartbeats never reflected the warmups")
                time.sleep(0.1)
            os.kill(hedge_fleet.host_pid(0), signal.SIGSTOP)
            try:
                # fresh corpus on an idle fleet -> host 0, which is
                # stopped: only the mirror can serve it
                hname = hedge_fleet.submit({
                    "job": job, "conf": cf, "inputs": [corpora[0]],
                    "tenant": "hedge",
                    "output": os.path.join(d, "served", "hedged")})
                hrow = hedge_fleet.collect([hname],
                                           timeout=7200)[hname]
            finally:
                os.kill(hedge_fleet.host_pid(0), signal.SIGCONT)
            if not hrow.get("ok"):
                raise RuntimeError(
                    f"hedging leg request failed: {hrow.get('error')}")
            hedges = hedge_fleet.router.stats["hedges"]
            hsnap = hedge_fleet.fault_snapshot()
            if hedges < 1:
                raise RuntimeError(
                    "hedging leg: stalled host never triggered a "
                    "mirror")
            if hsnap["stats"]["requeues"] or hsnap["stats"]["restarts"]:
                raise RuntimeError(
                    f"hedging leg: a stall must hedge, not "
                    f"requeue/restart ({hsnap['stats']})")
        twin = run_job(job, cf, [corpora[0]],
                       os.path.join(d, "twin", "hedged"))
        served = hrow["outputs"]
        for pa, pb in zip(sorted(twin.outputs), sorted(served)):
            with open(pa, "rb") as fa, open(pb, "rb") as fb:
                if fa.read() != fb.read():
                    raise RuntimeError(
                        f"hedged artifact differs from its solo twin "
                        f"({pb} vs {pa})")
        return {"rows": rows, "requests": len(load),
                "chaos_requeues": int(chaos_snap["stats"]["requeues"]),
                "chaos_restarts": int(chaos_snap["stats"]["restarts"]),
                "victim_host": int(victim) if killed else None,
                "chaos_kill_skipped": not killed,
                "hedges": int(hedges),
                "zero_lost": True, "zero_conflicting": True,
                "outputs_byte_identical": True}
    finally:
        shutil.rmtree(d, ignore_errors=True)


def shard_tripwire(rows: int = 10_000_000, floor: float = 1.5,
                   parallel_efficiency_floor: float = 0.75) -> dict:
    """avenir-shard tripwire: the multi-process sharded streaming
    driver must reproduce the solo runner byte-for-byte AND scale with
    the box. Three legs:

    **Byte-identity + speedup** — for TWO fold families (one
    Dataset-chunk: mutualInformation over the churn corpus; one
    raw-byte-block: markovStateTransitionModel over the sequence
    corpus), the solo runner executes in a pinned one-core child (its
    recorded seconds exclude interpreter/jax boot — the
    stream_scale_check child convention) and ``run_sharded(procs=2)``
    runs with each worker pinned to its own core, its scan clock
    starting at the workers' go barrier (boot paid concurrently, off
    the clock — the fleet warmup convention). Artifacts must be
    byte-identical per family; the GEOMEAN speedup is held to
    ``min(floor, capacity * parallel_efficiency_floor)`` with the box's
    2-process capacity probed on both sides and the min taken, and the
    throughput gate arms only where capacity >= 1.7 — the PR-12
    convention: no software runs two workers 1.5x faster than one on
    ~1.3 steal-throttled cores, so there the numbers bank as evidence.

    **Miner per-k leg** — frequentItemsApriori over the sequence
    corpus: the per-k candidate rounds (the dominant share of a mining
    job's wall) run DISTRIBUTED through the level-namespaced ledger,
    workers replaying their own encoded-block caches. Byte-identity vs
    the solo miner asserts UNCONDITIONALLY, the per-k counters must
    show the rounds actually ran distributed (``Shard:PerKBlocks`` >=
    plan blocks, ``Shard:PerKRounds`` >= 1), and the 2-process speedup
    is held to the same capacity-gated floor as the families above
    (banked as evidence on sub-1.7x boxes — the hardware-rounds
    convention).

    **SIGSTOP chaos** — one worker is stopped the moment it holds an
    uncommitted claim: the survivor steals the unclaimed tail, the
    straggler detector prices the stalled claim off the survivor's own
    span telemetry and redundantly re-dispatches it, and after SIGCONT
    the woken worker's late commit is REJECTED first-commit-wins.
    Asserted: every block committed (zero lost), ``Shard:DedupBlocks
    >= 1`` (the dedup actually fired), bytes identical to solo.
    """
    import os
    import shutil
    import signal
    import threading
    import time

    from avenir_tpu.data import churn_schema, generate_churn
    from avenir_tpu.dist import StragglerPolicy, run_sharded

    d = tempfile.mkdtemp(prefix="avenir_shard_tripwire_")
    try:
        churn = os.path.join(d, "churn.csv")
        blob = generate_churn(100_000, seed=51, as_csv=True)
        with open(churn, "w") as fh:
            for _ in range(max(rows // 100_000, 1)):
                fh.write(blob)
        schema = os.path.join(d, "churn.json")
        churn_schema().save(schema)
        seq = os.path.join(d, "seq.csv")
        seq_blob = "".join(
            f"c{i},{'T' if i % 2 else 'F'},L,M,H,M,L\n"
            for i in range(100_000))
        with open(seq, "w") as fh:
            for _ in range(max(rows // 100_000, 1)):
                fh.write(seq_blob)

        families = [
            ("mutualInformation",
             {"mut.feature.schema.file.path": schema,
              "mut.mutual.info.score.algorithms":
                  "mutual.info.maximization"}, churn),
            ("markovStateTransitionModel",
             {"mst.model.states": "L,M,H",
              "mst.class.label.field.ord": "1",
              "mst.skip.field.count": "2", "mst.class.labels": "T,F"},
             seq),
        ]
        n_cores = os.cpu_count() or 2
        pin = [i % n_cores for i in range(2)]

        def solo_child(job, conf, inp, out) -> float:
            """Solo arm in a fresh child pinned to ONE core: prints the
            run_job seconds (imports excluded — the established child
            protocol), so both arms compare scans, not boots."""
            import subprocess
            import sys as _sys

            code = (
                "import json, sys, time\n"
                "sys.path.insert(0, '.')\n"
                "import jax\n"
                "jax.config.update('jax_platforms', 'cpu')\n"
                "from avenir_tpu.runner import run_job\n"
                "job, conf, inp, out = (sys.argv[1], json.loads(sys.argv[2]),"
                " sys.argv[3], sys.argv[4])\n"
                "t0 = time.perf_counter()\n"
                "run_job(job, conf, [inp], out)\n"
                "print(json.dumps({'seconds': time.perf_counter() - t0}))\n")
            preexec = None
            if hasattr(os, "sched_setaffinity"):
                preexec = lambda: os.sched_setaffinity(0, {pin[0]})  # noqa: E731
            proc = subprocess.run(
                [_sys.executable, "-c", code, job, json.dumps(conf),
                 inp, out],
                capture_output=True, text=True, timeout=7200,
                preexec_fn=preexec)
            if proc.returncode != 0:
                raise RuntimeError(
                    f"solo {job} failed: {proc.stderr[-500:]}")
            return float(json.loads(
                proc.stdout.strip().splitlines()[-1])["seconds"])

        import contextlib

        try:
            from bench import _host_core_lock
        except ImportError:
            _host_core_lock = contextlib.nullcontext

        speedups, rows_out = [], {}
        with _host_core_lock():
            cap_before = host_parallel_capacity(2)
            for job, conf, inp in families:
                solo_out = os.path.join(d, f"solo_{job}")
                solo_s = solo_child(job, conf, inp, solo_out)
                res = run_sharded(job, conf, [inp],
                                  os.path.join(d, f"shard_{job}"),
                                  procs=2, pin_cores=pin)
                shard_s = float(res.counters["Shard:ScanSeconds"])
                # byte-identity per family (miner-style multi-file
                # outputs compare sorted, like every other tripwire)
                solo_files = ([solo_out] if os.path.isfile(solo_out)
                              else sorted(
                                  os.path.join(solo_out, f)
                                  for f in os.listdir(solo_out)))
                if len(solo_files) != len(res.outputs):
                    raise RuntimeError(
                        f"sharded {job} wrote {len(res.outputs)} "
                        f"outputs, solo wrote {len(solo_files)} — the "
                        f"zip below would silently skip the difference")
                for pa, pb in zip(solo_files, sorted(res.outputs)):
                    with open(pa, "rb") as fa, open(pb, "rb") as fb:
                        if fa.read() != fb.read():
                            raise RuntimeError(
                                f"sharded {job} artifact differs from "
                                f"its solo twin ({pb} vs {pa})")
                speedups.append(solo_s / max(shard_s, 1e-9))
                rows_out[job] = {
                    "solo_seconds": round(solo_s, 2),
                    "sharded_seconds": round(shard_s, 2),
                    "speedup": round(solo_s / max(shard_s, 1e-9), 2),
                    "counters": {k: v for k, v in res.counters.items()
                                 if k.startswith("Shard:")}}
            capacity = min(cap_before, host_parallel_capacity(2))

        speedup = float((speedups[0] * speedups[1]) ** 0.5)
        effective_floor = min(floor, capacity * parallel_efficiency_floor)
        throughput_gated = capacity >= 1.7
        if throughput_gated and speedup < effective_floor:
            raise RuntimeError(
                f"2-process sharded scan only {speedup:.2f}x solo "
                f"(floor {effective_floor:.2f}x = min({floor}, "
                f"{capacity:.2f} capacity * {parallel_efficiency_floor}); "
                f"per-family {[round(s, 2) for s in speedups]}) — "
                f"shard scale-out regressed")

        # -------------------------------------------- miner per-k leg
        fia_conf = {"fia.support.threshold": "0.3",
                    "fia.item.set.length": "3",
                    "fia.skip.field.count": "2"}
        with _host_core_lock():
            cap_m0 = host_parallel_capacity(2)
            solo_miner_out = os.path.join(d, "solo_fia")
            solo_miner_s = solo_child("frequentItemsApriori", fia_conf,
                                      seq, solo_miner_out)
            mres = run_sharded("frequentItemsApriori", fia_conf, [seq],
                               os.path.join(d, "shard_fia"), procs=2,
                               pin_cores=pin)
            cap_miner = min(cap_m0, host_parallel_capacity(2))
        miner_shard_s = float(mres.counters["Shard:ScanSeconds"])
        solo_files = sorted(os.path.join(solo_miner_out, f)
                            for f in os.listdir(solo_miner_out))
        if len(solo_files) != len(mres.outputs):
            raise RuntimeError(
                f"sharded miner wrote {len(mres.outputs)} outputs, "
                f"solo wrote {len(solo_files)}")
        for pa, pb in zip(solo_files, sorted(mres.outputs)):
            with open(pa, "rb") as fa, open(pb, "rb") as fb:
                if fa.read() != fb.read():
                    raise RuntimeError(
                        f"sharded miner artifact differs from its solo "
                        f"twin ({pb} vs {pa})")
        if mres.counters["Shard:PerKRounds"] < 1 \
                or mres.counters["Shard:PerKBlocks"] \
                < mres.counters["Shard:Blocks"]:
            raise RuntimeError(
                f"miner per-k rounds never ran distributed "
                f"(counters {mres.counters}) — the coordinator counted "
                f"candidates itself")
        miner_speedup = solo_miner_s / max(miner_shard_s, 1e-9)
        miner_floor = min(floor, cap_miner * parallel_efficiency_floor)
        miner_gated = cap_miner >= 1.7
        if miner_gated and miner_speedup < miner_floor:
            raise RuntimeError(
                f"2-process sharded MINER only {miner_speedup:.2f}x "
                f"solo (floor {miner_floor:.2f}x at capacity "
                f"{cap_miner:.2f}) — the distributed per-k rounds "
                f"regressed")
        miner_row = {
            "solo_seconds": round(solo_miner_s, 2),
            "sharded_seconds": round(miner_shard_s, 2),
            "perk_seconds": float(
                mres.counters.get("Shard:PerKSeconds", 0.0)),
            "speedup": round(miner_speedup, 2),
            "host_parallel_capacity": round(cap_miner, 2),
            "throughput_gated": miner_gated,
            "counters": {k: v for k, v in mres.counters.items()
                         if k.startswith("Shard:")}}

        # ---------------------------------------------- SIGSTOP chaos
        job, conf, inp = families[0]
        stopped: dict = {}
        watch_stop = threading.Event()

        def chaos_hook(pids, root):
            # the driver's test tap only HANDS the watcher its targets;
            # the thread itself is owned (started, joined bounded) by
            # the tripwire body below
            stopped["pids"] = pids
            stopped["root"] = root

        def watch():
            from avenir_tpu.dist import BlockLedger, load_plan

            while "root" not in stopped:
                if watch_stop.wait(0.002):
                    return
            pids, root = stopped["pids"], stopped["root"]
            ledger = BlockLedger(root)
            plan = None
            victim = None
            while not watch_stop.is_set():
                if plan is None:
                    try:
                        plan = load_plan(os.path.join(root, "plan.json"))
                    except Exception:
                        time.sleep(0.005)
                        continue
                if victim is None:
                    done = set(ledger.committed())
                    for bid, info in ledger.claims().items():
                        if bid not in done:
                            victim = info["worker"]
                            os.kill(pids[victim], signal.SIGSTOP)
                            # verify the claim is STILL uncommitted
                            # (the fold might have raced the stop)
                            if bid in set(ledger.committed()):
                                os.kill(pids[victim], signal.SIGCONT)
                                victim = None
                            break
                    time.sleep(0.002)
                    continue
                stopped["victim"] = victim
                if len(ledger.committed()) >= len(plan.blocks):
                    os.kill(pids[victim], signal.SIGCONT)
                    stopped["resumed"] = True
                    return
                time.sleep(0.01)

        watcher = threading.Thread(target=watch, daemon=True)
        watcher.start()
        chaos_policy = StragglerPolicy(mirror_floor_s=0.5,
                                       mirror_multiple=2.0, poll_s=0.02)
        try:
            res = run_sharded(job, conf, [inp],
                              os.path.join(d, "chaos_out"), procs=2,
                              pin_cores=pin, policy=chaos_policy,
                              worker_hook=chaos_hook)
        finally:
            # the watcher normally exits at SIGCONT; stop+join it
            # BOUNDED either way so a missed catch cannot leak the
            # thread past the tripwire
            watch_stop.set()
            watcher.join(30)
            if watcher.is_alive():
                raise RuntimeError("chaos watcher failed to stop")
        if "victim" not in stopped:
            raise RuntimeError(
                "chaos leg: the watcher never caught a worker holding "
                "an uncommitted claim — nothing was actually stalled")
        if res.counters["Shard:DedupBlocks"] < 1:
            raise RuntimeError(
                f"chaos leg: the stalled worker's block was never "
                f"redundantly re-dispatched and deduped "
                f"(counters {res.counters})")
        # zero lost blocks: run_sharded's merge REFUSES to run with any
        # block state missing (ShardError), so reaching a result at all
        # proves every plan block committed; make the claim explicit
        if not res.outputs or res.counters["Shard:Blocks"] < 1:
            raise RuntimeError("chaos leg lost its outputs")
        solo_out = os.path.join(d, f"solo_{job}")
        with open(solo_out, "rb") as fa, open(res.outputs[0], "rb") as fb:
            if fa.read() != fb.read():
                raise RuntimeError(
                    "chaos leg artifact differs from the solo twin — a "
                    "redundantly folded block leaked into the merge")
        return {"rows": rows, "floor": floor,
                "effective_floor": round(effective_floor, 2),
                "host_parallel_capacity": round(capacity, 2),
                "throughput_gated": throughput_gated,
                "speedup": round(speedup, 2),
                "families": rows_out,
                "miner": miner_row,
                "chaos_dedup_blocks": int(
                    res.counters["Shard:DedupBlocks"]),
                "chaos_stolen_blocks": int(
                    res.counters["Shard:StolenBlocks"]),
                "chaos_victim_worker": int(stopped["victim"]),
                "zero_lost_blocks": True,
                "outputs_byte_identical": True}
    finally:
        shutil.rmtree(d, ignore_errors=True)


def score_tripwire(queries: int = 512, floor: float = 3.0,
                   p99_ceiling_ms: float = 250.0,
                   min_hit_rate: float = 0.9,
                   fleet_scores_per_model: int = 40) -> dict:
    """Online-scoring perf tripwire for avenir-score: the SAME query
    stream answered two ways must show the coalescer's win without
    changing a single byte of any answer.

    **Coalescing leg** — `queries` markov scores fired from 32
    concurrent client threads into one ScorePlane (2ms window) must
    beat the same `queries` rows scored sequentially through
    ``score_once`` (the cold solo reference: load, predict one row,
    drop the model) by `floor`x in scores/sec. The plane's wins are
    exactly the PR's claims: ONE warm model load (model_loads == 1),
    windows folding many requests into one vectorized predict
    (predict_calls strictly under the request count), and every
    demuxed row BIT-IDENTICAL to its solo twin. The per-model
    end-to-end histogram's p99 must sit under `p99_ceiling_ms` — the
    coalescing window is a latency *budget*, never an unbounded queue.

    **Fleet leg** — two in-process JobServer+NetListener hosts behind
    a ScoreFront, two distinct models queried over real HTTP/1.1
    keep-alive sockets: the router must pin each model to one warm
    host (affinity hit rate ≥ `min_hit_rate`; with one miss per model
    the expected rate is (n-1)/n), every wire answer must byte-match
    its solo twin, and the fleet-merged snapshot must carry BOTH
    models' end-to-end histograms plus the additive score stats
    (merge_snapshots folding the per-host score sections is what the
    fleet report reads — a merge that drops a model's histogram would
    silently halve the fleet's p99 evidence)."""
    import math
    import os
    import shutil
    import threading
    import time

    from avenir_tpu.runner import run_job
    from avenir_tpu.server.score import ScorePlane, ScoreRequest, \
        score_once

    # a 24-state alphabet: the solo reference's cost is the per-score
    # model RELOAD (2 × 24×24 transition matrices), which is exactly
    # what the warm cache amortizes — a 3-state toy parses so fast the
    # comparison would measure thread scheduling, not the cache
    states = tuple(f"s{i:02d}" for i in range(24))
    mst_conf = {"mst.model.states": ",".join(states),
                "mst.class.label.field.ord": "1",
                "mst.skip.field.count": "2",
                "mst.class.labels": "T,F"}
    score_conf = {"field.delim": ",", "class.labels": "T,F",
                  "log.odds.threshold": "0", "skip.field.count": "2"}

    def seq_rows(start: int, n: int) -> list:
        return [f"c{i}," + ("T" if i % 2 else "F") + ","
                + ",".join(states[(i + j) % len(states)]
                           for j in range(6))
                for i in range(start, start + n)]

    d = tempfile.mkdtemp(prefix="avenir_score_tripwire_")
    try:
        models = []
        for m, start in enumerate((0, 7)):
            corpus = os.path.join(d, f"train_{m}.csv")
            with open(corpus, "w") as fh:
                fh.write("\n".join(seq_rows(start, 600)) + "\n")
            model = os.path.join(d, f"model_{m}.txt")
            run_job("markovStateTransitionModel", dict(mst_conf),
                    [corpus], model)
            models.append(model)
        model = models[0]
        rows = [seq_rows(i * 3, 6)[0] for i in range(queries)]

        # warm both sides' one-time costs off the clock (jit/imports)
        score_once("markov", model, rows[0], score_conf)

        t0 = time.perf_counter()
        solo = [score_once("markov", model, r, score_conf)
                for r in rows]
        t_solo = time.perf_counter() - t0

        plane = ScorePlane(window_ms=2.0, batch_max=64)
        try:
            plane.score(ScoreRequest("markov", model, rows[0],
                                     dict(score_conf)))
            warm_predicts = plane.predict_calls(model)
            out = [None] * queries
            # enough concurrent clients that each 2ms window coalesces
            # a real batch — at 8 the sequential window waits per
            # thread dominate and the comparison measures the window,
            # not the coalescing
            n_threads = 32

            def client(t: int) -> None:
                for i in range(t, queries, n_threads):
                    out[i] = plane.score(ScoreRequest(
                        "markov", model, rows[i], dict(score_conf)),
                        timeout=60.0).row

            t0 = time.perf_counter()
            threads = [threading.Thread(target=client, args=(t,))
                       for t in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            t_plane = time.perf_counter() - t0
            predicts = plane.predict_calls(model) - warm_predicts
            stats = plane.snapshot()["stats"]
            name = os.path.splitext(os.path.basename(model))[0]
            p99 = plane.hist_summaries()[
                f"score_{name}_total_ms"]["p99"]
        finally:
            plane.close()
        for i, (a, b) in enumerate(zip(solo, out)):
            if a != b:
                raise RuntimeError(
                    f"coalesced row {i} differs from its solo twin "
                    f"({b!r} vs {a!r}) — demux broke bit-identity")
        if stats["model_loads"] != 1:
            raise RuntimeError(
                f"plane loaded the model {stats['model_loads']} times "
                f"for one artifact — the warm cache is not holding")
        if predicts >= queries:
            raise RuntimeError(
                f"{predicts} vectorized dispatches for {queries} "
                f"requests — the window never coalesced anything")
        speedup = t_solo / max(t_plane, 1e-9)
        if speedup < floor:
            raise RuntimeError(
                f"coalesced scoring only {speedup:.2f}x the solo "
                f"reference (floor {floor}x; solo {t_solo:.2f}s, "
                f"plane {t_plane:.2f}s) — the warm-cache/coalescing "
                f"win regressed")
        if p99 > p99_ceiling_ms:
            raise RuntimeError(
                f"score p99 {p99:.1f}ms past the {p99_ceiling_ms}ms "
                f"ceiling — the window is queuing, not coalescing")

        # ---- fleet leg: 2 hosts, 2 models, real keep-alive sockets
        from avenir_tpu.net.fleet import ScoreFront
        from avenir_tpu.net.listener import NetListener
        from avenir_tpu.obs.report import merge_snapshots
        from avenir_tpu.server import JobServer

        fleet_rows = rows[:fleet_scores_per_model]
        solo_by_model = {m: [score_once("markov", m, r, score_conf)
                             for r in fleet_rows] for m in models}
        servers = [JobServer(workers=1,
                             state_root=os.path.join(d, f"h{i}"))
                   .start() for i in range(2)]
        listeners = [NetListener(s, port=0).start() for s in servers]
        try:
            front = ScoreFront([f"http://127.0.0.1:{lis.port}"
                                for lis in listeners])
            wire = {m: [None] * len(fleet_rows) for m in models}

            def fleet_client(m: str) -> None:
                for i, r in enumerate(fleet_rows):
                    wire[m][i] = front.score(
                        "markov", m, r, conf=dict(score_conf),
                        timeout=60.0)["row"]

            fthreads = [threading.Thread(target=fleet_client,
                                         args=(m,)) for m in models]
            for t in fthreads:
                t.start()
            for t in fthreads:
                t.join()
            hit_rate = front.router.affinity_hit_rate()
            front.close()
            snap = merge_snapshots([s.metrics_snapshot()
                                    for s in servers])
        finally:
            for lis in listeners:
                lis.stop()
            for srv in servers:
                srv.shutdown()
        for m in models:
            for i, (a, b) in enumerate(zip(solo_by_model[m],
                                           wire[m])):
                if a != b:
                    raise RuntimeError(
                        f"fleet-served row {i} of {m} differs from "
                        f"its solo twin ({b!r} vs {a!r})")
        if hit_rate < min_hit_rate:
            raise RuntimeError(
                f"score affinity hit rate {hit_rate:.2f} under the "
                f"{min_hit_rate} floor — repeat queries of one model "
                f"are not returning to its warm host")
        total = 2 * len(fleet_rows)
        fleet_stats = (snap.get("score") or {}).get("stats", {})
        if int(fleet_stats.get("scores", 0)) != total:
            raise RuntimeError(
                f"merged snapshot counts "
                f"{fleet_stats.get('scores')} scores, {total} were "
                f"served — merge_snapshots dropped a host's score "
                f"section")
        missing = [m for m in models
                   if "score_" + os.path.splitext(os.path.basename(
                       m))[0].replace(".", "_") + "_total_ms"
                   not in (snap.get("hists_raw") or {})]
        if missing:
            raise RuntimeError(
                f"merged snapshot is missing per-model score "
                f"histograms for {missing}")
        return {"queries": queries, "floor": floor,
                "speedup": round(speedup, 2),
                "scores_per_s_solo": round(queries / t_solo, 1),
                "scores_per_s_coalesced": round(
                    queries / max(t_plane, 1e-9), 1),
                "vectorized_dispatches": int(predicts),
                "dispatch_bound": int(math.ceil(queries / 64)),
                "model_loads": int(stats["model_loads"]),
                "p99_total_ms": round(p99, 3),
                "p99_ceiling_ms": p99_ceiling_ms,
                "fleet_scores": total,
                "fleet_affinity_hit_rate": round(hit_rate, 3),
                "fleet_hists_per_model": True,
                "rows_byte_identical": True}
    finally:
        shutil.rmtree(d, ignore_errors=True)


def main(n_devices: int = 8, quick: bool = False):
    # before the first `import jax`: the environment is read once, and
    # the children inherit it
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    os.environ["XLA_FLAGS"] = " ".join(
        flags + [f"--xla_force_host_platform_device_count={n_devices}"])
    import jax

    devices = jax.devices()
    if len(devices) != n_devices or devices[0].platform != "cpu":
        raise RuntimeError(
            f"wanted {n_devices} virtual CPU devices, got {devices}: JAX "
            "was initialised before bench_scaling.main set its environment")
    from avenir_tpu.parallel.scaling import measure_scaling

    # --quick: smoke-scale workloads (single-core hosts; CI)
    kw = dict(nb_rows_per_device=4_096, knn_queries_per_device=64,
              knn_train=1_024, iters=2) if quick else {}
    result = measure_scaling(devices, **kw)
    eff = result["efficiency_at_max"]
    value = float((eff["nb"] * eff["knn"]) ** 0.5)
    platform = devices[0].platform
    print(f"# platform={platform} table={result['table']}", file=sys.stderr)
    line = {
        "metric": "scaling_efficiency_nb_knn",
        "value": round(value, 3),
        "unit": "fraction_of_linear",
        "devices": eff["devices"],
        "platform": platform,
        "table": result["table"],
    }
    # HLO-validated collective-payload model + pod-scale projection
    for key in ("nb_hlo_allreduce_payload_bytes", "nb_analytic_payload_bytes",
                "payload_model_validated", "projection_8_to_256"):
        line[key] = result[key]
    if result.get("virtual_devices"):
        line["virtual_devices"] = True
        line["note"] = result["note"]
    line["miner_tripwire"] = miner_tripwire(4_000 if quick else 20_000)
    line["shared_scan_tripwire"] = shared_scan_tripwire(
        6_000 if quick else 30_000)
    # quick mode shrinks the corpus below where the fixed per-run costs
    # (checkpoint IO, footprint advisory) amortize, so the floor relaxes;
    # the real >=5x gate runs at the 10M-row proxy every full round
    line["incremental_tripwire"] = (
        incremental_tripwire(100_000, floor=1.3) if quick
        else incremental_tripwire())
    # quick mode shrinks the load below where batching amortizes the
    # fixed per-dispatch costs, so the jobs/min floor relaxes; the real
    # >=1.5x gate runs at the 10M-row proxy every full round
    line["server_tripwire"] = (
        server_tripwire(100_000, floor=1.2) if quick
        else server_tripwire())
    # the scale-out gate is capacity-scaled (see fleet_tripwire):
    # min(1.5, measured 2-process box capacity * efficiency floor).
    # quick runs the 1M proxy, NOT 100k: at 100k a full wave is ~0.2s,
    # so the ~1s fixed pipeline costs (spool polling, front pricing)
    # drown the parallel win in noise — 1M is the smallest scale where
    # the comparison measures scale-out, and quick also relaxes the
    # efficiency term for the residual fixed-cost share
    line["fleet_tripwire"] = (
        fleet_tripwire(1_000_000, parallel_efficiency_floor=0.7)
        if quick else fleet_tripwire())
    # the fault legs are deterministic (zero lost / zero conflicting /
    # mirror fires — no throughput floor), so quick differs only in
    # corpus scale: the 1M proxy vs the full round's 10M
    line["fleet_fault_tripwire"] = (
        fleet_fault_tripwire(1_000_000) if quick
        else fleet_fault_tripwire())
    # the sharded-scan gate follows the fleet convention: quick runs
    # the 1M proxy (smaller drowns the parallel win in fixed per-block
    # costs) with the efficiency term relaxed for the residual fixed
    # share; byte-identity and the SIGSTOP dedup leg assert everywhere
    line["shard_tripwire"] = (
        shard_tripwire(1_000_000, parallel_efficiency_floor=0.7)
        if quick else shard_tripwire())
    # quick mode's runs are short enough that scheduler jitter swamps
    # the 3% overhead bound; the real <=1.03x gate runs at the 10M-row
    # proxy every full round
    line["obs_tripwire"] = (
        obs_tripwire(100_000, ceiling=1.25) if quick
        else obs_tripwire())
    # quick mode's corpus is too small for the tuned knobs to buy real
    # wall clock, so the floor relaxes to parity (the chosen-knob log +
    # byte-identity asserts still gate); the real >=1.15x gate runs at
    # the 10M-row proxy every full round
    line["autotune_tripwire"] = (
        autotune_tripwire(100_000, floor=1.0) if quick
        else autotune_tripwire())
    # quick mode's corpus is too small for the parse share to dominate
    # the fused wall, so the repeat-scan floor relaxes; the real >=2x
    # gate (and the three parse-free replay legs) runs at the 10M-row
    # proxy every full round
    line["sidecar_tripwire"] = (
        sidecar_tripwire(100_000, floor=1.2) if quick
        else sidecar_tripwire())
    # quick mode fires fewer queries, so the fixed window/thread costs
    # weigh more and the scores/sec floor relaxes; the real >=3x gate
    # runs the full 512-query stream every full round — the
    # deterministic legs (bit-identity, one model load, coalesced
    # dispatch count, affinity routing, merged histograms) assert at
    # both scales
    line["score_tripwire"] = (
        score_tripwire(160, floor=1.3) if quick
        else score_tripwire())
    line["graftlint"] = graftlint_tripwire()
    print(json.dumps(line))


if __name__ == "__main__":
    args = [a for a in sys.argv[1:] if a != "--quick"]
    main(int(args[0]) if args else 8, quick="--quick" in sys.argv[1:])
