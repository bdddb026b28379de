"""100M-row streaming-scale demonstration (VERDICT r4 #3/#6 'done when').

Runs, each in its own subprocess (so peak-RSS is per-job):
  1. mutualInformation over 100M real on-disk churn rows (~3.8GB CSV);
  2. markovStateTransitionModel (per-class) over 100M sequence rows (~2GB);
asserting host RSS stays O(block) — a whole-file ingest of either input
would need >2x the file size resident; the streamed jobs are asserted
under 3GB regardless of input size.

With --extra, also runs the multi-pass miners over the same 100M rows:
  3. frequentItemsApriori (one streamed scan per itemset length; per-k
     re-scans replay the pass-1 encoded-block cache);
  4. candidateGenerationWithSelfJoin / GSP (one scan per sequence length,
     same cache replay).

With --fused, additionally measures the scan-sharing executor: NB + MI +
discriminant over the churn corpus run three-jobs-sequential (three full
CSV scans) and then FUSED through runner.run_shared (ONE scan, three fold
sinks), recording the speedup ratio and asserting the fused outputs are
byte-identical to the sequential ones.

With --incremental, additionally measures the delta-scan driver: a copy
of the churn corpus is cold-seeded through runner.run_incremental (block
fingerprints + final fold-state checkpoint), ~1% of rows are appended,
and the incremental refresh is timed against a cold full re-scan of the
appended file — byte-identity asserted, speedup recorded as the
incremental anchor of the round's STREAM_SCALE record. Both sides run
in a fresh child process, so ~8s of interpreter+jit startup is priced
into each: the anchor is meaningful at the 10M/100M-row scales this
tool exists for (bench_scaling.incremental_tripwire is the in-process
>=5x gate at the 10M proxy).

Writes one JSON line per job and a summary to STREAM_SCALE_r05.json
(merged into any existing records, so a partial re-run never erases
previously recorded jobs). Works on CPU (pins the platform; the point is
ingest scale, not device speed — bench.py measures the TPU fold rates).

The summary also carries the two streaming-correctness audit columns —
chunk-invariance (graftlint --flow) and shard-merge/resume (graftlint
--merge) status, as validated/total strings — so every scale record
states whether the folds it measured are still deterministic AND still
a merge algebra. --no-audits skips them (they add a couple of minutes
of proxy-scale runs next to an hours-long 100M anchor).

With --server, additionally measures the resident job server: the same
3-tenant mixed-kind open-loop load as bench_scaling.server_tripwire
(churn profilers + sequence jobs + one duplicate request), served by an
in-process JobServer vs sequential one-job-at-a-time execution, in a
fresh child — recording jobs/min both ways, the speedup, p50/p99 queue
wait, the per-request Server:* counters (Server:QueueWaitMs /
Server:BatchSize / Server:CompileHits / Server:AdmissionHeldMs) the
served JobResults carry, the avenir-trace latency histograms (the
summary prints queue-wait p99 and per-chunk scan-latency p99 columns
from the streaming accumulators), and a metrics.json snapshot written
next to the served artifacts — the same file a resident server
refreshes live for `python -m avenir_tpu stats`.

With --shard, additionally measures the multi-process sharded driver
(avenir_tpu.dist.run_sharded): mutualInformation (Dataset-chunk family)
and markovStateTransitionModel (raw-byte-block family) re-run with the
scan split across 2 worker processes through the block ledger, in a
fresh child — byte-identity vs the solo anchors asserted, the
Shard:Blocks/StolenBlocks/DedupBlocks/MergeMs counters recorded as
columns, and the summary gains `shard_speedup` (solo anchor seconds /
sharded scan seconds per job; the scan clock starts at the workers' go
barrier, matching the solo children's boot-excluded convention). A
MINER anchor rides along: frequentItemsApriori re-runs sharded with
its per-k candidate rounds distributed through the level-namespaced
ledger (workers replay their own encoded-block caches), byte-identity
per itemset file asserted, the Shard:PerKRounds/PerKBlocks/
PerKSeconds counters recorded, and the summary gains
`shard_miner_speedup`.

With --sidecar, additionally measures the columnar sidecar: each anchor
family runs three passes in one child — a jit-warmup pass with the
sidecar disabled, a cold pass that packs a fresh sidecar next to the
corpus, and a warm pass that replays it parse-free — recording
`sidecar_speedup` (cold seconds / warm seconds, both jit-warm so the
ratio prices ONLY the parse elimination), the sidecar's bytes-on-disk
ratio vs the CSV, and the Sidecar:HitBlocks / Sidecar:DeltaBlocks
counters; warm output asserted byte-identical to cold.

Usage: python tools/stream_scale_check.py [--rows N_MILLION] [--extra]
                                          [--fused] [--incremental]
                                          [--server] [--shard]
                                          [--sidecar] [--no-audits]
"""

import json
import os
import subprocess
import sys
import time

sys.path.insert(0, ".")

ROWS_M = int(sys.argv[sys.argv.index("--rows") + 1]) \
    if "--rows" in sys.argv else 100
CHURN_CSV = f"/tmp/avenir_scale_churn_{ROWS_M}m.csv"
SEQ_CSV = f"/tmp/avenir_scale_seq_{ROWS_M}m.csv"
RSS_LIMIT_MB = 3072
# only the canonical 100M run updates the tracked record file; proxy
# sizes (e.g. --rows 10, the CPU acceptance proxy) write a sibling so a
# 10M run can never clobber the 100M rows the record is anchored to
RECORD = ("STREAM_SCALE_r05.json" if ROWS_M == 100
          else f"/tmp/avenir_stream_scale_{ROWS_M}m.json")

_CHILD = r'''
import json, os, resource, sys, time
sys.path.insert(0, ".")
import jax
jax.config.update("jax_platforms", "cpu")
from avenir_tpu.runner import run_job

job, conf_json, inp, out = sys.argv[1:5]
t0 = time.perf_counter()
res = run_job(job, json.loads(conf_json), [inp], out)
dt = time.perf_counter() - t0
rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
rows = next((v for k, v in res.counters.items() if "Records" in k), None)
print(json.dumps({"job": job, "seconds": round(dt, 1),
                  "rows": rows, "peak_rss_mb": round(rss, 1),
                  "counters": res.counters}))
'''


_CHILD_SHARED = r'''
import json, os, resource, sys, time
sys.path.insert(0, ".")
import jax
jax.config.update("jax_platforms", "cpu")
from avenir_tpu.runner import run_shared

specs_json, inp, outdir = sys.argv[1:4]
specs = [(job, conf, os.path.join(outdir, job))
         for job, conf in json.loads(specs_json)]
t0 = time.perf_counter()
res = run_shared(specs, [inp])
dt = time.perf_counter() - t0
rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
print(json.dumps({"job": "sharedScan", "jobs": sorted(res),
                  "seconds": round(dt, 1), "peak_rss_mb": round(rss, 1),
                  "outputs": sorted(p for r in res.values()
                                    for p in r.outputs)}))
'''


_CHILD_INCR = r'''
import json, os, resource, sys, time
sys.path.insert(0, ".")
import jax
jax.config.update("jax_platforms", "cpu")
from avenir_tpu.runner import run_incremental

job, conf_json, inp, out, state = sys.argv[1:6]
t0 = time.perf_counter()
res = run_incremental(job, json.loads(conf_json), [inp], out,
                      state_dir=state)
dt = time.perf_counter() - t0
rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
print(json.dumps({"job": job, "seconds": round(dt, 1),
                  "peak_rss_mb": round(rss, 1),
                  "counters": res.counters, "outputs": res.outputs}))
'''


_CHILD_SERVER = r'''
import json, os, resource, sys, time
sys.path.insert(0, ".")
import jax
jax.config.update("jax_platforms", "cpu")
from avenir_tpu.analysis.mem import _RssSampler
from avenir_tpu.runner import run_job
from avenir_tpu.server import JobRequest, JobServer
from bench_scaling import server_load

churn, seq, schema, outdir = sys.argv[1:5]
# the ONE canonical load table — the anchor must measure exactly the
# load bench_scaling.server_tripwire gates
load = server_load(churn, seq, schema)
# jit warmup on a newline-aligned head slice of each corpus so neither
# phase pays first-compile costs (the bench tripwire's own protocol)
warm_dir = os.path.join(outdir, "warm")
os.makedirs(warm_dir, exist_ok=True)
warm = {}
for corpus in {c for _t, _j, _cf, c, _tag in load}:
    with open(corpus, "rb") as fh:
        blob = fh.read(1 << 18)
    dst = os.path.join(warm_dir, os.path.basename(corpus))
    with open(dst, "wb") as fh:
        fh.write(blob[:blob.rfind(b"\n") + 1])
    warm[corpus] = dst
seen = set()
for _tenant, job, cf, corpus, tag in load:
    key = (job, json.dumps(cf, sort_keys=True))
    if key not in seen:
        seen.add(key)
        run_job(job, cf, [warm[corpus]], os.path.join(warm_dir, f"w_{tag}"))
# served phase FIRST, its RSS sampled in isolation: the sequential twin
# is deliberately unbudgeted and CPython RSS is sticky, so running it
# first would attribute ITS peak to the admission-controlled server
server = JobServer(state_root=os.path.join(outdir, "state"))
tickets = {tag: server.submit(JobRequest(
               job, cf, [corpus], os.path.join(outdir, f"srv_{tag}"),
               tenant=tenant))
           for tenant, job, cf, corpus, tag in load}
t0 = time.perf_counter()
with _RssSampler() as sampler:
    server.start()
    server.drain(timeout=7200)
t_srv = time.perf_counter() - t0
served = {tag: t.result(timeout=60) for tag, t in tickets.items()}
stats = server.stats()
# the live metrics surface at anchor scale: the snapshot a resident
# server would be renaming every few seconds, written once here so the
# record keeps the full histogram summaries (queue wait, admission
# hold, dispatch, chunk latency) next to the per-request counters
server.metrics_path = os.path.join(outdir, "metrics.json")
server.write_metrics()
hists = server.metrics_snapshot()["hists"]
server.shutdown()
t0 = time.perf_counter()
for tenant, job, cf, corpus, tag in load:
    run_job(job, cf, [corpus], os.path.join(outdir, f"seq_{tag}"))
t_seq = time.perf_counter() - t0
for tag, res in served.items():
    for pa in sorted(res.outputs):
        rel = os.path.relpath(pa, os.path.join(outdir, f"srv_{tag}"))
        pb = os.path.join(outdir, f"seq_{tag}")
        pb = pb if rel == "." else os.path.join(pb, rel)
        assert open(pa, "rb").read() == open(pb, "rb").read(), (pa, pb)
rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
waits = sorted(r.counters["Server:QueueWaitMs"] for r in served.values())
print(json.dumps({
    "job": "jobServer", "requests": len(load),
    "sequential_seconds": round(t_seq, 1),
    "served_seconds": round(t_srv, 1),
    "jobs_per_min_sequential": round(len(load) / (t_seq / 60.0), 2),
    "jobs_per_min_served": round(len(load) / (t_srv / 60.0), 2),
    "speedup": round(t_seq / max(t_srv, 1e-9), 2),
    "p50_queue_wait_ms": waits[len(waits) // 2],
    "p99_queue_wait_ms": waits[-1],
    "peak_rss_mb": round(rss, 1),
    "server_peak_rss_mb": round(sampler.peak_rss / (1 << 20), 1),
    "outputs_byte_identical": True,
    "server_counters": {tag: {k: v for k, v in r.counters.items()
                              if k.startswith("Server:")}
                        for tag, r in served.items()},
    "hists": hists,
    "stats": {k: v for k, v in stats.items() if v},
}))
'''


_CHILD_SHARDED = r'''
import json, os, resource, sys, time
sys.path.insert(0, ".")
import jax
jax.config.update("jax_platforms", "cpu")
from avenir_tpu.dist import run_sharded

job, conf_json, inp, out, procs = sys.argv[1:6]
t0 = time.perf_counter()
res = run_sharded(job, json.loads(conf_json), [inp], out,
                  procs=int(procs))
dt = time.perf_counter() - t0
rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
print(json.dumps({"job": job, "seconds": round(dt, 1),
                  "scan_seconds": res.counters["Shard:ScanSeconds"],
                  "peak_rss_mb": round(rss, 1),
                  "counters": res.counters, "outputs": res.outputs}))
'''


_CHILD_SIDECAR = r'''
import json, os, resource, shutil, sys, time
sys.path.insert(0, ".")
import jax
jax.config.update("jax_platforms", "cpu")
from avenir_tpu.runner import run_job

job, conf_json, inp, outdir = sys.argv[1:5]
conf = json.loads(conf_json)
prefix = next(iter(conf)).split(".", 1)[0]
scdir = os.path.join(outdir, "sidecar")
shutil.rmtree(scdir, ignore_errors=True)

def blobs(path):
    if os.path.isdir(path):
        return {f: open(os.path.join(path, f), "rb").read()
                for f in sorted(os.listdir(path))}
    with open(path, "rb") as fh:
        return {".": fh.read()}

# pass 0: jit warmup with the sidecar DISABLED, so the cold pass below
# times parsing, not first-compile — the speedup must price only the
# parse elimination
run_job(job, {**conf, prefix + ".stream.sidecar": "false"}, [inp],
        os.path.join(outdir, job + "_jitwarm"))
conf[prefix + ".stream.sidecar.dir"] = scdir
cold_out = os.path.join(outdir, job + "_cold")
t0 = time.perf_counter()
cold = run_job(job, conf, [inp], cold_out)
t_cold = time.perf_counter() - t0
warm_out = os.path.join(outdir, job + "_warm")
t0 = time.perf_counter()
warm = run_job(job, conf, [inp], warm_out)
t_warm = time.perf_counter() - t0
assert blobs(cold_out) == blobs(warm_out), "warm output != cold output"
assert cold.counters.get("Sidecar:DeltaBlocks", 0) > 0, cold.counters
assert warm.counters.get("Sidecar:HitBlocks", 0) > 0, warm.counters
sc_bytes = sum(os.path.getsize(os.path.join(r, f))
               for r, _d, fs in os.walk(scdir) for f in fs)
rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
print(json.dumps({
    "job": job, "cold_seconds": round(t_cold, 2),
    "warm_seconds": round(t_warm, 2),
    "sidecar_speedup": round(t_cold / max(t_warm, 1e-9), 2),
    "sidecar_bytes": sc_bytes,
    "bytes_on_disk_ratio": round(sc_bytes / os.path.getsize(inp), 3),
    "hit_blocks": warm.counters.get("Sidecar:HitBlocks"),
    "delta_blocks": cold.counters.get("Sidecar:DeltaBlocks"),
    "peak_rss_mb": round(rss, 1),
    "outputs_byte_identical": True}))
'''


def ensure_file(path, blob, reps):
    want = len(blob.encode()) * reps
    if os.path.exists(path) and os.path.getsize(path) == want:
        return
    with open(path + ".tmp", "w") as fh:
        for _ in range(reps):
            fh.write(blob)
    os.replace(path + ".tmp", path)


def run_child(job, conf, inp, out, incremental_state=None):
    argv = ([sys.executable, "-c", _CHILD_INCR, job, json.dumps(conf),
             inp, out, incremental_state] if incremental_state
            else [sys.executable, "-c", _CHILD, job, json.dumps(conf),
                  inp, out])
    proc = subprocess.run(argv,
                          capture_output=True, text=True, timeout=7200)
    if proc.returncode != 0:
        raise RuntimeError(f"{job} failed: {proc.stderr[-500:]}")
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    # memory-oracle delta column: the runner attaches
    # Mem:PredictedPeakBytes (analysis/mem footprint model) next to the
    # measured Mem:PeakRSS, so every 100M anchor records the model's
    # error over time — the real-scale complement of the CI-scale
    # graftlint --mem band
    predicted = line.get("counters", {}).get("Mem:PredictedPeakBytes")
    if predicted:
        pred_mb = predicted / (1 << 20)
        line["predicted_peak_mb"] = round(pred_mb, 1)
        line["mem_model_delta_pct"] = round(
            100.0 * (line["peak_rss_mb"] - pred_mb) / pred_mb, 1)
    print(json.dumps(line), flush=True)
    assert line["peak_rss_mb"] < RSS_LIMIT_MB, \
        f"{job} RSS {line['peak_rss_mb']}MB not O(block)"
    return line


def residual_trend(job: str, inp: str) -> list:
    """The predicted-vs-measured RSS residual ratios the runner's
    always-on recording (runner._add_mem_counters -> avenir_tpu.tune)
    has accumulated for (job, corpus) — newest last. Every anchor run
    appends one, so across rounds this column shows whether the
    footprint model's real-scale error is drifting; [] when no profile
    exists (first round, or the store dir was cleaned)."""
    try:
        from avenir_tpu.tune import ProfileStore, corpus_digest, resolve_dir

        store = ProfileStore(resolve_dir(None, [inp]))
        prof = store.load(job, corpus_digest([inp]))
        if not prof:
            return []
        return [round(float(r["measured"]) / float(r["predicted"]), 3)
                for r in prof.get("residuals", [])
                if float(r.get("predicted", 0)) > 0]
    except Exception as e:                        # noqa: BLE001
        return [f"unavailable ({type(e).__name__})"]


def audit_status(mode: str) -> str:
    """"validated/total" of one graftlint streaming audit (--flow
    chunk-invariance or --merge shard-merge/resume), run in a child so
    this process stays jax-free; "unavailable (...)" instead of a raise
    because a broken auditor must not block recording a finished
    100M-row measurement — the bench tripwire is the hard gate."""
    key, flag = (("invariance_audit", "--flow") if mode == "invariance"
                 else ("merge_audit", "--merge"))
    verdict = ("invariance_validated" if mode == "invariance"
               else "merge_validated")
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join("tools", "graftlint.py"),
             flag, "--json"],
            capture_output=True, text=True, timeout=1800)
        rows = json.loads(proc.stdout)[key]
        ok = sum(1 for r in rows if r[verdict])
        return f"{ok}/{len(rows)}"
    except Exception as e:                        # noqa: BLE001
        return f"unavailable ({type(e).__name__})"


def main():
    import numpy as np

    jax_free_env = dict(os.environ)  # generation needs no jax at all
    del jax_free_env

    from avenir_tpu.data import churn_schema, generate_churn

    t0 = time.perf_counter()
    schema_path = "/tmp/avenir_scale_churn.json"
    churn_schema().save(schema_path)
    churn_blob = generate_churn(100_000, seed=9, as_csv=True)
    ensure_file(CHURN_CSV, churn_blob, ROWS_M * 10)

    rng = np.random.default_rng(12)
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
    ensure_file(SEQ_CSV, "\n".join(lines) + "\n", ROWS_M * 10)
    print(f"# inputs ready in {time.perf_counter()-t0:.0f}s: "
          f"{os.path.getsize(CHURN_CSV)>>20}MB churn, "
          f"{os.path.getsize(SEQ_CSV)>>20}MB sequences", flush=True)

    results = {"rows": ROWS_M * 1_000_000,
               "churn_csv_mb": os.path.getsize(CHURN_CSV) >> 20,
               "seq_csv_mb": os.path.getsize(SEQ_CSV) >> 20,
               "rss_limit_mb": RSS_LIMIT_MB}
    results["mutualInformation"] = run_child(
        "mutualInformation",
        {"mut.feature.schema.file.path": schema_path,
         "mut.mutual.info.score.algorithms": "mutual.info.maximization"},
        CHURN_CSV, "/tmp/avenir_scale_mi.txt")
    results["markovStateTransitionModel"] = run_child(
        "markovStateTransitionModel",
        {"mst.model.states": "L,M,H", "mst.class.label.field.ord": "1",
         "mst.skip.field.count": "2", "mst.class.labels": "T,F"},
        SEQ_CSV, "/tmp/avenir_scale_mst.txt")
    if "--extra" in sys.argv:
        # the multi-pass miners: one streamed scan per k over the same
        # 100M-row file (transactions reuse the sequence rows: tokens
        # after the meta fields are the items / the sequence)
        results["frequentItemsApriori"] = run_child(
            "frequentItemsApriori",
            {"fia.support.threshold": "0.3", "fia.item.set.length": "2",
             "fia.skip.field.count": "2",
             "fia.stream.block.size.mb": "64"},
            SEQ_CSV, "/tmp/avenir_scale_fia")
        results["candidateGenerationWithSelfJoin"] = run_child(
            "candidateGenerationWithSelfJoin",
            {"cgs.support.threshold": "0.3", "cgs.item.set.length": "2",
             "cgs.skip.field.count": "2",
             "cgs.stream.block.size.mb": "64"},
            SEQ_CSV, "/tmp/avenir_scale_gsp")
    if "--fused" in sys.argv:
        # scan-sharing A/B: the three churn profilers sequentially (one
        # full CSV scan EACH) vs fused through run_shared (ONE scan,
        # three fold sinks); outputs must be byte-identical
        jobs3 = [
            ("bayesianDistr",
             {"bad.feature.schema.file.path": schema_path}, "bad"),
            ("mutualInformation",
             {"mut.feature.schema.file.path": schema_path,
              "mut.mutual.info.score.algorithms":
                  "mutual.info.maximization"}, "mut"),
            ("fisherDiscriminant",
             {"fid.feature.schema.file.path": schema_path}, "fid"),
        ]
        seq_s, seq_outs = 0.0, []
        for job, conf, _p in jobs3:
            line = run_child(job, conf, CHURN_CSV,
                             f"/tmp/avenir_scale_seq_{job}.txt")
            seq_s += line["seconds"]
            results[f"sequential_{job}"] = line
            seq_outs.append(f"/tmp/avenir_scale_seq_{job}.txt")
        outdir = "/tmp/avenir_scale_fused"
        os.makedirs(outdir, exist_ok=True)
        proc = subprocess.run(
            [sys.executable, "-c", _CHILD_SHARED,
             json.dumps([(j, c) for j, c, _p in jobs3]), CHURN_CSV, outdir],
            capture_output=True, text=True, timeout=7200)
        if proc.returncode != 0:
            raise RuntimeError(f"fused scan failed: {proc.stderr[-500:]}")
        fused = json.loads(proc.stdout.strip().splitlines()[-1])
        print(json.dumps(fused), flush=True)
        assert fused["peak_rss_mb"] < RSS_LIMIT_MB
        for job, _conf, _p in jobs3:
            seq_out = f"/tmp/avenir_scale_seq_{job}.txt"
            fused_out = os.path.join(outdir, job)
            with open(seq_out, "rb") as fa, open(fused_out, "rb") as fb:
                assert fa.read() == fb.read(), \
                    f"fused output {fused_out} != sequential {seq_out}"
        fused["sequential_seconds"] = round(seq_s, 1)
        fused["speedup"] = round(seq_s / fused["seconds"], 2)
        fused["outputs_byte_identical"] = True
        results["sharedScan"] = fused
    if "--incremental" in sys.argv:
        # delta-scan anchor: cold-seed the driver's state on a COPY of
        # the churn corpus (the shared cached corpus file must keep its
        # exact size for ensure_file), append ~1% of rows, then time
        # incremental refresh vs cold full re-scan — byte-identical
        import shutil

        base = CHURN_CSV.replace(".csv", "_incr.csv")
        shutil.copyfile(CHURN_CSV, base)
        state = f"/tmp/avenir_scale_incr_state_{ROWS_M}m"
        shutil.rmtree(state, ignore_errors=True)
        conf = {"mut.feature.schema.file.path": schema_path,
                "mut.mutual.info.score.algorithms":
                    "mutual.info.maximization"}
        seed = run_child("mutualInformation", conf, base,
                         "/tmp/avenir_scale_incr_seed.txt",
                         incremental_state=state)
        from avenir_tpu.data import generate_churn as _gen

        append_blob = _gen(100_000, seed=10, as_csv=True)
        with open(base, "a") as fh:
            for _ in range(max(ROWS_M // 10, 1)):   # ~1% of the corpus
                fh.write(append_blob)
        cold = run_child("mutualInformation", conf, base,
                         "/tmp/avenir_scale_incr_cold.txt")
        incr = run_child("mutualInformation", conf, base,
                         "/tmp/avenir_scale_incr_refresh.txt",
                         incremental_state=state)
        with open("/tmp/avenir_scale_incr_cold.txt", "rb") as fa, \
                open("/tmp/avenir_scale_incr_refresh.txt", "rb") as fb:
            assert fa.read() == fb.read(), \
                "incremental refresh output != cold full re-scan"
        results["incremental"] = {
            "seed_seconds": seed["seconds"],
            "cold_seconds": cold["seconds"],
            "incremental_seconds": incr["seconds"],
            "speedup": round(cold["seconds"]
                             / max(incr["seconds"], 0.1), 2),
            "skipped_bytes": incr["counters"].get("Resume:SkippedBytes"),
            "hit_blocks": incr["counters"].get("Cache:HitBlocks"),
            "delta_blocks": incr["counters"].get("Cache:DeltaBlocks"),
            "outputs_byte_identical": True,
        }
        os.remove(base)
    if "--shard" in sys.argv:
        # sharded-scan A/B: the two anchor families re-run with the
        # scan split across 2 worker processes (block ledger, plan-
        # ordered merge), in a fresh child; byte-identity asserted
        # against the solo anchors above, shard counters recorded
        shard_jobs = [
            ("mutualInformation",
             {"mut.feature.schema.file.path": schema_path,
              "mut.mutual.info.score.algorithms":
                  "mutual.info.maximization"},
             CHURN_CSV, "/tmp/avenir_scale_mi_sharded.txt",
             "/tmp/avenir_scale_mi.txt"),
            ("markovStateTransitionModel",
             {"mst.model.states": "L,M,H",
              "mst.class.label.field.ord": "1",
              "mst.skip.field.count": "2", "mst.class.labels": "T,F"},
             SEQ_CSV, "/tmp/avenir_scale_mst_sharded.txt",
             "/tmp/avenir_scale_mst.txt"),
        ]
        for job, conf, inp, out, solo_out in shard_jobs:
            proc = subprocess.run(
                [sys.executable, "-c", _CHILD_SHARDED, job,
                 json.dumps(conf), inp, out, "2"],
                capture_output=True, text=True, timeout=7200)
            if proc.returncode != 0:
                raise RuntimeError(
                    f"sharded {job} failed: {proc.stderr[-500:]}")
            line = json.loads(proc.stdout.strip().splitlines()[-1])
            print(json.dumps(line), flush=True)
            assert line["peak_rss_mb"] < RSS_LIMIT_MB, \
                f"sharded {job} RSS {line['peak_rss_mb']}MB not O(block)"
            with open(solo_out, "rb") as fa, open(out, "rb") as fb:
                assert fa.read() == fb.read(), \
                    f"sharded {job} output != solo anchor {solo_out}"
            line["outputs_byte_identical"] = True
            line["solo_seconds"] = results[job]["seconds"]
            line["shard_speedup"] = round(
                results[job]["seconds"]
                / max(line["scan_seconds"], 1e-9), 2)
            results[f"sharded_{job}"] = line
        # miner anchor: the distributed per-k rounds at anchor scale —
        # solo fia (the --extra anchor when it already ran this
        # invocation, a fresh child otherwise) vs run_sharded;
        # byte-identity per itemset file, the Shard:PerK* counters and
        # the shard_miner_speedup column recorded
        fia_conf = {"fia.support.threshold": "0.3",
                    "fia.item.set.length": "2",
                    "fia.skip.field.count": "2",
                    "fia.stream.block.size.mb": "64"}
        solo_fia_out = "/tmp/avenir_scale_fia"
        if "frequentItemsApriori" not in results:
            results["frequentItemsApriori"] = run_child(
                "frequentItemsApriori", fia_conf, SEQ_CSV, solo_fia_out)
        out = "/tmp/avenir_scale_fia_sharded"
        proc = subprocess.run(
            [sys.executable, "-c", _CHILD_SHARDED,
             "frequentItemsApriori", json.dumps(fia_conf), SEQ_CSV,
             out, "2"],
            capture_output=True, text=True, timeout=7200)
        if proc.returncode != 0:
            raise RuntimeError(
                f"sharded miner failed: {proc.stderr[-500:]}")
        line = json.loads(proc.stdout.strip().splitlines()[-1])
        print(json.dumps(line), flush=True)
        assert line["peak_rss_mb"] < RSS_LIMIT_MB, \
            f"sharded miner RSS {line['peak_rss_mb']}MB not O(block)"
        assert line["counters"].get("Shard:PerKRounds", 0) >= 1, \
            "sharded miner ran zero distributed per-k rounds"
        solo_files = sorted(os.path.join(solo_fia_out, f)
                            for f in os.listdir(solo_fia_out))
        assert len(solo_files) == len(line["outputs"]), \
            (solo_files, line["outputs"])
        for pa, pb in zip(solo_files, sorted(line["outputs"])):
            with open(pa, "rb") as fa, open(pb, "rb") as fb:
                assert fa.read() == fb.read(), \
                    f"sharded miner output {pb} != solo {pa}"
        line["outputs_byte_identical"] = True
        line["solo_seconds"] = results["frequentItemsApriori"]["seconds"]
        line["shard_speedup"] = round(
            line["solo_seconds"] / max(line["scan_seconds"], 1e-9), 2)
        results["sharded_frequentItemsApriori"] = line
    if "--sidecar" in sys.argv:
        # columnar-sidecar A/B: cold pack (parse + write sidecar) vs
        # warm replay (parse-free) per anchor family, in one child with
        # a jit-warmup pass so the ratio prices only the parse work
        import shutil

        outdir = f"/tmp/avenir_scale_sidecar_{ROWS_M}m"
        shutil.rmtree(outdir, ignore_errors=True)
        os.makedirs(outdir, exist_ok=True)
        sc_jobs = [
            ("mutualInformation",
             {"mut.feature.schema.file.path": schema_path,
              "mut.mutual.info.score.algorithms":
                  "mutual.info.maximization"},
             CHURN_CSV),
            ("markovStateTransitionModel",
             {"mst.model.states": "L,M,H",
              "mst.class.label.field.ord": "1",
              "mst.skip.field.count": "2", "mst.class.labels": "T,F"},
             SEQ_CSV),
        ]
        for job, conf, inp in sc_jobs:
            proc = subprocess.run(
                [sys.executable, "-c", _CHILD_SIDECAR, job,
                 json.dumps(conf), inp, outdir],
                capture_output=True, text=True, timeout=7200)
            if proc.returncode != 0:
                raise RuntimeError(
                    f"sidecar {job} failed: {proc.stderr[-500:]}")
            line = json.loads(proc.stdout.strip().splitlines()[-1])
            print(json.dumps(line), flush=True)
            assert line["peak_rss_mb"] < RSS_LIMIT_MB, \
                f"sidecar {job} RSS {line['peak_rss_mb']}MB not O(block)"
            results[f"sidecar_{job}"] = line
    if "--server" in sys.argv:
        # resident-server anchor: the 3-tenant mixed-kind open-loop
        # load served by an in-process JobServer vs one-job-at-a-time,
        # in a fresh child (so both sides price the same startup), with
        # byte-identity asserted per served artifact and the Server:*
        # counters recorded per request
        outdir = f"/tmp/avenir_scale_server_{ROWS_M}m"
        import shutil

        shutil.rmtree(outdir, ignore_errors=True)
        os.makedirs(outdir, exist_ok=True)
        proc = subprocess.run(
            [sys.executable, "-c", _CHILD_SERVER,
             CHURN_CSV, SEQ_CSV, schema_path, outdir],
            capture_output=True, text=True, timeout=7200)
        if proc.returncode != 0:
            raise RuntimeError(f"server load failed: {proc.stderr[-800:]}")
        line = json.loads(proc.stdout.strip().splitlines()[-1])
        print(json.dumps(line), flush=True)
        # the served phase is the admission-controlled one; the lifetime
        # peak_rss_mb (also recorded) includes the unbudgeted sequential
        # twin and would assert the wrong phase
        assert line["server_peak_rss_mb"] < RSS_LIMIT_MB, \
            f"server RSS {line['server_peak_rss_mb']}MB not admission-bounded"
        results["jobServer"] = line
    merged = {}
    if os.path.exists(RECORD):
        try:
            merged = json.load(open(RECORD))
        except ValueError:
            merged = {}
    merged.update(results)
    with open(RECORD, "w") as fh:
        json.dump(merged, fh, indent=1)
    summary = {"stream_scale": "done",
               "mi_rows_per_sec": round(
                   results["rows"]
                   / results["mutualInformation"]["seconds"], 1),
               "mst_rows_per_sec": round(
                   results["rows"]
                   / results["markovStateTransitionModel"]["seconds"], 1)}
    # the miners carry their own Basic:RowsPerSec tripwire counter now —
    # surface it so a throughput regression shows in this summary line too
    for key, job in (("fia_rows_per_sec", "frequentItemsApriori"),
                     ("gsp_rows_per_sec", "candidateGenerationWithSelfJoin")):
        if job in results:
            summary[key] = results[job]["counters"].get("Basic:RowsPerSec")
    # predicted-vs-measured memory column per streamed job (model error
    # at real scale; the record file keeps the full per-job numbers)
    summary["mem_model_delta_pct"] = {
        job: line["mem_model_delta_pct"] for job, line in results.items()
        if isinstance(line, dict) and "mem_model_delta_pct" in line}
    # the residual TREND next to the single-run delta: every anchor's
    # predicted-vs-measured pair lands in the per-(job, corpus) autotune
    # profile store, so this column shows the model error across rounds
    # (the history the tuner's admission-correction factor learns from)
    summary["mem_residual_trend"] = {
        job: residual_trend(job, inp) for job, inp in
        (("mutualInformation", CHURN_CSV),
         ("markovStateTransitionModel", SEQ_CSV))}
    if "sharedScan" in results:
        summary["shared_scan_speedup"] = results["sharedScan"]["speedup"]
    # the incremental-speedup column: O(delta) refresh vs O(corpus)
    # re-scan after a ~1% append, byte-identity already asserted above
    if "incremental" in results:
        summary["incremental_speedup"] = results["incremental"]["speedup"]
    # the sharded-scan columns: solo anchor vs 2-process sharded scan
    # per family, plus the Shard:* ledger counters the sharded
    # JobResults carry (blocks / stolen / dedup / merge ms)
    shard_cols = {job: line for job, line in results.items()
                  if job.startswith("sharded_")}
    if shard_cols:
        summary["shard_speedup"] = {
            job[len("sharded_"):]: line["shard_speedup"]
            for job, line in shard_cols.items()}
        summary["shard_counters"] = {
            job[len("sharded_"):]: {
                k: line["counters"][k] for k in
                ("Shard:Blocks", "Shard:StolenBlocks",
                 "Shard:DedupBlocks", "Shard:MergeMs",
                 "Shard:PerKRounds", "Shard:PerKBlocks",
                 "Shard:PerKSeconds")
                if k in line.get("counters", {})}
            for job, line in shard_cols.items()}
        # the miner anchor's own column: the distributed per-k phase
        # is the throughput this PR exists for
        miner = shard_cols.get("sharded_frequentItemsApriori")
        if miner is not None:
            summary["shard_miner_speedup"] = miner["shard_speedup"]
    # the sidecar columns: parse-free warm replay vs cold pack per
    # family, the on-disk cost of the cache, and the hit/delta block
    # counters the two JobResults carried
    sc_cols = {job[len("sidecar_"):]: line for job, line in results.items()
               if job.startswith("sidecar_")}
    if sc_cols:
        summary["sidecar_speedup"] = {
            job: line["sidecar_speedup"] for job, line in sc_cols.items()}
        summary["sidecar_bytes_ratio"] = {
            job: line["bytes_on_disk_ratio"]
            for job, line in sc_cols.items()}
        summary["sidecar_counters"] = {
            job: {"hit_blocks": line["hit_blocks"],
                  "delta_blocks": line["delta_blocks"]}
            for job, line in sc_cols.items()}
    # the served-jobs/min column: batched multi-tenant serving vs
    # one-job-at-a-time, plus the served requests' Server:* counters
    if "jobServer" in results:
        summary["server_speedup"] = results["jobServer"]["speedup"]
        summary["server_jobs_per_min"] = \
            results["jobServer"]["jobs_per_min_served"]
        summary["server_p99_queue_wait_ms"] = \
            results["jobServer"]["p99_queue_wait_ms"]
        # the avenir-trace histogram columns: queue-wait p99 from the
        # server's streaming accumulator (not the sorted per-request
        # scalars above — same data, distribution view) and per-chunk
        # scan latency p99 from the process-global obs histogram
        hists = results["jobServer"].get("hists", {})
        for col, name in (("server_hist_queue_wait_p99_ms",
                           "queue_wait_ms"),
                          ("server_hist_admission_held_p99_ms",
                           "admission_held_ms"),
                          ("server_chunk_latency_p99_ms",
                           "chunk_latency_ms")):
            if name in hists:
                summary[col] = hists[name]["p99"]
    # the two streaming-correctness columns, side by side: the folds the
    # numbers above measured are chunk-layout-invariant AND a merge
    # algebra (shard-merge + checkpoint-resume byte-identical)
    if "--no-audits" not in sys.argv:
        summary["invariance_audit"] = audit_status("invariance")
        summary["merge_audit"] = audit_status("merge")
        merged.update({"invariance_audit": summary["invariance_audit"],
                       "merge_audit": summary["merge_audit"]})
        with open(RECORD, "w") as fh:
            json.dump(merged, fh, indent=1)
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
