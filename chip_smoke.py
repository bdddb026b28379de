"""chip_smoke.py — the quickest proof that the job and serve paths still
start on the chip.

    python chip_smoke.py                 # on a machine with one TPU chip
    python chip_smoke.py --dry-run-cpu   # the same phases, toy sizes, CPU

It drives the normal entry points only — `run_from_cli` (what
`python -m avenir_tpu <job> --conf ...` calls) and one
`python -m avenir_tpu serve --listen` process — at the full width of the
churn and e-learning schemas, on inputs `avenir_tpu.data` makes from
seeds, and checks each result by the repo's own means:

  device    the device rule admits this machine (fails fast when not)
  data      host only: CSVs, properties, and a host count of the churn CSV
  nb        bayesianDistr over >= 10M rows (several 64 MB blocks, the
            deferred device fold, a short last chunk): the model's counts
            equal the host count exactly; then bayesianPredictor
  knn_cold  nearestNeighbor at its defaults (exact Pallas kernel,
            manhattan), parity against the jnp route on a 256-query slice
  knn_warm  the same job in a fresh process: the compile cache's check
  knn_variants  device.packed.kernel=true, then device.fused.vote=true
  kernels   every Pallas kernel compiled, against the NumPy oracle of
            tools/tpu_kernel_check.py
  server    serve --listen: /submit?wait=1 for the NB and the kNN job
            (byte-identical to the batch artefacts), /score rows,
            /healthz, /metrics, SIGTERM -> drain -> exit 0

A chip belongs to one process at a time, so this parent never imports
jax: every phase that computes is a child, one after another, and the
server phase's client is plain urllib. Each phase prints one row that
names the platform, device kind and count as seen by the process that did
the work, its XLA compilations and its CSV parser.

It exits non-zero, and prints no result, when a phase fails, when a
working process is not on a TPU, when a Pallas call was interpreted or
gave way to the jnp route, or when the native parser was not built. On
success it prints two JSON lines. The first is the summary (versions, the
compile-cache directory, every phase row; it ends "claim": null), also
written to chiprun_out/chip_smoke.json. The last line of standard output
is the verdict and nothing else:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with the device as JAX reported it to the first working process. With
--dry-run-cpu the summary says "chip": false, "dry_run_ok": true and the
verdict says "ok": false: a dry run can never be read as a pass on the
chip.
"""

import argparse
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time
import traceback
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))

# ------------------------------------------------------------------ sizes
FULL = {
    "nb_rows": 10_650_011,        # ~389 MB: six 64 MB blocks and a short one
    "nb_blob_rows": 1_000_000,    # rows per generated blob (two seeds)
    "nb_predict_rows": 1_000_000,
    "nb_block_mb": None,          # the job's default (64)
    "knn_train": 131_072,
    "knn_queries": 8_192,
    "knn_parity_queries": 256,
    "score_rows": 8,
}
TOY = {
    "nb_rows": 30_011,
    "nb_blob_rows": 10_000,
    "nb_predict_rows": 2_000,
    "nb_block_mb": 0.25,          # so the toy run crosses blocks too
    "knn_train": 1_500,
    "knn_queries": 300,
    "knn_parity_queries": 64,
    "score_rows": 4,
}
NB_SEEDS = (7, 8)
KNN_K = 5
#: parity of a kNN job against the jnp route on the query slice: the
#: share of queries given the same class, and the largest difference in
#: any class's vote share (k=5: one swapped neighbour moves it by 0.2)
KNN_MIN_AGREE = 0.99
KNN_MAX_SHARE_DIFF = 0.2 + 1e-3

DEADLINE_S = 1150.0               # the contract allows 1200
PHASE_TIMEOUT_S = 700.0
PHASES = ("device", "data", "nb", "knn_cold", "knn_warm", "knn_variants",
          "kernels", "server")


class SmokeFailure(RuntimeError):
    """A phase did not meet its check."""


def check(cond, message):
    if not cond:
        raise SmokeFailure(message)


# =================================================================== paths
FILES = {
    "churn_schema": "churn.json", "churn_csv": "churn.csv",
    "churn_predict_csv": "churn_predict.csv",
    "nb_props": "nb.properties", "nb_oracle": "nb_oracle.json",
    "nb_model": "nb_model.csv", "nb_pred": "nb_pred.csv",
    "elearn_schema": "elearn.json", "knn_train": "knn_train.csv",
    "knn_test": "knn_test.csv", "knn_slice": "knn_slice.csv",
    "knn_props": "knn.properties", "knn_out": "knn_out.csv",
}


def paths(work):
    return {key: os.path.join(work, name) for key, name in FILES.items()}


def nb_conf(P, sizes):
    conf = {"bad.feature.schema.file.path": P["churn_schema"],
            "bap.feature.schema.file.path": P["churn_schema"],
            "bap.bayesian.model.file.path": P["nb_model"],
            "bap.validation.mode": "true"}
    if sizes["nb_block_mb"]:
        conf["stream.block.size.mb"] = str(sizes["nb_block_mb"])
    return conf


def knn_conf(P, **extra):
    conf = {"nen.feature.schema.file.path": P["elearn_schema"],
            "nen.top.match.count": str(KNN_K),
            "nen.validation.mode": "true",
            "nen.output.class.distr": "true"}
    conf.update(extra)
    return conf


def write_props(path, conf):
    with open(path, "w") as fh:
        for key, val in conf.items():
            fh.write(f"{key}={val}\n")


# ============================================================ phase bodies
# Everything below `phase_*` runs in a child process (except `server`,
# whose client is this jax-free parent).

def phase_device(P, sizes, dry):
    import importlib.metadata as md

    import jax
    import jaxlib
    from avenir_tpu.utils.devices import place_compile_cache, require_backend

    require_backend()
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    return {"versions": {"jax": jax.__version__,
                         "jaxlib": jaxlib.__version__, "libtpu": libtpu,
                         "python": sys.version.split()[0]},
            "compile_cache_dir": place_compile_cache()}


def _count_churn(lines):
    """{(class, ordinal, bin): rows} of churn CSV lines, by plain Python:
    the host oracle the NB model file is compared with."""
    from collections import Counter

    counts = Counter()
    for line in lines:
        f = line.split(",")
        cls = f[6]
        counts[(cls, 1, f[1])] += 1
        counts[(cls, 2, f[2])] += 1
        counts[(cls, 3, f[3])] += 1
        counts[(cls, 4, f[4])] += 1
        counts[(cls, 5, str(int(f[5]) // 12))] += 1   # bucketWidth 12
    return counts


def phase_data(P, sizes, dry):
    """Host only: this child runs with JAX_PLATFORMS=cpu and never starts
    a backend, so it cannot take the chip from the phases after it."""
    from collections import Counter

    from avenir_tpu.data import (churn_schema, elearn_schema,
                                 generate_churn, generate_elearn)

    churn_schema().save(P["churn_schema"])
    elearn_schema().save(P["elearn_schema"])
    write_props(P["nb_props"], nb_conf(P, sizes))
    write_props(P["knn_props"], knn_conf(P))

    # churn CSV: whole blobs from two seeds in turn, then part of one, so
    # the file ends in a short chunk; the oracle counts each blob once
    blob_rows, want = sizes["nb_blob_rows"], sizes["nb_rows"]
    blobs = [generate_churn(blob_rows, seed=s, as_csv=True) for s in NB_SEEDS]
    lines = [b.split("\n")[:-1] for b in blobs]
    per_blob = [_count_churn(ls) for ls in lines]
    oracle, written, i = Counter(), 0, 0
    with open(P["churn_csv"], "w") as fh:
        while want - written >= blob_rows:
            fh.write(blobs[i % 2])
            oracle.update(per_blob[i % 2])
            written += blob_rows
            i += 1
        tail = lines[i % 2][:want - written]
        if tail:
            fh.write("\n".join(tail) + "\n")
            oracle.update(_count_churn(tail))
    with open(P["nb_oracle"], "w") as fh:
        json.dump({"|".join(map(str, k)): v for k, v in oracle.items()}, fh)
    with open(P["churn_predict_csv"], "w") as fh:
        fh.write("\n".join(lines[0][:sizes["nb_predict_rows"]]) + "\n")

    def elearn_csv(n, seed, path):
        ds = generate_elearn(n, seed=seed)
        cols = [ds.column(f.ordinal) for f in ds.schema.feature_fields]
        ids, labels = ds.ids(), ds.labels()
        with open(path, "w") as fh:
            for r in range(n):
                fh.write(",".join(
                    [str(ids[r])] + [f"{c[r]:.3f}" for c in cols]
                    + [("fail", "pass")[labels[r]]]) + "\n")

    elearn_csv(sizes["knn_train"], 11, P["knn_train"])
    elearn_csv(sizes["knn_queries"], 12, P["knn_test"])
    with open(P["knn_test"]) as src, open(P["knn_slice"], "w") as dst:
        for _ in range(sizes["knn_parity_queries"]):
            dst.write(src.readline())
    return {"host_only": True,
            "churn_csv_mb": round(os.path.getsize(P["churn_csv"]) / 2**20, 1),
            "churn_rows": want}


def phase_nb(P, sizes, dry):
    from avenir_tpu.models import naive_bayes
    from avenir_tpu.runner import run_from_cli

    t0 = time.perf_counter()
    res = run_from_cli(["bayesianDistr", "--conf", P["nb_props"],
                        P["churn_csv"], P["nb_model"]])
    distr_s = time.perf_counter() - t0
    check(res.counters["Distribution Data:Records"] == sizes["nb_rows"],
          f"bayesianDistr folded {res.counters} rows, "
          f"wanted {sizes['nb_rows']}")
    with open(P["nb_oracle"]) as fh:
        oracle = json.load(fh)
    model = {}
    with open(P["nb_model"]) as fh:
        for line in fh:
            cls, ordinal, bin_, count = line.rstrip("\n").split(",")
            if cls and ordinal and bin_:
                model[f"{cls}|{ordinal}|{bin_}"] = int(count)
    check(model == oracle,
          "NB model counts differ from the host count of the same CSV: "
          f"{sorted(set(model.items()) ^ set(oracle.items()))[:6]}")
    # which branch of NaiveBayesModel.accumulate ran: the device fold is
    # the only caller of _fold_batch_kernel in this process
    fold_compiles = naive_bayes._fold_batch_kernel._cache_size()
    branch = "device" if fold_compiles else "host"
    check(dry or branch == "device",
          "the NB fold took the host branch on an accelerator")

    t0 = time.perf_counter()
    res = run_from_cli(["bayesianPredictor", "--conf", P["nb_props"],
                        P["churn_predict_csv"], P["nb_pred"]])
    predict_s = time.perf_counter() - t0
    with open(P["nb_pred"]) as fh:
        n_pred = sum(1 for _ in fh)
    check(n_pred == sizes["nb_predict_rows"],
          f"bayesianPredictor wrote {n_pred} rows")
    check(res.counters["Validation:Accuracy"] > 80,
          f"NB accuracy {res.counters}")
    block_mb = sizes["nb_block_mb"] or 64
    return {"rows": sizes["nb_rows"], "fold_branch": branch,
            "fold_compiles": fold_compiles,
            "blocks": math.ceil(os.path.getsize(P["churn_csv"])
                                / (block_mb * 2**20)),
            "counts_equal_host_oracle": True,
            "distr_s": round(distr_s, 2), "predict_s": round(predict_s, 2),
            "predict_accuracy": res.counters["Validation:Accuracy"]}


def spy_pallas():
    """Record every pallas_call this process traces: which kernel, and
    whether it was interpreted. The list is the evidence that a job ran
    its Pallas kernel compiled instead of giving way to the jnp route."""
    from jax.experimental import pallas as pl

    calls, real = [], pl.pallas_call

    def spy(kernel, *args, **kwargs):
        calls.append({"kernel": getattr(kernel, "func", kernel).__name__,
                      "interpret": bool(kwargs.get("interpret", False))})
        return real(kernel, *args, **kwargs)

    pl.pallas_call = spy
    return calls


def check_pallas(calls, kernel, dry):
    """On the chip: `kernel` was traced, and nothing was interpreted."""
    if dry:
        return "jnp (cpu dry run)" if not calls else "pallas interpreted"
    check(any(c["kernel"] == kernel for c in calls),
          f"no pallas_call of {kernel}: the job gave way to another "
          f"route (saw {calls})")
    check(not any(c["interpret"] for c in calls),
          f"a Pallas kernel ran in interpret mode: {calls}")
    return f"pallas:{kernel} compiled"


def knn_reference(P):
    """(class codes, vote shares) of the parity slice by the jnp route
    (ops.distance.blocked_topk_neighbors) at highest matmul precision."""
    import jax
    from avenir_tpu.core.dataset import Dataset
    from avenir_tpu.core.schema import FeatureSchema
    from avenir_tpu.models.knn import (NearestNeighborClassifier,
                                       NeighborIndex)

    schema = FeatureSchema.from_file(P["elearn_schema"])
    train = Dataset.from_csv(P["knn_train"], schema)
    ref = NearestNeighborClassifier(train, top_match_count=KNN_K)
    ref.index = NeighborIndex(train, k=KNN_K, metric="manhattan",
                              use_pallas=False)
    with jax.default_matmul_precision("highest"):
        pred, scores = ref.predict(Dataset.from_csv(P["knn_slice"], schema))
    return pred, scores / scores.sum(axis=1, keepdims=True)


def knn_job(P, sizes, out, props, reference):
    """One nearestNeighbor job through run_from_cli, checked against the
    reference on the parity slice."""
    import numpy as np
    from avenir_tpu.runner import run_from_cli

    t0 = time.perf_counter()
    res = run_from_cli(["nearestNeighbor", "--conf", props,
                        P["knn_train"], P["knn_test"], out])
    job_s = time.perf_counter() - t0
    with open(out) as fh:
        lines = fh.read().splitlines()
    check(len(lines) == sizes["knn_queries"],
          f"nearestNeighbor wrote {len(lines)} rows")
    check(res.counters["Validation:Accuracy"] > 90,
          f"kNN accuracy {res.counters}")
    ref_pred, ref_share = reference
    n = len(ref_pred)
    got_pred = np.array([("fail", "pass").index(ln.split(",")[1])
                         for ln in lines[:n]])
    got_share = np.array([[float(tok.split(":")[1])
                           for tok in ln.split(",")[2:]] for ln in lines[:n]])
    check(np.isfinite(got_share).all(), "non-finite vote shares")
    agree = float((got_pred == ref_pred).mean())
    share_diff = float(np.abs(got_share - ref_share).max())
    check(agree >= KNN_MIN_AGREE and share_diff <= KNN_MAX_SHARE_DIFF,
          f"kNN parity: class agreement {agree:.4f} (want >= "
          f"{KNN_MIN_AGREE}), vote-share diff {share_diff:.3f} (want <= "
          f"{KNN_MAX_SHARE_DIFF:.3f})")
    return {"job_s": round(job_s, 2), "class_agreement": agree,
            "max_vote_share_diff": round(share_diff, 4),
            "accuracy": res.counters["Validation:Accuracy"]}


def phase_knn(P, sizes, dry):
    """knn_cold and knn_warm: the default job, then parity."""
    calls = spy_pallas()
    row = knn_job(P, sizes, P["knn_out"], P["knn_props"], knn_reference(P))
    row["route"] = check_pallas(calls, "_knn_kernel", dry)
    row["parity"] = (f"{sizes['knn_parity_queries']} queries vs jnp route, "
                     f"agreement >= {KNN_MIN_AGREE}, vote share within "
                     f"{KNN_MAX_SHARE_DIFF:.3f}")
    return row


def phase_knn_variants(P, sizes, dry):
    calls = spy_pallas()
    reference = knn_reference(P)
    row = {}
    for name, key, kernel in (
            ("packed", "nen.device.packed.kernel", "_knn_kernel_lanes"),
            ("fused", "nen.device.fused.vote", "_knn_kernel_lanes_vote")):
        props = os.path.join(os.path.dirname(P["knn_props"]),
                             f"knn_{name}.properties")
        write_props(props, knn_conf(P, **{key: "true"}))
        del calls[:]
        row[name] = knn_job(P, sizes, P["knn_out"] + "." + name, props,
                            reference)
        row[name]["route"] = check_pallas(calls, kernel, dry)
    return row


def phase_kernels(P, sizes, dry):
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import tpu_kernel_check
    from avenir_tpu.utils.devices import require_backend

    require_backend()
    calls = spy_pallas()
    passed, total = tpu_kernel_check.run_cases(interpret=dry, quick=dry)
    check(passed == total, f"kernel check: {passed}/{total} cases passed")
    check(calls and all(c["interpret"] == dry for c in calls),
          f"kernel check ran {len(calls)} pallas_calls, interpreted: "
          f"{sorted({c['interpret'] for c in calls})}")
    return {"cases_passed": passed, "cases": total,
            "pallas_calls": len(calls), "interpreted": dry,
            "kernels": sorted({c["kernel"] for c in calls})}


CHILD_PHASES = {
    "device": phase_device, "data": phase_data, "nb": phase_nb,
    "knn_cold": phase_knn, "knn_warm": phase_knn,
    "knn_variants": phase_knn_variants, "kernels": phase_kernels,
}


def child_main(phase, work, dry):
    """Run one phase in this process; leave its row in <work>/<phase>.row."""
    sizes = TOY if dry else FULL
    row = CHILD_PHASES[phase](paths(work), sizes, dry)
    if not row.get("host_only"):
        from avenir_tpu.utils.devices import device_report

        row.update(device_report())
        check(dry or row["platform"] == "tpu",
              f"phase {phase} ran on {row['platform']!r}, not on a TPU")
        check(dry or row["parser"] == "native",
              "the native CSV parser was not built")
    with open(os.path.join(work, phase + ".row"), "w") as fh:
        json.dump(row, fh)
    return 0


# ============================================================== the parent
class Children:
    """Every process this script starts, so that it stops every one."""

    def __init__(self):
        self.procs = []

    def start(self, cmd, **kwargs):
        proc = subprocess.Popen(cmd, cwd=ROOT, start_new_session=True,
                                **kwargs)
        self.procs.append(proc)
        return proc

    def kill_all(self):
        for proc in self.procs:
            if proc.poll() is None:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
                proc.wait()


def run_child_phase(children, phase, work, dry, env, deadline):
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
           "--work", work] + (["--dry-run-cpu"] if dry else [])
    if phase == "data":
        env = dict(env, JAX_PLATFORMS="cpu")      # host only, by construction
    timeout = min(PHASE_TIMEOUT_S, deadline - time.monotonic())
    check(timeout > 0, f"no time left for phase {phase}")
    proc = children.start(cmd, env=env)
    try:
        rc = proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        raise SmokeFailure(f"phase {phase} still running after "
                           f"{timeout:.0f}s") from None
    check(rc == 0, f"phase {phase} exited {rc} (its traceback is above)")
    with open(os.path.join(work, phase + ".row")) as fh:
        return json.load(fh)


def http(method, url, body=None, timeout=660.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as exc:
        return exc.code, json.loads(exc.read() or b"{}")


def same_bytes(a, b):
    with open(a, "rb") as fa, open(b, "rb") as fb:
        return fa.read() == fb.read()


def phase_server(children, work, sizes, dry, env, deadline):
    """One serve --listen process; this client never touches jax."""
    P = paths(work)
    port_file = os.path.join(work, "port")
    log_path = os.path.join(work, "server.log")
    with open(log_path, "wb") as log:
        proc = children.start(
            [sys.executable, "-m", "avenir_tpu", "serve", "--listen",
             "127.0.0.1:0", "--port-file", port_file],
            env=env, stdout=log, stderr=log)
    try:
        while not os.path.exists(port_file):
            check(proc.poll() is None, "the server exited before it bound")
            check(time.monotonic() < deadline, "the server never bound")
            time.sleep(0.1)
        with open(port_file) as fh:
            url = f"http://127.0.0.1:{int(fh.read().strip())}"
        code, health = http("GET", url + "/healthz")
        check(code == 200 and health["status"] == "serving",
              f"/healthz {code} {health}")

        def submit(job, conf, inputs, output):
            code, row = http("POST", url + "/submit?wait=1",
                             {"job": job, "conf": conf, "inputs": inputs,
                              "output": output})
            check(code == 200 and row.get("ok"),
                  f"/submit {job}: {code} {row}")
            return row

        served_model = P["nb_model"] + ".served"
        submit("bayesianDistr", nb_conf(P, sizes), [P["churn_csv"]],
               served_model)
        check(same_bytes(served_model, P["nb_model"]),
              "served NB model differs from the batch artefact")
        served_knn = P["knn_out"] + ".served"
        submit("nearestNeighbor", knn_conf(P),
               [P["knn_train"], P["knn_test"]], served_knn)
        check(same_bytes(served_knn, P["knn_out"]),
              "served kNN output differs from the batch artefact")

        with open(P["churn_predict_csv"]) as fh:
            rows = [fh.readline().rstrip("\n")
                    for _ in range(sizes["score_rows"])]
        with open(P["nb_pred"]) as fh:
            want = [fh.readline().rstrip("\n") for _ in rows]
        for row, expect in zip(rows, want):
            code, got = http("POST", url + "/score", {
                "kind": "bayes", "model": P["nb_model"], "row": row,
                "conf": {"schema.path": P["churn_schema"],
                         "field.delim": ","}})
            check(code == 200 and got.get("row") == expect,
                  f"/score {code} {got} != batch line {expect!r}")

        code, metrics = http("GET", url + "/metrics")
        check(code == 200 and metrics["stats"].get("served", 0) >= 2,
              f"/metrics {code}: {metrics.get('stats')}")
        code, health = http("GET", url + "/healthz")
        check(code == 200, f"/healthz {code}")
        row = dict(health["device"])
        check(dry or row["platform"] == "tpu",
              f"the server ran on {row['platform']!r}, not on a TPU")
        check(dry or row["parser"] == "native",
              "the server's native CSV parser was not built")

        proc.send_signal(signal.SIGTERM)
        try:
            rc = proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            raise SmokeFailure("the server did not drain within 120s "
                               "of SIGTERM") from None
        check(rc == 0, f"the server exited {rc} after SIGTERM")
    except BaseException:
        with open(log_path, errors="replace") as fh:
            sys.stderr.write("---- server log tail ----\n"
                             + fh.read()[-4000:] + "\n")
        raise
    row.update({"jobs_byte_identical": ["bayesianDistr", "nearestNeighbor"],
                "scores_equal_batch": len(rows),
                "served": metrics["stats"]["served"],
                "sigterm_exit": 0})
    return row


def parent_main(dry):
    t_start = time.monotonic()
    deadline = t_start + DEADLINE_S
    sizes = TOY if dry else FULL
    env = dict(os.environ)
    if dry:
        # toy compiles take milliseconds; let the cache keep them, so the
        # dry run still shows a fresh process finding the placed cache
        env.update(JAX_PLATFORMS="cpu",
                   JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
                   JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1")
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    children = Children()
    rows = {}
    try:
        for phase in PHASES:
            t0 = time.monotonic()
            print(f"[chip_smoke] {phase} ...", flush=True)
            if phase == "server":
                row = phase_server(children, work, sizes, dry, env, deadline)
            else:
                row = run_child_phase(children, phase, work, dry, env,
                                      deadline)
            row["wall_s"] = round(time.monotonic() - t0, 1)
            rows[phase] = row
            print(f"[chip_smoke] {phase} ok {json.dumps(row)}", flush=True)
        cold, warm = rows["knn_cold"], rows["knn_warm"]
        # the placed cache: a fresh process finds the cold run's kernels.
        # Where the machine came with a warm cache the cold run hit it
        # too, and only the hits can be checked, not the seconds.
        check(warm["compile_cache_hits"] > 0,
              "the warm kNN process found nothing in the compile cache")
        check(cold["compile_cache_hits"] > 0
              or warm["compile_s"] < cold["compile_s"],
              f"warm compile {warm['compile_s']}s is not under cold "
              f"{cold['compile_s']}s")
    except Exception as exc:
        if not isinstance(exc, SmokeFailure):
            traceback.print_exc()
        print(f"[chip_smoke] FAILED: {exc}", file=sys.stderr, flush=True)
        return 1
    finally:
        children.kill_all()
        shutil.rmtree(work, ignore_errors=True)

    dev = rows["device"]
    verdict = {"ok": not dry,
               "device": {"platform": dev["platform"],
                          "kind": dev["device_kind"],
                          "count": dev["device_count"]}}
    summary = {
        **verdict,
        "chip": not dry,
        "versions": dev["versions"],
        "compile_cache_dir": dev["compile_cache_dir"],
        "knn_compile_s": {"cold": cold["compile_s"],
                          "warm": warm["compile_s"],
                          "cold_cache_hits": cold["compile_cache_hits"],
                          "warm_cache_hits": warm["compile_cache_hits"]},
        "phases": rows,
        "total_s": round(time.monotonic() - t_start, 1),
    }
    if dry:
        summary["dry_run_ok"] = True
    summary["claim"] = None                           # the last key
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    name = "chip_smoke_dry_run.json" if dry else "chip_smoke.json"
    with open(os.path.join(out_dir, name), "w") as fh:
        json.dump(summary, fh, indent=1)
    print(json.dumps(summary), flush=True)
    print(json.dumps(verdict), flush=True)            # the last line
    return 0


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dry-run-cpu", action="store_true",
                    help="the same phases at toy sizes under "
                         "JAX_PLATFORMS=cpu; never a pass on the chip")
    ap.add_argument("--phase", choices=sorted(CHILD_PHASES),
                    help=argparse.SUPPRESS)
    ap.add_argument("--work", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.phase:
        return child_main(args.phase, args.work, args.dry_run_cpu)
    return parent_main(args.dry_run_cpu)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
